"""The plain reference's shared half: the blockwise loop, the widening
of a wire leaf, and the verdict.

The forward itself (tokens to the hidden state, one block, the hidden
state to logits) is the configuration's architecture module's
(``benchmark/archs/<arch>.py``): float32 ``jax.numpy`` at ``highest``
matmul precision (on a TPU a float32 matmul otherwise runs in bfloat16
passes), sharing no code with the program.  Layers are taken one at a
time from ``blob(b) -> {leaf: fabricate.Leaf}`` (views of the wire blob)
and widened to float32 on the device by the codec's published formula,
so the reference never holds more than one layer and the head, and moves
each wire byte to the device once.
"""

from __future__ import annotations

import numpy as np


def _widen(jnp, leaf):
    """A wire leaf as float32 on the device: raw is bfloat16 as it
    stands; int8 is ``bfloat16(float32(q) * scale)``, row by row."""
    import ml_dtypes

    if leaf.codec == "raw":
        x = jnp.asarray(leaf.values.view(ml_dtypes.bfloat16))
    else:
        x = (jnp.asarray(leaf.values).astype(jnp.float32)
             * jnp.asarray(leaf.scale)[:, None]).astype(jnp.bfloat16)
    return x.astype(jnp.float32).reshape(leaf.shape)


def logits(config: dict, tokens, blob) -> np.ndarray:
    """float32 logits ``[batch, seq, vocab]`` for int tokens; ``blob(b)``
    yields blob ``b``'s leaves (``fabricate.blob_leaves``)."""
    import jax
    import jax.numpy as jnp

    from benchmark import archs

    arch = archs.of(config)
    dims = arch.dims(config)
    layer = jax.jit(lambda p, h: arch.ref_layer(jnp, jax, dims, p, h))
    with jax.default_matmul_precision("highest"):
        head = {k: _widen(jnp, v) for k, v in blob(dims["layers"]).items()}
        h = arch.ref_in(jnp, dims, head,
                        jnp.asarray(np.asarray(tokens, np.int32)))
        for b in range(dims["layers"]):
            p = {k: _widen(jnp, v) for k, v in blob(b).items()}
            h = jax.block_until_ready(layer(p, h))
            del p
        out = arch.ref_out(jnp, dims, head, h)
        return np.asarray(jax.device_get(out), np.float32)


def compare(got, ref: np.ndarray, tokens: np.ndarray, prompt_len: int,
            tolerance: float) -> dict:
    """The verdict on one set of served sequences.

    ``tokens`` is ``[batch, prompt_len + new]``: each prompt followed by
    what the system answered.  ``ref`` holds logits for ``tokens[:, :-1]``,
    so position ``prompt_len - 1 + i`` predicts answered token ``i``.
    ``got`` is the system's own logits for the same input, or for a
    prefix of it (a pod gives those of its boot prompt only; the model is
    causal, so they are compared with the reference's first positions).

    - logits agree within ``tolerance`` (relative L2);
    - every answered token equals the reference's argmax wherever the
      reference's margin (top-1 minus top-2) exceeds four times the
      measured logit error.  Random weights give near-ties; a token on
      the wrong side of a tie is rounding, not a fault.
    Without ``got`` the error is not measured: the margin rule then uses
    ``tolerance`` times the reference's logit scale (RMS) instead.
    """
    ref = np.asarray(ref, np.float32)
    out = {"tolerance": tolerance}
    if got is not None:
        got = np.asarray(got, np.float32)
        part = ref[:, :got.shape[1]]
        err = float(np.abs(got - part).max())
        rel = float(np.linalg.norm(got - part) / np.linalg.norm(part))
        out.update(rel_l2=rel, max_abs_err=err,
                   finite=bool(np.isfinite(got).all()),
                   logits_ok=bool(np.isfinite(got).all()
                                  and rel < tolerance))
    else:
        err = float(tolerance * np.sqrt(np.mean(ref * ref)))
        out.update(rel_l2=None, max_abs_err=None, logits_ok=None,
                   assumed_err=err)
    pred = ref[:, prompt_len - 1:, :]
    top2 = np.sort(np.partition(pred, -2, axis=-1)[..., -2:], axis=-1)
    stable = (top2[..., 1] - top2[..., 0]) > 4 * err
    same = pred.argmax(-1) == np.asarray(tokens)[:, prompt_len:]
    out.update(
        stable_positions=int(stable.sum()), positions=int(stable.size),
        agree_where_stable=int((same & stable).sum()),
        agree_anywhere=int(same.sum()),
        tokens_ok=bool((same | ~stable).all()))
    out["passed"] = bool(out["tokens_ok"] and out["logits_ok"] is not False)
    return out
