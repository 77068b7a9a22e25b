"""Fully-sharded training step: dp / sp / pp / ep / tp on one mesh.

The five parallelism strategies, each implemented with explicit collectives
inside a single fully-manual ``jax.shard_map`` program:

- **dp** — batch dim sharded; gradients all-reduced (psum) over ``dp``.
- **sp** — sequence dim sharded; ring attention rotates K/V blocks around
  the ``sp`` axis (``parallel/ring_attention.py``).
- **pp** — the stacked layer axis sharded over ``pp``: each stage owns
  n_layers/pp layers (exactly the reference's Assignment as stage
  placement); activations hand off stage→stage by ``ppermute``, and the
  sequential fill means logits are valid on stage 0 after the wrap-around.
  AD masks the in-fill garbage paths to zero cotangents automatically.
- **ep** — MoE expert dim sharded over ``ep``; each device computes its
  local experts densely and contributions combine by psum over ``ep``.
- **tp** — Megatron-style: attention heads and FFN hidden dim sharded over
  ``tp``; the row-parallel matmuls (wo, w2) psum their partial sums.  The
  lm head is vocab-sharded, with the softmax cross-entropy computed via
  pmax/psum over ``tp`` so no device materializes the full vocab.

Mesh axes are factored from the device count in priority order
tp → pp → sp → ep → dp, so an 8-chip slice runs (tp2, pp2, sp2) and larger
pods enable ep and dp too.
"""

from __future__ import annotations

import functools
import threading
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import make_mesh
from ..parallel.ring_attention import ring_attention
from ..utils import trace
from . import family
from .llama import ModelConfig, rms_norm, rope, route_topk

AXES = ("dp", "sp", "pp", "ep", "tp")


def _llama_only(cfg) -> None:
    """Every entry of this module refuses another family by name: its
    partition specs, local layer and pipeline stages are written out for
    Llama's nine leaves, its one FFN and its K/V cache."""
    family.only(
        cfg, ("llama",), "models/sharded.py",
        "its partition specs, sharded layer and pipeline stages (train "
        "step, build_pp_forward, build_pp_decode) are written for Llama's "
        "leaves and K/V cache; this family has no ep/tp/pp form yet")


def factor_mesh_axes(n_devices: int, cfg: ModelConfig) -> Dict[str, int]:
    """Split n_devices over (dp, sp, pp, ep, tp) round-robin in priority
    order tp → pp → sp → ep → dp, one prime factor per axis per round.

    tp must divide n_kv_heads, pp must divide n_layers, ep must divide
    n_experts (dense models keep ep=1); sp and dp are unconstrained."""
    _llama_only(cfg)
    sizes = {a: 1 for a in AXES}

    def accepts(axis: str, f: int) -> bool:
        if axis == "tp":
            return cfg.n_kv_heads % (sizes["tp"] * f) == 0
        if axis == "pp":
            return cfg.n_layers % (sizes["pp"] * f) == 0
        if axis == "ep":
            return cfg.n_experts > 0 and cfg.n_experts % (sizes["ep"] * f) == 0
        return True  # sp, dp unconstrained

    remaining = n_devices
    while remaining > 1:
        # dp accepts anything, so each pass always consumes a factor.
        for axis in ("tp", "pp", "sp", "ep", "dp"):
            if remaining == 1:
                break
            f = next(p for p in range(2, remaining + 1) if remaining % p == 0)
            if accepts(axis, f):
                sizes[axis] *= f
                remaining //= f
    return sizes


def make_train_mesh(n_devices: int, cfg: ModelConfig) -> Mesh:
    sizes = factor_mesh_axes(n_devices, cfg)
    return make_mesh([sizes[a] for a in AXES], AXES)


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    """PartitionSpec per parameter leaf (layer leaves lead with the
    pp-sharded stacked-layer axis)."""
    _llama_only(cfg)
    layers = {
        "wq": P("pp", None, "tp"),
        "wk": P("pp", None, "tp"),
        "wv": P("pp", None, "tp"),
        "wo": P("pp", "tp", None),
        "ln1": P("pp", None),
        "ln2": P("pp", None),
    }
    if cfg.n_experts:
        layers.update(
            router=P("pp", None, None),
            w1=P("pp", "ep", None, "tp"),
            w3=P("pp", "ep", None, "tp"),
            w2=P("pp", "ep", "tp", None),
        )
    else:
        layers.update(
            w1=P("pp", None, "tp"),
            w3=P("pp", None, "tp"),
            w2=P("pp", "tp", None),
        )
    return {
        "embed": P(),
        "layers": layers,
        "ln_f": P(),
        "lm_head": P(None, "tp"),
    }


def shard_params(params, mesh: Mesh, cfg: ModelConfig):
    """device_put every leaf under its spec (leaf orders align: the spec
    tree mirrors the param tree's dict structure)."""
    specs = param_specs(cfg)
    flat_p, treedef = jax.tree.flatten(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    placed = [
        jax.device_put(x, NamedSharding(mesh, s)) for x, s in zip(flat_p, flat_s)
    ]
    return jax.tree.unflatten(treedef, placed)


def _grad_reduce_axes(spec: P) -> Tuple[str, ...]:
    """Axes a parameter is replicated over — its gradient psum axes."""
    used = {a for part in spec if part for a in (part if isinstance(part, tuple) else (part,))}
    return tuple(a for a in AXES if a not in used)


# ---------------------------------------------------------------- per-device


def _local_layer(cfg: ModelConfig, p, x, q_pos):
    """One transformer layer on this device's shard (manual collectives)."""
    b, s_loc, d = x.shape
    hd = cfg.head_dim
    h_loc = p["wq"].shape[-1] // hd
    kv_loc = p["wk"].shape[-1] // hd

    xn = rms_norm(x, p["ln1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dq->bsq", xn, p["wq"]).reshape(b, s_loc, h_loc, hd)
    k = jnp.einsum("bsd,dq->bsq", xn, p["wk"]).reshape(b, s_loc, kv_loc, hd)
    v = jnp.einsum("bsd,dq->bsq", xn, p["wv"]).reshape(b, s_loc, kv_loc, hd)
    q = rope(q, q_pos, cfg.rope_theta)
    k = rope(k, q_pos, cfg.rope_theta)
    attn = ring_attention(q, k, v, "sp", s_loc)  # sp collective inside
    o_part = jnp.einsum("bsq,qd->bsd", attn.reshape(b, s_loc, h_loc * hd), p["wo"])
    x = x + lax.psum(o_part, "tp")  # tp row-parallel reduce

    xn = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.n_experts:
        e_loc = p["w1"].shape[0]
        ep_idx = lax.axis_index("ep")
        logits = jnp.einsum("bsd,de->bse", xn, p["router"]).astype(jnp.float32)
        weights = route_topk(jax.nn.softmax(logits, axis=-1), cfg)
        w_loc = lax.dynamic_slice_in_dim(weights, ep_idx * e_loc, e_loc, axis=-1)
        gate = jax.nn.silu(jnp.einsum("bsd,edf->besf", xn, p["w1"]))
        up = jnp.einsum("bsd,edf->besf", xn, p["w3"])
        out_part = jnp.einsum("besf,efd->besd", gate * up, p["w2"])
        mixed = jnp.einsum("besd,bse->bsd", out_part, w_loc.astype(x.dtype))
        x = x + lax.psum(mixed, ("ep", "tp"))
    else:
        gate = jax.nn.silu(jnp.einsum("bsd,df->bsf", xn, p["w1"]))
        up = jnp.einsum("bsd,df->bsf", xn, p["w3"])
        down_part = jnp.einsum("bsf,fd->bsd", gate * up, p["w2"])
        x = x + lax.psum(down_part, "tp")
    return x


def _local_loss(cfg: ModelConfig, pp_size: int, params, inputs, targets,
                remat: bool = False):
    """Per-device loss: embedding → pipeline loop → vocab-sharded CE.
    ``inputs``/``targets`` arrive pre-shifted on host so sequence sharding
    over sp never straddles the shift boundary.  ``remat``: checkpoint
    each scanned layer so the backward recomputes its activations
    instead of keeping every layer's live (O(1) vs O(n_layers) layer
    activations; bit-identical results)."""
    b, s_loc = inputs.shape
    sp_idx = lax.axis_index("sp")
    q_pos = sp_idx * s_loc + jnp.arange(s_loc)

    x = params["embed"][inputs]

    layer_fn = functools.partial(_local_layer, cfg)
    if remat:
        layer_fn = jax.checkpoint(layer_fn)

    def run_stage(x):
        def body(h, layer_p):
            return layer_fn(layer_p, h, q_pos), None

        return lax.scan(body, x, params["layers"])[0]

    # Sequential pipeline fill: stage s applies its layers at hop s; after
    # pp hops the fully-processed activations have wrapped back to stage 0.
    fwd = [(i, (i + 1) % pp_size) for i in range(pp_size)]
    for _ in range(pp_size):
        x = run_stage(x)
        if pp_size > 1:
            x = lax.ppermute(x, "pp", fwd)

    xn = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", xn, params["lm_head"],
                        preferred_element_type=jnp.float32)

    # Cross-entropy over the tp-sharded vocab: global logsumexp via
    # pmax+psum; the target logit is owned by exactly one tp member.
    v_loc = logits.shape[-1]
    tp_idx = lax.axis_index("tp")
    # Global max for stabilization only (gradient-neutral); pmax has no
    # diff rule, so gather the per-shard maxes instead.
    m_local = lax.stop_gradient(logits.max(axis=-1))
    m = lax.all_gather(m_local, "tp").max(axis=0)
    sumexp = lax.psum(jnp.exp(logits - m[..., None]).sum(axis=-1), "tp")
    lse = jnp.log(sumexp) + m
    tgt_local = targets - tp_idx * v_loc
    own = (tgt_local >= 0) & (tgt_local < v_loc)
    safe = jnp.clip(tgt_local, 0, v_loc - 1)
    picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    tgt_logit = lax.psum(jnp.where(own, picked, 0.0), "tp")
    nll = (lse - tgt_logit).mean()

    # Only stage 0 holds valid logits (wrap-around); other stages' paths
    # get zero cotangents through this mask.  The return value is this
    # device's SHARE of the global mean loss: the nll is computed
    # redundantly on every (tp, ep) member and split across (dp, sp) data
    # shards, so dividing by dp*sp*tp*ep makes the all-axis psum of shares
    # equal the global mean — and makes per-leaf gradient psums over each
    # leaf's replication group exact (validated against jax.grad of the
    # unsharded loss on 11 mesh shapes to ~1e-6).
    pp_idx = lax.axis_index("pp")
    denom = (
        lax.axis_size("dp")
        * lax.axis_size("sp")
        * lax.axis_size("tp")
        * lax.axis_size("ep")
    )
    return jnp.where(pp_idx == 0, nll, 0.0) / denom


def build_train_step(cfg: ModelConfig, mesh: Mesh, lr: float = 1e-3,
                     remat: bool = True):
    """jitted (params, tokens) -> (params, loss) over the 5-axis mesh.

    ``remat``: rematerialize each layer's activations in the backward
    pass (``jax.checkpoint`` on the scanned layer body) — the standard
    TPU memory/FLOPs trade: per-layer activations are not kept live
    across the whole backward, at the cost of one extra forward.
    Numerics are identical (tested)."""
    pp_size = mesh.shape["pp"]
    specs = param_specs(cfg)
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    loss_fn = functools.partial(_local_loss, cfg, pp_size, remat=remat)

    def per_device(params, inputs, targets):
        loss_share, grads = jax.value_and_grad(loss_fn)(
            params, inputs, targets)
        loss = lax.psum(loss_share, AXES)  # shares sum to the global mean
        flat_grads, treedef = jax.tree.flatten(grads)
        flat_grads = [
            lax.psum(g, axes) if (axes := _grad_reduce_axes(s)) else g
            for g, s in zip(flat_grads, flat_specs)
        ]
        grads = jax.tree.unflatten(treedef, flat_grads)
        new_params = jax.tree.map(
            lambda p, g: (
                p.astype(jnp.float32) - lr * g.astype(jnp.float32)
            ).astype(p.dtype),
            params,
            grads,
        )
        return new_params, loss

    step = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(specs, P("dp", "sp"), P("dp", "sp")),
        out_specs=(specs, P()),
        check_vma=False,
    )
    return jax.jit(step, donate_argnums=(0,))


def init_adamw_state(params):
    """AdamW moments, one (m, v) pair per leaf — f32 regardless of the
    param dtype (bf16 moments lose the small-update tail), sharded
    EXACTLY like their leaves (the state specs mirror param_specs)."""
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def adamw_state_specs(cfg: ModelConfig):
    """PartitionSpecs for ``init_adamw_state``'s tree: moments shard
    like params; the step counter is replicated."""
    specs = param_specs(cfg)
    return {"m": specs, "v": specs, "step": P()}


def build_adamw_train_step(cfg: ModelConfig, mesh: Mesh, lr: float = 1e-3,
                           betas=(0.9, 0.999), eps: float = 1e-8,
                           weight_decay: float = 0.01, remat: bool = True):
    """jitted (params, opt_state, inputs, targets) -> (params, opt_state,
    loss): AdamW with bias correction and decoupled weight decay, the
    moments sharded exactly like the params (each leaf's m/v live on the
    same devices as the leaf — no extra collectives beyond the gradient
    psums the SGD step already pays).  Params and state are donated."""
    pp_size = mesh.shape["pp"]
    specs = param_specs(cfg)
    state_specs = adamw_state_specs(cfg)
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    b1, b2 = betas
    loss_fn = functools.partial(_local_loss, cfg, pp_size, remat=remat)

    def per_device(params, opt_state, inputs, targets):
        loss_share, grads = jax.value_and_grad(loss_fn)(
            params, inputs, targets)
        loss = lax.psum(loss_share, AXES)
        flat_grads, treedef = jax.tree.flatten(grads)
        flat_grads = [
            lax.psum(g, axes) if (axes := _grad_reduce_axes(s)) else g
            for g, s in zip(flat_grads, flat_specs)
        ]
        grads = jax.tree.unflatten(treedef, flat_grads)
        t = opt_state["step"] + 1
        c1 = 1.0 - b1 ** t.astype(jnp.float32)
        c2 = 1.0 - b2 ** t.astype(jnp.float32)

        def upd(p, g, m, v):
            g = g.astype(jnp.float32)
            m = b1 * m + (1.0 - b1) * g
            v = b2 * v + (1.0 - b2) * g * g
            step_dir = (m / c1) / (jnp.sqrt(v / c2) + eps)
            new_p = (p.astype(jnp.float32)
                     - lr * (step_dir + weight_decay * p.astype(jnp.float32))
                     ).astype(p.dtype)
            return new_p, m, v

        out = jax.tree.map(upd, params, grads,
                           opt_state["m"], opt_state["v"])
        # tree of (p, m, v) tuples -> three trees
        new_params = jax.tree.map(lambda o: o[0], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
        new_m = jax.tree.map(lambda o: o[1], out,
                             is_leaf=lambda o: isinstance(o, tuple))
        new_v = jax.tree.map(lambda o: o[2], out,
                             is_leaf=lambda o: isinstance(o, tuple))
        return new_params, {"m": new_m, "v": new_v, "step": t}, loss

    step = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(specs, state_specs, P("dp", "sp"), P("dp", "sp")),
        out_specs=(specs, state_specs, P()),
        check_vma=False,
    )
    return jax.jit(step, donate_argnums=(0, 1))


def example_batch(cfg: ModelConfig, mesh: Mesh, batch: int = 0, seq: int = 0):
    """(inputs, targets) shaped to divide evenly over (dp, sp)."""
    dp, sp = mesh.shape["dp"], mesh.shape["sp"]
    batch = batch or 2 * dp
    seq = seq or 8 * sp
    assert batch % dp == 0 and seq % sp == 0
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab, size=(batch, seq + 1), dtype=np.int32)
    sharding = NamedSharding(mesh, P("dp", "sp"))
    inputs = jax.device_put(jnp.asarray(tokens[:, :-1]), sharding)
    targets = jax.device_put(jnp.asarray(tokens[:, 1:]), sharding)
    return inputs, targets


# ------------------------------------------------------------ pp inference

# A pod's pipelined programs, kept for the life of the process the way
# ``models/generate.py`` keeps the one-chip ones: key -> jitted function.
_PP_PROGRAMS: Dict[tuple, Any] = {}
_PP_PROGRAMS_MAX = 32  # generate.py's lru_cache size; the oldest key goes
_PP_PROGRAMS_LOCK = threading.Lock()


def _kept_pp_program(program, *key):
    """The jitted function that ``program(*key)`` builds, built on the
    first call with this key and kept.  A function object that stays the
    same is what ``jax.jit``'s own in-memory caches key on: an equal key
    neither traces, lowers nor loads again.  ``serve.pp_program.built``
    / ``.reused`` count which of the two a call was.  Built under the
    lock (wrapping is cheap, ``jax.jit`` compiles at the first call): two
    callers of one key must get ONE function, or each compiles its own."""
    with _PP_PROGRAMS_LOCK:
        fn = _PP_PROGRAMS.get((program, *key))
        if fn is not None:
            trace.count("serve.pp_program.reused")
            return fn
        fn = program(*key)
        if len(_PP_PROGRAMS) >= _PP_PROGRAMS_MAX:
            _PP_PROGRAMS.pop(next(iter(_PP_PROGRAMS)))
        _PP_PROGRAMS[(program, *key)] = fn
        trace.count("serve.pp_program.built")
        return fn


def build_pp_forward(cfg: ModelConfig, mesh: Mesh, pp_axis: str):
    """The pod's pipelined forward, ONE function per ``(cfg, mesh,
    pp_axis)`` for the life of the process (``_kept_pp_program``; a mesh
    made anew over the same devices and axis names is an equal key).
    Batch and prompt length are traced shapes that ``jax.jit`` keys on."""
    return _kept_pp_program(_pp_forward_program, cfg, mesh, pp_axis)


def build_pp_decode(cfg: ModelConfig, mesh: Mesh, pp_axis: str,
                    max_new: int):
    """The pod's pipelined greedy decode, ONE function per ``(cfg, mesh,
    pp_axis, max_new)`` for the life of the process, like
    ``build_pp_forward``."""
    return _kept_pp_program(_pp_decode_program, cfg, mesh, pp_axis, max_new)


def _pp_forward_program(cfg: ModelConfig, mesh: Mesh, pp_axis: str):
    """jitted (layers, counts, head, tokens) -> logits over a
    pipeline-sharded mesh: each stage holds its stacked slice resident
    (the Assignment's placement — what dissemination landed), head leaves
    are replicated, and activations hand off stage→stage by ``ppermute``
    exactly like the train step's pipeline fill.  Logits are valid on
    stage 0 after the wrap-around and broadcast by psum.

    UNEVEN contiguous partitions serve too: slices arrive PADDED to the
    deepest stage and ``counts`` [pp] (sharded along ``pp_axis``) gives
    each stage's real depth — the padded tail passes the hidden state
    through unchanged.

    Any extra mesh axes (e.g. tp) replicate the computation — this is the
    serving form of the staged placement, not the full 5-axis program.

    The function is KEPT across deliveries, so it closes over nothing
    that lives on a device (``cfg``, ``mesh``, ``pp``, ``fwd`` only): a
    swap, or the benchmark's cold round, deletes every live array."""
    _llama_only(cfg)
    from .llama import layer_apply

    pp = mesh.shape[pp_axis]
    fwd = [(i, (i + 1) % pp) for i in range(pp)]

    def per_device(layers_local, counts_local, head, tokens):
        count = counts_local[0]
        l_max = jax.tree.leaves(layers_local)[0].shape[0]
        positions = jnp.arange(tokens.shape[1])
        x = head["embed"][tokens]

        def body(h, scanned):
            layer_p, li = scanned
            h_new = layer_apply(layer_p, h, positions, cfg)
            return jnp.where(li < count, h_new, h), None

        for _ in range(pp):
            x = lax.scan(body, x, (layers_local, jnp.arange(l_max)))[0]
            if pp > 1:
                x = lax.ppermute(x, pp_axis, fwd)

        if pp > 1:
            # Broadcast the valid (stage-0) HIDDEN STATE, not the logits:
            # [b, s, d_model] over ICI instead of [b, s, vocab] — ~vocab/d
            # times less collective traffic for the same result.
            idx = lax.axis_index(pp_axis)
            x = lax.psum(jnp.where(idx == 0, x, 0.0), pp_axis)
        xn = rms_norm(x, head["ln_f"], cfg.norm_eps)
        return jnp.einsum(
            "bsd,dv->bsv", xn, head["lm_head"],
            preferred_element_type=jnp.float32,
        )

    f = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(pp_axis), P(pp_axis), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(f)


def _pp_decode_program(cfg: ModelConfig, mesh: Mesh, pp_axis: str,
                       max_new: int):
    """jitted (layers, counts, head, prompt) -> greedy token ids
    [b, max_new]: the KV-cached decode loop (``models/generate.py``) run
    as a lockstep pipeline collective over the staged placement — the
    multi-controller serving analogue of the reference's startup
    inference hook (message.go:216-241).

    Mechanics: in pipeline-rotation round r only stage r's application
    is REAL (the rotated copies other stages chew are in-fill garbage,
    same as ``_pp_forward_program``), so each stage masks its per-layer KV
    cache writes to ``(round == my_stage) & (layer < count)`` — the
    cache stays exact while every process executes the identical
    program.  The final hidden state wraps to stage 0, is psum-broadcast
    as [b, d_model], and argmax picks the next token identically on
    every device, so the replicated decode loop can never diverge.
    Uneven padded slices work exactly as in ``_pp_forward_program``, and
    like it the kept function closes over no device array (the KV cache
    is made inside the program)."""
    _llama_only(cfg)
    from .llama import layer_with_cache

    pp = mesh.shape[pp_axis]
    fwd = [(i, (i + 1) % pp) for i in range(pp)]

    def per_device(layers_local, counts_local, head, prompt):
        count = counts_local[0]
        idx = lax.axis_index(pp_axis)
        b, p = prompt.shape
        l_max = jax.tree.leaves(layers_local)[0].shape[0]
        max_len = p + max_new
        kc = jnp.zeros((l_max, b, max_len, cfg.n_kv_heads, cfg.head_dim),
                       cfg.dtype)
        vc = jnp.zeros_like(kc)

        def pipeline(x, positions, kc, vc):
            """One full pipelined pass; returns (last-pos logits, caches)."""
            for r in range(pp):
                real = idx == r

                def body(h, scanned):
                    layer_p, k_l, v_l, li = scanned
                    h_new, kv_new, _ = layer_with_cache(
                        layer_p, h, positions, {"k": k_l, "v": v_l}, cfg)
                    k_new, v_new = kv_new["k"], kv_new["v"]
                    valid = real & (li < count)
                    return (
                        jnp.where(valid, h_new, h),
                        (jnp.where(valid, k_new, k_l),
                         jnp.where(valid, v_new, v_l)),
                    )

                x, (kc, vc) = lax.scan(
                    body, x, (layers_local, kc, vc, jnp.arange(l_max)))
                if pp > 1:
                    x = lax.ppermute(x, pp_axis, fwd)
            if pp > 1:
                x = lax.psum(jnp.where(idx == 0, x, 0.0), pp_axis)
            xn = rms_norm(x[:, -1, :], head["ln_f"], cfg.norm_eps)
            logits = jnp.einsum("bd,dv->bv", xn, head["lm_head"],
                                preferred_element_type=jnp.float32)
            return logits, kc, vc

        logits, kc, vc = pipeline(
            head["embed"][prompt], jnp.arange(p), kc, vc)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if max_new == 1:
            return first[:, None]

        def step(carry, _):
            kc, vc, token, pos = carry
            logits, kc, vc = pipeline(
                head["embed"][token[:, None]], pos[None], kc, vc)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (kc, vc, nxt, pos + 1), token

        (_, _, last, _), toks = lax.scan(
            step, (kc, vc, first, jnp.asarray(p, jnp.int32)),
            None, length=max_new - 1,
        )
        return jnp.concatenate([toks.T, last[:, None]], axis=1)

    f = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(pp_axis), P(pp_axis), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(f)
