"""Autoregressive decoding through a cache: the booted engine serves.

The reference's startup hook gestures at "launching an inference engine"
(``/root/reference/distributor/message.go:216-241``); ``runtime/boot.py``
makes the hook assemble the model and produce logits.  This module is
the serving half: a jitted, TPU-shaped decode loop —

- **prefill**: one full-attention pass over the prompt that also writes
  every layer's serving state into a preallocated cache (``lax.dynamic_
  update_slice`` at static offsets);
- **decode**: ``lax.scan`` over steps, each step attending the single
  new query against the cache under a position mask (static shapes —
  the cache is sized to ``prompt + max_new`` up front, so XLA compiles
  ONE step program and reuses it every token).

Greedy decoding is exact: ``tests/test_hf.py`` pins the generated token
ids to the ``transformers`` implementation's ``generate`` on the same
checkpoint.  Sampling takes a temperature + PRNG key.

What the cache holds and how a block runs through it is the family's
(``models/family.py``: ``init_cache`` / ``layer_with_cache`` — Llama's K
and V per KV head in ``models/llama.py``, with the same ``moe_ffn`` as
the full forward for its routed variant; another family's latent
vectors in its own module); the loops here are the same for every
family, and what a family's blocks count on the way (routing
slots, say) comes back beside the tokens in the same transfer
(``generate_counted``).

**Draft and verify.**  A family that holds a module which drafts
(``family.drafter``: a multi-token-prediction module) decodes, at
temperature 0, by steps that yield one token or two: the step forwards
the committed token and the module's draft of the next — two positions —
through the stack and the cache; the first position's argmax IS the next
token; where it equals the draft the second position's argmax is emitted
too; the module then drafts again from the last accepted position.  A
rejected draft leaves a stale row in the cache (the stack's and the
module's) that the next step overwrites before anything attends it: a
step writes rows ``pos, pos + 1`` and every query masks the rows past its
own position.  The tokens are those of the one-token decode (the draft
decides how many a step yields, never which); the whole decode is one
jitted ``while_loop`` of static shapes.  Counted beside the family's own:
``decode_steps``, ``mtp_drafted`` (drafts put to the stack) and
``mtp_accepted`` (drafts whose second token was emitted), so that
``decode_steps + mtp_accepted + 1`` is the number of tokens a request was
answered with.  Sampling (temperature > 0) keeps the one-token decode.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import family
from .llama import ModelConfig

Cache = Dict[str, Any]  # the family's; stacked as its parameters are
Counters = Dict[str, jax.Array]  # the family's; int32 scalars


class MixedVersionError(ValueError):
    """A serving tree was about to assemble from blobs of more than one
    rollout version — a forward across mixed layer versions would emit
    garbage that LOOKS like a healthy decode (docs/swap.md)."""


def ensure_uniform_version(versions: Dict[int, str],
                           expected: str = "") -> str:
    """The live-swap version guard: every blob entering a serving
    params tree must carry the SAME rollout version tag (and, when
    ``expected`` is non-empty, exactly that one).  Raises
    :class:`MixedVersionError` otherwise; returns the uniform version.
    Runs where params are ASSEMBLED — the one chokepoint every flip
    goes through — so no decode step can ever span two versions."""
    tags = set(versions.values())
    if len(tags) > 1:
        raise MixedVersionError(
            f"refusing to assemble serving params across mixed layer "
            f"versions {sorted(tags)!r}: {dict(sorted(versions.items()))}")
    got = next(iter(tags)) if tags else ""
    if expected and got != expected:
        raise MixedVersionError(
            f"serving params version {got!r} does not match the "
            f"committed version {expected!r}")
    return got


def init_cache(cfg, batch: int, max_len: int) -> Cache:
    return family.of(cfg).init_cache(cfg, batch, max_len)


def _hidden_with_cache(params, tokens, positions, cache, cfg):
    """Stacked-layer forward that threads the cache; returns (the stack's
    last hidden state ``[b, s, d]``, updated cache, the blocks' counters
    added up over the layers): ``family.scan_stack`` over the parameters
    and the state (a uniform family's one scan over its whole tree)."""
    fam = family.of(cfg)

    def step(x, layer_p, layer_cache):
        return fam.layer_with_cache(layer_p, x, positions, layer_cache, cfg)

    return family.scan_stack(cfg, step, fam.embed(params, tokens, cfg),
                             params["layers"], cache)


def _forward_with_cache(params, tokens, positions, cache, cfg):
    """``_hidden_with_cache`` with the head over the LAST position:
    returns (its logits, updated cache, counters)."""
    x, cache, total = _hidden_with_cache(params, tokens, positions, cache,
                                         cfg)
    logits = family.of(cfg).logits(params, x[:, -1:, :], cfg)[:, 0, :]
    return logits, cache, total


def _pick(logits, step_key, temperature: float):
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        step_key, logits / temperature, axis=-1
    ).astype(jnp.int32)


@functools.lru_cache(maxsize=32)
def _prefill_fn(cfg: ModelConfig, p: int):
    @jax.jit
    def prefill(params, prompt, cache):
        with jax.named_scope("generate.prefill"):
            return _forward_with_cache(params, prompt, jnp.arange(p),
                                       cache, cfg)

    return prefill


@functools.lru_cache(maxsize=32)
def _decode_fn(cfg: ModelConfig, p: int, max_new: int, temperature: float):
    @jax.jit
    def decode(params, cache, first, keys, counted):
        def step(carry, scanned):
            cache, token, pos, counted = carry
            step_key, = scanned
            with jax.named_scope("generate.step"):
                logits, cache, more = _forward_with_cache(
                    params, token[:, None], pos[None], cache, cfg
                )
                nxt = _pick(logits, step_key, temperature)
            counted = jax.tree.map(jnp.add, counted, more)
            return (cache, nxt, pos + 1, counted), token

        (_, last, _, counted), toks = jax.lax.scan(
            step, (cache, first, jnp.asarray(p, jnp.int32), counted),
            (keys,), length=max_new - 1,
        )
        # toks holds tokens emitted BEFORE each step: [first, ...]; the
        # final pick is `last`.
        return jnp.concatenate([toks.T, last[:, None]], axis=1), counted

    return decode


@functools.lru_cache(maxsize=32)
def _decode_step_fn(cfg: ModelConfig, temperature: float):
    """ONE jitted decode step (vs ``_decode_fn``'s whole-generation
    scan): forward the carried token at ``pos``, pick the next.  The
    position is a traced scalar, so every step of a generation reuses
    the same compiled program — the per-token flip path costs one
    dispatch per token, not one compile."""

    @jax.jit
    def step(params, cache, token, pos, step_key):
        with jax.named_scope("generate.step"):
            logits, cache, _ = _forward_with_cache(
                params, token[:, None], pos[None], cache, cfg
            )
            return _pick(logits, step_key, temperature), cache

    return step


# ------------------------------------------------------ draft and verify

_DRAFT_COUNTERS = ("decode_steps", "mtp_drafted", "mtp_accepted")


def _drafts(cfg, temperature: float) -> bool:
    """Whether a request decodes by draft and verify: greedy, and the
    family holds a module that drafts."""
    return temperature <= 0 and family.drafter(cfg) is not None


def _argmax(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _add(counted: Counters, *more: Counters) -> Counters:
    out = dict(counted)
    for m in more:
        for name, c in m.items():
            out[name] = out[name] + c if name in out else c
    return out


@functools.lru_cache(maxsize=32)
def _draft_prefill_fn(cfg, p: int):
    """The prompt through the stack AND the module: (first token, the
    module's draft of the second, cache, counters — every name the decode
    will add to, so that the two programs' trees agree)."""
    fam, draft = family.of(cfg), family.drafter(cfg)

    @jax.jit
    def prefill(params, prompt, cache):
        with jax.named_scope("generate.prefill"):
            positions = jnp.arange(p)
            x, cache, counted = _hidden_with_cache(params, prompt, positions,
                                                   cache, cfg)
            first = _argmax(fam.logits(params, x[:, -1:, :], cfg)[:, 0, :])
            nxt = jnp.concatenate([prompt[:, 1:], first[:, None]], axis=1)
            logits, cache, more = draft(params, x, nxt, positions, cache,
                                        cfg, p - 1)
            zero = {name: jnp.zeros((), jnp.int32)
                    for name in _DRAFT_COUNTERS}
            return first, _argmax(logits), cache, _add(counted, more, zero)

    return prefill


def _draft_step(params, cache, token, guess, pos, may_accept, cfg):
    """One step: the committed ``token`` (at ``pos``) and the ``guess`` at
    the next through the stack, then the module over the same two
    positions.  Returns (the two positions' argmax ``[b, 2]``, whether
    the second is emitted too, the next draft, cache, counters).
    ``may_accept`` is False where a second token would be one too many.
    With a batch, a step yields two tokens only where every sequence's
    draft held (the rows of a batch share their positions)."""
    fam, draft = family.of(cfg), family.drafter(cfg)
    with jax.named_scope("generate.step"):
        positions = pos + jnp.arange(2)
        x, cache, counted = _hidden_with_cache(
            params, jnp.stack([token, guess], axis=1), positions, cache, cfg)
        picks = _argmax(fam.logits(params, x, cfg))
        ok = jnp.all(picks[:, 0] == guess) & may_accept
        logits, cache, more = draft(params, x, picks, positions, cache, cfg,
                                    ok.astype(jnp.int32))
    n = jnp.asarray(token.shape[0], jnp.int32)
    return picks, ok, _argmax(logits), cache, _add(counted, more, {
        "decode_steps": jnp.ones((), jnp.int32), "mtp_drafted": n,
        "mtp_accepted": n * ok.astype(jnp.int32)})


@functools.lru_cache(maxsize=32)
def _draft_decode_fn(cfg, p: int, max_new: int):
    """The whole decode after the prefill as ONE program: a ``while_loop``
    of ``_draft_step`` until ``max_new`` tokens stand in the output
    (between half as many steps as tokens and as many)."""

    @jax.jit
    def decode(params, cache, first, guess, counted):
        # one slot past max_new: a step always writes two
        out = jnp.zeros((first.shape[0], max_new + 1), jnp.int32)

        def step(c):
            n = c["n"]
            picks, ok, guess, cache, more = _draft_step(
                params, c["cache"], c["token"], c["guess"], p + n - 1,
                n + 1 < max_new, cfg)
            return {"n": n + 1 + ok.astype(jnp.int32),
                    "token": jnp.where(ok, picks[:, 1], picks[:, 0]),
                    "guess": guess, "cache": cache,
                    # a rejected second token is overwritten by the next
                    # step's first
                    "out": jax.lax.dynamic_update_slice(c["out"], picks,
                                                        (0, n)),
                    "counted": jax.tree.map(jnp.add, c["counted"], more)}

        c = jax.lax.while_loop(
            lambda c: c["n"] < max_new, step,
            {"n": jnp.ones((), jnp.int32), "token": first, "guess": guess,
             "cache": cache, "out": out.at[:, 0].set(first),
             "counted": counted})
        return c["out"][:, :max_new], c["counted"]

    return decode


@functools.lru_cache(maxsize=32)
def _draft_step_fn(cfg):
    """ONE jitted ``_draft_step`` (the per-token flip path's: position
    and ``may_accept`` are traced, so every step reuses the program)."""
    return jax.jit(functools.partial(_draft_step, cfg=cfg))


def generate_stepwise(
    params_fn,
    prompt: jax.Array,
    cfg: ModelConfig,
    max_new: int,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Token-at-a-time decoding that RE-READS the serving params before
    every step — the per-token flip granularity of docs/rollout.md: an
    in-flight generation finishes its current token on the params it
    holds and picks up a freshly committed version on the NEXT decode
    step, instead of pinning the flip behind the whole request.

    ``params_fn() -> (params, version)`` is called once for the prefill
    and once per decode step; the caller owns the per-step version
    guard (the receiver's provider runs ``ensure_uniform_version`` on
    the serving tree before returning it, so a step can never execute
    on a mixed-version tree).  With a CONSTANT provider the emitted
    tokens are exactly ``generate``'s — same kernels, same order, the
    scan merely unrolled into per-step dispatches.  Note the KV cache
    rows written before a mid-generation flip were computed under the
    PREVIOUS version — the documented semantics of per-token pickup
    (docs/rollout.md), not a bug: the alternative is serving the stale
    version for the whole request."""
    if max_new <= 0:
        raise ValueError(f"max_new must be positive, got {max_new}")
    if temperature > 0 and key is None:
        raise ValueError("sampling needs a PRNG key")
    b, p = prompt.shape
    cache = init_cache(cfg, b, p + max_new)
    params, _ = params_fn()
    if _drafts(cfg, temperature):
        token, guess, cache, _ = _draft_prefill_fn(cfg, p)(params, prompt,
                                                           cache)
        out, step = [token], _draft_step_fn(cfg)
        while len(out) < max_new:
            params, _ = params_fn()
            n = len(out)
            picks, ok, guess, cache, _ = step(
                params, cache, token, guess, jnp.asarray(p + n - 1, jnp.int32),
                jnp.asarray(n + 1 < max_new))
            out += [picks[:, 0], picks[:, 1]] if bool(ok) else [picks[:, 0]]
            token = out[-1]
        return jnp.stack(out, axis=1)
    logits, cache, _ = _prefill_fn(cfg, p)(params, prompt, cache)
    keys = (jax.random.split(key, max_new) if key is not None
            else jnp.zeros((max_new, 2), jnp.uint32))
    token = _pick(logits, keys[0], temperature)
    out = [token]
    step = _decode_step_fn(cfg, float(temperature))
    for i in range(1, max_new):
        params, _ = params_fn()
        token, cache = step(params, cache, token,
                            jnp.asarray(p + i - 1, jnp.int32), keys[i])
        out.append(token)
    return jnp.stack(out, axis=1)


def generate_counted(
    params: Dict[str, Any],
    prompt: jax.Array,
    cfg,
    max_new: int,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Counters]:
    """Decode ``max_new`` tokens after ``prompt`` [b, p] (int32).

    temperature 0 = greedy (exact — parity-tested against transformers);
    otherwise softmax sampling with ``key``.  Returns ([b, max_new], what
    the family's blocks counted over every position of the request — a
    dict of int32 scalars, empty for a family that counts nothing); both
    are outputs of the same jitted programs, so one ``device_get`` brings
    them.

    The prefill and decode programs are built per (cfg, shapes,
    temperature) and cached — repeated serving calls on a booted model
    reuse the compiled step, they don't re-trace."""
    if max_new <= 0:
        raise ValueError(f"max_new must be positive, got {max_new}")
    if temperature > 0 and key is None:
        raise ValueError("sampling needs a PRNG key")
    b, p = prompt.shape
    cache = init_cache(cfg, b, p + max_new)
    if _drafts(cfg, temperature):
        first, guess, cache, counted = _draft_prefill_fn(cfg, p)(
            params, prompt, cache)
        if max_new == 1:
            return first[:, None], counted
        return _draft_decode_fn(cfg, p, max_new)(params, cache, first, guess,
                                                 counted)

    logits, cache, counted = _prefill_fn(cfg, p)(params, prompt, cache)
    keys = (jax.random.split(key, max_new) if key is not None
            else jnp.zeros((max_new, 2), jnp.uint32))
    first = _pick(logits, keys[0], temperature)
    if max_new == 1:
        return first[:, None], counted
    return _decode_fn(cfg, p, max_new, temperature)(
        params, cache, first, keys[1:], counted
    )


def generate(params, prompt, cfg, max_new, temperature=0.0, key=None):
    """``generate_counted``'s tokens alone."""
    return generate_counted(params, prompt, cfg, max_new, temperature,
                            key)[0]
