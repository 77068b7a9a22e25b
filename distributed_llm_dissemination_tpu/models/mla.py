"""Latent attention (MLA), for the families that have it
(``models/longcat.py``, ``models/joyai.py``), and the float32 helpers
they share.

    MLA(x): cq = RMS(Wqa·x)·g·q_scale;  q = Wqb·cq -> heads of nope + rope
            [ckv | kr] = Wkva·x;  ckv = RMS(ckv)·g·kv_scale
            [k_nope | v] = Wkvb·ckv -> heads of nope + v;  kr: ONE rotary head for all
            rotary on q's rope part and on kr (interleaved pairs);  k = [k_nope | kr]
            out = Wo · softmax(q·k / sqrt(nope + rope), causal) · v

A configuration gives the sizes under the same names in both families
(``heads_held``, ``q_rank``, ``kv_rank``, ``nope_dim``, ``rope_dim``,
``v_dim``, ``rope_theta``, ``norm_eps``, ``dtype``); the leaves are
``wq_a``, ``q_norm``, ``wq_b``, ``wkv_a``, ``kv_norm``, ``wkv_b``, ``wo``,
each followed by the caller's ``sfx`` (LongCat has two attentions a
layer: ``"_0"``, ``"_1"``).  What a family chooses besides: the two
scales (LongCat's ``mla_scale_*``; 1.0 where there is none), the product
``mm`` its weights are multiplied with, the dtype ``carry`` that queries
and the latent pair are handed on in (and a latent cache keeps), and the
``precision`` of the attention's own two products.

Per position the latent cache keeps ``ckv`` (after its norm and scale)
and the rotated ``kr``; prefill and decode up-project the whole cache
through ``wkv_b`` at every step (``wkv_b`` is not absorbed into the query
and output projections).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _mm(spec: str, x, w):
    """A product of ``w.dtype`` operands accumulated in float32.  XLA's
    CPU backend has no bfloat16 x bfloat16 = float32 dot, so off the TPU
    the rounded operands are widened first: the same products, the same
    accumulator."""
    x = x.astype(w.dtype)
    if w.dtype == jnp.float32:
        return jnp.einsum(spec, x, w)
    return jax.lax.platform_dependent(
        x, w,
        tpu=lambda x, w: jnp.einsum(spec, x, w,
                                    preferred_element_type=jnp.float32),
        default=lambda x, w: jnp.einsum(spec, x.astype(jnp.float32),
                                        w.astype(jnp.float32)))


def _rms(x, w, eps: float, scale: float = 1.0):
    """``RMS(x)·w·scale`` in float32."""
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return x32 * (rms * scale) * w.astype(jnp.float32)


def _rope_pairs(x, positions, theta: float):
    """Rotary embedding over INTERLEAVED pairs ``(x[2i], x[2i+1])`` with
    ``theta ** (-i / (rd/2))``; x: [..., seq, heads, rd] float32.  The
    rotated pairs come back de-interleaved (all first members, then all
    second): queries and keys go through the same permutation, so every
    score is the published one, and nothing is interleaved back."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * freqs  # [seq, rd/2]
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def project(p, xn, positions, cfg, *, sfx: str = "", q_scale: float = 1.0,
            kv_scale: float = 1.0, mm=_mm, carry=None):
    """The normed hidden state to the queries ``[b, s, H, nope + rope]``
    (scaled, rope part rotated) and to what the latent cache keeps per
    position, all in ``carry`` (default ``cfg.dtype``; they are the next
    products' operands, with or without a cache): ``ckv [b, s, kv_rank]``
    (normed, scaled) and the rotated shared ``kr [b, s, rope]``."""
    carry = cfg.dtype if carry is None else carry
    b, s, _ = xn.shape
    # (Wqb·cq)·scale = Wqb·(cq·scale): the scale rides the norm's
    # float32 pass.
    cq = _rms(mm("bsd,dr->bsr", xn, p["wq_a" + sfx]), p["q_norm" + sfx],
              cfg.norm_eps, q_scale)
    q = mm("bsr,rq->bsq", cq, p["wq_b" + sfx]).reshape(
        b, s, cfg.heads_held, cfg.nope_dim + cfg.rope_dim)
    q = jnp.concatenate(
        [q[..., :cfg.nope_dim],
         _rope_pairs(q[..., cfg.nope_dim:], positions, cfg.rope_theta)], -1)
    kv = mm("bsd,dr->bsr", xn, p["wkv_a" + sfx])
    ckv = _rms(kv[..., :cfg.kv_rank], p["kv_norm" + sfx], cfg.norm_eps,
               kv_scale)
    kr = _rope_pairs(kv[..., None, cfg.kv_rank:], positions,
                     cfg.rope_theta)[..., 0, :]
    return q.astype(carry), ckv.astype(carry), kr.astype(carry)


def attend(p, q, ckv, kr, mask, cfg, *, sfx: str = "", mm=_mm,
           precision=None):
    """Queries ``[b, s, H, nope + rope]`` against latent keys ``ckv [b, t,
    kv_rank]`` / ``kr [b, t, rope]`` (a sequence's own, or the whole
    cache) under the additive ``mask [s, t]``; the held heads' share of
    ``Wo·attention``, float32.  The attention's own products take their
    operands in ``q.dtype``."""
    b, t, _ = ckv.shape
    h, nope = cfg.heads_held, cfg.nope_dim
    up = mm("bsr,rk->bsk", ckv, p["wkv_b" + sfx]).reshape(
        b, t, h, nope + cfg.v_dim).astype(q.dtype)
    scores = (
        jnp.einsum("bshd,bthd->bhst", q[..., :nope], up[..., :nope],
                   precision=precision, preferred_element_type=jnp.float32)
        + jnp.einsum("bshd,btd->bhst", q[..., nope:], kr,
                     precision=precision, preferred_element_type=jnp.float32)
    ) / np.sqrt(nope + cfg.rope_dim)
    probs = jax.nn.softmax(scores + mask, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, up[..., nope:],
                     precision=precision, preferred_element_type=jnp.float32)
    return mm("bsq,qd->bsd", out.reshape(b, q.shape[1], h * cfg.v_dim),
              p["wo" + sfx])
