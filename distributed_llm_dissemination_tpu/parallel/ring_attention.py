"""Ring attention: sequence/context parallelism over a mesh axis.

Long-context support is first-class in this framework: the sequence dim is
sharded over the ``sp`` mesh axis and attention runs blockwise — each
device keeps its Q shard resident while K/V blocks rotate around the ring
via ``lax.ppermute``, accumulating with an online (flash-style) softmax.
Communication of the next K/V block overlaps the current block's matmuls on
TPU (XLA schedules the ppermute DMA concurrently), so attention over an
S-long sequence costs S/sp memory per chip and n-1 neighbor hops.

The per-block compute is ``ops.flash_attention.block_attention`` — a
pallas TPU kernel when shapes are MXU-tileable (logits never leave VMEM),
the lax oracle otherwise — and the ring loop merges each block's partial
softmax stats with ``merge_partials``.  The K/V carry is kept in
[b, kvh, t, hd] layout so the kernel consumes it without per-hop
transposes; ``ppermute`` is layout-oblivious.

Training memory matches the ring-attention paper's budget because the
op defines its own backward: autodiff of the forward scan would stack
every rotated K/V block as a residual (n copies = the full sequence per
chip), so instead the custom vjp saves only the device's own shard
(q, k, v, out, lse) and RE-ROTATES K/V in the backward ring, with dK/dV
accumulators traveling alongside and arriving home after n hops.

This is new capability relative to the reference (which has no compute at
all, SURVEY §2.3); the pattern follows the public ring-attention /
blockwise-attention literature (see PAPERS.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.flash_attention import _NEG_INF, block_attention, merge_partials


def _vary(axis, *xs):
    """Mark freshly-created accumulators as device-varying over ``axis``
    (needed whenever the surrounding shard_map checks vma)."""
    return tuple(lax.pcast(x, (axis,), to="varying") for x in xs)


def _ring_forward(q, k, v, axis, s_local):
    """The forward ring; returns out plus the per-row log-sum-exp and the
    kernel-layout tensors the custom backward needs."""
    n = lax.axis_size(axis)
    my = lax.axis_index(axis)
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    group = h // kvh
    # [b, s, kvh, g|1, hd] -> kernel layouts (loop-invariant, done once).
    qg = q.reshape(b, sq, kvh, group, hd).transpose(0, 2, 3, 1, 4)
    kt = k.transpose(0, 2, 1, 3)  # [b, kvh, t, hd] — the ring carry layout
    vt = v.transpose(0, 2, 1, 3)
    q_off = (my * s_local).astype(jnp.float32)

    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, i):
        k_blk, v_blk, o, m, l = carry
        # The block in hand originated at device (my - i) mod n.
        src = (my - i) % n

        def visible(oml):
            part = block_attention(
                qg, k_blk, v_blk, q_off,
                (src * s_local).astype(jnp.float32),
            )
            return merge_partials(oml, part)

        # A block strictly in the future (src > my) is fully masked and
        # contributes nothing: skip its compute entirely — half the
        # per-step work on a causal ring (the hop still happens; the
        # ring's schedule is fixed).
        o, m, l = lax.cond(src <= my, visible, lambda oml: oml, (o, m, l))

        # Skip the final rotation: after the last accumulation the blocks
        # are discarded, so that hop would be a wasted ICI transfer.
        k_nxt, v_nxt = lax.cond(
            i < n - 1,
            lambda kv: (
                lax.ppermute(kv[0], axis, perm),
                lax.ppermute(kv[1], axis, perm),
            ),
            lambda kv: kv,
            (k_blk, v_blk),
        )
        return (k_nxt, v_nxt, o, m, l), None

    m0 = jnp.full((b, kvh, group, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, group, sq), jnp.float32)
    o0 = jnp.zeros((b, kvh, group, sq, hd), jnp.float32)
    m0, l0, o0 = _vary(axis, m0, l0, o0)
    (_, _, o_f, m_f, l_f), _ = lax.scan(
        step, (kt, vt, o0, m0, l0), jnp.arange(n)
    )
    l_safe = jnp.maximum(l_f, 1e-30)
    out_g = o_f / l_safe[..., None]  # normalized, f32, kernel layout
    lse = m_f + jnp.log(l_safe)  # per-row log-sum-exp
    out = out_g.astype(v.dtype)
    # [b, kv, g, s, hd] -> [b, s, h, hd]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd)
    return out, (qg, kt, vt, out_g, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str,
    s_local: int,
) -> jax.Array:
    """Causal GQA ring attention inside a manual (shard_map) context.

    q: [b, s_local, h, hd] — this device's query block (heads may be
    tp-sharded; grouping is h//kv locally).
    k, v: [b, s_local, kv, hd] — this device's key/value block, already
    position-encoded with *global* positions.
    Returns [b, s_local, h, hd].

    Differentiation runs a RING BACKWARD (``defvjp`` below): K/V blocks
    are re-rotated around the ``axis`` ring while dK/dV accumulators
    travel with them, arriving home after n hops.  Residual memory is
    the device's own O(S/sp) shard (q, k, v, out, lse) — NOT the n
    stacked K/V copies that autodiff of the forward scan would save,
    which is what makes long-context training fit the ring-attention
    memory budget.
    """
    out, _ = _ring_forward(q, k, v, axis, s_local)
    return out


def _ring_attention_fwd(q, k, v, axis, s_local):
    out, res = _ring_forward(q, k, v, axis, s_local)
    return out, res


def _ring_attention_bwd(axis, s_local, res, dout):
    """Flash-style ring backward: p = exp(s - lse) is recomputed per
    block; dK/dV ride the rotating carry and return home after n hops."""
    qg, kt, vt, out_g, lse = res
    n = lax.axis_size(axis)
    my = lax.axis_index(axis)
    b, kvh, group, sq, hd = qg.shape
    scale = 1.0 / np.sqrt(hd)
    h = kvh * group

    # Caller layout [b, s, h, hd] -> kernel layout, f32.
    dg = (
        dout.reshape(b, sq, kvh, group, hd)
        .transpose(0, 2, 3, 1, 4)
        .astype(jnp.float32)
    )
    # D_i = sum_d dout_i * out_i (the softmax-normalizer gradient term).
    d_row = jnp.einsum("bkgsh,bkgsh->bkgs", dg, out_g)

    q_ids = my * s_local + jnp.arange(sq)
    qg32 = qg.astype(jnp.float32)

    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, i):
        k_blk, v_blk, dk_blk, dv_blk, dq = carry
        src = (my - i) % n  # same visiting order as the forward

        def visible(args):
            k_blk, v_blk, dk_blk, dv_blk, dq = args
            k32 = k_blk.astype(jnp.float32)
            v32 = v_blk.astype(jnp.float32)
            s = jnp.einsum(
                "bkgsh,bkth->bkgst", qg32, k32,
                preferred_element_type=jnp.float32,
            ) * scale
            k_ids = src * s_local + jnp.arange(s_local)
            causal = q_ids[:, None] >= k_ids[None, :]
            s = jnp.where(causal, s, _NEG_INF)
            p = jnp.exp(s - lse[..., None])  # masked entries underflow to 0

            dv_blk = dv_blk + jnp.einsum("bkgst,bkgsh->bkth", p, dg)
            dp = jnp.einsum("bkgsh,bkth->bkgst", dg, v32)
            ds = p * (dp - d_row[..., None]) * scale
            dq = dq + jnp.einsum("bkgst,bkth->bkgsh", ds, k32)
            dk_blk = dk_blk + jnp.einsum("bkgst,bkgsh->bkth", ds, qg32)
            return dk_blk, dv_blk, dq

        # A block strictly in the future (src > my) has p == 0 for every
        # row: skip its five einsums — half the backward FLOPs on a
        # causal ring, the same skip the forward kernel does per tile.
        dk_blk, dv_blk, dq = lax.cond(
            src <= my,
            visible,
            lambda args: (args[2], args[3], args[4]),
            (k_blk, v_blk, dk_blk, dv_blk, dq),
        )

        # dK/dV need all n rotations to arrive home; K/V are dead after
        # the last accumulation, so skip their final hop (same wasted-
        # transfer elision as the forward).
        k_blk, v_blk = lax.cond(
            i < n - 1,
            lambda kv: (
                lax.ppermute(kv[0], axis, perm),
                lax.ppermute(kv[1], axis, perm),
            ),
            lambda kv: kv,
            (k_blk, v_blk),
        )
        dk_blk, dv_blk = (
            lax.ppermute(x, axis, perm) for x in (dk_blk, dv_blk)
        )
        return (k_blk, v_blk, dk_blk, dv_blk, dq), None

    zeros_kv = jnp.zeros((b, kvh, s_local, hd), jnp.float32)
    dq0 = jnp.zeros((b, kvh, group, sq, hd), jnp.float32)
    dk0, dv0, dq0 = _vary(axis, zeros_kv, zeros_kv, dq0)
    (_, _, dk_f, dv_f, dq_f), _ = lax.scan(
        step, (kt, vt, dk0, dv0, dq0), jnp.arange(n)
    )

    dq = (
        dq_f.transpose(0, 3, 1, 2, 4)
        .reshape(b, sq, h, hd)
        .astype(qg.dtype)
    )
    dk = dk_f.transpose(0, 2, 1, 3).astype(kt.dtype)
    dv = dv_f.transpose(0, 2, 1, 3).astype(vt.dtype)
    return dq, dk, dv


ring_attention.defvjp(_ring_attention_fwd, _ring_attention_bwd)
