"""Blockwise causal GQA attention — the pallas hot-op behind ring attention.

``block_attention`` computes one (Q block x KV block) partial attention
with LOCAL online-softmax statistics: it returns ``(pv, m, l)`` where
``m``/``l`` are the block's own running max / normalizer and ``pv`` the
unnormalized value sum.  The ring loop (``parallel/ring_attention.py``)
merges successive blocks' partials with the standard rescale
``exp(m - m_new)`` — so K/V rotation over ICI composes with on-chip
blockwise attention, the two halves of the ring-attention recipe.

Two interchangeable implementations:

- ``_block_attention_ref``: pure lax (einsum + where).  Runs anywhere,
  differentiates, and is the numerical oracle.  It materializes the
  [sq, t] logits in HBM — fine for short blocks, the memory hot spot for
  long ones.
- ``_block_attention_pallas``: a pallas TPU kernel.  Grid is
  (batch*kv_head*group, q_tiles, kv_tiles) with the kv tile dimension
  innermost, so for each Q tile the output block stays resident in VMEM
  while KV tiles stream through: logits live only as a
  [tile_q, tile_k] VMEM tile, never in HBM.  Entirely-masked KV tiles
  (future positions under the causal mask — half the work in a causal
  ring) are skipped with ``pl.when``.  Tile edges are the largest
  128-multiples up to 512 dividing the block (measured on v5e: 128-edge
  tiles are grid-overhead-bound and LOSE to the lax oracle past ~2k
  blocks, 512-edge tiles beat it ~1.3x; whole-block tiles blow VMEM).
  Batch and Q-tile grid axes are declared parallel for Mosaic; the kv
  axis is arbitrary (it carries the online-softmax accumulation).

The public ``block_attention`` picks pallas when the backend is TPU and
the shapes meet the MXU tiling constraints (hd and block lengths
multiples of 128), else falls back to lax.  It is forward-only:
differentiation happens one level up, in ``ring_attention``'s custom
vjp, which recomputes each block from the saved log-sum-exp while
re-rotating K/V around the ring — flash attention's recompute-the-
logits trade, composed with the ring's communication schedule.

The reference has no compute at all (SURVEY §2.3); this op exists for
the framework's long-context model path (ring attention over the ``sp``
mesh axis), which the reference's Assignment-as-pipeline-placement
implies but never executes.
"""

from __future__ import annotations


import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

_NEG_INF = -1e30  # finite: -inf would make (m - m_new) NaN on empty rows
TILE = 128  # MXU tiling granule: block edges must be multiples of this
MAX_TILE = 512  # largest tile edge (VMEM-safe, empirically fastest on v5e)


def _tile_edge(n: int) -> int:
    """Largest multiple of TILE up to MAX_TILE that divides ``n``."""
    start = min(n, MAX_TILE) // TILE * TILE  # candidates: 128-multiples only
    for cand in range(start, TILE - 1, -TILE):
        if n % cand == 0:
            return cand
    # eligible() gates the public path; a direct caller with a non-128-
    # multiple block must fail loudly, not get a non-MXU-tileable spec.
    raise ValueError(f"block edge {n} is not a multiple of {TILE}")

# Test hook: force the pallas path (interpret mode) off-TPU.
FORCE_PALLAS = False


def eligible(sq: int, t: int, hd: int) -> bool:
    """Shapes the pallas kernel accepts: MXU-tileable blocks."""
    return sq % TILE == 0 and t % TILE == 0 and hd % 128 == 0


def _use_pallas(sq: int, t: int, hd: int) -> bool:
    import os

    if os.environ.get("DLD_DISABLE_PALLAS_ATTN", "").lower() not in (
        "", "0", "false", "no",
    ):
        # Field escape hatch: flip to the lax oracle without a code
        # change (e.g. a Mosaic regression on a new TPU generation).
        return False
    if not eligible(sq, t, hd):
        return False
    return FORCE_PALLAS or jax.default_backend() == "tpu"


# ------------------------------------------------------------- lax oracle


def _block_attention_ref(qg, k, v, q_off, k_off):
    """qg: [b, kvh, g, sq, hd]; k, v: [b, kvh, t, hd]; offsets are the
    global positions of row/col 0 (f32 scalars holding integer values).
    Returns (pv f32, m f32, l f32) with shapes
    ([b, kvh, g, sq, hd], [b, kvh, g, sq], [b, kvh, g, sq])."""
    hd = qg.shape[-1]
    sq, t = qg.shape[3], k.shape[2]
    logits = jnp.einsum(
        "bkgsh,bkth->bkgst", qg, k, preferred_element_type=jnp.float32
    ) / np.sqrt(hd)
    q_ids = q_off.astype(jnp.int32) + jnp.arange(sq)
    k_ids = k_off.astype(jnp.int32) + jnp.arange(t)
    causal = q_ids[:, None] >= k_ids[None, :]
    logits = jnp.where(causal, logits, _NEG_INF)
    m = logits.max(axis=-1)
    p = jnp.exp(logits - m[..., None])
    # A fully-masked row (this whole KV block is in the row's future) has
    # m == _NEG_INF and p == 1 everywhere; zero it so (pv, l) are exact
    # partials and the caller's exp(m - m_new) rescale gets 0 * 0, not
    # garbage * 0.
    p = jnp.where((m > _NEG_INF / 2)[..., None], p, 0.0)
    l = p.sum(axis=-1)
    pv = jnp.einsum(
        "bkgst,bkth->bkgsh", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return pv, m, l


# ----------------------------------------------------------- pallas kernel


def _attn_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                 o_ref, m_ref, l_ref, *, tile_q: int, tile_k: int):
    j = pl.program_id(1)  # q tile
    kk = pl.program_id(2)  # kv tile (innermost: o/m/l stay resident)

    @pl.when(kk == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

    q_lo = qoff_ref[0, 0] + j * tile_q
    k_lo = koff_ref[0, 0] + kk * tile_k

    # The tile contributes iff its last query row can see its first key.
    @pl.when(q_lo + tile_q - 1 >= k_lo)
    def _():
        q = q_ref[0, 0, 0]  # [tile_q, hd]
        k = k_ref[0, 0]  # [tile_k, hd]
        v = v_ref[0, 0]
        hd = q.shape[-1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) / np.sqrt(hd)  # [tile_q, tile_k]
        q_ids = q_lo + lax.broadcasted_iota(jnp.int32, (tile_q, tile_k), 0)
        k_ids = k_lo + lax.broadcasted_iota(jnp.int32, (tile_q, tile_k), 1)
        s = jnp.where(q_ids >= k_ids, s, _NEG_INF)

        # Row stats are [tile_q, 1] column vectors: sublane-aligned with
        # the logits' query rows, so every broadcast below is rank-2.
        m_prev = m_ref[0, 0, 0]  # [tile_q, 1]
        l_prev = l_ref[0, 0, 0]
        o_prev = o_ref[0, 0, 0]  # [tile_q, hd]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # Rows whose visible keys start beyond this tile: see the oracle.
        p = jnp.where(m_new > _NEG_INF / 2, p, 0.0)
        l_ref[0, 0, 0] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[0, 0, 0] = o_prev * alpha + pv
        m_ref[0, 0, 0] = m_new


def _block_attention_pallas(qg, k, v, q_off, k_off, interpret):
    b, kvh, g, sq, hd = qg.shape
    t = k.shape[2]
    bh = b * kvh * g
    tile_q, tile_k = _tile_edge(sq), _tile_edge(t)
    grid = (bh, sq // tile_q, t // tile_k)

    def q_idx(i, j, kk):
        return (i // (kvh * g), (i // g) % kvh, i % g, j, 0)

    def kv_idx(i, j, kk):
        return (i // (kvh * g), (i // g) % kvh, kk, 0)

    stat_idx = q_idx  # same coordinates; stats blocks just have width 1

    # Scalar offsets ride SMEM on TPU; interpret mode accepts the same
    # spec (memory spaces are advisory there).
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    smem = pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0),
                        memory_space=pltpu.SMEM)

    # Stats carry a trailing singleton dim so kernel-side row vectors
    # are [TILE, 1] (sublane-aligned); squeezed off on return.
    # Inside shard_map the outputs vary over every mesh axis the inputs
    # do (vma): required by pallas_call when the mesh checks vma.
    vma = frozenset().union(*(jax.typeof(x).vma for x in (qg, k, v)))

    def _struct(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)

    out_shape = [
        _struct((b, kvh, g, sq, hd)),
        _struct((b, kvh, g, sq, 1)),
        _struct((b, kvh, g, sq, 1)),
    ]
    # Batch and q-tile axes are embarrassingly parallel; the kv axis is
    # "arbitrary" — it must run in order (online-softmax accumulation
    # into o/m/l).  Interpret mode (CPU tests) ignores compiler params.
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    pv, m, l = pl.pallas_call(
        functools.partial(_attn_kernel, tile_q=tile_q, tile_k=tile_k),
        grid=grid,
        in_specs=[
            smem,
            smem,
            pl.BlockSpec((1, 1, 1, tile_q, hd), q_idx),
            pl.BlockSpec((1, 1, tile_k, hd), kv_idx),
            pl.BlockSpec((1, 1, tile_k, hd), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, tile_q, hd), q_idx),
            pl.BlockSpec((1, 1, 1, tile_q, 1), stat_idx),
            pl.BlockSpec((1, 1, 1, tile_q, 1), stat_idx),
        ],
        out_shape=out_shape,
        interpret=interpret,
        **kwargs,
    )(
        q_off.astype(jnp.int32).reshape(1, 1),
        k_off.astype(jnp.int32).reshape(1, 1),
        qg, k, v,
    )
    return pv, m.squeeze(-1), l.squeeze(-1)


# ------------------------------------------------------------- public op


def block_attention(qg, k, v, q_off, k_off):
    """One KV block's partial attention (see module docstring).

    qg: [b, kvh, g, sq, hd]; k, v: [b, kvh, t, hd]; ``q_off``/``k_off``
    are f32 scalars holding the blocks' global start positions (f32 for
    a uniform traced-scalar convention; exact for any realistic
    sequence length).  Returns f32 (pv, m, l).

    This op is forward-only: its consumer, ``ring_attention``, defines
    its own custom vjp (the backward ring in
    ``parallel/ring_attention.py``), which never differentiates through
    this call."""
    sq, hd = qg.shape[3], qg.shape[4]
    t = k.shape[2]
    if _use_pallas(sq, t, hd):
        return _block_attention_pallas(
            qg, k, v, q_off, k_off,
            interpret=jax.default_backend() != "tpu",
        )
    return _block_attention_ref(qg, k, v, q_off, k_off)


def merge_partials(carry, part):
    """Online-softmax merge of a block's (pv, m, l) into the running
    (o, m, l) accumulator — all f32."""
    o, m, l = carry
    pv, m_blk, l_blk = part
    m_new = jnp.maximum(m, m_blk)
    alpha = jnp.exp(m - m_new)
    beta = jnp.exp(m_blk - m_new)
    l_new = l * alpha + l_blk * beta
    o_new = o * alpha[..., None] + pv * beta[..., None]
    return o_new, m_new, l_new
