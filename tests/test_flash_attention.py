"""Parity tests for the pallas blockwise-attention kernel.

The pallas path runs in interpret mode on the CPU test mesh (the kernel
is identical; only Mosaic compilation is skipped), and every case is
checked against the lax oracle ``_block_attention_ref`` — including the
ring-integrated and gradient paths, since the custom_vjp backward
rematerializes through the oracle.
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distributed_llm_dissemination_tpu.ops import flash_attention as fa
from distributed_llm_dissemination_tpu.parallel.ring_attention import (
    ring_attention,
)


@contextlib.contextmanager
def pallas_forced(on: bool):
    prev = fa.FORCE_PALLAS
    fa.FORCE_PALLAS = on
    try:
        yield
    finally:
        fa.FORCE_PALLAS = prev


@pytest.fixture
def force_pallas():
    with pallas_forced(True):
        yield


def _rand_qkv(key, b=1, kvh=2, g=2, sq=256, t=256, hd=128, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    qg = jax.random.normal(kq, (b, kvh, g, sq, hd), dtype)
    k = jax.random.normal(kk, (b, kvh, t, hd), dtype)
    v = jax.random.normal(kv, (b, kvh, t, hd), dtype)
    return qg, k, v


@pytest.mark.parametrize(
    "q_off,k_off",
    [
        (0, 0),  # self block: causal diagonal
        (256, 0),  # fully-visible past block
        (0, 256),  # fully-masked future block (kernel skips every tile)
        (128, 0),  # partially overlapping tiles
    ],
)
def test_block_parity_vs_oracle(force_pallas, q_off, k_off):
    qg, k, v = _rand_qkv(jax.random.PRNGKey(0))
    offs = (jnp.float32(q_off), jnp.float32(k_off))
    pv_p, m_p, l_p = fa.block_attention(qg, k, v, *offs)
    pv_r, m_r, l_r = fa._block_attention_ref(qg, k, v, *offs)
    np.testing.assert_allclose(m_p, m_r, rtol=1e-6)
    np.testing.assert_allclose(l_p, l_r, rtol=1e-5)
    np.testing.assert_allclose(pv_p, pv_r, rtol=1e-5, atol=1e-5)


def test_block_parity_bf16(force_pallas):
    qg, k, v = _rand_qkv(jax.random.PRNGKey(1), dtype=jnp.bfloat16)
    offs = (jnp.float32(0), jnp.float32(0))
    pv_p, m_p, l_p = fa.block_attention(qg, k, v, *offs)
    pv_r, m_r, l_r = fa._block_attention_ref(qg, k, v, *offs)
    np.testing.assert_allclose(m_p, m_r, rtol=1e-2)
    np.testing.assert_allclose(l_p, l_r, rtol=1e-2)
    np.testing.assert_allclose(pv_p, pv_r, rtol=5e-2, atol=5e-2)


def test_unaligned_shapes_fall_back_to_lax(force_pallas):
    # hd=64 violates the MXU lane constraint: the routing must pick the
    # oracle even with FORCE_PALLAS on, and the call must not crash.
    assert not fa._use_pallas(64, 64, 64)
    assert fa._use_pallas(256, 256, 128)
    qg, k, v = _rand_qkv(jax.random.PRNGKey(2), sq=64, t=64, hd=64)
    offs = (jnp.float32(0), jnp.float32(0))
    pv, m, l = fa.block_attention(qg, k, v, *offs)
    pv_r, m_r, l_r = fa._block_attention_ref(qg, k, v, *offs)
    np.testing.assert_allclose(pv, pv_r, rtol=1e-6, atol=1e-6)


def _ring_devices(n):
    return jax.devices()[:n]


def _run_ring(q, k, v, n, s_local):
    mesh = Mesh(np.array(_ring_devices(n)), ("sp",))
    f = jax.shard_map(
        functools.partial(ring_attention, axis="sp", s_local=s_local),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
        check_vma=False,  # matches production (models/sharded.py:262);
        # the pallas hlo interpreter can't satisfy the vma checker yet
    )
    return jax.jit(f)(q, k, v)


def _dense_causal(q, k, v):
    """Dense causal GQA oracle over the full (unsharded) sequence."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, hd)
    logits = jnp.einsum(
        "bskgh,btkh->bkgst", qg, k, preferred_element_type=jnp.float32
    ) / np.sqrt(hd)
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkh->bkgsh", p.astype(v.dtype), v)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd)


def test_ring_attention_pallas_matches_dense(force_pallas):
    n, s_local, hd = 4, 128, 128
    s = n * s_local
    key = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, s, 4, hd))
    k = jax.random.normal(kk, (1, s, 2, hd))
    v = jax.random.normal(kv, (1, s, 2, hd))
    out = _run_ring(q, k, v, n, s_local)
    ref = _dense_causal(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_ring_attention_pallas_matches_lax_path():
    n, s_local, hd = 4, 128, 128
    s = n * s_local
    key = jax.random.PRNGKey(4)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, s, 4, hd))
    k = jax.random.normal(kk, (1, s, 2, hd))
    v = jax.random.normal(kv, (1, s, 2, hd))
    with pallas_forced(True):
        out_p = _run_ring(q, k, v, n, s_local)
    with pallas_forced(False):
        out_l = _run_ring(q, k, v, n, s_local)
    np.testing.assert_allclose(out_p, out_l, rtol=1e-5, atol=1e-5)


def test_ring_attention_grads_match():
    """The ring backward consumes residuals (out, lse) produced by the
    forward — pallas-forward and lax-forward residuals must drive it to
    the same gradients."""
    n, s_local, hd = 2, 128, 128
    s = n * s_local
    key = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, s, 2, hd))
    k = jax.random.normal(kk, (1, s, 2, hd))
    v = jax.random.normal(kv, (1, s, 2, hd))

    def loss(q, k, v):
        out = _run_ring(q, k, v, n, s_local)
        return jnp.sum(out * out)

    with pallas_forced(True):
        gp = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    with pallas_forced(False):
        gl = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gl):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("n,kvh,h", [(2, 2, 2), (4, 2, 4)])
def test_ring_grads_match_dense_oracle(n, kvh, h):
    """The custom ring backward (K/V re-rotation, flash-style block
    grads) against plain autodiff of a dense causal softmax — a fully
    independent gradient path, including GQA grouping."""
    s_local, hd = 128, 128
    s = n * s_local
    key = jax.random.PRNGKey(6)
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (1, s, h, hd))
    k = jax.random.normal(kk, (1, s, kvh, hd))
    v = jax.random.normal(kv, (1, s, kvh, hd))
    dout = jax.random.normal(kg, (1, s, h, hd))

    def ring_loss(q, k, v):
        return jnp.sum(_run_ring(q, k, v, n, s_local) * dout)

    def dense_loss(q, k, v):
        return jnp.sum(_dense_causal(q, k, v) * dout)

    gr = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)


def test_tile_edge_selection():
    # Largest 128-multiple <= 512 dividing the block edge.
    assert fa._tile_edge(128) == 128
    assert fa._tile_edge(256) == 256
    assert fa._tile_edge(384) == 384
    assert fa._tile_edge(512) == 512
    assert fa._tile_edge(640) == 128   # 640 has no larger 128-mult divisor
    assert fa._tile_edge(1024) == 512  # capped at MAX_TILE
    with pytest.raises(ValueError, match="multiple of 128"):
        fa._tile_edge(200)  # non-128-multiple must fail loudly, not
        # silently drop trailing rows (grid floor-division)


@pytest.mark.parametrize(
    "sq,t",  # shapes whose q/kv tile edges DIFFER (the dynamic-tile paths)
    [
        (256, 512),  # tile_k > tile_q
        (640, 256),  # 640 -> 128-edge q tiles next to 256-edge kv tiles
    ],
)
def test_block_parity_mixed_tile_edges(force_pallas, sq, t):
    qg, k, v = _rand_qkv(jax.random.PRNGKey(3), sq=sq, t=t)
    offs = (jnp.float32(0), jnp.float32(0))
    pv_p, m_p, l_p = fa.block_attention(qg, k, v, *offs)
    pv_r, m_r, l_r = fa._block_attention_ref(qg, k, v, *offs)
    np.testing.assert_allclose(m_p, m_r, rtol=1e-6)
    np.testing.assert_allclose(l_p, l_r, rtol=1e-5)
    np.testing.assert_allclose(pv_p, pv_r, rtol=1e-5, atol=1e-5)
