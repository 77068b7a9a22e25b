"""Llama-style transformer: the model whose layers get disseminated.

The reference treats layers as opaque byte blobs sized like Llama-70B
shards (``/root/reference/conf/config.json``: 8 × 10.18 GiB) and its
``startupMsg`` is "the hook that would launch an inference engine"
(``distributor/message.go:216-241``).  This module supplies that engine:
a pure-JAX (pytree params + functional apply) Llama-3-family model — GQA
attention with RoPE, RMSNorm, SwiGLU FFN, optional MoE — so disseminated
weights boot a real jitted forward pass, and the preset configs give the
benchmark scenarios their true layer sizes.

All matmuls are einsums in bfloat16 with fp32 accumulation — large, batched,
MXU-friendly; no data-dependent Python control flow anywhere.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import family

Spec = Tuple[str, Tuple[int, ...]]
HF_ARCHITECTURE = "Llama"  # what models/hf.py accepts


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # Which module of ``models/`` answers for this configuration
    # (``models/family.py``): a class attribute, not a field.
    family = "llama"

    name: str = "tiny"
    vocab: int = 256
    d_model: int = 128
    n_layers: int = 4
    n_heads: int = 4
    n_kv_heads: int = 2
    d_ff: int = 256
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # MoE (expert-parallel) variant: 0 experts = dense SwiGLU.
    n_experts: int = 0
    top_k: int = 2

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def layer_nbytes(self) -> int:
        """Bytes of one transformer layer's params in this dtype — the
        'LayerSize' the dissemination configs should use."""
        return family.spec_nbytes(layer_param_specs(self), self.dtype)


# Real Llama-3 family shapes (public architecture constants) + test sizes.
CONFIGS: Dict[str, ModelConfig] = {
    "tiny": ModelConfig(),
    "tiny-moe": ModelConfig(name="tiny-moe", n_experts=4, top_k=2),
    # ~2 MiB/layer: big enough that the transport's 256 KiB burst bucket
    # is noise — the shape rate-limited wire benchmarks need.
    "tiny2": ModelConfig(
        name="tiny2", vocab=512, d_model=256, n_layers=4,
        n_heads=4, n_kv_heads=2, d_ff=1024,
    ),
    "llama3-8b": ModelConfig(
        name="llama3-8b", vocab=128256, d_model=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, d_ff=14336,
    ),
    # Flagship at reduced depth: full 8B layer SHAPE (so each layer blob
    # is the physical ~416 MiB) but 4 layers, fitting one chip next to
    # activations.  The driver's entry() compile check uses it; "v8k"
    # trims the vocab so the head blob doesn't dwarf the layers it escorts.
    "llama3-8b-d4": ModelConfig(
        name="llama3-8b-d4", vocab=128256, d_model=4096, n_layers=4,
        n_heads=32, n_kv_heads=8, d_ff=14336,
    ),
    "llama3-8b-d4v8k": ModelConfig(
        name="llama3-8b-d4v8k", vocab=8192, d_model=4096, n_layers=4,
        n_heads=32, n_kv_heads=8, d_ff=14336,
    ),
    "llama3-70b": ModelConfig(
        name="llama3-70b", vocab=128256, d_model=8192, n_layers=80,
        n_heads=64, n_kv_heads=8, d_ff=28672,
    ),
    "llama3-405b": ModelConfig(
        name="llama3-405b", vocab=128256, d_model=16384, n_layers=126,
        n_heads=128, n_kv_heads=8, d_ff=53248,
    ),
}


# --------------------------------------------------------------- blob leaves

def layer_param_specs(cfg: ModelConfig) -> List[Spec]:
    """(name, shape) of one layer's leaves, in canonical blob order."""
    d, f = cfg.d_model, cfg.d_ff
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    specs: List[Spec] = [
        ("wq", (d, h * hd)),
        ("wk", (d, kv * hd)),
        ("wv", (d, kv * hd)),
        ("wo", (h * hd, d)),
        ("ln1", (d,)),
        ("ln2", (d,)),
    ]
    if cfg.n_experts:
        e = cfg.n_experts
        specs += [
            ("router", (d, e)),
            ("w1", (e, d, f)),
            ("w3", (e, d, f)),
            ("w2", (e, f, d)),
        ]
    else:
        specs += [("w1", (d, f)), ("w3", (d, f)), ("w2", (f, d))]
    return specs


def head_param_specs(cfg: ModelConfig) -> List[Spec]:
    """(name, shape) of the non-layer leaves, in canonical blob order."""
    return [
        ("embed", (cfg.vocab, cfg.d_model)),
        ("ln_f", (cfg.d_model,)),
        ("lm_head", (cfg.d_model, cfg.vocab)),
    ]


# ---------------------------------------------------------------------- init

def init_layer_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """One transformer layer's weights as a flat dict pytree."""
    d, f = cfg.d_model, cfg.d_ff
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k = iter(jax.random.split(key, 8))
    scale = d ** -0.5
    p = {
        "wq": jax.random.normal(next(k), (d, h * hd), cfg.dtype) * scale,
        "wk": jax.random.normal(next(k), (d, kv * hd), cfg.dtype) * scale,
        "wv": jax.random.normal(next(k), (d, kv * hd), cfg.dtype) * scale,
        "wo": jax.random.normal(next(k), (h * hd, d), cfg.dtype) * scale,
        "ln1": jnp.ones((d,), cfg.dtype),
        "ln2": jnp.ones((d,), cfg.dtype),
    }
    if cfg.n_experts:
        e = cfg.n_experts
        p["router"] = jax.random.normal(next(k), (d, e), cfg.dtype) * scale
        p["w1"] = jax.random.normal(next(k), (e, d, f), cfg.dtype) * scale
        p["w3"] = jax.random.normal(next(k), (e, d, f), cfg.dtype) * scale
        p["w2"] = jax.random.normal(next(k), (e, f, d), cfg.dtype) * (f ** -0.5)
    else:
        p["w1"] = jax.random.normal(next(k), (d, f), cfg.dtype) * scale
        p["w3"] = jax.random.normal(next(k), (d, f), cfg.dtype) * scale
        p["w2"] = jax.random.normal(next(k), (f, d), cfg.dtype) * (f ** -0.5)
    return p


def init_head_params(
    cfg: ModelConfig, k_emb: jax.Array, k_out: jax.Array
) -> Dict[str, jax.Array]:
    """The non-layer weights (embed / final norm / lm head)."""
    return {
        "embed": jax.random.normal(k_emb, (cfg.vocab, cfg.d_model), cfg.dtype)
        * (cfg.d_model ** -0.5),
        "ln_f": jnp.ones((cfg.d_model,), cfg.dtype),
        "lm_head": jax.random.normal(
            k_out, (cfg.d_model, cfg.vocab), cfg.dtype
        ) * (cfg.d_model ** -0.5),
    }


def model_keys(cfg, key: jax.Array):
    """Deterministic per-component key split — exposed so one layer's
    weights can be regenerated in isolation (seeded dissemination blobs)
    bit-identically to ``init_params``.  The same for every family."""
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    return k_emb, jax.random.split(k_layers, cfg.n_layers), k_out


def init_params(cfg, key: jax.Array) -> Dict[str, Any]:
    """Full model params of any family.  Layer weights are STACKED along
    a leading layer axis — one pytree leaf per weight kind, by kind of
    layer where a family has several (``family.stack``) — so a layer is
    a slice (disseminable blob) and scan/pipeline stages index it; the
    head blob's leaves lie beside ``"layers"``."""
    fam = family.of(cfg)
    k_emb, layer_keys, k_out = model_keys(cfg, key)
    stacked = family.stack(
        cfg, range(cfg.n_layers),
        lambda lid: family.init_layer_params(cfg, layer_keys[lid], lid),
        jnp.stack)
    return {**fam.init_head_params(cfg, k_emb, k_out), "layers": stacked}


# ------------------------------------------------------------------- blocks

def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * w


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embeddings; x: [..., seq, heads, head_dim]."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def gqa_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal_mask: jax.Array
) -> jax.Array:
    """Grouped-query attention core.  q: [b, s, h, hd]; k/v: [b, s, kv, hd];
    mask: [sq, sk] additive."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    group = h // kvh
    qg = q.reshape(b, sq, kvh, group, hd)
    logits = jnp.einsum(
        "bskgh,btkh->bkgst", qg, k, preferred_element_type=jnp.float32
    ) / np.sqrt(hd)
    logits = logits + causal_mask  # broadcast over [b, kv, g, sq, sk]
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(b, sq, h, hd)


def qkv_proj(
    p: Dict[str, jax.Array], xn: jax.Array, positions: jax.Array,
    cfg: ModelConfig,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Project the normed hidden state to rotary-encoded q/k/v — shared
    by the training/forward path and the KV-cached serving path
    (models/generate.py), so the two can't drift."""
    b, s, _ = xn.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dq->bsq", xn, p["wq"]).reshape(b, s, h, hd)
    k = jnp.einsum("bsd,dq->bsq", xn, p["wk"]).reshape(b, s, kv, hd)
    v = jnp.einsum("bsd,dq->bsq", xn, p["wv"]).reshape(b, s, kv, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_block(
    p: Dict[str, jax.Array], x: jax.Array, positions: jax.Array, cfg: ModelConfig
) -> jax.Array:
    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    xn = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = qkv_proj(p, xn, positions, cfg)
    mask = jnp.where(
        positions[:, None] >= positions[None, :], 0.0, -jnp.inf
    ).astype(jnp.float32)
    out = gqa_attention(q, k, v, mask)
    return x + jnp.einsum("bsq,qd->bsd", out.reshape(b, s, h * hd), p["wo"])


def dense_ffn(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig) -> jax.Array:
    xn = rms_norm(x, p["ln2"], cfg.norm_eps)
    gate = jax.nn.silu(jnp.einsum("bsd,df->bsf", xn, p["w1"]))
    up = jnp.einsum("bsd,df->bsf", xn, p["w3"])
    return x + jnp.einsum("bsf,fd->bsd", gate * up, p["w2"])


def route_topk(weights: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Keep the top-k routing weights per token (tie-inclusive) and
    renormalize.  Shared by the dense-dispatch and the ep-sharded MoE paths
    so routing semantics cannot diverge."""
    if cfg.top_k >= cfg.n_experts:
        return weights
    top = jax.lax.top_k(weights, cfg.top_k)[0][..., -1:]
    weights = jnp.where(weights >= top, weights, 0.0)
    return weights / (weights.sum(-1, keepdims=True) + 1e-9)


def moe_ffn(p: Dict[str, jax.Array], x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Top-k routed mixture of SwiGLU experts (dense dispatch: every expert
    computes, gates zero out unrouted pairs — compile-friendly, and the
    expert dimension shards cleanly over the ep axis)."""
    xn = rms_norm(x, p["ln2"], cfg.norm_eps)
    logits = jnp.einsum("bsd,de->bse", xn, p["router"]).astype(jnp.float32)
    weights = route_topk(jax.nn.softmax(logits, axis=-1), cfg)
    gate = jax.nn.silu(jnp.einsum("bsd,edf->besf", xn, p["w1"]))
    up = jnp.einsum("bsd,edf->besf", xn, p["w3"])
    expert_out = jnp.einsum("besf,efd->besd", gate * up, p["w2"])
    mixed = jnp.einsum("besd,bse->bsd", expert_out, weights.astype(x.dtype))
    return x + mixed


def layer_apply(
    p: Dict[str, jax.Array], x: jax.Array, positions: jax.Array, cfg: ModelConfig
) -> jax.Array:
    x = attention_block(p, x, positions, cfg)
    if cfg.n_experts:
        return moe_ffn(p, x, cfg)
    return dense_ffn(p, x, cfg)


# ------------------------------------------------------- embedding and head

def embed(params: Dict[str, Any], tokens: jax.Array,
          cfg: ModelConfig) -> jax.Array:
    return params["embed"][tokens]


def logits(params: Dict[str, Any], x: jax.Array,
           cfg: ModelConfig) -> jax.Array:
    """Final norm and head over ``x`` [b, s, d]: float32 logits."""
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    # f32 accumulation, the same for the full forward and the KV-cached
    # decode (models/generate.py) — on bf16 checkpoints a lower-precision
    # accumulation in one of them could make greedy argmax diverge
    # between the two.
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"],
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------------ serving cache

KVCache = Dict[str, jax.Array]  # {"k","v"}: [n_layers, b, max_len, kvh, hd]


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> KVCache:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def layer_with_cache(
    p: Dict[str, jax.Array], x, positions, cache: KVCache, cfg: ModelConfig,
) -> Tuple[jax.Array, KVCache, Dict[str, jax.Array]]:
    """One layer over ``x`` [b, s, d]: writes this block's K/V into the
    layer's cache at ``positions`` and attends against the WHOLE (masked)
    cache — the same ``gqa_attention``/``dense_ffn`` kernels as the
    cache-less forward, with the causal mask generalized to cache-row
    validity.  Returns (x_out, cache, counters); this family counts
    nothing."""
    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    xn = rms_norm(x, p["ln1"], cfg.norm_eps)
    q, k, v = qkv_proj(p, xn, positions, cfg)
    # Contiguous block write at the first position (prefill writes the
    # prompt at 0; a decode step writes one row at pos).
    start = positions[0]
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k, (0, start, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v, (0, start, 0, 0))

    max_len = k_cache.shape[1]
    # Valid: the cache row holds a key at position <= this query's.
    k_valid = jnp.arange(max_len)[None, :] <= positions[:, None]  # [s, max]
    mask = jnp.where(k_valid, 0.0, -jnp.inf).astype(jnp.float32)
    out = gqa_attention(q, k_cache, v_cache, mask)
    x = x + jnp.einsum("bsq,qd->bsd", out.reshape(b, s, h * hd), p["wo"])
    ffn = moe_ffn if cfg.n_experts else dense_ffn
    return ffn(p, x, cfg), {"k": k_cache, "v": v_cache}, {}


# ------------------------------------------------------------------ forward

def apply_layers(layers, x: jax.Array, positions: jax.Array, cfg,
                 layer_ids=None) -> jax.Array:
    """The blocks of ``layer_ids`` (default: every layer) over ``x``, in
    order and without a cache (``family.scan_stack``: one traced body
    for each stretch of the stack whatever its length, and for a uniform
    family the one scan over its stacked tree)."""
    fam = family.of(cfg)

    def step(x, layer_p, _):
        return fam.layer_apply(layer_p, x, positions, cfg), None, {}

    return family.scan_stack(cfg, step, x, layers, None, layer_ids)[0]


def forward(params: Dict[str, Any], tokens: jax.Array, cfg) -> jax.Array:
    """Logits for [batch, seq] int tokens, for a configuration of any
    family (``models/family.py``: embedding, block and head are the
    family's; ``apply_layers`` runs the stack)."""
    fam = family.of(cfg)
    x = fam.embed(params, tokens, cfg)
    x = apply_layers(params["layers"], x, jnp.arange(tokens.shape[1]), cfg)
    return fam.logits(params, x, cfg)


def loss_fn(params: Dict[str, Any], tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Next-token cross-entropy (fp32 logits)."""
    logits = forward(params, tokens[:, :-1], cfg).astype(jnp.float32)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


@functools.partial(jax.jit, static_argnums=(2,))
def forward_jit(params, tokens, cfg):
    with jax.named_scope("model.forward"):
        return forward(params, tokens, cfg)
