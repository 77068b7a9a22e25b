"""The LFM2-MoE family: a stack whose layers are of several kinds — the
operator a gated short convolution or grouped-query attention, the
feed-forward a dense SwiGLU or a routed block of experts.

A layer, whatever its kind (``d`` = ``d_model``; every projection without
bias, stored ``[in, out]``; ``RMS(x) = x * rsqrt(mean(x^2) + eps)``):

    x += operator(RMS(x)·g_op)          x += feed_forward(RMS(x)·g_ffn)

    conv(x):  [B | C | u] = in_proj·x                      (d -> 3d)
              v = B * u
              c[t] = sum_k w[:, k] * v[t - (K-1) + k]      K = conv_kernel taps,
                                                           depthwise, causal, zeros
                                                           before position 0
              out_proj·(C * c)
    attn(x):  q, k, v = q_proj·x, k_proj·x, v_proj·x  -> heads of head_dim
              q, k = RMS(q)·g_q, RMS(k)·g_k  per head, BEFORE the rotary
              rotary (rotate-half) on q and k; causal softmax at
              1/sqrt(head_dim), n_heads / n_kv_heads query heads a key head
              out_proj·attention
    dense(x): w2·(silu(w1·x) * (w3·x))
    moe(x):   s = sigmoid(float32(x)·float32(gate))        over n_experts
              pick = top-k of (s + expert_bias)
              w = s[pick] / (sum of s[pick] + 1e-6) * route_scale
              sum over pick of w_e * expert_e(x)           no shared expert
    head:     logits = (RMS(x)·g_emb) · embed^T            one tensor for both

Which operator a layer has is ``layer_types`` (``"conv"`` or
``"full_attention"``); the first ``n_dense`` layers have the dense
feed-forward, the rest the routed one.  That makes up to four KINDS of
layer (``layer_kinds``: ``conv_dense``, ``conv_moe``, ``attn_dense``,
``attn_moe``), each with its own leaves, bytes and serving state; the
parameters and the state are stacked by kind (``models/family.py``), and
the block tells its kind from the leaves it is handed.

Arithmetic: float32 between the products, as ``models/longcat.py``, and
float32 INTO them too — a product with weights takes the ``cfg.dtype``
weights as they stand and the activations as two ``cfg.dtype`` terms
(``_mm``), accumulated in float32; attention's two products, the router's
logits, sigmoid and top-k and the mix of the experts' outputs are float32
at ``highest`` matmul precision; the convolution is elementwise float32.
Nothing but the weights is ever rounded to ``cfg.dtype``: the router
picks 4 of 64 by scores 0.02 apart, and on the chip every rounding of an
activation flipped picks (PERF.md section 6, PR 31).

Serving state, a layer, float32: the last ``conv_kernel`` rows of ``v``
for a conv layer (whatever the context's length: 24 KB a sequence at the
published width), K and V rows per key head for an attention layer (4 KB
a position; what a ``cfg.dtype`` cache at long contexts does to the
picks is not measured).  Every expert is held here, and dispatch is dense (every
expert runs over every token, unpicked pairs weigh zero); ``moe_touched``
counts what a gathered dispatch would read instead.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import mla
from .llama import Spec, rope
from .mla import _rms

HF_ARCHITECTURE = "Lfm2Moe"  # models/hf.py refuses it by name


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    family = "lfm2"

    name: str = "tiny-lfm2"
    vocab: int = 256
    d_model: int = 64
    # one entry a layer: "conv" or "full_attention"
    layer_types: Tuple[str, ...] = ("conv", "conv", "full_attention", "conv")
    n_dense: int = 2  # leading layers with the dense feed-forward
    conv_kernel: int = 3
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    d_ff: int = 128  # dense SwiGLU width
    d_expert: int = 32
    n_experts: int = 16
    top_k: int = 4
    route_scale: float = 1.0
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        bad = set(self.layer_types) - {"conv", "full_attention"}
        if bad or not self.layer_types:
            raise ValueError(f"{self.name}: layer_types {sorted(bad)}; "
                             "known: conv, full_attention")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError(f"{self.name}: heads {self.n_heads} / "
                             f"{self.n_kv_heads} of {self.head_dim}")
        if not 0 < self.top_k <= self.n_experts:
            raise ValueError(f"{self.name}: top_k {self.top_k} of "
                             f"{self.n_experts} experts")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)


CONFIGS: Dict[str, Lfm2Config] = {"tiny-lfm2": Lfm2Config()}


# --------------------------------------------------------------- blob leaves

def layer_kinds(cfg: Lfm2Config) -> List[str]:
    """The kind of each layer id, ``<operator>_<feed-forward>``."""
    return [("conv" if op == "conv" else "attn")
            + ("_dense" if i < cfg.n_dense else "_moe")
            for i, op in enumerate(cfg.layer_types)]


def layer_param_specs(cfg: Lfm2Config, kind: str) -> List[Spec]:
    """(name, shape) of a layer's leaves in wire order: the operator
    under its norm, then the feed-forward under its own."""
    d, hd = cfg.d_model, cfg.head_dim
    op, ffn = kind.split("_")
    specs: List[Spec] = [("operator_norm", (d,))]
    if op == "conv":
        specs += [("in_proj", (d, 3 * d)), ("conv", (d, cfg.conv_kernel)),
                  ("out_proj", (d, d))]
    else:
        specs += [("q_proj", (d, cfg.n_heads * hd)),
                  ("k_proj", (d, cfg.n_kv_heads * hd)),
                  ("v_proj", (d, cfg.n_kv_heads * hd)),
                  ("q_layernorm", (hd,)), ("k_layernorm", (hd,)),
                  ("out_proj", (cfg.n_heads * hd, d))]
    specs.append(("ffn_norm", (d,)))
    if ffn == "dense":
        return specs + [("w1", (d, cfg.d_ff)), ("w3", (d, cfg.d_ff)),
                        ("w2", (cfg.d_ff, d))]
    e, fe = cfg.n_experts, cfg.d_expert
    return specs + [("gate", (d, e)), ("expert_bias", (e,)),
                    ("ew1", (e, d, fe)), ("ew3", (e, d, fe)),
                    ("ew2", (e, fe, d))]


def head_param_specs(cfg: Lfm2Config) -> List[Spec]:
    """Embedding and head are ONE tensor: it is on the wire once."""
    return [("embed", (cfg.vocab, cfg.d_model)),
            ("embedding_norm", (cfg.d_model,))]


# ---------------------------------------------------------------------- init

def init_layer_params(cfg: Lfm2Config, key: jax.Array,
                      kind: str) -> Dict[str, jax.Array]:
    """Seeded leaves of one layer: matrices normal at ``fan_in ** -0.5``
    (the taps at ``conv_kernel ** -0.5``), norm gains one, and a live
    selection bias, normal at 0.1 (a tenth of the sigmoid's range)."""
    specs = layer_param_specs(cfg, kind)
    keys = jax.random.split(key, len(specs))
    p = {}
    for (name, shape), k in zip(specs, keys):
        if name == "expert_bias":
            p[name] = jax.random.normal(k, shape, cfg.dtype) * 0.1
        elif len(shape) == 1:
            p[name] = jnp.ones(shape, cfg.dtype)
        else:
            fan_in = shape[-1] if name == "conv" else shape[-2]
            p[name] = jax.random.normal(k, shape, cfg.dtype) * fan_in ** -0.5
    return p


def init_head_params(cfg: Lfm2Config, k_emb: jax.Array,
                     k_out: jax.Array) -> Dict[str, jax.Array]:
    return {
        "embed": jax.random.normal(k_emb, (cfg.vocab, cfg.d_model),
                                   cfg.dtype) * cfg.d_model ** -0.5,
        "embedding_norm": jnp.ones((cfg.d_model,), cfg.dtype),
    }


# ------------------------------------------------------------------- blocks

def _two_terms(x, dtype):
    """Float32 ``x`` as two ``dtype`` terms whose sum keeps 16 of its
    bits: ``hi`` is ``x`` with the low half of its word cleared (a
    bfloat16 is the top half of a float32, so the narrowing is exact) and
    ``lo`` the rest, rounded.  By the bits and not by ``x -
    float32(bfloat16(x))``: the TPU's compiler is allowed to skip a
    narrowing that is widened straight back, which leaves ``lo`` zero
    (found on the chip, PR 31)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(dtype), (x - hi).astype(dtype)


def _mm(spec: str, x, w):
    """A product of float32 activations ``x`` with the ``w.dtype``
    weights ``w``, accumulated in float32,
    with ``x`` carried as TWO ``w.dtype`` terms (``_two_terms``) side by
    side along the sequence axis ``s`` of ``spec``: one pass over ``w``,
    twice the rows, the halves of the result added.  ``w`` is exact as it
    stands, so the product keeps 16 bits of the activations where one
    rounded term keeps 8.  Why: this family's router picks 4 of 64 by
    scores that lie 0.02 apart, every activation rounded on the way into
    a product moves the stream the router reads, and a flipped pick moves
    a token's output by a quarter of its routed block (PERF.md section 6,
    PR 31).  Weights are read once either way, so a step that their bytes
    bound costs the same."""
    if w.dtype != jnp.bfloat16:
        return mla._mm(spec, x, w)
    ins, out = spec.split("->")
    both = mla._mm(spec, jnp.concatenate(
        _two_terms(x.astype(jnp.float32), w.dtype),
        axis=ins.split(",")[0].index("s")), w)
    first, second = jnp.split(both, 2, axis=out.index("s"))
    return first + second


def _short_conv(p, xn, state, cfg: Lfm2Config):
    """The gated short convolution over ``xn [b, s, d]`` after the rows
    ``state [b, K, d]`` (the last K rows of ``v`` before this call; zeros
    at the start of a sequence), float32 throughout.  Returns (output,
    new state)."""
    k = cfg.conv_kernel
    b_gate, c_gate, u = jnp.split(_mm("bsd,de->bse", xn, p["in_proj"]), 3, -1)
    seen = jnp.concatenate([state, b_gate * u], axis=1)  # [b, K + s, d]
    s = xn.shape[1]
    taps = p["conv"].astype(jnp.float32)
    # c[t] = sum_j w[:, j] * v[t - (K-1) + j]; v[t] is seen[K + t]
    c = sum(taps[:, j] * seen[:, 1 + j:1 + j + s] for j in range(k))
    return _mm("bsd,de->bse", c_gate * c, p["out_proj"]), seen[:, -k:]


def _attention(p, xn, positions, cache, cfg: Lfm2Config):
    """Grouped-query attention with a norm on every query and key head
    before the rotary, float32 throughout.  ``cache`` is None (the
    sequence attends itself, causal) or ``{"k", "v"}: [b, max_len, kv,
    hd]`` (float32), written at ``positions`` and attended whole under
    the row-validity mask."""
    b, s, _ = xn.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _mm("bsd,dq->bsq", xn, p["q_proj"]).reshape(b, s, h, hd)
    k = _mm("bsd,dq->bsq", xn, p["k_proj"]).reshape(b, s, kv, hd)
    v = _mm("bsd,dq->bsq", xn, p["v_proj"]).reshape(b, s, kv, hd)
    q = rope(_rms(q, p["q_layernorm"], cfg.norm_eps), positions,
             cfg.rope_theta)
    k = rope(_rms(k, p["k_layernorm"], cfg.norm_eps), positions,
             cfg.rope_theta)
    if cache is None:
        valid = positions[:, None] >= positions[None, :]
    else:
        # Contiguous block write at the first position (prefill writes
        # the prompt at 0; a decode step one row at pos).
        at = (0, positions[0], 0, 0)
        k = jax.lax.dynamic_update_slice(cache["k"], k, at)
        v = jax.lax.dynamic_update_slice(cache["v"], v, at)
        cache = {"k": k, "v": v}
        valid = jnp.arange(k.shape[1])[None, :] <= positions[:, None]
    mask = jnp.where(valid, 0.0, -jnp.inf).astype(jnp.float32)
    # float32 by float32 (queries, keys, probabilities, values): at the
    # TPU's default precision each would be rounded to bfloat16 on the
    # way in, and K and V rows rounded so were what still flipped picks
    # once the weights' products kept their activations (PR 31).
    exact = jax.lax.Precision.HIGHEST
    scores = jnp.einsum("bskgh,btkh->bkgst",
                        q.reshape(b, s, kv, h // kv, hd), k,
                        precision=exact) / np.sqrt(hd)
    out = jnp.einsum("bkgst,btkh->bskgh",
                     jax.nn.softmax(scores + mask, axis=-1), v,
                     precision=exact)
    return _mm("bsq,qd->bsd", out.reshape(b, s, h * hd), p["out_proj"]), cache


def _dense_ffn(p, xn):
    gate = jax.nn.silu(_mm("bsd,df->bsf", xn, p["w1"]))
    return _mm("bsf,fd->bsd", gate * _mm("bsd,df->bsf", xn, p["w3"]), p["w2"])


def route(p, xn, cfg: Lfm2Config):
    """A token's picks and their weights: ``(idx [b, s, top_k] int32,
    w [b, s, top_k] float32)``.  The bias picks and does not weigh."""
    scores = jax.nn.sigmoid(
        jnp.einsum("bsd,de->bse", xn.astype(jnp.float32),
                   p["gate"].astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + p["expert_bias"].astype(jnp.float32),
                           cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, w / (w.sum(-1, keepdims=True) + 1e-6) * cfg.route_scale


def routed_ffn(p, xn, idx, w, cfg: Lfm2Config):
    """The routed block's output (float32) by dense dispatch, and what
    was counted: ``moe_slots`` (positions x top_k), ``moe_held`` (slots
    whose expert is here: all of them), ``moe_touched`` (distinct experts
    that got a slot in this call — what a gathered dispatch would
    read)."""
    picked = idx[..., None] == jnp.arange(cfg.n_experts)  # [b, s, k, e]
    gate = (w[..., None] * picked).sum(-2)  # [b, s, e]
    g = jax.nn.silu(_mm("bsd,edf->besf", xn, p["ew1"]))
    out = _mm("besf,efd->besd", g * _mm("bsd,edf->besf", xn, p["ew3"]),
              p["ew2"])
    # float32 by float32: at the TPU's default precision the operands
    # would be rounded to bfloat16 on the way in
    mixed = jnp.einsum("besd,bse->bsd", out, gate,
                       precision=jax.lax.Precision.HIGHEST)
    return mixed, {
        "moe_slots": jnp.asarray(idx.size, jnp.int32),
        "moe_held": jnp.sum(picked, dtype=jnp.int32),
        "moe_touched": jnp.sum(picked.any((0, 1, 2)), dtype=jnp.int32)}


def layer_with_cache(p, x, positions, cache, cfg: Lfm2Config):
    """One layer of whichever kind ``p``'s leaves say, float32 between
    its products; the result takes ``x``'s dtype.  ``cache`` is None or
    this layer's state: ``{"v": [b, K, d]}`` (float32) for a conv layer, ``{"k",
    "v"}: [b, max_len, kv, hd]`` for an attention layer.  Returns (x,
    cache, counters); a dense layer counts nothing."""
    x32 = x.astype(jnp.float32)
    xn = _rms(x32, p["operator_norm"], cfg.norm_eps)
    if "in_proj" in p:
        with jax.named_scope("model.shortconv"):
            state = (jnp.zeros((x.shape[0], cfg.conv_kernel, x.shape[2]),
                               jnp.float32) if cache is None else cache["v"])
            y, state = _short_conv(p, xn, state, cfg)
            if cache is not None:
                cache = {"v": state}
    else:
        with jax.named_scope("model.attn"):
            y, cache = _attention(p, xn, positions, cache, cfg)
    x32 = x32 + y
    xn = _rms(x32, p["ffn_norm"], cfg.norm_eps)
    counted = {}
    if "gate" in p:
        with jax.named_scope("model.moe.route"):
            idx, w = route(p, xn, cfg)
        with jax.named_scope("model.moe.experts"):
            y, counted = routed_ffn(p, xn, idx, w, cfg)
    else:
        with jax.named_scope("model.ffn"):
            y = _dense_ffn(p, xn)
    return (x32 + y).astype(x.dtype), cache, counted


def layer_apply(p, x, positions, cfg: Lfm2Config):
    return layer_with_cache(p, x, positions, None, cfg)[0]


# ------------------------------------------------------- embedding and head

def embed(params: Dict[str, Any], tokens, cfg: Lfm2Config):
    """The embedding's rows, as the float32 residual stream."""
    return params["embed"][tokens].astype(jnp.float32)


def logits(params: Dict[str, Any], x, cfg: Lfm2Config):
    """The embedding's own norm, then the embedding transposed: float32
    logits."""
    return _mm("bsd,vd->bsv", _rms(x, params["embedding_norm"], cfg.norm_eps),
               params["embed"])


# ------------------------------------------------------------ serving cache

def init_cache(cfg: Lfm2Config, batch: int, max_len: int) -> Dict[str, Any]:
    """Stacked by kind, as the parameters: ``v`` rows for the conv
    kinds, K and V rows for the attention kinds — a conv layer has no K/V
    and its state does not grow with ``max_len``.  All float32 (module
    docstring): 4 KB a position and attention layer at the published
    widths."""
    out = {}
    for kind, n in collections.Counter(layer_kinds(cfg)).items():
        if kind.startswith("conv"):
            out[kind] = {"v": jnp.zeros(
                (n, batch, cfg.conv_kernel, cfg.d_model), jnp.float32)}
        else:
            kv = (n, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
            out[kind] = {"k": jnp.zeros(kv, jnp.float32),
                         "v": jnp.zeros(kv, jnp.float32)}
    return out
