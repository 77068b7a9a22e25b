"""The LongCat-Flash family: latent attention and a shortcut expert block
with zero-compute experts, held as one chip's share of a wider
deployment.

One published "layer" is a double block (``d`` = ``d_model``; every
projection without bias, stored ``[in, out]``):

    a0 = h  + MLA_0(RMS(h)·g)                      n0 = RMS(a0)·g
    m  = MoE(n0)                                   # starts here, lands at the end
    b0 = a0 + W2_0·(silu(W1_0·n0) * (W3_0·n0))     # dense SwiGLU
    a1 = b0 + MLA_1(RMS(b0)·g)                     n1 = RMS(a1)·g
    h' = a1 + W2_1·(silu(W1_1·n1) * (W3_1·n1)) + m

    MLA(x): cq = RMS(Wqa·x)·g;  q = (Wqb·cq)·sqrt(d/q_rank) -> heads of nope + rope
            [ckv | kr] = Wkva·x;  ckv = RMS(ckv)·g · sqrt(d/kv_rank)
            [k_nope | v] = Wkvb·ckv -> heads of nope + v;  kr: ONE rotary head for all
            rotary on q's rope part and on kr (interleaved pairs);  k = [k_nope | kr]
            out = Wo · softmax(q·k / sqrt(nope + rope), causal) · v
    MoE(x): s = softmax(float32(x) · float32(Wr))  over n_experts + n_zero outputs
            pick = top-k of (s + bias);  w = route_scale · s[pick]   (not renormalised)
            m = sum over pick of w_e · (e < n_experts ? expert_e(x) : x)

**The share.**  A configuration says what is held HERE: ``heads_held`` of
``n_heads`` attention heads (``wq_b``, ``wkv_b``, ``wo`` carry those heads'
columns and rows only), experts ``expert_first .. expert_first +
experts_held`` of ``n_experts``, and ``vocab`` rows of the vocabulary
(ids ``0 .. vocab``; embedding, logits and sampling are over the slice).
The router keeps every output, so a token's picks are the deployment's;
this chip adds its own experts' part and the identity experts' part (they
have no weights, so every chip of the deployment computes them) and
leaves out what absent experts and heads would add.  That partial result
goes on to the next sub-block; nothing here stands in for the other chips
or their exchange.  With the whole model held the block is the published
one.

bfloat16 operands with float32 accumulation in every product, as
``models/llama.py``; between the products this family stays in float32
(see "Where this family rounds" below).  The router's logits and softmax
are float32 at ``highest`` matmul precision (on a TPU a float32 product
otherwise runs in bfloat16 passes).

Serving goes through a **latent cache**: per position ``ckv`` (after its
norm and scale) and the rotated ``kr``, one pair per attention sub-block,
so two per layer.  Prefill and decode up-project the whole cache through
``wkv_b`` at every step; ``wkv_b`` is not absorbed into the query and
output projections.  The attention itself is ``models/mla.py``'s, shared
with ``models/joyai.py``; this family's part is the two scales.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import family, mla
from .llama import Spec
from .mla import _mm, _rms

HF_ARCHITECTURE = "LongcatFlash"  # models/hf.py refuses it by name


@dataclasses.dataclass(frozen=True)
class LongcatConfig:
    family = "longcat"

    name: str = "tiny-longcat"
    vocab: int = 256  # rows held here: ids 0 .. vocab
    d_model: int = 64
    n_layers: int = 2  # published layers, each a double block
    n_heads: int = 4  # published
    heads_held: int = 4
    q_rank: int = 16
    kv_rank: int = 8
    nope_dim: int = 8
    rope_dim: int = 4
    v_dim: int = 8
    d_ff: int = 128  # dense SwiGLU width
    d_expert: int = 32
    n_experts: int = 24  # routed; router outputs 0 .. n_experts
    n_zero: int = 8  # identity experts; router outputs n_experts ..
    experts_held: int = 24
    expert_first: int = 0
    top_k: int = 4
    route_scale: float = 6.0
    rope_theta: float = 1e7
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not 0 < self.heads_held <= self.n_heads:
            raise ValueError(f"{self.name}: heads_held {self.heads_held} "
                             f"of {self.n_heads}")
        if not (0 <= self.expert_first and self.experts_held > 0
                and self.expert_first + self.experts_held <= self.n_experts):
            raise ValueError(
                f"{self.name}: experts {self.expert_first}.."
                f"{self.expert_first + self.experts_held} of {self.n_experts}")
        if self.top_k > self.n_experts + self.n_zero or self.rope_dim % 2:
            raise ValueError(f"{self.name}: top_k or rope_dim out of range")

    def layer_nbytes(self) -> int:
        return family.spec_nbytes(layer_param_specs(self), self.dtype)


CONFIGS: Dict[str, LongcatConfig] = {"tiny-longcat": LongcatConfig()}


# --------------------------------------------------------------- blob leaves

def layer_param_specs(cfg: LongcatConfig) -> List[Spec]:
    """(name, shape) of one layer's leaves in wire order: the two
    attention + dense sub-blocks, then the routed block.  The two
    sub-blocks' matrices stay separate leaves (a stacked ``(2, d, d_ff)``
    leaf would double the largest kind in flight during assembly)."""
    d, f, h = cfg.d_model, cfg.d_ff, cfg.heads_held
    specs: List[Spec] = []
    for i in (0, 1):
        specs += [
            (f"ln_in_{i}", (d,)),
            (f"wq_a_{i}", (d, cfg.q_rank)),
            (f"q_norm_{i}", (cfg.q_rank,)),
            (f"wq_b_{i}", (cfg.q_rank, h * (cfg.nope_dim + cfg.rope_dim))),
            (f"wkv_a_{i}", (d, cfg.kv_rank + cfg.rope_dim)),
            (f"kv_norm_{i}", (cfg.kv_rank,)),
            (f"wkv_b_{i}", (cfg.kv_rank, h * (cfg.nope_dim + cfg.v_dim))),
            (f"wo_{i}", (h * cfg.v_dim, d)),
            (f"ln_post_{i}", (d,)),
            (f"w1_{i}", (d, f)),
            (f"w3_{i}", (d, f)),
            (f"w2_{i}", (f, d)),
        ]
    e, fe = cfg.experts_held, cfg.d_expert
    return specs + [
        ("router", (d, cfg.n_experts + cfg.n_zero)),
        ("router_bias", (cfg.n_experts + cfg.n_zero,)),
        ("ew1", (e, d, fe)),
        ("ew3", (e, d, fe)),
        ("ew2", (e, fe, d)),
    ]


def head_param_specs(cfg: LongcatConfig) -> List[Spec]:
    return [
        ("embed", (cfg.vocab, cfg.d_model)),
        ("ln_f", (cfg.d_model,)),
        ("lm_head", (cfg.d_model, cfg.vocab)),
    ]


# ---------------------------------------------------------------------- init

def init_layer_params(cfg: LongcatConfig, key: jax.Array) -> Dict[str, jax.Array]:
    """Seeded leaves of one layer: matrices normal at ``fan_in ** -0.5``,
    norm gains one, the score bias zero (a fresh router)."""
    specs = layer_param_specs(cfg)
    keys = jax.random.split(key, len(specs))
    p = {}
    for (name, shape), k in zip(specs, keys):
        if name == "router_bias":
            p[name] = jnp.zeros(shape, cfg.dtype)
        elif len(shape) == 1:
            p[name] = jnp.ones(shape, cfg.dtype)
        else:
            p[name] = (jax.random.normal(k, shape, cfg.dtype)
                       * shape[-2] ** -0.5)
    return p


def init_head_params(cfg: LongcatConfig, k_emb: jax.Array,
                     k_out: jax.Array) -> Dict[str, jax.Array]:
    scale = cfg.d_model ** -0.5
    return {
        "embed": jax.random.normal(k_emb, (cfg.vocab, cfg.d_model),
                                   cfg.dtype) * scale,
        "ln_f": jnp.ones((cfg.d_model,), cfg.dtype),
        "lm_head": jax.random.normal(k_out, (cfg.d_model, cfg.vocab),
                                     cfg.dtype) * scale,
    }


# ------------------------------------------------------------------- blocks
#
# Where this family rounds: at a matrix product's INPUTS, and nowhere
# else.  Products take ``cfg.dtype`` operands and accumulate in float32
# (``_mm``); the residual stream, the norms, SwiGLU's gate * up, the
# scales and the routed mix stay in float32 until the next product
# rounds them.  At 16 tokens that costs nothing (the weights' bytes
# bound every step), and it is what keeps a token's top-12 picks the
# float32 reference's: the router reads the stream, so every rounding
# of the stream is a chance to flip a near-tie, and a flipped identity
# slot moves a whole token's output (PERF.md section 6, PR 27).


def _mla_project(p, i: int, xn, positions, cfg: LongcatConfig):
    """Sub-block ``i``'s queries and latent pair (``mla.project``), with
    this family's two scales and in ``cfg.dtype``."""
    d = xn.shape[-1]
    return mla.project(p, xn, positions, cfg, sfx=f"_{i}",
                       q_scale=np.sqrt(d / cfg.q_rank),
                       kv_scale=np.sqrt(d / cfg.kv_rank))


def _mla_attend(p, i: int, q, ckv, kr, mask, cfg: LongcatConfig):
    """Sub-block ``i``'s held heads' share of ``Wo·attention``
    (``mla.attend``), float32."""
    return mla.attend(p, q, ckv, kr, mask, cfg, sfx=f"_{i}")


def _dense_ffn(p, i: int, xn):
    gate = jax.nn.silu(_mm("bsd,df->bsf", xn, p[f"w1_{i}"]))
    return _mm("bsf,fd->bsd", gate * _mm("bsd,df->bsf", xn, p[f"w3_{i}"]),
               p[f"w2_{i}"])


def route(p, xn, cfg: LongcatConfig):
    """A token's picks among ALL router outputs and their weights:
    ``(idx [b, s, top_k] int32, w [b, s, top_k] float32)``."""
    scores = jax.nn.softmax(
        jnp.einsum("bsd,de->bse", xn.astype(jnp.float32),
                   p["router"].astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST), axis=-1)
    _, idx = jax.lax.top_k(scores + p["router_bias"].astype(jnp.float32),
                           cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1) * cfg.route_scale
    return idx, w


def routed_part(p, xn, idx, w, cfg: LongcatConfig):
    """This chip's part of the routed block's output (float32): its own
    experts for the slots that picked them (dense dispatch: every held
    expert runs over every token and unpicked pairs weigh zero) and the
    identity experts' slots; a slot that picked an absent expert adds
    nothing.  Also what was counted: ``moe_slots`` / ``moe_held`` /
    ``moe_zero``."""
    held = (idx[..., None] - cfg.expert_first
            == jnp.arange(cfg.experts_held))  # [b, s, top_k, held]
    gate = (w[..., None] * held).sum(-2)  # [b, s, held]
    zero = idx >= cfg.n_experts
    g = jax.nn.silu(_mm("bsd,edf->besf", xn, p["ew1"]))
    out = _mm("besf,efd->besd", g * _mm("bsd,edf->besf", xn, p["ew3"]),
              p["ew2"])
    m = jnp.einsum("besd,bse->bsd", out, gate)
    m = m + (w * zero).sum(-1, keepdims=True) * xn
    return m, {"moe_slots": jnp.asarray(idx.size, jnp.int32),
               "moe_held": jnp.sum(held, dtype=jnp.int32),
               "moe_zero": jnp.sum(zero, dtype=jnp.int32)}


def layer_with_cache(p, x, positions, cache, cfg: LongcatConfig):
    """The double block, float32 between its products; the result takes
    ``x``'s dtype.  ``cache`` is None (attention over the sequence itself,
    causal) or this layer's slice of the latent cache, ``{"ckv": [2, b,
    max_len, kv_rank], "kr": [2, b, max_len, rope]}``: each attention
    sub-block then writes its rows at ``positions`` and attends the whole
    cache under the row-validity mask.  Returns (x, cache, counters)."""
    if cache is None:
        valid = positions[:, None] >= positions[None, :]
    else:
        valid = (jnp.arange(cache["ckv"].shape[2])[None, :]
                 <= positions[:, None])  # [s, max_len]
    mask = jnp.where(valid, 0.0, -jnp.inf).astype(jnp.float32)
    new_cache = {"ckv": [], "kr": []}

    def attention(i, h):
        with jax.named_scope("model.mla"):
            q, ckv, kr = _mla_project(
                p, i, _rms(h, p[f"ln_in_{i}"], cfg.norm_eps), positions, cfg)
            if cache is not None:
                # Contiguous block write at the first position (prefill
                # writes the prompt at 0; a decode step one row at pos).
                at = (0, positions[0], 0)
                ckv = jax.lax.dynamic_update_slice(cache["ckv"][i], ckv, at)
                kr = jax.lax.dynamic_update_slice(cache["kr"][i], kr, at)
                new_cache["ckv"].append(ckv)
                new_cache["kr"].append(kr)
            return h + _mla_attend(p, i, q, ckv, kr, mask, cfg)

    a0 = attention(0, x.astype(jnp.float32))
    n0 = _rms(a0, p["ln_post_0"], cfg.norm_eps)
    with jax.named_scope("model.moe.route"):
        idx, w = route(p, n0, cfg)
    with jax.named_scope("model.moe.experts"):
        m, counted = routed_part(p, n0, idx, w, cfg)
    with jax.named_scope("model.ffn"):
        b0 = a0 + _dense_ffn(p, 0, n0)
    a1 = attention(1, b0)
    with jax.named_scope("model.ffn"):
        out = a1 + _dense_ffn(
            p, 1, _rms(a1, p["ln_post_1"], cfg.norm_eps)) + m
    if cache is not None:
        cache = {k: jnp.stack(v) for k, v in new_cache.items()}
    return out.astype(x.dtype), cache, counted


def layer_apply(p, x, positions, cfg: LongcatConfig):
    return layer_with_cache(p, x, positions, None, cfg)[0]


# ------------------------------------------------------- embedding and head

def embed(params: Dict[str, Any], tokens, cfg: LongcatConfig):
    """The held rows of the embedding, as the float32 residual stream."""
    return params["embed"][tokens].astype(jnp.float32)


def logits(params: Dict[str, Any], x, cfg: LongcatConfig):
    """Final norm and the held slice of the head: float32 logits over
    ids ``0 .. vocab``."""
    return _mm("bsd,dv->bsv", _rms(x, params["ln_f"], cfg.norm_eps),
               params["lm_head"])


# ------------------------------------------------------------ serving cache

LatentCache = Dict[str, jax.Array]  # {"ckv","kr"}: [n_layers, 2, b, max_len, ·]


def init_cache(cfg: LongcatConfig, batch: int, max_len: int) -> LatentCache:
    lead = (cfg.n_layers, 2, batch, max_len)
    return {"ckv": jnp.zeros(lead + (cfg.kv_rank,), cfg.dtype),
            "kr": jnp.zeros(lead + (cfg.rope_dim,), cfg.dtype)}
