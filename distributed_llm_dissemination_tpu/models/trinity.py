"""The Trinity family (``model_type: afmoe``): window and full attention
mixed over the depth, every layer's attention gated, a routed block with
a shared expert beside sigmoid-routed ones — held as one rank's share of
an expert-parallel group.

A layer (``d`` = ``d_model``; every projection without bias, stored
``[in, out]``; ``RMS(x) = x * rsqrt(mean(x^2) + eps)``; four norms a
layer, the sandwich):

    x += RMS(attention(RMS(x)·g_in))·g_post_attn
    x += RMS(feed_forward(RMS(x)·g_pre_ffn))·g_post_ffn

    attention(x):
        q, k, v = q_proj·x, k_proj·x, v_proj·x   -> heads of head_dim
        q, k = RMS(q)·g_q, RMS(k)·g_k            per head
        rotary (rotate-half) on q and k          in a layer with a WINDOW
                                                 only: a full layer has no
                                                 positional encoding
        position i attends j with i - window < j <= i (window), or every
        j <= i (full); softmax at 1/sqrt(head_dim), n_heads / n_kv_heads
        query heads a key head
        o_proj·(attention * sigmoid(gate_proj·x))    gated BEFORE o_proj
    dense(x): w2·(silu(w1·x) * (w3·x))           the first n_dense layers
    moe(x):   ``models/routed.py``'s router (sigmoid, selection bias
              ``expert_bias``, renormalised over all picks, x route_scale)
              and this rank's experts' part  +  shared(x)
    embed:    embed[tokens] * sqrt(d)            (``mup_enabled``)
    head:     logits = lm_head·(RMS(x)·g_f)      embedding and head untied

**Kinds of layer** (``layer_kinds``): ``<dense|routed>_<sliding|full>``,
from ``layer_types`` and ``n_dense``.  A block tells its kind from the
leaves it is handed (``models/family.py``): the feed-forward by ``w1``
or ``gate``; the attention by the NAME of its input norm — ``window_norm``
in a layer with a window, ``attn_norm`` in a full one — since their
leaves are otherwise alike to the shape.

**The share.**  ``experts_held`` of ``n_experts`` routed experts are held
here (ids ``expert_first ..``) and ``vocab`` counts the rows of the
vocabulary held here (a slice from row 0: a sliced vocabulary is a
smaller vocabulary); the attention with every head, the shared expert,
the whole router with its bias and the norms are on every rank.  Nothing
here stands in for the other ranks or their exchange.

**Attention without an ``s x t`` array.**  A sequence attends itself a
block of ``BLOCK`` queries at a time (``_attend_blocks``: a scan over
query blocks, an online softmax over the key blocks each may see — a
layer with a window visits those inside its band alone); up to ``BLOCK``
positions it is the one plain softmax.  The dense feed-forward and the
shared expert run ``BLOCK`` positions at a time too
(``_swiglu_in_blocks``); the held experts run over the whole call by
``routed.dispatch``, each over the slots that picked it alone in a long
prompt (``routed.grouped``), every one over every position in a short
call (``routed.experts``).

**Serving state**, float32 as ``models/lfm2.py``'s: ``{"k", "v"}: [b,
rows, n_kv_heads, head_dim]`` a layer — ``rows = max_len`` in a full
layer, ``min(max_len, window)`` in a layer with a window: a RING, written
at ``position mod rows`` (keys are rotated before they are written, so
the ring's order does not matter; ``max_len mod rows`` is the position
too where nothing wraps, so both kinds write and mask alike).  A call
with ONE position is a decode step: its row is written, then the rows
are attended whole under ``row <= position`` (in a ring that has wrapped
every row is inside the window).  A call with more is a PREFILL FROM
POSITION 0 (``generate``'s only other use): it attends inside itself
under the band and leaves its last ``rows`` positions behind.  Counted a
call and layer, over the sequences of the batch: ``kv_rows`` (rows the
state holds that it did not before) and ``swa_evicted`` (positions
written over, or never kept: what a ring's layer no longer holds);
in a routed layer also ``moe_rows`` (the expert rows computed, padding
included: ``_feed_forward``).

Arithmetic as ``models/lfm2.py`` and ``models/joyai.py``: float32 between
the products and INTO them (``lfm2._mm``'s two ``cfg.dtype`` terms),
attention's own products, the router and the experts' mix float32 at
``highest`` precision.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import routed
from .lfm2 import _mm
from .llama import Spec, rope
from .mla import _rms

HF_ARCHITECTURE = "Afmoe"  # models/hf.py refuses it by name
_EXACT = jax.lax.Precision.HIGHEST
# Queries (and keys) a block of the blockwise attention, and positions a
# block of the dense feed-forward and the shared expert: a power of two,
# fixed here.
BLOCK = 512


@dataclasses.dataclass(frozen=True)
class TrinityConfig:
    family = "trinity"

    name: str = "tiny-trinity"
    vocab: int = 256  # rows of the vocabulary held here
    d_model: int = 64
    # one entry a layer: "sliding_attention" or "full_attention"
    layer_types: Tuple[str, ...] = (
        "sliding_attention", "sliding_attention", "sliding_attention",
        "full_attention", "sliding_attention", "sliding_attention")
    n_dense: int = 2  # leading layers with the dense feed-forward
    window: int = 8
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    d_ff: int = 128  # dense SwiGLU width
    d_expert: int = 32
    d_shared: int = 32  # the shared experts' widths added up
    n_experts: int = 16  # routed; router outputs 0 .. n_experts
    experts_held: int = 16
    expert_first: int = 0
    top_k: int = 4
    route_scale: float = 2.826
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        bad = set(self.layer_types) - {"sliding_attention", "full_attention"}
        if bad or not self.layer_types:
            raise ValueError(f"{self.name}: layer_types {sorted(bad)}; "
                             "known: sliding_attention, full_attention")
        if not (0 <= self.expert_first and self.experts_held > 0
                and self.expert_first + self.experts_held <= self.n_experts):
            raise ValueError(
                f"{self.name}: experts {self.expert_first}.."
                f"{self.expert_first + self.experts_held} of {self.n_experts}")
        if (self.n_heads % self.n_kv_heads or self.head_dim % 2
                or not 0 < self.top_k <= self.n_experts or self.window < 1
                or not 0 <= self.n_dense <= len(self.layer_types)):
            raise ValueError(f"{self.name}: heads, top_k, window or n_dense "
                             "out of range")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)


CONFIGS: Dict[str, TrinityConfig] = {"tiny-trinity": TrinityConfig()}


# --------------------------------------------------------------- blob leaves

def layer_kinds(cfg: TrinityConfig) -> List[str]:
    """The kind of each layer id, ``<feed-forward>_<attention>``."""
    return [("dense" if i < cfg.n_dense else "routed")
            + ("_sliding" if op == "sliding_attention" else "_full")
            for i, op in enumerate(cfg.layer_types)]


def layer_param_specs(cfg: TrinityConfig, kind: str) -> List[Spec]:
    """(name, shape) of a layer's leaves in wire order: the attention
    between its two norms, then the feed-forward between its own."""
    d, hd = cfg.d_model, cfg.head_dim
    ffn, attn = kind.split("_")
    specs: List[Spec] = [
        ("window_norm" if attn == "sliding" else "attn_norm", (d,)),
        ("q_proj", (d, cfg.n_heads * hd)),
        ("k_proj", (d, cfg.n_kv_heads * hd)),
        ("v_proj", (d, cfg.n_kv_heads * hd)),
        ("o_proj", (cfg.n_heads * hd, d)),
        ("gate_proj", (d, cfg.n_heads * hd)),
        ("q_norm", (hd,)), ("k_norm", (hd,)),
        ("post_attn_norm", (d,)), ("pre_ffn_norm", (d,)),
    ]
    if ffn == "dense":
        specs += [("w1", (d, cfg.d_ff)), ("w3", (d, cfg.d_ff)),
                  ("w2", (cfg.d_ff, d))]
    else:
        e, fe, fs = cfg.experts_held, cfg.d_expert, cfg.d_shared
        specs += [("gate", (d, cfg.n_experts)),
                  ("expert_bias", (cfg.n_experts,)),
                  ("sw1", (d, fs)), ("sw3", (d, fs)), ("sw2", (fs, d)),
                  ("ew1", (e, d, fe)), ("ew3", (e, d, fe)),
                  ("ew2", (e, fe, d))]
    return specs + [("post_ffn_norm", (d,))]


def head_param_specs(cfg: TrinityConfig) -> List[Spec]:
    return [("embed", (cfg.vocab, cfg.d_model)),
            ("ln_f", (cfg.d_model,)),
            ("lm_head", (cfg.d_model, cfg.vocab))]


# ---------------------------------------------------------------------- init

def init_layer_params(cfg: TrinityConfig, key: jax.Array,
                      kind: str) -> Dict[str, jax.Array]:
    """Seeded leaves of one layer: matrices normal at ``fan_in ** -0.5``,
    norm gains one, and a live selection bias, normal at 0.1 (a tenth of
    the sigmoid's range)."""
    specs = layer_param_specs(cfg, kind)
    keys = jax.random.split(key, len(specs))
    p = {}
    for (name, shape), k in zip(specs, keys):
        if name == "expert_bias":
            p[name] = jax.random.normal(k, shape, cfg.dtype) * 0.1
        elif len(shape) == 1:
            p[name] = jnp.ones(shape, cfg.dtype)
        else:
            p[name] = (jax.random.normal(k, shape, cfg.dtype)
                       * shape[-2] ** -0.5)
    return p


def init_head_params(cfg: TrinityConfig, k_emb: jax.Array,
                     k_out: jax.Array) -> Dict[str, jax.Array]:
    scale = cfg.d_model ** -0.5
    return {
        "embed": jax.random.normal(k_emb, (cfg.vocab, cfg.d_model),
                                   cfg.dtype) * scale,
        "ln_f": jnp.ones((cfg.d_model,), cfg.dtype),
        "lm_head": jax.random.normal(k_out, (cfg.d_model, cfg.vocab),
                                     cfg.dtype) * scale,
    }


# ---------------------------------------------------------------- attention

def _band(rows, cols, window: Optional[int]):
    """Which key positions ``cols`` each query position of ``rows`` may
    see: ``[rows, cols]`` bool."""
    valid = cols[None, :] <= rows[:, None]
    if window is not None:
        valid &= cols[None, :] > rows[:, None] - window
    return valid


def _attend(q, k, v, valid):
    """The one plain softmax: queries ``[b, s, kv, g, hd]`` against rows
    ``k``, ``v`` ``[b, t, kv, hd]`` under ``valid [s, t]``; float32 by
    float32 at ``highest`` (``lfm2._attention`` says why)."""
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k,
                        precision=_EXACT) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(valid, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bkgst,btkh->bskgh", probs, v, precision=_EXACT)


def _attend_blocks(q, k, v, window: Optional[int]):
    """A sequence's causal attention over itself — ``q [b, s, kv, g,
    hd]``, ``k``, ``v`` ``[b, s, kv, hd]``, within ``window`` positions
    or, with None, all before — without an ``s x s`` array: a scan over
    blocks of ``BLOCK`` queries, each an online softmax over the key
    blocks it may see, its own first (there every query sees at least
    itself, so the running maximum is finite from the start) and then
    back to the edge of its band.  A sequence of one block is the plain
    softmax."""
    b, s, kv, g, hd = q.shape
    if s <= BLOCK:
        at = jnp.arange(s)
        return _attend(q, k, v, _band(at, at, window))
    n = -(-s // BLOCK)
    # zero rows up to a whole block: as keys they lie after every real
    # query, as queries they are cut off below
    q, k, v = (jnp.pad(a, ((0, 0), (0, n * BLOCK - s)) + ((0, 0),)
                       * (a.ndim - 2)) for a in (q, k, v))
    k, v = (a.reshape(b, n, BLOCK, kv, hd) for a in (k, v))
    # key blocks before its own that a query block may see
    back = n if window is None else -(-(window - 1) // BLOCK)
    within = jnp.arange(BLOCK)

    def query_block(_, i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * BLOCK, BLOCK, axis=1)
        rows = i * BLOCK + within

        def key_block(t, carry):
            top, total, acc = carry
            j = i - t
            kj, vj = (jax.lax.dynamic_index_in_dim(a, j, 1, False)
                      for a in (k, v))
            scores = jnp.einsum("bskgh,btkh->bkgst", qi, kj,
                                precision=_EXACT) / np.sqrt(hd)
            scores = jnp.where(_band(rows, j * BLOCK + within, window),
                               scores, -jnp.inf)
            new_top = jnp.maximum(top, scores.max(-1))
            probs = jnp.exp(scores - new_top[..., None])
            keep = jnp.exp(top - new_top)
            return (new_top, total * keep + probs.sum(-1),
                    acc * keep[..., None] + jnp.einsum(
                        "bkgst,btkh->bkgsh", probs, vj, precision=_EXACT))

        zero = jnp.zeros((b, kv, g, BLOCK), jnp.float32)
        _, total, acc = jax.lax.fori_loop(
            0, jnp.minimum(i, back) + 1, key_block,
            (zero - jnp.inf, zero, jnp.zeros((b, kv, g, BLOCK, hd),
                                             jnp.float32)))
        return None, acc / total[..., None]

    _, out = jax.lax.scan(query_block, None, jnp.arange(n))
    # [n, b, kv, g, BLOCK, hd] -> [b, s, kv, g, hd]
    return out.transpose(1, 0, 4, 2, 3, 5).reshape(
        b, n * BLOCK, kv, g, hd)[:, :s]


def _attention(p, xn, positions, cache, window: Optional[int],
               cfg: TrinityConfig):
    """The gated attention of one layer over ``xn [b, s, d]`` (module
    docstring: the rotary with a window only; ``cache`` None, or this
    layer's rows, through which one position is a decode step and more
    are a prefill from position 0).  Returns (output, cache, counters)."""
    b, s, _ = xn.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _rms(_mm("bsd,dq->bsq", xn, p["q_proj"]).reshape(b, s, h, hd),
             p["q_norm"], cfg.norm_eps)
    k = _rms(_mm("bsd,dq->bsq", xn, p["k_proj"]).reshape(b, s, kv, hd),
             p["k_norm"], cfg.norm_eps)
    v = _mm("bsd,dq->bsq", xn, p["v_proj"]).reshape(b, s, kv, hd)
    if window is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = q.reshape(b, s, kv, h // kv, hd)
    counted = {}
    if cache is not None:
        rows = cache["k"].shape[1]
        first = positions[0]
        kept = (jnp.minimum(first + s, rows)
                - jnp.minimum(first, rows)).astype(jnp.int32)
        counted = {"kv_rows": kept * b, "swa_evicted": (s - kept) * b}
    if cache is not None and s == 1:
        at = (0, positions[0] % rows, 0, 0)
        k = jax.lax.dynamic_update_slice(cache["k"], k, at)
        v = jax.lax.dynamic_update_slice(cache["v"], v, at)
        cache = {"k": k, "v": v}
        out = _attend(q, k, v,
                      jnp.arange(rows)[None, :] <= positions[:, None])
    else:
        out = _attend_blocks(q, k, v, window)
        if cache is not None:
            # the last ``rows`` positions, each at its position mod rows
            last = [a[:, max(0, s - rows):] for a in (k, v)]
            if s > rows:
                last = [jnp.roll(a, s % rows, axis=1) for a in last]
            cache = {name: jax.lax.dynamic_update_slice(
                cache[name], a, (0, 0, 0, 0))
                for name, a in zip(("k", "v"), last)}
    with jax.named_scope("model.attn.gate"):
        out = out.reshape(b, s, h * hd) * jax.nn.sigmoid(
            _mm("bsd,dq->bsq", xn, p["gate_proj"]))
    return _mm("bsq,qd->bsd", out, p["o_proj"]), cache, counted


# ------------------------------------------------------------- feed-forward

def _swiglu_in_blocks(x, w1, w3, w2):
    """``routed.swiglu`` over ``x [b, s, d]`` — a function of each
    position alone — ``BLOCK`` positions at a time where the sequence has
    more."""
    b, s, d = x.shape
    if s <= BLOCK:
        return routed.swiglu(x, w1, w3, w2)
    n = -(-s // BLOCK)
    x = jnp.pad(x, ((0, 0), (0, n * BLOCK - s), (0, 0)))
    out = jax.lax.map(lambda xb: routed.swiglu(xb, w1, w3, w2),
                      jnp.moveaxis(x.reshape(b, n, BLOCK, d), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(b, n * BLOCK, d)[:, :s]


def _feed_forward(p, xn, cfg: TrinityConfig):
    """(output, counters): dense a block of positions at a time, or the
    router over the whole call (``moe_touched`` is a call's), this rank's
    experts by ``routed.dispatch`` over the whole call (dense for a
    decode step or a short prompt, grouped for a long prompt) and the
    shared one a block at a time.  ``moe_rows`` counts the expert rows
    computed: ``b·s·experts_held`` dense, the grouped loop's items times
    its rows an item."""
    if "gate" not in p:
        with jax.named_scope("model.ffn"):
            return _swiglu_in_blocks(xn, p["w1"], p["w3"], p["w2"]), {}
    with jax.named_scope("model.moe.route"):
        idx, w = routed.route(p, xn, cfg, "expert_bias")
        mine = routed.held(idx, cfg)
    with jax.named_scope("model.moe.experts"):
        y, rows = routed.dispatch(p, xn, idx, w, mine, cfg)
    with jax.named_scope("model.moe.shared"):
        y = y + _swiglu_in_blocks(xn, p["sw1"], p["sw3"], p["sw2"])
    return y, {**routed.counts(idx, mine), "moe_rows": rows}


# ------------------------------------------------------------------- blocks

def layer_with_cache(p, x, positions, cache, cfg: TrinityConfig):
    """One layer of whichever kind ``p``'s leaves say, float32 between
    its products; the result takes ``x``'s dtype.  ``cache`` is None or
    this layer's ``{"k", "v"}`` rows (module docstring).  Returns (x,
    cache, counters)."""
    x32 = x.astype(jnp.float32)
    windowed = "window_norm" in p
    xn = _rms(x32, p["window_norm" if windowed else "attn_norm"],
              cfg.norm_eps)
    with jax.named_scope("model.attn.window" if windowed
                         else "model.attn.full"):
        y, cache, counted = _attention(
            p, xn, positions, cache, cfg.window if windowed else None, cfg)
    x32 = x32 + _rms(y, p["post_attn_norm"], cfg.norm_eps)
    y, more = _feed_forward(p, _rms(x32, p["pre_ffn_norm"], cfg.norm_eps),
                            cfg)
    x32 = x32 + _rms(y, p["post_ffn_norm"], cfg.norm_eps)
    return x32.astype(x.dtype), cache, {**counted, **more}


def layer_apply(p, x, positions, cfg: TrinityConfig):
    return layer_with_cache(p, x, positions, None, cfg)[0]


# ------------------------------------------------------- embedding and head

def embed(params: Dict[str, Any], tokens, cfg: TrinityConfig):
    """The embedding's rows times ``sqrt(d)``, as the float32 residual
    stream."""
    return params["embed"][tokens].astype(jnp.float32) * np.sqrt(cfg.d_model)


def logits(params: Dict[str, Any], x, cfg: TrinityConfig):
    """Final norm and head: float32 logits."""
    return _mm("bsd,dv->bsv", _rms(x, params["ln_f"], cfg.norm_eps),
               params["lm_head"])


# ------------------------------------------------------------ serving cache

def init_cache(cfg: TrinityConfig, batch: int,
               max_len: int) -> Dict[str, Any]:
    """Stacked by kind, as the parameters: K and V rows per key head,
    float32 — ``max_len`` rows a full layer, a ring of ``min(max_len,
    window)`` a layer with a window (4 KB a row at the published
    widths)."""
    out = {}
    for kind, n in collections.Counter(layer_kinds(cfg)).items():
        rows = (min(max_len, cfg.window) if kind.endswith("sliding")
                else max_len)
        shape = (n, batch, rows, cfg.n_kv_heads, cfg.head_dim)
        out[kind] = {"k": jnp.zeros(shape, jnp.float32),
                     "v": jnp.zeros(shape, jnp.float32)}
    return out
