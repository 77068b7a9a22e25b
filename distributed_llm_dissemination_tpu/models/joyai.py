"""The JoyAI-LLM-Flash family: one latent attention a layer, a routed
block with a shared expert beside sigmoid-routed ones, and a
multi-token-prediction (MTP) module that is delivered as a blob and
drafts in ``generate`` — held as one rank's share of an expert-parallel
group.

A layer (``d`` = ``d_model``; every projection without bias, stored
``[in, out]``; ``RMS(x) = x * rsqrt(mean(x^2) + eps)``; the attention is
``models/mla.py``'s, with both of its scales 1):

    x += MLA(RMS(x)·g_attn)               x += feed_forward(RMS(x)·g_ffn)

    dense(x): w2·(silu(w1·x) * (w3·x))                  the first n_dense layers
    moe(x):   s = sigmoid(float32(x)·float32(gate))     over n_experts outputs
              pick = top-k of (s + gate_bias)           no group limit
              w = s[pick] / (sum of s[pick] + 1e-20) * route_scale
                                                        the sum over ALL picks,
                                                        held here or not
              sum over held picks of w_e * expert_e(x)  +  shared(x)
    head:     logits = lm_head·(RMS(x)·g_f)             embedding and head untied

    MTP module, for position i with the main model's output h_i and the
    NEXT token t_{i+1}:
              u_i = eh_proj·[RMS(embed(t_{i+1}))·g_e ; RMS(RMS(h_i)·g_f)·g_h]
              v_i = block(u_i)            one routed layer, its own attention
                                          and its own rows of the latent cache
              logits for token i + 2 = lm_head·(RMS(v_i)·g_s)

**Kinds of layer** (``layer_kinds``): ``dense``, ``moe`` and ``mtp``.
The module's blob is a layer blob like any other — its id is the last
layer id, ``n_layers - 1``, as the checkpoint numbers it, and the head
blob stays one past the layers — but it is no layer of the stack
(``side_kinds``): the forward's runs leave it out, and ``generate``
drafts with it (``drafts`` / ``draft``).  Its embedding and its output
head are the MAIN model's: they are in the head blob, on the wire and in
HBM once, and ``draft`` reads them from the parameter tree beside its own
leaves (``params["layers"]["mtp"]``).

**The share.**  ``experts_held`` of ``n_experts`` routed experts are held
here (ids ``expert_first ..``); the attention with every head, the shared
expert, the whole router with its bias, the norms and the whole
vocabulary are on every rank.  The router keeps every output, so a
token's picks and their weights are the deployment's; this rank adds its
own experts' part and the shared expert's (which every rank computes
alike: a sum over the ranks counts it once), and a pick of an absent
expert adds nothing.  Nothing here stands in for the other ranks or their
exchange.

The router and the share's dispatch are ``models/routed.py``'s
(``models/trinity.py``'s too; this family's selection bias is the leaf
``gate_bias``).

Arithmetic as ``models/lfm2.py``: float32 between the products and INTO
them — a product with weights takes the activations as two ``cfg.dtype``
terms (``lfm2._mm``); the attention's own products, the router and the
mix of the experts' outputs are float32 at ``highest`` precision — since
the router picks 8 of 256 by sigmoid scores a few hundredths apart
(PERF.md section 6, PR 33 has the reading with one rounded term).  The
latent cache is float32: 2.3 KB a position and layer.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from . import mla, routed
from .lfm2 import _mm
from .llama import Spec
from .mla import _rms

HF_ARCHITECTURE = "JoyAI"  # models/hf.py refuses it by name
_EXACT = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class JoyaiConfig:
    family = "joyai"

    name: str = "tiny-joyai"
    vocab: int = 256
    d_model: int = 64
    n_main: int = 3  # layers of the stack
    n_mtp: int = 1  # prediction modules delivered after them: 0 or 1
    n_dense: int = 1  # leading layers with the dense feed-forward
    n_heads: int = 4
    q_rank: int = 24
    kv_rank: int = 16
    nope_dim: int = 8
    rope_dim: int = 4
    v_dim: int = 8
    d_ff: int = 128  # dense SwiGLU width
    d_expert: int = 32
    d_shared: int = 32  # the shared experts' widths added up
    n_experts: int = 16  # routed; router outputs 0 .. n_experts
    experts_held: int = 16
    expert_first: int = 0
    top_k: int = 4
    route_scale: float = 2.5
    rope_theta: float = 32e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not (0 <= self.expert_first and self.experts_held > 0
                and self.expert_first + self.experts_held <= self.n_experts):
            raise ValueError(
                f"{self.name}: experts {self.expert_first}.."
                f"{self.expert_first + self.experts_held} of {self.n_experts}")
        if not 0 < self.top_k <= self.n_experts or self.rope_dim % 2:
            raise ValueError(f"{self.name}: top_k or rope_dim out of range")
        if self.n_mtp not in (0, 1) or not 0 <= self.n_dense <= self.n_main:
            raise ValueError(f"{self.name}: n_mtp {self.n_mtp} (0 or 1), "
                             f"n_dense {self.n_dense} of {self.n_main}")

    @property
    def n_layers(self) -> int:
        """Layer blobs: the stack's, then the module's."""
        return self.n_main + self.n_mtp

    @property
    def heads_held(self) -> int:
        """Every head is on every rank (``models/mla.py`` asks)."""
        return self.n_heads


CONFIGS: Dict[str, JoyaiConfig] = {"tiny-joyai": JoyaiConfig()}


# --------------------------------------------------------------- blob leaves

def layer_kinds(cfg: JoyaiConfig) -> List[str]:
    return (["dense"] * cfg.n_dense + ["moe"] * (cfg.n_main - cfg.n_dense)
            + ["mtp"] * cfg.n_mtp)


def side_kinds(cfg: JoyaiConfig) -> Tuple[str, ...]:
    """The module's blob is delivered as a layer and is none of the
    stack (``models/family.py``)."""
    return ("mtp",) if cfg.n_mtp else ()


def drafts(cfg: JoyaiConfig) -> bool:
    return cfg.n_mtp > 0


def layer_param_specs(cfg: JoyaiConfig, kind: str) -> List[Spec]:
    """(name, shape) of a layer's leaves in wire order: the attention
    under its norm, then the feed-forward under its own; the module's
    blob is a routed layer between its input side (``enorm``, ``hnorm``,
    ``eh_proj``) and its head's norm."""
    d, h = cfg.d_model, cfg.n_heads
    specs: List[Spec] = [
        ("attn_norm", (d,)),
        ("wq_a", (d, cfg.q_rank)),
        ("q_norm", (cfg.q_rank,)),
        ("wq_b", (cfg.q_rank, h * (cfg.nope_dim + cfg.rope_dim))),
        ("wkv_a", (d, cfg.kv_rank + cfg.rope_dim)),
        ("kv_norm", (cfg.kv_rank,)),
        ("wkv_b", (cfg.kv_rank, h * (cfg.nope_dim + cfg.v_dim))),
        ("wo", (h * cfg.v_dim, d)),
        ("ffn_norm", (d,)),
    ]
    if kind == "dense":
        return specs + [("w1", (d, cfg.d_ff)), ("w3", (d, cfg.d_ff)),
                        ("w2", (cfg.d_ff, d))]
    e, fe, fs = cfg.experts_held, cfg.d_expert, cfg.d_shared
    specs += [("gate", (d, cfg.n_experts)), ("gate_bias", (cfg.n_experts,)),
              ("sw1", (d, fs)), ("sw3", (d, fs)), ("sw2", (fs, d)),
              ("ew1", (e, d, fe)), ("ew3", (e, d, fe)), ("ew2", (e, fe, d))]
    if kind == "moe":
        return specs
    return ([("enorm", (d,)), ("hnorm", (d,)), ("eh_proj", (2 * d, d))]
            + specs + [("head_norm", (d,))])


def head_param_specs(cfg: JoyaiConfig) -> List[Spec]:
    return [("embed", (cfg.vocab, cfg.d_model)),
            ("ln_f", (cfg.d_model,)),
            ("lm_head", (cfg.d_model, cfg.vocab))]


# ---------------------------------------------------------------------- init

def init_layer_params(cfg: JoyaiConfig, key: jax.Array,
                      kind: str) -> Dict[str, jax.Array]:
    """Seeded leaves of one layer: matrices normal at ``fan_in ** -0.5``,
    norm gains one, and a live selection bias, normal at 0.1 (a tenth of
    the sigmoid's range)."""
    specs = layer_param_specs(cfg, kind)
    keys = jax.random.split(key, len(specs))
    p = {}
    for (name, shape), k in zip(specs, keys):
        if name == "gate_bias":
            p[name] = jax.random.normal(k, shape, cfg.dtype) * 0.1
        elif len(shape) == 1:
            p[name] = jnp.ones(shape, cfg.dtype)
        else:
            p[name] = (jax.random.normal(k, shape, cfg.dtype)
                       * shape[-2] ** -0.5)
    return p


def init_head_params(cfg: JoyaiConfig, k_emb: jax.Array,
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

def layer_with_cache(p, x, positions, cache, cfg: JoyaiConfig):
    """One layer of whichever kind ``p``'s leaves say (the module's block
    is a routed layer), float32 between its products; the result takes
    ``x``'s dtype.  ``cache`` is None (attention over the sequence itself,
    causal) or this layer's rows of the latent cache, ``{"ckv": [b,
    max_len, kv_rank], "kr": [b, max_len, rope]}`` (float32), written at
    ``positions`` and attended whole under the row-validity mask.
    Returns (x, cache, counters); a dense layer counts nothing."""
    x32 = x.astype(jnp.float32)
    if cache is None:
        valid = positions[:, None] >= positions[None, :]
    else:
        valid = (jnp.arange(cache["ckv"].shape[1])[None, :]
                 <= positions[:, None])  # [s, max_len]
    mask = jnp.where(valid, 0.0, -jnp.inf).astype(jnp.float32)
    with jax.named_scope("model.mla"):
        q, ckv, kr = mla.project(
            p, _rms(x32, p["attn_norm"], cfg.norm_eps), positions, cfg,
            mm=_mm, carry=jnp.float32)
        if cache is not None:
            # Contiguous block write at the first position (prefill
            # writes the prompt at 0; a decode step its rows at pos).
            at = (0, positions[0], 0)
            ckv = jax.lax.dynamic_update_slice(cache["ckv"], ckv, at)
            kr = jax.lax.dynamic_update_slice(cache["kr"], kr, at)
            cache = {"ckv": ckv, "kr": kr}
        x32 = x32 + mla.attend(p, q, ckv, kr, mask, cfg, mm=_mm,
                               precision=_EXACT)
    xn = _rms(x32, p["ffn_norm"], cfg.norm_eps)
    counted = {}
    if "gate" in p:
        with jax.named_scope("model.moe.route"):
            idx, w = routed.route(p, xn, cfg, "gate_bias")
        with jax.named_scope("model.moe.experts"):
            y, counted = routed.routed_part(p, xn, idx, w, cfg)
        with jax.named_scope("model.moe.shared"):
            y = y + routed.swiglu(xn, p["sw1"], p["sw3"], p["sw2"])
    else:
        with jax.named_scope("model.ffn"):
            y = routed.swiglu(xn, p["w1"], p["w3"], p["w2"])
    return (x32 + y).astype(x.dtype), cache, counted


def layer_apply(p, x, positions, cfg: JoyaiConfig):
    return layer_with_cache(p, x, positions, None, cfg)[0]


# ------------------------------------------------------- embedding and head

def embed(params: Dict[str, Any], tokens, cfg: JoyaiConfig):
    """The embedding's rows, as the float32 residual stream."""
    return params["embed"][tokens].astype(jnp.float32)


def logits(params: Dict[str, Any], x, cfg: JoyaiConfig):
    """Final norm and head: float32 logits."""
    return _mm("bsd,dv->bsv", _rms(x, params["ln_f"], cfg.norm_eps),
               params["lm_head"])


# ------------------------------------------------- the prediction module

def _mtp_hidden(mp, params, h, nxt, positions, cache, cfg: JoyaiConfig):
    """The module's block over positions whose main-model output (the
    stack's last hidden state, BEFORE the final norm) is ``h [b, s, d]``
    and whose next tokens are ``nxt [b, s]``; ``mp`` is the module's
    leaves, ``cache`` its rows of the latent cache or None.  The
    embedding half comes first in the concatenation."""
    e = _rms(embed(params, nxt, cfg), mp["enorm"], cfg.norm_eps)
    hn = _rms(_rms(h, params["ln_f"], cfg.norm_eps), mp["hnorm"],
              cfg.norm_eps)
    u = _mm("bsd,de->bse", jnp.concatenate([e, hn], axis=-1), mp["eh_proj"])
    return layer_with_cache(mp, u, positions, cache, cfg)


def _mtp_logits(mp, params, v, cfg: JoyaiConfig):
    """The module's own norm, then the MAIN model's head."""
    return _mm("bsd,dv->bsv", _rms(v, mp["head_norm"], cfg.norm_eps),
               params["lm_head"])


def _module(tree):
    """The one module's slice of a by-kind tree (its stack is of one)."""
    return jax.tree.map(lambda a: a[0], tree["mtp"])


def draft(params, h, nxt, positions, cache, cfg: JoyaiConfig, at):
    """What ``generate`` drafts with (``family.drafter``): the module over
    ``positions`` (``h`` the stack's last hidden state there, ``nxt`` the
    token after each), through its rows of ``cache`` (the whole serving
    state, by kind).  Returns (float32 logits ``[b, vocab]`` for the token
    two past position ``positions[at]``, the state, counters)."""
    with jax.named_scope("model.mtp"):
        mp = _module(params["layers"])
        v, rows, counted = _mtp_hidden(mp, params, h, nxt, positions,
                                       _module(cache), cfg)
        v = jax.lax.dynamic_slice_in_dim(v, at, 1, axis=1)
        cache = {**cache, "mtp": jax.tree.map(lambda a: a[None], rows)}
        return _mtp_logits(mp, params, v, cfg)[:, 0], cache, counted


def mtp_forward(params, tokens, cfg: JoyaiConfig):
    """The module's logits over a whole sequence without a cache,
    ``[b, s - 1, vocab]``: position ``i``'s (from the stack's output at
    ``i`` and token ``i + 1``) are for token ``i + 2``."""
    from .llama import apply_layers

    s = tokens.shape[1]
    h = apply_layers(params["layers"], embed(params, tokens, cfg),
                     jnp.arange(s), cfg)
    with jax.named_scope("model.mtp"):
        mp = _module(params["layers"])
        v, _, _ = _mtp_hidden(mp, params, h[:, :-1], tokens[:, 1:],
                              jnp.arange(s - 1), None, cfg)
        return _mtp_logits(mp, params, v, cfg)


# ------------------------------------------------------------ serving cache

def init_cache(cfg: JoyaiConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Stacked by kind, as the parameters: per layer and position the
    latent pair ``ckv`` / ``kr``, float32 (module docstring) — the
    module's rows beside the stack's."""
    return {kind: {"ckv": jnp.zeros((n, batch, max_len, cfg.kv_rank),
                                    jnp.float32),
                   "kr": jnp.zeros((n, batch, max_len, cfg.rope_dim),
                                   jnp.float32)}
            for kind, n in collections.Counter(layer_kinds(cfg)).items()}
