"""The LFM2-MoE architecture (LiquidAI/LFM2-24B-A2B, ``model_type:
lfm2_moe``): what the harness asks of an architecture
(``benchmark/archs/__init__.py``), for a configuration file in Hugging
Face's keys and for ``models/lfm2.py``.

The plain reference is straightforward ``jax.numpy``: no cache, no
kernels, no scan over layers, one expert at a time and no dispatch
tensor.  It shares
no code with ``models/lfm2.py`` and follows the published block
(``Lfm2MoeForCausalLM``; ``d`` = hidden_size; every projection without
bias, stored ``[in, out]``; ``RMS(x) = x * rsqrt(mean(x^2) + norm_eps)``):

    h   = embed[tokens]
    h  += operator(RMS(h)*g_operator)       h += feed_forward(RMS(h)*g_ffn)
    out = (RMS(h)*g_embedding) . embed^T    embedding and head: ONE tensor

    conv:  [B | C | u] = in_proj . x;  v = B * u
           c[t] = sum_k w[:, k] * v[t - (L-1) + k]     L = conv_L_cache taps,
                                                       depthwise, causal, zeros
                                                       before position 0, no bias
           out_proj . (C * c)
    attn:  q, k, v = q_proj . x, k_proj . x, v_proj . x;  RMS over each query
           head (q_layernorm) and each key head (k_layernorm) BEFORE the
           rotary; rotary in the half-split ("rotate_half") convention over
           the whole head at theta ** (-i / (hd/2)); causal softmax at
           1/sqrt(hd), heads / kv_heads query heads a key head; out_proj
    dense: w2 . (silu(w1 . x) * (w3 . x))
    moe:   s = sigmoid(x . gate);  pick = top-k of (s + expert_bias)
           w = s[pick] / (sum of s[pick] + 1e-6)  (norm_topk_prob)
               * routed_scaling_factor
           sum over pick of w_e * expert_e(x);  no shared expert

A layer's operator is its entry of ``layer_types`` (``conv`` or
``full_attention``); the first ``num_dense_layers`` layers have the dense
feed-forward, the others the routed one.  ``ref_layer`` is handed no layer
index: it tells a conv from an attention layer and a dense from a routed
one by the leaves in ``p`` (``in_proj``; ``gate``).

Assumed, because the catalog's config does not say (each also under
``assumed`` in the configuration's file): embedding and head tied
(``tie_word_embeddings``, the family's convention and its class's
default); ``head_dim`` = hidden_size / heads; the router and the
selection bias in float32 (everything here is); norm gains 1.

Departures from the published model, all of them the configuration's cut
(``reduced`` / ``deployment`` in its file): the first ``num_hidden_layers``
layers of the published stack, the rest on further pipeline stages.  No
width, expert or vocabulary row is cut.
"""

from __future__ import annotations

import numpy as np

PKG = "distributed_llm_dissemination_tpu"
# ``dims`` of the configuration this process registered (``register``):
# ``leaf`` is handed a boot result and no configuration.
_REGISTERED = None


# ---------------------------------------------------------- sizes and layout


def dims(config: dict) -> dict:
    """The sizes, from a configuration file in the source's own keys."""
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    types = list(config["layer_types"])
    layers = int(config["num_hidden_layers"])
    if len(types) != layers or set(types) - {"conv", "full_attention"}:
        raise ValueError(f"layer_types {types} for {layers} layers; an "
                         "entry is conv or full_attention")
    return {
        "d": d, "h": h, "kv": int(config["num_key_value_heads"]),
        "hd": int(config.get("head_dim") or d // h),
        "f": int(config["intermediate_size"]),
        "fe": int(config["moe_intermediate_size"]),
        "experts": int(config["num_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "route_scale": float(config["routed_scaling_factor"]),
        "taps": int(config["conv_L_cache"]),
        "types": types, "dense": int(config["num_dense_layers"]),
        "vocab": int(config["vocab_size"]), "layers": layers,
        "theta": float(config["rope_parameters"]["rope_theta"]),
        "eps": float(config["norm_eps"]),
    }


def layout(config: dict, blob_id: int) -> list:
    """``[(name, shape, fill)]`` of a blob's leaves in wire order: the
    operator under its norm, then the feed-forward under its own; which
    of each, by the blob's id.  Norm gains are exactly 1; every other
    leaf is seeded random, the selection bias too: a seeded fill (every
    value of magnitude 2^-7 .. 2^-5, either sign) against sigmoid scores
    of 0.5 +- 0.18 (logits of RMS 0.8 at the fabricated weights' scale)
    moves near-tied picks and leaves them to vary by token, so the leaf
    is live on the chip as well as delivered and read back.  (Where the
    scores are a softmax over hundreds of outputs the same fill would
    pick for every token alike: ``longcat_flash.py`` fills 0.)"""
    m = dims(config)
    d, hd = m["d"], m["hd"]
    if blob_id == m["layers"]:
        return [("embed", (m["vocab"], d), None),
                ("embedding_norm", (d,), 1.0)]
    out = [("operator_norm", (d,), 1.0)]
    if m["types"][blob_id] == "conv":
        out += [("in_proj", (d, 3 * d), None), ("conv", (d, m["taps"]), None),
                ("out_proj", (d, d), None)]
    else:
        out += [("q_proj", (d, m["h"] * hd), None),
                ("k_proj", (d, m["kv"] * hd), None),
                ("v_proj", (d, m["kv"] * hd), None),
                ("q_layernorm", (hd,), 1.0), ("k_layernorm", (hd,), 1.0),
                ("out_proj", (m["h"] * hd, d), None)]
    out.append(("ffn_norm", (d,), 1.0))
    if blob_id < m["dense"]:
        return out + [("w1", (d, m["f"]), None), ("w3", (d, m["f"]), None),
                      ("w2", (m["f"], d), None)]
    e, fe = m["experts"], m["fe"]
    return out + [("gate", (d, e), None),
                  ("expert_bias", (e,), None),
                  ("ew1", (e, d, fe), None), ("ew3", (e, d, fe), None),
                  ("ew2", (e, fe, d), None)]


# ------------------------------------------------------- the plain reference


def _rms_norm(jnp, x, gain, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * gain


def _rope(jnp, x, theta):
    """x: [batch, seq, heads, hd]; pair i is (x[i], x[i + hd/2])."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    angles = np.arange(x.shape[1], dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(angles))[None, :, None, :]
    sin = jnp.asarray(np.sin(angles))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _short_conv(jnp, m, p, x):
    """Position ``t`` sees ``v[t - (L-1)] .. v[t]`` under taps ``0 ..
    L-1``: the last tap is on the present."""
    d, taps, s = m["d"], m["taps"], x.shape[1]
    bcu = x @ p["in_proj"]
    b_gate, c_gate, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    v = b_gate * u
    padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
    c = 0.0
    for k in range(taps):
        c = c + p["conv"][:, k] * padded[:, k:k + s]
    return _gated(c_gate, c) @ p["out_proj"]


def _gated(c_gate, c):
    """``C * c``: a function of its own so that a control can leave the
    gate out (``tests/benchmark/test_bench_lfm2.py``); so is
    ``_head_norms``."""
    return c_gate * c


def _head_norms(jnp, m, p, q, k):
    return (_rms_norm(jnp, q, p["q_layernorm"], m["eps"]),
            _rms_norm(jnp, k, p["k_layernorm"], m["eps"]))


def _attention(jnp, jax, m, p, x):
    b, s, _ = x.shape
    nh, kv, hd = m["h"], m["kv"], m["hd"]
    q = (x @ p["q_proj"]).reshape(b, s, nh, hd)
    k = (x @ p["k_proj"]).reshape(b, s, kv, hd)
    v = (x @ p["v_proj"]).reshape(b, s, kv, hd)
    q, k = _head_norms(jnp, m, p, q, k)
    q, k = _rope(jnp, q, m["theta"]), _rope(jnp, k, m["theta"])
    k = jnp.repeat(k, nh // kv, axis=2)  # key head j serves heads j*g ..
    v = jnp.repeat(v, nh // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    scores = jnp.where(np.tril(np.ones((s, s), bool)), scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(b, s, nh * hd) @ p["out_proj"]


def _scores(jnp, jax, m, p, x):
    return jax.nn.sigmoid(x @ p["gate"])


def _route(jnp, jax, m, p, s):
    """``(pick, w)``: the ``top_k`` experts by score plus bias, and their
    weights from the scores alone."""
    pick = jnp.argsort(-(s + p["expert_bias"]), axis=-1)[..., :m["top_k"]]
    w = jnp.take_along_axis(s, pick, axis=-1)
    if m["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return pick, w * m["route_scale"]


def _moe(jnp, jax, m, p, x):
    """One expert at a time over every position, its output weighed by
    what the positions that picked it gave it (zero elsewhere); no
    dispatch tensor.  The loop is a ``lax.scan`` over the expert stacks:
    written out in Python, 64 experts at ``highest`` precision take the
    TPU's compiler 100 s for each kind of routed layer."""
    pick, w = _route(jnp, jax, m, p, _scores(jnp, jax, m, p, x))

    def one_expert(out, expert):
        e, w1, w3, w2 = expert
        y = (jax.nn.silu(x @ w1) * (x @ w3)) @ w2
        return out + jnp.where(pick == e, w, 0.0).sum(-1)[..., None] * y, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (jnp.arange(m["experts"]), p["ew1"], p["ew3"], p["ew2"]))
    return out


def _after_operator(jnp, jax, m, p, h):
    """The stream after the operator, and its normed form into the
    feed-forward."""
    x = _rms_norm(jnp, h, p["operator_norm"], m["eps"])
    h = h + (_short_conv(jnp, m, p, x) if "in_proj" in p
             else _attention(jnp, jax, m, p, x))
    return h, _rms_norm(jnp, h, p["ffn_norm"], m["eps"])


def picks(jnp, jax, dims, p, h):
    """The experts each position of ``h`` picks in this layer, ``[batch,
    seq, top_k]`` (None for a layer with the dense feed-forward): what
    the program's slot counters count."""
    if "gate" not in p:
        return None
    _, n = _after_operator(jnp, jax, dims, p, h)
    return _route(jnp, jax, dims, p, _scores(jnp, jax, dims, p, n))[0]


def ref_layer(jnp, jax, dims, p, h):
    m = dims
    h, n = _after_operator(jnp, jax, m, p, h)
    if "gate" in p:
        return h + _moe(jnp, jax, m, p, n)
    return h + (jax.nn.silu(n @ p["w1"]) * (n @ p["w3"])) @ p["w2"]


def ref_in(jnp, dims, head, tokens):
    return head["embed"][tokens]


def ref_out(jnp, dims, head, h):
    return _rms_norm(jnp, h, head["embedding_norm"],
                     dims["eps"]) @ head["embed"].T


# ------------------------------------------------------- the program's side


def register(config: dict, name: str):
    """``models.lfm2.CONFIGS[name] = Lfm2Config(...)`` in this process;
    the forward is the program's one jitted forward on the boot's
    parameters.  A program without the family fails here, at import."""
    import importlib

    lfm2 = importlib.import_module(PKG + ".models.lfm2")
    forward_jit = importlib.import_module(PKG + ".models.llama").forward_jit
    global _REGISTERED
    _REGISTERED = m = dims(config)
    if (config.get("conv_bias") or not config.get("use_expert_bias", True)
            or not m["norm_topk"]):
        raise SystemExit("models/lfm2.py has no convolution bias, always a "
                         "selection bias and always renormalises the picks' "
                         "weights; this config differs")
    lfm2.CONFIGS[name] = cfg = lfm2.Lfm2Config(
        name=name, vocab=m["vocab"], d_model=m["d"],
        layer_types=tuple(m["types"]), n_dense=m["dense"],
        conv_kernel=m["taps"], n_heads=m["h"], n_kv_heads=m["kv"],
        head_dim=m["hd"], d_ff=m["f"], d_expert=m["fe"],
        n_experts=m["experts"], top_k=m["top_k"],
        route_scale=m["route_scale"], rope_theta=m["theta"],
        norm_eps=m["eps"])
    return lambda boot, tokens: forward_jit(boot.params, tokens, cfg)


def _kind(m: dict, blob_id: int) -> str:
    """The program's name for layer ``blob_id``'s kind."""
    return (("conv" if m["types"][blob_id] == "conv" else "attn")
            + ("_dense" if blob_id < m["dense"] else "_moe"))


def leaf(boot, blob_id: int, name: str):
    """A boot of this family holds its layers' leaves stacked BY KIND of
    layer — ``params["layers"][kind][name]``, over the layers of that
    kind it holds in the order of their ids (a stage boot: its own
    layers' stacks alone) — beside the head's leaves.  The kinds are
    those of the configuration this process registered."""
    ids = list(boot.layer_ids)
    if blob_id not in ids:
        return boot.params[name]
    stacks = boot.params["layers"] if boot.kind == "full" else boot.params
    kind = _kind(_REGISTERED, blob_id)
    at = sum(1 for b in ids if b < blob_id and _kind(_REGISTERED, b) == kind)
    return stacks[kind][name][at]
