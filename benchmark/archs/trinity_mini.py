"""The Trinity-Mini architecture (arcee-ai/Trinity-Mini, ``model_type:
afmoe``, 26B-A3B): what the harness asks of an architecture
(``benchmark/archs/__init__.py``), for a configuration file in Hugging
Face's keys and for ``models/trinity.py``.

The plain reference is straightforward ``jax.numpy``: no cache, no ring,
no kernels, no scan over layers, one expert at a time and no dispatch
tensor.  It shares no code with ``models/trinity.py`` or
``models/routed.py`` and follows the published block (``d`` =
hidden_size; every projection without bias, stored ``[in, out]``;
``RMS(x) = x * rsqrt(mean(x^2) + rms_norm_eps)``):

    h   = embed[tokens] * sqrt(d)                       (mup_enabled)
    h  += RMS(attention(RMS(h)*g_in))*g_post_attn
    h  += RMS(feed_forward(RMS(h)*g_pre_ffn))*g_post_ffn
    out = lm_head . (RMS(h)*g_f)            embedding and head untied

    attention(x):
            q, k, v = q_proj . x, k_proj . x, v_proj . x -> heads of head_dim
            q, k = RMS(q)*g_q, RMS(k)*g_k   per head
            rotary (rotate-half, theta ** (-2i / head_dim), no scaling) on q
            and k in a ``sliding_attention`` layer ONLY; a ``full_attention``
            layer has no positional encoding
            position i attends j with i - sliding_window < j <= i (sliding)
            or every j <= i (full); softmax(q . k / sqrt(head_dim)) . v,
            num_attention_heads / num_key_value_heads query heads a key head
            o_proj . (attention * sigmoid(gate_proj . x))
    dense:  w2 . (silu(w1 . x) * (w3 . x))          layers < num_dense_layers
    moe:    s = sigmoid(x . gate)                   over num_experts outputs
            pick = top-k of (s + expert_bias)       n_group 1, topk_group 1:
                                                    no group limit
            w = s[pick] / (sum of s[pick] + 1e-20)  (route_norm)
                * route_scale
            sum over pick of w_e * expert_e(x)  +  shared(x)

Which attention a layer has is ``layer_types``; the module tells it from
the NAME of the layer's input norm in its blob (``window_norm`` or
``attn_norm``): the two kinds' leaves are otherwise alike to the shape,
and ``ref_layer`` is handed the leaves alone.

Computed in blocks, so that three sequences of 4103 positions fit beside
nothing else: attention a batch row (``lax.map``) and ``REF_BLOCK``
queries at a time, inside a block the plain ``softmax(q k^T / sqrt(hd) +
mask) v`` over the keys up to the block's end (a sliding layer: from the
first key its first query may see); the experts in a ``lax.scan`` (an
unrolled loop costs the TPU's compiler minutes).  That is a departure in
the order of the sums only.

Assumed, because the catalog's config does not say (each also under
``assumed`` in the configuration's file): the four norms a layer, the
per-head q/k norms before the rotary, the sigmoid output gate of width
heads x head_dim before ``o_proj``, no rotary in full layers, sqrt(d) as
the ``mup`` multiplier, the 1e-20, the router in float32 (everything
here is), ``expert_bias`` a delivered leaf filled like every matrix, norm
gains 1.

Departures from the published model, all of them the configuration's cut
(``reduced`` / ``deployment`` in its file; ``model-configs`` guide,
section 4): one rank's share of eight.  ``num_experts`` in the file
counts the experts HELD HERE (ids ``expert_first ..``; the published
count stands beside it under ``reduced``), ``vocab_size`` the rows of the
vocabulary held here (rows 0 ..), ``num_hidden_layers`` the leading
layers held here (``layer_types`` stays the published list and is read
up to that depth).  The router keeps its published width and its
renormalisation over all eight picks; a slot that picked an absent
expert adds nothing; the shared expert and the attention with every head
are on every rank.  Nothing stands in for the absent ranks.
"""

from __future__ import annotations

import numpy as np

PKG = "distributed_llm_dissemination_tpu"
REF_BLOCK = 512  # queries a block of the reference's attention
# ``dims`` of the configuration this process registered (``register``):
# ``leaf`` is handed a boot result and no configuration.
_REGISTERED = None


# ---------------------------------------------------------- sizes and layout


def dims(config: dict) -> dict:
    """The sizes, from a configuration file in the source's own keys."""
    reduced = config.get("reduced", {})
    held = int(config["num_experts"])
    layers = int(config["num_hidden_layers"])
    types = list(config["layer_types"])[:layers]
    if len(types) != layers:
        raise ValueError(f"layer_types has {len(types)} entries for "
                         f"{layers} layers")
    return {
        "d": int(config["hidden_size"]),
        "h": int(config["num_attention_heads"]),
        "kv": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]),
        "f": int(config["intermediate_size"]),
        "fe": int(config["moe_intermediate_size"]),
        "fs": int(config["moe_intermediate_size"])
        * int(config["num_shared_experts"]),
        "held": held, "first": int(config.get("expert_first", 0)),
        "routed": int(reduced.get("num_experts", {}).get("published", held)),
        "top_k": int(config["num_experts_per_tok"]),
        "route_norm": bool(config["route_norm"]),
        "route_scale": float(config["route_scale"]),
        "dense": int(config["num_dense_layers"]),
        "layers": layers, "types": types,
        "window": int(config["sliding_window"]),
        "vocab": int(config["vocab_size"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
    }


def _kind(m: dict, blob_id: int) -> str:
    """The program's name for layer blob ``blob_id``'s kind."""
    return (("dense" if blob_id < m["dense"] else "routed")
            + ("_sliding" if m["types"][blob_id] == "sliding_attention"
               else "_full"))


def layout(config: dict, blob_id: int) -> list:
    """``[(name, shape, fill)]`` of a blob's leaves in wire order: the
    attention between its two norms, then the feed-forward between its
    own.  Norm gains are exactly 1; every other leaf is seeded random,
    the selection bias too (every value of magnitude 2^-7 .. 2^-5, either
    sign, against sigmoid scores of 0.5 +- 0.18: it moves near-tied picks
    and leaves them to vary by token, as ``joyai_llm_flash.py``'s)."""
    m = dims(config)
    d, q, kv = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"]
    if blob_id == m["layers"]:
        return [("embed", (m["vocab"], d), None), ("ln_f", (d,), 1.0),
                ("lm_head", (d, m["vocab"]), None)]
    ffn, attn = _kind(m, blob_id).split("_")
    out = [("window_norm" if attn == "sliding" else "attn_norm", (d,), 1.0),
           ("q_proj", (d, q), None), ("k_proj", (d, kv), None),
           ("v_proj", (d, kv), None), ("o_proj", (q, d), None),
           ("gate_proj", (d, q), None),
           ("q_norm", (m["hd"],), 1.0), ("k_norm", (m["hd"],), 1.0),
           ("post_attn_norm", (d,), 1.0), ("pre_ffn_norm", (d,), 1.0)]
    if ffn == "dense":
        out += [("w1", (d, m["f"]), None), ("w3", (d, m["f"]), None),
                ("w2", (m["f"], d), None)]
    else:
        e, fe, fs = m["held"], m["fe"], m["fs"]
        out += [("gate", (d, m["routed"]), None),
                ("expert_bias", (m["routed"],), None),
                ("sw1", (d, fs), None), ("sw3", (d, fs), None),
                ("sw2", (fs, d), None),
                ("ew1", (e, d, fe), None), ("ew3", (e, d, fe), None),
                ("ew2", (e, fe, d), None)]
    return out + [("post_ffn_norm", (d,), 1.0)]


# ------------------------------------------------------- the plain reference


def _norm(jnp, m, p, name, x):
    """``RMS(x) * p[name]``.  By name, so that a control can leave one of
    a layer's norms out (``tests/test_trinity.py``); so are ``_gated``
    and ``_mup`` functions of their own."""
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + m["eps"])) * p[name]


def _rope(jnp, x, theta):
    """x: [batch, seq, heads, hd]; rotate-half: pair i is (x[i], x[i +
    hd/2]) at theta ** (-2i / hd)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    angles = np.arange(x.shape[1], dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(angles))[None, :, None, :]
    sin = jnp.asarray(np.sin(angles))[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _softmax_rows(jnp, jax, m, q, k, v, start, lo, window):
    """Queries at positions ``start ..`` (``[n, h, hd]``) against keys at
    positions ``lo ..`` (``[t, kv, hd]``): the plain softmax under the
    causal mask and the band."""
    g = m["h"] // m["kv"]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    rows = start + np.arange(q.shape[0])[:, None]
    cols = lo + np.arange(k.shape[0])[None, :]
    seen = cols <= rows
    if window is not None:
        seen &= cols > rows - window
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(m["hd"])
    scores = jnp.where(seen[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)


def _gated(jnp, jax, p, x, attn):
    return attn * jax.nn.sigmoid(x @ p["gate_proj"])


def _attention(jnp, jax, m, p, x, window):
    b, s, _ = x.shape
    q = _norm(jnp, m, p, "q_norm",
              (x @ p["q_proj"]).reshape(b, s, m["h"], m["hd"]))
    k = _norm(jnp, m, p, "k_norm",
              (x @ p["k_proj"]).reshape(b, s, m["kv"], m["hd"]))
    v = (x @ p["v_proj"]).reshape(b, s, m["kv"], m["hd"])
    if window is not None:  # no positional encoding in a full layer
        q, k = _rope(jnp, q, m["theta"]), _rope(jnp, k, m["theta"])

    def one_row(row):
        q, k, v = row
        out = []
        for start in range(0, s, REF_BLOCK):
            stop = min(s, start + REF_BLOCK)
            lo = 0 if window is None else max(0, start - window + 1)
            out.append(_softmax_rows(jnp, jax, m, q[start:stop], k[lo:stop],
                                     v[lo:stop], start, lo, window))
        return jnp.concatenate(out)

    attn = jax.lax.map(one_row, (q, k, v))
    return _gated(jnp, jax, p, x,
                  attn.reshape(b, s, m["h"] * m["hd"])) @ p["o_proj"]


def _swiglu(jax, x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _route(jnp, jax, m, p, x):
    """``(pick, w)``: the ``top_k`` router outputs by score plus bias, and
    their weights from the scores alone, renormalised over ALL the
    picks."""
    s = jax.nn.sigmoid(x @ p["gate"])
    pick = jnp.argsort(-(s + p["expert_bias"]), axis=-1)[..., :m["top_k"]]
    w = jnp.take_along_axis(s, pick, axis=-1)
    if m["route_norm"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return pick, w * m["route_scale"]


def _routed(jnp, jax, m, p, x):
    """The held experts' part: one expert at a time over every position
    (a ``lax.scan`` over the expert stacks), its output weighed by what
    the positions that picked it gave it; a pick of an expert that is not
    here adds nothing."""
    pick, w = _route(jnp, jax, m, p, x)

    def one_expert(out, expert):
        e, w1, w3, w2 = expert
        y = _swiglu(jax, x, w1, w3, w2)
        return out + jnp.where(pick == e, w, 0.0).sum(-1)[..., None] * y, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (m["first"] + jnp.arange(m["held"]), p["ew1"], p["ew3"], p["ew2"]))
    return out


def _shared(jnp, jax, m, p, x):
    """The always-on expert: every rank computes it alike."""
    return _swiglu(jax, x, p["sw1"], p["sw3"], p["sw2"])


def _after_attention(jnp, jax, m, p, h):
    """The stream after the attention, and its normed form into the
    feed-forward."""
    sliding = "window_norm" in p
    x = _norm(jnp, m, p, "window_norm" if sliding else "attn_norm", h)
    h = h + _norm(jnp, m, p, "post_attn_norm", _attention(
        jnp, jax, m, p, x, m["window"] if sliding else None))
    return h, _norm(jnp, m, p, "pre_ffn_norm", h)


def picks(jnp, jax, dims, p, h):
    """The router outputs each position of ``h`` picks in this block,
    ``[batch, seq, top_k]`` (None for a dense layer): what the program's
    slot counters count."""
    if "gate" not in p:
        return None
    _, n = _after_attention(jnp, jax, dims, p, h)
    return _route(jnp, jax, dims, p, n)[0]


def ref_layer(jnp, jax, dims, p, h):
    m = dims
    h, n = _after_attention(jnp, jax, m, p, h)
    if "gate" in p:
        y = _routed(jnp, jax, m, p, n) + _shared(jnp, jax, m, p, n)
    else:
        y = _swiglu(jax, n, p["w1"], p["w3"], p["w2"])
    return h + _norm(jnp, m, p, "post_ffn_norm", y)


def _mup(dims):
    return np.sqrt(dims["d"])


def ref_in(jnp, dims, head, tokens):
    return head["embed"][tokens] * _mup(dims)


def ref_out(jnp, dims, head, h):
    return _norm(jnp, dims, head, "ln_f", h) @ head["lm_head"]


# ------------------------------------------------------- the program's side


def register(config: dict, name: str):
    """``models.trinity.CONFIGS[name] = TrinityConfig(...)`` in this
    process; the forward is the program's one jitted forward on the
    boot's parameters.  A program without the family fails here, at
    import."""
    import importlib

    trinity = importlib.import_module(PKG + ".models.trinity")
    forward_jit = importlib.import_module(PKG + ".models.llama").forward_jit
    global _REGISTERED
    _REGISTERED = m = dims(config)
    if (config.get("score_func", "sigmoid") != "sigmoid"
            or (config.get("n_group", 1), config.get("topk_group", 1))
            != (1, 1) or not m["route_norm"] or config.get("rope_scaling")
            or config.get("tie_word_embeddings")
            or not config.get("mup_enabled", True)
            or config.get("hidden_act", "silu") != "silu"):
        raise SystemExit(
            "models/trinity.py scores by sigmoid with a selection bias and "
            "no group limit, renormalises the picks' weights, rotates "
            "without scaling, multiplies the embedding by sqrt(d) and has "
            "an untied head; this config differs")
    trinity.CONFIGS[name] = cfg = trinity.TrinityConfig(
        name=name, vocab=m["vocab"], d_model=m["d"],
        layer_types=tuple(m["types"]), n_dense=m["dense"],
        window=m["window"], n_heads=m["h"], n_kv_heads=m["kv"],
        head_dim=m["hd"], d_ff=m["f"], d_expert=m["fe"], d_shared=m["fs"],
        n_experts=m["routed"], experts_held=m["held"],
        expert_first=m["first"], top_k=m["top_k"],
        route_scale=m["route_scale"], rope_theta=m["theta"],
        norm_eps=m["eps"])
    return lambda boot, tokens: forward_jit(boot.params, tokens, cfg)


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
