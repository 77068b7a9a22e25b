"""The JoyAI-LLM-Flash architecture (jdopensource/JoyAI-LLM-Flash,
``model_type: joyai_llm_flash``, 48B-A2.7B): what the harness asks of an
architecture (``benchmark/archs/__init__.py``), for a configuration file
in Hugging Face's keys and for ``models/joyai.py``.

The plain reference is straightforward ``jax.numpy``: no cache, no
kernels, no scan over layers, one expert at a time and no dispatch
tensor.  It shares no code with ``models/joyai.py`` or ``models/mla.py``
and follows the published block (``d`` = hidden_size; every projection
without bias, stored ``[in, out]``; ``RMS(x) = x * rsqrt(mean(x^2) +
rms_norm_eps)``):

    h   = embed[tokens]
    h  += MLA(RMS(h)*g_attn)                h += feed_forward(RMS(h)*g_ffn)
    out = lm_head . (RMS(h)*g_f)            embedding and head untied

    MLA(x): cq = RMS(wq_a . x)*g_q;  q = wq_b . cq -> heads of nope + rope
            [ckv | kr] = wkv_a . x;  ckv = RMS(ckv)*g_kv
            [k_nope | v] = wkv_b . ckv, per head;  kr is ONE rotary head
            rotary on q's rope part and on kr, over INTERLEAVED pairs
            (x[2i], x[2i+1]) at theta ** (-2i / rope)  (rope_interleave)
            out = wo . softmax(q . [k_nope | kr] / sqrt(nope + rope), causal) . v
    dense:  w2 . (silu(w1 . x) * (w3 . x))          layers < first_k_dense_replace
    moe:    s = sigmoid(x . gate)                   over n_routed_experts outputs
            pick = top-k of (s + e_score_correction_bias)    n_group 1, topk_group 1:
                                                             no group limit
            w = s[pick] / (sum of s[pick] + 1e-20)  (norm_topk_prob)
                * routed_scaling_factor
            sum over pick of w_e * expert_e(x)  +  shared(x)   n_shared_experts
    MTP module (``num_nextn_predict_layers`` 1; the checkpoint's layer
    ``num_hidden_layers``), for position i with the stack's output h_i and
    the next token t_{i+1}:
            u_i = eh_proj . [RMS(embed[t_{i+1}])*g_e ; RMS(RMS(h_i)*g_f)*g_h]
            v_i = block(u_i)                        one routed layer
            logits for token i+2 = lm_head . (RMS(v_i)*g_s)

The module's blob is a layer blob (id ``layers - 1``; the head blob stays
one past the layers).  ``ref_layer`` hands the hidden state back
unchanged for it — it is no part of the main path — and ``ref_mtp`` gives
the module's logits by the equations above.

Assumed, because the catalog's config does not say (each also under
``assumed`` in the configuration's file):
- the module's embedding and output head are the main model's, not
  copies in its blob (the checkpoint's duplicates are not delivered);
- the module is fed the main model's output AFTER its final norm, and the
  embedding half comes first in the concatenation;
- the router and the selection bias in float32 (everything here is), the
  bias a delivered leaf filled like every matrix, norm gains 1, no
  masking of position 0 in the module.

Departures from the published model, all of them the configuration's cut
(``reduced`` / ``deployment`` in its file; ``model-configs`` guide,
section 4): one rank's share of four.  ``n_routed_experts`` in the file
counts the experts HELD HERE (ids ``expert_first ..``; the published
count stands beside it under ``reduced``) and ``num_hidden_layers`` the
leading layers of the stack held here.  The router keeps its published
width and its renormalisation over all eight picks, so a token's picks
and weights are the deployment's; a slot that picked an absent expert
adds nothing; the shared expert, the attention with every head and the
whole vocabulary are on every rank.  Nothing stands in for the absent
ranks.
"""

from __future__ import annotations

import numpy as np

PKG = "distributed_llm_dissemination_tpu"
# ``dims`` of the configuration this process registered (``register``),
# and the program's configuration object: ``leaf`` and ``program_mtp``
# are handed a boot result and no configuration.
_REGISTERED = None
_PROGRAM = None
_PROGRAM_MTP = None  # (that object, its jitted module forward)


# ---------------------------------------------------------- sizes and layout


def dims(config: dict) -> dict:
    """The sizes, from a configuration file in the source's own keys.
    ``layers`` counts the layer BLOBS: the stack's and the module's."""
    reduced = config.get("reduced", {})
    held = int(config["n_routed_experts"])
    main = int(config["num_hidden_layers"])
    mtp = int(config.get("num_nextn_predict_layers", 0))
    return {
        "d": int(config["hidden_size"]),
        "h": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v": int(config["v_head_dim"]),
        "f": int(config["intermediate_size"]),
        "fe": int(config["moe_intermediate_size"]),
        "fs": int(config["moe_intermediate_size"])
        * int(config["n_shared_experts"]),
        "held": held, "first": int(config.get("expert_first", 0)),
        "routed": int(reduced.get("n_routed_experts", {}).get(
            "published", held)),
        "top_k": int(config["num_experts_per_tok"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "route_scale": float(config["routed_scaling_factor"]),
        "dense": int(config["first_k_dense_replace"]),
        "main": main, "mtp": mtp, "layers": main + mtp,
        "vocab": int(config["vocab_size"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
    }


def _kind(m: dict, blob_id: int) -> str:
    """The program's name for layer blob ``blob_id``'s kind."""
    if blob_id >= m["main"]:
        return "mtp"
    return "dense" if blob_id < m["dense"] else "moe"


def layout(config: dict, blob_id: int) -> list:
    """``[(name, shape, fill)]`` of a blob's leaves in wire order: the
    attention under its norm, then the feed-forward under its own; the
    module's blob is a routed layer between its input side and its
    head's norm.  Norm gains are exactly 1; every other leaf is seeded
    random, the selection bias too (every value of magnitude 2^-7 ..
    2^-5, either sign, against sigmoid scores of 0.5 +- 0.18: it moves
    near-tied picks and leaves them to vary by token, as
    ``lfm2_moe.py``'s)."""
    m = dims(config)
    d, h = m["d"], m["h"]
    if blob_id == m["layers"]:
        return [("embed", (m["vocab"], d), None), ("ln_f", (d,), 1.0),
                ("lm_head", (d, m["vocab"]), None)]
    out = [("attn_norm", (d,), 1.0),
           ("wq_a", (d, m["q_rank"]), None),
           ("q_norm", (m["q_rank"],), 1.0),
           ("wq_b", (m["q_rank"], h * (m["nope"] + m["rope"])), None),
           ("wkv_a", (d, m["kv_rank"] + m["rope"]), None),
           ("kv_norm", (m["kv_rank"],), 1.0),
           ("wkv_b", (m["kv_rank"], h * (m["nope"] + m["v"])), None),
           ("wo", (h * m["v"], d), None),
           ("ffn_norm", (d,), 1.0)]
    kind = _kind(m, blob_id)
    if kind == "dense":
        return out + [("w1", (d, m["f"]), None), ("w3", (d, m["f"]), None),
                      ("w2", (m["f"], d), None)]
    e, fe, fs = m["held"], m["fe"], m["fs"]
    out += [("gate", (d, m["routed"]), None),
            ("gate_bias", (m["routed"],), None),
            ("sw1", (d, fs), None), ("sw3", (d, fs), None),
            ("sw2", (fs, d), None),
            ("ew1", (e, d, fe), None), ("ew3", (e, d, fe), None),
            ("ew2", (e, fe, d), None)]
    if kind == "moe":
        return out
    return ([("enorm", (d,), 1.0), ("hnorm", (d,), 1.0),
             ("eh_proj", (2 * d, d), None)]
            + out + [("head_norm", (d,), 1.0)])


# ------------------------------------------------------- the plain reference


def _rms_norm(jnp, x, gain, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * gain


def _rope(jnp, x, theta):
    """x: [batch, seq, heads, rd]; pair i is (x[2i], x[2i+1]), rotated in
    place (the pairs stay interleaved)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    angles = np.arange(x.shape[1], dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(angles))[None, :, None, :]
    sin = jnp.asarray(np.sin(angles))[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _mla(jnp, jax, m, p, x):
    b, s, _ = x.shape
    nh, nope, rope, v = m["h"], m["nope"], m["rope"], m["v"]
    cq = _rms_norm(jnp, x @ p["wq_a"], p["q_norm"], m["eps"])
    q = (cq @ p["wq_b"]).reshape(b, s, nh, nope + rope)
    kv = x @ p["wkv_a"]
    ckv = _rms_norm(jnp, kv[..., :m["kv_rank"]], p["kv_norm"], m["eps"])
    up = (ckv @ p["wkv_b"]).reshape(b, s, nh, nope + v)
    kr = _rope(jnp, kv[:, :, None, m["kv_rank"]:], m["theta"])
    q = jnp.concatenate([q[..., :nope],
                         _rope(jnp, q[..., nope:], m["theta"])], -1)
    k = jnp.concatenate([up[..., :nope],
                         jnp.broadcast_to(kr, (b, s, nh, rope))], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(nope + rope)
    scores = jnp.where(np.tril(np.ones((s, s), bool)), scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                      up[..., nope:])
    return attn.reshape(b, s, nh * v) @ p["wo"]


def _swiglu(jax, x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _scores(jnp, jax, m, p, x):
    return jax.nn.sigmoid(x @ p["gate"])


def _route(jnp, jax, m, p, s):
    """``(pick, w)``: the ``top_k`` router outputs by score plus bias, and
    their weights from the scores alone, renormalised over ALL the
    picks."""
    pick = jnp.argsort(-(s + p["gate_bias"]), axis=-1)[..., :m["top_k"]]
    w = jnp.take_along_axis(s, pick, axis=-1)
    if m["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return pick, w * m["route_scale"]


def _routed(jnp, jax, m, p, x):
    """The held experts' part: one expert at a time over every position
    (a ``lax.scan`` over the expert stacks: written out in Python, 64
    experts at ``highest`` precision take the TPU's compiler minutes),
    its output weighed by what the positions that picked it gave it; a
    pick of an expert that is not here adds nothing."""
    pick, w = _route(jnp, jax, m, p, _scores(jnp, jax, m, p, x))

    def one_expert(out, expert):
        e, w1, w3, w2 = expert
        y = _swiglu(jax, x, w1, w3, w2)
        return out + jnp.where(pick == e, w, 0.0).sum(-1)[..., None] * y, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (m["first"] + jnp.arange(m["held"]), p["ew1"], p["ew3"], p["ew2"]))
    return out


def _shared(jnp, jax, m, p, x):
    """The always-on expert: every rank computes it alike.  A function of
    its own so that a control can leave it out
    (``tests/benchmark/test_bench_joyai.py``); so are ``_hnorm`` and
    ``_halves``."""
    return _swiglu(jax, x, p["sw1"], p["sw3"], p["sw2"])


def _after_attention(jnp, jax, m, p, h):
    """The stream after the attention, and its normed form into the
    feed-forward."""
    h = h + _mla(jnp, jax, m, p,
                 _rms_norm(jnp, h, p["attn_norm"], m["eps"]))
    return h, _rms_norm(jnp, h, p["ffn_norm"], m["eps"])


def _block(jnp, jax, m, p, h):
    h, n = _after_attention(jnp, jax, m, p, h)
    if "gate" in p:
        return h + _routed(jnp, jax, m, p, n) + _shared(jnp, jax, m, p, n)
    return h + _swiglu(jax, n, p["w1"], p["w3"], p["w2"])


def picks(jnp, jax, dims, p, h):
    """The router outputs each position of ``h`` picks in this block,
    ``[batch, seq, top_k]`` (None for a dense layer): what the program's
    slot counters count."""
    if "gate" not in p:
        return None
    _, n = _after_attention(jnp, jax, dims, p, h)
    return _route(jnp, jax, dims, p, _scores(jnp, jax, dims, p, n))[0]


def ref_layer(jnp, jax, dims, p, h):
    """One block of the stack; the module's blob is none of it."""
    if "eh_proj" in p:
        return h
    return _block(jnp, jax, dims, p, h)


def ref_in(jnp, dims, head, tokens):
    return head["embed"][tokens]


def ref_out(jnp, dims, head, h):
    return _rms_norm(jnp, h, head["ln_f"], dims["eps"]) @ head["lm_head"]


def _hnorm(jnp, m, p, hn):
    return _rms_norm(jnp, hn, p["hnorm"], m["eps"])


def _halves(jnp, e, hn):
    return jnp.concatenate([e, hn], axis=-1)


def mtp_input(jnp, dims, head, p, h, tokens):
    """``u [batch, seq - 1, d]``: the module's block's input at positions
    ``0 .. seq - 2``, from the stack's last hidden state ``h [batch, seq,
    d]`` (before the final norm) and ``tokens [batch, seq]``."""
    m = dims
    e = _rms_norm(jnp, head["embed"][tokens[:, 1:]], p["enorm"], m["eps"])
    hn = _hnorm(jnp, m, p,
                _rms_norm(jnp, h[:, :-1], head["ln_f"], m["eps"]))
    return _halves(jnp, e, hn) @ p["eh_proj"]


def ref_mtp(jnp, jax, dims, head, p, h, tokens):
    """The module's logits ``[batch, seq - 1, vocab]``: position ``i``'s
    (from ``h[:, i]`` and ``tokens[:, i + 1]``) are for token ``i + 2``.
    ``head`` and ``p`` are the head blob's and the module's blob's leaves;
    embedding and output head are the main model's."""
    v = _block(jnp, jax, dims, p,
               mtp_input(jnp, dims, head, p, h, tokens))
    return _rms_norm(jnp, v, p["head_norm"], dims["eps"]) @ head["lm_head"]


# ------------------------------------------------------- the program's side


def register(config: dict, name: str):
    """``models.joyai.CONFIGS[name] = JoyaiConfig(...)`` in this process;
    the forward is the program's one jitted forward on the boot's
    parameters.  A program without the family fails here, at import."""
    import importlib

    joyai = importlib.import_module(PKG + ".models.joyai")
    forward_jit = importlib.import_module(PKG + ".models.llama").forward_jit
    global _REGISTERED, _PROGRAM
    _REGISTERED = m = dims(config)
    if (config.get("scoring_func", "sigmoid") != "sigmoid"
            or config.get("topk_method", "noaux_tc") != "noaux_tc"
            or (config.get("n_group", 1), config.get("topk_group", 1))
            != (1, 1) or not m["norm_topk"] or config.get("rope_scaling")
            or not config.get("rope_interleave", True)
            or config.get("tie_word_embeddings")
            or config.get("attention_bias")
            or config.get("moe_layer_freq", 1) != 1 or m["mtp"] > 1):
        raise SystemExit(
            "models/joyai.py scores by sigmoid with a selection bias and no "
            "group limit, renormalises the picks' weights, rotates "
            "interleaved pairs without scaling, has no attention bias, an "
            "untied head, a routed block in every layer after the dense "
            "ones and at most one prediction module; this config differs")
    joyai.CONFIGS[name] = _PROGRAM = cfg = joyai.JoyaiConfig(
        name=name, vocab=m["vocab"], d_model=m["d"], n_main=m["main"],
        n_mtp=m["mtp"], n_dense=m["dense"], n_heads=m["h"],
        q_rank=m["q_rank"], kv_rank=m["kv_rank"], nope_dim=m["nope"],
        rope_dim=m["rope"], v_dim=m["v"], d_ff=m["f"], d_expert=m["fe"],
        d_shared=m["fs"], n_experts=m["routed"], experts_held=m["held"],
        expert_first=m["first"], top_k=m["top_k"],
        route_scale=m["route_scale"], rope_theta=m["theta"],
        norm_eps=m["eps"])
    return lambda boot, tokens: forward_jit(boot.params, tokens, cfg)


def program_mtp(boot, tokens):
    """The program's own module logits on a boot result's delivered
    parameters (``models.joyai.mtp_forward``, jitted once for the
    configuration this process registered): ``[batch, seq - 1, vocab]``,
    what ``ref_mtp`` is held against."""
    global _PROGRAM_MTP
    if _PROGRAM_MTP is None or _PROGRAM_MTP[0] is not _PROGRAM:
        import importlib

        import jax

        joyai = importlib.import_module(PKG + ".models.joyai")
        cfg = _PROGRAM
        _PROGRAM_MTP = (cfg, jax.jit(
            lambda params, toks: joyai.mtp_forward(params, toks, cfg)))
    return _PROGRAM_MTP[1](boot.params, tokens)


def leaf(boot, blob_id: int, name: str):
    """A boot of this family holds its layers' leaves stacked BY KIND of
    layer — ``params["layers"][kind][name]``, over the layers of that
    kind it holds in the order of their ids (a stage boot: its own
    layers' stacks alone), the module's stack of one among them — beside
    the head's leaves.  The kinds are those of the configuration this
    process registered."""
    ids = list(boot.layer_ids)
    if blob_id not in ids:
        return boot.params[name]
    stacks = boot.params["layers"] if boot.kind == "full" else boot.params
    kind = _kind(_REGISTERED, blob_id)
    at = sum(1 for b in ids if b < blob_id and _kind(_REGISTERED, b) == kind)
    return stacks[kind][name][at]
