"""The LongCat-Flash architecture (the language model of
LongCat-Flash-Omni): what the harness asks of an architecture
(``benchmark/archs/__init__.py``), for a configuration file in the
source's keys (``config.json`` of meituan-longcat/LongCat-Flash-Omni) and
for ``models/longcat.py``.

The plain reference is straightforward ``jax.numpy``: no cache, no
kernels, no scan, experts and routing slots in Python loops.  It shares
no code with ``models/longcat.py`` and follows the published description
(``d`` = hidden_size; every projection without bias, stored ``[in,
out]``).  One published layer is a double block with the expert block on
a shortcut:

    a0 = h  + MLA_0(RMS(h)*g)                      n0 = RMS(a0)*g
    m  = MoE(n0)                                   # lands at the end
    b0 = a0 + W2_0 . (silu(W1_0 . n0) * (W3_0 . n0))
    a1 = b0 + MLA_1(RMS(b0)*g)                     n1 = RMS(a1)*g
    h' = a1 + W2_1 . (silu(W1_1 . n1) * (W3_1 . n1)) + m

    MLA(x): cq = RMS(Wqa . x)*g;  q = (Wqb . cq) * sqrt(d / q_lora_rank)
            [ckv | kr] = Wkva . x;  ckv = RMS(ckv)*g * sqrt(d / kv_lora_rank)
            [k_nope | v] = Wkvb . ckv, per head;  kr is ONE rotary head
            rotary on q's rope part and on kr;  k = [k_nope | kr]
            out = Wo . softmax(q . k / sqrt(nope + rope), causal) . v
    MoE(x): s = softmax(x . Wr) over n_routed + zero_expert_num outputs
            pick = top-k of (s + bias);  w = routed_scaling_factor * s[pick]
            m = sum over pick of w_e * (e < n_routed ? expert_e(x) : x)

Assumed, because the published config does not say (each also under
``assumed`` in the configuration's file):
- rotary over INTERLEAVED pairs ``(x[2i], x[2i+1])`` at ``theta ** (-2i /
  rope_dim)``, no rotary scaling (no ``rope_scaling`` key);
- the top-k weights are NOT renormalised (no ``norm_topk_prob`` key): they
  are the softmax scores themselves, without the bias, times the factor;
- ``mla_scale_q_lora`` multiplies the queries after ``Wqb`` and
  ``mla_scale_kv_lora`` the latent after its norm, as written above;
- the router runs in float32 (everything here does).

Departures from the published model, all of them the configuration's cut
(``reduced`` / ``deployment`` in its file; ``model-configs`` guide,
section 4): this chip's share of a wider deployment.  ``num_attention_
heads``, ``n_routed_experts`` and ``vocab_size`` in the file count what
is HELD HERE (the published counts stand beside them under ``reduced``):
``wq_b`` / ``wkv_b`` / ``wo`` carry the held heads' columns and rows, the
expert stacks the held experts (ids ``expert_first ..``), embedding and
head the held rows of the vocabulary.  The router keeps its published
width, so a token's picks are the deployment's; a slot that picked an
absent expert adds nothing, an identity expert's slot adds ``w * x`` (no
weights: every chip computes them), and the partial result goes on to
the next sub-block.  Nothing stands in for the absent chips.  Only the
language model is meant: the Omni release's audio and vision encoders
and its codec decoder are no weights of this configuration.
"""

from __future__ import annotations

import numpy as np

from benchmark.archs import llama

PKG = llama.PKG
_SUB = ("ln_in", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
        "ln_post", "w1", "w3", "w2")
_GAINS = ("ln_in", "q_norm", "kv_norm", "ln_post")


# ---------------------------------------------------------- sizes and layout


def dims(config: dict) -> dict:
    """The sizes, from a configuration file in the source's own keys.  A
    count that the file cuts to this chip's share keeps its published
    value under ``reduced``; the router needs the experts' one."""
    reduced = config.get("reduced", {})
    held = int(config["n_routed_experts"])
    d = int(config["hidden_size"])
    return {
        "d": d, "f": int(config["ffn_hidden_size"]),
        "fe": int(config["expert_ffn_hidden_size"]),
        "h": int(config["num_attention_heads"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v": int(config["v_head_dim"]),
        "q_scale": (d / int(config["q_lora_rank"])) ** 0.5
        if config["mla_scale_q_lora"] else 1.0,
        "kv_scale": (d / int(config["kv_lora_rank"])) ** 0.5
        if config["mla_scale_kv_lora"] else 1.0,
        "held": held, "first": int(config.get("expert_first", 0)),
        "routed": int(reduced.get("n_routed_experts", {}).get(
            "published", held)),
        "zero": int(config["zero_expert_num"]),
        "top_k": int(config["moe_topk"]),
        "route_scale": float(config["routed_scaling_factor"]),
        "vocab": int(config["vocab_size"]),
        "layers": int(config["num_layers"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
    }


def layout(config: dict, blob_id: int) -> list:
    """``[(name, shape, fill)]`` of a blob's leaves in wire order: the two
    attention + dense sub-blocks (separate leaves: a stacked ``(2, d, f)``
    leaf would double the largest kind in flight during assembly), then
    the routed block.  Norm gains are exactly 1.  The score bias is
    exactly 0: a seeded fill (every value of magnitude 2^-7 .. 2^-5)
    against scores of 0.001 .. 0.012 would pick the same outputs for
    every token; the leaf is delivered, read back and added all the same,
    and ``tests/benchmark/test_bench_longcat.py`` gives it live values."""
    m = dims(config)
    if blob_id == m["layers"]:
        return [("embed", (m["vocab"], m["d"]), None),
                ("ln_f", (m["d"],), 1.0),
                ("lm_head", (m["d"], m["vocab"]), None)]
    d, f, h = m["d"], m["f"], m["h"]
    shapes = {
        "ln_in": (d,), "wq_a": (d, m["q_rank"]), "q_norm": (m["q_rank"],),
        "wq_b": (m["q_rank"], h * (m["nope"] + m["rope"])),
        "wkv_a": (d, m["kv_rank"] + m["rope"]), "kv_norm": (m["kv_rank"],),
        "wkv_b": (m["kv_rank"], h * (m["nope"] + m["v"])),
        "wo": (h * m["v"], d), "ln_post": (d,),
        "w1": (d, f), "w3": (d, f), "w2": (f, d)}
    out = [(f"{name}_{i}", shapes[name], 1.0 if name in _GAINS else None)
           for i in (0, 1) for name in _SUB]
    n = m["routed"] + m["zero"]
    return out + [("router", (d, n), None), ("router_bias", (n,), 0.0),
                  ("ew1", (m["held"], d, m["fe"]), None),
                  ("ew3", (m["held"], d, m["fe"]), None),
                  ("ew2", (m["held"], m["fe"], d), None)]


# ------------------------------------------------------- the plain reference


def _rope(jnp, x, theta):
    """x: [batch, seq, heads, rope]; pair i is (x[2i], x[2i+1])."""
    rd = x.shape[-1]
    freqs = theta ** (-np.arange(0, rd, 2, dtype=np.float32) / rd)
    angles = np.arange(x.shape[1], dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(angles))[None, :, None, :]
    sin = jnp.asarray(np.sin(angles))[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _mla(jnp, jax, m, p, i, x):
    b, s, _ = x.shape
    h, nope = m["h"], m["nope"]
    cq = llama._rms_norm(jnp, x @ p[f"wq_a_{i}"], p[f"q_norm_{i}"], m["eps"])
    q = ((cq @ p[f"wq_b_{i}"]) * m["q_scale"]).reshape(
        b, s, h, nope + m["rope"])
    q = jnp.concatenate([q[..., :nope],
                         _rope(jnp, q[..., nope:], m["theta"])], axis=-1)
    kva = x @ p[f"wkv_a_{i}"]
    ckv = llama._rms_norm(jnp, kva[..., :m["kv_rank"]], p[f"kv_norm_{i}"],
                          m["eps"]) * m["kv_scale"]
    kr = _rope(jnp, kva[:, :, None, m["kv_rank"]:], m["theta"])
    kvb = (ckv @ p[f"wkv_b_{i}"]).reshape(b, s, h, nope + m["v"])
    k = jnp.concatenate(
        [kvb[..., :nope], jnp.broadcast_to(kr, (b, s, h, m["rope"]))], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(nope + m["rope"])
    scores = jnp.where(np.tril(np.ones((s, s), bool)), scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                      kvb[..., nope:])
    return attn.reshape(b, s, h * m["v"]) @ p[f"wo_{i}"]


def _ffn(jnp, jax, p, i, x):
    return (jax.nn.silu(x @ p[f"w1_{i}"]) * (x @ p[f"w3_{i}"])) @ p[f"w2_{i}"]


def _scores(jnp, jax, m, p, x):
    return jax.nn.softmax(x @ p["router"], axis=-1)


def _route(jnp, jax, m, p, s):
    """``(pick, w)``: the ``top_k`` outputs by score plus bias, and their
    weights from the scores alone."""
    pick = jnp.argsort(-(s + p["router_bias"]), axis=-1)[..., :m["top_k"]]
    return pick, jnp.take_along_axis(s, pick, axis=-1) * m["route_scale"]


def _identity_part(jnp, m, pick, w, x):
    return jnp.where(pick >= m["routed"], w, 0.0).sum(-1, keepdims=True) * x


def _moe(jnp, jax, m, p, x):
    pick, w = _route(jnp, jax, m, p, _scores(jnp, jax, m, p, x))
    out = _identity_part(jnp, m, pick, w, x)
    for e in range(m["held"]):
        y = (jax.nn.silu(x @ p["ew1"][e]) * (x @ p["ew3"][e])) @ p["ew2"][e]
        gate = jnp.where(pick == m["first"] + e, w, 0.0).sum(-1)
        out = out + gate[..., None] * y
    return out


def _to_router(jnp, jax, m, p, h):
    """The first attention sub-block: ``(a0, n0)``."""
    a0 = h + _mla(jnp, jax, m, p, 0,
                  llama._rms_norm(jnp, h, p["ln_in_0"], m["eps"]))
    return a0, llama._rms_norm(jnp, a0, p["ln_post_0"], m["eps"])


def picks(jnp, jax, dims, p, h):
    """The router outputs each position of ``h`` picks in this layer,
    ``[batch, seq, top_k]``: what the program's slot counters count."""
    _, n0 = _to_router(jnp, jax, dims, p, h)
    return _route(jnp, jax, dims, p, _scores(jnp, jax, dims, p, n0))[0]


def ref_layer(jnp, jax, dims, p, h):
    m = dims
    a0, n0 = _to_router(jnp, jax, m, p, h)
    moe = _moe(jnp, jax, m, p, n0)
    b0 = a0 + _ffn(jnp, jax, p, 0, n0)
    a1 = b0 + _mla(jnp, jax, m, p, 1,
                   llama._rms_norm(jnp, b0, p["ln_in_1"], m["eps"]))
    n1 = llama._rms_norm(jnp, a1, p["ln_post_1"], m["eps"])
    return a1 + _ffn(jnp, jax, p, 1, n1) + moe


# The head is Llama's over the held slice: embedding rows, final norm,
# output columns.
ref_in, ref_out = llama.ref_in, llama.ref_out


# ------------------------------------------------------- the program's side


def register(config: dict, name: str):
    """``models.longcat.CONFIGS[name] = LongcatConfig(...)`` in this
    process, told what it holds; the forward is the program's one jitted
    forward on the boot's parameters.  A program without the family fails
    here, at import."""
    import importlib

    longcat = importlib.import_module(PKG + ".models.longcat")
    forward_jit = importlib.import_module(PKG + ".models.llama").forward_jit
    m = dims(config)
    if not (config["mla_scale_q_lora"] and config["mla_scale_kv_lora"]):
        raise SystemExit("models/longcat.py applies both latent scales "
                         "(mla_scale_q_lora, mla_scale_kv_lora); this "
                         "config turns one off")
    reduced = config.get("reduced", {})
    longcat.CONFIGS[name] = cfg = longcat.LongcatConfig(
        name=name, vocab=m["vocab"], d_model=m["d"], n_layers=m["layers"],
        n_heads=int(reduced.get("num_attention_heads", {}).get(
            "published", m["h"])),
        heads_held=m["h"], q_rank=m["q_rank"], kv_rank=m["kv_rank"],
        nope_dim=m["nope"], rope_dim=m["rope"], v_dim=m["v"], d_ff=m["f"],
        d_expert=m["fe"], n_experts=m["routed"], n_zero=m["zero"],
        experts_held=m["held"], expert_first=m["first"], top_k=m["top_k"],
        route_scale=m["route_scale"], rope_theta=m["theta"], norm_eps=m["eps"])
    return lambda boot, tokens: forward_jit(boot.params, tokens, cfg)


# A full boot holds ``params["layers"][name]`` stacked over the layers
# beside the head's leaves, whatever the family.
leaf = llama.leaf
