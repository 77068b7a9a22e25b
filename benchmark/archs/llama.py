"""The Llama architecture (Mistral, Codestral, Llama): what the harness
asks of an architecture (``benchmark/archs/__init__.py``), for a
configuration file in Hugging Face's keys and for ``models/llama.py``.

The plain reference is straightforward ``jax.numpy``: no KV cache, no
kernels, no scan, no batching tricks.  It shares no code with
``models/llama.py`` and follows the published description of the
Mistral/Llama block:

    h   = embed[tokens]
    h  += Wo . attention(rope(Wq . n1), rope(Wk . n1), Wv . n1)
    h  += W2 . (silu(W1 . n2) * (W3 . n2))      n = RMSNorm(h) * gain
    out = lm_head . RMSNorm(h)

with grouped-query attention (each KV head serves ``heads / kv_heads``
query heads), a causal mask, and rotary embeddings in the half-split
("rotate_half") convention with ``theta ** (-i / (hd/2))``.  Weights are
stored ``[in, out]`` (``x @ W``), as the blob layout has them.
"""

from __future__ import annotations

import numpy as np

PKG = "distributed_llm_dissemination_tpu"


# ---------------------------------------------------------- sizes and layout


def dims(config: dict) -> dict:
    """The sizes the blob layout needs, from a configuration file in the
    source's own (Hugging Face) keys."""
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    return {
        "d": d, "h": h, "kv": int(config["num_key_value_heads"]),
        "hd": int(config.get("head_dim") or d // h),
        "f": int(config["intermediate_size"]),
        "vocab": int(config["vocab_size"]),
        "layers": int(config["num_hidden_layers"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
    }


def layout(config: dict, blob_id: int) -> list:
    """``[(name, shape, fill)]`` of a blob's leaves in wire order.  Blob
    ``layers`` is the head blob (embed, final norm, lm_head).  Norm gains
    are exactly 1; every other leaf is seeded random."""
    m = dims(config)
    d, f, h, kv, hd = m["d"], m["f"], m["h"], m["kv"], m["hd"]
    if blob_id == m["layers"]:
        return [("embed", (m["vocab"], d), None), ("ln_f", (d,), 1.0),
                ("lm_head", (d, m["vocab"]), None)]
    return [("wq", (d, h * hd), None), ("wk", (d, kv * hd), None),
            ("wv", (d, kv * hd), None), ("wo", (h * hd, d), None),
            ("ln1", (d,), 1.0), ("ln2", (d,), 1.0),
            ("w1", (d, f), None), ("w3", (d, f), None), ("w2", (f, d), None)]


# ------------------------------------------------------- the plain reference


def _rms_norm(jnp, x, gain, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * gain


def _rope(jnp, x, theta):
    # x: [batch, seq, heads, hd]
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    angles = np.arange(x.shape[1], dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(angles))[None, :, None, :]
    sin = jnp.asarray(np.sin(angles))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def ref_layer(jnp, jax, dims, p, h):
    b, s, _ = h.shape
    nh, kv, hd = dims["h"], dims["kv"], dims["hd"]
    n1 = _rms_norm(jnp, h, p["ln1"], dims["eps"])
    q = _rope(jnp, (n1 @ p["wq"]).reshape(b, s, nh, hd), dims["theta"])
    k = _rope(jnp, (n1 @ p["wk"]).reshape(b, s, kv, hd), dims["theta"])
    v = (n1 @ p["wv"]).reshape(b, s, kv, hd)
    k = jnp.repeat(k, nh // kv, axis=2)  # KV head j serves heads j*g..
    v = jnp.repeat(v, nh // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    h = h + attn.reshape(b, s, nh * hd) @ p["wo"]
    n2 = _rms_norm(jnp, h, p["ln2"], dims["eps"])
    return h + (jax.nn.silu(n2 @ p["w1"]) * (n2 @ p["w3"])) @ p["w2"]


def ref_in(jnp, dims, head, tokens):
    return head["embed"][tokens]


def ref_out(jnp, dims, head, h):
    return _rms_norm(jnp, h, head["ln_f"], dims["eps"]) @ head["lm_head"]


# ------------------------------------------------------- the program's side


def register(config: dict, name: str):
    """``CONFIGS[name] = ModelConfig(...)`` in this process; the forward
    is ``models.llama.forward_jit`` on the boot's parameters."""
    import importlib

    llama = importlib.import_module(PKG + ".models.llama")
    m = dims(config)
    if m["hd"] * m["h"] != m["d"]:
        raise SystemExit("models/llama.py derives head_dim from "
                         "hidden_size / heads; this config differs")
    llama.CONFIGS[name] = cfg = llama.ModelConfig(
        name=name, vocab=m["vocab"], d_model=m["d"],
        n_layers=m["layers"], n_heads=m["h"], n_kv_heads=m["kv"],
        d_ff=m["f"], rope_theta=m["theta"], norm_eps=m["eps"])
    return lambda boot, tokens: llama.forward_jit(boot.params, tokens, cfg)


def leaf(boot, blob_id: int, name: str):
    """A full boot holds ``params["layers"][name]`` stacked over the
    layers beside the head's leaves; a stage boot holds its own layers'
    stack alone (a pod keeps the head blob in wire form)."""
    ids = list(boot.layer_ids)
    if blob_id not in ids:
        return boot.params[name]
    stack = boot.params["layers"] if boot.kind == "full" else boot.params
    return stack[name][ids.index(blob_id)]
