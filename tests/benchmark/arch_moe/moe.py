"""A second architecture, present only as files: the program's routed
variant (``models/llama.py`` ``moe_ffn``), for a configuration in
Mixtral's Hugging Face keys.  The tests copy this file beside a temporary
manifest as ``benchmark/archs/moe.py``; no file of ``benchmark/`` knows of
it.

A layer is Llama's attention, then in place of the dense FFN

    w   = softmax(router . n2)                       n2 = RMSNorm(h) * gain
    w   = w where w >= the k-th largest, else 0      (ties all stay)
    w  /= sum(w) + 1e-9
    h  += sum_e w[e] * W2[e] . (silu(W1[e] . n2) * (W3[e] . n2))

with ``router (d, e)``, ``w1 / w3 (e, d, f)``, ``w2 (e, f, d)``: leaves
of rank 3, one row of an int8 blob per ``(expert, input row)``.  The head,
the registration's forward and the stacked read-back are Llama's.
"""

from __future__ import annotations

import numpy as np

from benchmark.archs import llama

# Held to ``run.py``'s 3% like every configuration.  Under random weights a
# token's k-th and (k+1)-th routing weights lie within bfloat16's rounding
# of each other now and then, and the program then routes that token to
# another expert than the float32 reference; at this directory's tiny
# configuration, 3 x 23 positions, 24 seeds in each codec (a CPU count, no
# device's), relative L2 read 0.56-1.91% raw and 0.54-2.00% int8.


def dims(config: dict) -> dict:
    return dict(llama.dims(config),
                experts=int(config["num_local_experts"]),
                top_k=int(config["num_experts_per_tok"]))


def layout(config: dict, blob_id: int) -> list:
    m = dims(config)
    if blob_id == m["layers"]:
        return llama.layout(config, blob_id)
    d, f, e = m["d"], m["f"], m["experts"]
    return llama.layout(config, blob_id)[:6] + [
        ("router", (d, e), None), ("w1", (e, d, f), None),
        ("w3", (e, d, f), None), ("w2", (e, f, d), None)]


def ref_layer(jnp, jax, dims, p, h):
    b, s, _ = h.shape
    nh, kv, hd = dims["h"], dims["kv"], dims["hd"]
    n1 = llama._rms_norm(jnp, h, p["ln1"], dims["eps"])
    q = llama._rope(jnp, (n1 @ p["wq"]).reshape(b, s, nh, hd), dims["theta"])
    k = llama._rope(jnp, (n1 @ p["wk"]).reshape(b, s, kv, hd), dims["theta"])
    v = (n1 @ p["wv"]).reshape(b, s, kv, hd)
    k = jnp.repeat(k, nh // kv, axis=2)
    v = jnp.repeat(v, nh // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    scores = jnp.where(np.tril(np.ones((s, s), bool)), scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    h = h + attn.reshape(b, s, nh * hd) @ p["wo"]
    n2 = llama._rms_norm(jnp, h, p["ln2"], dims["eps"])
    w = jax.nn.softmax(n2 @ p["router"], axis=-1)
    e, top_k = dims["experts"], dims["top_k"]
    if top_k < e:
        kth = jnp.sort(w, axis=-1)[..., e - top_k][..., None]
        w = jnp.where(w >= kth, w, 0.0)
        w = w / (w.sum(-1, keepdims=True) + 1e-9)
    for i in range(e):
        y = (jax.nn.silu(n2 @ p["w1"][i]) * (n2 @ p["w3"][i])) @ p["w2"][i]
        h = h + w[..., i:i + 1] * y
    return h


ref_in, ref_out, leaf = llama.ref_in, llama.ref_out, llama.leaf


def register(config: dict, name: str):
    import importlib

    prog = importlib.import_module(llama.PKG + ".models.llama")
    m = dims(config)
    prog.CONFIGS[name] = cfg = prog.ModelConfig(
        name=name, vocab=m["vocab"], d_model=m["d"], n_layers=m["layers"],
        n_heads=m["h"], n_kv_heads=m["kv"], d_ff=m["f"],
        rope_theta=m["theta"], norm_eps=m["eps"], n_experts=m["experts"],
        top_k=m["top_k"])
    return lambda boot, tokens: prog.forward_jit(boot.params, tokens, cfg)
