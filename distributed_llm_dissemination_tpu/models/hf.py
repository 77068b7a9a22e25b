"""Hugging Face Llama checkpoint import: real weights as dissemination blobs.

The reference fabricates dummy byte blobs (``cmd/config.go:94-171``); this
framework's seeded blobs already upgrade those to real-but-synthetic
weights.  This module closes the remaining gap to a production workflow:
point the topology at an on-disk Hugging Face Llama checkpoint —

    "Model": "hf:/path/to/checkpoint"

— and seeders fabricate their blobs FROM THE CHECKPOINT (per-layer slices
of the safetensors state dict, through the same ``serde`` wire format),
the schedulers ship them like any other blobs (transfer codecs compose),
and the booted engine runs the actual model.

The weight mapping is transposition-only because the compute conventions
match HF's Llama exactly: rotate-half rotary (``llama.rope`` expands to
HF's ``x*cos + rotate_half(x)*sin``), f32 RMSNorm with the same
cast-then-scale order, 1/sqrt(head_dim) attention scaling, SwiGLU.  A
parity test (``tests/test_hf.py``) checks our jitted forward against the
``transformers`` implementation on the same checkpoint.

Loading is lazy safetensors reads: fabricating one layer's blob touches
only that layer's nine tensors, so a seeder of one 70B layer pays one
layer's RAM, not the checkpoint's.  (``.bin`` torch checkpoints are not
supported — convert to safetensors first.)
"""

from __future__ import annotations

import functools
import json
import os
from typing import Any, Dict

import numpy as np

from . import family, llama
from .llama import ModelConfig

PREFIX = "hf:"

_DTYPES = {
    "float32": np.float32,
    "float16": np.float16,
    "bfloat16": "bfloat16",  # resolved via ml_dtypes below
}


def is_hf(name: str) -> bool:
    return name.startswith(PREFIX)


def _np_dtype(torch_dtype: str):
    name = torch_dtype or "float32"
    if name not in _DTYPES:
        # FP8/int-quantized checkpoints etc.: silently coercing to f32
        # would surface later as wrong-sized blobs — reject at config
        # time like every other unsupported checkpoint feature.
        raise ValueError(
            f"unsupported torch_dtype {name!r}; known: {sorted(_DTYPES)}"
        )
    dt = _DTYPES[name]
    if dt == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(dt)


@functools.lru_cache(maxsize=4)
def config_from_dir(path: str) -> ModelConfig:
    """Our ModelConfig from an HF checkpoint's config.json.

    Raises for checkpoint features our forward does NOT implement —
    booting one of those would produce silently wrong logits, the worst
    possible failure mode for a weights pipeline."""
    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    arch = (hf.get("architectures") or ["?"])[0]
    if llama.HF_ARCHITECTURE not in arch:
        theirs = [name for name in family.FAMILIES
                  if family.module(name).HF_ARCHITECTURE in arch]
        raise family.FamilyNotSupported(
            f"unsupported HF architecture {arch!r} (Llama only)"
            + (f": models/hf.py cannot load the {theirs[0]} family — it "
               "maps no checkpoint tensor names to that family's blob "
               "leaves" if theirs else ""))
    if hf.get("rope_scaling"):
        raise ValueError(
            f"checkpoint uses rope_scaling={hf['rope_scaling']!r} "
            "(Llama-3.1+ long-context scaling); this forward implements "
            "plain RoPE only — logits would silently diverge"
        )
    if hf.get("attention_bias") or hf.get("mlp_bias"):
        raise ValueError(
            "checkpoint uses attention/mlp biases; this forward is "
            "bias-free — logits would silently diverge"
        )
    d = int(hf["hidden_size"])
    heads = int(hf["num_attention_heads"])
    head_dim = int(hf.get("head_dim") or d // heads)
    if head_dim != d // heads:
        raise ValueError(
            f"explicit head_dim {head_dim} != hidden/heads {d // heads}: "
            "unsupported layout"
        )
    return ModelConfig(
        name=PREFIX + path,
        vocab=int(hf["vocab_size"]),
        d_model=d,
        n_layers=int(hf["num_hidden_layers"]),
        n_heads=heads,
        n_kv_heads=int(hf.get("num_key_value_heads") or heads),
        d_ff=int(hf["intermediate_size"]),
        rope_theta=float(hf.get("rope_theta") or 10000.0),
        norm_eps=float(hf.get("rms_norm_eps") or 1e-5),
        dtype=_np_dtype(hf.get("torch_dtype")),
    )


def config_from_name(name: str) -> ModelConfig:
    if not is_hf(name):
        raise ValueError(f"not an hf: model name: {name!r}")
    return config_from_dir(name[len(PREFIX):])


# Our leaf name -> (HF per-layer key suffix, transpose?).  Order is
# irrelevant here; blob encoding follows serde.layer_param_specs.
_LAYER_KEYS = {
    "wq": ("self_attn.q_proj.weight", True),
    "wk": ("self_attn.k_proj.weight", True),
    "wv": ("self_attn.v_proj.weight", True),
    "wo": ("self_attn.o_proj.weight", True),
    "ln1": ("input_layernorm.weight", False),
    "ln2": ("post_attention_layernorm.weight", False),
    "w1": ("mlp.gate_proj.weight", True),
    "w3": ("mlp.up_proj.weight", True),
    "w2": ("mlp.down_proj.weight", True),
}


@functools.lru_cache(maxsize=4)
def _weight_files(path: str) -> Dict[str, str]:
    """tensor name -> safetensors file, without decoding any tensor —
    a seeder fabricating ONE layer's blob must not pull the whole
    checkpoint into RAM."""
    from safetensors import safe_open

    st_files = sorted(
        f for f in os.listdir(path) if f.endswith(".safetensors")
    )
    index: Dict[str, str] = {}
    for fname in st_files:
        with safe_open(os.path.join(path, fname), framework="np") as f:
            for key in f.keys():
                index[key] = fname
    if not index:
        raise FileNotFoundError(f"no .safetensors weights in {path}")
    return index


def _read_tensor(path: str, name: str) -> np.ndarray:
    from safetensors import safe_open

    fname = _weight_files(path).get(name)
    if fname is None:
        raise KeyError(f"tensor {name!r} not in checkpoint {path}")
    with safe_open(os.path.join(path, fname), framework="np") as f:
        return f.get_tensor(name)


def _has_tensor(path: str, name: str) -> bool:
    return name in _weight_files(path)


def _leaf(path: str, name: str, transpose: bool, dtype) -> np.ndarray:
    t = _read_tensor(path, name)
    if transpose:
        t = t.T
    return np.ascontiguousarray(t).astype(dtype, copy=False)


def _layer_leaves(path: str, cfg: ModelConfig, i: int) -> Dict[str, np.ndarray]:
    dt = np.dtype(cfg.dtype)
    prefix = f"model.layers.{i}."
    return {
        ours: _leaf(path, prefix + key, tr, dt)
        for ours, (key, tr) in _LAYER_KEYS.items()
    }


def _head_leaves(path: str, cfg: ModelConfig) -> Dict[str, np.ndarray]:
    dt = np.dtype(cfg.dtype)
    embed = _leaf(path, "model.embed_tokens.weight", False, dt)
    if _has_tensor(path, "lm_head.weight"):
        lm_head = _leaf(path, "lm_head.weight", True, dt)
    else:  # tied embeddings
        lm_head = np.ascontiguousarray(embed.T)
    return {
        "embed": embed,
        "ln_f": _leaf(path, "model.norm.weight", False, dt),
        "lm_head": lm_head,
    }


def params_from_dir(path: str) -> Dict[str, Any]:
    """The full params pytree (our stacked-layer layout) from an HF
    checkpoint directory — every projection transposed from HF's
    [out, in] to our [in, out]."""
    cfg = config_from_dir(path)
    per_layer = [_layer_leaves(path, cfg, i) for i in range(cfg.n_layers)]
    head = _head_leaves(path, cfg)
    return {
        "embed": head["embed"],
        "layers": {
            k: np.stack([lp[k] for lp in per_layer]) for k in _LAYER_KEYS
        },
        "ln_f": head["ln_f"],
        "lm_head": head["lm_head"],
    }


def blob_from_name(name: str, blob_id: int) -> bytes:
    """One dissemination blob of an ``hf:<dir>`` model — what a seeder
    node fabricates from the checkpoint (``core.config.create_layers``).
    Loads ONLY that blob's tensors (lazy safetensors reads), so a seeder
    of one 70B layer pays one layer's RAM, not the checkpoint's."""
    from . import serde

    path = name[len(PREFIX):]
    cfg = config_from_dir(path)
    if blob_id == serde.head_blob_id(cfg):
        leaves = _head_leaves(path, cfg)
        return serde._encode(
            [leaves[n] for n, _ in serde.head_param_specs(cfg)]
        )
    if not 0 <= blob_id < cfg.n_layers:
        raise ValueError(f"blob {blob_id} out of range for {cfg.name}")
    leaves = _layer_leaves(path, cfg, blob_id)
    return serde._encode(
        [leaves[n] for n, _ in serde.layer_param_specs(cfg)]
    )
