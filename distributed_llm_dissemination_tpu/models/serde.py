"""Model params ↔ disseminable layer blobs.

The reference disseminates opaque byte blobs and its ``startupMsg`` is "the
hook that would launch an inference engine"
(``/root/reference/distributor/message.go:216-241``).  This module defines
the byte format that closes that loop for real: each transformer layer of a
model of any family (``models/family.py``) serializes to one blob (the dissemination unit), and
a receiver reassembles delivered blobs back into the stacked-layer params
pytree the jitted forward consumes.

Format (deterministic, self-describing via the ModelConfig):
- Blob ``i`` for ``0 <= i < n_layers`` is layer ``i``'s weights — each leaf
  in the fixed ``layer_param_specs`` order of ITS kind of layer (a
  family's layers need not be alike: ``blob_specs``), as raw C-order
  bytes of ``cfg.dtype``.
- Blob ``head_blob_id(cfg) == n_layers`` holds the non-layer params in
  ``head_param_specs`` order (Llama: ``embed``, ``ln_f``, ``lm_head``),
  same encoding.

Two decode paths, bit-identical by construction (and by test):
- **host**: numpy views over the blob bytes (zero-copy) — used when layers
  were delivered to host RAM.
- **device**: delivered blobs that already live in HBM as uint8 arrays
  (the ``-hbm`` ingest path) are reinterpreted *on device*
  (``_bytes_to_wide``) under one jit — no host round-trip; the bytes
  never leave the accelerator they were disseminated into.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import family
from .llama import ModelConfig, Spec


def layer_param_specs(cfg, layer_id: Optional[int] = None) -> List[Spec]:
    """(name, shape) of layer ``layer_id``'s leaves, in canonical blob
    order: the configuration's family says (``models/family.py``).  Where
    every layer is alike the id may be left out."""
    return family.layer_param_specs(cfg, layer_id)


def head_param_specs(cfg) -> List[Spec]:
    """(name, shape) of the non-layer leaves, in canonical blob order."""
    return family.of(cfg).head_param_specs(cfg)


def head_blob_id(cfg: ModelConfig) -> int:
    """The blob id carrying the non-layer leaves: one past the layers."""
    return cfg.n_layers


def blob_specs(cfg, blob_id: int) -> List[Spec]:
    """(name, shape) of blob ``blob_id``'s leaves: a blob's leaves depend
    on its id (the head blob's are the head's, a layer blob's those of
    its kind of layer)."""
    return (head_param_specs(cfg) if blob_id == head_blob_id(cfg)
            else layer_param_specs(cfg, blob_id))


def blob_kind(cfg, blob_id: int) -> str:
    """``"head"`` or the blob's kind of layer (``family.layer_kinds``)."""
    return ("head" if blob_id == head_blob_id(cfg)
            else family.layer_kinds(cfg)[blob_id])


def blob_nbytes(cfg: ModelConfig, blob_id: int) -> int:
    """Exact byte size of a blob: the sum over ITS leaves (for a family
    whose layers are all alike, ``cfg.layer_nbytes()`` for every layer
    blob)."""
    return family.spec_nbytes(blob_specs(cfg, blob_id), cfg.dtype)


def _encode(leaves: Sequence[np.ndarray]) -> bytes:
    return b"".join(np.ascontiguousarray(a).tobytes() for a in leaves)


def blobs_from_params(cfg: ModelConfig, params: Dict[str, Any]) -> Dict[int, bytes]:
    """Serialize a full params pytree into its dissemination blobs."""
    stacks = family.by_kind(cfg, jax.device_get(params["layers"]))
    blobs: Dict[int, bytes] = {}
    for kind, ids in family.group(cfg).items():
        for at, lid in enumerate(ids):
            blobs[lid] = _encode([np.asarray(stacks[kind][name][at])
                                  for name, _ in layer_param_specs(cfg, lid)])
    head = {name: np.asarray(jax.device_get(params[name]))
            for name, _ in head_param_specs(cfg)}
    blobs[head_blob_id(cfg)] = _encode(
        [head[name] for name, _ in head_param_specs(cfg)]
    )
    return blobs


def _split_blob(
    cfg: ModelConfig, data, specs: List[Spec]
) -> Dict[str, np.ndarray]:
    """Host path: zero-copy numpy views of one blob's leaves."""
    dt = np.dtype(cfg.dtype)
    buf = np.frombuffer(memoryview(data), dtype=np.uint8)
    out: Dict[str, np.ndarray] = {}
    off = 0
    for name, shape in specs:
        n = int(np.prod(shape)) * dt.itemsize
        out[name] = buf[off : off + n].view(dt).reshape(shape)
        off += n
    if off != len(buf):
        raise ValueError(f"blob size {len(buf)} != expected {off}")
    return out


def params_from_blobs(
    cfg: ModelConfig, blobs: Dict[int, Any]
) -> Dict[str, Any]:
    """Host path: reassemble the full params pytree from all blobs.

    ``blobs`` maps blob id → bytes-like.  Requires every layer blob plus
    the head blob.  Leaves are numpy (host) arrays; callers place them on
    device under whatever sharding the stage placement prescribes."""
    missing = [i for i in range(cfg.n_layers + 1) if i not in blobs]
    if missing:
        raise ValueError(f"missing blobs for full model: {missing}")
    return {**head_from_blob(cfg, blobs[head_blob_id(cfg)]),
            "layers": stacked_from_blobs(cfg, blobs, range(cfg.n_layers))}


def head_from_blob(cfg: ModelConfig, data) -> Dict[str, np.ndarray]:
    """Host path: the non-layer leaves as views over the head blob's
    bytes."""
    return _split_blob(cfg, data, head_param_specs(cfg))


def stacked_from_blobs(
    cfg: ModelConfig, blobs: Dict[int, Any], layer_ids: Sequence[int]
) -> Dict[str, Any]:
    """Host path: stacked params for a *contiguous subset* of layers — a
    pipeline stage's slice of the model — stacked by kind of layer, as
    the family holds them (``family.stack``)."""
    return family.stack(
        cfg, layer_ids,
        lambda lid: _split_blob(cfg, blobs[lid], layer_param_specs(cfg, lid)),
        np.stack)


def seeded_blob(cfg: ModelConfig, blob_id: int, seed: int = 0) -> bytes:
    """Regenerate ONE blob of the model ``init_params(cfg, key(seed))``
    would produce, bit-identically, without materializing the rest — how
    seeder nodes fabricate real (non-dummy) initial layers from just a
    config + seed, so every process agrees on the weights and a booted
    model can be checked against an independently initialized source."""
    import jax

    from .llama import model_keys

    fam = family.of(cfg)
    k_emb, layer_keys, k_out = model_keys(cfg, jax.random.key(seed))
    if blob_id == head_blob_id(cfg):
        head = fam.init_head_params(cfg, k_emb, k_out)
        leaves = [np.asarray(jax.device_get(head[name]))
                  for name, _ in head_param_specs(cfg)]
        return _encode(leaves)
    if not 0 <= blob_id < cfg.n_layers:
        raise ValueError(f"blob {blob_id} out of range for {cfg.name}")
    p = family.init_layer_params(cfg, layer_keys[blob_id], blob_id)
    return _encode([np.asarray(jax.device_get(p[name]))
                    for name, _ in layer_param_specs(cfg, blob_id)])


# ------------------------------------------------------------- device path

# One (32, 128) uint8 tile — 8 sublanes of 4 packed byte rows — is the
# widening kernel's grain: a leaf's whole tiles take the kernel, what is
# left of it (under 4 KiB) the strided slices.
_TILE_BYTES = 4096
# 32-bit sublane rows per grid step: 512 KiB in, 512 KiB out.  Timed on
# the v5e at 256 … 4096: flat from 1024 up (PERF.md §6, PR 25).
_BLOCK_ROWS = 1024


def widen_split(nbytes: int, itemsize: int) -> Tuple[int, int]:
    """``(fast, slow)``: how many of a leaf's ``nbytes`` wire bytes
    ``_bytes_to_wide`` widens with the kernel and how many with the
    strided slices.  The one dispatch rule — the widening follows it and
    the ``decode.stage`` span reports it (``quant.widen_bytes``).  It
    sees only what a trace sees: the static length and the item size.
    One-byte items are not widened at all (0, 0); 4-byte items (the
    quantized forms' scale vectors, a few KiB a leaf) keep the slices."""
    if itemsize == 1:
        return 0, 0
    fast = nbytes - nbytes % _TILE_BYTES if itemsize == 2 else 0
    return fast, nbytes - fast


def _pair_matrix() -> jax.Array:
    """bfloat16 (256, 128): two 128-byte planes side by side → 128
    little-endian 16-bit words, byte ``2j`` of a plane times 1 plus byte
    ``2j + 1`` times 256 landing in column ``j`` (``64 + j`` for the
    second plane)."""
    w = np.zeros((256, 128), np.float32)
    lane = np.arange(128)
    w[lane, lane // 2] = w[128 + lane, 64 + lane // 2] = np.where(
        lane % 2 == 0, 1, 256)
    return jnp.asarray(w, jnp.bfloat16)


def _widen16_kernel(x_ref, w_ref, o_ref):
    """(4t, 128) uint8 → (2t, 128) uint16, the same bytes in the same
    order.  The TPU keeps four byte rows in one 32-bit sublane, so the
    block is (t, 128) words whose byte ``k`` at lane ``l`` is blob byte
    ``512 i + 128 k + l``: each byte plane is 128 consecutive bytes with
    a word's two bytes in ADJACENT LANES.  Bringing them together is a
    lane permutation, which the VPU does a lane at a time (the strided
    slices) and the MXU as one multiplication by a 0/1/256 matrix: bytes
    (≤ 255) are exact in bfloat16, the sums (≤ 65535) in the float32
    accumulator.  Planes 0-1 make the row's words 0-127, planes 2-3 its
    words 128-255; 16-bit rows pack in pairs into a sublane, low half
    first, so ``lo | hi << 16`` is already the output tile."""
    v = pltpu.bitcast(x_ref[...], jnp.int32)
    planes = [((v >> (8 * k)) & 0xFF).astype(jnp.float32).astype(jnp.bfloat16)
              for k in range(4)]
    w = w_ref[...]
    lo = jnp.dot(jnp.concatenate(planes[:2], axis=1), w,
                 preferred_element_type=jnp.float32)
    hi = jnp.dot(jnp.concatenate(planes[2:], axis=1), w,
                 preferred_element_type=jnp.float32)
    word = lo.astype(jnp.int32) | (hi.astype(jnp.int32) << 16)
    o_ref[...] = pltpu.bitcast(word, jnp.uint16)


def _widen16_tiles(flat_u8: jax.Array, interpret: bool) -> jax.Array:
    """uint8[n] → uint16[n/2] for ``n`` a multiple of ``_TILE_BYTES``.
    The ``(n/128, 128)`` view IS the 1-D blob's tiling, so nothing is
    copied on the way in, and the output leaves 1-D the same way."""
    n = flat_u8.shape[0]
    rows = n // 512
    t = min(_BLOCK_ROWS, rows)
    out = pl.pallas_call(
        _widen16_kernel,
        grid=(pl.cdiv(rows, t),),
        in_specs=[pl.BlockSpec((4 * t, 128), lambda i: (i, 0)),
                  pl.BlockSpec((256, 128), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((2 * t, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((2 * rows, 128), jnp.uint16),
        interpret=interpret,
        name="bytes_to_wide",
    )(flat_u8.reshape(n // 128, 128), _pair_matrix())
    return out.reshape(n // 2)


def _widen_strided(flat_u8: jax.Array, k: int) -> jax.Array:
    """uint8[n*k] → uintK[n] by k byte-strided slices, shifts and ors:
    bit-exact at any length, and what every byte took until PR 25.  On
    the v5e each slice is a lane-by-lane gather — 0.12 s for one
    4096 × 14336 leaf, 0.94% of the decode's roofline (ledger, PR 24) —
    so it is kept for what the kernel leaves: a remainder under one
    tile, and 4-byte items."""
    wide = {2: jnp.uint16, 4: jnp.uint32}[k]
    n = flat_u8.shape[0] // k
    word = None
    for i in range(k):
        b = jax.lax.slice(flat_u8, (i,), (i + (n - 1) * k + 1,), (k,))
        piece = b.astype(wide) << (8 * i)  # little-endian byte order
        word = piece if word is None else word | piece
    return word


def _bytes_to_wide(flat_u8: jax.Array, dtype) -> jax.Array:
    """1-D uint8[n*k] → 1-D dtype[n] on device (k = itemsize): the
    little-endian memory view, bit for bit.

    Never the direct route — reshape to (..., k) and a widening
    ``bitcast_convert_type``: its k-minor intermediate gets a tiled TPU
    layout that pads k to the 128 lane tile (64x the logical bytes for
    bf16: a 27.9 GiB allocation per physical 416 MiB blob — the boot
    OOM; 15.3 GB of scratch for one leaf, checked again in PR 25).  Every
    intermediate here is 1-D or has a minor dimension of 128 or more.

    Whole tiles of 2-byte items go through ``_widen16_kernel`` (on the
    TPU a Mosaic kernel, elsewhere the same kernel interpreted), the
    rest through ``_widen_strided`` (``widen_split`` is the rule), the
    two are concatenated and the result takes a SAME-WIDTH bitcast."""
    dt = np.dtype(dtype)
    k = dt.itemsize
    if k == 1:
        return jax.lax.bitcast_convert_type(flat_u8, dtype)
    if k not in (2, 4):
        # 8-byte widths would need jax_enable_x64 (without it uint64
        # silently truncates to 32 bits); no model config uses them.
        raise ValueError(f"unsupported decode itemsize {k} for {dt}")
    fast, slow = widen_split(flat_u8.shape[0], k)
    parts = []
    if fast:
        parts.append(jax.lax.platform_dependent(
            flat_u8[:fast],
            tpu=functools.partial(_widen16_tiles, interpret=False),
            default=functools.partial(_widen16_tiles, interpret=True)))
    if slow:
        parts.append(_widen_strided(flat_u8[fast:], k))
    word = jnp.concatenate(parts)
    return jax.lax.bitcast_convert_type(word, dtype)


def _decode_blobs_impl(blobs_u8: Tuple[jax.Array, ...], specs: Tuple[Spec, ...],
                       dtype_name: str):
    """n separate 1-D uint8 blobs → {name: (n, *shape) dtype} on device.

    Each blob's leaves are sliced 1-D, widened 1-D
    (``_bytes_to_wide``), reshaped to the leaf's shape, and only then
    stacked per leaf.  An earlier form stacked the blobs into one
    (n, blob_len) array and sliced along axis 1; at physical layer
    sizes the TPU compiler laid the widening bitcast's intermediate out
    with a tiny minor dim padded to the 128 tile — 32-64x the logical
    bytes, a ~30 GiB allocation for four 416 MiB layers (the
    physical-size boot OOM).  With every intermediate strictly 1-D,
    128 wide or leaf-shaped (minor dims the leaf's own, large ones), no
    degenerate layout choice exists.

    What the program costs on the v5e for one 436 MB Mistral layer
    (PERF.md §6, PR 25): the leaf slices out of the blob, one kernel a
    leaf at HBM speed, and the reshape to the leaf's shape, which is a
    real relayout (a 1-D array's tiles are 2048 consecutive words, a
    matrix's 16 rows by 128 columns) — 3.6 ms of device time in all, 30%
    of the read-once write-once roofline, against 113 ms when every byte
    went through the strided slices."""
    dt = jnp.dtype(dtype_name)
    out = {}
    off = 0
    for name, shape in specs:
        n = int(np.prod(shape)) * dt.itemsize
        leaves = []
        with jax.named_scope(f"decode.blobs/{name}"):
            for blob in blobs_u8:
                leaf = jax.lax.slice(blob, (off,), (off + n,))
                leaves.append(_bytes_to_wide(leaf, dt).reshape(shape))
            out[name] = jnp.stack(leaves)
        off += n
    return out


# The traced name (compile logs, cache keys, the tests' compile-log
# oracle) comes from the wrapped function; keep the historical name.
_decode_blobs_impl.__name__ = "_decode_blobs"
_decode_blobs = functools.partial(
    jax.jit, static_argnums=(1, 2))(_decode_blobs_impl)
# Donated twin: the wire blobs are CONSUMED by the decode.  XLA honors
# donation as input→output aliasing, so it reuses a blob's HBM only
# where an output matches its layout; the boot pairs the donated call
# with dropping the store's blob references (``runtime/boot.py``), which
# is what actually collapses the blobs+params peak at 8B scale — and the
# streaming stager gets the same effect per blob, mid-wire.  A separate
# jitted callable on purpose: donation is part of the executable, so the
# two variants cache — in-memory and persistently — as distinct
# programs.
_decode_blobs_donated = jax.jit(
    _decode_blobs_impl, static_argnums=(1, 2), donate_argnums=(0,))

# Device-path consumers go through the codec-dispatch facade
# (``quant.stacked_from_device`` / ``quant.head_from_device`` /
# ``quant.device_decode_jit``) so the codec AND donation dispatch live
# in exactly one place.
