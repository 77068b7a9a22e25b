"""Quantized transfer codecs: shrink the bytes a layer costs on the wire.

Dissemination is bandwidth-bound — TTD is bytes over line rate
(SURVEY §6; the reference models it exactly that way in its flow solver,
``/root/reference/distributor/flow.go:221-270``).  A transfer codec
attacks the numerator: seeders encode each layer blob into a quantized
form (scales + narrow values), the wire and every scheduler see only the
smaller opaque blob, and the receiver dequantizes AFTER the bytes land —
on the accelerator, when the ``-hbm`` ingest staged them, so the host
never touches decoded weights.  The reference has no equivalent; it
ships raw bytes only.

Two quantized formats (leaves in ``serde``'s canonical order):

- **int8** (~0.50x bf16): per leaf, ``rows`` f32 scales then
  ``rows x cols`` int8 values, where a leaf of shape ``(..., cols)`` is
  flattened to ``(rows, cols)`` — per-output-row symmetric absmax
  scaling, ``x_hat = q * scale``.
- **int4** (~0.27x bf16): per leaf, ``rows x groups`` f32 scales
  (group = 128 columns when the leaf allows, else one group per row)
  then ``rows x cols/2`` packed bytes.  Packing pairs COLUMN HALVES,
  not neighbors: byte ``j`` of a row holds column ``j``'s nibble (low)
  and column ``j + cols/2``'s (high), so the device decode rebuilds the
  leaf with one large ``concatenate([lo, hi], axis=1)`` — a
  neighbor-interleave would need a ``(rows, cols/2, 2)`` intermediate
  whose tiny minor dim provokes the TPU tiled-layout padding blowup
  (the documented physical-size OOM class, see ``serde``).  Leaves that
  can't pack (1-D norm gains, odd columns) ride raw inside the blob —
  a negligible fraction of layer bytes.

Both are deterministic round-to-nearest (every seeder fabricating the
same seeded blob must agree byte-for-byte).

Decode paths mirror ``serde``'s two:
- host: numpy over the blob bytes;
- device: HBM-resident uint8 blobs are sliced, bitcast, and dequantized
  under one jit — XLA fuses the multiply into the bitcast reads, so the
  decode is one pass over HBM.

Codec choice is carried by the topology config (``ModelCodec``) next to
``Model``/``ModelSeed``: every node — seeder, scheduler, booting
receiver — derives identical blob sizes from (model, codec) alone.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import family, serde
from .llama import ModelConfig
from .serde import (
    Spec,
    blob_nbytes,
    blob_specs as _blob_specs,
    head_blob_id,
    head_param_specs,
    layer_param_specs,
)

CODECS = ("raw", "int8", "int4", "int8e", "int4e")
# Entropy wire forms (models/entropy.py): the quantized base form run
# through the DLE1 block coder.  Sizes are DATA-DEPENDENT — the codec
# plane prices them by actually encoding (``WireCodecPlane.ensure_sized``)
# instead of from (model, codec) alone — and decode is host-first (the
# byte-domain coder has no device program; the unpacked base then rides
# the base codec's normal paths).
ENTROPY_CODECS = {"int8e": "int8", "int4e": "int4"}
_SCALE_DT = np.float32
_QMAX = 127.0
_QMAX4 = 7.0
_GROUP4 = 128  # int4 scale-group width (one TPU lane tile of columns)


def _rows_cols(shape: Tuple[int, ...]) -> Tuple[int, int]:
    if len(shape) == 1:
        return 1, shape[0]
    return int(np.prod(shape[:-1])), shape[-1]


def _q4_layout(shape: Tuple[int, ...], itemsize: int):
    """One leaf's int4 wire layout: ``("raw", nbytes)`` for leaves that
    can't pack (1-D norm gains, odd columns), else
    ``("q4", rows, cols, groups)`` with groups of ``cols // groups``
    columns sharing one f32 scale."""
    rows, cols = _rows_cols(shape)
    if len(shape) == 1 or cols % 2:
        return ("raw", rows * cols * itemsize)
    # Scale groups and nibble packing are independent (packing pairs
    # column j with j + cols/2; dequant multiplies AFTER unpacking), so
    # grouping only needs the group width to divide cols.
    g = _GROUP4 if cols % _GROUP4 == 0 else cols
    return ("q4", rows, cols, cols // g)


def _q4_leaf_nbytes(layout) -> int:
    if layout[0] == "raw":
        return layout[1]
    _, rows, cols, groups = layout
    return rows * groups * _SCALE_DT().itemsize + rows * (cols // 2)


def blob_nbytes_codec(cfg: ModelConfig, blob_id: int, codec: str) -> int:
    """Exact wire size of a blob under ``codec``.  Entropy forms raise:
    their size depends on the bytes, not just (model, codec) — callers
    price them through the codec plane's true-size cache."""
    if codec == "raw":
        return blob_nbytes(cfg, blob_id)
    if codec in ENTROPY_CODECS:
        raise ValueError(
            f"codec {codec!r} is data-dependent; size it by encoding "
            "(WireCodecPlane.ensure_sized), not from the model config")
    if codec == "int4":
        itemsize = np.dtype(cfg.dtype).itemsize
        return sum(
            _q4_leaf_nbytes(_q4_layout(shape, itemsize))
            for _, shape in _blob_specs(cfg, blob_id)
        )
    if codec != "int8":
        raise ValueError(f"unknown codec {codec!r}; known: {CODECS}")
    total = 0
    for _, shape in _blob_specs(cfg, blob_id):
        rows, cols = _rows_cols(shape)
        total += rows * _SCALE_DT().itemsize + rows * cols
    return total


def encode_blob(cfg: ModelConfig, blob_id: int, raw: bytes, codec: str) -> bytes:
    """Encode a raw (cfg.dtype) blob into its wire form under ``codec``."""
    if codec == "raw":
        return raw
    if codec in ENTROPY_CODECS:
        from . import entropy

        return entropy.encode(
            encode_blob(cfg, blob_id, raw, ENTROPY_CODECS[codec]))
    if codec == "int4":
        return _encode_blob_q4(cfg, blob_id, raw)
    if codec != "int8":
        raise ValueError(f"unknown codec {codec!r}; known: {CODECS}")
    dt = np.dtype(cfg.dtype)
    buf = np.frombuffer(memoryview(raw), dtype=np.uint8)
    parts: List[bytes] = []
    off = 0
    for _, shape in _blob_specs(cfg, blob_id):
        rows, cols = _rows_cols(shape)
        n = rows * cols * dt.itemsize
        x = buf[off : off + n].view(dt).reshape(rows, cols).astype(np.float32)
        off += n
        scale = np.abs(x).max(axis=1) / _QMAX
        scale = np.where(scale > 0, scale, 1.0).astype(_SCALE_DT)
        q = np.clip(np.rint(x / scale[:, None]), -_QMAX, _QMAX).astype(np.int8)
        parts.append(scale.tobytes())
        parts.append(q.tobytes())
    if off != len(buf):
        raise ValueError(f"raw blob size {len(buf)} != expected {off}")
    return b"".join(parts)


def decode_blob_host(
    cfg: ModelConfig, blob_id: int, data, codec: str
) -> Dict[str, np.ndarray]:
    """Host path: decode one wire blob into {name: cfg.dtype array}."""
    specs = _blob_specs(cfg, blob_id)
    if codec == "raw":
        return serde._split_blob(cfg, data, specs)
    if codec in ENTROPY_CODECS:
        from . import entropy

        return decode_blob_host(cfg, blob_id, entropy.decode(data),
                                ENTROPY_CODECS[codec])
    if codec == "int4":
        return _decode_blob_q4_host(cfg, blob_id, data)
    if codec != "int8":
        raise ValueError(f"unknown codec {codec!r}; known: {CODECS}")
    dt = np.dtype(cfg.dtype)
    buf = np.frombuffer(memoryview(data), dtype=np.uint8)
    out: Dict[str, np.ndarray] = {}
    off = 0
    for name, shape in specs:
        rows, cols = _rows_cols(shape)
        sb = rows * _SCALE_DT().itemsize
        scale = buf[off : off + sb].view(_SCALE_DT).reshape(rows, 1)
        off += sb
        q = buf[off : off + rows * cols].view(np.int8).reshape(rows, cols)
        off += rows * cols
        out[name] = (q.astype(np.float32) * scale).astype(dt).reshape(shape)
    if off != len(buf):
        raise ValueError(f"wire blob size {len(buf)} != expected {off}")
    return out


# ---------------------------------------------------------- int4 host path


def _encode_blob_q4(cfg: ModelConfig, blob_id: int, raw: bytes) -> bytes:
    """Host encode under the int4 format (see module docstring)."""
    dt = np.dtype(cfg.dtype)
    buf = np.frombuffer(memoryview(raw), dtype=np.uint8)
    parts: List[bytes] = []
    off = 0
    for _, shape in _blob_specs(cfg, blob_id):
        layout = _q4_layout(shape, dt.itemsize)
        rows, cols = _rows_cols(shape)
        n = rows * cols * dt.itemsize
        if layout[0] == "raw":
            parts.append(buf[off : off + n].tobytes())
            off += n
            continue
        _, rows, cols, groups = layout
        g = cols // groups
        x = (buf[off : off + n].view(dt).reshape(rows, cols)
             .astype(np.float32))
        off += n
        scale = np.abs(x).reshape(rows, groups, g).max(axis=2) / _QMAX4
        scale = np.where(scale > 0, scale, 1.0).astype(_SCALE_DT)
        q = np.clip(
            np.rint(x.reshape(rows, groups, g) / scale[:, :, None]),
            -_QMAX4, _QMAX4,
        ).astype(np.int8).reshape(rows, cols)
        c2 = cols // 2
        packed = (((q[:, :c2] + 8) & 0xF)
                  | (((q[:, c2:] + 8) & 0xF) << 4)).astype(np.uint8)
        parts.append(scale.tobytes())
        parts.append(packed.tobytes())
    if off != len(buf):
        raise ValueError(f"raw blob size {len(buf)} != expected {off}")
    return b"".join(parts)


def _decode_blob_q4_host(
    cfg: ModelConfig, blob_id: int, data
) -> Dict[str, np.ndarray]:
    """Host decode of one int4 wire blob into {name: cfg.dtype array}."""
    dt = np.dtype(cfg.dtype)
    buf = np.frombuffer(memoryview(data), dtype=np.uint8)
    out: Dict[str, np.ndarray] = {}
    off = 0
    for name, shape in _blob_specs(cfg, blob_id):
        layout = _q4_layout(shape, dt.itemsize)
        if layout[0] == "raw":
            n = layout[1]
            out[name] = buf[off : off + n].view(dt).reshape(shape)
            off += n
            continue
        _, rows, cols, groups = layout
        g = cols // groups
        sb = rows * groups * _SCALE_DT().itemsize
        scale = buf[off : off + sb].view(_SCALE_DT).reshape(rows, groups)
        off += sb
        c2 = cols // 2
        packed = buf[off : off + rows * c2].view(np.uint8).reshape(rows, c2)
        off += rows * c2
        q = np.concatenate(
            [(packed & 0xF).astype(np.int8) - 8,
             (packed >> 4).astype(np.int8) - 8], axis=1)
        x = (q.astype(np.float32).reshape(rows, groups, g)
             * scale[:, :, None])
        out[name] = x.reshape(rows, cols).astype(dt).reshape(shape)
    if off != len(buf):
        raise ValueError(f"wire blob size {len(buf)} != expected {off}")
    return out


# ------------------------------------------------------------- device path


def _decode_qblobs_impl(blobs_u8, specs: Tuple[Spec, ...], dtype_name: str):
    """n separate 1-D uint8 qblobs → {name: (n, *shape) dtype} on device.

    Per-blob 1-D slices, leaf-shaped bitcasts, dequant multiply, then a
    per-leaf stack — same layout discipline as ``serde._decode_blobs``
    (a stacked (n, blob_len) intermediate provoked a dim0-minor tiled
    layout on TPU that padded n to the 128 tile: the physical-size boot
    OOM)."""
    dt = jnp.dtype(dtype_name)
    sdt = jnp.dtype(_SCALE_DT)
    out = {}
    off = 0
    for name, shape in specs:
        rows, cols = _rows_cols(shape)
        sb = rows * _SCALE_DT().itemsize  # one wire format: host's widths
        leaves = []
        with jax.named_scope(f"decode.qblobs/{name}"):
            for blob in blobs_u8:
                sraw = jax.lax.slice(blob, (off,), (off + sb,))
                scale = serde._bytes_to_wide(sraw, sdt)  # (rows,)
                qraw = jax.lax.slice(blob, (off + sb,),
                                     (off + sb + rows * cols,))
                q = serde._bytes_to_wide(qraw, jnp.int8).reshape(rows, cols)
                x = (q.astype(jnp.float32) * scale[:, None]).astype(dt)
                leaves.append(x.reshape(shape))
            out[name] = jnp.stack(leaves)
        off += sb + rows * cols
    return out


def _decode_q4blobs_impl(blobs_u8, specs: Tuple[Spec, ...], dtype_name: str):
    """n separate 1-D uint8 int4-codec blobs → {name: (n, *shape) dtype}
    on device.  Same layout discipline as ``_decode_qblobs``; the packed
    column-halves format means deinterleave is one big
    ``concatenate([lo, hi], axis=1)`` — no tiny-minor-dim intermediates
    (the TPU tiled-layout padding class, see module docstring)."""
    dt = jnp.dtype(dtype_name)
    sdt = jnp.dtype(_SCALE_DT)
    itemsize = dt.itemsize
    out = {}
    off = 0
    for name, shape in specs:
        layout = _q4_layout(shape, itemsize)
        leaves = []
        if layout[0] == "raw":
            n = layout[1]
            for blob in blobs_u8:
                raw = jax.lax.slice(blob, (off,), (off + n,))
                leaves.append(serde._bytes_to_wide(raw, dt).reshape(shape))
            out[name] = jnp.stack(leaves)
            off += n
            continue
        _, rows, cols, groups = layout
        g = cols // groups
        c2 = cols // 2
        sb = rows * groups * _SCALE_DT().itemsize
        for blob in blobs_u8:
            sraw = jax.lax.slice(blob, (off,), (off + sb,))
            scale = serde._bytes_to_wide(sraw, sdt).reshape(rows, groups)
            praw = jax.lax.slice(blob, (off + sb,),
                                 (off + sb + rows * c2,))
            packed = praw.reshape(rows, c2)
            q = jnp.concatenate(
                [(packed & 0xF).astype(jnp.int8) - 8,
                 (packed >> 4).astype(jnp.int8) - 8], axis=1)
            x = (q.astype(jnp.float32).reshape(rows, groups, g)
                 * scale[:, :, None]).astype(dt)
            leaves.append(x.reshape(shape))
        out[name] = jnp.stack(leaves)
        off += sb + rows * c2
    return out


# Traced names (compile logs / the tests' oracle) keep the historical
# jit names for both the plain and donated variants.
_decode_qblobs_impl.__name__ = "_decode_qblobs"
_decode_q4blobs_impl.__name__ = "_decode_q4blobs"
_decode_qblobs = functools.partial(
    jax.jit, static_argnums=(1, 2))(_decode_qblobs_impl)
_decode_q4blobs = functools.partial(
    jax.jit, static_argnums=(1, 2))(_decode_q4blobs_impl)
# Donated twins (see serde._decode_blobs_donated): the HBM wire blobs
# are consumed by the dequant; the callers' reference-drop does the
# actual freeing where XLA finds no aliasable output.
_decode_qblobs_donated = jax.jit(
    _decode_qblobs_impl, static_argnums=(1, 2), donate_argnums=(0,))
_decode_q4blobs_donated = jax.jit(
    _decode_q4blobs_impl, static_argnums=(1, 2), donate_argnums=(0,))


def decode_to_raw(cfg: ModelConfig, blob_id: int, data, codec: str) -> bytes:
    """Re-materialize the CANONICAL raw blob bytes from a wire-codec
    blob: host decode, then the leaves concatenated back in ``serde``'s
    spec order (a raw blob IS exactly that concatenation).  The wire
    receiver's normalization path (docs/codec.md): a holding delivered
    as int8/int4 becomes servable to any raw consumer — at the
    quantization error the operator opted into, not byte-identity with
    the original."""
    if codec == "raw":
        return bytes(data)
    decoded = decode_blob_host(cfg, blob_id, data, codec)
    return b"".join(
        np.ascontiguousarray(decoded[name]).tobytes()
        for name, _ in _blob_specs(cfg, blob_id)
    )


def codec_bench(cfg: Optional[ModelConfig] = None, blob_id: int = 0,
                device: bool = True) -> dict:
    """Micro-bench the wire codecs on THIS host — the measured basis of
    the codec-choice threshold (``DLD_CODEC_MIN_RATE``): a codec only
    pays when the link is slower than the encode/decode path, and that
    crossover is a property of the running container, not a guess.
    Returns {codec: {encode_gbps, decode_host_gbps, decode_device_gbps,
    ratio}} over one layer blob of ``cfg`` (default: the "tiny2" test
    model); rates are raw-bytes-per-second (the side the wire saves).
    ``device=False`` skips the jit decode (hosts without a warm XLA)."""
    import time

    if cfg is None:
        from .llama import CONFIGS

        cfg = CONFIGS["tiny2"]
    from .serde import seeded_blob

    raw = seeded_blob(cfg, blob_id, 0)

    def rate(fn, nbytes: int) -> float:
        fn()  # warm (jit compile / numpy allocator)
        t0 = time.monotonic()
        n = 0
        while time.monotonic() - t0 < 0.2:
            fn()
            n += 1
        dt = time.monotonic() - t0
        return round(nbytes * n / max(dt, 1e-9) / 1e9, 3)

    out: dict = {"raw_bytes": len(raw)}
    for codec in ("int8", "int4", "int8e", "int4e"):
        enc = encode_blob(cfg, blob_id, raw, codec)
        row = {
            "encoded_bytes": len(enc),
            "ratio": round(len(raw) / len(enc), 3),
            "encode_gbps": rate(
                lambda c=codec: encode_blob(cfg, blob_id, raw, c),
                len(raw)),
            "decode_host_gbps": rate(
                lambda c=codec, e=enc: decode_blob_host(cfg, blob_id, e, c),
                len(raw)),
            "decode_device_gbps": 0.0,
        }
        if device:
            specs = tuple(_blob_specs(cfg, blob_id))
            dt_name = np.dtype(cfg.dtype).name
            base = ENTROPY_CODECS.get(codec, codec)
            fn = device_decode_jit(base)
            if codec in ENTROPY_CODECS:
                # The honest device row for an entropy form is the boot
                # path it actually takes: host unwrap THEN the base jit.
                def dev_decode(e=enc, s=specs, c=codec, f=fn):
                    _, bb = host_unwrap(c, e)
                    leaves = f(
                        (jnp.asarray(np.frombuffer(bb, np.uint8)),),
                        s, dt_name)
                    jax.block_until_ready(leaves)
            else:
                arr = jnp.asarray(np.frombuffer(enc, np.uint8))

                def dev_decode(a=arr, s=specs, c=codec, f=fn):
                    leaves = f((a,), s, dt_name)
                    jax.block_until_ready(leaves)

            row["decode_device_gbps"] = rate(dev_decode, len(raw))
        out[codec] = row

    # Content-delta form (models/entropy.py): encode/decode rates over a
    # small-perturbation v2 of the same blob — the rollout-wave shape the
    # delta codec exists for.  ~1% of the bytes touched deterministically
    # (seeded), so the ratio row shows the regime where delta wins; a
    # high-churn v2 degrades toward 1.0x (docs/codec.md frames when delta
    # loses).  No device row: deltas reconstruct to RAW on the host
    # before ack — the device never sees the wire form.
    from . import entropy

    rng = np.random.default_rng(1)
    v2 = np.frombuffer(raw, np.uint8).copy()
    touched = rng.choice(len(v2), size=max(1, len(v2) // 100),
                         replace=False)
    v2[touched] ^= rng.integers(1, 256, size=len(touched)).astype(np.uint8)
    v2b = v2.tobytes()
    denc = entropy.delta_encode(v2b, raw)
    out["delta"] = {
        "encoded_bytes": len(denc),
        "ratio": round(len(raw) / len(denc), 3),
        "encode_gbps": rate(
            lambda: entropy.delta_encode(v2b, raw), len(raw)),
        "decode_host_gbps": rate(
            lambda: entropy.delta_decode(denc, raw), len(raw)),
        "decode_device_gbps": 0.0,
    }
    return out


def device_decode_jit(codec: str, donate: bool = False):
    """THE jitted device-decode program for ``codec``: callable as
    ``f(blobs_u8_tuple, specs_tuple, dtype_name)``.  One lookup shared by
    the boot (``runtime/boot.py``), the streaming stager
    (``runtime/stream_boot.py``) and the hint-time precompile — the three
    must agree on the exact callable (donated and plain variants are
    distinct executables) or a warmup warms the wrong program."""
    if codec == "raw":
        return serde._decode_blobs_donated if donate else serde._decode_blobs
    if codec in ENTROPY_CODECS:
        raise ValueError(
            f"codec {codec!r} has no device decode program — entropy "
            "forms unwrap on the host first (host_unwrap), then the "
            "base codec's jit applies")
    if codec == "int4":
        return _decode_q4blobs_donated if donate else _decode_q4blobs
    if codec != "int8":
        raise ValueError(f"unknown codec {codec!r}; known: {CODECS}")
    return _decode_qblobs_donated if donate else _decode_qblobs


def widen_bytes(codec: str, specs: Sequence[Spec],
                dtype_name: str) -> Tuple[int, int]:
    """``(fast, slow)`` wire bytes of ONE blob that
    ``device_decode_jit(codec)`` widens with the kernel and with the
    strided slices: ``serde.widen_split`` — the rule
    ``serde._bytes_to_wide`` dispatches on — summed over the byte runs
    the program hands it.  raw: every leaf, at the model's item size;
    int8: the scale vectors (its payload is one byte wide, a same-width
    bitcast); int4: its raw leaves and its scale vectors."""
    itemsize = np.dtype(dtype_name).itemsize
    scale = _SCALE_DT().itemsize
    runs = []
    for _, shape in specs:
        rows, cols = _rows_cols(shape)
        if codec == "raw":
            runs.append((rows * cols * itemsize, itemsize))
        elif codec == "int8":
            runs.append((rows * scale, scale))
        elif codec == "int4":
            layout = _q4_layout(shape, itemsize)
            runs.append((layout[1], itemsize) if layout[0] == "raw"
                        else (rows * layout[3] * scale, scale))
        else:
            raise ValueError(f"codec {codec!r} has no device decode program")
    fast, slow = zip(*(serde.widen_split(n, k) for n, k in runs))
    return sum(fast), sum(slow)


def host_unwrap(codec: str, data) -> Tuple[str, Any]:
    """Peel an entropy wire form back to its quantized BASE on the host
    (the byte-domain coder has no device program).  Returns
    ``(base_codec, base_bytes)`` — identity for every other codec — so
    device-path callers can prestage once and keep their jit dispatch
    unchanged (runtime/boot.py, parallel/collectives.py)."""
    base = ENTROPY_CODECS.get(codec)
    if base is None:
        return codec, data
    from . import entropy

    return base, entropy.decode(data)


# -------------------------------------------------- codec-dispatch facade
#
# boot_from_layers talks to the codec layer through these four calls, so
# adding a codec touches this module only.


def stacked_from_blobs_host(
    cfg: ModelConfig, blobs: Dict[int, Any], layer_ids: Sequence[int],
    codec: str,
) -> Dict[str, Any]:
    """Host path: stacked layer params from wire blobs under ``codec``,
    by kind of layer as the family holds them."""
    return family.stack(
        cfg, layer_ids,
        lambda lid: decode_blob_host(cfg, lid, blobs[lid], codec), np.stack)


def head_from_blob_host(cfg: ModelConfig, data, codec: str):
    """Host path: head leaves from the wire head blob under ``codec``."""
    return decode_blob_host(cfg, head_blob_id(cfg), data, codec)


def stacked_from_device(
    cfg: ModelConfig, blob_arrays: Sequence[Any], codec: str,
    donate: bool = False, layer_ids: Optional[Sequence[int]] = None,
) -> Dict[str, Any]:
    """Device path: stacked layer params from the HBM wire blobs of
    ``layer_ids`` (default: layers ``0 ..``), one decode program for each
    kind of layer among them.  ``donate``: consume the wire blobs in
    place (the caller must drop its own references — they are deleted
    after this call)."""
    if layer_ids is None:
        layer_ids = range(len(blob_arrays))
    arrays = dict(zip(layer_ids, blob_arrays))
    decode = device_decode_jit(codec, donate)
    dt_name = np.dtype(cfg.dtype).name
    return family.of_kinds(cfg, {
        kind: decode(tuple(arrays[lid] for lid in ids),
                     tuple(layer_param_specs(cfg, ids[0])), dt_name)
        for kind, ids in family.group(cfg, layer_ids).items()})


def head_from_device(cfg: ModelConfig, blob_u8, codec: str,
                     donate: bool = False) -> Dict[str, Any]:
    """Device path: head leaves from the HBM wire head blob."""
    decoded = device_decode_jit(codec, donate)(
        (blob_u8,), tuple(head_param_specs(cfg)),
        np.dtype(cfg.dtype).name,
    )
    return {name: arr[0] for name, arr in decoded.items()}
