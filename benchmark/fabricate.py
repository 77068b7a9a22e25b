"""Weights, prompts and read-back digests, all from ``--seed``.

The benchmark makes its own blobs: plain numpy, no JAX, a second or two
for 4 GB (the program's ``seeded_blob`` under ``jax.random`` on the CPU
costs a minute).  The byte layout of a blob is the published format of
``models/serde.py`` (raw) and ``models/quant.py`` (int8), written down
here a second time on purpose: this file is the yardstick's side of it,
and ``Leaf.bits`` is the plain numpy decode that ``correct`` holds the
device's decode to.

Raw blob: each leaf in order, C-order bytes of bfloat16.
int8 blob: per leaf, ``rows`` float32 scales, then ``rows x cols`` int8
(rows = product of all but the last dimension; a 1-D leaf is one row);
a decoded element is ``bfloat16(float32(q) * scale)``.
"""

from __future__ import annotations

import hashlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import archs

CODECS = ("raw", "int8")
# bfloat16 patterns: sign and 7 mantissa bits random, exponent 120 or
# 121, so |w| is in [2^-7, 2^-5) with an RMS near 0.018 — the d^-0.5
# scale of a trained layer at these widths, and never NaN or infinite.
_KEEP = np.uint16(0x80FF)
_EXP = np.uint16(0x3C00)
_INT8_RMS = 73.9  # RMS of a uniform int8
_TARGET_RMS = 0.0156


def model_dims(config: dict) -> dict:
    """The configuration's sizes as its architecture module gives them:
    ``layers`` (the layer blobs; the head blob is one past them),
    ``vocab``, and what the module's own functions need."""
    return archs.of(config).dims(config)


def _layout(config: dict, blob_id: int) -> list:
    """``[(name, shape, fill)]``: the architecture module's word on one
    blob."""
    arch = archs.of(config)
    if not 0 <= blob_id <= arch.dims(config)["layers"]:
        raise ValueError(f"blob {blob_id} out of range")
    return arch.layout(config, blob_id)


def blob_specs(config: dict, blob_id: int) -> list:
    """``[(name, shape)]`` of a blob's leaves in wire order.  Blob
    ``layers`` is the head blob."""
    return [(name, shape) for name, shape, _ in _layout(config, blob_id)]


def _rows_cols(shape: tuple) -> tuple:
    if len(shape) == 1:
        return 1, int(shape[0])
    return int(np.prod(shape[:-1])), int(shape[-1])


def blob_nbytes(config: dict, blob_id: int, codec: str = "raw") -> int:
    total = 0
    for _, shape in blob_specs(config, blob_id):
        rows, cols = _rows_cols(shape)
        total += rows * cols * 2 if codec == "raw" else rows * 4 + rows * cols
    return total


def model_nbytes(config: dict) -> int:
    """Decoded (bfloat16) parameter bytes of the whole configuration."""
    n = model_dims(config)["layers"]
    return sum(blob_nbytes(config, b, "raw") for b in range(n + 1))


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.Generator(np.random.SFC64(
        np.random.SeedSequence([int(seed), *map(int, stream)])))


def _random_bytes(rng: np.random.Generator, n: int) -> np.ndarray:
    words = rng.integers(0, 2 ** 64, (n + 7) // 8, dtype=np.uint64)
    return words.view(np.uint8)[:n]


def _bf16_bits(fill: float) -> np.uint16:
    """The bfloat16 pattern of a constant fill, which must be one."""
    bits = int(np.float32(fill).view(np.uint32))
    if bits & 0xFFFF:
        raise ValueError(f"a constant fill must be a bfloat16: {fill!r}")
    return np.uint16(bits >> 16)


def make_blob(config: dict, blob_id: int, seed: int,
              codec: str = "raw") -> np.ndarray:
    """One blob's bytes (a writable 1-D uint8 array), the same for the
    same ``(config, blob_id, seed, codec)`` in every process."""
    if codec not in CODECS:
        raise ValueError(f"unknown codec {codec!r}; known: {CODECS}")
    rng = _rng(seed, blob_id, CODECS.index(codec))
    out = _random_bytes(rng, blob_nbytes(config, blob_id, codec))
    off = 0
    for _, shape, fill in _layout(config, blob_id):
        rows, cols = _rows_cols(shape)
        held = None if fill is None else _bf16_bits(fill)
        if codec == "raw":
            n = rows * cols * 2
            leaf = out[off:off + n].view(np.uint16)
            if held is not None:
                leaf[:] = held
            else:
                np.bitwise_and(leaf, _KEEP, out=leaf)
                np.bitwise_or(leaf, _EXP, out=leaf)
            off += n
            continue
        scale = out[off:off + rows * 4].view(np.float32)
        off += rows * 4
        if held is not None:  # 127 times a 127th of it, in every element
            scale[:] = fill / 127.0
            out[off:off + rows * cols] = 127
        else:
            scale[:] = (_TARGET_RMS / _INT8_RMS) * rng.uniform(
                0.75, 1.25, rows).astype(np.float32)
        off += rows * cols
    assert off == len(out)
    return out


def make_blobs(config: dict, blob_ids, seed: int, codec: str = "raw",
               threads: int = 4) -> dict:
    """Several blobs at once; numpy drops the GIL in the bulk passes."""
    ids = list(blob_ids)
    return dict(zip(ids, in_threads(
        lambda b: make_blob(config, b, seed, codec), ids, threads)))


class Leaf:
    """One leaf inside a wire blob, seen as ``rows x cols``: a view, no
    copy.  ``bits()`` is the plain numpy decode of the whole leaf —
    exactly the bfloat16 patterns a correct decode puts on the device."""

    def __init__(self, name, shape, codec, values, scale=None):
        self.name, self.shape, self.codec = name, tuple(shape), codec
        self.values, self.scale = values, scale

    def bits(self, r0: int = 0, r1: int = None) -> np.ndarray:
        """Rows ``r0:r1`` of the decoded leaf as bfloat16 bit patterns
        (uint16).  int8: ``bfloat16(float32(q) * scale)``, the rounding
        done on the bits (a bfloat16 is the top half of a float32;
        rounding to it is round-to-nearest-even on the lower half) — ten
        times faster than converting through the bfloat16 dtype and the
        same to the bit."""
        if self.codec == "raw":
            return self.values[r0:r1]
        x = self.values[r0:r1].astype(np.float32) * self.scale[r0:r1, None]
        bits = x.view(np.uint32)
        bits += np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1))
        bits >>= 16
        return bits.astype(np.uint16)

    def blocks(self, nbytes: int = 1 << 21):
        """The decoded leaf in row blocks of about ``nbytes`` of float32
        each: the passes of ``bits`` then run in the cache."""
        rows, cols = self.values.shape
        step = max(1, nbytes // (4 * cols))
        return (self.bits(r, r + step) for r in range(0, rows, step))


def blob_leaves(config: dict, blob_id: int, data, codec: str = "raw") -> dict:
    """``{name: Leaf}`` over one wire blob's bytes."""
    buf = np.frombuffer(memoryview(data), dtype=np.uint8)
    out, off = {}, 0
    for name, shape in blob_specs(config, blob_id):
        rows, cols = _rows_cols(shape)
        if codec == "raw":
            n = rows * cols * 2
            out[name] = Leaf(name, shape, codec, buf[off:off + n].view(
                np.uint16).reshape(rows, cols))
            off += n
        else:
            scale = buf[off:off + rows * 4].view(np.float32)
            off += rows * 4
            q = buf[off:off + rows * cols].view(np.int8).reshape(rows, cols)
            off += rows * cols
            out[name] = Leaf(name, shape, codec, q, scale)
    if off != len(buf):
        raise ValueError(f"blob {blob_id}: {len(buf)} bytes, expected {off}")
    return out


def make_prompts(config: dict, seed: int, count: int, length: int) -> list:
    vocab = model_dims(config)["vocab"]
    rng = _rng(seed, 1 << 20)
    return [[int(t) for t in rng.integers(0, vocab, length)]
            for _ in range(count)]


# ------------------------------------------------------------- read-back
#
# ``correct`` reads the delivered model back from the device, whole, and
# compares it byte for byte with what the seeders hold, by the harness's
# own digest (hashlib; never ``utils/integrity.py``).


def digest(arrays) -> str:
    """blake2b over the bytes of C-contiguous arrays, in order."""
    h = hashlib.blake2b(digest_size=16)
    for a in arrays:
        h.update(np.ascontiguousarray(a).reshape(-1).view(np.uint8))
    return h.hexdigest()


def expected_digests(config: dict, blob_id: int, data, codec: str) -> dict:
    """The seeder's side of one blob: ``wire`` digests the bytes it holds
    and sends, ``leaves`` every leaf of their plain numpy decode, in wire
    order (raw: the same bytes, so the same digest)."""
    wire = digest([np.frombuffer(memoryview(data), dtype=np.uint8)])
    if codec == "raw":
        return {"wire": wire, "leaves": wire}
    leaves = blob_leaves(config, blob_id, data, codec)
    return {"wire": wire,
            "leaves": digest(block for leaf in leaves.values()
                             for block in leaf.blocks())}


def in_threads(fn, items, threads: int) -> list:
    """``[fn(x) for x in items]`` on a few threads (hashlib and numpy's
    bulk passes drop the GIL)."""
    items = list(items)
    with ThreadPoolExecutor(max(1, min(threads, len(items)))) as pool:
        return list(pool.map(fn, items))
