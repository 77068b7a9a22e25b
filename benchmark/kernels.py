"""Bytes and operations of each device kernel, from shapes alone, and the
table of peaks.  Kept with the benchmark so that no change to the
program can move a roofline share by recounting its own work.

A roofline share is the least time the chip could take — the larger of
operations over peak FLOP/s and bytes over peak bytes/s — over the time
the kernel took in the device trace.  Both kernels here move bytes and
compute next to nothing, so HBM bandwidth bounds them.
"""

from __future__ import annotations

import json
import os

from benchmark import fabricate

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The one table of peaks; an unknown device is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       "to benchmark/peaks.json with its source")
    return table[device_kind]


def wire_bytes(config: dict, codec: str) -> int:
    n = fabricate.model_dims(config)["layers"]
    return sum(fabricate.blob_nbytes(config, b, codec) for b in range(n + 1))


def splice_bytes(config: dict, codec: str) -> int:
    """``parallel/ingest.py`` ``_concat_pad``: every wire byte of every
    blob is read from its fragment buffer and written into the blob's
    one span buffer, once."""
    return 2 * wire_bytes(config, codec)


def decode_bytes(config: dict, codec: str) -> int:
    """``device_decode_jit``: every wire byte read once, every decoded
    bfloat16 parameter written once."""
    return wire_bytes(config, codec) + fabricate.model_nbytes(config)


def decode_ops(config: dict, codec: str) -> int:
    """raw is a bitcast (no arithmetic); int8 is one convert and one
    multiply per parameter."""
    if codec == "raw":
        return 0
    return 2 * (fabricate.model_nbytes(config) // 2)


def roofline_share(bytes_moved: float, ops: float, seconds: float,
                   device_kind: str) -> float:
    """Percent of the roofline; what bounds it is bytes unless the
    operations would take longer."""
    if seconds <= 0:
        raise ValueError("no kernel time")
    pk = peaks(device_kind)
    least = max(bytes_moved / pk["hbm_bytes_per_s"],
                ops / pk["bf16_flops_per_s"])
    return 100.0 * least / seconds
