"""Benchmark: the dissemination terminal hop, measured on its REAL code path.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "GB/s/chip", "vs_baseline": N, ...}

What runs is exactly what a mode-3 receiver runs on delivery
(``runtime/receiver.py`` → ``parallel/ingest.py``): a Llama-3-8B-sized
layer (~416 MiB) arrives as 8 byte-range fragments (the multi-sender
flow-job splits of the reference's mode 3, flow.go:193-211), each fragment
is written through ``ShardedLayerIngest.write`` (an async host→HBM DMA per
span piece), and ``finalize`` materializes the layer on the device set.
The clock covers write+finalize end to end — no proxy kernels.  It runs in
ONE process that holds the chip, and exits non-zero when JAX's platform is
not ``tpu``: there is no CPU form of this number.

Honest denominators, both reported:
- ``vs_baseline``: against the reference's modeled per-node NIC line rate,
  12.5 Gbit/s = 1.5625 GB/s (``/root/reference/conf/config.json``
  ``NetworkBW``) — the fastest the Go/TCP system can deliver layer bytes
  into a node's memory.
- ``link_fraction``: against this machine's *measured* raw host→device
  bandwidth (one bulk ``device_put`` of the same bytes) — the fraction of
  the physically available ingest link the real path achieves.
"""

import json
import os
import statistics
import sys
import time

import jax
import numpy as np

BASELINE_GBPS = 1.5625  # 12.5 Gbit/s reference NetworkBW, conf/config.json


def _harness_hash() -> str:
    """Provenance stamp (utils/provenance.py) — ties this record to the
    code that produced it; the repo hashes itself, so no fallback."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from distributed_llm_dissemination_tpu.utils.provenance import (
        harness_hash,
    )

    return harness_hash()


PARTS = 8  # fragments per layer (the reference scenario's seeder count)
TRIALS = 5  # pair budget; the loop stops early past BUDGET_S wall-clock
MIN_TRIALS = 2
BUDGET_S = 180.0


def split_offsets(total, n):
    base, rem = divmod(total, n)
    offs = []
    pos = 0
    for i in range(n):
        size = base + (1 if i < rem else 0)
        offs.append((pos, size))
        pos += size
    return offs


def ingest_once(total, frags, devices):
    """One layer through the receiver's incremental device-ingest path."""
    from distributed_llm_dissemination_tpu.parallel.ingest import (
        ShardedLayerIngest,
    )

    ing = ShardedLayerIngest(total, devices)
    for off, data in frags:
        ing.write(off, data)
    arr = ing.finalize()
    jax.block_until_ready(arr)
    return arr


def main() -> int:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        # A measurement path that finds no chip fails: a CPU run is
        # never written under the name of a device metric.
        print(f"bench.py needs a TPU; found platform "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1

    from distributed_llm_dissemination_tpu.models.llama import CONFIGS

    total = CONFIGS["llama3-8b"].layer_nbytes()  # ~416 MiB
    frags = [
        (off, np.random.default_rng(i).integers(
            0, 256, size, dtype=np.uint8).tobytes())
        for i, (off, size) in enumerate(split_offsets(total, PARTS))
    ]

    # Raw host→device ceiling: bulk transfers of the same byte count,
    # PAIRED with the ingest trials below — the link's achievable rate
    # drifts, so neither a single upfront probe nor even independent
    # medians give a stable ratio.  Each trial times raw-then-ingest
    # back to back and link_fraction is the MEDIAN OF THE PER-PAIR
    # RATIOS: adjacent samples share the drift, so the ratio cancels it.
    bulk = np.frombuffer(b"".join(d for _, d in frags), np.uint8)

    def raw_once() -> float:
        t0 = time.monotonic()
        jax.block_until_ready(jax.device_put(bulk, devices[0]))
        return time.monotonic() - t0

    # Warm both paths (compiles the finalize splice on the stream arm;
    # first DMA maps buffers), then alternate timings.
    # The budget clock starts BEFORE the warmup: in a slow link phase the
    # warmup itself costs a pair's worth of transfers, and a budget that
    # ignored it could still blow a CI timeout.
    bench_t0 = time.monotonic()
    raw_once()
    arr = ingest_once(total, frags, devices)
    times, raw_times, ratios = [], [], []
    for _ in range(TRIALS):
        arr = None  # free the previous layer BEFORE probing: the raw
        # measurement must see the same clean device the ingest gets.
        rt = raw_once()
        raw_times.append(rt)
        t0 = time.monotonic()
        arr = ingest_once(total, frags, devices)
        it = time.monotonic() - t0
        times.append(it)
        ratios.append(rt / it)
        # Paired ratios are drift-immune, so 2 pairs already give a
        # usable median — stop once the wall-clock budget is spent.
        if (len(ratios) >= MIN_TRIALS
                and time.monotonic() - bench_t0 > BUDGET_S):
            break
    del arr
    raw_dma_gbps = total / statistics.median(raw_times) / 1e9

    gbps = total / statistics.median(times) / 1e9
    link_fraction = statistics.median(ratios)
    # Compiled-collective reuse across the trials: every ingest after the
    # warmup must HIT the executable cache (same tiling shape), which is
    # the amortization the device plane banks on at multi-layer scale.
    from distributed_llm_dissemination_tpu.parallel import plan_cache

    cache_stats = plan_cache.stats()
    print(
        json.dumps(
            {
                "metric": "llama3-8b layer dissemination ingest "
                f"(ShardedLayerIngest: {PARTS} flow-job fragments -> "
                f"{total >> 20} MiB layer in HBM, {len(devices)} device(s))",
                "value": round(gbps, 3),
                "unit": "GB/s/chip",
                "vs_baseline": round(gbps / BASELINE_GBPS, 3),
                "device": {"platform": devices[0].platform,
                           "kind": devices[0].device_kind,
                           "count": len(devices)},
                "harness_hash": _harness_hash(),
                "raw_dma_gbps": round(raw_dma_gbps, 3),
                # Absolute rates ride the drifting link, so their spread
                # is reported too — read `value` with it in hand (the
                # drift-immune number is link_fraction).
                "value_spread": [
                    round(total / max(times) / 1e9, 3),
                    round(total / min(times) / 1e9, 3)],
                "link_fraction": round(link_fraction, 3),
                "link_fraction_spread": [
                    round(min(ratios), 3), round(max(ratios), 3)],
                "collective_cache": cache_stats,
                "note": "absolute GB/s is bound by this host's measured "
                        "device link (raw_dma_gbps); link_fraction is the "
                        "framework's efficiency on it — the median of "
                        "per-trial raw/ingest pair ratios (pairing cancels "
                        "the link's bandwidth drift); >1 means the "
                        "fragment ingest beats a single bulk DMA of the "
                        "same bytes: the ingest streams per-fragment async "
                        "DMAs and splices on-device",
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
