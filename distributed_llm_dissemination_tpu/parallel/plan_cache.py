"""Compiled-collective executable cache: the device plane's warm path.

Every gather the dissemination runtime dispatches is an XLA program
compiled for one (mesh, axis, tile pad, batch) shape.  At physical layer
sizes the compile dominates the transfer (the recorded
``physical_4node_fabric`` row spent ~22x the TCP row's wall clock, most
of it per-plan compile + dispatch latency) — yet mode-3 tilings repeat
across a model's layers, so the same executable can serve every one of
them.  This module makes that reuse explicit and measurable:

- ``ExecutableCache``: a keyed LRU over built executables with hit/miss
  counters and cumulative build (compile) seconds, so a run can assert
  "compiled once, reused k times" instead of hoping.
- ``bucket_pad``: rounds a tile pad up to a small bucket set (top three
  significant bits, <=12.5% waste) so layers of *near*-equal size land
  on the same executable key instead of each compiling their own.

The idea is the reusable-collective-program framing of arXiv:2112.01075
(redistribution as a compiled, portable collective) applied to the
dissemination terminal hop.  ``collectives.gather_tiles_at`` routes its
gather programs through ``GATHER_CACHE`` and its splice programs through
``SPLICE_CACHE``; ``stats()`` aggregates both (``log_stats()`` is the
"collective cache stats" record of ``cli/main.py`` and ``cli/podrun.py``).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Hashable

from ..utils import trace
from ..utils.logging import log

# Pads below this round up to exactly this (one bucket for all tiny
# tiles — a 17-byte and a 40-byte control blob share one program).
_BUCKET_FLOOR = 64


def bucket_pad(pad: int) -> int:
    """Round ``pad`` up to the bucket set: the next value with at most
    the top FOUR bits significant (64, 68, ..., 128, 136, 144, ...).
    Guarantees <=12.5% padding waste while collapsing the unbounded
    space of layer sizes onto 16 buckets per power of two — distinct
    layers of near-equal size then reuse one compiled gather."""
    if pad <= _BUCKET_FLOOR:
        return _BUCKET_FLOOR
    granule = 1 << max(0, pad.bit_length() - 4)
    return -(-pad // granule) * granule


class ExecutableCache:
    """Keyed LRU over built executables, with reuse accounting.

    ``get(key, builder)`` returns the cached executable for ``key`` or
    builds (and times) it.  Builds run under the lock on purpose: two
    concurrent plans with the same shape must compile ONCE, not race two
    multi-second XLA compiles for the same program."""

    def __init__(self, kind: str, capacity: int = 128):
        self.kind = kind
        self.capacity = capacity
        self._lock = threading.RLock()
        self._store: Dict[Hashable, object] = {}  # insertion-ordered LRU
        self.hits = 0
        self.misses = 0
        self.build_s = 0.0

    def get(self, key: Hashable, builder: Callable[[], object]):
        with self._lock:
            if key in self._store:
                self.hits += 1
                self._store[key] = self._store.pop(key)  # LRU touch
                return self._store[key]
            self.misses += 1
            t0 = time.monotonic()
            with trace.span("fabric.compile", kind=self.kind):
                built = builder()
            dt = time.monotonic() - t0
            self.build_s += dt
            self._store[key] = built
            while len(self._store) > self.capacity:
                self._store.pop(next(iter(self._store)))
            log.debug("collective executable built", kind=self.kind,
                      compile_ms=round(dt * 1000, 1),
                      cached=len(self._store))
            return built

    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "compile_ms": round(self.build_s * 1000, 1)}

    def reset(self) -> None:
        """Drop entries AND counters (tests/benchmarks isolate runs)."""
        with self._lock:
            self._store.clear()
            self.hits = 0
            self.misses = 0
            self.build_s = 0.0


# The two program families of the terminal hop: the collective itself
# (expensive: shard_map + all_gather, keyed only by mesh/axis/pad/batch
# so bucketed same-shape plans share it) and the device-local re-splice
# (cheap, keyed by the exact tile sizes).
GATHER_CACHE = ExecutableCache("gather")
SPLICE_CACHE = ExecutableCache("splice")


def stats() -> dict:
    """Aggregate cache stats for harness reports: overall hits/misses/
    compile plus the per-family split."""
    g, s = GATHER_CACHE.stats(), SPLICE_CACHE.stats()
    return {
        "hits": g["hits"] + s["hits"],
        "misses": g["misses"] + s["misses"],
        "compile_ms": round(g["compile_ms"] + s["compile_ms"], 1),
        "gather": g,
        "splice": s,
    }


def reset_stats() -> None:
    GATHER_CACHE.reset()
    SPLICE_CACHE.reset()


def log_stats() -> None:
    """One structured record of the run's executable reuse — harnesses
    grep this to assert hits > misses on multi-layer rounds."""
    log.info("collective cache stats", **stats())
