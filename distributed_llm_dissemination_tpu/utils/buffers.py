"""Receive-buffer allocation for the data plane.

``bytearray(n)`` zeroes its memory inside a single C call — for a
multi-hundred-MiB layer that is hundreds of milliseconds spent holding
the GIL, which starves every other thread in the node process (the
sender half of a relay, the control-plane loop) before the first byte is
even received.  ``np.empty`` returns unfaulted pages immediately; the
bytes are written exactly once by ``recv_into``/fragment writes, so the
zero-fill was pure waste.  The array supports the full buffer protocol
(slice assignment, ``memoryview``, ``bytes()``), so downstream LayerSrc
handling is unchanged.

Unfaulted pages are not free, though: their first write happens inside
``recv_into`` on the stripe threads, where the kernel maps, zeroes and
backs every page before the socket's bytes overwrite the zeroes — on
the v5e hosts ten times the CPU of the copy itself (PERF.md §6, PR 30).
So large buffers are LEASED from a process-wide pool of slabs that stay
mapped after their layer is dropped: a resident process pays the
faults of its first delivery and copies from then on.

A lease has no release call.  A reassembly buffer is read long after
its layer completes — ``memoryview`` slices in an ingest, views inside
an asynchronous ``jax.device_put``, the layer store, a ``jax.Array``
that adopted it on the CPU backend — so the slab goes back to the pool
when the last of them is gone, from a finalizer, and never sooner.
"""

from __future__ import annotations

import collections
import threading
import weakref

import numpy as np

from . import hostmem, trace

# numpy's own huge-page threshold: below it ``np.empty`` as ever.
POOL_MIN_BYTES = 4 << 20


class _Lease:
    """One lease of a slab: the object every view of the leased array
    keeps alive.  It exports the slab's bytes (``__buffer__``) and is no
    ndarray, so it sits at the bottom of every ``.base`` chain numpy
    builds however it collapses them, under every ``memoryview`` and
    every DLPack capsule; the pool hangs its finalizer here."""

    __slots__ = ("_view", "__weakref__")

    def __init__(self, view: memoryview):
        self._view = view

    def __buffer__(self, flags: int) -> memoryview:
        return self._view


class RecvPool:
    """Slabs of faulted host memory, leased by size.

    ``lease(n)`` takes a free slab of exactly ``n`` bytes, else the
    smallest free one that holds ``n`` and wastes at most an eighth of
    itself, else maps a fresh one exactly as ``hostmem.aligned_empty``
    does.  The pool never holds more than the most bytes that were out
    on lease at one time (``leased + free <= high water``): a returning
    slab over that is unmapped, and a miss unmaps free slabs, oldest
    first, until the fresh one fits under it — a host that switches
    models trades the old model's slabs for the new one's."""

    def __init__(self):
        self._lock = threading.Lock()
        self._free = []  # slabs, oldest first
        # Finalizers only append here (no lock: one can fire from a
        # collection that a line under ``_lock`` triggered); ``lease``
        # takes them in.
        self._returned = collections.deque()
        self._leased_bytes = 0
        self._high_water = 0

    def lease(self, n: int) -> np.ndarray:
        with self._lock:
            self._take_in_returned()
            at = self._pick(n)
            reused = at is not None
            slab = self._free.pop(at) if reused else hostmem.aligned_empty(n)
            self._leased_bytes += slab.nbytes
            self._high_water = max(self._high_water, self._leased_bytes)
            while self._free and (self._leased_bytes + self._free_bytes()
                                  > self._high_water):
                self._free.pop(0)
        trace.count("wire.buf.reused_bytes" if reused
                    else "wire.buf.fresh_bytes", n)
        lease = _Lease(memoryview(slab)[:n])
        weakref.finalize(lease, self._returned.append, slab)
        return np.frombuffer(lease, dtype=np.uint8)

    def _free_bytes(self) -> int:
        return sum(slab.nbytes for slab in self._free)

    def _pick(self, n: int):
        """Index of the free slab to lease for ``n`` bytes, or None."""
        best = None
        for at, slab in enumerate(self._free):
            if slab.nbytes == n:
                return at
            if (n < slab.nbytes <= n + slab.nbytes // 8
                    and (best is None
                         or slab.nbytes < self._free[best].nbytes)):
                best = at
        return best

    def _take_in_returned(self) -> None:
        while self._returned:
            slab = self._returned.popleft()
            self._leased_bytes -= slab.nbytes
            if (self._leased_bytes + self._free_bytes() + slab.nbytes
                    <= self._high_water):
                self._free.append(slab)

    def stats(self) -> dict:
        """What the pool holds now."""
        with self._lock:
            self._take_in_returned()
            return {"leased_bytes": self._leased_bytes,
                    "free_bytes": self._free_bytes(),
                    "free_slabs": len(self._free),
                    "high_water_bytes": self._high_water}

    def free_slabs(self) -> list:
        """The free slabs themselves, oldest first."""
        with self._lock:
            self._take_in_returned()
            return list(self._free)


_pool = RecvPool()  # process-wide: every receive path leases from it


def alloc_recv_buffer(n: int, sparse: bool = False) -> np.ndarray:
    """An n-byte write-once receive buffer (unzeroed, instant).

    Aligned (``hostmem.ALIGN``) so a completed reassembly buffer is
    directly adoptable as a CPU device array — the shared-buffer ingest
    then stages the layer with ZERO additional copies.

    ``sparse``: the buffer of a sharded holding, written only inside
    its shard's range; it keeps a mapping of its own whose other pages
    are never faulted, which a pool slab's all are."""
    if sparse or n < POOL_MIN_BYTES:
        return hostmem.aligned_empty(n)
    return _pool.lease(n)
