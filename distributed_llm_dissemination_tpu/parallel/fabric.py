"""Pod-fabric data plane: layer bytes ride the device fabric, not TCP.

The north-star replacement for the reference's inter-node data plane
(``/root/reference/distributor/transport.go:267-274, 308-373``): when every
node of a topology is a stage of ONE device mesh (a TPU pod), a scheduled
layer transfer needs no socket stream at all.  The leader turns its plan
into a ``DevicePlanMsg`` — a small control message listing per-sender byte
ranges — and the bytes move as device traffic:

1. each *seeder* uploads exactly its planned byte range onto its own
   stage's devices (the host→HBM hop it would have paid to serve a TCP
   send anyway),
2. the *destination* pulls every contribution into its stage's shard
   buffers — a device-to-device transfer that rides ICI on real hardware —
   and one tiled all-gather replicates the finished layer within the stage
   (``parallel.ingest.ShardedLayerIngest`` fed device arrays).

TCP carries only the control plane (announce/plan/ack/startup), exactly
the split SURVEY §1 calls the key design idea to preserve.

``FabricPlane`` is the rendezvous between the two halves.  Under a single
controller (one process addressing the whole mesh — the virtual-device
test topology, or a single-process pod driver) it is an in-process
registry: publish/collect by plan id.  Under multi-controller SPMD the
same hand-off is the compiled collective itself (every process enters
``jax.jit`` with its local shards); that path needs ``jax.distributed``
mesh formation first (see ``parallel/multihost.py``) and is documented in
the README runbook rather than wired here.

A sender serving the same layer to two destinations publishes one
contribution per plan (each dest's plan has its own id) — the fabric
analogue of the reference opening one fresh connection per transfer
(transport.go:267-274).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Dict, Iterator, List, Tuple

from ..utils import intervals, trace
from ..utils.logging import log


class FabricPlane:
    """In-process publish/collect rendezvous for device-plan transfers.

    Contributions are ``(byte_offset, uint8 device array)`` pairs keyed by
    plan id.  ``collect`` yields them *as they arrive*, so a destination
    overlaps its ICI ingest with the senders' later host→HBM uploads — a
    sender publishes a range piece by piece — instead of waiting for the
    full set."""

    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        # plan_id -> list of (offset, device array)
        self._contribs: Dict[str, List[Tuple[int, object]]] = {}
        # plan_id -> last-publish monotonic time, for stale-plan GC (a plan
        # whose dest died would otherwise pin device buffers forever).
        self._touched: Dict[str, float] = {}
        # Pod-delivery shard board (docs/fabric.md): key -> {rank: bytes}.
        # Unlike ``_contribs`` (one consumer per plan), every pod member
        # reads the SAME shard set — entries are refcounted out by
        # ``pod_done`` (one call per member) instead of consumed by the
        # first collect.  This is the single-controller stand-in for the
        # ICI hop: each member's shard crosses process memory, never the
        # accounted NIC links.
        self._pod_parts: Dict[object, Dict[int, bytes]] = {}
        self._pod_done: Dict[object, set] = {}

    def publish(self, plan_id: str, offset: int, arr) -> None:
        """Sender side: register one device-resident byte-range fragment."""
        self.publish_all(plan_id, [(offset, arr)])

    def publish_all(self, plan_id: str, pieces) -> None:
        """Sender side: register ``(offset, device array)`` fragments —
        a range uploaded in pieces — with one wake-up of the waiting
        destinations, not one per piece."""
        with self._cond:
            self._contribs.setdefault(plan_id, []).extend(pieces)
            self._touched[plan_id] = time.monotonic()
            self._cond.notify_all()

    def collect(
        self, plan_id: str, nbytes: int, timeout: float = 120.0
    ) -> Iterator[Tuple[int, object]]:
        """Destination side: yield contributions as they arrive, until
        they cover ``nbytes`` bytes of the layer (the plan's layout says
        how many: a sender may publish a range as any number of pieces,
        so their count says nothing; a piece published twice covers its
        bytes once).

        Raises ``TimeoutError`` if the remaining bytes don't show up in
        time (a crashed seeder — the leader's failure detector will
        re-plan; the superseding plan has a fresh id).  The plan's entries
        are discarded once fully consumed; abandon via ``discard``."""
        got = 0
        have: list = []  # disjoint byte intervals yielded so far
        deadline = time.monotonic() + timeout
        while intervals.covered(have) < nbytes:
            with self._cond:
                while len(self._contribs.get(plan_id, ())) <= got:
                    left = deadline - time.monotonic()
                    if left <= 0:
                        raise TimeoutError(
                            f"plan {plan_id}: {intervals.covered(have)}/"
                            f"{nbytes} bytes contributed after {timeout}s"
                        )
                    self._cond.wait(left)
                fresh = list(self._contribs[plan_id][got:])
            for off, arr in fresh:
                yield off, arr
                got += 1
                have = intervals.insert(have, off, off + int(arr.shape[0]))
        self.discard(plan_id)

    def discard(self, plan_id: str) -> None:
        """Drop a plan's buffered contributions (frees their device
        arrays once the consumer releases its references)."""
        with self._cond:
            self._contribs.pop(plan_id, None)
            self._touched.pop(plan_id, None)

    # --------------------------------------------- pod shard board

    def pod_publish(self, key, rank: int, data) -> None:
        """Pod member side: register shard ``rank``'s wire bytes under
        ``key`` (one key per (layer, pod)).  Duplicates no-op — a
        re-plan re-completion must not flap the set."""
        with self._cond:
            parts = self._pod_parts.setdefault(key, {})
            if rank not in parts:
                parts[rank] = bytes(data)
            self._touched[("pod", key)] = time.monotonic()
            self._cond.notify_all()

    def pod_wait_new(self, key, have: int, timeout: float):
        """Block until the board holds MORE than ``have`` shards for
        ``key`` (any completion order), then return a snapshot of the
        full ``{rank: bytes}`` map; None on timeout.  Members drain the
        board incrementally: each new shard feeds ``submit_shard`` the
        moment it appears, so the gather fires on the last arrival."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while len(self._pod_parts.get(key) or ()) <= have:
                left = deadline - time.monotonic()
                if left <= 0:
                    return None
                self._cond.wait(left)
            return dict(self._pod_parts[key])

    def pod_done(self, key, members: int, who=None) -> None:
        """Member ``who`` finished gathering ``key``; the entry drops
        once ``members`` DISTINCT members have (a set, not a counter —
        one member's retry after a timeout must not double-count and
        drop the board under a slower member still draining it)."""
        with self._cond:
            done = self._pod_done.setdefault(key, set())
            done.add(who)
            if len(done) >= members:
                self._pod_parts.pop(key, None)
                self._pod_done.pop(key, None)
                self._touched.pop(("pod", key), None)

    def gc(self, max_age: float = 600.0) -> int:
        """Drop plans idle longer than ``max_age`` seconds; returns how
        many were dropped.  Cheap enough to call opportunistically."""
        cutoff = time.monotonic() - max_age
        with self._cond:
            stale = [p for p, ts in self._touched.items() if ts < cutoff]
            for p in stale:
                if isinstance(p, tuple) and p and p[0] == "pod":
                    self._pod_parts.pop(p[1], None)
                    self._pod_done.pop(p[1], None)
                else:
                    self._contribs.pop(p, None)
                self._touched.pop(p, None)
        return len(stale)

    def pending(self) -> int:
        with self._cond:
            return len(self._contribs)


class PlanWindow:
    """Full in-flight window over dispatched plan collectives.

    JAX dispatch is async, but the legacy dest path round-tripped per
    plan: dispatch the gather, ``block_until_ready``, ack, next plan —
    so plan k+1's host staging and uploads idled behind plan k's
    collective.  This window keeps up to ``max_plans`` (and
    ``byte_budget`` bytes) of dispatched collectives in flight: callers
    ``submit`` the un-blocked array with completion callbacks and move
    straight on to the next plan's staging; a retirement thread blocks
    on the OLDEST array and fires ``on_ready`` only once its device work
    really finished — an ack can never name bytes that might still
    fail.  ``submit`` blocks (backpressure) when the window is full, so
    device memory stays bounded.

    The collective wall time of each plan (submit → device-ready) is a
    ``fabric.collective`` span (``utils.trace``)."""

    def __init__(self, max_plans: int = 4, byte_budget: int = 2 << 30):
        self.max_plans = max(1, max_plans)
        self.byte_budget = byte_budget
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._q: collections.deque = collections.deque()
        self._bytes = 0
        self._retiring = False  # a popped entry's callback is running
        self._closed = False
        # ONE retirement thread, started eagerly: a lazy check-and-start
        # from submit() could race two first submitters into two threads
        # both retiring the same queue head (double-ack + a dropped
        # callback).  The window itself is created lazily by its owner,
        # so idle receivers never pay for the thread.
        self._thread = threading.Thread(
            target=self._run, name="plan-window", daemon=True
        )
        self._thread.start()

    def submit(self, label: str, arr, nbytes: int,
               on_ready: Callable, on_error: Callable) -> None:
        """Enqueue one dispatched collective; blocks while the window is
        full (the caller IS the backpressure point)."""
        with self._cond:
            self._cond.wait_for(
                lambda: self._closed
                or (len(self._q) < self.max_plans
                    and (self._bytes + nbytes <= self.byte_budget
                         or not self._q))
            )
            if self._closed:
                raise RuntimeError("plan window closed")
            self._q.append((label, arr, nbytes, on_ready, on_error,
                            time.monotonic()))
            self._bytes += nbytes
            self._cond.notify_all()

    def _run(self) -> None:
        import jax

        while True:
            with self._cond:
                self._cond.wait_for(lambda: self._q or self._closed)
                if not self._q and self._closed:
                    return
                label, arr, nbytes, on_ready, on_error, t0 = self._q[0]
            err = None
            try:
                # the retirement thread's share of ``fabric.collective``
                # (which starts at submit, on the submitter's thread)
                with trace.span("fabric.collective.wait",
                                id=f"plan.{label}"):
                    jax.block_until_ready(arr)
            except Exception as e:  # noqa: BLE001 — surface via callback
                err = e
            t1 = time.monotonic()
            dt = t1 - t0
            # submit → device-ready, ended by the block_until_ready above
            trace.span_at("fabric.collective", t0, t1, id=f"plan.{label}",
                          bytes=nbytes)
            with self._cond:
                # Popped for CAPACITY before the callback runs (the next
                # submit may proceed), but drain() also waits on
                # _retiring so "drained" really means the callback —
                # store + ack — finished, not just the pop.
                self._q.popleft()
                self._bytes -= nbytes
                self._retiring = True
                self._cond.notify_all()
            try:
                if err is None:
                    on_ready(arr, dt)
                else:
                    on_error(err)
            except Exception as e:  # noqa: BLE001 — a callback must not
                log.error("plan window callback failed", plan=label,
                          err=repr(e))  # kill the retirement loop
            finally:
                with self._cond:
                    self._retiring = False
                    self._cond.notify_all()

    def drain(self, timeout: float = 120.0) -> bool:
        """Block until every submitted plan retired — queue empty AND the
        last retirement's callback returned (tests/shutdown: an ack may
        ride that callback)."""
        with self._cond:
            return self._cond.wait_for(
                lambda: not self._q and not self._retiring, timeout=timeout)

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
