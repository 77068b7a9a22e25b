"""Multi-controller SPMD fabric: layer bytes over the device mesh when
every node is its OWN OS process (one per TPU host).

The single-controller ``FabricPlane`` (``parallel/fabric.py``) hands
device arrays between threads of one process.  On a real pod there is no
such process: each host runs its own controller (the reference's
per-host process model, ``/root/reference/cmd/main.go:113-146``), all of
them joined into one JAX runtime by ``parallel/multihost.py``.  Data can
then only move between hosts through a COLLECTIVE that every process
enters with the same program — the multi-controller discipline of
jax.distributed.

This module is that discipline applied to dissemination:

- The leader turns a scheduled transfer into a ``DevicePlanMsg`` carrying
  a global sequence number and broadcasts it to EVERY node (not just the
  participants — all processes must enter the collective).
- Each process runs one ``SpmdFabric`` executor thread that executes
  plans strictly in seq order.  For plan k, every process derives the
  SAME scope and slot assignment from the message alone: the collective
  runs on the SUB-MESH of the participating stages (the senders' stages
  ∪ the dest's stage), each layout entry landing on an unused device
  rank of its sender's stage within that scope.  A process with no
  device in the scope advances the seq WITHOUT entering any collective
  — so the layer replicates onto the participants only (a 2-stage
  transfer on a 32-stage pod pays a 2-stage gather, not a pod-wide
  one), and plans with disjoint participants genuinely overlap across
  the pod.
- Participants upload the byte ranges they own onto their own local
  devices, assemble the scoped sharded array, and enter one compiled
  gather (``collectives.gather_tiles_at``); the byte traffic rides ICI
  on real hardware.
- The plan's dest keeps its local copy (stage-replicated, exactly the
  ``-hbm`` terminal state); other participants drop theirs immediately.
- Execution is PIPELINED: the executor dispatches a plan's uploads and
  gather asynchronously and only blocks when a small in-flight window
  fills (or the queue idles), so plan k+1's host→device uploads overlap
  plan k's collective.  Per-process seq order — and therefore the
  cross-process enqueue order every pair of participants agrees on — is
  unchanged; a plan's result resolves only once its device work really
  finished, so a dest never acks bytes that could still fail.

An empty-layout plan is a CANCELLATION: the leader aborted dispatch
mid-broadcast, and every process advances past the seq without entering
a collective — a process that entered while another skipped would hang
the pod, so cancellation must be globally ordered too.

Failure domain: a process that never receives seq k stalls the fabric
(later plans queue behind it).  That is inherent to lockstep SPMD — the
control plane (ordered, retried TCP) is the reliability layer, and the
executor logs loudly when a gap persists past ``gap_timeout``.
"""

from __future__ import annotations

import collections
import os
import threading
from typing import Dict, List, Optional, Tuple

from ..utils.logging import log

PLAN_WAIT_S = 120.0  # dest-side wait for its plan's collective
# Dispatched-but-unretired plans (bounds device memory).  The window is
# BYTE-budgeted: many small plans pipeline deep (their cost is per-plan
# dispatch latency, which the window amortizes), while multi-GiB plans
# keep only as many gathers in flight as the budget allows — one rule
# instead of a small/large mode switch.  Window depth is a LOCAL pacing
# choice: it never changes the per-process enqueue order, so processes
# with different depths still interoperate.
MAX_INFLIGHT = 16            # hard cap on dispatched-but-unretired plans
MAX_INFLIGHT_SMALL = MAX_INFLIGHT  # retained alias (older tests/docs)
INFLIGHT_BYTE_BUDGET = int(os.environ.get(
    "DLD_INFLIGHT_BYTE_BUDGET", 1 << 30))


class PlanFailed(RuntimeError):
    pass


class _Result:
    """One plan's outcome: a device array (dest), None (cancelled /
    not-dest), or an exception."""

    def __init__(self):
        self.event = threading.Event()
        self.value = None
        self.error: Optional[BaseException] = None

    def resolve(self, value=None, error: Optional[BaseException] = None):
        self.value = value
        self.error = error
        self.event.set()

    def get(self, timeout: float):
        if not self.event.wait(timeout):
            raise PlanFailed(f"no collective result after {timeout}s")
        if self.error is not None:
            raise PlanFailed(str(self.error)) from self.error
        return self.value


class SpmdFabric:
    """Per-process executor of globally-ordered fabric plans.

    ``placement`` must cover every node (``parallel.mesh.fabric_placement``)
    and be identical on all processes (host-aligned device order makes it
    so).  ``bind_store(layers, lock)`` is called by the node constructor:
    the executor reads ONLY this node's own byte ranges through it."""

    kind = "spmd"

    def __init__(self, placement, my_node: int, gap_timeout: float = 60.0):
        stages = list(placement.node_to_stage.values())
        if len(set(stages)) != len(stages):
            # Two nodes (= two processes) sharing a stage means some
            # node's byte ranges would sit on another process's devices:
            # that process can't fill them, and the owner would raise
            # mid-lockstep while peers hang in the collective.  Refuse
            # deterministically at startup on EVERY process instead.
            raise ValueError(
                "spmd fabric needs one stage per node (one stage == one "
                f"host); got node_to_stage={placement.node_to_stage} — "
                "size the mesh pipeline axis to the node count"
            )
        self.placement = placement
        self.my_node = my_node
        self.gap_timeout = gap_timeout
        # Stall-recovery hook (set by the owning node): called with the
        # ascending list of MISSING seqs each time the executor sits a
        # full gap_timeout on a hole — the node reports them to the
        # leader (PlanResendReqMsg) so the lockstep self-heals instead
        # of relying on a human reading "stalled" logs.  Rate-limited
        # naturally: one call per gap_timeout window.
        self.on_gap = None
        self._layers = None
        self._layers_lock: Optional[threading.Lock] = None
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._pending: Dict[int, object] = {}  # seq -> DevicePlanMsg
        self._results: Dict[str, _Result] = {}  # plan_id -> result
        self._next_seq = 0
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="spmd-fabric", daemon=True
        )
        self._thread.start()

    # ------------------------------------------------------------- wiring

    def bind_store(self, layers, lock: threading.Lock) -> None:
        self._layers = layers
        self._layers_lock = lock

    def _read_span(self, layer_id: int, off: int, size: int) -> Optional[bytes]:
        if self._layers is None:
            return None
        with self._layers_lock:
            layer = self._layers.get(layer_id)
        if layer is None:
            return None
        return layer.read_span(off, size)

    # ------------------------------------------------------------ protocol

    def submit(self, msg) -> _Result:
        """Enqueue one plan (any role); returns its result handle.  The
        dest waits on it; everyone else may drop it.

        A CANCELLATION (empty layout) for a still-pending seq replaces the
        original: the leader cancels when its broadcast partially failed,
        and a process that kept the original would enter a collective some
        peer never will.  (A plan already being executed can no longer be
        cancelled — that residual window is part of the pod failure
        domain, see the module docstring.)"""
        with self._cond:
            if self._closed:
                raise PlanFailed("fabric closed")
            res = self._results.get(msg.plan_id)
            if res is None:
                res = self._results[msg.plan_id] = _Result()
            if msg.seq < self._next_seq:
                return res  # already executed (or executing)
            if msg.seq in self._pending:
                if not msg.layout:
                    self._pending[msg.seq] = msg  # cancel overrides
                return res
            self._pending[msg.seq] = msg
            self._cond.notify_all()
        return res

    def wait_result(self, res: _Result, base_timeout: float = PLAN_WAIT_S):
        """Dest-side wait that tolerates a deep queue: a fixed wall clock
        would spuriously fail a late-seq plan during a healthy large
        startup (k earlier plans each pay compile + upload + a pod-wide
        collective).  The timeout only counts windows WITHOUT progress —
        as long as the executor keeps retiring seqs, keep waiting."""
        while True:
            with self._lock:
                seen = self._next_seq
            try:
                return res.get(base_timeout)
            except PlanFailed:
                with self._lock:
                    progressed = self._next_seq > seen
                if not progressed:
                    raise

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout=5.0)

    # ------------------------------------------------------------ executor

    def _retire_oldest(self, inflight) -> None:
        """Block until the oldest dispatched plan's device work finished,
        then resolve its result — success and failure both surface HERE,
        so a dest only ever acks bytes that really landed."""
        import time as _time

        import jax

        from ..utils import trace

        plan_id, res, value, out, _sz, t0 = inflight.popleft()
        try:
            jax.block_until_ready(out)
        except Exception as e:  # noqa: BLE001 — resolve, don't die
            log.error("spmd fabric plan failed", plan=plan_id, err=repr(e))
            res.resolve(error=e)
            return
        trace.span_at("fabric.collective", t0, _time.monotonic(),
                      id=f"plan.{plan_id}", node=self.my_node, bytes=_sz)
        res.resolve(value=value)

    def _run(self) -> None:
        # (plan_id, result, dest value, gathered array, bytes)
        # dispatched but not yet known-finished.  The deque IS the
        # pipeline: dispatch runs ahead of completion by up to the
        # size-aware window (MAX_INFLIGHT, or MAX_INFLIGHT_SMALL when
        # everything in flight is small).
        inflight = collections.deque()
        while True:
            with self._cond:
                waited = self._cond.wait_for(
                    lambda: self._closed or self._next_seq in self._pending,
                    # With work in flight, don't sleep the whole gap:
                    # retire it while the queue is idle.
                    timeout=0.02 if inflight else self.gap_timeout,
                )
                if self._closed:
                    for res in self._results.values():
                        if not res.event.is_set():
                            res.resolve(error=PlanFailed("fabric closed"))
                    return
                msg = None
                stalled_on = sorted(self._pending) if self._pending else []
                if waited:
                    msg = self._pending.pop(self._next_seq)
                    self._next_seq += 1
                    # Kept (resolved) in _results so late duplicate
                    # deliveries get the settled handle instead of a
                    # dangling fresh one; the map grows by one small entry
                    # per plan per run.
                    res = self._results[msg.plan_id]
            if msg is None:
                if inflight:
                    self._retire_oldest(inflight)
                elif stalled_on:
                    # Later seqs queued behind a gap: the pod-wide
                    # lockstep is stalled.  Make it loud AND ask the
                    # control plane to heal it (on_gap → the leader
                    # re-sends its retained plan, or cancels the seq).
                    missing = sorted(
                        set(range(self._next_seq, max(stalled_on) + 1))
                        - set(stalled_on)
                    )
                    log.error(
                        "spmd fabric stalled waiting for plan seq",
                        next_seq=self._next_seq,
                        queued=stalled_on,
                        missing=missing,
                    )
                    hook = self.on_gap
                    if hook is not None and missing:
                        try:
                            hook(missing)
                        except Exception as e:  # noqa: BLE001 — advisory
                            log.error("on_gap hook failed", err=repr(e))
                continue
            try:
                value, out = self._execute(msg)
            except Exception as e:  # noqa: BLE001 — resolve, don't die
                log.error("spmd fabric plan failed", plan=msg.plan_id,
                          err=repr(e))
                res.resolve(error=e)
                continue
            if out is None:  # cancelled / not a participant: no device work
                res.resolve(value=value)
                continue
            import time as _time

            inflight.append((msg.plan_id, res, value, out, msg.total_size,
                             _time.monotonic()))
            # Byte-budgeted window: retire the oldest until the in-flight
            # set fits the budget (always keeping at least one dispatched
            # plan — a single over-budget plan still pipelines with the
            # next one's host staging) and the hard count cap.
            while (len(inflight) > MAX_INFLIGHT
                   or (sum(e[4] for e in inflight) > INFLIGHT_BYTE_BUDGET
                       and len(inflight) > 1)):
                self._retire_oldest(inflight)

    # ----------------------------------------------------------- collective

    def _plan_scope(self, msg) -> list:
        """The sub-mesh of a plan: the participating stages' devices (the
        senders' stages ∪ the dest's stage) in stage order — identical on
        every process (the placement is).  The collective runs on exactly
        these devices; everyone else sits the plan out."""
        stages = sorted(
            {self.placement.node_to_stage[s] for s, _, _ in msg.layout}
            | {self.placement.node_to_stage[msg.dest_id]}
        )
        return [d for st in stages for d in self.placement.stage_devices(st)]

    def _slot_assignment(
        self, layout: List[Tuple[int, int, int]], flat: list
    ) -> Tuple[Tuple[int, ...], Tuple[int, ...], Dict[int, Tuple[int, int, int]]]:
        """Deterministic (message-only) mapping of layout entries to device
        ranks WITHIN the plan's scope: each contribution lands on an unused
        device of its sender's stage.  Returns (sizes by rank, ranks in
        offset order, rank -> (sender, offset, size))."""
        rank_of = {id(d): i for i, d in enumerate(flat)}
        used: set = set()
        by_rank: Dict[int, Tuple[int, int, int]] = {}
        order: List[int] = []
        for sender, off, size in sorted(layout, key=lambda e: e[1]):
            stage_ranks = [rank_of[id(d)]
                           for d in self.placement.devices_for_node(sender)]
            free = [r for r in stage_ranks if r not in used]
            if not free:
                raise PlanFailed(
                    f"sender {sender} has more ranges than stage devices"
                )
            r = free[0]
            used.add(r)
            by_rank[r] = (sender, off, size)
            order.append(r)
        sizes = tuple(
            by_rank[r][2] if r in by_rank else 0 for r in range(len(flat))
        )
        return sizes, tuple(order), by_rank

    def _execute(self, msg):
        """Dispatch one plan's uploads + gather.  Returns (dest value,
        gathered array) — the array is a live device-work handle the
        caller retires later — or (None, None) when there is nothing to
        enter (cancellation, or this process is outside the scope)."""
        if not msg.layout:
            log.info("spmd fabric plan cancelled", plan=msg.plan_id)
            return None, None
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .collectives import gather_tiles_at
        from .ingest import flat_mesh

        flat = self._plan_scope(msg)
        proc = jax.process_index()
        if not any(d.process_index == proc for d in flat):
            # Out of scope: the participants' collective doesn't involve
            # this process's devices; just advance the seq.
            return None, None
        from .plan_cache import bucket_pad

        sizes, order, by_rank = self._slot_assignment(msg.layout, flat)
        total = sum(sizes)
        if total != msg.total_size:
            raise PlanFailed(
                f"layout covers {total} bytes, plan says {msg.total_size}"
            )
        # Bucketed tile pad: plans with near-equal splits reuse ONE
        # compiled gather (plan_cache) instead of compiling per layer.
        pad = bucket_pad(max(sizes))
        mesh = flat_mesh(flat, axis="fabric")

        # My ranges MUST sit on my local devices (one stage == one host
        # under the host-aligned order) — otherwise this process would
        # silently contribute zeros.  Checked before any device work so
        # the failure is loud, not corrupt.
        for rank, (sender, _, _) in by_rank.items():
            if sender == self.my_node and flat[rank].process_index != proc:
                raise PlanFailed(
                    f"my range's slot (rank {rank}) is not a local device; "
                    "placement is not host-aligned"
                )

        from ..utils import trace

        shards = []
        with trace.span("fabric.upload", id=f"plan.{msg.plan_id}",
                        node=self.my_node):
            for rank, dev in enumerate(flat):
                if dev.process_index != proc:
                    continue
                buf = np.zeros(pad, np.uint8)
                entry = by_rank.get(rank)
                if entry is not None and entry[0] == self.my_node:
                    _, off, size = entry
                    data = self._read_span(msg.layer_id, off, size)
                    if data is None:
                        raise PlanFailed(
                            f"no local bytes for layer {msg.layer_id}"
                        )
                    buf[:size] = np.frombuffer(data, np.uint8)
                shards.append(jax.device_put(buf, dev))

        v = jax.make_array_from_single_device_arrays(
            (len(flat) * pad,), NamedSharding(mesh, P("fabric")), shards
        )
        # NOT blocked here: the caller's in-flight window retires it, so
        # the next plan's uploads overlap this gather on the device queue.
        out = gather_tiles_at(mesh, "fabric", sizes, order, pad=pad)(v)
        # Pod-delivery reconstruction (docs/fabric.md): every node in the
        # advisory keep-list retains the gathered layer, not just the
        # nominal dest — one collective materializes the full tree on
        # ALL pod members.
        keepers = {msg.dest_id} | {int(n) for n in (msg.pod or ())}
        if self.my_node not in keepers:
            return None, out
        # Keep the LOCAL copy: the gather leaves the full layer replicated
        # on every scope device; this node's addressable shards are its
        # stage's devices (host-aligned order) — re-wrap them as a local
        # stage-replicated array, the -hbm terminal state.
        local_shards = [s.data for s in out.addressable_shards]
        stage = self.placement.node_to_stage[self.my_node]
        stage_mesh = self.placement.stage_mesh(stage)
        try:
            arr = jax.make_array_from_single_device_arrays(
                out.shape, NamedSharding(stage_mesh, P()), local_shards
            )
        except Exception:  # noqa: BLE001 — single-device copy still correct
            arr = local_shards[0]
        return arr, out
