"""Incremental sharded HBM ingest: fragments land on-mesh as they arrive.

The runtime-facing device plane for receivers.  The reference's terminal
state is host RAM (``/root/reference/distributor/node.go:435-446``); the
TPU-native terminal state is the layer replicated in the HBM of its
pipeline stage's devices.  The naive way to get there — assemble on host,
then ``device_put`` the full layer replicated — pays the host→device link
``layer_size × n_devices`` bytes and only starts after the last network
byte.  This module does it the TPU way, with a platform-split terminal
hop (the two physical situations want opposite designs):

- **Accelerator (stream)**: the layer's byte range is tiled across the
  stage's devices (the same offset/size shape as a mode-3 flow plan,
  flow.go:193-211); each arriving fragment is cut against that tiling and
  each piece is DMA'd to its device immediately as its OWN buffer
  (``jax.device_put`` is asynchronous, so piece k+1's host-side staging
  overlaps piece k's DMA — and all of it overlaps the network receive).
  ``finalize`` splices the pieces with one on-device concat per device —
  HBM-bandwidth work, negligible next to the host-link DMA — then one
  tiled ``all_gather`` replicates the layer across the stage over ICI.
  PCIe carries ``layer_size`` bytes exactly once, pipelined; no
  preallocated zero-fill, no per-piece read-modify-write of a big buffer.
- **CPU backend (host-accumulate)**: there is no host→device link —
  "device memory" IS host memory, so any ``device_put`` is pure-overhead
  copying (measured ~5× slower than a plain memcpy on the bench host).
  Fragments are memcpy'd into a preallocated 64-byte-aligned host buffer
  per span, and ``finalize`` adopts each buffer zero-copy as its device's
  array via DLPack (``utils.hostmem``).  The full layer materializes with
  ONE host memcpy total — faster than the naive bulk ``device_put`` of
  the same bytes.

``ingest_bytes`` is the one-shot form (whole buffer already on host) used
by mode-0/1/2 receivers; it routes through
``parallel.plan.execute_flow_plan`` with jobs synthesized by
``ops.reassembly.split_offsets`` — i.e. the dissemination runtime executes
its terminal hop as a flow plan on the mesh.
"""

from __future__ import annotations

import functools
import threading
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.reassembly import split_offsets
from ..sched.flow import FlowJob
from ..utils import hostmem, intervals, trace
from .collectives import gather_tiles, gather_tiles_batched
from .plan import execute_flow_plan
from .plan_cache import bucket_pad


def flat_mesh(devices: Sequence[jax.Device], axis: str = "ingest") -> Mesh:
    """A 1-axis mesh over an explicit device list (a stage's devices)."""
    return Mesh(np.asarray(list(devices), dtype=object), (axis,))


def synthesize_jobs(total_bytes: int, n: int, layer_id: int = 0) -> List[FlowJob]:
    """An even byte-range tiling of a layer as FlowJobs — the shape a mode-3
    plan has, for stages where the real per-seeder split isn't available."""
    return [
        FlowJob(sender_id=r, layer_id=layer_id, dest_id=0,
                data_size=size, offset=off)
        for r, (off, size) in enumerate(split_offsets(total_bytes, n))
        if size > 0
    ]


def ingest_bytes(data, devices: Sequence[jax.Device]) -> jax.Array:
    """One-shot sharded ingest: split ``data`` across ``devices`` (1/n of
    the host→device traffic each) and all-gather over ICI so the full
    layer lands replicated on all of them.  Returns a uint8 jax.Array.

    Single-CPU-device fast path: copy once into an aligned buffer and
    adopt it zero-copy (``utils.hostmem``) — a plain ``device_put`` here
    would memcpy the same bytes twice as slowly for no semantic gain."""
    data = memoryview(data)
    n = len(devices)
    if n == 1:
        if devices[0].platform == "cpu":
            buf = hostmem.aligned_empty(len(data))
            hostmem.copy_into(buf, 0, data)
            return hostmem.adopt_as_device_array(buf, devices[0])
        return jax.device_put(np.frombuffer(data, dtype=np.uint8), devices[0])
    if len(data) < n:
        # Too small to tile one byte per device; still must land replicated
        # on ALL the stage's devices (the documented contract).
        return jax.device_put(
            np.frombuffer(data, dtype=np.uint8),
            NamedSharding(flat_mesh(devices), P()),
        )
    mesh = flat_mesh(devices)
    jobs = synthesize_jobs(len(data), n)
    frags = [bytes(data[j.offset : j.offset + j.data_size]) for j in jobs]
    return execute_flow_plan(jobs, frags, mesh, "ingest", dtype=jnp.uint8)


def _concat_pad_impl(pieces, pad: int):
    """Splice offset-ordered pieces into one padded span buffer — a single
    compiled HBM-local concat (cached per piece-shape tuple, which repeats
    across a run's layers: every layer of a model shares its flow split)."""
    with jax.named_scope("ingest.splice"):
        buf = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
        if buf.shape[0] < pad:
            buf = jnp.pad(buf, (0, pad - buf.shape[0]))
    return buf


_concat_pad_impl.__name__ = "_concat_pad"  # keep the traced name
_concat_pad = functools.partial(
    jax.jit, static_argnames=("pad",))(_concat_pad_impl)
# (A donate_argnums twin was tried here and measured useless: XLA
# donation is input→output ALIASING, and no concat output can alias an
# input buffer — the warning fires and nothing frees early.  The early-
# free that does work is reference-dropping: _span_buffers re-points the
# retained piece lists at the spliced buffers, so the piece originals
# free the moment the splice retires instead of living until close.)


class ShardedLayerIngest:
    """Incremental device ingest of one layer onto a device set.

    Fragments arrive in any order with byte offsets (the mode-3 receive
    path, node.go:1520-1567); ``write`` lands each piece on its span's
    device immediately — overlapping HBM ingest with the network receive —
    and ``finalize`` runs the splice + gather once coverage is complete.

    Thread-safe: the receiver's handler pool may deliver fragments
    concurrently.  ``write`` CLAIMS its uncovered byte ranges under the
    lock before moving any bytes (so overlapping duplicates never copy
    twice, and concurrent writers can't both land the same range), then
    does the heavy byte movement outside the lock
    (``utils.intervals.ClaimedCoverage`` — a failed claim rolls its
    coverage back), and ``finalize`` blocks until coverage is complete
    AND no claim is outstanding — so a completion handler racing a
    sibling fragment handler can never splice a buffer with holes.

    Peak device footprint is ~2× the layer's span bytes during the splice
    (pieces + concat output), same order as the gather epilogue the
    multi-device path already pays; the piece originals free the moment
    the splice retires (``_span_buffers`` re-points their retained
    references at the spliced buffers) instead of living until close.
    """

    def __init__(self, total_bytes: int, devices: Sequence[jax.Device],
                 stream: Optional[bool] = None, trace_id=None, node=None):
        if total_bytes <= 0:
            raise ValueError("empty layer")
        self.total = total_bytes
        # What this ingest's spans (``ingest.write``,
        # ``ingest.finalize.*``) are filed under: the blob's pair id and
        # the seat, when the owner knows them.
        self.trace_id = trace_id
        self.node = node
        # Seconds ``_span_buffers`` spent blocked on coverage and
        # in-flight writes (no byte moves in them).
        self.waited_s = 0.0
        self.devices = list(devices)
        n = len(self.devices)
        # One span per device; spans differ by <=1 byte, buffers are padded
        # to the largest so the final gather is one tiled collective.
        self.spans: List[Tuple[int, int]] = list(split_offsets(total_bytes, n))
        self.pad = max(size for _, size in self.spans)
        # Gather pad: bucketed (plan_cache.bucket_pad) so near-equal
        # layers share ONE compiled gather executable.  Single-device
        # sets skip the gather entirely — their buffer must stay exactly
        # total-sized (zero-copy adoption depends on it).
        self.gpad = bucket_pad(self.pad) if n > 1 else self.pad
        # ``stream`` overrides the platform auto-split (None): tests and
        # CPU-mesh dryruns use it to exercise the accelerator arm.
        if stream is None:
            stream = not all(d.platform == "cpu" for d in self.devices)
        self._cpu = not stream
        self._lock = threading.Lock()
        self._complete = threading.Condition(self._lock)
        # Claim/commit coverage (shared discipline with the receiver's
        # fragment assembly): failed claims roll back, salvage reads only
        # committed ranges.
        self._cov = intervals.ClaimedCoverage()
        self._failed = False
        self._closed = False  # finalize/salvage ran: late writes no-op
        if self._cpu:
            # Host-accumulate (see module docstring).  gpad-sized so the
            # multi-device gather needs no reallocation; the tail past the
            # span's real size is never read (gather_tiles slices it off).
            self._host: Optional[List[np.ndarray]] = [
                hostmem.aligned_empty(self.gpad) for _ in range(n)
            ]
            self._pieces: Optional[List[List[Tuple[int, jax.Array]]]] = None
        else:
            self._host = None
            self._pieces = [[] for _ in range(n)]  # (local_off, piece)

    def share_host_buffer(self, buf) -> bool:
        """Adopt the caller's reassembly buffer as this ingest's (single)
        span buffer — the zero-copy CPU arm.

        When it succeeds, the caller's own assembly writes ARE the ingest
        (it reports them via :meth:`mark`), ``write`` is never needed,
        and ``finalize`` adopts the very same memory as the device array:
        the layer is staged with zero ingest-side copies.  Only valid on
        the CPU arm with one span (multi-span tilings place different
        byte ranges on different devices), with an adoptable buffer, and
        before any coverage landed.  Idempotent for the same buffer."""
        if not self._cpu or len(self.spans) != 1:
            return False
        with self._lock:
            if self._closed or self._failed:
                return False
            if self._host is not None and self._host[0] is buf:
                return True
            if self._cov.committed() or not self._cov.idle():
                return False  # bytes already landed in the old buffer
            if not (isinstance(buf, np.ndarray)
                    and hostmem.is_adoptable(buf)
                    and buf.nbytes == self.pad):
                return False
            self._host = [buf]
            return True

    def mark(self, offset: int, end: int) -> None:
        """Record externally-written coverage (shared-buffer mode): the
        caller already placed ``[offset, end)`` into the shared span
        buffer; only the coverage accounting remains."""
        with self._lock:
            if self._closed:
                return
            tok, _ = self._cov.claim(offset, end)
            if tok is not None:
                self._cov.commit(tok)
            if self._cov.idle():
                self._complete.notify_all()

    def write(self, offset: int, data) -> None:
        """Cut ``data`` (at absolute byte ``offset``) against the device
        tiling; move each piece toward its device's span.

        ``data`` is either a host buffer (bytes/bytearray/memoryview —
        the TCP receive path: pieces are host→device DMAs) or a 1-D uint8
        ``jax.Array`` already resident on some device (the pod-fabric
        path, ``parallel/fabric.py``: pieces are device→device transfers,
        which ride ICI on real hardware — the host link carries nothing)."""
        is_device = isinstance(data, jax.Array)
        if is_device:
            if data.ndim != 1 or data.dtype != np.uint8:
                raise ValueError("device fragments must be 1-D uint8")
            length = int(data.shape[0])
        else:
            data = memoryview(data)
            length = len(data)
        end = offset + length
        if offset < 0 or end > self.total:
            raise ValueError(
                f"fragment [{offset}, {end}) outside layer of {self.total} bytes"
            )
        with trace.span("ingest.write", id=self.trace_id, node=self.node,
                        offset=offset, bytes=length):
            self._write(offset, end, data, is_device)

    def _write(self, offset: int, end: int, data, is_device: bool) -> None:
        with self._lock:
            if self._closed:
                # A late duplicate racing finalize: its bytes are already
                # covered (finalize only runs at full coverage).
                return
            tok, claims = self._cov.claim(offset, end)
            if tok is None:
                return  # full duplicate — idempotent
        landed: List[Tuple[int, int, jax.Array]] = []
        try:
            for lo, hi in claims:
                for r, (s_off, s_size) in enumerate(self.spans):
                    a = max(lo, s_off)
                    b = min(hi, s_off + s_size)
                    if a >= b:
                        continue
                    if self._cpu:
                        src = data[a - offset : b - offset]
                        piece = (np.asarray(src) if is_device
                                 else np.frombuffer(src, np.uint8))
                        # Claimed ranges are exclusive: concurrent writers
                        # memcpy into disjoint slices, safely lock-free
                        # (memmove-grade, GIL released — hostmem).
                        hostmem.copy_into(self._host[r], a - s_off, piece)
                    else:
                        if is_device:
                            # a fragment whole inside this span goes as it
                            # is; a true sub-range is sliced on its source
                            src = (data if (a, b) == (offset, end)
                                   else data[a - offset : b - offset])
                        else:
                            src = np.frombuffer(
                                data[a - offset : b - offset], np.uint8)
                        landed.append(
                            (r, a - s_off,
                             jax.device_put(src, self.devices[r]))
                        )
        except Exception:
            with self._lock:
                # Roll the claim's coverage back (its bytes never landed —
                # salvage must not report them) and poison the ingest so
                # finalize falls back to bulk staging.
                self._cov.abort(tok)
                self._failed = True
                self._complete.notify_all()
            raise
        with self._lock:
            self._cov.commit(tok)
            if not self._closed and self._pieces is not None:
                for r, local_off, piece in landed:
                    self._pieces[r].append((local_off, piece))
            if self._cov.idle():
                # Wakes finalize (full coverage) and salvage (quiescence).
                self._complete.notify_all()

    def _quiesce(self, timeout: float = 30.0) -> None:
        """Wait until no write claim is in flight (test/diagnostic hook;
        does NOT wait for full coverage)."""
        with self._lock:
            self._complete.wait_for(self._cov.idle, timeout=timeout)

    def fail(self) -> None:
        """Mark the ingest broken (a device write failed); wakes any
        ``finalize`` waiter, which then raises so the caller falls back to
        bulk staging."""
        with self._lock:
            self._failed = True
            self._complete.notify_all()

    def salvage(self) -> List[Tuple[int, bytes]]:
        """Read the covered byte ranges back out of the span buffers —
        the escape hatch when the gather collective (or a later write)
        fails: everything successfully written is already staged, so a
        host-side fallback assembly needs no retained copies of the
        in-flight fragments.  Closes the ingest."""
        with self._lock:
            # Quiesce in-flight claims first: coverage is reserved BEFORE
            # bytes move, so reading mid-claim could return holes; a
            # claim still in flight past the timeout is excluded by
            # committed().
            self._complete.wait_for(self._cov.idle, timeout=30.0)
            self._closed = True
            covered = self._cov.committed()
            if self._cpu:
                out: List[Tuple[int, bytes]] = []
                for s, e in covered:
                    for r, (s_off, s_size) in enumerate(self.spans):
                        lo = max(s, s_off)
                        hi = min(e, s_off + s_size)
                        if lo < hi:
                            out.append((
                                lo,
                                self._host[r][lo - s_off : hi - s_off]
                                .tobytes(),
                            ))
                return out
            pieces = [sorted(p) for p in self._pieces]
        out = []
        for r, (s_off, s_size) in enumerate(self.spans):
            for local_off, piece in pieces[r]:
                data = jax.device_get(piece).tobytes()
                # Spliced pieces are gpad-padded past the span's real
                # size; the pad tail is not layer bytes.
                data = data[: max(0, s_size - local_off)]
                if data:
                    out.append((s_off + local_off, data))
        return out

    def _splice(self, r: int, pieces: List[Tuple[int, jax.Array]]) -> jax.Array:
        """One device's offset-ordered pieces → its padded span buffer.
        Full coverage + exclusive claims guarantee the pieces tile the
        span exactly, so this is a straight concat (+ tail pad)."""
        if not pieces:  # a zero-size span (more devices than bytes)
            with jax.default_device(self.devices[r]):
                return jnp.zeros(self.gpad, dtype=jnp.uint8)
        if len(pieces) == 1 and pieces[0][1].shape[0] == self.gpad:
            return pieces[0][1]  # whole span arrived as one piece: no copy
        return _concat_pad([p for _, p in pieces], self.gpad)

    def _span_buffers(self, timeout: float = 120.0) -> List[jax.Array]:
        """Block until coverage is complete, close the ingest, and return
        one gpad-sized device-resident span buffer per device — the
        staged halves of the terminal gather.  The shared head of
        ``finalize`` and ``finalize_many``."""
        with self._lock:
            with trace.span("ingest.finalize.wait", id=self.trace_id,
                            node=self.node) as waited:
                self._complete.wait_for(
                    lambda: self._failed or self._cov.complete(self.total),
                    timeout=timeout,
                )
            self.waited_s = waited.seconds
            self._closed = True  # any write from here on is a no-op
            if self._failed:
                raise RuntimeError("ingest failed; fall back to bulk staging")
            if not self._cov.complete(self.total):
                landed = intervals.covered(self._cov.committed())
                raise RuntimeError(
                    f"ingest incomplete after {timeout}s: "
                    f"{landed}/{self.total} bytes landed"
                )
            pieces = (None if self._pieces is None
                      else [sorted(p) for p in self._pieces])
        n = len(self.devices)
        if self._cpu:
            # Zero-copy adoption: the aligned host buffers BECOME the
            # device arrays (the write memcpy was the only byte movement).
            # _closed guarantees nothing writes the buffers ever again.
            return [hostmem.adopt_as_device_array(b, d)
                    for b, d in zip(self._host, self.devices)]
        with trace.span("ingest.finalize.splice", id=self.trace_id,
                        node=self.node):
            bufs = [self._splice(r, pieces[r]) for r in range(n)]
        with self._lock:
            # Early free: the piece originals are only retained for
            # salvage; the spliced buffers carry the same committed
            # bytes (salvage clamps their gpad tails to the span size),
            # so re-pointing releases the originals' device memory now
            # instead of at close.
            self._pieces = [[(0, b)] for b in bufs]
        return bufs

    def finalize(self, timeout: float = 120.0) -> jax.Array:
        """Splice the spans and (multi-device) all-gather them into the
        full layer, replicated on every device of the set.  Blocks until
        the ingest's own coverage is complete and no write is in flight.
        The returned array's device work may still be in flight — callers
        that must not ack unreal bytes block on it (or hand it to a
        ``fabric.PlanWindow``)."""
        bufs = self._span_buffers(timeout)
        n = len(self.devices)
        if n == 1:  # split_offsets(total, 1): pad == gpad == total
            return bufs[0]
        mesh = flat_mesh(self.devices)
        global_shape = (n * self.gpad,)
        v = jax.make_array_from_single_device_arrays(
            global_shape, NamedSharding(mesh, P("ingest")), bufs
        )
        sizes = tuple(size for _, size in self.spans)
        return gather_tiles(mesh, "ingest", sizes, pad=self.gpad)(v)


def finalize_many(ingests: Sequence["ShardedLayerIngest"],
                  timeout: float = 120.0) -> List[jax.Array]:
    """Plan batching at the terminal hop: K same-tiling ingests finish as
    ONE batched gather — one collective dispatch and one compiled
    executable for the whole batch, instead of K serial finalizes.

    All ingests must share the device set and span tiling (equal-size
    layers — the common mode-3 case; ``runtime/receiver.py`` groups them
    by the leader's batch hints).  Each device concatenates its K span
    buffers locally (HBM-bandwidth work) and the batched gather
    replicates every layer on every device.  Returns one replicated
    layer per ingest, in order; raises if any ingest failed or the
    tilings differ — the caller then falls back to per-plan finalize."""
    if not ingests:
        return []
    first = ingests[0]
    if len(ingests) == 1:
        return [first.finalize(timeout)]
    for ing in ingests[1:]:
        if (ing.devices != first.devices or ing.spans != first.spans
                or ing._cpu != first._cpu or ing.gpad != first.gpad):
            raise ValueError("batched ingests must share device tiling")
    n = len(first.devices)
    if n == 1:
        # No gather to batch: each finalize is already collective-free.
        return [ing.finalize(timeout) for ing in ingests]
    k = len(ingests)
    per_ingest = [ing._span_buffers(timeout) for ing in ingests]
    # Device-local stacking: K gpad-sized tiles back to back.  The
    # inputs are committed to device r, so the concat runs there.
    shards = [
        jnp.concatenate([per_ingest[i][r] for i in range(k)])
        for r in range(n)
    ]
    mesh = flat_mesh(first.devices)
    v = jax.make_array_from_single_device_arrays(
        (n * k * first.gpad,), NamedSharding(mesh, P("ingest")), shards
    )
    sizes = tuple(size for _, size in first.spans)
    out = gather_tiles_batched(
        mesh, "ingest", sizes, tuple(range(n)), k, pad=first.gpad
    )(v)
    return [out[i] for i in range(k)]


def hbm_headroom_bytes(device=None):
    """Free HBM on ``device`` (default: the first local device), or
    ``None`` when the platform doesn't report memory stats (CPU
    backend, some plugins).  The zero-downtime swap's staging policy
    reads this per layer (docs/swap.md): a v2 blob decodes straight
    into HBM only when the headroom comfortably covers it, and falls
    back to host-RAM staging when tight — ``None`` means "unknown",
    which callers treat per their own risk posture (the swap treats it
    as roomy: on the CPU backend device memory IS host memory)."""
    try:
        import jax

        d = device if device is not None else jax.devices()[0]
        stats = d.memory_stats()
        if not stats:
            return None
        limit = stats.get("bytes_limit")
        used = stats.get("bytes_in_use")
        if limit is None or used is None:
            return None
        return max(0, int(limit) - int(used))
    except Exception:  # noqa: BLE001 — a probe must never break staging
        return None
