"""The program's own tracing: interval spans, counters, and their dump.

One primitive (docs/observability.md).  ``span`` times a block where the
work happens and files ``{name, id, parent, t0, t1, thread, node,
fields}`` in the run-scoped ring of ``utils/telemetry.py``; ``span_at``
files an interval whose ends the call site already holds (a wait that
began on another thread, a ``t0``/``dt`` pair).  ``t0``/``t1`` are
``time.monotonic()`` — one clock for every process of a host.  ``id`` is
the pair id ``telemetry.span_id(dest, layer)`` wherever a blob is
concerned (a request id for serve spans, a plan id for fabric spans), so
all spans of one blob share an identifier; ``parent`` names the span
that caused this one and defaults to the span open on this thread.

When ``jax`` is already imported in the process, a ``span`` also runs
inside ``jax.profiler.TraceAnnotation``: a flag check while no trace is
running, and during a profiler capture the program's spans land in the
trace's host plane on the same clock as the device planes.  (``span_at``
cannot: an annotation is opened and closed by the thread that runs it.)

``phase_totals()`` is every span of the run summed by name (the ring
summed by name, while the ring has dropped nothing); ``add_phase`` is
the older writer API and records a span too.  The entry points
(``cli.main``, ``cli.genreq``, ``cli.podrun.run_pod``) write the ring out
as their last log records with ``dump_spans``.
"""

from __future__ import annotations

import sys
import threading
import time

from . import telemetry as _telemetry

# At most this many spans in one ``"spans"`` log record.
DUMP_CHUNK = 256

_tls = threading.local()


def _annotation(name: str, span_id):
    """``jax.profiler.TraceAnnotation`` when jax is already imported (the
    leader and the seeders of a TCP topology never import it), else
    None."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    if span_id is None:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.TraceAnnotation(name, id=str(span_id))


class span:
    """Time a block as an interval span::

        with trace.span("ingest.finalize", id=pair, node=me) as sp:
            ...
            sp.set(bytes=n)

    A span opened inside another on the same thread takes that one's
    name as ``parent`` and inherits its ``id`` and ``node`` unless given
    its own.  The span is recorded even when the block raises (with
    ``error`` among its fields), so a trace shows failed work instead of
    omitting it."""

    __slots__ = ("rec", "_ann", "_outer")

    def __init__(self, name: str, id=None, parent=None, node=None,
                 **fields):
        self.rec = {"name": name, "id": id, "parent": parent,
                    "node": node, "fields": fields}

    def set(self, **fields) -> None:
        self.rec["fields"].update(fields)

    @property
    def seconds(self) -> float:
        """The span's length, once it has ended."""
        return self.rec["t1"] - self.rec["t0"]

    def __enter__(self) -> "span":
        rec = self.rec
        outer = self._outer = getattr(_tls, "open", None)
        if outer is not None:
            for key, inherited in (("parent", outer["name"]),
                                   ("id", outer["id"]),
                                   ("node", outer["node"])):
                if rec[key] is None:
                    rec[key] = inherited
        _tls.open = rec
        self._ann = _annotation(rec["name"], rec["id"])
        if self._ann is not None:
            self._ann.__enter__()
        rec["t0"] = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        rec = self.rec
        rec["t1"] = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _tls.open = self._outer
        if exc is not None:
            rec["fields"]["error"] = repr(exc)
        rec["thread"] = threading.current_thread().name
        _telemetry.record_span(rec)
        return False


def span_at(name: str, t0: float, t1: float, id=None, parent=None,
            node=None, **fields) -> None:
    """File an interval whose ends (``time.monotonic()``) the call site
    already holds."""
    _telemetry.record_span({
        "name": name, "id": id, "parent": parent, "node": node,
        "fields": fields, "t0": t0, "t1": t1,
        "thread": threading.current_thread().name})


def spans() -> list:
    """The ring's interval spans, oldest first."""
    return _telemetry.interval_spans()


def add_phase(name: str, seconds: float) -> None:
    """A duration whose start nobody kept, as a span ending now."""
    _telemetry.add_phase(name, seconds)


def phase_totals() -> dict:
    """``{name: {"ms": summed_milliseconds, "n": samples}}``: the ring's
    interval spans summed by name.  Thread time: concurrent spans
    overlap, so a total may exceed the run's wall clock."""
    return _telemetry.default().phase_totals()


def reset_phases() -> None:
    _telemetry.default().reset_phases()


# ------------------------------------------------------------ event counters
#
# In-process sums of EVENTS (docs/integrity.md and every plane since):
# CRC drops, NACKs, retransmitted bytes, device-path fallbacks, XLA
# compilations.  Stored in the run-scoped telemetry registry.


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the named event counter."""
    _telemetry.count(name, n)


def counter_totals() -> dict:
    """``{name: total}`` so far."""
    return _telemetry.default().counter_totals()


def reset_counters() -> None:
    _telemetry.default().reset_counters()


def reset_run() -> None:
    """Clear ALL run-scoped accounting (spans, counters, gauges,
    histograms, per-link flight recorder) — the between-runs reset."""
    _telemetry.reset_run()


# ------------------------------------------------------------- compilations

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "xla.cache_hits",
                 "/jax/compilation_cache/cache_misses": "xla.cache_misses"}
_watching = False


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        count("xla.compiles")
        count("xla.compile_ms", round(seconds * 1000))


def _on_event(event: str, **_kw) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        count(name)


def watch_compiles() -> None:
    """Count what XLA compiles in this process from here on, through
    ``jax.monitoring`` (once per process; imports jax).  ``xla.compiles``
    / ``xla.compile_ms``: programs that were in no in-memory cache and
    went to the backend (compiled, or read from the persistent cache),
    and the whole milliseconds that took;
    ``xla.cache_hits`` / ``xla.cache_misses``: which of the two."""
    global _watching
    if _watching:
        return
    _watching = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


# -------------------------------------------------------------------- dump


def dump_spans(log, since: float = None) -> int:
    """Write the ring out as log records: ``"spans"`` records of at most
    ``DUMP_CHUNK`` interval spans each, then one ``"span counters"``
    record (event counters, and among them the process's CPU since the
    registry's last ``reset_run()``, ``proc.cpu_ms`` / ``proc.cpu_sys_ms``,
    read now; how many spans the ring dropped — a dump with ``dropped``
    above 0 is a window, not the run — and one reading of both
    clocks).  ``since``: only spans that ended at or
    after that ``time.monotonic()`` (a process that never resets its
    registry between runs).  Returns how many spans were written."""
    out = []
    for rec in _telemetry.interval_spans():
        if since is not None and rec["t1"] < since:
            continue
        rec = {k: v for k, v in rec.items() if v not in (None, {})}
        rec["t0"], rec["t1"] = round(rec["t0"], 6), round(rec["t1"], 6)
        out.append(rec)
    parts = max(1, -(-len(out) // DUMP_CHUNK))
    for i in range(parts):
        log.info("spans", part=i + 1, of=parts,
                 spans=out[i * DUMP_CHUNK:(i + 1) * DUMP_CHUNK])
    counters = counter_totals()
    counters.update(_telemetry.default().proc_cpu())
    log.info("span counters", counters=counters, spans=len(out),
             dropped=counters.get("telemetry.intervals_dropped", 0),
             mono=round(time.monotonic(), 6),
             wall_ms=round(time.time() * 1000.0, 3))
    return len(out)
