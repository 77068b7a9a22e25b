"""Critical-path analysis over pair-lifecycle spans (docs/observability.md).

The telemetry plane answers "where did every byte go"; this module
answers "why did THIS delivery take THIS long".  Input is the merged
cluster span-event list (``utils/telemetry.fold_spans`` — each event a
``{"span", "phase", "t_ms", "node", ...}`` dict recorded where the
transition actually happened); output is

- per-span **phase chains** (``build_spans``): the last event per phase,
  clock-aligned when per-node offsets are supplied, with per-segment
  durations bucketed into the attribution vocabulary — ``queue``
  (planned→dispatched: command propagation + sender queueing), ``wire``
  (dispatched→wire-complete, first-byte latency included), ``verify``
  (→verified), ``stage`` (→staged), ``ack`` (→acked ack propagation +
  leader handling), ``flip`` (→flipped, swap/rollout pairs);
- the **critical chain** (``critical_chain``): walking back from the
  last-finishing span, each predecessor is the latest span finishing at
  or before the current one's start — the chain of blocking spans whose
  windows (plus the idle gaps between them, reported separately as the
  honest "unattributed" residual) tile the achieved TTD;
- the **attribution summary** (``analyze``): chain phase totals, the
  predicted-vs-achieved gap decomposed per phase and per link, and the
  reconciliation fraction (how much of the achieved TTD the chain's
  windows cover).

Phase names are the one canonical tuple ``telemetry.SPAN_PHASES``; the
tier-1 static drift check pins each to a live ``span_event`` call site.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from . import telemetry

# Re-exported so consumers (cli/trace.py flow arrows, the drift check)
# have one import for the vocabulary.
PHASES = telemetry.SPAN_PHASES

# segment = (from_phase, to_phase, attribution bucket)
SEGMENTS = (
    ("planned", "dispatched", "queue"),
    ("dispatched", "first_byte", "wire"),
    ("first_byte", "wire_complete", "wire"),
    ("wire_complete", "verified", "verify"),
    ("verified", "staged", "stage"),
    ("staged", "acked", "ack"),
    ("acked", "flipped", "flip"),
)

BUCKETS = ("queue", "wire", "verify", "stage", "ack", "flip")


def build_spans(events, offsets: Optional[dict] = None) -> Dict[str, dict]:
    """Events → ``{span: {"phases": {phase: t_ms}, ...attrs}}``.

    The LAST event per (span, phase) wins — a re-delivery (digest
    mismatch, salvage) overwrites its earlier attempt's timestamps,
    which is the honest reading: the chain then shows the attempt that
    actually completed.  ``offsets`` is the per-node clock-offset map
    (leader clock minus node clock, ms — the RUN_REPORT's
    ``clock_offsets_ms``); each event shifts by its recording node's
    offset so cross-node segments don't go negative on skewed hosts."""
    offsets = offsets or {}
    out: Dict[str, dict] = {}
    for ev in events or ():
        span = ev.get("span")
        phase = ev.get("phase")
        t = ev.get("t_ms")
        if not span or phase not in PHASES or not isinstance(
                t, (int, float)):
            continue
        t = float(t) + float(offsets.get(str(ev.get("node", "")), 0.0))
        rec = out.setdefault(str(span), {"phases": {}})
        rec["phases"][phase] = t
        for k in ("src", "dest", "layer", "job", "bytes", "codec",
                  "shard", "version", "parent"):
            if k in ev:
                rec[k] = ev[k]
    for span, rec in out.items():
        ph = rec["phases"]
        order = [p for p in PHASES if p in ph]
        if order:
            rec["start_ms"] = min(ph[p] for p in order)
            rec["end_ms"] = max(ph[p] for p in order)
        if "dest" not in rec or "layer" not in rec:
            # The deterministic id IS (dest, layer) — recover them for
            # events recorded without the fields.
            try:
                d, l = span.split(".", 1)
                rec.setdefault("dest", int(d))
                rec.setdefault("layer", int(l))
            except ValueError:
                pass
    return out


def phase_durations(rec: dict) -> Dict[str, float]:
    """One span's segment durations (seconds), bucketed.  Missing
    intermediate phases collapse: each present phase's segment runs
    from the PREVIOUS present phase, filed under the later phase's
    bucket — the chain's buckets always tile the span window exactly."""
    ph = rec.get("phases") or {}
    present = [p for p in PHASES if p in ph]
    out: Dict[str, float] = {}
    bucket_of = {to: b for _, to, b in SEGMENTS}
    for prev, cur in zip(present, present[1:]):
        dt = max(0.0, (ph[cur] - ph[prev]) / 1000.0)
        b = bucket_of.get(cur)
        if b is not None:
            out[b] = out.get(b, 0.0) + dt
    return out


def critical_chain(spans: Dict[str, dict],
                   terminal: str = "acked") -> List[str]:
    """The blocking chain, latest-first walk returned earliest-first.

    Anchor: the span whose ``terminal`` phase (falling back to its last
    present phase) is LATEST — the delivery that finished the run.
    Predecessor step: among spans ending at or before the current
    span's start, the one ending latest — the span whose completion
    unblocked (or most nearly abutted) the current one; ties break by
    span id for determinism.  Stops when no span ends earlier."""

    def end_of(rec):
        ph = rec.get("phases") or {}
        if terminal in ph:
            return ph[terminal]
        return rec.get("end_ms", float("-inf"))

    todo = {s: rec for s, rec in spans.items()
            if rec.get("phases") and rec.get("start_ms") is not None}
    if not todo:
        return []
    chain: List[str] = []
    cur = max(sorted(todo), key=lambda s: end_of(todo[s]))
    while cur is not None:
        chain.append(cur)
        start = todo[cur]["start_ms"]
        best, best_end = None, float("-inf")
        for s, rec in sorted(todo.items()):
            if s in chain:
                continue
            e = end_of(rec)
            if e <= start and e > best_end:
                best, best_end = s, e
        cur = best
    chain.reverse()
    return chain


def analyze(events, ttd_s: Optional[float] = None,
            predicted_s: Optional[float] = None,
            offsets: Optional[dict] = None,
            spans: Optional[Dict[str, dict]] = None) -> dict:
    """The full attribution: build spans, walk the chain, total the
    buckets, decompose the predicted-vs-achieved gap, split the wire
    time per link.  Returns a JSON-ready dict (the RUN_REPORT's
    ``critical_path`` section).  ``spans``: a prebuilt ``build_spans``
    table — callers that also render waterfalls pass it so the event
    list is grouped once, not twice."""
    if spans is None:
        spans = build_spans(events, offsets=offsets)
    chain_ids = critical_chain(spans)
    chain: List[dict] = []
    phase_totals: Dict[str, float] = {}
    per_link: Dict[str, float] = {}
    idle_s = 0.0
    prev_end = None
    for sid in chain_ids:
        rec = spans[sid]
        durs = phase_durations(rec)
        for b, v in durs.items():
            phase_totals[b] = phase_totals.get(b, 0.0) + v
        if "src" in rec and "dest" in rec:
            key = f"{rec['src']}->{rec['dest']}"
            per_link[key] = round(
                per_link.get(key, 0.0) + durs.get("wire", 0.0), 4)
        if prev_end is not None:
            idle_s += max(0.0, (rec["start_ms"] - prev_end) / 1000.0)
        prev_end = max(prev_end or rec["end_ms"], rec["end_ms"])
        chain.append({
            "span": sid,
            "dest": rec.get("dest"), "layer": rec.get("layer"),
            "src": rec.get("src"), "job": rec.get("job", ""),
            "start_ms": round(rec["start_ms"], 1),
            "end_ms": round(rec["end_ms"], 1),
            "phases_s": {b: round(v, 4) for b, v in sorted(durs.items())},
        })
    window_s = ((chain[-1]["end_ms"] - chain[0]["start_ms"]) / 1000.0
                if chain else 0.0)
    attributed_s = sum(phase_totals.values())
    out = {
        "spans_seen": len(spans),
        "chain": chain,
        "phase_totals_s": {b: round(phase_totals.get(b, 0.0), 4)
                           for b in BUCKETS if b in phase_totals},
        "idle_s": round(idle_s, 4),
        "window_s": round(window_s, 4),
        "attributed_s": round(attributed_s, 4),
        "per_link_wire_s": dict(sorted(per_link.items())),
    }
    if window_s > 0:
        # The honest residual: wall the chain's phases can't explain —
        # the idle gaps between chained spans (re-plan latency, solver
        # waits) — as a fraction of the chain window.
        out["unattributed_frac"] = round(
            max(0.0, window_s - attributed_s) / window_s, 4)
    if ttd_s:
        out["ttd_s"] = round(ttd_s, 4)
        out["coverage_frac"] = round(window_s / ttd_s, 4)
    if predicted_s is not None:
        out["predicted_s"] = round(predicted_s, 4)
        if ttd_s:
            out["gap_s"] = round(ttd_s - predicted_s, 4)
            # Decompose the gap: phases the model never priced, plus
            # the wire's own excess over the modeled transfer time,
            # plus inter-span idle.  Signed — a wire FASTER than
            # modeled shows as negative excess, honestly.
            gap = {b: round(phase_totals.get(b, 0.0), 4)
                   for b in BUCKETS
                   if b != "wire" and phase_totals.get(b)}
            gap["wire_excess"] = round(
                phase_totals.get("wire", 0.0) - predicted_s, 4)
            gap["idle"] = round(idle_s, 4)
            out["gap_attribution_s"] = gap
    return out


def waterfall_lines(spans: Dict[str, dict], width: int = 40,
                    limit: int = 24, job: Optional[str] = None
                    ) -> List[str]:
    """A fixed-width text waterfall (the per-job md rendering): one bar
    per span, offset/scaled to the observed window.  ``job`` filters to
    one dissemination job's spans ("" = the base run); ``limit`` keeps
    a fleet-scale run's table readable (dropped rows are announced)."""
    rows = [(sid, rec) for sid, rec in sorted(spans.items())
            if rec.get("start_ms") is not None
            and (job is None or rec.get("job", "") == job)]
    if not rows:
        return []
    t0 = min(rec["start_ms"] for _, rec in rows)
    t1 = max(rec["end_ms"] for _, rec in rows)
    span_ms = max(t1 - t0, 1e-9)
    rows.sort(key=lambda kv: (kv[1]["start_ms"], kv[0]))
    shown = rows[:max(1, int(limit))]
    lines = []
    for sid, rec in shown:
        lo = int((rec["start_ms"] - t0) / span_ms * width)
        hi = max(lo + 1, int((rec["end_ms"] - t0) / span_ms * width))
        bar = " " * lo + "#" * (hi - lo)
        dur = (rec["end_ms"] - rec["start_ms"]) / 1000.0
        lines.append(f"`{bar:<{width}}` {sid} "
                     f"({rec.get('src', '?')}→{rec.get('dest', '?')}, "
                     f"{dur:.3f}s)")
    if len(rows) > len(shown):
        lines.append(f"… {len(rows) - len(shown)} more spans not shown")
    return lines
