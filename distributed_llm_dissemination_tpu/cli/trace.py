"""Export merged node logs as a Chrome/Perfetto trace.

The reference's only "trace viewer" is jq post-processing of merged JSON
logs (``/root/reference/conf/collect_logs.sh:14-16``); this tool turns
the same log stream into the Chrome Trace Event Format, so a whole
dissemination run — per-layer receives, per-job sends, solver time,
crashes, resume points — renders as a timeline in ``chrome://tracing``
or https://ui.perfetto.dev.

Mapping:
- one **process row per node** (the ``node`` field);
- log records carrying a duration (layer receives ``duration_ms``, job
  sends ``send_dur_ms``, flow solves ``computation_ms``) become complete
  ("X") slices, laid out on a per-layer track;
- lifecycle markers (timer start/stop, crash declarations, resume
  events) become instant ("i") events;
- reassembly progress (``layer fragment stored``) becomes a per-layer
  counter ("C") track.

- the entry points' ``"spans"`` dumps (``utils/trace.dump_spans``)
  become one slice per interval span on a per-thread track, placed on
  the wall clock by the ``"span counters"`` record's clock pair; the
  ``decode.stage`` spans' ``fast_bytes`` / ``slow_bytes``, the
  ``boot.assemble`` spans' ``kinds``, the ``serve.generate`` spans'
  ``moe_slots`` / ``moe_held`` / ``moe_touched`` and the
  ``fabric.publish`` spans' ``bytes`` / ``host_copy_bytes`` / ``pieces``
  are added up and printed on stderr, and beside them the counters
  ``wire.buf.reused_bytes`` / ``wire.buf.fresh_bytes`` and
  ``wire.pace.job_bytes`` / ``wire.pace.wait_ms`` of the
  ``"span counters"`` records (give one round's logs for the round's
  sum).

Usage:
    python -m distributed_llm_dissemination_tpu.cli.trace logs/ -o run.trace.json
    python -m ....trace merged.jsonl            # from collect_logs output
    python -m ....trace --xplane <profiler dir or .xplane.pb>

``--xplane`` reads a JAX profiler capture instead of logs: the program's
span annotations sit in its host plane on the same clock as the device
planes, so every idle gap of the device is split by what the host was
doing in it (``idle_gap_table``).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Iterable, List

from .collect_logs import iter_records

# The layers of the span vocabulary (docs/observability.md): an event of
# a profiler's host plane whose name starts with one of them is one of
# the program's spans.
SPAN_LAYERS = ("plan.", "wire.", "ingest.", "decode.", "boot.", "serve.",
               "fabric.")

# message -> (slice name, duration field)
_DURATION_RULES = {
    "(a fraction of) layer received": ("receive layer", "duration_ms"),
    "finished sending layer": ("send layer", "send_dur_ms"),
    "Job assignment completed": ("flow solve", "computation_ms"),
    "decoded tokens after boot": ("decode", "decode_ms"),
}

_INSTANT_MESSAGES = {
    "timer start",
    "timer stop: startup",
    "timer stop: first token",
    "node declared crashed",
    "declared-dead node announced again; reviving",
    "node re-announced; re-planning",
    "resuming partial layer",
    "restored partial layer from checkpoint",
    "steal a job",
    "job assignment",
    "job completed",
    "layer fully received",
    "received startup: ready",
    # Device data plane (fabric) + boot lifecycle:
    "pod fabric up",
    "dispatching device plan",
    "layer landed over device fabric",
    "layer assembled on host after fabric failure",
    "layer staged to HBM",
    "model booted from disseminated layers",
    "pipeline stage booted from disseminated layers",
    "released fabric upload cache",
    # Multi-controller fabric + serving lifecycle:
    "spmd fabric up",
    "spmd fabric plan cancelled",
    "spmd fabric stalled waiting for plan seq",
    "pod serve dispatched",
    "pod serve cancelled: pod no longer servable",
    "pod pipelined forward from staged weights",
    # Round 4: pod generation + topology planning markers.  (All three
    # solver variants are marked so comparing solver modes in a trace
    # never loses the event; the leader-level "Job assignment completed"
    # duration slice still carries the timing for every mode.)
    "pod decoded tokens from staged weights",
    "pod generated token ids",
    "job assignment calculated",
    "job assignment calculated (native)",
    "job assignment calculated (topology)",
    "job assignment calculated (topology LP)",
    "topology solve degraded to flat replan",
    # Fabric-assisted pod delivery (docs/fabric.md): the NIC shard
    # phase, the on-mesh reconstruction, and its degrade edges.
    "pod delivery planned",
    "pod shard published for on-mesh gather",
    "layer materialized from shards (on-mesh gather)",
    "pod delivery materialized full tree",
    "dispatching pod gather plan",
    "pod delivery degraded to host path",
    "pod gather timed out; degrading to host path",
    "pod member gone; degrading its pod to host path",
    # Intra-group chain dissemination (docs/hierarchy.md): the planned
    # member-to-member relay, its per-fragment forwards, and the two
    # repair edges (mid-chain NACK service, dead-hop redrive).
    "group chain planned",
    "chain forward roles installed",
    "relaying layer downstream",
    "NACK served from in-flight partial coverage",
    # Telemetry plane (docs/observability.md):
    "clock offset estimated",
    "cluster telemetry",
    # Causal observability (spans + fleet health + live job progress):
    "fleet health event",
    "fleet health timeline",
    "job progress",
}


def clock_offsets(records) -> dict:
    """Per-node clock offsets (leader clock MINUS node clock, ms) from
    the nodes' announce-time TimeSync estimates ("clock offset
    estimated" records, runtime/receiver.py).  A node that logged
    several (re-announce after a restart or takeover) keeps the LAST —
    its clock may have been corrected, and the most recent probe is the
    freshest estimate."""
    offsets: dict = {}
    for rec in records:
        if rec.get("message") == "clock offset estimated":
            off = rec.get("offset_ms")
            if isinstance(off, (int, float)):
                offsets[rec.get("node", "?")] = float(off)
    return offsets


def _layer_of(rec: dict):
    for key in ("layerID", "layer"):
        if key in rec:
            return rec[key]
    return None


def span_flow_events(records, offsets: dict) -> List[dict]:
    """Perfetto flow arrows from the pair-lifecycle span timeline
    (docs/observability.md): the LAST "cluster telemetry" dump carries
    the merged span events; each span becomes one flow chain — a thin
    anchor slice per phase on its recording node's row (named
    ``span <id> <phase>``) plus s/t/f flow events with the span id —
    so the leader's plan visibly arrows into the sender's dispatch and
    the dest's receive/verify/stage across process rows."""
    from ..utils.critical_path import PHASES

    spans_dump = None
    for rec in records:
        if rec.get("message") == "cluster telemetry" and rec.get("spans"):
            spans_dump = rec["spans"]  # last one wins (failover re-dump)
    if not spans_dump:
        return []
    by_span: dict = {}
    for ev in spans_dump:
        s, ph, t = ev.get("span"), ev.get("phase"), ev.get("t_ms")
        if not s or ph not in PHASES or not isinstance(t, (int, float)):
            continue
        by_span.setdefault(str(s), {})[ph] = ev
    events: List[dict] = []
    for flow_id, (span, phases) in enumerate(sorted(by_span.items()), 1):
        chain = [phases[p] for p in PHASES if p in phases]
        if len(chain) < 2:
            continue
        for k, ev in enumerate(chain):
            pid = str(ev.get("node", "?"))
            ts_us = (float(ev["t_ms"]) + offsets.get(pid, 0.0)) * 1000.0
            layer = ev.get("layer")
            tid = int(layer) if layer is not None else 0
            events.append({
                "ph": "X", "pid": pid, "tid": tid,
                "name": f"span {span} {ev['phase']}",
                "ts": ts_us, "dur": 100.0,  # 0.1 ms anchor slice
                "args": {k2: v for k2, v in ev.items()
                         if k2 not in ("t_ms",)},
            })
            flow_ph = ("s" if k == 0
                       else "f" if k == len(chain) - 1 else "t")
            events.append({
                "ph": flow_ph, "cat": "span", "id": flow_id,
                "pid": pid, "tid": tid, "name": f"span {span}",
                "ts": ts_us + 1.0,
                **({"bp": "e"} if flow_ph == "f" else {}),
            })
    return events


def interval_span_events(records, offsets: dict) -> List[dict]:
    """One slice per interval span of the nodes' ``"spans"`` dumps.  A
    span's ends are CLOCK_MONOTONIC; the same node's ``"span counters"``
    record read both clocks at once, which places them on the wall
    clock (then shifted like every other record of that node)."""
    wall_minus_mono = {}
    for rec in records:
        if (rec.get("message") == "span counters"
                and isinstance(rec.get("mono"), (int, float))
                and isinstance(rec.get("wall_ms"), (int, float))):
            wall_minus_mono[rec.get("node", "?")] = (
                rec["wall_ms"] - rec["mono"] * 1000.0)
    events: List[dict] = []
    for rec in records:
        pid = rec.get("node", "?")
        if rec.get("message") != "spans" or pid not in wall_minus_mono:
            continue
        shift = wall_minus_mono[pid] + offsets.get(pid, 0.0)
        for sp in rec.get("spans") or ():
            events.append({
                "ph": "X", "pid": pid, "tid": sp.get("thread", "spans"),
                "name": sp["name"],
                "ts": (sp["t0"] * 1000.0 + shift) * 1000.0,
                "dur": (sp["t1"] - sp["t0"]) * 1e6,
                "args": {k: v for k, v in sp.items()
                         if k not in ("name", "t0", "t1", "thread")},
            })
    return events


def _field_totals(events: List[dict], name: str, fields) -> dict:
    """The spans ``name`` that carry ``fields[0]``, counted, and each of
    ``fields`` added up over them.  Empty when no such span is there."""
    found = [ev["args"].get("fields") or {} for ev in events
             if ev.get("ph") == "X" and ev.get("name") == name]
    found = [f for f in found if fields[0] in f]
    if not found:
        return {}
    return {"spans": len(found),
            **{k: sum(f.get(k, 0) for f in found) for k in fields}}


def decode_widen_totals(events: List[dict]) -> dict:
    """How the logs' device decodes widened their bytes: the
    ``decode.stage`` slices' ``fast_bytes`` (the kernel) and
    ``slow_bytes`` (the strided slices) added up, over one round's logs
    the round's sum (``models/serde.py`` ``widen_split``).  Empty when
    no such span is there."""
    return _field_totals(events, "decode.stage",
                         ("fast_bytes", "slow_bytes"))


def fabric_publish_totals(events: List[dict]) -> dict:
    """What the logs' seeding seats published onto the device fabric: the
    ``fabric.publish`` slices' ``bytes``, ``host_copy_bytes`` (copied on
    the host before their upload: 0 where the host holds the layer) and
    ``pieces`` added up, over one round's logs the round's sum
    (``runtime/send.py`` ``contribute_device_plan``)."""
    return _field_totals(events, "fabric.publish",
                         ("bytes", "host_copy_bytes", "pieces"))


def assemble_totals(events: List[dict]) -> dict:
    """The stacks of parameters the logs' boots built: the
    ``boot.assemble`` slices' ``kinds`` added up — one stack a kind of
    layer held (``models/family.py``), so 1 a boot where every layer is
    alike."""
    return _field_totals(events, "boot.assemble", ("kinds",))


def routed_slot_totals(events: List[dict]) -> dict:
    """What the logs' served requests routed: the ``serve.generate``
    slices' ``moe_slots`` (positions x routed layers x top-k),
    ``moe_held`` (slots whose expert is held here) and ``moe_touched``
    (per routed layer and step, the distinct experts that got a slot:
    what a gathered dispatch would read; a family that does not count it
    adds nothing) added up.  Empty for a family that routes nothing."""
    return _field_totals(events, "serve.generate",
                         ("moe_slots", "moe_held", "moe_touched"))


def draft_totals(events: List[dict]) -> dict:
    """How the logs' served requests were decoded by draft and verify
    (``models/generate.py``): the ``serve.generate`` slices'
    ``decode_steps``, ``mtp_drafted`` (drafts put to the stack) and
    ``mtp_accepted`` (drafts whose second token was emitted) added up, so
    that steps + accepted + one first token a request are the tokens
    answered.  Empty for a family without a module that drafts."""
    return _field_totals(events, "serve.generate",
                         ("decode_steps", "mtp_drafted", "mtp_accepted"))


def _counter_totals(records: Iterable[dict], prefix: str, keys) -> dict:
    """The ``"span counters"`` records' counters ``<prefix><key>`` added
    up, over one round's logs the round's sums.  Empty when no log
    carries any of them."""
    totals = dict.fromkeys(keys, 0)
    for rec in records:
        if rec.get("message") == "span counters":
            for key in totals:
                totals[key] += (rec.get("counters") or {}).get(
                    prefix + key, 0)
    return totals if any(totals.values()) else {}


def recv_buffer_totals(records: Iterable[dict]) -> dict:
    """Where the logs' receivers got their reassembly buffers:
    ``wire.buf.reused_bytes`` (leased from a pool slab that lay free,
    already faulted) and ``wire.buf.fresh_bytes`` (mapped anew)
    (``utils/buffers.py``)."""
    return _counter_totals(records, "wire.buf.",
                           ("reused_bytes", "fresh_bytes"))


def job_pace_totals(records: Iterable[dict]) -> dict:
    """What the logs' senders put through a flow job's pacer:
    ``wire.pace.job_bytes`` and the milliseconds their threads slept in
    one, ``wire.pace.wait_ms`` — 0 while every job ran behind its plan
    (``utils/rate.JobPacer``)."""
    return _counter_totals(records, "wire.pace.", ("job_bytes", "wait_ms"))


def to_trace_events(records: Iterable[dict],
                    align_clocks: bool = True) -> List[dict]:
    """Chrome trace events from merged log records.

    ``align_clocks`` (default on) applies each node's announce-time
    clock-offset estimate ("clock offset estimated" records) to ALL of
    that node's timestamps, so multi-HOST timelines — where wall clocks
    can disagree by hundreds of ms — line up on the leader's clock
    instead of rendering receives before their sends.  Nodes without an
    estimate (the leader itself, pre-telemetry logs) pass through
    unshifted, which is exactly the old behavior."""
    records = list(records)
    offsets = clock_offsets(records) if align_clocks else {}
    # Flow arrows from the span timeline (docs/observability.md) ride
    # alongside the log-derived slices; same clock alignment.
    events: List[dict] = list(span_flow_events(records, offsets))
    events += interval_span_events(records, offsets)
    seen_pids = set()
    for rec in records:
        msg = rec.get("message")
        t = rec.get("time")
        if msg is None or not isinstance(t, (int, float)):
            continue
        pid = rec.get("node", "?")
        # offset = leader clock - node clock, so node time + offset is
        # the event on the LEADER's timeline.
        t = t + offsets.get(pid, 0.0)
        ts_us = t * 1000.0  # unix-ms -> µs
        layer = _layer_of(rec)
        tid = int(layer) if layer is not None else 0
        if pid not in seen_pids:
            seen_pids.add(pid)
            events.append({
                "ph": "M", "pid": pid, "name": "process_name",
                "args": {"name": f"node {pid}"},
            })

        # Known duration-carrying messages get curated slice names; any
        # other record with a duration_ms field becomes a slice named by
        # its message.
        rule = _DURATION_RULES.get(msg)
        if rule is None and isinstance(rec.get("duration_ms"), (int, float)):
            rule = (msg, "duration_ms")
        if rule is not None:
            name, dur_field = rule
            dur_ms = rec.get(dur_field)
            if isinstance(dur_ms, (int, float)):
                events.append({
                    "ph": "X",
                    "pid": pid,
                    "tid": tid,
                    "name": f"{name} {layer}" if layer is not None else name,
                    "ts": ts_us - dur_ms * 1000.0,  # log records the end
                    "dur": dur_ms * 1000.0,
                    "args": {k: v for k, v in rec.items()
                             if k not in ("message", "time", "level")},
                })
                continue
        if msg == "layer fragment stored":
            events.append({
                "ph": "C",
                "pid": pid,
                "name": f"layer {layer} bytes",
                "ts": ts_us,
                "args": {"received": rec.get("received", 0)},
            })
            continue
        if msg in _INSTANT_MESSAGES:
            events.append({
                "ph": "i",
                "pid": pid,
                "tid": tid,
                "name": msg,
                "ts": ts_us,
                "s": "p",  # process-scoped marker
                "args": {k: v for k, v in rec.items()
                         if k not in ("message", "time", "level")},
            })
    events.sort(key=lambda e: e.get("ts", 0))
    return events


# ------------------------------------------------ profiler capture → gaps


def _merged(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(merged: list, lo: float, hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


# Where a TPU capture holds the executed operations: the planes of the
# chips, and on each the lines of the ops (the other lines are steps,
# modules and name scopes that span them).
DEVICE_PLANE = "/device:TPU:"
OP_LINES = ("XLA Ops", "Async XLA Ops")


def idle_gap_table(planes, device_plane: str = DEVICE_PLANE,
                   op_lines=OP_LINES,
                   window_event: str = "", from_span: str = "",
                   to_span: str = "", top: int = 5) -> dict:
    """Split the device's idle gaps by the program's spans.

    ``planes``: ``[(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])]`` of one profiler capture.  Busy is the union of
    the operations on the ``device_plane`` planes' ``op_lines``; a gap
    is the rest of the window (``window_event``: a host annotation that
    brackets it, else first to last operation; ``from_span`` /
    ``to_span`` narrow it to the first start of one span name and the
    last end of another, which is how a phase of a cold start is cut
    out: ``wire.recv`` to ``ingest.ack`` is the delivery).  The program's spans
    are the host planes' events named in ``SPAN_LAYERS``.  For the
    ``top`` longest gaps and for all gaps together: the seconds each
    span name overlaps (spans overlap each other, so these may add up
    to more than the gap) and the seconds no span covers."""
    busy, spans, window = [], {}, None
    for pname, lines in planes:
        device = pname.startswith(device_plane)
        for lname, events in lines:
            if device and lname.split("/", 1)[0] in op_lines:
                busy += [(s, s + d) for _, s, d in events if d > 0]
                continue
            for name, s, d in events:
                if name.startswith(SPAN_LAYERS):
                    spans.setdefault(name, []).append((s, s + d))
                elif window_event and name == window_event:
                    window = (s, s + d)
    if not busy:
        raise SystemExit(f"no operation on any {device_plane}* plane")
    busy = _merged(busy)
    w0, w1 = window or (busy[0][0], busy[-1][1])
    if from_span in spans:
        w0 = min(s for s, _ in spans[from_span])
    if to_span in spans:
        w1 = max(e for _, e in spans[to_span])
    edges = [w0] + [x for s, e in busy for x in (max(s, w0), min(e, w1))
                    if s < w1 and e > w0] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    by_name = {name: _merged(iv) for name, iv in spans.items()}
    covered = _merged(iv for ivs in spans.values() for iv in ivs)

    def split(pieces) -> dict:
        row = {name: sum(_overlap(m, lo, hi) for lo, hi in pieces) * 1e-9
               for name, m in by_name.items()}
        row = {k: round(v, 6) for k, v in sorted(
            row.items(), key=lambda kv: -kv[1]) if v > 0}
        idle = sum(hi - lo for lo, hi in pieces)
        row["uncovered"] = round(
            (idle - sum(_overlap(covered, lo, hi) for lo, hi in pieces))
            * 1e-9, 6)
        return {"idle_s": round(idle * 1e-9, 6), "by_span_s": row}

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {"window_s": round((w1 - w0) * 1e-9, 6),
            "busy_s": round(_overlap(busy, w0, w1) * 1e-9, 6),
            "span_names": sorted(by_name),
            "all_gaps": split(gaps),
            "longest_gaps": [dict(start_s=round((lo - w0) * 1e-9, 6),
                                  **split([(lo, hi)]))
                             for lo, hi in longest]}


def load_xplane(path: str) -> list:
    """A profiler capture (its directory, or the ``.xplane.pb``) as
    ``idle_gap_table`` wants it."""
    import glob
    import os

    from jax.profiler import ProfileData

    if os.path.isdir(path):
        hits = sorted(glob.glob(os.path.join(
            path, "**", "*.xplane.pb"), recursive=True))
        if not hits:
            raise SystemExit(f"no .xplane.pb under {path}")
        path = hits[-1]
    return [(plane.name,
             [(line.name, [(e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events]) for line in plane.lines])
            for plane in ProfileData.from_file(path).planes]


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="trace", description=__doc__)
    p.add_argument("paths", nargs="*", help="log files or directories")
    p.add_argument("--xplane", default="",
                   help="a JAX profiler capture (directory or .xplane.pb):"
                        " print its idle gaps split by the program's "
                        "spans, as JSON, instead of converting logs")
    p.add_argument("--window", default="",
                   help="a host annotation that brackets the window "
                        "(default: first to last device operation)")
    p.add_argument("--from-span", default="",
                   help="start the window at this span's first start")
    p.add_argument("--to-span", default="",
                   help="end the window at this span's last end")
    p.add_argument("-o", "--output", default="-",
                   help="trace JSON output (default: stdout)")
    p.add_argument("--raw-clocks", action="store_true",
                   help="skip clock-offset correction (render each "
                        "node's timestamps as logged)")
    args = p.parse_args(argv)
    if args.xplane:
        json.dump(idle_gap_table(load_xplane(args.xplane),
                                 window_event=args.window,
                                 from_span=args.from_span,
                                 to_span=args.to_span),
                  sys.stdout, indent=1)
        print()
        return 0
    if not args.paths:
        p.error("give log paths, or --xplane")

    records = list(iter_records(args.paths))
    events = to_trace_events(records, align_clocks=not args.raw_clocks)
    leased = recv_buffer_totals(records)
    if leased:
        print("wire.buf leased {reused_bytes} B from slabs that lay free, "
              "mapped {fresh_bytes} B anew".format(**leased),
              file=sys.stderr)
    paced = job_pace_totals(records)
    if paced:
        print("wire.pace held {job_bytes} B to their jobs' plans, "
              "sending threads slept {wait_ms} ms in it".format(**paced),
              file=sys.stderr)
    widened = decode_widen_totals(events)
    if widened:
        print("decode.stage widened {fast_bytes} B with the kernel, "
              "{slow_bytes} B with the strided slices ({spans} spans)"
              .format(**widened), file=sys.stderr)
    assembled = assemble_totals(events)
    if assembled:
        print("boot.assemble built {kinds} stacks of parameters, one a "
              "kind of layer held ({spans} spans)".format(**assembled),
              file=sys.stderr)
    routed = routed_slot_totals(events)
    if routed:
        print("serve.generate routed {moe_slots} slots, {moe_held} of them "
              "to experts held here, over {moe_touched} expert-reads a "
              "gathered dispatch would make ({spans} spans)"
              .format(**routed), file=sys.stderr)
    drafted = draft_totals(events)
    if drafted:
        print("serve.generate decoded in {decode_steps} steps, "
              "{mtp_accepted} of {mtp_drafted} drafts accepted "
              "({spans} spans)".format(**drafted), file=sys.stderr)
    published = fabric_publish_totals(events)
    if published:
        print("fabric.publish put {bytes} B on the fabric in {pieces} "
              "pieces, {host_copy_bytes} B of them copied on the host "
              "first ({spans} spans)".format(**published), file=sys.stderr)
    doc = {"traceEvents": events, "displayTimeUnit": "ms"}
    if args.output == "-":
        json.dump(doc, sys.stdout)
    else:
        with open(args.output, "w") as f:
            json.dump(doc, f)
        print(f"{len(events)} trace events -> {args.output}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
