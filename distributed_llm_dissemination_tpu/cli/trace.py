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
  ``moe_slots`` / ``moe_held`` / ``moe_touched`` / ``moe_rows`` and
  ``kv_rows`` / ``swa_evicted`` and the ``fabric.publish`` spans' ``bytes`` / ``host_copy_bytes`` / ``pieces``
  are added up and printed on stderr, and beside them the counters
  ``wire.buf.reused_bytes`` / ``wire.buf.fresh_bytes`` and
  ``wire.pace.job_bytes`` / ``wire.pace.wait_ms`` of the
  ``"span counters"`` records (give one round's logs for the round's
  sum); and one block, *the wire hop* (``wire_hop``): what the sending
  seats' ``wire.job`` / ``wire.fragment`` / ``wire.send`` /
  ``wire.send.write`` spans and the destination's ``wire.serve`` /
  ``wire.recv`` say of one delivery together, and each seat's
  ``proc.cpu_ms``.

Usage:
    python -m distributed_llm_dissemination_tpu.cli.trace logs/ -o run.trace.json
    python -m ....trace merged.jsonl            # from collect_logs output
    python -m ....trace --xplane <profiler dir or .xplane.pb> [logs...]

``--xplane`` reads a JAX profiler capture instead of logs: the program's
span annotations sit in its host plane on the same clock as the device
planes, so every idle gap of the device is split by what the host was
doing in it (``idle_gap_table``).  Only the process that holds the chip
is in a capture.  Given the round's seat logs beside it, the dumped
spans of the seats that are NOT in the capture (leader, seeder) are laid
onto the capture's clock and split the gaps like the annotations do:
the destination's spans are in both, so the offset between the two
clocks is measured (``clock_bridge``), printed, and refused when its
spread is over a millisecond.
"""

from __future__ import annotations

import argparse
import json
import statistics
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


def expert_row_totals(events: List[dict]) -> dict:
    """The expert rows the logs' served requests computed
    (``models/trinity.py``): the ``serve.generate`` slices' ``moe_rows``
    added up — per routed layer and call ``b·s·experts_held`` where every
    held expert ran over every position, the grouped loop's items times
    its rows an item where each ran over its own slots.  Empty for a
    family that counts none."""
    return _field_totals(events, "serve.generate", ("moe_rows",))


def cache_row_totals(events: List[dict]) -> dict:
    """What the logs' served requests kept of their positions
    (``models/trinity.py``): the ``serve.generate`` slices' ``kv_rows``
    (K/V rows the caches hold at a request's end, all layers) and
    ``swa_evicted`` (positions a sliding-window layer's ring wrote over
    or never kept) added up.  Empty for a family that counts neither."""
    return _field_totals(events, "serve.generate",
                         ("kv_rows", "swa_evicted"))


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

# ------------------------------------------------------------- the wire hop


def dumped_spans(records: Iterable[dict]) -> dict:
    """``{seat: [span, ...]}`` of the logs' ``"spans"`` records: a span
    belongs to the seat it names (``node``), else to its log's."""
    out: dict = {}
    for rec in records:
        if rec.get("message") == "spans":
            for sp in rec.get("spans") or ():
                out.setdefault(str(sp.get("node", rec.get("node", "?"))),
                               []).append(sp)
    return out


def _quantile(values, q: float) -> float:
    xs = sorted(values)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def _depth_seconds(intervals, w0: float, w1: float) -> dict:
    """The seconds of ``[w0, w1]`` with 0 / 1-3 / 4-7 / 8+ of
    ``intervals`` open at once."""
    out = {"0": 0.0, "1-3": 0.0, "4-7": 0.0, "8+": 0.0}
    edges = sorted(edge for t0, t1 in intervals if t1 > w0 and t0 < w1
                   for edge in ((max(t0, w0), 1), (min(t1, w1), -1)))
    depth, at = 0, w0
    for t, step in edges + [(w1, 0)]:
        key = ("0" if depth == 0 else "1-3" if depth < 4
               else "4-7" if depth < 8 else "8+")
        out[key] += t - at
        depth, at = depth + step, t
    return {k: round(v, 6) for k, v in out.items()}


def frames_by_key(spans, name: str) -> dict:
    """``{(id, offset): [span, ...]}`` of the spans ``name``, each list
    in order of start — the key a frame has on both ends of the hop
    (a retransmitted frame is there twice)."""
    out: dict = {}
    for sp in sorted(spans, key=lambda sp: sp["t0"]):
        fields = sp.get("fields") or {}
        if sp["name"] == name and "offset" in fields:
            out.setdefault((sp.get("id"), fields["offset"]), []).append(sp)
    return out


def hop_lags(send_spans, recv_spans) -> list:
    """Per frame, the receiving end's ``wire.recv`` start less the
    sending end's ``wire.send.write`` start, frames joined on (``id``,
    ``offset``); of a frame written more than once (a retry, a
    retransmit) the k-th write meets the k-th receive."""
    writes = frames_by_key(send_spans, "wire.send.write")
    recvs = frames_by_key(recv_spans, "wire.recv")
    return [r["t0"] - w["t0"] for key, ws in writes.items()
            for w, r in zip(ws, recvs.get(key, ()))]


def serve_self_split(served, children) -> dict:
    """Where a seat's ``wire.serve`` spans (``served``) spend what is
    not their ``children``'s: per frame, the span's start → its first
    ``wire.recv``'s start (``before_read``: pick-up, ``setblocking``,
    the stripe bookkeeping, the sink's claim) and its last child's end
    → its own end (``after_verify``: the telemetry row, the log line,
    the ``put``, the re-arm).  A frame's children are those on its
    thread inside its time; a frame that never read is left out."""
    kids: dict = {}
    for sp in children:
        kids.setdefault(sp.get("thread"), []).append(sp)
    before, after = [], []
    for sv in served:
        inner = [sp for sp in kids.get(sv.get("thread"), ())
                 if sv["t0"] <= sp["t0"] and sp["t1"] <= sv["t1"]]
        reads = [sp["t0"] for sp in inner if sp["name"] == "wire.recv"]
        if reads:
            before.append(min(reads) - sv["t0"])
            after.append(sv["t1"] - max(sp["t1"] for sp in inner))
    out = {}
    for key, xs in (("before_read", before), ("after_verify", after)):
        out[key + "_s"] = round(sum(xs), 6)
        out[key + "_ms"] = {
            "median": round(statistics.median(xs) * 1e3, 3) if xs else 0.0,
            "p90": round(_quantile(xs, 0.9) * 1e3, 3) if xs else 0.0}
    return out


def wire_hop(records: Iterable[dict]) -> dict:
    """What the dumps of one delivery say of the hop between a sending
    thread and a free receive thread (docs/observability.md): per
    ``wire.job`` the commanded and the achieved rate and where its
    threads' seconds went; how many frames were being written and read
    at once, over the delivery; the lag between a frame's first byte
    written and its first byte read; how full the receive pool ran and
    where its self time lies (``serve_self_split``); and every seat's
    CPU.  Empty without a ``wire.job`` span."""
    records = list(records)
    dumps = dumped_spans(records)
    spans = [sp for seat in dumps.values() for sp in seat]
    jobs = [sp for sp in spans if sp["name"] == "wire.job"]
    if not jobs:
        return {}

    def named(name, seat=None):
        return [sp for sp in (dumps.get(seat, ()) if seat else spans)
                if sp["name"] == name]

    def fsum(found, field):
        return round(sum((sp.get("fields") or {}).get(field, 0)
                         for sp in found), 6)

    def wall(found):
        return round(sum(sp["t1"] - sp["t0"] for sp in found), 6)

    serves = frames_by_key(spans, "wire.serve")
    rows = []
    for job in sorted(jobs, key=lambda sp: sp["t0"]):
        seat = str(job.get("node", "?"))

        def inside(name):
            return [sp for sp in named(name, seat)
                    if sp.get("id") == job.get("id")
                    and job["t0"] <= sp["t0"] and sp["t1"] <= job["t1"]]

        f = job.get("fields") or {}
        sends, writes = inside("wire.send"), inside("wire.send.write")
        served = [sv for key in frames_by_key(sends, "wire.send")
                  for sv in serves.get(key, ())]
        dur = max(job["t1"] - job["t0"], 1e-9)
        rows.append({
            "seat": seat, "id": job.get("id"), "job": f.get("job", ""),
            "bytes": f.get("bytes", 0),
            "commanded_mibps": round(f.get("rate", 0) / 2 ** 20, 1),
            "achieved_mibps": round(f.get("bytes", 0) / dur / 2 ** 20, 1),
            "fragments": f.get("fragments", 0), "frames": len(sends),
            "write_s": wall(writes), "write_cpu_s": fsum(writes, "cpu"),
            "barrier_s": fsum(inside("wire.fragment"), "barrier_s"),
            "send_queued_s": fsum(sends, "queued_s"),
            "serve_queued_s": fsum(served, "queued_s"),
            "pace_s": wall(inside("wire.pace")),
            "crc_s": fsum(sends, "crc_s")})
    writes, recvs = named("wire.send.write"), named("wire.recv")
    ends = writes + recvs
    w0 = min(sp["t0"] for sp in jobs + ends)
    w1 = max(sp["t1"] for sp in ends) if ends else max(
        sp["t1"] for sp in jobs)
    lags = hop_lags(writes, recvs)
    hop = {"jobs": rows, "delivery_s": round(w1 - w0, 6),
           "writing": _depth_seconds(
               [(sp["t0"], sp["t1"]) for sp in writes], w0, w1),
           "reading": _depth_seconds(
               [(sp["t0"], sp["t1"]) for sp in recvs], w0, w1),
           "frames_joined": len(lags), "frames_read": len(recvs)}
    if lags:
        hop["lag_ms"] = {"median": round(statistics.median(lags) * 1e3, 3),
                         "p90": round(_quantile(lags, 0.9) * 1e3, 3)}
    pools = {}
    for seat, mine in sorted(dumps.items()):
        served = [sp for sp in mine if sp["name"] == "wire.serve"]
        if not served:
            continue
        threads = len({sp.get("thread") for sp in served})
        inner = [sp for sp in mine if sp.get("parent") == "wire.serve"]
        pools[seat] = {
            "threads": threads, "frames": len(served),
            "busy_s": wall(served),
            "occupancy": round(wall(served)
                               / (threads * max(w1 - w0, 1e-9)), 4),
            "queued_s": fsum(served, "queued_s"),
            "self_s": round(wall(served) - wall(inner), 6),
            **serve_self_split(served, inner)}
    hop["receive_pools"] = pools
    hop["cpu_ms"] = {
        str(rec.get("node", "?")): {
            "cpu_ms": rec["counters"]["proc.cpu_ms"],
            "sys_ms": rec["counters"].get("proc.cpu_sys_ms", 0)}
        for rec in records if rec.get("message") == "span counters"
        and "proc.cpu_ms" in (rec.get("counters") or {})}
    return hop


def print_wire_hop(hop: dict, file) -> None:
    """``wire_hop``'s table as lines of text."""
    def say(line):
        print(line, file=file)

    say(f"the wire hop: {len(hop['jobs'])} jobs over "
        f"{hop['delivery_s']:.3f} s")
    for r in hop["jobs"]:
        say("  seat {seat} job {id}: {commanded_mibps} -> {achieved_mibps} "
            "MiB/s, {fragments} fragments, {frames} frames; threads' "
            "seconds: wire.send.write {write_s} (cpu {write_cpu_s}), "
            "barrier {barrier_s}, queued for data-tx {send_queued_s} / "
            "for data-rx {serve_queued_s}, wire.pace {pace_s}, checksum "
            "cpu {crc_s}".format(**r))
    for what, name in (("writing", "wire.send.write"),
                       ("reading", "wire.recv")):
        d = hop[what]
        say(f"  seconds with 0 / 1-3 / 4-7 / 8+ frames inside {name}: "
            f"{d['0']} / {d['1-3']} / {d['4-7']} / {d['8+']}")
    if "lag_ms" in hop:
        say("  wire.recv.t0 - wire.send.write.t0 over {n} of {m} frames: "
            "median {median} ms, p90 {p90} ms".format(
                n=hop["frames_joined"], m=hop["frames_read"],
                **hop["lag_ms"]))
    for seat, p in hop["receive_pools"].items():
        say("  seat {seat} receive pool: wire.serve {busy_s} s in {frames} "
            "frames on {threads} data-rx threads = occupancy {occupancy}; "
            "queued {queued_s} s, self time {self_s} s".format(
                seat=seat, **p))
        say("    of the self time, before the first wire.recv "
            "{before_read_s} s (a frame: median {b[median]} ms, p90 "
            "{b[p90]} ms), after the last child {after_verify_s} s "
            "(median {a[median]} ms, p90 {a[p90]} ms)".format(
                b=p["before_read_ms"], a=p["after_verify_ms"], **p))
    for seat, c in sorted(hop["cpu_ms"].items()):
        say(f"  seat {seat}: proc.cpu_ms {c['cpu_ms']} "
            f"(system {c['sys_ms']})")


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


def load_xplane(path: str, annotations: list = None) -> list:
    """A profiler capture (its directory, or the ``.xplane.pb``) as
    ``idle_gap_table`` wants it.  ``annotations``: a list that gets
    ``(name, id, start_ns)`` of every event that is one of the program's
    spans (``clock_bridge`` matches them with a dump)."""
    import glob
    import os

    from jax.profiler import ProfileData

    if os.path.isdir(path):
        hits = sorted(glob.glob(os.path.join(
            path, "**", "*.xplane.pb"), recursive=True))
        if not hits:
            raise SystemExit(f"no .xplane.pb under {path}")
        path = hits[-1]
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                events.append((e.name, float(e.start_ns),
                               float(e.duration_ns)))
                if annotations is not None and e.name.startswith(
                        SPAN_LAYERS):
                    annotations.append(
                        (e.name, dict(e.stats).get("id"),
                         float(e.start_ns)))
            lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes


def _id_key(span_id):
    """A span id as the profiler hands it back: ``"2.10"`` went in as
    text and comes out as the number 2.1."""
    try:
        return float(span_id)
    except (TypeError, ValueError):
        return span_id


# A bridge between the two clocks is refused over this spread.
BRIDGE_MAX_SPREAD_S = 1e-3


def clock_bridge(annotations: list, dumps: dict) -> dict:
    """The capture's clock less CLOCK_MONOTONIC, measured on the spans
    that are in both: a ``trace.span`` of the process that was captured
    is an annotation in the capture (``annotations``: ``(name, id,
    start_ns)``) and a record of its dump (``dumps``: ``dumped_spans``).
    The spans of one (name, id) are matched in order of their start
    where both sides have as many; ``offset_s`` is the median of
    ``annotation.start - dump.t0``, ``spread_s`` the distance between
    its quartiles, ``seats`` the seats that matched (they are in the
    capture), ``others`` those that did not."""
    starts: dict = {}
    for name, span_id, start_ns in annotations:
        starts.setdefault((name, _id_key(span_id)), []).append(start_ns)
    diffs, seats, others = [], [], []
    for seat, spans in sorted(dumps.items()):
        mine: dict = {}
        for sp in spans:
            mine.setdefault((sp["name"], _id_key(sp.get("id"))),
                            []).append(sp["t0"])
        found = [a * 1e-9 - t0 for key, t0s in mine.items()
                 if len(starts.get(key, ())) == len(t0s)
                 for a, t0 in zip(sorted(starts[key]), sorted(t0s))]
        (seats if found else others).append(seat)
        diffs += found
    if not diffs:
        raise SystemExit("no span of the logs is an annotation of the "
                         "capture: are they of the same round?")
    q1, _, q3 = (statistics.quantiles(diffs, n=4) if len(diffs) > 1
                 else (diffs[0],) * 3)
    bridge = {"offset_s": round(statistics.median(diffs), 9),
              "spread_s": round(q3 - q1, 9), "matched": len(diffs),
              "seats": seats, "others": others}
    if bridge["spread_s"] > BRIDGE_MAX_SPREAD_S:
        raise SystemExit(f"clock bridge refused: {bridge}")
    return bridge


def bridged_planes(planes: list, annotations: list, records) -> tuple:
    """``planes`` with one more plane a seat that is NOT in the capture:
    its dumped spans on the capture's clock.  Returns the planes and the
    bridge."""
    dumps = dumped_spans(records)
    bridge = clock_bridge(annotations, dumps)
    planes = list(planes)
    for seat in bridge["others"]:
        by_thread: dict = {}
        for sp in dumps[seat]:
            by_thread.setdefault(sp.get("thread", "spans"), []).append(
                (sp["name"], (sp["t0"] + bridge["offset_s"]) * 1e9,
                 (sp["t1"] - sp["t0"]) * 1e9))
        planes.append((f"/dump:seat {seat}", sorted(by_thread.items())))
    return planes, bridge


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="trace", description=__doc__)
    p.add_argument("paths", nargs="*",
                   help="log files or directories (with --xplane: the "
                        "same round's seat logs, whose spans join the "
                        "table)")
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
        annotations = [] if args.paths else None
        planes, bridge = load_xplane(args.xplane, annotations), None
        if args.paths:
            planes, bridge = bridged_planes(
                planes, annotations, iter_records(args.paths))
            print("clock bridge: capture - CLOCK_MONOTONIC = "
                  "{offset_s} s over {matched} spans of seats {seats}, "
                  "spread {spread_s} s; laid onto the capture's clock: "
                  "seats {others}".format(**bridge), file=sys.stderr)
        table = idle_gap_table(planes, window_event=args.window,
                               from_span=args.from_span,
                               to_span=args.to_span)
        if bridge:
            table["clock_bridge"] = bridge
        json.dump(table, sys.stdout, indent=1)
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
    hop = wire_hop(records)
    if hop:
        print_wire_hop(hop, sys.stderr)
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
        computed = expert_row_totals(events)
        computed = (f" ({computed['moe_rows']} expert rows computed)"
                    if computed else "")
        print("serve.generate routed {moe_slots} slots, {moe_held} of them "
              "to experts held here{computed}, over {moe_touched} "
              "expert-reads a gathered dispatch would make ({spans} spans)"
              .format(**routed, computed=computed), file=sys.stderr)
    rows = cache_row_totals(events)
    if rows:
        print("serve.generate left {kv_rows} K/V rows in its caches, "
              "{swa_evicted} positions written over in sliding-window "
              "rings ({spans} spans)".format(**rows), file=sys.stderr)
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
