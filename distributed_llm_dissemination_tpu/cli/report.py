"""One-command run report: ``RUN_REPORT.{json,md}`` (docs/observability.md).

Every run already emits the raw material — the leader's folded cluster
telemetry (``runtime/leader.cluster_telemetry``), the timer records, the
integrity/failover counters — but until now each harness hand-rolled its
own tables from ad-hoc greps.  This module is the ONE renderer: a typed
report dict with a provenance hash, built either

- **live**, from a leader object at the end of a run
  (``build_from_leader`` — the ``cli.main -report`` path; a promoted
  standby's adopted leader works identically, so a failover run still
  yields a complete report), or
- **offline**, from merged per-node JSON logs
  (``build_from_records`` — the ``python -m ...cli.report logs/`` path,
  reading the leader's end-of-run "cluster telemetry" dump).

The per-(src, dest) link table's ``delivered_bytes`` are the receiver
runtime's COMMITTED bytes (claims actually landed — duplicates count
nothing), so in a clean run they reconcile byte-exactly with the
delivered layer bytes of the goal state; the dual-backend test asserts
exactly that.

Usage:
    python -m distributed_llm_dissemination_tpu.cli.report logs/ -o RUN_REPORT
    python -m ...cli.main -id 0 -f conf.json -m 3 -report RUN_REPORT
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time
from typing import Iterable, List, Optional

from ..utils.provenance import harness_hash

SCHEMA = "dld-run-report/v1"

# Link-table column order (md rendering); missing fields render "—".
_LINK_COLS = (
    "delivered_bytes", "rx_bytes", "rx_frames", "rx_stripe_frames",
    "rx_placed_frames", "tx_bytes", "tx_frames", "tx_stripe_frames",
    "wire_s", "verify_s", "place_s",
    "crc_drops", "nacks", "retransmit_bytes",
)


def report_hash(report: dict) -> str:
    """Deterministic content hash of the report (minus the hash field
    itself) — the provenance stamp that ties event counts quoted
    elsewhere to exactly one report artifact."""
    doc = {k: v for k, v in report.items() if k != "provenance"}
    blob = json.dumps(doc, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _finish(report: dict) -> dict:
    report["provenance"] = report_hash(report)
    return report


def _split_counters(counters: dict) -> dict:
    """Group cluster counters by plane prefix (integrity./failover./
    telemetry.) — the report sections docs/integrity.md and
    docs/failover.md point their readers at."""
    out: dict = {"integrity": {}, "failover": {}, "telemetry": {},
                 "other": {}}
    for name, v in sorted((counters or {}).items()):
        plane, _, rest = name.partition(".")
        if plane in ("integrity", "failover", "telemetry") and rest:
            out[plane][rest] = v
        else:
            out["other"][name] = v
    return out


def _link_rows(links: dict) -> List[dict]:
    rows = []
    for key, fields in sorted(
            (links or {}).items(),
            key=lambda kv: (kv[1].get("src", 0), kv[1].get("dest", 0))):
        row = dict(fields)
        if "src" not in row or "dest" not in row:
            base, _, job = key.partition("#")
            try:
                s, d = base.split("->", 1)
                row["src"], row["dest"] = int(s), int(d)
            except ValueError:
                continue
            if job:
                row["job"] = job
        wire_s = row.get("wire_s") or 0.0
        delivered = row.get("delivered_bytes") or 0
        if wire_s > 0 and delivered:
            # Goodput over the link's summed wire-wait (thread-time:
            # concurrent stripes overlap, so this can exceed what one
            # socket could carry — that is the point of striping).
            row["wire_gbps"] = round(delivered / wire_s / 1e9, 3)
        rows.append(row)
    return rows


def build(cluster: dict, ttd_s: Optional[float] = None,
          ttft_s: Optional[float] = None,
          predicted_s: Optional[float] = None,
          solve_ms: Optional[float] = None,
          extra: Optional[dict] = None) -> dict:
    """Assemble the report from a folded cluster-telemetry table (the
    shape ``runtime/leader.cluster_telemetry`` returns)."""
    from ..utils import critical_path as cp

    nodes = cluster.get("nodes") or {}
    counters = cluster.get("counters") or {}
    offsets = {}
    phases: dict = {}
    threads_by_plane: dict = {}
    for node_id, snap in sorted(nodes.items(), key=lambda kv: str(kv[0])):
        gauges = snap.get("gauges") or {}
        if "clock_offset_ms" in gauges:
            offsets[str(node_id)] = gauges["clock_offset_ms"]
        for name, v in gauges.items():
            if name.startswith("phase."):
                phases.setdefault(str(node_id), {})[
                    name[len("phase."):]] = v
            elif name.startswith("threads_"):
                # Thread census (utils/threads.py): live thread counts
                # by plane per node — the audit trail that the bounded
                # data pools actually bound (docs/transport.md).
                threads_by_plane.setdefault(str(node_id), {})[
                    name[len("threads_"):]] = int(v)
    # Job plane (docs/service.md): rows tagged "src->dest#job" are the
    # per-job ADDITIVE split of the base rows — they render in their own
    # section so the base table still reconciles byte-exactly.
    all_rows = _link_rows(cluster.get("links") or {})
    base_rows = [r for r in all_rows if "job" not in r]
    job_rows: dict = {}
    for r in all_rows:
        if "job" in r:
            job_rows.setdefault(r["job"], []).append(r)
    report = {
        "schema": SCHEMA,
        "generated_unix_ms": int(time.time() * 1000),
        "harness_hash": harness_hash(),
        "ttd_s": round(ttd_s, 6) if ttd_s is not None else None,
        "ttft_s": round(ttft_s, 6) if ttft_s is not None else None,
        "predicted_s": (round(predicted_s, 6)
                        if predicted_s is not None else None),
        "solve_ms": round(solve_ms, 3) if solve_ms is not None else None,
        "links": base_rows,
        "job_links": job_rows,
        "counters": dict(sorted(counters.items())),
        "planes": _split_counters(counters),
        "phases_ms_by_node": phases,
        "threads_by_plane": threads_by_plane,
        "clock_offsets_ms": offsets,
        "nodes": {str(n): {"counters": snap.get("counters") or {},
                           "gauges": snap.get("gauges") or {}}
                  for n, snap in sorted(nodes.items(),
                                        key=lambda kv: str(kv[0]))},
    }
    # Causal observability (docs/observability.md): the merged span
    # timeline → the critical-path/attribution section + per-job
    # waterfalls; the leader-derived fleet health timeline verbatim.
    spans = cluster.get("spans") or []
    if spans:
        span_recs = cp.build_spans(spans, offsets=offsets)
        report["critical_path"] = cp.analyze(
            spans, ttd_s=ttd_s, predicted_s=predicted_s,
            offsets=offsets, spans=span_recs)
        jobs_seen = sorted({rec.get("job", "")
                            for rec in span_recs.values()})
        # Keyed by the job id VERBATIM ("" = the base run) — a job
        # literally named "base" must not collide with the base run's
        # waterfall; the renderer labels "" as "base run".
        report["span_waterfalls"] = {
            j: cp.waterfall_lines(span_recs, job=j) for j in jobs_seen}
    health = cluster.get("health") or {}
    if health.get("events") or health.get("intervals"):
        report["health"] = {
            "events": health.get("events") or [],
            "intervals": health.get("intervals") or [],
        }
    if extra:
        report.update(extra)
    return _finish(report)


def build_from_leader(leader, ttd_s: Optional[float] = None,
                      ttft_s: Optional[float] = None,
                      extra: Optional[dict] = None) -> dict:
    """The live path: fold the leader's cluster table now and stamp the
    run's headline timings.  Works on an ADOPTED leader too — the shadow
    replication carried the dead predecessor's table, and every live
    node's cumulative reports refreshed it since."""
    pred_ms = getattr(leader, "predicted_ttd_ms", 0)
    # Admitted-job table (docs/service.md): rides the report whenever
    # the leader ran as a service (empty single-run tables add nothing).
    jobs = getattr(leader, "jobs", None)
    table = jobs.table() if jobs is not None else {}
    if table:
        extra = dict(extra or {})
        extra.setdefault("jobs", table)
    # Per-dest wire-vs-decoded byte columns (docs/codec.md): the link
    # table reconciles against WIRE bytes; the decoded side is its own
    # column, never conflated.
    dest_fn = getattr(leader, "dest_bytes_table", None)
    if dest_fn is not None:
        dests = dest_fn()
        if dests:
            extra = dict(extra or {})
            extra.setdefault("dests", dests)
    return build(
        leader.cluster_telemetry(), ttd_s=ttd_s, ttft_s=ttft_s,
        predicted_s=(pred_ms / 1000.0) if pred_ms else None,
        solve_ms=getattr(leader, "solve_ms", 0.0) or None,
        extra=extra)


def build_from_records(records: Iterable[dict],
                       extra: Optional[dict] = None) -> dict:
    """The offline path: reconstruct the report from merged per-node
    JSON logs — the leader's end-of-run "cluster telemetry" dump (last
    one wins: a failover run's adopted leader re-dumps), the timer
    records, and each node's clock-offset estimate."""
    from .trace import clock_offsets

    records = list(records)
    cluster: dict = {"nodes": {}, "counters": {}, "links": {}}
    t_start = t_stop = None
    ttft_s = predicted_s = solve_ms = None
    # The one scanner of "clock offset estimated" records — shared with
    # the Perfetto aligner, so the record shape has a single consumer.
    offsets = {str(n): off for n, off in clock_offsets(records).items()}
    for rec in records:
        msg = rec.get("message")
        if msg == "cluster telemetry":
            links = rec.get("links") or {}
            counters = rec.get("counters") or {}
            gauges = rec.get("gauges") or {}
            cluster = {
                "nodes": {n: {"counters": {}, "gauges": g}
                          for n, g in gauges.items()},
                "counters": counters,
                "links": links,
                # The dump carries the merged span timeline + health
                # view (docs/observability.md) — the offline report's
                # critical-path and health sections read them back.
                "spans": rec.get("spans") or [],
                "health": rec.get("health") or {},
            }
        elif msg == "timer start":
            t_start = rec.get("time")
        elif msg == "timer stop: startup":
            t_stop = rec.get("time")
        elif msg == "timer stop: first token":
            ttft_s = rec.get("seconds")
        elif msg == "Predicted time to deliver":
            predicted_s = rec.get("seconds")
            solve_ms = rec.get("solve_ms")
    ttd_s = ((t_stop - t_start) / 1000.0
             if t_start is not None and t_stop is not None else None)
    for node, off in offsets.items():
        cluster["nodes"].setdefault(
            node, {"counters": {}, "gauges": {}})
        cluster["nodes"][node].setdefault("gauges", {})[
            "clock_offset_ms"] = off
    return build(cluster, ttd_s=ttd_s, ttft_s=ttft_s,
                 predicted_s=predicted_s, solve_ms=solve_ms, extra=extra)


# ------------------------------------------------------------- rendering


def _fmt(v) -> str:
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:g}"
    return str(v)


def _fmt_unit(v, unit: str) -> str:
    return "—" if v is None else f"{_fmt(v)}{unit}"


def render_md(report: dict) -> str:
    lines = [
        "# Run report",
        "",
        f"Schema `{report['schema']}` · harness `{report['harness_hash']}`"
        f" · provenance `{report.get('provenance', '?')}`",
        "",
        "| TTD | TTFT | predicted (mode 3) | solve |",
        "|---|---|---|---|",
        f"| {_fmt_unit(report.get('ttd_s'), 's')} "
        f"| {_fmt_unit(report.get('ttft_s'), 's')} "
        f"| {_fmt_unit(report.get('predicted_s'), 's')} "
        f"| {_fmt_unit(report.get('solve_ms'), 'ms')} |",
        "",
    ]
    links = report.get("links") or []
    if links:
        lines += [
            "## Per-link flight recorder",
            "",
            "`delivered` is the dest runtime's COMMITTED bytes (the "
            "byte-exact reconciliation number); `wire/verify/place` are "
            "the link's stall seconds (thread-time — concurrent stripes "
            "overlap); `stripe occupancy` is stripe frames over total "
            "frames on the tx side.",
            "",
            "| link | delivered | wire GB/s | rx frames (striped/placed)"
            " | tx frames (striped) | wire s | verify s | place s "
            "| drops | NACKs | retx bytes |",
            "|---|---|---|---|---|---|---|---|---|---|---|",
        ]
        for row in links:
            lines.append(
                f"| {row['src']}→{row['dest']} "
                f"| {_fmt(row.get('delivered_bytes'))} "
                f"| {_fmt(row.get('wire_gbps'))} "
                f"| {_fmt(row.get('rx_frames'))} "
                f"({_fmt(row.get('rx_stripe_frames', 0))}/"
                f"{_fmt(row.get('rx_placed_frames', 0))}) "
                f"| {_fmt(row.get('tx_frames'))} "
                f"({_fmt(row.get('tx_stripe_frames', 0))}) "
                f"| {_fmt(row.get('wire_s'))} "
                f"| {_fmt(row.get('verify_s'))} "
                f"| {_fmt(row.get('place_s'))} "
                f"| {_fmt(row.get('crc_drops', 0))} "
                f"| {_fmt(row.get('nacks', 0))} "
                f"| {_fmt(row.get('retransmit_bytes', 0))} |")
        lines.append("")
    dests = report.get("dests") or {}
    if dests:
        lines += [
            "## Per-dest wire vs decoded bytes (docs/codec.md)",
            "",
            "`wire` is what crossed the network for each delivered "
            "pair (the ENCODED size for quantized transfers — the "
            "column the link table reconciles against); `decoded` is "
            "what the dest materializes.  Two columns on purpose: the "
            "two are never conflated.",
            "",
            "| dest | wire bytes | decoded bytes | layers (quantized) |",
            "|---|---|---|---|",
        ]
        for dest, row in sorted(dests.items(), key=lambda kv: kv[0]):
            lines.append(
                f"| {dest} | {_fmt(row.get('wire_bytes'))} "
                f"| {_fmt(row.get('decoded_bytes'))} "
                f"| {_fmt(row.get('layers'))} "
                f"({_fmt(row.get('codec_layers', 0))}) |")
        lines.append("")
    jobs = report.get("jobs") or {}
    job_links = report.get("job_links") or {}
    if jobs or job_links:
        lines += [
            "## Dissemination jobs (docs/service.md)",
            "",
            "Per-job link rows are an ADDITIVE split of the base table "
            "above (frames serving a job file on both).",
            "",
        ]
        for jid, row in sorted(jobs.items()):
            lines.append(
                f"- `{jid}`: {row.get('State')} "
                f"(priority {row.get('Priority')}, kind "
                f"{row.get('Kind')}, {row.get('RemainingPairs')}/"
                f"{row.get('TotalPairs')} pairs remaining, "
                f"{row.get('ResolvedAtAdmit')} resolved at admit, "
                f"{row.get('DroppedPairs')} dropped)")
        for jid, rows in sorted(job_links.items()):
            delivered = sum(r.get("delivered_bytes") or 0 for r in rows)
            per = ", ".join(
                f"{r['src']}→{r['dest']}: "
                f"{_fmt(r.get('delivered_bytes', 0))}B"
                for r in rows)
            lines.append(f"- `{jid}` links ({delivered} B delivered): "
                         f"{per}")
        lines.append("")
    cp = report.get("critical_path") or {}
    if cp.get("chain"):
        lines += [
            "## Critical path (docs/observability.md)",
            "",
            "The chain of blocking delivery spans whose windows tile "
            "the achieved TTD; per-phase totals attribute the "
            "predicted-vs-achieved gap (`idle` is the honest residual "
            "— wall between chained spans no live span explains).",
            "",
            f"Window {_fmt_unit(cp.get('window_s'), 's')} over "
            f"{len(cp['chain'])} blocking span(s) of "
            f"{cp.get('spans_seen')} seen · attributed "
            f"{_fmt_unit(cp.get('attributed_s'), 's')} · idle "
            f"{_fmt_unit(cp.get('idle_s'), 's')} · TTD coverage "
            f"{_fmt(cp.get('coverage_frac'))} · unattributed frac "
            f"{_fmt(cp.get('unattributed_frac'))}",
            "",
            "| phase | seconds |",
            "|---|---|",
        ]
        for b, v in sorted((cp.get("phase_totals_s") or {}).items()):
            lines.append(f"| {b} | {_fmt(v)} |")
        lines.append("")
        gap = cp.get("gap_attribution_s") or {}
        if gap:
            lines += [
                f"Predicted {_fmt_unit(cp.get('predicted_s'), 's')} vs "
                f"achieved {_fmt_unit(cp.get('ttd_s'), 's')} — gap "
                f"{_fmt_unit(cp.get('gap_s'), 's')} decomposed: "
                + ", ".join(f"{k}={_fmt(v)}s"
                            for k, v in sorted(gap.items())),
                "",
            ]
        per_link = cp.get("per_link_wire_s") or {}
        if per_link:
            lines += ["Per-link wire seconds on the chain: "
                      + ", ".join(f"{k}: {_fmt(v)}s"
                                  for k, v in sorted(per_link.items())),
                      ""]
        for entry in cp["chain"]:
            ph = ", ".join(f"{k}={_fmt(v)}s"
                           for k, v in (entry.get("phases_s") or {}).items())
            lines.append(
                f"- span `{entry['span']}` "
                f"({_fmt(entry.get('src'))}→{_fmt(entry.get('dest'))}, "
                f"layer {_fmt(entry.get('layer'))}"
                + (f", job `{entry['job']}`" if entry.get("job") else "")
                + f"): {ph}")
        lines.append("")
    waterfalls = report.get("span_waterfalls") or {}
    for jname, rows in sorted(waterfalls.items()):
        if not rows:
            continue
        lines += [f"### Delivery waterfall — "
                  f"{f'job `{jname}`' if jname else 'base run'}",
                  ""]
        lines += [f"- {row}" for row in rows]
        lines.append("")
    health = report.get("health") or {}
    if health.get("events"):
        lines += [
            "## Fleet health timeline (docs/observability.md)",
            "",
            "Straggler/recovery events derived from per-interval "
            "deltas of the cumulative metrics reports, with onset "
            "timestamps (`-watch` printed these live).",
            "",
        ]
        for ev in health["events"]:
            lines.append(
                f"- t={_fmt(ev.get('t_ms'))}ms `{ev.get('kind')}` "
                f"link {ev.get('link')} achieved "
                f"{_fmt(ev.get('achieved_bps'))} B/s vs modeled "
                f"{_fmt(ev.get('modeled_bps'))} B/s "
                f"(frac {_fmt(ev.get('frac'))})")
        lines.append("")
    planes = report.get("planes") or {}
    for plane, doc in (("integrity", "docs/integrity.md"),
                       ("failover", "docs/failover.md")):
        counts = planes.get(plane) or {}
        if counts:
            lines += [f"## {plane.capitalize()} events ({doc})", ""]
            lines += [f"- `{k}`: {v}" for k, v in sorted(counts.items())]
            lines.append("")
    offsets = report.get("clock_offsets_ms") or {}
    if offsets:
        lines += [
            "## Clock offsets (leader clock minus node clock)",
            "",
            "Estimated at announce time from the TimeSync round trip; "
            "`cli/trace.py` applies these so multi-host Perfetto "
            "timelines line up.",
            "",
        ]
        lines += [f"- node {n}: {_fmt(v)} ms"
                  for n, v in sorted(offsets.items())]
        lines.append("")
    phases = report.get("phases_ms_by_node") or {}
    if phases:
        lines += ["## Phase totals by node (ms, thread-time sums)", ""]
        for node, per in sorted(phases.items()):
            items = ", ".join(f"{k}={_fmt(v)}"
                              for k, v in sorted(per.items()))
            lines.append(f"- node {node}: {items}")
        lines.append("")
    threads = report.get("threads_by_plane") or {}
    if threads:
        lines += [
            "## Threads by plane (live census at last report)",
            "",
            "Data-plane threads are bounded by the worker pools "
            "(utils/threads.py; docs/transport.md) — connection count "
            "never implies thread count.",
            "",
        ]
        for node, per in sorted(threads.items()):
            items = ", ".join(f"{k}={v}"
                              for k, v in sorted(per.items()))
            lines.append(f"- node {node}: {items}")
        lines.append("")
    other = (report.get("planes") or {}).get("other") or {}
    if other:
        lines += ["## Other counters", ""]
        lines += [f"- `{k}`: {v}" for k, v in sorted(other.items())]
        lines.append("")
    return "\n".join(lines)


def write_report(report: dict, out: str) -> dict:
    """Write ``<out>.json`` and ``<out>.md`` (an ``out`` ending in
    ``.json``/``.md`` is treated as the prefix; a directory gets
    ``RUN_REPORT`` inside it).  Returns {json, md, provenance}."""
    prefix = out
    if os.path.isdir(out):
        prefix = os.path.join(out, "RUN_REPORT")
    elif prefix.endswith((".json", ".md")):
        prefix = os.path.splitext(prefix)[0]
    json_path, md_path = prefix + ".json", prefix + ".md"
    with open(json_path, "w") as f:
        json.dump(report, f, indent=1)
    with open(md_path, "w") as f:
        f.write(render_md(report))
    return {"json": json_path, "md": md_path,
            "provenance": report.get("provenance")}


def main(argv: Optional[list] = None) -> int:
    p = argparse.ArgumentParser(prog="report", description=__doc__)
    p.add_argument("paths", nargs="+", help="log files or directories")
    p.add_argument("-o", "--output", default="RUN_REPORT",
                   help="output prefix (writes <prefix>.json and "
                        "<prefix>.md)")
    args = p.parse_args(argv)
    from .collect_logs import iter_records

    report = build_from_records(iter_records(args.paths))
    paths = write_report(report, args.output)
    print(f"run report -> {paths['json']} / {paths['md']} "
          f"(provenance {paths['provenance']})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
