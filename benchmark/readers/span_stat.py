"""A statistic over the program's own interval spans of a round.

The program's entry points write their span ring out as their last log
records: ``"spans"`` records, each a list of ``{name, id, parent, t0, t1,
thread, node, fields}`` on CLOCK_MONOTONIC, and one ``"span counters"``
record (``utils/trace.dump_spans``).  This reader takes them from one
role's log.

``names``: the span names that count (none given: every span).
``nodes``: ``"dest"`` keeps the spans of the seats the traffic file marks
as destinations, and those that name no seat (a pod is one process with
one registry, so its spans carry ``node``).
``stat``:

- ``union``      seconds covered by at least one of the spans (elapsed);
- ``sum``        their durations added up (thread time: they overlap);
- ``count``      how many there are;
- ``median``     the median duration;
- ``field_sum``  their field ``field`` added up (a span without it adds
  nothing; no span with it gives ``None``);
- ``uncovered``  the seconds of ``window`` that NO span covers, the
  spans named in ``exclude`` left aside (wrappers that only wait).
  ``window`` is ``{"start": <key of the round's record>, "end_span":
  <span name>}``: from that CLOCK_MONOTONIC reading to the end of the
  last such span;
- ``counter``    the event counter ``counter`` of the ``"span counters"``
  record (0 where the record is there and the counter never fired).

A log without a ``"spans"`` record (a program from before the spans)
gives ``None``: the metric is left out of the line.  So does a dump whose
``"span counters"`` record says the ring ``dropped`` spans: a statistic
over a truncated window is not the round's (the counters are cumulative
and are still read).
"""

import statistics


def dest_nodes(traffic: dict) -> set:
    seats = traffic.get("seats")
    if isinstance(seats, int):  # a pod: seat 0 leads and only seeds
        return set(range(1, seats))
    return {s["id"] for s in seats or () if s.get("role") == "dest"}


def spans_of(ctx: dict, role: str, nodes=None):
    """The role's dumped spans (``None`` without a dump, or with one
    that lost spans), and its ``"span counters"`` record."""
    log = ctx["logs_by_role"].get(role, ())
    dumps = [r for r in log if r.get("message") == "spans"]
    counters = next((r for r in log
                     if r.get("message") == "span counters"), None)
    if not dumps or (counters or {}).get("dropped", 0) > 0:
        return None, counters
    spans = [s for r in dumps for s in r.get("spans", ())]
    if nodes == "dest":
        keep = dest_nodes(ctx["traffic"])
        spans = [s for s in spans
                 if s.get("node") is None or s["node"] in keep]
    return spans, counters


def union_s(intervals) -> float:
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted(intervals):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total


def read(ctx, role, stat, names=None, nodes=None, window=None,
         exclude=(), counter=None, field=None, scale=1.0):
    spans, counters = spans_of(ctx, role, nodes)
    if stat == "counter":
        if counters is None:
            return None
        return float(counters.get("counters", {}).get(counter, 0)) * scale
    if spans is None:
        return None
    if stat == "field_sum":
        vals = [s["fields"][field] for s in spans
                if s["name"] in names and field in s.get("fields", ())]
        return sum(vals) * scale if vals else None
    if stat == "uncovered":
        t0 = ctx["round"].get(window["start"])
        ends = [s["t1"] for s in spans if s["name"] == window["end_span"]]
        if t0 is None or not ends or max(ends) <= t0:
            return None
        t1 = max(ends)
        covered = union_s((max(s["t0"], t0), min(s["t1"], t1))
                          for s in spans if s["t1"] > t0 and s["t0"] < t1
                          and s["name"] not in exclude)
        return (t1 - t0 - covered) * scale
    mine = [(s["t0"], s["t1"]) for s in spans
            if names is None or s["name"] in names]
    if stat == "count":
        return float(len(mine)) * scale
    if not mine:
        return None
    if stat == "union":
        return union_s(mine) * scale
    if stat == "sum":
        return sum(t1 - t0 for t0, t1 in mine) * scale
    if stat == "median":
        return statistics.median(t1 - t0 for t0, t1 in mine) * scale
    raise ValueError(f"unknown stat {stat!r}")
