"""From the profiler's ``.xplane.pb`` to numbers: device busy time, the
operations that took most of it, module (jitted program) totals, and the
idle gaps attributed to what the host was doing.

Read with ``jax.profiler.ProfileData`` and nothing else.  A TPU trace has
one plane per chip, ``/device:TPU:<n>``, whose lines ``XLA Ops`` and
``Async XLA Ops`` hold one event per executed HLO operation (the second
the asynchronous copies, start to done) and ``XLA Modules`` one per
executed program (``jit__concat_pad(...)``).  An operation's event is
named by its whole HLO text, ``%fusion.63 = bf16[...] fusion(...)``; the
reduction keeps the instruction's name, ``fusion.63``.  Busy is the
union of the op intervals; idle is the rest of the window.  Event times are
nanoseconds on the trace's own clock; the harness wraps the traced round
in a ``TraceAnnotation`` whose start it also reads on CLOCK_MONOTONIC,
and that pair maps the host's phases onto the trace.

Which planes and lines are the devices' is data (``TPU``, the default).
The profiler writes a plane only for a chip that ran something: a pod's
leader chip, which only seeds bytes, has none (seen on the v5e).  So the
caller says how many chips hold a destination seat — each of those
ingests and boots — and a trace in which fewer chips than that ran an
operation inside the window is refused (``TraceError``): an idle share
is never made from nothing, and busy time is averaged over the chips
that worked.  The CPU rehearsals in ``tests/benchmark/`` bring a
selection of their own.
"""

from __future__ import annotations

import glob
import os

ANCHOR = "bench.round"
# The devices of a trace: planes by name prefix, one per chip; the lines
# of executed operations and of executed programs by name.
TPU = {"plane": "/device:TPU:", "ops": ["XLA Ops", "Async XLA Ops"],
       "modules": "XLA Modules", "plane_per_chip": True}


class TraceError(ValueError):
    pass


def find_trace(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str) -> list:
    """Planes as plain data: ``[{name, lines: [{name, events: [(name,
    start_ns, duration_ns)]}]}]``."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: list) -> list:
    """Merged ``[(start, end)]`` of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def op_name(event_name: str) -> str:
    """``%fusion.63 = bf16[...] fusion(...)`` → ``fusion.63``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def _device_lines(planes: list, select: dict) -> list:
    """``[(ops events, modules events)]`` per selected plane.  A line is
    selected by its name, or by its name before a ``/<thread id>``."""
    def named(line, names):
        return line["name"].split("/", 1)[0] in names

    out = []
    for p in planes:
        if not p["name"].startswith(select["plane"]):
            continue
        ops = [(op_name(n), s, d) for l in p["lines"]
               if named(l, select["ops"])
               for n, s, d in l["events"] if d > 0]
        mods = [e for l in p["lines"] if named(l, [select["modules"]])
                for e in l["events"]]
        out.append((ops, mods))
    return out


def anchor(planes: list):
    """``(start_ns, duration_ns)`` of the harness's annotation."""
    for p in planes:
        for l in p["lines"]:
            for name, s, d in l["events"]:
                if name == ANCHOR:
                    return s, d
    return None


def reduce(planes: list, phases: list = None, anchor_mono: float = None,
           working: int = 1, select: dict = None) -> dict:
    """The numbers of one traced window of a run in which ``working``
    chips hold a destination seat.

    ``phases``: ``[(name, t0, t1)]`` on CLOCK_MONOTONIC (seconds);
    ``anchor_mono``: that clock's reading when the annotation began.
    """
    select = select or TPU
    devices = _device_lines(planes, select)
    need = working if select["plane_per_chip"] else 1
    if len(devices) < need:
        raise TraceError(
            f"the trace holds {len(devices)} {select['plane']}* plane(s), "
            f"{need} chip(s) worked; planes: {[p['name'] for p in planes]}")
    mark = anchor(planes)
    every = [(s, s + d) for ops, _ in devices for _, s, d in ops]
    if not every:
        raise TraceError(f"no operation on any {select['plane']}* plane")
    if mark is not None:
        w0, w1 = mark[0], mark[0] + mark[1]
    else:
        w0, w1 = min(s for s, _ in every), max(e for _, e in every)
    busy, totals, modules, first = [], {}, {}, None
    for ops, mods in devices:
        merged = union([(max(s, w0), min(s + d, w1)) for _, s, d in ops
                        if s + d > w0 and s < w1])
        if not merged:
            continue  # a chip that ran nothing inside the window
        first = merged if first is None else first
        busy.append(sum(e - s for s, e in merged))
        for name, _, d in ops:
            totals[name] = totals.get(name, 0.0) + d
        for name, _, d in mods:
            key = name.split("(", 1)[0]
            modules[key] = modules.get(key, 0.0) + d
    if len(busy) < need:
        raise TraceError(f"{len(busy)} chip(s) ran an operation inside "
                         f"the traced window, {need} hold a destination")
    n = len(busy)
    gaps = {}
    # the first chip's idle gaps, by the host's phases
    edges = [w0] + [x for s, e in first for x in (s, e)] + [w1]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    for g0, g1 in idle:
        for name, p0, p1 in _phases_ns(phases, mark, anchor_mono, w0, w1):
            lap = min(g1, p1) - max(g0, p0)
            if lap > 0:
                gaps[name] = gaps.get(name, 0.0) + lap
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    return {
        "devices": n,
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "modules_s": {k: v / n * 1e-9 for k, v in modules.items()},
        "ops_s": {k: v / n * 1e-9 for k, v in totals.items()},
        "breakdown": {
            "device_ops": [[k, v / n * 1e-9] for k, v in top],
            "idle_gaps": [[k, v * 1e-9] for k, v in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:10]],
        },
    }


def _phases_ns(phases, mark, anchor_mono, w0, w1) -> list:
    """The host's phases on the trace's clock; without an anchor every
    gap is simply unattributed."""
    if not phases or mark is None or anchor_mono is None:
        return [("unattributed", w0, w1)]
    out = []
    for name, t0, t1 in phases:
        p0 = mark[0] + (t0 - anchor_mono) * 1e9 if t0 > float("-inf") else w0
        p1 = mark[0] + (t1 - anchor_mono) * 1e9 if t1 < float("inf") else w1
        out.append((name, max(p0, w0), min(p1, w1)))
    return out


def kernel_seconds(red: dict, module: str = None, ops: list = None) -> float:
    """Device seconds of a kernel: a jitted program by (part of) its
    module name, or HLO operations by name prefix."""
    total = 0.0
    if module:
        total += sum(v for k, v in red["modules_s"].items() if module in k)
    for prefix in ops or ():
        total += sum(v for k, v in red["ops_s"].items()
                     if k.startswith(prefix))
    return total


def reduce_dir(trace_dir: str, phases=None, anchor_mono=None,
               working: int = 1, select: dict = None) -> dict:
    return reduce(load(find_trace(trace_dir)), phases, anchor_mono, working,
                  select)
