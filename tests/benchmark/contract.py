"""The driver's contract for ``BENCHMARK.json``, written down for the
tests: everything it would refuse before a run, as strings.  The harness
itself does not call this; the driver checks the real thing."""

import json
import re
import statistics

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def problems(man) -> list:
    """What the contract would refuse in ``man`` (a ``Manifest``)."""
    d, bad = man.data, []

    def name_ok(n, what):
        if not isinstance(n, str) or not NAME.match(n):
            bad.append(f"{what}: bad name {n!r}")

    def line_ok(s, what):
        if (not isinstance(s, str) or not 1 <= len(s) <= 200
                or "\n" in s or "\t" in s):
            bad.append(f"{what}: not one line of 1..200 characters")

    if set(d) != TOP_KEYS:
        bad.append(f"top-level keys {sorted(d)} != {sorted(TOP_KEYS)}")
        return bad
    if not (isinstance(d["run_seconds"], int)
            and 1 <= d["run_seconds"] <= 51):
        bad.append("run_seconds must be a whole number in 1..51")
    if not 1 <= len(d["command"]) <= 32:
        bad.append("command: 1..32 strings")
    for word in d["command"]:
        line_ok(word, "command")
        if word.startswith("/") or ".." in word.split("/"):
            bad.append(f"command word {word!r} leaves the repo")
    paths = d["paths"]
    if not 1 <= len(paths) <= 16:
        bad.append("paths: 1..16 directories")
    for p in paths:
        if not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p):
            bad.append(f"path {p!r}")

    def under_paths(f):
        return any(f == p or f.startswith(p.rstrip("/") + "/")
                   for p in paths)

    seen = set()
    for c in d["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config keys {sorted(c)}")
            continue
        name_ok(c["name"], "config")
        line_ok(c["source"], "config source")
        line_ok(c["why"], "config why")
        if not under_paths(c["file"]):
            bad.append(f"config file {c['file']} outside paths")
        if len(c["reduced"]) > 16:
            bad.append("reduced: at most 16 keys")
        for k in c["reduced"]:
            name_ok(k, "reduced")
            if re.search(r"(_dim|_rank|hidden_size|intermediate_size|"
                         r"head_dim|experts_per_tok)$", k):
                bad.append(f"reduced names a width: {k}")
        if c["name"] in seen:
            bad.append(f"duplicate config {c['name']}")
        seen.add(c["name"])
    configs = {c.get("name") for c in d["configs"]}
    cells, pairs = set(), set()
    for w in d["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload keys {sorted(w)}")
            continue
        for k in ("name", "config", "traffic"):
            name_ok(w[k], f"workload {k}")
        line_ok(w["why"], "workload why")
        if w["chips"] not in (1, 4):
            bad.append(f"{w['name']}: chips must be 1 or 4")
        if w["config"] not in configs:
            bad.append(f"{w['name']}: unknown config {w['config']}")
        if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
            bad.append(f"duplicate cell {w['name']}")
        cells.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
    if not 1 <= len(d["workloads"]) <= 24:
        bad.append("workloads: 1..24 cells")
    four = sum(1 for w in d["workloads"] if w.get("chips") == 4)
    if four > max(1, len(d["workloads"]) // 4):
        bad.append(f"{four} four-chip cells is over a quarter")
    used = {w.get("config") for w in d["workloads"]}
    for c in configs - used:
        bad.append(f"config {c} is used by no cell")

    names = set()
    e2e = {m.get("name") for m in d["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")
    for group, keys in (("end_to_end", {"name", "unit", "better",
                                        "bound", "source"}),
                        ("per_layer", {"name", "unit", "better",
                                       "source", "layer", "moves"})):
        for m in d[group]:
            if set(m) - {"workloads"} != keys:
                bad.append(f"{group} keys {sorted(m)}")
                continue
            name_ok(m["name"], group)
            if not UNIT.match(m["unit"]):
                bad.append(f"{m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"{m['name']}: better={m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"{m['name']}: source={m['source']!r}")
            if m["name"] in names:
                bad.append(f"duplicate metric {m['name']}")
            names.add(m["name"])
            for w in m.get("workloads", ()):
                if w not in cells:
                    bad.append(f"{m['name']}: unknown cell {w}")
            if group == "end_to_end":
                if m["source"] not in ("host_clock", "device_trace"):
                    bad.append(f"{m['name']}: end-to-end source")
                if not 0 < m["bound"] <= 0.1:
                    bad.append(f"{m['name']}: bound {m['bound']}")
            else:
                line_ok(m["layer"], "layer")
                if m["moves"] not in e2e:
                    bad.append(f"{m['name']} moves unknown "
                               f"{m['moves']}")
    # every cell: setup_s, another end-to-end metric, a per-layer one;
    # and a per-layer metric's target is reported wherever it is.
    for w in cells:
        mine = {m["name"] for m in man.metrics_for(w, "end_to_end")}
        if "setup_s" not in mine or len(mine) < 2:
            bad.append(f"{w}: needs setup_s and one more end-to-end")
        layer = man.metrics_for(w, "per_layer")
        if not layer:
            bad.append(f"{w}: no per-layer metric")
        for m in layer:
            if m.get("moves") not in mine:
                bad.append(f"{w}: {m['name']} moves {m.get('moves')}, "
                           "which this cell does not report")
    if len(json.dumps(d)) > 64 * 1024:
        bad.append("manifest over 64 KiB")
    return bad


def spread(xs: list) -> float:
    """Interquartile distance over the median, as the contract has it."""
    if len(xs) < 2:
        return 0.0
    q = statistics.quantiles(xs, n=4)
    return (q[2] - q[0]) / statistics.median(xs)
