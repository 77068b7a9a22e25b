"""From rounds to a run's numbers, and the log helpers the readers share.

A run is a warm-up round (round 0, thrown away, part of set-up) and then
as many counted cold-start rounds as the window holds.  A run's value
for each timing is the MEDIAN over its counted rounds — never a minimum,
never a mean with the warm-up in it.  Every round, warm-up included, is
one line of ``rounds.jsonl``.
"""

from __future__ import annotations

import json
import os
import statistics

# Any of these in a destination's log means the device path quietly
# degraded to the host (runtime/receiver.py, boot.py, stream_boot.py,
# parallel/collectives.py): the round fails.  Copied from chip_smoke.py.
FALLBACK_LINES = (
    "HBM staging failed; acking host RAM",
    "ingest finalize failed; bulk staging instead",
    "incremental device ingest failed; will stage at completion",
    "device ingest unavailable for layer",
    "streamed assembly failed; bulk assembly instead",
    "streamed boot staging failed for blob; bulk assembly will cover it",
    "streamed staging still in flight at collect; boot falls back to "
    "bulk assembly",
    "fewer devices than shards; gathering on host instead of the mesh",
    "boot assembled on the host although -hbm staging was asked for",
    "post-boot decode failed",
    "model boot failed",
    "device path degraded under -hbm; exiting non-zero",
)

TIMINGS = ("ttd_s", "ttft_s", "cold_start_s")


def json_lines(path: str) -> list:
    out = []
    if os.path.exists(path):
        with open(path, errors="replace") as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    out.append(rec)
    return out


def records(log: list, message: str) -> list:
    return [r for r in log if r.get("message") == message]


def first(log: list, message: str):
    return next((r for r in log if r.get("message") == message), None)


def cache_entries(cache_dir: str) -> set:
    """The compile cache's entries (``chip_smoke.py``'s count): a counted
    round should add none."""
    try:
        return {f for f in os.listdir(cache_dir) if f.endswith("-cache")}
    except OSError:
        return set()


def phase_chain(marks: list) -> list:
    """``[(name, t0, t1)]`` from ``[(name, start or None)]`` in order: a
    phase lasts until the next one that has a start; the first begins
    and the last ends with the window."""
    out, t_prev, name_prev = [], float("-inf"), marks[0][0]
    for name, t in marks[1:]:
        if t is None:
            continue
        out.append((name_prev, t_prev, t))
        t_prev, name_prev = t, name
    out.append((name_prev, t_prev, float("inf")))
    return out


def counted(rounds: list) -> list:
    """The rounds a run's numbers come from: every round after the
    warm-up that started, failed ones left out of the medians (they are
    counted in ``failed``)."""
    return [r for r in rounds if r.get("round", 0) >= 1]


def reduce_run(rounds: list) -> dict:
    """``{"attempted", "failed", "values": {timing: median}}`` from a
    run's round records (the contents of ``rounds.jsonl``)."""
    cnt = counted(rounds)
    good = [r for r in cnt if r.get("ok")]
    values = {}
    for key in TIMINGS:
        xs = [r[key] for r in good if r.get(key) is not None]
        if xs:
            values[key] = statistics.median(xs)
    peaks = [r["peak_bytes"] for r in rounds if r.get("peak_bytes")]
    if peaks:
        values["hbm_peak_gib"] = max(peaks) / 2 ** 30
    warm = [r for r in rounds if r.get("round") == 0]
    return {"attempted": len(cnt), "failed": len(cnt) - len(good),
            "warmup_ok": bool(warm and warm[0].get("ok")),
            "values": values}


def readback_problems(expected: dict, got: dict) -> list:
    """Where the blobs read back from the devices differ from what the
    seeder holds.  A blob is delivered as decoded ``leaves`` or kept in
    its ``wire`` form; one of the two must be there and equal."""
    bad = []
    for b, want in expected.items():
        rec = got.get(b, {})
        if not rec:
            bad.append(f"blob {b}: nothing resident to read back")
        for form, found in rec.items():
            if found != want[form]:
                bad.append(f"blob {b}: delivered {form} differ from the "
                           "seeder's")
    return bad


def write_rounds(path: str, rounds: list) -> None:
    with open(path, "w") as f:
        for r in rounds:
            f.write(json.dumps(r) + "\n")
