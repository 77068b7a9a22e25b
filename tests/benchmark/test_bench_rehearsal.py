"""Whole runs of every traffic mix, rehearsed on the CPU at tiny width.

Each run is the real command in a process of its own (the harness starts
the seats itself).  Without the stub it must stop at the no-TPU check
with a clear message and no result line; with the check replaced — from
the test, there is no option for it — it must print a last line with
exactly the contract's keys."""

import json
import os

import pytest

from bench_helpers import (MOE_MIXES, REPO, RESULT_KEYS, add_second_arch,
                           rehearse, traffic_mixes)
from benchmark import rounds
from benchmark.manifest import Manifest
from contract import problems

MIXES = traffic_mixes()  # every committed mix, in a cell or not


def _last_line(proc):
    lines = proc.stdout.strip().splitlines()
    assert lines, proc.stderr[-2000:]
    return lines[-1]


@pytest.mark.parametrize("mix", MIXES)
def test_a_run_off_the_tpu_fails_and_prints_no_result(tiny_manifest, mix):
    manifest, tag = tiny_manifest
    proc = rehearse(manifest, f"{tag}.{mix}", stub=False)
    assert proc.returncode != 0
    assert "needs a tpu device; JAX found none" in proc.stdout
    assert not _last_line(proc).startswith("{")  # no result line


# The second architecture (tests/benchmark/arch_moe/, files beside the
# temporary manifest and nowhere else) goes through the same whole run.
CASES = ([("llama", m, t) for m in MIXES for t in (0, 1)]
         + [("moe", m, 0) for m in MOE_MIXES] + [("moe", MOE_MIXES[0], 1)])


@pytest.mark.parametrize("arch,mix,trace", CASES)
def test_a_rehearsed_run_prints_the_contracts_last_line(tiny_manifest, arch,
                                                        mix, trace):
    manifest, tag = tiny_manifest
    cell = f"{tag}.{mix}"
    if arch == "moe":
        add_second_arch(manifest, tag)
        cell = f"{tag}.moe.{mix}"
        assert problems(Manifest(manifest)) == []
    proc = rehearse(manifest, cell, stub=True, trace=trace)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    line = json.loads(_last_line(proc))
    # every blob is read back whole, leaf by leaf of the architecture's
    # own layout (the second one's has leaves of rank 3), and the logits
    # of either architecture are held to the one tolerance
    assert "read-back: 5 whole blobs" in proc.stdout
    assert ", 0 mismatches" in proc.stdout
    ref = json.loads(proc.stdout.split("reference: ", 1)[1].splitlines()[0])
    assert ref["passed"] and ref["tolerance"] == 0.03
    assert set(line) == RESULT_KEYS | ({"breakdown"} if trace else set())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"  # named, never a device claim
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    man = Manifest(manifest)
    group = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in man.metrics_for(cell, group)}
    assert line["metrics"], line
    for name, rec in line["metrics"].items():
        assert set(rec) == {"value", "unit"} and rec["unit"] == allowed[name]
        assert isinstance(rec["value"], float)
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in line["breakdown"].values())
    else:
        assert {"setup_s", "ttft_s", "cold_start_s"} == set(line["metrics"])
    # every round is kept, the warm-up first, and the run's value is the
    # median of the counted ones
    rs = rounds.json_lines(os.path.join(REPO, "chiprun_out", "bench", cell,
                                        "rounds.jsonl"))
    assert [r["round"] for r in rs] == list(range(len(rs)))
    assert len(rs) == line["attempted"] + 1
    assert all("layer" in r and r["ok"] for r in rs)
    if not trace:
        want = rounds.reduce_run(rs)["values"]["ttft_s"]
        assert line["metrics"]["ttft_s"]["value"] == pytest.approx(want)


def test_a_new_cell_with_a_new_reader_runs_without_touching_a_file(
        tiny_manifest, tmp_path):
    """A fourth cell, a third configuration, a new traffic mix and a new
    per-layer metric with its own reader, all as new files beside a new
    manifest; the run on the CPU reports the new metric."""
    manifest, tag = tiny_manifest
    root = os.path.dirname(manifest)
    for sub in ("traffic", "metrics", "readers"):
        os.makedirs(os.path.join(root, "benchmark", sub), exist_ok=True)
    with open(os.path.join(root, "benchmark", "configs", "third.json"),
              "w") as f:
        json.dump({"hidden_size": 64, "num_attention_heads": 2,
                   "num_key_value_heads": 1, "intermediate_size": 128,
                   "vocab_size": 128, "num_hidden_layers": 3,
                   "rope_theta": 10000.0, "rms_norm_eps": 1e-05}, f)
    mix = Manifest(manifest).traffic("cold-raw")
    mix.update(requests=2)  # the boot decode's shapes: nothing compiles
    # a seeder capped through ``Sources``, as the multi-sender cell of
    # PERF.md's Open questions will be: data, no code
    next(s for s in mix["seats"] if s["role"] == "seeder")[
        "rate_limit"] = 50_000_000
    with open(os.path.join(root, "benchmark", "traffic", "two-short.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "benchmark", "metrics",
                           "serve.requests.json"), "w") as f:
        json.dump({"layer": "serve", "unit": "count", "reader": "count_msgs",
                   "moves": "cold_start_s", "source": "program_counter",
                   "args": {"role": "dest",
                            "message": "served generation request"}}, f)
    with open(os.path.join(root, "benchmark", "readers", "count_msgs.py"),
              "w") as f:
        f.write("def read(ctx, role, message):\n"
                "    return float(sum(1 for r in ctx['logs_by_role'][role]\n"
                "                     if r.get('message') == message))\n")
    with open(manifest) as f:
        d = json.load(f)
    cell = f"{tag}.fourth"
    d["configs"].append({"name": "third", "source": "tests", "reduced": [],
                         "file": "benchmark/configs/third.json",
                         "why": "a third configuration"})
    d["workloads"].append({"name": cell, "config": "third",
                           "traffic": "two-short", "chips": 1,
                           "why": "a fourth cell"})
    d["per_layer"].append({"name": "serve.requests", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "serve", "moves": "cold_start_s",
                           "workloads": [cell]})
    with open(manifest, "w") as f:
        json.dump(d, f)
    assert problems(Manifest(manifest)) == []
    proc = rehearse(manifest, cell, stub=True, trace=1)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    line = json.loads(_last_line(proc))
    assert line["correct"] is True
    assert line["metrics"]["serve.requests"] == {"value": 2.0,
                                                 "unit": "count"}
    assert "boot.tail_s" in line["metrics"]  # the old readers still read
    with open(os.path.join(REPO, "chiprun_out", "bench", cell, "round_01",
                           "topology.json")) as f:
        caps = {n["Id"]: n["Sources"]["1"] for n in json.load(f)["Nodes"]}
    assert caps == {0: 0, 1: 50_000_000, 2: 0, 3: 0}


def test_the_benchmark_fails_where_the_program_is_not(tmp_path):
    """In a directory that holds only the manifest and ``paths``."""
    import shutil
    import subprocess
    import sys

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         Manifest().data["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert "not in this checkout" in proc.stderr
    assert not proc.stdout.strip()
