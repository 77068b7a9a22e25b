#!/usr/bin/env python3
"""The benchmark: cold start to a serving replica, in rounds.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process tree: this parent (which never imports JAX — a
parent that touched JAX would hold the chip) and the resident seats of
``child.py``.  Set-up fabricates the weights from ``--seed``, starts the
seats, and runs round 0, a warm-up that is thrown away.  Then the window
opens: complete cold starts, one after another, while another still
fits in ``--seconds``.  Each timing the run reports is the median over
its counted rounds; every round is a line of
``chiprun_out/bench/<cell>/rounds.jsonl``.  After the window the
delivered model is read back and held to the plain reference.

The last line of stdout is the result, to the contract: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``).  Off the TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # process start, as near as Python lets us

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import archs, launch, rounds as R  # noqa: E402
from benchmark.launch import BenchFailure  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402

# The platform a run must find.  There is no option and no environment
# switch for it; the CPU rehearsals in tests/benchmark/ replace it there.
PLATFORM = "tpu"
# Which planes of the profiler's trace are the devices': None is
# ``xplane.TPU``.  Replaced together with PLATFORM, and only there.
TRACE_SELECT = None
# Logit agreement, bf16 forward on the device vs the float32 reference,
# relative L2.  bf16 keeps 8 mantissa bits (eps 2^-8 = 3.9e-3) and the
# error compounds through the layers and the head; chip_smoke.py argues
# for 12 eps = 5e-2.  Measured on the v5e at these shapes it is 2.06-2.08%
# (raw), 1.65-1.74% (int8) and 1.37-1.46% (pod) over 55 runs and 7 seeds,
# so the benchmark holds the system to 3e-2.  The drivers hand it to the
# seats.
LOGITS_TOL = 3e-2


def say(msg: str) -> None:
    """An earlier line: progress and evidence, never the result."""
    print(f"[bench +{time.monotonic() - T_START:6.1f}s] {msg}", flush=True)


class Run:
    """What one invocation knows; handed to the drivers and (as ``ctx``)
    to the per-layer readers."""

    def __init__(self, args, manifest: Manifest):
        self.manifest = manifest
        self.cell = manifest.workload(args.workload)
        self.config_entry, self.config = manifest.config(self.cell["config"])
        # found and held to its hooks here, before a seat is started
        self.arch = archs.of(self.config)
        self.traffic = manifest.traffic(self.cell["traffic"])
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.out = os.path.join(REPO, "chiprun_out", "bench",
                                self.cell["name"])
        shutil.rmtree(self.out, ignore_errors=True)
        os.makedirs(self.out)
        self.kids = launch.Children(self.out)
        self.platform = PLATFORM
        self.trace_select = TRACE_SELECT
        self.say = say
        self.traced_round = None
        self.rounds = []
        self.device = None
        self.logits_tol = LOGITS_TOL

    # ----------------------------------------------------------- the window

    def run_rounds(self, one_round) -> float:
        """Round 0 (warm-up), then counted rounds while one more fits.
        Returns ``setup_s``: process start → the window opening."""
        say("round 0 (warm-up, thrown away; set-up)")
        rec = one_round(0, False)
        self.rounds.append(rec)
        R.write_rounds(os.path.join(self.out, "rounds.jsonl"), self.rounds)
        if not rec["ok"]:
            raise BenchFailure(f"warm-up round failed: {rec.get('error')}")
        t_open = time.monotonic()
        setup_s = t_open - T_START
        say(f"window opens: setup_s={setup_s:.3f}, {self.seconds:g}s")
        longest, k = 0.0, 0
        while True:
            spent = time.monotonic() - t_open
            if k >= 1 and spent + longest > self.seconds:
                break
            k += 1
            t0 = time.monotonic()
            rec = one_round(k, self.trace and k == 1)
            rec["round_wall_s"] = time.monotonic() - t0
            longest = max(longest, rec["round_wall_s"])
            self.rounds.append(rec)
            R.write_rounds(os.path.join(self.out, "rounds.jsonl"),
                           self.rounds)
            say(f"round {k}: " + " ".join(
                f"{key}={rec[key]:.4f}" for key in R.TIMINGS
                if rec.get(key) is not None)
                + ("" if rec["ok"] else f" FAILED: {rec.get('error')}"))
        self.window_s = time.monotonic() - t_open
        return setup_s

    def found_device(self, device: dict) -> None:
        """The device as JAX reported it to the process that holds it;
        another platform, or fewer chips than the cell asks for, ends the
        run."""
        self.device = device
        say(f"device: {device}")
        if (device["platform"] != self.platform
                or device["count"] < self.cell["chips"]):
            raise BenchFailure(
                f"needs {self.cell['chips']} {self.platform} device(s); "
                f"JAX found {device}")

    # ------------------------------------------------------- per-layer side

    def layer_values(self, round_ctx: dict, traced: bool) -> dict:
        """Call every per-layer reader of this cell on one round.  A
        reader that finds nothing returns None and is left out."""
        out = {}
        for m in self.manifest.metrics_for(self.cell["name"], "per_layer"):
            if (m["source"] == "device_trace") != traced:
                continue
            spec = self.manifest.metric_spec(m["name"])
            read = self.manifest.reader(spec["reader"])
            try:
                v = read(round_ctx, **spec.get("args", {}))
            except (KeyError, IndexError, TypeError, ValueError,
                    ZeroDivisionError) as e:
                say(f"reader {spec['reader']} for {m['name']}: {e!r}")
                v = None
            if v is not None:
                out[m["name"]] = float(v)
        return out

    def ctx(self, **more) -> dict:
        from benchmark import fabricate, kernels

        return {"config": self.config, "traffic": self.traffic,
                "codec": self.traffic.get("codec", "raw"),
                "device": self.device, "kernels": kernels,
                "fabricate": fabricate, **more}

    # ------------------------------------------------------------ the result

    def result(self, setup_s: float, correct: bool, trace_red) -> dict:
        red = R.reduce_run(self.rounds)
        values = dict(red["values"], setup_s=setup_s)
        group = "per_layer" if self.trace else "end_to_end"
        metrics = {}
        if self.trace:
            good = [r for r in R.counted(self.rounds) if r.get("ok")]
            for m in self.manifest.metrics_for(self.cell["name"], group):
                xs = [r["layer"][m["name"]] for r in good
                      if m["name"] in r.get("layer", {})]
                if m["source"] == "device_trace":
                    xs = [(trace_red or {}).get("layer", {}).get(m["name"])]
                    xs = [x for x in xs if x is not None]
                if xs:
                    metrics[m["name"]] = {"value": statistics.median(xs),
                                          "unit": m["unit"]}
        else:
            for m in self.manifest.metrics_for(self.cell["name"], group):
                if m["name"] in values:
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
        device = dict(self.device)
        peaks = [r["peak_bytes"] for r in self.rounds if r.get("peak_bytes")]
        device["memory_peak_bytes"] = max(peaks) if peaks else 0
        line = {"correct": bool(correct and red["warmup_ok"]
                                and red["failed"] == 0
                                and red["attempted"] >= 1),
                "attempted": red["attempted"], "failed": red["failed"],
                "metrics": metrics, "device": device}
        if self.trace and trace_red:
            device["busy_s"] = trace_red["busy_s"]
            device["window_s"] = trace_red["window_s"]
            line["breakdown"] = trace_red["breakdown"]
        return line


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, default=0, choices=(0, 1))
    p.add_argument("--manifest", default=None,
                   help="another BENCHMARK.json (tests; new cells are "
                        "looked up beside it first)")
    args = p.parse_args(argv)
    manifest = Manifest(args.manifest)
    if args.seconds is None:
        args.seconds = manifest.data["run_seconds"]
    try:
        import distributed_llm_dissemination_tpu  # noqa: F401
    except ImportError:
        print("the system under test is not in this checkout",
              file=sys.stderr)
        return 2
    run = Run(args, manifest)
    # The traffic file names its entry point (``cli.main`` seats over TCP,
    # ``cli.podrun`` in one process); its driver is a file found by name.
    driver = manifest.module("drivers",
                             run.traffic["entry"].replace(".", "_"))
    line = None
    try:
        line = driver.run(run)
    except BenchFailure as e:
        say(f"FAILED: {e}")
    finally:
        run.kids.end_all()
    if line is None:
        return 1  # and no result line
    with open(os.path.join(run.out, "result.json"), "w") as f:
        json.dump(line, f, indent=1)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
