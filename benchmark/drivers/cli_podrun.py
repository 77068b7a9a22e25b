"""Driver for traffic with ``"entry": "cli.podrun"``: every seat of a pod
in ONE process over the device fabric (``run_pod``), as
``chip_smoke.py``'s four-chip phase runs it.

Seat 0 leads and seeds every blob; the other seats partition the model
into contiguous pipeline stages, as even as the depth allows (the last
also holds the head blob).  A round is one
``run_pod``: delivery over the fabric, stage boots, one pipelined
forward, the pod's decode.  Its clocks are ``run_pod``'s own ``ttd_s``
and ``ttft_s``; ``cold_start_s`` ends when the pod's decode has
returned (``on_delivered``) and starts at the leader's ``timer start``.
"""

from __future__ import annotations

import json
import os

from benchmark import fabricate, rounds as R
from benchmark.launch import BenchFailure

R_TIMEOUT = 1100.0


def topology(run) -> dict:
    t = run.traffic
    n = fabricate.model_dims(run.config)["layers"]
    seats = int(t["seats"])
    if n < seats - 1:
        raise BenchFailure(f"{seats - 1} stages need as many layers")
    head = n
    every = {str(b): {} for b in range(head + 1)}
    # contiguous stages as even as the depth allows (the earlier ones get
    # the remainder); the last also holds the head blob
    stages = seats - 1
    cuts = [-(-n * i // stages) for i in range(stages)] + [head + 1]
    return {
        "Model": run.model_name, "ModelSeed": 0,
        "Nodes": [{"Id": i, "Addr": str(i), "IsLeader": i == 0,
                   "Sources": {"2": 0},
                   "NetworkBW": int(t["network_bw"]),
                   "InitialLayers": {"2": every} if i == 0 else {}}
                  for i in range(seats)],
        "Assignment": {str(i): {str(b): {}
                                for b in range(cuts[i - 1], cuts[i])}
                       for i in range(1, seats)},
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [seats],
                 "PipelineAxis": "nodes", "Fabric": True},
    }


def run(run) -> dict:
    t = run.traffic
    say = run.say
    run.model_name = run.cell["config"]
    n_blobs = fabricate.model_dims(run.config)["layers"] + 1
    say(f"cores: {len(os.sched_getaffinity(0))} usable; one process, "
        "unfenced")
    spec = {"config": run.config, "traffic": t, "seed": run.seed,
            "model_name": run.model_name, "role": "pod",
            "platforms": run.platform, "holds": list(range(n_blobs))}
    run.kids.start("pod", spec, run.platform)
    setup = run.kids.call("pod", 600.0, cmd="setup")
    run.found_device(setup["device"])
    say(f"fabricate_s={setup['fabricate_s']:.2f}")
    cache_dir = setup["cache_dir"]
    state = {"tokens": None, "logits": None}

    def one_round(k: int, traced: bool) -> dict:
        rdir = os.path.join(run.out, f"round_{k:02d}")
        os.makedirs(rdir)
        conf_path = os.path.join(rdir, "topology.json")
        with open(conf_path, "w") as f:
            json.dump(topology(run), f, indent=1)
        before = R.cache_entries(cache_dir)
        rec = {"round": k, "ok": False, "traced": traced}
        rep = run.kids.call(
            "pod", R_TIMEOUT, cmd="round", prefix=os.path.join(rdir, "pod"),
            conf=conf_path, tokens=t["gen_tokens"],
            trace_dir=os.path.join(run.out, "trace") if traced else None)
        log = R.json_lines(os.path.join(rdir, "pod.jsonl"))
        try:
            _assemble(run, rec, rep, log, state)
        except BenchFailure as e:
            rec["error"] = str(e)
        rec["cache_new_entries"] = len(R.cache_entries(cache_dir) - before)
        roles = {"leader": log, "dest": log, "pod": log}
        rec["layer"] = run.layer_values(
            run.ctx(round=rec, logs_by_role=roles, trace=None), traced=False)
        if traced:
            run.traced_round = {"rec": rec, "roles": roles}
        return rec

    setup_s = run.run_rounds(one_round)

    # ---- after the window, outside every timing: what the seats of the
    # last round hold, whole, against what the leader seeded
    rb = run.kids.call("pod", 600.0, cmd="readback")
    want = run.kids.call("pod", 600.0, cmd="expected")
    bad = R.readback_problems(want["expected"], rb["got"])
    say(f"read-back: {len(rb['got'])} whole blobs in {rb['seconds']:.2f}s "
        f"(the leader's digests in {want['seconds']:.2f}s), "
        f"{len(bad)} mismatches {bad[:3]}")
    seq = [[0] * t["prompt_len"] + state["tokens"]]
    ref = run.kids.call("pod", 900.0, cmd="reference", tokens=seq,
                        prompt_len=t["prompt_len"], tolerance=run.logits_tol)
    say(f"reference: {json.dumps(ref)}")
    correct = not bad and bool(ref["passed"])
    trace_red = None
    if run.trace and run.traced_round:
        tr = run.traced_round
        red = run.kids.call("pod", 600.0, cmd="reduce",
                            trace_dir=os.path.join(run.out, "trace"),
                            phases=_phases(tr["rec"], tr["roles"]["pod"]),
                            select=run.trace_select,
                            # seat 0 only seeds: its chip runs nothing
                            working=int(t["seats"]) - 1)
        red["layer"] = run.layer_values(
            run.ctx(round=tr["rec"], logs_by_role=tr["roles"], trace=red),
            traced=True)
        trace_red = red
    return run.result(setup_s, correct, trace_red)


def _assemble(run, rec, rep, log, state) -> None:
    if rep.get("rc") != 0:
        raise BenchFailure(f"pod rc={rep.get('rc')}: {rep.get('error')}")
    summary = rep["summary"]
    rec.update(peak_bytes=rep["peak_bytes"],
               bytes_before=rep["bytes_before"], summary=summary)
    for key in ("ttd_s", "ttft_s", "pod_forward_s", "pod_decode_s",
                "tokens"):
        if key not in summary:
            raise BenchFailure(f"run_pod's summary has no {key}")
    rec["ttd_s"], rec["ttft_s"] = summary["ttd_s"], summary["ttft_s"]
    start = R.first(log, "timer start")
    if start is None:
        raise BenchFailure("leader logged no timer start")
    rec["timer_start_mono"] = start["mono"]
    rec["cold_start_s"] = rep["decoded_mono"] - start["mono"]
    if len(summary["tokens"]) != run.traffic["gen_tokens"]:
        raise BenchFailure(f"pod decoded {summary['tokens']}")
    if state["tokens"] is not None and summary["tokens"] != state["tokens"]:
        raise BenchFailure(f"tokens changed between rounds: "
                           f"{summary['tokens']} != {state['tokens']}")
    state["tokens"] = summary["tokens"]
    bad = sorted({r["message"] for r in log
                  if r.get("message") in R.FALLBACK_LINES})
    if bad:
        raise BenchFailure(f"pod fell off the device path: {bad}")
    if rep.get("not_hbm"):
        raise BenchFailure(f"layers not in HBM: {rep['not_hbm']}")
    rec["ok"] = True


def _phases(rec: dict, log: list) -> list:
    marks = [("announce_and_plan_before_the_timer", None),
             ("deliver_fabric_gather_ingest", rec.get("timer_start_mono")),
             ("stage_boots", (R.first(log, "Time to deliver")
                              or {}).get("mono")),
             ("pod_assembly_forward_decode",
              (R.first(log, "Time to first token") or {}).get("mono"))]
    return R.phase_chain(marks)
