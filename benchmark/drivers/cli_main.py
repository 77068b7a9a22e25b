"""Driver for traffic with ``"entry": "cli.main"``: seats as processes
over loopback TCP, one of them holding the chip.

The traffic file lists the seats (``role``: leader, seeder, dest,
requester), which of them hold the model's blobs, the codec the blobs
are held and sent in, the requests, and how many cores are fenced off
for the seeders' side.  A round is: fresh ports, a topology file, then
``cli.main`` in every seat at once and ``cli.genreq`` from the requester
as soon as the destination serves.
"""

from __future__ import annotations

import json
import os

from benchmark import fabricate, launch, rounds as R
from benchmark.launch import BenchFailure


def _blob_ids(config: dict) -> list:
    return list(range(fabricate.model_dims(config)["layers"] + 1))


def topology(run, addrs: dict) -> dict:
    t = run.traffic
    blobs = {str(b): {} for b in _blob_ids(run.config)}
    nodes = []
    for seat in t["seats"]:
        nodes.append({
            "Id": seat["id"], "Addr": addrs[seat["id"]],
            "NetworkBW": int(t["network_bw"]),
            "IsLeader": seat["role"] == "leader",
            "Sources": {"1": int(seat.get("rate_limit", 0))},
            "InitialLayers": {"1": blobs} if seat.get("holds") else {}})
    dest = next(s for s in t["seats"] if s["role"] == "dest")
    conf = {"Model": run.model_name, "ModelSeed": 0, "Nodes": nodes,
            "Assignment": {str(dest["id"]): blobs},
            "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [1]}}
    if t.get("codec", "raw") != "raw":
        conf["ModelCodec"] = t["codec"]
    return conf


def run(run) -> dict:
    t = run.traffic
    seats = {s["name"]: s for s in t["seats"]}
    dest = next(s for s in t["seats"] if s["role"] == "dest")
    leader = next(s for s in t["seats"] if s["role"] == "leader")
    requester = next(s for s in t["seats"] if s["role"] == "requester")
    run.model_name = run.cell["config"]
    mine = sorted(os.sched_getaffinity(0))
    side, rest = launch.fence(mine, t.get("seeder_cores", 0))
    say = run.say
    say(f"cores: {len(mine)} usable; seeders+requester on {side}, "
        f"destination on {rest}" if side else
        f"cores: {len(mine)} usable, too few to fence: running unfenced")

    base = {"config": run.config, "traffic": t, "seed": run.seed,
            "model_name": run.model_name}
    for name, seat in seats.items():
        if seat["role"] == "dest":
            spec = dict(base, role="dest", cores=rest,
                        platforms=run.platform)
            run.kids.start(name, spec, run.platform)
        elif seat["role"] == "requester":
            run.kids.start(name, dict(base, role="requester", cores=side),
                           "cpu")
        else:
            spec = dict(base, role="seat", cores=side, setup_cores=mine,
                        holds=_blob_ids(run.config) if seat.get("holds")
                        else [])
            run.kids.start(name, spec, "cpu")
    for name in seats:
        run.kids.send(name, cmd="setup")
    setup = {name: run.kids.recv(name, 600.0) for name in seats}
    run.found_device(setup[dest["name"]]["device"])
    for name, rec in setup.items():
        say(f"seat {name}: fence={rec['fence']}"
            + (f" fabricate_s={rec['fabricate_s']:.2f}"
               if "fabricate_s" in rec else ""))
    cache_dir = setup[dest["name"]]["cache_dir"]
    prompts = fabricate.make_prompts(run.config, run.seed, t["requests"],
                                     t["prompt_len"])
    state = {"tokens": None}

    def start_seats(traced: bool, rdir: str, addrs: dict,
                    cancel: str) -> None:
        conf_path = os.path.join(rdir, "topology.json")
        with open(conf_path, "w") as f:
            json.dump(topology(run, addrs), f, indent=1)
        main = ["-f", conf_path, "-m", str(t.get("mode", 3)), "-bw", "300"]
        for name, seat in seats.items():
            prefix = os.path.join(rdir, name)
            if seat["role"] == "leader":
                run.kids.send(name, cmd="round", prefix=prefix,
                              argv=[*main, "-id", str(seat["id"])])
            elif seat["role"] == "seeder":
                run.kids.send(name, cmd="round", prefix=prefix,
                              argv=[*main, "-id", str(seat["id"]),
                                    "-boot", "none"])
            elif seat["role"] == "dest":
                run.kids.send(
                    name, cmd="round", prefix=prefix,
                    trace_dir=os.path.join(run.out, "trace") if traced
                    else None,
                    argv=[*main, "-id", str(seat["id"]), "-hbm",
                          "-gen", str(t["gen_tokens"]),
                          "-serve", f"{t['serve_window_s']:g}"])
            else:
                run.kids.send(
                    name, cmd="round", prefix=prefix, conf=conf_path,
                    watch=os.path.join(rdir, dest["name"] + ".out"),
                    node=dest["id"], id=seat["id"],
                    tokens=t["gen_tokens"], prompts=prompts,
                    timeout=R_TIMEOUT, cancel=cancel)

    def one_round(k: int, traced: bool) -> dict:
        rdir = os.path.join(run.out, f"round_{k:02d}")
        os.makedirs(rdir)
        cancel = os.path.join(rdir, "cancelled")
        cache_before = R.cache_entries(cache_dir)
        rec = {"round": k, "ok": False, "traced": traced}
        logs = {name: [] for name in seats}
        try:
            # the seats' ports stay held until every seat has answered
            with launch.held_addrs(len(t["seats"])) as free:
                start_seats(traced, rdir, dict(zip(
                    (s["id"] for s in t["seats"]), free)), cancel)
                replies = run.kids.gather(
                    list(seats), R_TIMEOUT + 60,
                    on_failure=lambda: open(cancel, "w").close())
            logs = _logs(rdir, seats)
            _assemble(run, rec, rdir, logs, replies, dest, leader,
                      requester, state)
        except BenchFailure as e:
            if "is gone" in str(e) or "exited" in str(e) \
                    or "no answer" in str(e):
                raise  # a seat is lost or stuck: the run cannot go on
            rec["error"] = str(e)
        rec["cache_new_entries"] = len(R.cache_entries(cache_dir)
                                       - cache_before)
        rec["layer"] = run.layer_values(
            run.ctx(round=rec, logs_by_role=_by_role(t, logs), trace=None),
            traced=False)
        if traced:
            run.traced_round = {"rec": rec, "logs": logs, "rdir": rdir}
        return rec

    setup_s = run.run_rounds(one_round)

    # ---- after the window, outside every timing
    # The leader digests what it holds while the destination reads the
    # delivered model back, whole.
    run.kids.send(leader["name"], cmd="expected")
    run.kids.send(dest["name"], cmd="readback")
    want = run.kids.recv(leader["name"], 600.0)
    rb = run.kids.recv(dest["name"], 600.0)
    bad = R.readback_problems(want["expected"], rb["got"])
    for b, w in want["expected"].items():
        place = rb["placement"].get(b, {})
        if place.get("location") != "HBM" or place.get("bytes") != w["bytes"]:
            bad.append(f"blob {b}: final placement {place}")
    say(f"read-back: {len(rb['got'])} whole blobs in {rb['seconds']:.2f}s "
        f"(the leader's digests in {want['seconds']:.2f}s), "
        f"{len(bad)} mismatches {bad[:3]}")
    correct = not bad
    seq = [p + toks for p, toks in zip(prompts, state["tokens"])]
    ref = run.kids.call(dest["name"], 900.0, cmd="reference", tokens=seq,
                        prompt_len=t["prompt_len"], tolerance=run.logits_tol)
    say(f"reference: {json.dumps(ref)}")
    correct &= bool(ref["passed"])
    trace_red = None
    if run.trace and getattr(run, "traced_round", None):
        trace_red = _reduce_trace(run, dest["name"])
    return run.result(setup_s, correct, trace_red)


R_TIMEOUT = 900.0


def _logs(rdir: str, seats: dict) -> dict:
    return {name: R.json_lines(os.path.join(rdir, name + ".jsonl"))
            for name in seats}


def _by_role(traffic: dict, logs: dict) -> dict:
    return {s["role"]: logs[s["name"]] for s in traffic["seats"]}


def _read(path: str) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()
    except OSError:
        return ""


def _assemble(run, rec, rdir, logs, replies, dest, leader, requester,
              state) -> None:
    """One round's record from what the processes themselves said."""
    t = run.traffic
    for name, rep in replies.items():
        if rep.get("rc") != 0:
            raise BenchFailure(f"{name} rc={rep.get('rc')}: "
                               f"{rep.get('error', '')}")
    drep = replies[dest["name"]]
    rec.update(peak_bytes=drep["peak_bytes"],
               bytes_before=drep["bytes_before"],
               bytes_after=drep["bytes_after"])
    if drep.get("counters"):
        raise BenchFailure(f"device path degraded: {drep['counters']}")
    out = _read(os.path.join(rdir, leader["name"] + ".out"))
    for key, label in (("ttd_s", "Time to deliver: "),
                       ("ttft_s", "Time to first token: ")):
        if label not in out:
            raise BenchFailure(f"leader printed no {label!r}")
        rec[key] = float(out.split(label, 1)[1].split("s", 1)[0])
    start = R.first(logs[leader["name"]], "timer start")
    if start is None:
        raise BenchFailure("leader logged no timer start")
    rec["timer_start_mono"] = start["mono"]
    answers = replies[requester["name"]]["answers"]
    for i, a in enumerate(answers):
        if a["rc"] != 0 or len(a.get("tokens") or ()) != t["gen_tokens"]:
            raise BenchFailure(f"request {i} failed: {a}")
    rec["cold_start_s"] = answers[-1]["mono"] - start["mono"]
    rec["serving_mono"] = replies[requester["name"]]["serving_mono"]
    rec["answers_mono"] = [a["mono"] for a in answers]
    tokens = [a["tokens"] for a in answers]
    if state["tokens"] is not None and tokens != state["tokens"]:
        raise BenchFailure(f"tokens changed between rounds: {tokens} "
                           f"!= {state['tokens']}")
    state["tokens"] = tokens
    dlog = logs[dest["name"]]
    bad = sorted({r["message"] for r in dlog
                  if r.get("message") in R.FALLBACK_LINES})
    if bad:
        raise BenchFailure(f"destination fell off the device path: {bad}")
    staged = {r["layerID"]: r for r in R.records(dlog, "layer staged to HBM")}
    n_blobs = fabricate.model_dims(run.config)["layers"] + 1
    for b in range(n_blobs):
        on = (staged.get(b) or {}).get("devices", [])
        if not on or any(d.split(":")[0] != run.platform for d in on):
            raise BenchFailure(f"blob {b} was not staged to a "
                               f"{run.platform} device: {staged.get(b)}")
    boot = R.first(dlog, "model booted from disseminated layers")
    if boot is None or boot.get("kind") != "full" \
            or "host assembly" in boot.get("via", "host assembly"):
        raise BenchFailure(f"no full device boot: {boot}")
    rec["ok"] = True


def _phases(rec: dict, logs: dict, dest: str, leader: str) -> list:
    """The harness's phases of a round on CLOCK_MONOTONIC, for the idle
    gaps: ``[(name, t0, t1)]``."""
    d, l = logs[dest], logs[leader]
    marks = [("announce_and_plan_before_the_timer", None),
             ("deliver_wire_verify_ingest", rec["timer_start_mono"]),
             ("boot_assembly_first_forward",
              (R.first(l, "Time to deliver") or {}).get("mono")),
             ("post_boot_decode",
              (R.first(d, "model booted from disseminated layers")
               or {}).get("mono")),
             ("wait_for_requests",
              (R.first(d, "decoded tokens after boot") or {}).get("mono")),
             ("serve_requests", rec.get("serving_mono")),
             ("serve_window_idle", (rec.get("answers_mono") or [None])[-1]),
             ("final_placement_and_close",
              (R.first(d, "final layer placement") or {}).get("mono"))]
    return R.phase_chain(marks)


def _reduce_trace(run, dest_name: str) -> dict:
    tr = run.traced_round
    leader = next(s["name"] for s in run.traffic["seats"]
                  if s["role"] == "leader")
    phases = _phases(tr["rec"], tr["logs"], dest_name, leader)
    red = run.kids.call(dest_name, 600.0, cmd="reduce",
                        trace_dir=os.path.join(run.out, "trace"),
                        phases=phases, select=run.trace_select,
                        working=sum(1 for s in run.traffic["seats"]
                                    if s["role"] == "dest"))
    ctx = run.ctx(round=tr["rec"], trace=red,
                  logs_by_role=_by_role(run.traffic, tr["logs"]))
    red["layer"] = run.layer_values(ctx, traced=True)
    return red
