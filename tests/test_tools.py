"""Tools + shipped configs: diskspeed, collect_logs, conf/*.json.

The reference ships diskspeed (diskspeed/main.go), collect_logs.sh, and
conf/config.json; these tests cover our equivalents end to end.
"""

import json
import os
import re
import subprocess
import sys

import pytest

from distributed_llm_dissemination_tpu.cli import collect_logs, diskspeed
from distributed_llm_dissemination_tpu.core import config as cfg

CONF_DIR = "conf"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- diskspeed


def test_diskspeed_parse_size():
    assert diskspeed.parse_size("1024") == 1024
    assert diskspeed.parse_size("4K") == 4096
    assert diskspeed.parse_size("2M") == 2 << 20
    assert diskspeed.parse_size("1.5G") == int(1.5 * (1 << 30))


def test_diskspeed_end_to_end(tmp_path, capsys):
    f = tmp_path / "t.bin"
    rc = diskspeed.main([str(f), "--size", "2M", "--drop-caches"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    rec = json.loads(out[-1])
    assert rec["bytes"] == 2 << 20
    assert rec["unit"] == "MiB/s"
    assert rec["value"] > 0
    assert rec["sources_rate"] > 0
    assert f.stat().st_size == 2 << 20


# ------------------------------------------------------------- collect_logs


def _writelog(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")


def test_collect_logs_merge_and_rebase(tmp_path):
    # Leader log: timer start at t=2000; receiver events straddle it.
    _writelog(tmp_path / "leader.jsonl", [
        {"level": "info", "time": 1500, "node": "0", "message": "start listening"},
        {"level": "info", "time": 2000, "node": "0", "message": "timer start"},
        {"level": "info", "time": 2600, "node": "0", "message": "timer stop: startup"},
    ])
    _writelog(tmp_path / "recv.jsonl", [
        {"level": "info", "time": 2400, "node": "1", "message": "layer received"},
        {"level": "info", "time": 1900, "node": "1", "message": "announce"},
        "not json at all",  # ignored junk line
    ])
    (tmp_path / "recv.jsonl").write_text(
        (tmp_path / "recv.jsonl").read_text() + "junk line\n"
    )

    merged = collect_logs.merge(collect_logs.iter_records([str(tmp_path)]))
    assert [r["time"] for r in merged] == sorted(r["time"] for r in merged)
    by_msg = {r["message"]: r for r in merged}
    assert by_msg["timer start"]["rel_ms"] == 0
    assert by_msg["announce"]["rel_ms"] == -100
    assert by_msg["layer received"]["rel_ms"] == 400
    assert collect_logs.time_to_deliver(merged) == 600


def test_collect_logs_cli(tmp_path, capsys):
    _writelog(tmp_path / "a.jsonl", [
        {"time": 10, "message": "timer start"},
        {"time": 35, "message": "timer stop: startup"},
    ])
    out_file = tmp_path / "merged.jsonl"
    rc = collect_logs.main([str(tmp_path / "a.jsonl"), "-o", str(out_file)])
    assert rc == 0
    lines = [json.loads(x) for x in out_file.read_text().splitlines()]
    assert lines[0]["rel_ms"] == 0 and lines[1]["rel_ms"] == 25
    assert "time to deliver: 25" in capsys.readouterr().err


# -------------------------------------------------------------------- trace


def test_trace_events_from_logs(tmp_path):
    from distributed_llm_dissemination_tpu.cli import trace

    _writelog(tmp_path / "run.jsonl", [
        {"level": "info", "time": 2000, "node": "0", "message": "timer start"},
        {"level": "info", "time": 2500, "node": "1", "layerID": 3,
         "duration_ms": 400.0, "layer_size": 1000, "total_size": 1000,
         "message": "(a fraction of) layer received"},
        {"level": "info", "time": 2500, "node": "1", "layerID": 3,
         "received": 1000, "total": 1000, "message": "layer fragment stored"},
        {"level": "info", "time": 2600, "node": "0", "layer": 3, "dest": 1,
         "send_dur_ms": 500.0, "message": "finished sending layer"},
        {"level": "info", "time": 2700, "node": "0",
         "message": "timer stop: startup"},
        {"level": "info", "time": 2800, "node": "0", "message": "ignored noise"},
    ])
    events = trace.to_trace_events(collect_logs.iter_records([str(tmp_path)]))

    slices = [e for e in events if e["ph"] == "X"]
    assert {s["name"] for s in slices} == {"receive layer 3", "send layer 3"}
    recv = next(s for s in slices if s["name"] == "receive layer 3")
    # End-time log rebased to start: ts = (2500 - 400) ms in µs.
    assert recv["ts"] == (2500 - 400) * 1000.0
    assert recv["dur"] == 400 * 1000.0
    assert recv["pid"] == "1" and recv["tid"] == 3

    instants = {e["name"] for e in events if e["ph"] == "i"}
    assert {"timer start", "timer stop: startup"} <= instants
    assert "ignored noise" not in instants

    counters = [e for e in events if e["ph"] == "C"]
    assert counters and counters[0]["args"]["received"] == 1000

    # Process-name metadata for every node that appears.
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert names == {"node 0", "node 1"}

    # Sorted by timestamp — viewers require monotone input.
    ts = [e["ts"] for e in events if "ts" in e]
    assert ts == sorted(ts)


def test_trace_cli_writes_valid_json(tmp_path, capsys):
    from distributed_llm_dissemination_tpu.cli import trace

    _writelog(tmp_path / "run.jsonl", [
        {"time": 1000, "node": "0", "message": "timer start"},
    ])
    out = tmp_path / "run.trace.json"
    rc = trace.main([str(tmp_path / "run.jsonl"), "-o", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["displayTimeUnit"] == "ms"
    assert any(e["name"] == "timer start" for e in doc["traceEvents"])


def test_span_logs_duration():
    """The span primitive files an interval in the run's ring (it used
    to log a ``duration_ms`` record that nothing read), error and all,
    and ``cli.trace`` renders the dumped ring as slices."""
    import io

    import pytest as _pytest

    from distributed_llm_dissemination_tpu.cli.trace import to_trace_events
    from distributed_llm_dissemination_tpu.utils import trace
    from distributed_llm_dissemination_tpu.utils.logging import JsonLogger

    with trace.span("ingest.write", id="1.7", node=1, layerID=7):
        pass
    rec = trace.spans()[-1]
    assert rec["name"] == "ingest.write" and rec["fields"] == {"layerID": 7}
    assert rec["t1"] - rec["t0"] >= 0

    with _pytest.raises(ValueError):
        with trace.span("ingest.ack"):
            raise ValueError("boom")
    rec = trace.spans()[-1]
    assert rec["name"] == "ingest.ack" and "boom" in rec["fields"]["error"]

    buf = io.StringIO()
    assert trace.dump_spans(JsonLogger(node="1", stream=buf)) == 2
    events = to_trace_events(json.loads(line)
                             for line in buf.getvalue().splitlines())
    slices = [e for e in events if e["ph"] == "X"]
    assert [e["name"] for e in slices] == ["ingest.write", "ingest.ack"]
    assert slices[0]["args"]["id"] == "1.7" and slices[0]["dur"] >= 0


# ----------------------------------------------------------- shipped configs


@pytest.mark.parametrize("name,nodes,layers", [
    ("reference_8node.json", 8, 8),
    ("local_4node.json", 5, 4),
    ("tpu_v5e32_llama70b.json", 8, 80),
    ("boot_tiny_4node_int8.json", 4, 5),
    ("boot_tiny_4node_int4.json", 4, 5),
])
def test_shipped_configs_load(name, nodes, layers):
    conf = cfg.read_json(f"{CONF_DIR}/{name}")
    assert len(conf.nodes) == nodes
    leader = cfg.get_leader_conf(conf)
    assert leader.is_leader
    assigned = set()
    for lids in conf.assignment.values():
        assigned |= set(lids)
    assert len(assigned) == layers
    # Every assigned layer must be seeded somewhere (node disk/RAM or client).
    seeded = set()
    for nc in conf.nodes:
        for by_layer in nc.initial_layers.values():
            seeded |= set(by_layer)
    for cc in conf.clients:
        seeded |= set(cc.layers_rate_limit)
    assert assigned <= seeded


@pytest.mark.parametrize("codec", ["int8", "int4"])
def test_quantized_config_sizes_match_codec(codec):
    from distributed_llm_dissemination_tpu.models import quant
    from distributed_llm_dissemination_tpu.models.llama import CONFIGS

    conf = cfg.read_json(f"{CONF_DIR}/boot_tiny_4node_{codec}.json")
    assert conf.model_codec == codec
    mcfg = CONFIGS[conf.model]
    for nc in conf.nodes:
        for by_layer in nc.initial_layers.values():
            for lid, size in by_layer.items():
                assert size == quant.blob_nbytes_codec(mcfg, lid, codec)


def test_v5e32_config_matches_llama70b():
    from distributed_llm_dissemination_tpu.models.llama import CONFIGS

    conf = cfg.read_json(f"{CONF_DIR}/tpu_v5e32_llama70b.json")
    assert conf.layer_size == CONFIGS["llama3-70b"].layer_nbytes()
    assert conf.mesh is not None
    assert conf.mesh.axis_names == ["pp", "tp"]
    assert conf.mesh.axis_sizes == [8, 4]
    # Pipeline placement: each stage gets a contiguous, disjoint layer range.
    seen = set()
    for stage, lids in sorted(conf.assignment.items()):
        ids = sorted(lids)
        assert ids == list(range(ids[0], ids[0] + len(ids)))
        assert not (set(ids) & seen)
        seen |= set(ids)
    assert len(seen) == 80


@pytest.mark.parametrize("mode", [0, 1, 2, 3])
def test_local_4node_runs_end_to_end(tmp_path, free_port, mode):
    """Spawn the real CLI against conf/local_4node.json (real TCP on
    free loopback ports, 5 processes) in every mode and assert the
    leader prints Time to deliver — the reference's manual smoke run,
    automated."""
    with open(os.path.join(REPO, CONF_DIR, "local_4node.json")) as f:
        conf = json.load(f)
    for n in conf["Nodes"]:
        n["Addr"] = f"127.0.0.1:{free_port()}"
    conf_path = str(tmp_path / "local_4node.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f)

    def spawn(node_id, **kw):
        return subprocess.Popen(
            [sys.executable, "-m",
             "distributed_llm_dissemination_tpu.cli.main",
             "-id", str(node_id), "-f", conf_path, "-m", str(mode)],
            stdout=subprocess.PIPE, **kw)

    procs = []
    try:
        # The leader first: its listener is up before a receiver dials.
        leader = spawn(0, stderr=subprocess.PIPE)
        procs.append(leader)
        for i in range(1, 5):
            procs.append(spawn(i, stderr=subprocess.DEVNULL))
        out, err = leader.communicate(timeout=60)
        m = re.search(rb"Time to deliver: ([0-9.]+)s", out)
        assert m, err[-2000:]
        ttd = float(m.group(1))
        assert 0 < ttd < 30
        if mode == 3:
            # The millisecond-granular flow solver: a 3x1MiB
            # dissemination must not be paced to the reference's
            # 1-second integer-time floor.
            assert ttd < 0.5, f"mode 3 TTD {ttd}s looks 1s-padded"
        for p in procs[1:]:
            assert p.wait(timeout=30) == 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def test_genconf_scenarios_parse_and_match_shapes(tmp_path):
    # The four BASELINE benchmark topologies regenerate deterministically,
    # parse through the loader, and keep their driver-named shapes.
    from distributed_llm_dissemination_tpu.cli import genconf

    genconf.main(["-o", str(tmp_path)])
    shapes = {
        "bench_8node_llama8b.json": (8, 32, 400 << 20),
        "bench_16node_llama70b.json": (16, 80, int(1.6 * (1 << 30))),
        "bench_32node_pipeline.json": (32, 80, int(1.6 * (1 << 30))),
        "bench_64node_llama405b.json": (64, 126, int(3.2 * (1 << 30))),
    }
    for name, (nodes, layers, size) in shapes.items():
        c = cfg.read_json(str(tmp_path / name))
        assert len(c.nodes) == nodes
        assigned = {lid for v in c.assignment.values() for lid in v}
        assert assigned == set(range(layers))
        assert c.layer_size == size
        # The shipped copy matches the generator (no drift).
        shipped = cfg.read_json(os.path.join(REPO, CONF_DIR, name))
        assert shipped == c


def test_pipeline_scenario_assignment_is_contiguous(tmp_path):
    from distributed_llm_dissemination_tpu.cli import genconf

    genconf.main(["-o", str(tmp_path)])
    c = cfg.read_json(str(tmp_path / "bench_32node_pipeline.json"))
    pos = 0
    for dest in sorted(c.assignment):
        lids = sorted(c.assignment[dest])
        assert lids == list(range(pos, pos + len(lids))), dest
        pos += len(lids)
    assert pos == 80


@pytest.mark.timeout(240)
def test_daemon_submit_jobs_cli_end_to_end(tmp_path):
    """The dissemination service CLI (docs/service.md): a -daemon
    leader + daemon-held receivers finish the boot run, then a one-shot
    `-submit` seat admits a job over the wire and `-jobs` polls the
    table until the job is done — the full from-run-to-service loop,
    real processes, real TCP."""
    import socket
    import time as _time

    with open(f"{CONF_DIR}/local_4node.json") as f:
        conf = json.load(f)
    # Dynamic ports + one extra IDLE seat (id 5) for the submitter.
    conf["Nodes"].append({"Id": 5, "Addr": ":0", "NetworkBW": 12500000000})
    socks = [socket.socket() for _ in conf["Nodes"]]
    try:
        for s_, n in zip(socks, conf["Nodes"]):
            s_.bind(("127.0.0.1", 0))
            n["Addr"] = f"127.0.0.1:{s_.getsockname()[1]}"
    finally:
        for s_ in socks:
            s_.close()
    conf_path = str(tmp_path / "daemon.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f)
    spec_path = str(tmp_path / "job.json")
    with open(spec_path, "w") as f:
        # Node 2 doesn't hold layer 0; holders: the leader and node 4.
        json.dump({"JobID": "cli-push", "Priority": 1,
                   "Assignment": {"2": [0]}}, f)

    cli = [sys.executable, "-m",
           "distributed_llm_dissemination_tpu.cli.main", "-f", conf_path,
           "-m", "3", "-daemon", "150"]
    procs = []
    try:
        for i in range(1, 5):
            procs.append(subprocess.Popen(
                cli + ["-id", str(i)],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL))
        leader = subprocess.Popen(
            cli + ["-id", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)
        procs.append(leader)

        def jobtool(*extra):
            return subprocess.run(
                [sys.executable, "-m",
                 "distributed_llm_dissemination_tpu.cli.main",
                 "-f", conf_path, "-id", "5", *extra],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                timeout=60)

        # Submit retries until the daemon window is open (the initial
        # delivery may still be running).  Generous: every probe below
        # is a fresh interpreter (~seconds each on this loaded 2-core
        # box), and the budget is shared with the completion poll.
        deadline = _time.monotonic() + 140
        while True:
            sub = jobtool("-submit", spec_path)
            if sub.returncode == 0:
                break
            assert _time.monotonic() < deadline, sub.stdout[-2000:]
            _time.sleep(0.5)
        admitted = json.loads(sub.stdout)
        assert "cli-push" in admitted["jobs"], admitted

        while True:
            q = jobtool("-jobs")
            assert q.returncode == 0, q.stdout[-2000:]
            table = json.loads(q.stdout)["jobs"]
            if table.get("cli-push", {}).get("State") == "done":
                break
            assert _time.monotonic() < deadline, table
            _time.sleep(0.5)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


@pytest.mark.slow
@pytest.mark.timeout(420)
def test_boot_cli_generates_tokens(tmp_path):
    """The full CLI serving loop: boot_tiny topology with -gen — the
    assignee boots the delivered model AND decodes tokens; the leader
    prints Time to first token."""
    import socket

    with open(f"{CONF_DIR}/boot_tiny_4node.json") as f:
        conf = json.load(f)
    # Hold every probe socket until all ports are collected: closing one
    # at a time leaves a window where another process claims it.
    socks = [socket.socket() for _ in conf["Nodes"]]
    try:
        for s_, n in zip(socks, conf["Nodes"]):
            s_.bind(("127.0.0.1", 0))
            n["Addr"] = f"127.0.0.1:{s_.getsockname()[1]}"
    finally:
        for s_ in socks:
            s_.close()
    conf_path = str(tmp_path / "boot.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    cli = [sys.executable, "-m",
           "distributed_llm_dissemination_tpu.cli.main",
           "-f", conf_path, "-m", "3", "-gen", "2"]
    procs = []
    try:
        for i in range(1, 4):
            procs.append(subprocess.Popen(
                cli + ["-id", str(i)],
                stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
                env=env, text=True))
        leader = subprocess.run(
            cli + ["-id", "0"], stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, timeout=180, env=env, text=True,
        )
        assert "Time to deliver" in leader.stdout
        assert "Time to first token" in leader.stdout
        errs = {}
        for i, p in enumerate(procs, start=1):
            _, errs[i] = p.communicate(timeout=30)
            assert p.returncode == 0, errs[i][-2000:]
        # The assignee (node 3) decoded tokens after its full boot.
        assert '"generated": 2' in errs[3], errs[3][-2000:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def test_genreq_default_seat_skips_client_attached_nodes():
    """A client-attached seat DOES run cli.main (the leader awaits it),
    so its address is live — the default requester seat must not pick
    it, or the bind fails / hijacks that seat's replies."""
    from distributed_llm_dissemination_tpu.cli.genreq import _idle_seat
    from distributed_llm_dissemination_tpu.core.config import Config

    conf = Config.from_json({
        "Nodes": [
            {"Id": 0, "Addr": "a:1", "IsLeader": True},
            {"Id": 1, "Addr": "a:2"},   # assignee
            {"Id": 2, "Addr": "a:3"},   # idle — the right default
            {"Id": 3, "Addr": "a:4"},   # client-attached: must be skipped
        ],
        "Clients": [{"Id": 3, "Addr": "a:5"}],
        "Assignment": {"1": {"0": {}}},
        "LayerSize": 4,
    })
    assert _idle_seat(conf) == 2


@pytest.mark.slow
@pytest.mark.timeout(420)
def test_genreq_cli_serves_inference(tmp_path):
    """The terminal pipeline step over the real CLI: disseminate + boot
    with a -serve window, then cli.genreq asks the booted node for
    tokens from an idle topology seat and gets the engine's greedy ids."""
    import socket

    with open(f"{CONF_DIR}/boot_tiny_4node.json") as f:
        conf = json.load(f)
    conf["Nodes"].append({
        "Id": 4, "Addr": "", "NetworkBW": 12500000000,
        "Sources": {"2": 0}, "InitialLayers": {},
    })
    socks = [socket.socket() for _ in conf["Nodes"]]
    try:
        for s_, n in zip(socks, conf["Nodes"]):
            s_.bind(("127.0.0.1", 0))
            n["Addr"] = f"127.0.0.1:{s_.getsockname()[1]}"
    finally:
        for s_ in socks:
            s_.close()
    conf_path = str(tmp_path / "boot_serve.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    cli = [sys.executable, "-m",
           "distributed_llm_dissemination_tpu.cli.main",
           "-f", conf_path, "-m", "3", "-serve", "120"]
    procs = []
    try:
        for i in range(1, 4):
            procs.append(subprocess.Popen(
                cli + ["-id", str(i)],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                env=env))
        leader = subprocess.run(
            cli + ["-id", "0"], stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, timeout=180, env=env, text=True,
        )
        assert "Time to first token" in leader.stdout

        prompt = [5, 7, 11]
        req = subprocess.run(
            [sys.executable, "-m",
             "distributed_llm_dissemination_tpu.cli.genreq",
             "-f", conf_path, "-node", "3",
             "-prompt", ",".join(map(str, prompt)), "-n", "4"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            timeout=120, env=env, text=True,
        )
        assert req.returncode == 0, req.stderr[-2000:]
        rec = json.loads(req.stdout.strip().splitlines()[-1])
        assert rec["node"] == 3 and rec["prompt"] == prompt

        import jax
        import jax.numpy as jnp
        import numpy as np

        from distributed_llm_dissemination_tpu.models.generate import (
            generate,
        )
        from distributed_llm_dissemination_tpu.models.llama import (
            CONFIGS,
            init_params,
        )

        mcfg = CONFIGS[conf["Model"]]
        want = generate(
            init_params(mcfg, jax.random.key(conf.get("ModelSeed", 0))),
            jnp.asarray([prompt], jnp.int32), mcfg, max_new=4)
        assert rec["tokens"] == np.asarray(jax.device_get(want))[0].tolist()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


@pytest.mark.slow
@pytest.mark.timeout(420)
def test_train_cli_disseminates_then_trains_and_resumes(tmp_path):
    """cli.train end to end: mode-3 pod dissemination lands the blobs,
    the delivered bytes become sharded params, AdamW steps run (loss
    falls), the state checkpoints — and -resume continues the exact
    trajectory without re-disseminating."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    ckpt = str(tmp_path / "state")
    cli = [sys.executable, "-m",
           "distributed_llm_dissemination_tpu.cli.train",
           "-f", os.path.join(CONF_DIR, "train_tiny_pod.json"),
           "-ckpt", ckpt]
    first = subprocess.run(cli + ["-steps", "3"], stdout=subprocess.PIPE,
                           stderr=subprocess.DEVNULL, timeout=600,
                           env=env, text=True)
    assert first.returncode == 0
    rec = json.loads(first.stdout.strip().splitlines()[-1])
    assert rec["final_step"] == 3 and len(rec["losses"]) == 3
    assert rec["losses"][-1] < rec["losses"][0]  # it actually trains
    assert rec["ttd_s"] > 0  # the weights really disseminated first

    again = subprocess.run(cli + ["-steps", "2", "-resume"],
                           stdout=subprocess.PIPE,
                           stderr=subprocess.DEVNULL, timeout=600,
                           env=env, text=True)
    assert again.returncode == 0
    rec2 = json.loads(again.stdout.strip().splitlines()[-1])
    assert rec2["resumed_step"] == 3 and rec2["final_step"] == 5
    assert "ttd_s" not in rec2  # resume skips dissemination
    assert rec2["losses"][-1] < rec["losses"][-1]  # still descending
