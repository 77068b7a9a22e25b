"""TTD matrix: time-to-deliver across all four modes, recorded.

The reference's primary metric is time-to-deliver, printed per run
(``/root/reference/cmd/main.go:173-181``) and never recorded anywhere.
This harness runs the REAL CLI (one OS process per node, loopback TCP —
the reference's own benchmark shape, ``distributor/node_test.go:275-326``)
for every mode over the shipped topologies and emits a checked-in matrix,
including the north-star secondary target: mode 1 (peer retransmission)
matching mode 0 (leader broadcast) completion time.

    python -m distributed_llm_dissemination_tpu.cli.ttd_matrix \
        -o TTD_MATRIX.json [-scale BYTES] [-trials N]

Scenarios:
- ``local_4node``: 4 receivers + leader, 3 dummy layers @1 MiB.
- ``reference_8node``: the reference benchmark topology (7 seeders co-send
  one cold node's full model) with LayerSize scaled from 10.18 GiB down to
  ``-scale`` bytes so the matrix runs on loopback in seconds.  Rates and
  NIC bandwidths stay at their configured (physical) values — the matrix
  compares the MODES' scheduling behavior, which scaled-down rates would
  drown in artificial pacing.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import subprocess
import sys
import tempfile
import time

CONF_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "conf")
_TTD_RE = re.compile(r"Time to deliver: ([0-9.]+)s")
# Mode-3 plan fidelity: the leader prints its solver's min-time next to
# the achieved TTD (cli.main); recorded as predicted_s/solve_ms columns.
_PRED_RE = re.compile(
    r"Predicted time to deliver: ([0-9.]+)s \(solve ([0-9.]+)ms\)")


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def measure_loopback_gbps(streams: int = 1, per_stream: int = 192 << 20,
                          chunk: int = 1 << 20) -> float:
    """This host's RAW loopback TCP bandwidth: ``streams`` concurrent
    sender/receiver thread pairs move ``per_stream`` bytes each through
    plain sockets (sendall / recv_into, no framing, no assembly) and the
    aggregate bytes-over-wall-clock is the ceiling the physical rows are
    judged against — the same honest-denominator pattern as bench.py's
    ``raw_dma_gbps``/``link_fraction``.  Multi-stream probes measure what
    the STRIPED data plane can draw on; on small hosts the loopback is
    CPU-bound, so more streams than cores can come back SLOWER than one —
    which is exactly why the ceiling must be measured, not assumed."""
    import socket
    import threading

    srv = socket.create_server(("127.0.0.1", 0))
    port = srv.getsockname()[1]

    def sender():
        with socket.create_connection(("127.0.0.1", port)) as s:
            buf = memoryview(bytearray(chunk))
            sent = 0
            while sent < per_stream:
                s.sendall(buf[: min(chunk, per_stream - sent)])
                sent += chunk

    # Bytes each receiver REALLY got: a sender thread dying mid-stream
    # (its exception is swallowed by the thread) must shrink the
    # numerator, not silently inflate the recorded ceiling.
    delivered = [0] * streams

    def receiver(conn, slot):
        with conn:
            buf = bytearray(4 << 20)
            while delivered[slot] < per_stream:
                r = conn.recv_into(buf)
                if r == 0:
                    return
                delivered[slot] += r

    senders = [threading.Thread(target=sender, daemon=True)
               for _ in range(streams)]
    t0 = time.monotonic()
    for t in senders:
        t.start()
    # A sender whose connect fails dies with its exception swallowed by
    # the thread; without a timeout the accept() below would then hang
    # the whole harness before any node process even spawns.  A failed
    # probe returns 0.0 and the caller skips the ceiling columns.
    srv.settimeout(30.0)
    receivers = []
    accepted = []
    try:
        for i in range(streams):
            conn = srv.accept()[0]
            accepted.append(conn)
            receivers.append(threading.Thread(
                target=receiver, args=(conn, i)))
    except OSError:
        print("loopback ceiling probe failed (accept timeout); "
              "skipping ceiling columns", file=sys.stderr)
        # Release everything or the stuck senders outlive the probe:
        # closing the accepted conns fails their peers' sendall, and
        # closing the listener fails any connect still retrying.
        for conn in accepted:
            conn.close()
        srv.close()
        for t in senders:
            t.join(timeout=5.0)
        return 0.0
    for t in receivers:
        t.start()
    for t in receivers:
        t.join()
    dt = time.monotonic() - t0
    for t in senders:
        t.join(timeout=10.0)
    srv.close()
    return round(sum(delivered) / max(dt, 1e-9) / 1e9, 3)


def _cpu_env(base: dict = None) -> dict:
    from distributed_llm_dissemination_tpu.utils.env import cpu_pinned_env

    return cpu_pinned_env(base)


def _localize_config(src_path: str, out_path: str,
                     scale_to: int = 0, mutate=None) -> None:
    """Rewrite node/client addresses to free loopback ports (the shipped
    configs use fixed ports that anything else on the host may hold) and,
    when ``scale_to`` > 0, scale every LayerSize down to loopback-friendly
    bytes; rates and NIC bandwidths keep their configured (physical)
    values.  ``mutate``: optional callback applied to the loaded dict
    before the rewrite — scenario-specific edits share this one
    load/write path."""
    with open(src_path) as f:
        conf = json.load(f)
    if mutate is not None:
        mutate(conf)
    if scale_to > 0:
        if "LayerSize" in conf:
            conf["LayerSize"] = scale_to
        for n in conf["Nodes"]:
            for by_layer in (n.get("InitialLayers") or {}).values():
                for lc in by_layer.values():
                    if "LayerSize" in lc:
                        lc["LayerSize"] = scale_to
    for n in conf["Nodes"]:
        n["Addr"] = f"127.0.0.1:{_free_port()}"
    for c in conf.get("Clients") or []:
        c["Addr"] = f"127.0.0.1:{_free_port()}"
    with open(out_path, "w") as f:
        json.dump(conf, f)


def run_once(conf_path: str, mode: int, timeout: float = 120.0,
             env: dict = None, extra_args=()) -> float:
    """One full dissemination via the real CLI; returns the leader's TTD.
    ``extra_args`` go to every node process (not external clients), e.g.
    ("-boot", "none") for dissemination-only runs of boot topologies."""
    with open(conf_path) as f:
        conf = json.load(f)
    leader_id = next(n["Id"] for n in conf["Nodes"]
                     if n.get("IsLeader") or n.get("isLeader"))
    receiver_ids = [n["Id"] for n in conf["Nodes"] if n["Id"] != leader_id]
    client_ids = [c["Id"] for c in conf.get("Clients") or []]

    def spawn(node_id, extra=()):
        return subprocess.Popen(
            [sys.executable, "-m",
             "distributed_llm_dissemination_tpu.cli.main",
             "-id", str(node_id), "-f", conf_path, "-m", str(mode), *extra],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        )

    procs = []
    try:
        leader = spawn(leader_id, extra_args)
        procs.append(leader)
        time.sleep(0.3)  # listener up before the dial-retry window matters
        for rid in receiver_ids:
            procs.append(spawn(rid, extra_args))
        for cid in client_ids:
            procs.append(spawn(cid, ("-c",)))
        out, _ = leader.communicate(timeout=timeout)
        text = out.decode()
        m = _TTD_RE.search(text)
        if not m:
            raise RuntimeError(
                f"no TTD in leader output (mode {mode}): {out[-2000:]!r}"
            )
        pm = _PRED_RE.search(text)
        run_once.last_predicted = (
            (float(pm.group(1)), float(pm.group(2))) if pm else None)
        for p in procs[1:]:
            if p.args[-1] != "-c":  # clients run forever; killed below
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    # Known container flake (see run_span_overhead): a
                    # seat sporadically wedges in its post-run
                    # ack-requeue loop.  The TTD above is already
                    # measured, so kill the straggler instead of
                    # failing the whole matrix.
                    print(f"warn: post-run seat wedge (pid {p.pid}), "
                          "killing — known container flake",
                          file=sys.stderr)
                    p.kill()
        return float(m.group(1))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def _parse_summary_line(out: str):
    """podrun's machine-readable summary (the last JSON line carrying
    ``ttd_s``): collective-cache stats + phase totals, or None."""
    summary = None
    for line in out.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                d = json.loads(line)
            except ValueError:
                continue
            if "ttd_s" in d:
                summary = d
    return summary


def run_once_pod(conf_path: str, mode: int, timeout: float = 240.0) -> float:
    """One fabric dissemination via the single-controller pod driver
    (cli.podrun) on a virtual 8-device CPU mesh; returns the TTD.  The
    layer bytes move over the device plane — this row measures the
    fabric's scheduling + ingest path, not TCP."""
    env = _cpu_env()
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    proc = subprocess.run(
        [sys.executable, "-m",
         "distributed_llm_dissemination_tpu.cli.podrun",
         "-f", conf_path, "-m", str(mode)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        timeout=timeout, env=env,
    )
    out = proc.stdout.decode()
    m = _TTD_RE.search(out)
    if not m:
        raise RuntimeError(
            f"no TTD in podrun output (mode {mode}): {proc.stdout[-2000:]!r}"
        )
    # Stash the run's machine-readable summary (collective-cache stats,
    # phase totals) for run_matrix to fold into the scenario record.
    summary = _parse_summary_line(out)
    run_once_pod.last_summary = summary
    run_once_pod.last_predicted = (
        (summary["predicted_s"], summary.get("solve_ms", 0.0))
        if summary and "predicted_s" in summary else None)
    return float(m.group(1))


def spmd_two_proc_config(scale: int, layers: int = 3) -> dict:
    """A 2-process multi-controller SPMD fabric topology (leader seeds,
    node 1 assigned): one OS process per node, one jax.distributed
    runtime, layer bytes as lockstep collectives
    (``parallel/spmd_fabric.py``).  Free loopback ports are assigned
    here.  THE shared builder: the recorded matrix row and the 2-process
    e2e tests (tests/test_spmd_fabric.py) exercise the same topology."""
    return {
        "Nodes": [
            {"Id": 0, "Addr": f"127.0.0.1:{_free_port()}", "IsLeader": True,
             "NetworkBW": 12500000000, "Sources": {"2": 0},
             "InitialLayers": {"2": {str(i): {"LayerSize": scale}
                                     for i in range(layers)}}},
            {"Id": 1, "Addr": f"127.0.0.1:{_free_port()}",
             "NetworkBW": 12500000000, "Sources": {"2": 0},
             "InitialLayers": {}},
        ],
        "Assignment": {"1": {str(i): {} for i in range(layers)}},
        "LayerSize": scale,
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [2],
                 "PipelineAxis": "nodes", "Fabric": True},
        "Distributed": {"Coordinator": f"127.0.0.1:{_free_port()}",
                        "CpuCollectives": "gloo"},
    }


def spmd_pod_config(scale: int, layers: int = 2) -> dict:
    """A 3-process SPMD pod-delivery topology (docs/fabric.md): leader
    0 seeds; nodes 1 and 2 form ONE pod and both want every layer —
    the NIC ships each member its 1/2 shard (host TCP), and the leader
    dispatches the pod gather as a lockstep collective that leaves the
    full tree on BOTH members.  The shared builder for the 3-process
    e2e test (tests/test_spmd_fabric.py)."""
    return {
        "Nodes": [
            {"Id": 0, "Addr": f"127.0.0.1:{_free_port()}",
             "IsLeader": True, "NetworkBW": 12500000000,
             "Sources": {"2": 0},
             "InitialLayers": {"2": {str(i): {"LayerSize": scale}
                                     for i in range(layers)}}},
            {"Id": 1, "Addr": f"127.0.0.1:{_free_port()}",
             "NetworkBW": 12500000000, "Sources": {"2": 0},
             "InitialLayers": {}},
            {"Id": 2, "Addr": f"127.0.0.1:{_free_port()}",
             "NetworkBW": 12500000000, "Sources": {"2": 0},
             "InitialLayers": {}},
        ],
        "Assignment": {"1": {str(i): {} for i in range(layers)},
                       "2": {str(i): {} for i in range(layers)}},
        "LayerSize": scale,
        "Pods": [[1, 2]],
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [3],
                 "PipelineAxis": "nodes", "Fabric": True},
        "Distributed": {"Coordinator": f"127.0.0.1:{_free_port()}",
                        "CpuCollectives": "gloo"},
    }


def _spmd_config(out_path: str, scale: int) -> None:
    with open(out_path, "w") as f:
        json.dump(spmd_two_proc_config(scale), f)


def run_once_spmd(conf_path: str, mode: int, timeout: float = 240.0) -> float:
    """One dissemination over the multi-controller SPMD fabric: the REAL
    per-node CLI, one OS process per node, collectives over gloo."""
    env = _cpu_env()
    env.pop("XLA_FLAGS", None)  # one device per process
    return run_once(conf_path, mode, timeout, env=env)


def run_matrix(scale: int, trials: int, modes=(0, 1, 2, 3),
               timeout: float = 240.0) -> dict:
    with tempfile.TemporaryDirectory() as td:
        local4 = os.path.join(td, "local_4node.json")
        _localize_config(os.path.join(CONF_DIR, "local_4node.json"), local4)
        scaled = os.path.join(td, "reference_8node_scaled.json")
        _localize_config(os.path.join(CONF_DIR, "reference_8node.json"),
                         scaled, scale_to=scale)
        fabric = os.path.join(td, "pod_fabric_4node.json")
        _localize_config(os.path.join(CONF_DIR, "pod_fabric_4node.json"),
                         fabric, scale_to=scale)
        spmd = os.path.join(td, "spmd_2proc.json")
        _spmd_config(spmd, scale)

        def drop_fabric(conf):
            # Host-path run of the 2-slice topology: the mode-3 leader
            # still receives Mesh.Slices/DcnBW (the topology LP paces
            # cross-slice senders to the pair capacity) but no process
            # needs the 32-device fabric mesh.
            conf.get("Mesh", {}).pop("Fabric", None)

        dcn = os.path.join(td, "tpu_2slice_dcn.json")
        _localize_config(os.path.join(CONF_DIR, "tpu_2slice_dcn.json"),
                         dcn, scale_to=scale, mutate=drop_fabric)
        scenarios = {
            "local_4node": (local4, run_once),
            f"reference_8node@{scale >> 20}MiB": (scaled, run_once),
            f"dcn_2slice_8node@{scale >> 20}MiB": (dcn, run_once),
            f"pod_fabric_4node@{scale >> 20}MiB": (fabric, run_once_pod),
            f"spmd_fabric_2proc@{scale >> 20}MiB": (spmd, run_once_spmd),
        }
        results: dict = {"scenarios": {}, "scale_bytes": scale,
                         "trials": trials}
        for name, (path, runner) in scenarios.items():
            per_mode = {}
            for mode in modes:
                ts = [runner(path, mode, timeout) for _ in range(trials)]
                per_mode[str(mode)] = {
                    "ttd_s": round(statistics.median(ts), 4),
                    "all": [round(t, 4) for t in ts],
                }
                summary = getattr(runner, "last_summary", None)
                if summary and summary.get("collective_cache"):
                    per_mode[str(mode)]["collective_cache"] = (
                        summary["collective_cache"])
                if summary and summary.get("telemetry"):
                    # Each pod run's counter/histogram snapshot rides its
                    # row — event counts come from the run's own flight
                    # recorder, not hand-collected greps.
                    per_mode[str(mode)]["telemetry"] = summary["telemetry"]
                if mode == 3:
                    # Plan fidelity: the last trial's solver prediction
                    # (deterministic across trials) next to achieved TTD.
                    pred = getattr(runner, "last_predicted", None)
                    if pred is None and runner is run_once_spmd:
                        pred = getattr(run_once, "last_predicted", None)
                    if pred:
                        per_mode["3"]["predicted_s"] = round(pred[0], 4)
                        per_mode["3"]["solve_ms"] = round(pred[1], 3)
                print(f"{name} mode {mode}: TTD {per_mode[str(mode)]['ttd_s']}s",
                      file=sys.stderr, flush=True)
            if "0" in per_mode and "1" in per_mode:
                per_mode["mode1_vs_mode0"] = round(
                    per_mode["1"]["ttd_s"] / max(per_mode["0"]["ttd_s"], 1e-9), 3
                )
            results["scenarios"][name] = per_mode
    return results


def _codec_variant(src_path: str, out_path: str, codec: str,
                   rate: int) -> None:
    """boot_tiny_4node's topology, retargeted at the tiny2 model (~2 MiB
    layers, so the 256 KiB burst bucket is noise), every in-RAM source
    rate-limited to ``rate`` B/s, under the given transfer codec — the
    A/B pair where TTD is bytes over a fixed rate, so the codec's
    wire-size ratio shows up as the TTD ratio."""
    def mutate(conf):
        conf["Model"] = "tiny2"
        conf["ModelCodec"] = codec
        for n in conf["Nodes"]:
            n["Sources"] = {"2": rate}

    _localize_config(src_path, out_path, mutate=mutate)


def run_codec_ab(trials: int, rate: int = 4 << 20, mode: int = 3,
                 timeout: float = 240.0) -> dict:
    """Measured codec benefit: the same model topology disseminated
    raw vs int8 vs int4 at a fixed source rate (models/quant.py shrinks
    the blob bytes ~0.51x / ~0.27x, so mode-3 completion time should
    shrink by roughly the same ratio; the transport's reference-parity
    256 KiB burst bucket gives each job a free head start, so at tiny2's
    ~2 MiB layers the measured ratios sit a bit below the pure size
    ratios)."""
    out: dict = {"rate_bytes_per_s": rate, "mode": mode, "model": "tiny2"}
    # Blob fabrication imports jax in the receivers: CPU-pinned so the
    # row measures the rate-limited wire, not the device.  -boot none
    # skips the post-TTD model boot (compile seconds per run that the
    # TTD timer doesn't even see).
    env = _cpu_env()
    with tempfile.TemporaryDirectory() as td:
        for codec in ("raw", "int8", "int4"):
            path = os.path.join(td, f"boot_{codec}.json")
            _codec_variant(os.path.join(CONF_DIR, "boot_tiny_4node.json"),
                           path, codec, rate)
            ts = [run_once(path, mode, timeout, env=env,
                           extra_args=("-boot", "none"))
                  for _ in range(trials)]
            out[codec] = {"ttd_s": round(statistics.median(ts), 4),
                          "all": [round(t, 4) for t in ts]}
            print(f"codec {codec}: TTD {out[codec]['ttd_s']}s",
                  file=sys.stderr, flush=True)
    for codec in ("int8", "int4"):
        out[f"{codec}_vs_raw"] = round(
            out[codec]["ttd_s"] / max(out["raw"]["ttd_s"], 1e-9), 3
        )
    return out


def _codec_wire_variant(src_path: str, out_path: str, wire_codec: str,
                        rate: int) -> None:
    """boot_tiny_4node retargeted at tiny2 with RAW canonical blobs and
    every in-RAM source rate-limited — the rate-limited BASELINE the
    negotiated wire codec exists for.  ``wire_codec`` "" leaves the
    run canonical (the A side)."""
    def mutate(conf):
        conf["Model"] = "tiny2"
        if wire_codec:
            conf["WireCodec"] = wire_codec
        for n in conf["Nodes"]:
            n["Sources"] = {"2": rate}

    _localize_config(src_path, out_path, mutate=mutate)


def run_codec_wire(trials: int, rate: int = 4 << 20, mode: int = 3,
                   timeout: float = 240.0) -> dict:
    """The NEGOTIATED wire-codec row (docs/codec.md): the same
    raw-canonical tiny2 topology disseminated with and without
    ``WireCodec: int8`` at a fixed slow source rate.  Unlike
    ``run_codec_ab`` (which re-fabricates the whole run's blobs in the
    codec), here the SEEDERS HOLD RAW BYTES and the leader chooses the
    encoded form per transfer — encode-on-send, decode-at-staging,
    codec-qualified digests — so the TTD ratio measures the negotiated
    plane end to end.  The RUN_REPORT's per-dest table cross-checks the
    wire bytes against ``quant.blob_nbytes_codec`` exactly."""
    from ..models import quant
    from ..models.llama import CONFIGS

    mcfg = CONFIGS["tiny2"]
    blob_ids = list(range(5))  # boot_tiny_4node assigns blobs 0-4
    raw_bytes = sum(quant.blob_nbytes_codec(mcfg, b, "raw")
                    for b in blob_ids)
    int8_bytes = sum(quant.blob_nbytes_codec(mcfg, b, "int8")
                     for b in blob_ids)
    out: dict = {"rate_bytes_per_s": rate, "mode": mode, "model": "tiny2",
                 "raw_bytes_per_dest": raw_bytes,
                 "int8_bytes_per_dest": int8_bytes,
                 "ratio": round(raw_bytes / int8_bytes, 4)}
    env = _cpu_env()
    with tempfile.TemporaryDirectory() as td:
        for label, wire in (("raw_wire", ""), ("int8_wire", "int8")):
            path = os.path.join(td, f"wire_{label}.json")
            _codec_wire_variant(
                os.path.join(CONF_DIR, "boot_tiny_4node.json"),
                path, wire, rate)
            report = os.path.join(td, f"report_{label}")
            ts = []
            for k in range(trials):
                extra = ["-boot", "none"]
                if k == 0:
                    extra += ["-report", report]
                ts.append(run_once(path, mode, timeout, env=env,
                                   extra_args=tuple(extra)))
            row = {"ttd_s": round(statistics.median(ts), 4),
                   "all": [round(t, 4) for t in ts]}
            try:
                with open(report + ".json") as f:
                    rep = json.load(f)
                row["dests"] = rep.get("dests") or {}
                row["codec_counters"] = {
                    k: v for k, v in (rep.get("counters") or {}).items()
                    if k.startswith("codec.")}
                row["provenance"] = rep.get("provenance", "")
            except (OSError, ValueError):
                row["dests"] = {}
            ts_str = row["ttd_s"]
            print(f"codec_wire {label}: TTD {ts_str}s",
                  file=sys.stderr, flush=True)
            out[label] = row
    out["int8_vs_raw"] = round(
        out["int8_wire"]["ttd_s"] / max(out["raw_wire"]["ttd_s"], 1e-9), 3)
    # The acceptance cross-check: every dest's delivered wire bytes
    # must be EXACTLY the blob_nbytes_codec sums (int8 run), and the
    # TTD must drop ~proportionally to the compression ratio.
    dests = out["int8_wire"].get("dests") or {}
    out["wire_bytes_exact"] = bool(dests) and all(
        row.get("wire_bytes") == int8_bytes for row in dests.values())
    expect = 1.0 / out["ratio"]
    out["bound"] = {
        "expected_ttd_fraction": round(expect, 4),
        # The transport's reference-parity 256 KiB burst bucket gives
        # each job a free head start at these ~1-2 MiB layers, so allow
        # a generous margin above the pure size ratio.
        "met": out["int8_vs_raw"] <= expect * 1.35 + 0.05,
    }
    out["entropy"] = run_codec_wire_entropy(trials, rate=rate, mode=mode,
                                            timeout=timeout)
    return out


def run_codec_wire_entropy(trials: int, rate: int = 4 << 20,
                           mode: int = 3,
                           timeout: float = 240.0) -> dict:
    """The ENTROPY-CODED wire arm (docs/codec.md): the same tiny2
    topology under ``WireCodec: int8e``.  Entropy forms are
    DATA-DEPENDENT — their size is known only by encoding — so the
    leader must hold the blobs to price them: this variant seeds the
    leader with the full blob set (both arms, so the A/B stays fair)
    and the acceptance bar is EXACTNESS, not a byte win: every dest's
    delivered wire bytes must equal the solver-priced encoded sizes
    (computed independently here by DLE1-encoding the run's seeded
    blobs).  On tiny2's seeded-random weights the quantized bytes are
    near-incompressible, so int8e lands a hair ABOVE int8 — recorded
    honestly; the order-of-magnitude entropy wins live on sparse/
    low-entropy layers and on the delta rows."""
    from ..models import quant, serde
    from ..models.llama import CONFIGS

    mcfg = CONFIGS["tiny2"]
    blob_ids = list(range(5))  # boot_tiny_4node assigns blobs 0-4
    raw_bytes = sum(quant.blob_nbytes_codec(mcfg, b, "raw")
                    for b in blob_ids)
    # The independent pricing: encode the SAME seeded blobs the run
    # fabricates (ModelSeed 0) and sum the true DLE1 sizes.
    int8e_bytes = sum(
        len(quant.encode_blob(mcfg, b, serde.seeded_blob(mcfg, b, 0),
                              "int8e"))
        for b in blob_ids)
    int8_bytes = sum(quant.blob_nbytes_codec(mcfg, b, "int8")
                     for b in blob_ids)

    def variant(src_path: str, out_path: str, wire_codec: str) -> None:
        def mutate(conf):
            conf["Model"] = "tiny2"
            if wire_codec:
                conf["WireCodec"] = wire_codec
            # Seed the leader with every blob any seeder holds: the
            # data-dependent sizing encodes the leader's own copy.
            blobs: dict = {}
            for n in conf["Nodes"]:
                for by_layer in (n.get("InitialLayers") or {}).values():
                    blobs.update(by_layer)
            lead = next(n for n in conf["Nodes"] if n.get("IsLeader"))
            lead["InitialLayers"] = {"2": dict(blobs)}
            for n in conf["Nodes"]:
                n["Sources"] = {"2": rate}

        _localize_config(src_path, out_path, mutate=mutate)

    out: dict = {"rate_bytes_per_s": rate, "mode": mode,
                 "model": "tiny2",
                 "raw_bytes_per_dest": raw_bytes,
                 "int8_bytes_per_dest": int8_bytes,
                 "int8e_bytes_per_dest": int8e_bytes,
                 "ratio_vs_raw": round(raw_bytes / int8e_bytes, 4),
                 "int8e_vs_int8_bytes": round(int8e_bytes / int8_bytes,
                                              4)}
    env = _cpu_env()
    with tempfile.TemporaryDirectory() as td:
        for label, wire in (("raw_wire", ""), ("int8e_wire", "int8e")):
            path = os.path.join(td, f"wire_{label}.json")
            variant(os.path.join(CONF_DIR, "boot_tiny_4node.json"),
                    path, wire)
            report = os.path.join(td, f"report_{label}")
            ts = []
            for k in range(trials):
                extra = ["-boot", "none"]
                if k == 0:
                    extra += ["-report", report]
                ts.append(run_once(path, mode, timeout, env=env,
                                   extra_args=tuple(extra)))
            row = {"ttd_s": round(statistics.median(ts), 4),
                   "all": [round(t, 4) for t in ts]}
            try:
                with open(report + ".json") as f:
                    rep = json.load(f)
                row["dests"] = rep.get("dests") or {}
                row["codec_counters"] = {
                    k: v for k, v in (rep.get("counters") or {}).items()
                    if k.startswith("codec.")}
                row["provenance"] = rep.get("provenance", "")
            except (OSError, ValueError):
                row["dests"] = {}
            print(f"codec_wire entropy {label}: TTD {row['ttd_s']}s",
                  file=sys.stderr, flush=True)
            out[label] = row
    out["int8e_vs_raw"] = round(
        out["int8e_wire"]["ttd_s"] / max(out["raw_wire"]["ttd_s"], 1e-9),
        3)
    # The acceptance bar: wire bytes per dest EXACTLY equal the
    # solver-priced entropy sizes.
    dests = out["int8e_wire"].get("dests") or {}
    out["wire_bytes_exact"] = bool(dests) and all(
        row.get("wire_bytes") == int8e_bytes for row in dests.values())
    return out


# The driver-provided BASELINE.json scenarios (#2-#5), materialized by
# cli.genconf: (config file, the modes to record).  The 64-node row runs
# ALL FOUR modes so the mode-3 solver is exercised — and its solve time
# recorded — at the scenario's full node count (VERDICT item 6).
BASELINE_SCENARIOS = (
    ("bench_8node_llama8b.json", (0,)),
    ("bench_16node_llama70b.json", (1,)),
    ("bench_32node_pipeline.json", (1,)),
    ("bench_64node_llama405b.json", (0, 1, 2, 3)),
)


def run_baseline_scenarios(scale: int = 64 << 20,
                           timeout: float = 1200.0) -> dict:
    """Recorded TTDs for the BASELINE scenarios, at ≥64 MiB layers.

    Layer sizes scale down from physical (64-node Llama-405B at full
    size needs a real cluster) but stay big enough that the bandwidth
    term — not per-transfer overhead — dominates; node counts and
    schedules stay faithful: up to 64 OS processes over loopback, the
    reference's own benchmark shape.  Each scenario records its per-mode
    rows with the layer bytes; mode-3 rows carry the solver's
    predicted_s and solve_ms."""
    if scale <= 0:
        raise ValueError("baseline scale must be positive (bytes)")
    out = {}
    with tempfile.TemporaryDirectory() as td:
        for name, modes in BASELINE_SCENARIOS:
            local = os.path.join(td, name)
            _localize_config(os.path.join(CONF_DIR, name), local,
                             scale_to=scale)
            key = f"{os.path.splitext(name)[0]}@{scale >> 20}MiB"
            rows = []
            for mode in modes:
                ttd = run_once(local, mode, timeout)
                row = {"mode": mode, "ttd_s": round(ttd, 4),
                       "layer_bytes": scale}
                pred = getattr(run_once, "last_predicted", None)
                if mode == 3 and pred:
                    row["predicted_s"] = round(pred[0], 4)
                    row["solve_ms"] = round(pred[1], 3)
                rows.append(row)
                print(f"{key} mode {mode}: TTD {ttd:.4f}s",
                      file=sys.stderr, flush=True)
            out[key] = rows
    return out


def run_north_star(timeout_unused: float = 0.0) -> dict:
    """VERDICT item 5: argue the BASELINE north-star target (<10 s /
    ≥70% ICI utilization for Llama-70B's 80 layers on a v5e-32) by
    MODEL — run the mode-3 solver on ``conf/tpu_v5e32_llama70b.json``
    exactly as the leader would and record predicted completion time,
    aggregate rate, and the dest-side ICI-utilization fraction.  No
    hardware in the loop: the solver is the only instrument this
    environment allows, and its prediction-vs-achieved fidelity is
    regression-guarded separately (the predicted_s columns).

    Three rows, same assignment (each of 8 hosts ends up holding its 10
    pipeline-stage layers):
    - ``shipped``: the config as checked in — ONE seeder whose 80 blobs
      sit behind a 3 GB/s disk-class source;
    - ``mem_seeder``: the same seeder's blobs re-typed in-RAM (source
      uncapped, its 25 GB/s line rate is the ceiling);
    - ``mem_4seeders``: hot-spare replicas — 4 of the 8 hosts hold the
      full blob set in RAM, the paper's multi-seeder co-send shape.
    The variants isolate WHERE the target lives: the solver hits <10 s
    the moment sources stop being the bottleneck, and ≥70% dest-side
    utilization with replicated in-RAM seeders."""
    from ..core import config as cfgmod
    from ..core.types import LayerLocation, LayerMeta, SourceType
    from ..sched import make_flow_graph

    conf = cfgmod.read_json(
        os.path.join(CONF_DIR, "tpu_v5e32_llama70b.json"))
    line_bw = {nc.id: nc.network_bw for nc in conf.nodes}
    shipped_holdings = {}
    sizes = {}
    for nc in conf.nodes:
        by_node = {}
        for st, by_layer in (nc.initial_layers or {}).items():
            rate = nc.sources.get(st, 0)
            for lid, size in by_layer.items():
                size = size or conf.layer_size
                by_node[lid] = (st, rate, size)
                sizes[lid] = size
        if by_node:
            shipped_holdings[nc.id] = by_node
    topo = conf.mesh.topology() if conf.mesh is not None else None

    def solve(label: str, holdings: dict) -> dict:
        status = {nc.id: {} for nc in conf.nodes}
        layer_sizes = {}
        for node_id, by_node in holdings.items():
            for lid, (st, rate, size) in by_node.items():
                loc = (LayerLocation.DISK if st == SourceType.DISK
                       else LayerLocation.INMEM)
                status[node_id][lid] = LayerMeta(
                    location=loc, limit_rate=rate, source_type=st,
                    data_size=size)
                layer_sizes[lid] = size
        # The leader's assign_jobs discipline: pairs the dest already
        # holds are satisfied, the solver plans the rest.
        modified = {}
        for dest, lids in conf.assignment.items():
            for lid, meta in lids.items():
                if lid in status.get(dest, {}):
                    continue
                modified.setdefault(dest, {})[lid] = meta
        t0 = time.monotonic()
        graph = make_flow_graph(modified, status, layer_sizes, line_bw,
                                topology=topo)
        t_ms, jobs = graph.get_job_assignment()
        solve_ms = (time.monotonic() - t0) * 1000
        wire = sum(j.data_size for jl in jobs.values() for j in jl)
        pred_s = t_ms / 1000.0
        dests = {j.dest_id for jl in jobs.values() for j in jl}
        dest_cap = sum(line_bw[d] for d in sorted(dests))
        agg_gbps = wire / max(pred_s, 1e-9) / 1e9
        rec = {
            "label": label,
            "wire_bytes": wire,
            "predicted_s": round(pred_s, 3),
            "solve_ms": round(solve_ms, 1),
            "aggregate_gbps": round(agg_gbps, 2),
            "dest_line_gbps": round(dest_cap / 1e9, 1),
            "ici_utilization": round(agg_gbps / max(dest_cap / 1e9, 1e-9),
                                     3),
        }
        rec["meets_time"] = pred_s < 10.0
        rec["meets_utilization"] = rec["ici_utilization"] >= 0.70
        print(f"north_star {label}: predicted {pred_s:.2f}s, "
              f"{rec['ici_utilization']:.0%} dest-side utilization "
              f"(solve {solve_ms:.0f}ms)", file=sys.stderr, flush=True)
        return rec

    mem1 = {n: {lid: (SourceType.MEM, 0, size)
                for lid, (_st, _r, size) in by.items()}
            for n, by in shipped_holdings.items()}
    seeders4 = sorted(line_bw)[:4]
    mem4 = {n: {lid: (SourceType.MEM, 0, sizes[lid]) for lid in sizes}
            for n in seeders4}
    return {
        "config": "tpu_v5e32_llama70b.json",
        "layers": len(sizes),
        "layer_bytes": next(iter(sizes.values())) if sizes else 0,
        "target": {"time_s": 10.0, "utilization": 0.70},
        "rows": [
            solve("shipped (1 disk seeder @3GB/s)", shipped_holdings),
            solve("mem_seeder (1 in-RAM seeder)", mem1),
            solve("mem_4seeders (hot-spare replicas)", mem4),
        ],
    }


_TTFT_RE = re.compile(r"Time to first token: ([0-9.]+)s")


# Seeded fault schedule for the physical row's FAULTED sibling
# (transport/faults.py): corrupt every 7th and drop every 11th inbound
# layer frame below the CRC check, duplicate every 13th outbound layer
# send — each capped at 6 firings per node so recovery cost is bounded
# and the run stays deterministic.
PHYSICAL_FAULT_SPEC = "seed=3,corrupt=7,dropin=11,dup=13,times=6"


def physical_config() -> tuple:
    """PHYSICAL-size scenario: 2 seeders hold the ``llama3-8b-d4v8k``
    blobs — four ~416 MiB layers (EXACTLY the per-layer bytes ``bench.py``
    measures: the full 8B layer shape) plus a vocab-trimmed head — and
    one cold dest is assigned everything, mode 3 with ``-hbm`` staging
    and a model boot (TTFT).  Returns (conf dict, per-layer bytes, the
    dest's total assigned bytes)."""
    from ..models import quant, serde
    from ..models.llama import CONFIGS

    mcfg = CONFIGS["llama3-8b-d4v8k"]
    head_id = serde.head_blob_id(mcfg)
    nodes = []
    for i in range(3):
        nodes.append({
            "Id": i, "Addr": f"127.0.0.1:{_free_port()}",
            "NetworkBW": 10**10, "IsLeader": i == 0,
            "Sources": {"1": 0},
            "InitialLayers": (
                {"1": {str(b): {} for b in range(head_id + 1)}}
                if i < 2 else {}),
        })
    conf = {
        "Model": mcfg.name, "ModelSeed": 0,
        "Nodes": nodes,
        "Assignment": {"2": {str(b): {} for b in range(head_id + 1)}},
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [1]},
    }
    layer_bytes = quant.blob_nbytes_codec(mcfg, 0, "raw")
    total = sum(quant.blob_nbytes_codec(mcfg, b, "raw")
                for b in range(head_id + 1))
    return conf, layer_bytes, total


def physical_fabric_config() -> tuple:
    """PHYSICAL-size pod-fabric scenario: leader + 2 seeders hold the
    ``llama3-8b-d4v8k`` blobs, one cold dest (stage 3 of a [4, 2] mesh)
    is assigned everything — the device plane carries the 416 MiB
    layers, TCP only control.  Returns (conf dict, total bytes)."""
    from ..models import quant, serde
    from ..models.llama import CONFIGS

    mcfg = CONFIGS["llama3-8b-d4v8k"]
    head_id = serde.head_blob_id(mcfg)
    blobs = {str(b): {} for b in range(head_id + 1)}
    nodes = []
    for i in range(4):
        nodes.append({
            "Id": i, "Addr": str(i), "NetworkBW": 10**10,
            "IsLeader": i == 0, "Sources": {"1": 0},
            "InitialLayers": ({"1": dict(blobs)} if i < 3 else {}),
        })
    conf = {
        "Model": mcfg.name, "ModelSeed": 0,
        "Nodes": nodes,
        "Assignment": {"3": dict(blobs)},
        "Mesh": {"AxisNames": ["nodes", "tp"], "AxisSizes": [4, 2],
                 "PipelineAxis": "nodes", "Fabric": True,
                 "IciBW": 90_000_000_000},
    }
    total = sum(quant.blob_nbytes_codec(mcfg, b, "raw")
                for b in range(head_id + 1))
    return conf, total


def run_physical_fabric(timeout: float = 2400.0) -> dict:
    """The physical row's DEVICE-PLANE sibling (VERDICT r4 ask#5): the
    same ~1.8 GiB model, but the layer bytes ride the pod fabric
    (single-controller FabricPlane over the virtual 8-device CPU mesh —
    the one real chip can't host a [4, 2] mesh, so the collective path
    runs on the CPU mesh and the real-chip evidence stays with the
    ``-hbm`` TCP row).  Records TTD + achieved GB/s + the zero-TCP
    assertion next to the host-path row."""
    conf, total = physical_fabric_config()
    env = _cpu_env()
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "physical_fabric.json")
        with open(path, "w") as f:
            json.dump(conf, f)
        proc = subprocess.run(
            [sys.executable, "-m",
             "distributed_llm_dissemination_tpu.cli.podrun",
             "-f", path, "-m", "3"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            timeout=timeout, env=env,
        )
    out = proc.stdout.decode()
    err = proc.stderr.decode()
    ttd_m = _TTD_RE.search(out)
    if proc.returncode != 0 or not ttd_m:
        raise RuntimeError(
            f"physical fabric run failed rc={proc.returncode}: "
            f"{err[-2000:]!r}")
    ttd = float(ttd_m.group(1))
    # podrun's machine-readable summary line carries the run's compiled-
    # collective cache stats and per-phase totals (compile / upload /
    # collective / splice) — the attribution the 47 s row lacked.
    summary = _parse_summary_line(out)
    rec = {
        "scenario": "physical_4node_fabric_llama8b-d4@416MiB-layers",
        "mode": 3,
        "backend": "cpu-mesh8",  # virtual 8-device CPU mesh (see doc)
        "total_bytes": total,
        "ttd_s": round(ttd, 4),
        "achieved_gbps": round(total / ttd / 1e9, 3),
        # Zero layer bytes on TCP: every delivery rode the fabric.  The
        # count matches the receiver's EXACT per-fragment log message —
        # a wording drift breaks the harness loudly (a KeyError in the
        # markdown) instead of silently reporting "none" forever.
        "fabric_deliveries": err.count("layer landed over device fabric"),
        "tcp_layer_fragments": err.count("(a fraction of) layer received"),
    }
    if summary is not None:
        if summary.get("collective_cache"):
            rec["collective_cache"] = summary["collective_cache"]
        if summary.get("plan_phases"):
            rec["plan_phases"] = summary["plan_phases"]
    ttft_m = _TTFT_RE.search(out)
    if ttft_m:
        rec["ttft_s"] = round(float(ttft_m.group(1)), 4)
    cache = rec.get("collective_cache") or {}
    print(f"physical fabric: TTD {ttd:.2f}s "
          f"({rec['achieved_gbps']} GB/s over the device plane; "
          f"gather cache {cache.get('hits', '?')} hits / "
          f"{cache.get('misses', '?')} misses)",
          file=sys.stderr, flush=True)
    return rec


def _physical_phases(dest_log: str) -> dict:
    """Decompose the dest's TTD from its JSON log: where the seconds
    went, per phase (VERDICT r4 asked exactly this of the 19.6 s run).

    - ``wire_recv_ms``: summed per-fragment socket receive durations
      (the transport's own measurement, node.go:1180-1186 parity);
      striped fragments log one entry per stripe, so concurrent stripes
      each contribute their own wall time (thread-time sum).
    - ``assembly_copy_ms`` / ``ingest_write_ms``: summed host memcpy
      and device-ingest write time (receiver phase accumulators).
    - ``recv_span_ms``: max per-layer wall span first-fragment→complete.
    - ``stage_ms``: summed HBM staging (ingest finalize / bulk put).
    - ``boot_ms``: the model boot (startup hook → engine ready).
    - ``fragments`` / ``placed_fragments``: delivered fragments (stripes
      included) and how many of them the zero-copy sink landed directly
      in the reassembly buffer — the receive-to-stage overlap evidence:
      a placed fragment's bytes are already where staging adopts them,
      so its device-ingest accounting runs DURING the wire receive.
    """
    wire = copy = ingest = stage = boot = 0.0
    span = stream_wait = precompile = stream = stream_wire = 0.0
    layers = frags = placed = streamed = streamed_wire = 0
    crc_ms = digest_ms = 0.0
    crc_dropped = nacks = 0
    nacked_bytes = 0
    boot_via = ""
    precompile_in_wire = None
    staged_on = set()
    with open(dest_log) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            m = rec.get("message", "")
            if m == "corrupt layer fragment dropped":
                # TTL prunes share the message with reason="stale"; the
                # table's column is CRC-detected corruption only, to
                # match the integrity.crc_drop counter.
                if rec.get("reason") != "stale":
                    crc_dropped += 1
            elif m == "layer fragment NACKed":
                nacks += 1
                nacked_bytes += int(rec.get("bytes", 0))
            elif m == "layer digest verified":
                digest_ms += float(rec.get("digest_ms", 0.0))
            if m == "(a fraction of) layer received":
                wire += float(rec.get("duration_ms", 0.0))
                crc_ms += float(rec.get("crc_ms", 0.0))
            elif m == "layer fully received":
                copy += float(rec.get("copy_ms", 0.0))
                ingest += float(rec.get("ingest_ms", 0.0))
                span = max(span, float(rec.get("recv_span_ms", 0.0)))
                frags += int(rec.get("fragments", 0))
                placed += int(rec.get("placed_fragments", 0))
                layers += 1
            elif m == "layer staged to HBM":
                stage += float(rec.get("stage_ms", 0.0))
                staged_on.update(d.split(":")[0]
                                 for d in rec.get("devices", ()))
            elif m == "model booted from disseminated layers":
                boot += float(rec.get("ttft_ms", 0.0))
                stream_wait += float(rec.get("stream_wait_ms", 0.0))
                boot_via = rec.get("via", boot_via)
            elif m == "boot programs precompiled during dissemination":
                precompile += float(rec.get("compile_s", 0.0)) * 1000
                precompile_in_wire = bool(rec.get("in_wire", False))
            elif m == "layer boot-staged (streamed)":
                streamed += 1
                stream += float(rec.get("stage_ms", 0.0))
                if rec.get("in_wire"):
                    streamed_wire += 1
                    stream_wire += float(rec.get("stage_ms", 0.0))
    return {
        "layers": layers,
        "fragments": frags,
        "placed_fragments": placed,
        "wire_recv_ms": round(wire, 1),
        "assembly_copy_ms": round(copy, 1),
        "ingest_write_ms": round(ingest, 1),
        "max_layer_recv_span_ms": round(span, 1),
        "stage_ms": round(stage, 1),
        "boot_ms": round(boot, 1),
        "boot_via": boot_via,
        # The platform(s) the dest's layers were staged onto, as the
        # dest itself logged them — the record's ``backend``.
        "staged_on": sorted(staged_on),
        # TTFT pipeline evidence: hint-time compile (and whether it
        # finished inside the wire window), per-blob streamed staging
        # (and how much of it overlapped the wire), and the boot's wait
        # for any staging tail.
        "precompile_ms": round(precompile, 1),
        "precompile_in_wire": precompile_in_wire,
        "stream_stage_ms": round(stream, 1),
        "stream_stage_in_wire_ms": round(stream_wire, 1),
        "streamed_blobs": streamed,
        "streamed_blobs_in_wire": streamed_wire,
        "boot_stream_wait_ms": round(stream_wait, 1),
        # Integrity plane (docs/integrity.md): per-fragment CRC verify
        # (thread-time sum over all receive threads) and once-per-layer
        # digest verify on the dest, plus corruption-recovery counters.
        "crc_verify_ms": round(crc_ms, 1),
        "digest_verify_ms": round(digest_ms, 1),
        "crc_dropped_frames": crc_dropped,
        "nacks_sent": nacks,
        "nacked_bytes": nacked_bytes,
    }


def _retransmits_from_logs(logdir: str) -> dict:
    """Sum the SENDER-side NACK retransmit records across every node's
    log (the dest NACKs; seeders/leader re-send)."""
    frags = 0
    total = 0
    for name in sorted(os.listdir(logdir)):
        if not name.endswith(".jsonl"):
            continue
        with open(os.path.join(logdir, name)) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("message") == "NACK retransmit":
                    frags += 1
                    total += int(rec.get("bytes", 0))
    return {"retransmitted_fragments": frags, "retransmitted_bytes": total}


def run_physical(timeout: float = 1200.0, trace_out: str = "",
                 cache_dir: str = "", label: str = "",
                 faults: str = "", integrity_off: bool = False) -> dict:
    """One recorded run at PHYSICAL layer size (no -scale): ties the TTD
    story to the bench's measured ingest bandwidth — TTD, TTFT, and the
    achieved dest ingest rate.  ONE process holds the device: the dest
    (ambient environment, ``-hbm``); leader and seeder are CPU-pinned
    byte servers.  The record's ``backend`` is what the dest logged its
    layers staged onto.
    ``trace_out``: also merge the per-node JSON logs and write a
    Chrome-trace of the run there (the observability pipeline exercised
    on the recorded scenario itself).
    ``cache_dir``: persistent compilation cache directory handed to the
    node processes (JAX_COMPILATION_CACHE_DIR) — the cold run writes it,
    the warm run's boot reads it; ``label`` tags the record
    ("cold"/"warm").
    Seeders run ``-boot none``: only the DEST's boot is the metric, and
    a seeder pointlessly booting its own full copy would contend for the
    same cores during the measured window.
    ``faults``: a ``transport/faults.py`` spec handed to every node
    (``-test-faults``) — the FAULTED sibling row: seeded corruption/
    drops below the CRC check plus duplicated sends, which the
    integrity plane must recover byte-exactly (digests verified at the
    dest); the record carries the NACK/retransmit counts and the TTD
    degradation vs the clean row."""
    env = dict(os.environ)
    if cache_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    if integrity_off:
        # The integrity-OFF sibling: same scenario with CRC stamping/
        # verification and layer digests disabled — the wall-clock delta
        # to the clean (integrity-on) row is the checksum overhead the
        # ≤5%-of-TTD acceptance criterion measures.
        env["DLD_WIRE_CRC"] = "0"
        env["DLD_LAYER_DIGESTS"] = "0"
    # The host's measured loopback ceiling: one raw stream, and the
    # striped data plane's stream count — the denominator that makes the
    # achieved rate attributable (bench.py's raw_dma_gbps/link_fraction
    # pattern, applied to the wire).  Probed BEFORE the node processes
    # spawn: the run saturates small hosts end to end (and the dest's
    # boot outlives the TTD), so a probe next to live processes would
    # understate the ceiling and flatter the fraction.
    from ..transport.tcp import STRIPE_COUNT

    loop_raw = measure_loopback_gbps(1)
    loop_striped = measure_loopback_gbps(max(2, STRIPE_COUNT))
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "physical_3node.json")
        conf, layer_bytes, total = physical_config()
        with open(path, "w") as f:
            json.dump(conf, f)
        receiver_ids = [n["Id"] for n in conf["Nodes"]
                        if not n.get("IsLeader")]
        leader_addr = next(n["Addr"] for n in conf["Nodes"]
                           if n.get("IsLeader"))
        logdir = os.path.join(td, "logs")
        os.makedirs(logdir)

        errfs = []
        dest_ids = {int(k) for k in conf.get("Assignment", {})}

        def spawn(node_id, extra=()):
            # Per-node JSON logs (zerolog-style, on stderr) captured to
            # files: the same artifacts a deployment's collect_logs
            # gathers, here feeding the committed trace.
            errf = open(os.path.join(logdir, f"node{node_id}.jsonl"), "wb")
            errfs.append(errf)
            fault_flags = ("-test-faults", faults) if faults else ()
            # One chip-holding child: only the dest stages (-hbm) and
            # boots; everyone else serves bytes from a CPU-pinned
            # process.
            is_dest = node_id in dest_ids
            return subprocess.Popen(
                [sys.executable, "-m",
                 "distributed_llm_dissemination_tpu.cli.main",
                 "-id", str(node_id), "-f", path, "-m", "3",
                 *(("-hbm",) if is_dest else ()),
                 *fault_flags, *extra],
                stdout=subprocess.PIPE, stderr=errf,
                env=env if is_dest else _cpu_env(env),
            )

        def wait_listening(proc, addr: str, budget: float) -> None:
            # The leader fabricates ~2 GiB of seeded blobs BEFORE it
            # listens; receivers only retry dialing for ~10 s, so spawn
            # them once the port actually answers.  A leader that DIED
            # during fabrication must fail the run now, not after the
            # whole budget.
            import socket

            host, port = addr.rsplit(":", 1)
            deadline = time.monotonic() + budget
            while time.monotonic() < deadline:
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"leader exited rc={proc.returncode} before "
                        "listening (fabrication failure?)")
                try:
                    with socket.create_connection((host, int(port)),
                                                  timeout=2.0):
                        return
                except OSError:
                    time.sleep(1.0)
            raise RuntimeError(f"leader never listened on {addr}")

        procs = []
        try:
            leader = spawn(0)
            procs.append(leader)
            wait_listening(leader, leader_addr, budget=600.0)
            for rid in receiver_ids:
                # Seeders opt out of booting (they report "skipped");
                # only the dest's boot is measured.
                procs.append(spawn(
                    rid, () if rid in dest_ids else ("-boot", "none")))
            out, _ = leader.communicate(timeout=timeout)
            text = out.decode()
            ttd_m = _TTD_RE.search(text)
            ttft_m = _TTFT_RE.search(text)
            pred_m = _PRED_RE.search(text)
            if not ttd_m:
                raise RuntimeError(
                    f"no TTD in physical run output: {text[-2000:]!r}")
            ttd = float(ttd_m.group(1))
            ceiling = max(loop_raw, loop_striped)
            rec = {
                "scenario": "physical_3node_llama8b-d4@416MiB-layers",
                "mode": 3, "hbm": True,
                "backend": "unknown",  # set from the dest's log below
                "layer_bytes": layer_bytes,
                "total_bytes": total,
                "ttd_s": round(ttd, 4),
                "achieved_gbps": round(total / ttd / 1e9, 3),
                "stripes": STRIPE_COUNT,
            }
            if label:
                rec["cache"] = label
            if faults:
                rec["fault_spec"] = faults
            if pred_m:
                rec["predicted_s"] = round(float(pred_m.group(1)), 4)
                rec["solve_ms"] = round(float(pred_m.group(2)), 3)
            # 0.0 = that probe arm failed (accept timeout): record only
            # the arms that really measured, never a bogus zero ceiling.
            if loop_raw > 0:
                rec["loopback_raw_gbps"] = loop_raw
            if loop_striped > 0:
                rec["loopback_striped_gbps"] = loop_striped
            if ceiling > 0:
                rec["link_fraction"] = round(
                    total / ttd / 1e9 / ceiling, 3)
            if ttft_m:
                rec["ttft_s"] = round(float(ttft_m.group(1)), 4)
            try:
                # The run's own RUN_REPORT (cli/report.py), built from
                # the same per-node logs: the row embeds its provenance
                # hash + folded event counters, so the integrity/
                # failover numbers in this record are traceable to one
                # report artifact instead of hand-collected.
                from . import collect_logs as _cl
                from . import report as report_mod

                rep = report_mod.build_from_records(
                    _cl.iter_records([logdir]))
                rec["run_report"] = {
                    "provenance": rep.get("provenance"),
                    "counters": rep.get("counters"),
                }
            except Exception as e:  # noqa: BLE001 — report is a bonus
                print(f"run report build failed: {e!r}", file=sys.stderr)
            try:
                rec["phases"] = _physical_phases(
                    os.path.join(logdir, "node2.jsonl"))
                ph = rec["phases"]
                rec["backend"] = "+".join(ph["staged_on"]) or "host"
                integ = _retransmits_from_logs(logdir)
                # The acceptance metric: dest-side checksum thread-time
                # (per-fragment CRC + once-per-layer digest) over the
                # TTD wall clock.  Thread-time over wall-time, so
                # overlapped verification (concurrent stripe receivers)
                # can honestly exceed its wall-clock share.
                integ["crc_overhead_frac"] = round(
                    (ph["crc_verify_ms"] + ph["digest_verify_ms"])
                    / max(ttd * 1000.0, 1e-9), 4)
                integ["verify_ms"] = round(
                    ph["crc_verify_ms"] + ph["digest_verify_ms"], 1)
                integ["crc_dropped_frames"] = ph["crc_dropped_frames"]
                integ["nacks_sent"] = ph["nacks_sent"]
                rec["integrity"] = integ
            except Exception as e:  # noqa: BLE001 — breakdown is a bonus
                print(f"phase breakdown failed: {e!r}", file=sys.stderr)
            if trace_out:
                # Receivers exit shortly after their boot reports; wait
                # so the trace gets their final events too.
                for p in procs[1:]:
                    try:
                        p.wait(timeout=60)
                    except subprocess.TimeoutExpired:
                        pass
                try:
                    from . import collect_logs, trace as trace_mod

                    # Same pipeline as `cli.trace logs/` (to_trace_events
                    # sorts internally; merge() would leak rel_ms into
                    # every event's args and diverge from that path).
                    events = trace_mod.to_trace_events(
                        collect_logs.iter_records([logdir]))
                    with open(trace_out, "w") as f:
                        json.dump({"traceEvents": events,
                                   "displayTimeUnit": "ms"}, f)
                    rec["trace_events"] = len(events)
                except Exception as e:  # noqa: BLE001 — trace is a bonus
                    print(f"trace export failed: {e!r}", file=sys.stderr)
            print(f"physical: TTD {ttd:.2f}s "
                  f"({rec['achieved_gbps']} GB/s into the dest, "
                  f"backend {rec['backend']})", file=sys.stderr, flush=True)
            return rec
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            for f in errfs:
                f.close()


def _cache_evidence(results: dict) -> dict:
    """Build the 'compiled-collective cache: reuse evidence' table from
    the run's own records (the pod scenarios' per-mode summaries and
    the physical fabric row), so a full re-measure regenerates it
    instead of silently dropping a hand-curated key."""
    ev = {}
    for name, per_mode in (results.get("scenarios") or {}).items():
        if "fabric" not in name:
            continue
        for mode in ("0", "1", "2", "3"):
            cc = (per_mode.get(mode) or {}).get("collective_cache")
            if cc:
                note = (" (batched)" if mode == "3" else "")
                ev[f"{name} mode {mode}{note}"] = {
                    k: cc[k] for k in ("hits", "misses", "compile_ms")
                    if k in cc}
    fab = results.get("physical_fabric") or {}
    if fab.get("collective_cache"):
        cc = fab["collective_cache"]
        ev[f"{fab.get('scenario', 'physical_fabric')} (batched)"] = {
            k: cc[k] for k in ("hits", "misses", "compile_ms") if k in cc}
    return ev


def run_failover(layer_bytes: int = 96 << 20, n_workers: int = 2,
                 lease: float = 0.25, expiry: float = 0.6,
                 kill_frac: float = 0.5, timeout: float = 180.0) -> dict:
    """Control-plane HA at physical-row sizes (docs/failover.md): one
    clean HA-armed mode-3 run over loopback TCP, then an identical run
    with the leader KILLED at ``kill_frac`` of the clean TTD.  Records
    time-to-recover (TTR: kill → delivery resumed to completion) and
    the failover overhead vs the clean sibling.  In-process (threads,
    real TCP transports): the leader kill is a surgical freeze of the
    leader's loops — exactly the mid-run death the standby must absorb
    — with the wall clock honest end to end."""
    import threading

    from ..core.types import (
        LayerMeta,
        LayerLocation,
        LayerSrc,
        SourceType,
    )
    from ..runtime import (
        FlowRetransmitLeaderNode,
        FlowRetransmitReceiverNode,
        Node,
        StandbyController,
    )
    from ..transport import TcpTransport

    ids = list(range(n_workers + 2))  # 0 leader, 1 standby, 2.. workers
    block = os.urandom(1 << 20)

    def mem_layer(lid: int) -> LayerSrc:
        reps = (layer_bytes + len(block) - 1) // len(block)
        data = bytearray((block * reps)[:layer_bytes])
        data[:8] = lid.to_bytes(8, "big")  # distinct per layer
        return LayerSrc(inmem_data=data, data_size=layer_bytes,
                        meta=LayerMeta(location=LayerLocation.INMEM,
                                       source_type=SourceType.MEM))

    def build():
        ts = {i: TcpTransport("127.0.0.1:0") for i in ids}
        reg = {i: t.get_address() for i, t in ts.items()}
        for t in ts.values():
            t.addr_registry.update(reg)
        assignment = {w: {w - 2: LayerMeta()}
                      for w in range(2, n_workers + 2)}
        seed = lambda: {i: mem_layer(i)  # noqa: E731
                        for i in range(n_workers)}
        leader = FlowRetransmitLeaderNode(
            Node(0, 0, ts[0]), seed(), assignment,
            {i: 10 ** 10 for i in ids},
            expected_nodes=set(ids[1:]), standbys=[1],
            lease_interval=lease, epoch=0)
        standby = FlowRetransmitReceiverNode(
            Node(1, 0, ts[1]), seed(), heartbeat_interval=lease)
        ctl = StandbyController(
            standby, rank=0, lease_timeout=expiry, standbys=[1], mode=3,
            node_network_bw={i: 10 ** 10 for i in ids},
            failure_timeout=0.0, lease_interval=lease)
        workers = [FlowRetransmitReceiverNode(
            Node(w, 0, ts[w]), {}, heartbeat_interval=lease)
            for w in range(2, n_workers + 2)]
        return leader, standby, ctl, workers, ts, assignment

    def teardown(leader, standby, ctl, workers, ts):
        ctl.close()
        leader.close()
        for r in [standby] + workers:
            r.close()
        for t in ts.values():
            t.close()

    def one_run(kill_at_s=None):
        # Run-scoped telemetry: both runs share this process, so each
        # starts from a clean registry (the trace.py global-bleed fix) —
        # the embedded counters below are THIS run's events only.
        from ..utils import telemetry

        telemetry.reset_run()
        leader, standby, ctl, workers, ts, assignment = build()
        try:
            standby.announce()
            for w in workers:
                w.announce()
            leader.start_distribution().get(timeout=timeout)
            t0 = time.monotonic()
            rec = {}
            if kill_at_s is not None:
                time.sleep(kill_at_s)
                t_kill = time.monotonic()
                leader.close()  # the mid-run death
                if not ctl.promoted.wait(timeout=timeout):
                    raise TimeoutError("standby never promoted")
                rec["takeover_s"] = round(
                    time.monotonic() - t_kill, 4)
                ready_q = ctl.leader.ready()
            else:
                ready_q = leader.ready()
            import queue as _q

            try:
                ready_q.get(timeout=timeout)
            except _q.Empty:
                raise TimeoutError("delivery never completed")
            now = time.monotonic()
            rec["total_s"] = round(now - t0, 4)
            if kill_at_s is not None:
                rec["kill_at_s"] = round(t_kill - t0, 4)
                rec["ttr_s"] = round(now - t_kill, 4)
            # Byte-exactness: every worker's layer matches its seed.
            for w in workers:
                for lid in assignment[w.node.my_id]:
                    got = bytes(w.layers[lid].inmem_data)
                    want = bytes(mem_layer(lid).inmem_data)
                    if got != want:
                        raise AssertionError(
                            f"layer {lid} corrupt after failover")
            rec["byte_exact"] = True
            # The row's event counts come from the run's own flight
            # recorder + RUN_REPORT (cli/report.py) — the report is
            # built from whichever leader FINISHED the run (the adopted
            # one on the killed run: the replicated cluster picture is
            # part of what this row evidences).
            from . import report as report_mod

            live = ctl.leader if kill_at_s is not None else leader
            rep = report_mod.build_from_leader(live,
                                               ttd_s=rec["total_s"])
            rec["telemetry"] = telemetry.snapshot().get("counters")
            rec["run_report"] = rep.get("provenance")
            rec["report_links"] = len(rep.get("links") or [])
            return rec
        finally:
            teardown(leader, standby, ctl, workers, ts)

    clean = one_run()
    kill_at = max(0.05, clean["total_s"] * kill_frac)
    killed = one_run(kill_at_s=kill_at)
    from ..utils.provenance import harness_hash

    return {
        "harness_hash": harness_hash(),
        "mode": 3,
        "backend": "tcp-loopback",
        "layer_bytes": layer_bytes,
        "n_workers": n_workers,
        "lease_interval_s": lease,
        "standby_expiry_s": expiry,
        "clean": clean,
        "killed": killed,
        "overhead_s": round(killed["total_s"] - clean["total_s"], 4),
    }


def _dest_wire_bytes(links: dict, node_id) -> dict:
    """Per-dest NIC accounting off the folded link table: rx and
    delivered bytes summed over the base (un-job-tagged) rows ending at
    ``node_id`` — one definition for every row that reconciles wire
    bytes per dest."""
    rx = sum(row.get("rx_bytes", 0) for key, row in links.items()
             if "#" not in key and key.endswith(f"->{node_id}"))
    delivered = sum(row.get("delivered_bytes", 0)
                    for key, row in links.items()
                    if "#" not in key and key.endswith(f"->{node_id}"))
    return {"rx_bytes": rx, "delivered_bytes": delivered}


def _service_rig(n_layers: int, layer_bytes: int, assignment,
                 bw_per_node: int, n_dests: int = 2, fabric=None,
                 pods=None, codec: bool = False):
    """Leader 0 (mode 3, holds every layer) + dests 1..n over loopback
    TCP — the in-process rig the service-plane rows run on.

    ``fabric``/``pods`` (docs/fabric.md): a shared in-process
    ``FabricPlane`` (its pod shard board is the single-controller
    stand-in for the ICI hop) + the pod grouping, for the
    fabric-assisted pod-delivery row.

    ``codec``: wire every node with a model-less ``WireCodecPlane``
    (docs/codec.md).  With no model config only the content-DELTA form
    can encode (whole-form sizes derive from blob layouts), and the
    leader's ``wire_codec`` stays "raw" — so the rows that set this
    exercise exactly the delta path: dests announce the "delta"
    capability, the leader prices encoded (v2 − base) streams, and
    reconstruction verifies against the stamped full-form digest."""
    from ..core.types import (
        LayerMeta,
        LayerLocation,
        LayerSrc,
        SourceType,
    )
    from ..runtime import (
        FlowRetransmitLeaderNode,
        FlowRetransmitReceiverNode,
        Node,
    )
    from ..runtime.codec import WireCodecPlane
    from ..transport import TcpTransport

    ids = list(range(n_dests + 1))
    block = os.urandom(1 << 20)

    def mem_layer(lid: int) -> LayerSrc:
        reps = (layer_bytes + len(block) - 1) // len(block)
        data = bytearray((block * reps)[:layer_bytes])
        data[:8] = lid.to_bytes(8, "big")
        return LayerSrc(inmem_data=data, data_size=layer_bytes,
                        meta=LayerMeta(location=LayerLocation.INMEM,
                                       source_type=SourceType.MEM))

    ts = {i: TcpTransport("127.0.0.1:0") for i in ids}
    reg = {i: t.get_address() for i, t in ts.items()}
    for t in ts.values():
        t.addr_registry.update(reg)
    # One plane PER NODE (never shared): each role wires its own
    # base_resolver (leader: goal digests; receiver: content store).
    plane = (lambda: WireCodecPlane(None)) if codec else (lambda: None)
    leader = FlowRetransmitLeaderNode(
        Node(0, 0, ts[0]), {i: mem_layer(i) for i in range(n_layers)},
        assignment, {i: bw_per_node for i in ids},
        expected_nodes=set(ids[1:]), fabric=fabric, pods=pods,
        codecs=plane())
    dests = [FlowRetransmitReceiverNode(Node(i, 0, ts[i]), {},
                                        fabric=fabric, codecs=plane())
             for i in ids[1:]]
    return leader, dests, ts, mem_layer


def _service_teardown(leader, dests, ts):
    leader.close()
    for r in dests:
        r.close()
    for t in ts.values():
        t.close()


def run_service_jobs(layer_bytes: int = 32 << 20,
                     bw: int = 200_000_000,
                     timeout: float = 300.0) -> dict:
    """Two overlapping dissemination jobs, different priorities, one
    shared source NIC (docs/service.md): the leader daemon admits both
    at once; the joint solver gives the HIGH tier the full modeled link
    and the LOW tier the preemption-floor residue, and the per-job link
    telemetry + per-job completion walls record the split actually
    achieved.  Byte-exact with digests verified (the jobs only complete
    through the ack gate)."""
    import queue as _q

    from ..core.types import LayerMeta
    from ..utils import telemetry
    from ..utils.provenance import harness_hash
    from . import report as report_mod

    telemetry.reset_run()
    assignment = {}  # service-only: the daemon starts with an empty goal
    leader, dests, ts, mem_layer = _service_rig(
        2, layer_bytes, assignment, bw, n_dests=2)
    try:
        for r in dests:
            r.announce()
        leader.start_distribution().get(timeout=timeout)
        leader.ready().get(timeout=timeout)  # empty base goal: instant
        t0 = time.monotonic()
        s_hi = leader.submit_job("push-hi", {1: {0: LayerMeta()}},
                                 priority=2)
        s_lo = leader.submit_job("push-lo", {2: {1: LayerMeta()}},
                                 priority=1)
        done_at = {}
        deadline = time.monotonic() + timeout
        while len(done_at) < 2:
            if time.monotonic() > deadline:
                raise TimeoutError("service jobs never completed")
            for jid, row in leader.jobs.table().items():
                if row["State"] == "done" and jid not in done_at:
                    done_at[jid] = round(time.monotonic() - t0, 4)
            time.sleep(0.02)
        try:
            leader.ready().get(timeout=timeout)
        except _q.Empty:
            pass
        # Byte-exact + digest-verified.
        for r, lid in ((dests[0], 0), (dests[1], 1)):
            want = bytes(mem_layer(lid).inmem_data)
            if bytes(r.layers[lid].inmem_data) != want:
                raise AssertionError(f"job layer {lid} corrupt")
            expected = r._expected_digest(lid)
            if expected is not None and lid not in r._digest_ok:
                raise AssertionError(f"layer {lid} digest unverified")
        intended = {jid: leader._tier_time.get(jid)
                    for jid in ("push-hi", "push-lo")}
        links = telemetry.snapshot()["links"]
        per_job_links = {
            key: {f: row[f] for f in ("delivered_bytes", "rx_bytes",
                                      "tx_bytes") if f in row}
            for key, row in links.items() if "#" in key}
        rep = report_mod.build_from_leader(leader)
        return {
            "harness_hash": harness_hash(),
            "backend": "tcp-loopback",
            "mode": 3,
            "layer_bytes": layer_bytes,
            "modeled_bw_bps": bw,
            "jobs": {
                "push-hi": {"priority": 2, "summary": s_hi},
                "push-lo": {"priority": 1, "summary": s_lo},
            },
            # The solver's INTENDED split: each tier's min-time budget
            # (ms) — hi gets the full modeled link, lo the 1/16
            # preemption-floor residue (sched.flow.PREEMPT_FLOOR_SHIFT).
            "intended_tier_ms": intended,
            "measured_done_s": done_at,
            "per_job_links": per_job_links,
            "byte_exact": True,
            "table": leader.jobs.table(),
            "run_report": rep.get("provenance"),
        }
    finally:
        _service_teardown(leader, dests, ts)


def _perturbed(src, stride: int = 1024, salt: int = 0) -> bytearray:
    """A small-perturbation v2 of ``src``'s bytes: every ``stride``-th
    byte flipped (deterministic) — the rollout shape the content-delta
    codec exists for: ~0.1% of positions changed, scattered through the
    whole layer, so whole-layer content dedup can't help but an encoded
    XOR delta is tiny.  ``salt`` offsets the perturbed positions so two
    perturbed layers never mutate the SAME positions — otherwise each
    would be the other's closest base (the XOR cancels) and the leader
    would pin a base the dests don't hold yet."""
    data = bytearray(src.inmem_data)
    for off in range(salt % stride, len(data), stride):
        data[off] ^= 0xA5
    return data


def run_delta_rollout(layer_bytes: int = 16 << 20, n_layers: int = 4,
                      changed: int = 1, perturb_stride: int = 1024,
                      bw: int = 200_000_000,
                      timeout: float = 300.0) -> dict:
    """v2 delta rollout against a populated content store + the
    content-delta wire codec (docs/service.md, docs/codec.md): after a
    v1 run delivers ``n_layers`` to the dest, a v2 job re-keys them
    under new layer ids — ``changed`` of them small-perturbation
    siblings of their v1 bytes, the rest byte-identical.  The
    content-addressed store must resolve the UNCHANGED layers locally
    (zero wire bytes), and the leader must ship each CHANGED layer as
    an encoded ``delta:<v1-digest>`` stream the dest reconstructs and
    verifies against the stamped full-form digest — so the shipped
    bytes land far below even the changed layers' raw size.  The row
    records both wins plus the honest encode cost (the leader's
    XOR+DLE1 wall time, ``codec_encode``)."""
    from ..core.types import LayerMeta
    from ..utils import integrity, telemetry, trace
    from ..utils.provenance import harness_hash
    from . import report as report_mod

    telemetry.reset_run()
    trace.reset_phases()
    assignment = {1: {i: LayerMeta() for i in range(n_layers)}}
    # v2 ids are 100+i; ids < 100+changed are perturbed v1 bytes, the
    # rest reuse v1 bytes verbatim (unchanged).  ``bw`` models the NIC
    # at or below the delta negotiation threshold
    # (runtime/codec.DELTA_MIN_RATE_DEFAULT) so the pairs qualify.
    leader, dests, ts, mem_layer = _service_rig(
        n_layers, layer_bytes, assignment, bw, n_dests=1, codec=True)
    try:
        dests[0].announce()
        t0 = time.monotonic()
        leader.ready().get(timeout=timeout)
        v1_s = round(time.monotonic() - t0, 4)
        base_rx = telemetry.snapshot()["links"].get(
            "0->1", {}).get("rx_bytes", 0)
        from ..core.types import LayerLocation, LayerSrc, SourceType

        with leader._lock:
            for i in range(changed):
                data = _perturbed(leader.layers[i], perturb_stride,
                                   salt=1 + 7 * i)
                leader.layers[100 + i] = LayerSrc(
                    inmem_data=data, data_size=len(data),
                    meta=LayerMeta(location=LayerLocation.INMEM,
                                   source_type=SourceType.MEM))
            for i in range(changed, n_layers):
                leader.layers[100 + i] = leader.layers[i]
        digests = {}
        for i in range(n_layers):
            src = leader.layers[100 + i]
            digests[100 + i] = integrity.layer_digest(
                bytes(src.inmem_data))
        t1 = time.monotonic()
        leader.submit_job(
            "v2-rollout", {1: {100 + i: LayerMeta()
                               for i in range(n_layers)}},
            priority=1, kind="push", digests=digests)
        leader.ready().get(timeout=timeout)
        v2_s = round(time.monotonic() - t1, 4)
        for i in range(n_layers):
            src = dests[0].layers.get(100 + i)
            want = leader.layers[100 + i]
            if src is None or bytes(src.inmem_data) != bytes(
                    want.inmem_data):
                raise AssertionError(f"v2 layer {100 + i} corrupt")
            # Digest-exact: the dest VERIFIED each v2 pair (changed
            # pairs verify twice — the delta stream, then the
            # reconstructed full form).
            if 100 + i not in dests[0]._digest_ok:
                raise AssertionError(
                    f"v2 layer {100 + i} digest unverified")
        links = telemetry.snapshot()["links"]
        v2_rx = sum(row.get("rx_bytes", 0) for key, row in links.items()
                    if key.endswith("#v2-rollout"))
        counters = trace.counter_totals()
        phases = trace.phase_totals()
        rep = report_mod.build_from_leader(leader)
        model_bytes = n_layers * layer_bytes
        changed_raw = changed * layer_bytes
        return {
            "harness_hash": harness_hash(),
            "backend": "tcp-loopback",
            "mode": 3,
            "layer_bytes": layer_bytes,
            "n_layers": n_layers,
            "changed_layers": changed,
            "perturb_stride": perturb_stride,
            "modeled_bw_bps": bw,
            "model_bytes": model_bytes,
            "changed_fraction": round(changed / n_layers, 4),
            "v1_full_push_s": v1_s,
            "v1_wire_bytes": base_rx,
            "v2_delta_push_s": v2_s,
            "v2_wire_bytes": v2_rx,
            "v2_bound_bytes": changed_raw,
            "bound_met": bool(0 < v2_rx <= changed_raw),
            # The tentpole bar: the changed layers' wire bytes are an
            # encoded (v2 − v1) stream, not whole raw layers — under
            # 25% of the changed layers' raw size (with the stride-
            # perturbation above, well under 5%).
            "delta_bound_bytes": changed_raw // 4,
            "delta_bound_met": bool(0 < v2_rx <= changed_raw // 4),
            "delta_pairs_chosen": counters.get(
                "codec.delta_pairs_chosen", 0),
            "delta_wire_bytes": counters.get("codec.delta_wire_bytes", 0),
            "delta_raw_bytes": counters.get("codec.delta_raw_bytes", 0),
            "delta_reconstructed": counters.get(
                "codec.delta_reconstructed", 0),
            # Honest encode-cost accounting: thread-time the leader
            # spent XOR+DLE1-encoding (cached once per layer; a CFS
            # container's noisy clock makes this a ceiling, not a
            # precise per-byte rate).
            "encode_ms": phases.get("codec_encode", {}).get("ms", 0.0),
            "resolved_layers": counters.get("store.resolved_layers", 0),
            "resolved_bytes": counters.get("store.resolved_bytes", 0),
            "leader_skipped": counters.get("store.leader_skipped", 0),
            "byte_exact": True,
            "digest_exact": True,
            "run_report": rep.get("provenance"),
        }
    finally:
        _service_teardown(leader, dests, ts)


def run_delta_wave(layer_bytes: int = 8 << 20, n_layers: int = 3,
                   changed: int = 2, perturb_stride: int = 1024,
                   bw: int = 200_000_000,
                   timeout: float = 300.0) -> dict:
    """Rollout WAVE over a grouped cluster, shipped as deltas
    (docs/rollout.md × docs/hierarchy.md × docs/codec.md): root 0 seeds
    ``n_layers`` v1 layers to one group of 3 (sub-leader + 2 members)
    through the group plan, then rolls a v2 that perturbs ``changed``
    layers in two version-qualified waves — wave 1 lands v2 on the
    group-ingress sub-leader, wave 2 fans it to the members.  Every v2
    pair must ship as an encoded ``delta:<v1-digest>`` stream: the
    root encodes against its own v1, and the SUB-LEADER (holding
    reconstructed v2 + verified v1) re-encodes the byte-identical
    stream for its members — striped byte ranges of one delta blob
    through the group chain, the "sharded delta wave" composition.
    Records per-wave wall + wire bytes and the root-vs-group split."""
    from ..core.types import LayerMeta
    from ..runtime import (
        HierarchicalFlowLeaderNode,
        FlowRetransmitReceiverNode,
        Node,
        SubLeaderController,
    )
    from ..runtime.codec import WireCodecPlane
    from ..transport import TcpTransport
    from ..utils import integrity, telemetry, trace
    from ..utils.provenance import harness_hash

    telemetry.reset_run()
    trace.reset_phases()
    ids = [0, 1, 2, 3]
    sub, members = 1, [1, 2, 3]
    block = os.urandom(1 << 20)

    def mem_layer(lid: int):
        from ..core.types import (
            LayerLocation,
            LayerSrc,
            SourceType,
        )

        reps = (layer_bytes + len(block) - 1) // len(block)
        data = bytearray((block * reps)[:layer_bytes])
        data[:8] = lid.to_bytes(8, "big")
        return LayerSrc(inmem_data=data, data_size=layer_bytes,
                        meta=LayerMeta(location=LayerLocation.INMEM,
                                       source_type=SourceType.MEM))

    ts = {i: TcpTransport("127.0.0.1:0") for i in ids}
    reg = {i: t.get_address() for i, t in ts.items()}
    for t in ts.values():
        t.addr_registry.update(reg)
    assignment = {i: {lid: LayerMeta() for lid in range(n_layers)}
                  for i in members}
    leader = HierarchicalFlowLeaderNode(
        Node(0, 0, ts[0]),
        {lid: mem_layer(lid) for lid in range(n_layers)},
        assignment, {i: bw for i in ids},
        groups={0: {"leader": sub, "members": members}},
        expected_nodes={sub}, codecs=WireCodecPlane(None))
    recvs = {i: FlowRetransmitReceiverNode(
        Node(i, 0 if i == sub else sub, ts[i]), {},
        codecs=WireCodecPlane(None)) for i in members}
    ctl = SubLeaderController(recvs[sub], 0, members)
    try:
        for r in recvs.values():
            r.announce()
        t0 = time.monotonic()
        leader.start_distribution().get(timeout=timeout)
        leader.ready().get(timeout=timeout)
        v1_s = round(time.monotonic() - t0, 4)

        def link_rx(frm, to):
            links = telemetry.snapshot()["links"]
            return sum(row.get("rx_bytes", 0)
                       for key, row in links.items()
                       if "#" not in key
                       and key.startswith(f"{frm}->")
                       and key.endswith(f"->{to}"))

        v1_root_tx = sum(link_rx(0, m) for m in members)
        from ..core.types import LayerLocation, LayerSrc, SourceType

        with leader._lock:
            for i in range(changed):
                data = _perturbed(leader.layers[i], perturb_stride,
                                   salt=1 + 7 * i)
                leader.layers[100 + i] = LayerSrc(
                    inmem_data=data, data_size=len(data),
                    meta=LayerMeta(location=LayerLocation.INMEM,
                                   source_type=SourceType.MEM))
        digests = {100 + i: integrity.layer_digest(
            bytes(leader.layers[100 + i].inmem_data))
            for i in range(changed)}
        waves = []
        rx_before = {m: link_rx(0, m) for m in members}
        for w, wave_dests in enumerate(([sub],
                                        [m for m in members
                                         if m != sub])):
            tw = time.monotonic()
            leader.submit_job(
                f"wave-{w + 1}",
                {d: {100 + i: LayerMeta() for i in range(changed)}
                 for d in wave_dests},
                priority=1, kind="push", version="v2", digests=digests)
            leader.ready().get(timeout=timeout)
            rx_now = {m: link_rx(0, m) for m in members}
            waves.append({
                "dests": wave_dests,
                "wall_s": round(time.monotonic() - tw, 4),
                "root_wire_bytes": sum(
                    rx_now[m] - rx_before[m] for m in members),
            })
            rx_before = rx_now
        for m in members:
            r = recvs[m]
            for i in range(changed):
                src = r.layers.get(100 + i)
                want = leader.layers[100 + i]
                if src is None or bytes(src.inmem_data) != bytes(
                        want.inmem_data):
                    raise AssertionError(
                        f"wave layer {100 + i} corrupt at {m}")
                if src.meta.version != "v2":
                    raise AssertionError(
                        f"wave layer {100 + i} at {m} lost its "
                        f"version tag: {src.meta.version!r}")
                if 100 + i not in r._digest_ok:
                    raise AssertionError(
                        f"wave layer {100 + i} at {m} unverified")
        counters = trace.counter_totals()
        changed_raw = changed * layer_bytes
        total_wire = sum(w["root_wire_bytes"] for w in waves)
        group_wire = sum(link_rx(sub, m) for m in members if m != sub)
        return {
            "harness_hash": harness_hash(),
            "backend": "tcp-loopback",
            "mode": 3,
            "layer_bytes": layer_bytes,
            "n_layers": n_layers,
            "changed_layers": changed,
            "perturb_stride": perturb_stride,
            "modeled_bw_bps": bw,
            "group": {"leader": sub, "members": members},
            "version": "v2",
            "v1_group_push_s": v1_s,
            "v1_root_wire_bytes": v1_root_tx,
            "waves": waves,
            "wave_wire_bytes": total_wire,
            "changed_raw_bytes": changed_raw,
            # Every replica materialized v2 but the root's NIC carried
            # only encoded delta streams — and wave 2 rode the group
            # chain (sub-leader re-encode), not the root.
            "delta_bound_met": bool(
                0 < total_wire <= changed_raw // 4),
            "delta_pairs_chosen": counters.get(
                "codec.delta_pairs_chosen", 0),
            "delta_reconstructed": counters.get(
                "codec.delta_reconstructed", 0),
            "delta_wire_bytes": counters.get("codec.delta_wire_bytes", 0),
            "delta_raw_bytes": counters.get("codec.delta_raw_bytes", 0),
            "group_wire_bytes": group_wire,
            "byte_exact": True,
            "digest_exact": True,
        }
    finally:
        ctl.close()
        leader.close()
        for r in recvs.values():
            r.close()
        for t in ts.values():
            t.close()


def run_sharded_delivery(layer_bytes: int = 64 << 20, n_layers: int = 2,
                         n_shards: int = 4, bw: int = 10 ** 9,
                         timeout: float = 600.0) -> dict:
    """Sharded delivery vs full-layer delivery (docs/sharding.md): the
    same multi-dest goal — ``n_shards`` dests, ``n_layers`` ×
    ``layer_bytes`` layers from one leader — run twice, once with every
    dest pulling FULL layers and once with each dest's target the
    ``1/n@k`` shard spec.  Records wire bytes per dest (must be ≈ the
    shard fraction, within 10%), TTD + predicted-vs-achieved for both
    runs, and the post-gather on-mesh layer's byte-exactness against
    the stamped full-layer digest — the acceptance bars of ROADMAP
    item 1."""
    from ..core.types import LayerMeta, shard_range, shard_specs_for
    from ..parallel.collectives import gather_byte_shards
    from ..utils import telemetry
    from ..utils.provenance import harness_hash
    from . import report as report_mod

    specs = shard_specs_for(n_shards)

    def one_run(sharded: bool) -> dict:
        telemetry.reset_run()
        assignment = {
            k + 1: {lid: LayerMeta(shard=specs[k] if sharded else "")
                    for lid in range(n_layers)}
            for k in range(n_shards)
        }
        leader, dests, ts, mem_layer = _service_rig(
            n_layers, layer_bytes, assignment, bw, n_dests=n_shards)
        try:
            t0 = time.monotonic()
            for r in dests:
                r.announce()
            leader.ready().get(timeout=timeout)
            ttd = round(time.monotonic() - t0, 4)
            links = telemetry.snapshot()["links"]
            per_dest = {r.node.my_id: _dest_wire_bytes(links,
                                                       r.node.my_id)
                        for r in dests}
            rec = {
                "ttd_s": ttd,
                "predicted_s": round(leader.predicted_ttd_ms / 1000.0, 4),
                "solve_ms": leader.solve_ms,
                "wire_bytes_per_dest": per_dest,
            }
            if sharded:
                # The acceptance gate: the dests' shards gather on-mesh
                # into layers byte-exact against the stamped digests.
                gathered_ok = 0
                for lid in range(n_layers):
                    parts = []
                    for k, r in enumerate(dests):
                        off, size = shard_range(specs[k], layer_bytes)
                        parts.append((k, bytes(
                            memoryview(r.layers[lid].inmem_data)
                            [off:off + size])))
                    out = gather_byte_shards(
                        parts, layer_bytes,
                        verify_digest=leader.layer_digests.get(lid))
                    if out != bytes(mem_layer(lid).inmem_data):
                        raise AssertionError(
                            f"gathered layer {lid} not byte-exact")
                    gathered_ok += 1
                rec["gathered_layers_byte_exact"] = gathered_ok
            else:
                # Byte-exactness of the full-layer sibling.
                for lid in range(n_layers):
                    for r in dests:
                        if bytes(r.layers[lid].inmem_data) != bytes(
                                mem_layer(lid).inmem_data):
                            raise AssertionError(
                                f"full layer {lid} corrupt at "
                                f"{r.node.my_id}")
            rep = report_mod.build_from_leader(leader)
            rec["run_report"] = rep.get("provenance")
            return rec
        finally:
            _service_teardown(leader, dests, ts)

    full = one_run(sharded=False)
    shard = one_run(sharded=True)
    frac_bytes = sum(shard_range(specs[k], layer_bytes)[1]
                     for k in range(n_shards)) // n_shards * n_layers
    bound_lo, bound_hi = frac_bytes, round(frac_bytes * 1.1)
    within = all(bound_lo <= d["rx_bytes"] <= bound_hi
                 for d in shard["wire_bytes_per_dest"].values())
    return {
        "harness_hash": harness_hash(),
        "backend": "tcp-loopback",
        "mode": 3,
        "layer_bytes": layer_bytes,
        "n_layers": n_layers,
        "n_dests": n_shards,
        "shard_fraction": f"1/{n_shards}",
        "modeled_bw_bps": bw,
        "full": full,
        "sharded": shard,
        "shard_bytes_per_dest_bound": [bound_lo, bound_hi],
        "wire_within_10pct": within,
        "ttd_ratio": round(shard["ttd_s"] / max(full["ttd_s"], 1e-9), 4),
    }


def run_fabric_delivery(layer_bytes: int = 32 << 20, n_layers: int = 2,
                        pod_size: int = 4, bw: int = 10 ** 9,
                        timeout: float = 600.0) -> dict:
    """Fabric-assisted pod delivery vs host-path fan-out
    (docs/fabric.md): the same topology — one leader, ``pod_size``
    replica dests all wanting all ``n_layers`` × ``layer_bytes`` layers
    — run twice.  HOST path: every replica pulls every full layer over
    its NIC (pod ingress = model_bytes × replicas).  FABRIC-ASSISTED:
    the leader pod-plans one 1/R shard per host over the NIC and the
    replicas materialize the full tree over the on-mesh gather (pod
    ingress ≈ model_bytes).  Records per-pod NIC wire bytes (byte-exact
    via the telemetry link table reconcile), TTD, per-replica
    tree-digest exactness against the leader's stamped full-layer
    digests, and RUN_REPORT provenance."""
    from ..core.types import LayerMeta, shard_range
    from ..parallel.fabric import FabricPlane
    from ..utils import integrity, telemetry, trace
    from ..utils.provenance import harness_hash
    from . import report as report_mod

    model_bytes = n_layers * layer_bytes

    def one_run(pod: bool) -> dict:
        telemetry.reset_run()
        assignment = {
            k + 1: {lid: LayerMeta() for lid in range(n_layers)}
            for k in range(pod_size)
        }
        members = list(range(1, pod_size + 1))
        leader, dests, ts, mem_layer = _service_rig(
            n_layers, layer_bytes, assignment, bw, n_dests=pod_size,
            fabric=FabricPlane() if pod else None,
            pods={0: members} if pod else None)
        try:
            t0 = time.monotonic()
            for r in dests:
                r.announce()
            leader.ready().get(timeout=timeout)
            ttd = round(time.monotonic() - t0, 4)
            links = telemetry.snapshot()["links"]
            per_dest = {r.node.my_id: _dest_wire_bytes(links,
                                                       r.node.my_id)
                        for r in dests}
            # The acceptance gate: every replica's FULL tree, byte-
            # and digest-exact against the leader's stamped full-layer
            # digests (for the pod run this is the post-gather state).
            exact = 0
            for r in dests:
                for lid in range(n_layers):
                    src = r.layers[lid]
                    if src.meta.shard:
                        raise AssertionError(
                            f"dest {r.node.my_id} layer {lid} is still "
                            f"a shard holding ({src.meta.shard})")
                    tree = bytes(src.inmem_data)
                    if tree != bytes(mem_layer(lid).inmem_data):
                        raise AssertionError(
                            f"dest {r.node.my_id} layer {lid} tree not "
                            "byte-exact")
                    stamped = leader.layer_digests.get(lid)
                    if stamped and not integrity.digest_matches(
                            tree, stamped):
                        raise AssertionError(
                            f"dest {r.node.my_id} layer {lid} tree "
                            "fails the stamped digest")
                    exact += 1
            pod_wire = sum(d["rx_bytes"] for d in per_dest.values())
            pod_delivered = sum(d["delivered_bytes"]
                                for d in per_dest.values())
            counters = trace.counter_totals()
            rep = report_mod.build_from_leader(leader)
            return {
                "ttd_s": ttd,
                "predicted_s": round(leader.predicted_ttd_ms / 1000.0,
                                     4),
                "solve_ms": leader.solve_ms,
                "pod_nic_wire_bytes": pod_wire,
                "pod_delivered_bytes": pod_delivered,
                "wire_bytes_per_dest": per_dest,
                "trees_digest_exact": exact,
                "gathers": counters.get("shard.gathered_layers", 0),
                "run_report": rep.get("provenance"),
            }
        finally:
            _service_teardown(leader, dests, ts)

    host = one_run(pod=False)
    fab = one_run(pod=True)
    # Per-pod ingress bars: host path ships model_bytes × R; the
    # fabric-assisted run must land within 10% of model_bytes (framing
    # overhead only — the byte-exact reconcile is on delivered bytes).
    fab_ok = (model_bytes
              <= fab["pod_nic_wire_bytes"] <= round(model_bytes * 1.1))
    return {
        "harness_hash": harness_hash(),
        "backend": "tcp-loopback",
        "mode": 3,
        "layer_bytes": layer_bytes,
        "n_layers": n_layers,
        "replicas": pod_size,
        "model_bytes": model_bytes,
        "modeled_bw_bps": bw,
        "host_path": host,
        "fabric_assisted": fab,
        "pod_wire_bound": [model_bytes, round(model_bytes * 1.1)],
        "pod_wire_within_10pct": fab_ok,
        "pod_delivered_exact": fab["pod_delivered_bytes"] == sum(
            shard_range(f"1/{pod_size}@{k}", layer_bytes)[1]
            for k in range(pod_size) for _ in range(n_layers)),
        "wire_ratio_vs_host": round(
            fab["pod_nic_wire_bytes"]
            / max(host["pod_nic_wire_bytes"], 1), 4),
        "ttd_ratio_vs_host": round(
            fab["ttd_s"] / max(host["ttd_s"], 1e-9), 4),
        "byte_exact": True,
    }


def run_fanout(sizes=(64, 256), n_layers: int = 2,
               layer_bytes: int = 256 << 10,
               timeout: float = 600.0) -> dict:
    """Scale-out acceptance row (docs/hierarchy.md; ROADMAP item 1):
    the SAME inmem BASELINE goal — every one of N dests wants every
    layer from the one seeding root — run flat (mode 3) and
    hierarchically (sqrt-sized groups under sub-leaders), at each fleet
    size in ``sizes``.  Records, per run: root flow-solve wall, the
    count of control messages the ROOT's loop handled
    (``ctrl.handled.<root>``), TTD, and RUN_REPORT provenance.  The
    bar: from N=64 to N=256 the hierarchical root's solve wall and
    handled-message count must grow SUB-LINEARLY in N while the flat
    root's grow ~linearly — and the hierarchical absolute numbers must
    beat the flat ones at 256."""
    from ..core.types import LayerMeta
    from ..runtime import (
        FlowRetransmitLeaderNode,
        FlowRetransmitReceiverNode,
        HierarchicalFlowLeaderNode,
        Node,
        SubLeaderController,
        partition_groups,
    )
    from ..transport import reset_registry
    from ..transport.inmem import InmemTransport
    from ..utils import telemetry
    from ..utils.provenance import harness_hash
    from . import report as report_mod

    pattern = bytes(range(256))

    def mem_blob(lid: int):
        from ..core.types import LayerLocation, LayerSrc, SourceType

        rot = (lid * 37) % 256
        data = bytearray((pattern[rot:] + pattern[:rot])
                         * (layer_bytes // 256))
        return LayerSrc(inmem_data=data, data_size=len(data),
                        meta=LayerMeta(location=LayerLocation.INMEM,
                                       source_type=SourceType.MEM))

    def one_run(n: int, hier: bool) -> dict:
        reset_registry()
        telemetry.reset_run()
        ids = list(range(n + 1))
        registry = {i: f"n{i}" for i in ids}
        ts = {i: InmemTransport(registry[i], addr_registry=registry)
              for i in ids}
        assignment = {i: {lid: LayerMeta() for lid in range(n_layers)}
                      for i in ids[1:]}
        layers = {lid: mem_blob(lid) for lid in range(n_layers)}
        bw = {i: 10 ** 9 for i in ids}
        recvs, ctls = {}, []
        groups = {}
        if hier:
            groups = partition_groups(ids[1:])  # ~sqrt(N)-sized groups
            subs = {rec["leader"] for rec in groups.values()}
            leader = HierarchicalFlowLeaderNode(
                Node(0, 0, ts[0]), layers, assignment, bw,
                groups=groups, expected_nodes=subs)
            for gid, rec in sorted(groups.items()):
                sub = rec["leader"]
                r = FlowRetransmitReceiverNode(Node(sub, 0, ts[sub]), {})
                ctls.append(SubLeaderController(r, gid, rec["members"]))
                recvs[sub] = r
                for m in rec["members"]:
                    if m != sub:
                        recvs[m] = FlowRetransmitReceiverNode(
                            Node(m, sub, ts[m]), {})
        else:
            leader = FlowRetransmitLeaderNode(
                Node(0, 0, ts[0]), layers, assignment, bw,
                expected_nodes=set(ids[1:]))
            for i in ids[1:]:
                recvs[i] = FlowRetransmitReceiverNode(
                    Node(i, 0, ts[i]), {})
        try:
            t0 = time.monotonic()
            for i in sorted(recvs):
                recvs[i].announce()
            leader.start_distribution().get(timeout=timeout)
            leader.ready().get(timeout=timeout)
            ttd = round(time.monotonic() - t0, 4)
            bad = 0
            for i in ids[1:]:
                for lid in range(n_layers):
                    if bytes(recvs[i].layers[lid].inmem_data) != bytes(
                            mem_blob(lid).inmem_data):
                        bad += 1
            if bad:
                raise AssertionError(
                    f"{bad} corrupt deliveries at n={n} hier={hier}")
            snap = telemetry.snapshot()
            counters = snap["counters"]
            # Byte-exact link reconcile (docs/hierarchy.md): the base
            # "src->dest" link rows claim delivered bytes exactly once
            # per dest pair, so their sum must equal N x model bytes no
            # matter how many member-to-member hops carried them.
            delivered = sum(int(row.get("delivered_bytes", 0))
                            for key, row in snap["links"].items()
                            if "#" not in key)
            egress = int(counters.get("hier.subleader_egress_bytes", 0))
            rep = report_mod.build_from_leader(leader)
            return {
                "n_nodes": n,
                "control": "hierarchical" if hier else "flat",
                "groups": len(groups),
                "ttd_s": ttd,
                "solve_ms": leader.solve_ms,
                "predicted_s": round(leader.predicted_ttd_ms / 1000.0, 4),
                "root_handled_msgs": int(counters.get("ctrl.handled.0",
                                                      0)),
                "byte_exact_deliveries": n * n_layers,
                "chain_plans": int(counters.get("hier.chain_plans", 0)),
                "relay_bytes": int(counters.get("hier.relay_bytes", 0)),
                "subleader_egress_bytes": egress,
                "egress_bytes_per_subleader": (
                    round(egress / len(groups)) if groups else 0),
                "link_reconcile_exact":
                    delivered == n * n_layers * layer_bytes,
                "run_report": rep.get("provenance"),
            }
        finally:
            for c in ctls:
                c.close()
            leader.close()
            for r in recvs.values():
                r.close()
            for t in ts.values():
                t.close()
            reset_registry()

    # An N-node in-process fleet must not lazily grow N x 16 handler
    # threads; 2 per seat is plenty for the control traffic here.
    prior_workers = os.environ.get("DLD_MSGLOOP_WORKERS")
    os.environ["DLD_MSGLOOP_WORKERS"] = "2"
    try:
        rows = []
        for n in sizes:
            for hier in (False, True):
                row = one_run(n, hier)
                rows.append(row)
                print(f"fanout n={n} {row['control']}: TTD "
                      f"{row['ttd_s']}s solve {row['solve_ms']}ms "
                      f"root-handled {row['root_handled_msgs']}",
                      file=sys.stderr, flush=True)
    finally:
        if prior_workers is None:
            os.environ.pop("DLD_MSGLOOP_WORKERS", None)
        else:
            os.environ["DLD_MSGLOOP_WORKERS"] = prior_workers

    def pick(n, control):
        return next(r for r in rows
                    if r["n_nodes"] == n and r["control"] == control)

    lo, hi = sizes[0], sizes[-1]
    node_growth = hi / lo
    flat_lo, flat_hi = pick(lo, "flat"), pick(hi, "flat")
    hier_lo, hier_hi = pick(lo, "hierarchical"), pick(hi, "hierarchical")
    msg_growth_flat = round(flat_hi["root_handled_msgs"]
                            / max(flat_lo["root_handled_msgs"], 1), 3)
    msg_growth_hier = round(hier_hi["root_handled_msgs"]
                            / max(hier_lo["root_handled_msgs"], 1), 3)
    solve_growth_flat = round(flat_hi["solve_ms"]
                              / max(flat_lo["solve_ms"], 1e-9), 3)
    solve_growth_hier = round(hier_hi["solve_ms"]
                              / max(hier_lo["solve_ms"], 1e-9), 3)
    # Chain-vs-star egress at the top size (docs/hierarchy.md): under
    # the old sub-leader star every one of the (N - n_groups) non-sub
    # members would be a full copy out of its sub's NIC; the chain
    # ships each group ~one copy and lets members relay the rest, so
    # of each group's R copies only 1/R leaves the sub — (R-1)/R of
    # the fan rides member-to-member links.
    model_bytes = n_layers * layer_bytes
    star_bytes = (hier_hi["n_nodes"] - hier_hi["groups"]) * model_bytes
    chain_bytes = hier_hi["subleader_egress_bytes"]
    return {
        "harness_hash": harness_hash(),
        "backend": "inmem",
        "mode": 3,
        "n_layers": n_layers,
        "layer_bytes": layer_bytes,
        "group_sizing": "sqrt",
        "rows": rows,
        "node_growth": node_growth,
        "root_msgs_growth": {"flat": msg_growth_flat,
                             "hierarchical": msg_growth_hier},
        "solve_growth": {"flat": solve_growth_flat,
                         "hierarchical": solve_growth_hier},
        # The acceptance bars (docs/hierarchy.md): sub-linear growth in
        # N for the hierarchical root, and absolutely cheaper than the
        # flat root at the top size.
        "msgs_sublinear": (msg_growth_hier < node_growth
                           and hier_hi["root_handled_msgs"]
                           < flat_hi["root_handled_msgs"]),
        "solve_sublinear": (solve_growth_hier < node_growth
                            and hier_hi["solve_ms"]
                            < flat_hi["solve_ms"]),
        "chain_egress": {
            "subleader_egress_bytes": chain_bytes,
            "egress_bytes_per_subleader":
                hier_hi["egress_bytes_per_subleader"],
            "relay_bytes": hier_hi["relay_bytes"],
            "star_equivalent_bytes": star_bytes,
            "egress_savings_frac": (round(1.0 - chain_bytes / star_bytes,
                                          3) if star_bytes else 0.0),
        },
        "links_reconcile_exact": all(r["link_reconcile_exact"]
                                     for r in rows),
    }


def run_elasticity(joiner_counts=(2, 6), n_base: int = 2,
                   n_layers: int = 3, layer_bytes: int = 256 << 10,
                   timeout: float = 120.0) -> dict:
    """Elastic-membership acceptance row (docs/membership.md; ROADMAP
    item 5): the base goal disseminates from ONE origin seeder (the
    leader) to ``n_base`` configured dests; then N UNCONFIGURED nodes
    JOIN the running cluster concurrently and must reach full coverage
    byte-exactly.  Per variant the row records the origin-seeder wire
    bytes into the joiners vs the bytes peer holders served, and the
    bars: the MAJORITY of refill bytes come from peer holders, and
    origin bytes grow sub-linearly in the joiner count (the join
    refill policy avoids the origin whenever peers can serve)."""
    import threading as _threading

    from ..core.types import LayerMeta
    from ..runtime import (
        FlowRetransmitLeaderNode,
        FlowRetransmitReceiverNode,
        Node,
    )
    from ..transport import reset_registry
    from ..transport.inmem import InmemTransport
    from ..utils import telemetry
    from ..utils.provenance import harness_hash
    from . import report as report_mod

    pattern = bytes(range(256))

    def mem_blob(lid: int):
        from ..core.types import LayerLocation, LayerSrc, SourceType

        rot = (lid * 53) % 256
        data = bytearray((pattern[rot:] + pattern[:rot])
                         * (layer_bytes // 256))
        return LayerSrc(inmem_data=data, data_size=len(data),
                        meta=LayerMeta(location=LayerLocation.INMEM,
                                       source_type=SourceType.MEM))

    def one_run(n_joiners: int) -> dict:
        reset_registry()
        telemetry.reset_run()
        ids = list(range(n_base + 1))
        registry = {i: f"n{i}" for i in ids}
        ts = {i: InmemTransport(registry[i], addr_registry=registry)
              for i in ids}
        assignment = {i: {lid: LayerMeta() for lid in range(n_layers)}
                      for i in ids[1:]}
        leader = FlowRetransmitLeaderNode(
            Node(0, 0, ts[0]), {lid: mem_blob(lid)
                                for lid in range(n_layers)},
            assignment, {i: 10 ** 9 for i in ids},
            expected_nodes=set(ids[1:]))
        recvs = {i: FlowRetransmitReceiverNode(Node(i, 0, ts[i]), {})
                 for i in ids[1:]}
        joiners = {}
        try:
            for r in recvs.values():
                r.announce()
            leader.start_distribution().get(timeout=timeout)
            leader.ready().get(timeout=timeout)
            # The joiners arrive CONCURRENTLY, mid-service: each join
            # admits a refill job that overlaps the others' in-flight
            # dissemination.
            t0 = time.monotonic()
            for k in range(n_joiners):
                jid = 100 + k
                tj = InmemTransport(f"n{jid}",
                                    addr_registry={0: registry[0]})
                ts[jid] = tj
                joiners[jid] = FlowRetransmitReceiverNode(
                    Node(jid, 0, tj), {})
            threads = [_threading.Thread(
                target=joiners[jid].join, kwargs={"timeout": timeout},
                daemon=True) for jid in joiners]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout)

            def covered():
                for j in joiners.values():
                    for lid in range(n_layers):
                        src = j.layers.get(lid)
                        if src is None or bytes(src.inmem_data) != bytes(
                                mem_blob(lid).inmem_data):
                            return False
                return True

            deadline = time.monotonic() + timeout
            while not covered():
                if time.monotonic() > deadline:
                    raise AssertionError(
                        f"joiners not covered at n={n_joiners}")
                time.sleep(0.02)
            cover_s = round(time.monotonic() - t0, 4)
            # BASE rows only: job-tagged fields file on the base row
            # AND the #job split row — summing both double-counts.
            origin_bytes = peer_bytes = 0
            for key, row in telemetry.snapshot()["links"].items():
                if "#" in key:
                    continue
                s, d = key.split("->")
                if int(d) >= 100:
                    b = int(row.get("tx_bytes", 0))
                    if int(s) == 0:
                        origin_bytes += b
                    else:
                        peer_bytes += b
            rep = report_mod.build_from_leader(leader)
            total = origin_bytes + peer_bytes
            return {
                "n_joiners": n_joiners,
                "coverage_s": cover_s,
                "origin_bytes": origin_bytes,
                "peer_bytes": peer_bytes,
                "peer_fraction": round(peer_bytes / total, 4)
                                 if total else 0.0,
                "byte_exact_deliveries": n_joiners * n_layers,
                "members": leader.membership.size(),
                "run_report": rep.get("provenance"),
            }
        finally:
            leader.close()
            for r in list(recvs.values()) + list(joiners.values()):
                r.close()
            for t in ts.values():
                t.close()
            reset_registry()

    rows = []
    for n in joiner_counts:
        row = one_run(n)
        rows.append(row)
        print(f"elasticity n_joiners={n}: origin "
              f"{row['origin_bytes']} B, peers {row['peer_bytes']} B "
              f"(peer fraction {row['peer_fraction']}), covered in "
              f"{row['coverage_s']}s", file=sys.stderr, flush=True)
    lo, hi = rows[0], rows[-1]
    joiner_growth = hi["n_joiners"] / max(lo["n_joiners"], 1)
    origin_growth = (hi["origin_bytes"] / lo["origin_bytes"]
                     if lo["origin_bytes"] else
                     (0.0 if not hi["origin_bytes"] else float("inf")))
    return {
        "harness_hash": harness_hash(),
        "backend": "inmem",
        "mode": 3,
        "n_base": n_base,
        "n_layers": n_layers,
        "layer_bytes": layer_bytes,
        "rows": rows,
        "joiner_growth": joiner_growth,
        "origin_growth": round(origin_growth, 3),
        # The acceptance bars (docs/membership.md): refills come mostly
        # from peer holders, and origin bytes grow sub-linearly in the
        # joiner count.
        "peers_majority": all(r["peer_fraction"] > 0.5 for r in rows
                              if r["origin_bytes"] + r["peer_bytes"]),
        "origin_sublinear": origin_growth < joiner_growth,
    }


def run_live_swap(warm_s: float = 1.5, after_s: float = 1.5,
                  timeout: float = 300.0) -> dict:
    """Zero-downtime weight swap under live traffic (docs/swap.md, the
    ROADMAP item-4 acceptance row): a tiny-model replica serves
    generation requests continuously while a ``kind="swap"`` job
    disseminates v2 under version-tagged ids; the epoch-fenced commit
    flips the serving params atomically.  Records tokens/s and p99
    request latency BEFORE / DURING / AFTER the swap, the request
    failure count (the bar: zero), per-blob v2 digest verification,
    and RUN_REPORT provenance.  Runs in-process over the inmem
    backend: the row measures the SERVING dip attributable to the
    swap machinery, not loopback-TCP scheduling noise (the dual-
    backend wire path is tier-1-tested in tests/test_swap.py)."""
    import threading

    import jax

    from ..core.types import (
        LayerLocation,
        LayerMeta,
        LayerSrc,
        SourceType,
    )
    from ..models import serde
    from ..models.llama import CONFIGS, init_params
    from ..runtime import (
        FlowRetransmitLeaderNode,
        FlowRetransmitReceiverNode,
        Node,
    )
    from ..runtime.client import GenRequester
    from ..transport import InmemTransport
    from ..utils import integrity, telemetry, trace
    from ..utils.provenance import harness_hash
    from . import report as report_mod

    telemetry.reset_run()
    cfg = CONFIGS["tiny"]
    swap_base = 1000
    v1 = serde.blobs_from_params(cfg, init_params(cfg, jax.random.key(0)))
    v2 = serde.blobs_from_params(cfg, init_params(cfg, jax.random.key(1)))

    def blob_layer(data: bytes) -> LayerSrc:
        return LayerSrc(inmem_data=bytearray(data), data_size=len(data),
                        meta=LayerMeta(location=LayerLocation.INMEM,
                                       source_type=SourceType.MEM))

    ids = [0, 1, 9]
    ts = {i: InmemTransport(str(i)) for i in ids}
    seed = {b: blob_layer(v1[b]) for b in v1}
    seed.update({swap_base + b: blob_layer(v2[b]) for b in v2})
    base = {1: {b: LayerMeta() for b in v1}}
    leader = FlowRetransmitLeaderNode(
        Node(0, 0, ts[0]), seed, base, {i: 10 ** 9 for i in ids},
        expected_nodes={1})
    dest = FlowRetransmitReceiverNode(Node(1, 0, ts[1]), {}, boot_cfg=cfg)
    requester = GenRequester(ts[9], my_id=9)
    prompt, max_new = [3, 5, 7], 8
    lat: dict = {"before": [], "during": [], "after": []}
    failures: list = []
    phase = ["before"]
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            t0 = time.monotonic()
            try:
                requester.request(1, prompt, max_new, timeout=timeout)
                lat[phase[0]].append(time.monotonic() - t0)
            except Exception as e:  # noqa: BLE001 — any failure counts
                failures.append(repr(e))
            time.sleep(0.01)

    def stats(xs):
        if not xs:
            return {"requests": 0}
        xs = sorted(xs)
        p99 = xs[min(len(xs) - 1, int(len(xs) * 0.99))]
        return {"requests": len(xs),
                "tokens_per_s": round(max_new * len(xs) / sum(xs), 2),
                "p50_ms": round(xs[len(xs) // 2] * 1000, 1),
                "p99_ms": round(p99 * 1000, 1)}

    try:
        dest.announce()
        leader.ready().get(timeout=timeout)
        leader.boot_ready().get(timeout=timeout)
        requester.request(1, prompt, max_new, timeout=timeout)  # warm jit
        t = threading.Thread(target=hammer, daemon=True)
        t.start()
        time.sleep(warm_s)
        phase[0] = "during"
        t_swap = time.monotonic()
        leader.submit_job(
            "swap-v2",
            {1: {swap_base + b: LayerMeta() for b in v2}},
            priority=2, kind="swap", version="v2", swap_base=swap_base)
        deadline = time.monotonic() + timeout
        while dest.serving_version != "v2":
            if time.monotonic() > deadline:
                raise TimeoutError("swap never flipped")
            time.sleep(0.02)
        swap_s = time.monotonic() - t_swap
        phase[0] = "after"
        time.sleep(after_s)
        stop.set()
        t.join(timeout=timeout)
        table = leader.swap_table()["v2"]
        digests_ok = (all(swap_base + b in dest._digest_ok for b in v2)
                      if integrity.digests_enabled() else None)
        counters = trace.counter_totals()
        rep = report_mod.build_from_leader(leader)
        before, during, after = (stats(lat[k])
                                 for k in ("before", "during", "after"))
        dip = None
        if before.get("tokens_per_s") and during.get("tokens_per_s"):
            dip = round(1 - during["tokens_per_s"]
                        / before["tokens_per_s"], 4)
        return {
            "harness_hash": harness_hash(),
            "backend": "inmem",
            "mode": 3,
            "model": "tiny",
            "v2_model_bytes": sum(len(b) for b in v2.values()),
            "swap_wall_s": round(swap_s, 4),
            "request_failures": len(failures),
            "zero_failures": not failures,
            "before": before,
            "during": during,
            "after": after,
            "tokens_per_s_dip_frac": dip,
            "v2_digests_verified": digests_ok,
            "flips": counters.get("swap.flips", 0),
            "served_version_after": dest.serving_version,
            "swap_table": table,
            "run_report": rep.get("provenance"),
        }
    finally:
        stop.set()
        requester.close()
        _service_teardown(leader, [dest], ts)


def run_rollout(soak_s: float = 2.5, p99_ms: float = 2000.0,
                bad_delay_ms: float = 1500.0,
                timeout: float = 300.0) -> dict:
    """SLO-guarded rollout pipeline under live traffic (docs/rollout.md,
    the ROADMAP item-3 acceptance row): a continuous request stream
    drives three tiny-model replicas while a ``kind="rollout"`` job
    ships v2 through three canary waves.  Wave 1 is the INJECTED BAD
    WAVE — its replica's answers ride a seeded ``slowserve`` transport
    delay, so its soak p99 breaches the declared SLO: the pipeline must
    auto-PAUSE and roll that wave back to v1 through the revert-abort
    while wave 0 KEEPS serving v2 and wave 2 stays staged-but-held.
    The bars: zero dropped requests fleet-wide, the breach verdict
    recorded with per-replica p99, earlier wave still on v2 after the
    rollback.  In-process inmem (the dual-backend wire path is
    tier-1-tested in tests/test_rollout.py); RUN_REPORT provenance
    recorded."""
    import threading

    import jax

    from ..core.types import (
        LayerLocation,
        LayerMeta,
        LayerSrc,
        SourceType,
    )
    from ..models import serde
    from ..models.llama import CONFIGS, init_params
    from ..runtime import (
        FlowRetransmitLeaderNode,
        FlowRetransmitReceiverNode,
        Node,
    )
    from ..runtime.client import GenRequester
    from ..transport import InmemTransport
    from ..transport.faults import FaultyTransport, rules_from_spec
    from ..utils import telemetry, trace
    from ..utils.provenance import harness_hash
    from . import report as report_mod

    telemetry.reset_run()
    prior_metrics = os.environ.get("DLD_METRICS_INTERVAL_S")
    os.environ["DLD_METRICS_INTERVAL_S"] = "0.25"
    cfg = CONFIGS["tiny"]
    swap_base = 1000
    v1 = serde.blobs_from_params(cfg, init_params(cfg, jax.random.key(0)))
    v2 = serde.blobs_from_params(cfg, init_params(cfg, jax.random.key(1)))

    def blob_layer(data: bytes) -> LayerSrc:
        return LayerSrc(inmem_data=bytearray(data), data_size=len(data),
                        meta=LayerMeta(location=LayerLocation.INMEM,
                                       source_type=SourceType.MEM))

    replicas_ids = [1, 2, 3]
    bad = 2  # wave 1's replica
    ids = [0, *replicas_ids, 9]
    ts = {i: InmemTransport(str(i)) for i in ids}
    fault_spec = f"slowserve={bad_delay_ms:g}"
    seed, rules = rules_from_spec(fault_spec)
    ts[bad] = FaultyTransport(ts[bad], rules, seed=seed)
    seed_layers = {b: blob_layer(v1[b]) for b in v1}
    seed_layers.update({swap_base + b: blob_layer(v2[b]) for b in v2})
    base = {r: {b: LayerMeta() for b in v1} for r in replicas_ids}
    leader = FlowRetransmitLeaderNode(
        Node(0, 0, ts[0]), seed_layers, base,
        {i: 10 ** 9 for i in ids}, expected_nodes=set(replicas_ids))
    replicas = {r: FlowRetransmitReceiverNode(Node(r, 0, ts[r]), {},
                                              boot_cfg=cfg)
                for r in replicas_ids}
    requester = GenRequester(ts[9], my_id=9)
    prompt, max_new = [3, 5, 7], 8
    failures: list = []
    served = {r: 0 for r in replicas_ids}
    stop = threading.Event()

    def hammer(replica):
        while not stop.is_set():
            try:
                requester.request(replica, prompt, max_new,
                                  timeout=timeout)
                served[replica] += 1
            except Exception as e:  # noqa: BLE001 — any failure counts
                failures.append(repr(e))
            time.sleep(0.03)

    threads = [threading.Thread(target=hammer, args=(r,), daemon=True)
               for r in replicas_ids]
    try:
        for r in replicas.values():
            r.announce()
        leader.ready().get(timeout=timeout)
        leader.boot_ready().get(timeout=timeout)
        for r in replicas_ids:  # warm the decode jits pre-rollout
            requester.request(r, prompt, max_new, timeout=timeout)
        for t in threads:
            t.start()
        t_roll = time.monotonic()
        leader.submit_job(
            "roll-v2",
            {r: {swap_base + b: LayerMeta() for b in v2}
             for r in replicas_ids},
            priority=2, kind="rollout", version="v2",
            swap_base=swap_base, waves=[[1], [2], [3]],
            slo={"P99Ms": p99_ms, "MaxFailures": 5, "SoakS": soak_s},
            split=0.5)
        deadline = time.monotonic() + timeout

        def row():
            return leader.rollouts.summary("roll-v2")

        while row().get("State") != "paused":
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"bad wave never breached: {row()}")
            time.sleep(0.05)
        pause_s = time.monotonic() - t_roll
        # The rollback fence is in flight: wait for the replica revert.
        while replicas[bad].serving_version != "":
            if time.monotonic() > deadline:
                raise TimeoutError("bad wave never reverted to v1")
            time.sleep(0.05)
        time.sleep(0.5)  # post-rollback serving window
        stop.set()
        for t in threads:
            t.join(timeout=timeout)
        final = row()
        traffic = final["Traffic"]
        counters = trace.counter_totals()
        rep = report_mod.build_from_leader(leader)
        # Post-rollback serving probes: wave 0 keeps v2, the rolled-
        # back wave answers v1 again, wave 2 never flipped.
        def toks(seed_):
            from ..models.generate import generate
            import jax.numpy as jnp

            out = generate(init_params(cfg, jax.random.key(seed_)),
                           jnp.asarray([prompt], jnp.int32), cfg,
                           max_new=max_new)
            return [int(t) for t in jax.device_get(out)[0]]

        v1_tokens, v2_tokens = toks(0), toks(1)
        probes = {r: requester.request(r, prompt, max_new,
                                       timeout=timeout)
                  for r in replicas_ids}
        return {
            "harness_hash": harness_hash(),
            "backend": "inmem",
            "mode": 3,
            "model": "tiny",
            "waves": final["Waves"],
            "wave_states": final["WaveStates"],
            "slo": final["SLO"],
            "split": final["Split"],
            "fault_spec": fault_spec,
            "state": final["State"],
            "paused_reason": final["PausedReason"],
            "verdicts": final["Verdicts"],
            "wall_to_breach_pause_s": round(pause_s, 3),
            "request_failures": len(failures),
            "zero_failures": not failures,
            "requests_served": dict(served),
            "traffic_after": traffic,
            "wave0_keeps_v2": probes[1] == v2_tokens,
            "bad_wave_back_on_v1": probes[bad] == v1_tokens,
            "wave2_never_flipped": probes[3] == v1_tokens,
            "serving_versions": {r: replicas[r].serving_version
                                 for r in replicas_ids},
            "slo_breaches": counters.get("rollout.slo_breach", 0),
            "reverts": counters.get("swap.reverted", 0),
            "waves_passed": counters.get("rollout.wave_passed", 0),
            "run_report": rep.get("provenance"),
        }
    finally:
        stop.set()
        requester.close()
        if prior_metrics is None:
            os.environ.pop("DLD_METRICS_INTERVAL_S", None)
        else:
            os.environ["DLD_METRICS_INTERVAL_S"] = prior_metrics
        _service_teardown(leader, list(replicas.values()), ts)


def _rollout_md(lines, results) -> None:
    ro = results.get("rollout")
    if not ro:
        return
    bars = {
        "zero dropped requests": ro["zero_failures"],
        "bad wave auto-halted (SLO breach -> pause)":
            ro["state"] == "paused" and ro["slo_breaches"] >= 1,
        "bad wave rolled back to v1": ro["bad_wave_back_on_v1"],
        "earlier wave keeps serving v2": ro["wave0_keeps_v2"],
    }
    lines += [
        "## SLO-guarded rollout pipeline (docs/rollout.md)",
        "",
        f"A continuous request stream drives 3 tiny-model replicas "
        f"({ro['backend']} backend, mode {ro['mode']}) through a "
        f"3-wave `kind=\"rollout\"` pipeline (waves {ro['waves']}, "
        f"SLO p99 <= {ro['slo']['p99_ms']:g}ms over "
        f"{ro['slo']['soak_s']:g}s soaks, split {ro['split']}).  "
        f"Wave 1's replica is the injected bad wave "
        f"(`{ro['fault_spec']}`): its soak breached and the pipeline "
        f"paused after {ro['wall_to_breach_pause_s']}s "
        f"(`{ro['paused_reason']}`).",
        "",
        "| bar | met |",
        "|---|---|",
    ]
    for name, met in bars.items():
        lines.append(f"| {name} | {'MET' if met else 'NOT MET'} |")
    lines += [
        "",
        f"Wave states `{ro['wave_states']}`; verdicts: "
        + "; ".join(
            f"wave {w}: {v['verdict']}"
            + (f" (p99 {next(iter(v['replicas'].values()))['p99_ms']}"
               "ms)" if v.get("replicas") else "")
            for w, v in sorted(ro["verdicts"].items()))
        + f".  {sum(ro['requests_served'].values())} requests served, "
        f"{ro['request_failures']} failed.  Traffic pools after the "
        f"rollback: v2={ro['traffic_after']['v2']} "
        f"v1={ro['traffic_after']['v1']} at split "
        f"{ro['traffic_after']['split']}.  Run report "
        f"`{ro.get('run_report')}`.",
        "",
    ]


def run_autonomy(p99_ms: float = 250.0, hot_delay_ms: float = 600.0,
                 bulk_bytes: int = 24 << 20, bw: int = 25_000_000,
                 slow_rate: int = 2 << 20, timeout: float = 300.0,
                 kill_switch: bool = False) -> dict:
    """The closed-loop fleet-autonomy acceptance row (docs/autonomy.md,
    ROADMAP item 4): a serving fleet takes TWO concurrent injections —
    a ``slowserve`` hot replica breaching the serve SLO and a ``slow=``
    straggler link under a bulk transfer — and the leader's policy
    engine must converge the fleet back inside SLO with ZERO operator
    verbs: the replica set grown onto a spare (join+refill through
    ``submit_job``), the slow link demoted and re-planned around
    through the flow solver, the breaching replica quarantined out of
    the serve rotation, every action audited and span-attributed in
    RUN_REPORT.  ``kill_switch=True`` runs the SAME injections under
    ``DLD_POLICY=0``: sensing stays live (``held_manual`` audit
    records) but nothing fires — the sibling row proving the zero-verb
    convergence was the ENGINE, not a coincidence."""
    import threading

    import jax

    from ..core.types import (
        LayerLocation,
        LayerMeta,
        LayerSrc,
        SourceType,
    )
    from ..models import serde
    from ..models.llama import CONFIGS, init_params
    from ..runtime import (
        FlowRetransmitLeaderNode,
        FlowRetransmitReceiverNode,
        Node,
    )
    from ..runtime import send as send_mod
    from ..runtime.client import GenRequester
    from ..transport import InmemTransport
    from ..transport.faults import FaultyTransport, rules_from_spec
    from ..utils import telemetry, trace
    from ..utils.provenance import harness_hash
    from . import report as report_mod

    telemetry.reset_run()
    prior_metrics = os.environ.get("DLD_METRICS_INTERVAL_S")
    prior_policy = os.environ.get("DLD_POLICY")
    prior_sustain = os.environ.get("DLD_STRAGGLER_N")
    prior_frag = send_mod.FLOW_FRAGMENT_BYTES
    os.environ["DLD_METRICS_INTERVAL_S"] = "0.25"
    os.environ["DLD_POLICY"] = "0" if kill_switch else "1"
    # Two sustained intervals before a straggler flags: a pair planned
    # mid-interval legitimately reads 0 B/s once — judging on a single
    # interval would false-flag the very link the re-plan just chose.
    os.environ["DLD_STRAGGLER_N"] = "2"
    # Small fragments so the throttled link shows per-interval progress
    # to the straggler detector instead of one late burst.
    send_mod.FLOW_FRAGMENT_BYTES = 256 << 10
    cfg = CONFIGS["tiny"]
    v1 = serde.blobs_from_params(cfg, init_params(cfg, jax.random.key(0)))

    def blob_layer(data) -> LayerSrc:
        return LayerSrc(inmem_data=bytearray(data), data_size=len(data),
                        meta=LayerMeta(location=LayerLocation.INMEM,
                                       source_type=SourceType.MEM))

    replicas_ids = [1, 2]
    hot = 2                      # the slowserve-injected breacher
    bulk_dest, spare = 3, 4      # straggler-link dest; growable seat
    bulk_lid = 7000
    bulk = os.urandom(bulk_bytes)
    ids = [0, 1, 2, bulk_dest, spare]
    ts = {i: InmemTransport(str(i)) for i in ids + [9]}
    hot_spec = f"slowserve={hot_delay_ms:g}"
    _, hot_rules = rules_from_spec(hot_spec)
    ts[hot] = FaultyTransport(ts[hot], hot_rules, seed=7)
    slow_spec = f"slow={slow_rate}@{bulk_dest}"
    _, slow_rules = rules_from_spec(slow_spec)
    leader_t = FaultyTransport(ts[0], slow_rules, seed=7)
    seed_layers = {b: blob_layer(v1[b]) for b in v1}
    seed_layers[bulk_lid] = blob_layer(bulk)
    base = {r: {b: LayerMeta() for b in v1} for r in replicas_ids}
    leader = FlowRetransmitLeaderNode(
        Node(0, 0, leader_t), seed_layers, base,
        {i: bw for i in ids},
        expected_nodes={1, 2, bulk_dest, spare})
    rules = [
        {"Rule": "grow_on_serve_pressure", "P99Ms": p99_ms,
         "Sustain": 2, "CooldownS": 60.0},
        {"Rule": "quarantine_breacher", "P99Ms": p99_ms,
         "Breaches": 2, "CooldownS": 60.0},
        {"Rule": "replan_straggler", "FloorFrac": 0.1, "CooldownS": 5.0},
    ]
    leader.policy.arm(rules)
    replicas = {r: FlowRetransmitReceiverNode(Node(r, 0, ts[r]), {},
                                              boot_cfg=cfg)
                for r in replicas_ids}
    # Replica 1 also holds the bulk layer: the re-plan's alternative
    # source once the leader's own link to the dest is demoted.
    others = {
        bulk_dest: FlowRetransmitReceiverNode(Node(bulk_dest, 0,
                                                   ts[bulk_dest]), {}),
        spare: FlowRetransmitReceiverNode(Node(spare, 0, ts[spare]), {}),
    }
    requester = GenRequester(ts[9], my_id=9)
    prompt, max_new = [3, 5, 7], 8
    failures: list = []
    latencies: list = []         # (wall mono t, replica, ms)
    stop = threading.Event()

    def hammer(replica):
        # The request router honors the leader's serve-rotation mask —
        # exactly what the A/B split does in-process (docs/autonomy.md).
        while not stop.is_set():
            if replica in leader.serve_quarantined():
                time.sleep(0.1)
                continue
            t0 = time.monotonic()
            try:
                requester.request(replica, prompt, max_new,
                                  timeout=timeout)
                latencies.append((time.monotonic(), replica,
                                  (time.monotonic() - t0) * 1000.0))
            except Exception as e:  # noqa: BLE001 — any failure counts
                failures.append(repr(e))
            time.sleep(0.03)

    threads = [threading.Thread(target=hammer, args=(r,), daemon=True,
                                name=f"autonomy-hammer-{r}")
               for r in replicas_ids]
    try:
        for r in [*replicas.values(), *others.values()]:
            r.announce()
        leader.ready().get(timeout=timeout)
        leader.boot_ready().get(timeout=timeout)
        # Replica 1 gains the bulk layer out of band (an announce of
        # held state, like any member-held source) so the solver has a
        # second holder to route around the demoted leader link.
        replicas[1].layers[bulk_lid] = blob_layer(bulk)
        replicas[1].announce()
        for r in replicas_ids:  # warm the decode jits
            requester.request(r, prompt, max_new, timeout=timeout)
        for t in threads:
            t.start()
        t0 = time.monotonic()
        leader.submit_job("bulk", {bulk_dest: {bulk_lid: LayerMeta()}},
                          priority=1)
        deadline = time.monotonic() + timeout

        def audits(action, outcome=None):
            return [a for a in leader.policy.table()["Audit"]
                    if a.get("Action") == action
                    and (outcome is None or a.get("Outcome") == outcome)]

        if kill_switch:
            # The engine must SENSE both injections but HOLD: wait for
            # the held_manual audit trail instead of actions.
            while not (audits("quarantine", "held_manual")
                       and audits("replan", "held_manual")):
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"held_manual audits never appeared: "
                        f"{leader.policy.table()['Audit']}")
                time.sleep(0.05)
            time.sleep(0.6)  # more intervals: prove it KEEPS holding
            stop.set()
            for t in threads:
                t.join(timeout=timeout)
            counters = trace.counter_totals()
            tbl = leader.policy.table()
            fired = {a: counters.get(f"policy.action_{a}", 0)
                     for a in ("grow", "replan", "quarantine", "rehome")}
            return {
                "harness_hash": harness_hash(),
                "backend": "inmem",
                "mode": 3,
                "kill_switch": True,
                "env": "DLD_POLICY=0",
                "fault_specs": [hot_spec, slow_spec],
                "sensed_held_manual": {
                    "quarantine": len(audits("quarantine",
                                             "held_manual")),
                    "replan": len(audits("replan", "held_manual")),
                },
                "actions_fired": fired,
                "zero_actions": not any(fired.values()),
                "quarantined": sorted(leader.serve_quarantined()),
                "link_demotions": {f"{s}->{d}": b for (s, d), b
                                   in leader.policy.demotions().items()},
                "policy_jobs": sorted(
                    j for j in leader.jobs.table()
                    if str(j).startswith("policy-")),
                "engine_active": tbl["Active"],
                "request_failures": len(failures),
            }

        # ---- closed loop: wait for each autonomous action to land ----
        def wait_for(pred, what):
            while not pred():
                if time.monotonic() > deadline:
                    raise TimeoutError(f"autonomy never {what}: "
                                       f"{leader.policy.table()}")
                time.sleep(0.05)

        wait_for(lambda: hot in leader.serve_quarantined(),
                 "quarantined the breacher")
        t_quar = time.monotonic()
        wait_for(lambda: audits("replan"), "re-planned the straggler")

        def job_done(jid):
            job = leader.jobs.get(jid)
            return job is not None and job.state == "done"

        wait_for(lambda: job_done("bulk"), "finished the bulk transfer")
        bulk_wall = round(time.monotonic() - t0, 3)

        def grow_done():
            jids = [r.get("Job") for r in audits("grow") if r.get("Job")]
            return any(job_done(j) for j in jids)

        wait_for(grow_done, "grew the replica set")
        time.sleep(1.0)  # post-quarantine serving window for the SLO bar
        stop.set()
        for t in threads:
            t.join(timeout=timeout)
        # One more report round so every node's final span ring lands.
        leader.await_metrics(newer_than=time.monotonic() - 0.01,
                             timeout=5.0)
        counters = trace.counter_totals()
        tbl = leader.policy.table()
        table = leader.cluster_telemetry()
        rep = report_mod.build_from_leader(leader)
        policy_spans = sorted({e.get("span") for e in table["spans"]
                               if str(e.get("span", "")
                                      ).startswith("policy:")})
        grow_jobs = sorted({r.get("Job") for r in audits("grow")
                            if r.get("Job")})
        spare_layers = sorted(leader.status.get(spare) or {})
        straggler = [e for e in leader.health.events()
                     if e.get("kind") == "straggler_link"
                     and e.get("link") == f"0->{bulk_dest}"]
        post = sorted(ms for (t, r, ms) in latencies
                      if t > t_quar + 0.3 and r != hot)
        post_p99 = (round(post[min(len(post) - 1,
                                   int(0.99 * len(post)))], 1)
                    if post else None)
        return {
            "harness_hash": harness_hash(),
            "backend": "inmem",
            "mode": 3,
            "model": "tiny",
            "kill_switch": False,
            "rules": rules,
            "slo_p99_ms": p99_ms,
            "fault_specs": [hot_spec, slow_spec],
            "operator_verbs": 0,   # structural: no ctl message is sent
            "quarantined": sorted(leader.serve_quarantined()),
            "breacher_quarantined": hot in leader.serve_quarantined(),
            "wall_to_quarantine_s": round(t_quar - t0, 3),
            "straggler_flagged_live": bool(straggler),
            "straggler_frac": (straggler[0].get("frac")
                               if straggler else None),
            "link_demotions": {f"{s}->{d}": b for (s, d), b
                               in leader.policy.demotions().items()},
            "bulk_done_s": bulk_wall,
            "grow_jobs": grow_jobs,
            "spare_grown_layers": len(spare_layers),
            "spare_holds_model": all(
                b in spare_layers for b in v1),
            "post_quarantine_p99_ms": post_p99,
            "slo_reconverged": (post_p99 is not None
                                and post_p99 <= p99_ms),
            "request_failures": len(failures),
            "zero_failures": not failures,
            "requests_total": len(latencies),
            "actions_fired": {a: counters.get(f"policy.action_{a}", 0)
                              for a in ("grow", "replan",
                                        "quarantine", "rehome")},
            "audit_tail": tbl["Audit"][-8:],
            "policy_spans": policy_spans,
            "span_attributed": bool(policy_spans),
            "run_report": rep.get("provenance"),
        }
    finally:
        stop.set()
        requester.close()
        send_mod.FLOW_FRAGMENT_BYTES = prior_frag
        if prior_metrics is None:
            os.environ.pop("DLD_METRICS_INTERVAL_S", None)
        else:
            os.environ["DLD_METRICS_INTERVAL_S"] = prior_metrics
        if prior_policy is None:
            os.environ.pop("DLD_POLICY", None)
        else:
            os.environ["DLD_POLICY"] = prior_policy
        if prior_sustain is None:
            os.environ.pop("DLD_STRAGGLER_N", None)
        else:
            os.environ["DLD_STRAGGLER_N"] = prior_sustain
        _service_teardown(
            leader, [*replicas.values(), *others.values()], ts)
        leader_t.close()


def _autonomy_md(lines, results) -> None:
    au = results.get("autonomy")
    if not au or not au.get("closed_loop"):
        return
    cl, ks = au["closed_loop"], au.get("kill_switch") or {}
    bars = {
        "breaching replica quarantined (serve-rotation mask)":
            cl["breacher_quarantined"],
        "straggler link flagged live and re-planned around":
            cl["straggler_flagged_live"] and bool(cl["link_demotions"]),
        "replica set grown onto the spare (join+refill)":
            cl["spare_holds_model"],
        "fleet back inside SLO after quarantine":
            cl["slo_reconverged"],
        "zero operator verbs": cl["operator_verbs"] == 0,
        "zero dropped requests": cl["zero_failures"],
        "every action span-attributed in RUN_REPORT":
            cl["span_attributed"],
    }
    if ks:
        bars["DLD_POLICY=0 sibling: sensed but ZERO actions"] = (
            ks.get("zero_actions") and not ks.get("quarantined")
            and not ks.get("link_demotions")
            and not ks.get("policy_jobs"))
    lines += [
        "## Closed-loop fleet autonomy (docs/autonomy.md)",
        "",
        f"A serving fleet ({cl['backend']} backend, mode {cl['mode']}) "
        f"takes two concurrent injections — `{cl['fault_specs'][0]}` on "
        f"a hot replica and `{cl['fault_specs'][1]}` under a bulk "
        f"transfer — and the leader's policy engine converges it back "
        f"inside the p99 <= {cl['slo_p99_ms']:g}ms SLO with zero "
        f"operator verbs: quarantine after "
        f"{cl['wall_to_quarantine_s']}s, link demoted to "
        f"{cl['link_demotions']}, bulk done in {cl['bulk_done_s']}s, "
        f"post-quarantine p99 {cl['post_quarantine_p99_ms']}ms.",
        "",
        "| bar | met |",
        "|---|---|",
    ]
    for name, met in bars.items():
        lines.append(f"| {name} | {'MET' if met else 'NOT MET'} |")
    lines += [
        "",
        f"Actions fired: {cl['actions_fired']}; policy spans "
        f"{cl['policy_spans']}; {cl['requests_total']} requests served, "
        f"{cl['request_failures']} failed.  "
        + (f"Kill-switch sibling ({ks.get('env')}): held_manual audits "
           f"{ks.get('sensed_held_manual')}, actions fired "
           f"{ks.get('actions_fired')}.  " if ks else "")
        + f"Run report `{cl.get('run_report')}`.",
        "",
    ]


def _swap_md(lines, results) -> None:
    sw = results.get("live_swap")
    if not sw:
        return
    lines += [
        "## Zero-downtime weight swap (docs/swap.md)",
        "",
        f"A tiny-model replica serves generation traffic continuously "
        f"({sw['backend']} backend, mode {sw['mode']}) while a "
        "`kind=\"swap\"` job disseminates v2 under version-tagged ids "
        "and the epoch-fenced `SwapCommitMsg` flips the serving params "
        "atomically between requests — "
        f"**{sw['request_failures']} failed requests** "
        f"(bar: zero → {'MET' if sw['zero_failures'] else 'NOT MET'}), "
        f"v2 digests verified: {sw['v2_digests_verified']}, swap wall "
        f"{sw['swap_wall_s']}s:",
        "",
        "| phase | requests | tokens/s | p50 | p99 |",
        "|---|---|---|---|---|",
    ]
    for k in ("before", "during", "after"):
        ph = sw[k]
        if not ph.get("requests"):
            lines.append(f"| {k} | 0 | — | — | — |")
            continue
        lines.append(
            f"| {k} | {ph['requests']} | {ph['tokens_per_s']} | "
            f"{ph['p50_ms']}ms | {ph['p99_ms']}ms |")
    dip = sw.get("tokens_per_s_dip_frac")
    lines += [
        "",
        (f"tokens/s dip during the swap: {dip:+.1%} vs before "
         if dip is not None else "tokens/s dip: n/a ")
        + f"(served version after: `{sw['served_version_after']}`; "
        f"run report `{sw.get('run_report')}`).",
        "",
    ]


def run_telemetry_overhead(scale: int = 64 << 20, trials: int = 3,
                           scenario: str = "bench_8node_llama8b.json",
                           mode: int = 0,
                           timeout: float = 600.0) -> dict:
    """The always-on telemetry plane's measured cost (docs/
    observability.md acceptance): the same BASELINE scenario run with
    the flight recorder + periodic reports ON (default) and OFF
    (``DLD_TELEMETRY=0``), recorded as a TTD delta.  Medians over
    ``trials``; the target is ≤2% — read with this container's CFS
    drift error bar in mind (the markdown says so)."""
    out: dict = {"scenario": f"{os.path.splitext(scenario)[0]}"
                             f"@{scale >> 20}MiB",
                 "mode": mode, "trials": trials}
    with tempfile.TemporaryDirectory() as td:
        local = os.path.join(td, scenario)
        _localize_config(os.path.join(CONF_DIR, scenario), local,
                         scale_to=scale)
        for label, env_val in (("on", "1"), ("off", "0")):
            env = dict(os.environ)
            env["DLD_TELEMETRY"] = env_val
            ts = [run_once(local, mode, timeout, env=env)
                  for _ in range(trials)]
            out[label] = {"ttd_s": round(statistics.median(ts), 4),
                          "all": [round(t, 4) for t in ts]}
            print(f"telemetry {label}: TTD {out[label]['ttd_s']}s",
                  file=sys.stderr, flush=True)
    out["delta_frac"] = round(
        (out["on"]["ttd_s"] - out["off"]["ttd_s"])
        / max(out["off"]["ttd_s"], 1e-9), 4)
    out["meets_2pct"] = out["delta_frac"] <= 0.02
    return out


def run_attribution(layer_bytes: int = 8 << 20, n_fast: int = 2,
                    bw: int = 25_000_000, slow_rate: int = 2 << 20,
                    timeout: float = 300.0) -> dict:
    """The causal-observability acceptance row (docs/observability.md):
    a mode-3 multi-node run — leader 0 seeding ``n_fast`` fast dests
    plus one dest behind an injected ``slow=`` fault link — whose
    achieved TTD must be EXPLAINED: the critical-path span chain's
    window reconciles with the measured TTD within ±10%, the
    predicted-vs-achieved gap decomposes per phase with no unattributed
    residual above 15%, and the straggler link appears both in the LIVE
    health events (onset stamped mid-run) and in the RUN_REPORT
    critical path's per-link wire split."""
    from ..core.types import LayerMeta
    from ..transport.faults import FaultyTransport, rules_from_spec
    from ..utils import critical_path as cp
    from ..utils import telemetry
    from ..utils.provenance import harness_hash
    from . import report as report_mod
    from ..runtime import (
        FlowRetransmitLeaderNode,
        FlowRetransmitReceiverNode,
        Node,
    )
    from ..runtime import send as send_mod
    from ..transport import TcpTransport

    telemetry.reset_run()
    slow_dest = n_fast + 1
    ids = list(range(n_fast + 2))
    # Small flow fragments so the throttled link trickles per-interval
    # progress (the straggler detector judges interval deltas) instead
    # of landing one late burst.
    prior_frag = send_mod.FLOW_FRAGMENT_BYTES
    prior_interval = os.environ.get("DLD_METRICS_INTERVAL_S")
    send_mod.FLOW_FRAGMENT_BYTES = 256 << 10
    os.environ["DLD_METRICS_INTERVAL_S"] = "0.25"
    block = os.urandom(1 << 20)

    def mem_layer(lid: int):
        from ..core.types import (
            LayerLocation,
            LayerSrc,
            SourceType,
        )

        reps = (layer_bytes + len(block) - 1) // len(block)
        data = bytearray((block * reps)[:layer_bytes])
        data[:8] = lid.to_bytes(8, "big")
        return LayerSrc(inmem_data=data, data_size=layer_bytes,
                        meta=LayerMeta(location=LayerLocation.INMEM,
                                       source_type=SourceType.MEM))

    ts = {i: TcpTransport("127.0.0.1:0") for i in ids}
    reg = {i: t.get_address() for i, t in ts.items()}
    for t in ts.values():
        t.addr_registry.update(reg)
    _, rules = rules_from_spec(f"slow={slow_rate}@{slow_dest}")
    leader_t = FaultyTransport(ts[0], rules, seed=11)
    assignment = {d: {lid: LayerMeta() for lid in range(2)}
                  for d in range(1, n_fast + 1)}
    assignment[slow_dest] = {0: LayerMeta()}
    leader = FlowRetransmitLeaderNode(
        Node(0, 0, leader_t), {lid: mem_layer(lid) for lid in range(2)},
        assignment, {i: bw for i in ids}, expected_nodes=set(ids[1:]))
    dests = [FlowRetransmitReceiverNode(Node(i, 0, ts[i]), {})
             for i in ids[1:]]
    try:
        t0 = time.monotonic()
        for r in dests:
            r.announce()
        leader.ready().get(timeout=timeout)
        ttd = round(time.monotonic() - t0, 4)
        predicted = (leader.predicted_ttd_ms or 0) / 1000.0
        # One more report round so every dest's final span ring lands.
        leader.await_metrics(newer_than=time.monotonic() - 0.01,
                             timeout=5.0)
        table = leader.cluster_telemetry()
        res = cp.analyze(table["spans"], ttd_s=ttd,
                         predicted_s=round(predicted, 4))
        rep = report_mod.build_from_leader(leader, ttd_s=ttd)
        health_events = leader.health.events()
        straggler = [e for e in health_events
                     if e.get("kind") == "straggler_link"
                     and e.get("link") == f"0->{slow_dest}"]
        slow_on_chain = any(c.get("dest") == slow_dest
                            for c in res["chain"])
        slow_in_links = f"0->{slow_dest}" in res["per_link_wire_s"]
        coverage = res.get("coverage_frac") or 0.0
        unattrib = res.get("unattributed_frac")
        return {
            "harness_hash": harness_hash(),
            "backend": "tcp-loopback",
            "mode": 3,
            "layer_bytes": layer_bytes,
            "n_dests": n_fast + 1,
            "modeled_bw_bps": bw,
            "slow_link": {"link": f"0->{slow_dest}",
                          "injected_rate_bps": slow_rate},
            "ttd_s": ttd,
            "predicted_s": round(predicted, 4),
            "critical_path": {
                "window_s": res["window_s"],
                "coverage_frac": coverage,
                "attributed_s": res["attributed_s"],
                "idle_s": res["idle_s"],
                "unattributed_frac": unattrib,
                "phase_totals_s": res["phase_totals_s"],
                "gap_attribution_s": res.get("gap_attribution_s"),
                "per_link_wire_s": res["per_link_wire_s"],
                "chain_spans": [c["span"] for c in res["chain"]],
            },
            "reconciles_10pct": bool(abs(coverage - 1.0) <= 0.10),
            "unattributed_le_15pct": bool(
                unattrib is not None and unattrib <= 0.15),
            "straggler_flagged_live": bool(straggler),
            "straggler_onset_t_ms": (straggler[0]["t_ms"]
                                     if straggler else None),
            "straggler_on_critical_path": bool(slow_on_chain
                                               and slow_in_links),
            "health_events": health_events,
            # Dual-backend span correlation + takeover survival are
            # tier-1-tested; the row names the tests it leans on.
            "span_correlation_tests":
                "tests/test_observability.py::"
                "test_span_chain_full_lifecycle_e2e[inmem|tcp]",
            "takeover_tests":
                "tests/test_observability.py::"
                "test_adopted_leader_still_yields_complete_report + "
                "test_health_events_and_spans_ride_shadow_replication",
            "run_report": rep.get("provenance"),
        }
    finally:
        send_mod.FLOW_FRAGMENT_BYTES = prior_frag
        if prior_interval is None:
            os.environ.pop("DLD_METRICS_INTERVAL_S", None)
        else:
            os.environ["DLD_METRICS_INTERVAL_S"] = prior_interval
        leader.close()
        for r in dests:
            r.close()
        for t in ts.values():
            t.close()
        leader_t.close()


def _attribution_md(lines, results) -> None:
    at = results.get("attribution")
    if not at:
        return
    cp_res = at["critical_path"]
    phases = ", ".join(f"{k}={v}s"
                       for k, v in sorted(cp_res["phase_totals_s"].items()))
    gap = ", ".join(f"{k}={v}s"
                    for k, v in sorted(
                        (cp_res.get("gap_attribution_s") or {}).items()))
    lines += [
        "## Explainable delivery: critical-path TTD attribution "
        "(docs/observability.md)",
        "",
        f"Mode-3 over loopback TCP: leader 0 seeds {at['n_dests']} "
        f"dests ({at['layer_bytes'] >> 20} MiB layers, modeled "
        f"{at['modeled_bw_bps'] / 1e6:.0f} MB/s links); link "
        f"`{at['slow_link']['link']}` is injected "
        f"`slow={at['slow_link']['injected_rate_bps']}` "
        f"({at['slow_link']['injected_rate_bps'] >> 20} MiB/s) — the "
        "run's whole question is whether the observability plane "
        "EXPLAINS the resulting TTD without being told about the "
        "fault.",
        "",
        "| bar | value | met |",
        "|---|---|---|",
        f"| chain window vs achieved TTD (±10%) | "
        f"{cp_res['window_s']}s vs {at['ttd_s']}s "
        f"(coverage {cp_res['coverage_frac']}) | "
        f"{'yes' if at['reconciles_10pct'] else 'NO'} |",
        f"| unattributed residual ≤15% | "
        f"{cp_res['unattributed_frac']} | "
        f"{'yes' if at['unattributed_le_15pct'] else 'NO'} |",
        f"| straggler flagged LIVE (health event, onset mid-run) | "
        f"onset t={at['straggler_onset_t_ms']}ms | "
        f"{'yes' if at['straggler_flagged_live'] else 'NO'} |",
        f"| straggler on the RUN_REPORT critical path | chain spans "
        f"{cp_res['chain_spans']} | "
        f"{'yes' if at['straggler_on_critical_path'] else 'NO'} |",
        "",
        f"Predicted {at['predicted_s']}s vs achieved {at['ttd_s']}s "
        f"— phase totals on the chain: {phases}.  Gap decomposition: "
        f"{gap}.  Per-link wire seconds: "
        + ", ".join(f"{k}: {v}s"
                    for k, v in sorted(
                        cp_res["per_link_wire_s"].items()))
        + " — the injected link carries the excess, as it must.",
        "",
        f"Dual-backend span correlation: {at['span_correlation_tests']} "
        f"(tier-1).  Leader-kill keeping span/health state through "
        f"takeover: {at['takeover_tests']} (tier-1).  RUN_REPORT "
        f"provenance `{at.get('run_report')}` (harness "
        f"`{at.get('harness_hash')}`).",
        "",
    ]


def run_span_overhead(scale: int = 64 << 20, trials: int = 3,
                      scenario: str = "bench_8node_llama8b.json",
                      mode: int = 0,
                      timeout: float = 600.0) -> dict:
    """The span recorder's measured cost (docs/observability.md): the
    same BASELINE scenario with span recording ON (default) vs OFF
    (``DLD_SPANS=0``) — the PR-6 telemetry-overhead A/B, but with the
    arms INTERLEAVED (on, off, on, off, …): this container's CFS state
    drifts 30-50% across minutes (measured: a sequential-arm run read
    +45% that an off/on/off interleave immediately contradicted), so
    sequential arms measure the drift, not the knob; adjacent pairs
    largely cancel it.  Medians per arm + per-pair deltas recorded."""
    import subprocess as _sp

    out: dict = {"scenario": f"{os.path.splitext(scenario)[0]}"
                             f"@{scale >> 20}MiB",
                 "mode": mode, "trials": trials, "retries": 0,
                 "interleaved": True}
    with tempfile.TemporaryDirectory() as td:
        local = os.path.join(td, scenario)
        _localize_config(os.path.join(CONF_DIR, scenario), local,
                         scale_to=scale)

        def one_trial(env) -> float:
            # This container sporadically wedges ONE seat of an 8-node
            # run in its post-run ack-requeue loop (pre-existing;
            # reproduced on the unmodified tree) — a hung HARNESS trial
            # is not a measurement, so it retries bounded and counted,
            # never silently.
            for attempt in range(3):
                try:
                    return run_once(local, mode, timeout, env=env)
                except _sp.TimeoutExpired:
                    out["retries"] += 1
                    print("trial wedged in the known post-run requeue "
                          "loop; retrying", file=sys.stderr, flush=True)
            raise TimeoutError("span-overhead trial wedged 3x")

        arms: dict = {"on": [], "off": []}
        for k in range(trials):
            for label, env_val in (("on", "1"), ("off", "0")):
                env = dict(os.environ)
                env["DLD_SPANS"] = env_val
                t = one_trial(env)
                arms[label].append(t)
                print(f"spans {label} trial {k}: TTD {t:.3f}s",
                      file=sys.stderr, flush=True)
        for label, ts in arms.items():
            out[label] = {"ttd_s": round(statistics.median(ts), 4),
                          "all": [round(t, 4) for t in ts]}
    out["delta_frac"] = round(
        (out["on"]["ttd_s"] - out["off"]["ttd_s"])
        / max(out["off"]["ttd_s"], 1e-9), 4)
    # Per-pair deltas: each pair is two adjacent same-minute runs —
    # the drift-cancelling view the markdown reports next to the
    # arm medians.
    out["pair_deltas"] = [
        round((a - b) / max(b, 1e-9), 4)
        for a, b in zip(arms["on"], arms["off"])]
    return out


def _span_overhead_md(lines, results) -> None:
    ov = results.get("span_overhead")
    if not ov:
        return
    spread_on = ov["on"]["all"]
    spread = round((max(spread_on) - min(spread_on))
                   / max(min(spread_on), 1e-9), 3)
    pairs = ov.get("pair_deltas") or []
    pair_str = (", ".join(f"{p:+.1%}" for p in pairs)
                if pairs else "—")
    lines += [
        "## Span-recording overhead (docs/observability.md)",
        "",
        f"The `{ov['scenario']}` BASELINE scenario (mode {ov['mode']}, "
        f"{ov['trials']} trial pairs, arms INTERLEAVED on/off/on/off — "
        "this container's CFS state drifts 30-50% across minutes, so "
        "sequential arms measure the drift, not the knob) with "
        "pair-lifecycle span recording ON vs OFF (`DLD_SPANS=0`).  The "
        "hot path is one bounded-deque append under the registry lock "
        "per LIFECYCLE EDGE (a handful per delivered layer — not per "
        "frame), so the expected cost is below this host's noise "
        "floor:",
        "",
        "| spans | TTD (median) | trials | arm delta |",
        "|---|---|---|---|",
        f"| on | {ov['on']['ttd_s']}s | {ov['on']['all']} | "
        f"{ov['delta_frac']:+.1%} |",
        f"| off (`DLD_SPANS=0`) | {ov['off']['ttd_s']}s | "
        f"{ov['off']['all']} | — |",
        "",
        f"Per-pair (adjacent-run) deltas: {pair_str}.  "
        f"(on-arm trial spread: {spread:.1%} of the fastest trial"
        + (f"; {ov['retries']} wedged trial(s) retried — the known "
           "pre-existing post-run requeue flake, reproduced on the "
           "unmodified tree" if ov.get("retries") else "")
        + ".  A delta inside the spread — either sign — is "
        "indistinguishable from zero on this 2-core CFS-throttled "
        "container; re-measure on quiet multi-core hardware for a "
        "tight number.)",
        "",
    ]


def _telemetry_overhead_md(lines, results) -> None:
    ov = results.get("telemetry_overhead")
    if not ov:
        return
    spread_on = ov["on"]["all"]
    spread = round((max(spread_on) - min(spread_on))
                   / max(min(spread_on), 1e-9), 3)
    lines += [
        "## Always-on telemetry overhead (docs/observability.md)",
        "",
        f"The `{ov['scenario']}` BASELINE scenario (mode {ov['mode']}, "
        f"median of {ov['trials']}) with the per-link flight recorder + "
        "periodic MetricsReportMsg shipping ON vs OFF "
        "(`DLD_TELEMETRY=0`).  The instrumented hot path is one dict "
        "update under a lock per MiB-scale frame; the ≤2% acceptance "
        "bar is judged on the TTD delta below, read against this "
        "container's run-to-run CFS drift (the ON-arm trial spread is "
        "the error bar):",
        "",
        "| telemetry | TTD | trials | delta | ≤2%? |",
        "|---|---|---|---|---|",
        f"| on | {ov['on']['ttd_s']}s | {ov['on']['all']} | "
        f"{ov['delta_frac']:+.1%} | "
        f"{'yes' if ov['meets_2pct'] else 'NO'} |",
        f"| off (`DLD_TELEMETRY=0`) | {ov['off']['ttd_s']}s | "
        f"{ov['off']['all']} | — | — |",
        "",
        f"(on-arm trial spread: {spread:.1%} of the fastest trial.)",
        "",
    ]
    if ov["delta_frac"] < -0.02:
        lines += [
            "A negative delta this large is NOT telemetry making the "
            "run faster — it is the container's CFS burst-budget drift "
            "dwarfing the effect under measurement (the per-arm trial "
            "spreads above are of the same order).  The honest "
            "conclusion is: the overhead is indistinguishable from "
            "zero at this host's noise floor, which satisfies the ≤2% "
            "bar; re-measure on quiet multi-core hardware for a tight "
            "number.",
            "",
        ]


def _service_md(lines, results) -> None:
    sj = results.get("service_jobs")
    dr = results.get("delta_rollout")
    if not sj and not dr:
        return
    lines.append("## Dissemination service: multi-job scheduling + "
                 "content-addressed delta rollouts")
    lines.append("")
    if sj:
        lines.append(
            "Two overlapping jobs, different priorities, one shared "
            f"source NIC modeled at {sj['modeled_bw_bps'] / 1e6:.0f} "
            "MB/s (docs/service.md): the joint solver gives the high "
            "tier the full link and the low tier the 1/16 preemption-"
            "floor residue; the per-job link rows and completion walls "
            "are the split actually achieved.")
        lines.append("")
        lines.append("| job | priority | intended tier budget | "
                     "measured completion | delivered (per-job link "
                     "rows) | byte-exact |")
        lines.append("|---|---|---|---|---|---|")
        for jid in sorted(sj["jobs"]):
            prio = sj["jobs"][jid]["priority"]
            t_int = sj["intended_tier_ms"].get(jid)
            t_meas = sj["measured_done_s"].get(jid)
            delivered = sum(
                row.get("delivered_bytes", 0)
                for key, row in sj["per_job_links"].items()
                if key.endswith(f"#{jid}"))
            lines.append(
                f"| `{jid}` | {prio} | "
                f"{t_int / 1000.0 if t_int else '?'}s | {t_meas}s | "
                f"{delivered >> 20} MiB | {sj['byte_exact']} |")
        lines.append("")
        lines.append(f"RUN_REPORT provenance `{sj.get('run_report')}` "
                     f"(harness `{sj.get('harness_hash')}`).")
        lines.append("")
    if dr:
        frac = dr["changed_fraction"]
        lines.append(
            f"Delta rollout: v2 re-keys {dr['n_layers']} × "
            f"{dr['layer_bytes'] >> 20} MiB layers under new ids with "
            f"{dr['changed_layers']} small-perturbation sibling(s) "
            f"(~1/{dr.get('perturb_stride', '?')} of positions "
            f"flipped; changed fraction {frac}).  The content store "
            "resolves unchanged layers locally (zero wire bytes), and "
            "the changed layers ship as encoded `delta:<v1-digest>` "
            "streams (docs/codec.md) the dest reconstructs and "
            "verifies against the stamped full-form digest — so the "
            "wire bound tightens from changed-fraction × model bytes "
            "to < 25% of even the CHANGED layers' raw size.")
        lines.append("")
        lines.append("| push | wall | wire bytes | bound | met |")
        lines.append("|---|---|---|---|---|")
        lines.append(f"| v1 full | {dr['v1_full_push_s']}s | "
                     f"{dr['v1_wire_bytes'] >> 20} MiB | — | — |")
        lines.append(
            f"| v2 delta | {dr['v2_delta_push_s']}s | "
            f"{dr['v2_wire_bytes'] / 1048576:.2f} MiB | ≤ "
            f"{dr['v2_bound_bytes'] >> 20} MiB raw / ≤ "
            f"{dr.get('delta_bound_bytes', 0) / 1048576:.1f} MiB delta "
            f"| {dr['bound_met']} / "
            f"{dr.get('delta_bound_met', '—')} |")
        lines.append("")
        lines.append(
            f"{dr['resolved_layers']} layers "
            f"({dr['resolved_bytes'] >> 20} MiB) resolved from the "
            f"dest's content store with zero wire bytes; the leader's "
            f"planner skipped {dr['leader_skipped']} content-equal "
            f"pair(s); {dr.get('delta_pairs_chosen', 0)} pair(s) "
            f"shipped as deltas ({dr.get('delta_wire_bytes', 0)} wire "
            f"bytes reconstructing {dr.get('delta_raw_bytes', 0)} raw "
            f"bytes), XOR+DLE1 encode cost "
            f"{dr.get('encode_ms', 0)} ms thread-time (a ceiling on "
            "this CFS-throttled container, cached once per layer).  "
            f"Digest-exact: {dr.get('digest_exact', False)}.  "
            f"RUN_REPORT provenance `{dr.get('run_report')}` "
            f"(harness `{dr.get('harness_hash')}`).")
        lines.append("")
    dw = results.get("delta_wave")
    if dw:
        grp = dw["group"]
        lines.append(
            f"Sharded delta rollout wave (docs/rollout.md × "
            f"docs/hierarchy.md × docs/codec.md): root 0 seeds "
            f"{dw['n_layers']} × {dw['layer_bytes'] >> 20} MiB v1 "
            f"layers to group {{sub-leader {grp['leader']}, members "
            f"{grp['members']}}} through the group plan, then rolls "
            f"{dw['changed_layers']} perturbed v2 layer(s) "
            f"(version `{dw['version']}`) in "
            f"{len(dw['waves'])} waves — every v2 pair an encoded "
            "delta stream, wave 2 re-encoded and fanned out by the "
            "SUB-LEADER (striped byte ranges of one delta blob through "
            "the group chain), not the root.")
        lines.append("")
        lines.append("| wave | dests | wall | root wire bytes |")
        lines.append("|---|---|---|---|")
        for i, w in enumerate(dw["waves"]):
            lines.append(
                f"| {i + 1} | {w['dests']} | {w['wall_s']}s | "
                f"{w['root_wire_bytes']} |")
        lines.append("")
        lines.append(
            f"v1 group push: {dw['v1_group_push_s']}s, "
            f"{dw['v1_root_wire_bytes'] >> 20} MiB over the root NIC.  "
            f"v2 waves: {dw['wave_wire_bytes']} root wire bytes total "
            f"vs {dw['changed_raw_bytes'] >> 20} MiB changed-raw "
            f"(< 25% bound met: {dw['delta_bound_met']}); "
            f"{dw['delta_pairs_chosen']} delta pair(s) chosen, "
            f"{dw['delta_reconstructed']} reconstruction(s), group-"
            f"internal wire {dw['group_wire_bytes']} bytes.  Byte-"
            f"exact {dw['byte_exact']}, digest-exact "
            f"{dw['digest_exact']}, version tags preserved.")
        lines.append("")


def _failover_md(lines, results) -> None:
    fo = results.get("failover")
    if not fo:
        return
    lines.append("## Failover: time-to-recover (leader killed mid-run)")
    lines.append("")
    lines.append(
        "Control-plane HA (docs/failover.md) at physical-row sizes: a "
        "clean HA-armed mode-3 run vs an identical run whose leader is "
        f"killed at ~{fo['killed'].get('kill_at_s', '?')}s.  TTR = kill "
        "→ delivery resumed to byte-exact completion (includes the "
        f"standby's ~{fo['standby_expiry_s']}s lease-expiry wait — the "
        "detection time IS part of recovery); overhead = killed total "
        "− clean total.")
    lines.append("")
    lines.append("| run | layers | total | kill at | TTR | "
                 "detect+promote | byte-exact |")
    lines.append("|---|---|---|---|---|---|---|")
    size = f"{fo['n_workers']}× {fo['layer_bytes'] >> 20} MiB"
    c, k = fo["clean"], fo["killed"]
    lines.append(f"| clean | {size} | {c['total_s']}s | — | — | — | "
                 f"{c['byte_exact']} |")
    lines.append(
        f"| leader killed | {size} | {k['total_s']}s | "
        f"{k['kill_at_s']}s | {k['ttr_s']}s | {k['takeover_s']}s | "
        f"{k['byte_exact']} |")
    lines.append("")
    if fo["killed"].get("run_report"):
        lines.append(
            "Event counts for both rows come from each run's own "
            "telemetry snapshot; the killed run's RUN_REPORT was built "
            "from the ADOPTED leader (provenance "
            f"`{fo['killed']['run_report']}`, "
            f"{fo['killed'].get('report_links', '?')} link rows — the "
            "replicated cluster picture surviving the takeover is part "
            "of what this row evidences).")
        lines.append("")
    lines.append(
        f"Failover overhead vs clean: **{fo['overhead_s']}s** "
        f"(lease interval {fo['lease_interval_s']}s, standby expiry "
        f"{fo['standby_expiry_s']}s; `harness_hash` "
        f"{fo['harness_hash']}).  `detect+promote` spans kill → "
        "promoted leader live, dominated by the DELIBERATE lease-expiry "
        "wait (the adoption itself — shadow import + epoch bump + "
        "re-plan dispatch — logs as takeover_ms, tens of ms); the rest "
        "of TTR is re-sending what the dead leader had not delivered "
        "(the promoted leader re-drives from the shadow immediately; "
        "worker re-announces then re-ack what already landed, and "
        "duplicate sends are absorbed by interval reassembly).")
    lines.append("")


def _fanout_md(lines, results) -> None:
    fo = results.get("fanout")
    if not fo:
        return
    lines += [
        "## Fleet fan-out: flat vs hierarchical control "
        "(docs/hierarchy.md)",
        "",
        f"The same inmem BASELINE goal — every dest wants "
        f"{fo['n_layers']} × {fo['layer_bytes'] >> 10} KiB layers from "
        "the one seeding root — run flat (mode 3) and under "
        "sqrt-sized sub-leader groups, at each fleet size.  "
        "`root handled` counts control messages the ROOT's message "
        "loop dispatched (`ctrl.handled.<root>`); every run is "
        "byte-exact at every dest.",
        "",
        "| nodes | control | groups | root solve (ms) | root handled "
        "msgs | sub egress/sub | relayed | links exact | TTD |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in fo["rows"]:
        if r.get("groups"):
            egress = f"{r.get('egress_bytes_per_subleader', 0) >> 10} KiB"
            relay = f"{r.get('relay_bytes', 0) >> 10} KiB"
        else:
            egress = relay = "—"
        lines.append(
            f"| {r['n_nodes']} | {r['control']} | {r['groups'] or '—'} "
            f"| {r['solve_ms']} | {r['root_handled_msgs']} | "
            f"{egress} | {relay} | "
            f"{'yes' if r.get('link_reconcile_exact') else 'NO'} | "
            f"{r['ttd_s']}s |")
    mg, sg = fo["root_msgs_growth"], fo["solve_growth"]
    lines += [
        "",
        f"Growth {fo['rows'][0]['n_nodes']}→"
        f"{fo['rows'][-1]['n_nodes']} nodes (×{fo['node_growth']:.0f} "
        f"fleet): root-handled messages ×{mg['flat']} flat vs "
        f"×{mg['hierarchical']} hierarchical; solve wall "
        f"×{sg['flat']} flat vs ×{sg['hierarchical']} hierarchical.  "
        f"Sub-linear bars: messages "
        f"**{'MET' if fo['msgs_sublinear'] else 'NOT MET'}**, solve "
        f"**{'MET' if fo['solve_sublinear'] else 'NOT MET'}**.",
        "",
    ]
    ce = fo.get("chain_egress")
    if ce:
        lines += [
            f"Member-to-member chains (docs/hierarchy.md): at "
            f"{fo['rows'][-1]['n_nodes']} nodes each sub-leader "
            f"egressed {ce['egress_bytes_per_subleader'] >> 10} KiB "
            f"(~one model copy) instead of the star's one-copy-per-"
            f"member — {ce['subleader_egress_bytes'] >> 10} KiB total "
            f"vs {ce['star_equivalent_bytes'] >> 10} KiB star-"
            f"equivalent, a {ce['egress_savings_frac']:.0%} egress "
            f"saving; of each R-member group's fan, (R−1)/R rides "
            f"member-to-member relay links "
            f"({ce['relay_bytes'] >> 10} KiB relayed).  Link tables "
            f"reconcile byte-exactly across every hop: "
            f"**{'yes' if fo.get('links_reconcile_exact') else 'NO'}**.",
            "",
        ]
    lines += [
        "Honest framing: TTD at these sizes is dominated by the "
        "2-core container's scheduler, not the wire; the row's bars "
        "are the CONTROL-plane costs (solve wall, root-handled "
        "messages) and the egress/relay BYTE counts, which are "
        "load-independent — every seat shares one CFS quota, so "
        "relaying off the sub's NIC shows up here as bytes moved off "
        "the bottleneck link, not as wall-clock TTD wins.",
        "",
    ]


def _elasticity_md(lines, results) -> None:
    el = results.get("elasticity")
    if not el:
        return
    lines += [
        "## Elastic membership: join mid-run, refill from the swarm "
        "(docs/membership.md)",
        "",
        f"The base goal ({el['n_base']} configured dests × "
        f"{el['n_layers']} × {el['layer_bytes'] >> 10} KiB layers from "
        "ONE origin seeder) disseminates; then N UNCONFIGURED nodes "
        "JOIN the running cluster concurrently.  Each joiner is "
        "admitted as a dest immediately (a `kind=\"join\"` refill job) "
        "and the refill policy avoids the ORIGIN seeder whenever "
        "current peer holders can serve — admission cost must not "
        "scale with origin bandwidth.  Every joiner ends byte-exact "
        "(digest-verified before acking, default integrity plane).",
        "",
        "| joiners | origin refill bytes | peer refill bytes | peer "
        "fraction | coverage | RUN_REPORT |",
        "|---|---|---|---|---|---|",
    ]
    for r in el["rows"]:
        lines.append(
            f"| {r['n_joiners']} | {r['origin_bytes']} | "
            f"{r['peer_bytes']} | {r['peer_fraction']} | "
            f"{r['coverage_s']}s | {str(r.get('run_report'))[:12]} |")
    lines += [
        "",
        f"Joiner growth ×{el['joiner_growth']:.0f} → origin-bytes "
        f"growth ×{el['origin_growth']}.  Bars: peers-majority "
        f"**{'MET' if el['peers_majority'] else 'NOT MET'}**, "
        f"origin-bytes sub-linear "
        f"**{'MET' if el['origin_sublinear'] else 'NOT MET'}**.",
        "",
        "Honest framing: joiners here arrive AFTER the base goal "
        "covered the configured dests (the service-era steady state), "
        "so peers hold every layer and the origin serves zero refill "
        "bytes; a joiner arriving before any peer holds a layer is "
        "served by the origin — the avoid set is advisory and "
        "deliverability always wins (docs/membership.md).",
        "",
    ]


def _sharded_md(lines, results) -> None:
    sd = results.get("sharded_delivery")
    if not sd:
        return
    lb, nl = sd["layer_bytes"], sd["n_layers"]
    full, shard = sd["full"], sd["sharded"]
    lo, hi = sd["shard_bytes_per_dest_bound"]
    lines += [
        "## Sharded delivery: disseminate into the destination sharding "
        "(docs/sharding.md)",
        "",
        f"The same multi-dest goal — {sd['n_dests']} dests × {nl} × "
        f"{lb >> 20} MiB layers from one leader over "
        f"{sd['backend']} (mode {sd['mode']}) — run with FULL-layer "
        f"targets vs `{sd['shard_fraction']}@k` shard targets.  Wire "
        "bytes per dest must land within 10% of fraction × layer bytes "
        f"× layers (bound [{lo >> 20}, {hi >> 20}] MiB); the dests' "
        "shards must gather on-mesh into layers byte-exact against the "
        "stamped full-layer digests.",
        "",
        "| targets | TTD | predicted | wire bytes/dest | gathered "
        "byte-exact |",
        "|---|---|---|---|---|",
    ]

    def _per_dest(rec):
        vals = sorted(d["rx_bytes"]
                      for d in rec["wire_bytes_per_dest"].values())
        return f"{vals[0] >> 20}–{vals[-1] >> 20} MiB"

    lines.append(f"| full layers | {full['ttd_s']}s | "
                 f"{full['predicted_s']}s | {_per_dest(full)} | — |")
    lines.append(
        f"| `{sd['shard_fraction']}` shards | {shard['ttd_s']}s | "
        f"{shard['predicted_s']}s | {_per_dest(shard)} | "
        f"{shard.get('gathered_layers_byte_exact', 0)}/{nl} layers |")
    lines += [
        "",
        f"Wire-bytes-per-dest within 10% of the fraction: "
        f"**{'yes' if sd['wire_within_10pct'] else 'NO'}**; TTD ratio "
        f"sharded/full = {sd['ttd_ratio']} (the proportional-improvement "
        "check — on this 2-core container the CPU, not the modeled "
        "link, can bound small runs; read against the trial spread).  "
        f"RUN_REPORT provenance full `{full.get('run_report')}`, "
        f"sharded `{shard.get('run_report')}` "
        f"(harness `{sd.get('harness_hash')}`).",
        "",
    ]


def _fabric_delivery_md(lines, results) -> None:
    fd = results.get("fabric_delivery")
    if not fd:
        return
    host, fab = fd["host_path"], fd["fabric_assisted"]
    mb = fd["model_bytes"]
    lo, hi = fd["pod_wire_bound"]
    n_trees = fd["replicas"] * fd["n_layers"]
    lines += [
        "## Fabric-assisted pod delivery: 1/N per host over the NIC, "
        "the rest over ICI (docs/fabric.md)",
        "",
        f"The same topology — {fd['replicas']} replica dests × "
        f"{fd['n_layers']} × {fd['layer_bytes'] >> 20} MiB layers from "
        f"one leader over {fd['backend']} (mode {fd['mode']}) — run "
        "HOST-PATH (every replica pulls every full layer: pod NIC "
        "ingress = model_bytes × replicas) vs FABRIC-ASSISTED (the "
        "leader pod-plans one `1/R@k` shard per host; the full tree "
        "materializes over the on-mesh gather, digest-checked against "
        "the leader's stamped full-layer digest).",
        "",
        "| path | TTD | predicted | pod NIC wire bytes | trees "
        "digest-exact |",
        "|---|---|---|---|---|",
        f"| host (full × R) | {host['ttd_s']}s | {host['predicted_s']}s "
        f"| {host['pod_nic_wire_bytes'] >> 20} MiB | "
        f"{host['trees_digest_exact']}/{n_trees} |",
        f"| fabric-assisted | {fab['ttd_s']}s | {fab['predicted_s']}s "
        f"| {fab['pod_nic_wire_bytes'] >> 20} MiB | "
        f"{fab['trees_digest_exact']}/{n_trees} |",
        "",
        f"Pod NIC ingress ≈ model_bytes ({mb >> 20} MiB; bound "
        f"[{lo >> 20}, {hi >> 20}] MiB): "
        f"**{'MET' if fd['pod_wire_within_10pct'] else 'NOT MET'}** — "
        f"wire ratio fabric/host = {fd['wire_ratio_vs_host']} "
        f"(ideal 1/R = {round(1 / fd['replicas'], 4)}), delivered "
        "shard bytes byte-exact against the link-table reconcile: "
        f"**{'yes' if fd['pod_delivered_exact'] else 'NO'}**.  TTD "
        f"ratio fabric/host = {fd['ttd_ratio_vs_host']} (the CFS "
        "caveat of the PR 6 precedent applies: on this 2-core "
        "container the gather's host-side CPU work shares cores with "
        "the TCP stack, so wall-clock gains understate a real pod, "
        "where the modeled NIC — not CPU — is the bottleneck and the "
        "gather rides ICI).  RUN_REPORT provenance host "
        f"`{host.get('run_report')}`, fabric `{fab.get('run_report')}` "
        f"(harness `{fd.get('harness_hash')}`).",
        "",
    ]


def to_markdown(results: dict) -> str:
    lines = [
        "# TTD matrix",
        "",
        "Time-to-deliver (median of "
        f"{results['trials']} runs). TCP scenarios run the real CLI over "
        "loopback, one process per node; the pod_fabric scenario runs "
        "cli.podrun on a virtual 8-device mesh with layer bytes on the "
        "device plane (zero TCP layer bytes); the spmd_fabric scenario "
        "runs the per-node CLI as TWO real OS processes joined into one "
        "jax.distributed runtime, layer bytes as lockstep collectives "
        "(gloo on CPU — the absolute number is dominated by per-plan "
        "compile+collective latency, not bandwidth); the dcn_2slice "
        "scenario keeps Mesh.Slices/DcnBW so mode 3 runs the topology-"
        "aware solve — attribution-first on the native Dinic (round 5), "
        "so the common case never touches scipy and the solve costs "
        "~10 ms cold. North-star secondary "
        "target: mode 1 ≈ mode 0 — note that at loopback-scaled layer "
        "sizes fixed per-transfer overhead (connection setup, protocol "
        "round-trips) dominates both numbers, so ratios within ~1.5x "
        "meet the target; at physical sizes the bandwidth term dominates "
        "and the ratio tightens toward 1.",
        "",
        "| scenario | mode 0 | mode 1 | mode 2 | mode 3 | mode1/mode0 |",
        "|---|---|---|---|---|---|",
    ]
    for name, per_mode in results["scenarios"].items():
        row = [name]
        for m in ("0", "1", "2", "3"):
            if m not in per_mode:
                row.append("—")
                continue
            cell = f"{per_mode[m]['ttd_s']}s"
            if m == "3" and "predicted_s" in per_mode[m]:
                # Plan fidelity: the solver's min-time next to achieved.
                cell += f" (pred {per_mode[m]['predicted_s']}s)"
            row.append(cell)
        row.append(str(per_mode.get("mode1_vs_mode0", "—")))
        lines.append("| " + " | ".join(row) + " |")
    lines.append("")
    ab = results.get("codec_ab")
    if ab:
        lines += [
            "## Transfer codec A/B (measured quantization benefit)",
            "",
            "boot_tiny_4node's topology retargeted at the "
            f"`{ab.get('model', 'tiny2')}` model (~2 MiB layers, so the "
            "256 KiB burst bucket is noise), every source rate-limited "
            f"to {ab['rate_bytes_per_s'] >> 20} MiB/s, mode {ab['mode']}: "
            "TTD is bytes over a fixed rate, so each codec's wire-size "
            "ratio (~0.51x int8, ~0.27x int4) appears as the TTD ratio "
            "(slightly below it: each job's burst head start is "
            "codec-independent).",
            "",
            "| codec | TTD | vs raw |",
            "|---|---|---|",
            f"| raw | {ab['raw']['ttd_s']}s | |",
            f"| int8 | {ab['int8']['ttd_s']}s | {ab['int8_vs_raw']} |",
        ]
        if "int4" in ab:
            lines.append(
                f"| int4 | {ab['int4']['ttd_s']}s | {ab['int4_vs_raw']} |")
        lines.append("")
    cw = results.get("codec_wire")
    if cw:
        dests = (cw.get("int8_wire") or {}).get("dests") or {}
        exact = ("byte-exact" if cw.get("wire_bytes_exact")
                 else "NOT byte-exact")
        lines += [
            "## Negotiated wire codec (docs/codec.md)",
            "",
            "Same rate-limited tiny2 topology, but the seeders hold RAW "
            "canonical blobs and the leader negotiates the wire form "
            "per transfer (`WireCodec: int8`): encode-on-send at the "
            "seeder, decode-at-staging at the dest, codec-qualified "
            "digests/acks, and the flow solver sizing each pair by its "
            "ENCODED bytes (effective capacity = bandwidth x ratio).  "
            f"Wire bytes per dest (RUN_REPORT `dests` table): {exact} "
            f"against `quant.blob_nbytes_codec` "
            f"({cw.get('int8_bytes_per_dest')} B int8 vs "
            f"{cw.get('raw_bytes_per_dest')} B raw, ratio "
            f"{cw.get('ratio')}x).",
            "",
            "| wire | TTD | vs raw | bound (≤ ~1/ratio + burst margin) |",
            "|---|---|---|---|",
            f"| raw | {cw['raw_wire']['ttd_s']}s | | |",
            f"| int8 | {cw['int8_wire']['ttd_s']}s "
            f"| {cw['int8_vs_raw']} "
            f"| {'MET' if cw['bound']['met'] else 'NOT MET'} "
            f"(expected ≲ {cw['bound']['expected_ttd_fraction']}) |",
            "",
        ]
        if dests:
            lines += ["Per-dest wire vs decoded bytes (int8 run):", ""]
            for dest, row in sorted(dests.items()):
                lines.append(
                    f"- dest {dest}: wire {row.get('wire_bytes')} B, "
                    f"decoded {row.get('decoded_bytes')} B "
                    f"({row.get('codec_layers')}/{row.get('layers')} "
                    "layers quantized)")
            lines.append("")
        en = cw.get("entropy")
        if en:
            e_exact = ("byte-exact" if en.get("wire_bytes_exact")
                       else "NOT byte-exact")
            lines += [
                "**Entropy-coded arm (`WireCodec: int8e`):** same "
                "topology with the leader seeded (data-dependent "
                "sizing encodes the leader's own copy); wire bytes "
                f"per dest {e_exact} against the independently "
                "DLE1-encoded seeded blobs "
                f"({en.get('int8e_bytes_per_dest')} B int8e vs "
                f"{en.get('int8_bytes_per_dest')} B int8, "
                f"{en.get('int8e_vs_int8_bytes')}x — seeded-random "
                "weights are near-incompressible, so the entropy pass "
                "is priced at its TRUE size and honestly loses a hair "
                "here; it wins on sparse/low-entropy layers and the "
                "delta rows).  TTD "
                f"{en['int8e_wire']['ttd_s']}s vs raw-seeded "
                f"{en['raw_wire']['ttd_s']}s "
                f"({en.get('int8e_vs_raw')}).",
                "",
            ]
    cb = results.get("codec_bench")
    if cb:
        lines += [
            "## Wire-codec micro-bench (encode/decode GB/s on this host)",
            "",
            "`quant.codec_bench` over one tiny2 layer blob "
            f"({cb.get('raw_bytes', 0)} B raw); rates are RAW bytes "
            "per second (the side the wire saves).  The codec-choice "
            "thresholds (`DLD_CODEC_MIN_RATE`, `DLD_ENTROPY_MIN_RATE`, "
            "`DLD_DELTA_MIN_RATE`) should sit well below the slowest "
            "of these — a link faster than the codec pass gains "
            "nothing from encoded shipping.  The delta row encodes "
            "against a 1%-perturbed sibling (the rollout shape).",
            "",
            "| codec | ratio | encode | host decode | device decode |",
            "|---|---|---|---|---|",
        ]
        for codec in ("int8", "int4", "int8e", "int4e", "delta"):
            row = cb.get(codec) or {}
            if not row:
                continue
            lines.append(
                f"| {codec} | {row.get('ratio')}x "
                f"| {row.get('encode_gbps')} GB/s "
                f"| {row.get('decode_host_gbps')} GB/s "
                f"| {row.get('decode_device_gbps')} GB/s |")
        lines.append("")
    phys = results.get("physical")
    if phys:
        lines += [
            "## Physical-size run (ties the TTD story to the bench)",
            "",
            "Mode 3 with `-hbm`: two seeders co-send the "
            "`llama3-8b-d4v8k` model — four ~416 MiB layers, the exact "
            "per-layer bytes `bench.py` measures (full 8B layer shape; "
            "vocab-trimmed head so it doesn't dwarf the layers) — to one "
            "cold dest that stages into device memory and boots "
            "(TTFT).  Loopback TCP, STRIPED: each flow fragment past "
            "the transport's stripe threshold rides "
            f"{phys.get('stripes', '?')} pooled data connections in "
            "parallel (`transport/tcp.py`).  The achieved rate is the "
            "dest's whole-model ingest, network receive + device "
            "staging end to end; the loopback ceiling columns are this "
            "host's MEASURED raw socket bandwidth (1 stream / the "
            "stripe count), probed next to the run — the fraction makes "
            "the number attributable and regression-guarded the same "
            "way bench.py's `link_fraction` does for the device hop.",
            "",
            "| scenario | backend | TTD | TTFT | achieved ingest | "
            "loopback ceiling (1s / striped) | link fraction |",
            "|---|---|---|---|---|---|---|",
            f"| {phys['scenario']} | {phys['backend']} | "
            f"{phys['ttd_s']}s | "
            + (f"{phys['ttft_s']}s" if "ttft_s" in phys else "—")
            + f" | {phys['achieved_gbps']} GB/s | "
            + (f"{phys.get('loopback_raw_gbps', '—')} / "
               f"{phys.get('loopback_striped_gbps', '—')} GB/s"
               if ("loopback_raw_gbps" in phys
                   or "loopback_striped_gbps" in phys) else "—")
            + " | "
            + (f"{phys['link_fraction']}"
               if "link_fraction" in phys else "—")
            + " |",
            "",
        ]
        prior = phys.get("prior")
        same_backend = (not prior
                        or prior.get("backend", phys.get("backend"))
                        == phys.get("backend"))
        if prior and "stripes" not in prior and same_backend:
            # Only a PRE-striping, SAME-backend prior gets the striping
            # attribution — a later regeneration carries a post-striping
            # prior (it has a "stripes" field), and a backend flip
            # (cpu vs accelerator) would otherwise be reported as this
            # PR's speedup.
            lines += [
                "**Before/after (the striped-data-plane PR):** the "
                f"prior recorded row was {prior['ttd_s']}s at "
                f"{prior['achieved_gbps']} GB/s — each (seeder, layer) "
                "transfer was ONE serial socket stream.  With "
                "multi-socket striping, scatter-gather framing, and "
                "receive-to-stage streaming the re-measured row is "
                f"{phys['ttd_s']}s at {phys['achieved_gbps']} GB/s "
                f"({round(phys['achieved_gbps'] / max(prior['achieved_gbps'], 1e-9), 2)}x), "
                "with the remaining gap to the measured loopback "
                "ceiling attributed by the phase table below.",
                "",
            ]
        elif prior:
            lines += [
                f"Previous recorded row: {prior['ttd_s']}s at "
                f"{prior['achieved_gbps']} GB/s (run-to-run drift on "
                "this host is dominated by its bursty CPU budget — "
                "compare link fractions, not absolute rates).",
                "",
            ]
        wire = phys.get("wire_only")
        if wire:
            lines += [
                "Wire-only sibling (same topology, `-boot none`, "
                "measured for attribution): "
                f"TTD {wire['ttd_s']}s = {wire['achieved_gbps']} GB/s.  "
                "The delta to the recorded row is the boot PRECOMPILE "
                "overlap (BootHint fires at distribution start, so XLA "
                "compiles the forward WHILE the bytes are on the wire) "
                "— free concurrency on multi-core hosts, but on this "
                "2-core container the compile threads and the wire "
                "share cores, which is a host property, not a data-"
                "plane regression; the ceiling columns carry the same "
                "caveat (the container's CPU budget is bursty, so the "
                "raw-socket ceiling itself drifts several-fold between "
                "probes).",
                "",
            ]
        cold = phys.get("cold")
        if cold:
            wph = phys.get("phases") or {}
            cph = cold.get("phases") or {}

            def ttft_row(tag, rec, ph):
                boot_ms = ph.get("boot_ms", 0.0)
                pre = ph.get("precompile_ms")
                pre_cell = ("—" if pre is None else
                            f"{pre}ms"
                            + (" (in-wire)" if ph.get("precompile_in_wire")
                               else " (post-startup)"))
                streamed = ph.get("streamed_blobs", 0)
                stream_cell = (
                    f"{ph.get('stream_stage_ms', 0.0)}ms "
                    f"({ph.get('streamed_blobs_in_wire', 0)}/{streamed} "
                    "blobs in-wire)" if streamed else "—")
                ttft = rec.get("ttft_s")
                ttd = rec.get("ttd_s")
                bar = (round(ttft / (ttd + boot_ms / 1000), 2)
                       if ttft and ttd else None)
                return (f"| {tag} | {ttd}s | "
                        + (f"{ttft}s" if ttft else "—")
                        + f" | {boot_ms}ms | {pre_cell} | {stream_cell} | "
                        + (f"{bar}" if bar is not None else "—") + " |")

            lines += [
                "### TTFT: persistent compilation cache + streamed "
                "staging (cold vs warm)",
                "",
                "The same scenario run twice against one "
                "`JAX_COMPILATION_CACHE_DIR`: the cold run compiles (and "
                "writes the cache) — its one-time compile overlaps the "
                "wire via the BootHint precompile; the warm run's "
                "compiles are DISK READS, so its boot tail is assembly "
                "+ forward only.  `streamed staging` is the per-layer "
                "receive-to-device boot path "
                "(`runtime/stream_boot.py`): each delivered layer's "
                "decode/upload runs the moment its interval set "
                "completes, concurrent with the remaining transfers.  "
                "`TTFT/(TTD+boot)` is the acceptance ratio — the "
                "leader-observed TTFT against delivery plus the dest's "
                "own boot tail (protocol overhead is the remainder); "
                "the VERDICT item 4 bar is warm TTFT ≤ TTD + decode "
                "+ ~20%.  Seeders run `-boot none` in both rows (only "
                "the dest's boot is the metric; a seeder booting its "
                "own copy would contend for the same 2 cores).",
                "",
                "| cache | TTD | TTFT | boot tail | hint precompile | "
                "streamed stage | TTFT/(TTD+boot) |",
                "|---|---|---|---|---|---|---|",
                ttft_row("cold", cold, cph),
                ttft_row("warm", phys, wph),
                "",
            ]
            prior = phys.get("prior")
            if prior and prior.get("ttft_s"):
                lines += [
                    "**Record vs prior:** the previously recorded row "
                    f"was TTD {prior['ttd_s']}s / TTFT "
                    f"{prior['ttft_s']}s; re-measured here as cold TTD "
                    f"{cold.get('ttd_s')}s / TTFT {cold.get('ttft_s')}s "
                    f"and warm TTD {phys.get('ttd_s')}s / TTFT "
                    f"{phys.get('ttft_s')}s.  These rows run with the "
                    "integrity plane ON (per-fragment wire checksum + "
                    "per-layer digest verify — its measured cost and "
                    "the integrity-OFF sibling are in the integrity "
                    "table below); the rest of the row-to-row movement "
                    "is this host's bursty CPU budget (compare "
                    "within-run siblings, not absolute cross-run "
                    "rates).",
                    "",
                ]
        fab = results.get("physical_fabric")
        if fab:
            frags = fab.get("tcp_layer_fragments",
                            int(fab.get("tcp_layer_bytes", False)))
            lines += [
                "The device-plane sibling: same model, layer bytes over "
                "the pod fabric (virtual 8-device CPU mesh; the single "
                "real chip can't host a [4, 2] mesh, so the collective "
                "runs on the CPU mesh and the real-chip evidence stays "
                "with the `-hbm` row above).  Zero TCP layer bytes "
                "asserted from the run's own logs (exact-match count of "
                "the receiver's per-fragment message):",
                "",
                "| scenario | backend | TTD | achieved | fabric "
                "deliveries | TCP layer fragments |",
                "|---|---|---|---|---|---|",
                f"| {fab['scenario']} | {fab['backend']} | "
                f"{fab['ttd_s']}s | {fab['achieved_gbps']} GB/s | "
                f"{fab['fabric_deliveries']} | "
                f"{f'{frags} (bug)' if frags else 'none'} |",
                "",
            ]
            cache = fab.get("collective_cache")
            phases = fab.get("plan_phases")
            if cache or phases:
                lines += [
                    "Per-plan phase breakdown of the fabric row "
                    "(thread-time sums across the run's plans; phases "
                    "from concurrent plans overlap, so sums can exceed "
                    "the TTD wall clock) and the compiled-collective "
                    "cache's reuse — warm plans skip XLA entirely, so "
                    "`compile` is a one-time cost the batch amortizes:",
                    "",
                    "| compile | upload | collective | splice | cache "
                    "hits | cache misses |",
                    "|---|---|---|---|---|---|",
                ]

                row = []
                for name in ("upload", "collective", "splice"):
                    ms = (phases or {}).get(name, {}).get("ms")
                    row.append(f"{ms}ms" if ms is not None else "—")
                compile_ms = (cache or {}).get("compile_ms")
                lines += [
                    "| " + " | ".join(
                        [f"{compile_ms}ms" if compile_ms is not None
                         else "—"] + row
                        + [str((cache or {}).get("hits", "—")),
                           str((cache or {}).get("misses", "—"))]
                    ) + " |",
                    "",
                ]
            prior = fab.get("prior")
            if prior:
                tcp_ttd = phys.get("ttd_s")
                ratio = (round(fab["ttd_s"] / tcp_ttd, 1)
                         if tcp_ttd else None)
                prior_ratio = prior.get("vs_tcp_same_host")
                lines += [
                    "**Before/after (the warm-path PR):** the prior "
                    f"recorded fabric row was {prior['ttd_s']}s "
                    f"({prior['achieved_gbps']} GB/s) at "
                    f"{prior_ratio}x its same-host TCP sibling "
                    f"({prior['host']}).  With the compiled-executable "
                    "cache + plan batching + full in-flight window, the "
                    f"re-measured row is {fab['ttd_s']}s at "
                    + (f"{ratio}x" if ratio else "—")
                    + " the same-host TCP row — per-plan XLA compile is "
                    "amortized to the one-time `compile` column above "
                    "(warm/batched plans skip it entirely), and the "
                    "remaining gap is the `collective` column: on the "
                    "virtual CPU mesh every \"ICI\" byte is an emulated "
                    "8-way host memcpy, the exact term real ICI hardware "
                    "accelerates.",
                    "",
                ]
        evidence = results.get("collective_cache_evidence")
        if evidence:
            lines += [
                "### Compiled-collective cache: reuse evidence",
                "",
                "Per-run `collective cache stats` (hits / misses / "
                "one-time compile) from the runs' own summaries — "
                "mode 3 batches same-size plans into ONE gather (so its "
                "miss count is the batch count, not the layer count); "
                "unbatched rounds show the warm-path hits directly:",
                "",
                "| run | hits | misses | compile |",
                "|---|---|---|---|",
            ]
            for name, c in evidence.items():
                lines.append(
                    f"| {name} | {c.get('hits', '—')} | "
                    f"{c.get('misses', '—')} | "
                    f"{c.get('compile_ms', '—')}ms |")
            lines.append("")
        ph = phys.get("phases")
        if ph:
            lines += [
                "Phase breakdown from the dest's log (thread-time sums; "
                "concurrent fragment/stripe handlers overlap, so sums "
                "can exceed the TTD wall clock).  Zero copy_ms/"
                "ingest_ms = the zero-copy receive landed socket bytes "
                "directly in the reassembly buffer and staging adopted "
                "that buffer:",
                "",
                "| wire recv | assembly copy | ingest write | stage | "
                "boot |",
                "|---|---|---|---|---|",
                f"| {ph['wire_recv_ms']}ms | {ph['assembly_copy_ms']}ms "
                f"| {ph['ingest_write_ms']}ms | {ph['stage_ms']}ms | "
                f"{ph['boot_ms']}ms |",
                "",
            ]
            if "fragments" in ph:
                span = ph.get("max_layer_recv_span_ms", 0.0)
                tail = ph.get("stage_ms", 0.0)
                lines += [
                    "Receive/stage overlap: fragments (stripes "
                    "included) whose bytes the sink PLACED directly in "
                    "the reassembly buffer stage as offsets complete — "
                    "their device-side accounting runs during the wire "
                    "receive, so only the post-completion `stage tail` "
                    "is serial with the wire:",
                    "",
                    "| fragments | placed (zero-copy) | in-recv ingest "
                    "| max layer recv span | stage tail after recv |",
                    "|---|---|---|---|---|",
                    f"| {ph['fragments']} | {ph['placed_fragments']} | "
                    f"{ph['ingest_write_ms']}ms | {span}ms | "
                    f"{tail}ms |",
                    "",
                ]
        integ = phys.get("integrity")
        if integ:
            lines += [
                "### Integrity plane (docs/integrity.md)",
                "",
                "Every wire frame carries an advisory checksum "
                "(xxh3-64 where the extension is importable, crc32 "
                "otherwise — the hash-rate table below is the measured "
                "why) verified before delivery; every completed layer "
                "verifies its leader-stamped digest (xxh3-128/"
                "blake2b-128, self-describing stamp) before it is acked "
                "or staged.  `verify_ms` is dest-side checksum THREAD "
                "time (concurrent stripe receivers verify in parallel); "
                "`crc_overhead_frac` is that thread time over the TTD "
                "wall clock — verification rides receive threads that "
                "overlap the wire, so the WALL-clock cost (the ≤5% "
                "acceptance metric) is the integrity-OFF row's delta "
                "below.  The faulted "
                "sibling runs the SAME scenario under a seeded schedule "
                "of injected corruption/drops (below the CRC check) and "
                "duplicated sends; delivery must still be byte-exact "
                "(digests verified), with recovery cost visible as TTD "
                "degradation + retransmitted bytes:",
                "",
                "| row | TTD | verify_ms (crc+digest) | "
                "crc_overhead_frac | dropped frames | NACKs | "
                "retransmitted bytes |",
                "|---|---|---|---|---|---|---|",
                f"| clean | {phys['ttd_s']}s | {integ['verify_ms']}ms | "
                f"{integ['crc_overhead_frac']:.2%} | "
                f"{integ['crc_dropped_frames']} | {integ['nacks_sent']} "
                f"| {integ['retransmitted_bytes']} |",
            ]
            nc = phys.get("nocheck")
            if nc:
                delta = round(
                    (phys["ttd_s"] - nc["ttd_s"])
                    / max(nc["ttd_s"], 1e-9), 4)
                lines.append(
                    f"| integrity OFF (`DLD_WIRE_CRC=0 "
                    f"DLD_LAYER_DIGESTS=0`) | {nc['ttd_s']}s "
                    f"(wall-clock delta to clean: {delta:+.1%}) | — | — "
                    "| — | — | — |")
            fl = phys.get("faulted")
            fi = (fl or {}).get("integrity")
            if fl and fi:
                degr = round(fl["ttd_s"] / max(phys["ttd_s"], 1e-9), 2)
                lines.append(
                    f"| faulted (`{fl.get('fault_spec', '?')}`) | "
                    f"{fl['ttd_s']}s ({degr}x clean) | "
                    f"{fi['verify_ms']}ms | "
                    f"{fi['crc_overhead_frac']:.2%} | "
                    f"{fi['crc_dropped_frames']} | {fi['nacks_sent']} | "
                    f"{fi['retransmitted_bytes']} |")
            cold = phys.get("cold") or {}
            if nc and cold.get("ttd_s"):
                spread = abs(phys["ttd_s"] - cold["ttd_s"]) / min(
                    phys["ttd_s"], cold["ttd_s"])
                met = (phys["ttd_s"] - nc["ttd_s"]) / nc["ttd_s"] <= 0.05
                lines += [
                    "",
                    f"The ≤5% overhead bar is "
                    f"{'MET' if met else 'NOT met'} as measured on this "
                    "container — read the delta with its error bar: the "
                    "clean row's same-config cold/warm spread in this "
                    f"very run is {spread:.0%} (CFS burst-budget drift, "
                    "the 0.36-2.7 GB/s raw-loopback band the striping "
                    "PR recorded), the same order as the overhead being "
                    "measured.  The drift-free attribution is the "
                    "thread-time column: verification is DRAM-rate "
                    "hashing sharing 2 CPUs with both seeder processes "
                    "and the dest's boot, so its thread share shrinks "
                    "wherever receive threads have an idle core to ride "
                    "(any real multi-core host); the per-byte verify "
                    "cost itself is bounded by the hash-rate table "
                    "below, not by this box's contention.",
                ]
            lines.append("")
    bench = results.get("integrity_bench")
    if bench:
        lines += [
            "## Integrity hash rates (measured on this host)",
            "",
            f"Why `{bench.get('fragment_algo', 'crc32')}` per FRAGMENT "
            f"and `{bench.get('digest_algo', 'blake2b')}`-128 per LAYER "
            f"(`utils/integrity.hash_bench`, {bench['bytes'] >> 20} MiB "
            "buffer): the fragment check sits on the per-stripe receive "
            "hot path (thread-concurrent, must track wire rate), the "
            "layer digest runs once per layer as the end-to-end "
            "identity.  The threat model is corruption, not adversarial "
            "substitution, so 128 random-collision bits are equivalent "
            "across algorithms and the fastest wins "
            "(`DLD_DIGEST_ALGO=blake2b` buys the cryptographic identity "
            "at the measured cost):",
            "",
            "| crc32 | adler32 | xxh3-64 | xxh3-128 | blake2b-128 | "
            "sha256 |",
            "|---|---|---|---|---|---|",
            f"| {bench['crc32_gbps']} GB/s | {bench['adler32_gbps']} "
            f"GB/s | {bench.get('xxh3_64_gbps', 0.0)} GB/s | "
            f"{bench.get('xxh3_128_gbps', 0.0)} GB/s | "
            f"{bench['blake2b_gbps']} GB/s | "
            f"{bench['sha256_gbps']} GB/s |",
            "",
        ]
    ns = results.get("north_star_model")
    if ns:
        tgt = ns.get("target", {})
        lines += [
            "## north_star_model: the v5e-32 / Llama-70B target, argued "
            "by model",
            "",
            f"The mode-3 solver run on `conf/{ns['config']}` exactly as "
            f"the leader would ({ns['layers']} layers x "
            f"{ns['layer_bytes'] / 2**30:.2f} GiB, 8 hosts x 4 chips, "
            "25 GB/s per-host line rate) — the hardware-independent way "
            "this environment allows the BASELINE north-star row "
            f"(<{tgt.get('time_s', 10):g} s at "
            f">={tgt.get('utilization', 0.7):.0%} of ICI line rate) to "
            "be argued.  `utilization` is dest-side: aggregate planned "
            "ingest over the receiving hosts' summed line rate.  The "
            "three rows isolate the bottleneck: the SHIPPED config is "
            "source-bound (one seeder's 3 GB/s disk class caps the whole "
            "pod — no schedule can beat bytes/rate), and the target is "
            "met exactly when the blobs sit in RAM on replicated "
            "seeders, the paper's multi-seeder co-send shape.",
            "",
            "| sources | predicted completion | aggregate | dest-side "
            "ICI utilization | solve | <10s | >=70% |",
            "|---|---|---|---|---|---|---|",
        ]
        for row in ns.get("rows", []):
            lines.append(
                f"| {row['label']} | {row['predicted_s']}s | "
                f"{row['aggregate_gbps']} GB/s | "
                f"{row['ici_utilization']:.1%} of "
                f"{row['dest_line_gbps']} GB/s | {row['solve_ms']}ms | "
                f"{'yes' if row['meets_time'] else 'NO'} | "
                f"{'yes' if row['meets_utilization'] else 'NO'} |")
        lines.append("")
    baseline = results.get("baseline_scenarios")
    if baseline:
        lines += [
            "## BASELINE.json scenarios (#2-#5)",
            "",
            "Driver-named benchmark topologies (cli.genconf), run over "
            "loopback with faithful node counts and schedules — 8 to 64 "
            "OS processes — at >=64 MiB layers, so the bandwidth term "
            "(not per-transfer overhead) dominates.  The 64-node row "
            "runs ALL FOUR modes, exercising the mode-3 solver at the "
            "scenario's full node count; its predicted_s/solve time are "
            "recorded next to the achieved TTD.",
            "",
            "| scenario | mode | layer bytes | TTD | mode-3 predicted | "
            "solve |",
            "|---|---|---|---|---|---|",
        ]
        for name, rows in baseline.items():
            if isinstance(rows, dict):  # pre-64MiB record (carried over)
                rows = [rows]
            for rec in rows:
                size = rec.get("layer_bytes")
                lines.append(
                    f"| {name} | {rec['mode']} | "
                    + (f"{size >> 20} MiB" if size else "—")
                    + f" | {rec['ttd_s']}s | "
                    + (f"{rec['predicted_s']}s" if "predicted_s" in rec
                       else "—")
                    + " | "
                    + (f"{rec['solve_ms']}ms" if "solve_ms" in rec
                       else "—") + " |")
        lines.append("")
    _telemetry_overhead_md(lines, results)
    _span_overhead_md(lines, results)
    _attribution_md(lines, results)
    _failover_md(lines, results)
    _service_md(lines, results)
    _fanout_md(lines, results)
    _elasticity_md(lines, results)
    _sharded_md(lines, results)
    _fabric_delivery_md(lines, results)
    _swap_md(lines, results)
    _rollout_md(lines, results)
    _autonomy_md(lines, results)
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="ttd_matrix", prefix_chars="-")
    p.add_argument("-o", type=str, default="TTD_MATRIX.json")
    p.add_argument("-scale", type=int, default=8 << 20,
                   help="scaled LayerSize bytes for the reference scenario")
    p.add_argument("-trials", type=int, default=3)
    p.add_argument("-baseline", action="store_true",
                   help="also run the BASELINE.json scenarios #2-#5 "
                        "(8-64 processes; minutes of wall time)")
    p.add_argument("-baseline-scale", type=int, default=64 << 20,
                   help="LayerSize bytes for the BASELINE scenarios "
                        "(>=64 MiB so bandwidth dominates)")
    p.add_argument("-physical", action="store_true",
                   help="also run the physical-size scenario (~1.8 GiB "
                        "over loopback + device staging + a boot)")
    p.add_argument("-trace", type=str, default="",
                   help="with -physical: also write a Chrome trace of "
                        "the run (merged per-node logs) to this path")
    p.add_argument("-telemetry-overhead", action="store_true",
                   help="also measure the always-on telemetry plane's "
                        "TTD cost on a BASELINE scenario (ON vs "
                        "DLD_TELEMETRY=0; docs/observability.md)")
    p.add_argument("-failover", action="store_true",
                   help="also measure control-plane failover at "
                        "physical-row sizes: clean HA-armed mode-3 run "
                        "vs leader-killed sibling; records TTR and the "
                        "failover overhead (docs/failover.md)")
    p.add_argument("-service", action="store_true",
                   help="also measure the multi-job service plane "
                        "(docs/service.md): two overlapping jobs with "
                        "the per-link priority split, and a v2 delta "
                        "rollout's shipped bytes vs changed-fraction × "
                        "model bytes against the content store")
    p.add_argument("-swap", action="store_true",
                   help="also measure the zero-downtime weight swap "
                        "row (tokens/s + p99 before/during/after a "
                        "mid-serve v1→v2 swap; docs/swap.md)")
    p.add_argument("-rollout", action="store_true",
                   help="also measure the SLO-guarded rollout pipeline "
                        "(docs/rollout.md): a continuous request "
                        "stream through a 3-wave rollout with an "
                        "injected bad wave — auto-pause on the SLO "
                        "breach, rollback to v1, earlier waves keep "
                        "v2, zero dropped requests")
    p.add_argument("-autonomy", action="store_true",
                   help="also run the closed-loop fleet-autonomy row "
                        "(docs/autonomy.md): a slowserve hot replica + "
                        "a slow= straggler link under live traffic — "
                        "the policy engine must grow the replica set, "
                        "re-plan around the slow link, quarantine the "
                        "breacher and converge back inside SLO with "
                        "zero operator verbs, plus the DLD_POLICY=0 "
                        "kill-switch sibling showing the same "
                        "injections NOT acted on")
    p.add_argument("-sharded", action="store_true",
                   help="also measure sharded delivery "
                        "(docs/sharding.md): the multi-dest 64 MiB "
                        "full-layer vs 1/4-shard comparison — wire "
                        "bytes per dest, TTD, predicted-vs-achieved, "
                        "and the post-gather digest check")
    p.add_argument("-fabric-delivery", action="store_true",
                   dest="fabric_delivery",
                   help="also measure fabric-assisted pod delivery "
                        "(docs/fabric.md): the same replica-pod "
                        "topology run host-path vs pod-sharded — "
                        "per-pod NIC wire bytes must land within 10%% "
                        "of model_bytes (not model_bytes × replicas), "
                        "every replica's gathered tree digest-exact")
    p.add_argument("-fanout", action="store_true",
                   help="also measure the fleet fan-out row "
                        "(docs/hierarchy.md): 64- and 256-node inmem "
                        "BASELINE, flat mode-3 vs hierarchical "
                        "sub-leaders — root solve wall, root-handled "
                        "control message count, TTD")
    p.add_argument("-elasticity", action="store_true",
                   help="also measure elastic membership "
                        "(docs/membership.md): N unconfigured nodes "
                        "JOIN the running cluster concurrently — "
                        "origin-seeder vs peer-holder refill bytes, "
                        "coverage byte-exactness, and the sub-linear "
                        "origin-bytes bar")
    p.add_argument("-attribution", action="store_true",
                   help="also run the explainable-delivery row "
                        "(docs/observability.md): a mode-3 multi-node "
                        "run with an injected slow= straggler link — "
                        "the critical-path span chain must reconcile "
                        "with the achieved TTD (±10%%), decompose the "
                        "predicted-vs-achieved gap per phase, and flag "
                        "the straggler live")
    p.add_argument("-span-overhead", action="store_true",
                   help="also measure span recording's TTD cost on a "
                        "BASELINE scenario (ON vs DLD_SPANS=0; "
                        "docs/observability.md)")
    p.add_argument("-codec-wire", action="store_true",
                   help="also measure the NEGOTIATED wire codec "
                        "(docs/codec.md): raw-canonical seeders, "
                        "leader-chosen int8 wire over a rate-limited "
                        "topology — TTD vs raw, byte-exact wire "
                        "accounting, plus the encode/decode "
                        "micro-bench")
    args = p.parse_args(argv)
    if args.trace and not args.physical:
        p.error("-trace needs -physical (it traces that run)")
    results = run_matrix(args.scale, args.trials)
    results["codec_ab"] = run_codec_ab(args.trials)
    prior_doc = None
    if os.path.exists(args.o):
        try:
            with open(args.o) as f:
                prior_doc = json.load(f)
        except (OSError, ValueError):
            prior_doc = None
    # The solver-by-model north-star record is cheap (a few solves, no
    # processes): regenerate it on every run.
    results["north_star_model"] = run_north_star()
    # Hash-rate micro-bench on THIS host: the measured justification for
    # crc32 on the per-fragment hot path vs blake2b for the per-layer
    # digest (docs/integrity.md).  Cheap; regenerated every run.
    from ..utils.integrity import hash_bench

    results["integrity_bench"] = hash_bench()
    if args.baseline:
        if args.baseline_scale < 64 << 20:
            # Smaller layers are fine for iterating, but the RECORDED
            # matrix wants the bandwidth-dominated regime — say so
            # instead of silently clamping.
            print(f"note: -baseline-scale {args.baseline_scale} is below "
                  "the 64 MiB bandwidth-dominated regime the recorded "
                  "matrix uses", file=sys.stderr)
        results["baseline_scenarios"] = run_baseline_scenarios(
            args.baseline_scale)
    elif prior_doc and prior_doc.get("baseline_scenarios"):
        # A refresh without -baseline must not erase the recorded
        # BASELINE scenario results (minutes of 64-process wall time).
        results["baseline_scenarios"] = prior_doc["baseline_scenarios"]
    if args.physical:
        # Cold-then-warm against ONE persistent compilation cache: the
        # cold run writes it (its compile overlaps the wire via the
        # BootHint precompile), the warm run reads it — the pair is the
        # TTFT cold/warm breakdown the markdown renders.
        import shutil

        cachedir = tempfile.mkdtemp(prefix="dld-compile-cache-")
        try:
            cold = run_physical(trace_out=args.trace, cache_dir=cachedir,
                                label="cold")
            warm = run_physical(cache_dir=cachedir, label="warm")
            # FAULTED sibling (integrity plane): same scenario, warm
            # cache, with a seeded schedule of corruption/drops below
            # the CRC check plus duplicated sends on every node — the
            # recovery (NACK retransmits, digest verify) must deliver
            # byte-exactly; the row records the TTD degradation.
            try:
                nocheck = run_physical(cache_dir=cachedir,
                                       label="nocheck",
                                       integrity_off=True)
                warm["nocheck"] = {
                    k: nocheck[k]
                    for k in ("ttd_s", "ttft_s", "achieved_gbps")
                    if k in nocheck
                }
            except Exception as e:  # noqa: BLE001 — clean rows still record
                print(f"integrity-off physical run failed: {e!r}",
                      file=sys.stderr)
            try:
                faulted = run_physical(cache_dir=cachedir,
                                       label="faulted",
                                       faults=PHYSICAL_FAULT_SPEC)
                warm["faulted"] = {
                    k: faulted[k]
                    for k in ("ttd_s", "ttft_s", "achieved_gbps",
                              "integrity", "fault_spec")
                    if k in faulted
                }
            except Exception as e:  # noqa: BLE001 — clean rows still record
                print(f"faulted physical run failed: {e!r}",
                      file=sys.stderr)
        finally:
            shutil.rmtree(cachedir, ignore_errors=True)
        warm["cold"] = {
            k: cold[k] for k in ("ttd_s", "ttft_s", "achieved_gbps",
                                 "phases", "cache", "predicted_s")
            if k in cold
        }
        results["physical"] = warm
        # Before/after: carry the superseded record's headline numbers so
        # the regenerated markdown states the delta it claims.
        prior_phys = (prior_doc or {}).get("physical")
        if prior_phys and "ttd_s" in prior_phys:
            results["physical"]["prior"] = {
                "ttd_s": prior_phys["ttd_s"],
                "achieved_gbps": prior_phys["achieved_gbps"],
                "backend": prior_phys.get("backend", ""),
            }
            if "ttft_s" in prior_phys:
                results["physical"]["prior"]["ttft_s"] = (
                    prior_phys["ttft_s"])
            if "stripes" in prior_phys:
                # Marks the prior as post-striping: the markdown then
                # reports plain run-to-run drift instead of attributing
                # the delta to the striping PR.
                results["physical"]["prior"]["stripes"] = (
                    prior_phys["stripes"])
        if prior_phys and prior_phys.get("wire_only"):
            # Hand-measured attribution sibling (-boot none): carried
            # forward like baseline_scenarios, not re-measured here.
            results["physical"].setdefault(
                "wire_only", prior_phys["wire_only"])
        results["physical_fabric"] = run_physical_fabric()
        fab_prior = (prior_doc or {}).get("physical_fabric") or {}
        if fab_prior.get("prior"):
            results["physical_fabric"].setdefault(
                "prior", fab_prior["prior"])
    else:
        for key in ("physical", "physical_fabric"):
            if prior_doc and prior_doc.get(key):
                results[key] = prior_doc[key]
    if args.telemetry_overhead:
        results["telemetry_overhead"] = run_telemetry_overhead()
    elif prior_doc and prior_doc.get("telemetry_overhead"):
        results["telemetry_overhead"] = prior_doc["telemetry_overhead"]
    if args.span_overhead:
        results["span_overhead"] = run_span_overhead()
    elif prior_doc and prior_doc.get("span_overhead"):
        results["span_overhead"] = prior_doc["span_overhead"]
    if args.attribution:
        results["attribution"] = run_attribution()
    elif prior_doc and prior_doc.get("attribution"):
        results["attribution"] = prior_doc["attribution"]
    if args.failover:
        results["failover"] = run_failover()
    elif prior_doc and prior_doc.get("failover"):
        results["failover"] = prior_doc["failover"]
    if args.service:
        results["service_jobs"] = run_service_jobs()
        results["delta_rollout"] = run_delta_rollout()
        results["delta_wave"] = run_delta_wave()
    else:
        for key in ("service_jobs", "delta_rollout", "delta_wave"):
            if prior_doc and prior_doc.get(key):
                results[key] = prior_doc[key]
    if args.sharded:
        results["sharded_delivery"] = run_sharded_delivery()
    elif prior_doc and prior_doc.get("sharded_delivery"):
        results["sharded_delivery"] = prior_doc["sharded_delivery"]
    if args.fabric_delivery:
        results["fabric_delivery"] = run_fabric_delivery()
    elif prior_doc and prior_doc.get("fabric_delivery"):
        results["fabric_delivery"] = prior_doc["fabric_delivery"]
    if args.fanout:
        results["fanout"] = run_fanout()
    elif prior_doc and prior_doc.get("fanout"):
        results["fanout"] = prior_doc["fanout"]
    if args.swap:
        results["live_swap"] = run_live_swap()
    elif prior_doc and prior_doc.get("live_swap"):
        results["live_swap"] = prior_doc["live_swap"]
    if args.rollout:
        results["rollout"] = run_rollout()
    elif prior_doc and prior_doc.get("rollout"):
        results["rollout"] = prior_doc["rollout"]
    if args.autonomy:
        results["autonomy"] = {
            "closed_loop": run_autonomy(),
            "kill_switch": run_autonomy(kill_switch=True),
        }
    elif prior_doc and prior_doc.get("autonomy"):
        results["autonomy"] = prior_doc["autonomy"]
    if args.elasticity:
        results["elasticity"] = run_elasticity()
    elif prior_doc and prior_doc.get("elasticity"):
        results["elasticity"] = prior_doc["elasticity"]
    if args.codec_wire:
        results["codec_wire"] = run_codec_wire(args.trials)
        from ..models.quant import codec_bench

        results["codec_bench"] = codec_bench()
    else:
        for key in ("codec_wire", "codec_bench"):
            if prior_doc and prior_doc.get(key):
                results[key] = prior_doc[key]
    # Regenerate the cache-reuse evidence from THIS run's records;
    # fall back to the prior document's (e.g. hand-recorded SPMD rows)
    # when the run produced none.
    evidence = _cache_evidence(results)
    if not evidence and prior_doc:
        evidence = prior_doc.get("collective_cache_evidence") or {}
    if evidence:
        results["collective_cache_evidence"] = evidence
    with open(args.o, "w") as f:
        json.dump(results, f, indent=1)
    md = os.path.splitext(args.o)[0] + ".md"
    with open(md, "w") as f:
        f.write(to_markdown(results))
    print(json.dumps(results["scenarios"], indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
