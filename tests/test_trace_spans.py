"""The one tracing primitive (utils/trace.py over the telemetry ring):
interval spans, their vocabulary, their dump, and their annotations in a
profiler capture."""

import json
import os
import re
import socket
import subprocess
import sys
import threading
import time

import pytest

from distributed_llm_dissemination_tpu.utils import telemetry, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "distributed_llm_dissemination_tpu")


def by_name(name):
    return [s for s in trace.spans() if s["name"] == name]


# ------------------------------------------------------------ the primitive

def test_nested_spans_record_parent_and_inherit_id_and_node():
    with trace.span("ingest.finalize", id="2.3", node=2, bytes=10):
        with trace.span("ingest.finalize.wait"):
            time.sleep(0.002)
        with trace.span("ingest.finalize.ready", id="other"):
            pass
    outer, = by_name("ingest.finalize")
    wait, = by_name("ingest.finalize.wait")
    ready, = by_name("ingest.finalize.ready")
    assert outer["parent"] is None and outer["fields"] == {"bytes": 10}
    assert (wait["parent"], wait["id"], wait["node"]) == (
        "ingest.finalize", "2.3", 2)
    assert (ready["parent"], ready["id"]) == ("ingest.finalize", "other")
    for child in (wait, ready):
        assert outer["t0"] <= child["t0"] <= child["t1"] <= outer["t1"]
    assert wait["t1"] - wait["t0"] >= 0.002
    assert outer["thread"] == threading.current_thread().name
    # the stack unwound: a later span on this thread has no parent
    with trace.span("wire.crc"):
        pass
    assert by_name("wire.crc")[0]["parent"] is None


def test_spans_of_one_blob_share_an_id_across_threads():
    pair = telemetry.span_id(2, 7)
    assert pair == "2.7"

    def stripe(k):
        with trace.span("wire.recv", id=pair, offset=k):
            time.sleep(0.001)

    threads = [threading.Thread(target=stripe, args=(k,), name=f"rx-{k}")
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    with trace.span("ingest.finalize", id=pair):
        pass
    mine = [s for s in trace.spans() if s["id"] == pair]
    assert len(mine) == 5
    assert {s["thread"] for s in mine} >= {f"rx-{k}" for k in range(4)}
    # no cross-thread parent by accident
    assert all(s["parent"] is None for s in mine)


def test_span_at_files_an_interval_the_caller_holds():
    t0 = time.monotonic()
    trace.span_at("wire.queue", t0, t0 + 0.25, id="2.1", node=2, src=0)
    rec, = by_name("wire.queue")
    assert rec["t1"] - rec["t0"] == pytest.approx(0.25)
    assert rec["fields"] == {"src": 0} and rec["node"] == 2


def test_an_exception_still_records_the_span():
    with pytest.raises(ValueError):
        with trace.span("decode.stage", id="2.0") as sp:
            sp.set(in_wire=True)
            raise ValueError("boom")
    rec, = by_name("decode.stage")
    assert "boom" in rec["fields"]["error"] and rec["fields"]["in_wire"]
    assert rec["t0"] <= rec["t1"]
    with trace.span("wire.crc"):  # the thread's stack was unwound
        pass
    assert by_name("wire.crc")[0]["parent"] is None


def test_the_ring_is_bounded_and_counts_its_drops(monkeypatch):
    monkeypatch.setenv("DLD_SPAN_RING", "64")
    telemetry.reset_run()
    for i in range(100):
        trace.span_at("wire.recv", float(i), float(i) + 0.5)
    kept = trace.spans()
    assert len(kept) == 64 and kept[0]["t0"] == 36.0  # oldest out first
    assert trace.counter_totals()["telemetry.intervals_dropped"] == 36
    # the ring is the dump's window; the totals stay the whole run's
    assert trace.phase_totals()["wire.recv"] == {"ms": 50000.0, "n": 100}


def test_many_frames_cannot_push_the_lifecycle_instants_out(monkeypatch):
    """A delivery of many stripe frames files four interval spans a
    frame; the pair-lifecycle instants (the critical-path walk,
    ``MetricsReportMsg.Spans``) have a ring of their own."""
    monkeypatch.setenv("DLD_SPAN_RING", "64")
    telemetry.reset_run()
    telemetry.span_event("2.1", "planned", node=0)
    for i in range(1000):
        trace.span_at("wire.recv", float(i), float(i) + 0.5)
    telemetry.span_event("2.1", "staged", node=2)
    assert [e["phase"] for e in telemetry.span_events()] == [
        "planned", "staged"]
    assert "mono" in telemetry.span_events()[0]
    assert telemetry.snapshot()["spans"] == telemetry.span_events()
    assert len(trace.spans()) == 64
    c = trace.counter_totals()
    assert c["telemetry.intervals_dropped"] == 936
    assert "telemetry.spans_dropped" not in c
    for i in range(70):  # the instants' own bound
        telemetry.span_event("2.2", "first_byte", node=2)
    assert len(telemetry.span_events()) == 64
    assert trace.counter_totals()["telemetry.spans_dropped"] == 8


def test_phase_totals_are_the_sums_of_the_ring():
    """While the ring has dropped nothing."""
    trace.span_at("fabric.collective", 10.0, 12.0)
    trace.span_at("fabric.collective", 11.0, 12.5)
    trace.add_phase("codec_encode", 0.25)
    with trace.span("fabric.upload"):
        pass
    totals = trace.phase_totals()
    sums = {}
    for s in trace.spans():
        ms, n = sums.get(s["name"], (0.0, 0))
        sums[s["name"]] = (ms + (s["t1"] - s["t0"]) * 1000.0, n + 1)
    assert set(totals) == set(sums) == {
        "fabric.collective", "codec_encode", "fabric.upload"}
    for name, (ms, n) in sums.items():
        assert totals[name]["n"] == n
        assert totals[name]["ms"] == pytest.approx(ms, abs=0.06)
    assert totals["fabric.collective"] == {"ms": 3500.0, "n": 2}
    assert telemetry.snapshot()["phases"] == totals
    # interval spans are local: the shipped section holds instants only
    assert telemetry.snapshot()["spans"] == []
    trace.reset_phases()
    assert trace.phase_totals() == {} and trace.spans() == []


def test_the_pod_summary_keeps_the_phase_names_its_readers_look_up():
    from distributed_llm_dissemination_tpu.cli.podrun import plan_phases

    trace.span_at("fabric.collective", 1.0, 3.0)
    trace.span_at("fabric.upload", 1.0, 1.5)
    out = plan_phases(trace.phase_totals())
    assert out["collective"] == out["fabric.collective"] == {
        "ms": 2000.0, "n": 1}
    assert out["upload"]["ms"] == 500.0 and "splice" not in out


def test_dump_writes_chunked_span_records_and_one_counters_record():
    import io

    from distributed_llm_dissemination_tpu.utils.logging import JsonLogger

    for i in range(trace.DUMP_CHUNK + 10):
        trace.span_at("wire.recv", float(i), float(i) + 1.0, id="2.0",
                      bytes=16)
    trace.count("xla.compiles", 3)
    buf = io.StringIO()
    n = trace.dump_spans(JsonLogger(node="2", stream=buf), since=5.5)
    recs = [json.loads(line) for line in buf.getvalue().splitlines()]
    dumps = [r for r in recs if r["message"] == "spans"]
    assert [len(r["spans"]) for r in dumps] == [trace.DUMP_CHUNK, 5]
    assert n == trace.DUMP_CHUNK + 5  # ``since`` cut the first five
    assert dumps[0]["spans"][0] == {
        "name": "wire.recv", "id": "2.0", "fields": {"bytes": 16},
        "t0": 5.0, "t1": 6.0,
        "thread": threading.current_thread().name}
    last = recs[-1]
    assert last["message"] == "span counters"
    assert last["counters"]["xla.compiles"] == 3 and last["dropped"] == 0
    assert last["mono"] <= time.monotonic() and last["wall_ms"] > 1e12


def test_compilations_are_counted_through_jax_monitoring():
    import jax
    import jax.numpy as jnp

    trace.watch_compiles()
    trace.watch_compiles()  # once per process, however often asked

    @jax.jit
    def fresh(x):
        return x * 3 + 41

    x = jnp.arange(7)
    x.block_until_ready()  # whatever making the input compiles
    before = trace.counter_totals().get("xla.compiles", 0)
    fresh(x).block_until_ready()
    c = trace.counter_totals()
    assert c["xla.compiles"] == before + 1 and c["xla.compile_ms"] >= 0
    assert isinstance(c["xla.compile_ms"], int)
    assert c.get("xla.cache_hits", 0) + c.get("xla.cache_misses", 0) \
        <= c["xla.compiles"]
    fresh(x).block_until_ready()  # in memory now
    assert trace.counter_totals()["xla.compiles"] == before + 1


# ----------------------------------------------------------- the vocabulary

HOP_SPANS = ("wire.job", "wire.fragment", "wire.send", "wire.send.write",
             "wire.serve")
# Read by ``dump_spans`` at dump time: in no registry, so pinned here.
PROC_COUNTERS = ("proc.cpu_ms", "proc.cpu_sys_ms")


def _package_source():
    out = {}
    for root, dirs, names in os.walk(PKG):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in sorted(names):
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as f:
                    out[os.path.relpath(path, PKG)] = f.read()
    return out


def test_every_span_and_counter_name_is_pinned_to_a_call_site_and_documents():
    """A renamed span must fail here, not fall silent in a metric: every
    name of the vocabulary has a live call site, every call site a name
    of the vocabulary, and PERF.md section 3 and docs/observability.md
    list them all."""
    source = _package_source()
    defining = os.path.join("utils", "telemetry.py")
    blob = "\n".join(text for path, text in source.items()
                     if path != defining)
    names = telemetry.SPAN_NAMES + telemetry.PHASE_NAMES
    missing = [n for n in names + telemetry.XLA_COUNTERS
               if f'"{n}"' not in blob]
    missing += [n for n in PROC_COUNTERS
                if f'"{n}"' not in source[defining]]
    assert not missing, f"no quoted call site in the package: {missing}"
    called = set(re.findall(
        r'trace\.(?:span|span_at|add_phase)\(\s*"([^"]+)"', blob))
    assert called - set(names) == set(), (
        f"spans recorded outside the vocabulary: {called - set(names)}")
    assert set(telemetry.SPAN_NAMES) - called == set(), (
        "vocabulary names no trace.span/span_at call records: "
        f"{set(telemetry.SPAN_NAMES) - called}")
    for doc in ("PERF.md", os.path.join("docs", "observability.md")):
        with open(os.path.join(REPO, doc)) as f:
            text = f.read()
        absent = [n for n in telemetry.SPAN_NAMES + telemetry.XLA_COUNTERS
                  + PROC_COUNTERS if f"`{n}`" not in text]
        assert not absent, f"{doc} does not list {absent}"
    # the sending side and the hand-off (ISSUE 35) are spans of the one
    # vocabulary, each with a row in PERF.md section 3's table of layers;
    # the phase they replaced has neither a name nor a row any more
    assert set(HOP_SPANS) <= set(telemetry.SPAN_NAMES)
    with open(os.path.join(REPO, "PERF.md")) as f:
        layers = f.read().split("## 3. Layers")[1].split("## 4. Cells")[0]
    rows = [line for line in layers.splitlines() if line.startswith("| ")]
    for name in HOP_SPANS + PROC_COUNTERS:
        assert any(f"`{name}`" in row for row in rows), name
    assert telemetry.PHASE_NAMES == ("codec_encode",)
    assert not any("integrity_crc_send" in row for row in rows)


def test_nothing_of_the_removed_tracing_is_left():
    """What ISSUE 24 took away because nothing read it, and the one
    phase ISSUE 35 turned into a field (``wire.send``'s ``crc_s``)."""
    blob = "\n".join(_package_source().values())
    for gone in ("tcp.rx_frame_ms", "integrity_crc_recv",
                 "integrity_digest", "boot_stream_stage",
                 "boot_stream_in_wire", "boot_precompile_in_wire",
                 "integrity_crc_send"):
        assert gone not in blob, gone


# ------------------------------------------- through the normal entry points

def _free_ports(conf):
    socks = [socket.socket() for _ in conf["Nodes"]]
    try:
        for s_, n in zip(socks, conf["Nodes"]):
            s_.bind(("127.0.0.1", 0))
            n["Addr"] = f"127.0.0.1:{s_.getsockname()[1]}"
    finally:
        for s_ in socks:
            s_.close()


def _dumped(stderr_text):
    spans, counters = [], None
    for line in stderr_text.splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if rec.get("message") == "spans":
            spans += rec["spans"]
        elif rec.get("message") == "span counters":
            counters = rec
    return spans, counters


# (On the CPU the ingest adopts the reassembly buffer: no ``ingest.write``
# and no splice.  The accelerator arm's spans are the next test's.)
DEST_SPANS = {
    "wire.recv", "wire.crc", "wire.digest", "wire.queue",
    "ingest.finalize", "ingest.finalize.wait",
    "ingest.finalize.ready", "ingest.ack", "decode.stage",
    "boot.wait_stream", "boot.assemble", "boot.first_forward",
    "boot.precompile", "serve.queue", "serve.generate", "serve.reply"}


@pytest.mark.timeout(170)
def test_cli_delivery_boot_and_one_request_dump_every_destination_span(
        tmp_path):
    """One CPU loopback delivery + boot + one request through cli.main
    and cli.genreq: every destination-side span name is in the
    destination's dump, each t0 <= t1, children inside their parents,
    all spans of a blob under its pair id; the leader dumps its plan
    spans and the requester its request."""
    blobs = {str(b): {} for b in range(5)}  # tiny: 4 layers and the head
    conf = {
        "Model": "tiny", "ModelSeed": 0,
        "Nodes": [
            {"Id": 0, "Addr": "", "NetworkBW": 10 ** 10, "IsLeader": True,
             "Sources": {"1": 0}, "InitialLayers": {"1": blobs}},
            {"Id": 1, "Addr": "", "NetworkBW": 10 ** 10, "Sources": {},
             "InitialLayers": {}},
            {"Id": 2, "Addr": "", "NetworkBW": 10 ** 10, "Sources": {},
             "InitialLayers": {}},
        ],
        "Assignment": {"1": blobs},
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [1]},
    }
    _free_ports(conf)
    conf_path = str(tmp_path / "conf.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    cli = [sys.executable, "-m",
           "distributed_llm_dissemination_tpu.cli.main", "-f", conf_path,
           "-m", "3"]
    err_path = str(tmp_path / "dest.err")
    with open(err_path, "w") as err:
        dest = subprocess.Popen(
            cli + ["-id", "1", "-hbm", "-gen", "2", "-serve", "12"],
            stdout=subprocess.PIPE, stderr=err, env=env, text=True)
    try:
        leader = subprocess.run(cli + ["-id", "0"], capture_output=True,
                                text=True, timeout=120, env=env)
        assert "Time to first token" in leader.stdout, leader.stderr[-2000:]
        for line in dest.stdout:  # up to the serve window
            if line.startswith("serving for"):
                break
        req = subprocess.run(
            [sys.executable, "-m",
             "distributed_llm_dissemination_tpu.cli.genreq", "-f",
             conf_path, "-node", "1", "-id", "2", "-prompt", "5,7,11",
             "-n", "2", "-t", "60"],
            capture_output=True, text=True, timeout=90, env=env)
        assert req.returncode == 0, req.stderr[-2000:]
        assert dest.wait(timeout=60) == 0
    finally:
        if dest.poll() is None:
            dest.kill()
    with open(err_path) as f:
        spans, counters = _dumped(f.read())
    names = {s["name"] for s in spans}
    assert DEST_SPANS - names == set(), sorted(DEST_SPANS - names)
    assert all(s["t0"] <= s["t1"] for s in spans)
    for child in spans:
        if not child.get("parent"):
            continue
        parents = [p for p in spans if p["name"] == child["parent"]
                   and p["thread"] == child["thread"]
                   and p["t0"] <= child["t0"] and child["t1"] <= p["t1"]]
        assert parents, child
    blob_ids = {telemetry.span_id(1, b) for b in range(5)}
    for name in ("wire.recv", "wire.crc", "wire.queue", "ingest.finalize",
                 "ingest.finalize.wait", "ingest.ack", "decode.stage"):
        assert {s["id"] for s in spans if s["name"] == name} == blob_ids, name
    # one request: its three spans share one id
    assert len({s["id"] for s in spans
                if s["name"].startswith("serve.")}) == 1
    staged = [s for s in spans if s["name"] == "decode.stage"]
    assert all("in_wire" in s["fields"] for s in staged)
    assert counters["counters"]["xla.compiles"] > 0  # a cold cache
    assert counters["dropped"] == 0
    lspans, _ = _dumped(leader.stderr)
    assert {s["name"] for s in lspans} >= {"plan.solve", "plan.dispatch"}
    rspans, _ = _dumped(req.stderr)
    assert [s["name"] for s in rspans] == ["serve.request"]
    # wait_ms rides "layer staged to HBM" beside stage_ms
    with open(err_path) as f:
        staged_recs = [json.loads(line) for line in f
                       if '"layer staged to HBM"' in line]
    assert staged_recs and all(
        0 <= r["wait_ms"] <= r["stage_ms"] for r in staged_recs)


def test_the_accelerator_arm_of_the_ingest_records_write_and_splice():
    import jax
    import numpy as np

    from distributed_llm_dissemination_tpu.parallel.ingest import (
        ShardedLayerIngest,
    )

    data = np.arange(4096, dtype=np.uint8).tobytes()
    ing = ShardedLayerIngest(len(data), jax.devices()[:1], stream=True,
                             trace_id="2.5", node=2)
    for off in (2048, 0):
        ing.write(off, data[off:off + 2048])
    with trace.span("ingest.finalize", id="2.5", node=2):
        arr = ing.finalize()
    assert bytes(np.asarray(arr)) == data
    writes = by_name("ingest.write")
    assert [w["fields"] for w in writes] == [
        {"offset": 2048, "bytes": 2048}, {"offset": 0, "bytes": 2048}]
    wait, = by_name("ingest.finalize.wait")
    splice, = by_name("ingest.finalize.splice")
    outer, = by_name("ingest.finalize")
    for rec in (*writes, wait, splice):
        assert (rec["id"], rec["node"]) == ("2.5", 2)
    assert wait["parent"] == splice["parent"] == "ingest.finalize"
    assert outer["t0"] <= wait["t0"] <= wait["t1"] <= splice["t0"]
    assert ing.waited_s == pytest.approx(wait["t1"] - wait["t0"])


# -------------------------------------------------- in a profiler's capture

@pytest.mark.timeout(120)
def test_spans_land_in_a_profiler_capture_as_annotations(tmp_path):
    """The harness's own loader finds the program's spans in the host
    plane of a CPU capture, and the program's tool splits the idle gaps
    by them."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    from benchmark import xplane

    from distributed_llm_dissemination_tpu.cli import trace as trace_cli

    x = jnp.ones((128, 128))
    (x @ x).block_until_ready()  # compiled before the capture
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.round"):
            with trace.span("ingest.finalize", id="2.3", node=2):
                with trace.span("ingest.finalize.wait"):
                    time.sleep(0.02)
                (x @ x).block_until_ready()
            time.sleep(0.01)  # nothing of the program covers this
    finally:
        jax.profiler.stop_trace()
    planes = xplane.load(xplane.find_trace(str(tmp_path)))
    found = {n: (s, d) for p in planes if p["name"].startswith("/host:")
             for line in p["lines"] for n, s, d in line["events"]
             if n.startswith(("ingest.", "bench."))}
    assert set(found) == {"bench.round", "ingest.finalize",
                          "ingest.finalize.wait"}
    (s0, d0), (s1, d1) = found["ingest.finalize"], found[
        "ingest.finalize.wait"]
    assert s0 <= s1 and s1 + d1 <= s0 + d0 and d1 >= 0.02e9
    # the same interval on the two clocks, within the capture's overhead
    rec = [s for s in trace.spans() if s["name"] == "ingest.finalize.wait"]
    assert (rec[0]["t1"] - rec[0]["t0"]) * 1e9 == pytest.approx(d1, rel=0.2)

    table = trace_cli.idle_gap_table(
        trace_cli.load_xplane(str(tmp_path)), device_plane="/host:CPU",
        op_lines=("tf_XLAPjRtCpuClient",), window_event="bench.round")
    every = table["all_gaps"]
    assert table["span_names"] == ["ingest.finalize",
                                   "ingest.finalize.wait"]
    assert every["by_span_s"]["ingest.finalize.wait"] >= 0.02
    assert 0.009 <= every["by_span_s"]["uncovered"] <= every["idle_s"]
    assert every["by_span_s"]["ingest.finalize"] <= every["idle_s"]
    narrowed = trace_cli.idle_gap_table(
        trace_cli.load_xplane(str(tmp_path)), device_plane="/host:CPU",
        op_lines=("tf_XLAPjRtCpuClient",),
        from_span="ingest.finalize.wait", to_span="ingest.finalize.wait")
    assert narrowed["window_s"] == pytest.approx(d1 * 1e-9, rel=1e-3)


def test_the_gap_table_of_a_chip_trace_without_spans_is_all_uncovered():
    from distributed_llm_dissemination_tpu.cli import trace as trace_cli

    table = trace_cli.idle_gap_table(trace_cli.load_xplane(os.path.join(
        REPO, "benchmark", "testdata", "small.xplane.pb")),
        window_event="bench.round")
    assert table["span_names"] == []
    every = table["all_gaps"]
    assert every["by_span_s"] == {"uncovered": every["idle_s"]}
    assert every["idle_s"] + table["busy_s"] == pytest.approx(
        table["window_s"], abs=1e-5)
    assert len(table["longest_gaps"]) == 5
