"""The hop that sets the pace, spanned on both ends (ISSUE 35): the
sending process's ``wire.job`` → ``wire.fragment`` → ``wire.send`` →
``wire.send.write``, the receive pool's ``wire.serve`` around a frame's
``wire.recv`` and ``wire.crc``, every seat's ``proc.cpu_ms``, and what
``cli.trace`` makes of them: the hop block and the clock bridge that lays
a seat without a profiler onto a capture's clock.

Over loopback TCP at KiB sizes, the stripe threshold lowered by the
knobs ``tests/test_pacing.py`` uses."""

import io
import json
import socket
import threading
import time

import pytest

from distributed_llm_dissemination_tpu.cli import trace as cli_trace
from distributed_llm_dissemination_tpu.core.types import (
    LayerLocation,
    LayerMeta,
    LayerSrc,
)
from distributed_llm_dissemination_tpu.runtime import Node
from distributed_llm_dissemination_tpu.runtime import send as send_mod
from distributed_llm_dissemination_tpu.transport import LayerMsg, TcpTransport
from distributed_llm_dissemination_tpu.transport import tcp as tcp_mod
from distributed_llm_dissemination_tpu.transport.messages import (
    FlowRetransmitMsg,
)
from distributed_llm_dissemination_tpu.utils import telemetry, threads, trace
from distributed_llm_dissemination_tpu.utils.logging import JsonLogger

RECV_TIMEOUT = 15.0
PAIR = telemetry.span_id(1, 5)  # every frame below is layer 5 for seat 1
STRIPE = 64 * 1024
FRAGMENT = 4 * STRIPE
SIZE = 4 * FRAGMENT


def by_name(name, spans=None):
    return [s for s in (trace.spans() if spans is None else spans)
            if s["name"] == name]


@pytest.fixture
def small_stripes(monkeypatch):
    """A fragment of 256 KiB goes out as four stripes of 64 KiB, and a
    commanded rate of a few MiB/s counts as a budget."""
    monkeypatch.setattr(tcp_mod, "STRIPE_THRESHOLD", STRIPE)
    monkeypatch.setattr(tcp_mod, "STRIPE_MIN", 16 * 1024)
    monkeypatch.setattr(tcp_mod, "STRIPE_COUNT", 4)
    monkeypatch.setattr(tcp_mod, "STRIPE_PACED_MIN_RATE", 10 ** 6)
    monkeypatch.setattr(send_mod, "FLOW_FRAGMENT_BYTES", STRIPE)


@pytest.fixture
def pair():
    """A sender (seat 0) and a destination (seat 1) over loopback."""
    ts = [TcpTransport("127.0.0.1:0") for _ in range(2)]
    for t in ts:
        t.addr_registry.update({i: x.get_address()
                                for i, x in enumerate(ts)})
    nodes = [Node(i, 0, t) for i, t in enumerate(ts)]
    yield nodes
    for t in ts:
        t.close()


def payload(size=SIZE) -> bytes:
    return bytes((i * 31 + 7) % 256 for i in range(size))


def mem_layer(data: bytes) -> LayerSrc:
    return LayerSrc(inmem_data=bytearray(data), data_size=len(data),
                    meta=LayerMeta(location=LayerLocation.INMEM))


def run_job(nodes, data: bytes, rate: int) -> bytes:
    """One flow job of layer 5 from seat 0 to seat 1; returns what the
    destination holds once every frame has landed and every receive
    thread has handed its connection back."""
    send_mod.handle_flow_retransmit(
        nodes[0], {5: mem_layer(data)}, threading.Lock(),
        lambda lid, dest: None,
        FlowRetransmitMsg(0, 5, 1, len(data), 0, rate, job_id="j1"))
    got, have = bytearray(len(data)), 0
    while have < len(data):
        src = nodes[1].transport.deliver().get(
            timeout=RECV_TIMEOUT).layer_src
        got[src.offset:src.offset + src.data_size] = bytes(src.inmem_data)
        have += src.data_size
    deadline = time.monotonic() + RECV_TIMEOUT
    while len(by_name("wire.serve")) < len(by_name("wire.send")):
        assert time.monotonic() < deadline, "a receive thread never ended"
        time.sleep(0.005)
    return bytes(got)


def inside(child, parent) -> bool:
    return parent["t0"] <= child["t0"] and child["t1"] <= parent["t1"]


# ------------------------------------------------------- the sending side

def test_a_striped_flow_job_files_job_fragments_and_a_send_and_write_a_frame(
        small_stripes, pair):
    data = payload()
    assert run_job(pair, data, rate=0) == data
    job, = by_name("wire.job")
    frags, sends = by_name("wire.fragment"), by_name("wire.send")
    writes = by_name("wire.send.write")
    assert job["fields"] == {"job": "j1", "bytes": SIZE, "rate": 0,
                             "codec": "", "fragments": 4}
    assert (job["parent"], job["node"]) == (None, 0)
    assert len(frags) == 4 and len(sends) == len(writes) == 16
    assert {s["id"] for s in [job, *frags, *sends, *writes]} == {PAIR}
    assert {s["node"] for s in [*frags, *sends, *writes]} == {0}
    # the fragments are the job's children by thread, one after another
    assert all(f["parent"] == "wire.job" and f["thread"] == job["thread"]
               and inside(f, job) for f in frags)
    assert sorted(f["fields"]["offset"] for f in frags) == [
        k * FRAGMENT for k in range(4)]
    for f in frags:
        assert f["fields"]["bytes"] == FRAGMENT
        assert f["fields"]["streams"] == 4
        assert f["fields"]["barrier_s"] >= 0 and f["fields"]["stolen"] >= 0
    # a frame: one wire.send under its fragment (named, it may run on a
    # data-tx thread) and its wire.send.write under it by thread
    assert sorted(s["fields"]["offset"] for s in sends) == [
        k * STRIPE for k in range(16)]
    for s in sends:
        assert s["parent"] == "wire.fragment"
        frag, = [f for f in frags if f["fields"]["offset"]
                 <= s["fields"]["offset"]
                 < f["fields"]["offset"] + FRAGMENT]
        assert inside(s, frag)
        assert s["fields"]["stripe"] == (
            s["fields"]["offset"] - frag["fields"]["offset"]) // STRIPE
        assert s["fields"]["attempts"] == 1
        assert s["fields"]["conn"] in ("pooled", "dialed")
        assert ("dial_s" in s["fields"]) == (s["fields"]["conn"] == "dialed")
        assert 0 <= s["fields"]["crc_s"] < 1.0
        w, = [w for w in writes
              if w["fields"]["offset"] == s["fields"]["offset"]]
        assert w["parent"] == "wire.send" and w["thread"] == s["thread"]
        assert inside(w, s) and w["fields"]["bytes"] == STRIPE
        assert 0 <= w["fields"]["cpu"] <= w["t1"] - w["t0"] + 0.01
    # the caller's own stripe never queued; a pooled one was handed over
    # before a thread started on it
    for s in sends:
        if s["fields"]["stripe"] == 0:
            assert s["fields"]["queued_s"] == 0.0
            assert s["thread"] == job["thread"]
        else:
            assert s["fields"]["queued_s"] >= 0.0
    assert sum(s["fields"]["stripe"] == 0 for s in sends) == 4
    # the old phase is gone, its number is crc_s
    assert "integrity_crc_send" not in trace.phase_totals()


def test_every_frame_sent_joins_one_frame_received_and_the_bytes_add_up(
        small_stripes, pair):
    data = payload()
    assert run_job(pair, data, rate=0) == data
    sends, recvs = by_name("wire.send"), by_name("wire.recv")
    key = lambda s: (s["id"], s["fields"]["offset"])  # noqa: E731
    assert sorted(map(key, sends)) == sorted(map(key, recvs))
    assert len(set(map(key, sends))) == len(sends) == 16
    assert sum(s["fields"]["bytes"] for s in sends) == sum(
        r["fields"]["bytes"] for r in recvs) == SIZE
    assert {r["node"] for r in recvs} == {1}
    # one clock on both ends: a frame is not read before it is written
    writes = by_name("wire.send.write")
    lags = cli_trace.hop_lags(writes, recvs)
    assert len(lags) == 16 and min(lags) > -0.001


def test_an_unstriped_fragment_is_one_frame_on_the_callers_thread(pair):
    """Under the stripe threshold: one stream, no barrier."""
    data = payload(32 * 1024)
    pair[0].transport.send(1, LayerMsg(0, 5, mem_layer(data), len(data)))
    assert bytes(pair[1].transport.deliver().get(
        timeout=RECV_TIMEOUT).layer_src.inmem_data) == data
    frag, = by_name("wire.fragment")
    send, = by_name("wire.send")
    write, = by_name("wire.send.write")
    assert frag["fields"] == {"bytes": len(data), "offset": 0,
                              "streams": 1, "barrier_s": 0.0, "stolen": 0}
    # no flow job stamped a span id: the pair id is minted from the seat
    assert frag["id"] == send["id"] == write["id"] == PAIR
    assert frag["parent"] is None and send["parent"] == "wire.fragment"
    assert send["fields"]["stripe"] == 0 and send["fields"]["queued_s"] == 0
    assert write["parent"] == "wire.send"
    assert send["thread"] == write["thread"] == frag["thread"]


def test_a_paced_jobs_sleep_is_a_child_of_the_write_it_interrupts(
        small_stripes, pair):
    """1 MiB at 16 MiB/s and a burst of 256 KiB: the job is ahead of its
    plan after the first fragment and sleeps inside the writes."""
    data = payload()
    t0 = time.monotonic()
    assert run_job(pair, data, rate=16 << 20) == data
    assert time.monotonic() - t0 >= (SIZE - 256 * 1024) / (16 << 20) - 0.01
    paces, writes = by_name("wire.pace"), by_name("wire.send.write")
    assert paces
    for p in paces:
        assert p["parent"] == "wire.send.write" and p["id"] == PAIR
        assert [w for w in writes if w["thread"] == p["thread"]
                and inside(p, w)], p
    job, = by_name("wire.job")
    assert job["fields"]["rate"] == 16 << 20
    # a write's seconds are its CPU, a full socket, or these sleeps
    slept = sum(p["t1"] - p["t0"] for p in paces)
    assert sum(w["t1"] - w["t0"] for w in writes) >= slept * 0.99


def test_a_send_that_fails_once_redials_and_says_so(pair):
    tx = pair[0].transport
    a, b = socket.socketpair()
    a.close()
    b.close()  # a pooled connection that died while it idled
    tx._data_pool[tx.addr_registry[1]] = [a]
    data = payload(32 * 1024)
    tx.send(1, LayerMsg(0, 5, mem_layer(data), len(data)))
    assert bytes(pair[1].transport.deliver().get(
        timeout=RECV_TIMEOUT).layer_src.inmem_data) == data
    send, = by_name("wire.send")
    assert send["fields"]["attempts"] == 2
    assert send["fields"]["conn"] == "redialed"
    assert send["fields"]["dial_s"] > 0
    first, second = by_name("wire.send.write")  # the retry is inside
    assert "error" in first["fields"] and "error" not in second["fields"]
    assert inside(first, send) and inside(second, send)
    assert "error" not in send["fields"]


def test_run_all_returns_how_many_queued_tasks_the_caller_stole():
    pool = threads.WorkerPool(1, "steal-test")  # no data-plane name: its
    # worker outlives the test and would count in the thread census
    busy, release, ran = threading.Event(), threading.Event(), []

    def block():
        busy.set()
        assert release.wait(RECV_TIMEOUT)

    blocker = pool.submit(block)
    assert busy.wait(RECV_TIMEOUT)  # the one worker is taken
    try:
        stolen = pool.run_all(
            [(lambda k: ran.append((k, threading.current_thread().name)),
              k) for k in range(4)])
    finally:
        release.set()
    assert blocker.wait(RECV_TIMEOUT)
    # the first call is the caller's own; it stole the other three
    assert stolen == 3 and sorted(k for k, _ in ran) == [0, 1, 2, 3]
    assert {name for _, name in ran} == {threading.current_thread().name}
    assert pool.run_all([]) == 0
    assert pool.run_all([(ran.append, "alone")]) == 0


# ------------------------------------------------------ the receiving side

def test_wire_serve_contains_the_frames_recv_and_crc(small_stripes, pair):
    data = payload()
    assert run_job(pair, data, rate=0) == data
    serves = by_name("wire.serve")
    assert len(serves) == 16
    for sv in serves:
        assert (sv["id"], sv["node"], sv["parent"]) == (PAIR, 1, None)
        assert sv["thread"].startswith("data-rx")
        assert sv["fields"]["bytes"] == STRIPE
        assert sv["fields"]["queued_s"] >= 0.0
        inner = [s for s in trace.spans() if s["thread"] == sv["thread"]
                 and s["parent"] == "wire.serve" and inside(s, sv)]
        assert sorted(s["name"] for s in inner) == ["wire.crc", "wire.recv"]
        assert {s["fields"].get("offset", sv["fields"]["offset"])
                for s in inner} == {sv["fields"]["offset"]}
    assert sorted(sv["fields"]["offset"] for sv in serves) == [
        k * STRIPE for k in range(16)]


# ---------------------------------------------------------- the seats' CPU

def burn(seconds: float) -> None:
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        sum(range(1000))


def dumped_counters() -> dict:
    buf = io.StringIO()
    trace.dump_spans(JsonLogger(node="0", stream=buf))
    rec = json.loads(buf.getvalue().splitlines()[-1])
    assert rec["message"] == "span counters"
    return rec["counters"]


def test_proc_cpu_restarts_at_reset_run_and_grows_between_dumps():
    telemetry.reset_run()
    burn(0.05)
    first = dumped_counters()
    assert 40 <= first["proc.cpu_ms"] < 5000
    assert 0 <= first["proc.cpu_sys_ms"] <= first["proc.cpu_ms"]
    burn(0.05)
    second = dumped_counters()
    assert second["proc.cpu_ms"] >= first["proc.cpu_ms"] + 40
    assert second["proc.cpu_sys_ms"] >= first["proc.cpu_sys_ms"]
    telemetry.reset_run()
    assert dumped_counters()["proc.cpu_ms"] < first["proc.cpu_ms"]
    # read at dump time, never stored: the registry holds no such counter
    assert "proc.cpu_ms" not in trace.counter_totals()
    # a registry that never reset counts from the process's start
    assert telemetry.Telemetry().proc_cpu()["proc.cpu_ms"] >= second[
        "proc.cpu_ms"]


# --------------------------------------------------- cli.trace: the hop block

def sp(name, t0, t1, node, thread="t", id=PAIR, parent=None, **fields):
    rec = {"name": name, "t0": t0, "t1": t1, "thread": thread, "id": id,
           "node": node, "fields": fields}
    if parent:
        rec["parent"] = parent
    return rec


def two_seat_records():
    """A leader (seat 0) that sends one job of one fragment in two
    frames, and a destination (seat 1) that reads them."""
    leader = [
        sp("wire.job", 10.0, 12.0, 0, job="j", bytes=2 << 20,
           rate=4 << 20, fragments=1, codec=""),
        sp("wire.fragment", 10.0, 12.0, 0, parent="wire.job",
           bytes=2 << 20, offset=0, streams=2, barrier_s=0.5, stolen=0),
        sp("wire.send", 10.0, 11.5, 0, parent="wire.fragment",
           bytes=1 << 20, offset=0, stripe=0, queued_s=0.0, crc_s=0.01),
        sp("wire.send.write", 10.25, 11.5, 0, parent="wire.send",
           bytes=1 << 20, offset=0, cpu=0.25),
        sp("wire.pace", 10.5, 10.75, 0, parent="wire.send.write"),
        sp("wire.send", 10.5, 12.0, 0, "data-tx-0", parent="wire.fragment",
           bytes=1 << 20, offset=1 << 20, stripe=1, queued_s=0.5,
           crc_s=0.01),
        sp("wire.send.write", 10.5, 12.0, 0, "data-tx-0",
           parent="wire.send", bytes=1 << 20, offset=1 << 20, cpu=0.5),
    ]
    dest = [
        sp("wire.serve", 10.375, 11.75, 1, "data-rx-0", bytes=1 << 20,
           offset=0, queued_s=0.125),
        sp("wire.recv", 10.5, 11.5, 1, "data-rx-0", parent="wire.serve",
           bytes=1 << 20, offset=0),
        sp("wire.crc", 11.5, 11.625, 1, "data-rx-0", parent="wire.serve"),
        sp("wire.serve", 10.75, 12.5, 1, "data-rx-1", bytes=1 << 20,
           offset=1 << 20, queued_s=0.25),
        sp("wire.recv", 11.0, 12.0, 1, "data-rx-1", parent="wire.serve",
           bytes=1 << 20, offset=1 << 20),
    ]
    return [
        {"message": "spans", "node": "0", "spans": leader},
        {"message": "span counters", "node": "0", "dropped": 0,
         "counters": {"proc.cpu_ms": 1400, "proc.cpu_sys_ms": 600}},
        {"message": "spans", "node": "1", "spans": dest},
        {"message": "span counters", "node": "1", "dropped": 0,
         "counters": {"proc.cpu_ms": 3100, "proc.cpu_sys_ms": 900}},
    ]


def test_the_hop_block_adds_a_delivery_up_from_both_ends():
    hop = cli_trace.wire_hop(two_seat_records())
    row, = hop["jobs"]
    assert row == {
        "seat": "0", "id": PAIR, "job": "j", "bytes": 2 << 20,
        "commanded_mibps": 4.0, "achieved_mibps": 1.0, "fragments": 1,
        "frames": 2, "write_s": 2.75, "write_cpu_s": 0.75,
        "barrier_s": 0.5, "send_queued_s": 0.5, "serve_queued_s": 0.375,
        "pace_s": 0.25, "crc_s": 0.02}
    # job start to the last byte read; the write at 10.25 leaves a
    # quarter of a second with nothing being written
    assert hop["delivery_s"] == 2.0
    assert hop["writing"] == {"0": 0.25, "1-3": 1.75, "4-7": 0.0, "8+": 0.0}
    assert hop["reading"] == {"0": 0.5, "1-3": 1.5, "4-7": 0.0, "8+": 0.0}
    assert (hop["frames_joined"], hop["frames_read"]) == (2, 2)
    assert hop["lag_ms"] == {"median": 375.0, "p90": 500.0}
    # a frame's self time splits at its first read: picked up an eighth
    # and a quarter of a second before it, handed back an eighth (after
    # the checksum) and a half (after the read: no checksum there) after
    assert hop["receive_pools"] == {"1": {
        "threads": 2, "frames": 2, "busy_s": 3.125, "occupancy": 0.7812,
        "queued_s": 0.375, "self_s": 1.0,
        "before_read_s": 0.375,
        "before_read_ms": {"median": 187.5, "p90": 250.0},
        "after_verify_s": 0.625,
        "after_verify_ms": {"median": 312.5, "p90": 500.0}}}
    assert hop["cpu_ms"] == {"0": {"cpu_ms": 1400, "sys_ms": 600},
                             "1": {"cpu_ms": 3100, "sys_ms": 900}}
    out = io.StringIO()
    cli_trace.print_wire_hop(hop, out)
    text = out.getvalue()
    assert "4.0 -> 1.0 MiB/s" in text and "occupancy 0.7812;" in text
    assert ("before the first wire.recv 0.375 s (a frame: median 187.5 ms, "
            "p90 250.0 ms), after the last child 0.625 s (median 312.5 ms, "
            "p90 500.0 ms)") in text
    assert "median 375.0 ms, p90 500.0 ms" in text
    assert "seat 0: proc.cpu_ms 1400 (system 600)" in text
    # logs without a flow job have no hop to show
    assert cli_trace.wire_hop(two_seat_records()[2:]) == {}


def test_the_hop_block_of_a_real_job_reads_both_ends(small_stripes, pair,
                                                     capsys, tmp_path):
    data = payload()
    assert run_job(pair, data, rate=0) == data
    buf = io.StringIO()
    trace.dump_spans(JsonLogger(node="0", stream=buf))
    log = tmp_path / "seats.jsonl"
    log.write_text(buf.getvalue())
    hop = cli_trace.wire_hop(json.loads(line)
                             for line in buf.getvalue().splitlines())
    row, = hop["jobs"]
    assert (row["seat"], row["frames"], row["fragments"]) == ("0", 16, 4)
    assert row["bytes"] == SIZE and row["achieved_mibps"] > 0
    assert hop["frames_joined"] == hop["frames_read"] == 16
    assert hop["receive_pools"]["1"]["frames"] == 16
    assert 0 < hop["receive_pools"]["1"]["occupancy"] <= 1.0
    assert sum(hop["writing"].values()) == pytest.approx(
        hop["delivery_s"], abs=1e-4)
    assert cli_trace.main([str(log), "-o", str(tmp_path / "t.json")]) == 0
    err = capsys.readouterr().err
    assert "the wire hop: 1 jobs" in err and "receive pool" in err


# ------------------------------------------------ cli.trace: the clock bridge

def test_the_bridge_is_the_median_offset_of_the_spans_in_both():
    """Seat 1 is in the capture (its spans are annotations 1000 s
    later, to a few microseconds); seat 0 is not."""
    dumps = cli_trace.dumped_spans(two_seat_records())
    both = [s for s in dumps["1"] if s["name"] != "wire.crc"]
    jitter = [2e-6, 4e-6, 6e-6, 8e-6]
    ann = [(s["name"], 1.5, (s["t0"] + 1000.0 + j) * 1e9)
           for s, j in zip(both, jitter)]  # the id "1.5" comes back a number
    bridge = cli_trace.clock_bridge(ann, dumps)
    assert bridge["seats"] == ["1"] and bridge["others"] == ["0"]
    assert bridge["matched"] == 4
    assert bridge["offset_s"] == pytest.approx(1000.0 + 5e-6, abs=1e-6)
    assert bridge["spread_s"] == pytest.approx(5e-6, abs=1e-6)
    # a spread over a millisecond is no bridge, and neither is no match
    far = [(n, i, t + k * 3e6) for k, (n, i, t) in enumerate(ann)]
    with pytest.raises(SystemExit, match="refused"):
        cli_trace.clock_bridge(far, dumps)
    with pytest.raises(SystemExit, match="same round"):
        cli_trace.clock_bridge([("boot.assemble", None, 5.0)], dumps)


@pytest.mark.timeout(120)
def test_a_seat_without_a_profiler_joins_the_captures_gap_table(tmp_path):
    """The process that is captured records ``wire.recv`` spans (in the
    capture as annotations, in its dump on CLOCK_MONOTONIC); a second
    seat's dump, made up on the same clock, has the sending side's
    spans.  With the logs beside the capture the table has columns for
    ``wire.send.write``, ``wire.fragment`` and ``wire.job``; without
    them it is what it was."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((128, 128))
    (x @ x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "cap"), profiler_options=opts)
    try:
        t_job = time.monotonic()
        (x @ x).block_until_ready()
        for k in range(4):
            with trace.span("wire.recv", id=PAIR, node=1, offset=k):
                time.sleep(0.005)
        (x @ x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    buf = io.StringIO()
    trace.dump_spans(JsonLogger(node="1", stream=buf))
    first = by_name("wire.recv")[0]
    leader = [sp("wire.job", t_job, first["t1"], 0),
              sp("wire.fragment", first["t0"], first["t1"], 0),
              sp("wire.send.write", first["t0"], first["t1"], 0)]
    log = tmp_path / "seats.jsonl"
    log.write_text(buf.getvalue() + json.dumps(
        {"message": "spans", "node": "0", "spans": leader}) + "\n")

    ann = []
    planes = cli_trace.load_xplane(str(tmp_path / "cap"), ann)
    assert sorted(a[0] for a in ann) == ["wire.recv"] * 4
    cpu = {"device_plane": "/host:CPU", "op_lines": ("tf_XLAPjRtCpuClient",)}
    alone = cli_trace.idle_gap_table(planes, **cpu)
    assert alone["span_names"] == ["wire.recv"]
    with open(log) as f:
        joined, bridge = cli_trace.bridged_planes(
            planes, ann, (json.loads(line) for line in f))
    assert bridge["seats"] == ["1"] and bridge["others"] == ["0"]
    assert bridge["matched"] == 4 and bridge["spread_s"] < 1e-3
    table = cli_trace.idle_gap_table(joined, **cpu)
    assert table["span_names"] == ["wire.fragment", "wire.job", "wire.recv",
                                   "wire.send.write"]
    assert (table["window_s"], table["busy_s"]) == (
        alone["window_s"], alone["busy_s"])
    gaps = table["all_gaps"]["by_span_s"]
    # the made-up write is the first receive's twin: the same seconds of
    # idle device lie under both, to the bridge's spread
    assert gaps["wire.send.write"] == pytest.approx(
        min(gaps["wire.recv"], first["t1"] - first["t0"]), abs=2e-3)
    assert gaps["wire.job"] >= gaps["wire.send.write"] > 0.004


def test_the_tool_without_logs_prints_what_it_printed_and_refuses_strangers(
        tmp_path, capsys):
    """On the capture recorded on the v5e: no logs, the table alone,
    key for key; logs of another round are no bridge."""
    import os

    small = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "testdata",
        "small.xplane.pb")
    assert cli_trace.main(["--xplane", small]) == 0
    said = capsys.readouterr()
    assert json.loads(said.out) == cli_trace.idle_gap_table(
        cli_trace.load_xplane(small))
    assert "clock_bridge" not in said.out and "bridge" not in said.err
    log = tmp_path / "seats.jsonl"
    log.write_text("".join(json.dumps(r) + "\n"
                           for r in two_seat_records()))
    with pytest.raises(SystemExit, match="same round"):
        cli_trace.main(["--xplane", small, str(log)])
