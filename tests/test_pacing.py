"""One pacer a flow job (ISSUE 32): a job's fragments and stripes write
through the same ``JobPacer``, which holds the JOB under
``burst + rate * (now - the job's first byte)``; a message that brings no
pacer is paced alone by ``PacedWriter``, to the byte and to the wait.

Wherever a time is asserted the clock is the test's: ``sleep`` advances
it and nothing else does, so nothing waits and nothing is unsteady.
"""

import functools
import sys
import threading
import types

import pytest

from distributed_llm_dissemination_tpu.core.types import (
    LayerLocation,
    LayerMeta,
    LayerSrc,
)
from distributed_llm_dissemination_tpu.runtime import Node
from distributed_llm_dissemination_tpu.runtime import send as send_mod
from distributed_llm_dissemination_tpu.transport import (
    LayerMsg,
    TcpTransport,
    reset_registry,
)
from distributed_llm_dissemination_tpu.transport import tcp as tcp_mod
from distributed_llm_dissemination_tpu.transport.messages import (
    FlowRetransmitMsg,
    LayerNackMsg,
)
from distributed_llm_dissemination_tpu.utils import rate as rate_mod
from distributed_llm_dissemination_tpu.utils import threads, trace
from distributed_llm_dissemination_tpu.utils.rate import (
    DEFAULT_BURST,
    JobPacer,
    PacedWriter,
    TokenBucket,
    effective_burst,
)

RECV_TIMEOUT = 15.0
RATE = 200 * DEFAULT_BURST  # 50 MiB/s: one quantum of 256 KiB is 5 ms
QUANTUM_S = DEFAULT_BURST / RATE


class FakeClock:
    """A wall clock on which only sleeps take time (and ``tick`` more on
    every reading, for tests in which time passes between writes).
    ``sleep(d)`` ends ``d`` after the sleeping thread's last reading, as
    a real sleep sized from that reading does, so the sleeps of threads
    that wait together overlap."""

    def __init__(self, tick: float = 0.0):
        self.t = 100.0
        self.tick = tick
        self.sleeps = []
        self._lock = threading.Lock()
        self._read = threading.local()

    def now(self) -> float:
        with self._lock:
            self.t += self.tick
            self._read.t = self.t
            return self.t

    def sleep(self, d: float) -> None:
        with self._lock:
            self.sleeps.append(d)
            # A hair over, as every real sleep is: the budget a sleep
            # was sized for is there when it ends, whatever the floats.
            self.t = max(self.t, self._read.t + d + 1e-9)

    def as_time_module(self):
        """What ``utils/rate.py`` reads as ``time`` (``TokenBucket``)."""
        return types.SimpleNamespace(monotonic=self.now, sleep=self.sleep)


def counters_since(before: dict) -> dict:
    now = trace.counter_totals()
    return {k: now.get(k, 0) - before.get(k, 0)
            for k in ("wire.pace.job_bytes", "wire.pace.wait_ms")}


@pytest.fixture(autouse=True)
def _clean():
    reset_registry()
    yield
    reset_registry()


# ------------------------------------------------------------- the pacer


@pytest.mark.parametrize("n_threads", [2, 4, 8])
def test_threads_through_one_job_pacer_never_pass_the_ceiling(n_threads):
    """(1) At every write the job's cumulative bytes are at most
    ``burst + rate * elapsed`` — whichever thread writes, however the
    threads interleave, with time passing between the writes."""
    clock = FakeClock(tick=QUANTUM_S / 7)
    pacer = JobPacer(RATE, clock=clock.now, sleep=clock.sleep)
    seen = []  # (cumulative bytes, clock) at each write
    lock = threading.Lock()
    total = [0]

    def write(chunk):
        with lock:
            total[0] += len(chunk)
            seen.append((total[0], clock.now()))

    t_first = clock.t  # no later than the job's first byte
    payload = bytes(6 * DEFAULT_BURST + 1234)
    workers = [threading.Thread(target=pacer.write, args=(write, payload))
               for _ in range(n_threads)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # interleave the threads as hard as can be
    try:
        for w in workers:
            w.start()
        for w in workers:
            w.join(RECV_TIMEOUT)
    finally:
        sys.setswitchinterval(switch)
    assert not any(w.is_alive() for w in workers)
    assert total[0] == n_threads * len(payload)
    assert len(seen) == n_threads * 7
    for cum, now in seen:
        assert cum <= pacer.burst + RATE * (now - t_first) + 1e-3, (cum, now)
    # ... and the pacer held the job TO its plan, not under it: the last
    # byte went out within a quantum of size / rate.
    assert seen[-1][1] - t_first <= total[0] / RATE + QUANTUM_S


def test_a_stripe_that_starts_late_catches_up_then_sleeps():
    """(2) The job sent one quantum and then nothing for k quanta (its
    next stripe queued for a worker): the late stripe writes k quanta
    without a sleep — the job is back on its plan — and the next one
    sleeps a quantum, as a job that is ahead does."""
    k = 5
    clock = FakeClock()
    pacer = JobPacer(RATE, clock=clock.now, sleep=clock.sleep)
    out = bytearray()
    before = trace.counter_totals()
    pacer.write(out.extend, bytes(DEFAULT_BURST))  # the job's first byte
    clock.t += k * QUANTUM_S  # queued: nobody writes
    pacer.write(out.extend, bytes(k * DEFAULT_BURST))
    assert clock.sleeps == []
    assert counters_since(before)["wire.pace.wait_ms"] == 0
    pacer.write(out.extend, bytes(DEFAULT_BURST))
    assert clock.sleeps == [pytest.approx(QUANTUM_S)]
    assert len(out) == (k + 2) * DEFAULT_BURST
    # A token bucket of the same rate forfeits the idle time: it holds
    # one quantum at most, so the same k quanta cost it k - 1 sleeps.
    lost = FakeClock()
    bucket = TokenBucket(RATE)
    bucket._last = lost.t
    rate_time = rate_mod.time
    rate_mod.time = lost.as_time_module()
    try:
        bucket.wait_n(DEFAULT_BURST)
        lost.t += k * QUANTUM_S
        for _ in range(k):
            bucket.wait_n(DEFAULT_BURST)
    finally:
        rate_mod.time = rate_time
    assert len(lost.sleeps) == k - 1


@pytest.mark.parametrize("rate", [4 << 20, RATE, 10 ** 10])
def test_a_job_ahead_of_its_plan_sleeps_as_a_token_bucket_does(
        rate, monkeypatch):
    """(3) Written back to back, a job makes the ``sendall`` sizes and
    the sleeps that a ``PacedWriter`` of its rate makes today."""
    payload = bytes(5 * effective_burst(rate) + 999)
    clock = FakeClock()
    sizes = []
    JobPacer(rate, clock=clock.now, sleep=clock.sleep).write(
        lambda c: sizes.append(len(c)), payload)

    old = FakeClock()
    monkeypatch.setattr(rate_mod, "time", old.as_time_module())
    old_sizes = []
    PacedWriter(lambda c: old_sizes.append(len(c)), rate).write(payload)
    assert sizes == old_sizes
    assert clock.sleeps == pytest.approx(old.sleeps, abs=1e-8)
    assert len(clock.sleeps) == 5


def test_the_counters_say_a_job_behind_its_plan_never_waited():
    """(6) ``wire.pace.job_bytes`` is the job's bytes; ``wire.pace.wait_ms``
    is 0 while the job is behind its plan and the sleeps' milliseconds
    once it is ahead, each of them under a ``wire.pace`` span."""
    clock = FakeClock(tick=2 * QUANTUM_S)  # the writes are the slow part
    pacer = JobPacer(RATE, span_id="2.7", job="j1", clock=clock.now,
                     sleep=clock.sleep)
    before = trace.counter_totals()
    n_spans = len([s for s in trace.spans() if s["name"] == "wire.pace"])
    pacer.write(lambda c: None, bytes(9 * DEFAULT_BURST + 17))
    got = counters_since(before)
    assert got == {"wire.pace.job_bytes": 9 * DEFAULT_BURST + 17,
                   "wire.pace.wait_ms": 0}
    assert clock.sleeps == []

    ahead = FakeClock()
    pacer = JobPacer(RATE, span_id="2.7", job="j1", clock=ahead.now,
                     sleep=ahead.sleep)
    before = trace.counter_totals()
    pacer.write(lambda c: None, bytes(4 * DEFAULT_BURST))
    got = counters_since(before)
    assert got["wire.pace.job_bytes"] == 4 * DEFAULT_BURST
    assert got["wire.pace.wait_ms"] == round(3 * QUANTUM_S * 1000)
    spans = [s for s in trace.spans() if s["name"] == "wire.pace"][n_spans:]
    assert len(spans) == 3
    assert all(s["id"] == "2.7" and s["fields"] == {
        "job": "j1", "bytes": DEFAULT_BURST} for s in spans)


def test_a_job_pacer_needs_a_rate():
    with pytest.raises(ValueError):
        JobPacer(0)


# ------------------------------------------------------- over loopback TCP


def tcp_pair():
    ts = [TcpTransport("127.0.0.1:0") for _ in range(2)]
    for t in ts:
        t.addr_registry.update({i: x.get_address()
                                for i, x in enumerate(ts)})
    return ts


def close_all(ts):
    for t in ts:
        t.close()


def mem_layer(data: bytes, rate: int = 0) -> LayerSrc:
    return LayerSrc(inmem_data=bytearray(data), data_size=len(data),
                    meta=LayerMeta(location=LayerLocation.INMEM,
                                   limit_rate=rate))


@pytest.fixture
def small_stripes(monkeypatch):
    """KiB-scale payloads stripe four ways, and a commanded rate of a
    few MiB/s counts as a budget."""
    monkeypatch.setattr(tcp_mod, "STRIPE_THRESHOLD", 64 * 1024)
    monkeypatch.setattr(tcp_mod, "STRIPE_MIN", 16 * 1024)
    monkeypatch.setattr(tcp_mod, "STRIPE_COUNT", 4)
    monkeypatch.setattr(tcp_mod, "STRIPE_PACED_MIN_RATE", 10 ** 6)
    # (not a data-plane name: the pool's worker outlives the test, and a
    # stray ``data-*`` thread counts against tests/test_threads.py's
    # ceiling when both files share a pytest worker)
    monkeypatch.setattr(threads, "_tx", threads.WorkerPool(1, "pace-tx-one"))


def spy_stripes(transport) -> set:
    """``(stripe_idx, stripe_n)`` of every stripe frame that lands."""
    seen, landed = set(), transport._receive_stripe

    def spy(conn, envelope, header):
        seen.add((header.stripe_idx, header.stripe_n))
        return landed(conn, envelope, header)

    transport._receive_stripe = spy
    return seen


def collect(transport, size) -> bytes:
    """The destination's bytes of one layer, every fragment put at its
    offset."""
    got, have = bytearray(size), 0
    while have < size:
        src = transport.deliver().get(timeout=RECV_TIMEOUT).layer_src
        got[src.offset : src.offset + src.data_size] = bytes(src.inmem_data)
        have += src.data_size
    return bytes(got)


def test_a_job_of_four_stripes_through_one_tx_worker_ends_on_its_plan(
        small_stripes, monkeypatch):
    """(4) A flow job in two fragments of four stripes, the tx pool one
    worker: on a clock where only the pacer's sleeps take time the job
    ends within a quantum of ``size / rate`` (four buckets at a quarter
    of the rate, run by one worker one after another, take four times
    that), its pacer counted the job's bytes, and the destination holds
    the source's bytes."""
    rate = 8 << 20
    size = 2 << 20
    monkeypatch.setattr(send_mod, "FLOW_FRAGMENT_BYTES", size // 8)
    clock = FakeClock()
    monkeypatch.setattr(send_mod, "JobPacer", functools.partial(
        JobPacer, clock=clock.now, sleep=clock.sleep))
    payload = bytes((i * 31 + 7) % 256 for i in range(size))
    ts = tcp_pair()
    try:
        before = trace.counter_totals()
        stripes = spy_stripes(ts[1])
        t0 = clock.t
        send_mod.handle_flow_retransmit(
            Node(0, 0, ts[0]), {5: mem_layer(payload)}, threading.Lock(),
            lambda lid, dest: None,
            FlowRetransmitMsg(0, 5, 1, size, 0, rate))
        assert collect(ts[1], size) == payload
        assert stripes == {(i, 4) for i in range(4)}
        burst = effective_burst(rate)
        assert (size - burst) / rate <= clock.t - t0 <= size / rate
        assert len(clock.sleeps) == size // burst - 1  # one a quantum
        got = counters_since(before)
        assert got["wire.pace.job_bytes"] == size
        # a sleep is measured to the next reading, which a stripe that
        # slept beside it may have moved on: no less than was asked for
        assert got["wire.pace.wait_ms"] >= sum(
            int(s * 1000) for s in clock.sleeps) > 0
    finally:
        close_all(ts)


def test_a_striped_message_without_a_job_is_its_own_job(small_stripes,
                                                        monkeypatch):
    """A budget-scale message that brings no pacer (a capped holder's
    ``send_layer``) still stripes, and its stripes share ONE budget:
    the message's rate, not a quarter of it each."""
    rate = 8 << 20
    size = 1 << 20
    made = []

    def one_pacer(rate_, **kw):
        made.append(JobPacer(rate_, **kw))
        return made[-1]

    monkeypatch.setattr(tcp_mod, "JobPacer", one_pacer)
    payload = bytes((i * 13 + 5) % 256 for i in range(size))
    ts = tcp_pair()
    try:
        before = trace.counter_totals()
        stripes = spy_stripes(ts[1])
        ts[0].send(1, LayerMsg(0, 3, mem_layer(payload, rate), size))
        assert collect(ts[1], size) == payload
        assert stripes == {(i, 4) for i in range(4)}
        assert [p.rate for p in made] == [float(rate)]
        assert counters_since(before)["wire.pace.job_bytes"] == size
    finally:
        close_all(ts)


class SpyWriter(PacedWriter):
    """``PacedWriter`` as the transport builds it, its rate and the
    sizes of its ``sendall``s kept."""

    made = []

    def __init__(self, write, rate, burst=DEFAULT_BURST):
        self.rate, self.sizes = rate, []
        SpyWriter.made.append(self)

        def spied(chunk):
            self.sizes.append(len(chunk))
            write(chunk)

        super().__init__(spied, rate, burst)


def bucket_model(size: int, rate: int):
    """The ``sendall`` sizes and sleeps of one fresh token bucket writing
    ``size`` bytes back to back: what a message without a pacer makes."""
    burst = effective_burst(rate)
    sizes = [min(burst, size - off) for off in range(0, size, burst)]
    tokens, sleeps = float(burst), []
    for n in sizes:
        if tokens < n:
            sleeps.append((n - tokens) / rate)
            tokens = float(n)
        tokens -= n
    return sizes, sleeps


def _send_capped(ts, payload, rate):
    """A holder with a ``Sources`` cap, as modes 0-2 send its layer."""
    send_mod.send_layer(Node(0, 0, ts[0]), 1, 4, mem_layer(payload, rate))


def _send_nack(ts, payload, rate):
    """A NACK for the layer's second half, served at the holder's cap."""
    half = len(payload) // 2
    assert send_mod.NackRetransmitter().handle(
        Node(0, 0, ts[0]), {4: mem_layer(payload, rate)}, threading.Lock(),
        LayerNackMsg(1, 4, half, len(payload) - half, len(payload)))


@pytest.mark.parametrize("send,offset", [(_send_capped, 0),
                                         (_send_nack, 384 * 1024)],
                         ids=["sources-cap", "nack-retransmit"])
def test_a_message_without_a_pacer_is_paced_alone_as_before(
        send, offset, small_stripes, monkeypatch):
    """(5) A ``Sources`` cap under ``STRIPE_PACED_MIN_RATE`` and a NACK
    retransmit bring no pacer: one stream, one ``PacedWriter`` at
    ``meta.limit_rate``, exactly a fresh bucket's ``sendall`` sizes and
    waits — and no job pacer is ever built."""
    monkeypatch.setattr(tcp_mod, "STRIPE_PACED_MIN_RATE", 10 ** 9)
    rate = 16 << 20
    size = 768 * 1024

    def no_pacer(*a, **kw):
        raise AssertionError("a job pacer for a message without a job")

    monkeypatch.setattr(tcp_mod, "JobPacer", no_pacer)
    monkeypatch.setattr(send_mod, "JobPacer", no_pacer)
    SpyWriter.made = []
    monkeypatch.setattr(tcp_mod, "PacedWriter", SpyWriter)
    clock = FakeClock()
    monkeypatch.setattr(rate_mod, "time", clock.as_time_module())
    payload = bytes((i * 7 + 3) % 256 for i in range(size))
    ts = tcp_pair()
    try:
        before = trace.counter_totals()
        send(ts, payload, rate)
        msg = ts[1].deliver().get(timeout=RECV_TIMEOUT)
        assert msg.stripe_n == 1 and msg.layer_src.offset == offset
        assert bytes(msg.layer_src.inmem_data) == payload[offset:]
        sizes, sleeps = bucket_model(size - offset, rate)
        assert len(SpyWriter.made) == 1
        assert SpyWriter.made[0].rate == rate
        assert SpyWriter.made[0].sizes == sizes
        assert clock.sleeps == pytest.approx(sleeps)
        assert counters_since(before) == {"wire.pace.job_bytes": 0,
                                          "wire.pace.wait_ms": 0}
    finally:
        close_all(ts)


# ------------------------------------------- through a leader, and cli.trace


def test_a_mode3_delivery_goes_through_job_pacers_byte_for_byte(
        monkeypatch):
    """A whole mode-3 delivery over loopback TCP: the leader's plan
    commands every job at a rate, so every wire byte is written through
    a job's pacer and the destination holds the source's bytes."""
    from distributed_llm_dissemination_tpu.runtime import (
        FlowRetransmitLeaderNode,
        FlowRetransmitReceiverNode,
    )

    size = 96 * 1024
    monkeypatch.setattr(send_mod, "FLOW_FRAGMENT_BYTES", 32 * 1024)
    layers = {lid: bytes((lid * 37 + i) % 256 for i in range(size))
              for lid in range(3)}
    ts = tcp_pair()
    leader = FlowRetransmitLeaderNode(
        Node(0, 0, ts[0]), {lid: mem_layer(b) for lid, b in layers.items()},
        {1: {lid: LayerMeta() for lid in layers}}, {0: 10 ** 9, 1: 10 ** 9})
    cold = FlowRetransmitReceiverNode(Node(1, 0, ts[1]), {})
    try:
        before = trace.counter_totals()
        cold.announce()
        assert leader.ready().get(timeout=RECV_TIMEOUT)
        cold.ready().get(timeout=RECV_TIMEOUT)
        for lid, data in layers.items():
            assert bytes(cold.layers[lid].inmem_data) == data
        assert counters_since(before)["wire.pace.job_bytes"] == 3 * size
    finally:
        close_all(ts)


def test_cli_trace_adds_up_the_pace_counters_of_the_logs_it_is_given():
    from distributed_llm_dissemination_tpu.cli import trace as cli_trace

    records = [
        {"message": "span counters", "node": 0,
         "counters": {"wire.pace.job_bytes": 3000, "wire.pace.wait_ms": 12}},
        {"message": "span counters", "node": 1,
         "counters": {"wire.pace.job_bytes": 500}},
        {"message": "span counters", "node": 2,
         "counters": {"wire.buf.reused_bytes": 3500}},
        {"message": "spans", "spans": []},
    ]
    assert cli_trace.job_pace_totals(records) == {
        "job_bytes": 3500, "wait_ms": 12}
    assert cli_trace.job_pace_totals(records[2:]) == {}
    assert cli_trace.recv_buffer_totals(records) == {
        "reused_bytes": 3500, "fresh_bytes": 0}
