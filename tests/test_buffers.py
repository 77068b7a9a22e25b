"""The receive-buffer pool (utils/buffers.py): a lease ends with its last
reference and never sooner, large buffers of one size reuse one mapping,
and the pool holds no more than was ever out on lease at once."""

import gc
import threading

import numpy as np
import pytest

from distributed_llm_dissemination_tpu.utils import buffers, hostmem, trace

MIB = 1 << 20
N = 5 * MIB  # over the pool's threshold


@pytest.fixture
def pool(monkeypatch):
    """A pool of this test's own, behind ``alloc_recv_buffer``."""
    fresh = buffers.RecvPool()
    monkeypatch.setattr(buffers, "_pool", fresh)
    trace.reset_counters()
    return fresh


def _counts():
    totals = trace.counter_totals()
    return (totals.get("wire.buf.reused_bytes", 0),
            totals.get("wire.buf.fresh_bytes", 0))


def test_leased_buffer_is_what_callers_always_got(pool):
    buf = buffers.alloc_recv_buffer(N)
    assert isinstance(buf, np.ndarray) and buf.dtype == np.uint8
    assert buf.shape == (N,) and buf.flags["C_CONTIGUOUS"]
    assert buf.flags["WRITEABLE"]
    assert buf.ctypes.data % hostmem.ALIGN == 0
    assert hostmem.is_adoptable(buf)
    buf[:] = 7
    assert bytes(memoryview(buf)[N - 3:]) == b"\x07\x07\x07"


def _hold_with_memoryview_slice(buf):
    return memoryview(buf)[1024:4096]


def _hold_with_frombuffer_of_a_slice(buf):
    return np.frombuffer(memoryview(buf)[64:64 + MIB], dtype=np.uint8)


def _hold_with_array_slice_of_a_slice(buf):
    return buf[10:N - 10][5:50]


def _hold_with_adopted_jax_array(buf):
    import jax

    arr = hostmem.adopt_as_device_array(buf, jax.devices("cpu")[0])
    assert arr.unsafe_buffer_pointer() == buf.ctypes.data  # aliased
    return arr


@pytest.mark.parametrize("hold", [
    _hold_with_memoryview_slice,
    _hold_with_frombuffer_of_a_slice,
    _hold_with_array_slice_of_a_slice,
    _hold_with_adopted_jax_array,
], ids=lambda f: f.__name__[len("_hold_with_"):])
def test_lease_returns_only_after_its_last_reference_dies(pool, hold):
    buf = buffers.alloc_recv_buffer(N)
    addr = buf.ctypes.data
    buf[:4096] = 3
    held = hold(buf)
    del buf
    gc.collect()
    assert pool.stats() == {"leased_bytes": N, "free_bytes": 0,
                            "free_slabs": 0, "high_water_bytes": N}
    # still out: a second lease of the size must map its own memory
    other = buffers.alloc_recv_buffer(N)
    assert other.ctypes.data != addr
    del other
    del held
    gc.collect()
    assert pool.stats()["leased_bytes"] == 0
    assert pool.stats()["free_slabs"] == 2


def test_same_size_gets_the_same_address_and_counts_as_reused(pool):
    first = buffers.alloc_recv_buffer(N)
    addr = first.ctypes.data
    assert _counts() == (0, N)
    del first
    second = buffers.alloc_recv_buffer(N)
    assert second.ctypes.data == addr
    assert _counts() == (N, N)


def test_a_larger_slab_serves_a_request_that_wastes_at_most_an_eighth(pool):
    big = buffers.alloc_recv_buffer(8 * MIB)
    addr = big.ctypes.data
    del big
    snug = buffers.alloc_recv_buffer(7 * MIB)  # wastes exactly an eighth
    assert snug.ctypes.data == addr and snug.shape == (7 * MIB,)
    del snug
    loose = buffers.alloc_recv_buffer(7 * MIB - 1)  # a byte more than that
    assert loose.ctypes.data != addr
    assert _counts() == (7 * MIB, 8 * MIB + 7 * MIB - 1)


def test_exact_size_is_preferred_to_a_larger_slab(pool):
    big, exact = (buffers.alloc_recv_buffer(8 * MIB),
                  buffers.alloc_recv_buffer(7 * MIB))
    addr = exact.ctypes.data
    del big, exact
    assert buffers.alloc_recv_buffer(7 * MIB).ctypes.data == addr


def test_under_the_threshold_nothing_is_pooled(pool):
    small = buffers.alloc_recv_buffer(buffers.POOL_MIN_BYTES - 1)
    assert hostmem.is_adoptable(small)
    del small
    assert pool.stats()["high_water_bytes"] == 0
    assert _counts() == (0, 0)


def test_sparse_buffer_of_a_sharded_holding_is_no_pool_slab(pool):
    buf = buffers.alloc_recv_buffer(N, sparse=True)
    assert hostmem.is_adoptable(buf) and buf.shape == (N,)
    assert pool.stats()["leased_bytes"] == 0
    del buf
    assert pool.stats()["free_slabs"] == 0
    assert _counts() == (0, 0)


def test_retention_never_exceeds_the_high_water_mark(pool):
    a, b = buffers.alloc_recv_buffer(N), buffers.alloc_recv_buffer(N)
    del a, b
    assert pool.stats() == {"leased_bytes": 0, "free_bytes": 2 * N,
                            "free_slabs": 2, "high_water_bytes": 2 * N}
    # three more of another size, one at a time: never over two slabs' worth
    for _ in range(3):
        c = buffers.alloc_recv_buffer(6 * MIB)
        st = pool.stats()
        assert st["leased_bytes"] + st["free_bytes"] <= st["high_water_bytes"]
        del c
    st = pool.stats()
    assert st["high_water_bytes"] == 2 * N
    assert st["leased_bytes"] + st["free_bytes"] <= 2 * N


def test_a_miss_of_another_size_evicts_free_slabs_oldest_first(pool):
    bufs = [buffers.alloc_recv_buffer(N) for _ in range(3)]
    addrs = [b.ctypes.data for b in bufs]
    while bufs:
        del bufs[0]  # free list: oldest first
    assert [s.ctypes.data for s in pool.free_slabs()] == addrs
    other = buffers.alloc_recv_buffer(6 * MIB)  # a miss: no slab fits
    # 6 MiB out and 15 MiB free under a mark of 15: the two oldest go
    assert [s.ctypes.data for s in pool.free_slabs()] == addrs[2:]
    del other
    st = pool.stats()
    assert st["free_bytes"] == N + 6 * MIB
    assert st["high_water_bytes"] == 3 * N


def test_stale_bytes_of_a_reused_slab_are_the_previous_leases(pool):
    """What the receivers' coverage tracking exists for: a reused slab is
    NOT zeroed (zeroing is the cost the pool removes)."""
    first = buffers.alloc_recv_buffer(N)
    first[:] = 0xAB
    del first
    again = buffers.alloc_recv_buffer(N)
    assert int(again[0]) == 0xAB and int(again[N - 1]) == 0xAB


def test_eight_threads_lose_no_slab_and_share_none(pool):
    import sys

    errors = []
    rounds = 60

    def worker(k):
        try:
            for i in range(rounds):
                buf = buffers.alloc_recv_buffer(6 * MIB if i % 10 == 0 else N)
                buf[:64] = k
                buf[-64:] = k
                view = memoryview(buf)[8:32]
                del buf
                if bytes(view) != bytes([k]) * 24:
                    errors.append(f"thread {k}: slab handed out twice")
                del view
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(1, 9)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert errors == []
    gc.collect()
    st = pool.stats()
    assert st["leased_bytes"] == 0
    assert st["free_bytes"] <= st["high_water_bytes"] <= 8 * 6 * MIB
    reused, fresh = _counts()
    assert reused + fresh == 8 * (6 * (6 * MIB) + 54 * N)
    # every lease was either a slab that lay free or a new one: the pool
    # mapped far fewer than it handed out
    assert reused > fresh


def test_cli_trace_adds_up_the_lease_counters_of_the_logs_it_is_given():
    from distributed_llm_dissemination_tpu.cli import trace as cli_trace

    records = [
        {"message": "span counters", "node": 1,
         "counters": {"wire.buf.reused_bytes": 3 * N, "xla.compiles": 2}},
        {"message": "span counters", "node": 2,
         "counters": {"wire.buf.reused_bytes": N,
                      "wire.buf.fresh_bytes": 2 * N}},
        {"message": "span counters", "node": 0, "counters": {}},  # a leader
        {"message": "spans", "spans": []},
    ]
    assert cli_trace.recv_buffer_totals(records) == {
        "reused_bytes": 4 * N, "fresh_bytes": 2 * N}
    assert cli_trace.recv_buffer_totals(records[2:]) == {}


# ------------------------------------------------ through the receivers

def _delivery(kind, shards):
    """One mode-3 delivery of two 256 KiB layers to ``max(shards, 1)``
    destinations; ``shards`` > 0 assigns each its ``1/n@k`` share."""
    from distributed_llm_dissemination_tpu.core.types import (
        LayerMeta, shard_specs_for)
    from distributed_llm_dissemination_tpu.runtime import (
        FlowRetransmitLeaderNode, FlowRetransmitReceiverNode, Node)
    from test_node import close_all, make_transports, mem_layer

    size, n_dests = 1 << 18, max(shards, 1)
    ids = list(range(n_dests + 1))
    ts, _ = make_transports(kind, ids)
    specs = shard_specs_for(shards) if shards else [""]
    assignment = {k + 1: {lid: LayerMeta(shard=specs[k]) for lid in (0, 1)}
                  for k in range(n_dests)}
    leader = FlowRetransmitLeaderNode(
        Node(0, 0, ts[0]), {lid: mem_layer(lid, size) for lid in (0, 1)},
        assignment, {i: 1 << 30 for i in ids})
    receivers = [FlowRetransmitReceiverNode(Node(i, 0, ts[i]), {})
                 for i in ids[1:]]
    try:
        for r in receivers:
            r.announce()
        leader.start_distribution().get(timeout=30)
        leader.ready().get(timeout=30)
    finally:
        close_all(leader, receivers, ts)
    return 2 * size


def test_a_shard_specd_layers_buffer_is_not_a_pool_slab(pool, monkeypatch):
    """The receivers ask for a sparse buffer wherever the layer has a
    shard spec (the leader's stamp, or the frame's own tag); the same
    delivery unsharded leases every layer."""
    monkeypatch.setattr(buffers, "POOL_MIN_BYTES", 1 << 16)
    _delivery("inmem", shards=2)
    assert pool.stats()["high_water_bytes"] == 0
    assert _counts() == (0, 0)
    whole = _delivery("inmem", shards=0)
    assert pool.stats()["high_water_bytes"] == whole
    assert _counts() == (0, whole)


def test_the_transport_sink_leases_for_whole_layers_only(pool, monkeypatch):
    """The zero-copy sink (the benchmark cells' path): a layer stamped
    with a shard spec lands in a mapping of its own, its neighbour in a
    pool slab."""
    from distributed_llm_dissemination_tpu.runtime import (
        FlowRetransmitReceiverNode, Node)
    from test_node import make_transports

    monkeypatch.setattr(buffers, "POOL_MIN_BYTES", 1 << 16)
    total = 1 << 18
    ts, _ = make_transports("inmem", [0, 1])
    r = FlowRetransmitReceiverNode(Node(1, 0, ts[1]), {})
    try:
        r._shard_specs[0] = "1/2@1"
        view, _tok, _abort = r._layer_sink(0, total, total // 2, 4096)
        assert len(view) == 4096
        assert pool.stats()["leased_bytes"] == 0
        view, _tok, _abort = r._layer_sink(1, total, 0, 4096)
        assert pool.stats()["leased_bytes"] == total
        assert _counts() == (0, total)
    finally:
        r.close()
        for t in ts.values():
            t.close()
    del view
    gc.collect()
    assert pool.stats()["leased_bytes"] == 0  # close dropped the partials


@pytest.mark.timeout(170)
def test_second_delivery_through_cli_main_reuses_every_buffer(
        pool, monkeypatch, tmp_path):
    """A resident destination runs ``cli.main``'s receiver path twice
    (the leader a process of its own each time, as in the benchmark).
    Between the deliveries the node is closed and every free slab is
    overwritten with 0xFF; the second delivery must lease the first
    one's slabs for ALL its wire bytes — so a closed node and the idle
    pool workers let go of them — and deliver digest-exact layers out
    of the stale memory."""
    import importlib
    import json
    import os
    import subprocess
    import sys

    from distributed_llm_dissemination_tpu.models import family
    from distributed_llm_dissemination_tpu.models.serde import seeded_blob
    from distributed_llm_dissemination_tpu.utils import integrity
    from test_trace_spans import _free_ports

    cli_main = importlib.import_module(
        "distributed_llm_dissemination_tpu.cli.main")
    monkeypatch.setattr(buffers, "POOL_MIN_BYTES", 1024)
    built = []
    real = cli_main.FlowRetransmitReceiverNode

    class Remembered(real):
        def __init__(self, *a, **kw):
            built.append(self)
            super().__init__(*a, **kw)

    monkeypatch.setattr(cli_main, "FlowRetransmitReceiverNode", Remembered)
    blobs = {str(b): {} for b in range(5)}  # tiny: 4 layers and the head
    conf = {
        "Model": "tiny", "ModelSeed": 0,
        "Nodes": [
            {"Id": 0, "Addr": "", "NetworkBW": 10 ** 10, "IsLeader": True,
             "Sources": {"1": 0}, "InitialLayers": {"1": blobs}},
            {"Id": 1, "Addr": "", "NetworkBW": 10 ** 10, "Sources": {},
             "InitialLayers": {}},
        ],
        "Assignment": {"1": blobs},
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [1]},
    }
    mcfg = family.config("tiny")
    want = {b: integrity.layer_digest(seeded_blob(mcfg, b, 0))
            for b in range(5)}
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def deliver(k):
        _free_ports(conf)
        conf_path = str(tmp_path / f"conf{k}.json")
        with open(conf_path, "w") as f:
            json.dump(conf, f)
        argv = ["-f", conf_path, "-m", "3"]
        trace.reset_run()
        with open(tmp_path / f"leader{k}.err", "w") as err:
            leader = subprocess.Popen(
                [sys.executable, "-m",
                 "distributed_llm_dissemination_tpu.cli.main", *argv,
                 "-id", "0"],
                stdout=subprocess.PIPE, stderr=err, env=env, text=True)
            try:
                assert cli_main.main([*argv, "-id", "1", "-hbm"]) == 0
                out, _ = leader.communicate(timeout=120)
            finally:
                if leader.poll() is None:
                    leader.kill()
        assert leader.returncode == 0 and "Time to first token" in out
        node = built.pop()
        got = {b: integrity.layer_digest(
            memoryview(node.layers[b].inmem_data)) for b in range(5)}
        wire = sum(node.layers[b].data_size for b in range(5))
        node.close()
        return got, wire

    got, wire = deliver(0)
    assert got == want
    assert _counts() == (0, wire)
    gc.collect()  # as the benchmark's resident destination does
    st = pool.stats()
    assert st["leased_bytes"] == 0, "the first delivery's buffers are held"
    assert st["free_bytes"] == wire == st["high_water_bytes"]
    for slab in pool.free_slabs():
        slab[:] = 0xFF

    got, wire2 = deliver(1)
    assert got == want and wire2 == wire
    assert _counts() == (wire, 0)  # reset_run: the second delivery's own
    gc.collect()
    assert pool.stats()["high_water_bytes"] == wire
