"""Pod-fabric data plane: scheduled transfers ride the device mesh, TCP
carries only control messages.

The north-star integration the reference can't do (its data plane is
per-transfer TCP byte streams, /root/reference/distributor/transport.go:
267-274, 308-373): here the full announce → schedule → transfer → HBM →
ack → startup protocol runs with ZERO layer bytes on the transport — every
byte moves as device traffic via ``DevicePlanMsg`` + ``FabricPlane`` +
``ShardedLayerIngest``, in all four scheduling modes.
"""

import threading
import time

import jax
import numpy as np
import pytest

from distributed_llm_dissemination_tpu.core.types import (
    LayerLocation,
    LayerMeta,
    LayerSrc,
    SourceType,
)
from distributed_llm_dissemination_tpu.parallel import (
    FabricPlane,
    array_to_bytes,
    fabric_placement,
    make_mesh,
)
from distributed_llm_dissemination_tpu.parallel.ingest import ShardedLayerIngest
from distributed_llm_dissemination_tpu.runtime import (
    FlowRetransmitLeaderNode,
    FlowRetransmitReceiverNode,
    LeaderNode,
    Node,
    PullRetransmitLeaderNode,
    ReceiverNode,
    RetransmitLeaderNode,
    RetransmitReceiverNode,
)
from distributed_llm_dissemination_tpu.runtime.checkpoint import (
    LayerCheckpointStore,
)
from distributed_llm_dissemination_tpu.transport import (
    TcpTransport,
    reset_registry,
)
from distributed_llm_dissemination_tpu.transport.inmem import InmemTransport
from distributed_llm_dissemination_tpu.utils import trace
from distributed_llm_dissemination_tpu.transport.messages import (
    DevicePlanMsg,
    MsgType,
    decode_msg,
)

TIMEOUT = 15.0
LAYER_SIZE = 64 * 1024


@pytest.fixture(autouse=True)
def _clean():
    reset_registry()
    yield
    reset_registry()


def layer_bytes(layer_id: int, size: int = LAYER_SIZE) -> bytes:
    return bytes([(layer_id * 37 + i) % 256 for i in range(size)])


def mem_layer(layer_id: int, size: int = LAYER_SIZE, rate: int = 0) -> LayerSrc:
    data = bytearray(layer_bytes(layer_id, size))
    return LayerSrc(
        inmem_data=data,
        data_size=len(data),
        meta=LayerMeta(location=LayerLocation.INMEM,
                       source_type=SourceType.MEM, limit_rate=rate),
    )


def inmem_transports(ids):
    return {
        i: InmemTransport(str(i), addr_registry={j: str(j) for j in ids})
        for i in ids
    }


def tcp_transports(ids):
    ts = {i: TcpTransport("127.0.0.1:0") for i in ids}
    registry = {i: ts[i].get_address() for i in ids}
    for t in ts.values():
        t.addr_registry.update(registry)
    return ts


def spy_sends(transports):
    """Record every (src, dest, msg-type-name) crossing each transport."""
    sent = []
    for i, t in transports.items():
        orig = t.send

        def spy(dest, msg, _orig=orig, _i=i):
            sent.append((_i, dest, type(msg).__name__))
            _orig(dest, msg)

        t.send = spy
    return sent


def run_distribution(leader, receivers, assignment):
    for r in receivers:
        r.announce()
    assert leader.start_distribution().get(timeout=TIMEOUT) == assignment
    assert leader.ready().get(timeout=TIMEOUT) == assignment
    for r in receivers:
        r.ready().get(timeout=TIMEOUT)


def close_all(leader, receivers, ts):
    leader.close()
    for r in receivers:
        r.close()
    for t in ts.values():
        t.close()


def check_fabric_landing(receiver, placement, layer_ids):
    """Fabric-delivered layer: HBM, on the node's stage devices, exact."""
    stage_devices = set(placement.devices_for_node(receiver.node.my_id))
    for lid in layer_ids:
        src = receiver.layers[lid]
        assert src.meta.location == LayerLocation.HBM
        assert src.inmem_data is None  # no host copy ever existed
        assert set(src.device_array.devices()) == stage_devices
        assert array_to_bytes(src.device_array) == layer_bytes(lid, src.data_size)


# ------------------------------------------------------------ message codec


def test_device_plan_msg_roundtrip():
    msg = DevicePlanMsg(0, "5.3.17", 5, 3, 1 << 30,
                        [(0, 0, 1 << 29), (2, 1 << 29, 1 << 29)])
    decoded = decode_msg(MsgType.DEVICE_PLAN, msg.to_payload())
    assert decoded == msg
    # JSON-safe: the payload survives an actual dump/load cycle (what the
    # TCP envelope does).
    import json

    assert decode_msg(MsgType.DEVICE_PLAN,
                      json.loads(json.dumps(msg.to_payload()))) == msg


# ------------------------------------------------------------- FabricPlane


def test_fabric_plane_collect_yields_as_published(cpu_devices):
    plane = FabricPlane()
    a0 = jax.device_put(np.arange(4, dtype=np.uint8), cpu_devices[0])
    plane.publish("p", 0, a0)

    got = []

    def consume():
        for off, arr in plane.collect("p", 8, timeout=5.0):
            got.append((off, bytes(np.asarray(arr))))

    t = threading.Thread(target=consume)
    t.start()
    time.sleep(0.1)
    a1 = jax.device_put(np.arange(4, 8, dtype=np.uint8), cpu_devices[1])
    plane.publish("p", 4, a1)
    t.join(timeout=5.0)
    assert got == [(0, bytes(range(4))), (4, bytes(range(4, 8)))]
    assert plane.pending() == 0  # consumed plans are discarded


def test_fabric_plane_collect_times_out():
    plane = FabricPlane()
    with pytest.raises(TimeoutError):
        list(plane.collect("missing", 1, timeout=0.2))


def test_fabric_plane_gc_drops_stale_plans(cpu_devices):
    plane = FabricPlane()
    plane.publish("dead", 0, jax.device_put(np.zeros(4, np.uint8),
                                            cpu_devices[0]))
    assert plane.gc(max_age=0.0) == 1
    assert plane.pending() == 0


# -------------------------------------------------------- fabric placement


def test_fabric_placement_covers_seeders(cpu_devices):
    mesh = make_mesh((4, 2), ("pp", "tp"))
    assignment = {3: {0: LayerMeta()}}
    p = fabric_placement([0, 1, 2, 3], assignment, mesh, "pp")
    # Assignee keeps stage 0 (assignment ranking); extras fill free stages
    # in id order; every node has devices to contribute from.
    assert p.node_to_stage[3] == 0
    assert sorted(p.node_to_stage) == [0, 1, 2, 3]
    assert sorted(p.node_to_stage.values()) == [0, 1, 2, 3]
    for n in range(4):
        assert len(p.devices_for_node(n)) == 2


def test_fabric_placement_shares_stages_when_short(cpu_devices):
    mesh = make_mesh((2, 4), ("pp", "tp"))
    assignment = {5: {0: LayerMeta()}}
    with pytest.warns(UserWarning, match="share"):
        p = fabric_placement([0, 1, 2, 5], assignment, mesh, "pp")
    assert p.node_to_stage[5] == 0
    assert all(n in p.node_to_stage for n in (0, 1, 2))


# ------------------------------------------------- device-fed sharded ingest


@pytest.mark.parametrize("stream", [False, True])
def test_sharded_ingest_accepts_device_fragments(cpu_devices, stream):
    total = 4096
    data = layer_bytes(9, total)
    ing = ShardedLayerIngest(total, cpu_devices[:4], stream=stream)
    # Mixed feeding: a host fragment and two device-resident fragments
    # (what the fabric dest does), out of order.
    ing.write(1024, data[1024:3000])
    ing.write(3000, jax.device_put(
        np.frombuffer(data[3000:], np.uint8), cpu_devices[6]))
    ing.write(0, jax.device_put(
        np.frombuffer(data[:1024], np.uint8), cpu_devices[7]))
    arr = ing.finalize()
    assert array_to_bytes(arr) == data
    assert set(arr.devices()) == set(cpu_devices[:4])


@pytest.mark.parametrize("stream", [False, True])
def test_sharded_ingest_salvage_reads_back_written_bytes(cpu_devices, stream):
    """salvage(): the fallback assembly source when the gather fails —
    covered ranges come back byte-exact from the shard buffers, and
    uncovered ranges are not claimed."""
    total = 4096
    data = layer_bytes(5, total)
    ing = ShardedLayerIngest(total, cpu_devices[:4], stream=stream)
    ing.write(0, data[:1000])
    ing.write(2500, data[2500:4096])
    got = ing.salvage()
    buf = bytearray(total)
    covered = 0
    for off, piece in got:
        buf[off : off + len(piece)] = piece
        covered += len(piece)
    assert covered == 1000 + (4096 - 2500)
    assert bytes(buf[:1000]) == data[:1000]
    assert bytes(buf[2500:]) == data[2500:]
    assert bytes(buf[1000:2500]) == b"\x00" * 1500  # never claimed


def test_sharded_ingest_rejects_non_uint8_device_fragment(cpu_devices):
    ing = ShardedLayerIngest(64, cpu_devices[:2])
    with pytest.raises(ValueError, match="uint8"):
        ing.write(0, jax.device_put(np.zeros(8, np.float32), cpu_devices[0]))


# ------------------------------------------------- full-protocol, all modes


def _fabric_cluster(mode, ids, assignment, seeders, transports,
                    rate: int = 0, layer_count: int = 2):
    """Build a leader + receivers sharing one fabric over ``transports``.

    ``seeders``: node ids (beyond the leader) pre-holding every layer."""
    mesh = make_mesh((len(ids), 8 // len(ids)) if 8 % len(ids) == 0
                     else (len(ids),),
                     ("pp", "tp") if 8 % len(ids) == 0 else ("pp",))
    placement = fabric_placement(list(ids), assignment, mesh, "pp")
    fabric = FabricPlane()
    layers = {i: mem_layer(i, rate=rate) for i in range(layer_count)}
    kwargs = dict(expected_nodes=set(ids), fabric=fabric,
                  placement=placement)
    leader_cls = {0: LeaderNode, 1: RetransmitLeaderNode,
                  2: PullRetransmitLeaderNode}.get(mode)
    if leader_cls is None:
        bw = {i: 10_000_000 for i in ids}
        leader = FlowRetransmitLeaderNode(
            Node(0, 0, transports[0]), dict(layers), assignment, bw, **kwargs)
    else:
        leader = leader_cls(Node(0, 0, transports[0]), dict(layers),
                            assignment, **kwargs)
    recv_cls = {0: ReceiverNode, 1: RetransmitReceiverNode,
                2: RetransmitReceiverNode}.get(mode, FlowRetransmitReceiverNode)
    receivers = [
        recv_cls(Node(i, 0, transports[i]),
                 {k: mem_layer(k, rate=rate) for k in layers} if i in seeders
                 else {},
                 fabric=fabric, placement=placement)
        for i in ids if i != 0
    ]
    return leader, receivers, placement


@pytest.mark.parametrize("mode", [0, 1, 2, 3])
def test_all_modes_zero_layer_bytes_on_transport(cpu_devices, mode):
    ids = range(4)
    ts = inmem_transports(ids)
    sent = spy_sends(ts)
    assignment = {3: {0: LayerMeta(), 1: LayerMeta()}}
    leader, receivers, placement = _fabric_cluster(
        mode, ids, assignment, seeders={1, 2}, transports=ts)
    try:
        run_distribution(leader, receivers, assignment)
        dest = receivers[-1]
        check_fabric_landing(dest, placement, [0, 1])
        # The north-star assertion: the transport carried ONLY control
        # messages — no LayerMsg ever crossed it.
        kinds = {k for _, _, k in sent}
        assert "LayerMsg" not in kinds
        assert "DevicePlanMsg" in kinds
        # The leader's live status records HBM delivery.
        assert leader.status[3][0].location == LayerLocation.HBM
    finally:
        close_all(leader, receivers, ts)


def test_mode3_multi_sender_split_over_fabric(cpu_devices):
    """Tight NIC budgets force the flow solver to split one layer across
    several seeders; each range enters the fabric from its own stage."""
    ids = range(4)
    ts = inmem_transports(ids)
    sent_plans = []
    for i, t in ts.items():
        orig = t.send

        def spy(dest, msg, _orig=orig):
            if isinstance(msg, DevicePlanMsg):
                sent_plans.append(msg)
            _orig(dest, msg)

        t.send = spy
    assignment = {3: {0: LayerMeta()}}
    mesh = make_mesh((4, 2), ("pp", "tp"))
    placement = fabric_placement(list(ids), assignment, mesh, "pp")
    fabric = FabricPlane()
    bw = {i: 100_000 for i in ids}
    leader = FlowRetransmitLeaderNode(
        Node(0, 0, ts[0]), {0: mem_layer(0, rate=40_000)}, assignment, bw,
        expected_nodes=set(ids), fabric=fabric, placement=placement)
    receivers = [
        FlowRetransmitReceiverNode(
            Node(i, 0, ts[i]),
            {0: mem_layer(0, rate=40_000)} if i != 3 else {},
            fabric=fabric, placement=placement)
        for i in (1, 2, 3)
    ]
    try:
        run_distribution(leader, receivers, assignment)
        check_fabric_landing(receivers[-1], placement, [0])
        layouts = {m.plan_id: m.layout for m in sent_plans}
        senders = {s for lay in layouts.values() for s, _, _ in lay}
        assert len(senders) >= 2, f"expected a multi-sender split, got {senders}"
        # Each plan's layout tiles the layer exactly.
        for lay in layouts.values():
            spans = sorted((o, o + z) for _, o, z in lay)
            pos = 0
            for s, e in spans:
                assert s == pos
                pos = e
            assert pos == LAYER_SIZE
    finally:
        close_all(leader, receivers, ts)


def test_fabric_over_real_tcp_control_plane(cpu_devices):
    """DevicePlanMsg survives the real TCP envelope: same protocol, real
    sockets for control, fabric for bytes."""
    ids = range(3)
    ts = tcp_transports(ids)
    sent = spy_sends(ts)
    assignment = {2: {0: LayerMeta(), 1: LayerMeta()}}
    mesh = make_mesh((3, 2), ("pp", "tp"), devices=list(cpu_devices)[:6])
    placement = fabric_placement(list(ids), assignment, mesh, "pp")
    fabric = FabricPlane()
    bw = {i: 10_000_000 for i in ids}
    leader = FlowRetransmitLeaderNode(
        Node(0, 0, ts[0]), {i: mem_layer(i) for i in range(2)}, assignment,
        bw, expected_nodes=set(ids), fabric=fabric, placement=placement)
    receivers = [
        FlowRetransmitReceiverNode(
            Node(i, 0, ts[i]),
            {k: mem_layer(k) for k in range(2)} if i == 1 else {},
            fabric=fabric, placement=placement)
        for i in (1, 2)
    ]
    try:
        run_distribution(leader, receivers, assignment)
        check_fabric_landing(receivers[-1], placement, [0, 1])
        assert "LayerMsg" not in {k for _, _, k in sent}
    finally:
        close_all(leader, receivers, ts)


def test_client_held_layer_falls_back_to_host_path(cpu_devices):
    """A layer whose only source is an external client can't enter the
    fabric; the leader routes that transfer over the host path while the
    rest of the run stays on the device plane."""
    from distributed_llm_dissemination_tpu.core.types import CLIENT_ID
    from distributed_llm_dissemination_tpu.runtime import Client
    from distributed_llm_dissemination_tpu.core.config import (
        create_client_layer_info,
    )

    ids = [0, 1, 2]
    ts = inmem_transports(ids)
    # Node 1's external client holds layer 1; node 1 knows of it as a
    # CLIENT-located record.
    client_transport = InmemTransport(
        "c1", addr_registry={1: "1"}, is_client=True)
    ts[1].addr_registry[CLIENT_ID] = "c1"
    client_layer = mem_layer(1)
    client_layer.meta.source_type = SourceType.CLIENT
    client_layer.meta.limit_rate = 10_000_000
    client = Client(1, client_transport, {1: client_layer})
    sent = spy_sends(ts)

    assignment = {2: {0: LayerMeta(), 1: LayerMeta()}}
    mesh = make_mesh((3, 2), ("pp", "tp"), devices=list(cpu_devices)[:6])
    placement = fabric_placement(ids, assignment, mesh, "pp")
    fabric = FabricPlane()
    leader = RetransmitLeaderNode(
        Node(0, 0, ts[0]), {0: mem_layer(0)}, assignment,
        expected_nodes=set(ids), fabric=fabric, placement=placement)
    receivers = [
        RetransmitReceiverNode(
            Node(1, 0, ts[1]),
            {1: create_client_layer_info(1, LAYER_SIZE, 10_000_000)},
            fabric=fabric, placement=placement),
        RetransmitReceiverNode(Node(2, 0, ts[2]), {}, fabric=fabric,
                               placement=placement),
    ]
    try:
        run_distribution(leader, receivers, assignment)
        dest = receivers[-1]
        # Layer 0 rode the fabric; layer 1 came from the client over the
        # host path (pipe relay), so it lands host-resident.
        check_fabric_landing(dest, placement, [0])
        assert dest.layers[1].meta.location == LayerLocation.INMEM
        assert bytes(dest.layers[1].inmem_data) == layer_bytes(1)
        kinds = {k for _, _, k in sent}
        assert "DevicePlanMsg" in kinds
    finally:
        client_transport.close()
        close_all(leader, receivers, ts)


def test_resumed_partial_layer_completes_over_fabric(cpu_devices, tmp_path):
    """A checkpoint-restored dest announces partial coverage; the fabric
    plan ships only the gaps and the ingest seeds itself from the restored
    bytes — resume works on the device plane too."""
    data = layer_bytes(0)
    half = LAYER_SIZE // 2
    store = LayerCheckpointStore(str(tmp_path))
    store.write_fragment(0, 0, data[:half], [(0, half)], LAYER_SIZE)

    ids = range(3)
    ts = inmem_transports(ids)
    plans = []
    for i, t in ts.items():
        orig = t.send

        def spy(dest, msg, _orig=orig):
            if isinstance(msg, DevicePlanMsg):
                plans.append(msg)
            _orig(dest, msg)

        t.send = spy
    assignment = {2: {0: LayerMeta()}}
    mesh = make_mesh((3, 2), ("pp", "tp"), devices=list(cpu_devices)[:6])
    placement = fabric_placement(list(ids), assignment, mesh, "pp")
    fabric = FabricPlane()
    bw = {i: 10_000_000 for i in ids}
    leader = FlowRetransmitLeaderNode(
        Node(0, 0, ts[0]), {0: mem_layer(0)}, assignment, bw,
        expected_nodes=set(ids), fabric=fabric, placement=placement)
    receivers = [
        FlowRetransmitReceiverNode(Node(1, 0, ts[1]), {0: mem_layer(0)},
                                   fabric=fabric, placement=placement),
        FlowRetransmitReceiverNode(Node(2, 0, ts[2]), {},
                                   checkpoint_dir=str(tmp_path),
                                   fabric=fabric, placement=placement),
    ]
    try:
        run_distribution(leader, receivers, assignment)
        dest = receivers[-1]
        check_fabric_landing(dest, placement, [0])
        # Only the gap crossed the fabric: every planned range lies in the
        # uncovered second half.
        assert plans, "expected a device plan"
        for m in {p.plan_id: p for p in plans}.values():
            for _, off, size in m.layout:
                assert off >= half and off + size <= LAYER_SIZE
        # The checkpoint journal is cleaned up on completion.
        assert LayerCheckpointStore(str(tmp_path)).load() == {}
    finally:
        close_all(leader, receivers, ts)


def test_fabric_ingest_failure_falls_back_to_host_assembly(cpu_devices,
                                                           monkeypatch):
    """Liveness: a device-side ingest failure on a live dest must not hang
    the run (the dest keeps heartbeating, so the leader never re-plans for
    it) — the dest assembles the collected contributions on host and acks
    INMEM, the same delivery-beats-staging fallback as the host path."""
    from distributed_llm_dissemination_tpu.parallel import ingest as ingest_mod

    class Broken:
        def __init__(self, *a, **k):
            raise RuntimeError("device allocation failed")

    monkeypatch.setattr(ingest_mod, "ShardedLayerIngest", Broken)

    ids = range(3)
    ts = inmem_transports(ids)
    assignment = {2: {0: LayerMeta()}}
    mesh = make_mesh((3, 2), ("pp", "tp"), devices=list(cpu_devices)[:6])
    placement = fabric_placement(list(ids), assignment, mesh, "pp")
    fabric = FabricPlane()
    bw = {i: 10_000_000 for i in ids}
    leader = FlowRetransmitLeaderNode(
        Node(0, 0, ts[0]), {0: mem_layer(0)}, assignment, bw,
        expected_nodes=set(ids), fabric=fabric, placement=placement)
    receivers = [
        FlowRetransmitReceiverNode(Node(1, 0, ts[1]), {0: mem_layer(0)},
                                   fabric=fabric, placement=placement),
        FlowRetransmitReceiverNode(Node(2, 0, ts[2]), {},
                                   fabric=fabric, placement=placement),
    ]
    try:
        run_distribution(leader, receivers, assignment)
        dest = receivers[-1]
        src = dest.layers[0]
        assert src.meta.location == LayerLocation.INMEM
        assert bytes(src.inmem_data) == layer_bytes(0)
        assert leader.status[2][0].location == LayerLocation.INMEM
    finally:
        close_all(leader, receivers, ts)


def test_multi_dest_contribution_caches_one_device_upload(cpu_devices):
    """A seeder serving the same layer to two destinations uploads it to
    its own HBM once: the full-layer device copy is cached on the record
    and both plans' contributions slice device-side."""
    ids = range(4)
    ts = inmem_transports(ids)
    assignment = {2: {0: LayerMeta()}, 3: {0: LayerMeta()}}
    mesh = make_mesh((4, 2), ("pp", "tp"))
    placement = fabric_placement(list(ids), assignment, mesh, "pp")
    fabric = FabricPlane()
    leader = RetransmitLeaderNode(
        Node(0, 0, ts[0]), {}, assignment, expected_nodes=set(ids),
        fabric=fabric, placement=placement)
    seeder = RetransmitReceiverNode(Node(1, 0, ts[1]), {0: mem_layer(0)},
                                    fabric=fabric, placement=placement)
    dests = [
        RetransmitReceiverNode(Node(i, 0, ts[i]), {}, fabric=fabric,
                               placement=placement)
        for i in (2, 3)
    ]
    try:
        run_distribution(leader, [seeder] + dests, assignment)
        for d in dests:
            check_fabric_landing(d, placement, [0])
        # On startup the cache is released: the seeder's record is back
        # to host-only (its HBM belongs to whatever boots next).
        src = seeder.layers[0]
        assert src.device_array is None
        assert src.meta.location == LayerLocation.INMEM
    finally:
        close_all(leader, [seeder] + dests, ts)


def chunks_to_bytes(chunks) -> bytes:
    """An upload's offset-ordered chunks back as the layer's bytes."""
    assert [off for off, _ in chunks] == sorted(off for off, _ in chunks)
    return b"".join(array_to_bytes(arr) for _, arr in chunks)


def test_fabric_upload_cache_unit(cpu_devices, monkeypatch):
    """One upload serves many plans, and is the layer in chunks put from
    a view of the held bytes; eviction and clear release the HBM copies;
    a failed upload is memoized on the record."""
    import unittest.mock as mock

    from distributed_llm_dissemination_tpu.runtime import send

    monkeypatch.setattr(send, "UPLOAD_CHUNK_BYTES", LAYER_SIZE // 4)
    cache = send._FabricUploadCache()
    cache.budget = 3 * LAYER_SIZE  # room for 3 entries

    puts = []
    real_put = jax.device_put

    def counting_put(x, d=None, **kw):
        puts.append(x)
        return real_put(x, d, **kw)

    def uploads_of(layer, layer_id):
        """How many host→device puts one more plan of ``layer`` costs."""
        before = len(puts)
        with mock.patch.object(jax, "device_put", counting_put):
            cache.get_or_put(layer, layer_id, cpu_devices[0])
        return len(puts) - before

    layers = [mem_layer(i) for i in range(4)]

    with mock.patch.object(jax, "device_put", counting_put):
        a, copied = cache.get_or_put(layers[0], 0, cpu_devices[0])
        b, again = cache.get_or_put(layers[0], 0, cpu_devices[0])
    assert a is b and len(puts) == 4  # second plan reused the upload
    assert [off for off, _ in a] == [i * LAYER_SIZE // 4 for i in range(4)]
    assert chunks_to_bytes(a) == layer_bytes(0)
    # The host holds the layer: every chunk went up from a view of the
    # record's own bytes, nothing was copied on the host first.
    assert copied == 0 and again == 0
    held = np.frombuffer(layers[0].inmem_data, np.uint8)
    assert all(np.shares_memory(x, held) for x in puts)

    # LRU: touch layer 0, insert 1..3 — budget 3 evicts the stale entry
    # (layer 1), never the re-touched layer 0.
    cache.get_or_put(layers[1], 1, cpu_devices[0])
    cache.get_or_put(layers[0], 0, cpu_devices[0])  # touch
    cache.get_or_put(layers[2], 2, cpu_devices[0])
    cache.get_or_put(layers[3], 3, cpu_devices[0])
    assert uploads_of(layers[0], 0) == 0, "the re-touched entry stays"
    assert uploads_of(layers[3], 3) == 0
    assert uploads_of(layers[1], 1) == 4, "LRU should evict the coldest"
    # the cache keeps its copies to itself: a record's device_array is a
    # STAGED layer's, and these are host-held
    assert all(rec.device_array is None for rec in layers)

    assert cache.clear() > 0
    assert cache._bytes == 0 and not cache._order

    # clear() latches the cache closed: a late plan's upload serves its
    # caller but is NOT retained (the booted model owns the HBM) until
    # reopen() re-arms a new cycle.
    stale = mem_layer(9)
    dev, _ = cache.get_or_put(stale, 9, cpu_devices[0])
    assert chunks_to_bytes(dev) == layer_bytes(9)  # the plan is still served
    assert uploads_of(stale, 9) == 4  # ...but nothing was retained
    cache.reopen()
    assert uploads_of(stale, 9) == 4
    assert uploads_of(stale, 9) == 0  # retained again

    # Failure memoized on the record, not by object address.
    broken = mem_layer(0)

    def failing_put(x, d=None, **kw):
        raise RuntimeError("no HBM")

    with mock.patch.object(jax, "device_put", failing_put):
        assert cache.get_or_put(broken, 0, cpu_devices[0]) == (None, 0)
    assert broken.upload_failed
    assert cache.get_or_put(broken, 0, cpu_devices[0]) == (None, 0)  # no re-read


def publish_spans():
    return [s for s in trace.spans() if s["name"] == "fabric.publish"]


@pytest.mark.parametrize("chunk,pieces_a_layer", [(2 * LAYER_SIZE, 1),
                                                  (24 * 1024, 3)])
def test_host_resident_seeder_publishes_its_bytes_in_place(
        cpu_devices, monkeypatch, chunk, pieces_a_layer):
    """A seeder whose host holds the layer uploads it from a view of those
    bytes, whole or in chunks: its ``fabric.publish`` spans count no byte
    copied on the host, and what lands has the source's sha256."""
    import hashlib

    from distributed_llm_dissemination_tpu.cli import trace as cli_trace
    from distributed_llm_dissemination_tpu.runtime import send

    monkeypatch.setattr(send, "UPLOAD_CHUNK_BYTES", chunk)
    trace.reset_run()
    ids = range(4)
    ts = inmem_transports(ids)
    assignment = {3: {0: LayerMeta(), 1: LayerMeta()}}
    leader, receivers, placement = _fabric_cluster(
        1, ids, assignment, seeders={1}, transports=ts)
    try:
        run_distribution(leader, receivers, assignment)
        dest = receivers[-1]
        check_fabric_landing(dest, placement, [0, 1])
        for lid in (0, 1):
            assert (hashlib.sha256(array_to_bytes(
                dest.layers[lid].device_array)).hexdigest()
                == hashlib.sha256(layer_bytes(lid)).hexdigest())
        spans = publish_spans()
        assert len(spans) == 2
        for sp in spans:
            assert sp["fields"]["host_copy_bytes"] == 0
            assert sp["fields"]["bytes"] == LAYER_SIZE
            assert sp["fields"]["pieces"] == pieces_a_layer
        events = [{"ph": "X", "name": s["name"],
                   "args": {"fields": s["fields"]}} for s in trace.spans()]
        assert cli_trace.fabric_publish_totals(events) == {
            "spans": 2, "bytes": 2 * LAYER_SIZE, "host_copy_bytes": 0,
            "pieces": 2 * pieces_a_layer}
        assert cli_trace.fabric_publish_totals([]) == {}
    finally:
        close_all(leader, receivers, ts)


def _one_seeder(cpu_devices, layer):
    """A seeder seat (node 1) holding ``layer`` as layer 0, a fabric and a
    placement: what ``contribute_device_plan`` needs, without a cluster."""
    ids = range(3)
    ts = inmem_transports(ids)
    mesh = make_mesh((3, 2), ("pp", "tp"), devices=list(cpu_devices)[:6])
    placement = fabric_placement(list(ids), {2: {0: LayerMeta()}}, mesh, "pp")
    return Node(1, 0, ts[1]), {0: layer}, FabricPlane(), placement, ts


def _plan(plan_id, layout, total=LAYER_SIZE):
    return DevicePlanMsg(0, plan_id, 0, 2, total, layout)


def collected(fabric, msg, timeout=5.0):
    """A plan's contributions as one ``{offset: bytes}`` map."""
    return {off: array_to_bytes(arr) for off, arr in
            fabric.collect(msg.plan_id, msg.layout_bytes, timeout=timeout)}


@pytest.mark.parametrize("off,size", [(4096, 8192), (0, LAYER_SIZE)])
def test_disk_seeder_copies_exactly_the_contributed_span(
        cpu_devices, tmp_path, off, size):
    """A ``DISK`` store has no bytes to view: its publish reads the span
    it contributes and reports exactly that — a small range only the
    range, the whole layer once however many plans ask for it."""
    from distributed_llm_dissemination_tpu.runtime.send import (
        contribute_device_plan,
        release_upload_cache,
        reopen_upload_cache,
    )

    path = tmp_path / "layer0.bin"
    path.write_bytes(layer_bytes(0))
    layer = LayerSrc(fp=str(path), data_size=LAYER_SIZE,
                     meta=LayerMeta(location=LayerLocation.DISK,
                                    source_type=SourceType.DISK))
    node, layers, fabric, placement, ts = _one_seeder(cpu_devices, layer)
    reopen_upload_cache()
    trace.reset_run()
    try:
        for plan_id in ("a", "b"):
            msg = _plan(plan_id, [(1, off, size)])
            contribute_device_plan(node, layers, threading.Lock(), fabric,
                                   placement, msg)
            got = collected(fabric, msg)
            assert b"".join(got[o] for o in sorted(got)) == (
                layer_bytes(0)[off:off + size])
        first, second = publish_spans()
        assert first["fields"]["host_copy_bytes"] == size
        assert first["fields"]["bytes"] == second["fields"]["bytes"] == size
        # the whole layer stays uploaded for the second plan; a small
        # range is read again (the cache keeps whole layers only)
        assert second["fields"]["host_copy_bytes"] == (
            0 if size == LAYER_SIZE else size)
        assert layer.inmem_data is None  # never materialized whole on host
    finally:
        release_upload_cache()
        for t in ts.values():
            t.close()


@pytest.mark.parametrize("held", ["bytearray", "memoryview", "bytes",
                                  "hbm-with-host-buffer", "fragment-offset"])
def test_view_span_views_what_the_host_holds(cpu_devices, held):
    """``view_span`` is ``read_span``'s bytes without the copy, for every
    kind of buffer a record's ``inmem_data`` is in practice."""
    data = layer_bytes(3)
    base = 0
    meta = LayerMeta(location=LayerLocation.INMEM)
    if held == "bytearray":
        buf = bytearray(data)
    elif held == "memoryview":  # the benchmark's blobs: a numpy array's
        buf = memoryview(np.frombuffer(data, np.uint8).copy())
    elif held == "bytes":
        buf = data
    elif held == "hbm-with-host-buffer":
        buf = bytearray(data)
        meta = LayerMeta(location=LayerLocation.HBM)
    else:  # a record that starts inside its buffer
        buf, base = bytearray(data), 512
    src = LayerSrc(inmem_data=buf, data_size=LAYER_SIZE - base, offset=base,
                   meta=meta)
    view = src.view_span(100, 1000)
    assert view.dtype == np.uint8 and view.shape == (1000,)
    assert view.tobytes() == src.read_span(100, 1000)
    assert view.tobytes() == data[base + 100:base + 1100]
    assert np.shares_memory(view, np.frombuffer(buf, np.uint8))
    assert src.view_span(0, src.data_size).tobytes() == src.read_range()
    if isinstance(buf, bytearray):
        # the view exports the buffer: the bytes cannot move under an
        # upload made from it
        with pytest.raises(BufferError):
            buf.extend(b"x")
    with pytest.raises(ValueError):
        src.view_span(LAYER_SIZE, 16)  # past the end: an error, no short read


@pytest.mark.parametrize("stream", [False, True])
def test_plan_in_chunks_out_of_order_completes_on_bytes_covered(
        cpu_devices, stream):
    """A range published as pieces, in any order and one of them twice,
    is one plan: the collect ends when the plan's bytes are covered, not
    at a count of contributions, and the ingest lands the layer exact."""
    plane = FabricPlane()
    data = layer_bytes(5)
    step = LAYER_SIZE // 8
    order = [5, 0, 7, 2, 2, 1, 6, 4, 3]  # piece 2 twice
    pieces = [(i * step, jax.device_put(
        np.frombuffer(data[i * step:(i + 1) * step], np.uint8),
        cpu_devices[i % 2])) for i in order]
    ingest = ShardedLayerIngest(LAYER_SIZE, list(cpu_devices)[2:4],
                                stream=stream)

    def seed():
        for k in range(0, len(pieces), 3):
            time.sleep(0.02)
            plane.publish_all("p", pieces[k:k + 3])

    t = threading.Thread(target=seed)
    t.start()
    seen = 0
    for off, arr in plane.collect("p", LAYER_SIZE, timeout=5.0):
        ingest.write(off, arr)
        seen += 1
    t.join(timeout=5.0)
    assert seen == len(pieces)  # the last piece completed it, none sooner
    assert array_to_bytes(ingest.finalize(timeout=5.0)) == data
    assert plane.pending() == 0


def test_plan_whose_seeder_stops_half_way_times_out(cpu_devices):
    """Half a plan's bytes, however many pieces they came in, is not the
    plan: the collect waits for the rest and times out with the count."""
    plane = FabricPlane()
    half = LAYER_SIZE // 2
    plane.publish_all("p", [
        (off, jax.device_put(np.zeros(half // 4, np.uint8), cpu_devices[0]))
        for off in range(0, half, half // 4)])
    seen = []
    with pytest.raises(TimeoutError, match=f"{half}/{LAYER_SIZE} bytes"):
        for item in plane.collect("p", LAYER_SIZE, timeout=0.3):
            seen.append(item)
    assert len(seen) == 4  # what was there was handed over first


def test_publish_of_a_held_layer_never_holds_the_gil_for_its_length(
        cpu_devices):
    """The property the pod's cold start rests on: while a seat publishes
    a 64 MiB layer its host holds, the process's other threads keep
    running.  A thread that sleeps 1 ms a turn got at most one turn
    inside the ``bytes()`` copy the publish used to make (one C call that
    never releases the GIL); now its longest stall is a fraction of that
    copy's time."""
    from distributed_llm_dissemination_tpu.runtime.send import (
        contribute_device_plan,
        release_upload_cache,
        reopen_upload_cache,
    )

    size = 64 << 20
    data = bytearray(size)
    data[::4096] = bytes(range(256)) * (size // 4096 // 256)

    class Ticker:
        def __enter__(self):
            self.stalls, self._stop = [], False
            self._turning = threading.Event()
            self._t = threading.Thread(target=self._run, daemon=True)
            self._t.start()
            assert self._turning.wait(5.0)  # measure from a running thread
            return self

        def _run(self):
            last = time.perf_counter()
            self._turning.set()
            while not self._stop:
                time.sleep(0.001)
                now = time.perf_counter()
                self.stalls.append(now - last)
                last = now

        def __exit__(self, *a):
            self._stop = True
            self._t.join(timeout=5.0)

    def once():
        with Ticker() as under_copy:
            t0 = time.perf_counter()
            copy = bytes(memoryview(data)[0:size])
            copy_s = time.perf_counter() - t0
        del copy
        layer = LayerSrc(inmem_data=data, data_size=size,
                         meta=LayerMeta(location=LayerLocation.INMEM))
        node, layers, fabric, placement, ts = _one_seeder(cpu_devices, layer)
        reopen_upload_cache()
        trace.reset_run()
        try:
            with Ticker() as under_publish:
                for k in range(4):
                    contribute_device_plan(
                        node, layers, threading.Lock(), fabric, placement,
                        _plan(f"p{k}", [(1, 0, size)], total=size))
            span = publish_spans()[0]["fields"]
            assert span["host_copy_bytes"] == 0 and span["bytes"] == size
            assert span["pieces"] == 8  # 64 MiB in UPLOAD_CHUNK_BYTES
            first = next(fabric.collect("p0", 1, timeout=5.0))
            assert array_to_bytes(first[1])[:4096] == bytes(data[:4096])
        finally:
            release_upload_cache()
            for t in ts.values():
                t.close()
        return (max(under_publish.stalls, default=0.0), copy_s,
                max(under_copy.stalls, default=0.0))

    # A loaded machine stalls any thread now and then: the property has
    # to show in one of three tries; the copy's own stall is there every
    # time (it is the whole copy).
    tries = [once() for _ in range(3)]
    assert all(copy_stall >= 0.5 * copy_s for _, copy_s, copy_stall in tries)
    assert any(stall < 0.25 * copy_s for stall, copy_s, _ in tries), tries


def test_fabric_collect_timeout_triggers_replan_recovery(cpu_devices,
                                                         monkeypatch):
    """Liveness: a plan whose contributions never arrive (lost seeder
    message, deep device fault) must not strand the dest forever — the
    dest is alive and heartbeating, so the failure detector won't fire.
    After the collect timeout the dest re-announces, and the leader's
    re-announce path re-plans the missing layer; the retry delivers."""
    from distributed_llm_dissemination_tpu.runtime import receiver as recv_mod

    monkeypatch.setattr(ReceiverNode, "FABRIC_COLLECT_TIMEOUT", 0.5)
    real_contribute = recv_mod.contribute_device_plan
    dropped = []

    def flaky_contribute(node, layers, lock, fabric, placement, msg, **kw):
        # The FIRST plan's contribution is lost; retries go through.
        if not dropped:
            dropped.append(msg.plan_id)
            return
        real_contribute(node, layers, lock, fabric, placement, msg, **kw)

    monkeypatch.setattr(recv_mod, "contribute_device_plan", flaky_contribute)

    ids = range(3)
    ts = inmem_transports(ids)
    assignment = {2: {0: LayerMeta()}}
    mesh = make_mesh((3, 2), ("pp", "tp"), devices=list(cpu_devices)[:6])
    placement = fabric_placement(list(ids), assignment, mesh, "pp")
    fabric = FabricPlane()
    leader = RetransmitLeaderNode(
        Node(0, 0, ts[0]), {}, assignment, expected_nodes=set(ids),
        fabric=fabric, placement=placement)
    receivers = [
        RetransmitReceiverNode(Node(1, 0, ts[1]), {0: mem_layer(0)},
                               fabric=fabric, placement=placement),
        RetransmitReceiverNode(Node(2, 0, ts[2]), {},
                               fabric=fabric, placement=placement),
    ]
    try:
        run_distribution(leader, receivers, assignment)
        assert dropped, "the fault was never injected"
        check_fabric_landing(receivers[-1], placement, [0])
    finally:
        close_all(leader, receivers, ts)


def test_hbm_only_layer_is_host_readable(cpu_devices):
    """A fabric-delivered layer (device array, no host copy) still serves
    the host paths: read_range materializes a cached host copy from HBM —
    so an HBM owner can re-serve peers and host-assemble at boot."""
    arr = jax.device_put(np.frombuffer(layer_bytes(0), np.uint8),
                         cpu_devices[0])
    src = LayerSrc(data_size=LAYER_SIZE,
                   meta=LayerMeta(location=LayerLocation.HBM),
                   device_array=arr)
    assert src.read_range() == layer_bytes(0)
    assert src.inmem_data is not None  # cached: later reads are free
    assert src.read_bytes() == layer_bytes(0)


def test_fabric_delivered_owner_reserves_to_second_dest(cpu_devices):
    """The full ownership chain: node 1 receives a layer over the fabric
    (HBM-only), then an assignment update makes it the preferred sender
    for node 2 — its contribution comes straight from its device array,
    and the whole chain still moves zero layer bytes over the transport.
    Regression: ack-derived status entries must carry the layer size, or
    the new owner is silently disqualified as a fabric sender."""
    ids = range(4)
    ts = inmem_transports(ids)
    sent = []
    plans = []
    for i, t in ts.items():
        orig = t.send

        def spy(dest, msg, _orig=orig, _i=i):
            sent.append((_i, dest, type(msg).__name__))
            if isinstance(msg, DevicePlanMsg):
                plans.append(msg)
            _orig(dest, msg)

        t.send = spy
    assignment = {1: {0: LayerMeta()}}
    mesh = make_mesh((4, 2), ("pp", "tp"))
    placement = fabric_placement(list(ids), assignment, mesh, "pp")
    fabric = FabricPlane()
    # Seeder 3 serves at a finite rate; once node 1 owns the layer its
    # ack-entry rate (0 = unlimited) makes it the preferred mode-2 sender.
    leader = PullRetransmitLeaderNode(
        Node(0, 0, ts[0]), {}, assignment, expected_nodes=set(ids),
        fabric=fabric, placement=placement)
    receivers = [
        RetransmitReceiverNode(
            Node(i, 0, ts[i]),
            {0: mem_layer(0, rate=1_000_000)} if i == 3 else {},
            fabric=fabric, placement=placement)
        for i in (1, 2, 3)
    ]
    try:
        run_distribution(leader, receivers, assignment)
        check_fabric_landing(receivers[0], placement, [0])
        # The ack-derived status row must know the layer's size.
        assert leader.status[1][0].data_size == LAYER_SIZE

        leader.update({1: {0: LayerMeta()}, 2: {0: LayerMeta()}})
        assert leader.ready().get(timeout=TIMEOUT)
        check_fabric_landing(receivers[1], placement, [0])
        assert "LayerMsg" not in {k for _, _, k in sent}
        # Node 1 (the fabric-delivered owner) was the second hop's sender.
        second_hop = [m for m in plans if m.dest_id == 2]
        assert second_hop and all(
            s == 1 for m in second_hop for s, _, _ in m.layout
        ), f"expected node 1 to serve the second dest, got {second_hop}"
    finally:
        close_all(leader, receivers, ts)


def test_fabric_bandwidths_prefer_ici():
    """Mesh.IciBW overrides every node's NIC for the fabric flow solve;
    without it, NetworkBW passes through unchanged."""
    from distributed_llm_dissemination_tpu.cli.podrun import fabric_bandwidths
    from distributed_llm_dissemination_tpu.core import config as cfg

    base = {
        "Nodes": [{"Id": 0, "Addr": ":1", "IsLeader": True,
                   "NetworkBW": 111},
                  {"Id": 1, "Addr": ":2", "NetworkBW": 222}],
        "Assignment": {}, "LayerSize": 1,
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [2], "Fabric": True,
                 "IciBW": 90_000_000_000},
    }
    conf = cfg.Config.from_json(base)
    assert fabric_bandwidths(conf) == {0: 90_000_000_000, 1: 90_000_000_000}
    base["Mesh"].pop("IciBW")
    conf = cfg.Config.from_json(base)
    assert fabric_bandwidths(conf) == {0: 111, 1: 222}


def test_mesh_slices_build_pod_topology():
    """Mesh.Slices + DcnBW parse into the solver's PodTopology; either
    missing means single-slice (no DCN modeling)."""
    from distributed_llm_dissemination_tpu.core import config as cfg

    base = {
        "Nodes": [{"Id": 0, "Addr": ":1", "IsLeader": True, "NetworkBW": 1},
                  {"Id": 4, "Addr": ":2", "NetworkBW": 1}],
        "Assignment": {}, "LayerSize": 1,
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [2], "Fabric": True,
                 "Slices": {"0": 0, "4": 1}, "DcnBW": 12_500_000_000},
    }
    topo = cfg.Config.from_json(base).mesh.topology()
    assert topo is not None
    assert topo.slices() == {0: 0, 4: 1}
    assert topo.dcn_bw == 12_500_000_000
    base["Mesh"].pop("DcnBW")
    assert cfg.Config.from_json(base).mesh.topology() is None
    # The shipped 2-slice example config round-trips through the loader.
    conf = cfg.read_json("conf/tpu_2slice_dcn.json")
    topo = conf.mesh.topology()
    assert topo is not None and len(set(topo.slices().values())) == 2


@pytest.mark.slow
@pytest.mark.timeout(420)
def test_podrun_fabric_v5e32_shape(tmp_path):
    """The north-star topology at virtual scale: the shipped v5e-32
    Llama-3-70B pipeline placement (8 hosts x 4 chips, 80 layers, every
    node a stage) disseminates over the fabric on a 32-device virtual
    mesh — run as a subprocess so this test gets its own 32-device
    backend (the session's conftest mesh is 8)."""
    import json
    import subprocess
    import sys

    with open("conf/tpu_v5e32_llama70b.json") as f:
        conf = json.load(f)
    conf["Mesh"]["Fabric"] = True
    for n in conf["Nodes"]:
        for by_layer in (n.get("InitialLayers") or {}).values():
            for lc in by_layer.values():
                lc["LayerSize"] = 64 * 1024
    conf["LayerSize"] = 64 * 1024
    conf_path = tmp_path / "v5e32_fabric.json"
    conf_path.write_text(json.dumps(conf))

    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
    proc = subprocess.run(
        [sys.executable, "-m",
         "distributed_llm_dissemination_tpu.cli.podrun",
         "-f", str(conf_path), "-m", "3"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        timeout=300, env=env, text=True,
    )
    assert proc.returncode == 0, f"podrun failed:\n{proc.stderr[-3000:]}"
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["fabric"] is True
    assert summary["nodes"] == 8
    assert 0 < summary["ttd_s"] < 120
    # Every layer moved on the device plane: no TCP/LayerMsg host sends
    # appear in the run's logs (the control messages do).
    assert "dispatching device plan" in proc.stderr
    assert "start sending layer" not in proc.stderr


def test_podrun_cli(tmp_path, cpu_devices):
    """The single-controller pod driver end-to-end (in-process, not a
    subprocess: podrun shares this test session's virtual mesh)."""
    from distributed_llm_dissemination_tpu.cli.podrun import run_pod
    from distributed_llm_dissemination_tpu.core import config as cfg

    conf = cfg.read_json("conf/pod_fabric_4node.json")
    # Shrink layers for test speed.
    for nc in conf.nodes:
        for by_layer in nc.initial_layers.values():
            for lid in by_layer:
                by_layer[lid] = 256 * 1024
    summary = run_pod(conf, mode=3, timeout=60.0)
    assert summary["fabric"] is True
    assert summary["ttd_s"] > 0
    assert summary["nodes"] == 4


def test_mode3_equal_layers_batch_into_one_gather(cpu_devices):
    """Plan batching e2e: equal-size layers to one dest get stamped with
    one batch id by the leader and land byte-exact in HBM — the dest
    finishes the group through ONE batched gather (finalize_many)."""
    from distributed_llm_dissemination_tpu.parallel import plan_cache

    ids = range(4)
    ts = inmem_transports(ids)
    sent_plans = []
    for i, t in ts.items():
        orig = t.send

        def spy(dest, msg, _orig=orig):
            if isinstance(msg, DevicePlanMsg):
                sent_plans.append(msg)
            _orig(dest, msg)

        t.send = spy
    assignment = {3: {0: LayerMeta(), 1: LayerMeta(), 2: LayerMeta()}}
    leader, receivers, placement = _fabric_cluster(
        3, ids, assignment, seeders={1, 2}, transports=ts, layer_count=3)
    plan_cache.reset_stats()
    try:
        run_distribution(leader, receivers, assignment)
        check_fabric_landing(receivers[-1], placement, [0, 1, 2])
        # The leader stamped same-dest equal-size plans as one batch.
        stamped = {m.plan_id: (m.batch_id, m.batch_n) for m in sent_plans
                   if m.batch_id}
        assert stamped, "no batch hints on equal-size same-dest plans"
        batch_ns = {bn for _, bn in stamped.values()}
        assert max(batch_ns) >= 2
        # Amortization: one batched gather for the whole group — fewer
        # compiled collectives than delivered layers.
        stats = plan_cache.GATHER_CACHE.stats()
        assert stats["misses"] < 3, stats
    finally:
        close_all(leader, receivers, ts)
