"""Integrity-plane tests (docs/integrity.md): per-fragment CRC drop +
NACK retransmit, leader-stamped layer digests (mismatch re-opens the
covered intervals instead of acking), journal resume rejecting tampered
disk bytes, the deterministic fault-injection transport, and the chaos
soak — modes 0-3 on both backends under seeded corrupt/drop/dup/delay
faults must deliver byte-exactly with no corrupted fragment ever
reaching interval accounting, the journal, or a device buffer.
"""

import os
import queue
import threading
import time
import zlib

import pytest

from distributed_llm_dissemination_tpu.core.types import (
    LayerLocation,
    LayerMeta,
    LayerSrc,
    SourceType,
)
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
    FaultRule,
    FaultyTransport,
    InmemTransport,
    LayerMsg,
    LayerNackMsg,
    MsgType,
    TcpTransport,
    reset_registry,
    rules_from_spec,
)
from distributed_llm_dissemination_tpu.transport.messages import (
    DevicePlanMsg,
    LayerDigestsMsg,
)
from distributed_llm_dissemination_tpu.utils import integrity, trace

TIMEOUT = 10.0


@pytest.fixture(autouse=True)
def _clean():
    reset_registry()
    trace.reset_counters()
    yield
    reset_registry()


def layer_bytes(layer_id: int, size: int = 4096) -> bytes:
    return bytes([(layer_id * 37 + i) % 256 for i in range(size)])


def mem_layer(layer_id: int, size: int = 4096) -> LayerSrc:
    data = bytearray(layer_bytes(layer_id, size))
    return LayerSrc(
        inmem_data=data, data_size=len(data),
        meta=LayerMeta(location=LayerLocation.INMEM,
                       source_type=SourceType.MEM),
    )


def make_transports(kind, ids):
    if kind == "inmem":
        registry = {i: f"n{i}" for i in ids}
        return {i: InmemTransport(registry[i], addr_registry=registry)
                for i in ids}
    ts = {i: TcpTransport("127.0.0.1:0") for i in ids}
    registry = {i: ts[i].get_address() for i in ids}
    for t in ts.values():
        t.addr_registry.update(registry)
    return ts


def close_all(leader, receivers, transports):
    leader.close()
    for r in receivers:
        r.close()
    for t in transports.values():
        t.close()


# --------------------------------------------------------------- primitives


def test_integrity_helpers():
    data = b"x" * 100_000
    assert integrity.fragment_crc(data) == (zlib.crc32(data) & 0xFFFFFFFF)
    # Negotiated fragment stamp: xxh3 where available, crc32 otherwise.
    algo, value = integrity.fragment_checksum(data)
    assert algo in ("xxh3", "crc32")
    assert integrity.checksum_of(data, algo) == value
    kwargs = {"xxh3": value} if algo == "xxh3" else {"crc": value}
    assert integrity.verify_stamp(data, **kwargs) is True
    assert integrity.verify_stamp(b"y" + data[1:], **kwargs) is False
    assert integrity.verify_stamp(data) is None  # unstamped: advisory
    # Self-describing digest: "xxh3:<hex>" or bare hex (blake2b-128).
    d = integrity.layer_digest(data)
    if d.startswith("xxh3:"):
        assert len(d) == len("xxh3:") + 32
    else:
        assert len(d) == 2 * integrity.DIGEST_SIZE
    assert d != integrity.layer_digest(b"y" + data[1:])
    assert integrity.digest_matches(data, d)
    # Cross-algorithm interop: a blake2b stamp verifies by ITS OWN
    # algorithm even when the local default is xxh3.
    b2 = integrity.layer_digest(data, algo="blake2b")
    assert len(b2) == 2 * integrity.DIGEST_SIZE
    assert integrity.digest_matches(data, b2)
    assert not integrity.digest_matches(b"y" + data[1:], b2)
    src = mem_layer(3)
    assert integrity.digest_layer_src(src) == integrity.layer_digest(
        bytes(src.inmem_data))


def test_file_checksum_matches_inmem(tmp_path):
    data = layer_bytes(5, 300_000)
    p = tmp_path / "blob"
    p.write_bytes(b"pad" + data + b"tail")
    algo, value = integrity.file_checksum(str(p), 3, len(data))
    assert (algo, value) == integrity.fragment_checksum(data)
    assert integrity.file_crc(str(p), 3, len(data)) == \
        integrity.fragment_crc(data)


def test_hash_bench_shape():
    rates = integrity.hash_bench(nbytes=2 << 20)
    for key in ("crc32_gbps", "blake2b_gbps"):
        assert rates[key] > 0


_REL = integrity._XXH3_RELEASE_MIN


def _as_container(kind: str, data: bytes):
    if kind == "bytes":
        return data
    if kind == "bytearray":
        return bytearray(data)
    if kind == "memoryview":
        # A slice at an odd offset of a larger buffer, as a frame's view
        # into a reassembly buffer is.
        return memoryview(bytearray(b"\x5a" * 3 + data + b"\xa5" * 5))[
            3 : 3 + len(data)]
    import numpy as np

    return np.frombuffer(bytearray(data), dtype=np.uint8)


@pytest.mark.parametrize("kind",
                         ["bytes", "bytearray", "memoryview", "numpy"])
@pytest.mark.parametrize(
    "length", [0, 1, 4096, _REL - 1, _REL, _REL + 1, 16 << 20],
    ids=["0", "1", "4KiB", "below", "threshold", "above", "16MiB"])
def test_frame_checksum_same_bits_every_length(length, kind):
    """The frame stamp is xxh3-64 of the bytes whichever call computes
    it (one-shot below ``_XXH3_RELEASE_MIN``, the GIL-releasing
    ``update`` from it on): a frame stamped by the old code verifies
    under the new and the reverse."""
    xxhash = pytest.importorskip("xxhash")
    data = (layer_bytes(11, 4099) * (length // 4099 + 1))[:length]
    v = _as_container(kind, data)
    want = xxhash.xxh3_64_intdigest(data)
    assert integrity.fragment_checksum(v) == ("xxh3", want)
    assert integrity.checksum_of(v, "xxh3") == want
    assert integrity.verify_stamp(v, xxh3=want) is True
    if length:
        flipped = bytearray(data)
        flipped[length // 2] ^= 0x10
        assert integrity.verify_stamp(
            _as_container(kind, bytes(flipped)), xxh3=want) is False
    else:
        assert integrity.verify_stamp(v, xxh3=want ^ 1) is False


def ticker_gap(fn):
    """Run ``fn`` on this thread beside a ticker thread that spins in
    pure Python: ``(fn's seconds, the ticker's longest gap)``.  A native
    call that holds the GIL shows as a gap as long as the call; one that
    releases it as about one switch interval (PERF.md §6, PR 36, has the
    chip host's readings of this probe)."""
    stop, started, gap = threading.Event(), threading.Event(), [0.0]

    def tick():
        last = time.monotonic()
        started.set()
        while not stop.is_set():
            now = time.monotonic()
            gap[0] = max(gap[0], now - last)
            last = now

    t = threading.Thread(target=tick, name="gil-ticker", daemon=True)
    t.start()
    started.wait()
    time.sleep(0.02)  # the ticker is spinning, and holds the GIL
    gap[0] = 0.0
    t0 = time.monotonic()
    fn()
    took, longest = time.monotonic() - t0, gap[0]
    stop.set()
    t.join()
    return took, longest


def test_frame_checksum_lets_python_run():
    """While one thread verifies a long frame, another thread keeps
    running Python: the ticker's longest gap stays under half the hash's
    time (the one-shot call stalled it for four fifths).  Best of three,
    so a busy host's scheduler does not decide it."""
    xxhash = pytest.importorskip("xxhash")
    view = memoryview(bytearray(os.urandom(1 << 20)) * 256)
    want = xxhash.xxh3_64_intdigest(view)
    tries = []
    for _ in range(3):
        ok = []
        took, gap = ticker_gap(
            lambda: ok.append(integrity.verify_stamp(view, xxh3=want)))
        assert ok == [True]
        tries.append((gap / took, gap, took))
        if gap < took / 2:
            return
    pytest.fail(f"ticker stalled for over half the hash in every try: "
                f"{tries}")


def test_fault_rules_deterministic():
    seed, rules = rules_from_spec("seed=2,corrupt=3,times=2")
    assert seed == 2
    (rule,) = rules
    fires = [rule.should_fire(seed) for _ in range(12)]
    # Phase seed%3 = 2 -> fires on the 3rd and 6th matches, then the
    # times cap silences it.
    assert fires == [False, False, True, False, False, True] + [False] * 6


# --------------------------------------------------- fault transport (unit)


@pytest.mark.parametrize("kind", ["inmem", "tcp"])
def test_fault_transport_drops_plan_seq_first_delivery(kind):
    ts = make_transports(kind, range(2))
    try:
        seed, rules = rules_from_spec("drop-plan-seqs=5")
        faulty = FaultyTransport(ts[1], rules, seed=seed)
        plan = DevicePlanMsg(0, "p.5", 0, 1, 10, [(0, 0, 10)], seq=5)
        other = DevicePlanMsg(0, "p.6", 0, 1, 10, [(0, 0, 10)], seq=6)
        ts[0].send(1, plan)
        ts[0].send(1, other)
        got = faulty.deliver().get(timeout=TIMEOUT)
        assert got.seq == 6  # seq 5's first delivery vanished
        ts[0].send(1, plan)  # the re-send (gap recovery) passes
        assert faulty.deliver().get(timeout=TIMEOUT).seq == 5
        assert faulty.stats["drop"] == 1
    finally:
        for t in ts.values():
            t.close()


def test_fault_transport_outbound_reset_and_dup():
    ts = make_transports("inmem", range(2))
    try:
        rules = [FaultRule("reset", "out", msg_type=MsgType.LAYER, times=1),
                 FaultRule("dup", "out", msg_type=MsgType.LAYER, times=1)]
        faulty = FaultyTransport(ts[0], rules)
        msg = LayerMsg(0, 7, mem_layer(7), 4096)
        with pytest.raises(ConnectionError):
            faulty.send(1, msg)
        faulty.send(1, msg)  # reset exhausted; dup fires -> two copies
        ts[1].deliver().get(timeout=TIMEOUT)
        ts[1].deliver().get(timeout=TIMEOUT)
        assert faulty.stats["reset"] == 1 and faulty.stats["dup"] == 1
    finally:
        for t in ts.values():
            t.close()


# ------------------------------------------------- CRC drop + NACK (wired)


@pytest.mark.parametrize("kind", ["inmem", "tcp"])
def test_corrupt_layer_dropped_nacked_and_retransmitted(kind):
    """Mode 0 end to end: the first delivery of the layer is corrupted
    below the CRC check on the dest's transport; the transport drops it
    (it never reaches the store), the dest NACKs, the leader
    retransmits, and delivery completes byte-exact."""
    ts = make_transports(kind, range(2))
    seed, rules = rules_from_spec("corrupt=1,times=1")
    faulty = FaultyTransport(ts[1], rules, seed=seed)
    assignment = {1: {0: LayerMeta()}}
    leader = LeaderNode(Node(0, 0, ts[0]), {0: mem_layer(0)}, assignment)
    receiver = ReceiverNode(Node(1, 0, faulty), {})
    try:
        receiver.announce()
        leader.ready().get(timeout=TIMEOUT)
        receiver.ready().get(timeout=TIMEOUT)
        assert bytes(receiver.layers[0].inmem_data) == layer_bytes(0)
        assert faulty.stats["corrupt"] == 1
        counts = trace.counter_totals()
        assert counts.get("integrity.crc_drop", 0) >= 1
        assert counts.get("integrity.nack_sent", 0) >= 1
        assert counts.get("integrity.retransmit_frags", 0) >= 1
        # The digest stamped by the leader verified on the dest.
        assert 0 in receiver._digest_ok
    finally:
        close_all(leader, [receiver], ts)


@pytest.mark.parametrize("kind", ["inmem", "tcp"])
def test_mode3_corrupt_fragment_nack_retransmit(kind):
    """Mode 3: one fragment of a multi-fragment flow transfer is
    dropped by injection; the NACKed byte range is retransmitted and
    interval reassembly completes byte-exactly."""
    ts = make_transports(kind, range(2))
    seed, rules = rules_from_spec("dropin=1,times=1")
    faulty = FaultyTransport(ts[1], rules, seed=seed)
    size = 96 * 1024
    os.environ["DLD_FLOW_FRAGMENT_BYTES"] = str(32 * 1024)
    import distributed_llm_dissemination_tpu.runtime.send as send_mod

    old_frag = send_mod.FLOW_FRAGMENT_BYTES
    send_mod.FLOW_FRAGMENT_BYTES = 32 * 1024
    assignment = {1: {0: LayerMeta()}}
    leader = FlowRetransmitLeaderNode(
        Node(0, 0, ts[0]), {0: mem_layer(0, size)}, assignment,
        node_network_bw={0: 10 ** 9, 1: 10 ** 9},
    )
    receiver = FlowRetransmitReceiverNode(Node(1, 0, faulty), {})
    try:
        receiver.announce()
        leader.ready().get(timeout=TIMEOUT)
        receiver.ready().get(timeout=TIMEOUT)
        assert bytes(receiver.layers[0].inmem_data) == layer_bytes(0, size)
        counts = trace.counter_totals()
        assert counts.get("integrity.nack_sent", 0) >= 1
        assert counts.get("integrity.retransmit_frags", 0) >= 1
    finally:
        send_mod.FLOW_FRAGMENT_BYTES = old_frag
        os.environ.pop("DLD_FLOW_FRAGMENT_BYTES", None)
        close_all(leader, [receiver], ts)


def test_gap_watchdog_renacks_quiet_partial_layer(monkeypatch):
    """Silent frame loss (the retransmit itself eaten, a reset
    mid-flight): a partial layer whose coverage sits still for a full
    watchdog interval gets its uncovered gaps re-NACKed (reason
    "stale") to the last-seen sender — recovery never depends on one
    NACK round-trip surviving the faulty path — and a late fragment
    still completes the layer byte-exactly."""
    monkeypatch.setenv("DLD_GAP_NACK_S", "0.2")
    ts = make_transports("inmem", range(2))
    receiver = FlowRetransmitReceiverNode(
        Node(1, 0, ts[1]), {}, start_loop=False)
    try:
        size = 8192
        data = layer_bytes(0, size)
        first = LayerSrc(
            inmem_data=bytearray(data[:4096]), data_size=4096, offset=0,
            meta=LayerMeta(location=LayerLocation.INMEM))
        receiver.handle_layer(LayerMsg(0, 0, first, size))
        nack = ts[0].deliver().get(timeout=TIMEOUT)
        assert isinstance(nack, LayerNackMsg)
        assert (nack.layer_id, nack.offset, nack.size) == (0, 4096, 4096)
        assert nack.reason == "stale"
        assert trace.counter_totals().get("integrity.gap_renack", 0) >= 1
        second = LayerSrc(
            inmem_data=bytearray(data[4096:]), data_size=4096, offset=4096,
            meta=LayerMeta(location=LayerLocation.INMEM))
        receiver.handle_layer(LayerMsg(0, 0, second, size))
        assert bytes(receiver.layers[0].inmem_data) == data
        # Completion cleans the watchdog bookkeeping with the partials.
        assert 0 not in receiver._frag_src and 0 not in receiver._frag_t
        while True:  # further stale NACKs may precede the ack
            msg = ts[0].deliver().get(timeout=TIMEOUT)
            if type(msg).__name__ == "AckMsg":
                assert msg.layer_id == 0
                break
            assert isinstance(msg, LayerNackMsg)
    finally:
        receiver.close()
        for t in ts.values():
            t.close()


def test_gap_watchdog_armed_by_corrupt_first_fragment(monkeypatch):
    """A layer whose FIRST (and only) frame was dropped as corrupt has
    no successful store to arm the watchdog — the corrupt report itself
    must arm it, or an eaten retransmit stalls the layer until crash
    detection."""
    monkeypatch.setenv("DLD_GAP_NACK_S", "0.2")
    ts = make_transports("inmem", range(2))
    receiver = FlowRetransmitReceiverNode(
        Node(1, 0, ts[1]), {}, start_loop=False)
    try:
        size = 8192
        # The zero-copy sink claims the range, the transport fails the
        # CRC and rolls the claim back, then reports the drop.
        view, tok, abort = receiver._layer_sink(0, size, 0, 4096)
        abort()
        receiver._on_corrupt_fragment(0, 0, 0, 4096, size, "crc")
        assert receiver._frag_src.get(0) == 0  # watchdog armed
        first = ts[0].deliver().get(timeout=TIMEOUT)
        assert isinstance(first, LayerNackMsg) and first.reason == "crc"
        # The immediate NACK's retransmit never arrives: the quiet-gap
        # ticker re-requests the WHOLE uncovered layer.
        stale = ts[0].deliver().get(timeout=TIMEOUT)
        assert isinstance(stale, LayerNackMsg)
        assert (stale.offset, stale.size) == (0, size)
        assert stale.reason == "stale"
    finally:
        receiver.close()
        for t in ts.values():
            t.close()


# ------------------------------------------------------------ layer digests


def test_leader_own_digest_wins_over_conflicting_announce():
    """A rotted holder's announce racing the leader's background hash
    must not let the rot self-verify: the leader's own digest (just
    computed from local bytes) overrides, loudly."""
    import types

    fake = types.SimpleNamespace(
        layers={0: mem_layer(0)},
        _lock=threading.Lock(),
        # Rotted announce: same algorithm as the leader's own digest —
        # a DIFFERENT-algorithm stamp is a capability difference, not a
        # conflict, and must not alarm.
        layer_digests={0: integrity.layer_digest(b"rotted bytes")},
        _digests_ready=threading.Event(),
    )
    LeaderNode._compute_own_digests(fake)
    assert fake.layer_digests[0] == integrity.layer_digest(layer_bytes(0))
    assert fake._digests_ready.is_set()
    assert trace.counter_totals().get("integrity.digest_conflict", 0) == 1


def test_mixed_algorithm_digest_announce_is_not_a_conflict():
    """Holders with different hash capabilities stamp different STRINGS
    over identical bytes (xxh3:<hex> vs bare blake2b hex) — that is a
    capability difference, not corruption: no conflict alarm, and the
    leader's own digest still wins the stamp."""
    import types

    own_algo = integrity.digest_algo()
    if own_algo != "xxh3":
        pytest.skip("no second digest algorithm available on this host")
    other_stamp = integrity.layer_digest(layer_bytes(0), algo="blake2b")
    fake = types.SimpleNamespace(
        layers={0: mem_layer(0)},
        _lock=threading.Lock(),
        layer_digests={0: other_stamp},
        _digests_ready=threading.Event(),
    )
    LeaderNode._compute_own_digests(fake)
    assert fake.layer_digests[0] == integrity.layer_digest(layer_bytes(0))
    assert trace.counter_totals().get("integrity.digest_conflict", 0) == 0


def test_digest_check_uses_stamp_algorithm():
    data = layer_bytes(3)
    for algo in ("blake2b", None):
        stamp = integrity.layer_digest(data, algo=algo)
        ok, dt, got = integrity.digest_check(data, stamp)
        assert ok is True and got == stamp and dt >= 0.0
        bad, _, _ = integrity.digest_check(b"y" + data[1:], stamp)
        assert bad is False
    assert integrity.digest_matches(data, integrity.layer_digest(data))


def test_digest_mismatch_whole_layer_not_stored_and_nacked():
    ts = make_transports("inmem", range(2))
    receiver = ReceiverNode(Node(1, 0, ts[1]), {}, start_loop=False)
    try:
        receiver.handle_layer_digests(
            LayerDigestsMsg(0, {0: "00" * integrity.DIGEST_SIZE}))
        receiver.handle_layer(LayerMsg(0, 0, mem_layer(0), 4096))
        assert 0 not in receiver.layers  # never stored, never acked
        nack = ts[0].deliver().get(timeout=TIMEOUT)
        assert isinstance(nack, LayerNackMsg)
        assert (nack.layer_id, nack.offset, nack.size) == (0, 0, 4096)
        assert nack.reason == "digest"
        # Correct stamp -> the same bytes land and ack.
        receiver.layer_digests[0] = integrity.layer_digest(layer_bytes(0))
        receiver.handle_layer(LayerMsg(0, 0, mem_layer(0), 4096))
        assert bytes(receiver.layers[0].inmem_data) == layer_bytes(0)
    finally:
        receiver.close()
        for t in ts.values():
            t.close()


@pytest.mark.parametrize("order", ["fwd", "rev"])
def test_mode3_digest_mismatch_reopens_intervals(order, tmp_path):
    """A completed mode-3 layer whose digest mismatches is DEMOTED:
    store entry removed, partial state + journal wiped, re-announce
    fired — and never acked.  A correct re-delivery (any fragment
    order) then completes, verifies, journals cleanly, and acks."""
    ts = make_transports("inmem", range(2))
    receiver = FlowRetransmitReceiverNode(
        Node(1, 0, ts[1]), {}, start_loop=False,
        checkpoint_dir=str(tmp_path / "ckpt"))
    try:
        size = 8192
        data = layer_bytes(0, size)
        receiver.handle_layer_digests(
            LayerDigestsMsg(0, {0: "00" * integrity.DIGEST_SIZE}))

        def feed():
            halves = [(0, data[:4096]), (4096, data[4096:])]
            if order == "rev":
                halves.reverse()
            for off, chunk in halves:
                frag = LayerSrc(
                    inmem_data=bytearray(chunk), data_size=len(chunk),
                    offset=off,
                    meta=LayerMeta(location=LayerLocation.INMEM))
                receiver.handle_layer(LayerMsg(0, 0, frag, size))

        def next_protocol_msg():
            # The announce path also emits advisory telemetry traffic
            # (TimeSyncMsg probes, MetricsReportMsg snapshots —
            # docs/observability.md); this test cares about the
            # PROTOCOL sequence, so skip those.
            while True:
                msg = ts[0].deliver().get(timeout=TIMEOUT)
                if type(msg).__name__ not in ("TimeSyncMsg",
                                              "MetricsReportMsg"):
                    return msg

        feed()
        assert 0 not in receiver.layers  # demoted, not acked
        assert 0 not in receiver._partial  # intervals re-opened
        assert not os.path.exists(
            str(tmp_path / "ckpt" / "0.meta.json"))  # journal wiped
        # The mismatch triggered a recovery re-announce to the leader.
        ann = next_protocol_msg()
        assert type(ann).__name__ == "AnnounceMsg"
        # Correct stamp -> re-delivery completes and acks.
        receiver.layer_digests[0] = integrity.layer_digest(data)
        feed()
        assert bytes(receiver.layers[0].inmem_data) == data
        ack = next_protocol_msg()
        assert type(ack).__name__ == "AckMsg" and ack.layer_id == 0
    finally:
        receiver.close()
        for t in ts.values():
            t.close()


def test_stamp_after_delivery_demotes_corrupt_layer():
    """Handlers run on an unordered pool, so a layer can land (and ack)
    BEFORE its digest stamp arrives.  The late stamp must re-check the
    held copy retroactively: a mismatch demotes it and re-announces."""
    ts = make_transports("inmem", range(2))
    receiver = ReceiverNode(Node(1, 0, ts[1]), {}, start_loop=False)
    try:
        # No digest known yet -> the layer stores and acks.
        receiver.handle_layer(LayerMsg(0, 0, mem_layer(0), 4096))
        assert 0 in receiver.layers
        ack = ts[0].deliver().get(timeout=TIMEOUT)
        assert type(ack).__name__ == "AckMsg"
        # The stamp arrives late and mismatches: demote + re-announce.
        receiver.handle_layer_digests(
            LayerDigestsMsg(0, {0: "00" * integrity.DIGEST_SIZE}))
        assert 0 not in receiver.layers
        ann = ts[0].deliver().get(timeout=TIMEOUT)
        assert type(ann).__name__ == "AnnounceMsg"
        # A MATCHING late stamp leaves a held layer alone.
        receiver.layer_digests.clear()
        receiver.handle_layer(LayerMsg(0, 0, mem_layer(0), 4096))
        receiver.handle_layer_digests(
            LayerDigestsMsg(0, {0: integrity.layer_digest(layer_bytes(0))}))
        assert bytes(receiver.layers[0].inmem_data) == layer_bytes(0)
        assert 0 in receiver._digest_ok
    finally:
        receiver.close()
        for t in ts.values():
            t.close()


def test_stream_stager_rejects_bad_digest_bulk_boot_infills():
    """The streamed stager verifies each blob before decode dispatch: a
    bad digest fails that blob's staging (absent from collect); blobs
    the ack path already verified skip the re-hash."""
    from distributed_llm_dissemination_tpu.models import serde
    from distributed_llm_dissemination_tpu.models.llama import CONFIGS
    from distributed_llm_dissemination_tpu.runtime.stream_boot import (
        StreamingBootStager,
    )

    cfg = CONFIGS["tiny"]
    blobs = {bid: serde.seeded_blob(cfg, bid, seed=0)
             for bid in range(serde.head_blob_id(cfg) + 1)}
    digests = {bid: integrity.layer_digest(b) for bid, b in blobs.items()}
    bad_id = 0
    digests[bad_id] = "00" * integrity.DIGEST_SIZE
    verified = set()
    stager = StreamingBootStager(
        cfg, digest_lookup=digests.get, digest_verified=verified)
    try:
        for bid, b in blobs.items():
            src = LayerSrc(inmem_data=bytearray(b), data_size=len(b),
                           meta=LayerMeta(location=LayerLocation.INMEM))
            assert stager.submit(bid, src)
        staged = stager.collect(list(blobs), timeout=60.0)
        assert bad_id not in staged  # staging failed its digest check
        assert set(staged) == set(blobs) - {bad_id}
        # Good blobs are now memoized as verified.
        assert verified == set(blobs) - {bad_id}
        assert trace.counter_totals().get(
            "integrity.digest_mismatch", 0) >= 1
    finally:
        stager.close()


def test_stager_invalidate_allows_restage():
    """The stamp-race teardown: a blob staged BEFORE its (mismatching)
    digest stamp arrived is invalidated on demotion — the dedup marker
    clears, the redelivered bytes re-stage, and collect() returns leaves
    decoded from the NEW bytes, not the corrupt ones."""
    import numpy as np

    from distributed_llm_dissemination_tpu.models import serde
    from distributed_llm_dissemination_tpu.models.llama import CONFIGS
    from distributed_llm_dissemination_tpu.runtime.stream_boot import (
        StreamingBootStager,
    )

    cfg = CONFIGS["tiny"]
    corrupt = serde.seeded_blob(cfg, 0, seed=1)  # "wrong" bytes
    good = serde.seeded_blob(cfg, 0, seed=0)

    def src_of(b):
        return LayerSrc(inmem_data=bytearray(b), data_size=len(b),
                        meta=LayerMeta(location=LayerLocation.INMEM))

    stager = StreamingBootStager(cfg)
    try:
        assert stager.submit(0, src_of(corrupt))
        first = stager.collect([0], timeout=60.0)[0]
        assert not stager.submit(0, src_of(good))  # duplicate: no-op
        stager.invalidate(0)
        assert stager.submit(0, src_of(good))  # marker cleared: restages
        second = stager.collect([0], timeout=60.0)[0]
        leaf = next(iter(first))
        assert not np.array_equal(np.asarray(first[leaf]),
                                  np.asarray(second[leaf]))
    finally:
        stager.close()


# ----------------------------------------------------------------- journal


def test_journal_resume_rejects_tampered_disk_bytes(tmp_path):
    store = LayerCheckpointStore(str(tmp_path))
    a = layer_bytes(1, 4096)
    b = layer_bytes(2, 4096)
    crcs = [(0, 4096, zlib.crc32(a) & 0xFFFFFFFF),
            (4096, 4096, zlib.crc32(b) & 0xFFFFFFFF)]
    store.write_bytes(1, 0, a, 8192)
    store.write_bytes(1, 4096, b, 8192)
    store.write_meta(1, [(0, 8192)], 8192, frag_crcs=crcs)
    # Clean resume: everything covered.
    state = store.load()
    buf, covered, total = state[1]
    assert covered == [(0, 8192)] and bytes(buf) == a + b
    # Tamper one byte of the SECOND fragment on disk.
    part = tmp_path / "1.part"
    raw = bytearray(part.read_bytes())
    raw[5000] ^= 0xFF
    part.write_bytes(bytes(raw))
    state = LayerCheckpointStore(str(tmp_path)).load()
    buf, covered, total = state[1]
    assert covered == [(0, 4096)]  # tampered range re-opened
    assert bytes(buf[:4096]) == a
    assert trace.counter_totals().get(
        "integrity.journal_bad_range", 0) == 1


def test_journal_legacy_meta_without_crcs_still_loads(tmp_path):
    store = LayerCheckpointStore(str(tmp_path))
    a = layer_bytes(1, 1024)
    store.write_bytes(1, 0, a, 1024)
    store.write_meta(1, [(0, 1024)], 1024)  # no FragCrcs (legacy)
    state = store.load()
    assert state[1][1] == [(0, 1024)]


# --------------------------------------------------- stale-group TTL NACK


def test_ttl_pruned_stripe_group_is_nacked(monkeypatch):
    """A striped transfer abandoned mid-way (sender died after stripe 0)
    is TTL-pruned AND NACKed: the receiver asks the source for the whole
    span instead of waiting for crash detection."""
    from distributed_llm_dissemination_tpu.transport import tcp as tcp_mod

    monkeypatch.setattr(tcp_mod, "_STRIPE_GROUP_TTL", 0.4)
    ts = make_transports("tcp", range(2))
    got = queue.Queue()
    ts[1].on_corrupt = lambda *a: got.put(a)
    try:
        payload = layer_bytes(9, 64 * 1024)
        sub = LayerSrc(inmem_data=bytearray(payload), data_size=32 * 1024,
                       offset=0,
                       meta=LayerMeta(location=LayerLocation.INMEM))
        stripe = {"idx": 0, "n": 2, "off": 0, "span": len(payload),
                  "tid": "deadbeef"}
        dest = ts[1].get_address()
        ts[0]._send_one_stream(dest, LayerMsg(0, 9, sub, len(payload)),
                               stripe=stripe)
        src_id, layer_id, off, size, total, reason = got.get(timeout=TIMEOUT)
        assert (src_id, layer_id, off, size) == (0, 9, 0, len(payload))
        assert reason == "stale"
        with ts[1]._lock:
            assert not ts[1]._stripe_groups  # buffer released
    finally:
        for t in ts.values():
            t.close()


# -------------------------------------------------------------- chaos soak


def _build_cluster(kind, mode, n_receivers=3, layer_size=24 * 1024,
                   fault_spec=""):
    """1 leader + n receivers, every node's transport wrapped in the
    seeded fault layer.  Receiver i+1 initially holds layer 100+i (so
    modes 1-3 retransmit peer-held layers); the leader holds layers
    0..n-1.  Mode 0's leader sends only its OWN layers, so peer-held
    layers are assigned only in modes 1-3."""
    ids = range(n_receivers + 1)
    raw = make_transports(kind, ids)
    ts = {}
    for i in ids:
        if fault_spec:
            seed, rules = rules_from_spec(fault_spec)
            ts[i] = FaultyTransport(raw[i], rules, seed=seed + i)
        else:
            ts[i] = raw[i]
    assignment = {}
    for i in range(n_receivers):
        want = {i: LayerMeta()}
        if mode != 0:
            want[100 + ((i + 1) % n_receivers)] = LayerMeta()
        assignment[i + 1] = want
    leader_layers = {i: mem_layer(i, layer_size)
                     for i in range(n_receivers)}
    lnode = Node(0, 0, ts[0])
    if mode == 0:
        leader = LeaderNode(lnode, leader_layers, assignment)
    elif mode == 1:
        leader = RetransmitLeaderNode(lnode, leader_layers, assignment)
    elif mode == 2:
        leader = PullRetransmitLeaderNode(lnode, leader_layers, assignment)
    else:
        leader = FlowRetransmitLeaderNode(
            lnode, leader_layers, assignment,
            node_network_bw={i: 10 ** 9 for i in ids})
    receivers = []
    for i in range(n_receivers):
        held = {100 + i: mem_layer(100 + i, layer_size)}
        rnode = Node(i + 1, 0, ts[i + 1])
        cls = (ReceiverNode if mode == 0
               else RetransmitReceiverNode if mode in (1, 2)
               else FlowRetransmitReceiverNode)
        receivers.append(cls(rnode, held))
    return leader, receivers, ts, assignment


CHAOS_SPEC = "seed=1,corrupt=3,dropin=5,dup=4,delay=7:5,times=6"


@pytest.mark.slow
@pytest.mark.timeout(420)
@pytest.mark.parametrize("kind", ["inmem", "tcp"])
@pytest.mark.parametrize("mode", [0, 1, 2, 3])
def test_chaos_soak_byte_exact_under_faults(kind, mode):
    """The acceptance soak: modes 0-3 on both backends under a seeded
    schedule of corrupted + dropped (below the CRC check) + duplicated
    + delayed frames.  Every layer must land byte-exactly, every
    digest-stamped layer must verify, and no corrupted fragment may
    reach interval accounting or the store (byte-exactness + the
    drop/NACK counters prove both).  (Send-side ``reset`` faults are
    exercised separately — their recovery channel is crash detection,
    not the NACK plane.)"""
    leader, receivers, ts, assignment = _build_cluster(
        kind, mode, fault_spec=CHAOS_SPEC)
    try:
        for r in receivers:
            r.announce()
        leader.ready().get(timeout=120.0)
        for r in receivers:
            r.ready().get(timeout=TIMEOUT)
        for r in receivers:
            for lid in assignment[r.node.my_id]:
                src = r.layers[lid]
                assert bytes(src.inmem_data) == layer_bytes(
                    lid, src.data_size), (kind, mode, lid)
                # End-to-end digest verified wherever one was stamped.
                expected = r._expected_digest(lid)
                if expected is not None:
                    assert integrity.layer_digest(
                        bytes(src.inmem_data)) == expected
        counts = trace.counter_totals()
        fired = sum(t.stats["corrupt"] + t.stats["drop"]
                    for t in ts.values() if isinstance(t, FaultyTransport))
        assert fired > 0, "the fault schedule never fired; soak is vacuous"
        assert counts.get("integrity.crc_drop", 0) >= 1
        assert counts.get("integrity.retransmit_frags", 0) >= 1
    finally:
        close_all(leader, receivers, ts)
