"""Multi-controller SPMD fabric (parallel/spmd_fabric.py).

Units cover the lockstep executor (seq ordering, cancellation override,
deterministic slot assignment) with a stubbed collective; the e2e tests
run TWO real OS processes through the real CLI — one JAX runtime via
jax.distributed, layer bytes as collectives, zero layer bytes on TCP.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from distributed_llm_dissemination_tpu.core import config as cfg
from distributed_llm_dissemination_tpu.parallel.mesh import (
    fabric_placement,
    make_mesh,
)
from distributed_llm_dissemination_tpu.parallel.spmd_fabric import (
    PlanFailed,
    SpmdFabric,
)
from distributed_llm_dissemination_tpu.transport.messages import DevicePlanMsg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _plan(seq, layout, plan_id=None, dest=1, layer=0, total=None):
    total = sum(s for _, _, s in layout) if total is None else total
    return DevicePlanMsg(0, plan_id or f"{layer}.{dest}.{seq}", layer, dest,
                         total, layout, seq=seq)


@pytest.fixture
def placement(cpu_devices):
    mesh = make_mesh((2, 4), ("nodes", "tp"))
    return fabric_placement([0, 1], {1: {0: None}}, mesh, "nodes")


def _whole_mesh(placement):
    import numpy as np

    return list(np.ravel(placement.mesh.devices))


def test_slot_assignment_puts_ranges_on_sender_stage(placement):
    fab = SpmdFabric(placement, my_node=0)
    try:
        sizes, order, by_rank = fab._slot_assignment(
            [(1, 100, 50), (0, 0, 100)], _whole_mesh(placement)
        )
        # The assignee (node 1) owns stage 0 = ranks 0-3; the extra
        # (node 0) fills stage 1 = ranks 4-7.  Offset order: node 0's
        # range first (rank 4), then node 1's (rank 0).
        assert order == (4, 0)
        assert sizes[4] == 100 and sizes[0] == 50
        assert sum(sizes) == 150
        assert by_rank[4][0] == 0 and by_rank[0][0] == 1
    finally:
        fab.close()


def test_slot_assignment_round_robins_within_stage(placement):
    fab = SpmdFabric(placement, my_node=0)
    try:
        sizes, order, _ = fab._slot_assignment(
            [(0, 0, 10), (0, 10, 10), (0, 20, 10)], _whole_mesh(placement)
        )
        assert order == (4, 5, 6)  # node 0's stage is ranks 4-7
        # A 5th range from a 4-device stage must fail deterministically.
        with pytest.raises(PlanFailed, match="more ranges"):
            fab._slot_assignment([(0, i * 10, 10) for i in range(5)],
                                 _whole_mesh(placement))
    finally:
        fab.close()


def test_executor_runs_plans_in_seq_order(placement, monkeypatch):
    fab = SpmdFabric(placement, my_node=0)
    ran = []
    monkeypatch.setattr(
        fab, "_execute",
        lambda msg: ran.append(msg.seq) or (f"v{msg.seq}", None),
    )
    try:
        # Submit out of order: 2, 0, 1.
        r2 = fab.submit(_plan(2, [(0, 0, 4)]))
        r0 = fab.submit(_plan(0, [(0, 0, 4)]))
        r1 = fab.submit(_plan(1, [(0, 0, 4)]))
        assert r0.get(10.0) == "v0"
        assert r1.get(10.0) == "v1"
        assert r2.get(10.0) == "v2"
        assert ran == [0, 1, 2]
    finally:
        fab.close()


def test_cancellation_overrides_pending_plan(placement, monkeypatch):
    fab = SpmdFabric(placement, my_node=0)
    ran = []
    real_execute = fab._execute
    monkeypatch.setattr(
        fab, "_execute",
        lambda msg: ran.append((msg.seq, len(msg.layout)))
        or real_execute(msg) if not msg.layout else (None, None),
    )
    try:
        # seq 1 arrives first (queued behind the gap), then its cancel,
        # then seq 0: the executor must run 0, then the CANCELLED 1.
        fab.submit(_plan(1, [(0, 0, 4)], plan_id="p1"))
        fab.submit(_plan(1, [], plan_id="p1"))
        r0 = fab.submit(_plan(0, [], plan_id="p0"))
        assert r0.get(10.0) is None
        deadline = time.monotonic() + 10
        while len(ran) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert ran == [(0, 0), (1, 0)]
    finally:
        fab.close()


def test_duplicate_submit_returns_same_handle(placement, monkeypatch):
    fab = SpmdFabric(placement, my_node=0)
    monkeypatch.setattr(fab, "_execute", lambda msg: ("x", None))
    try:
        a = fab.submit(_plan(0, [(0, 0, 4)], plan_id="p"))
        b = fab.submit(_plan(0, [(0, 0, 4)], plan_id="p"))
        assert a is b
        assert a.get(10.0) == "x"
        # A late duplicate after execution gets the settled handle.
        c = fab.submit(_plan(0, [(0, 0, 4)], plan_id="p"))
        assert c.get(0.1) == "x"
    finally:
        fab.close()


def test_plan_scope_is_participating_stages_only(cpu_devices):
    """The collective's sub-mesh is senders' stages ∪ dest's stage — a
    2-party transfer on a wider pod must not drag every stage into the
    gather (the round-3 pod-wide replication this replaces)."""
    mesh = make_mesh((4, 2), ("nodes", "tp"))
    p = fabric_placement([0, 1, 2, 3], {3: {0: None}}, mesh, "nodes")
    fab = SpmdFabric(p, my_node=0)
    try:
        scope = fab._plan_scope(_plan(0, [(1, 0, 64)], dest=3))
        want = set(p.devices_for_node(1)) | set(p.devices_for_node(3))
        assert set(scope) == want and len(scope) == 4
        # Multi-sender: all senders' stages join.
        scope = fab._plan_scope(
            _plan(1, [(0, 0, 32), (2, 32, 32)], dest=3))
        assert set(scope) == (set(p.devices_for_node(0))
                              | set(p.devices_for_node(2))
                              | set(p.devices_for_node(3)))
    finally:
        fab.close()


def test_out_of_scope_process_advances_seq_without_collective(
    placement, monkeypatch
):
    """A process with no device in a plan's scope must skip the
    collective entirely and still retire the seq (lockstep liveness)."""
    import jax

    fab = SpmdFabric(placement, my_node=0)
    monkeypatch.setattr(jax, "process_index", lambda: 99)  # nothing local
    try:
        r0 = fab.submit(_plan(0, [(0, 0, 8)]))
        assert r0.get(10.0) is None  # skipped, not executed
        # The seq advanced: a later plan isn't stuck behind it.  (Sender
        # 1 == dest 1 keeps my node a zero-contributing participant, so
        # no layer store is needed.)
        monkeypatch.undo()
        r1 = fab.submit(_plan(1, [(1, 0, 8)], dest=1, layer=1))
        assert fab.wait_result(r1) is None  # my_node=0 is not the dest
    finally:
        fab.close()


def test_executor_pipelines_dispatch_ahead_of_completion(
    placement, monkeypatch
):
    """The in-flight window: plan k+1 (and k+2) dispatch BEFORE plan k's
    device work completes — N plans' wall-clock is bounded by the
    collective stream, not N × (upload + collective + block)."""
    import threading

    events = []
    release = threading.Event()

    class FakeOut:
        def __init__(self, seq):
            self.seq = seq

        def block_until_ready(self):
            release.wait(10.0)
            events.append(("retired", self.seq))

    fab = SpmdFabric(placement, my_node=0)
    monkeypatch.setattr(
        fab, "_execute",
        lambda msg: events.append(("dispatched", msg.seq))
        or (f"v{msg.seq}", FakeOut(msg.seq)),
    )
    try:
        rs = [fab.submit(_plan(k, [(0, 0, 4)], layer=k)) for k in range(4)]
        deadline = time.monotonic() + 10
        # The in-flight window (small plans pipeline up to
        # MAX_INFLIGHT_SMALL deep): plans 0,1,2 all dispatch while 0 is
        # still unfinished; retires happen when the window fills or the
        # queue idles — never before a later plan's dispatch here.
        while (events.count(("dispatched", 2)) == 0
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert ("dispatched", 0) in events
        assert ("dispatched", 1) in events
        assert ("dispatched", 2) in events
        assert ("retired", 0) not in events  # 0 still in flight
        release.set()
        assert [r.get(10.0) for r in rs] == ["v0", "v1", "v2", "v3"]
        assert events.index(("dispatched", 2)) < events.index(("retired", 0))
    finally:
        release.set()
        fab.close()


def test_layout_total_mismatch_fails_the_plan(placement):
    fab = SpmdFabric(placement, my_node=0)
    try:
        res = fab.submit(_plan(0, [(0, 0, 8)], total=16))
        with pytest.raises(PlanFailed, match="plan says 16"):
            res.get(10.0)
    finally:
        fab.close()


def test_executor_gap_reports_missing_seqs(placement, monkeypatch):
    """A hole in the seq stream (a plan this process never received,
    with later plans queued behind it) fires the on_gap hook with the
    missing seqs — the leader-report half of the stall recovery."""
    fab = SpmdFabric(placement, my_node=0, gap_timeout=0.2)
    reports = []
    fab.on_gap = reports.append
    try:
        # seqs 1 and 3 arrive; 0 and 2 never do.
        fab.submit(_plan(1, []))  # cancellations: no device work needed
        fab.submit(_plan(3, []))
        deadline = time.monotonic() + 10.0
        while not reports and time.monotonic() < deadline:
            time.sleep(0.02)
        assert reports and reports[0] == [0, 2], reports
        # Healing the first hole advances past seq 1; the next report
        # names only the remaining hole.
        fab.submit(_plan(0, []))
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if any(r == [2] for r in reports):
                break
            time.sleep(0.02)
        assert any(r == [2] for r in reports), reports
    finally:
        fab.close()


def test_leader_resends_retained_plan_on_gap_report():
    """handle_plan_resend: a known seq re-sends the retained plan to the
    requester; an unknown seq gets a cancellation so the requester can
    advance past the hole either way."""
    from distributed_llm_dissemination_tpu.transport import InmemTransport
    from distributed_llm_dissemination_tpu.transport.messages import (
        PlanResendReqMsg,
    )

    leader, t0 = _leader_with_spmd()
    t1 = InmemTransport("1")
    try:
        plan = _plan(5, [(0, 0, 100)], dest=1)
        with leader._lock:
            leader._sent_plans[5] = plan
        leader.handle_plan_resend(PlanResendReqMsg(1, [5, 99]))
        got = [t1.deliver().get(timeout=5.0) for _ in range(2)]
        by_seq = {m.seq: m for m in got}
        assert set(by_seq) == {5, 99}
        assert by_seq[5].plan_id == plan.plan_id
        assert by_seq[5].layout == [(0, 0, 100)]
        assert by_seq[99].layout == []  # unknown: cancellation
    finally:
        leader.close()
        t0.close()
        t1.close()


def test_broadcast_retains_operative_message_per_seq():
    """The re-send store must hold the plan normally, and the CANCEL
    when the broadcast partially failed (re-sending the original after
    peers skipped the seq would wedge the requester in a collective)."""
    from distributed_llm_dissemination_tpu.transport import InmemTransport

    leader, t0 = _leader_with_spmd()
    peers = [InmemTransport(str(i)) for i in (1, 2)]
    try:
        ok = leader._broadcast_spmd_plan(_plan(0, [(0, 0, 10)], dest=1))
        assert ok
        assert leader._sent_plans[0].layout == [(0, 0, 10)]

        # Unsendable participant (no registered transport for node 9):
        # broadcast fails, cancel supersedes.
        leader.status[9] = dict(leader.status[1])
        ok = leader._broadcast_spmd_plan(_plan(1, [(9, 0, 10)], dest=1))
        assert not ok
        assert leader._sent_plans[1].layout == []
    finally:
        leader.close()
        t0.close()
        for t in peers:
            t.close()


def test_plan_watchdog_rebroadcasts_then_cancels(monkeypatch):
    """Tail-gap liveness: a plan nobody acks is re-broadcast on a timer,
    and past the retry budget the watchdog KEEPS re-broadcasting — the
    give-up cancel is crash-gated (a cancel fired while the dest is
    merely slow would advance gap processes while peers sit inside the
    collective).  Only once a participant is declared crashed (fabric
    disabled) is the seq cancelled."""
    from distributed_llm_dissemination_tpu.core.types import (
        LayerLocation,
        LayerMeta,
    )
    from distributed_llm_dissemination_tpu.runtime import LeaderNode, Node
    from distributed_llm_dissemination_tpu.runtime.leader import (
        LeaderNode as _LN,
    )
    from distributed_llm_dissemination_tpu.transport import (
        InmemTransport,
        reset_registry,
    )

    monkeypatch.setattr(_LN, "PLAN_ACK_TIMEOUT", 0.25)
    monkeypatch.setattr(_LN, "PLAN_WATCH_PERIOD", 0.05)
    monkeypatch.setattr(_LN, "PLAN_REBROADCASTS", 2)
    reset_registry()
    t0 = InmemTransport("0")
    t1 = InmemTransport("1")
    t2 = InmemTransport("2")
    leader = LeaderNode(Node(0, 0, t0), {}, {1: {0: LayerMeta()}},
                        start_loop=True, fabric=_FakeSpmdFabric(),
                        placement=_FakePlacement([0, 1, 2]))
    leader.status[1] = {
        0: LayerMeta(location=LayerLocation.INMEM, data_size=100)
    }
    leader.status[2] = {}
    try:
        assert leader._broadcast_spmd_plan(_plan(0, [(0, 0, 100)], dest=1))
        got = []
        deadline = time.monotonic() + 10.0
        # Original + the 2 budgeted re-broadcasts + at least one PAST-
        # budget re-broadcast: no cancel while nobody is declared dead.
        while len(got) < 4 and time.monotonic() < deadline:
            try:
                m = t1.deliver().get(timeout=0.5)
            except Exception:  # noqa: BLE001 — queue.Empty
                continue
            if isinstance(m, DevicePlanMsg):
                got.append(m)
        assert len(got) == 4, [(m.seq, m.layout) for m in got]
        assert [bool(m.layout) for m in got] == [True, True, True, True]
        assert all(m.seq == 0 for m in got)
        with leader._lock:
            assert 0 in leader._plan_watch  # still chasing, not cancelled
            assert leader._sent_plans[0].layout  # plan retained, no cancel

        # Declare a participant crashed: the fabric is disabled and the
        # watched seq is cancelled so gap processes stop waiting on it.
        leader.crash(2)
        assert leader._fabric_disabled
        cancel = None
        deadline = time.monotonic() + 10.0
        while cancel is None and time.monotonic() < deadline:
            try:
                m = t1.deliver().get(timeout=0.5)
            except Exception:  # noqa: BLE001 — queue.Empty
                continue
            if isinstance(m, DevicePlanMsg) and not m.layout:
                cancel = m
        assert cancel is not None and cancel.seq == 0
        with leader._lock:
            assert 0 not in leader._plan_watch  # chase abandoned
            assert leader._sent_plans[0].layout == []  # cancel retained

        # An ACKED plan is never chased: broadcast + ack, then silence.
        assert leader._broadcast_spmd_plan(_plan(1, [(0, 0, 100)], dest=1))
        deadline = time.monotonic() + 2.0
        plan1 = None
        while plan1 is None and time.monotonic() < deadline:
            try:
                m = t1.deliver().get(timeout=0.5)
            except Exception:  # noqa: BLE001 — queue.Empty
                continue
            # The crash above may interleave StartupMsg etc.; wait for
            # the fresh plan specifically.
            if isinstance(m, DevicePlanMsg) and m.seq == 1:
                plan1 = m
        assert plan1 is not None
        from distributed_llm_dissemination_tpu.transport.messages import (
            AckMsg,
        )

        leader.handle_ack(AckMsg(1, 0, LayerLocation.INMEM))
        with leader._lock:
            assert 1 not in leader._plan_watch
        deadline = time.monotonic() + 0.8
        while time.monotonic() < deadline:
            try:
                extra = t1.deliver().get(timeout=0.2)
            except Exception:  # noqa: BLE001 — queue.Empty
                continue
            # The satisfying ack legitimately triggers StartupMsg etc.;
            # only a DevicePlanMsg would be a spurious re-broadcast.
            assert not isinstance(extra, DevicePlanMsg), extra
    finally:
        leader.close()
        t0.close()
        t1.close()


# ---------------------------------------------------------- 2-process e2e


def _spmd_conf(free_port, layers=2, size=262144):
    from distributed_llm_dissemination_tpu.cli.genconf import (
        spmd_two_proc_config,
    )

    return spmd_two_proc_config(size, layers, free_port)


def _run_two_process(conf_json, mode, tag=""):
    # Unique per (mode, tag): concurrent tests must not share the file.
    conf_path = os.path.join(REPO, f".pytest-spmd-{mode}{tag}.json")
    with open(conf_path, "w") as f:
        json.dump(conf_json, f)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one device per process
    cli = [sys.executable, "-m",
           "distributed_llm_dissemination_tpu.cli.main",
           "-f", conf_path, "-m", str(mode)]
    try:
        recv = subprocess.Popen(cli + ["-id", "1"], stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, env=env, text=True)
        lead = subprocess.Popen(cli + ["-id", "0"], stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, env=env, text=True)
        try:
            lead_out, lead_err = lead.communicate(timeout=240)
            recv_out, recv_err = recv.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            lead.kill()
            recv.kill()
            raise
        return (lead.returncode, lead_out, lead_err,
                recv.returncode, recv_out, recv_err)
    finally:
        for p in (locals().get("recv"), locals().get("lead")):
            if p is not None and p.poll() is None:
                p.kill()
        if os.path.exists(conf_path):
            os.remove(conf_path)


@pytest.mark.parametrize("mode", [0, 3])
def test_two_process_spmd_fabric_dissemination(mode, free_port):
    """Layer bytes move between two real OS processes as collectives over
    the shared JAX runtime; the TCP transport carries control only."""
    rc0, lead_out, lead_err, rc1, recv_out, recv_err = _run_two_process(
        _spmd_conf(free_port), mode
    )
    assert rc0 == 0, f"leader failed:\n{lead_err[-3000:]}"
    assert rc1 == 0, f"receiver failed:\n{recv_err[-3000:]}"
    assert "Time to deliver" in lead_out
    assert "ready" in recv_out
    # The layers landed over the SPMD fabric, on the receiver's device.
    assert "layer landed over device fabric" in recv_err
    assert '"spmd": true' in recv_err
    # Zero layer bytes on the wire: the TCP data plane never ran.
    assert "layer received" not in recv_err
    assert "dispatching device plan" in lead_err


def test_two_process_spmd_heals_dropped_plan(free_port):
    """VERDICT r4 ask#7 e2e: one participant's DevicePlanMsg is dropped
    (fault injection) — the executor detects the seq gap, reports it,
    the leader re-sends its retained plan, and the run still reaches
    ready() with the layers over the FABRIC (not the host path)."""
    conf = _spmd_conf(free_port, layers=3)
    conf_path = os.path.join(REPO, ".pytest-spmd-heal.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["DLD_SPMD_GAP_TIMEOUT"] = "1.5"
    cli = [sys.executable, "-m",
           "distributed_llm_dissemination_tpu.cli.main",
           "-f", conf_path, "-m", "3"]
    recv = lead = None
    try:
        # The receiver process drops its FIRST delivery of plan seq 0
        # (the EXPLICIT construction-gated fault flag; seqs 1-2 queue
        # behind the hole).
        recv = subprocess.Popen(
            cli + ["-id", "1", "-test-drop-plan-seqs", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            text=True)
        lead = subprocess.Popen(cli + ["-id", "0"], stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, env=env, text=True)
        lead_out, lead_err = lead.communicate(timeout=240)
        recv_out, recv_err = recv.communicate(timeout=60)
        assert lead.returncode == 0, f"leader failed:\n{lead_err[-3000:]}"
        assert recv.returncode == 0, f"receiver failed:\n{recv_err[-3000:]}"
        assert "Time to deliver" in lead_out
        assert "ready" in recv_out
        # The fault actually fired (the fault-injection TRANSPORT now,
        # transport/faults.py — the old receiver-side drop path is
        # gone), the gap was detected and reported, and the leader
        # healed it.
        assert "FAULT: dropping inbound control message" in recv_err
        assert "requesting re-send of missing spmd plans" in recv_err
        assert "re-sent spmd plan after gap report" in lead_err
        # Delivery still rode the device fabric — zero TCP layer bytes.
        assert "layer landed over device fabric" in recv_err
        assert "layer received" not in recv_err
    finally:
        for p in (recv, lead):
            if p is not None and p.poll() is None:
                p.kill()
        if os.path.exists(conf_path):
            os.remove(conf_path)


def test_two_process_spmd_heals_dropped_tail_plan(free_port):
    """The receiver-side gap report can't see a dropped LAST plan
    (nothing queues behind it) — the leader's watchdog re-broadcast
    must heal it.  One layer = one plan = seq 0 IS the tail."""
    conf = _spmd_conf(free_port, layers=1)
    conf_path = os.path.join(REPO, ".pytest-spmd-tail.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["DLD_PLAN_ACK_TIMEOUT"] = "2.0"
    cli = [sys.executable, "-m",
           "distributed_llm_dissemination_tpu.cli.main",
           "-f", conf_path, "-m", "3"]
    recv = lead = None
    try:
        recv = subprocess.Popen(
            cli + ["-id", "1", "-test-drop-plan-seqs", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            text=True)
        lead = subprocess.Popen(cli + ["-id", "0"], stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, env=env, text=True)
        lead_out, lead_err = lead.communicate(timeout=240)
        recv_out, recv_err = recv.communicate(timeout=60)
        assert lead.returncode == 0, f"leader failed:\n{lead_err[-3000:]}"
        assert recv.returncode == 0, f"receiver failed:\n{recv_err[-3000:]}"
        assert "Time to deliver" in lead_out
        assert "FAULT: dropping inbound control message" in recv_err
        assert "re-broadcasting unacked spmd plan" in lead_err
        # Healed over the fabric, no TCP layer bytes.
        assert "layer landed over device fabric" in recv_err
        assert "layer received" not in recv_err
    finally:
        for p in (recv, lead):
            if p is not None and p.poll() is None:
                p.kill()
        if os.path.exists(conf_path):
            os.remove(conf_path)


@pytest.mark.slow
@pytest.mark.timeout(420)
def test_two_process_spmd_int8_boot(free_port):
    """Codec x SPMD x boot: int8 blobs cross two real OS processes as
    collectives, and the dest boots the model from the HBM-landed bytes
    with on-device dequantization."""
    from distributed_llm_dissemination_tpu.models import quant
    from distributed_llm_dissemination_tpu.models.llama import CONFIGS

    mcfg = CONFIGS["tiny"]
    conf = _spmd_conf(free_port, layers=0)
    conf["Model"] = "tiny"
    conf["ModelSeed"] = 0
    conf["ModelCodec"] = "int8"
    blob_ids = list(range(mcfg.n_layers + 1))
    conf["Nodes"][0]["InitialLayers"] = {
        "2": {str(b): {"LayerSize": quant.blob_nbytes_codec(mcfg, b, "int8")}
              for b in blob_ids}
    }
    conf["Assignment"] = {"1": {str(b): {} for b in blob_ids}}
    rc0, lead_out, lead_err, rc1, recv_out, recv_err = _run_two_process(
        conf, 3, tag="-int8"
    )
    assert rc0 == 0, f"leader failed:\n{lead_err[-3000:]}"
    assert rc1 == 0, f"receiver failed:\n{recv_err[-3000:]}"
    assert "Time to deliver" in lead_out
    assert "Time to first token" in lead_out
    assert '"spmd": true' in recv_err
    assert "layer received" not in recv_err  # zero TCP layer bytes
    # The boot dequantized on-device from the fabric-landed blobs.
    assert "device int8 dequant" in recv_err
    assert '"kind": "full"' in recv_err


# ------------------------------------------------- leader gating (units)


class _FakeSpmdFabric:
    kind = "spmd"

    def bind_store(self, layers, lock):
        pass


class _FakePlacement:
    def __init__(self, nodes, per_stage=4):
        self.node_to_stage = {n: i for i, n in enumerate(nodes)}
        self._per_stage = per_stage

    def devices_for_node(self, node):
        return [object()] * self._per_stage


def _leader_with_spmd(nodes=(0, 1, 2)):
    from distributed_llm_dissemination_tpu.core.types import (
        LayerLocation,
        LayerMeta,
    )
    from distributed_llm_dissemination_tpu.runtime import LeaderNode, Node
    from distributed_llm_dissemination_tpu.transport import (
        InmemTransport,
        reset_registry,
    )

    reset_registry()
    t = InmemTransport("0")
    leader = LeaderNode(Node(0, 0, t), {}, {1: {0: LayerMeta()}},
                        start_loop=False, fabric=_FakeSpmdFabric(),
                        placement=_FakePlacement(nodes))
    for n in nodes[1:]:
        leader.status[n] = {
            0: LayerMeta(location=LayerLocation.INMEM, data_size=100)
        }
    return leader, t


def test_fabric_ok_rejects_gaps_only_layout_under_spmd():
    # A resumed dest's plan covers only its gaps; the SPMD collective
    # rebuilds the WHOLE layer from the plan, so such a transfer must
    # ride the host path (not livelock on a deterministic PlanFailed).
    leader, t = _leader_with_spmd()
    try:
        assert leader._fabric_ok(0, [(1, 0, 100)], 2, 100)
        assert not leader._fabric_ok(0, [(1, 40, 60)], 2, 100)  # gap at 0
        assert not leader._fabric_ok(0, [(1, 0, 60)], 2, 100)  # short tail
        assert not leader._fabric_ok(
            0, [(1, 0, 30), (1, 50, 50)], 2, 100  # hole in the middle
        )
        # A sender with more ranges than its stage has device slots would
        # fail deterministically in every executor: host path instead.
        five = [(1, i * 20, 20) for i in range(5)]
        assert not leader._fabric_ok(0, five, 2, 100)
        # total is REQUIRED — a legacy call must not skip the checks.
        with pytest.raises(TypeError):
            leader._fabric_ok(0, [(1, 40, 60)], 2)
    finally:
        leader.close()
        t.close()


def test_reannounce_disables_spmd_fabric():
    # A restarted process has a fresh executor (seq 0) and may be outside
    # the jax.distributed runtime: one more fabric plan would hang every
    # survivor inside the collective.  Any re-announce flips to host path.
    from distributed_llm_dissemination_tpu.transport.messages import (
        AnnounceMsg,
    )

    leader, t = _leader_with_spmd()
    try:
        leader._started = True
        assert not leader._fabric_disabled
        leader.handle_announce(AnnounceMsg(1, {}))
        assert leader._fabric_disabled
        assert not leader._fabric_ok(0, [(1, 0, 100)], 2, 100)
    finally:
        leader.close()
        t.close()


@pytest.mark.slow
@pytest.mark.timeout(420)
def test_three_process_spmd_pipeline_serves(free_port):
    """Multi-controller serving: three real OS processes (leader seeds,
    two stage assignees), dissemination over the SPMD fabric, stage
    boots, then BOTH members enter the pod-wide pipelined forward.  The
    head blob is assigned to every stage (the serving convention)."""
    from distributed_llm_dissemination_tpu.models import serde
    from distributed_llm_dissemination_tpu.models.llama import CONFIGS

    mcfg = CONFIGS["tiny"]
    head_id = serde.head_blob_id(mcfg)
    cut = mcfg.n_layers // 2
    conf = {
        "Model": "tiny", "ModelSeed": 0,
        "Nodes": [
            {"Id": 0, "Addr": f"127.0.0.1:{free_port()}", "IsLeader": True,
             "NetworkBW": 10**9, "Sources": {"2": 0},
             "InitialLayers": {"2": {str(b): {} for b in range(head_id + 1)}}},
            {"Id": 1, "Addr": f"127.0.0.1:{free_port()}",
             "NetworkBW": 10**9, "Sources": {"2": 0}, "InitialLayers": {}},
            {"Id": 2, "Addr": f"127.0.0.1:{free_port()}",
             "NetworkBW": 10**9, "Sources": {"2": 0}, "InitialLayers": {}},
        ],
        "Assignment": {
            "1": {str(b): {} for b in list(range(cut)) + [head_id]},
            "2": {str(b): {} for b in list(range(cut, head_id))
                  + [head_id]},
        },
        "LayerSize": 1,
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [3],
                 "PipelineAxis": "nodes", "Fabric": True},
        "Distributed": {"Coordinator": f"127.0.0.1:{free_port()}",
                        "CpuCollectives": "gloo"},
    }
    conf_path = os.path.join(REPO, ".pytest-spmd-serve.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one device per process
    cli = [sys.executable, "-m",
           "distributed_llm_dissemination_tpu.cli.main",
           "-f", conf_path, "-m", "3"]
    procs = {}
    try:
        for i in (1, 2):
            procs[i] = subprocess.Popen(
                cli + ["-id", str(i)], stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, env=env, text=True)
        procs[0] = subprocess.Popen(
            cli + ["-id", "0"], stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, env=env, text=True)
        outs = {}
        for i, p in procs.items():
            try:
                outs[i] = p.communicate(timeout=420)
            except subprocess.TimeoutExpired:
                for q in procs.values():
                    q.kill()
                raise
        for i, p in procs.items():
            assert p.returncode == 0, (
                f"node {i} failed:\n{outs[i][1][-3000:]}"
            )
        assert "Time to first token" in outs[0][0]
        for i in (1, 2):
            err = outs[i][1]
            assert "pod pipelined forward from staged weights" in err, (
                f"node {i} never served:\n{err[-3000:]}"
            )
            assert '"spmd": true' in err
            assert "layer received" not in err  # zero TCP layer bytes
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        if os.path.exists(conf_path):
            os.remove(conf_path)


@pytest.mark.timeout(420)
def test_three_process_spmd_pod_delivery(free_port):
    """Fabric-assisted pod delivery across three real OS processes
    (docs/fabric.md): the leader pod-plans one 1/2 shard per member
    over host TCP, then broadcasts ONE lockstep gather plan whose
    keep-list leaves the full tree on BOTH members — each verifies the
    stamped full-layer digest and acks the FULL layer; the run only
    completes once every tree materialized."""
    from distributed_llm_dissemination_tpu.cli.genconf import (
        spmd_pod_config,
    )

    conf = spmd_pod_config(1 << 16, 2, free_port)
    conf_path = os.path.join(REPO, ".pytest-spmd-pod.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one device per process
    cli = [sys.executable, "-m",
           "distributed_llm_dissemination_tpu.cli.main",
           "-f", conf_path, "-m", "3"]
    procs = {}
    try:
        for i in (1, 2):
            procs[i] = subprocess.Popen(
                cli + ["-id", str(i)], stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, env=env, text=True)
        procs[0] = subprocess.Popen(
            cli + ["-id", "0"], stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, env=env, text=True)
        outs = {}
        for i, p in procs.items():
            try:
                outs[i] = p.communicate(timeout=420)
            except subprocess.TimeoutExpired:
                for q in procs.values():
                    q.kill()
                raise
        for i, p in procs.items():
            assert p.returncode == 0, (
                f"node {i} failed:\n{outs[i][1][-3000:]}"
            )
        lead_err = outs[0][1]
        assert "pod delivery planned" in lead_err
        assert "dispatching pod gather plan" in lead_err
        assert "pod pair materialized its full tree" in lead_err
        for i in (1, 2):
            err = outs[i][1]
            # Phase 1: the member's SHARD rode host TCP (the NIC) —
            # unlike plain SPMD runs, where zero layer bytes touch TCP.
            assert "layer fully received" in err, err[-3000:]
            # Phase 2: the gather left the full tree here, verified.
            assert "pod delivery materialized full tree" in err, (
                f"node {i} never materialized:\n{err[-3000:]}"
            )
        assert "Time to deliver" in outs[0][0]
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        if os.path.exists(conf_path):
            os.remove(conf_path)


def test_serve_members_accepts_uneven_partition():
    """Round-4 lift: contiguous but UNEVEN slices (all holding the head)
    are servable; gaps still aren't."""
    leader, t = _leader_with_spmd()
    try:
        head = 4
        leader.boot_enabled = True
        leader.assignment = {
            1: {b: None for b in [0, 1, 2, head]},
            2: {b: None for b in [3, head]},
        }
        assert leader.serve_members() == ([1, 2], [3, 1])
        # A gap (layer 2 unassigned) cancels serving.
        leader.assignment = {
            1: {b: None for b in [0, 1, head]},
            2: {b: None for b in [3, head]},
        }
        assert leader.serve_members() is None
    finally:
        leader.close()
        t.close()


@pytest.mark.slow
@pytest.mark.timeout(420)
def test_three_process_spmd_uneven_pod_decode(free_port):
    """Multi-controller GENERATION: three real OS processes, an UNEVEN
    stage partition (3/1 of tiny's 4 layers), dissemination over the
    SPMD fabric, stage boots, then -gen 5 makes every member enter the
    lockstep KV-cached greedy decode — both members must emit EXACTLY
    the token ids the single-process decode loop produces."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_dissemination_tpu.models import serde
    from distributed_llm_dissemination_tpu.models.generate import generate
    from distributed_llm_dissemination_tpu.models.llama import (
        CONFIGS,
        init_params,
    )

    mcfg = CONFIGS["tiny"]
    head_id = serde.head_blob_id(mcfg)
    cut = 3  # stages of depth 3 and 1
    conf = {
        "Model": "tiny", "ModelSeed": 0,
        "Nodes": [
            {"Id": 0, "Addr": f"127.0.0.1:{free_port()}", "IsLeader": True,
             "NetworkBW": 10**9, "Sources": {"2": 0},
             "InitialLayers": {"2": {str(b): {} for b in range(head_id + 1)}}},
            {"Id": 1, "Addr": f"127.0.0.1:{free_port()}",
             "NetworkBW": 10**9, "Sources": {"2": 0}, "InitialLayers": {}},
            {"Id": 2, "Addr": f"127.0.0.1:{free_port()}",
             "NetworkBW": 10**9, "Sources": {"2": 0}, "InitialLayers": {}},
        ],
        "Assignment": {
            "1": {str(b): {} for b in list(range(cut)) + [head_id]},
            "2": {str(b): {} for b in list(range(cut, head_id))
                  + [head_id]},
        },
        "LayerSize": 1,
        # Slices + DcnBW compose with the SPMD fabric: the leader plans
        # cross-slice transfers through the topology LP while the bytes
        # ride the lockstep collectives.
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [3],
                 "PipelineAxis": "nodes", "Fabric": True,
                 "Slices": {"0": 0, "1": 0, "2": 1}, "DcnBW": 10**9},
        "Distributed": {"Coordinator": f"127.0.0.1:{free_port()}",
                        "CpuCollectives": "gloo"},
    }
    conf_path = os.path.join(REPO, ".pytest-spmd-decode.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one device per process
    cli = [sys.executable, "-m",
           "distributed_llm_dissemination_tpu.cli.main",
           "-f", conf_path, "-m", "3"]
    procs = {}
    try:
        for i in (1, 2):
            procs[i] = subprocess.Popen(
                cli + ["-id", str(i)], stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, env=env, text=True)
        procs[0] = subprocess.Popen(
            cli + ["-id", "0", "-gen", "5"], stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, env=env, text=True)
        outs = {}
        for i, p in procs.items():
            try:
                outs[i] = p.communicate(timeout=420)
            except subprocess.TimeoutExpired:
                for q in procs.values():
                    q.kill()
                raise
        for i, p in procs.items():
            assert p.returncode == 0, (
                f"node {i} failed:\n{outs[i][1][-3000:]}"
            )
        # The leader planned through the topology solver (Slices + DcnBW
        # in the Mesh section) — composition with the SPMD fabric.  The
        # attribution-first path tags "(topology)"; "(topology LP)"
        # appears only when holdings force the exact LP.
        assert ("job assignment calculated (topology" in outs[0][1]
                ), outs[0][1][-2000:]
        want = generate(init_params(mcfg, jax.random.key(0)),
                        jnp.zeros((1, 16), jnp.int32), mcfg, max_new=5)
        want_ids = [int(t) for t in np.asarray(want)[0]]
        for i in (1, 2):
            err = outs[i][1]
            assert "pod decoded tokens from staged weights" in err, (
                f"node {i} never decoded:\n{err[-3000:]}"
            )
            m = re.search(r'"tokens": \[([0-9, ]+)\]', err)
            assert m, f"node {i} logged no token ids:\n{err[-2000:]}"
            got = [int(t) for t in m.group(1).split(",")]
            assert got == want_ids, (i, got, want_ids)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        if os.path.exists(conf_path):
            os.remove(conf_path)


def _spy_serves(t):
    """Capture ServeMsgs the transport would deliver."""
    from distributed_llm_dissemination_tpu.transport.messages import ServeMsg

    sent = []
    orig = t.send

    def spy(dest, msg):
        if isinstance(msg, ServeMsg):
            sent.append((dest, msg))
        else:
            orig(dest, msg)

    t.send = spy
    return sent


def test_dispatch_serve_carries_snapshot_counts_and_gen():
    """The ServeMsg's member depths come from the SAME assignment
    snapshot the membership was validated on, plus the leader's -gen."""
    leader, t = _leader_with_spmd()
    sent = _spy_serves(t)
    try:
        head = 4
        leader.boot_enabled = True
        leader.serve_generate = 7
        leader.assignment = {
            1: {b: None for b in [0, 1, 2, head]},
            2: {b: None for b in [3, head]},
        }
        leader._boot_kinds = {1: "stage", 2: "stage"}
        leader._dispatch_serve()
        members_msgs = [m for _, m in sent if m.members]
        assert members_msgs, "no ServeMsg with members broadcast"
        m = members_msgs[0]
        assert m.members == [1, 2]
        assert m.counts == [3, 1]
        assert m.gen == 7
    finally:
        leader.close()
        t.close()


def test_dispatch_serve_cancels_when_a_member_boot_is_not_stage():
    """A member that reported a non-stage boot can't enter the serving
    collective: promised receivers get the CANCELLATION (empty members)
    instead of hanging in a collective the member never joins."""
    leader, t = _leader_with_spmd()
    sent = _spy_serves(t)
    try:
        head = 4
        leader.boot_enabled = True
        leader.assignment = {
            1: {b: None for b in [0, 1, head]},
            2: {b: None for b in [2, 3, head]},
        }
        leader._boot_kinds = {1: "stage", 2: "full"}  # 2 booted FULL
        leader._serve_promised = True
        leader._dispatch_serve()
        assert sent, "promised receivers must be released"
        assert all(m.members == [] for _, m in sent)
    finally:
        leader.close()
        t.close()
