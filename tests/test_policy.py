"""Closed-loop fleet autonomy tests (docs/autonomy.md): the leader-side
policy engine that senses the folded cluster signals every metrics
interval and drives the leader's own chokepoints with zero operator
verbs.

What the tentpole demands:

- rule admission is LOUD: a bad ``Policies`` block (unknown rule,
  unknown/missing/out-of-range param) is refused at config parse, never
  deferred to fire time;
- the ``DLD_POLICY`` kill-switch drops an armed fleet to manual on the
  NEXT tick: sensing continues (``held_manual`` audit records), nothing
  fires;
- cooldown and hysteresis: a breach streak resets on one good interval,
  a fired rule stays quiet for its cooldown, and a FLAPPING straggler
  link is demoted exactly once (the installed demotion absorbs the
  flap);
- the ``flap=P@T1-T2[:N]`` seeded fault is sugar over partition windows
  (deterministic, bounded);
- the PR-9 revoke "wrong-eat race" is closed by generation keying: a
  stale revoke can no longer eat the re-plan's fresh command for the
  same (job, dest, layer);
- a leader killed MID-ACTION hands the armed rules, cooldowns and the
  in-flight action to the promoted standby, which completes it at the
  bumped epoch without double-firing (both backends);
- the ``POLICY_ACTIONS`` vocabulary is pinned to live ``_fire``
  dispatch sites and to docs/autonomy.md rows (static drift check).
"""

import os
import time

import pytest

from distributed_llm_dissemination_tpu.core.config import Config
from distributed_llm_dissemination_tpu.core.types import LayerMeta
from distributed_llm_dissemination_tpu.runtime import (
    FlowRetransmitLeaderNode,
    FlowRetransmitReceiverNode,
    Node,
    StandbyController,
)
from distributed_llm_dissemination_tpu.runtime.policy import (
    POLICY_ACTIONS,
    PolicyEngine,
    validate_policies,
)
from distributed_llm_dissemination_tpu.runtime.send import RevokeRegistry
from distributed_llm_dissemination_tpu.transport import reset_registry
from distributed_llm_dissemination_tpu.transport.faults import (
    FaultRule,
    FaultyTransport,
    rules_from_spec,
)
from distributed_llm_dissemination_tpu.transport.messages import MsgType
from distributed_llm_dissemination_tpu.utils import telemetry, trace

from test_node import close_all, layer_bytes, make_transports, mem_layer

TIMEOUT = 15.0
LEASE = 0.15
STANDBY_EXPIRY = 0.5
HB = 0.1


@pytest.fixture(autouse=True)
def _clean():
    reset_registry()
    yield
    reset_registry()


def _wait_for(cond, timeout=TIMEOUT, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


# ----------------------------------------------------- rule admission


def test_validate_policies_fills_defaults_and_coerces():
    rules = validate_policies([
        {"Rule": "grow_on_serve_pressure", "P99Ms": "250"},
        {"Rule": "replan_straggler"},
    ])
    assert rules[0] == {"Rule": "grow_on_serve_pressure", "P99Ms": 250.0,
                       "Sustain": 2, "CooldownS": 30.0, "MaxGrows": 1}
    assert rules[1]["FloorFrac"] == 0.1
    assert rules[1]["LiftOnRecovery"] is True
    assert validate_policies(None) == []
    assert validate_policies([]) == []


@pytest.mark.parametrize("bad,needle", [
    ([{"Rule": "reboot_everything"}], "unknown rule"),
    ([{"Rule": "quarantine_breacher", "P99Ms": 10, "Zap": 1}],
     "unknown params"),
    ([{"Rule": "quarantine_breacher"}], "missing required"),
    ([{"Rule": "quarantine_breacher", "P99Ms": -5}], "must be > 0"),
    ([{"Rule": "quarantine_breacher", "P99Ms": 10, "Breaches": 0}],
     "must be >= 1"),
    ([{"Rule": "rehome_on_loss", "SuspectFrac": 1.0}], "must be in"),
    (["not-an-object"], "not an object"),
    ({"Rule": "replan_straggler"}, "must be a list"),
])
def test_validate_policies_refuses_bad_rules_loudly(bad, needle):
    with pytest.raises(ValueError) as e:
        validate_policies(bad)
    assert needle in str(e.value)


def test_config_policies_block_validated_at_parse():
    """A bad rule fails Config.from_json — admission, not fire time."""
    good = Config.from_json({
        "Nodes": [], "Assignment": {},
        "Policies": [{"Rule": "quarantine_breacher", "P99Ms": 100}]})
    assert good.policies[0]["Breaches"] == 2  # defaults filled at parse
    with pytest.raises(ValueError) as e:
        Config.from_json({"Nodes": [], "Assignment": {},
                          "Policies": [{"Rule": "nope"}]})
    assert "unknown rule" in str(e.value)


# -------------------------------------------- engine units (stub leader)


class _StubJobs:
    def __init__(self):
        self.states = {}

    def get(self, jid):
        state = self.states.get(jid)
        if state is None:
            return None
        return type("J", (), {"state": state, "dropped_pairs": 0})()


class _StubLeader:
    """The engine's leader surface: chokepoints recorded, not executed."""

    def __init__(self):
        self.epoch = 0
        self.node = type("N", (), {"my_id": 0})()
        self.jobs = _StubJobs()
        self.replicated = []
        self.demotes = []
        self.lifts = []
        self.grows = []

    def _replicate(self, kind, **data):
        self.replicated.append(kind)

    def policy_demote_link(self, s, d, bps):
        self.demotes.append((int(s), int(d), int(bps)))

    def policy_lift_link(self, s, d):
        self.lifts.append((int(s), int(d)))

    def policy_grow(self, node, action_id):
        self.grows.append((int(node), action_id))
        jid = f"policy-{action_id}"
        self.jobs.states[jid] = "active"
        return jid


def _serve_snap(node, n_req, fast=0, slow=0):
    """A cumulative metrics snapshot: ``fast`` samples land in the
    <=16ms bucket, ``slow`` in the <=1024ms bucket (HIST_BUCKETS_MS)."""
    buckets = [0] * (len(telemetry.HIST_BUCKETS_MS) + 1)
    buckets[2] = fast
    buckets[5] = slow
    return {"counters": {f"serve.requests.n{node}": n_req},
            "hists": {f"serve.latency_ms.n{node}": {
                "buckets": buckets, "n": fast + slow, "sum_ms": 0.0}}}


def _engine(rules):
    stub = _StubLeader()
    eng = PolicyEngine(stub)
    eng.arm(rules)
    return stub, eng


def test_quarantine_needs_a_sustained_streak_and_resets_on_recovery():
    _, eng = _engine([{"Rule": "quarantine_breacher", "P99Ms": 200,
                       "Breaches": 2}])
    eng.tick(2, _serve_snap(2, 5, slow=5), [])          # baseline
    eng.tick(2, _serve_snap(2, 10, slow=10), [])        # breach 1
    assert eng.quarantined() == set()                   # streak < bar
    eng.tick(2, _serve_snap(2, 15, slow=10, fast=5), [])  # good interval
    eng.tick(2, _serve_snap(2, 20, slow=15, fast=5), [])  # breach 1 AGAIN
    assert eng.quarantined() == set(), (
        "one good interval must reset the breach streak (hysteresis)")
    eng.tick(2, _serve_snap(2, 25, slow=20, fast=5), [])  # breach 2
    assert eng.quarantined() == {2}
    audit = eng.table()["Audit"]
    assert [a["Action"] for a in audit if a["Outcome"] == "done"] == [
        "quarantine"]


def test_grow_cooldown_blocks_refire_and_maxgrows_caps():
    stub, eng = _engine([{"Rule": "grow_on_serve_pressure", "P99Ms": 200,
                          "Sustain": 1, "CooldownS": 3600.0,
                          "MaxGrows": 0}])
    eng.tick(2, _serve_snap(2, 5, slow=5), [])
    eng.tick(2, _serve_snap(2, 10, slow=10), [])     # fires
    assert len(stub.grows) == 1
    eng.tick(2, _serve_snap(2, 15, slow=15), [])     # still breaching
    eng.tick(2, _serve_snap(2, 20, slow=20), [])
    assert len(stub.grows) == 1, (
        "the rule cooldown must hold a sustained breach to ONE grow")
    # MaxGrows caps per-replica grows even after the cooldown expires.
    stub2, eng2 = _engine([{"Rule": "grow_on_serve_pressure",
                            "P99Ms": 200, "Sustain": 1, "CooldownS": 0.0,
                            "MaxGrows": 1}])
    eng2.tick(2, _serve_snap(2, 5, slow=5), [])
    eng2.tick(2, _serve_snap(2, 10, slow=10), [])
    eng2.tick(2, _serve_snap(2, 15, slow=15), [])
    assert len(stub2.grows) == 1, "MaxGrows=1 must cap the second grow"


def test_kill_switch_drops_to_manual_mid_action(monkeypatch):
    """Flipping DLD_POLICY mid-run holds the NEXT decision: streaks and
    sensing stay warm, the decision is audited held_manual, and no
    actuator fires until the switch flips back."""
    stub, eng = _engine([{"Rule": "quarantine_breacher", "P99Ms": 200,
                          "Breaches": 1, "CooldownS": 0.0},
                         {"Rule": "replan_straggler", "CooldownS": 0.0}])
    monkeypatch.setenv("DLD_POLICY", "1")
    assert eng.active()
    eng.tick(2, _serve_snap(2, 5, slow=5), [])
    eng.tick(2, _serve_snap(2, 10, slow=10), [])
    assert eng.quarantined() == {2}                 # armed: acts
    monkeypatch.setenv("DLD_POLICY", "0")           # mid-run flip
    assert not eng.active()
    ev = {"kind": "straggler_link", "link": "0->3", "src": 0, "dest": 3,
          "achieved_bps": 1, "modeled_bps": 100, "frac": 0.01,
          "intervals": 1}
    eng.tick(3, {}, [ev])
    assert stub.demotes == [], "manual mode must not fire actuators"
    held = [a for a in eng.table()["Audit"]
            if a.get("Outcome") == "held_manual"]
    assert held and held[-1]["Action"] == "replan", (
        "the held decision must leave a held_manual audit record")
    monkeypatch.setenv("DLD_POLICY", "1")           # flip back
    eng.tick(3, {}, [dict(ev)])
    assert stub.demotes == [(0, 3, 10)], (
        "re-armed: the same signal fires (floor 0.1 x modeled)")


def test_flapping_link_is_demoted_once_and_lifted_on_recovery():
    stub, eng = _engine([{"Rule": "replan_straggler", "FloorFrac": 0.1,
                          "CooldownS": 3600.0}])
    ev = {"kind": "straggler_link", "link": "0->3", "src": 0, "dest": 3,
          "achieved_bps": 5, "modeled_bps": 1000, "frac": 0.005,
          "intervals": 2}
    eng.tick(3, {}, [ev])
    assert stub.demotes == [(0, 3, 100)]
    # The flap: the same link straggles again while demoted — absorbed.
    eng.tick(3, {}, [dict(ev)])
    eng.tick(3, {}, [dict(ev)])
    assert len(stub.demotes) == 1, (
        "a flapping link must be re-planned ONCE, not toggled per tick")
    rec = {"kind": "link_recovered", "link": "0->3", "src": 0, "dest": 3,
           "achieved_bps": 900, "modeled_bps": 1000, "frac": 0.9,
           "intervals": 3}
    eng.tick(3, {}, [rec])
    assert stub.lifts == [(0, 3)]
    assert eng.demotions() == {}
    # Straggles again inside the rule cooldown: the re-demote is held.
    eng.tick(3, {}, [dict(ev)])
    assert len(stub.demotes) == 1, (
        "the cooldown must debounce the re-demote after a lift")


def test_engine_state_roundtrips_through_replication():
    """to_json -> load: the successor inherits rules, mask, demotions,
    in-flight actions and REMAINING cooldown seconds."""
    stub, eng = _engine([{"Rule": "quarantine_breacher", "P99Ms": 200,
                          "Breaches": 1, "CooldownS": 600.0}])
    eng.tick(2, _serve_snap(2, 5, slow=5), [])
    eng.tick(2, _serve_snap(2, 10, slow=10), [])
    state = eng.to_json()
    assert state["Quarantined"] == [2]
    key = "quarantine_breacher|2"
    assert 0 < state["Cooldowns"][key] <= 600.0
    eng2 = PolicyEngine(_StubLeader())
    eng2.load(state)
    assert eng2.quarantined() == {2}
    assert eng2.table()["Rules"] == eng.table()["Rules"]
    # The re-armed cooldown still holds the rule on the successor: the
    # same breach again produces NO new audit record (the inherited
    # ring carries the original fire; nothing is appended).
    audit_before = eng2.table()["Audit"]
    eng2.tick(2, _serve_snap(2, 5, slow=5), [])
    eng2.tick(2, _serve_snap(2, 10, slow=10), [])
    assert eng2.table()["Audit"] == audit_before, (
        "inherited cooldown must block an early re-fire")


# ------------------------------------------------- flap= seeded fault


def test_flap_spec_expands_to_partition_windows():
    _, rules = rules_from_spec("flap=2@1-3:4")
    parts = [r for r in rules if r.kind == "partition"]
    assert len(parts) == 4
    assert all(r.dest == 2 and r.direction == "out" for r in parts)
    # W = (3-1)/(2*4) = 0.25: DOWN [1,1.25) [1.5,1.75) [2,2.25) [2.5,2.75)
    windows = sorted((r.t_start, r.t_end) for r in parts)
    assert windows == [(1.0, 1.25), (1.5, 1.75), (2.0, 2.25),
                       (2.5, 2.75)]
    # Default cycle count, T1 defaulting to 0.
    _, rules3 = rules_from_spec("flap=7@-6")
    assert len([r for r in rules3 if r.kind == "partition"]) == 3
    assert min(r.t_start for r in rules3) == 0.0


@pytest.mark.parametrize("spec", ["flap=2@5", "flap=2@3-1", "flap=2@1-3:0"])
def test_flap_spec_refuses_unbounded_or_degenerate_windows(spec):
    with pytest.raises(ValueError):
        rules_from_spec(spec)


# --------------------------------------- revoke wrong-eat race (PR 9)


def test_revoke_generation_keying_closes_the_wrong_eat_race():
    reg = RevokeRegistry()
    # Legacy behavior (gen 0 both sides): first match eats, spent after.
    reg.add("j", [(2, 7)])
    assert reg.consume("j", 2, 7)
    assert not reg.consume("j", 2, 7)
    # The race: a revoke fencing plan gen 1 lands LATE at a slow
    # sender, after the gen-2 re-plan already re-dispatched the same
    # (job, dest, layer).  The fresh command must survive...
    reg.add("j", [(2, 7)], gen=1)
    assert not reg.consume("j", 2, 7, gen=2), (
        "a stale revoke ate the re-plan's fresh command (wrong-eat)")
    # ...WITHOUT disarming the entry: the stale gen-1 send it fences
    # may still be queued (or mid-fragments) behind the fresh one, and
    # must still be eaten when it checks.
    assert reg.consume("j", 2, 7, gen=1), (
        "the surviving fresh command disarmed the revoke for the "
        "stale send it was fencing")
    assert not reg.consume("j", 2, 7, gen=1)  # spent by the real match
    # A command at or below the revoke's generation IS eaten.
    reg.add("j", [(2, 7)], gen=3)
    assert reg.consume("j", 2, 7, gen=3)
    # A re-delivered older revoke never lowers an installed fence.
    reg.add("j", [(2, 7)], gen=5)
    reg.add("j", [(2, 7)], gen=4)
    assert not reg.consume("j", 2, 7, gen=6)
    # Base-run sends (no job id) are never revoked.
    assert not reg.consume("", 2, 7, gen=0)


def test_revoke_ttl_still_bounds_unconsumed_entries(monkeypatch):
    reg = RevokeRegistry()
    reg.add("j", [(2, 7)], gen=2)
    monkeypatch.setattr(RevokeRegistry, "TTL_S", -1.0)
    assert not reg.consume("j", 2, 7, gen=1), (
        "an expired revoke must read as never-revoked")


# ------------------------- leader killed mid-action (both backends)


def _build_policy_ha_cluster(kind):
    """Leader 0 (lease-beaconing, wedged LAYER sends), standby seat 5
    (EMPTY store — the only live holder of the model is the wedged
    leader, so a grow job CANNOT complete before the kill), assigned
    worker 2, spare seat 3 (announced, unassigned).  Seat ids chosen so
    ``membership.spares`` deterministically places the grow on seat 3
    (placeable seats sort by id; the standby's higher id keeps it
    last).  The wedge guarantees the action is still in flight at kill
    time on both backends — no sleep races."""
    ids = [0, 5, 2, 3]
    raw, _ = make_transports(kind, ids)
    ts = dict(raw)
    ts[0] = FaultyTransport(
        raw[0], [FaultRule("drop", "out", msg_type=MsgType.LAYER)],
        seed=1)
    assignment = {2: {0: LayerMeta()}}
    layer_size = 24 * 1024
    leader = FlowRetransmitLeaderNode(
        Node(0, 0, ts[0]),
        {i: mem_layer(i, layer_size) for i in range(2)},
        assignment, {i: 10 ** 9 for i in ids},
        expected_nodes={5, 2, 3}, standbys=[5], lease_interval=LEASE,
        epoch=0)
    standby = FlowRetransmitReceiverNode(Node(5, 0, ts[5]), {},
                                         heartbeat_interval=HB)
    ctl = StandbyController(
        standby, rank=0, lease_timeout=STANDBY_EXPIRY, standbys=[5],
        mode=3, node_network_bw={i: 10 ** 9 for i in ids},
        failure_timeout=0.0, lease_interval=LEASE)
    workers = [FlowRetransmitReceiverNode(Node(w, 0, ts[w]), {},
                                          heartbeat_interval=HB)
               for w in (2, 3)]
    return leader, standby, ctl, workers, ts, layer_size


@pytest.mark.timeout(60)
@pytest.mark.parametrize("kind", ["inmem", "tcp"])
def test_leader_killed_mid_action_standby_completes_it(kind, monkeypatch):
    """The acceptance scenario: the engine fires a grow (join+refill
    job) whose bytes are still in flight when the leader dies.  The
    promoted standby must inherit the armed rules + the in-flight
    action through the replicated Policy state, complete the job at the
    bumped epoch through the job plane, and close the action out in its
    OWN audit — exactly once, no double fire, no drop."""
    monkeypatch.setenv("DLD_METRICS_INTERVAL_S", "0.25")
    monkeypatch.setenv("DLD_POLICY", "1")
    before = dict(trace.counter_totals())
    leader, standby, ctl, workers, ts, layer_size = (
        _build_policy_ha_cluster(kind))
    rules = [{"Rule": "grow_on_serve_pressure", "P99Ms": 100.0,
              "Sustain": 2}]
    try:
        leader.policy.arm(rules)
        standby.announce()
        for w in workers:
            w.announce()
        leader.start_distribution().get(timeout=TIMEOUT)
        # Fire the grow through the engine's own execution path: copy
        # the leader-held model onto the one placeable spare (seat 3).
        leader.policy._execute({
            "Action": "grow", "Rule": "grow_on_serve_pressure",
            "Target": 0, "Reason": "test: sustained serve pressure"})
        tbl = leader.policy.table()
        assert tbl["Inflight"], "the grow must be in flight (wedged NIC)"
        (aid, rec), = tbl["Inflight"].items()
        jid = rec["Job"]
        assert jid == f"policy-{aid}"
        assert leader.jobs.get(jid).state == "active"
        # The policy state AND the job record provably reached the
        # shadow BEFORE the kill — this failover inherits, not re-plans
        # from nothing.
        _wait_for(lambda: aid in (ctl.shadow.policy.get("Inflight")
                                  or {}),
                  what="policy inflight replication to the shadow")
        _wait_for(lambda: jid in ctl.shadow.jobs,
                  what="job replication to the shadow")
        _wait_for(lambda: ctl._armed, what="standby lease observation")
        leader.close()
        # By promotion time the ex-standby's own store holds the
        # layers (the only other holder died with the leader): it is
        # the refill source at the bumped epoch.
        for lid in range(2):
            standby.layers[lid] = mem_layer(lid, layer_size)
        _wait_for(ctl.promoted.is_set, what="standby promotion")
        new_leader = ctl.leader
        assert new_leader is not None and new_leader.epoch == 1
        # Inherited: the armed rules survived the failover verbatim.
        assert new_leader.policy.table()["Rules"] == validate_policies(
            rules)
        # The takeover resume audited the inheritance AT the new epoch
        # (``promoted`` is set before ``resume_from_takeover`` runs, so
        # the audit row is waited for, not assumed).
        _wait_for(lambda: any(
            a.get("Action") == "resume" and a.get("Epoch") == 1
            for a in new_leader.policy.table()["Audit"]),
            what="the takeover's resume audit at epoch 1")
        _wait_for(lambda: getattr(new_leader.jobs.get(jid), "state", "")
                  == "done", what="inherited grow job completion")
        # The action closes out in the successor's audit on its next
        # metrics tick — done, not re-fired, not dropped.
        _wait_for(lambda: any(
            a.get("ID") == aid and a.get("Outcome") in (
                "done", "done_degraded")
            for a in new_leader.policy.table()["Audit"]),
            what="inherited action completing in the audit")
        assert not new_leader.policy.table()["Inflight"]
        spare = workers[1]
        for lid in range(2):
            src = spare.layers.get(lid)
            assert src is not None, (kind, lid)
            assert bytes(src.inmem_data) == layer_bytes(lid, layer_size)
        after = trace.counter_totals()
        assert after.get("policy.action_grow", 0) - before.get(
            "policy.action_grow", 0) == 1, "double-fired across failover"
    finally:
        ctl.close()
        close_all(leader, [standby] + workers, ts)


# ------------------------------ the real leader's replan actuator


@pytest.mark.parametrize("kind", ["inmem", "tcp"])
def test_demoted_link_is_planned_around_by_the_leader(kind):
    """The replan actuator on a real mode-3 leader (docs/autonomy.md):
    with two holders of every layer, a demotion of the leader's own
    link to the dest prices that arc at its measured rate, so the solve
    that follows sources the dest from the other holder — the link
    table shows where the bytes went."""
    telemetry.reset_run()
    before = trace.counter_totals().get("policy.link_demotions", 0)
    size, lids = 64 * 1024, [0, 1]
    ids = [0, 1, 2]
    ts, _ = make_transports(kind, ids)
    leader = FlowRetransmitLeaderNode(
        Node(0, 0, ts[0]), {lid: mem_layer(lid, size) for lid in lids},
        {2: {lid: LayerMeta() for lid in lids}},
        {i: 10 ** 9 for i in ids}, expected_nodes={1, 2})
    peer = FlowRetransmitReceiverNode(
        Node(1, 0, ts[1]), {lid: mem_layer(lid, size) for lid in lids},
        heartbeat_interval=HB)
    dest = FlowRetransmitReceiverNode(Node(2, 0, ts[2]), {},
                                      heartbeat_interval=HB)
    try:
        leader.policy_demote_link(0, 2, 1000)
        peer.announce()
        dest.announce()
        leader.start_distribution().get(timeout=TIMEOUT)
        leader.ready().get(timeout=TIMEOUT)
        for lid in lids:
            assert bytes(dest.layers[lid].inmem_data) == layer_bytes(
                lid, size)
        links = telemetry.snapshot()["links"]
        slow = links.get("0->2", {}).get("delivered_bytes", 0)
        # The demoted arc keeps its honest budget: 1000 B/s over a plan
        # of a millisecond or so is a byte a layer.
        assert slow <= 16 * len(lids), links
        assert links["1->2"]["delivered_bytes"] + slow == len(lids) * size
        assert trace.counter_totals()["policy.link_demotions"] == before + 1
    finally:
        close_all(leader, [peer, dest], ts)


# --------------------------------------------------- static drift check


def test_policy_actions_vocab_pinned_to_fire_sites_and_docs():
    """Satellite: the audited action vocabulary can't silently diverge
    from what the engine can do or what the operator doc claims.  Every
    POLICY_ACTIONS entry must have a live dispatch site in
    runtime/policy.py's _fire and a row in docs/autonomy.md."""
    import distributed_llm_dissemination_tpu.runtime.policy as policy_mod

    assert POLICY_ACTIONS == ("grow", "replan", "quarantine", "rehome")
    src = open(policy_mod.__file__.replace(".pyc", ".py")).read()
    fire = src[src.index("def _fire"):src.index("def _complete_inflight")]
    docs = open(os.path.join(os.path.dirname(__file__), os.pardir,
                             "docs", "autonomy.md")).read()
    for action in POLICY_ACTIONS:
        assert f'if action == "{action}":' in fire, (
            f"POLICY_ACTIONS lists {action!r} but _fire has no dispatch "
            f"site for it")
        assert f"`{action}`" in docs, (
            f"POLICY_ACTIONS lists {action!r} but docs/autonomy.md has "
            f"no row for it")
