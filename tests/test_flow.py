"""Max-flow scheduler unit tests (reference has none for flow.go).

Every scenario runs against both the pure-Python Edmonds–Karp solver and
the native C++ Dinic solver — the dual-backend pattern the transport tests
use, applied to the scheduler."""

import random

import pytest

from distributed_llm_dissemination_tpu.core.types import LayerMeta, SourceType
from distributed_llm_dissemination_tpu.sched.flow import FlowGraph
from distributed_llm_dissemination_tpu.sched.native import NativeFlowGraph
from distributed_llm_dissemination_tpu.native import load_flow_solver


needs_native = pytest.mark.skipif(
    load_flow_solver() is None,
    reason="native flow solver unavailable (no C++ toolchain)",
)

SOLVERS = [FlowGraph, pytest.param(NativeFlowGraph, marks=needs_native)]


def _meta(rate=0, st=SourceType.MEM):
    return LayerMeta(limit_rate=rate, source_type=st)


def check_tiling(jobs, layer_sizes):
    """Every layer's jobs tile [0, size) contiguously without overlap."""
    by_layer = {}
    for js in jobs.values():
        for j in js:
            by_layer.setdefault(j.layer_id, []).append(j)
    for lid, chunks in by_layer.items():
        spans = sorted((c.offset, c.offset + c.data_size) for c in chunks)
        assert spans[0][0] == 0 and spans[-1][1] == layer_sizes[lid]
        for (_, e1), (s2, _) in zip(spans, spans[1:]):
            assert e1 == s2


@pytest.mark.parametrize("solver", SOLVERS)
def test_single_sender_min_time(solver):
    # One sender at 100 B/s NIC, one 100-B layer -> t = 1000 ms (the
    # solver's time axis is milliseconds).
    g = solver(
        assignment={1: {0: _meta()}},
        status={0: {0: _meta(rate=100)}},
        layer_sizes={0: 100},
        node_network_bw={0: 100, 1: 100},
    )
    t, jobs = g.get_job_assignment()
    assert t == 1000
    assert jobs[0][0].data_size == 100 and jobs[0][0].offset == 0


@pytest.mark.parametrize("solver", SOLVERS)
def test_two_senders_split_layer(solver):
    # Two seeders, each 100 B/s, receiver NIC 200 B/s, 200-B layer:
    # optimal t = 1000 ms with the layer split across both senders.
    g = solver(
        assignment={2: {0: _meta()}},
        status={0: {0: _meta(rate=100)}, 1: {0: _meta(rate=100)}},
        layer_sizes={0: 200},
        node_network_bw={0: 100, 1: 100, 2: 200},
    )
    t, jobs = g.get_job_assignment()
    assert t == 1000
    check_tiling(jobs, {0: 200})


@pytest.mark.parametrize("solver", SOLVERS)
def test_heterogeneous_rates_proportional_split(solver):
    # 10 B/s + 90 B/s senders, 100-B layer, receiver 100 B/s -> t=1000 ms,
    # bytes split proportional to rates.
    g = solver(
        assignment={2: {0: _meta()}},
        status={0: {0: _meta(rate=10)}, 1: {0: _meta(rate=90)}},
        layer_sizes={0: 100},
        node_network_bw={0: 100, 1: 100, 2: 100},
    )
    t, jobs = g.get_job_assignment()
    assert t == 1000
    sizes = {s: sum(j.data_size for j in js) for s, js in jobs.items()}
    assert sizes.get(0, 0) <= 10
    assert sizes.get(1, 0) >= 90


@pytest.mark.parametrize("solver", SOLVERS)
def test_receiver_nic_bound(solver):
    # Plenty of senders but the receiver NIC (100 B/s) is the bottleneck
    # for 800 B -> t = 8000 ms.
    status = {i: {0: _meta(rate=1000)} for i in range(4)}
    g = solver(
        assignment={9: {0: _meta()}},
        status=status,
        layer_sizes={0: 800},
        node_network_bw={**{i: 1000 for i in range(4)}, 9: 100},
    )
    t, _ = g.get_job_assignment()
    assert t == 8000


@pytest.mark.parametrize("solver", SOLVERS)
def test_unlimited_rate_uses_nic_bw(solver):
    # limit_rate 0 means unlimited: capacity falls back to NIC bandwidth
    # (deviation from the reference, which would model a dead edge).
    g = solver(
        assignment={1: {0: _meta()}},
        status={0: {0: _meta(rate=0)}},
        layer_sizes={0: 500},
        node_network_bw={0: 100, 1: 100},
    )
    t, jobs = g.get_job_assignment()
    assert t == 5000
    assert jobs[0][0].data_size == 500


@pytest.mark.parametrize("solver", SOLVERS)
def test_multiple_layers_multiple_receivers(solver):
    # 2 layers to 2 different receivers from one seeder at 100 B/s:
    # 200 B total -> t = 2000 ms.
    g = solver(
        assignment={1: {0: _meta()}, 2: {1: _meta()}},
        status={0: {0: _meta(rate=100), 1: _meta(rate=100)}},
        layer_sizes={0: 100, 1: 100},
        node_network_bw={0: 100, 1: 100, 2: 100},
    )
    t, jobs = g.get_job_assignment()
    assert t == 2000
    total = sum(j.data_size for js in jobs.values() for j in js)
    assert total == 200


@pytest.mark.parametrize("solver", SOLVERS)
def test_deterministic_schedule(solver):
    kwargs = dict(
        assignment={2: {0: _meta()}},
        status={0: {0: _meta(rate=100)}, 1: {0: _meta(rate=100)}},
        layer_sizes={0: 200},
        node_network_bw={0: 100, 1: 100, 2: 200},
    )
    t1, j1 = solver(**kwargs).get_job_assignment()
    t2, j2 = solver(**kwargs).get_job_assignment()
    assert t1 == t2
    assert {
        s: [(j.layer_id, j.data_size, j.offset) for j in js] for s, js in j1.items()
    } == {
        s: [(j.layer_id, j.data_size, j.offset) for j in js] for s, js in j2.items()
    }


@needs_native
def test_native_matches_python_on_random_instances():
    """Property test: for random clusters, native and Python solvers agree
    on the minimum completion time, and both produce valid tilings (the
    exact split may differ — any max flow is an optimal plan)."""
    rng = random.Random(7)
    for _ in range(20):
        n_senders = rng.randint(1, 6)
        n_layers = rng.randint(1, 5)
        layer_sizes = {lid: rng.randint(1, 10_000) for lid in range(n_layers)}
        status = {}
        for s in range(n_senders):
            held = rng.sample(range(n_layers), rng.randint(1, n_layers))
            status[s] = {
                lid: _meta(rate=rng.choice([0, 50, 100, 1000]),
                           st=rng.choice(list(SourceType)))
                for lid in held
            }
        # Ensure every layer has at least one owner.
        for lid in range(n_layers):
            if not any(lid in held for held in status.values()):
                status[rng.randrange(n_senders)][lid] = _meta(rate=100)
        receiver = 100
        assignment = {receiver: {lid: _meta() for lid in range(n_layers)}}
        bw = {i: rng.choice([100, 500, 2000]) for i in status}
        bw[receiver] = rng.choice([100, 500, 2000])

        t_py, jobs_py = FlowGraph(assignment, status, layer_sizes, bw).get_job_assignment()
        t_nat, jobs_nat = NativeFlowGraph(
            assignment, status, layer_sizes, bw
        ).get_job_assignment()
        assert t_py == t_nat
        check_tiling(jobs_py, layer_sizes)
        check_tiling(jobs_nat, layer_sizes)


@pytest.mark.parametrize("solver", SOLVERS)
def test_multi_dest_replication(solver):
    # One layer assigned to TWO receivers (PP-stage replication) — the
    # reference errors on this (node.go:1078, :1092).  One seeder at
    # 100 B/s must send 2 x 100 B -> t = 2000 ms, with per-dest full copies.
    g = solver(
        assignment={1: {0: _meta()}, 2: {0: _meta()}},
        status={0: {0: _meta(rate=100)}},
        layer_sizes={0: 100},
        node_network_bw={0: 200, 1: 100, 2: 100},
    )
    t, jobs = g.get_job_assignment()
    assert t == 2000
    by_dest = {}
    for js in jobs.values():
        for j in js:
            by_dest.setdefault(j.dest_id, []).append(j)
    assert set(by_dest) == {1, 2}
    for dest, chunks in by_dest.items():
        spans = sorted((c.offset, c.offset + c.data_size) for c in chunks)
        assert spans[0][0] == 0 and spans[-1][1] == 100


@pytest.mark.parametrize("solver", SOLVERS)
def test_multi_dest_multi_sender_split(solver):
    # Two seeders, two receivers, one 200-B layer each way: senders split
    # each dest's copy; all four (sender, dest) flows are attributable.
    g = solver(
        assignment={2: {0: _meta()}, 3: {0: _meta()}},
        status={0: {0: _meta(rate=100)}, 1: {0: _meta(rate=100)}},
        layer_sizes={0: 200},
        node_network_bw={0: 100, 1: 100, 2: 100, 3: 100},
    )
    t, jobs = g.get_job_assignment()
    # 400 B total through 200 B/s of sender capacity -> t = 2000 ms.
    assert t == 2000
    for dest in (2, 3):
        chunks = [j for js in jobs.values() for j in js if j.dest_id == dest]
        spans = sorted((c.offset, c.offset + c.data_size) for c in chunks)
        assert spans[0][0] == 0 and spans[-1][1] == 200
        for (_, e1), (s2, _) in zip(spans, spans[1:]):
            assert e1 == s2


@pytest.mark.parametrize("solver", SOLVERS)
def test_remaining_override_plans_partial_bytes(solver):
    # Resume support in the solver itself: dest 1 already holds 75 of the
    # 100 bytes, dest 2 needs all 100 -> 125 B at 100 B/s -> exactly
    # 1250 ms (millisecond granularity: no padding to a whole second),
    # with dest 1 planned for exactly 25 bytes.
    g = solver(
        assignment={1: {0: _meta()}, 2: {0: _meta()}},
        status={0: {0: _meta(rate=100)}},
        layer_sizes={0: 100},
        node_network_bw={0: 200, 1: 100, 2: 100},
        remaining={(0, 1): 25},
    )
    t, jobs = g.get_job_assignment()
    assert t == 1250
    sizes = {}
    for js in jobs.values():
        for j in js:
            sizes[j.dest_id] = sizes.get(j.dest_id, 0) + j.data_size
    assert sizes == {1: 25, 2: 100}


@needs_native
def test_native_pod_scale_schedule():
    """v5e-32-shaped instance: 31 seeders x 80 layers to one cold host.
    The native solver must produce a valid tiling at the receiver-NIC
    lower bound; this is the graph size where the Python path takes
    tens of seconds and the native one milliseconds."""
    n_nodes, n_layers = 32, 80
    layer_size = 1_750_000_000  # ~1.75 GB per layer (70B-class / 80)
    bw = {i: 1_562_500_000 for i in range(n_nodes)}
    status = {
        i: {lid: _meta(rate=209_715_200, st=SourceType.DISK)
            for lid in range(n_layers)}
        for i in range(n_nodes - 1)
    }
    assignment = {n_nodes - 1: {lid: _meta() for lid in range(n_layers)}}
    sizes = {lid: layer_size for lid in range(n_layers)}
    g = NativeFlowGraph(assignment, status, sizes, bw)
    t, jobs = g.get_job_assignment()
    check_tiling(jobs, sizes)
    # Receiver NIC is the bottleneck: 80 * 1.75e9 / 1.5625e9 = 89.6 s —
    # exactly 89600 ms (the reference's integer-second search pads to 90).
    assert t == 89600


# ------------------------------------------------------- pod topology (DCN)


def test_topology_dcn_bottleneck_routes_around_thin_edge():
    """2-slice pod, one cross-slice seeder, one intra-slice seeder, DCN
    10 B/ms vs node links 100/200 B/ms: the plan must lean on the
    intra-slice sender (~10x the bytes) and pace the cross-slice one to
    the DCN capacity — the reference's flat-NIC model (flow.go:221-270)
    would split 50/50 and miss its deadline on real hardware."""
    from distributed_llm_dissemination_tpu.sched.flow import PodTopology

    topo = PodTopology.make({0: 0, 1: 1, 2: 1}, dcn_bw=10_000)  # B/s
    assignment = {2: {0: _meta()}}
    status = {0: {0: _meta(rate=100_000)}, 1: {0: _meta(rate=100_000)}}
    sizes = {0: 100_000}  # 100 KB
    bw = {0: 100_000, 1: 100_000, 2: 200_000}
    g = FlowGraph(assignment, status, sizes, bw, topology=topo)
    t, jobs = g.get_job_assignment()
    check_tiling(jobs, sizes)
    # 110 KB/s aggregate (100 intra + 10 DCN) over 100 KB -> ~909.1 ms,
    # vs 500 ms for the (wrong) flat model.
    assert 909 <= t <= 911
    by_sender = {s: sum(j.data_size for j in js) for s, js in jobs.items()}
    # Cross-slice sender is capped by the DCN edge, intra does the rest.
    assert by_sender[0] <= 10_000 * t // 1000 + 1
    assert by_sender[1] >= 9 * by_sender[0]

    # Same instance, flat model: the optimistic 50/50 plan.
    g_flat = FlowGraph(assignment, status, sizes, bw)
    t_flat, _ = g_flat.get_job_assignment()
    assert t_flat == 500


def test_topology_same_slice_matches_flat_model():
    """All nodes on one slice: the topology solver must reproduce the
    flat schedule exactly (no DCN edge in any path)."""
    from distributed_llm_dissemination_tpu.sched.flow import PodTopology

    topo = PodTopology.make({0: 0, 1: 0, 2: 0}, dcn_bw=1)
    kwargs = dict(
        assignment={2: {0: _meta(), 1: _meta()}},
        status={0: {0: _meta(rate=100), 1: _meta(rate=100)},
                1: {0: _meta(rate=100), 1: _meta(rate=100)}},
        layer_sizes={0: 100, 1: 100},
        node_network_bw={0: 100, 1: 100, 2: 200},
    )
    t_topo, jobs_topo = FlowGraph(topology=topo, **kwargs).get_job_assignment()
    t_flat, jobs_flat = FlowGraph(**kwargs).get_job_assignment()
    assert t_topo == t_flat
    assert jobs_topo == jobs_flat


def test_topology_attribution_rejects_holdings_cheat():
    """The relaxed pair vertex would let a fast sender's bytes 'become'
    a layer only a slow sender holds; the transportation re-attribution
    must reject that and push the completion time to the slow sender's
    honest schedule."""
    from distributed_llm_dissemination_tpu.sched.flow import PodTopology

    # Slice 0: node 0 holds ONLY layer 0 (fast), node 1 holds ONLY
    # layer 1 (rate-limited to 1 B/ms).  Dest (slice 1) needs both.
    topo = PodTopology.make({0: 0, 1: 0, 2: 1}, dcn_bw=1_000_000)
    g = FlowGraph(
        assignment={2: {0: _meta(), 1: _meta()}},
        status={0: {0: _meta(rate=100_000)},
                1: {1: _meta(rate=1_000)}},
        layer_sizes={0: 100_000, 1: 100_000},
        node_network_bw={0: 1_000_000, 1: 1_000_000, 2: 1_000_000},
    )
    g_topo = FlowGraph(
        assignment={2: {0: _meta(), 1: _meta()}},
        status={0: {0: _meta(rate=100_000)},
                1: {1: _meta(rate=1_000)}},
        layer_sizes={0: 100_000, 1: 100_000},
        node_network_bw={0: 1_000_000, 1: 1_000_000, 2: 1_000_000},
        topology=topo,
    )
    t_flat, _ = g.get_job_assignment()
    t_topo, jobs = g_topo.get_job_assignment()
    # Both models bound on node 1's 1 B/ms for its 100 KB layer: 100 s.
    # The topology run must agree (the DCN is wide; what matters is that
    # attribution never lets node 0 'carry' layer 1 through the pair
    # edge) and every job must come from a sender that holds the layer.
    assert t_topo == t_flat == 100_000
    check_tiling(jobs, {0: 100_000, 1: 100_000})
    for sender, js in jobs.items():
        for j in js:
            held = {0: {0}, 1: {1}}[sender]
            assert j.layer_id in held


def test_topology_fallback_without_scipy(monkeypatch):
    """The no-scipy relaxed-graph + attribution path handles the common
    (full-holdings) case identically to the LP, and the adversarial
    holdings case degrades to a valid flat replan instead of an invalid
    tiling."""
    from distributed_llm_dissemination_tpu.sched import flow as flow_mod

    monkeypatch.setattr(flow_mod, "_have_lp", lambda: False)
    topo = flow_mod.PodTopology.make({0: 0, 1: 1, 2: 1}, dcn_bw=10_000)
    g = FlowGraph(
        assignment={2: {0: _meta()}},
        status={0: {0: _meta(rate=100_000)}, 1: {0: _meta(rate=100_000)}},
        layer_sizes={0: 100_000},
        node_network_bw={0: 100_000, 1: 100_000, 2: 200_000},
        topology=topo,
    )
    t, jobs = g.get_job_assignment()
    check_tiling(jobs, {0: 100_000})
    assert 909 <= t <= 911  # same DCN-aware bound as the LP path
    by_sender = {s: sum(j.data_size for j in js) for s, js in jobs.items()}
    assert by_sender[0] <= 10_000 * t // 1000 + 1

    # Adversarial holdings: attribution may fail; the fallback must still
    # emit a valid complete tiling (flat replan).
    g2 = FlowGraph(
        assignment={2: {0: _meta(), 1: _meta()}},
        status={0: {0: _meta(rate=100_000)}, 1: {1: _meta(rate=1_000)}},
        layer_sizes={0: 100_000, 1: 100_000},
        node_network_bw={0: 1_000_000, 1: 1_000_000, 2: 1_000_000},
        topology=flow_mod.PodTopology.make({0: 0, 1: 0, 2: 1},
                                           dcn_bw=1_000_000),
    )
    t2, jobs2 = g2.get_job_assignment()
    check_tiling(jobs2, {0: 100_000, 1: 100_000})
    for sender, js in jobs2.items():
        for j in js:
            assert j.layer_id in {0: {0}, 1: {1}}[sender]


def test_torus_path_dimension_ordered_shorter_wrap():
    from distributed_llm_dissemination_tpu.sched.flow import PodTopology

    # Ring of 4 (one slice): 0..3 at coords 0..3.
    topo = PodTopology.make({0: 0, 1: 0, 2: 0, 3: 0}, dcn_bw=0,
                            slice_shape=[4], ici_link_bw=10)
    assert topo.ici_path(1, 2) == ((0, 1, 2),)
    assert topo.ici_path(3, 2) == ((0, 3, 2),)  # shorter wrap: downward
    # Distance-2 tie breaks upward: 0→1→2, not 0→3→2.
    assert topo.ici_path(0, 2) == ((0, 0, 1), (0, 1, 2))
    assert topo.ici_path(2, 0) == ((0, 2, 3), (0, 3, 0))
    assert topo.ici_path(1, 1) == ()
    # 2-D torus: dimension order (rows first), per-dim shorter wrap.
    topo2 = PodTopology.make({i: 0 for i in range(6)}, dcn_bw=0,
                             slice_shape=[2, 3], ici_link_bw=10)
    # node 0 = (0,0), node 5 = (1,2): row 0→1 then col 0→2 via wrap.
    assert topo2.ici_path(0, 5) == ((0, 0, 3), (0, 3, 5))


def test_torus_link_bottleneck_spreads_bytes_across_links():
    """SURVEY §7 hard part (the DCN test's shape, one level down): a
    ring of 4 where two senders' routes share the dest's one in-link —
    the plan must give the third sender (whose route uses the other
    in-link) its full share, and cap the sharing pair to one link's
    budget.  The flat model (huge NICs) would miss the deadline ~50x."""
    from distributed_llm_dissemination_tpu.sched.flow import PodTopology

    topo = PodTopology.make({i: 0 for i in range(4)}, dcn_bw=0,
                            slice_shape=[4], ici_link_bw=10_000)
    kwargs = dict(
        assignment={2: {0: _meta()}},
        # Senders 0, 1, 3 hold the layer; dest is node 2.  Routes:
        # 1→2 on link (1,2); 3→2 on link (3,2); 0 ties and goes up
        # 0→1→2 — SHARING link (1,2) with sender 1.
        status={0: {0: _meta(rate=1_000_000)},
                1: {0: _meta(rate=1_000_000)},
                3: {0: _meta(rate=1_000_000)}},
        layer_sizes={0: 100_000},
        node_network_bw={i: 1_000_000 for i in range(4)},
    )
    g = FlowGraph(topology=topo, **kwargs)
    t, jobs = g.get_job_assignment()
    check_tiling(jobs, {0: 100_000})
    # Two in-links to the dest at 10 kB/s each → 20 kB/s aggregate →
    # 100 kB needs ~5000 ms (vs ~100 ms for the link-blind plan).
    assert 4990 <= t <= 5015, t
    by_sender = {s: sum(j.data_size for j in js) for s, js in jobs.items()}
    # Sender 3 owns the uncontended in-link: half the bytes.
    assert by_sender.get(3, 0) >= 49_000, by_sender
    # Senders 0+1 share link (1,2): combined at most its budget.
    shared = by_sender.get(0, 0) + by_sender.get(1, 0)
    assert shared <= 10_000 * t // 1000 + len(jobs) + 1, (shared, t)

    # The link-blind solver (same instance, no torus) is ~50x faster in
    # its own model — the gap the per-link edges exist to close.
    t_flat, _ = FlowGraph(**kwargs).get_job_assignment()
    assert t_flat <= 150


def test_torus_without_scipy_degrades_loudly_but_validly(monkeypatch):
    from distributed_llm_dissemination_tpu.sched import flow as flow_mod

    monkeypatch.setattr(flow_mod, "_have_lp", lambda: False)
    topo = flow_mod.PodTopology.make({i: 0 for i in range(4)}, dcn_bw=0,
                                     slice_shape=[4], ici_link_bw=10_000)
    g = FlowGraph(
        assignment={2: {0: _meta()}},
        status={1: {0: _meta(rate=100_000)}},
        layer_sizes={0: 100_000},
        node_network_bw={i: 1_000_000 for i in range(4)},
        topology=topo,
    )
    t, jobs = g.get_job_assignment()
    check_tiling(jobs, {0: 100_000})  # valid plan, link caps dropped
    assert t == 1000  # the per-node model's answer


@needs_native
def test_native_topology_matches_python_on_random_instances():
    """Property test (the round-5 native-topology path): with a
    PodTopology, the native Dinic relaxed search and the Python one must
    agree on the minimum completion time, and the full planning paths
    must emit identical min times with valid, holdings-true tilings."""
    from distributed_llm_dissemination_tpu.sched.flow import PodTopology

    rng = random.Random(11)
    for _ in range(20):
        n_senders = rng.randint(1, 5)
        n_layers = rng.randint(1, 4)
        n_slices = rng.randint(2, 3)
        layer_sizes = {lid: rng.randint(1, 10_000)
                       for lid in range(n_layers)}
        status = {}
        for s in range(n_senders):
            held = rng.sample(range(n_layers), rng.randint(1, n_layers))
            status[s] = {lid: _meta(rate=rng.choice([0, 50, 100, 1000]))
                         for lid in held}
        for lid in range(n_layers):
            if not any(lid in held for held in status.values()):
                status[rng.randrange(n_senders)][lid] = _meta(rate=100)
        receivers = [100, 101][: rng.randint(1, 2)]
        assignment = {r: {lid: _meta() for lid in range(n_layers)}
                      for r in receivers}
        bw = {i: rng.choice([100, 500, 2000]) for i in status}
        for r in receivers:
            bw[r] = rng.choice([100, 500, 2000])
        slice_of = {i: rng.randrange(n_slices) for i in bw}
        topo = PodTopology.make(slice_of, dcn_bw=rng.choice([10, 100, 1000]))

        kwargs = dict(assignment=assignment, status=status,
                      layer_sizes=layer_sizes, node_network_bw=bw,
                      topology=topo)
        required = sum(layer_sizes[lid] for r in receivers
                       for lid in assignment[r])
        gp = FlowGraph(**kwargs)
        gn = NativeFlowGraph(**kwargs)
        tb_py = gp._relaxed_bound(required)
        tb_nat = gn._relaxed_bound(required)
        assert tb_py == tb_nat, (tb_py, tb_nat, slice_of)

        t_py, jobs_py = FlowGraph(**kwargs).get_job_assignment()
        t_nat, jobs_nat = NativeFlowGraph(**kwargs).get_job_assignment()
        assert t_py == t_nat
        for jobs in (jobs_py, jobs_nat):
            # Per (layer, dest): a contiguous non-overlapping tiling of
            # [0, size) — each dest needs its own full copy.
            by_pair = {}
            for js in jobs.values():
                for j in js:
                    by_pair.setdefault((j.layer_id, j.dest_id), []).append(j)
            assert set(by_pair) == {(lid, r) for r in receivers
                                    for lid in range(n_layers)}
            for (lid, _r), chunks in by_pair.items():
                spans = sorted((c.offset, c.offset + c.data_size)
                               for c in chunks)
                assert spans[0][0] == 0
                assert spans[-1][1] == layer_sizes[lid]
                for (_, e1), (s2, _) in zip(spans, spans[1:]):
                    assert e1 == s2
            for sender, js in jobs.items():
                for j in js:
                    assert j.layer_id in status[sender]


def test_topology_delivered_layer_rate_does_not_leak_into_class_cap():
    """Regression (round-4 review): a DELIVERED (dest-less) layer's
    metadata must not inflate its source class's capacity in either
    solver — the LP and the flat graph must agree on the completion
    time, and the relaxed seed must stay a valid lower bound."""
    from distributed_llm_dissemination_tpu.sched.flow import PodTopology

    kwargs = dict(
        assignment={1: {0: _meta()}},
        # Layer 1 is already delivered (no dests) and announces a huge
        # rate on the same source class; layer 0 is the real work.
        status={0: {0: _meta(rate=1_000), 1: _meta(rate=10**9)}},
        layer_sizes={0: 10_000, 1: 10_000},
        node_network_bw={0: 10**9, 1: 10**9},
    )
    t_flat, jobs_flat = FlowGraph(**kwargs).get_job_assignment()
    topo = PodTopology.make({0: 0, 1: 1}, dcn_bw=10**9)
    t_topo, jobs_topo = FlowGraph(topology=topo, **kwargs).get_job_assignment()
    assert t_flat == t_topo == 10_000  # 10 KB at the class's real 1 KB/s
    check_tiling(jobs_topo, {0: 10_000})


def test_run_north_star_solves():
    """The north-star target by model: the mode-3 solver on
    conf/tpu_v5e32_llama70b.json as the leader would run it, under three
    sets of holdings with one assignment (each of 8 hosts ends up with
    its 10 pipeline-stage layers) — the shipped config (ONE seeder
    behind a 3 GB/s disk-class source), the same seeder's blobs in RAM,
    and 4 of the 8 hosts holding the full set in RAM.  The solver hits
    <10 s the moment sources stop being the bottleneck, and >=70%
    dest-side utilization with replicated in-RAM seeders.  Counts of a
    solve; no clock but the solver's own."""
    import os
    import time

    from distributed_llm_dissemination_tpu.core import config as cfgmod
    from distributed_llm_dissemination_tpu.core.types import LayerLocation
    from distributed_llm_dissemination_tpu.sched import make_flow_graph

    conf = cfgmod.read_json(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "conf", "tpu_v5e32_llama70b.json"))
    line_bw = {nc.id: nc.network_bw for nc in conf.nodes}
    shipped, sizes = {}, {}
    for nc in conf.nodes:
        by_node = {}
        for st, by_layer in (nc.initial_layers or {}).items():
            for lid, size in by_layer.items():
                sizes[lid] = size or conf.layer_size
                by_node[lid] = (st, nc.sources.get(st, 0), sizes[lid])
        if by_node:
            shipped[nc.id] = by_node
    assert len(sizes) == 80

    def solve(holdings):
        status = {nc.id: {} for nc in conf.nodes}
        for node_id, by_node in holdings.items():
            for lid, (st, rate, size) in by_node.items():
                loc = (LayerLocation.DISK if st == SourceType.DISK
                       else LayerLocation.INMEM)
                status[node_id][lid] = LayerMeta(
                    location=loc, limit_rate=rate, source_type=st,
                    data_size=size)
        # The leader's assign_jobs discipline: pairs the dest already
        # holds are satisfied, the solver plans the rest.
        wanted = {}
        for dest, lids in conf.assignment.items():
            for lid, meta in lids.items():
                if lid not in status.get(dest, {}):
                    wanted.setdefault(dest, {})[lid] = meta
        t0 = time.monotonic()
        t_ms, jobs = make_flow_graph(
            wanted, status, dict(sizes), line_bw,
            topology=conf.mesh.topology()).get_job_assignment()
        solve_ms = (time.monotonic() - t0) * 1000
        wire = sum(j.data_size for jl in jobs.values() for j in jl)
        dests = {j.dest_id for jl in jobs.values() for j in jl}
        pred_s = t_ms / 1000.0
        return {"wire_bytes": wire, "solve_ms": solve_ms,
                "meets_time": pred_s < 10.0,
                "ici_utilization": wire / max(pred_s, 1e-9)
                / sum(line_bw[d] for d in dests)}

    mem1 = {n: {lid: (SourceType.MEM, 0, size)
                for lid, (_st, _r, size) in by.items()}
            for n, by in shipped.items()}
    mem4 = {n: {lid: (SourceType.MEM, 0, sizes[lid]) for lid in sizes}
            for n in sorted(line_bw)[:4]}
    rows = [solve(shipped), solve(mem1), solve(mem4)]
    assert [r["meets_time"] for r in rows] == [False, True, True]
    assert rows[2]["ici_utilization"] >= 0.70
    assert all(r["wire_bytes"] > 0 and r["solve_ms"] > 0 for r in rows)
