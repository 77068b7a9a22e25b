"""Pod-level pipelined serving (runtime/pp_serve.py): disseminate a model
across two pipeline stages, then run ONE forward across the pod from the
landed stage weights and compare with the unsharded reference."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_dissemination_tpu.core.types import (
    LayerLocation,
    LayerMeta,
    LayerSrc,
    SourceType,
)
from distributed_llm_dissemination_tpu.models import serde, sharded
from distributed_llm_dissemination_tpu.models.llama import (
    CONFIGS,
    forward_jit,
    init_params,
)
from distributed_llm_dissemination_tpu.parallel.mesh import (
    assignment_to_placement,
    make_mesh,
)
from distributed_llm_dissemination_tpu.runtime import (
    FlowRetransmitLeaderNode,
    FlowRetransmitReceiverNode,
    Node,
)
from distributed_llm_dissemination_tpu.runtime.pp_serve import (
    assemble_pp_params,
    pod_decode,
    pod_forward,
)
from distributed_llm_dissemination_tpu.transport import (
    InmemTransport,
    reset_registry,
)
from distributed_llm_dissemination_tpu.utils import trace

TIMEOUT = 60.0  # generous: suites run 3-wide on loaded CI hosts
CFG = CONFIGS["tiny"]
SEED = 0


@pytest.fixture(autouse=True)
def _clean():
    reset_registry()
    yield
    reset_registry()


def blob_layer(data: bytes) -> LayerSrc:
    return LayerSrc(
        inmem_data=bytearray(data), data_size=len(data),
        meta=LayerMeta(location=LayerLocation.INMEM,
                       source_type=SourceType.MEM),
    )


@contextlib.contextmanager
def two_stage_boots(mcfg, cut):
    """Shared harness: disseminate ``mcfg``'s seeded blobs across two
    stages split at ``cut`` (stage 2 also gets the head blob), wait for
    the stage boots, and yield (placement, results, stores)."""
    head_id = serde.head_blob_id(mcfg)
    blobs = {b: serde.seeded_blob(mcfg, b, SEED) for b in range(head_id + 1)}
    mesh = make_mesh((2, 4), ("pp", "tp"))
    assignment = {
        1: {b: LayerMeta() for b in range(cut)},
        2: {b: LayerMeta() for b in range(cut, head_id + 1)},
    }
    placement = assignment_to_placement(assignment, mesh, "pp")
    ts = {i: InmemTransport(str(i)) for i in range(3)}
    leader = FlowRetransmitLeaderNode(
        Node(0, 0, ts[0]),
        {b: blob_layer(d) for b, d in blobs.items()},
        assignment, {i: 10**9 for i in range(3)}, expected_nodes={1, 2},
    )
    receivers = {
        i: FlowRetransmitReceiverNode(
            Node(i, 0, ts[i]), {}, stage_hbm=True, placement=placement,
            boot_cfg=mcfg,
        )
        for i in (1, 2)
    }
    try:
        for r in receivers.values():
            r.announce()
        assert leader.start_distribution().get(timeout=TIMEOUT) == assignment
        assert leader.ready().get(timeout=TIMEOUT) == assignment
        booted = leader.boot_ready().get(timeout=60)
        assert set(booted) == {1, 2}
        results = {i: r.boot_result for i, r in receivers.items()}
        stores = {i: r.layers for i, r in receivers.items()}
        yield placement, results, stores
    finally:
        leader.close()
        for r in receivers.values():
            r.close()
        for t in ts.values():
            t.close()


def test_two_stage_dissemination_then_pod_forward(cpu_devices):
    with two_stage_boots(CFG, CFG.n_layers // 2) as (
        placement, results, stores,
    ):
        assert all(r.kind == "stage" for r in results.values())
        tokens = jnp.asarray(np.arange(32).reshape(2, 16) % CFG.vocab,
                             jnp.int32)
        out = pod_forward(CFG, placement, results, stores, tokens)
        assert out is not None, "pod not servable"
        logits, dt = out
        assert dt > 0

        want = forward_jit(init_params(CFG, jax.random.key(SEED)), tokens, CFG)
        np.testing.assert_allclose(
            np.asarray(jax.device_get(logits)),
            np.asarray(jax.device_get(want), np.float32),
            rtol=2e-2, atol=2e-2,
        )


def test_uneven_partition_forward_and_decode(cpu_devices):
    """UNEVEN contiguous stage slices (3/1 of tiny's 4 layers) serve:
    the padded pipeline forward matches the unsharded reference, and the
    pod's KV-cached greedy decode emits exactly the tokens the
    single-process decode loop (models/generate.py) does."""
    from distributed_llm_dissemination_tpu.models.generate import generate
    from distributed_llm_dissemination_tpu.runtime.pp_serve import pod_decode

    # Stages of depth 3 and 1 — the round-3 code refused this.
    with two_stage_boots(CFG, 3) as (placement, results, stores):
        assert [len(r.layer_ids) for r in results.values()] == [3, 1]
        tokens = jnp.asarray(np.arange(32).reshape(2, 16) % CFG.vocab,
                             jnp.int32)
        out = pod_forward(CFG, placement, results, stores, tokens)
        assert out is not None, "uneven pod not servable"
        logits, _ = out
        full = init_params(CFG, jax.random.key(SEED))
        want = forward_jit(full, tokens, CFG)
        np.testing.assert_allclose(
            np.asarray(jax.device_get(logits)),
            np.asarray(jax.device_get(want), np.float32),
            rtol=2e-2, atol=2e-2,
        )

        prompt = jnp.zeros((1, 16), jnp.int32)
        dec = pod_decode(CFG, placement, results, stores, max_new=6,
                         prompt=prompt)
        assert dec is not None
        toks, _ = dec
        want_toks = generate(full, prompt, CFG, max_new=6)
        np.testing.assert_array_equal(np.asarray(toks),
                                      np.asarray(want_toks))


def test_pod_forward_skips_non_partition(cpu_devices):
    # A full boot (one node holds everything) is not a pipeline: the
    # assembler must decline, not crash.
    mesh = make_mesh((2, 4), ("pp", "tp"))
    placement = assignment_to_placement({1: {0: LayerMeta()}}, mesh, "pp")

    class R:
        kind = "full"
        params = {}
        layer_ids = list(range(CFG.n_layers))

    assert pod_forward(CFG, placement, {1: R()}, {1: {}}) is None


def test_podrun_pipeline_assignment_serves(cpu_devices):
    """podrun end-to-end: a fabric topology whose Assignment splits the
    model across two stages — after the stage boots, the pod serves (the
    summary carries pod_forward_s)."""
    from distributed_llm_dissemination_tpu.cli.podrun import run_pod
    from distributed_llm_dissemination_tpu.core import config as cfg_mod

    head_id = serde.head_blob_id(CFG)
    cut = CFG.n_layers // 2
    d = {
        "Model": "tiny", "ModelSeed": SEED,
        "Nodes": [
            {"Id": 0, "Addr": "0", "IsLeader": True, "Sources": {"2": 0},
             "NetworkBW": 10**9,
             "InitialLayers": {"2": {str(b): {} for b in range(head_id + 1)}}},
            {"Id": 1, "Addr": "1", "Sources": {"2": 0}, "NetworkBW": 10**9,
             "InitialLayers": {}},
            {"Id": 2, "Addr": "2", "Sources": {"2": 0}, "NetworkBW": 10**9,
             "InitialLayers": {}},
        ],
        "Assignment": {
            "1": {str(b): {} for b in range(cut)},
            "2": {str(b): {} for b in range(cut, head_id + 1)},
        },
        "Mesh": {"AxisNames": ["nodes", "tp"], "AxisSizes": [4, 2],
                 "PipelineAxis": "nodes", "Fabric": True},
    }
    conf = cfg_mod.Config.from_json(d)
    summary = run_pod(conf, mode=3, timeout=120.0)
    assert summary["boot_nodes"] == 2
    assert summary.get("pod_forward_s", 0) > 0


def test_moe_pod_decode_matches_single_process(cpu_devices):
    """MoE pipeline serving GENERATES: the expert-routed layer runs under
    the pod's lockstep KV-cached decode and emits exactly the
    single-process loop's ids (the dense and MoE paths share one
    attention/cache implementation — models/generate.py)."""
    from distributed_llm_dissemination_tpu.models.generate import generate
    from distributed_llm_dissemination_tpu.runtime.pp_serve import pod_decode

    mcfg = CONFIGS["tiny-moe"]
    with two_stage_boots(mcfg, mcfg.n_layers // 2) as (
        placement, results, stores,
    ):
        prompt = jnp.zeros((1, 8), jnp.int32)
        dec = pod_decode(mcfg, placement, results, stores, max_new=4,
                         prompt=prompt)
        assert dec is not None, "MoE pod not servable"
        toks, _ = dec
        want = generate(init_params(mcfg, jax.random.key(SEED)), prompt,
                        mcfg, max_new=4)
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(want))


# --------------------------------------------- the pod keeps its programs
#
# ``build_pp_forward`` / ``build_pp_decode`` keep ONE jitted function per
# (configuration, sub-mesh, axis, length) for the life of the process, so
# a pod that takes a second delivery serves it without sending anything
# to the backend.  Every test here starts from an empty table: the tests
# above built the same keys in this process.

WINDOW_COUNTERS = {"compiles": "xla.compiles",
                   "built": "serve.pp_program.built",
                   "reused": "serve.pp_program.reused"}


@pytest.fixture
def no_kept_programs():
    sharded._PP_PROGRAMS.clear()
    trace.watch_compiles()
    yield
    sharded._PP_PROGRAMS.clear()


def serve_round(mcfg=CFG, max_new=4):
    """One delivery and its serve window the way ``cli.podrun`` runs it
    (assemble once, one forward, one decode of the boot prompt): what the
    window added to ``WINDOW_COUNTERS``, the logits and the tokens."""
    with two_stage_boots(mcfg, mcfg.n_layers // 2) as (
        placement, results, stores,
    ):
        assembled = assemble_pp_params(mcfg, placement, results, stores)
        prompt = jax.block_until_ready(jnp.zeros((1, 16), jnp.int32))
        before = trace.counter_totals()
        logits, _ = pod_forward(mcfg, placement, results, stores, prompt,
                                assembled=assembled)
        toks, _ = pod_decode(mcfg, placement, results, stores,
                             max_new=max_new, prompt=prompt,
                             assembled=assembled)
        after = trace.counter_totals()
        added = {short: after.get(k, 0) - before.get(k, 0)
                 for short, k in WINDOW_COUNTERS.items()}
        return added, np.asarray(logits), np.asarray(toks)


def test_second_round_sends_no_program_to_the_backend(
        cpu_devices, no_kept_programs):
    (first, _, _), (second, _, _) = serve_round(), serve_round()
    assert first["compiles"] == 2, "a forward and a decode, once"
    assert second["compiles"] == 0, "the second delivery found both kept"


def test_built_and_reused_count_the_two_programs(
        cpu_devices, no_kept_programs):
    """What ``benchmark/metrics/serve.pp_programs_reused.json`` reads:
    the warm-up round builds 2 and reuses 0, a counted round 0 and 2."""
    (first, logits_1, toks_1), (second, logits_2, toks_2) = (
        serve_round(), serve_round())
    assert (first["built"], first["reused"]) == (2, 0)
    assert (second["built"], second["reused"]) == (0, 2)
    # the same seed delivered twice: the kept programs serve the same
    np.testing.assert_array_equal(logits_1, logits_2)
    np.testing.assert_array_equal(toks_1, toks_2)


@pytest.mark.parametrize("what", ["max_new", "sub-mesh", "cfg"])
def test_another_key_builds_a_program_of_its_own(
        cpu_devices, no_kept_programs, what):
    mesh = make_mesh((2, 4), ("pp", "tp"))
    kept = (sharded.build_pp_forward(CFG, mesh, "pp"),
            sharded.build_pp_decode(CFG, mesh, "pp", 4))
    cfg2, mesh2, n2 = {
        "max_new": (CFG, mesh, 5),
        # the same axes over other devices (the stages the other way round)
        "sub-mesh": (CFG, jax.sharding.Mesh(mesh.devices[::-1],
                                            mesh.axis_names), 4),
        "cfg": (CONFIGS["tiny-moe"], mesh, 4),
    }[what]
    other = (sharded.build_pp_forward(cfg2, mesh2, "pp"),
             sharded.build_pp_decode(cfg2, mesh2, "pp", n2))
    assert other[1] is not kept[1]
    # the forward has no length in its key: another max_new shares it
    assert (other[0] is kept[0]) == (what == "max_new")
    built = trace.counter_totals()["serve.pp_program.built"]
    assert built == (3 if what == "max_new" else 4)
    # an equal key is the same function, whoever made the mesh object
    again = jax.sharding.Mesh(mesh.devices.copy(), mesh.axis_names)
    assert sharded.build_pp_forward(CFG, again, "pp") is kept[0]
    assert sharded.build_pp_decode(CFG, again, "pp", 4) is kept[1]
    assert trace.counter_totals()["serve.pp_program.built"] == built


def test_a_refused_family_keeps_nothing(no_kept_programs):
    from distributed_llm_dissemination_tpu.models import family, longcat

    with pytest.raises(family.FamilyNotSupported):
        sharded.build_pp_forward(longcat.CONFIGS["tiny-longcat"], None, "pp")
    assert not sharded._PP_PROGRAMS
    assert "serve.pp_program.built" not in trace.counter_totals()


def test_the_table_of_kept_programs_is_bounded(
        cpu_devices, no_kept_programs, monkeypatch):
    monkeypatch.setattr(sharded, "_PP_PROGRAMS_MAX", 2)
    mesh = make_mesh((2, 4), ("pp", "tp"))
    first = sharded.build_pp_decode(CFG, mesh, "pp", 1)
    for n in (2, 3):
        sharded.build_pp_decode(CFG, mesh, "pp", n)
    assert len(sharded._PP_PROGRAMS) == 2
    # the oldest key went, and is built again when it is asked for
    assert sharded.build_pp_decode(CFG, mesh, "pp", 1) is not first


def test_callers_of_one_key_at_once_get_one_function(
        cpu_devices, no_kept_programs):
    """Two functions for one key would each compile their own program."""
    import sys
    import threading

    mesh = make_mesh((2, 4), ("pp", "tp"))
    start, got = threading.Barrier(16), []

    def call():
        start.wait(timeout=TIMEOUT)
        got.append(sharded.build_pp_decode(CFG, mesh, "pp", 4))

    threads = [threading.Thread(target=call, name=f"pp-build-{i}")
               for i in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=TIMEOUT)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(got) == 16 and all(fn is got[0] for fn in got)
    totals = trace.counter_totals()
    assert (totals["serve.pp_program.built"],
            totals["serve.pp_program.reused"]) == (1, 15)


def test_kept_program_equals_a_fresh_build_bit_for_bit(
        cpu_devices, no_kept_programs):
    with two_stage_boots(CFG, 3) as (placement, results, stores):
        mesh, layers, counts, head = assemble_pp_params(
            CFG, placement, results, stores)
        prompt = jnp.asarray(np.arange(32).reshape(2, 16) % CFG.vocab,
                             jnp.int32)
        for _ in range(2):  # the second pass runs the kept functions
            logits, _ = pod_forward(CFG, placement, results, stores, prompt)
            toks, _ = pod_decode(CFG, placement, results, stores, max_new=6,
                                 prompt=prompt)
        assert trace.counter_totals()["serve.pp_program.reused"] == 2
        fresh_logits = sharded._pp_forward_program(CFG, mesh, "pp")(
            layers, counts, head, prompt)
        fresh_toks = sharded._pp_decode_program(CFG, mesh, "pp", 6)(
            layers, counts, head, prompt)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(fresh_logits))
        np.testing.assert_array_equal(np.asarray(toks),
                                      np.asarray(fresh_toks))


def test_a_round_after_every_array_was_deleted_serves_from_the_kept(
        cpu_devices, no_kept_programs):
    """The benchmark's cold round (``benchmark/child.py`` ``make_cold``)
    and a deployment's swap delete the device arrays of the last
    delivery: a kept program closes over none, so it still serves.  Here
    every array made since the test began goes, whoever points at it."""
    there_before = {id(a) for a in jax.live_arrays()}
    first, logits_1, toks_1 = serve_round()
    for a in jax.live_arrays():
        if id(a) not in there_before:
            a.delete()
    second, logits_2, toks_2 = serve_round()
    assert first == {"compiles": 2, "built": 2, "reused": 0}
    assert second == {"compiles": 0, "built": 0, "reused": 2}
    np.testing.assert_array_equal(logits_1, logits_2)
    np.testing.assert_array_equal(toks_1, toks_2)
