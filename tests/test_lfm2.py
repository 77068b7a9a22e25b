"""A family whose layers are of several kinds (``models/lfm2.py``: gated
short convolution or attention, dense or routed feed-forward) behind the
family table: which kind a layer id is, a blob's leaves by its id,
parameters and serving state stacked by kind, one decode program and one
stack a kind in the boot, a streamed boot that serves — and the entry
points that have not learnt the family refusing it by name."""

import contextlib
import dataclasses
import json
import logging

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
from distributed_llm_dissemination_tpu.models import (
    family,
    generate,
    lfm2,
    llama,
    quant,
    serde,
)
from distributed_llm_dissemination_tpu.runtime import boot
from distributed_llm_dissemination_tpu.runtime.stream_boot import (
    StreamingBootStager,
)
from distributed_llm_dissemination_tpu.transport import reset_registry
from distributed_llm_dissemination_tpu.utils import trace

TINY = lfm2.CONFIGS["tiny-lfm2"]  # conv_dense x2, attn_moe, conv_moe
# Two periods after the dense layers: each routed kind's stack is read
# twice, a layer of it at a time (``family.scan_stack`` scans over the
# four layers with a switch on the kind and takes each layer out of its
# kind's stack by index; the committed cut has no such stretch, the
# published depth has one of thirty-eight layers).
TWO = dataclasses.replace(
    TINY, name="tiny-lfm2-two",
    layer_types=("conv", "conv", "full_attention", "conv", "full_attention",
                 "conv"))
TIMEOUT = 60.0


@pytest.fixture(autouse=True)
def _clean():
    reset_registry()
    trace.reset_run()
    yield
    reset_registry()


def blob_layer(data: bytes, device: bool = False) -> LayerSrc:
    src = LayerSrc(inmem_data=bytearray(data), data_size=len(data),
                   meta=LayerMeta(location=LayerLocation.INMEM,
                                  source_type=SourceType.MEM))
    if device:
        src.device_array = jax.device_put(np.frombuffer(data, np.uint8),
                                          jax.devices()[0])
    return src


def seeded_layers(cfg, seed: int = 0, device: bool = False) -> dict:
    return {b: blob_layer(serde.seeded_blob(cfg, b, seed), device)
            for b in range(cfg.n_layers + 1)}


@contextlib.contextmanager
def compile_log():
    """Every program JAX compiles inside the block, by its log line."""
    records = []

    class Handler(logging.Handler):
        def emit(self, r):
            records.append(r.getMessage())

    h = Handler()
    lg = logging.getLogger("jax._src.interpreters.pxla")
    old = lg.level
    lg.addHandler(h)
    lg.setLevel(logging.DEBUG)
    jax.config.update("jax_log_compiles", True)
    try:
        yield records
    finally:
        jax.config.update("jax_log_compiles", False)
        lg.removeHandler(h)
        lg.setLevel(old)


# ------------------------------------------ one place says a layer's kind


def test_the_table_says_which_kind_a_layer_is():
    assert family.layer_kinds(TINY) == (
        "conv_dense", "conv_dense", "attn_moe", "conv_moe")
    assert family.group(TINY) == {"conv_dense": [0, 1], "attn_moe": [2],
                                  "conv_moe": [3]}
    assert family.stretches(TINY) == [
        [("conv_dense", 0), ("conv_dense", 1)], [("attn_moe", 0)],
        [("conv_moe", 0)]]
    # kinds that alternate, each run a PART of its kind's stack, are ONE
    # stretch (one scan with a switch on the kind)
    assert family.stretches(TWO) == [
        [("conv_dense", 0), ("conv_dense", 1)],
        [("attn_moe", 0), ("conv_moe", 0), ("attn_moe", 1), ("conv_moe", 1)]]
    # a stage's slice: places count among the layers HELD
    assert family.group(TWO, [3, 4, 5]) == {"conv_moe": [3, 5],
                                            "attn_moe": [4]}
    assert family.stretches(TWO, [3, 4, 5]) == [
        [("conv_moe", 0)], [("attn_moe", 0)], [("conv_moe", 1)]]
    # a family whose layers are alike is the case of one kind
    tiny = llama.CONFIGS["tiny"]
    assert family.layer_kinds(tiny) == (family.ONE_KIND,) * 4
    assert family.stretches(tiny) == [
        [(family.ONE_KIND, i) for i in range(4)]]
    tree = {"w": np.zeros((4, 2))}
    assert family.by_kind(tiny, tree) == {family.ONE_KIND: tree}
    assert family.of_kinds(tiny, {family.ONE_KIND: tree}) is tree
    by = {"conv_dense": {}, "attn_moe": {}}
    assert family.by_kind(TINY, by) is by and family.of_kinds(TINY, by) is by


def test_a_blobs_leaves_depend_on_its_id():
    names = {b: [n for n, _ in serde.blob_specs(TINY, b)] for b in range(5)}
    assert names[0] == names[1] == [
        "operator_norm", "in_proj", "conv", "out_proj", "ffn_norm",
        "w1", "w3", "w2"]
    assert names[2] == [
        "operator_norm", "q_proj", "k_proj", "v_proj", "q_layernorm",
        "k_layernorm", "out_proj", "ffn_norm", "gate", "expert_bias",
        "ew1", "ew3", "ew2"]
    assert names[3] == [
        "operator_norm", "in_proj", "conv", "out_proj", "ffn_norm",
        "gate", "expert_bias", "ew1", "ew3", "ew2"]
    assert names[4] == ["embed", "embedding_norm"]  # no lm_head: tied
    shapes = dict(serde.blob_specs(TINY, 3))
    assert shapes["conv"] == (64, 3) and shapes["in_proj"] == (64, 192)
    assert shapes["expert_bias"] == (16,) and shapes["ew2"] == (16, 32, 64)
    assert [serde.blob_kind(TINY, b) for b in range(5)] == [
        "conv_dense", "conv_dense", "attn_moe", "conv_moe", "head"]
    sizes = [serde.blob_nbytes(TINY, b) for b in range(5)]
    assert sizes[0] == sizes[1] and len(set(sizes)) == 4
    with pytest.raises(ValueError, match="are not all alike"):
        serde.layer_param_specs(TINY)
    assert not hasattr(TINY, "layer_nbytes")  # no one size of a layer


@pytest.mark.parametrize("cfg", [TINY, TWO], ids=lambda c: c.name)
def test_params_are_stacked_by_kind_and_round_trip_through_their_blobs(cfg):
    params = llama.init_params(cfg, jax.random.key(3))
    groups = family.group(cfg)
    assert set(params["layers"]) == set(groups)
    for kind, ids in groups.items():
        specs = serde.layer_param_specs(cfg, ids[0])
        assert {n: a.shape for n, a in params["layers"][kind].items()} == {
            n: (len(ids), *shape) for n, shape in specs}
    blobs = serde.blobs_from_params(cfg, params)
    assert sorted(blobs) == list(range(cfg.n_layers + 1))
    assert all(len(blobs[b]) == serde.blob_nbytes(cfg, b) for b in blobs)
    back = serde.params_from_blobs(cfg, blobs)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for b in blobs:  # one blob regenerated alone: the same bytes
        assert serde.seeded_blob(cfg, b, 3) == blobs[b]
    # a stage's slice, stacked by kind among the layers held
    part = serde.stacked_from_blobs(cfg, blobs, [2, 3])
    assert {k: v["ffn_norm"].shape[0] for k, v in part.items()} == {
        "attn_moe": 1, "conv_moe": 1}


@pytest.mark.parametrize("blob", [0, 2, 3, 4])
@pytest.mark.parametrize("codec", ["int8", "int4"])
def test_every_kind_of_blob_goes_through_the_quantized_codecs(codec, blob):
    """A ``(64, 3)`` convolution leaf (int8: a scale a row for three
    values; int4: odd columns ride raw), 16-wide head norms, a 16-value
    bias and rank-3 expert stacks through encode, host decode and the
    device decode program: the same bits both ways."""
    raw = serde.seeded_blob(TINY, blob, 5)
    wire = quant.encode_blob(TINY, blob, raw, codec)
    assert len(wire) == quant.blob_nbytes_codec(TINY, blob, codec)
    host = quant.decode_blob_host(TINY, blob, wire, codec)
    specs = tuple(serde.blob_specs(TINY, blob))
    dev = quant.device_decode_jit(codec)(
        (jnp.asarray(np.frombuffer(wire, np.uint8)),), specs, "bfloat16")
    for name, shape in specs:
        assert host[name].shape == shape
        assert np.array_equal(np.asarray(dev[name][0]).view(np.uint16),
                              np.asarray(host[name]).view(np.uint16)), name
    again = quant.decode_to_raw(TINY, blob, wire, codec)
    assert len(again) == len(raw)


def test_the_bulk_device_decode_is_one_program_a_kind(cpu_devices):
    blobs = {b: serde.seeded_blob(TWO, b, 1) for b in range(TWO.n_layers)}
    with compile_log() as records:
        dev = quant.stacked_from_device(
            TWO, [jnp.asarray(np.frombuffer(blobs[b], np.uint8))
                  for b in blobs], "raw")
    assert len([r for r in records
                if r.startswith("Compiling jit(_decode_blobs)")]) <= 3
    host = serde.stacked_from_blobs(TWO, blobs, range(TWO.n_layers))
    assert jax.tree.structure(dev) == jax.tree.structure(host)
    for a, b in zip(jax.tree.leaves(dev), jax.tree.leaves(host)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- serving, by both states


@pytest.mark.parametrize("cfg", [TINY, TWO], ids=lambda c: c.name)
def test_greedy_decode_through_both_kinds_of_state_equals_the_full_forward(
        cfg):
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(1))
    prompt = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, (2, 9)), jnp.int32)
    toks, counted = generate.generate_counted(params, prompt, cfg, 6)
    seq = jnp.concatenate([prompt, toks], axis=1)
    want = jnp.argmax(llama.forward(params, seq[:, :-1], cfg)[:, 8:], -1)
    assert np.array_equal(np.asarray(toks), np.asarray(want))
    routed = sum(k.endswith("_moe") for k in family.layer_kinds(cfg))
    got = {k: int(v) for k, v in counted.items()}
    slots = 2 * (9 + 5) * routed * cfg.top_k
    assert got["moe_slots"] == got["moe_held"] == slots
    # per routed layer: the prefill touches at most all 16 experts and at
    # least top_k; each of the 5 steps between 4 and 8 (two sequences)
    assert routed * (cfg.top_k + 5 * cfg.top_k) <= got["moe_touched"] <= (
        routed * (cfg.n_experts + 5 * 2 * cfg.top_k))


def test_a_token_at_a_time_decode_equals_the_scanned_one():
    params = llama.init_params(TWO, jax.random.key(2))
    prompt = jnp.asarray([[5, 9, 200, 31, 7]], jnp.int32)
    want = np.asarray(generate.generate(params, prompt, TWO, 5))
    got = np.asarray(generate.generate_stepwise(
        lambda: (params, "v1"), prompt, TWO, 5))
    assert np.array_equal(got, want)


def test_the_cache_holds_no_kv_for_a_conv_layer():
    cache = generate.init_cache(TWO, 3, 24)
    assert jax.tree.map(lambda a: a.shape, cache) == {
        "conv_dense": {"v": (2, 3, 3, 64)},
        "conv_moe": {"v": (2, 3, 3, 64)},
        "attn_moe": {"k": (2, 3, 24, 2, 16), "v": (2, 3, 24, 2, 16)}}
    # a conv layer's state does not grow with the context
    longer = generate.init_cache(TWO, 3, 4096)
    assert longer["conv_moe"]["v"].shape == cache["conv_moe"]["v"].shape
    # nothing but the weights is rounded: both kinds of state are float32
    assert {a.dtype for a in jax.tree.leaves(cache)} == {
        jnp.dtype(jnp.float32)}


def test_a_product_keeps_sixteen_bits_of_its_activations():
    """``_mm`` hands the activations to a product as two bfloat16 terms
    made from the float32 word's bits (never by narrowing and widening
    back, which the TPU's compiler may skip): their sum is the
    activation to 2^-16, and the product against bfloat16 weights is the
    float32 product to a few 1e-6 where one rounded term reads 2e-3."""
    from distributed_llm_dissemination_tpu.models import longcat

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 5, 64)) * 3.0, jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 32)) / 8, jnp.bfloat16)
    hi, lo = lfm2._two_terms(x, jnp.bfloat16)
    assert hi.dtype == lo.dtype == jnp.bfloat16
    back = hi.astype(jnp.float32) + lo.astype(jnp.float32)
    assert float(jnp.abs(back - x).max() / jnp.abs(x).max()) < 2.0 ** -15
    with jax.default_matmul_precision("highest"):
        exact = jnp.einsum("bsd,de->bse", x, w.astype(jnp.float32))
    rel = lambda got: float(jnp.linalg.norm(got - exact)  # noqa: E731
                            / jnp.linalg.norm(exact))
    assert rel(lfm2._mm("bsd,de->bse", x, w)) < 2e-5
    assert rel(longcat._mm("bsd,de->bse", x, w)) > 1e-3
    # the sequence axis wherever the spec has it; float32 weights as is
    y = jnp.asarray(rng.standard_normal((2, 3, 5, 16)), jnp.float32)
    ew = jnp.asarray(rng.standard_normal((3, 16, 8)) / 4, jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        want = jnp.einsum("besf,efd->besd", y, ew.astype(jnp.float32))
    got = lfm2._mm("besf,efd->besd", y, ew)
    assert got.shape == want.shape
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 2e-5
    w32 = w.astype(jnp.float32)
    assert np.array_equal(np.asarray(lfm2._mm("bsd,de->bse", x, w32)),
                          np.asarray(jnp.einsum("bsd,de->bse", x, w32)))


def test_a_configuration_must_know_its_layer_types():
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(TINY, layer_types=("conv", "mamba"))
    with pytest.raises(ValueError, match="top_k"):
        dataclasses.replace(TINY, top_k=17)


# --------------------------------------------------- boot, for the family


def test_a_full_boot_serves_what_generate_serves_and_a_slice_boots_a_stage():
    cfg = TINY
    layers = seeded_layers(cfg, seed=4)
    assert boot.classify_held_blobs(cfg, layers) == ([0, 1, 2, 3], True)
    res = boot.boot_from_layers(cfg, layers, generate_tokens=4)
    params = llama.init_params(cfg, jax.random.key(4))
    assert res.kind == "full"
    assert jax.tree.structure(res.params) == jax.tree.structure(params)
    zeros = jnp.zeros((1, 16), jnp.int32)
    assert np.array_equal(np.asarray(res.logits),
                          np.asarray(llama.forward_jit(params, zeros, cfg)))
    assert np.array_equal(np.asarray(res.tokens), np.asarray(
        generate.generate(params, zeros, cfg, 4)))
    span, = [s for s in trace.spans() if s["name"] == "boot.assemble"]
    assert span["fields"]["kinds"] == 3
    # layers 1..3 are a stage of three kinds
    stage = boot.boot_from_layers(cfg, {b: layers[b] for b in (1, 2, 3)})
    assert stage.kind == "stage" and stage.activations.shape == (1, 16, 64)
    assert {k: v["ffn_norm"].shape[0] for k, v in stage.params.items()} == {
        "conv_dense": 1, "attn_moe": 1, "conv_moe": 1}
    warmed = boot.precompile_boot(cfg, [1, 2, 3])
    assert warmed["compiled"] == ["stage_forward"]


def test_a_uniform_family_assembles_one_kind():
    cfg = llama.CONFIGS["tiny"]
    layers = {b: blob_layer(serde.seeded_blob(cfg, b, 0))
              for b in range(cfg.n_layers + 1)}
    boot.boot_from_layers(cfg, layers)
    span, = [s for s in trace.spans() if s["name"] == "boot.assemble"]
    assert span["fields"]["kinds"] == 1


def test_precompile_warms_every_kinds_programs_and_the_streamed_boot_compiles_none(
        cpu_devices):
    """``precompile_boot`` for a streamed ``-hbm`` boot warms one 1-blob
    decode program for EACH kind of layer held, the head's, and the
    forward; the streamed staging of every blob, in any order, and the
    boot's first forward then compile none of them again."""
    cfg = dataclasses.replace(TWO, name="tiny-lfm2-warm", vocab=240)
    ids = list(range(cfg.n_layers + 1))
    rec = boot.precompile_boot(cfg, ids, device_blobs=True, streamed=True)
    assert rec["compiled"] == [
        "decode[raw]x1/conv_dense", "decode[raw]x1/attn_moe",
        "decode[raw]x1/conv_moe", "decode[raw]head", "forward"]
    layers = seeded_layers(cfg, device=True)
    stager = StreamingBootStager(cfg, node_id=7)
    try:
        with compile_log() as records:
            for b in reversed(ids):  # any completion order
                assert stager.submit(b, layers[b])
            res = boot.boot_from_layers(cfg, layers, stager=stager)
    finally:
        stager.close()
    assert res.via == "streamed per-layer"
    again = [r for r in records if r.startswith((
        "Compiling jit(_decode_blobs)", "Compiling jit(forward_jit)"))]
    assert not again, again
    want = llama.forward_jit(llama.init_params(cfg, jax.random.key(0)),
                             jnp.zeros((1, 16), jnp.int32), cfg)
    assert np.array_equal(np.asarray(res.logits), np.asarray(want))
    staged = [s["fields"] for s in trace.spans()
              if s["name"] == "decode.stage"]
    assert sorted(f["kind"] for f in staged) == sorted(
        list(family.layer_kinds(cfg)) + ["head"])
    assert all(f["fast_bytes"] + f["slow_bytes"] > 0 for f in staged)
    # the bulk (unstreamed) boot warms one n-blob program a kind
    bulk = boot.precompile_boot(cfg, ids, device_blobs=True, streamed=False)
    assert bulk["compiled"][:3] == [
        "decode[raw]x2/conv_dense", "decode[raw]x2/attn_moe",
        "decode[raw]x2/conv_moe"]


def test_the_boot_takes_each_kinds_staged_leaves_over_and_frees_them():
    """After a streamed boot no per-layer leaf is alive beside the
    stacked parameters: each was taken out of its blob's dict as its kind
    was stacked.  (A kind of ONE layer is stacked without a copy — the
    staged leaf, its leading axis of 1 included, is the parameter.)"""
    import gc
    import weakref

    ids = list(range(TINY.n_layers + 1))
    layers = seeded_layers(TINY)
    stager = StreamingBootStager(TINY)
    try:
        for b in ids:
            assert stager.submit(b, layers[b])
        staged = stager.collect(ids, timeout=TIMEOUT)
        alive = [weakref.ref(a) for leaves in staged.values()
                 for a in leaves.values()]
        del staged
        first = boot.boot_from_layers(TINY, layers, stager=stager)
        assert first.via == "streamed per-layer"
        gc.collect()
        kept = {id(a) for a in jax.tree.leaves(first.params)}
        assert all(ref() is None or id(ref()) in kept for ref in alive)
        two_layers = len(serde.layer_param_specs(TINY, 0))
        assert sum(ref() is None for ref in alive) >= 2 * two_layers
    finally:
        stager.close()


def test_a_streamed_boot_over_the_inmem_transport_serves_what_generate_serves():
    """Dissemination end to end: the leader seeds the five blobs of three
    sizes, node 1 stages each as it lands, boots and answers a request
    from a third seat with the tokens ``generate`` gives on an
    independently initialised model."""
    from distributed_llm_dissemination_tpu.runtime import (
        LeaderNode,
        Node,
        ReceiverNode,
    )
    from distributed_llm_dissemination_tpu.runtime.client import GenRequester
    from distributed_llm_dissemination_tpu.transport import InmemTransport

    cfg = TINY
    params = llama.init_params(cfg, jax.random.key(0))
    blobs = serde.blobs_from_params(cfg, params)
    ts = {i: InmemTransport(str(i)) for i in range(3)}
    leader = LeaderNode(Node(0, 0, ts[0]),
                        {b: blob_layer(d) for b, d in blobs.items()},
                        {1: {b: LayerMeta() for b in blobs}})
    dest = ReceiverNode(Node(1, 0, ts[1]), {}, boot_cfg=cfg)
    try:
        dest.announce()
        assert leader.start_distribution().get(timeout=TIMEOUT)
        assert leader.ready().get(timeout=TIMEOUT)
        dest.ready().get(timeout=TIMEOUT)
        assert set(leader.boot_ready().get(timeout=TIMEOUT)) == {1}
        assert dest._boot_stager.staged_count == len(blobs)
        assert dest.boot_result.via == "streamed per-layer"
        requester = GenRequester(ts[2])
        try:
            prompt = [5, 7, 11, 13, 200, 3]
            got = requester.request(1, prompt, max_new=6, timeout=TIMEOUT)
        finally:
            requester.close()
        want = generate.generate(params, jnp.asarray([prompt], jnp.int32),
                                 cfg, max_new=6)
        assert got == np.asarray(want)[0].tolist()
        served, = [s["fields"] for s in trace.spans()
                   if s["name"] == "serve.generate"]
        assert served["moe_slots"] == served["moe_held"] == 11 * 2 * 4
        assert 2 * (4 + 5 * 4) <= served["moe_touched"] <= 2 * (16 + 5 * 4)
    finally:
        leader.close()
        dest.close()
        for t in ts.values():
            t.close()


def test_a_live_swap_assembles_the_tree_by_kind():
    """``runtime/swap.py`` stacks a staged version's per-blob leaves as
    the family holds them (what state a flip carries over is open:
    ROADMAP Queue 2)."""
    from distributed_llm_dissemination_tpu.runtime.swap import SwapController

    params = llama.init_params(TINY, jax.random.key(6))
    blobs = serde.blobs_from_params(TINY, params)
    per_slot = {b: serde._split_blob(TINY, blobs[b],
                                     serde.blob_specs(TINY, b))
                for b in range(TINY.n_layers)}
    head = serde.head_from_blob(TINY, blobs[TINY.n_layers])

    class Receiver:
        boot_cfg = TINY

    ctl = SwapController.__new__(SwapController)
    ctl.r = Receiver()
    tree = ctl._assemble(per_slot, head)
    assert jax.tree.structure(tree) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert all(per_slot[b] for b in per_slot)  # its staging is untouched


# ----------------------------------------------------------- the refusals


def _pod_conf(tmp_path, model):
    from distributed_llm_dissemination_tpu.core import config as pcfg

    path = tmp_path / "pod.json"
    path.write_text(json.dumps({
        "Model": model, "ModelSeed": 0,
        "Nodes": [{"Id": 0, "Addr": "0", "IsLeader": True,
                   "Sources": {"1": 0}, "InitialLayers": {"1": {"0": {}}}},
                  {"Id": 1, "Addr": "1", "InitialLayers": {}}],
        "Assignment": {"1": {"0": {}}}, "LayerSize": 1,
        "Mesh": {"AxisNames": ["pp"], "AxisSizes": [2],
                 "PipelineAxis": "pp"}}))
    return pcfg.read_json(str(path))


def _refused_by_sharded(tmp_path):
    from distributed_llm_dissemination_tpu.models import sharded

    for call in (lambda: sharded.factor_mesh_axes(8, TINY),
                 lambda: sharded.param_specs(TINY),
                 lambda: sharded.build_pp_forward(TINY, None, "pp")):
        with pytest.raises(family.FamilyNotSupported) as e:
            call()
    return str(e.value), "models/sharded.py"


def _refused_by_train_ckpt(tmp_path):
    from distributed_llm_dissemination_tpu.models import train_ckpt

    with pytest.raises(family.FamilyNotSupported) as e:
        train_ckpt.restore_train_state(str(tmp_path), TINY, None)
    return str(e.value), "models/train_ckpt.py"


def _refused_by_podrun(tmp_path):
    from distributed_llm_dissemination_tpu.cli.podrun import run_pod

    with pytest.raises(SystemExit) as e:
        run_pod(_pod_conf(tmp_path, "tiny-lfm2"), boot="tiny-lfm2")
    assert e.value.code not in (0, None)
    return str(e.value), "cli.podrun.run_pod"


def _refused_by_train(tmp_path):
    from distributed_llm_dissemination_tpu.cli import train

    _pod_conf(tmp_path, "tiny-lfm2")
    with pytest.raises(SystemExit) as e:
        train.main(["-f", str(tmp_path / "pod.json"), "-steps", "1"])
    assert e.value.code not in (0, None)
    return str(e.value), "cli.train"


@pytest.mark.parametrize("refused", [
    _refused_by_sharded, _refused_by_train_ckpt, _refused_by_podrun,
    _refused_by_train], ids=lambda f: f.__name__[12:])
def test_an_entry_point_that_has_not_learnt_the_family_refuses_it_by_name(
        refused, tmp_path, cpu_devices):
    said, here = refused(tmp_path)
    assert f"{here} cannot run 'tiny-lfm2' of the lfm2 family" in said
    assert "(it knows llama): " in said and len(said.split(": ", 1)[1]) > 40


def test_hf_config_from_dir_refuses_the_family_by_name(tmp_path):
    from distributed_llm_dissemination_tpu.models import hf

    (tmp_path / "config.json").write_text(json.dumps(
        {"architectures": ["Lfm2MoeForCausalLM"], "hidden_size": 2048}))
    with pytest.raises(family.FamilyNotSupported,
                       match="cannot load the lfm2 family"):
        hf.config_from_dir(str(tmp_path))


def test_cli_main_knows_the_family_by_its_configurations_names():
    from distributed_llm_dissemination_tpu.cli.main import boot_config

    assert boot_config("tiny-lfm2") is TINY
    assert "tiny-lfm2" in family.known()
