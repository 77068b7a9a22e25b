"""The model-family seam (``models/family.py``): Llama behind it to the
bit, a second family through serde, generate and boot, and the entry
points that have not learnt it refusing it by name."""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_dissemination_tpu.models import (
    family,
    generate,
    llama,
    longcat,
    quant,
    serde,
)

TINY = longcat.CONFIGS["tiny-longcat"]
# A rank's share of the tiny model: 2 of 4 heads, experts 8..15 of 24.
SHARE = dataclasses.replace(TINY, name="tiny-share", heads_held=2,
                            experts_held=8, expert_first=8)
# sha256 of ``serde.seeded_blob(cfg, 0, 0)`` and of the head blob, taken
# from the parent's code (PR 26, when ``serde`` held the nine leaves
# itself).
PARENT_BLOBS = {
    "tiny": ("3dc1087386cf5a67c4dd2af580129ea51167e5da51b75de0097f24d9e8c2c0ef",
             "851376f1f437658c1ce27194fd3628d666c64be31db95d057db5e62fd714c34d"),
    "tiny-moe": ("481073d7eb6ce4cd0670e58477761ed323dfc4d3264f4fac2b9bafc2d522e4e6",
                 "851376f1f437658c1ce27194fd3628d666c64be31db95d057db5e62fd714c34d"),
    "tiny2": ("cb1b59c8f40e036181b1983bf7c9b7d8c9433cc931df9cf99f45d7999ea0e437",
              "495d38148c791dbad394c6cd033fce43c0a74d657ee4d983b2113df4d2ecca74"),
}


# ------------------------------------------------------------- the table

def test_a_kind_beside_the_stack_is_in_no_run_and_keeps_its_program_and_stack():
    """A family may deliver a blob as a layer that is no layer of the
    stack (``side_kinds``: JoyAI's prediction module): the runs leave it
    out — a forward does not read its leaves — and every blob-handling
    path treats it as one more kind."""
    from distributed_llm_dissemination_tpu.models import joyai
    from distributed_llm_dissemination_tpu.runtime import boot

    cfg = joyai.CONFIGS["tiny-joyai"]
    assert family.side_kinds(cfg) == ("mtp",) and family.side_kinds(TINY) == ()
    assert family.stretches(cfg) == [[("dense", 0)],
                                     [("moe", 0), ("moe", 1)]]
    assert family.group(cfg)["mtp"] == [cfg.n_layers - 1]
    params = llama.init_params(cfg, jax.random.key(0))
    toks = jnp.arange(8, dtype=jnp.int32)[None]
    want = np.asarray(llama.forward(params, toks, cfg))
    other = dict(params, layers=dict(
        params["layers"], mtp=jax.tree.map(lambda a: a * 0,
                                           params["layers"]["mtp"])))
    assert np.array_equal(np.asarray(llama.forward(other, toks, cfg)), want)
    blobs = serde.blobs_from_params(cfg, params)
    dev = quant.stacked_from_device(
        cfg, [jnp.asarray(np.frombuffer(blobs[b], np.uint8))
              for b in range(cfg.n_layers)], "raw")
    assert set(dev) == {"dense", "moe", "mtp"}
    assert dev["mtp"]["eh_proj"].shape == (1, 128, 64)
    warmed = boot.precompile_boot(cfg, range(cfg.n_layers + 1),
                                  device_blobs=True, streamed=True)
    assert "decode[raw]x1/mtp" in warmed["compiled"]




def test_every_family_answers_under_the_same_names():
    asked = ("CONFIGS", "HF_ARCHITECTURE", "layer_param_specs",
             "head_param_specs", "init_layer_params", "init_head_params",
             "embed", "layer_apply", "logits", "init_cache",
             "layer_with_cache")
    for name in family.FAMILIES:
        mod = family.module(name)
        assert [a for a in asked if not hasattr(mod, a)] == [], name
        assert all(cfg.family == name for cfg in mod.CONFIGS.values())


def test_a_name_resolves_in_whichever_family_has_it():
    assert family.config("tiny") is llama.CONFIGS["tiny"]
    assert family.config("tiny-longcat") is TINY
    assert family.of(TINY) is longcat and family.of(
        llama.CONFIGS["tiny"]) is llama
    assert {"tiny", "tiny-moe", "llama3-70b", "tiny-longcat"} <= set(
        family.known())
    with pytest.raises(KeyError):
        family.config("no-such-model")


def test_an_unknown_boot_model_lists_the_names_of_every_family():
    from distributed_llm_dissemination_tpu.cli.main import boot_config

    assert boot_config("tiny-longcat") is TINY
    assert boot_config("none") is None
    with pytest.raises(SystemExit) as e:
        boot_config("no-such-model")
    assert "'tiny-longcat'" in str(e.value) and "'tiny2'" in str(e.value)


@pytest.mark.parametrize("name", family.known())
def test_layer_nbytes_is_the_sum_over_the_familys_specs(name):
    """A blob's bytes are the sum over the leaves of ITS layer; where
    every layer is alike that is ``cfg.layer_nbytes()`` for each."""
    cfg = family.config(name)
    item = np.dtype(cfg.dtype).itemsize
    for b in range(cfg.n_layers):
        want = sum(int(np.prod(shape)) for _, shape
                   in serde.layer_param_specs(cfg, b)) * item
        assert serde.blob_nbytes(cfg, b) == want
        assert quant.blob_nbytes_codec(cfg, b, "raw") == want
    if set(family.layer_kinds(cfg)) == {family.ONE_KIND}:
        assert serde.layer_param_specs(cfg) == serde.layer_param_specs(cfg, 0)
        assert cfg.layer_nbytes() == serde.blob_nbytes(cfg, 0)


@pytest.mark.parametrize("name", sorted(PARENT_BLOBS))
def test_llamas_nine_leaf_blobs_keep_their_sha256(name):
    cfg = llama.CONFIGS[name]
    got = (hashlib.sha256(serde.seeded_blob(cfg, 0, 0)).hexdigest(),
           hashlib.sha256(serde.seeded_blob(cfg, cfg.n_layers, 0)).hexdigest())
    assert got == PARENT_BLOBS[name]
    assert [n for n, _ in serde.layer_param_specs(llama.CONFIGS["tiny"])] == [
        "wq", "wk", "wv", "wo", "ln1", "ln2", "w1", "w3", "w2"]


# ------------------------------------------ the second family through serde


def test_longcat_layer_blob_has_twenty_nine_leaves_three_of_rank_3():
    specs = serde.layer_param_specs(SHARE)
    assert len(specs) == 29 and len({n for n, _ in specs}) == 29
    assert [n for n, s in specs if len(s) == 3] == ["ew1", "ew3", "ew2"]
    one_d = [n for n, s in specs if len(s) == 1]
    assert len(one_d) == 9 and "router_bias" in one_d
    shapes = dict(specs)
    # the share: held heads' columns and rows, held experts, ALL outputs
    assert shapes["wq_b_0"] == (16, 2 * 12) and shapes["wo_1"] == (16, 64)
    assert shapes["wkv_b_0"] == (8, 2 * 16)
    assert shapes["ew1"] == (8, 64, 32) and shapes["router"] == (64, 32)
    assert [n for n, _ in serde.head_param_specs(SHARE)] == [
        "embed", "ln_f", "lm_head"]


@pytest.mark.parametrize("cfg", [TINY, SHARE], ids=lambda c: c.name)
def test_longcat_params_round_trip_through_their_blobs(cfg):
    params = llama.init_params(cfg, jax.random.key(3))
    blobs = serde.blobs_from_params(cfg, params)
    assert sorted(blobs) == list(range(cfg.n_layers + 1))
    assert all(len(blobs[b]) == serde.blob_nbytes(cfg, b) for b in blobs)
    back = serde.params_from_blobs(cfg, blobs)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert serde.seeded_blob(cfg, 1, 3) == blobs[1]
    assert serde.seeded_blob(cfg, cfg.n_layers, 3) == blobs[cfg.n_layers]


@pytest.mark.parametrize("codec", ["int8", "int4"])
def test_longcat_blobs_go_through_the_quantized_codecs(codec):
    """Rank-3 expert stacks and nine 1-D leaves through encode, host
    decode and the device decode program: the same bits both ways."""
    raw = serde.seeded_blob(SHARE, 0, 5)
    wire = quant.encode_blob(SHARE, 0, raw, codec)
    assert len(wire) == quant.blob_nbytes_codec(SHARE, 0, codec)
    host = quant.decode_blob_host(SHARE, 0, wire, codec)
    dev = quant.stacked_from_device(
        SHARE, [jnp.asarray(np.frombuffer(wire, np.uint8))], codec)
    for name, shape in serde.layer_param_specs(SHARE):
        assert host[name].shape == shape
        assert np.array_equal(np.asarray(dev[name][0]).view(np.uint16),
                              np.asarray(host[name]).view(np.uint16)), name


# --------------------------------------------- generate, for every family


@pytest.mark.parametrize("cfg", [llama.CONFIGS["tiny"],
                                 llama.CONFIGS["tiny-moe"], TINY, SHARE],
                         ids=lambda c: c.name)
def test_greedy_decode_through_the_cache_equals_the_full_forward(cfg):
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(1))
    prompt = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, (2, 9)), jnp.int32)
    toks, counted = generate.generate_counted(params, prompt, cfg, 6)
    seq = jnp.concatenate([prompt, toks], axis=1)
    want = jnp.argmax(llama.forward(params, seq[:, :-1], cfg)[:, 8:], -1)
    assert np.array_equal(np.asarray(toks), np.asarray(want))
    assert np.array_equal(np.asarray(generate.generate(params, prompt, cfg, 6)),
                          np.asarray(toks))
    if cfg.family == "llama":
        assert counted == {}  # this family counts nothing
    else:
        slots = 2 * (9 + 5) * cfg.n_layers * cfg.top_k
        got = {k: int(v) for k, v in counted.items()}
        assert got["moe_slots"] == slots
        assert 0 < got["moe_zero"] < slots
        assert got["moe_held"] + got["moe_zero"] <= slots
        if cfg.experts_held == cfg.n_experts:  # nothing absent
            assert got["moe_held"] + got["moe_zero"] == slots


def test_a_token_at_a_time_decode_equals_the_scanned_one_for_longcat():
    params = llama.init_params(TINY, jax.random.key(2))
    prompt = jnp.asarray([[5, 9, 200, 31, 7]], jnp.int32)
    want = np.asarray(generate.generate(params, prompt, TINY, 5))
    got = np.asarray(generate.generate_stepwise(
        lambda: (params, "v1"), prompt, TINY, 5))
    assert np.array_equal(got, want)


def test_the_latent_cache_holds_two_pairs_a_layer_and_no_heads():
    cache = generate.init_cache(SHARE, 3, 24)
    assert {k: v.shape for k, v in cache.items()} == {
        "ckv": (SHARE.n_layers, 2, 3, 24, SHARE.kv_rank),
        "kr": (SHARE.n_layers, 2, 3, 24, SHARE.rope_dim)}
    kv = generate.init_cache(llama.CONFIGS["tiny"], 3, 24)
    assert sorted(kv) == ["k", "v"] and kv["k"].shape == (4, 3, 24, 2, 32)


def test_a_share_must_lie_inside_the_model():
    with pytest.raises(ValueError, match="heads_held"):
        dataclasses.replace(TINY, heads_held=5)
    with pytest.raises(ValueError, match="experts"):
        dataclasses.replace(TINY, experts_held=8, expert_first=20)


# --------------------------------------------------- boot, for the family


def test_a_full_boot_of_longcat_blobs_serves_what_generate_serves():
    from distributed_llm_dissemination_tpu.core.types import (
        LayerLocation,
        LayerMeta,
        LayerSrc,
    )
    from distributed_llm_dissemination_tpu.runtime import boot

    cfg = SHARE
    layers = {}
    for b in range(cfg.n_layers + 1):
        blob = serde.seeded_blob(cfg, b, 4)
        layers[b] = LayerSrc(inmem_data=bytearray(blob), data_size=len(blob),
                             meta=LayerMeta(location=LayerLocation.INMEM))
    assert boot.classify_held_blobs(cfg, layers) == ([0, 1], True)
    res = boot.boot_from_layers(cfg, layers, generate_tokens=4)
    params = llama.init_params(cfg, jax.random.key(4))
    assert res.kind == "full" and set(res.params) == set(params)
    want = llama.forward_jit(params, jnp.zeros((1, 16), jnp.int32), cfg)
    assert np.array_equal(np.asarray(res.logits), np.asarray(want))
    assert np.array_equal(
        np.asarray(res.tokens), np.asarray(generate.generate(
            params, jnp.zeros((1, 16), jnp.int32), cfg, 4)))
    warmed = boot.precompile_boot(cfg, list(layers))
    assert warmed["compiled"] == ["forward"]
    stage = boot.boot_from_layers(cfg, {0: layers[0], 1: layers[1]})
    assert stage.kind == "stage" and stage.activations.shape == (1, 16, 64)


# ----------------------------------------------------------- the refusals


def test_models_sharded_refuses_the_family_by_name(cpu_devices):
    from distributed_llm_dissemination_tpu.models import sharded

    for call in (lambda: sharded.factor_mesh_axes(8, TINY),
                 lambda: sharded.param_specs(TINY),
                 lambda: sharded.make_train_mesh(8, TINY),
                 lambda: sharded.build_pp_forward(TINY, None, "pp"),
                 lambda: sharded.build_pp_decode(TINY, None, "pp", 4)):
        with pytest.raises(family.FamilyNotSupported,
                           match="models/sharded.py cannot run "
                                 "'tiny-longcat' of the longcat family"):
            call()
    assert sharded.param_specs(llama.CONFIGS["tiny"])  # Llama as before


def test_train_ckpt_refuses_the_family_by_name(tmp_path):
    from distributed_llm_dissemination_tpu.models import train_ckpt

    with pytest.raises(family.FamilyNotSupported,
                       match="models/train_ckpt.py cannot run"):
        train_ckpt.restore_train_state(str(tmp_path), TINY, None)


def test_hf_config_from_dir_refuses_the_family_by_name(tmp_path):
    from distributed_llm_dissemination_tpu.models import hf

    (tmp_path / "config.json").write_text(json.dumps(
        {"architectures": ["LongcatFlashForCausalLM"], "hidden_size": 6144}))
    with pytest.raises(ValueError, match="cannot load the longcat family"):
        hf.config_from_dir(str(tmp_path))
    other = tmp_path / "other"
    other.mkdir()
    (other / "config.json").write_text(json.dumps(
        {"architectures": ["MambaForCausalLM"]}))
    with pytest.raises(ValueError, match=r"'MambaForCausalLM' \(Llama only\)$"):
        hf.config_from_dir(str(other))


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


def test_run_pod_refuses_the_family_and_exits_non_zero(tmp_path, cpu_devices):
    from distributed_llm_dissemination_tpu.cli.podrun import run_pod

    with pytest.raises(SystemExit) as e:
        run_pod(_pod_conf(tmp_path, "tiny-longcat"), boot="tiny-longcat")
    assert e.value.code not in (0, None)
    assert ("cli.podrun.run_pod cannot run 'tiny-longcat' of the longcat "
            "family") in str(e.value)


@pytest.mark.parametrize("resume", [False, True])
def test_cli_train_refuses_the_family_and_exits_non_zero(tmp_path, resume):
    from distributed_llm_dissemination_tpu.cli import train

    conf = tmp_path / "pod.json"
    _pod_conf(tmp_path, "tiny-longcat")
    argv = ["-f", str(conf), "-steps", "1"]
    if resume:
        argv += ["-ckpt", str(tmp_path / "ck"), "-resume"]
    with pytest.raises(SystemExit) as e:
        train.main(argv)
    assert e.value.code not in (0, None)
    assert "cli.train cannot run 'tiny-longcat' of the longcat family" in str(
        e.value)


# ---------------------------- kinds that alternate, and the shared router


def _lowered_serving_programs(cfg):
    """StableHLO of ``cfg``'s prefill (16 positions) and decode (8 new
    tokens) as ``generate`` builds them, lowered for abstract inputs."""
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: generate.init_cache(cfg, 1, 24))
    prompt = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    prefill = generate._draft_prefill_fn(cfg, 16)
    first, guess, rows, counted = jax.eval_shape(prefill, params, prompt,
                                                 cache)
    return (prefill.lower(params, prompt, cache).as_text(),
            generate._draft_decode_fn(cfg, 16, 8).lower(
                params, rows, first, guess, counted).as_text())


def test_the_shared_router_gives_joyai_the_stablehlo_it_had(monkeypatch):
    """``models/routed.py`` (the sigmoid router and the share's dense
    dispatch, lifted out of ``models/joyai.py`` for two families) against
    the functions as ``joyai.py`` had them before, kept here word for
    word: JoyAI's prefill and decode at its tiny preset lower to the same
    StableHLO, character for character — and to the text's digest taken
    on the parent commit (PR 36), which also holds ``family.scan_stack``
    to the scans ``generate`` made itself."""
    from distributed_llm_dissemination_tpu.models import joyai, routed
    from distributed_llm_dissemination_tpu.models.lfm2 import _mm

    exact = jax.lax.Precision.HIGHEST

    def swiglu(xn, w1, w3, w2):
        gate = jax.nn.silu(_mm("bsd,df->bsf", xn, w1))
        return _mm("bsf,fd->bsd", gate * _mm("bsd,df->bsf", xn, w3), w2)

    def route(p, xn, cfg, bias):
        scores = jax.nn.sigmoid(
            jnp.einsum("bsd,de->bse", xn.astype(jnp.float32),
                       p["gate"].astype(jnp.float32), precision=exact))
        _, idx = jax.lax.top_k(scores + p["gate_bias"].astype(jnp.float32),
                               cfg.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        return idx, w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.route_scale

    def routed_part(p, xn, idx, w, cfg):
        held = (idx[..., None] - cfg.expert_first
                == jnp.arange(cfg.experts_held))  # [b, s, top_k, held]
        gate = (w[..., None] * held).sum(-2)  # [b, s, held]
        g = jax.nn.silu(_mm("bsd,edf->besf", xn, p["ew1"]))
        out = _mm("besf,efd->besd", g * _mm("bsd,edf->besf", xn, p["ew3"]),
                  p["ew2"])
        mixed = jnp.einsum("besd,bse->bsd", out, gate, precision=exact)
        return mixed, {
            "moe_slots": jnp.asarray(idx.size, jnp.int32),
            "moe_held": jnp.sum(held, dtype=jnp.int32),
            "moe_touched": jnp.sum(held.any((0, 1, 2)), dtype=jnp.int32)}

    tiny = joyai.CONFIGS["tiny-joyai"]
    now = _lowered_serving_programs(
        dataclasses.replace(tiny, name="tiny-joyai-shared"))
    for name, fn in (("swiglu", swiglu), ("route", route),
                     ("routed_part", routed_part)):
        monkeypatch.setattr(routed, name, fn)
    before = _lowered_serving_programs(
        dataclasses.replace(tiny, name="tiny-joyai-as-it-was"))
    assert now == before
    assert "stablehlo.while" in now[1] and len(now[0]) > 10_000
    assert [hashlib.sha256(text.encode()).hexdigest()
            for text in _lowered_serving_programs(tiny)] == [
        "afa4e3e32a6985acbd746d048438853bfd877343cd1271d35a000b7f4a768f57",
        "f715fd2176c1d5f400c3fc0eb3b443ccec886f69d49f955f020ca53e9efe00fb"]


def test_ten_runs_of_three_kinds_round_trip_through_the_stacks():
    """Eighteen layers whose kinds alternate for four periods: ten runs
    of three kinds are two stretches (the dense kind's whole stack, and
    sixteen layers of two kinds under one ``lax.switch``); ``family.
    stack`` puts each layer at its place in its kind's stack and
    ``scan_stack`` visits them in the stack's order, each with its own
    leaves and its own row of state, and puts each row back where it
    took it."""
    from distributed_llm_dissemination_tpu.models import trinity

    period = ("sliding_attention",) * 3 + ("full_attention",)
    cfg = dataclasses.replace(trinity.CONFIGS["tiny-trinity"], name="runs",
                              layer_types=(period * 5)[:18])
    kinds = family.layer_kinds(cfg)
    runs = [k for i, k in enumerate(kinds) if i == 0 or kinds[i - 1] != k]
    assert len(runs) == 10 and len(set(kinds)) == 3
    dense, mixed = family.stretches(cfg)
    assert dense == [("dense_sliding", 0), ("dense_sliding", 1)]
    assert len(mixed) == 16 and {k for k, _ in mixed} == {
        "routed_sliding", "routed_full"}
    # a layer's one leaf holds its id; its state starts at zero
    params = family.stack(cfg, range(18),
                          lambda lid: {n: np.full((1,), lid, np.float32)
                                       for n, _ in family.layer_param_specs(
                                           cfg, lid)}, np.stack)
    params = {kind: {"q_norm": jnp.asarray(leaves["q_norm"])}
              for kind, leaves in params.items()}
    assert {k: v["q_norm"][:, 0].tolist() for k, v in params.items()} == {
        "dense_sliding": [0, 1], "routed_full": [3, 7, 11, 15],
        "routed_sliding": [2, 4, 5, 6, 8, 9, 10, 12, 13, 14, 16, 17]}
    state = jax.tree.map(jnp.zeros_like, params)

    def step(x, p, row):
        # x: (how many layers so far, the last layer's id)
        ok = p["q_norm"][0] == x[0]
        return (jnp.stack([x[0] + 1, p["q_norm"][0]]),
                {"q_norm": row["q_norm"] + x[0] + 100.0},
                {"in_order": ok.astype(jnp.int32)})

    x, after, counted = jax.jit(lambda x, p, s: family.scan_stack(
        cfg, step, x, p, s))(jnp.zeros((2,)), params, state)
    assert x.tolist() == [18.0, 17.0] and int(counted["in_order"]) == 18
    # each row of state was written once, by the layer it belongs to
    assert jax.tree.map(lambda a, p: (a[:, 0] - 100 == p[:, 0]).all().item(),
                        after, params) == jax.tree.map(lambda _: True, params)
    # a stage's slice walks its own layers alone, by their places in the
    # stacks it was handed
    held = [6, 7, 8, 9]
    part = {kind: {"q_norm": jnp.asarray([[float(i)] for i in ids])}
            for kind, ids in family.group(cfg, held).items()}

    def note(x, p, _):
        # the ids in the order met, as the digits of a number
        return x * 10 + p["q_norm"][0], None, {}

    met, _, _ = family.scan_stack(cfg, note, jnp.zeros(()), part, None, held)
    assert float(met) == 6789.0
