"""The LFM2-MoE architecture module (``benchmark/archs/lfm2_moe.py``)
against the program's family (``models/lfm2.py``), at tiny width on the
CPU: the layout both sides share blob by blob, the plain reference
against the program's forward and its decode through both kinds of
state, the controls the one tolerance must catch, the slot counters, the
committed configuration against the published one, and whole harness
runs of a tiny configuration beside a temporary manifest.

The tiny configuration (``arch_lfm2/tiny-lfm2.json``) has the committed
one's shape: two leading dense layers and one period of the pattern
(conv, conv, full_attention, conv, conv), 16 experts, top-4.
"""

import json
import os
import shutil

import numpy as np
import pytest

from bench_helpers import REPO, decoded, rehearse
from benchmark import archs, fabricate, reference
from benchmark.manifest import Manifest
from contract import problems

HERE = os.path.dirname(os.path.abspath(__file__))
TINY_FILE = os.path.join(HERE, "arch_lfm2", "tiny-lfm2.json")
ARCH_FILE = os.path.join(REPO, "benchmark", "archs", "lfm2_moe.py")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOLERANCE = 0.03  # run.py's, for every architecture
# The published configuration (config.json of LiquidAI/LFM2-24B-A2B as
# the catalog beside the model-configs guide holds it), but for the two
# keys of the cut.
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
PUBLISHED_TYPES = (["conv", "conv"]
                   + ["full_attention", "conv", "conv", "conv"] * 9
                   + ["full_attention", "conv"])


def tiny(**changed) -> dict:
    """The tiny configuration as ``Manifest.config`` would hand it out."""
    with open(TINY_FILE) as f:
        return dict(json.load(f), arch_file=ARCH_FILE, **changed)


TINY = tiny()
ARCH = archs.of(TINY)


def program_config(config: dict, name: str, **changed):
    """The program's configuration object as the module registers it."""
    import dataclasses

    from distributed_llm_dissemination_tpu.models import lfm2

    ARCH.register(config, name)
    return dataclasses.replace(lfm2.CONFIGS[name], **changed)


def seeded_model(config: dict, seed: int):
    """``{blob: {leaf: float32 array}}`` the test makes itself: matrices
    normal at ``fan_in ** -0.5`` (the taps at ``taps ** -0.5``:
    activations of order one in every operator), gains 1, and a LIVE
    selection bias, normal at 0.05 — a quarter of the sigmoid scores'
    spread, so it moves picks and leaves them to vary by token."""
    rng = np.random.default_rng(seed)
    m = ARCH.dims(config)
    model = {}
    for b in range(m["layers"] + 1):
        model[b] = {}
        for name, shape, fill in ARCH.layout(config, b):
            if name == "expert_bias":
                leaf = rng.standard_normal(shape) * 0.05
            elif fill is not None:
                leaf = np.full(shape, fill)
            else:
                fan_in = shape[-1] if name == "conv" else shape[-2]
                leaf = rng.standard_normal(shape) * fan_in ** -0.5
            model[b][name] = leaf.astype(np.float32)
    return m, model, rng.integers(0, m["vocab"], (3, 23))


def ref_logits(m: dict, model: dict, toks) -> np.ndarray:
    """The module's reference, block by block, on arrays as they are."""
    import jax
    import jax.numpy as jnp

    n = m["layers"]
    with jax.default_matmul_precision("highest"):
        head = {k: jnp.asarray(v) for k, v in model[n].items()}
        h = ARCH.ref_in(jnp, m, head, jnp.asarray(toks))
        for b in range(n):
            h = ARCH.ref_layer(
                jnp, jax, m, {k: jnp.asarray(v) for k, v in model[b].items()},
                h)
        return np.asarray(ARCH.ref_out(jnp, m, head, h))


def stacked(cfg, m: dict, model: dict) -> dict:
    """The program's parameter tree from per-blob leaves: stacked by
    kind of layer, as the family table says."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models import family

    n = m["layers"]
    layers = family.stack(cfg, range(n), lambda b: dict(model[b]), np.stack)
    return jax.tree.map(jnp.asarray, {"layers": layers, **model[n]})


def rel(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ------------------------------------------- (f) one layout on both sides


@pytest.mark.parametrize("name", ["tiny", "lfm2-24b-a2b-d6"])
def test_the_programs_specs_equal_the_modules_layout_for_every_blob(name):
    from distributed_llm_dissemination_tpu.models import quant, serde

    config = TINY if name == "tiny" else Manifest().config(name)[1]
    cfg = program_config(config, "layout-" + name)
    n = fabricate.model_dims(config)["layers"]
    assert serde.head_blob_id(cfg) == n
    for b in range(n + 1):
        assert serde.blob_specs(cfg, b) == fabricate.blob_specs(config, b)
        for codec in fabricate.CODECS:
            assert quant.blob_nbytes_codec(cfg, b, codec) == (
                fabricate.blob_nbytes(config, b, codec))
    kinds = {serde.blob_kind(cfg, b): fabricate.blob_nbytes(config, b)
             for b in range(n)}
    assert sorted(kinds) == ["attn_moe", "conv_dense", "conv_moe"]
    assert len(set(kinds.values())) == 3


def test_the_committed_configuration_is_the_published_one_cut_in_depth():
    """Every published key as published but for ``num_hidden_layers`` and
    ``layer_types`` (each with its published value, its value here and
    why); what was assumed; the deployment; the bytes of each kind of
    blob, of the head and of the replica."""
    entry, config = Manifest().config("lfm2-24b-a2b-d6")
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    cut = {"num_hidden_layers": (40, 6),
           "layer_types": (PUBLISHED_TYPES, PUBLISHED_TYPES[:6])}
    assert list(config["reduced"]) == entry["reduced"] == list(cut)
    for key, (published, here) in cut.items():
        rec = config["reduced"][key]
        assert (rec["published"], rec["here"], config[key]) == (
            published, here, here) and len(rec["why"]) > 40
    extra = set(config) - set(PUBLISHED) - set(cut)
    assert extra == {"arch", "arch_file", "source", "reduced", "assumed",
                     "deployment"}
    assert set(config["assumed"]) == {
        "tie_word_embeddings", "head_dim", "router_dtype", "expert_bias",
        "norm_gains", "conv"}
    assert "first of seven pipeline stages" in config["deployment"]
    assert entry["source"] == config["source"]
    if os.path.exists(CATALOG):  # the catalog's own row, where it is
        with open(CATALOG) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "LFM2-24B-A2B"]
        assert row["source_url"] == config["source"]
        assert {k: v for k, v in row["config"].items()
                if k not in cut} == PUBLISHED
        assert row["config"]["layer_types"] == PUBLISHED_TYPES
        assert row["config"]["num_hidden_layers"] == 40
    sizes = [fabricate.blob_nbytes(config, b) for b in range(7)]
    assert sizes == [178_278_400, 178_278_400, 1_229_201_792,
                     1_241_796_736, 1_241_796_736, 1_241_796_736,
                     268_439_552]
    assert fabricate.model_nbytes(config) == 5_579_588_352
    m = fabricate.model_dims(config)
    assert (m["experts"], m["top_k"], m["vocab"], m["hd"]) == (
        64, 4, 65536, 64)
    fills = {b: {n: f for n, _, f in ARCH.layout(config, b)}
             for b in (0, 2, 3, 6)}
    assert [len(fills[b]) for b in (0, 2, 3, 6)] == [8, 13, 10, 2]
    assert fills[3]["expert_bias"] is None and fills[3]["conv"] is None
    assert sorted(k for k, v in fills[2].items() if v == 1.0) == [
        "ffn_norm", "k_layernorm", "operator_norm", "q_layernorm"]
    assert "lm_head" not in fills[6]  # embedding and head: one tensor


def test_the_module_registers_the_pattern_it_was_given():
    cfg = program_config(TINY, "pattern-told")
    assert cfg.layer_types == ("conv", "conv", "full_attention", "conv",
                               "conv") and cfg.n_layers == 5
    assert (cfg.n_dense, cfg.n_experts, cfg.top_k, cfg.conv_kernel,
            cfg.head_dim) == (2, 16, 4, 3, 16)
    with pytest.raises(ValueError, match="layer_types"):
        ARCH.dims(tiny(num_hidden_layers=4))
    for differs in ({"conv_bias": True}, {"norm_topk_prob": False},
                    {"use_expert_bias": False}):
        with pytest.raises(SystemExit, match="this config differs"):
            ARCH.register(tiny(**differs), "differs")


# ------------------------- (b) the program against the reference, float32


@pytest.mark.parametrize("codec", fabricate.CODECS)
def test_the_reference_agrees_with_the_programs_forward(codec):
    """Two implementations that share no code, float32 both, the same
    blobs of the harness's own fill (gains 1, a seeded selection bias):
    they agree to float32 rounding."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models.llama import forward

    cfg = program_config(TINY, "ref-" + codec, dtype=jnp.float32)
    m = fabricate.model_dims(TINY)
    n = m["layers"]
    blobs = {b: fabricate.make_blob(TINY, b, 7, codec) for b in range(n + 1)}
    model = {b: decoded(TINY, b, blobs[b], codec) for b in blobs}
    toks = np.asarray(fabricate.make_prompts(TINY, 7, 3, 16))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(stacked(cfg, m, model), jnp.asarray(toks),
                                  cfg))
    got = reference.logits(TINY, toks, lambda b: fabricate.blob_leaves(
        TINY, b, blobs[b], codec))
    assert got.shape == (3, 16, m["vocab"])
    # 1e-5: float32 rounding through five layers; a near-tied fourth pick
    # that parts the two sides would read a hundred times that
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("seed", [0, 1])
def test_prefill_and_decode_through_both_kinds_of_state_equal_the_reference(
        seed):
    """A live selection bias; the program's full forward within 1e-5 of
    the reference, then its prefill of 15 positions and 8 decode steps —
    conv state and K/V rows side by side — against the full forward's
    LOGITS at every position."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models import generate, llama

    cfg = program_config(TINY, f"cache-{seed}", dtype=jnp.float32)
    m, model, toks = seeded_model(TINY, seed)
    params = stacked(cfg, m, model)
    want = ref_logits(m, model, toks)
    with jax.default_matmul_precision("highest"):
        full = np.asarray(llama.forward(params, jnp.asarray(toks), cfg))
        assert rel(full, want) < 1e-5
        cache = generate.init_cache(cfg, 3, 23)
        assert {k: sorted(v) for k, v in cache.items()} == {
            "conv_dense": ["v"], "conv_moe": ["v"], "attn_moe": ["k", "v"]}
        assert cache["conv_moe"]["v"].shape == (2, 3, 3, 64)  # no K/V rows
        got, cache, _ = generate._prefill_fn(cfg, 15)(
            params, jnp.asarray(toks[:, :15]), cache)
        errs = [np.abs(np.asarray(got) - full[:, 14]).max()]
        for t in range(15, 23):
            got, cache, _ = generate._forward_with_cache(
                params, jnp.asarray(toks[:, t:t + 1]), jnp.asarray([t]),
                cache, cfg)
            errs.append(np.abs(np.asarray(got) - full[:, t]).max())
    # float32 rounding, relative to the largest logit
    assert len(errs) == 9 and max(errs) < 1e-5 * np.abs(full).max() * 10


def test_the_scanned_decode_equals_the_stepwise_one():
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models import generate

    cfg = program_config(TINY, "scan-step", dtype=jnp.float32)
    m, model, toks = seeded_model(TINY, 2)
    params = stacked(cfg, m, model)
    with jax.default_matmul_precision("highest"):
        prompt = jnp.asarray(toks[:, :16])
        scanned = np.asarray(generate.generate(params, prompt, cfg, 8))
        stepped = np.asarray(generate.generate_stepwise(
            lambda: (params, "v"), prompt, cfg, 8))
    assert np.array_equal(scanned, stepped)


# ------------------------ (d) controls that the one tolerance must catch
#
# Each control leaves one piece of the published mathematics out of the
# reference and is held against the faithful reference on the same arrays
# (normal weights, a live selection bias, 3 x 23 positions, the tiny
# model, seeds 0-5).  Relative L2 of the logits, beside the tolerance of
# 3% (readings of this file's own runs on the CPU, float32):
#
#   C gate dropped (out_proj . c)         138 - 142%
#   q/k head norms dropped                9.5 - 12.5%
#   norm_topk_prob's division dropped      57 - 63%
#   taps reversed in time                 130 - 138%
#   selection bias dropped                 16 - 19%


def _patched(name, fn):
    def control(m, model, monkeypatch):
        monkeypatch.setattr(ARCH, name, fn(getattr(ARCH, name)))
        return m, model
    return control


def _taps_reversed(m, model, monkeypatch):
    return m, {b: (dict(p, conv=p["conv"][:, ::-1].copy()) if "conv" in p
                   else p) for b, p in model.items()}


def _bias_dropped(m, model, monkeypatch):
    return m, {b: (dict(p, expert_bias=np.zeros_like(p["expert_bias"]))
                   if "expert_bias" in p else p) for b, p in model.items()}


CONTROLS = {
    "C gate dropped": _patched("_gated", lambda _: lambda c_gate, c: c),
    "head norms dropped": _patched(
        "_head_norms", lambda _: lambda jnp, m, p, q, k: (q, k)),
    "top-k division dropped": lambda m, model, mp: (
        dict(m, norm_topk=False), model),
    "taps reversed in time": _taps_reversed,
    "selection bias dropped": _bias_dropped,
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_piece_of_the_mathematics_left_out_fails_the_tolerance(
        control, seed, monkeypatch):
    m, model, toks = seeded_model(TINY, seed)
    want = ref_logits(m, model, toks)
    got = ref_logits(*CONTROLS[control](m, model, monkeypatch), toks)
    assert rel(got, want) > TOLERANCE, control


# --------------------------------------------------- (e) the slot counters


def test_the_programs_slot_counts_equal_the_references_own_picks():
    """A float32 program run (prefill of 16, 7 decode steps, as a served
    request, three sequences) counts ``moe_slots`` / ``moe_held`` /
    ``moe_touched``; the reference's own picks on the same 23 positions,
    counted here call by call (the prefill's 16 positions, then each
    step's one), give the same three numbers exactly."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models import generate

    cfg = program_config(TINY, "counted", dtype=jnp.float32)
    m, model, toks = seeded_model(TINY, 5)
    params = stacked(cfg, m, model)
    with jax.default_matmul_precision("highest"):
        served, counted = generate.generate_counted(
            params, jnp.asarray(toks[:, :16]), cfg, 8)
        seq = np.concatenate([toks[:, :16], np.asarray(served)], axis=1)
        head = {k: jnp.asarray(v) for k, v in model[m["layers"]].items()}
        h = ARCH.ref_in(jnp, m, head, jnp.asarray(seq[:, :-1]))
        want = {"moe_slots": 0, "moe_held": 0, "moe_touched": 0}
        routed = 0
        for b in range(m["layers"]):
            p = {k: jnp.asarray(v) for k, v in model[b].items()}
            pick = ARCH.picks(jnp, jax, m, p, h)
            if pick is not None:
                routed += 1
                pick = np.asarray(pick)
                want["moe_slots"] += pick.size
                want["moe_held"] += pick.size  # every expert is here
                calls = [pick[:, :16]] + [pick[:, t:t + 1]
                                          for t in range(16, 23)]
                want["moe_touched"] += sum(len(np.unique(c)) for c in calls)
            h = ARCH.ref_layer(jnp, jax, m, p, h)
    assert {k: int(v) for k, v in counted.items()} == want
    assert routed == 3 and want["moe_slots"] == 3 * 23 * 3 * m["top_k"]
    assert want["moe_touched"] < 3 * 8 * m["experts"]  # the dense read


# ------------------------------ (a) whole harness runs, as files and entries


MIXES = ("cold-raw", "cold-int8")


def add_lfm2(manifest: str, tag: str) -> None:
    """The tiny configuration as a NEW FILE beside ``manifest`` (its
    module is the committed one, found in the checkout) and new entries:
    a cell ``<tag>.lfm2.<mix>`` for each of ``MIXES`` reporting what the
    Llama cell of that mix reports.  The committed manifest's metrics of
    the routed families arrive with ``write_tiny_root`` under the tiny
    ``cold-raw`` cell; they follow to the new one."""
    root = os.path.dirname(manifest)
    shutil.copy(TINY_FILE, os.path.join(root, "benchmark", "configs"))
    with open(manifest) as f:
        d = json.load(f)
    d["configs"].append({"name": "tinylfm2", "source": "tests",
                         "reduced": ["num_hidden_layers", "layer_types"],
                         "file": "benchmark/configs/tiny-lfm2.json",
                         "why": "three kinds of layer at tiny width"})
    for mix in MIXES:
        d["workloads"].append({
            "name": f"{tag}.lfm2.{mix}", "config": "tinylfm2",
            "traffic": mix, "chips": 1,
            "why": "a committed mix under the LFM2-MoE architecture"})
    for metric in d["end_to_end"] + d["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] += [f"{tag}.lfm2.{mix}" for mix in MIXES
                                    if f"{tag}.{mix}" in metric["workloads"]]
    with open(manifest, "w") as f:
        json.dump(d, f)


@pytest.mark.parametrize("mix,trace", [("cold-raw", 0), ("cold-int8", 0),
                                       ("cold-raw", 1)])
def test_a_rehearsed_lfm2_run_ends_correct(tiny_manifest, mix, trace):
    """fabricate -> ``cli.main`` -> ingest -> boot -> serve -> read-back
    -> reference, the whole harness on the tiny pattern: every blob read
    back leaf by leaf of ITS layout, the logits inside the one tolerance,
    and in the traced run this PR's two metrics and the slot counters
    read from the program's spans."""
    manifest, tag = tiny_manifest
    add_lfm2(manifest, tag)
    assert problems(Manifest(manifest)) == []
    cell = f"{tag}.lfm2.{mix}"
    proc = rehearse(manifest, cell, stub=True, trace=trace)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "read-back: 6 whole blobs" in proc.stdout
    assert ", 0 mismatches" in proc.stdout
    ref = json.loads(proc.stdout.split("reference: ", 1)[1].splitlines()[0])
    assert ref["passed"] and ref["tolerance"] == TOLERANCE
    assert 0 < ref["rel_l2"] < TOLERANCE
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    if not trace:
        assert {"setup_s", "ttft_s", "cold_start_s"} == set(line["metrics"])
        return
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # 3 requests of 16 + 8 tokens: 23 positions each, 3 routed layers, top-4
    assert got["serve.moe_slots"] == 3 * 23 * 3 * 4
    assert got["serve.moe_held_slots"] == got["serve.moe_slots"]
    # a request: per routed layer the prefill's distinct experts (4 .. 16)
    # and 4 at each of its 7 steps
    assert 3 * 3 * (4 + 28) <= got["serve.moe_touched"] <= 3 * 3 * (16 + 28)
    assert got["boot.assemble_kinds"] == 3
    assert got["decode.slow_bytes"] > 0  # gains, head norms and the bias
    assert "serve.moe_zero_slots" not in got
    assert {"wire.ttd_s", "ingest.hbm_peak_gib", "boot.first_forward_s",
            "serve.req_ms", "serve.queue_ms", "wire.buf_reused_bytes"} <= set(
        got)
