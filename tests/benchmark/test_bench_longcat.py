"""The LongCat-Flash architecture module (``benchmark/archs/
longcat_flash.py``) against the program's family (``models/longcat.py``),
at tiny width on the CPU: the layout both sides share, the plain
reference against the program's forward and its latent-cache decode, the
share adding up to the uncut model, the controls the one tolerance must
catch, the slot counters, and whole harness runs of a tiny configuration
beside a temporary manifest.

The tiny configuration (``arch_longcat/tiny-longcat.json``) is a rank's
share like the committed one: 2 of 4 heads, experts 8..15 of 24, all 24 +
8 router outputs, top-4.  Whole runs of it, 24 seeds under each of
``cold-raw`` and ``cold-int8`` (CPU counts, no device's; 3 x 23
positions; seeds 2147483659 + 1009 i): relative L2 of the program's
bfloat16 logits against the reference read 0.19-0.31% raw and 0.18-0.25%
int8 but for one int8 seed at 2.39% (a routing near-tie flipped where
the program's float32 stream and the reference part), every run
``correct`` with 0 failed — held to ``run.py``'s one 3%.
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
TINY_FILE = os.path.join(HERE, "arch_longcat", "tiny-longcat.json")
ARCH_FILE = os.path.join(REPO, "benchmark", "archs", "longcat_flash.py")
MIXES = ("cold-raw", "cold-int8")
TOLERANCE = 0.03  # run.py's, for every architecture


def tiny(**changed) -> dict:
    """The tiny configuration as ``Manifest.config`` would hand it out."""
    with open(TINY_FILE) as f:
        return dict(json.load(f), arch_file=ARCH_FILE, **changed)


SHARE = tiny()
UNCUT = tiny(num_attention_heads=4, n_routed_experts=24, expert_first=0,
             reduced={})
ARCH = archs.of(SHARE)


def program_config(config: dict, name: str, **changed):
    """The program's configuration object as the module registers it."""
    import dataclasses

    from distributed_llm_dissemination_tpu.models import longcat

    ARCH.register(config, name)
    return dataclasses.replace(longcat.CONFIGS[name], **changed)


def seeded_model(config: dict, seed: int, bias: float = 0.1):
    """``{blob: {leaf: float32 array}}`` the test makes itself: matrices
    normal at ``fan_in ** -0.5`` (activations of order one, scores that
    differ), gains 1, and a LIVE score bias, normal at ``bias`` times the
    spread of the first layer's scores."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    m = ARCH.dims(config)
    model = {}
    for b in range(m["layers"] + 1):
        model[b] = {
            name: (np.full(shape, fill, np.float32) if fill is not None else
                   (rng.standard_normal(shape)
                    * shape[-2] ** -0.5).astype(np.float32))
            for name, shape, fill in ARCH.layout(config, b)}
    toks = rng.integers(0, m["vocab"], (3, 23))
    with jax.default_matmul_precision("highest"):
        p0 = {k: jnp.asarray(v) for k, v in model[0].items()}
        h = ARCH.ref_in(jnp, m, model[m["layers"]], jnp.asarray(toks))
        _, n0 = ARCH._to_router(jnp, jax, m, p0, h)
        spread = float(np.asarray(ARCH._scores(jnp, jax, m, p0, n0)).std())
    for b in range(m["layers"]):
        model[b]["router_bias"] = (
            rng.standard_normal(m["routed"] + m["zero"])
            * spread * bias).astype(np.float32)
    return m, model, toks


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


def stacked(m: dict, model: dict) -> dict:
    """The program's parameter tree from per-blob leaves."""
    import jax
    import jax.numpy as jnp

    n = m["layers"]
    return jax.tree.map(jnp.asarray, {
        "layers": {k: np.stack([model[b][k] for b in range(n)])
                   for k in model[0]}, **model[n]})


def rel(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ------------------------------------------- (f) one layout on both sides


@pytest.mark.parametrize("name", ["tiny share", "tiny uncut",
                                  "longcat-flash-omni-d4"])
def test_the_programs_specs_equal_the_modules_layout_leaf_for_leaf(name):
    from distributed_llm_dissemination_tpu.models import quant, serde

    config = {"tiny share": SHARE, "tiny uncut": UNCUT}.get(name)
    if config is None:
        _, config = Manifest().config(name)
    cfg = program_config(config, "layout-" + name.replace(" ", "-"))
    n = fabricate.model_dims(config)["layers"]
    assert serde.head_blob_id(cfg) == n
    assert serde.layer_param_specs(cfg) == fabricate.blob_specs(config, 0)
    assert serde.head_param_specs(cfg) == fabricate.blob_specs(config, n)
    for codec in fabricate.CODECS:
        for b in (0, n):
            assert quant.blob_nbytes_codec(cfg, b, codec) == (
                fabricate.blob_nbytes(config, b, codec))
    assert cfg.layer_nbytes() == fabricate.blob_nbytes(config, 0)


def test_the_committed_configuration_is_the_published_one_cut_to_a_share():
    """Every width as published, exactly four counts cut (each with its
    published value, its value here and why), what was assumed, and the
    64-chip deployment; a layer, the head and a replica in bytes."""
    entry, config = Manifest().config("longcat-flash-omni-d4")
    assert {k: config[k] for k in (
        "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "moe_topk", "zero_expert_num",
        "routed_scaling_factor", "rope_theta", "rms_norm_eps",
        "mla_scale_q_lora", "mla_scale_kv_lora", "zero_expert_type",
        "max_position_embeddings", "attention_bias", "attention_method")} == {
        "hidden_size": 6144, "ffn_hidden_size": 12288,
        "expert_ffn_hidden_size": 2048, "q_lora_rank": 1536,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "moe_topk": 12, "zero_expert_num": 256,
        "routed_scaling_factor": 6, "rope_theta": 10000000,
        "rms_norm_eps": 1e-05, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "zero_expert_type": "identity",
        "max_position_embeddings": 131072, "attention_bias": False,
        "attention_method": "MLA"}
    cut = {"num_layers": (28, 4), "n_routed_experts": (512, 8),
           "num_attention_heads": (64, 8), "vocab_size": (131072, 16384)}
    assert list(config["reduced"]) == entry["reduced"] == list(cut)
    for key, (published, here) in cut.items():
        rec = config["reduced"][key]
        assert (rec["published"], rec["here"], config[key]) == (
            published, here, here) and len(rec["why"]) > 40
    assert set(config["assumed"]) == {"rotary", "norm_topk_prob", "mla_scale",
                                      "router_dtype", "router_bias"}
    assert "64 chips share each layer" in config["deployment"]
    assert entry["source"] == config["source"]
    m = fabricate.model_dims(config)
    assert (m["routed"], m["zero"], m["held"], m["first"]) == (512, 256, 8, 0)
    assert (fabricate.blob_nbytes(config, 0), fabricate.blob_nbytes(config, 4),
            fabricate.model_nbytes(config)) == (
        1_610_147_328, 402_665_472, 6_843_254_784)
    fills = {name: fill for name, _, fill in ARCH.layout(config, 0)}
    assert len(fills) == 29 and fills["router_bias"] == 0.0
    assert sorted(k for k, v in fills.items() if v == 1.0) == sorted(
        f"{g}_{i}" for g in ("ln_in", "q_norm", "kv_norm", "ln_post")
        for i in (0, 1))


def test_the_module_registers_the_share_it_was_given():
    cfg = program_config(SHARE, "share-told")
    assert (cfg.n_heads, cfg.heads_held, cfg.n_experts, cfg.n_zero,
            cfg.experts_held, cfg.expert_first, cfg.top_k) == (
        4, 2, 24, 8, 8, 8, 4)
    whole = program_config(UNCUT, "uncut-told")
    assert (whole.heads_held, whole.experts_held, whole.expert_first) == (
        4, 24, 0)


# ------------------------- (b) the program against the reference, float32


@pytest.mark.parametrize("codec", fabricate.CODECS)
def test_the_reference_agrees_with_the_programs_forward(codec):
    """Two implementations that share no code, float32 both, the same
    blobs of the harness's own fill (gains 1, score bias 0): they agree to
    float32 rounding."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models.llama import forward

    cfg = program_config(SHARE, "ref-" + codec, dtype=jnp.float32)
    m = fabricate.model_dims(SHARE)
    n = m["layers"]
    blobs = {b: fabricate.make_blob(SHARE, b, 7, codec) for b in range(n + 1)}
    model = {b: decoded(SHARE, b, blobs[b], codec) for b in blobs}
    toks = np.asarray(fabricate.make_prompts(SHARE, 7, 3, 16))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(stacked(m, model), jnp.asarray(toks), cfg))
    got = reference.logits(SHARE, toks, lambda b: fabricate.blob_leaves(
        SHARE, b, blobs[b], codec))
    assert got.shape == (3, 16, m["vocab"])
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("config", [SHARE, UNCUT], ids=["share", "uncut"])
@pytest.mark.parametrize("seed", [0, 1])
def test_prefill_and_decode_through_the_latent_cache_equal_the_reference(
        config, seed):
    """A live score bias; the program's full forward within 1e-5 of the
    reference, then its prefill of 15 positions and 8 decode steps through
    the latent cache against the full forward's logits at EVERY
    position."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models import generate, llama

    cfg = program_config(config, f"cache-{seed}", dtype=jnp.float32)
    m, model, toks = seeded_model(config, seed)
    params = stacked(m, model)
    want = ref_logits(m, model, toks)
    with jax.default_matmul_precision("highest"):
        full = np.asarray(llama.forward(params, jnp.asarray(toks), cfg))
        assert rel(full, want) < 1e-5
        cache = generate.init_cache(cfg, 3, 23)
        assert sorted(cache) == ["ckv", "kr"]  # latent: no per-head K or V
        got, cache, _ = generate._prefill_fn(cfg, 15)(
            params, jnp.asarray(toks[:, :15]), cache)
        errs = [np.abs(np.asarray(got) - full[:, 14]).max()]
        for t in range(15, 23):
            got, cache, _ = generate._forward_with_cache(
                params, jnp.asarray(toks[:, t:t + 1]), jnp.asarray([t]),
                cache, cfg)
            errs.append(np.abs(np.asarray(got) - full[:, t]).max())
    assert len(errs) == 9 and max(errs) < 1e-5 * np.abs(full).max() * 10


# --------------------------------------------- (c) the share adds up


def _first_half(m, model, toks):
    """The uncut reference's hidden state into layer 0's first attention
    sub-block (normed) and into its routed block."""
    import jax
    import jax.numpy as jnp

    p = {k: jnp.asarray(v) for k, v in model[0].items()}
    h = ARCH.ref_in(jnp, m, model[m["layers"]], jnp.asarray(toks))
    xn = h / jnp.sqrt(jnp.mean(h * h, -1, keepdims=True) + m["eps"])
    _, n0 = ARCH._to_router(jnp, jax, m, p, h)
    return p, xn * p["ln_in_0"], n0


@pytest.mark.parametrize("sub", [0, 1])
def test_the_head_shares_add_up_to_the_uncut_attention(sub):
    """Over all head shares (4 heads, 2 a share) the program's attention
    sub-block output, each share given its own heads' columns of ``wq_b``
    / ``wkv_b`` and rows of ``wo``, equals the uncut reference's."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models import longcat

    m, model, toks = seeded_model(UNCUT, 3)
    part = program_config(SHARE, "heads", dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        p, xn, _ = _first_half(m, model, toks)
        want = np.asarray(ARCH._mla(jnp, jax, m, p, sub, xn))
        pos = jnp.arange(toks.shape[1])
        mask = jnp.where(pos[:, None] >= pos[None, :], 0.0, -jnp.inf)
        total = 0.0
        for share in range(m["h"] // part.heads_held):
            heads = slice(share * part.heads_held,
                          (share + 1) * part.heads_held)
            cols = lambda w, per: w.reshape(  # noqa: E731
                w.shape[0], m["h"], per)[:, heads].reshape(w.shape[0], -1)
            mine = dict(p)
            mine[f"wq_b_{sub}"] = cols(p[f"wq_b_{sub}"], m["nope"] + m["rope"])
            mine[f"wkv_b_{sub}"] = cols(p[f"wkv_b_{sub}"], m["nope"] + m["v"])
            mine[f"wo_{sub}"] = p[f"wo_{sub}"].reshape(
                m["h"], m["v"], -1)[heads].reshape(-1, m["d"])
            q, ckv, kr = longcat._mla_project(mine, sub, xn, pos, part)
            total = total + np.asarray(longcat._mla_attend(
                mine, sub, q, ckv, kr, mask, part))
    assert rel(total, want) < 1e-5


def test_the_expert_shares_add_up_with_the_identity_part_counted_once():
    """Over all expert shares (24 experts, 8 a share) the program's routed
    part, every share routing over all 32 outputs, adds up to the uncut
    reference's routed block once the identity experts' part — which
    every share computes alike — is counted once; so do the counters."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models import longcat

    m, model, toks = seeded_model(UNCUT, 4)
    part = program_config(SHARE, "experts", dtype=jnp.float32)
    shares = m["routed"] // part.experts_held
    with jax.default_matmul_precision("highest"):
        p, _, n0 = _first_half(m, model, toks)
        want = np.asarray(ARCH._moe(jnp, jax, m, p, n0))
        pick, w = ARCH._route(jnp, jax, m, p, ARCH._scores(jnp, jax, m, p, n0))
        alike = np.asarray(ARCH._identity_part(jnp, m, pick, w, n0))
        total, counted = 0.0, []
        for share in range(shares):
            cfg = dataclasses.replace(
                part, expert_first=share * part.experts_held)
            held = slice(cfg.expert_first, cfg.expert_first + cfg.experts_held)
            mine = dict(p, ew1=p["ew1"][held], ew3=p["ew3"][held],
                        ew2=p["ew2"][held])
            idx, wts = longcat.route(mine, n0, cfg)
            out, c = longcat.routed_part(mine, n0, idx, wts, cfg)
            total = total + np.asarray(out)
            counted.append({k: int(v) for k, v in c.items()})
    assert np.abs(alike).max() > 0.01  # the identity part is live
    assert rel(total - (shares - 1) * alike, want) < 1e-5
    slots = pick.size
    assert all(c["moe_slots"] == slots for c in counted)
    assert len({c["moe_zero"] for c in counted}) == 1  # every chip alike
    assert sum(c["moe_held"] for c in counted) + counted[0]["moe_zero"] == slots


# ------------------------ (d) controls that the one tolerance must catch
#
# Each control leaves one piece of the published mathematics out of the
# reference and is held against the faithful reference on the same
# arrays (normal weights, a live score bias at a tenth of the scores'
# spread, 3 x 23 positions, the uncut tiny model, seeds 0-5).  Relative
# L2 of the logits, beside the tolerance of 3%:
#
#   score bias dropped            8.8 - 15.6%
#   sqrt(d / q_lora_rank) dropped  49 - 65%
#   sqrt(d / kv_lora_rank) dropped 91 - 102%
#   identity experts dropped       35 - 50%
#   top-k renormalised             47 - 57%
#
# and one that the tolerance does NOT reliably catch, so it is no control
# and is pinned as what it is (the last test of this section):
#
#   router in bfloat16            0.6 - 18%, median 2.6% (12 seeds; with
#                                 96 + 32 outputs and top-12: 0.3 - 2.0%)


def _bias_dropped(m, model):
    return m, {b: (dict(p, router_bias=np.zeros_like(p["router_bias"]))
                   if "router_bias" in p else p) for b, p in model.items()}


def _patched(name, fn):
    def control(m, model, monkeypatch):
        monkeypatch.setattr(ARCH, name, fn(getattr(ARCH, name)))
        return m, model
    return control


def _renormalised(route):
    def renorm(jnp, jax, m, p, s):
        pick, w = route(jnp, jax, m, p, s)
        return pick, w / w.sum(-1, keepdims=True) * m["route_scale"]
    return renorm


def _bfloat16_scores(_):
    def scores(jnp, jax, m, p, x):
        return jax.nn.softmax(
            x.astype(jnp.bfloat16) @ p["router"].astype(jnp.bfloat16),
            axis=-1).astype(jnp.float32)
    return scores


CONTROLS = {
    "score bias dropped": lambda m, model, mp: _bias_dropped(m, model),
    "q scale dropped": lambda m, model, mp: (dict(m, q_scale=1.0), model),
    "kv scale dropped": lambda m, model, mp: (dict(m, kv_scale=1.0), model),
    "identity experts dropped": _patched(
        "_identity_part", lambda _: lambda jnp, m, pick, w, x: 0.0 * x),
    "top-k renormalised": _patched("_route", _renormalised),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_piece_of_the_mathematics_left_out_fails_the_tolerance(
        control, seed, monkeypatch):
    m, model, toks = seeded_model(UNCUT, seed)
    want = ref_logits(m, model, toks)
    got = ref_logits(*CONTROLS[control](m, model, monkeypatch), toks)
    assert rel(got, want) > TOLERANCE, control


def test_a_router_in_bfloat16_moves_picks_but_not_reliably_past_the_tolerance(
        monkeypatch):
    """No control: rounding the router's operands and its softmax to
    bfloat16 flips near-tied picks (the slot counters of the next section
    see that exactly) but moves the logits by about the tolerance, under
    it on most seeds.  ``correct`` alone therefore does not hold a
    program to a float32 router; PERF.md section 7 says so."""
    import jax
    import jax.numpy as jnp

    readings, flipped = [], 0
    for seed in range(6):
        m, model, toks = seeded_model(UNCUT, seed)
        want = ref_logits(m, model, toks)
        p = {k: jnp.asarray(v) for k, v in model[0].items()}
        h = ARCH.ref_in(jnp, m, model[m["layers"]], jnp.asarray(toks))
        exact = np.sort(np.asarray(ARCH.picks(jnp, jax, m, p, h)), -1)
        with monkeypatch.context() as mp:
            mp.setattr(ARCH, "_scores", _bfloat16_scores(None))
            readings.append(rel(ref_logits(m, model, toks), want))
            rounded = np.sort(np.asarray(ARCH.picks(jnp, jax, m, p, h)), -1)
        flipped += int((exact != rounded).any(-1).sum())
    assert flipped > 0
    assert 0.001 < min(readings) and np.median(readings) < 2 * TOLERANCE


# --------------------------------------------------- (e) the slot counters


@pytest.mark.parametrize("config", [SHARE, UNCUT], ids=["share", "uncut"])
def test_the_programs_slot_counts_equal_the_references_own_picks(config):
    """A float32 program run (prefill of 16, 7 decode steps, as a served
    request) counts ``moe_slots`` / ``moe_held`` / ``moe_zero``; the
    reference's own picks on the same 23 positions, counted here, give
    the same three numbers exactly."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models import generate

    cfg = program_config(config, "counted", dtype=jnp.float32)
    m, model, toks = seeded_model(config, 5)
    params = stacked(m, model)
    with jax.default_matmul_precision("highest"):
        served, counted = generate.generate_counted(
            params, jnp.asarray(toks[:, :16]), cfg, 8)
        seq = np.concatenate([toks[:, :16], np.asarray(served)], axis=1)
        head = {k: jnp.asarray(v) for k, v in model[m["layers"]].items()}
        h = ARCH.ref_in(jnp, m, head, jnp.asarray(seq[:, :-1]))
        want = {"moe_slots": 0, "moe_held": 0, "moe_zero": 0}
        for b in range(m["layers"]):
            p = {k: jnp.asarray(v) for k, v in model[b].items()}
            pick = np.asarray(ARCH.picks(jnp, jax, m, p, h))
            want["moe_slots"] += pick.size
            want["moe_held"] += int(((pick >= m["first"]) & (
                pick < m["first"] + m["held"])).sum())
            want["moe_zero"] += int((pick >= m["routed"]).sum())
            h = ARCH.ref_layer(jnp, jax, m, p, h)
    assert {k: int(v) for k, v in counted.items()} == want
    assert want["moe_slots"] == 3 * 23 * m["layers"] * m["top_k"]
    assert 0 < want["moe_held"] and 0 < want["moe_zero"]


# ------------------------------ (a) whole harness runs, as files and entries


def add_longcat(manifest: str, tag: str) -> None:
    """The tiny configuration as a NEW FILE beside ``manifest`` (its
    module is the committed one, found in the checkout) and new entries:
    a cell ``<tag>.longcat.<mix>`` for each of ``MIXES`` reporting what
    the Llama cell of that mix reports.  The committed manifest's four
    metrics of this PR arrive with ``write_tiny_root`` under the tiny
    ``cold-raw`` cell; they follow to the new one."""
    root = os.path.dirname(manifest)
    shutil.copy(TINY_FILE, os.path.join(root, "benchmark", "configs"))
    with open(manifest) as f:
        d = json.load(f)
    d["configs"].append({"name": "tinylongcat", "source": "tests",
                         "reduced": ["n_routed_experts",
                                     "num_attention_heads"],
                         "file": "benchmark/configs/tiny-longcat.json",
                         "why": "a rank's share at tiny width"})
    for mix in MIXES:
        d["workloads"].append({
            "name": f"{tag}.longcat.{mix}", "config": "tinylongcat",
            "traffic": mix, "chips": 1,
            "why": "a committed mix under the LongCat-Flash architecture"})
    for metric in d["end_to_end"] + d["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] += [f"{tag}.longcat.{mix}" for mix in MIXES
                                    if f"{tag}.{mix}" in metric["workloads"]]
    with open(manifest, "w") as f:
        json.dump(d, f)


@pytest.mark.parametrize("mix,trace", [("cold-raw", 0), ("cold-int8", 0),
                                       ("cold-raw", 1)])
def test_a_rehearsed_longcat_run_ends_correct(tiny_manifest, mix, trace):
    """fabricate -> ``cli.main`` -> ingest -> boot -> serve -> read-back
    -> reference, the whole harness on the tiny share: every blob read
    back leaf by leaf of the 29-leaf layout, the logits inside the one
    tolerance, and in the traced run this PR's four metrics read from the
    program's spans."""
    manifest, tag = tiny_manifest
    add_longcat(manifest, tag)
    assert problems(Manifest(manifest)) == []
    cell = f"{tag}.longcat.{mix}"
    proc = rehearse(manifest, cell, stub=True, trace=trace)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "read-back: 5 whole blobs" in proc.stdout
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
    # 3 requests of 16 + 8 tokens: 23 positions each, 4 layers, top-4
    assert got["serve.moe_slots"] == 3 * 23 * 4 * 4
    assert 0 < got["serve.moe_held_slots"] < got["serve.moe_slots"]
    assert 0 < got["serve.moe_zero_slots"] < got["serve.moe_slots"]
    assert got["decode.slow_bytes"] >= 0
    assert {"wire.ttd_s", "ingest.hbm_peak_gib", "boot.first_forward_s",
            "serve.req_ms", "serve.queue_ms"} <= set(got)
