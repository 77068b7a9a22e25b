"""The JoyAI-LLM-Flash architecture module
(``benchmark/archs/joyai_llm_flash.py``) against the program's family
(``models/joyai.py``), at tiny width on the CPU: the layout both sides
share blob by blob, the plain reference against the program's forward
and the module's logits (``ref_mtp``), the four expert shares against
the uncut layer with the shared expert counted once, the controls the
one tolerance must catch, the slot and draft counters, the committed
configuration against the published one, and whole harness runs of a
tiny configuration beside a temporary manifest.

The tiny configuration (``arch_joyai/tiny-joyai.json``) has the committed
one's shape: a leading dense layer, two routed layers of 16 experts
(top-4, one shared expert) and the module's blob, four kinds of blob.
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
TINY_FILE = os.path.join(HERE, "arch_joyai", "tiny-joyai.json")
ARCH_FILE = os.path.join(REPO, "benchmark", "archs", "joyai_llm_flash.py")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOLERANCE = 0.03  # run.py's, for every architecture
# The published configuration (config.json of jdopensource/JoyAI-LLM-Flash
# as the catalog beside the model-configs guide holds it), but for the
# two keys of the cut.
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
    "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}
GAINS = ("attn_norm", "q_norm", "kv_norm", "ffn_norm", "ln_f", "enorm",
         "hnorm", "head_norm")


def tiny(**changed) -> dict:
    """The tiny configuration as ``Manifest.config`` would hand it out."""
    with open(TINY_FILE) as f:
        return dict(json.load(f), arch_file=ARCH_FILE, **changed)


def tiny_share(first: int) -> dict:
    """One rank of four of the tiny configuration: experts ``first ..
    first + 4`` of 16, everything else whole."""
    return tiny(n_routed_experts=4, expert_first=first,
                reduced={"n_routed_experts": {"published": 16, "here": 4}})


TINY = tiny()
ARCH = archs.of(TINY)


def program_config(config: dict, name: str, **changed):
    """The program's configuration object as the module registers it."""
    import dataclasses

    from distributed_llm_dissemination_tpu.models import joyai

    ARCH.register(config, name)
    return dataclasses.replace(joyai.CONFIGS[name], **changed)


def seeded_model(config: dict, seed: int):
    """``{blob: {leaf: float32 array}}`` the test makes itself: matrices
    normal at ``fan_in ** -0.5``, gains 1 — but ``hnorm``'s, uniform in
    0.5 .. 1.5 (its input has an RMS of one already, so with a gain of
    exactly 1 the norm is an identity that no control could miss) — and
    a LIVE selection bias, normal at 0.05, a quarter of the sigmoid
    scores' spread."""
    rng = np.random.default_rng(seed)
    m = ARCH.dims(config)
    model = {}
    for b in range(m["layers"] + 1):
        model[b] = {}
        for name, shape, fill in ARCH.layout(config, b):
            if name == "gate_bias":
                leaf = rng.standard_normal(shape) * 0.05
            elif name == "hnorm":
                leaf = rng.uniform(0.5, 1.5, shape)
            elif fill is not None:
                leaf = np.full(shape, fill)
            else:
                leaf = rng.standard_normal(shape) * shape[-2] ** -0.5
            model[b][name] = leaf.astype(np.float32)
    return m, model, rng.integers(0, m["vocab"], (3, 23))


def ref_hidden(m: dict, model: dict, toks):
    """The stack's last hidden state by the module's reference, block by
    block, on arrays as they are (the module's blob is handed to
    ``ref_layer`` like every layer blob, and changes nothing)."""
    import jax
    import jax.numpy as jnp

    n = m["layers"]
    head = {k: jnp.asarray(v) for k, v in model[n].items()}
    h = ARCH.ref_in(jnp, m, head, jnp.asarray(toks))
    for b in range(n):
        h = ARCH.ref_layer(
            jnp, jax, m, {k: jnp.asarray(v) for k, v in model[b].items()}, h)
    return head, h


def ref_logits(m: dict, model: dict, toks) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        head, h = ref_hidden(m, model, toks)
        return np.asarray(ARCH.ref_out(jnp, m, head, h))


def ref_module_logits(m: dict, model: dict, toks) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        head, h = ref_hidden(m, model, toks)
        p = {k: jnp.asarray(v) for k, v in model[m["layers"] - 1].items()}
        return np.asarray(ARCH.ref_mtp(jnp, jax, m, head, p, h,
                                       jnp.asarray(toks)))


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


@pytest.mark.parametrize("name", ["tiny", "share", "joyai-llm-flash-d6"])
def test_the_programs_specs_equal_the_modules_layout_for_every_blob(name):
    from distributed_llm_dissemination_tpu.models import quant, serde

    config = {"tiny": TINY, "share": tiny_share(8)}.get(name) or (
        Manifest().config(name)[1])
    cfg = program_config(config, "layout-" + name)
    n = fabricate.model_dims(config)["layers"]
    assert serde.head_blob_id(cfg) == n == cfg.n_layers
    for b in range(n + 1):
        assert serde.blob_specs(cfg, b) == fabricate.blob_specs(config, b)
        for codec in fabricate.CODECS:
            assert quant.blob_nbytes_codec(cfg, b, codec) == (
                fabricate.blob_nbytes(config, b, codec))
    kinds = {serde.blob_kind(cfg, b): fabricate.blob_nbytes(config, b)
             for b in range(n)}
    assert sorted(kinds) == ["dense", "moe", "mtp"]
    assert len(set(kinds.values())) == 3
    assert serde.blob_kind(cfg, n - 1) == "mtp"  # numbered last


def test_the_committed_configuration_is_the_published_one_cut_in_two_keys():
    """Every published key as published but for ``num_hidden_layers`` and
    ``n_routed_experts`` (each with its published value, its value here
    and why); what was assumed; the deployment; the bytes of each kind of
    blob, of the head and of the replica recounted from ``layout``."""
    entry, config = Manifest().config("joyai-llm-flash-d6")
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    cut = {"num_hidden_layers": (40, 6), "n_routed_experts": (256, 64)}
    assert list(config["reduced"]) == entry["reduced"] == list(cut)
    for key, (published, here) in cut.items():
        rec = config["reduced"][key]
        assert (rec["published"], rec["here"], config[key]) == (
            published, here, here) and len(rec["why"]) > 40
    extra = set(config) - set(PUBLISHED) - set(cut)
    assert extra == {"arch", "arch_file", "source", "reduced", "assumed",
                     "deployment", "expert_first"}
    assert config["expert_first"] == 0
    assert set(config["assumed"]) == {
        "mtp_shared_embedding_and_head", "mtp_input", "router_dtype",
        "norm_gains", "rotary", "renormalisation"}
    assert "one rank of four that share each layer" in config["deployment"]
    assert "first of seven pipeline stages" in config["deployment"]
    assert entry["source"] == config["source"]
    if os.path.exists(CATALOG):  # the catalog's own row, where it is
        with open(CATALOG) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "JoyAI-LLM-Flash"]
        assert row["source_url"] == config["source"]
        assert {k: v for k, v in row["config"].items()
                if k not in cut} == PUBLISHED
        assert (row["config"]["num_hidden_layers"],
                row["config"]["n_routed_experts"]) == (40, 256)
    sizes = [fabricate.blob_nbytes(config, b) for b in range(8)]
    assert sizes == [140_783_616] + [667_169_280] * 5 + [
        683_958_784, 1_059_065_856]
    assert fabricate.model_nbytes(config) == 5_219_654_656
    m = fabricate.model_dims(config)
    assert (m["layers"], m["main"], m["mtp"], m["routed"], m["held"],
            m["top_k"], m["vocab"], m["h"]) == (7, 6, 1, 256, 64, 8,
                                                129280, 32)
    fills = {b: {n: f for n, _, f in ARCH.layout(config, b)}
             for b in (0, 1, 6, 7)}
    assert [len(fills[b]) for b in (0, 1, 6, 7)] == [12, 17, 21, 3]
    assert fills[1]["gate_bias"] is None and fills[1]["gate"] is None
    assert sorted(k for k, v in fills[6].items() if v == 1.0) == sorted(
        g for g in GAINS if g != "ln_f")
    # the module's embedding and head are the main model's: not its blob's
    assert not {"embed", "lm_head"} & set(fills[6])
    assert set(fills[7]) == {"embed", "ln_f", "lm_head"}


def test_the_module_registers_what_it_was_given():
    cfg = program_config(tiny_share(8), "share-told")
    assert (cfg.n_main, cfg.n_mtp, cfg.n_layers, cfg.n_dense) == (3, 1, 4, 1)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_first, cfg.top_k,
            cfg.d_shared, cfg.route_scale) == (16, 4, 8, 4, 32, 2.5)
    assert (cfg.q_rank, cfg.kv_rank, cfg.nope_dim, cfg.rope_dim,
            cfg.v_dim) == (24, 16, 8, 4, 8)
    bare = program_config(tiny(num_nextn_predict_layers=0), "no-module")
    assert bare.n_layers == 3 and ARCH.dims(
        tiny(num_nextn_predict_layers=0))["layers"] == 3
    for differs in ({"scoring_func": "softmax"}, {"norm_topk_prob": False},
                    {"n_group": 8}, {"tie_word_embeddings": True},
                    {"rope_scaling": {"type": "yarn"}},
                    {"num_nextn_predict_layers": 2}):
        with pytest.raises(SystemExit, match="this config differs"):
            ARCH.register(tiny(**differs), "differs")


# ------------------------- (b) the program against the reference, float32


@pytest.mark.parametrize("share", ["uncut", "share"])
@pytest.mark.parametrize("codec", fabricate.CODECS)
def test_the_reference_agrees_with_the_programs_forward(codec, share):
    """Two implementations that share no code, float32 both, the same
    blobs of the harness's own fill (gains 1, a seeded selection bias),
    through the harness's own loop over EVERY layer blob, the module's
    included: they agree to float32 rounding, whole and as one rank of
    four."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models.llama import forward

    config = TINY if share == "uncut" else tiny_share(4)
    cfg = program_config(config, f"ref-{codec}-{share}", dtype=jnp.float32)
    m = fabricate.model_dims(config)
    n = m["layers"]
    blobs = {b: fabricate.make_blob(config, b, 7, codec)
             for b in range(n + 1)}
    model = {b: decoded(config, b, blobs[b], codec) for b in blobs}
    toks = np.asarray(fabricate.make_prompts(config, 7, 3, 16))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(stacked(cfg, m, model), jnp.asarray(toks),
                                  cfg))
    got = reference.logits(config, toks, lambda b: fabricate.blob_leaves(
        config, b, blobs[b], codec))
    assert got.shape == (3, 16, m["vocab"])
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("share", ["uncut", "share"])
@pytest.mark.parametrize("seed", [0, 1])
def test_ref_mtp_agrees_with_the_programs_module_logits(seed, share):
    """The module by the reference's equations against
    ``models.joyai.mtp_forward`` (embedding half first, the main model's
    output after its final norm, the main model's embedding and head):
    float32 rounding apart; and through ``program_mtp``, as the chip
    comparison calls it, on bfloat16 weights against the reference on
    the same rounded weights."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models import joyai

    config = TINY if share == "uncut" else tiny_share(12)
    cfg = program_config(config, f"mtp-{seed}-{share}", dtype=jnp.float32)
    m, model, toks = seeded_model(config, seed)
    want = ref_module_logits(m, model, toks)
    assert want.shape == (3, 22, m["vocab"])
    params = stacked(cfg, m, model)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(joyai.mtp_forward(params, jnp.asarray(toks), cfg))
    assert rel(got, want) < 1e-5
    # the module's logits are no copy of the main model's
    assert rel(want, ref_logits(m, model, toks)[:, 1:]) > 0.5

    class Boot:
        pass

    boot = Boot()
    boot.params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    rounded = {b: {k: np.asarray(jnp.asarray(v, jnp.bfloat16), np.float32)
                   for k, v in p.items()} for b, p in model.items()}
    ARCH.register(config, f"mtp-boot-{seed}-{share}")
    served = np.asarray(ARCH.program_mtp(boot, jnp.asarray(toks)))
    assert served.shape == want.shape
    assert rel(served, ref_module_logits(m, rounded, toks)) < 1e-3


@pytest.mark.parametrize("seed", [0, 1])
def test_prefill_and_decode_through_the_latent_cache_equal_the_reference(
        seed):
    """The program's full forward within 1e-5 of the reference, then its
    prefill of 15 positions and 8 one-token steps through the latent
    cache against the full forward's LOGITS at every position."""
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
            "dense": ["ckv", "kr"], "moe": ["ckv", "kr"],
            "mtp": ["ckv", "kr"]}
        got, cache, _ = generate._prefill_fn(cfg, 15)(
            params, jnp.asarray(toks[:, :15]), cache)
        errs = [np.abs(np.asarray(got) - full[:, 14]).max()]
        for t in range(15, 23):
            got, cache, _ = generate._forward_with_cache(
                params, jnp.asarray(toks[:, t:t + 1]), jnp.asarray([t]),
                cache, cfg)
            errs.append(np.abs(np.asarray(got) - full[:, t]).max())
    assert len(errs) == 9 and max(errs) < 1e-5 * np.abs(full).max() * 10


# ------------------ (c) four shares and one shared expert: the uncut layer


@pytest.mark.parametrize("blob", [1, 3], ids=["routed-layer", "module"])
@pytest.mark.parametrize("seed", [0, 1])
def test_the_four_expert_shares_add_up_to_the_uncut_reference(seed, blob):
    """``model-configs`` guide, section 4: the parts of the result that
    the four ranks' held experts give (``expert_first`` 0, 4, 8, 12 of
    16), with what every rank computes alike — the shared expert, and the
    attention — counted ONCE, are what the uncut reference gives for the
    whole block; and a rank alone is not."""
    import jax
    import jax.numpy as jnp

    m, model, _ = seeded_model(TINY, seed)
    p = {k: jnp.asarray(v) for k, v in model[blob].items()}
    h = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (3, 23, m["d"])).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ARCH._block(jnp, jax, m, p, h))
        after, n = ARCH._after_attention(jnp, jax, m, p, h)
        shared = np.asarray(ARCH._shared(jnp, jax, m, p, n))
        total = np.asarray(after) + shared  # counted once
        alone = []
        for first in (0, 4, 8, 12):
            config = tiny_share(first)
            ms = ARCH.dims(config)
            assert (ms["held"], ms["first"], ms["routed"]) == (4, first, 16)
            mine = dict(p, **{k: p[k][first:first + 4]
                              for k in ("ew1", "ew3", "ew2")})
            assert [tuple(mine[n].shape) for n, _, _ in ARCH.layout(
                config, blob)] == [s for _, s, _ in ARCH.layout(config, blob)]
            rank = np.asarray(ARCH._block(jnp, jax, ms, mine, h))
            part = rank - np.asarray(after) - shared
            alone.append(rel(rank, whole))
            total = total + part
    assert rel(total, whole) < 1e-5
    assert min(alone) > TOLERANCE  # one rank's partial result is no layer
    # counting the shared expert four times is caught
    assert rel(total + 3 * shared, whole) > TOLERANCE


# ------------------------ (d) controls that the one tolerance must catch
#
# Each control leaves one piece of the published mathematics out of the
# reference and is held against the faithful reference on the same arrays
# (normal weights, a live selection bias, hnorm gains 0.5 .. 1.5, 3 x 23
# positions, the tiny model, seeds 0-2).  The first four are read on the
# main logits, the last two on the module's (they change nothing else).
# Relative L2 beside the tolerance of 3% (readings of this file's own
# runs on the CPU, float32, seeds 0-5):
#
#   no shared expert                       52 - 62%
#   no renormalisation                     71 - 79%
#   no routed scaling factor               38 - 48%
#   selection bias dropped                 29 - 40%
#   halves swapped                        138 - 141%
#   hnorm left out                         31 - 35%


def _patched(name, fn):
    def control(m, model, monkeypatch):
        monkeypatch.setattr(ARCH, name, fn(getattr(ARCH, name)))
        return m, model
    return control


def _bias_dropped(m, model, monkeypatch):
    return m, {b: (dict(p, gate_bias=np.zeros_like(p["gate_bias"]))
                   if "gate_bias" in p else p) for b, p in model.items()}


CONTROLS = {
    "no shared expert": (ref_logits, _patched(
        "_shared", lambda _: lambda jnp, jax, m, p, x: 0.0 * x)),
    "no renormalisation": (ref_logits, lambda m, model, mp: (
        dict(m, norm_topk=False), model)),
    "no routed scaling factor": (ref_logits, lambda m, model, mp: (
        dict(m, route_scale=1.0), model)),
    "selection bias dropped": (ref_logits, _bias_dropped),
    "halves swapped": (ref_module_logits, _patched(
        "_halves", lambda real: lambda jnp, e, hn: real(jnp, hn, e))),
    "hnorm left out": (ref_module_logits, _patched(
        "_hnorm", lambda _: lambda jnp, m, p, hn: hn)),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_piece_of_the_mathematics_left_out_fails_the_tolerance(
        control, seed, monkeypatch):
    read, change = CONTROLS[control]
    m, model, toks = seeded_model(TINY, seed)
    want = read(m, model, toks)
    got = read(*change(m, model, monkeypatch), toks)
    assert rel(got, want) > TOLERANCE, (control, rel(got, want))


# ----------------------------------------- (e) the slot and draft counters


def test_the_programs_slot_and_draft_counts_equal_the_references_own():
    """A float32 program run as a served request (one sequence, prefill
    of 16, draft and verify to 12 tokens at a vocabulary of 16, so that
    drafts hold and fail) counts its steps, drafts and acceptances; the
    reference's own logits on the served sequence, walked here the way a
    draft-and-verify decode walks them, give the same three numbers, and
    the slots follow from the steps.  The prefill's ``moe_held`` and
    ``moe_touched`` at one rank of four equal the reference's picks."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models import generate

    config = tiny_share(4)
    config["vocab_size"] = 16
    cfg = program_config(config, "counted", dtype=jnp.float32)
    m, model, toks = seeded_model(config, 3)
    params = stacked(cfg, m, model)
    p_len, new = 16, 12
    seen = {"steps": 0, "accepted": 0}
    for row in range(3):
        prompt = toks[row:row + 1, :p_len]
        with jax.default_matmul_precision("highest"):
            served, counted = generate.generate_counted(
                params, jnp.asarray(prompt), cfg, new)
            seq = np.concatenate([prompt, np.asarray(served)], axis=1)
            main = ref_logits(m, model, seq)
            module = ref_module_logits(m, model, seq)
        # served = the reference's argmax wherever it is not a near-tie
        top2 = np.sort(main[0, p_len - 1:-1], axis=-1)[:, -2:]
        clear = top2[:, 1] - top2[:, 0] > 1e-4
        assert (main[0, p_len - 1:-1].argmax(-1) == seq[0, p_len:])[
            clear].all()
        # walk: n tokens stand; the draft for token n is the module's
        # argmax at position p + n - 2
        n, steps, accepted = 1, 0, 0
        while n < new:
            guess = module[0, p_len + n - 2].argmax()
            ok = bool(guess == seq[0, p_len + n]) and n + 1 < new
            steps, accepted, n = steps + 1, accepted + ok, n + 1 + ok
        got = {k: int(v) for k, v in counted.items()}
        assert (got["decode_steps"], got["mtp_drafted"],
                got["mtp_accepted"]) == (steps, steps, accepted)
        assert steps + accepted + 1 == new
        routed = m["main"] - m["dense"] + m["mtp"]
        assert got["moe_slots"] == (p_len + 2 * steps) * routed * m["top_k"]
        assert 0 < got["moe_held"] < got["moe_slots"]  # 4 of 16 experts
        seen["steps"] += steps
        seen["accepted"] += accepted
    assert 0 < seen["accepted"] < seen["steps"]
    # the prefill alone, against the reference's picks layer by layer
    with jax.default_matmul_precision("highest"):
        prompt = toks[:1, :p_len]
        cache = generate.init_cache(cfg, 1, p_len + 1)
        first, _, _, counted = generate._draft_prefill_fn(cfg, p_len)(
            params, jnp.asarray(prompt), cache)
        head, h = {k: jnp.asarray(v) for k, v in model[m["layers"]].items()}, None
        h = ARCH.ref_in(jnp, m, head, jnp.asarray(prompt))
        want = {"moe_slots": 0, "moe_held": 0, "moe_touched": 0}

        def count(pick):
            pick = np.asarray(pick)
            here = (pick >= m["first"]) & (pick < m["first"] + m["held"])
            want["moe_slots"] += pick.size
            want["moe_held"] += int(here.sum())
            want["moe_touched"] += len(np.unique(pick[here]))

        for b in range(m["main"]):
            p = {k: jnp.asarray(v) for k, v in model[b].items()}
            if ARCH.picks(jnp, jax, m, p, h) is not None:
                count(ARCH.picks(jnp, jax, m, p, h))
            h = ARCH.ref_layer(jnp, jax, m, p, h)
        mp = {k: jnp.asarray(v) for k, v in model[m["main"]].items()}
        nxt = np.concatenate([prompt, np.asarray(first)[:, None]], axis=1)
        # the module's block over the prompt's positions: pad h by one
        # position that mtp_input drops
        u = ARCH.mtp_input(jnp, m, head, mp,
                           jnp.concatenate([h, h[:, -1:]], axis=1),
                           jnp.asarray(nxt))
        count(ARCH.picks(jnp, jax, m, mp, u))
    assert {k: int(counted[k]) for k in want} == want
    assert int(counted["decode_steps"]) == int(counted["mtp_drafted"]) == 0


# ------------------------------ (a) whole harness runs, as files and entries


MIXES = ("cold-raw", "cold-int8")


def add_joyai(manifest: str, tag: str) -> None:
    """The tiny configuration as a NEW FILE beside ``manifest`` (its
    module is the committed one, found in the checkout) and new entries:
    a cell ``<tag>.joyai.<mix>`` for each of ``MIXES`` reporting what the
    tiny cell of that mix reports — the committed manifest's metrics of
    the routed families and of this one's decode arrive with
    ``write_tiny_root`` under the tiny ``cold-raw`` cell."""
    root = os.path.dirname(manifest)
    shutil.copy(TINY_FILE, os.path.join(root, "benchmark", "configs"))
    with open(manifest) as f:
        d = json.load(f)
    d["configs"].append({"name": "tinyjoyai", "source": "tests",
                         "reduced": [],
                         "file": "benchmark/configs/tiny-joyai.json",
                         "why": "four kinds of blob at tiny width"})
    for mix in MIXES:
        d["workloads"].append({
            "name": f"{tag}.joyai.{mix}", "config": "tinyjoyai",
            "traffic": mix, "chips": 1,
            "why": "a committed mix under the JoyAI-LLM-Flash architecture"})
    for metric in d["end_to_end"] + d["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] += [f"{tag}.joyai.{mix}" for mix in MIXES
                                    if f"{tag}.{mix}" in metric["workloads"]]
    with open(manifest, "w") as f:
        json.dump(d, f)


@pytest.mark.parametrize("mix,trace", [("cold-raw", 0), ("cold-int8", 0),
                                       ("cold-raw", 1)])
def test_a_rehearsed_joyai_run_ends_correct(tiny_manifest, mix, trace):
    """fabricate -> ``cli.main`` -> ingest -> boot -> serve -> read-back
    -> reference, the whole harness on the tiny configuration: every blob
    read back leaf by leaf of ITS layout, the module's included; the
    logits inside the one tolerance; the 24 tokens served by draft and
    verify; and in the traced run this PR's three metrics and the others
    of the cell read from the program's spans."""
    manifest, tag = tiny_manifest
    add_joyai(manifest, tag)
    assert problems(Manifest(manifest)) == []
    cell = f"{tag}.joyai.{mix}"
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
    # 3 requests of 16 + 8 tokens by draft and verify
    steps, accepted = got["serve.decode_steps"], got["serve.mtp_accepted"]
    assert steps + accepted + 3 == 24 and got["serve.mtp_drafted"] == steps
    assert 12 <= steps <= 21
    # 16 prompt positions and two a step, 2 routed layers and the module
    assert got["serve.moe_slots"] == (3 * 16 + 2 * steps) * 3 * 4
    assert got["serve.moe_held_slots"] == got["serve.moe_slots"]
    assert 0 < got["serve.moe_touched"] <= 3 * 16 * (3 + steps)
    assert got["boot.assemble_kinds"] == 3
    assert got["boot.compiles_in_window"] == 0
    assert got["decode.slow_bytes"] > 0  # gains, the bias, the norms
    assert "serve.moe_zero_slots" not in got
    assert {"wire.ttd_s", "ingest.hbm_peak_gib", "boot.first_forward_s",
            "serve.req_ms", "serve.queue_ms", "wire.buf_reused_bytes"} <= set(
        got)
