"""The JoyAI-LLM-Flash family (``models/joyai.py``) at ``tiny-joyai`` on
the CPU: a blob that is delivered as a layer and is no layer of the stack
(the multi-token-prediction module) through the table, serde, the codecs
and the boot; the latent attention it shares with LongCat; the routed
block's share with its shared expert counted once; and a decode whose
steps yield one token or two — draft and verify — token for token the
one-token decode's."""

import dataclasses
import json

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
    joyai,
    llama,
    longcat,
    mla,
    quant,
    routed,
    serde,
)
from distributed_llm_dissemination_tpu.runtime import boot
from distributed_llm_dissemination_tpu.runtime.stream_boot import (
    StreamingBootStager,
)
from distributed_llm_dissemination_tpu.transport import reset_registry
from distributed_llm_dissemination_tpu.utils import trace

TINY = joyai.CONFIGS["tiny-joyai"]  # dense, moe, moe, mtp
F32 = dataclasses.replace(TINY, name="tiny-joyai-f32", dtype=jnp.float32)
# A vocabulary small enough that a random module's draft is sometimes the
# token: both branches of a step are taken.
SMALL = dataclasses.replace(TINY, name="tiny-joyai-v16", vocab=16)
TIMEOUT = 60.0
# ``generate(init_params(cfg, key(0)), arange(16) % vocab, cfg, 8)`` as the
# parent commit (PR 32) gives it on this CPU, before ``generate`` knew a
# step of two tokens.
PARENT_TOKENS = {
    "tiny": [29, 148, 147, 41, 51, 51, 51, 51],
    "tiny-moe": [212, 136, 249, 41, 41, 41, 41, 41],
    "tiny-longcat": [90, 253, 41, 170, 229, 181, 223, 166],
    "tiny-lfm2": [157, 117, 158, 201, 192, 171, 144, 55],
}


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


def one_token_decode(params, prompt, cfg, max_new: int):
    """The decode every family had before this one: the prefill, then one
    token a step (``_decode_fn``), whatever the family could draft."""
    b, p = prompt.shape
    cache = generate.init_cache(cfg, b, p + max_new)
    logits, cache, counted = generate._prefill_fn(cfg, p)(params, prompt,
                                                          cache)
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    return generate._decode_fn(cfg, p, max_new, 0.0)(
        params, cache, first, jnp.zeros((max_new - 1, 2), jnp.uint32),
        counted)


# ------------------------------------------ the table, and the blob beside


def test_the_module_is_a_layer_blob_and_no_layer_of_the_stack():
    assert TINY.n_layers == 4 and serde.head_blob_id(TINY) == 4
    assert family.layer_kinds(TINY) == ("dense", "moe", "moe", "mtp")
    assert family.side_kinds(TINY) == ("mtp",)
    assert family.group(TINY) == {"dense": [0], "moe": [1, 2], "mtp": [3]}
    assert family.stretches(TINY) == [[("dense", 0)],
                                      [("moe", 0), ("moe", 1)]]
    assert family.stretches(TINY, [2, 3]) == [[("moe", 0)]]
    assert family.stretches(TINY, [3]) == []
    params = llama.init_params(TINY, jax.random.key(0))
    cache = generate.init_cache(TINY, 1, 8)
    assert set(params["layers"]) == set(cache) == {"dense", "moe", "mtp"}
    assert family.drafter(TINY) is joyai.draft
    bare = dataclasses.replace(TINY, name="tiny-joyai-bare", n_mtp=0)
    assert family.side_kinds(bare) == () and family.drafter(bare) is None
    assert bare.n_layers == 3 and "mtp" not in family.layer_kinds(bare)
    for other in ("tiny", "tiny-longcat", "tiny-lfm2"):
        cfg = family.config(other)
        assert family.side_kinds(cfg) == () and family.drafter(cfg) is None
    with pytest.raises(ValueError, match="n_mtp"):
        dataclasses.replace(TINY, n_mtp=2)
    with pytest.raises(ValueError, match="experts"):
        dataclasses.replace(TINY, expert_first=8)


def test_a_blobs_leaves_and_bytes_depend_on_its_kind():
    names = {b: [n for n, _ in serde.blob_specs(TINY, b)] for b in range(5)}
    attn = ["attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
            "wkv_b", "wo", "ffn_norm"]
    moe = attn + ["gate", "gate_bias", "sw1", "sw3", "sw2", "ew1", "ew3",
                  "ew2"]
    assert names[0] == attn + ["w1", "w3", "w2"]
    assert names[1] == names[2] == moe
    assert names[3] == ["enorm", "hnorm", "eh_proj"] + moe + ["head_norm"]
    assert names[4] == ["embed", "ln_f", "lm_head"]  # the module has none
    assert [serde.blob_kind(TINY, b) for b in range(5)] == [
        "dense", "moe", "moe", "mtp", "head"]
    shapes = dict(serde.blob_specs(TINY, 3))
    assert shapes["eh_proj"] == (128, 64) and shapes["gate"] == (64, 16)
    assert shapes["wq_b"] == (24, 4 * 12) and shapes["wkv_a"] == (64, 20)
    assert shapes["ew2"] == (16, 32, 64) and shapes["sw1"] == (64, 32)
    sizes = [serde.blob_nbytes(TINY, b) for b in range(5)]
    assert sizes == [63568, 225392, 225392, 242160, 65664]
    # the module's blob: a routed layer, two norms, eh_proj, its head norm
    assert sizes[3] - sizes[1] == 2 * (3 * 64 + 128 * 64)
    # a share holds its experts' stacks alone; router and bias stay whole
    share = dataclasses.replace(TINY, name="tiny-joyai-share",
                                experts_held=4, expert_first=8)
    held = dict(serde.blob_specs(share, 1))
    assert held["ew1"] == (4, 64, 32) and held["gate_bias"] == (16,)
    with pytest.raises(ValueError, match="are not all alike"):
        serde.layer_param_specs(TINY)


def test_params_are_stacked_by_kind_and_round_trip_through_their_blobs():
    params = llama.init_params(TINY, jax.random.key(3))
    for kind, ids in family.group(TINY).items():
        specs = serde.layer_param_specs(TINY, ids[0])
        assert {n: a.shape for n, a in params["layers"][kind].items()} == {
            n: (len(ids), *shape) for n, shape in specs}
    blobs = serde.blobs_from_params(TINY, params)
    assert sorted(blobs) == [0, 1, 2, 3, 4]
    assert all(len(blobs[b]) == serde.blob_nbytes(TINY, b) for b in blobs)
    back = serde.params_from_blobs(TINY, blobs)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    for b in blobs:  # one blob regenerated alone: the same bytes
        assert serde.seeded_blob(TINY, b, 3) == blobs[b]
    part = serde.stacked_from_blobs(TINY, blobs, [2, 3])
    assert {k: v["ffn_norm"].shape[0] for k, v in part.items()} == {
        "moe": 1, "mtp": 1}


@pytest.mark.parametrize("blob", [0, 1, 3, 4])
@pytest.mark.parametrize("codec", ["int8", "int4"])
def test_every_kind_of_blob_goes_through_the_quantized_codecs(codec, blob):
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
    assert len(quant.decode_to_raw(TINY, blob, wire, codec)) == len(raw)


# ------------------------------------------------- the shared attention


def test_longcat_and_this_family_call_one_latent_attention():
    """LongCat's two wrappers are ``mla.project`` / ``mla.attend`` with
    its scales; this family passes 1.0, its own product and float32."""
    cfg = longcat.CONFIGS["tiny-longcat"]
    p = longcat.init_layer_params(cfg, jax.random.key(1))
    xn = jax.random.normal(jax.random.key(2), (2, 5, cfg.d_model))
    pos = jnp.arange(5)
    mask = jnp.where(pos[:, None] >= pos[None, :], 0.0, -jnp.inf)
    q, ckv, kr = longcat._mla_project(p, 1, xn, pos, cfg)
    want = mla.project(p, xn, pos, cfg, sfx="_1",
                       q_scale=np.sqrt(cfg.d_model / cfg.q_rank),
                       kv_scale=np.sqrt(cfg.d_model / cfg.kv_rank))
    assert all(np.array_equal(a, b) for a, b in zip((q, ckv, kr), want))
    assert q.dtype == cfg.dtype
    assert np.array_equal(longcat._mla_attend(p, 1, q, ckv, kr, mask, cfg),
                          mla.attend(p, q, ckv, kr, mask, cfg, sfx="_1"))
    assert longcat._mm is mla._mm and longcat._rms is mla._rms
    mine = joyai.init_layer_params(TINY, jax.random.key(1), "dense")
    q, ckv, kr = mla.project(mine, xn, pos, TINY, mm=joyai._mm,
                             carry=jnp.float32)
    assert q.shape == (2, 5, 4, 12) and ckv.shape == (2, 5, 16)
    assert {q.dtype, ckv.dtype, kr.dtype} == {jnp.dtype(jnp.float32)}


# ------------------------------------- the share, the shared expert once


def test_four_shares_and_one_shared_expert_add_up_to_the_uncut_block():
    """The routed block's result for the whole layer = the four ranks'
    held-expert parts + the shared expert ONCE: the router and the
    renormalisation over all picks are every rank's alike."""
    cfg = dataclasses.replace(F32, name="tiny-joyai-uncut")
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     joyai.init_layer_params(cfg, jax.random.key(7), "moe"))
    xn = jax.random.normal(jax.random.key(8), (2, 9, cfg.d_model))
    idx, w = routed.route(p, xn, cfg, "gate_bias")
    assert np.allclose(np.asarray(w.sum(-1)), cfg.route_scale, rtol=1e-6)
    whole, counted = routed.routed_part(p, xn, idx, w, cfg)
    assert int(counted["moe_held"]) == int(counted["moe_slots"]) == 2 * 9 * 4
    parts, held = 0.0, 0
    for first in (0, 4, 8, 12):
        rank = dataclasses.replace(cfg, name=f"rank{first}", experts_held=4,
                                   expert_first=first)
        mine = dict(p, **{k: p[k][first:first + 4]
                          for k in ("ew1", "ew3", "ew2")})
        ridx, rw = routed.route(mine, xn, rank, "gate_bias")
        assert np.array_equal(ridx, idx) and np.array_equal(rw, w)
        part, c = routed.routed_part(mine, xn, ridx, rw, rank)
        parts, held = parts + part, held + int(c["moe_held"])
    assert held == 2 * 9 * 4  # every slot is held by exactly one rank
    assert np.allclose(np.asarray(parts), np.asarray(whole), atol=1e-5)
    # the layer: attention + routed + shared; a rank's result differs from
    # the whole layer's by the absent experts' part alone
    x = jax.random.normal(jax.random.key(9), (2, 9, cfg.d_model))
    pos = jnp.arange(9)
    full = joyai.layer_apply(p, x, pos, cfg)
    rank0 = dataclasses.replace(cfg, name="rank0", experts_held=4)
    mine = dict(p, **{k: p[k][:4] for k in ("ew1", "ew3", "ew2")})
    got = joyai.layer_apply(mine, x, pos, rank0)
    assert not np.allclose(np.asarray(got), np.asarray(full), atol=1e-3)


# --------------------------------------------- serving through the cache


def test_prefill_and_one_token_decode_through_the_latent_cache_equal_the_full_forward():
    params = llama.init_params(F32, jax.random.key(1))
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, F32.vocab, (2, 15)), jnp.int32)
    full = np.asarray(llama.forward(params, toks, F32))
    cache = generate.init_cache(F32, 2, 15)
    assert jax.tree.map(lambda a: a.shape, cache) == {
        "dense": {"ckv": (1, 2, 15, 16), "kr": (1, 2, 15, 4)},
        "moe": {"ckv": (2, 2, 15, 16), "kr": (2, 2, 15, 4)},
        "mtp": {"ckv": (1, 2, 15, 16), "kr": (1, 2, 15, 4)}}
    assert {a.dtype for a in jax.tree.leaves(cache)} == {
        jnp.dtype(jnp.float32)}
    got, cache, counted = generate._prefill_fn(F32, 9)(params, toks[:, :9],
                                                       cache)
    errs = [np.abs(np.asarray(got) - full[:, 8]).max()]
    for t in range(9, 15):
        got, cache, _ = generate._forward_with_cache(
            params, toks[:, t:t + 1], jnp.asarray([t]), cache, F32)
        errs.append(np.abs(np.asarray(got) - full[:, t]).max())
    assert max(errs) < 1e-4 * np.abs(full).max()
    # the stack's two routed layers count; the module's rows were untouched
    assert int(counted["moe_slots"]) == 2 * 9 * 2 * F32.top_k
    assert not np.asarray(cache["mtp"]["ckv"]).any()


def test_the_modules_logits_through_its_cache_equal_its_full_forward():
    """``draft`` over the prompt through the module's rows of the cache,
    then position by position, against ``mtp_forward`` without one."""
    params = llama.init_params(F32, jax.random.key(2))
    toks = jnp.asarray(np.random.default_rng(2).integers(
        0, F32.vocab, (2, 12)), jnp.int32)
    want = np.asarray(joyai.mtp_forward(params, toks, F32))
    assert want.shape == (2, 11, F32.vocab)
    cache = generate.init_cache(F32, 2, 12)
    h, cache, _ = generate._hidden_with_cache(params, toks, jnp.arange(12),
                                              cache, F32)
    rows = generate.init_cache(F32, 2, 12)
    got, rows, counted = joyai.draft(params, h[:, :7], toks[:, 1:8],
                                     jnp.arange(7), rows, F32, 6)
    errs = [np.abs(np.asarray(got) - want[:, 6]).max()]
    assert int(counted["moe_slots"]) == 2 * 7 * F32.top_k
    for t in range(7, 11):
        got, rows, _ = joyai.draft(params, h[:, t:t + 1], toks[:, t + 1:t + 2],
                                   jnp.asarray([t]), rows, F32, 0)
        errs.append(np.abs(np.asarray(got) - want[:, t]).max())
    assert max(errs) < 1e-4 * np.abs(want).max()
    assert not np.asarray(rows["moe"]["ckv"]).any()  # the stack's: untouched


# ------------------------------------------------------ draft and verify


@pytest.mark.parametrize("seed", range(12))
def test_draft_and_verify_equals_the_one_token_decode_token_for_token(seed):
    params = llama.init_params(SMALL, jax.random.key(seed))
    prompt = jax.random.randint(jax.random.key(100 + seed), (1, 16), 0,
                                SMALL.vocab)
    want, plain = one_token_decode(params, prompt, SMALL, 12)
    got, counted = generate.generate_counted(params, prompt, SMALL, 12)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    stepped = generate.generate_stepwise(lambda: (params, "v"), prompt,
                                         SMALL, 12)
    assert np.array_equal(np.asarray(stepped), np.asarray(want))
    c = {k: int(v) for k, v in counted.items()}
    assert c["decode_steps"] + c["mtp_accepted"] + 1 == 12
    assert c["mtp_drafted"] == c["decode_steps"] <= 11
    # two positions a step through the stack's two routed layers and the
    # module's one, after the prompt's sixteen
    assert c["moe_slots"] == (16 + 2 * c["decode_steps"]) * 3 * SMALL.top_k
    assert set(plain) == {"moe_slots", "moe_held", "moe_touched"}


def test_both_branches_of_a_step_are_taken():
    """Over the seeds of the test above some drafts hold and some do
    not: ``0 < mtp_accepted < mtp_drafted``, and a batch yields two
    tokens only where every sequence's draft held."""
    drafted = accepted = 0
    for seed in range(12):
        params = llama.init_params(SMALL, jax.random.key(seed))
        prompt = jax.random.randint(jax.random.key(100 + seed), (1, 16), 0,
                                    SMALL.vocab)
        _, counted = generate.generate_counted(params, prompt, SMALL, 12)
        drafted += int(counted["mtp_drafted"])
        accepted += int(counted["mtp_accepted"])
    assert 0 < accepted < drafted
    params = llama.init_params(SMALL, jax.random.key(0))
    prompts = jax.random.randint(jax.random.key(5), (3, 16), 0, SMALL.vocab)
    want, _ = one_token_decode(params, prompts, SMALL, 12)
    got, counted = generate.generate_counted(params, prompts, SMALL, 12)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert int(counted["mtp_drafted"]) == 3 * int(counted["decode_steps"])


def test_a_module_that_always_drafts_right_halves_the_steps():
    """A model whose head favours one token whatever it is fed: stack and
    module agree, every draft holds, and eleven tokens take six steps —
    the last one's second token would be one too many and is not
    counted."""
    params = llama.init_params(SMALL, jax.random.key(3))
    params["ln_f"] = params["ln_f"] * 0.0
    params["layers"]["mtp"]["head_norm"] = (
        params["layers"]["mtp"]["head_norm"] * 0.0)
    # every logit is 0 on both sides: argmax is token 0 everywhere
    prompt = jnp.zeros((1, 8), jnp.int32)
    for max_new, steps, accepted in ((12, 6, 5), (11, 5, 5), (2, 1, 0),
                                     (1, 0, 0)):
        got, counted = generate.generate_counted(params, prompt, SMALL,
                                                 max_new)
        assert np.asarray(got).tolist() == [[0] * max_new]
        assert (int(counted["decode_steps"]),
                int(counted["mtp_accepted"])) == (steps, accepted)


def test_sampling_keeps_the_one_token_decode():
    params = llama.init_params(TINY, jax.random.key(4))
    prompt = jnp.asarray([[5, 9, 200, 31, 7]], jnp.int32)
    key = jax.random.key(11)
    got, counted = generate.generate_counted(params, prompt, TINY, 6,
                                             temperature=0.8, key=key)
    assert set(counted) == {"moe_slots", "moe_held", "moe_touched"}
    assert int(counted["moe_slots"]) == (5 + 5) * 2 * TINY.top_k
    again = generate.generate(params, prompt, TINY, 6, temperature=0.8,
                              key=key)
    assert np.array_equal(np.asarray(got), np.asarray(again))
    stepped = generate.generate_stepwise(lambda: (params, "v"), prompt, TINY,
                                         6, temperature=0.8, key=key)
    assert np.array_equal(np.asarray(got), np.asarray(stepped))


@pytest.mark.parametrize("name", sorted(PARENT_TOKENS))
def test_every_other_familys_generate_is_what_it_was(name):
    cfg = family.config(name)
    params = llama.init_params(cfg, jax.random.key(0))
    prompt = jnp.arange(16, dtype=jnp.int32)[None] % cfg.vocab
    got, counted = generate.generate_counted(params, prompt, cfg, 8)
    want, plain = one_token_decode(params, prompt, cfg, 8)
    assert np.asarray(got).tolist() == np.asarray(want).tolist() == [
        PARENT_TOKENS[name]]
    assert set(counted) == set(plain)
    assert not set(counted) & {"decode_steps", "mtp_drafted", "mtp_accepted"}


# --------------------------------------------------- boot, for the family


def test_a_full_boot_serves_what_generate_serves_and_a_slice_boots_a_stage():
    cfg = TINY
    layers = seeded_layers(cfg, seed=4)
    assert boot.classify_held_blobs(cfg, layers) == ([0, 1, 2, 3], True)
    res = boot.boot_from_layers(cfg, layers, generate_tokens=6)
    params = llama.init_params(cfg, jax.random.key(4))
    assert res.kind == "full"
    assert jax.tree.structure(res.params) == jax.tree.structure(params)
    zeros = jnp.zeros((1, 16), jnp.int32)
    assert np.array_equal(np.asarray(res.logits),
                          np.asarray(llama.forward_jit(params, zeros, cfg)))
    assert np.array_equal(np.asarray(res.tokens), np.asarray(
        one_token_decode(params, zeros, cfg, 6)[0]))
    span, = [s for s in trace.spans() if s["name"] == "boot.assemble"]
    assert span["fields"]["kinds"] == 3
    # the module is bound to the head blob's leaves: ONE embedding, one head
    assert sorted(k for k in res.params if k != "layers") == [
        "embed", "lm_head", "ln_f"]
    assert "embed" not in res.params["layers"]["mtp"]
    # layers 2..3: a stage that holds the module runs its one stack layer
    stage = boot.boot_from_layers(cfg, {b: layers[b] for b in (2, 3)})
    assert stage.kind == "stage" and stage.activations.shape == (1, 16, 64)
    assert {k: v["ffn_norm"].shape[0] for k, v in stage.params.items()} == {
        "moe": 1, "mtp": 1}
    assert boot.precompile_boot(cfg, [2, 3])["compiled"] == ["stage_forward"]


@pytest.mark.parametrize("order", [[3, 0, 1, 2, 4], [0, 1, 2, 4, 3],
                                   [4, 0, 3, 1, 2]],
                         ids=["first", "last", "between"])
def test_a_streamed_boot_binds_the_module_whenever_its_blob_arrives(
        order, cpu_devices):
    """The module's blob before every other, after the head's, and in
    between: the stager decodes it with its own kind's program, the boot
    stacks it beside the stack's kinds, and the booted tree drafts with
    the head blob's embedding and head."""
    cfg = dataclasses.replace(TINY, name="tiny-joyai-warm", vocab=240)
    ids = list(range(cfg.n_layers + 1))
    rec = boot.precompile_boot(cfg, ids, device_blobs=True, streamed=True)
    assert rec["compiled"] == [
        "decode[raw]x1/dense", "decode[raw]x1/moe", "decode[raw]x1/mtp",
        "decode[raw]head", "forward"]
    layers = seeded_layers(cfg, device=True)
    stager = StreamingBootStager(cfg, node_id=7)
    try:
        for b in order:
            assert stager.submit(b, layers[b])
        res = boot.boot_from_layers(cfg, layers, stager=stager,
                                    generate_tokens=6)
    finally:
        stager.close()
    assert res.via == "streamed per-layer"
    params = llama.init_params(cfg, jax.random.key(0))
    for a, b in zip(jax.tree.leaves(res.params), jax.tree.leaves(params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    zeros = jnp.zeros((1, 16), jnp.int32)
    assert np.array_equal(np.asarray(res.tokens), np.asarray(
        one_token_decode(params, zeros, cfg, 6)[0]))
    staged = [s["fields"] for s in trace.spans()
              if s["name"] == "decode.stage"]
    assert sorted(f["kind"] for f in staged) == [
        "dense", "head", "moe", "moe", "mtp"]


def test_a_streamed_boot_over_the_inmem_transport_answers_by_draft_and_verify():
    """Dissemination end to end: the leader seeds the five blobs of four
    kinds, node 1 stages each as it lands, boots and answers a request
    from a third seat with the one-token decode's tokens — and says how
    it decoded them."""
    from distributed_llm_dissemination_tpu.cli import trace as cli_trace
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
        requester = GenRequester(ts[2])
        try:
            prompt = [5, 7, 11, 13, 200, 3]
            got = requester.request(1, prompt, max_new=6, timeout=TIMEOUT)
            warm = requester.request(1, prompt, max_new=6, timeout=TIMEOUT,
                                     temperature=0.7, seed=3)
        finally:
            requester.close()
        want, _ = one_token_decode(params, jnp.asarray([prompt], jnp.int32),
                                   cfg, 6)
        assert got == np.asarray(want)[0].tolist() and len(warm) == 6
        served, sampled = [s["fields"] for s in trace.spans()
                           if s["name"] == "serve.generate"]
        steps = served["decode_steps"]
        assert steps + served["mtp_accepted"] + 1 == served["new_tokens"] == 6
        assert served["mtp_drafted"] == steps
        assert served["moe_slots"] == served["moe_held"] == (
            (6 + 2 * steps) * 3 * cfg.top_k)
        assert "decode_steps" not in sampled  # sampling: one token a step
        events = [{"ph": "X", "name": s["name"],
                   "args": {"fields": s["fields"]}} for s in trace.spans()]
        assert cli_trace.draft_totals(events) == {
            "spans": 1, "decode_steps": steps, "mtp_drafted": steps,
            "mtp_accepted": served["mtp_accepted"]}
    finally:
        leader.close()
        dest.close()
        for t in ts.values():
            t.close()


def test_a_live_swap_assembles_the_module_beside_the_stack():
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
        run_pod(_pod_conf(tmp_path, "tiny-joyai"), boot="tiny-joyai")
    assert e.value.code not in (0, None)
    return str(e.value), "cli.podrun.run_pod"


def _refused_by_train(tmp_path):
    from distributed_llm_dissemination_tpu.cli import train

    _pod_conf(tmp_path, "tiny-joyai")
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
    assert f"{here} cannot run 'tiny-joyai' of the joyai family" in said
    assert "(it knows llama): " in said and len(said.split(": ", 1)[1]) > 40


def test_hf_config_from_dir_refuses_the_family_by_name(tmp_path):
    from distributed_llm_dissemination_tpu.models import hf

    (tmp_path / "config.json").write_text(json.dumps(
        {"architectures": ["JoyAILLMFlashForCausalLM"], "hidden_size": 2048}))
    with pytest.raises(family.FamilyNotSupported,
                       match="cannot load the joyai family"):
        hf.config_from_dir(str(tmp_path))


def test_cli_main_knows_the_family_by_its_configurations_names():
    from distributed_llm_dissemination_tpu.cli.main import boot_config

    assert boot_config("tiny-joyai") is TINY
    assert "tiny-joyai" in family.known()
