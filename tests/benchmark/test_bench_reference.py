"""The plain reference against the program's own forward, at tiny width."""

import numpy as np
import pytest

from bench_helpers import TINY, decoded
from benchmark import fabricate, reference


@pytest.fixture(scope="module")
def tiny_model():
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models.llama import ModelConfig

    m = fabricate.model_dims(TINY)
    cfg = ModelConfig(name="t", vocab=m["vocab"], d_model=m["d"],
                      n_layers=m["layers"], n_heads=m["h"],
                      n_kv_heads=m["kv"], d_ff=m["f"],
                      rope_theta=m["theta"], norm_eps=m["eps"],
                      dtype=jnp.float32)
    return cfg, m


@pytest.mark.parametrize("codec", fabricate.CODECS)
def test_reference_agrees_with_models_llama_forward(tiny_model, codec):
    """Two implementations that share no code, float32 both, the same
    blobs: they agree to float32 rounding (1e-5 relative is ~100 ulp
    through four layers), far inside what bf16 against float32 gives."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models.llama import forward

    cfg, m = tiny_model
    n = m["layers"]
    blobs = {b: fabricate.make_blob(TINY, b, 7, codec)
             for b in range(n + 1)}
    leaves = {b: decoded(TINY, b, blobs[b], codec) for b in blobs}
    params = {"embed": leaves[n]["embed"], "ln_f": leaves[n]["ln_f"],
              "lm_head": leaves[n]["lm_head"],
              "layers": {k: np.stack([leaves[b][k] for b in range(n)])
                         for k in leaves[0]}}
    toks = np.asarray(fabricate.make_prompts(TINY, 7, 3, 16))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(jax.tree.map(jnp.asarray, params),
                                  jnp.asarray(toks), cfg))
    got = reference.logits(TINY, toks, lambda b: fabricate.blob_leaves(
        TINY, b, blobs[b], codec))
    assert got.shape == (3, 16, m["vocab"])
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-5


def _case():
    rng = np.random.default_rng(0)
    ref = rng.standard_normal((2, 5, 50)).astype(np.float32)
    prompt_len = 3
    answered = ref[:, prompt_len - 1:, :].argmax(-1)  # 3 tokens each
    tokens = np.concatenate(
        [np.zeros((2, prompt_len), np.int64), answered], axis=1)
    return ref, tokens, prompt_len


def test_verdict_passes_when_logits_and_tokens_agree():
    ref, tokens, p = _case()
    v = reference.compare(ref + 1e-4, ref, tokens, p, 5e-2)
    assert v["passed"] and v["tokens_ok"] and v["logits_ok"]
    assert v["positions"] == 6


def test_verdict_fails_on_logits_outside_the_tolerance():
    ref, tokens, p = _case()
    v = reference.compare(ref * 1.2, ref, tokens, p, 5e-2)
    assert not v["logits_ok"] and not v["passed"]


def test_a_wrong_token_fails_only_where_the_reference_is_sure():
    ref, tokens, p = _case()
    wrong = tokens.copy()
    wrong[0, p] = (wrong[0, p] + 1) % 50
    sure = reference.compare(ref + 1e-6, ref, wrong, p, 5e-2)
    assert not sure["tokens_ok"] and not sure["passed"]
    # the same wrong token under a logit error as large as the margin
    # is a tie on rounding, not a fault
    noisy = ref + 0.5 * np.sign(np.random.default_rng(1).standard_normal(
        ref.shape)).astype(np.float32)
    tie = reference.compare(noisy, ref, wrong, p, 10.0)
    assert tie["tokens_ok"]


def test_a_pod_gives_logits_for_its_boot_prompt_only():
    ref, tokens, p = _case()
    v = reference.compare(ref[:, :p] + 1e-4, ref, tokens, p, 5e-2)
    assert v["passed"] and v["rel_l2"] < 1e-3
