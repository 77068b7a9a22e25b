"""The architecture seam (``benchmark/archs/``): Llama behind it to the
bit, a second architecture through it as new files only, and the
failures of a configuration that names none or half of one."""

import hashlib
import json
import os
import re
import shutil

import numpy as np
import pytest

from bench_helpers import (ARCH_MOE, REPO, TINY, add_second_arch, decoded,
                           load_config, moe_config, rehearse)
from benchmark import archs, fabricate, kernels, reference
from benchmark.manifest import Manifest, ManifestError

SEED = 2147483659
# sha256, taken from the parent's code (PR 25, ``fabricate.py`` and
# ``reference.py`` before the move) on the tiny Llama configuration under
# SEED: blob 0, the head blob, and ``reference.logits`` on all five blobs
# for ``make_prompts(TINY, SEED, 3, 16)``.
PARENT = {
    "raw": ("7b25d60cc1c316be0c95b51efd7d3de7e8292b28af9e6d62d76732e009f3050d",
            "7f4f98f9fe7bf2233292e3bbc5bdb60df267c369242b99e62b4493de10843674",
            "f79450a0370d83c3e66f03a66e5d6e58e8e51487248dd6e614da4e821269f2ad"),
    "int8": ("9514ea3aaecfdbc9ea658d6b627929d9c337f17bfcfd9ca00d9bd528a7a8f188",
             "20363eb286c778f49700fa5a9e68ab0792d1bd416a81131ca733e7edf66d68c5",
             "e132861b03ef8187bfc5cfb77e955389bc8fdacb9f95f58b0e9a7ca67d1061b8"),
}
# PERF.md §4, and what the parent's kernels.py counted from them:
# (layer blob, head blob, replica) raw, then decode_bytes / splice_bytes
# raw and int8.
SIZES = {
    "mistral-7b-v0.3-d8": (436_224_000, 536_879_104, 4_026_671_104,
                           8_053_342_208, 6_041_399_364,
                           8_053_342_208, 4_029_456_520),
    "codestral-22b-v0.1-d9": (780_165_120, 805_318_656, 7_826_804_736,
                              15_653_609_472, 11_742_279_756,
                              15_653_609_472, 7_830_950_040),
}


def _sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(a)).hexdigest()


@pytest.mark.parametrize("codec", fabricate.CODECS)
def test_llama_blobs_are_the_parents_byte_for_byte(codec):
    n = fabricate.model_dims(TINY)["layers"]
    assert (_sha(fabricate.make_blob(TINY, 0, SEED, codec)),
            _sha(fabricate.make_blob(TINY, n, SEED, codec))
            ) == PARENT[codec][:2]


@pytest.mark.parametrize("codec", fabricate.CODECS)
def test_llama_reference_logits_are_the_parents_bit_for_bit(codec):
    n = fabricate.model_dims(TINY)["layers"]
    blobs = {b: fabricate.make_blob(TINY, b, SEED, codec)
             for b in range(n + 1)}
    toks = np.asarray(fabricate.make_prompts(TINY, SEED, 3, 16))
    got = reference.logits(TINY, toks, lambda b: fabricate.blob_leaves(
        TINY, b, blobs[b], codec))
    assert _sha(got) == PARENT[codec][2]


@pytest.mark.parametrize("name", sorted(SIZES))
def test_committed_configurations_keep_their_byte_counts(name):
    config = load_config(name)
    n = fabricate.model_dims(config)["layers"]
    assert (fabricate.blob_nbytes(config, 0), fabricate.blob_nbytes(config, n),
            fabricate.model_nbytes(config),
            kernels.decode_bytes(config, "raw"),
            kernels.decode_bytes(config, "int8"),
            kernels.splice_bytes(config, "raw"),
            kernels.splice_bytes(config, "int8")) == SIZES[name]


@pytest.mark.parametrize("codec", fabricate.CODECS)
def test_second_arch_reference_agrees_with_the_programs_forward(codec):
    """As ``test_bench_reference.py`` does for Llama: float32 both, the
    same blobs, ``models.llama.forward`` with its routed FFN."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models.llama import (
        ModelConfig,
        forward,
    )

    config = moe_config()
    m = fabricate.model_dims(config)
    n = m["layers"]
    cfg = ModelConfig(name="t", vocab=m["vocab"], d_model=m["d"], n_layers=n,
                      n_heads=m["h"], n_kv_heads=m["kv"], d_ff=m["f"],
                      rope_theta=m["theta"], norm_eps=m["eps"],
                      n_experts=m["experts"], top_k=m["top_k"],
                      dtype=jnp.float32)
    shapes = dict(fabricate.blob_specs(config, 0))
    assert shapes["w1"] == (m["experts"], m["d"], m["f"])  # rank 3
    blobs = {b: fabricate.make_blob(config, b, 7, codec)
             for b in range(n + 1)}
    leaves = {b: decoded(config, b, blobs[b], codec) for b in blobs}
    params = {"layers": {k: np.stack([leaves[b][k] for b in range(n)])
                         for k in leaves[0]}, **leaves[n]}
    toks = np.asarray(fabricate.make_prompts(config, 7, 3, 16))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(jax.tree.map(jnp.asarray, params),
                                  jnp.asarray(toks), cfg))
    got = reference.logits(config, toks, lambda b: fabricate.blob_leaves(
        config, b, blobs[b], codec))
    assert got.shape == (3, 16, m["vocab"])
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-5


@pytest.mark.parametrize("codec", fabricate.CODECS)
def test_a_leaf_is_filled_as_its_module_says(tmp_path, codec):
    """A constant other than a gain's 1 (a routing bias, say) decodes to
    itself in every element, whatever the leaf's rank; one that no
    bfloat16 holds is refused."""
    path = tmp_path / "biased.py"
    path.write_text(
        "from benchmark.archs.llama import *  # noqa: F401,F403\n"
        "from benchmark.archs import llama\n"
        "def layout(config, blob_id):\n"
        "    return llama.layout(config, blob_id) + [\n"
        "        ('bias', (2, 3, 8), config['bias'])]\n")
    config = dict(TINY, arch_file=str(path), bias=-0.375)
    blob = fabricate.make_blob(config, 0, SEED, codec)
    assert len(blob) == fabricate.blob_nbytes(config, 0, codec)
    got = decoded(config, 0, blob, codec)
    assert got["bias"].shape == (2, 3, 8) and (got["bias"] == -0.375).all()
    assert (got["ln1"] == 1.0).all() and len(set(got["wq"].ravel())) > 100
    with pytest.raises(ValueError, match="must be a bfloat16"):
        fabricate.make_blob(dict(config, bias=0.1), 0, SEED, codec)


def test_a_rank_3_leaf_read_back_wrong_makes_the_run_incorrect(
        tiny_manifest):
    """The read-back compares the expert stacks too: a module whose
    ``leaf`` hands back the experts in another order ends a whole
    rehearsed run with ``correct`` false, every layer blob a mismatch."""
    manifest, tag = tiny_manifest
    add_second_arch(manifest, tag)
    with open(os.path.join(os.path.dirname(manifest), "benchmark", "archs",
                           "moe.py"), "a") as f:
        f.write("\n\ndef leaf(boot, blob_id, name):\n"
                "    x = llama.leaf(boot, blob_id, name)\n"
                "    return x[::-1] if x.ndim == 3 else x\n")
    proc = rehearse(manifest, f"{tag}.moe.cold-raw", stub=True)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert "read-back: 5 whole blobs" in proc.stdout
    assert ", 4 mismatches" in proc.stdout


# ---------------------------------------------------------------- failures


def _bad_root(tiny_manifest, arch_file=None):
    manifest, tag = tiny_manifest
    add_second_arch(manifest, tag)
    root = os.path.dirname(manifest)
    if arch_file is not None:
        shutil.copy(arch_file, os.path.join(root, "benchmark", "archs"))
    return manifest, f"{tag}.moe.cold-raw", os.path.join(
        root, "benchmark", "configs", "tiny-moe.json")


def test_an_arch_that_names_no_module_fails_at_start_with_the_known_ones(
        tiny_manifest):
    manifest, cell, config_file = _bad_root(tiny_manifest)
    with open(config_file, "w") as f:
        json.dump(dict(moe_config(), arch="mamba"), f)
    proc = rehearse(manifest, cell, stub=True)
    assert proc.returncode != 0 and not proc.stdout.strip()  # no seat ran
    assert ("names the architecture 'mamba'; known: ['llama', 'moe']"
            in proc.stderr)
    # one lookup: a configuration that did not come from Manifest.config
    # is refused, not looked up a second way
    with pytest.raises(ManifestError, match="take it from Manifest.config"):
        archs.of({"arch": "llama"})


def test_a_module_that_lacks_a_hook_fails_at_start_naming_it(tiny_manifest,
                                                             tmp_path):
    half = tmp_path / "half.py"
    with open(os.path.join(ARCH_MOE, "moe.py")) as f:
        half.write_text(f.read().replace("ref_in, ref_out, leaf =",
                                         "ref_in, ref_out, _leaf ="))
    manifest, cell, config_file = _bad_root(tiny_manifest, half)
    with open(config_file, "w") as f:
        json.dump(dict(moe_config(), arch="half"), f)
    proc = rehearse(manifest, cell, stub=True)
    assert proc.returncode != 0 and not proc.stdout.strip()
    assert "half.py lacks the hook(s) leaf" in proc.stderr


def test_manifest_config_names_the_module_beside_it_first(tiny_manifest):
    manifest, tag = tiny_manifest
    add_second_arch(manifest, tag)
    man = Manifest(manifest)
    _, moe = man.config("tinymoe")
    _, tiny = man.config("tinycfg")  # no ``arch`` key: llama, from here
    assert moe["arch_file"] == os.path.join(
        os.path.dirname(manifest), "benchmark", "archs", "moe.py")
    assert tiny["arch_file"] == os.path.join(REPO, "benchmark", "archs",
                                             "llama.py")
    assert archs.of(tiny) is archs.of(TINY)
    assert man.archs() == ["llama", "moe"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        for c in json.load(f)["configs"]:  # the committed files name none
            assert "arch" not in load_config(c["name"])


# ------------------------------------------------- what the shared files say

LLAMA_WORDS = re.compile(
    r"\b(wq|wk|wv|wo|ln1|ln2|ln_f|w1|w2|w3|lm_head|embed|hidden_size|"
    r"num_attention_heads|num_key_value_heads|intermediate_size|vocab_size|"
    r"num_hidden_layers|rope_theta|rms_norm_eps|head_dim|ModelConfig|"
    r"forward_jit)\b|models\.llama|models/llama|params\[\"layers\"\]")
SHARED = ["fabricate.py", "reference.py", "child.py", "run.py", "kernels.py",
          "launch.py", "manifest.py", "rounds.py", "archs/__init__.py",
          "drivers/cli_main.py", "drivers/cli_podrun.py"]


@pytest.mark.parametrize("name", SHARED)
def test_no_shared_file_names_a_llama_leaf_key_or_module(name):
    """Llama lives in ``benchmark/archs/llama.py`` and nowhere else."""
    with open(os.path.join(REPO, "benchmark", name)) as f:
        lines = f.read().splitlines()
    hits = [(i + 1, line) for i, line in enumerate(lines)
            if LLAMA_WORDS.search(line)]
    assert not hits, hits
