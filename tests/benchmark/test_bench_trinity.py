"""The Trinity-Mini architecture module (``benchmark/archs/
trinity_mini.py``), its committed configuration, traffic mix, cell and
metrics, at tiny width on the CPU: the layout both sides share blob by
blob, the committed cut against the published configuration, the plain
reference against the program's forward through the harness's own loop,
and whole harness runs of a tiny configuration beside a temporary
manifest — one that ends ``correct``, and two with a fault that must not.
(``tests/test_trinity.py`` holds the program to the reference piece by
piece.)

The tiny configuration (``arch_trinity/tiny-trinity.json``) has the
committed one's shape in small: two dense layers, then routed ones of 16
experts (top-4, one shared), three with a window of 8 to one without, six
layers; its traffic is the committed mix with a prompt of 21 positions,
more than twice the window, so that every ring wraps in the prefill and
again under the answer.
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
TINY_FILE = os.path.join(HERE, "arch_trinity", "tiny-trinity.json")
ARCH_FILE = os.path.join(REPO, "benchmark", "archs", "trinity_mini.py")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TOLERANCE = 0.03  # run.py's, for every architecture
CELL = "cold-raw-4k.trinity-mini"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# The published configuration (config.json of arcee-ai/Trinity-Mini as
# the catalog beside the model-configs guide holds it), but for the three
# keys of the cut.
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": PERIOD * 8, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True}


def tiny(**changed) -> dict:
    """The tiny configuration as ``Manifest.config`` would hand it out."""
    with open(TINY_FILE) as f:
        return dict(json.load(f), arch_file=ARCH_FILE, **changed)


def tiny_share(first: int) -> dict:
    """One rank of eight of the tiny configuration: experts ``first``,
    ``first + 1`` of 16 and rows 0 .. 31 of 256, everything else whole."""
    return tiny(num_experts=2, expert_first=first, vocab_size=32,
                reduced={"num_experts": {"published": 16, "here": 2},
                         "vocab_size": {"published": 256, "here": 32}})


TINY = tiny()
ARCH = archs.of(TINY)


def program_config(config: dict, name: str, **changed):
    """The program's configuration object as the module registers it."""
    import dataclasses

    from distributed_llm_dissemination_tpu.models import trinity

    ARCH.register(config, name)
    return dataclasses.replace(trinity.CONFIGS[name], **changed)


def rel(got, want) -> float:
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# ----------------------------------------------- one layout on both sides


@pytest.mark.parametrize("name", ["tiny", "share", "trinity-mini-d18"])
def test_the_programs_specs_equal_the_modules_layout_for_every_blob(name):
    from distributed_llm_dissemination_tpu.models import quant, serde

    config = {"tiny": TINY, "share": tiny_share(6)}.get(name) or (
        Manifest().config(name)[1])
    cfg = program_config(config, "layout-" + name)
    n = fabricate.model_dims(config)["layers"]
    assert serde.head_blob_id(cfg) == n == cfg.n_layers
    for b in range(n + 1):
        assert serde.blob_specs(cfg, b) == fabricate.blob_specs(config, b)
        for codec in fabricate.CODECS:
            assert quant.blob_nbytes_codec(cfg, b, codec) == (
                fabricate.blob_nbytes(config, b, codec))
    kinds = [serde.blob_kind(cfg, b) for b in range(n)]
    assert sorted(set(kinds)) == ["dense_sliding", "routed_full",
                                  "routed_sliding"]
    sizes = {k: fabricate.blob_nbytes(config, kinds.index(k))
             for k in set(kinds)}
    # a full layer's blob is a windowed one's to the byte: two sizes of
    # layer blob, three kinds
    assert sizes["routed_full"] == sizes["routed_sliding"] != (
        sizes["dense_sliding"])


def test_the_committed_configuration_is_the_published_one_cut_in_three_keys():
    """Every published key as published but for ``num_hidden_layers``,
    ``num_experts`` and ``vocab_size`` (each with its published value,
    its value here and why); what was assumed; the deployment; the bytes
    of each blob and of the replica recounted from ``layout`` (the
    numbers ``PERF.md`` section 4 gives)."""
    entry, config = Manifest().config("trinity-mini-d18")
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    cut = {"num_hidden_layers": (32, 18), "num_experts": (128, 16),
           "vocab_size": (200192, 25024)}
    assert list(config["reduced"]) == entry["reduced"] == list(cut)
    for key, (published, here) in cut.items():
        rec = config["reduced"][key]
        assert (rec["published"], rec["here"], config[key]) == (
            published, here, here) and len(rec["why"]) > 40
    extra = set(config) - set(PUBLISHED) - set(cut)
    assert extra == {"arch", "arch_file", "source", "reduced", "assumed",
                     "deployment", "expert_first"}
    assert config["expert_first"] == 0 and config["arch"] == "trinity_mini"
    assert set(config["assumed"]) == {
        "norms", "qk_norm", "output_gate", "rotary", "mup_multiplier",
        "renormalisation", "router_dtype", "sliding_window"}
    assert "every gain 1.0" in config["assumed"]["norms"]
    assert "2^-7 .. 2^-5" in config["assumed"]["router_dtype"]
    assert config["deployment"].startswith(
        "one rank of eight that share each layer (experts and vocabulary 8 "
        "ways; attention, shared expert, router, norms on every rank) on "
        "the first of two pipeline stages (layers 0-17 of 32), the head's "
        "slice held here so that the rank answers alone")
    assert entry["source"] == config["source"]
    if os.path.exists(CATALOG):  # the catalog's own row, where it is
        with open(CATALOG) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "Trinity-Mini"]
        assert row["source_url"] == config["source"]
        assert {k: v for k, v in row["config"].items()
                if k not in cut} == PUBLISHED
        assert [row["config"][k] for k in cut] == [32, 128, 200192]
    m = fabricate.model_dims(config)
    assert (m["layers"], m["dense"], m["routed"], m["held"], m["top_k"],
            m["vocab"], m["h"], m["kv"], m["hd"], m["window"]) == (
        18, 2, 128, 16, 8, 25024, 32, 4, 128, 2048)
    assert m["types"] == (PERIOD * 5)[:18]
    sizes = [fabricate.blob_nbytes(config, b) for b in range(19)]
    assert sizes == [130_040_320] * 2 + [268_976_896] * 16 + [205_000_704]
    assert fabricate.model_nbytes(config) == 4_768_711_680
    assert "4,768,711,680 B" in config["reduced"]["num_hidden_layers"]["why"]
    assert "19 blobs of three sizes" in config["deployment"]
    fills = {b: {n: f for n, _, f in ARCH.layout(config, b)}
             for b in (0, 2, 3, 18)}
    assert [len(fills[b]) for b in (0, 2, 3, 18)] == [14, 19, 19, 3]
    assert "window_norm" in fills[2] and "attn_norm" in fills[3]
    assert fills[2]["expert_bias"] is None and fills[2]["gate"] is None
    assert sorted(k for k, v in fills[3].items() if v == 1.0) == [
        "attn_norm", "k_norm", "post_attn_norm", "post_ffn_norm",
        "pre_ffn_norm", "q_norm"]


def test_the_traffic_is_cold_raw_but_for_the_prompt_the_window_and_what():
    man = Manifest()
    new, old = man.traffic("cold-raw-4k"), man.traffic("cold-raw")
    assert {k for k in set(new) | set(old) if new.get(k) != old.get(k)} == {
        "prompt_len", "serve_window_s", "what"}
    assert (new["prompt_len"], new["gen_tokens"], new["requests"]) == (
        4096, 8, 3)
    assert new["serve_window_s"] * 2 == int(new["serve_window_s"] * 2)
    assert "4096" in new["what"] and "serve_window_s" in new["what"]


def test_the_manifest_gained_one_configuration_one_cell_and_two_metrics():
    man = Manifest()
    assert problems(man) == []
    d = man.data
    assert [w["name"] for w in d["workloads"]][-1] == CELL
    assert len(d["workloads"]) == 7 and len(d["configs"]) == 6
    assert sum(w["chips"] == 4 for w in d["workloads"]) == 1
    cell = man.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini-d18", "cold-raw-4k", 1)
    for said in ("4096+8", "19 blobs of 3 sizes", "4.77 GB", "18/32",
                 "cannot see"):
        assert said in cell["why"], said
    assert [m["name"] for m in d["per_layer"]][-2:] == [
        "serve.swa_evicted_rows", "serve.kv_rows_held"]
    mine = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    joyai = {m["name"] for m in man.metrics_for("cold-raw.joyai-llm-flash",
                                                "per_layer")}
    # what the JoyAI cell reports, but for its draft counters and the
    # strided-slice bytes, and this cell's two
    assert mine - joyai == {"serve.swa_evicted_rows", "serve.kv_rows_held"}
    assert joyai - mine == {"serve.mtp_drafted", "serve.mtp_accepted",
                            "serve.decode_steps", "decode.slow_bytes"}
    for name, field, sound in (
            ("serve.swa_evicted_rows", "swa_evicted", "86,310"),
            ("serve.kv_rows_held", "kv_rows", "135,252")):
        spec = man.metric_spec(name)
        assert spec["reader"] == "span_stat" and spec["args"] == {
            "role": "dest", "names": ["serve.generate"],
            "stat": "field_sum", "field": field}
        assert sound in spec["what"] and spec["moves"] == "cold_start_s"
    # the sound readings, recounted: 4103 positions go through 14 layers
    # with a window of 2048 and 4 without, three requests a round
    assert 3 * (4103 - 2048) * 14 == 86_310
    assert 3 * (14 * 2048 + 4 * 4103) == 135_252


def test_the_module_registers_what_it_was_given():
    cfg = program_config(tiny_share(6), "share-told")
    assert (cfg.n_layers, cfg.n_dense, cfg.window, cfg.vocab) == (6, 2, 8, 32)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_first, cfg.top_k,
            cfg.d_shared, cfg.route_scale) == (16, 2, 6, 4, 32, 2.826)
    assert cfg.layer_types == tuple(PERIOD + PERIOD[:2])
    for differs in ({"score_func": "softmax"}, {"route_norm": False},
                    {"n_group": 8}, {"tie_word_embeddings": True},
                    {"rope_scaling": {"type": "yarn"}},
                    {"mup_enabled": False}):
        with pytest.raises(SystemExit, match="this config differs"):
            ARCH.register(tiny(**differs), "differs")
    with pytest.raises(ValueError, match="layer_types"):
        ARCH.dims(tiny(num_hidden_layers=9))


# ----------------------- the program against the reference, float32, whole


@pytest.mark.parametrize("share", ["uncut", "share"])
@pytest.mark.parametrize("codec", fabricate.CODECS)
def test_the_reference_agrees_with_the_programs_forward(codec, share,
                                                        monkeypatch):
    """Two implementations that share no code, float32 both, the same
    blobs of the harness's own fill (gains 1, a seeded selection bias),
    through the harness's own loop over every layer blob, 29 positions
    under a window of 8 with both sides in blocks (4 and 8): they agree
    to float32 rounding, whole and as one rank of eight."""
    import jax
    import jax.numpy as jnp

    from distributed_llm_dissemination_tpu.models import family, trinity
    from distributed_llm_dissemination_tpu.models.llama import forward

    monkeypatch.setattr(trinity, "BLOCK", 4)
    monkeypatch.setattr(ARCH, "REF_BLOCK", 8)
    config = TINY if share == "uncut" else tiny_share(4)
    cfg = program_config(config, f"ref-{codec}-{share}", dtype=jnp.float32)
    m = fabricate.model_dims(config)
    n = m["layers"]
    blobs = {b: fabricate.make_blob(config, b, 7, codec)
             for b in range(n + 1)}
    model = {b: decoded(config, b, blobs[b], codec) for b in blobs}
    layers = family.stack(cfg, range(n), lambda b: dict(model[b]), np.stack)
    params = jax.tree.map(jnp.asarray, {"layers": layers, **model[n]})
    toks = np.asarray(fabricate.make_prompts(config, 7, 3, 29))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(params, jnp.asarray(toks), cfg))
    got = reference.logits(config, toks, lambda b: fabricate.blob_leaves(
        config, b, blobs[b], codec))
    assert got.shape == (3, 29, m["vocab"])
    assert rel(got, want) < 1e-5


# ------------------------------ whole harness runs, as files and entries


def add_trinity(manifest: str, tag: str, arch_patch: str = "") -> str:
    """The tiny configuration and its traffic (the committed mix with a
    prompt of 21 positions) as NEW FILES beside ``manifest`` and new
    entries: the cell ``<tag>.trinity`` reporting what the committed cell
    reports.  With ``arch_patch`` the architecture module beside the
    manifest is the committed one followed by those lines (a fault put
    into the program at ``register``)."""
    root = os.path.dirname(manifest)
    shutil.copy(TINY_FILE, os.path.join(root, "benchmark", "configs"))
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "cold-raw-4k.json")) as f:
        traffic = dict(json.load(f), prompt_len=21, serve_window_s=2.5)
    with open(os.path.join(root, "benchmark", "traffic", "tiny-4k.json"),
              "w") as f:
        json.dump(traffic, f)
    if arch_patch:
        os.makedirs(os.path.join(root, "benchmark", "archs"), exist_ok=True)
        with open(ARCH_FILE) as f:
            code = f.read()
        with open(os.path.join(root, "benchmark", "archs",
                               "trinity_mini.py"), "w") as f:
            f.write(code + "\n\n" + arch_patch)
    with open(manifest) as f:
        d = json.load(f)
    d["configs"].append({"name": "tinytrinity", "source": "tests",
                         "reduced": [],
                         "file": "benchmark/configs/tiny-trinity.json",
                         "why": "three kinds of layer at tiny width"})
    cell = f"{tag}.trinity"
    d["workloads"].append({
        "name": cell, "config": "tinytrinity", "traffic": "tiny-4k",
        "chips": 1, "why": "the committed mix, a prompt of 21 positions, "
                           "under the Trinity-Mini architecture"})
    for metric in d["end_to_end"] + d["per_layer"]:
        if f"{tag}.cold-raw-4k" in metric.get("workloads", ()):
            metric["workloads"].append(cell)
    with open(manifest, "w") as f:
        json.dump(d, f)
    return cell


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsed_trinity_run_ends_correct(tiny_manifest, trace):
    """fabricate -> ``cli.main`` -> ingest -> boot -> serve -> read-back
    -> reference, the whole harness on the tiny configuration: seven
    blobs read back leaf by leaf; the logits inside the one tolerance; 3
    requests of 21 + 8 served through rings that wrap; and in the traced
    run this PR's two metrics and the others of the cell read from the
    program's spans."""
    manifest, tag = tiny_manifest
    cell = add_trinity(manifest, tag)
    assert problems(Manifest(manifest)) == []
    proc = rehearse(manifest, cell, stub=True, trace=trace)
    line = last_line(proc)
    assert "read-back: 7 whole blobs" in proc.stdout
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
    # 28 positions a request go through 5 layers with a window of 8 and
    # one without; 4 routed layers of top-4, every expert held
    assert got["serve.swa_evicted_rows"] == 3 * (28 - 8) * 5
    assert got["serve.kv_rows_held"] == 3 * (5 * 8 + 28)
    assert got["serve.moe_slots"] == 3 * 28 * 4 * 4
    assert got["serve.moe_held_slots"] == got["serve.moe_slots"]
    assert 0 < got["serve.moe_touched"] <= 3 * 8 * 4 * 16
    assert got["boot.assemble_kinds"] == 3
    assert got["boot.compiles_in_window"] == 0
    assert {"wire.ttd_s", "ingest.hbm_peak_gib", "boot.first_forward_s",
            "serve.req_ms", "serve.queue_ms", "wire.buf_reused_bytes"} <= set(
        got)


# A fault put into the PROGRAM when the seat registers the configuration
# (the module beside the temporary manifest is found first): the harness,
# the reference and the traffic are the committed ones.
RING_ONE_ROW_SHORT = '''
def _faulty(register):
    def wrapped(config, name):
        import importlib
        trinity = importlib.import_module(PKG + ".models.trinity")
        real = trinity.init_cache
        def init_cache(cfg, batch, max_len):
            cache = real(cfg, batch, max_len)
            return {kind: (rows if kind.endswith("full") else
                           {k: a[:, :, :cfg.window - 1]
                            for k, a in rows.items()})
                    for kind, rows in cache.items()}
        trinity.init_cache = init_cache
        return register(config, name)
    return wrapped
register = _faulty(register)
'''
EXPERTS_IN_ANOTHER_ORDER = '''
def _faulty(leaf):
    def wrapped(boot, blob_id, name):
        got = leaf(boot, blob_id, name)
        return got[::-1] if name in ("ew1", "ew3", "ew2") else got
    return wrapped
leaf = _faulty(leaf)
'''


@pytest.mark.parametrize("fault", ["ring", "experts"])
def test_a_rehearsed_run_with_a_fault_ends_not_correct(tiny_manifest, fault):
    """The same run with every ring one row short of its window (the
    served tokens leave the reference's argmax where its margin is
    stable: the teacher-forced logits, which go through no cache, still
    agree) and with the experts' stacks handed back in another order
    (the read-back's digests differ): ``correct`` is false, and for that
    reason."""
    manifest, tag = tiny_manifest
    cell = add_trinity(manifest, tag, {
        "ring": RING_ONE_ROW_SHORT, "experts": EXPERTS_IN_ANOTHER_ORDER}[
        fault])
    proc = rehearse(manifest, cell, stub=True)
    line = last_line(proc)
    assert line["correct"] is False and line["failed"] == 0
    ref = json.loads(proc.stdout.split("reference: ", 1)[1].splitlines()[0])
    assert ref["logits_ok"] is True
    if fault == "ring":
        assert ", 0 mismatches" in proc.stdout
        assert ref["tokens_ok"] is False and not ref["passed"]
    else:
        assert ", 4 mismatches" in proc.stdout  # the four routed layers
        assert ref["passed"]
