"""The yardstick's own arithmetic, on the CPU, without a process."""

import os

import numpy as np
import pytest

import contract
from bench_helpers import REPO, TINY, decoded, load_config
from benchmark import fabricate, kernels, launch, rounds
from benchmark.manifest import Manifest

MISTRAL = load_config("mistral-7b-v0.3-d8")
CODESTRAL = load_config("codestral-22b-v0.1-d9")


# ------------------------------------------------------------ fabrication

@pytest.mark.parametrize("codec", fabricate.CODECS)
def test_blobs_are_the_same_for_the_same_seed_and_differ_otherwise(codec):
    big = 2 ** 31 + 11  # the driver's seeds are large
    a = fabricate.make_blob(TINY, 1, big, codec)
    b = fabricate.make_blob(TINY, 1, big, codec)
    assert a.tobytes() == b.tobytes()
    assert a.tobytes() != fabricate.make_blob(TINY, 1, big + 1,
                                              codec).tobytes()
    assert a.tobytes() != fabricate.make_blob(TINY, 2, big, codec).tobytes()


@pytest.mark.parametrize("codec", fabricate.CODECS)
def test_blob_layout_is_the_programs_own(codec):
    """The harness writes the wire format down a second time; it must be
    the one ``models/serde.py`` and ``models/quant.py`` decode."""
    from distributed_llm_dissemination_tpu.models import quant
    from distributed_llm_dissemination_tpu.models.llama import ModelConfig

    m = fabricate.model_dims(TINY)
    cfg = ModelConfig(name="t", vocab=m["vocab"], d_model=m["d"],
                      n_layers=m["layers"], n_heads=m["h"],
                      n_kv_heads=m["kv"], d_ff=m["f"])
    for b in (0, m["layers"]):
        blob = fabricate.make_blob(TINY, b, 5, codec)
        assert len(blob) == quant.blob_nbytes_codec(cfg, b, codec)
        theirs = quant.decode_blob_host(cfg, b, blob.tobytes(), codec)
        mine = decoded(TINY, b, blob, codec)
        assert set(theirs) == set(mine)
        for name in mine:
            assert np.array_equal(np.asarray(theirs[name], np.float32),
                                  mine[name]), (b, name)
            assert np.isfinite(mine[name]).all()


def test_fabricated_weights_have_a_trained_layers_scale():
    w = decoded(TINY, 0, fabricate.make_blob(TINY, 0, 1))
    assert 0.01 < w["wq"].std() < 0.03
    assert np.all(w["ln1"] == 1.0)
    q = decoded(TINY, 0, fabricate.make_blob(TINY, 0, 1, "int8"), "int8")
    assert 0.008 < q["wq"].std() < 0.03
    assert np.all(q["ln2"] == 1.0)


@pytest.mark.parametrize("config,layer,head,total", [
    (MISTRAL, 436_224_000, 536_879_104, 4_026_671_104),
    (CODESTRAL, 780_165_120, 805_318_656, 7_826_804_736),
])
def test_blob_sizes_at_the_published_widths(config, layer, head, total):
    n = config["num_hidden_layers"]
    assert fabricate.blob_nbytes(config, 0) == layer
    assert fabricate.blob_nbytes(config, n) == head
    assert fabricate.model_nbytes(config) == total


@pytest.mark.parametrize("codec", fabricate.CODECS)
def test_the_read_back_digest_covers_every_byte_of_every_leaf(codec):
    """One flipped bit anywhere in a blob changes the digest of its
    decoded leaves (int8: a weight or a scale) and of its wire form."""
    blob = fabricate.make_blob(TINY, 0, 9, codec)
    want = fabricate.expected_digests(TINY, 0, blob, codec)
    assert want == fabricate.expected_digests(TINY, 0, blob.copy(), codec)
    assert (want["wire"] == want["leaves"]) == (codec == "raw")
    # the last leaf's values end the blob (int8: below its scales, whose
    # low bits a bfloat16 result need not see)
    tail = fabricate.blob_leaves(TINY, 0, blob, codec)["w2"].values.nbytes
    rng = np.random.default_rng(3)
    for back in [1, tail, *rng.integers(1, tail, 6)]:
        flipped = blob.copy()
        flipped[len(blob) - back] ^= 0x10
        got = fabricate.expected_digests(TINY, 0, flipped, codec)
        assert got["wire"] != want["wire"], back
        assert got["leaves"] != want["leaves"], back
    flipped = blob.copy()
    flipped[0] ^= 0x10  # the first leaf's first byte
    assert fabricate.expected_digests(TINY, 0, flipped, codec)[
        "wire"] != want["wire"]


@pytest.mark.parametrize("codec", fabricate.CODECS)
def test_the_digest_of_decoded_leaves_is_what_a_device_would_read_back(codec):
    """The device holds bfloat16 leaves; digested in wire order they give
    the seeder's ``leaves`` digest."""
    import ml_dtypes

    blob = fabricate.make_blob(TINY, 1, 4, codec)
    on_device = [np.asarray(x).astype(ml_dtypes.bfloat16)
                 for x in decoded(TINY, 1, blob, codec).values()]
    assert fabricate.digest(on_device) == fabricate.expected_digests(
        TINY, 1, blob, codec)["leaves"]


def test_read_back_problems_name_the_blob_that_differs_or_is_missing():
    want = {"0": {"wire": "a", "leaves": "b", "bytes": 1},
            "1": {"wire": "c", "leaves": "d", "bytes": 1}}
    assert rounds.readback_problems(
        want, {"0": {"leaves": "b"}, "1": {"wire": "c"}}) == []
    bad = rounds.readback_problems(want, {"0": {"leaves": "x"}})
    assert len(bad) == 2 and "blob 0" in bad[0] and "blob 1" in bad[1]


def test_a_round_may_start_only_on_a_cold_device():
    """Before the warm-up nothing has run; after it its programs stay
    loaded (bytes as measured on the v5e) and no round may add to them as
    much as the smallest weight matrix."""
    import types

    from benchmark import child

    def holder():
        h = child.DeviceHolder.__new__(child.DeviceHolder)
        h.baseline = 27136
        h.jax = types.SimpleNamespace(live_arrays=lambda: [])
        return h

    h = holder()
    assert h.not_cold(27136) is None
    assert h.not_cold(14616064) is None and h.level == 14616064
    assert h.not_cold(14616064) is None
    smallest = min(fabricate.blob_nbytes(MISTRAL, 0, "int8"),
                   4096 * 1024 + 4096 * 4)  # wk as int8 with its scales
    assert child.ROUND_SLACK_BYTES < smallest
    assert "not cold" in h.not_cold(14616064 + smallest)
    assert "not cold" in holder().not_cold(27136 + (65 << 20))


def test_prompts_come_from_the_seed_and_stay_inside_the_vocabulary():
    p = fabricate.make_prompts(MISTRAL, 2 ** 31 + 1, 3, 16)
    assert p == fabricate.make_prompts(MISTRAL, 2 ** 31 + 1, 3, 16)
    assert len(p) == 3 and all(len(x) == 16 for x in p)
    assert all(0 <= t < 32768 for x in p for t in x)


# ---------------------------------------------------------------- rounds

def _recorded():
    return rounds.json_lines(os.path.join(
        REPO, "benchmark", "testdata", "rounds.jsonl"))


def test_per_run_value_is_the_median_of_counted_rounds_without_the_warmup():
    rs = _recorded()
    assert rs[0]["round"] == 0 and len(rs) >= 4
    red = rounds.reduce_run(rs)
    counted = [r for r in rs if r["round"] >= 1]
    assert red["attempted"] == len(counted) and red["failed"] == 0
    for key in rounds.TIMINGS:
        xs = sorted(r[key] for r in counted)
        mid = len(xs) // 2
        want = xs[mid] if len(xs) % 2 else (xs[mid - 1] + xs[mid]) / 2
        assert red["values"][key] == pytest.approx(want)
        assert red["values"][key] != min(xs)  # never the minimum
    # the warm-up round is slower and would move a mean; it is not in it
    warm = dict(rs[0], ttd_s=1e6)
    assert rounds.reduce_run([warm] + rs[1:])["values"] == red["values"]
    assert red["values"]["hbm_peak_gib"] == max(
        r["peak_bytes"] for r in rs) / 2 ** 30


def test_a_failed_round_counts_as_attempted_and_failed():
    rs = _recorded()
    rs[2] = dict(rs[2], ok=False, error="a request failed")
    red = rounds.reduce_run(rs)
    assert red["failed"] == 1 and red["attempted"] == len(rs) - 1
    assert rounds.reduce_run([dict(rs[0], ok=False)] + rs[1:])[
        "warmup_ok"] is False


def test_spread_is_the_interquartile_distance_over_the_median():
    xs = [3.0, 3.1, 3.2, 3.3, 3.4, 3.5]
    import statistics

    q = statistics.quantiles(xs, n=4)
    assert contract.spread(xs) == pytest.approx((q[2] - q[0]) / 3.25)
    assert contract.spread([1.0]) == 0.0


# ----------------------------------------------------------------- fence

@pytest.mark.parametrize("n,seeders", [(13, 4), (30, 4), (8, 4)])
def test_fence_splits_the_cores_it_is_given(n, seeders):
    cores = list(range(100, 100 + n))  # a cpuset, not 0..n-1
    side, rest = launch.fence(cores, seeders)
    assert len(side) == seeders and len(rest) == n - seeders
    assert set(side) | set(rest) == set(cores)
    assert not set(side) & set(rest)
    assert min(side) > max(rest)  # the LAST cores go to the seeders


@pytest.mark.parametrize("n,seeders", [(4, 4), (5, 4), (2, 1), (13, 0)])
def test_fence_gives_up_when_there_is_nothing_to_split(n, seeders):
    assert launch.fence(range(n), seeders) == (None, None)


def test_child_environment_drops_the_platform_and_the_drivers_variable(
        monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("BENCH_RUN", "17")
    env = launch.child_env("tpu")
    assert env["JAX_PLATFORMS"] == "tpu" and "BENCH_RUN" not in env
    assert env["PYTHONPATH"].split(os.pathsep)[0] == REPO


# --------------------------------------------------------------- kernels

def test_kernel_bytes_come_from_the_shapes():
    wire_raw = 4_026_671_104
    assert kernels.wire_bytes(MISTRAL, "raw") == wire_raw
    assert kernels.splice_bytes(MISTRAL, "raw") == 2 * wire_raw
    assert kernels.decode_bytes(MISTRAL, "raw") == 2 * wire_raw
    wire_q = kernels.wire_bytes(MISTRAL, "int8")
    # one byte a parameter and one float32 scale a row
    assert wire_raw / 2 < wire_q < wire_raw / 2 * 1.002
    assert kernels.decode_bytes(MISTRAL, "int8") == wire_q + wire_raw
    assert kernels.decode_ops(MISTRAL, "raw") == 0
    assert kernels.decode_ops(MISTRAL, "int8") == wire_raw  # 2 per param


def test_peaks_come_from_the_one_table_and_an_unknown_device_is_an_error():
    pk = kernels.peaks("TPU v5 lite")
    assert pk["hbm_bytes_per_s"] == 819e9
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError):
        kernels.peaks("cpu")
    with pytest.raises(KeyError):
        kernels.peaks("_source")


def test_roofline_share_is_least_time_over_kernel_time():
    # 8.19 GB at 819 GB/s is 10 ms; taken in 20 ms that is 50%
    assert kernels.roofline_share(8.19e9, 0, 0.020,
                                  "TPU v5 lite") == pytest.approx(50.0)
    # operations bound it when they would take longer than the bytes
    assert kernels.roofline_share(8.19e9, 197e12 * 0.03, 0.06,
                                  "TPU v5 lite") == pytest.approx(50.0)
    with pytest.raises(ValueError):
        kernels.roofline_share(1, 0, 0.0, "TPU v5 lite")


# --------------------------------------------------------------- readers

LOG = [
    {"message": "timer start", "mono": 10.0},
    {"message": "Job assignment completed", "mono": 10.104},
    {"message": "(a fraction of) layer received", "mono": 10.5,
     "layer_size": 1_000_000_000, "duration_ms": 400.0, "crc_ms": 30.0},
    {"message": "(a fraction of) layer received", "mono": 11.1,
     "layer_size": 1_000_000_000, "duration_ms": 300.0, "crc_ms": 50.0},
    {"message": "layer digest verified", "mono": 11.2, "digest_ms": 120.0},
    {"message": "layer staged to HBM", "mono": 11.6, "stage_ms": 300.0},
    {"message": "layer staged to HBM", "mono": 11.7, "stage_ms": 500.0},
    {"message": "Time to deliver", "mono": 11.9},
    {"message": "served generation request", "decode_ms": 47.0},
    {"message": "served generation request", "decode_ms": 49.0},
    {"message": "served generation request", "decode_ms": 90.0},
    {"message": "layer fully received", "total_bytes": 78_848 * 4},
]
CTX = {"logs_by_role": {"leader": LOG, "dest": LOG}, "config": TINY,
       "codec": "raw", "fabricate": fabricate, "kernels": kernels,
       "device": {"kind": "TPU v5 lite"},
       "round": {"ttd_s": 3.0, "ttft_s": 3.5, "cache_new_entries": 0,
                 "peak_bytes": 3 * 2 ** 30}}

READINGS = {
    "plan.dispatch_ms": 104.0,
    "wire.recv_rate": 2.0,  # 2 GB over (11.1 - (10.5 - 0.4)) s
    "wire.verify_busy_s": 0.2,
    "ingest.finalize_busy_s": 0.8,
    "ingest.tail_s": 0.8,
    "boot.tail_s": 0.5,
    "wire.ttd_s": 3.0,
    "ingest.hbm_peak_gib": 3.0,
    "boot.cache_misses": 0.0,
    "serve.req_ms": 49.0,
}


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_each_reader_reads_its_metric_from_the_log(metric):
    man = Manifest()
    spec = man.metric_spec(metric)
    got = man.reader(spec["reader"])(CTX, **spec.get("args", {}))
    assert got == pytest.approx(READINGS[metric])


def test_a_reader_that_finds_nothing_returns_nothing():
    man = Manifest()
    empty = dict(CTX, logs_by_role={}, round={}, trace=None)
    for m in man.data["per_layer"]:
        spec = man.metric_spec(m["name"])
        assert man.reader(spec["reader"])(empty,
                                          **spec.get("args", {})) is None


def test_roofline_reader_divides_counted_bytes_by_traced_time():
    man = Manifest()
    spec = man.metric_spec("kernel.splice_roofline")
    nbytes = kernels.splice_bytes(MISTRAL, "raw")
    red = {"modules_s": {"jit__concat_pad": 2 * nbytes / 819e9,
                         "jit_other": 1.0}, "ops_s": {}}
    ctx = dict(CTX, config=MISTRAL, trace=red)
    got = man.reader(spec["reader"])(ctx, **spec["args"])
    assert got == pytest.approx(50.0)
    pod = {"round": {"summary": {"plan_phases": {
        "collective": {"ms": 1930.0, "n": 5}}}}}
    spec = man.metric_spec("fabric.collective_busy_s")
    assert man.reader(spec["reader"])(pod, **spec["args"]) == 1.93


def test_a_thread_that_ended_since_it_was_listed_does_not_unfence_the_rest(
        monkeypatch):
    """``pin`` lists the process's threads and fences each; one that has
    ended in between is gone, not a refusal (on the v5e it once left a
    whole leader on every core, PR 26)."""
    import os

    from benchmark import child

    mine = sorted(os.sched_getaffinity(0))
    real, seen = os.sched_setaffinity, []

    def setaffinity(tid, cores):
        if tid == 2 ** 22 + 1:  # above any pid: a thread that has ended
            raise ProcessLookupError(3, "No such process")
        seen.append(tid)
        real(tid, cores)

    listed = os.listdir("/proc/self/task")
    monkeypatch.setattr(os, "sched_setaffinity", setaffinity)
    monkeypatch.setattr(os, "listdir",
                        lambda path: [str(2 ** 22 + 1)] + listed)
    got = child.pin(mine[:1])
    monkeypatch.undo()
    try:
        assert got == {"cores": mine[:1], "fenced": True}
        assert sorted(seen) == sorted(int(t) for t in listed)
    finally:
        for tid in listed:
            os.sched_setaffinity(int(tid), mine)
