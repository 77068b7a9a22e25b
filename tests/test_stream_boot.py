"""The TTFT pipeline: persistent compilation cache, per-layer streamed
staging, and donated staging.

Three properties under test (ISSUE 3):
- warm-vs-cold persistent cache: a boot whose in-memory jit caches are
  gone still pays zero NEW compile-cache writes — every program is
  served from JAX's persistent cache, placed at process entry
  (``utils.env.place_compile_cache``);
- per-layer staging order-invariance: blobs streamed in ANY completion
  order assemble to byte-identical params (and to the bulk, unstreamed
  assembly);
- donation correctness: forward output is unchanged with donation on or
  off, and donation really consumes the wire blobs.
"""

import dataclasses
import os

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
from distributed_llm_dissemination_tpu.models import quant, serde
from distributed_llm_dissemination_tpu.models.llama import CONFIGS, forward_jit, init_params
from distributed_llm_dissemination_tpu.runtime.boot import (
    boot_from_layers,
    precompile_boot,
)
from distributed_llm_dissemination_tpu.runtime.stream_boot import (
    StreamingBootStager,
)

CFG = CONFIGS["tiny"]
SEED = 0
TIMEOUT = 30.0


def blob_layer(data: bytes) -> LayerSrc:
    return LayerSrc(
        inmem_data=bytearray(data),
        data_size=len(data),
        meta=LayerMeta(location=LayerLocation.INMEM,
                       source_type=SourceType.MEM),
    )


def seeded_layers(cfg, codec: str = "raw", device: bool = False):
    """{blob_id: LayerSrc} for the full model, optionally with the wire
    blob ALSO resident on device (the -hbm shape)."""
    ids = list(range(cfg.n_layers)) + [serde.head_blob_id(cfg)]
    out = {}
    dev = jax.devices()[0]
    for bid in ids:
        enc = quant.encode_blob(
            cfg, bid, serde.seeded_blob(cfg, bid, SEED), codec)
        src = blob_layer(enc)
        if device:
            src.device_array = jax.device_put(
                np.frombuffer(enc, np.uint8), dev)
        out[bid] = src
    return out


def stage_all(cfg, layers, order, codec: str = "raw") -> StreamingBootStager:
    stager = StreamingBootStager(cfg, codec=codec)
    for bid in order:
        assert stager.submit(bid, layers[bid])
    return stager


def leaves_bytes(params) -> dict:
    return {name: np.asarray(jax.device_get(a)).tobytes()
            for name, a in params["layers"].items()}


# -------------------------------------------------- streamed staging parity


def test_streamed_host_path_order_invariant_and_bulk_identical():
    """Layers submitted forward vs REVERSED produce byte-identical
    params, both equal to the bulk (unstreamed) assembly — completion
    order cannot leak into the booted model."""
    ids = list(range(CFG.n_layers)) + [serde.head_blob_id(CFG)]
    runs = {}
    for tag, order in (("fwd", ids), ("rev", list(reversed(ids)))):
        layers = seeded_layers(CFG)
        stager = stage_all(CFG, layers, order)
        try:
            res = boot_from_layers(CFG, layers, stager=stager)
        finally:
            stager.close()
        assert res.kind == "full"
        assert stager.staged_count == len(ids)
        runs[tag] = res
    bulk = boot_from_layers(CFG, seeded_layers(CFG))
    want = leaves_bytes(bulk.params)
    for tag, res in runs.items():
        assert leaves_bytes(res.params) == want, tag
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(res.logits), np.float32),
            np.asarray(jax.device_get(bulk.logits), np.float32))


def test_streamed_device_path_matches_bulk(cpu_devices):
    """-hbm shape: HBM-resident int8 wire blobs streamed per-blob boot to
    the same logits as the bulk n-blob decode."""
    cfg = dataclasses.replace(CFG, vocab=224)
    layers = seeded_layers(cfg, codec="int8", device=True)
    ids = sorted(layers)
    stager = stage_all(cfg, layers, ids, codec="int8")
    try:
        res = boot_from_layers(cfg, layers, codec="int8", stager=stager)
    finally:
        stager.close()
    assert res.kind == "full"
    bulk = boot_from_layers(cfg, seeded_layers(cfg, codec="int8",
                                               device=True), codec="int8")
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(res.logits), np.float32),
        np.asarray(jax.device_get(bulk.logits), np.float32))


def test_decode_stage_span_says_how_the_blob_widened(cpu_devices):
    """A staged device-resident raw blob's ``decode.stage`` span carries
    ``fast_bytes`` (whole 4 KiB tiles, the widening kernel) and
    ``slow_bytes`` (the rest, the strided slices), together the blob;
    ``cli.trace`` adds them up; and at the benchmark's widths — a
    count from the specs, nothing staged — nothing is left to the
    slices."""
    from distributed_llm_dissemination_tpu.cli import trace as cli_trace
    from distributed_llm_dissemination_tpu.utils import trace

    trace.reset_run()
    layers = seeded_layers(CFG, device=True)
    stager = stage_all(CFG, layers, [0])
    try:
        assert set(stager.collect([0], timeout=TIMEOUT)) == {0}
    finally:
        stager.close()
    span, = [s for s in trace.spans() if s["name"] == "decode.stage"]
    fields = span["fields"]
    assert fields["fast_bytes"] + fields["slow_bytes"] == layers[0].data_size
    assert fields["slow_bytes"] == 2 * 2 * CFG.d_model  # the two norms
    assert fields["fast_bytes"] % 4096 == 0
    events = [{"ph": "X", "name": s["name"], "args": {"fields": s["fields"]}}
              for s in trace.spans()]
    assert cli_trace.decode_widen_totals(events) == {
        "spans": 1, "fast_bytes": fields["fast_bytes"],
        "slow_bytes": fields["slow_bytes"]}
    # the host path widens nothing on the device, and says nothing
    trace.reset_run()
    stager = stage_all(CFG, seeded_layers(CFG), [1])
    try:
        stager.collect([1], timeout=TIMEOUT)
    finally:
        stager.close()
    span, = [s for s in trace.spans() if s["name"] == "decode.stage"]
    assert "fast_bytes" not in span["fields"]
    assert cli_trace.decode_widen_totals([]) == {}
    # Mistral-7B and Codestral-22B widths (the benchmark's two models)
    for d, h, kv, f, vocab in ((4096, 32, 8, 14336, 32768),
                               (6144, 48, 8, 16384, 32768)):
        cfg = dataclasses.replace(CFG, d_model=d, n_heads=h, n_kv_heads=kv,
                                  d_ff=f, vocab=vocab)
        for bid in (0, serde.head_blob_id(cfg)):
            specs = (serde.head_param_specs(cfg) if bid else
                     serde.layer_param_specs(cfg))
            assert quant.widen_bytes("raw", specs, "bfloat16") == (
                serde.blob_nbytes(cfg, bid), 0)
        # int8 sends the same layer with 4-byte scale vectors: those
        # are all it widens, and they keep the slices
        rows = sum(int(np.prod(s[:-1])) if len(s) > 1 else 1
                   for _, s in serde.layer_param_specs(cfg))
        assert quant.widen_bytes(
            "int8", serde.layer_param_specs(cfg), "bfloat16") == (0, 4 * rows)


def test_streamed_stage_boot_contiguous_slice():
    blobs = {bid: blob_layer(serde.seeded_blob(CFG, bid, SEED))
             for bid in (1, 2)}
    stager = stage_all(CFG, blobs, [2, 1])
    try:
        res = boot_from_layers(CFG, blobs, stager=stager)
    finally:
        stager.close()
    assert res.kind == "stage"
    want = boot_from_layers(
        CFG, {bid: blob_layer(serde.seeded_blob(CFG, bid, SEED))
              for bid in (1, 2)})
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(res.activations), np.float32),
        np.asarray(jax.device_get(want.activations), np.float32))


def test_partial_stream_infills_missing_blobs():
    """A stager that covered only SOME blobs must not force a bulk (or
    host) reassembly: the boot infills the missing blobs with the same
    per-blob staging and still produces bit-identical logits."""
    ids = list(range(CFG.n_layers)) + [serde.head_blob_id(CFG)]
    layers = seeded_layers(CFG)
    stager = stage_all(CFG, layers, ids[::2])  # every other blob only
    try:
        res = boot_from_layers(CFG, layers, stager=stager)
    finally:
        stager.close()
    assert res.kind == "full"
    bulk = boot_from_layers(CFG, seeded_layers(CFG))
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(res.logits), np.float32),
        np.asarray(jax.device_get(bulk.logits), np.float32))


def test_stager_rejects_duplicates_and_unknown_blobs():
    layers = seeded_layers(CFG)
    stager = StreamingBootStager(CFG)
    try:
        assert stager.submit(0, layers[0])
        assert not stager.submit(0, layers[0])  # idempotent
        assert not stager.submit(serde.head_blob_id(CFG) + 7, layers[0])
        streamed = stager.collect([0])
        assert set(streamed) == {0}
    finally:
        stager.close()


# --------------------------------------------------------- donated staging


def test_donation_on_off_forward_identical(monkeypatch):
    """The acceptance property: forward output unchanged with donation
    on/off — and the donated boot really CONSUMES the wire blobs (the
    store's device references are cleared; XLA additionally aliases
    wherever an output layout matches; later readers fall back to host
    bytes)."""
    cfg = dataclasses.replace(CFG, vocab=256)
    monkeypatch.setenv("DLD_BOOT_DONATE", "0")
    layers_off = seeded_layers(cfg, device=True)
    arrs_off = [layers_off[lid].device_array for lid in sorted(layers_off)]
    res_off = boot_from_layers(cfg, layers_off)
    assert all(not a.is_deleted() for a in arrs_off)
    assert all(layers_off[lid].device_array is not None
               for lid in layers_off)

    monkeypatch.setenv("DLD_BOOT_DONATE", "1")
    layers_on = seeded_layers(cfg, device=True)
    res_on = boot_from_layers(cfg, layers_on)
    # Consumed: the store's references are cleared — later readers fall
    # back to the host bytes.
    assert all(layers_on[lid].device_array is None for lid in layers_on)
    assert layers_on[0].read_bytes()  # host fallback intact

    np.testing.assert_array_equal(
        np.asarray(jax.device_get(res_on.logits), np.float32),
        np.asarray(jax.device_get(res_off.logits), np.float32))


def test_streamed_staging_releases_consumable_blobs(monkeypatch):
    """The streaming stager's per-blob release: with donation forced,
    each decoded blob's device reference is dropped the moment its
    decode is dispatched — mid-wire, not at boot — so HBM holds
    params-so-far + the in-flight blob instead of every wire blob."""
    monkeypatch.setenv("DLD_BOOT_DONATE", "1")
    cfg = dataclasses.replace(CFG, vocab=240)
    layers = seeded_layers(cfg, device=True)
    ids = sorted(layers)
    stager = stage_all(cfg, layers, ids)
    try:
        streamed = stager.collect(ids)
        assert set(streamed) == set(ids)
        assert all(layers[lid].device_array is None for lid in ids)
        res = boot_from_layers(cfg, layers, stager=stager)
    finally:
        stager.close()
    assert res.kind == "full"
    want = boot_from_layers(cfg, seeded_layers(cfg))
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(res.logits), np.float32),
        np.asarray(jax.device_get(want.logits), np.float32))


def test_auto_donation_skips_cpu_backend(monkeypatch):
    """Auto mode must NOT donate on the CPU backend: staged arrays there
    can be zero-copy adoptions of the very host buffers retransmits
    read."""
    monkeypatch.delenv("DLD_BOOT_DONATE", raising=False)
    cfg = dataclasses.replace(CFG, vocab=272)
    layers = seeded_layers(cfg, device=True)
    arrs = [layers[lid].device_array for lid in sorted(layers)]
    res = boot_from_layers(cfg, layers)
    assert res.kind == "full"
    assert all(not a.is_deleted() for a in arrs)
    assert all(layers[lid].device_array is not None for lid in layers)


def test_spliced_salvage_roundtrip(cpu_devices):
    """After the splice, the piece originals are released (re-pointed at
    the spliced span buffers) — and salvage reads those buffers clamped
    to the real span size: no gpad-pad bytes leak into a host fallback
    assembly."""
    from distributed_llm_dissemination_tpu.parallel.ingest import (
        ShardedLayerIngest,
    )

    total = 1000
    data = bytes(os.urandom(total))
    ing = ShardedLayerIngest(total, cpu_devices[:2], stream=True)
    for off in range(0, total, 100):
        ing.write(off, data[off:off + 100])
    bufs = ing._span_buffers(timeout=TIMEOUT)
    assert len(bufs) == 2
    out = ing.salvage()
    rebuilt = bytearray(total)
    covered = 0
    for off, chunk in out:
        rebuilt[off:off + len(chunk)] = chunk
        covered += len(chunk)
    assert covered == total  # exactly the layer bytes, no pad tail
    assert bytes(rebuilt) == data


# ------------------------------------------------ persistent compile cache


import contextlib
import logging


def _cache_entries(d) -> set:
    return {f for f in os.listdir(d) if f.endswith("-cache")}


@contextlib.contextmanager
def _pcache_log():
    """Capture jax's persistent-cache hit/miss records — the honest
    oracle for whether a compile was served from disk."""
    records = []

    class H(logging.Handler):
        def emit(self, r):
            records.append(r.getMessage())

    h = H()
    lg = logging.getLogger("jax._src.compiler")
    old = lg.level
    lg.addHandler(h)
    lg.setLevel(logging.DEBUG)
    try:
        yield records
    finally:
        lg.removeHandler(h)
        lg.setLevel(old)


def _hits(records, name):
    return [r for r in records
            if f"Persistent compilation cache hit for '{name}'" in r]


def _misses(records, name):
    return [r for r in records
            if "CACHE MISS" in r.upper() and f"'{name}'" in r]


_WARM_COLD_CHILD = r"""
import dataclasses, json, logging, os, sys
from distributed_llm_dissemination_tpu.utils.env import (
    DEFAULT_COMPILE_CACHE_DIR, place_compile_cache)
placed = place_compile_cache()  # process entry, before jax
import jax, numpy as np
from distributed_llm_dissemination_tpu.core.types import (
    LayerLocation, LayerMeta, LayerSrc)
from distributed_llm_dissemination_tpu.models import serde
from distributed_llm_dissemination_tpu.models.llama import CONFIGS
from distributed_llm_dissemination_tpu.runtime.boot import boot_from_layers

records = []
class H(logging.Handler):
    def emit(self, r):
        records.append(r.getMessage())
lg = logging.getLogger("jax._src.compiler")
lg.addHandler(H()); lg.setLevel(logging.DEBUG)

cfg = dataclasses.replace(CONFIGS["tiny"], vocab=304)
ids = list(range(cfg.n_layers)) + [serde.head_blob_id(cfg)]
blobs = {b: serde.seeded_blob(cfg, b, 0) for b in ids}
def boot():
    return boot_from_layers(cfg, {b: LayerSrc(
        inmem_data=bytearray(d), data_size=len(d),
        meta=LayerMeta(location=LayerLocation.INMEM))
        for b, d in blobs.items()})
def fwd(kind):
    return [r for r in records if "'jit_forward_jit'" in r
            and kind in r.upper()]
records.clear(); r1 = boot(); cold_miss = len(fwd("CACHE MISS"))
entries = sorted(f for f in os.listdir(placed) if f.endswith("-cache"))
jax.clear_caches()  # the warm-HOST shape: no in-memory executables
records.clear(); r2 = boot()
print(json.dumps({
    "placed": placed, "jax_dir": jax.config.jax_compilation_cache_dir,
    "default_dir": DEFAULT_COMPILE_CACHE_DIR,
    "cold_miss": cold_miss, "entries": len(entries),
    "forward_entry": any(e.startswith("jit_forward_jit") for e in entries),
    "warm_hit": len(fwd("CACHE HIT")), "warm_miss": len(fwd("CACHE MISS")),
    "equal": bool(np.array_equal(
        np.asarray(jax.device_get(r1.logits), np.float32),
        np.asarray(jax.device_get(r2.logits), np.float32)))}))
"""


def test_persistent_cache_warm_boot_serves_forward_from_disk(tmp_path):
    """A fresh process with ``JAX_COMPILATION_CACHE_DIR=<X>`` set from
    outside: the cold boot populates <X> (and only <X>); after clearing
    every in-memory jit cache (the warm-HOST shape), a second boot's
    forward is a persistent-cache HIT, never a miss — and the logits
    are identical."""
    import json
    import subprocess
    import sys

    cachedir = tmp_path / "pcache"
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(cachedir)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", _WARM_COLD_CHILD], env=env,
                         capture_output=True, text=True, timeout=100)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["placed"] == rec["jax_dir"] == str(cachedir)
    assert rec["placed"] != rec["default_dir"]
    assert rec["cold_miss"], "oracle broken: cold boot logged no miss"
    assert rec["entries"] and rec["forward_entry"], rec
    assert rec["warm_hit"] and not rec["warm_miss"], rec
    assert rec["equal"]


def test_precompile_writes_cache_boot_reads_it():
    """The cross-run story in one process: hint-time precompile_boot
    WRITES the session's persistent cache; with in-memory caches
    dropped, the boot's forward comes from disk."""
    cachedir = jax.config.jax_compilation_cache_dir
    assert cachedir, "conftest places the compile cache before jax"
    cfg = dataclasses.replace(CFG, vocab=336)
    ids = list(range(cfg.n_layers)) + [serde.head_blob_id(cfg)]
    rec = precompile_boot(cfg, ids)
    assert rec["compiled"] == ["forward"]
    assert rec["persistent_cache"] is True
    assert any(e.startswith("jit_forward_jit")
               for e in _cache_entries(cachedir))
    jax.clear_caches()
    layers = {bid: blob_layer(serde.seeded_blob(cfg, bid, SEED))
              for bid in ids}
    with _pcache_log() as records:
        res = boot_from_layers(cfg, layers)
    assert res.kind == "full"
    assert _hits(records, "jit_forward_jit"), (
        "boot did not read the precompile's persistent-cache entry")


def test_compile_cache_placement_rules(monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set it wins untouched; where
    it is not, ONE fixed git-ignored path inside the checkout — and no
    code path points JAX's cache anywhere by itself."""
    import subprocess

    from distributed_llm_dissemination_tpu.utils import env as env_util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for var in ("JAX_COMPILATION_CACHE_DIR",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"):
        monkeypatch.delenv(var, raising=False)
    placed = env_util.place_compile_cache()
    assert placed == env_util.DEFAULT_COMPILE_CACHE_DIR
    assert os.path.dirname(placed) == repo
    with open(os.path.join(repo, ".gitignore")) as f:
        assert os.path.basename(placed) + "/" in f.read().split()
    assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where/else")
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")
    assert env_util.place_compile_cache() == "/some/where/else"
    assert os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "2"
    hits = subprocess.run(
        ["grep", "-rlE", "--include=*.py",
         'jax_compilation_cache_dir", |DLD_COMPILE_' 'CACHE_DIR',
         os.path.join(repo, "distributed_llm_dissemination_tpu"),
         os.path.join(repo, "chip_smoke.py")],
        capture_output=True, text=True).stdout.split()
    assert not hits, hits


# -------------------------------------------- streamed precompile coverage


def test_precompile_streamed_warms_the_stager_decode(cpu_devices):
    """streamed=True warms the 1-blob decode the stager actually calls:
    the stager's decodes then hit the cache (compile-log oracle, with a
    cold control via the unwarmed sibling config in test_boot)."""
    import contextlib
    import logging

    @contextlib.contextmanager
    def compile_log():
        records = []

        class H(logging.Handler):
            def emit(self, r):
                records.append(r.getMessage())

        h = H()
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

    cfg = dataclasses.replace(CFG, vocab=368)
    ids = list(range(cfg.n_layers)) + [serde.head_blob_id(cfg)]
    rec = precompile_boot(cfg, ids, codec="int8", device_blobs=True,
                          streamed=True)
    assert rec["compiled"] == ["decode[int8]x1", "decode[int8]head",
                               "forward"]
    layers = seeded_layers(cfg, codec="int8", device=True)
    stager = StreamingBootStager(cfg, codec="int8")
    try:
        with compile_log() as records:
            for bid in ids:
                stager.submit(bid, layers[bid])
            streamed = stager.collect(ids)
        assert set(streamed) == set(ids)
        hits = [r for r in records
                if r.startswith("Compiling jit(_decode_qblobs)")]
        assert not hits, f"stager decode recompiled: {hits}"
    finally:
        stager.close()


# ------------------------------------------------------- receiver e2e path


def test_receiver_streams_layers_into_the_boot():
    """Dissemination end to end (inmem transport): every delivered layer
    is submitted to the stager mid-run, and the startup boot's logits
    match an independently initialized source model bit-for-bit."""
    from distributed_llm_dissemination_tpu.runtime import (
        LeaderNode,
        Node,
        ReceiverNode,
    )
    from distributed_llm_dissemination_tpu.transport import InmemTransport

    params = init_params(CFG, jax.random.key(SEED))
    blobs = serde.blobs_from_params(CFG, params)
    assignment = {1: {bid: LayerMeta() for bid in blobs}}
    ts = {i: InmemTransport(str(i)) for i in (0, 1)}
    leader = LeaderNode(
        Node(0, 0, ts[0]),
        {bid: blob_layer(b) for bid, b in blobs.items()},
        assignment, expected_nodes={1},
    )
    leader.boot_enabled = True
    receiver = ReceiverNode(Node(1, 0, ts[1]), {}, boot_cfg=CFG)
    try:
        assert receiver._boot_stager is not None  # stream boot default-on
        receiver.announce()
        leader.ready().get(timeout=TIMEOUT)
        receiver.ready().get(timeout=TIMEOUT)
        booted = leader.boot_ready().get(timeout=TIMEOUT)
        assert set(booted) == {1}
        assert receiver._boot_stager.staged_count == len(blobs)
        res = receiver.boot_result
        assert res is not None and res.kind == "full"
        tokens = jnp.zeros((1, 16), jnp.int32)
        want = forward_jit(params, tokens, CFG)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(res.logits), np.float32),
            np.asarray(jax.device_get(want), np.float32))
    finally:
        leader.close()
        receiver.close()
        for t in ts.values():
            t.close()


def test_stream_boot_env_gate(monkeypatch):
    from distributed_llm_dissemination_tpu.runtime import Node, ReceiverNode
    from distributed_llm_dissemination_tpu.transport import InmemTransport

    monkeypatch.setenv("DLD_STREAM_BOOT", "0")
    t = InmemTransport("5")
    r = ReceiverNode(Node(5, 0, t), {}, boot_cfg=CFG)
    try:
        assert r._boot_stager is None
    finally:
        r.close()
        t.close()


@pytest.mark.parametrize("family_name", ["tiny", "tiny-longcat"])
def test_the_boot_takes_the_staged_leaves_over_and_frees_them(family_name):
    """Assembly pops every staged leaf as it stacks it and the stager
    drops its own references (PR 27: a 6.4 GiB replica held twice through
    assembly did not fit a 15.75 GiB chip), so after a boot no per-layer
    leaf is alive anywhere but inside the stacked parameters; the count of
    what was staged stays, and a second boot of the same store — nothing
    streamed is left for it — still assembles the same model."""
    import gc
    import weakref

    from distributed_llm_dissemination_tpu.models import family

    cfg = family.config(family_name)
    ids = list(range(cfg.n_layers)) + [serde.head_blob_id(cfg)]
    layers = seeded_layers(cfg)
    stager = stage_all(cfg, layers, ids)
    try:
        staged = stager.collect(ids, timeout=TIMEOUT)
        assert set(staged) == set(ids)
        alive = [weakref.ref(a) for leaves in staged.values()
                 for a in leaves.values()]
        del staged
        first = boot_from_layers(cfg, layers, stager=stager)
        assert first.via == "streamed per-layer"
        gc.collect()
        assert all(ref() is None for ref in alive)
        assert stager.collect(ids, timeout=TIMEOUT) == {}
        assert stager.staged_count == len(ids)
        again = boot_from_layers(cfg, layers, stager=stager)
    finally:
        stager.close()
    assert again.via != "streamed per-layer"
    assert np.array_equal(np.asarray(first.logits), np.asarray(again.logits))
    want = forward_jit(init_params(cfg, jax.random.key(SEED)),
                       jnp.zeros((1, 16), jnp.int32), cfg)
    assert np.array_equal(np.asarray(first.logits), np.asarray(want))
