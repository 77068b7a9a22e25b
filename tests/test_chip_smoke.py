"""``chip_smoke.py``'s plumbing, on the CPU, so the script stays runnable
between chip sessions — and the rules it stands on: one process per
chip, no CPU fallback on a measurement path, a ``-hbm`` process that
cannot end clean after falling off the device path.

What a CPU run can show: that the orchestration starts, watches and
stops its processes, that the destination's logs are read correctly,
that the served tokens agree with the same-blob reference, that a
second process runs from the compile cache.  What it can never do is
PASS: the kernel check demands Mosaic, so ``ok`` is false and no
success line is printed off the chip.
"""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_smoke_in_child(model: str, platform: str, timeout: float,
                        cache_dir: str = None) -> dict:
    """run_smoke in a FRESH interpreter (this pytest process imported
    jax long ago, and the parent's jax-freedom is part of the contract);
    the child also reports whether the orchestration imported jax.
    ``cache_dir``: a compile cache of the smoke's own
    (``JAX_COMPILATION_CACHE_DIR``, which the program honours) — the
    smoke counts the entries its second process writes, and in the
    checkout's shared directory another test worker's compile lands in
    that count."""
    env = dict(os.environ)
    if cache_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    code = (
        "import json, sys, chip_smoke\n"
        f"r = chip_smoke.run_smoke({model!r}, {platform!r}, serve_s=12)\n"
        "r['parent_imported_jax'] = 'jax' in sys.modules\n"
        "print(json.dumps(r))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def tiny_cpu_smoke(tmp_path_factory):
    return _run_smoke_in_child(
        "tiny", "cpu", timeout=240,
        cache_dir=str(tmp_path_factory.mktemp("smoke_compile_cache")))


@pytest.mark.timeout(300)
def test_smoke_drives_the_main_path_and_keeps_the_parent_off_jax(
        tiny_cpu_smoke):
    r = tiny_cpu_smoke
    assert r["parent_imported_jax"] is False
    # One process per device-holding role, visible in the logs.
    assert r["platforms"] == {"leader": "cpu", "seeder": "cpu",
                              "dest": "cpu"}
    assert r["bytes_delivered"] == sum(b["bytes"]
                                       for b in r["blobs"].values())
    for blob in r["blobs"].values():
        assert blob["location"] == "HBM"
        assert blob["staged_on"] and all(
            d.startswith("cpu:") for d in blob["staged_on"])
        assert blob["staged_via"] == "incremental ingest"
    assert r["boot"]["kind"] == "full"
    assert "host assembly" not in r["boot"]["via"]
    assert r["flow_solver"] in ("native", "python")
    assert len(r["requests"]) == 3
    for req in r["requests"]:
        assert req["agree"] and len(req["tokens"]) == chip_smoke.GEN
        assert req["tokens"] == req["reference_tokens"]
    assert r["reference"]["ok"], r["reference"]
    assert r["device"]["platform"] == "cpu"
    assert r["versions"]["jax"]


@pytest.mark.timeout(300)
def test_smoke_drives_a_family_whose_layers_are_of_several_kinds(
        tmp_path_factory):
    """The tiny third family beside Llama: five blobs of four sizes over
    TCP to one destination that stages each by its kind, boots and
    serves what a second process's ``generate`` gives on the same blobs;
    the four-chip phase is ``run_pod``'s, which refuses the family."""
    r = _run_smoke_in_child(
        "tiny-lfm2", "cpu", timeout=240,
        cache_dir=str(tmp_path_factory.mktemp("smoke_lfm2_cache")))
    assert r["ok"] is False and "kernel check failed" in r["error"]  # the CPU
    assert len(r["blobs"]) == 5
    assert len({b["bytes"] for b in r["blobs"].values()}) == 4
    assert all(b["location"] == "HBM" for b in r["blobs"].values())
    assert r["boot"]["kind"] == "full"
    assert "host assembly" not in r["boot"]["via"]
    for req in r["requests"]:
        assert req["agree"] and req["tokens"] == req["reference_tokens"]
    assert r["reference"]["ok"], r["reference"]
    assert "cannot run 'tiny-lfm2' of the lfm2 family" in r["pod_refusal"]


def test_smoke_second_process_runs_from_the_compile_cache(tiny_cpu_smoke):
    cache = tiny_cpu_smoke["compile_cache"]
    second = cache["second_process"]
    assert second["dir"] == cache["dir"]
    assert second["shared_hits"] > 0 and second["shared_misses"] == 0
    assert second["shared_new_entries"] == 0
    assert cache["entries_after_one_chip"] >= cache["entries_before"]


def test_smoke_cannot_pass_off_the_chip(tiny_cpu_smoke):
    """No interpret-mode dry pass: off the TPU the public entry selects
    lax, the kernel phase fails, and so does the smoke."""
    r = tiny_cpu_smoke
    assert r["ok"] is False and r["claim"] is None
    for blk in ("512", "2048"):
        k = r["kernel"][blk]
        assert k["ok"] is False
        assert k["selected_pallas"] is False and k["interpret"] is True
        assert k["mosaic_custom_call"] is False
    assert "kernel check failed" in r["error"]


@pytest.mark.timeout(120)
def test_smoke_fails_fast_on_a_platform_that_does_not_exist():
    r = _run_smoke_in_child("tiny", "no_such_platform", timeout=100)
    assert r["ok"] is False and r["device"] is None
    assert "dest exited rc=" in r["error"]
    assert r["smoke_timings_s"]["wall"] < 90
    assert r["parent_imported_jax"] is False


def test_success_line_is_printed_only_on_success(monkeypatch, capsys):
    asked = []

    def fake(model, platform):
        asked.append((model, platform))
        return result

    monkeypatch.setattr(chip_smoke, "run_smoke", fake)
    result = {"ok": False, "device": None, "error": "no chip"}
    assert chip_smoke.main([]) == 1
    assert capsys.readouterr().out == ""
    result = {"ok": True, "device": {"platform": "tpu", "kind": "TPU v5 "
                                     "lite", "count": 1}, "claim": None,
              "model": "llama3-8b-d4"}
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    # The full report rides the line before; the LAST line is the
    # checker's contract, exactly these keys and nothing else.
    assert json.loads(lines[-2]) == result
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite",
                               "count": 1}}
    assert len(lines) == 2
    # __main__ asks for the full-width model on the TPU: nothing here can
    # land on the CPU by default.
    assert asked == [("llama3-8b-d4", "tpu")] * 2


@pytest.mark.timeout(120)
def test_smoke_fails_in_a_directory_that_holds_only_the_script(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=100)
    assert out.returncode != 0
    assert out.stdout == ""


@pytest.mark.timeout(180)
def test_pod_child_lands_four_seats_on_four_devices():
    """The four-chip phase's child on the virtual CPU mesh: four seats
    on four distinct devices, zero LayerMsg on the transport, stage
    boots, pod tokens equal to single-process generate."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--child",
         "pod", "tiny", "cpu"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=150)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["ok"], r
    assert r["distinct_devices"] == 4 and len(r["seats"]) == 4
    assert r["layer_msgs_on_transport"] == 0
    assert r["pod_tokens"] == r["reference_tokens"]
    assert {b["kind"] for b in r["boots"].values()} == {"stage"}


# ------------------------------------------------ one process per chip


def _args(*argv):
    from distributed_llm_dissemination_tpu.cli.main import build_parser

    return build_parser().parse_args(["-f", "x", *argv])


def _conf(**extra):
    from distributed_llm_dissemination_tpu.core.config import Config

    return Config.from_json({
        "Model": "tiny",
        "Nodes": [
            {"Id": 0, "Addr": "a:1", "IsLeader": True,
             "InitialLayers": {"2": {"0": {}}}},
            {"Id": 1, "Addr": "a:2", "InitialLayers": {"2": {"0": {}}}},
            {"Id": 2, "Addr": "a:3"},
        ],
        "Assignment": {"2": {"0": {}}},
        **extra})


def test_only_staging_booting_or_fabric_roles_hold_the_device():
    from distributed_llm_dissemination_tpu.cli.main import holds_device
    from distributed_llm_dissemination_tpu.core.config import get_node_conf

    conf = _conf()
    leader, seeder, dest = (get_node_conf(conf, i) for i in range(3))
    assert not holds_device(_args("-id", "0", "-hbm"), conf, leader)
    assert not holds_device(_args("-id", "1", "-boot", "none"), conf, seeder)
    assert holds_device(_args("-id", "1"), conf, seeder)  # boots by default
    assert holds_device(_args("-id", "2", "-hbm", "-boot", "none"), conf,
                        dest)
    assert not holds_device(_args("-id", "2", "-l"), conf, dest)
    fabric = _conf(Mesh={"AxisNames": ["nodes"], "AxisSizes": [3],
                         "Fabric": True},
                   Distributed={"Coordinator": "a:9"})
    assert holds_device(_args("-id", "0"), fabric, leader)


@pytest.mark.timeout(120)
def test_a_seeding_process_never_initialises_an_inherited_platform(
        tmp_path):
    """``cli.main -l`` fabricates real seeded blobs (jax.random) — and
    does so on the CPU even when the environment it inherits names a
    platform list that cannot initialise."""
    conf_path = tmp_path / "conf.json"
    conf_path.write_text(json.dumps({
        "Model": "tiny",
        "Nodes": [{"Id": 0, "Addr": "127.0.0.1:1", "IsLeader": True,
                   "Sources": {"2": 0},
                   "InitialLayers": {"2": {"0": {}, "4": {}}}}],
        "Assignment": {}}))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "no_such_platform"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, env.get("PYTHONPATH", "")])
    out = subprocess.run(
        [sys.executable, "-m",
         "distributed_llm_dissemination_tpu.cli.main", "-id", "0",
         "-f", str(conf_path), "-l", "-s", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=100)
    assert out.returncode == 0, out.stderr[-2000:]
    assert (tmp_path / "layers" / "0" / "4.layer").stat().st_size > 0


# ----------------------------------------- -hbm cannot end clean degraded


def test_hbm_staging_fallback_is_counted_and_fails_the_process(
        monkeypatch):
    """"Delivery beats staging" stays: a layer whose device landing fails
    is acked from host RAM.  But the fallback counts itself, and a
    process that was asked for -hbm reads the counters at exit."""
    import jax  # noqa: F401  (the device plane under test)

    from distributed_llm_dissemination_tpu.cli.main import (
        device_path_degradations,
    )
    from distributed_llm_dissemination_tpu.core.types import (
        LayerLocation,
        LayerMeta,
        LayerSrc,
    )
    from distributed_llm_dissemination_tpu.runtime import Node, ReceiverNode
    from distributed_llm_dissemination_tpu.transport import (
        InmemTransport,
        reset_registry,
    )
    from distributed_llm_dissemination_tpu.transport.messages import (
        AckMsg,
        LayerMsg,
    )

    assert device_path_degradations() == {}
    reset_registry()
    try:
        registry = {0: "deg_l", 1: "deg_r"}
        tl = InmemTransport("deg_l", addr_registry=registry)
        tr = InmemTransport("deg_r", addr_registry=registry)
        recv = ReceiverNode(Node(1, 0, tr), {}, start_loop=False,
                            stage_hbm=True)

        def no_device(src):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

        monkeypatch.setattr(recv._mover, "stage", no_device)
        payload = bytes(range(256)) * 8
        recv.handle_layer(LayerMsg(
            0, 5,
            LayerSrc(inmem_data=bytearray(payload), data_size=len(payload),
                     meta=LayerMeta(location=LayerLocation.INMEM)),
            len(payload)))
        ack = tl.deliver().get_nowait()
        assert isinstance(ack, AckMsg)
        assert ack.location == LayerLocation.INMEM  # delivered, from RAM
        assert device_path_degradations() == {
            "device.degraded.stage_inmem": 1}
        recv.close()
        tl.close()
        tr.close()
    finally:
        reset_registry()


def test_boot_result_says_how_it_assembled():
    """``BootResult.via`` is what a -hbm receiver reads to tell a
    device-path boot from a host assembly."""
    from distributed_llm_dissemination_tpu.core.types import (
        LayerLocation,
        LayerMeta,
        LayerSrc,
    )
    from distributed_llm_dissemination_tpu.models import serde
    from distributed_llm_dissemination_tpu.models.llama import CONFIGS
    from distributed_llm_dissemination_tpu.runtime.boot import (
        boot_from_layers,
    )

    cfg = CONFIGS["tiny"]
    layers = {}
    for b in range(serde.head_blob_id(cfg) + 1):
        data = serde.seeded_blob(cfg, b, 0)
        layers[b] = LayerSrc(inmem_data=bytearray(data),
                             data_size=len(data),
                             meta=LayerMeta(location=LayerLocation.INMEM))
    res = boot_from_layers(cfg, layers)
    assert res.kind == "full" and res.via == "host assembly"
