#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the product's main path ONCE, through the entry points a user
calls, at the full width of one model the repo supports:

    seeders -> TCP -> ShardedLayerIngest (stream arm) -> device decode /
    streamed boot -> forward -> generate -> GenerateReqMsg

- ``cli.main`` leader + peer seeder (CPU-pinned byte servers) deliver the
  model's seeded blobs over loopback TCP, mode 3, to ONE cold destination
  that holds the chip (``-hbm -gen 8 -serve``); ``cli.genreq`` then asks
  it for tokens three times from an idle seat.
- After the server has exited, a second chip-holding process rebuilds the
  model from the SAME blobs (``seeded_blob``, made on the CPU exactly as
  the seeders made them), re-runs the served requests and a plain float32
  CPU forward, and compiles ``ops/flash_attention.block_attention`` with
  Mosaic at the 512 and 2048 blocks.  It runs the programs the server
  compiled, so it also shows the persistent compile cache working.
- With four or more devices, ``cli.podrun``'s ``run_pod`` delivers the
  same model over the device fabric to a four-stage mesh and the pod's
  decode must equal single-process ``generate``.

The parent process never imports jax: a parent that touched JAX would
hold the chip and every child that needs it would fail or hang.  It only
writes the config, starts processes, reads their logs and exits.  Every
wait is bounded, children are killed in a ``finally``, any phase that
fails fails the run, and nothing here falls back to the CPU.

Stdout is written only on success, and is two lines: the full report as
one JSON object, then — the LAST line — exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as JAX reported it.  Logs and the full report (success
or not) land in ``chiprun_out/chip_smoke/``.  Timings in the report are
smoke timings: they are not results and go in no performance record.
"""

from __future__ import annotations

import json
import os
import shutil
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")
PKG = "distributed_llm_dissemination_tpu"

SEED = 0
# One compiled decode serves the boot's -gen AND every request: the
# requests use the boot decode's shapes (16-token prompt, 8 new tokens).
GEN = 8
PROMPT_LEN = 16
PROMPTS = [[(37 * r + 11 * i + 5) % 251 for i in range(PROMPT_LEN)]
           for r in range(3)]
DEADLINE_S = 1150.0  # the whole smoke, compilation included

# Any of these in the destination's log means the device path quietly
# degraded to the host (runtime/receiver.py, boot.py, stream_boot.py,
# parallel/collectives.py): the run fails.
FALLBACK_LINES = (
    "HBM staging failed; acking host RAM",
    "ingest finalize failed; bulk staging instead",
    "incremental device ingest failed; will stage at completion",
    "device ingest unavailable for layer",
    "streamed assembly failed; bulk assembly instead",
    "streamed boot staging failed for blob; bulk assembly will cover it",
    "streamed staging still in flight at collect; boot falls back to "
    "bulk assembly",
    "fewer devices than shards; gathering on host instead of the mesh",
    "boot assembled on the host although -hbm staging was asked for",
    "post-boot decode failed",
    "model boot failed",
    "device path degraded under -hbm; exiting non-zero",
)

# Kernel agreement: the MXU truncates f32 matmul inputs to bf16 (~6e-3
# relative at these shapes, shared by the Pallas kernel and the lax
# oracle), so 2e-2 of the output's scale flags a real kernel defect.
KERNEL_TOL = 2e-2
# Logit agreement, bf16 forward on the device vs plain float32 on the
# CPU: bf16 keeps 8 mantissa bits (eps 2^-8 = 3.9e-3) and the error
# compounds through 4 layers + the head; 5e-2 relative L2 is ~12 eps.
LOGITS_TOL = 5e-2


class SmokeFailure(Exception):
    pass


_T0 = time.monotonic()


def _log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def _child_env(jax_platforms: str) -> dict:
    """A child's environment, built explicitly: the platform list is
    never inherited (this sandbox exports JAX_PLATFORMS=cpu, a chip
    machine may too)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME")}
    env["JAX_PLATFORMS"] = jax_platforms
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO, env.get("PYTHONPATH", "")) if p)
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _free_addrs(n: int) -> list:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [f"127.0.0.1:{s.getsockname()[1]}" for s in socks]
    finally:
        for s in socks:
            s.close()


def _json_lines(path: str) -> list:
    out = []
    if os.path.exists(path):
        with open(path, errors="replace") as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    out.append(rec)
    return out


def _tail(path: str, n: int = 1500) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()[-n:]
    except OSError:
        return ""


def _cache_entries(cache_dir: str) -> set:
    try:
        return {f for f in os.listdir(cache_dir) if f.endswith("-cache")}
    except OSError:
        return set()


class _Children:
    """Every process the smoke starts; all of them die with it."""

    def __init__(self):
        self.procs = {}
        self._files = []

    def start(self, name: str, argv: list, env: dict):
        out = open(os.path.join(OUT, f"{name}.out"), "wb")
        err = open(os.path.join(OUT, f"{name}.jsonl"), "wb")
        self._files += [out, err]
        p = subprocess.Popen([sys.executable, *argv], stdout=out,
                             stderr=err, env=env, cwd=REPO)
        self.procs[name] = p
        return p

    def wait(self, name: str, timeout: float) -> int:
        try:
            return self.procs[name].wait(timeout=max(1.0, timeout))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{name} still running after "
                               f"{timeout:.0f}s") from None

    def check_alive_or_clean(self) -> None:
        for name, p in self.procs.items():
            rc = p.poll()
            if rc not in (None, 0):
                raise SmokeFailure(
                    f"{name} exited rc={rc}: "
                    f"{_tail(os.path.join(OUT, name + '.jsonl'))}")

    def kill_all(self) -> None:
        for p in self.procs.values():
            if p.poll() is None:
                p.kill()
        for p in self.procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
        for f in self._files:
            f.close()


def _run_child(name: str, argv: list, env: dict, timeout: float) -> dict:
    """Run one of this file's own children to its end; its last stdout
    line is its JSON report."""
    try:
        with open(os.path.join(OUT, f"{name}.err"), "wb") as err:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--child",
                 *argv],
                env=env, cwd=REPO, timeout=max(1.0, timeout),
                stdout=subprocess.PIPE, stderr=err)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"{name} child exceeded {timeout:.0f}s") from None
    with open(os.path.join(OUT, f"{name}.out"), "wb") as f:
        f.write(proc.stdout)
    if proc.returncode != 0:
        raise SmokeFailure(
            f"{name} child exited rc={proc.returncode}: "
            f"{_tail(os.path.join(OUT, name + '.err'))}")
    try:
        return json.loads(proc.stdout.decode().strip().splitlines()[-1])
    except (ValueError, IndexError):
        raise SmokeFailure(f"{name} child printed no report") from None


# --------------------------------------------------------------- the parent


def run_smoke(model: str, platform: str, serve_s: float = 40.0) -> dict:
    """The whole smoke for ``model`` with the destination on JAX platform
    ``platform``; returns the result dict (``ok`` says whether every
    phase passed).  Both arguments are required: there is no environment
    switch and no default that could land on the CPU."""
    # JAX-free; decides ONCE where every child keeps compiled programs
    # (JAX_COMPILATION_CACHE_DIR if set from outside, else the one fixed
    # in-checkout path) — the children inherit it.
    from distributed_llm_dissemination_tpu.utils.env import (
        place_compile_cache,
    )

    cache_dir = place_compile_cache()
    t_start = time.monotonic()
    left = lambda: DEADLINE_S - (time.monotonic() - t_start)  # noqa: E731
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    result = {"ok": False, "device": None, "model": model,
              "platform_asked": platform,
              "compile_cache": {"dir": cache_dir}}
    kids = _Children()
    try:
        _one_chip_phase(model, platform, serve_s, kids, left, result)
        _reference_phase(model, platform, left, result)
        n_dev = result["device"]["count"]
        if result["pod_refusal"]:
            result["four_chip"] = f"skipped: {result['pod_refusal']}"
        elif n_dev >= 4:
            _log("four-chip phase: run_pod on a four-stage mesh")
            result["four_chip"] = _run_child(
                "pod", ["pod", model, platform], _child_env(platform),
                left())
            if not result["four_chip"].get("ok"):
                raise SmokeFailure(
                    f"four-chip phase failed: {result['four_chip']}")
        else:
            result["four_chip"] = f"skipped: {n_dev} devices"
        result["ok"] = True
    except SmokeFailure as e:
        result["error"] = str(e)
        _log(f"FAILED: {e}")
    finally:
        kids.kill_all()
        result["smoke_timings_s"] = dict(
            result.get("smoke_timings_s", {}),
            wall=round(time.monotonic() - t_start, 1))
        result["claim"] = None
        with open(os.path.join(OUT, "result.json"), "w") as f:
            json.dump(result, f, indent=1)
    return result


def _one_chip_phase(model, platform, serve_s, kids, left, result) -> None:
    desc = _run_child("describe", ["describe", model], _child_env("cpu"),
                      min(120.0, left()))
    blob_bytes = {int(b): n for b, n in desc["blobs"].items()}
    result["pod_refusal"] = desc["pod_refusal"]
    blobs = {str(b): {} for b in blob_bytes}
    addrs = _free_addrs(4)
    # physical_config()'s shape: leader and one peer seeder hold every
    # blob, one cold destination is assigned everything; seat 3 is idle
    # (the requester's address).
    conf = {
        "Model": model, "ModelSeed": SEED,
        "Nodes": [{"Id": i, "Addr": addrs[i], "NetworkBW": 10**10,
                   "IsLeader": i == 0, "Sources": {"1": 0},
                   "InitialLayers": {"1": blobs} if i < 2 else {}}
                  for i in range(4)],
        "Assignment": {"2": blobs},
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [1]},
    }
    conf_path = os.path.join(OUT, "smoke_topology.json")
    with open(conf_path, "w") as f:
        json.dump(conf, f, indent=1)

    cache = result["compile_cache"]
    cache["entries_before"] = len(_cache_entries(cache["dir"]))
    main = ["-m", f"{PKG}.cli.main", "-f", conf_path, "-m", "3",
            "-bw", "600"]
    _log(f"one-chip phase: {model}, {sum(blob_bytes.values())} wire bytes, "
         f"destination on {platform!r}")
    kids.start("leader", [*main, "-id", "0"], _child_env("cpu"))
    deadline = time.monotonic() + 60
    host, port = addrs[0].rsplit(":", 1)
    while True:  # the leader binds before it fabricates
        kids.check_alive_or_clean()
        try:
            socket.create_connection((host, int(port)), timeout=1).close()
            break
        except OSError:
            if time.monotonic() > deadline:
                raise SmokeFailure("leader never listened") from None
            time.sleep(0.2)
    kids.start("seeder", [*main, "-id", "1", "-boot", "none"],
               _child_env("cpu"))
    # The ONE chip-holding process: a missing chip is an initialisation
    # error here, never a CPU run.
    kids.start("dest", [*main, "-id", "2", "-hbm", "-gen", str(GEN),
                        "-serve", f"{serve_s:g}"], _child_env(platform))

    dest_out = os.path.join(OUT, "dest.out")
    boot_deadline = time.monotonic() + min(700.0, left())
    while "serving for" not in _tail(dest_out, 400):
        kids.check_alive_or_clean()
        if kids.procs["dest"].poll() is not None:
            raise SmokeFailure(
                "destination exited before serving: "
                f"{_tail(os.path.join(OUT, 'dest.jsonl'))}")
        if time.monotonic() > boot_deadline:
            raise SmokeFailure("destination never reached its serve window")
        time.sleep(0.25)
    t_serving = time.monotonic()
    _log("destination is serving; sending three requests")

    requests = []
    for i, prompt in enumerate(PROMPTS):
        name = f"genreq{i}"
        kids.start(name, ["-m", f"{PKG}.cli.genreq", "-f", conf_path,
                          "-node", "2", "-id", "3", "-n", str(GEN),
                          "-prompt", ",".join(map(str, prompt)),
                          "-t", "60"], _child_env("cpu"))
        rc = kids.wait(name, min(90.0, left()))
        lines = _tail(os.path.join(OUT, f"{name}.out")).strip().splitlines()
        try:
            rec = json.loads(lines[-1])
        except (ValueError, IndexError):
            rec = {}
        if rc != 0 or len(rec.get("tokens", ())) != GEN:
            raise SmokeFailure(f"request {i} failed rc={rc}: {rec} "
                               f"{_tail(os.path.join(OUT, name + '.jsonl'))}")
        requests.append({"prompt": prompt, "tokens": rec["tokens"]})
    if time.monotonic() - t_serving > serve_s:
        raise SmokeFailure("requests outlasted the serve window")

    for name in ("leader", "dest", "seeder"):
        rc = kids.wait(name, min(serve_s + 120.0, left()))
        if rc != 0:
            raise SmokeFailure(
                f"{name} exited rc={rc}: "
                f"{_tail(os.path.join(OUT, name + '.jsonl'))}")
    _log("one-chip phase: all node processes exited 0; reading logs")

    # ---- what the processes themselves recorded
    logs = {n: _json_lines(os.path.join(OUT, f"{n}.jsonl"))
            for n in ("leader", "seeder", "dest")}

    def first(name, message):
        return next((r for r in logs[name] if r.get("message") == message),
                    None)

    platforms = {}
    for name in logs:
        rec = first(name, "jax backend")
        if rec is None:
            raise SmokeFailure(f"{name} logged no jax backend")
        platforms[name] = rec["platform"]
    if platforms != {"leader": "cpu", "seeder": "cpu", "dest": platform}:
        raise SmokeFailure(f"one process per chip violated: {platforms}")

    bad = sorted({r["message"] for r in logs["dest"]
                  if r.get("message") in FALLBACK_LINES})
    if bad:
        raise SmokeFailure(f"destination fell off the device path: {bad}")

    staged = {r["layerID"]: r for r in logs["dest"]
              if r.get("message") == "layer staged to HBM"}
    final = (first("dest", "final layer placement") or {}).get("layers", {})
    per_blob = {}
    for b, nbytes in sorted(blob_bytes.items()):
        st, fin = staged.get(b), final.get(str(b), {})
        per_blob[str(b)] = {
            "bytes": nbytes,
            "location": fin.get("location"),
            "staged_on": (st or {}).get("devices", []),
            "staged_via": (st or {}).get("via"),
        }
        on = per_blob[str(b)]["staged_on"]
        if (fin.get("location") != "HBM" or fin.get("bytes") != nbytes
                or not on
                or any(d.split(":")[0] != platform for d in on)
                or any(d.split(":")[0] != platform
                       for d in fin.get("devices", []))):
            raise SmokeFailure(
                f"blob {b} did not end in HBM on a {platform} device: "
                f"{per_blob[str(b)]} final={fin}")

    boot = first("dest", "model booted from disseminated layers")
    if boot is None or boot.get("kind") != "full":
        raise SmokeFailure(f"no full boot in the destination's log: {boot}")
    if "host assembly" in boot.get("via", "host assembly"):
        raise SmokeFailure(f"boot assembled on the host: {boot}")
    decoded = first("dest", "decoded tokens after boot")
    if decoded is None or decoded.get("generated") != GEN:
        raise SmokeFailure(f"-gen {GEN} did not decode: {decoded}")

    leader_out = _tail(os.path.join(OUT, "leader.out"), 4000)
    timings = {}
    for key, label in (("ttd", "Time to deliver: "),
                       ("ttft", "Time to first token: ")):
        if label not in leader_out:
            raise SmokeFailure(f"leader printed no {label!r}")
        timings[key] = float(
            leader_out.split(label, 1)[1].split("s", 1)[0])

    solver = ("native" if first("leader", "job assignment calculated "
                                "(native)") else "python")
    if solver == "python" and not first("leader",
                                        "job assignment calculated"):
        raise SmokeFailure("leader logged no flow solve")

    result.update(
        bytes_delivered=sum(blob_bytes.values()),
        blobs=per_blob,
        boot={"kind": boot["kind"], "via": boot["via"]},
        platforms=platforms,
        requests=requests,
        flow_solver=solver,
        smoke_timings_s=timings,
    )
    cache["entries_after_one_chip"] = len(_cache_entries(cache["dir"]))


def _reference_phase(model, platform, left, result) -> None:
    """The second chip-holding process (the server has exited): the
    same-blob reference, the compile-cache evidence, the kernel check."""
    _log("reference phase: same-blob reference, cache hits, kernel check")
    served_path = os.path.join(OUT, "served.json")
    with open(served_path, "w") as f:
        json.dump(result["requests"], f)
    # The reference seeds on the CPU exactly as the seeders did, so this
    # child is given the CPU backend NEXT TO the asked platform; the
    # asked platform stays first (the default) and must initialise.
    plats = platform if platform == "cpu" else f"{platform},cpu"
    ref = _run_child("reference", ["reference", model, platform,
                                   served_path], _child_env(plats),
                     min(600.0, left()))
    cache = result["compile_cache"]
    cache["entries_after_reference"] = len(_cache_entries(cache["dir"]))
    cache["second_process"] = ref.pop("cache")
    result["device"] = ref.pop("device")
    result["versions"] = ref.pop("versions")
    for req, agree, want in zip(result["requests"], ref["served_agree"],
                                ref["reference_tokens"]):
        req["agree"] = agree
        req["reference_tokens"] = want
    result["reference"] = ref["logits"]
    result["kernel"] = ref["kernel"]
    if result["device"]["platform"] != platform:
        raise SmokeFailure(f"reference ran on {result['device']}")
    if not all(ref["served_agree"]):
        raise SmokeFailure(
            f"served tokens differ from the same-blob reference: "
            f"{result['requests']}")
    if not ref["logits"]["ok"]:
        raise SmokeFailure(f"logits disagree: {ref['logits']}")
    sp = cache["second_process"]
    if sp["dir"] != cache["dir"]:
        raise SmokeFailure(f"children disagree on the cache dir: {sp}")
    if not sp["shared_hits"] or sp["shared_new_entries"]:
        raise SmokeFailure(
            f"second process did not run from the compile cache: {sp}")
    bad = {k: v for k, v in ref["kernel"].items() if not v.get("ok")}
    if bad:
        raise SmokeFailure(f"block_attention kernel check failed: {bad}")


# ------------------------------------------------------------- the children
#
# Everything below imports jax and runs only in a child process.


def _child_describe(model: str) -> dict:
    """The model's blobs and their bytes (a blob's size depends on its
    kind of layer), and what ``run_pod`` says to its family: None, or the
    sentence it refuses it with."""
    from distributed_llm_dissemination_tpu.models import family, serde

    cfg = family.config(model)
    return {"blobs": {b: serde.blob_nbytes(cfg, b)
                      for b in range(serde.head_blob_id(cfg) + 1)},
            "pod_refusal": _pod_refusal(family, cfg)}


def _pod_refusal(family, cfg):
    """``cli.podrun.run_pod`` knows one family: the four-chip phase is
    no part of the smoke of another."""
    try:
        family.only(cfg, ("llama",), "chip_smoke's four-chip phase",
                    "it drives cli.podrun.run_pod, whose stage boots and "
                    "pod decode know Llama's block and K/V cache only")
    except family.FamilyNotSupported as e:
        return str(e)
    return None


def _device_report(jax) -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def _child_reference(model: str, platform: str, served_path: str) -> dict:
    from distributed_llm_dissemination_tpu.utils.env import (
        place_compile_cache,
    )

    cache_dir = place_compile_cache()  # process entry, before jax
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_dissemination_tpu.models import family, serde
    from distributed_llm_dissemination_tpu.models.generate import generate
    from distributed_llm_dissemination_tpu.models.llama import (
        forward,
        forward_jit,
    )
    from distributed_llm_dissemination_tpu.utils.provenance import (
        harness_hash,
    )

    events = {"hits": 0, "misses": 0}
    # Per hit, JAX reports (stored compile time - read) and the read;
    # their sum is the compile time stored with the entry, which JAX
    # truncates to whole seconds.
    saved, reads = [], []

    def on_event(event, **_):
        if event.endswith("/compilation_cache/cache_hits"):
            events["hits"] += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            events["misses"] += 1

    def on_duration(event, seconds, **_):
        if event.endswith("/compilation_cache/compile_time_saved_sec"):
            saved.append(seconds)
        elif event.endswith("/compilation_cache/cache_retrieval_time_sec"):
            reads.append(seconds)

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    report = {"device": _device_report(jax)}
    if report["device"]["platform"] != platform:
        raise SystemExit(f"default platform is {report['device']}, "
                         f"asked for {platform!r}")
    versions = {"jax": jax.__version__, "harness_hash": harness_hash()}
    try:
        import jaxlib
        versions["jaxlib"] = jaxlib.__version__
        import libtpu
        versions["libtpu"] = libtpu.__version__
    except ImportError:
        pass
    report["versions"] = versions

    cfg = family.config(model)
    dev = jax.devices()[0]
    cpu = jax.devices("cpu")[0]
    with open(served_path) as f:
        served = json.load(f)

    # The SAME blobs: seeded on the CPU, as the seeder processes did.
    with jax.default_device(cpu):
        blobs = {b: serde.seeded_blob(cfg, b, SEED)
                 for b in range(serde.head_blob_id(cfg) + 1)}
    host_params = serde.params_from_blobs(cfg, blobs)
    params = jax.device_put(host_params, dev)

    # ---- the programs the server compiled: boot forward, served decode
    before = _cache_entries(cache_dir)
    events.update(hits=0, misses=0)
    saved.clear()
    reads.clear()
    zeros = jnp.zeros((1, PROMPT_LEN), jnp.int32)
    boot_logits = jax.block_until_ready(forward_jit(params, zeros, cfg))
    want, agree = [], []
    for req in served:
        toks = generate(params, jnp.asarray([req["prompt"]], jnp.int32),
                        cfg, max_new=GEN)
        want.append([int(t) for t in np.asarray(jax.device_get(toks))[0]])
        agree.append(want[-1] == req["tokens"])
    report["cache"] = {
        "dir": cache_dir,
        "shared_hits": events["hits"],
        "shared_misses": events["misses"],
        "shared_new_entries": len(_cache_entries(cache_dir) - before),
        # JAX's default threshold persists only programs that took
        # >= 1 s to compile: how many of these hits it would have lost
        # (stored compile time 0 s).
        "shared_hits_compiled_under_1s": sum(
            1 for s, r in zip(saved, reads) if s + r < 0.5),
        "cache_read_s": round(sum(reads), 2),
    }
    report["reference_tokens"] = want
    report["served_agree"] = agree

    # ---- logits: the device's bf16 forward vs plain float32 on the CPU
    prompt = jnp.asarray([served[0]["prompt"]], jnp.int32)
    got = np.asarray(jax.device_get(forward_jit(params, prompt, cfg)),
                     np.float32)
    with jax.default_device(cpu):
        params32 = jax.tree.map(
            lambda a: jnp.asarray(a, jnp.float32), host_params)
        ref = np.asarray(jax.jit(forward, static_argnums=2)(
            params32, jax.device_put(prompt, cpu), cfg), np.float32)
    del params32
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    max_err = float(np.abs(got - ref).max())
    top2 = np.sort(np.partition(ref, -2, axis=-1)[..., -2:], axis=-1)
    stable = (top2[..., 1] - top2[..., 0]) > 4 * max_err
    same = got.argmax(-1) == ref.argmax(-1)
    finite = bool(np.isfinite(got).all()
                  and np.isfinite(np.asarray(boot_logits)).all())
    report["logits"] = {
        "shape": list(got.shape), "finite": finite,
        "rel_l2_vs_f32_cpu": rel, "tolerance": LOGITS_TOL,
        "max_abs_err": max_err,
        "greedy_ids_agree_where_stable":
            f"{int((same & stable).sum())}/{int(stable.sum())}",
        "ok": bool(finite and rel < LOGITS_TOL
                   and (same | ~stable).all()
                   and got.shape == (1, PROMPT_LEN, cfg.vocab)),
    }
    del params, host_params, blobs
    report["kernel"] = _kernel_check(jax, jnp, np)
    return report


def _kernel_check(jax, jnp, np) -> dict:
    """``block_attention`` — the public entry — compiled by Mosaic at the
    512 and 2048 blocks and compared with the lax oracle.  Off the TPU
    the entry selects lax (or interpret mode): that FAILS here, it is
    not a dry pass."""
    from distributed_llm_dissemination_tpu.ops import flash_attention as fa

    out = {}
    zero = jnp.float32(0.0)
    for blk, kvh in ((512, 2), (2048, 8)):
        rng = np.random.default_rng(blk)
        qg = jnp.asarray(rng.standard_normal((1, kvh, 4, blk, 128)),
                         jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, kvh, blk, 128)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, kvh, blk, 128)), jnp.float32)
        rec = {
            "selected_pallas": bool(fa._use_pallas(blk, blk, 128)),
            # what block_attention hands the kernel as ``interpret``
            "interpret": jax.default_backend() != "tpu",
        }
        try:
            lowered = jax.jit(fa.block_attention).lower(qg, k, v, zero, zero)
            rec["mosaic_custom_call"] = "tpu_custom_call" in lowered.as_text()
            pv, m, l = jax.block_until_ready(
                lowered.compile()(qg, k, v, zero, zero))
            pv_r, m_r, l_r = jax.block_until_ready(
                jax.jit(fa._block_attention_ref)(qg, k, v, zero, zero))
            scale = float(jnp.abs(pv_r).max())
            rec["rel_err_vs_lax"] = float(jnp.abs(pv - pv_r).max()) / scale
            rec["stats_close"] = bool(
                jnp.allclose(m, m_r, rtol=KERNEL_TOL, atol=KERNEL_TOL)
                and jnp.allclose(l, l_r, rtol=KERNEL_TOL, atol=KERNEL_TOL))
            rec["tolerance"] = KERNEL_TOL
            rec["ok"] = bool(rec["selected_pallas"] and not rec["interpret"]
                             and rec["mosaic_custom_call"]
                             and rec["rel_err_vs_lax"] < KERNEL_TOL
                             and rec["stats_close"])
        except Exception as e:  # noqa: BLE001 — a refusal fails the smoke
            rec.update(ok=False, error=repr(e)[:2000])
        out[str(blk)] = rec
    return out


def _child_pod(model: str, platform: str) -> dict:
    """Four seats on four chips in ONE process: ``cli.podrun``'s
    ``run_pod`` over the device fabric, then the pod's tokens against
    single-process ``generate`` on the same blobs."""
    from distributed_llm_dissemination_tpu.utils.env import (
        place_compile_cache,
    )

    place_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_llm_dissemination_tpu.cli.podrun import run_pod
    from distributed_llm_dissemination_tpu.core.config import Config
    from distributed_llm_dissemination_tpu.core.types import LayerLocation
    from distributed_llm_dissemination_tpu.models import serde
    from distributed_llm_dissemination_tpu.models.generate import generate
    from distributed_llm_dissemination_tpu.models.llama import CONFIGS
    from distributed_llm_dissemination_tpu.transport.inmem import (
        InmemTransport,
    )
    from distributed_llm_dissemination_tpu.transport.messages import LayerMsg
    from distributed_llm_dissemination_tpu.utils import logging as ulog

    ulog.configure(node="pod")
    report = {"ok": False, "device": _device_report(jax)}
    if report["device"]["platform"] != platform:
        raise SystemExit(f"default platform is {report['device']}")
    cfg = CONFIGS[model]
    head = serde.head_blob_id(cfg)
    if cfg.n_layers < 3:
        raise SystemExit("the four-seat partition needs >= 3 layers")
    every = {str(b): {} for b in range(head + 1)}
    cuts = [0, cfg.n_layers - 2, cfg.n_layers - 1, head + 1]
    conf = Config.from_json({
        "Model": model, "ModelSeed": SEED,
        "Nodes": [{"Id": i, "Addr": str(i), "IsLeader": i == 0,
                   "Sources": {"2": 0}, "NetworkBW": 10**10,
                   "InitialLayers": {"2": every} if i == 0 else {}}
                  for i in range(4)],
        # Seats 1-3 partition the model (the last also holds the head);
        # seat 0, the leader, seeds everything from its own chip.
        "Assignment": {str(i): {str(b): {}
                                for b in range(cuts[i - 1], cuts[i])}
                       for i in (1, 2, 3)},
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [4],
                 "PipelineAxis": "nodes", "Fabric": True},
    })

    layer_msgs = []
    real_send = InmemTransport.send

    def counting_send(self, dest, msg, *a, **kw):
        if isinstance(msg, LayerMsg):
            layer_msgs.append((dest, msg.layer_id))
        return real_send(self, dest, msg, *a, **kw)

    seats = {}

    def on_delivered(leader, receivers):
        placement = leader.placement
        seats[leader.node.my_id] = sorted(
            d.id for d in placement.devices_for_node(leader.node.my_id))
        for r in receivers:
            ids, bad = set(), []
            for lid, src in r.layers.items():
                if src.meta.location != LayerLocation.HBM:
                    bad.append(lid)
                if src.device_array is not None:
                    ids |= {d.id for d in src.device_array.devices()}
            for leaf in jax.tree.leaves(r.boot_result.params):
                ids |= {d.id for d in leaf.devices()}
            if bad:
                raise SystemExit(f"seat {r.node.my_id}: layers {bad} "
                                 "not in HBM")
            seats[r.node.my_id] = sorted(ids)
            report.setdefault("boots", {})[str(r.node.my_id)] = {
                "kind": r.boot_result.kind, "via": r.boot_result.via,
                "layers": list(r.boot_result.layer_ids)}

    InmemTransport.send = counting_send
    try:
        summary = run_pod(conf, mode=3, boot=model, gen=GEN,
                          on_delivered=on_delivered)
    finally:
        InmemTransport.send = real_send

    blobs = {b: serde.seeded_blob(cfg, b, SEED) for b in range(head + 1)}
    params = jax.tree.map(jnp.asarray, serde.params_from_blobs(cfg, blobs))
    want = generate(params, jnp.zeros((1, PROMPT_LEN), jnp.int32), cfg,
                    max_new=GEN)
    want = [int(t) for t in np.asarray(jax.device_get(want))[0]]
    flat = [i for ids in seats.values() for i in ids]
    report.update(
        seats={str(n): ids for n, ids in sorted(seats.items())},
        distinct_devices=len(set(flat)),
        layer_msgs_on_transport=len(layer_msgs),
        pod_tokens=summary.get("tokens"), reference_tokens=want,
        pod_forward=("pod_forward_s" in summary),
        smoke_timings_s={k: summary[k] for k in
                         ("ttd_s", "ttft_s", "pod_forward_s", "pod_decode_s")
                         if k in summary},
    )
    report["ok"] = bool(
        len(seats) == 4 and len(flat) == 4 and len(set(flat)) == 4
        and not layer_msgs and report["pod_forward"]
        and all(b["kind"] == "stage" for b in report["boots"].values())
        and summary.get("tokens") == want)
    return report


def verdict_line(result: dict) -> dict:
    """The last stdout line, to the checker's contract: EXACTLY the keys
    ``ok`` and ``device``, the device as JAX reported it to the child
    that held it.  Everything else rides the report line before it."""
    dev = result["device"]
    return {"ok": bool(result["ok"]),
            "device": {"platform": str(dev["platform"]),
                       "kind": str(dev["kind"]),
                       "count": int(dev["count"])}}


def main(argv: list) -> int:
    if argv[:1] == ["--child"]:
        child = {"describe": _child_describe, "reference": _child_reference,
                 "pod": _child_pod}[argv[1]]
        print(json.dumps(child(*argv[2:])), flush=True)
        return 0
    result = run_smoke("llama3-8b-d4", "tpu")
    if not result["ok"]:
        return 1  # and no result line
    print(json.dumps(result))  # the full report (also in result.json)
    print(json.dumps(verdict_line(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
