#!/usr/bin/env python3
"""A resident seat of the benchmark: one process, many rounds.

``python3 benchmark/child.py <spec.json>`` — started by ``launch.py``,
never by hand.  The spec names the role (``seat``: a leader or a peer
seeder; ``dest``: the chip holder; ``requester``; ``pod``: all seats of a
pod in one process), the configuration, the traffic parameters, the seed
and the cores this process may use.  Commands arrive as JSON lines on
stdin and each is answered by one JSON line on the stdout the process was
started with; from then on file descriptors 1 and 2 belong to the
program and point at the current round's ``<seat>.out`` / ``<seat>.jsonl``.

What the child does to the program, and nothing more:

- registers the configuration (its architecture module's ``register``);
- answers ``core.config.create_layers`` with the blobs the harness made
  from ``--seed`` (made once, in set-up, and page-touched);
- remembers the node objects ``cli.main`` builds, so that a round can be
  closed and the delivered model read back;
- stamps every log record with ``CLOCK_MONOTONIC`` (one clock for all
  processes of a host).

Each round then goes through the normal entry points: ``cli.main.main``,
``cli.genreq.main``, ``cli.podrun.run_pod``.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import archs, fabricate  # noqa: E402  (numpy only)

PKG = "distributed_llm_dissemination_tpu"
# What the device may still hold when a round starts.  Every array is
# deleted between rounds, so what is left is the programs the runtime keeps
# loaded: on the v5e 14,616,064 B after a raw round, 11,496,960 B after
# int8, 7,822,336 B in the pod, the same to the byte before every counted
# round of every run made (over fifty).  The warm-up round may leave that much over the level
# before round 0; no later round may leave more than the warm-up did, to
# within less than the model's smallest weight matrix in any wire form.
PROGRAMS_SLACK_BYTES = 64 << 20
ROUND_SLACK_BYTES = 4 << 20


class StampedStream:
    """stderr with ``"mono"`` spliced into every JSON record."""

    def __init__(self, raw):
        self.raw = raw

    def write(self, text: str) -> int:
        if text.startswith("{") and text.endswith("}\n"):
            text = f'{text[:-2]}, "mono": {time.monotonic():.6f}}}\n'
        return self.raw.write(text)

    def flush(self) -> None:
        self.raw.flush()

    def __getattr__(self, name):
        return getattr(self.raw, name)


def pin(cores) -> dict:
    """Fence this process (every thread of it) onto ``cores``.  A refusal
    is reported, never fatal; a thread that ended since it was listed is
    no refusal (it left a leader wholly unfenced once, PR 26)."""
    if not cores:
        return {"cores": sorted(os.sched_getaffinity(0)), "fenced": False}
    try:
        for tid in os.listdir("/proc/self/task"):
            with contextlib.suppress(ProcessLookupError):
                os.sched_setaffinity(int(tid), cores)
        return {"cores": sorted(os.sched_getaffinity(0)), "fenced": True}
    except (OSError, ValueError) as e:
        return {"cores": sorted(os.sched_getaffinity(0)), "fenced": False,
                "refused": repr(e)}


class Child:
    def __init__(self, spec: dict):
        self.spec = spec
        self.config = spec["config"]
        self.arch = archs.of(self.config)
        self.traffic = spec["traffic"]
        self.seed = int(spec["seed"])
        self.codec = self.traffic.get("codec", "raw")
        self.ctl = os.fdopen(os.dup(1), "w")
        self.captured = []
        self.blobs = {}

    # ------------------------------------------------------------ plumbing

    def reply(self, **rec) -> None:
        self.ctl.write(json.dumps(rec) + "\n")
        self.ctl.flush()

    def redirect(self, prefix: str) -> None:
        """fd 1 and 2 → this round's files (C-level writers included)."""
        sys.stdout.flush()
        sys.stderr.flush()
        for fd, path in ((1, prefix + ".out"), (2, prefix + ".jsonl")):
            f = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
            os.dup2(f, fd)
            os.close(f)

    def install(self) -> None:
        """Everything that touches the program, in one place."""
        from distributed_llm_dissemination_tpu.utils.env import (
            place_compile_cache,
        )

        self.cache_dir = place_compile_cache()  # before jax is imported
        if self.spec.get("platforms"):
            os.environ["JAX_PLATFORMS"] = self.spec["platforms"]
        sys.stderr = StampedStream(sys.stderr)

        from distributed_llm_dissemination_tpu.core import config as pcfg

        self.model_name = self.spec["model_name"]
        self.forward = self.arch.register(self.config, self.model_name)

        def create_layers(my_conf, save_disk, storage_path=".", model="",
                          model_seed=0, model_codec="raw"):
            layers = {}
            for source_type, by_layer in my_conf.initial_layers.items():
                for lid in by_layer:
                    blob = self.blobs[lid]
                    src = pcfg.LayerSrc(
                        inmem_data=blob, fp="", data_size=len(blob), offset=0,
                        meta=pcfg.LayerMeta(
                            location=pcfg.LayerLocation.INMEM,
                            source_type=source_type,
                            limit_rate=my_conf.sources.get(source_type, 0)))
                    layers[lid] = src
            return layers

        pcfg.create_layers = create_layers

    def capture(self, module, *class_names) -> None:
        """Remember every instance the entry point builds of these node
        classes (a subclass in the entry module's namespace)."""
        for name in class_names:
            base = getattr(module, name)
            bucket = self.captured

            class Remembered(base):  # noqa: D401
                def __init__(self, *a, **kw):
                    bucket.append(self)
                    super().__init__(*a, **kw)

            Remembered.__name__ = base.__name__
            Remembered.__qualname__ = base.__qualname__
            setattr(module, name, Remembered)

    def close_captured(self) -> None:
        for node in self.captured:
            try:
                node.close()
            except Exception as e:  # noqa: BLE001 — a round's teardown
                print(f"close failed: {e!r}", file=sys.stderr)
        self.captured.clear()

    def make_blobs(self, blob_ids) -> float:
        t0 = time.monotonic()
        made = fabricate.make_blobs(
            self.config, blob_ids, self.seed, self.codec,
            threads=len(os.sched_getaffinity(0)))
        # The program's LayerSrc wants a bytearray-like it can slice and
        # send; a numpy uint8 array's memoryview serves (no copy).  The
        # arrays were just written, so every page is resident.
        self.blobs = {b: memoryview(a) for b, a in made.items()}
        return time.monotonic() - t0

    def do_expected(self, cmd) -> dict:
        """What this seat holds, digested whole.  After the window, so
        on every core: the fence has done its work."""
        t0 = time.monotonic()
        pin(self.spec.get("setup_cores"))
        ids = sorted(self.blobs)
        found = fabricate.in_threads(
            lambda b: dict(fabricate.expected_digests(
                self.config, b, self.blobs[b], self.codec),
                bytes=len(self.blobs[b])),
            ids, len(os.sched_getaffinity(0)))
        return {"expected": {str(b): d for b, d in zip(ids, found)},
                "seconds": time.monotonic() - t0}

    # ------------------------------------------------------------ the loop

    def serve(self) -> int:
        handlers = {name[3:]: getattr(self, name) for name in dir(self)
                    if name.startswith("do_")}
        for line in sys.stdin:
            cmd = json.loads(line)
            if cmd["cmd"] == "exit":
                self.reply(ok=True)
                return 0
            try:
                self.reply(ok=True, **(handlers[cmd["cmd"]](cmd) or {}))
            except SystemExit as e:
                self.reply(ok=False, error=f"SystemExit: {e}")
            except Exception as e:  # noqa: BLE001 — reported to the parent
                import traceback

                traceback.print_exc()
                self.reply(ok=False, error=repr(e))
        return 0


# ---------------------------------------------------------------- the roles


class Seat(Child):
    """A leader or a peer seeder: a JAX-on-the-CPU byte server."""

    def do_setup(self, cmd) -> dict:
        self.install()
        self.cli_main = importlib.import_module(PKG + ".cli.main")
        self.capture(self.cli_main, "FlowRetransmitLeaderNode",
                     "FlowRetransmitReceiverNode")
        wide = pin(self.spec.get("setup_cores"))
        fab_s = self.make_blobs(self.spec["holds"])
        fence = pin(self.spec.get("cores"))
        return {"fabricate_s": fab_s, "fence": fence, "setup_cores": wide}

    def do_round(self, cmd) -> dict:
        self.redirect(cmd["prefix"])
        try:
            rc = self.cli_main.main(cmd["argv"])
        finally:
            self.close_captured()
            from distributed_llm_dissemination_tpu.utils import trace

            trace.reset_run()
        return {"rc": rc}


class Requester(Child):
    """The idle seat: waits for the destination's serve window, sends the
    prompts one after another, stamps each answer."""

    def do_setup(self, cmd) -> dict:
        self.genreq = importlib.import_module(PKG + ".cli.genreq")
        # Parsing a topology with a ModelCodec imports the codec table
        # (and JAX with it): seconds, once — here, not in a round.
        os.environ["JAX_PLATFORMS"] = "cpu"
        importlib.import_module(PKG + ".models.quant")
        sys.stderr = StampedStream(sys.stderr)
        return {"fence": pin(self.spec.get("cores"))}

    def do_round(self, cmd) -> dict:
        import io

        self.redirect(cmd["prefix"])
        deadline = time.monotonic() + cmd["timeout"]
        seen = 0
        while True:  # the destination prints "serving for" when it is up
            try:
                with open(cmd["watch"], "rb") as f:
                    f.seek(seen)
                    chunk = f.read()
            except OSError:
                chunk = b""
            if b"serving for" in chunk:
                break
            seen += max(0, len(chunk) - 16)
            if time.monotonic() > deadline:
                return {"rc": 1, "error": "destination never served"}
            if os.path.exists(cmd["cancel"]):
                return {"rc": 1, "error": "round cancelled: a seat failed"}
            time.sleep(0.002)
        t_serving = time.monotonic()
        answers = []
        for prompt in cmd["prompts"]:
            out = io.StringIO()
            with contextlib.redirect_stdout(out):
                rc = self.genreq.main([
                    "-f", cmd["conf"], "-node", str(cmd["node"]),
                    "-id", str(cmd["id"]), "-n", str(cmd["tokens"]),
                    "-prompt", ",".join(map(str, prompt)), "-t", "60"])
            t = time.monotonic()
            try:
                rec = json.loads(out.getvalue().strip().splitlines()[-1])
            except (ValueError, IndexError):
                rec = {"error": out.getvalue()[-500:]}
            answers.append({"rc": rc, "mono": t,
                            "tokens": rec.get("tokens"),
                            "error": rec.get("error")})
        return {"rc": 0, "serving_mono": t_serving, "answers": answers}


class DeviceHolder(Child):
    """What the destination and the pod share: they hold the chips."""

    kept = None
    anchor_mono = None
    level = None  # bytes_in_use after the warm-up round and its teardown

    def devices(self) -> dict:
        """The devices as JAX reports them to this process.  It was told
        the platform (never inherited, never a fallback): none there is a
        failure."""
        try:
            devs = self.jax.local_devices()
        except RuntimeError as e:
            raise SystemExit(
                f"needs a {self.spec.get('platforms')} device; JAX found "
                f"none: {str(e).splitlines()[0]}") from None
        return {"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs)}

    def memory(self, key: str) -> list:
        out = []
        for d in self.jax.local_devices():
            stats = d.memory_stats() or {}
            out.append(int(stats.get(key, 0)))
        return out

    def bytes_in_use(self) -> int:
        """The fullest device's ``bytes_in_use``; where the backend keeps
        no such statistic (the CPU, in rehearsals) the bytes of the live
        arrays, which is what it would count."""
        if self.jax.local_devices()[0].memory_stats() is None:
            return sum(int(a.nbytes) for a in self.jax.live_arrays())
        return max(self.memory("bytes_in_use"))

    def make_cold(self) -> int:
        """Close and drop the last round's node, free every device buffer
        (wire blobs, params, whatever a cache kept), and say what the
        device still holds."""
        self.kept = None
        self.close_captured()
        gc.collect()
        # The program's process-wide pools (tcp-evloop, data-rx-*) keep
        # their last job, and through it the closed node, alive.  A new
        # process would hold nothing: delete every device buffer that is
        # left, whoever still points at it.
        for a in self.jax.live_arrays():
            a.delete()
        return self.bytes_in_use()

    def not_cold(self, before: int):
        """Why a round may not start with ``before`` bytes in use, or
        None.  Before the warm-up round nothing has run; before the first
        counted round the warm-up's programs are loaded (``level``)."""
        if self.level is None:
            limit = self.baseline + PROGRAMS_SLACK_BYTES
            if before > self.baseline:
                self.level = before
        else:
            limit = self.level + ROUND_SLACK_BYTES
        if before <= limit:
            return None
        held = sorted(((a.nbytes, str(a.dtype), tuple(a.shape))
                       for a in self.jax.live_arrays()), reverse=True)[:5]
        return (f"device not cold: {before} bytes in use, {self.baseline} "
                f"before round 0, {self.level} after the warm-up; largest "
                f"live arrays {held}")

    @contextlib.contextmanager
    def tracing(self, trace_dir):
        """Trace one round: the profiler around it, and inside an
        annotation whose start is also read on CLOCK_MONOTONIC (the pair
        maps the host's phases onto the trace's clock).  No Python
        tracer: it costs more than the round."""
        if not trace_dir:
            yield
            return
        prof = self.jax.profiler
        opts = prof.ProfileOptions()
        opts.python_tracer_level = 0
        prof.start_trace(trace_dir, profiler_options=opts)
        try:
            self.anchor_mono = time.monotonic()
            with prof.TraceAnnotation("bench.round"):
                yield
        finally:
            prof.stop_trace()

    def do_reduce(self, cmd) -> dict:
        from benchmark import xplane

        return xplane.reduce_dir(cmd["trace_dir"], cmd.get("phases"),
                                 self.anchor_mono, cmd["working"],
                                 cmd.get("select"))

    def read_blobs(self, held: dict) -> dict:
        """Whole blobs read back from the device and digested by the
        harness's own hashlib.  ``held[b]`` is ``("leaves", boot)`` with
        ``boot`` the boot result whose resident params hold its decoded
        leaves (the architecture module finds each), or
        ``("wire", array)`` where the model keeps the wire blob itself."""
        import numpy as np

        def one(b):
            kind, what = held[b]
            if kind == "wire":
                return {"wire": fabricate.digest([np.asarray(what)])}
            return {"leaves": fabricate.digest(
                np.asarray(self.arch.leaf(what, b, name))
                for name, _ in fabricate.blob_specs(self.config, b))}

        ids = sorted(held)
        found = fabricate.in_threads(one, ids, len(os.sched_getaffinity(0)))
        return {str(b): rec for b, rec in zip(ids, found)}


class Dest(DeviceHolder):
    """The one process that holds the chip.  Each round takes
    ``cli.main``'s receiver path afresh; in between every device buffer is
    dropped, and the round may start only when the device is as empty as
    it was before round 0."""

    def do_setup(self, cmd) -> dict:
        self.install()
        fence = pin(self.spec.get("cores"))
        import jax

        cli_main = importlib.import_module(PKG + ".cli.main")

        self.jax, self.cli_main = jax, cli_main
        self.capture(cli_main, "FlowRetransmitReceiverNode")
        self.device = self.devices()
        self.baseline = self.bytes_in_use()
        return {"device": self.device, "fence": fence,
                "baseline_bytes": self.baseline, "cache_dir": self.cache_dir}

    def do_round(self, cmd) -> dict:
        before = self.make_cold()
        why = self.not_cold(before)
        if why:
            return {"rc": 1, "cold": False, "bytes_before": before,
                    "error": why}
        self.redirect(cmd["prefix"])
        from distributed_llm_dissemination_tpu.utils import trace

        trace.reset_run()
        t0 = time.monotonic()
        with self.tracing(cmd.get("trace_dir")):
            rc = self.cli_main.main(cmd["argv"])
        t1 = time.monotonic()
        # The node stays alive until the next round (or the read-back).
        self.kept = self.captured[-1] if self.captured else None
        return {"rc": rc, "cold": True, "bytes_before": before,
                "bytes_after": self.bytes_in_use(),
                "peak_bytes": max(self.memory("peak_bytes_in_use")),
                "main_mono": [t0, t1],
                "counters": {k: v for k, v in trace.counter_totals().items()
                             if k.startswith("device.degraded.")}}

    # ---- after the window: read-back and the plain reference

    def do_readback(self, cmd) -> dict:
        """Every leaf of the delivered model, whole."""
        t0 = time.monotonic()
        node = self.kept
        if node is None or node.boot_result is None:
            raise RuntimeError("no booted node to read back")
        n_blobs = fabricate.model_dims(self.config)["layers"] + 1
        held = {b: ("leaves", node.boot_result) for b in range(n_blobs)}
        return {"got": self.read_blobs(held),
                "seconds": time.monotonic() - t0,
                "placement": node.layer_placement()}

    def do_reference(self, cmd) -> dict:
        """The program's forward on the DELIVERED params against the plain
        float32 reference on the same blobs, teacher-forced over the
        served tokens."""
        t0 = time.monotonic()
        import numpy as np

        from benchmark import reference

        jax = self.jax
        tokens = np.asarray(cmd["tokens"], np.int32)  # prompt + served
        inputs = tokens[:, :-1]
        got = np.asarray(jax.device_get(self.forward(
            self.kept.boot_result, jax.numpy.asarray(inputs))), np.float32)
        self.make_cold()
        t1 = time.monotonic()
        n_blobs = fabricate.model_dims(self.config)["layers"] + 1
        blobs = fabricate.make_blobs(
            self.config, range(n_blobs), self.seed, self.codec,
            threads=len(os.sched_getaffinity(0)))
        ref = reference.logits(
            self.config, inputs,
            lambda b: fabricate.blob_leaves(self.config, b, blobs[b],
                                            self.codec))
        verdict = reference.compare(got, ref, tokens, cmd["prompt_len"],
                                    cmd["tolerance"])
        verdict.update(system_forward_s=t1 - t0,
                       reference_s=time.monotonic() - t1)
        return verdict


class Pod(DeviceHolder):
    """Every seat of a pod in ONE process over the device fabric:
    ``cli.podrun.run_pod`` per round."""

    def do_setup(self, cmd) -> dict:
        self.install()
        import jax

        podrun = importlib.import_module(PKG + ".cli.podrun")
        from distributed_llm_dissemination_tpu.utils import logging as ulog

        self.jax, self.podrun = jax, podrun
        ulog.configure(node="pod")
        # run_pod drops the logits of its one pipelined forward; keep
        # them (on the device) for the comparison with the reference.
        pp_serve = importlib.import_module(PKG + ".runtime.pp_serve")
        real_forward = pp_serve.pod_forward

        def pod_forward(*a, **kw):
            served = real_forward(*a, **kw)
            self.pod_logits = served[0] if served is not None else None
            return served

        pp_serve.pod_forward = pod_forward
        self.pod_logits = None
        self.device = self.devices()
        fab_s = self.make_blobs(self.spec["holds"])
        self.baseline = self.bytes_in_use()
        return {"device": self.device, "fabricate_s": fab_s,
                "fence": pin(None), "baseline_bytes": self.baseline,
                "cache_dir": self.cache_dir}

    def do_round(self, cmd) -> dict:
        before = self.make_cold()  # run_pod closed its own nodes
        why = self.not_cold(before)
        if why:
            return {"rc": 1, "cold": False, "bytes_before": before,
                    "error": why}
        self.redirect(cmd["prefix"])
        from distributed_llm_dissemination_tpu.core.config import Config
        from distributed_llm_dissemination_tpu.utils import trace

        trace.reset_run()
        with open(cmd["conf"]) as f:
            conf = Config.from_json(json.load(f))
        harvest = {}

        def on_delivered(leader, receivers):
            harvest["decoded_mono"] = time.monotonic()
            from distributed_llm_dissemination_tpu.core.types import (
                LayerLocation,
            )

            harvest["not_hbm"] = [
                [r.node.my_id, lid] for r in receivers
                for lid, src in r.layers.items()
                if src.meta.location != LayerLocation.HBM]
            harvest["boots"] = {str(r.node.my_id): {
                "kind": r.boot_result.kind, "via": r.boot_result.via}
                for r in receivers if r.boot_result is not None}
            # what each seat holds stays referenced (no copy, no time)
            # until the next round or the read-back after the window
            self.kept = [(r.boot_result,
                          {b: getattr(src, "device_array", None)
                           for b, src in r.layers.items()})
                         for r in receivers]
            if self.pod_logits is not None:
                import numpy as np

                self.last_logits = np.asarray(
                    self.jax.device_get(self.pod_logits), np.float32)
                self.pod_logits = None

        with self.tracing(cmd.get("trace_dir")):
            summary = self.podrun.run_pod(
                conf, mode=3, boot=self.model_name, gen=cmd["tokens"],
                on_delivered=on_delivered)
        for k in ("telemetry", "collective_cache"):
            summary.pop(k, None)
        return {"rc": 0, "cold": True, "bytes_before": before,
                "peak_bytes": max(self.memory("peak_bytes_in_use")),
                "summary": summary, **harvest}

    def do_readback(self, cmd) -> dict:
        """Every layer blob whole from its stage's resident params; the
        head blob, which the pod keeps in its wire form, whole too."""
        t0 = time.monotonic()
        held = {}
        for res, wires in self.kept or ():
            staged = list(res.layer_ids) if res is not None else []
            for b, wire in wires.items():
                if b in staged:
                    held[b] = ("leaves", res)
                elif wire is not None:
                    held[b] = ("wire", wire)
        return {"got": self.read_blobs(held),
                "seconds": time.monotonic() - t0}

    def do_reference(self, cmd) -> dict:
        t0 = time.monotonic()
        import numpy as np

        from benchmark import reference

        self.make_cold()
        tokens = np.asarray(cmd["tokens"], np.int32)
        ref = reference.logits(
            self.config, tokens[:, :-1],
            lambda b: fabricate.blob_leaves(self.config, b, self.blobs[b],
                                            self.codec))
        verdict = reference.compare(getattr(self, "last_logits", None), ref,
                                    tokens, cmd["prompt_len"],
                                    cmd["tolerance"])
        verdict["reference_s"] = time.monotonic() - t0
        return verdict


ROLES = {"seat": Seat, "dest": Dest, "requester": Requester, "pod": Pod}


def main(argv: list) -> int:
    with open(argv[0]) as f:
        spec = json.load(f)
    return ROLES[spec["role"]](spec).serve()


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
