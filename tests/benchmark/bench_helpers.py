"""Helpers of the benchmark's own tests: a tiny-width copy of the
manifest in a temporary directory, so that nothing committed is touched
and every rehearsal runs on the CPU in seconds."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_FILE = {"hidden_size": 128, "num_attention_heads": 4,
             "num_key_value_heads": 2, "intermediate_size": 256,
             "vocab_size": 256, "num_hidden_layers": 4,
             "rope_theta": 500000.0, "rms_norm_eps": 1e-05}


def with_arch(config: dict, root: str = REPO) -> dict:
    """A configuration file's contents as ``Manifest.config`` hands them
    out: with the path of its architecture module under ``root``."""
    return dict(config, arch_file=os.path.join(
        root, "benchmark", "archs", config.get("arch", "llama") + ".py"))


TINY = with_arch(TINY_FILE)

# The second architecture: the program's routed variant, as files that
# only ever sit beside a temporary manifest (``add_second_arch``).
ARCH_MOE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "arch_moe")
MOE_MIXES = ("cold-raw", "cold-int8")  # a pod of it is not asked for

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}

# The CPU backend has no device plane in a trace: in a rehearsal the PjRt
# client's host threads stand in for it, so that the whole path runs.
# The stand-in lives here, never in the yardstick.
CPU_STAND_IN = {"plane": "/host:CPU", "ops": ["tf_XLAPjRtCpuClient"],
                "modules": "no such line", "plane_per_chip": False}


def decoded(config: dict, blob_id: int, blob, codec: str = "raw") -> dict:
    """``{leaf: float32 array}``: the harness's plain numpy decode of a
    wire blob, widened (a bfloat16 is the top half of a float32)."""
    import numpy as np

    from benchmark import fabricate

    return {name: (leaf.bits().astype(np.uint32) << 16).view(
        np.float32).reshape(leaf.shape)
        for name, leaf in fabricate.blob_leaves(config, blob_id, blob,
                                                codec).items()}


def traffic_mixes() -> list:
    return sorted(f[:-5] for f in os.listdir(os.path.join(
        REPO, "benchmark", "traffic")) if f.endswith(".json"))


def load_config(name: str) -> dict:
    """A committed configuration file, whether or not a cell uses it, as
    ``Manifest.config`` would hand it out."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        return with_arch(json.load(f))


def write_tiny_root(root, tag: str) -> str:
    """A manifest beside ``root`` whose cells are the committed ones at
    tiny width, renamed with ``tag`` so that parallel tests do not share
    an output directory.  Returns the manifest's path."""
    os.makedirs(os.path.join(root, "benchmark", "configs"), exist_ok=True)
    with open(os.path.join(root, "benchmark", "configs", "tiny.json"),
              "w") as f:
        json.dump(TINY_FILE, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        d = json.load(f)
    d["configs"] = [{"name": "tinycfg", "source": "tests",
                     "file": "benchmark/configs/tiny.json", "reduced": [],
                     "why": "tiny width for the CPU"}]
    # one cell for every committed traffic mix (whether or not a committed
    # cell uses it), with a serve window that a loaded test machine cannot
    # miss; the copies are found beside the manifest before the committed
    # files, like any new mix
    os.makedirs(os.path.join(root, "benchmark", "traffic"), exist_ok=True)
    old_cells = {w["name"]: w["traffic"] for w in d["workloads"]}
    d["workloads"] = []
    for mix in traffic_mixes():
        with open(os.path.join(REPO, "benchmark", "traffic",
                               mix + ".json")) as f:
            t = json.load(f)
        if "serve_window_s" in t:
            t["serve_window_s"] = 2.5
        with open(os.path.join(root, "benchmark", "traffic", mix + ".json"),
                  "w") as f:
            json.dump(t, f)
        d["workloads"].append({
            "name": f"{tag}.{mix}", "config": "tinycfg", "traffic": mix,
            "chips": 4 if t["entry"] == "cli.podrun" else 1,
            "why": "a committed traffic mix at tiny width"})
    for m in d["end_to_end"] + d["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [f"{tag}.{old_cells[w]}"
                              for w in m["workloads"]]
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(d, f)
    return path


def moe_config() -> dict:
    """The second architecture's tiny configuration as ``Manifest.config``
    would hand it out, its module found where it lies."""
    with open(os.path.join(ARCH_MOE, "tiny-moe.json")) as f:
        return dict(json.load(f), arch_file=os.path.join(ARCH_MOE, "moe.py"))


def add_second_arch(manifest: str, tag: str) -> None:
    """The second architecture as NEW FILES beside ``manifest`` (its
    module, its configuration) and new entries in it: a cell
    ``<tag>.moe.<mix>`` for each of ``MOE_MIXES``, reporting what the
    Llama cell of that mix reports."""
    root = os.path.dirname(manifest)
    os.makedirs(os.path.join(root, "benchmark", "archs"), exist_ok=True)
    shutil.copy(os.path.join(ARCH_MOE, "moe.py"),
                os.path.join(root, "benchmark", "archs"))
    shutil.copy(os.path.join(ARCH_MOE, "tiny-moe.json"),
                os.path.join(root, "benchmark", "configs"))
    with open(manifest) as f:
        d = json.load(f)
    d["configs"].append({"name": "tinymoe", "source": "tests", "reduced": [],
                         "file": "benchmark/configs/tiny-moe.json",
                         "why": "a second architecture at tiny width"})
    for mix in MOE_MIXES:
        d["workloads"].append({
            "name": f"{tag}.moe.{mix}", "config": "tinymoe", "traffic": mix,
            "chips": 1, "why": "a committed mix under a second architecture"})
    for m in d["end_to_end"] + d["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [f"{tag}.moe.{mix}" for mix in MOE_MIXES
                               if f"{tag}.{mix}" in m["workloads"]]
    with open(manifest, "w") as f:
        json.dump(d, f)


def rehearse(manifest: str, workload: str, *, stub: bool, trace: int = 0,
             seconds: float = 1.0, seed: int = 2147483659, devices: int = 4):
    """One whole run on the CPU in a process of its own; with ``stub``
    the no-TPU check is replaced (the only way to replace it)."""
    code = ("import sys; sys.path.insert(0, %r); from benchmark import run; "
            "%s sys.exit(run.main(sys.argv[1:]))"
            % (REPO, f"run.PLATFORM = 'cpu'; run.TRACE_SELECT = "
                     f"{CPU_STAND_IN!r};" if stub else ""))
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={devices}"))
    return subprocess.run(
        [sys.executable, "-c", code, "--manifest", manifest, "--workload",
         workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=170)


