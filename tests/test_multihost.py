"""Multi-host mesh formation (parallel/multihost.py).

Covers both halves of the VERDICT ask: unit-tested rank/coordinator
derivation from the topology config, and a REAL 2-process CPU smoke run —
two OS processes join one JAX runtime via ``maybe_initialize`` and each
sees the other's devices (the reference's per-host process model,
/root/reference/cmd/main.go:113-146, lifted onto one device runtime).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from distributed_llm_dissemination_tpu.core import config as cfg
from distributed_llm_dissemination_tpu.parallel.multihost import (
    DEFAULT_COORDINATOR_PORT,
    derive_layout,
    maybe_initialize,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_conf(n_nodes=3, leader_addr="10.0.0.5:9080", distributed=None):
    d = {
        "Nodes": [
            {"Id": i, "Addr": leader_addr if i == 0 else f"10.0.0.{5+i}:9080",
             "IsLeader": i == 0}
            for i in range(n_nodes)
        ],
        "Assignment": {},
        "LayerSize": 1,
    }
    if distributed is not None:
        d["Distributed"] = distributed
    return cfg.Config.from_json(d)


# ------------------------------------------------------------- derivation


def test_layout_ranks_follow_sorted_node_ids():
    conf = make_conf(3)
    for rank, node in enumerate([0, 1, 2]):
        lay = derive_layout(conf, node)
        assert lay.process_id == rank
        assert lay.num_processes == 3


def test_layout_coordinator_defaults_to_leader_host():
    lay = derive_layout(make_conf(leader_addr="10.0.0.5:9080"), 1)
    assert lay.coordinator == f"10.0.0.5:{DEFAULT_COORDINATOR_PORT}"
    # A port-only leader addr (the reference's ":8080" style) falls back
    # to loopback — the single-host dev shape.
    lay = derive_layout(make_conf(leader_addr=":9080"), 1)
    assert lay.coordinator == f"127.0.0.1:{DEFAULT_COORDINATOR_PORT}"


def test_layout_explicit_coordinator_wins():
    conf = make_conf(distributed={"Coordinator": "coord.example:555"})
    assert derive_layout(conf, 2).coordinator == "coord.example:555"


def test_layout_unknown_node_rejected():
    with pytest.raises(ValueError, match="not in config"):
        derive_layout(make_conf(3), 99)


def test_maybe_initialize_single_host_is_noop():
    # No Distributed section -> None; single-node topology -> None (even
    # with the section present).  Neither touches jax.
    assert maybe_initialize(make_conf(3), 0) is None
    assert maybe_initialize(make_conf(1, distributed={}), 0) is None


def test_distributed_conf_parsing():
    conf = make_conf(distributed={})
    assert conf.distributed is not None
    assert conf.distributed.coordinator == ""
    conf = make_conf(distributed={"Coordinator": "h:1", "CpuCollectives": "gloo"})
    assert conf.distributed.cpu_collectives == "gloo"
    assert make_conf().distributed is None


# ---------------------------------------------------------- 2-process smoke


_CHILD = textwrap.dedent("""
    import json, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    from distributed_llm_dissemination_tpu.core import config as cfg
    from distributed_llm_dissemination_tpu.parallel.multihost import (
        maybe_initialize,
    )

    conf = cfg.Config.from_json(json.loads(sys.argv[1]))
    my_id = int(sys.argv[2])
    layout = maybe_initialize(conf, my_id)
    assert layout is not None
    print(json.dumps({
        "id": my_id,
        "process_id": layout.process_id,
        "local": len(jax.local_devices()),
        "global": len(jax.devices()),
    }), flush=True)
""")


def test_host_aligned_device_order_single_process():
    # Single process: the plain device list, untouched.
    import jax

    from distributed_llm_dissemination_tpu.parallel.multihost import (
        host_aligned_device_order,
    )

    conf = make_conf(3)
    assert host_aligned_device_order(conf, {2: {0: None}}) == list(jax.devices())


class _FakeDev:
    def __init__(self, process_index, i):
        self.process_index = process_index
        self.i = i

    def __repr__(self):
        return f"d{self.process_index}.{self.i}"


def _fake_pod(monkeypatch, n_proc, per_proc):
    import jax

    devs = [_FakeDev(p, i) for p in range(n_proc) for i in range(per_proc)]
    monkeypatch.setattr(jax, "process_count", lambda: n_proc)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devs)
    return devs


def test_host_aligned_leading_axis(monkeypatch):
    from distributed_llm_dissemination_tpu.parallel.multihost import (
        host_aligned_device_order,
    )

    _fake_pod(monkeypatch, 2, 1)
    conf = make_conf(2)
    conf.mesh = cfg.MeshConf(axis_names=["nodes"], axis_sizes=[2],
                             pipeline_axis="nodes")
    # Assignee is node 1 (process rank 1): stage 0 must hold ITS device.
    order = host_aligned_device_order(conf, {1: {0: None}})
    assert [d.process_index for d in order] == [1, 0]


def test_host_aligned_trailing_pipeline_axis(monkeypatch):
    import numpy as np

    from distributed_llm_dissemination_tpu.parallel.multihost import (
        host_aligned_device_order,
    )

    _fake_pod(monkeypatch, 2, 2)
    conf = make_conf(2)
    conf.mesh = cfg.MeshConf(axis_names=["tp", "nodes"], axis_sizes=[2, 2],
                             pipeline_axis="nodes")
    order = host_aligned_device_order(conf, {1: {0: None}})
    # make_mesh reshapes row-major to (tp=2, nodes=2): the slice along the
    # trailing 'nodes' axis at stage s must be one process's block.
    grid = np.asarray(order, dtype=object).reshape(2, 2)
    assert {d.process_index for d in grid[:, 0]} == {1}  # assignee's host
    assert {d.process_index for d in grid[:, 1]} == {0}


def test_host_aligned_rejects_stage_host_mismatch(monkeypatch):
    from distributed_llm_dissemination_tpu.parallel.multihost import (
        host_aligned_device_order,
    )

    _fake_pod(monkeypatch, 2, 2)
    conf = make_conf(2)
    conf.mesh = cfg.MeshConf(axis_names=["nodes"], axis_sizes=[4],
                             pipeline_axis="nodes")
    with pytest.raises(ValueError, match="one stage == one host"):
        host_aligned_device_order(conf, {1: {0: None}})


def test_host_aligned_reports_uneven_counts(monkeypatch):
    import jax

    from distributed_llm_dissemination_tpu.parallel.multihost import (
        host_aligned_device_order,
    )

    devs = [_FakeDev(0, 0), _FakeDev(0, 1), _FakeDev(1, 0)]
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devs)
    conf = make_conf(2)
    conf.mesh = cfg.MeshConf(axis_names=["nodes"], axis_sizes=[2],
                             pipeline_axis="nodes")
    with pytest.raises(ValueError, match=r"\{0: 2, 1: 1\}"):
        host_aligned_device_order(conf, {1: {0: None}})


def test_two_process_hbm_dissemination(free_port):
    """The full multi-host loop through the REAL CLI: two processes join
    one JAX runtime, the mesh's stages align to each node's host, and the
    receiver lands its delivered layers in (its own host's) device memory
    — the leader reports TTD, the receiver logs the HBM staging."""
    port = free_port()
    p0, p1 = free_port(), free_port()
    conf_path = os.path.join(REPO, ".pytest-2proc-hbm.json")
    conf_json = {
        "Nodes": [
            {"Id": 0, "Addr": f"127.0.0.1:{p0}", "IsLeader": True,
             "NetworkBW": 12500000000, "Sources": {"2": 0},
             "InitialLayers": {"2": {"0": {"LayerSize": 262144},
                                     "1": {"LayerSize": 262144}}}},
            {"Id": 1, "Addr": f"127.0.0.1:{p1}",
             "NetworkBW": 12500000000, "Sources": {"2": 0},
             "InitialLayers": {}},
        ],
        "Assignment": {"1": {"0": {}, "1": {}}},
        "LayerSize": 262144,
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [2],
                 "PipelineAxis": "nodes"},
        "Distributed": {"Coordinator": f"127.0.0.1:{port}",
                        "CpuCollectives": "gloo"},
    }
    with open(conf_path, "w") as f:
        json.dump(conf_json, f)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one device per process
    cli = [sys.executable, "-m", "distributed_llm_dissemination_tpu.cli.main",
           "-f", conf_path, "-m", "0", "-hbm"]
    try:
        recv = subprocess.Popen(cli + ["-id", "1"], stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, env=env, text=True)
        lead = subprocess.Popen(cli + ["-id", "0"], stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, env=env, text=True)
        try:
            lead_out, lead_err = lead.communicate(timeout=180)
            recv_out, recv_err = recv.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            lead.kill()
            recv.kill()
            raise
        assert lead.returncode == 0, f"leader failed:\n{lead_err[-3000:]}"
        assert recv.returncode == 0, f"receiver failed:\n{recv_err[-3000:]}"
        assert "Time to deliver" in lead_out
        assert "ready" in recv_out
        # The receiver really staged to device memory on its own host.
        assert "layer staged to HBM" in recv_err
        assert "global_devices\": 2" in lead_err.replace("'", '"') or \
            '"global_devices": 2' in lead_err
    finally:
        for p in (locals().get("recv"), locals().get("lead")):
            if p is not None and p.poll() is None:
                p.kill()
        if os.path.exists(conf_path):
            os.remove(conf_path)


def test_two_process_cpu_smoke(free_port):
    """Two real OS processes form one JAX runtime from the same config:
    each contributes its local CPU device; both see global=2."""
    port = free_port()
    conf_json = json.dumps({
        "Nodes": [
            {"Id": 0, "Addr": "127.0.0.1:9080", "IsLeader": True},
            {"Id": 1, "Addr": "127.0.0.1:9081"},
        ],
        "Assignment": {},
        "LayerSize": 1,
        "Distributed": {"Coordinator": f"127.0.0.1:{port}",
                        "CpuCollectives": "gloo"},
    })
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)  # one device per process, no virtual fan-out
    procs = [
        subprocess.Popen([sys.executable, "-c", _CHILD, conf_json, str(i)],
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         env=env, text=True)
        for i in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, f"child failed:\n{err}"
        outs.append(json.loads(out.strip().splitlines()[-1]))
    by_id = {o["id"]: o for o in outs}
    assert by_id[0]["process_id"] == 0 and by_id[1]["process_id"] == 1
    for o in outs:
        assert o["local"] == 1
        assert o["global"] == 2, f"devices not federated: {o}"


_TRAIN_CHILD = textwrap.dedent("""
    import json, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    from distributed_llm_dissemination_tpu.core import config as cfg
    from distributed_llm_dissemination_tpu.parallel.multihost import (
        maybe_initialize,
    )
    from distributed_llm_dissemination_tpu.models.llama import (
        CONFIGS, init_params,
    )
    from distributed_llm_dissemination_tpu.models.sharded import (
        build_adamw_train_step, example_batch, init_adamw_state,
        make_train_mesh, shard_params,
    )

    conf = cfg.Config.from_json(json.loads(sys.argv[1]))
    my_id = int(sys.argv[2])
    layout = maybe_initialize(conf, my_id)
    assert layout is not None
    n = len(jax.devices())
    assert n == 8, f"devices not federated: {n}"
    mcfg = CONFIGS["tiny"]
    mesh = make_train_mesh(n, mcfg)
    params = shard_params(init_params(mcfg, jax.random.key(0)), mesh, mcfg)
    opt = init_adamw_state(params)
    step = build_adamw_train_step(mcfg, mesh, lr=3e-3)
    inputs, targets = example_batch(mcfg, mesh)
    losses = []
    for _ in range(2):
        params, opt, loss = step(params, opt, inputs, targets)
        losses.append(round(float(loss), 6))
    print(json.dumps({"id": my_id, "global": n, "losses": losses}),
          flush=True)
""")


@pytest.mark.slow  # ~33 s wall: over the 30 s tier-1 per-test budget
def test_two_process_training_step(free_port):
    """TRAINING across processes: two OS processes join one runtime
    (4 virtual CPU devices each), build ONE global 8-device train mesh,
    and run AdamW steps whose gradient psums cross the process boundary
    (gloo) — both report identical, decreasing losses."""
    port = free_port()
    conf_json = json.dumps({
        "Nodes": [
            {"Id": 0, "Addr": "127.0.0.1:9082", "IsLeader": True},
            {"Id": 1, "Addr": "127.0.0.1:9083"},
        ],
        "Assignment": {},
        "LayerSize": 1,
        "Distributed": {"Coordinator": f"127.0.0.1:{port}",
                        "CpuCollectives": "gloo"},
    })
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _TRAIN_CHILD, conf_json, str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            env=env, text=True)
        for i in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, f"child failed:\n{err[-3000:]}"
        outs.append(json.loads(out.strip().splitlines()[-1]))
    assert outs[0]["losses"] == outs[1]["losses"]
    assert outs[0]["losses"][1] < outs[0]["losses"][0]
