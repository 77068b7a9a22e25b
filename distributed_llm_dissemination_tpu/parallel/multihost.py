"""Multi-host mesh formation: jax.distributed wiring from the topology.

The reference scales by one OS process per host, wired by the JSON config
(``/root/reference/cmd/main.go:113-146``) — each process only ever talks
TCP.  A TPU pod needs one more layer: every per-host process must join ONE
JAX runtime (``jax.distributed.initialize``) so ``jax.devices()`` spans the
pod and a configured Mesh can place stages across hosts.  This module
derives that wiring from the same JSON topology (node list order → process
rank, leader's host → coordinator), so multi-host runs need no extra
flags — the config that describes the cluster also forms the mesh.

Single-host runs (no ``Distributed`` section) are a clean no-op.  On CPU
backends cross-process collectives need gloo; the init flips
``jax_cpu_collectives_implementation`` automatically so the 2-process CPU
smoke deployment (tests/test_multihost.py) and a real TPU pod share one
code path.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from ..core.config import Config
from ..core.types import NodeID
from ..utils.logging import log

# JAX's own default coordinator port, reused when the config names none.
DEFAULT_COORDINATOR_PORT = 8476


@dataclasses.dataclass
class ProcessLayout:
    """One node-process's place in the pod-wide JAX runtime."""

    coordinator: str
    num_processes: int
    process_id: int


def derive_layout(conf: Config, my_id: NodeID) -> ProcessLayout:
    """Map this node to a jax.distributed process rank.

    Rank = the node's position in the id-sorted node list (stable across
    hosts: every process derives the same order from the same config).
    Coordinator = the configured ``Distributed.Coordinator``, else the
    leader node's host on JAX's default coordinator port — the leader host
    is already the one address every node must reach."""
    ids = sorted(nc.id for nc in conf.nodes)
    if my_id not in ids:
        raise ValueError(f"node {my_id} not in config nodes {ids}")
    coordinator = ""
    if conf.distributed is not None:
        coordinator = conf.distributed.coordinator
    if not coordinator:
        from ..core.config import get_leader_conf

        leader_addr = get_leader_conf(conf).addr
        host = leader_addr.rsplit(":", 1)[0] if ":" in leader_addr else leader_addr
        coordinator = f"{host or '127.0.0.1'}:{DEFAULT_COORDINATOR_PORT}"
    return ProcessLayout(
        coordinator=coordinator,
        num_processes=len(ids),
        process_id=ids.index(my_id),
    )


def host_aligned_device_order(conf: Config, assignment) -> list:
    """Global device list reordered so pipeline-stage blocks follow node
    locality: stage i's devices are the ones owned by the process that
    runs the node mapped to stage i.

    On a multi-host mesh, ``jax.devices()`` comes back in process order —
    but stage order is semantic (contiguous layers on consecutive stages,
    ``mesh.ranked_assignees``), and node id ↔ process rank follows the
    id-sorted node list (``derive_layout``).  A mesh built over the raw
    device order would hand node N a stage whose devices live on some
    other host, and every ``device_put`` of a delivered layer would fail.
    Feeding THIS order to ``make_mesh`` makes each node's stage locally
    addressable, so ``-hbm`` works across hosts.

    Works for any pipeline-axis position: the order returned is the
    row-major flattening of a device array whose index s along the
    pipeline axis is exactly process-rank-of-stage-s's device block — so
    ``make_mesh``'s plain reshape reproduces the alignment.  Requires one
    pipeline stage's device count to equal one process's (stage ↔ host,
    the TPU-VM shape); single-process runs return the plain device
    list."""
    import jax

    if jax.process_count() <= 1 or conf.mesh is None:
        return list(jax.devices())
    import numpy as np

    from .mesh import ranked_assignees

    shape = tuple(conf.mesh.axis_sizes)
    names = list(conf.mesh.axis_names)
    k = names.index(conf.mesh.pipeline_axis)
    n_stages = shape[k]
    per_stage = int(np.prod(shape)) // n_stages

    ids = sorted(nc.id for nc in conf.nodes)
    by_proc: dict = {}
    for d in jax.devices():
        by_proc.setdefault(d.process_index, []).append(d)
    counts = {rank: len(devs) for rank, devs in by_proc.items()}
    if len(set(counts.values())) != 1:
        raise ValueError(
            f"uneven devices per process {counts}: host-aligned stages "
            "need a uniform TPU-VM shape"
        )
    per_proc = next(iter(counts.values()))
    if per_stage != per_proc:
        raise ValueError(
            f"one pipeline stage spans {per_stage} devices but each "
            f"process owns {per_proc}: shape the mesh so one stage == "
            f"one host (mesh {dict(zip(names, shape))}, "
            f"{len(by_proc)} processes)"
        )
    staged = ranked_assignees(assignment)
    stage_nodes = staged + [n for n in ids if n not in set(staged)]
    if n_stages > len(stage_nodes):
        raise ValueError(
            f"mesh has {n_stages} pipeline stages but only "
            f"{len(stage_nodes)} configured nodes to own them"
        )
    blocks = [by_proc[ids.index(node_id)] for node_id in stage_nodes[:n_stages]]
    rest_shape = shape[:k] + shape[k + 1 :]
    arr = np.empty((n_stages, per_stage), dtype=object)
    for s, block in enumerate(blocks):
        arr[s] = block
    arr = np.moveaxis(arr.reshape((n_stages,) + rest_shape), 0, k)
    return list(arr.reshape(-1))


def maybe_initialize(conf: Config, my_id: NodeID) -> Optional[ProcessLayout]:
    """Join the pod-wide JAX runtime when the config asks for one.

    Returns the layout when ``jax.distributed`` was initialized, ``None``
    for the single-host fallback (no ``Distributed`` section, or a
    single-node topology).  Must run before the first JAX backend use in
    the process — the CLI calls it right after parsing the config."""
    if conf.distributed is None or len(conf.nodes) < 2:
        return None
    layout = derive_layout(conf, my_id)
    import jax

    if conf.distributed.cpu_collectives:
        try:
            jax.config.update("jax_cpu_collectives_implementation",
                              conf.distributed.cpu_collectives)
        except (ValueError, RuntimeError) as e:
            log.warn("couldn't set cpu collectives", err=repr(e))
    log.info("joining pod-wide jax runtime",
             coordinator=layout.coordinator,
             process_id=layout.process_id,
             num_processes=layout.num_processes)
    jax.distributed.initialize(
        coordinator_address=layout.coordinator,
        num_processes=layout.num_processes,
        process_id=layout.process_id,
    )
    log.info("pod-wide jax runtime up",
             local_devices=len(jax.local_devices()),
             global_devices=len(jax.devices()))
    return layout


def maybe_shutdown() -> None:
    """Leave the pod-wide JAX runtime in an orderly way at process exit.

    ``jax.distributed.initialize`` starts C++ service/heartbeat threads
    that interpreter teardown destroys while still joinable — an
    occasional ``std::terminate`` (SIGABRT) on an otherwise-successful
    run.  Shutting the client down first joins them.  No-op when the
    runtime was never initialized; peer-already-gone errors are expected
    at exit (the other end of a finished run may close first) and only
    logged."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return
    try:
        client = jax._src.distributed.global_state.client
    except AttributeError:
        client = None
    if client is None:
        return
    try:
        jax.distributed.shutdown()
        log.info("pod-wide jax runtime shut down")
    except Exception as e:  # noqa: BLE001 — exit path must not raise
        log.warn("pod-wide jax runtime shutdown failed", err=repr(e))
