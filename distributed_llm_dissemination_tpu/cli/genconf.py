"""Generate the five BASELINE benchmark topologies as config files.

``BASELINE.json`` (driver-provided) names five scenarios; the first is the
reference's own shape (shipped as ``conf/local_4node.json``), the rest are
materialized here so they can be run anywhere:

1. 4 nodes, 3 dummy layers @1 MiB, mode 0            → conf/local_4node.json
2. 8-node mode-0 broadcast, 32 layers @400 MiB       → bench_8node_llama8b.json
3. 16-node mode-1 retransmit, 80 layers @1.6 GiB     → bench_16node_llama70b.json
4. 32-node contiguous pipeline Assignment, mode 1    → bench_32node_pipeline.json
5. 64-node pod, 126 layers @3.2 GiB + disk sources   → bench_64node_llama405b.json

Shape choices (documented here because the driver's scenario lines name
sizes, not topologies): scenario 2 is a pure broadcast — the leader seeds
every layer, every other node is assigned all of them.  Scenario 3 spreads
partial seeds over the first half of the nodes (mode 1's raison d'être:
peers co-serve) with the second half cold and assigned everything.
Scenario 4 assigns each non-leader node one contiguous layer range — the
pipeline-stage placement the Assignment doubles as (SURVEY §2.3).
Scenario 5 is scenario 4 at Llama-3-405B scale with layers seeded on DISK
(SourceType 1 @200 MiB/s, the reference's NVMe rate) on the leader plus
seven replica seeders — the disk-spill path.

    python -m distributed_llm_dissemination_tpu.cli.genconf -o conf/

``spmd_two_proc_config`` / ``spmd_pod_config`` build the loopback
topologies of the multi-controller SPMD fabric (one OS process per node);
they are not written to ``conf/`` because their ports are picked per run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

MIB = 1 << 20
GIB = 1 << 30
NIC_BW = 1_562_500_000  # 12.5 Gbit/s, the reference's modeled NetworkBW
DISK_RATE = 209_715_200  # 200 MiB/s, the reference's NVMe source rate


def _node(node_id: int, port: int, leader: bool = False,
          source_type: int = 2, rate: int = 0, layers=None,
          layer_size: int = 0) -> dict:
    d = {
        "Id": node_id,
        "Addr": f":{port}",
        "NetworkBW": NIC_BW,
        "Sources": {str(source_type): rate},
        "InitialLayers": {},
    }
    if leader:
        d["IsLeader"] = True
    if layers:
        d["InitialLayers"] = {
            str(source_type): {str(lid): {"LayerSize": layer_size}
                               for lid in layers}
        }
    return d


def _contiguous_assignment(dests, n_layers: int) -> dict:
    """Each dest gets one contiguous slice — pipeline-stage placement."""
    per, rem = divmod(n_layers, len(dests))
    out, pos = {}, 0
    for i, dest in enumerate(dests):
        take = per + (1 if i < rem else 0)
        out[str(dest)] = {str(lid): {} for lid in range(pos, pos + take)}
        pos += take
    return out


def scenario_8node_llama8b() -> dict:
    """#2: 8-node mode-0 broadcast, 32 layers @400 MiB (Llama-3-8B)."""
    n_layers, size = 32, 400 * MIB
    nodes = [_node(0, 9180, leader=True, layers=range(n_layers),
                   layer_size=size)]
    nodes += [_node(i, 9180 + i) for i in range(1, 8)]
    return {
        "Nodes": nodes,
        "Assignment": {str(i): {str(lid): {} for lid in range(n_layers)}
                       for i in range(1, 8)},
        "LayerSize": size,
    }


def scenario_16node_llama70b() -> dict:
    """#3: 16-node mode-1, 80 layers @1.6 GiB (Llama-3-70B); nodes 1-7
    partially seed (10 layers each) so peers co-serve, nodes 8-15 cold."""
    n_layers, size = 80, int(1.6 * GIB)
    nodes = [_node(0, 9280, leader=True, layers=range(n_layers),
                   layer_size=size)]
    for i in range(1, 8):
        seed = range((i - 1) * 10, i * 10)
        nodes.append(_node(i, 9280 + i, layers=seed, layer_size=size))
    nodes += [_node(i, 9280 + i) for i in range(8, 16)]
    return {
        "Nodes": nodes,
        "Assignment": {str(i): {str(lid): {} for lid in range(n_layers)}
                       for i in range(8, 16)},
        "LayerSize": size,
    }


def scenario_32node_pipeline() -> dict:
    """#4: 32-node contiguous pipeline Assignment (80 layers), mode 1."""
    n_layers, size = 80, int(1.6 * GIB)
    nodes = [_node(0, 9380, leader=True, layers=range(n_layers),
                   layer_size=size)]
    nodes += [_node(i, 9380 + i) for i in range(1, 32)]
    return {
        "Nodes": nodes,
        "Assignment": _contiguous_assignment(list(range(1, 32)), n_layers),
        "LayerSize": size,
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [32],
                 "PipelineAxis": "nodes"},
    }


def scenario_64node_llama405b() -> dict:
    """#5: 64-node pod, 126 layers @3.2 GiB (Llama-3-405B), mode 1, layers
    seeded on DISK (the NVMe spill path) on the leader + 7 replicas."""
    n_layers, size = 126, int(3.2 * GIB)
    nodes = [_node(0, 9480, leader=True, source_type=1, rate=DISK_RATE,
                   layers=range(n_layers), layer_size=size)]
    for i in range(1, 8):  # disk replica seeders
        nodes.append(_node(i, 9480 + i, source_type=1, rate=DISK_RATE,
                           layers=range(n_layers), layer_size=size))
    nodes += [_node(i, 9480 + i) for i in range(8, 64)]
    return {
        "Nodes": nodes,
        "Assignment": _contiguous_assignment(list(range(8, 64)), n_layers),
        "LayerSize": size,
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [64],
                 "PipelineAxis": "nodes"},
    }


def _spmd_nodes(n: int, scale: int, layers: int, port) -> list:
    """Leader 0 seeds ``layers`` layers in RAM; nodes 1..n-1 start cold."""
    nodes = [{"Id": i, "Addr": f"127.0.0.1:{port()}",
              "NetworkBW": 12500000000, "Sources": {"2": 0},
              "InitialLayers": {}} for i in range(n)]
    nodes[0]["IsLeader"] = True
    nodes[0]["InitialLayers"] = {
        "2": {str(i): {"LayerSize": scale} for i in range(layers)}}
    return nodes


def _spmd_mesh(n: int, port) -> dict:
    return {
        "Mesh": {"AxisNames": ["nodes"], "AxisSizes": [n],
                 "PipelineAxis": "nodes", "Fabric": True},
        "Distributed": {"Coordinator": f"127.0.0.1:{port()}",
                        "CpuCollectives": "gloo"},
    }


def spmd_two_proc_config(scale: int, layers: int, port) -> dict:
    """A 2-process multi-controller SPMD fabric topology on loopback
    (leader seeds, node 1 assigned): one OS process per node, one
    jax.distributed runtime, layer bytes as lockstep collectives
    (``parallel/spmd_fabric.py``).  ``port()`` returns a free port each
    time it is called (three are taken)."""
    return {
        "Nodes": _spmd_nodes(2, scale, layers, port),
        "Assignment": {"1": {str(i): {} for i in range(layers)}},
        "LayerSize": scale,
        **_spmd_mesh(2, port),
    }


def spmd_pod_config(scale: int, layers: int, port) -> dict:
    """A 3-process SPMD pod-delivery topology (docs/fabric.md): leader
    0 seeds; nodes 1 and 2 form ONE pod and both want every layer —
    the NIC ships each member its 1/2 shard (host TCP), and the leader
    dispatches the pod gather as a lockstep collective that leaves the
    full tree on BOTH members.  ``port()`` as above (four are taken)."""
    return {
        "Nodes": _spmd_nodes(3, scale, layers, port),
        "Assignment": {"1": {str(i): {} for i in range(layers)},
                       "2": {str(i): {} for i in range(layers)}},
        "LayerSize": scale,
        "Pods": [[1, 2]],
        **_spmd_mesh(3, port),
    }


SCENARIOS = {
    "bench_8node_llama8b.json": scenario_8node_llama8b,
    "bench_16node_llama70b.json": scenario_16node_llama70b,
    "bench_32node_pipeline.json": scenario_32node_pipeline,
    "bench_64node_llama405b.json": scenario_64node_llama405b,
}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="genconf", prefix_chars="-")
    p.add_argument("-o", type=str, default="conf",
                   help="output directory for the generated configs")
    args = p.parse_args(argv)
    os.makedirs(args.o, exist_ok=True)
    for name, builder in SCENARIOS.items():
        path = os.path.join(args.o, name)
        with open(path, "w") as f:
            json.dump(builder(), f, indent=1)
        print(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
