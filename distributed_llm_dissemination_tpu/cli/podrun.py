"""Single-controller pod driver: one process, whole mesh, fabric data plane.

The deployment shape the reference cannot express: its data plane is one OS
process per node streaming TCP (``/root/reference/cmd/main.go:113-146``,
``distributor/transport.go:267-274``).  On a TPU pod under a single
controller, one Python process addresses every chip — so this driver hosts
ALL the topology's nodes in-process (control plane on the in-memory
transport), maps each node to a pipeline stage of the configured device
mesh, and lets every scheduled layer transfer ride the device fabric
(``parallel/fabric.py``): seeders upload their planned byte ranges to their
own stage's HBM, destinations ingest them over ICI.  No layer byte ever
touches a socket.

    python -m distributed_llm_dissemination_tpu.cli.podrun -f conf.json -m 3

Prints the reference's "Time to deliver" (cmd/main.go:173-181) and one
machine-readable JSON summary line.  For multi-process/multi-host
deployments use ``cli.main`` (TCP data plane) — the SPMD fabric across
processes needs ``jax.distributed`` mesh formation; see the README runbook.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict

from ..core import config as cfg
from ..runtime import (
    FlowRetransmitLeaderNode,
    FlowRetransmitReceiverNode,
    LeaderNode,
    Node,
    PullRetransmitLeaderNode,
    ReceiverNode,
    RetransmitLeaderNode,
    RetransmitReceiverNode,
)
from ..transport.inmem import InmemTransport
from ..utils import env as env_util
from ..utils import logging as ulog

_LEADERS = {
    0: LeaderNode,
    1: RetransmitLeaderNode,
    2: PullRetransmitLeaderNode,
    3: FlowRetransmitLeaderNode,
}
_RECEIVERS = {
    0: ReceiverNode,
    1: RetransmitReceiverNode,
    2: RetransmitReceiverNode,
    3: FlowRetransmitReceiverNode,
}


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="podrun", description=__doc__,
                                prefix_chars="-")
    p.add_argument("-f", type=str, required=True,
                   help="filename of topology JSON file (Mesh section "
                        "required; Fabric implied)")
    p.add_argument("-m", type=int, default=3, choices=[0, 1, 2, 3],
                   help="0: naive, 1: retransmit, 2: pull, 3: max-flow")
    p.add_argument("-boot", type=str, default="",
                   help="model config name: boot the model from the "
                        "fabric-delivered blobs and report TTFT")
    p.add_argument("-gen", type=int, default=0,
                   help="after a servable pipeline boot, greedy-decode "
                        "this many tokens across the pod (KV-cached)")
    p.add_argument("-report", type=str, default="",
                   help="write RUN_REPORT.{json,md} at this path/prefix "
                        "when the run completes (cli/report.py)")
    p.add_argument("-v", action="store_true", help="output debug messages")
    return p


def fabric_bandwidths(conf: cfg.Config) -> Dict[int, int]:
    """Per-node bandwidths for the mode-3 flow solve on a fabric.

    With ``Mesh.IciBW`` set, every node plans against the stage's ICI
    capacity — the device plane carries the bytes, so the NIC is not in
    the path; per-source LimitRates still cap seeders.  Without it, the
    configured NetworkBW is used as-is."""
    ici = conf.mesh.ici_bw if conf.mesh is not None else 0
    return {nc.id: (ici if ici > 0 else nc.network_bw) for nc in conf.nodes}


# The summary's ``plan_phases`` keeps these three short names beside the
# span names they are sums of: benchmark/readers/pod_phase.py looks them
# up.
_PLAN_PHASE_NAMES = {"upload": "fabric.upload",
                     "collective": "fabric.collective",
                     "splice": "fabric.splice"}


def plan_phases(totals: dict) -> dict:
    out = dict(totals)
    for short, name in _PLAN_PHASE_NAMES.items():
        if name in totals:
            out[short] = totals[name]
    return out


def run_pod(conf: cfg.Config, mode: int = 3, boot: str = "",
            timeout: float = 600.0, gen: int = 0,
            on_delivered=None, report: str = "") -> Dict[str, float]:
    """Drive one full pod dissemination; returns the timing summary.

    Callable from tests/benchmarks; the fabric and placement span every
    configured node (seeders contribute from their own stages)."""
    if conf.mesh is None:
        raise SystemExit("podrun needs a Mesh section in the config")
    from ..parallel.fabric import FabricPlane
    from ..parallel.mesh import fabric_placement, mesh_from_conf

    from ..utils import trace as utrace

    utrace.watch_compiles()  # this process holds the pod's devices
    mesh = mesh_from_conf(conf.mesh)
    node_ids = [nc.id for nc in conf.nodes]
    placement = fabric_placement(node_ids, conf.assignment, mesh,
                                 conf.mesh.pipeline_axis)
    fabric = FabricPlane()
    ulog.log.info("pod fabric up",
                  mesh={n: s for n, s in zip(conf.mesh.axis_names,
                                             conf.mesh.axis_sizes)},
                  stages={str(n): s for n, s in placement.node_to_stage.items()})

    transports = {
        nc.id: InmemTransport(str(nc.id),
                              addr_registry={i: str(i) for i in node_ids})
        for nc in conf.nodes
    }
    leader_conf = cfg.get_leader_conf(conf)
    from .main import boot_config  # same validation as the per-node CLI

    boot_cfg = boot_config(boot or conf.model)
    if boot_cfg is not None:
        from ..models import family

        try:
            family.only(boot_cfg, ("llama",), "cli.podrun.run_pod",
                        "a pod's stage boots, pipelined forward and pod "
                        "decode (runtime/pp_serve.py, models/sharded.py) "
                        "know Llama's block and K/V cache only")
        except family.FamilyNotSupported as e:
            raise SystemExit(str(e))

    leader = None
    receivers = []
    try:
        for nc in conf.nodes:
            layers = cfg.create_layers(nc, save_disk=False,
                                       model=conf.model,
                                       model_seed=conf.model_seed,
                                       model_codec=conf.model_codec)
            node = Node(nc.id, leader_conf.id, transports[nc.id])
            if nc.id == leader_conf.id:
                kwargs = dict(expected_nodes=set(node_ids),
                              fabric=fabric, placement=placement)
                if mode == 3:
                    leader = _LEADERS[3](node, layers, conf.assignment,
                                         fabric_bandwidths(conf),
                                         topology=conf.mesh.topology(),
                                         **kwargs)
                else:
                    leader = _LEADERS[mode](node, layers, conf.assignment,
                                            **kwargs)
                leader.boot_enabled = boot_cfg is not None
            else:
                receivers.append(_RECEIVERS[mode](
                    node, layers, fabric=fabric, placement=placement,
                    boot_cfg=boot_cfg, boot_codec=conf.model_codec,
                ))
        for r in receivers:
            r.announce()
        leader.start_distribution().get(timeout=timeout)
        t0 = time.monotonic()
        leader.ready().get(timeout=timeout)
        ttd = time.monotonic() - t0
        ulog.log.info("Time to deliver", seconds=round(ttd, 6))
        print(f"Time to deliver: {ttd:.6f}s", flush=True)
        # Phase attribution for THIS dissemination, sampled at ready
        # (before any boot compiles muddy the water).
        from ..parallel import plan_cache

        plan_cache.log_stats()
        summary = {"mode": mode, "ttd_s": round(ttd, 6),
                   "nodes": len(node_ids), "fabric": True,
                   "plan_phases": plan_phases(utrace.phase_totals())}
        if boot_cfg is not None:
            booted = leader.boot_ready().get(timeout=timeout)
            ttft = time.monotonic() - t0
            ulog.log.info("Time to first token", seconds=round(ttft, 6))
            print(f"Time to first token: {ttft:.6f}s", flush=True)
            summary["ttft_s"] = round(ttft, 6)
            summary["boot_nodes"] = len(booted)
            # When the stage boots partition the model, the POD serves as
            # one pipelined model from the landed weights (pp_serve).
            from ..runtime.pp_serve import assemble_pp_params, pod_forward

            results = {r.node.my_id: r.boot_result for r in receivers}
            stores = {r.node.my_id: r.layers for r in receivers}
            assembled = assemble_pp_params(boot_cfg, placement, results,
                                           stores, conf.model_codec)
            with utrace.span("serve.pod_forward", node=leader_conf.id):
                served = pod_forward(boot_cfg, placement, results, stores,
                                     codec=conf.model_codec,
                                     assembled=assembled)
            if served is not None:
                _, pod_s = served
                summary["pod_forward_s"] = round(pod_s, 6)
                print(f"Pod pipelined forward: {pod_s:.6f}s", flush=True)
            if served is not None and gen > 0:
                from ..runtime.pp_serve import pod_decode

                with utrace.span("serve.pod_decode", node=leader_conf.id,
                                 new_tokens=gen):
                    dec = pod_decode(boot_cfg, placement, results, stores,
                                     max_new=gen, codec=conf.model_codec,
                                     assembled=assembled)
                if dec is not None:
                    toks, dec_s = dec
                    summary["pod_decode_s"] = round(dec_s, 6)
                    summary["tokens"] = [int(t) for t in toks[0]]
                    print(f"Pod decoded {toks.shape[1]} tokens: "
                          f"{summary['tokens']}", flush=True)
        if on_delivered is not None:
            # Harvest hook (cli.train): read the DELIVERED layer stores
            # while the nodes are still alive; runs before any close.
            on_delivered(leader, receivers)
        if report:
            from . import report as report_mod

            rep = report_mod.build_from_leader(
                leader, ttd_s=ttd, ttft_s=summary.get("ttft_s"))
            paths = report_mod.write_report(rep, report)
            summary["run_report"] = paths["provenance"]
            print(f"Run report: {paths['json']} "
                  f"(provenance {paths['provenance']})", flush=True)
        print(json.dumps(summary), flush=True)
        return summary
    finally:
        # The pod's interval spans (every seat's: they carry ``node``)
        # and counters, as the run's last log records.
        utrace.dump_spans(ulog.log)
        if leader is not None:
            leader.close()
        for r in receivers:
            r.close()
        for t in transports.values():
            t.close()


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    ulog.configure(node="pod", verbose=args.v)
    # Before anything can import jax: where compiled programs persist.
    env_util.place_compile_cache()
    conf = cfg.read_json(args.f)
    run_pod(conf, mode=args.m, boot=args.boot, gen=max(0, args.gen),
            report=args.report)
    return 0


if __name__ == "__main__":
    sys.exit(main())
