"""Request inference from a booted deployment.

The terminal step of the whole pipeline: after ``cli.main`` disseminated
the weights and the startup hook booted the engine, any topology node's
seat can ask it for tokens —

    python -m distributed_llm_dissemination_tpu.cli.genreq \\
        -f conf.json -id 2 -node 3 -prompt 128000,3923,374 -n 16

binds node 2's address from the topology, sends a ``GenerateReqMsg`` to
node 3, and prints the decoded ids as JSON on stdout.  ``-id`` must name
a topology node NOT also running ``cli.main`` in this process space (the
request/response plane multiplexes on the node's address; default: the
highest node id with no assignment and no initial layers, the natural
"idle seat").
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from ..core import config as cfg_mod
from ..runtime.client import GenRequester
from ..transport.tcp import TcpTransport
from ..utils import logging as ulog
from ..utils import trace
from ..utils.logging import log


def _idle_seat(conf) -> int:
    """The highest node id holding nothing, assigned nothing, and with
    no attached external client — client-attached seats DO run cli.main
    (the leader awaits them), so their address is already bound by a
    live node process and binding it here would fail or hijack replies."""
    client_ids = {cc.id for cc in conf.clients}
    for nc in sorted(conf.nodes, key=lambda n: -n.id):
        holds = any(nc.initial_layers.values()) if nc.initial_layers else False
        if (not holds and nc.id not in conf.assignment
                and not nc.is_leader and nc.id not in client_ids):
            return nc.id
    raise SystemExit(
        "no idle node seat in the topology; pass -id explicitly")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="genreq")
    p.add_argument("-f", type=str, required=True, help="topology JSON")
    p.add_argument("-node", type=int, required=True,
                   help="the booted node to ask")
    p.add_argument("-prompt", type=str, default="",
                   help="comma-separated prompt token ids")
    p.add_argument("-text", type=str, default="",
                   help="prompt as text — needs an hf:<dir> Model whose "
                        "checkpoint dir has a tokenizer; the reply then "
                        "also carries decoded text")
    p.add_argument("-n", type=int, default=16, help="tokens to decode")
    p.add_argument("-temp", type=float, default=0.0,
                   help="sampling temperature (0 = greedy)")
    p.add_argument("-seed", type=int, default=0,
                   help="sampling seed (same seed, same tokens)")
    p.add_argument("-id", type=int, default=-1,
                   help="this requester's node seat (default: the "
                        "highest idle node in the topology)")
    p.add_argument("-t", type=float, default=300.0, help="reply timeout s")
    p.add_argument("-v", action="store_true")
    args = p.parse_args(argv)
    ulog.configure(node="genreq", verbose=args.v)

    conf = cfg_mod.read_json(args.f)
    my_id = args.id if args.id >= 0 else _idle_seat(conf)
    by_id = {nc.id: nc for nc in conf.nodes}
    if my_id not in by_id:
        raise SystemExit(f"-id {my_id} is not a topology node")
    if args.node not in by_id:
        raise SystemExit(f"-node {args.node} is not a topology node")
    if bool(args.prompt) == bool(args.text):
        raise SystemExit("give exactly one of -prompt (token ids) or "
                         "-text (needs an hf: Model)")

    tokenizer = None
    if args.text:
        if not conf.model.startswith("hf:"):
            raise SystemExit(
                f"-text needs an hf:<dir> Model (config has "
                f"{conf.model!r}); use -prompt with token ids")
        from transformers import AutoTokenizer  # noqa: PLC0415

        tokenizer = AutoTokenizer.from_pretrained(conf.model[3:])
        prompt = [int(t) for t in tokenizer.encode(args.text)]
    else:
        prompt = [int(t) for t in args.prompt.split(",") if t.strip()]

    transport = TcpTransport(by_id[my_id].addr)
    transport.addr_registry.update({nc.id: nc.addr for nc in conf.nodes})
    requester = GenRequester(transport, my_id=my_id)
    t_sent = time.monotonic()
    try:
        with trace.span("serve.request", id=f"req.{my_id}", node=my_id,
                        dest=args.node):
            tokens = requester.request(args.node, prompt, args.n,
                                       timeout=args.t,
                                       temperature=args.temp,
                                       seed=args.seed)
    except (RuntimeError, TimeoutError, OSError, ConnectionError) as e:
        log.error("generation request failed", err=str(e))
        print(json.dumps({"error": str(e)}))
        return 1
    finally:
        # This request's spans, as the last log records: the requester
        # may be a resident process that never resets its registry.
        trace.dump_spans(log, since=t_sent)
        requester.close()
        transport.close()
    rec = {"node": args.node, "prompt": prompt, "tokens": tokens}
    if tokenizer is not None:
        rec["text"] = tokenizer.decode(tokens)
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
