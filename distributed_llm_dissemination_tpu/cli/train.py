"""Dissemination → training bring-up, as one driveable command.

The reference stops at "bytes delivered + startup signal"; the point of
delivering weights to a TPU pod is to USE them.  This CLI closes the
training half of that loop:

    python -m distributed_llm_dissemination_tpu.cli.train \\
        -f conf/boot_tiny_4node.json -steps 20 -ckpt /ckpt/run1

1. Disseminates the topology's model blobs over the pod fabric
   (``cli.podrun`` machinery — mode 3, single controller), so the
   weights land exactly as a deployment's would;
2. assembles the delivered blobs into params (the boot path) and shards
   them onto the 5-axis training mesh (``models.sharded``);
3. runs AdamW steps (f32 moments sharded like the params, layer
   rematerialization) on a seeded self-supervised batch stream;
4. optionally checkpoints the training state (``models.train_ckpt``) —
   and ``-resume`` continues bit-exactly from a saved state, skipping
   the dissemination entirely (the weights' bytes already live in the
   optimizer trajectory).

Summary JSON on stdout: ttd/boot seconds, per-step losses, ckpt path.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from ..core import config as cfg_mod
from ..utils import logging as ulog
from ..utils.logging import log


def _trainable_config(name: str):
    """The topology's model (a named configuration or ``hf:<dir>``),
    refused at entry if its family has no sharded train step
    (``models/sharded.py`` knows Llama's only)."""
    from ..models import family, hf

    if hf.is_hf(name):
        return hf.config_from_name(name)
    try:
        mcfg = family.config(name)
        family.only(mcfg, ("llama",), "cli.train",
                    "the sharded AdamW train step and its checkpoint "
                    "(models/sharded.py, models/train_ckpt.py) know "
                    "Llama's leaves only")
    except KeyError:
        raise SystemExit(f"unknown Model {name!r}; known: {family.known()}")
    except family.FamilyNotSupported as e:
        raise SystemExit(str(e))
    return mcfg


def _params_from_dissemination(conf, timeout: float):
    """Run one mode-3 pod dissemination and return (params, cfg,
    timings) assembled from the DELIVERED blobs on the dest."""
    from ..models import serde
    from ..models.serde import params_from_blobs

    from .podrun import run_pod  # noqa: PLC0415 — heavy import path

    mcfg = _trainable_config(conf.model)
    head_id = serde.head_blob_id(mcfg)
    want = set(range(head_id + 1))
    blobs: dict = {}

    def harvest(_leader, receivers):
        # Assignees only: a seeder's own copy of a blob proves nothing
        # about delivery — the training weights must be the ones the
        # dissemination actually landed.
        dests = set(conf.assignment)
        for r in receivers:
            if r.node.my_id not in dests:
                continue
            for bid, src in r.layers.items():
                if bid in want and bid not in blobs:
                    blobs[bid] = bytes(
                        src.inmem_data if src.inmem_data is not None
                        else src.read_bytes())

    t0 = time.monotonic()
    summary = dict(run_pod(conf, mode=3, timeout=timeout,
                           on_delivered=harvest))
    missing = want - set(blobs)
    if missing:
        raise SystemExit(
            f"dissemination left blobs missing: {sorted(missing)}")
    if conf.model_codec != "raw":
        import numpy as np

        from ..models import quant

        raws = {}
        for bid, data in blobs.items():
            dec = quant.decode_blob_host(mcfg, bid, data, conf.model_codec)
            raw = bytearray()
            for _nm, arr in dec.items():
                raw += np.ascontiguousarray(arr).tobytes()
            raws[bid] = bytes(raw)
        params = params_from_blobs(mcfg, raws)
    else:
        params = params_from_blobs(mcfg, blobs)
    summary["assemble_s"] = round(
        time.monotonic() - t0 - summary.get("ttd_s", 0.0), 3)
    return params, mcfg, summary


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="train")
    p.add_argument("-f", type=str, required=True,
                   help="topology JSON with a Model section")
    p.add_argument("-steps", type=int, default=10)
    p.add_argument("-lr", type=float, default=1e-3)
    p.add_argument("-batch", type=int, default=0,
                   help="global batch (default: 2*dp)")
    p.add_argument("-seq", type=int, default=0,
                   help="sequence length (default: 8*sp)")
    p.add_argument("-ckpt", type=str, default="",
                   help="save the final (params, opt) state here")
    p.add_argument("-resume", action="store_true",
                   help="restore state from -ckpt instead of "
                        "disseminating; continues the trajectory exactly")
    p.add_argument("-t", type=float, default=600.0,
                   help="dissemination timeout seconds")
    p.add_argument("-v", action="store_true")
    args = p.parse_args(argv)
    ulog.configure(node="train", verbose=args.v)

    conf = cfg_mod.read_json(args.f)
    if not conf.model:
        raise SystemExit("training needs a Model section in the topology")
    if args.resume and not args.ckpt:
        raise SystemExit("-resume needs -ckpt")

    import jax

    from ..models.sharded import (
        build_adamw_train_step,
        example_batch,
        factor_mesh_axes,
        init_adamw_state,
        make_train_mesh,
        shard_params,
    )
    from ..models.train_ckpt import restore_train_state, save_train_state

    summary: dict = {}
    if args.resume:
        mcfg = _trainable_config(conf.model)
        mesh = make_train_mesh(len(jax.devices()), mcfg)
        params, opt = restore_train_state(args.ckpt, mcfg, mesh)
        summary["resumed_step"] = int(opt["step"])
        log.info("training state restored", step=summary["resumed_step"])
    else:
        params, mcfg, summary = _params_from_dissemination(conf, args.t)
        mesh = make_train_mesh(len(jax.devices()), mcfg)
        params = shard_params(params, mesh, mcfg)
        opt = init_adamw_state(params)

    step = build_adamw_train_step(mcfg, mesh, lr=args.lr)
    inputs, targets = example_batch(mcfg, mesh, batch=args.batch,
                                    seq=args.seq)
    losses = []
    t0 = time.monotonic()
    for _ in range(args.steps):
        params, opt, loss = step(params, opt, inputs, targets)
        losses.append(round(float(loss), 4))
    train_s = time.monotonic() - t0
    log.info("training ran", steps=args.steps, losses=losses)

    if args.ckpt:
        save_train_state(args.ckpt, params, opt)
        summary["ckpt"] = args.ckpt
    summary.update({
        "mesh": factor_mesh_axes(len(jax.devices()), mcfg),
        "steps": args.steps,
        "final_step": int(opt["step"]),
        "losses": losses,
        "train_s": round(train_s, 3),
    })
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
