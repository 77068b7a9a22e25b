"""Pod-level pipelined serving from disseminated stage weights.

The Assignment IS a pipeline placement (SURVEY §2.3): dissemination lands
each stage's layer slice on that stage's devices and the per-node boot
proves the slice usable (``runtime/boot.py`` stage boots).  This module
closes the last gap — the POD serves as one model:

1. ``assemble_pp_params`` lifts each stage's resident stacked params
   (``BootResult.params``, already on the stage's devices) into global
   pipeline-sharded arrays — ``make_array_from_single_device_arrays``
   over the full mesh, so NO weight bytes move; the head leaves (held by
   whichever stage received the head blob) are broadcast mesh-wide over
   ICI (the one small replicated piece).
2. ``pod_forward`` runs ``models.sharded.build_pp_forward``: activations
   hand off stage→stage by ``ppermute``, logits valid on stage 0.

Two controller shapes, like the fabric itself:

- single-controller (``cli/podrun.py``): ``pod_forward`` — one process
  addresses the whole mesh;
- multi-controller (``spmd_pod_forward`` / ``spmd_pod_decode``): after
  boots, the leader broadcasts a ``ServeMsg`` and every MEMBER process
  (one per stage) enters the same compiled collective over the sub-mesh
  of the member stages, feeding its local shards — the serving analogue
  of the SPMD fabric's lockstep (``parallel/spmd_fabric.py``).  The head
  blob must be assigned to EVERY stage (the config convention for
  multi-controller serving), since a process can only decode what its
  own store holds.

Generation is first-class: ``pod_decode`` / ``spmd_pod_decode`` run the
KV-cached greedy loop (``models.sharded.build_pp_decode``) across the
stages, and UNEVEN contiguous stage slices serve (padded to the deepest
stage; the counts vector masks the tail) — both lifted in round 4.

The two programs are the PROCESS's, not a delivery's: ``build_pp_forward``
/ ``build_pp_decode`` keep one jitted function per (configuration,
sub-mesh, axis, length), so a pod that stays up and takes a new delivery
serves it without tracing, lowering or loading anything again (counters
``serve.pp_program.built`` / ``.reused``).  Every caller here passes the
delivery's arrays as ARGUMENTS; a kept program closes over none.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from ..utils.logging import log


def _stage_order(cfg, placement, results) -> Optional[list]:
    """Stage-ordered list of (node, stacked-params, depth) when the boots
    form a full contiguous partition of the layers (UNEVEN slices are
    fine — they pad to the deepest stage); None (with a log) otherwise."""
    staged = {n: r for n, r in results.items()
              if r is not None and r.kind == "stage" and r.params is not None}
    if not staged:
        return None
    by_stage = sorted(staged, key=lambda n: placement.node_to_stage[n])
    covered = [lid for n in by_stage for lid in staged[n].layer_ids]
    if covered != list(range(cfg.n_layers)):
        log.info("pod serve skipped: stage boots don't partition the "
                 "layers", covered=covered)
        return None
    return [(n, staged[n].params, len(staged[n].layer_ids))
            for n in by_stage]


def _pad_stack(leaf, l_max: int):
    """Zero-pad a stacked layer leaf [L, ...] to [l_max, ...] (the padded
    tail is masked out of the pipeline by the counts vector)."""
    import jax.numpy as jnp

    l = leaf.shape[0]
    if l == l_max:
        return leaf
    return jnp.pad(leaf, [(0, l_max - l)] + [(0, 0)] * (leaf.ndim - 1))


def _head_leaves(cfg, stores, codec: str):
    """Decode embed/ln_f/lm_head from whichever node's store holds the
    head blob (device path when it landed in HBM)."""
    from ..models import serde
    from .boot import decode_head

    head_id = serde.head_blob_id(cfg)
    for node_id, layers in stores.items():
        src = layers.get(head_id)
        if src is not None:
            return decode_head(cfg, src, codec)
    return None


def assemble_pp_params(cfg, placement, results: Dict[int, Any],
                       stores: Dict[int, Any], codec: str = "raw"):
    """Global pipeline-sharded params from the stage boots' resident
    arrays; None when the pod doesn't form a servable pipeline.  Returns
    (mesh, layers, counts, head) — slices padded to the deepest stage,
    ``counts`` [pp] carrying each stage's real depth."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    order = _stage_order(cfg, placement, results)
    if order is None:
        return None
    head = _head_leaves(cfg, stores, codec)
    if head is None:
        log.info("pod serve skipped: no node holds the head blob")
        return None
    pp_axis = placement.pipeline_axis
    # Serve on the SUB-mesh of exactly the booted stages: a pod fabric
    # maps seeders and the leader onto stages too, and those hold no
    # model slice.
    mesh = _submesh(placement,
                    [placement.node_to_stage[n] for n, _, _ in order])
    l_max = max(depth for _, _, depth in order)

    flat_devices = list(np.ravel(mesh.devices))
    layers_global = {}
    leaf_names = list(order[0][1].keys())
    for name in leaf_names:
        shards = {}
        for node_id, stacked, _depth in order:
            stage = placement.node_to_stage[node_id]
            leaf = jax.device_put(
                _pad_stack(stacked[name], l_max),
                NamedSharding(placement.stage_mesh(stage), P()),
            )
            for s in leaf.addressable_shards:
                shards[s.device] = s.data
        per_dev = [shards[d] for d in flat_devices]
        slice_shape = per_dev[0].shape
        global_shape = (len(order) * l_max,) + slice_shape[1:]
        spec = P(*([pp_axis] + [None] * (len(slice_shape) - 1)))
        layers_global[name] = jax.make_array_from_single_device_arrays(
            global_shape, NamedSharding(mesh, spec), per_dev
        )
    counts = jax.device_put(
        jnp.asarray([depth for _, _, depth in order], jnp.int32),
        NamedSharding(mesh, P(pp_axis)),
    )
    head = {
        name: jax.device_put(jnp.asarray(a), NamedSharding(mesh, P()))
        for name, a in head.items()
    }
    return mesh, layers_global, counts, head


def _submesh(placement, stage_idx):
    import numpy as np
    from jax.sharding import Mesh

    k = list(placement.mesh.axis_names).index(placement.pipeline_axis)
    return Mesh(np.take(placement.mesh.devices, stage_idx, axis=k),
                placement.mesh.axis_names)


def _spmd_assemble(cfg, placement, members, my_node, stacked, store,
                   codec: str, member_counts=None):
    """Shared multi-controller assembly: this process's resident stage
    params (padded to the deepest member stage) lifted into the global
    pipeline-sharded tree over the members' sub-mesh, plus the counts
    vector, the replicated head leaves, and a ``replicated`` helper.

    ``member_counts``: per-member stage depths aligned with ``members``
    (from the leader's ServeMsg); defaults to even n_layers/len(members)
    — the pre-round-4 convention."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..models import serde
    from .boot import decode_head

    pp_axis = placement.pipeline_axis
    mesh = _submesh(placement,
                    [placement.node_to_stage[n] for n in members])
    if member_counts is None:
        member_counts = [cfg.n_layers // len(members)] * len(members)
    l_max = max(member_counts)

    def replicated(a):
        """A mesh-global replicated array from this process's local value
        (each process contributes identical content for its devices)."""
        local = [d for d in np.ravel(mesh.devices)
                 if d.process_index == jax.process_index()]
        arr = jnp.asarray(a)
        shards = [jax.device_put(arr, d) for d in local]
        return jax.make_array_from_single_device_arrays(
            arr.shape, NamedSharding(mesh, P()), shards
        )

    stage = placement.node_to_stage[my_node]
    stage_sharding = NamedSharding(placement.stage_mesh(stage), P())
    layers_global = {}
    for name, leaf in stacked.items():
        leaf = jax.device_put(_pad_stack(leaf, l_max), stage_sharding)
        shards = {s.device: s.data for s in leaf.addressable_shards}
        local = [d for d in np.ravel(mesh.devices) if d in shards]
        global_shape = (len(members) * l_max,) + tuple(leaf.shape[1:])
        spec = P(*([pp_axis] + [None] * (leaf.ndim - 1)))
        layers_global[name] = jax.make_array_from_single_device_arrays(
            global_shape, NamedSharding(mesh, spec),
            [shards[d] for d in local],
        )

    # Per-stage depth vector, sharded along the pipeline axis: each
    # process contributes its OWN count for its local devices (a plain
    # device_put can't address the other processes' devices).
    my_count = jnp.asarray(
        [member_counts[members.index(my_node)]], jnp.int32)
    local = [d for d in np.ravel(mesh.devices)
             if d.process_index == jax.process_index()]
    counts = jax.make_array_from_single_device_arrays(
        (len(members),), NamedSharding(mesh, P(pp_axis)),
        [jax.device_put(my_count, d) for d in local],
    )

    head_src = store.get(serde.head_blob_id(cfg))
    if head_src is None:
        raise RuntimeError(
            "multi-controller serving needs the head blob assigned to "
            "every stage; this node's store has none"
        )
    head = {name: replicated(a)
            for name, a in decode_head(cfg, head_src, codec).items()}
    return mesh, layers_global, counts, head, replicated


def spmd_pod_forward(cfg, placement, members, my_node, stacked, store,
                     codec: str = "raw", batch: int = 1, seq_len: int = 16,
                     member_counts=None):
    """Multi-controller serving: called by EVERY member process on
    ``ServeMsg``.  ``stacked`` is this process's resident stage params
    (``BootResult.params``); ``store`` its layer store (holds the head
    blob — assigned to every stage by convention).  Returns
    (logits, seconds) on members, None on non-members."""
    import time

    import jax
    import jax.numpy as jnp

    from ..models.sharded import build_pp_forward

    if my_node not in members:
        return None
    t0 = time.monotonic()
    mesh, layers_global, counts, head, replicated = _spmd_assemble(
        cfg, placement, members, my_node, stacked, store, codec,
        member_counts)
    tokens = replicated(jnp.zeros((batch, seq_len), jnp.int32))

    fwd = build_pp_forward(cfg, mesh, placement.pipeline_axis)
    logits = fwd(layers_global, counts, head, tokens)
    jax.block_until_ready(logits)
    dt = time.monotonic() - t0
    log.info("pod pipelined forward from staged weights", spmd=True,
             stages=len(members), seconds=round(dt, 3))
    return logits, dt


def spmd_pod_decode(cfg, placement, members, my_node, stacked, store,
                    max_new: int, codec: str = "raw", batch: int = 1,
                    prompt_len: int = 16, member_counts=None):
    """Multi-controller KV-cached GREEDY decode: every member process
    enters the same compiled pipelined decode collective
    (``models.sharded.build_pp_decode``) and emits identical token ids —
    the pod serves generation, not just one forward.  Returns
    (tokens [batch, max_new], seconds) on members, None on non-members."""
    import time

    import jax
    import jax.numpy as jnp

    from ..models.sharded import build_pp_decode

    if my_node not in members:
        return None
    t0 = time.monotonic()
    mesh, layers_global, counts, head, replicated = _spmd_assemble(
        cfg, placement, members, my_node, stacked, store, codec,
        member_counts)
    # The boot prompt (decode_after_boot's convention): deterministic on
    # every process, so the replicated greedy loop cannot diverge.
    prompt = replicated(jnp.zeros((batch, prompt_len), jnp.int32))

    dec = build_pp_decode(cfg, mesh, placement.pipeline_axis, max_new)
    toks = dec(layers_global, counts, head, prompt)
    jax.block_until_ready(toks)
    dt = time.monotonic() - t0
    log.info("pod decoded tokens from staged weights", spmd=True,
             stages=len(members), generated=int(toks.shape[1]),
             seconds=round(dt, 3))
    return toks, dt


def pod_forward(cfg, placement, results, stores, tokens=None,
                codec: str = "raw", assembled=None):
    """One pipelined forward across the pod's stages from the landed
    weights; returns (logits, seconds) or None when not servable.
    ``assembled``: a prior ``assemble_pp_params`` result to reuse (a
    -gen run otherwise re-assembles the whole model for the decode)."""
    import time

    import jax
    import jax.numpy as jnp

    from ..models.sharded import build_pp_forward

    if assembled is None:
        assembled = assemble_pp_params(cfg, placement, results, stores,
                                       codec)
    if assembled is None:
        return None
    mesh, layers_global, counts, head = assembled
    if tokens is None:
        tokens = jnp.zeros((1, 16), jnp.int32)
    t0 = time.monotonic()
    fwd = build_pp_forward(cfg, mesh, placement.pipeline_axis)
    logits = fwd(layers_global, counts, head, tokens)
    jax.block_until_ready(logits)
    dt = time.monotonic() - t0
    log.info("pod pipelined forward from staged weights",
             stages=mesh.shape[placement.pipeline_axis],
             seconds=round(dt, 3))
    return logits, dt


def pod_decode(cfg, placement, results, stores, max_new: int,
               prompt=None, codec: str = "raw", assembled=None):
    """Single-controller pod generation: KV-cached greedy decode across
    the stages from the landed weights; (tokens, seconds) or None."""
    import time

    import jax
    import jax.numpy as jnp

    from ..models.sharded import build_pp_decode

    if assembled is None:
        assembled = assemble_pp_params(cfg, placement, results, stores,
                                       codec)
    if assembled is None:
        return None
    mesh, layers_global, counts, head = assembled
    if prompt is None:
        prompt = jnp.zeros((1, 16), jnp.int32)  # the boot prompt
    t0 = time.monotonic()
    dec = build_pp_decode(cfg, mesh, placement.pipeline_axis, max_new)
    toks = dec(layers_global, counts, head, prompt)
    jax.block_until_ready(toks)
    dt = time.monotonic() - t0
    log.info("pod decoded tokens from staged weights",
             stages=mesh.shape[placement.pipeline_axis],
             generated=int(toks.shape[1]), seconds=round(dt, 3))
    return toks, dt
