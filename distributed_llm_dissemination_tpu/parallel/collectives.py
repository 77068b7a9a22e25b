"""Dissemination as XLA collectives over a device mesh.

The TPU data plane replacing the reference's TCP byte streams
(``/root/reference/distributor/transport.go``): each dissemination mode has
a collective-program equivalent compiled via ``jax.shard_map`` onto a
``jax.sharding.Mesh`` so the layer bytes ride ICI into HBM (SURVEY §5.8):

- **mode 0** (leader broadcast, node.go:326-352) → ``replicate`` /
  ``one_to_all``: a single source's HBM copy lands on every device.
- **mode 1** (peer retransmission, node.go:554-608) → ``ring_broadcast``:
  an explicit ``ppermute`` ring relay — each hop forwards the layer to its
  neighbor while later hops are still pending; the device analogue of the
  cut-through pipe relay (transport.go:144-196).
- **mode 3** (multi-sender byte-range split, flow.go:193-211) →
  ``allgather_shards``: every seeder holds a byte-range shard and one
  tiled ``all_gather`` reassembles the full layer everywhere at the full
  bisection bandwidth.

These programs are jit-compiled once per (shape, mesh) and reused per
layer; the scalar plumbing stays on host (the control plane).

``gather_tiles`` is the one the runtime ships bytes through: the fabric
dest's ingest (``ingest.ShardedLayerIngest.finalize``) and the device-
executed flow plan (``plan.execute_flow_plan``) both compile it.  The
mode-shaped programs (``ring_broadcast``/``one_to_all``/``permute_blocks``)
are schedule-parity forms kept for comparison tests and as building
blocks for topology-aware schedules.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# XLA's CPU backend deadlocks when two collective EXECUTIONS over
# overlapping device sets interleave: each execution's per-device worker
# threads can join the other's rendezvous ("waiting for all participants
# to arrive at rendezvous RendezvousKey{run_id=861}" next to run_id=862,
# both wedged forever).  Re-checked on jax 0.9.0: 6,400 lock-free
# concurrent gathers did not reproduce it, but one of seven lock-free
# runs of the device-plane tests lost a test to its 30 s timeout — the
# lock stays until that is explained.
# Concurrent plans DO dispatch gathers concurrently (handler threads,
# the in-flight window), so on the CPU backend every gather runs
# dispatch→completion under one process-wide lock.  Accelerator
# backends keep the fully-async pipeline — ordered device streams make
# concurrent dispatch safe there.
_CPU_COLLECTIVE_LOCK = threading.Lock()


def _collective_guard():
    if jax.default_backend() == "cpu":
        return _CPU_COLLECTIVE_LOCK
    return contextlib.nullcontext()


def replicate(x: jax.Array, mesh: Mesh) -> jax.Array:
    """Mode-0 equivalent: replicate onto every device of the mesh.  XLA
    emits the broadcast (single source → all) over ICI."""
    return jax.device_put(x, NamedSharding(mesh, P()))


def shard_along(x: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """Split a 1-D layer into per-device byte-range shards along ``axis``
    (the device-plane form of flow.go's offset/dataSize jobs)."""
    return jax.device_put(x, NamedSharding(mesh, P(axis)))


def gather_tiles(mesh: Mesh, axis: str, sizes: Tuple[int, ...], pad=None):
    """Compiled: each device holds one PADDED tile of a byte blob (tile i
    is ``sizes[i]`` real elements); one ``all_gather`` + static re-splice
    yields the full blob replicated on every device of the mesh.

    THE terminal-hop collective of the dissemination runtime: both
    ``plan.execute_flow_plan`` (a mode-3 flow schedule executed as one
    device program) and ``ingest.ShardedLayerIngest.finalize`` (the
    receiver's incremental HBM ingest) compile through here — unequal
    flow-job splits are padded to the largest tile (or ``pad``), and the
    re-splice slices the real sizes back out.

    The identity-order case of ``gather_tiles_at`` (one shared builder,
    one compile cache)."""
    return gather_tiles_at(mesh, axis, sizes, tuple(range(len(sizes))),
                           pad=pad)


def _gather_padded(mesh: Mesh, axis: str, pad: int, k: int, dtype):
    """The cached COLLECTIVE program: every device contributes ``k``
    padded tiles; one tiled ``all_gather`` replicates all n*k tiles as a
    ``(n, k, pad)`` array on every device.  Keyed ONLY by (mesh, axis,
    pad, k, dtype) — the tile sizes are deliberately absent, so every
    plan whose pads land in the same ``plan_cache.bucket_pad`` bucket
    reuses one executable instead of compiling its own."""
    from .plan_cache import GATHER_CACHE

    n = mesh.shape[axis]
    key = (mesh, axis, n, pad, k, np.dtype(dtype).str)

    def build():
        def per_device(frag):
            return lax.all_gather(frag.reshape(k, pad), axis)  # (n, k, pad)

        fn = jax.jit(
            lambda v: jax.shard_map(
                per_device, mesh=mesh,
                in_specs=P(axis), out_specs=P(),
                check_vma=False,
            )(v)
        )
        # Compile EAGERLY when the runtime supports it, so the build
        # time in the cache stats is the real XLA compile (and the first
        # plan's dispatch doesn't pay it inside its collective phase).
        try:
            spec = jax.ShapeDtypeStruct(
                (n * k * pad,), np.dtype(dtype),
                sharding=NamedSharding(mesh, P(axis)))
            return fn.lower(spec).compile()
        except Exception:  # noqa: BLE001 — lazy jit is still correct
            return fn

    return GATHER_CACHE.get(key, build)


def _splice_tiles(sizes: Tuple[int, ...], order: Tuple[int, ...], k: int):
    """The cached RE-SPLICE program: slice each gathered tile back to its
    real size and concatenate in offset order — device-local HBM work,
    no collective.  Keyed by the exact sizes (a static-shape program),
    but cheap to compile next to the gather."""
    from .plan_cache import SPLICE_CACHE

    def build():
        def fn(g):  # (n, k, pad) replicated
            outs = []
            for kk in range(k):
                parts = [lax.slice(g[r, kk], (0,), (sizes[r],))
                         for r in order if sizes[r] > 0]
                outs.append(jnp.concatenate(parts) if len(parts) > 1
                            else parts[0])
            return outs[0] if k == 1 else jnp.stack(outs)

        return jax.jit(fn)

    return SPLICE_CACHE.get((sizes, order, k), build)


def gather_tiles_at(mesh: Mesh, axis: str, sizes: Tuple[int, ...],
                    order: Tuple[int, ...], pad=None):
    """``gather_tiles`` with an explicit re-splice permutation: the blob's
    k-th byte range (in offset order) lives on device rank ``order[k]``.
    The multi-controller SPMD fabric needs this because contributions sit
    on their SENDER's stage devices — whichever mesh ranks those are —
    not on ranks sorted by offset.

    ``pad``: the per-tile padded element count the caller staged its
    buffers at (>= max(sizes)); defaults to max(sizes).  Callers bucket
    it (``plan_cache.bucket_pad``) so same-bucket plans share ONE
    compiled collective; the splice slices the real sizes back out."""
    pad_ = int(pad) if pad else (max(sizes) if sizes else 0)

    def run(v):
        with _collective_guard():
            g = _gather_padded(mesh, axis, pad_, 1, v.dtype)(v)
            out = _splice_tiles(tuple(sizes), tuple(order), 1)(g)
            if jax.default_backend() == "cpu":
                jax.block_until_ready(out)  # execution ends inside the lock
            return out

    return run


def gather_tiles_batched(mesh: Mesh, axis: str, sizes: Tuple[int, ...],
                         order: Tuple[int, ...], k: int, pad=None):
    """Plan batching: K same-tiling blobs move as ONE collective.

    Each device stages its K tiles back to back (``(k * pad,)`` per
    device, tile j of blob i at ``i * pad``); one ``all_gather``
    replicates all of them and the splice returns ``(k, total)`` — blob
    i is row i.  One dispatch + one executable for K layers, which is
    exactly what amortizes per-plan latency when a model's same-shape
    layers ship together."""
    if k <= 0:
        raise ValueError(f"batch size must be positive, got {k}")
    pad_ = int(pad) if pad else (max(sizes) if sizes else 0)

    def run(v):
        with _collective_guard():
            g = _gather_padded(mesh, axis, pad_, k, v.dtype)(v)
            out = _splice_tiles(tuple(sizes), tuple(order), k)(g)
            if jax.default_backend() == "cpu":
                jax.block_until_ready(out)
            return out if k > 1 else out.reshape(1, -1)

    return run


def gather_byte_shards(parts, total: int, verify_digest=None,
                       codec: str = "", decode=None):
    """Materialize a FULL layer from its byte-range shards on-mesh
    (docs/sharding.md, docs/fabric.md): each ``(shard_index, bytes)``
    part is one ``1/N@K`` floor-split slice of a ``total``-byte layer;
    the N tiles land one-per-device on an N-device mesh and ONE tiled
    ``all_gather`` (the existing ``gather_tiles`` path — padded tiles,
    static re-splice) replicates the layer, which is then read back
    byte-exact.  On a real pod the hop is ICI at bisection bandwidth —
    the wire never carried more than each dest's shard.

    ``parts``: iterable of ``(k, data)`` covering ALL of [0, N) in any
    order.  ``verify_digest``: optional stamped full-layer digest in
    the shards' WIRE form — the gathered blob is checked against it
    before being returned (the acceptance gate: post-gather bytes must
    match the pre-shard stamp; for quantized pod deliveries this is
    the leader's codec-qualified full digest).

    Codec awareness (docs/codec.md): the shards may be slices of a
    quantized wire blob — ``codec`` names the form ("" = canonical) and
    ``decode = (cfg, blob_id)`` asks for the per-blob dequant: on the
    mesh path the gathered blob is ALREADY HBM-resident, so
    ``quant.device_decode_jit(codec)`` consumes the replicated device
    array directly (no host round trip) and the call returns
    ``(wire_bytes, leaves)`` with the decoded leaves carrying the
    stager's leading length-1 axis; the host-fallback path decodes on
    host.  Without ``decode`` the return is plain wire bytes.

    The tile pad is bucketed (``plan_cache.bucket_pad``) so every
    same-bucket layer of a model reuses ONE compiled gather program —
    the pod-delivery reconstruction compiles once, not per layer.

    Falls back to a host-side concatenation — loudly, counted on
    ``shard.gather_host_fallback`` — when the runtime has fewer devices
    than shards (the gather is then still byte-exact, just not an ICI
    collective)."""
    from ..core.types import shard_range
    from ..utils import trace
    from ..utils.logging import log

    by_k = {}
    for k, data in parts:
        by_k[int(k)] = data
    n = len(by_k)
    if n == 0 or sorted(by_k) != list(range(n)):
        raise ValueError(f"shard set incomplete: have {sorted(by_k)}")
    sizes = []
    for k in range(n):
        off, size = shard_range(f"1/{n}@{k}" if n > 1 else "", total)
        if len(by_k[k]) != size:
            raise ValueError(
                f"shard {k}/{n} is {len(by_k[k])} bytes; spec says {size}")
        sizes.append(size)

    gathered_dev = None
    if n == 1:
        out = bytes(by_k[0])
    elif len(jax.devices()) < n:
        trace.count("shard.gather_host_fallback")
        trace.count("device.degraded.shard_gather_host")
        log.warn("fewer devices than shards; gathering on host instead "
                 "of the mesh", shards=n, devices=len(jax.devices()))
        out = b"".join(bytes(by_k[k]) for k in range(n))
    else:
        from .plan_cache import bucket_pad

        devices = jax.devices()[:n]
        mesh = Mesh(np.array(devices), ("shards",))
        # Bucketed pad: same-bucket layers share one compiled gather
        # (plan_cache) — the splice slices the real sizes back out.
        pad = bucket_pad(max(sizes))
        staged = np.zeros((n, pad), dtype=np.uint8)
        for k in range(n):
            staged[k, : sizes[k]] = np.frombuffer(bytes(by_k[k]), np.uint8)
        v = jax.device_put(
            staged.reshape(n * pad),
            NamedSharding(mesh, P("shards")))
        gathered_dev = gather_tiles(mesh, "shards", tuple(sizes),
                                    pad=pad)(v)
        out = np.asarray(jax.device_get(gathered_dev)).tobytes()[:total]
    if len(out) != total:
        raise ValueError(f"gathered {len(out)} bytes; layer is {total}")
    if verify_digest:
        from ..utils import integrity

        if not integrity.digest_matches(out, verify_digest):
            raise ValueError("gathered layer failed the stamped "
                             "full-layer digest")
    trace.count("shard.gathered_layers")
    if decode is None:
        return out
    # Dequant AFTER the gather (and only after the digest gate above —
    # corrupt bytes must never reach the decode): the device path feeds
    # the already-replicated HBM blob straight into the codec's jit.
    # Advisory: a decode failure (bytes that aren't a model blob) costs
    # only the staged leaves — the materialized wire bytes still return.
    try:
        leaves = _decode_gathered(out, gathered_dev, total, codec, decode)
    except Exception as e:  # noqa: BLE001 — decode is an optimization
        log.warn("post-gather dequant failed; bulk staging will cover "
                 "the blob", err=repr(e))
        leaves = None
    return out, leaves


def _decode_gathered(wire: bytes, gathered_dev, total: int, codec: str,
                     decode):
    """The codec-aware tail of ``gather_byte_shards``: decode the
    gathered wire blob into staged leaves ({name: (1, *shape)} — the
    streaming stager's layout) under ``codec``, on device when the
    gather left an HBM-resident copy."""
    from ..models import quant, serde
    from ..utils import trace

    cfg, blob_id = decode
    specs = tuple(serde.blob_specs(cfg, blob_id))
    dt_name = np.dtype(cfg.dtype).name
    if not codec:
        codec = "raw"
    if gathered_dev is not None and codec not in quant.ENTROPY_CODECS:
        # The replicated gather output is padded past ``total``; the
        # decode jits take exact-size blobs — one device-local slice.
        # Entropy forms have no device decode program: they fall to the
        # host branch (the gather kept the host wire copy).
        blob = jax.lax.slice(gathered_dev, (0,), (total,))
        trace.count("pod.device_dequants")
        return quant.device_decode_jit(codec)((blob,), specs, dt_name)
    decoded = quant.decode_blob_host(cfg, blob_id, wire, codec)
    return {name: arr[None] for name, arr in decoded.items()}


@functools.lru_cache(maxsize=64)
def _allgather_fn(mesh: Mesh, axis: str):
    @jax.jit
    def gather(v):
        return jax.shard_map(
            lambda s: lax.all_gather(s, axis, tiled=True),
            mesh=mesh,
            in_specs=P(axis),
            out_specs=P(),
            check_vma=False,
        )(v)

    return gather


def allgather_shards(shards: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """Mode-3 equivalent: every device contributes its shard; the full
    layer materializes replicated on all devices in one collective."""
    return _allgather_fn(mesh, axis)(shards)


@functools.lru_cache(maxsize=64)
def _ring_broadcast_fn(mesh: Mesh, axis: str, src: int):
    n = mesh.shape[axis]
    fwd: Tuple[Tuple[int, int], ...] = tuple((i, (i + 1) % n) for i in range(n))

    def per_device(buf):
        idx = lax.axis_index(axis)
        # Hop distance from the source along the ring.
        dist = (idx - src) % n

        def step(k, b):
            recv = lax.ppermute(b, axis, fwd)
            # Devices exactly k hops downstream adopt the relayed copy;
            # earlier hops already hold it, later hops wait their turn.
            return jnp.where(dist == k, recv, b)

        return lax.fori_loop(1, n, step, buf)

    @jax.jit
    def broadcast(v):
        return jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=P(axis),
            out_specs=P(axis),
            check_vma=False,
        )(v)

    return broadcast


def ring_broadcast(
    per_device: jax.Array, mesh: Mesh, axis: str, src: int = 0
) -> jax.Array:
    """Mode-1 equivalent: relay the source device's block around the ring
    with n-1 ``ppermute`` hops until every device holds it.

    ``per_device`` is sharded along ``axis`` (one block per device); the
    result is also sharded, with every block equal to the source's.  On a
    TPU torus each hop is a neighbor ICI transfer, so the relay pipelines
    exactly like the reference's TeeReader cut-through chain."""
    return _ring_broadcast_fn(mesh, axis, src)(per_device)


@functools.lru_cache(maxsize=64)
def _permute_fn(mesh: Mesh, axis: str, perm: Tuple[Tuple[int, int], ...]):
    @jax.jit
    def permute(v):
        return jax.shard_map(
            lambda s: lax.ppermute(s, axis, perm),
            mesh=mesh,
            in_specs=P(axis),
            out_specs=P(axis),
            check_vma=False,
        )(v)

    return permute


def permute_blocks(
    per_device: jax.Array,
    mesh: Mesh,
    axis: str,
    perm: Sequence[Tuple[int, int]],
) -> jax.Array:
    """General leader-directed point-to-point schedule: one
    ``collective_permute`` step moving each source's block to its dest —
    the device-plane form of a batch of retransmitMsg commands
    (distributor/message.go:94-118)."""
    return _permute_fn(mesh, axis, tuple(perm))(per_device)


@functools.lru_cache(maxsize=64)
def _one_to_all_fn(mesh: Mesh, axis: str, src: int):
    @jax.jit
    def run(v):
        def per_device(s):
            idx = lax.axis_index(axis)
            contrib = jnp.where(idx == src, s, jnp.zeros_like(s))
            return lax.psum(contrib, axis)

        return jax.shard_map(
            per_device, mesh=mesh, in_specs=P(axis), out_specs=P(),
            check_vma=False,
        )(v)

    return run


def one_to_all(
    x: jax.Array, mesh: Mesh, axis: str, src: int = 0
) -> jax.Array:
    """Mode-0 as an explicit collective: zero-mask every non-source block
    and psum — the source's block lands everywhere.  Prefer ``replicate``
    (XLA broadcast) in production; this exists for schedule parity tests."""
    return _one_to_all_fn(mesh, axis, src)(x)
