"""Model boot from disseminated bytes: the startup hook, made real.

The reference broadcasts a ``startupMsg`` whose handler is a stub — "the
hook that would launch an inference engine"
(``/root/reference/distributor/message.go:216-241``,
``distributor/node.go:1387-1389``).  Here the hook boots one: a receiver
assembles its delivered layer blobs into its model family's params
(``models/family.py``) and runs a jitted forward pass, so dissemination ends at a *serving model*, not a pile
of bytes — and the leader can report time-to-first-token next to TTD.

Two boot shapes, chosen by what the node holds:
- **full**: the node's blobs cover every layer plus the head blob — the
  whole model boots and produces real logits (the reference benchmark
  scenario: one cold node receives the complete model).
- **stage**: the node holds a contiguous slice of layers (a pipeline
  stage) — its stacked stage params run over dummy activations, proving
  the stage's weights are resident and usable on its devices.

Assembly prefers the device path: blobs that the ``-hbm`` ingest landed in
HBM are bit-reinterpreted on the accelerator (``serde.stacked_from_device_
blobs``) — the disseminated bytes never make a host round-trip.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, Optional, Sequence

from ..core.types import LayersSrc
from ..utils import env as env_util, trace
from ..utils.logging import log

# The boot's jitted programs are MODULE-LEVEL singletons (llama.forward_jit
# and _stage_forward_jitted below): precompile_boot lowers + compiles the
# same callables boot_from_layers calls, so a precompile during
# dissemination turns the boot-time jit call into a cache hit.
_stage_fwd_lock = threading.Lock()
_stage_fwd = None

# BootResult.via of a boot whose layer params were assembled from host
# bytes — what a receiver asked for -hbm must never see.
VIA_HOST_ASSEMBLY = "host assembly"


def blob_donate_ok(src) -> bool:
    """Whether the boot may CONSUME this blob's device copy (donated
    staging).  Policy (``utils.env.boot_donate_mode``): off = never;
    force = always (tests/benchmarks — unsafe with CPU-adopted buffers);
    auto = only when a host fallback survives the consumption (later
    retransmits / update() cycles read ``inmem_data``/disk once
    ``device_array`` is gone) AND the blob lives on a non-CPU device —
    the CPU backend zero-copy-adopts host buffers, and donating an
    adopted array lets XLA overwrite the memory ``inmem_data`` aliases."""
    mode = env_util.boot_donate_mode()
    if mode == "off":
        return False
    arr = getattr(src, "device_array", None)
    if arr is None:
        return False
    if mode == "force":
        return True
    # A host RAM copy is the only fallback that survives: a bare disk
    # path does NOT — read_span's DISK branch is gated on
    # location == DISK, and an HBM-located record with device_array
    # cleared and no inmem_data has no readable bytes at all.
    if src.inmem_data is None:
        return False
    try:
        return all(d.platform != "cpu" for d in arr.devices())
    except Exception:  # noqa: BLE001 — unknown array kind: keep it
        return False


def _stage_forward_jitted():
    """The stage boot's forward (``llama.apply_layers`` over the stacked
    stage params: a scan for each run of one kind of layer), jitted once
    per process.  The dummy activation input is DONATED (it is boot-local
    and dead after the call) so XLA can run the scan's carry in place;
    the stacked params are NOT — they are the boot's product
    (``BootResult.params``) and must stay resident.  ``layer_ids`` (a
    tuple, static) says which layers the stage holds: their kinds are the
    family's to tell."""
    global _stage_fwd
    with _stage_fwd_lock:
        if _stage_fwd is None:
            import functools

            import jax
            import jax.numpy as jnp

            from ..models.llama import apply_layers

            @functools.partial(jax.jit, static_argnums=(2, 3),
                               donate_argnums=(1,))
            def stage_forward(stacked, x, cfg, layer_ids):
                return apply_layers(stacked, x, jnp.arange(x.shape[1]), cfg,
                                    layer_ids)

            _stage_fwd = stage_forward
    return _stage_fwd


@dataclasses.dataclass
class BootResult:
    kind: str  # "full" | "stage"
    seconds: float  # wall time: blob assembly + compile + first forward
    layer_ids: Sequence[int]
    logits: Any = None  # full boots only
    activations: Any = None  # stage boots only
    tokens: Any = None  # full boots with generate_tokens > 0
    # The assembled params stay RESIDENT — they are the product of the
    # dissemination (full boots: the whole pytree; stage boots: this
    # stage's stacked layer dict, on its stage's devices) and what
    # pod-level pipelined serving (runtime/pp_serve.py) consumes.
    params: Any = None
    # How the layer params were assembled ("streamed per-layer", "device
    # bitcast", "host assembly", ...): a receiver asked for -hbm reads it
    # to tell a device-path boot from one that fell back to the host.
    via: str = ""


def classify_held_blobs(cfg, held_ids) -> tuple:
    """The boot's view of a held blob-id set: ``(layer_ids, full)``.
    Raises ValueError for sets no boot shape accepts (no layers, or a
    non-contiguous slice).  THE shared classifier: ``boot_from_layers``
    and ``precompile_boot`` must agree on what a set means, or a hint-
    time precompile warms the wrong program."""
    from ..models import serde

    head_id = serde.head_blob_id(cfg)
    held = sorted(b for b in set(held_ids) if b <= head_id)
    layer_ids = [b for b in held if b < head_id]
    if not layer_ids:
        raise ValueError(f"no model layer blobs among held layers {held}")
    if layer_ids != list(range(layer_ids[0], layer_ids[0] + len(layer_ids))):
        raise ValueError(f"held layer blobs are not contiguous: {layer_ids}")
    full = set(held) >= set(range(head_id + 1))
    return layer_ids, full


def _device_blob(src) -> Optional[Any]:
    """The layer's HBM-resident uint8 array, when ingest staged one."""
    arr = getattr(src, "device_array", None)
    if arr is None:
        return None
    try:
        import numpy as np

        if arr.dtype == np.uint8 and arr.ndim == 1:
            return arr
    except Exception:  # noqa: BLE001 — any surprise: use host bytes
        return None
    return None


def verify_blob_digest(blob_id: int, src, digest_lookup,
                       digest_verified) -> None:
    """Integrity backstop at the boot boundary (docs/integrity.md):
    verify a blob's HOST bytes against its expected layer digest
    (stamp-described algorithm: xxh3-128 or blake2b-128) before any
    decode/device placement dispatches.  Skips blobs the
    receiver's ack gate already verified (``digest_verified``), blobs
    without a known digest, and device-only blobs (their bytes were
    verified before staging).  Raises ``ValueError`` on mismatch — the
    streamed stager fails that blob's staging; the bulk boot fails
    loudly (a corrupted model must never serve)."""
    if digest_lookup is None:
        return
    if digest_verified is not None and blob_id in digest_verified:
        return
    expected = digest_lookup(blob_id)
    if expected is None or src.inmem_data is None:
        return
    from ..utils import integrity

    with trace.span("wire.digest", bytes=src.data_size, at="boot"):
        ok, dt, got = integrity.digest_check(
            memoryview(src.inmem_data)[
                src.offset : src.offset + src.data_size],
            expected)
    if ok is None:
        return  # xxh3 stamp, no xxhash here: advisory skip
    if not ok:
        trace.count("integrity.digest_mismatch")
        raise ValueError(
            f"blob {blob_id} failed its boot-time digest check "
            f"(expected {expected}, got {got})")
    if digest_verified is not None:
        digest_verified.add(blob_id)


def stage_blob_leaves(cfg, blob_id: int, src, codec: str = "raw",
                      sharding=None, span=None) -> dict:
    """ONE blob's share of the boot: its decoded leaves, each with a
    leading length-1 axis so assembly is a uniform per-leaf concatenate.
    THE shared per-blob staging: the streaming stager runs it mid-wire
    (``runtime/stream_boot.py``) and ``boot_from_layers`` runs the same
    code to infill any blob the stager missed — both must produce
    identical bits and hit the same compiled programs.

    Device path: the HBM-resident wire blob decodes under the PLAIN
    (non-donated) 1-blob codec jit — callers release consumable blobs by
    dropping references (``blob_donate_ok``), never by ``donate_argnums``
    (a concurrent flow-retransmit reader holding the array would crash
    on an XLA-deleted buffer); the program is the one of the blob's KIND
    (``serde.blob_specs``: a blob's leaves depend on its id).  ``span``
    (the caller's ``decode.stage``) learns the ``kind`` and, on this
    path, how the program widens the blob (``fast_bytes``,
    ``slow_bytes``: ``quant.widen_bytes``).  Host path: numpy decode +
    async ``device_put`` per leaf (under ``sharding`` when given)."""
    import jax
    import numpy as np

    from ..models import quant, serde

    specs = tuple(serde.blob_specs(cfg, blob_id))
    if span is not None:
        span.set(kind=serde.blob_kind(cfg, blob_id))
    arr = _device_blob(src)
    data = None
    if arr is not None and codec in quant.ENTROPY_CODECS:
        # Entropy forms have no device decode program (docs/codec.md):
        # pull the HBM-resident wire blob back to host, unwrap there
        # (decode_blob_host runs the DLE1 pass before the base decode),
        # and stage via the host path.  Boot-path cost:
        # quant.codec_bench measures it on the running host.
        data = np.asarray(arr).tobytes()
        if blob_donate_ok(src):
            src.device_array = None
        arr = None
    if arr is not None:
        dt_name = np.dtype(cfg.dtype).name
        if span is not None:
            fast, slow = quant.widen_bytes(codec, specs, dt_name)
            span.set(fast_bytes=fast, slow_bytes=slow)
        decode = quant.device_decode_jit(codec, donate=False)
        leaves = decode((arr,), specs, dt_name)
        if blob_donate_ok(src):
            src.device_array = None
        return leaves
    if data is None:
        data = (src.inmem_data if src.inmem_data is not None
                else src.read_bytes())
    host = quant.decode_blob_host(cfg, blob_id, data, codec)
    out = {}
    for name, _ in specs:
        a = host[name][None]  # leading axis: uniform concat assembly
        out[name] = (jax.device_put(a, sharding)
                     if sharding is not None else jax.device_put(a))
    return out


def decode_head(cfg, src, codec: str = "raw", donate: bool = False):
    """The non-layer leaves from a head-blob ``LayerSrc`` — the
    device path when the blob is HBM-resident (jax arrays), the host
    path otherwise (numpy).  Shared by the full boot and pod serving
    (``runtime/pp_serve.py``) so the decode dispatch lives once.
    ``donate``: consume the device blob in place (the record's
    ``device_array`` is cleared — host fallback serves later readers)."""
    from ..models import quant

    dev = _device_blob(src)
    if dev is not None and codec in quant.ENTROPY_CODECS:
        # No device decode program for entropy forms: host unwrap,
        # exactly like stage_blob_leaves (docs/codec.md).
        import numpy as np

        data = np.asarray(dev).tobytes()
        if donate:
            src.device_array = None
        return quant.head_from_blob_host(cfg, data, codec)
    if dev is not None:
        out = quant.head_from_device(cfg, dev, codec, donate=donate)
        if donate:
            src.device_array = None
        return out
    data = src.inmem_data if src.inmem_data is not None else src.read_bytes()
    return quant.head_from_blob_host(cfg, data, codec)


def decode_after_boot(cfg, res, n: int, tokens=None):
    """Greedy-decode ``n`` tokens from a FULL boot's resident params
    (the KV-cached serving loop, models/generate.py); records
    ``res.tokens``.  THE shared post-boot decode: ``boot_from_layers``'s
    ``generate_tokens`` and the receiver's ``-gen`` both route here, and
    both keep it out of the TTFT clock — serving time, not boot time."""
    import jax
    import jax.numpy as jnp

    from ..models.generate import generate

    if n <= 0:
        return None
    if res.kind != "full" or res.params is None:
        log.warn("decode skipped: -gen needs a FULL boot (this node "
                 "booted a pipeline stage)", kind=res.kind, requested=n)
        return None
    t_gen = time.monotonic()
    if tokens is None:
        tokens = jnp.zeros((1, 16), jnp.int32)
    toks = generate(res.params, tokens, cfg, max_new=n)
    jax.block_until_ready(toks)
    res.tokens = toks
    log.info("decoded tokens after boot", generated=int(toks.shape[1]),
             decode_ms=round((time.monotonic() - t_gen) * 1000, 1))
    return toks


def boot_from_layers(
    cfg,
    layers: LayersSrc,
    placement=None,
    node_id=None,
    tokens=None,
    codec: str = "raw",
    generate_tokens: int = 0,
    stager=None,
    digest_lookup=None,
    digest_verified=None,
) -> BootResult:
    """Assemble delivered blobs into model params and run one forward.

    ``layers``: the receiver's store after dissemination.  ``placement``:
    when given (with ``node_id``), params land replicated on this node's
    stage devices via ``StagePlacement``; otherwise the default device.
    ``codec``: the transfer codec the blobs were encoded with
    (``models/quant.py``); quantized ("int8"/"int4") blobs are
    dequantized during assembly — on-device when they were ingested to
    HBM.
    ``stager``: a ``runtime.stream_boot.StreamingBootStager`` that has
    been decoding layers per-blob AS THEY ARRIVED; when it covers every
    layer blob, assembly is one device-local concatenate per leaf (the
    decode + host→device work already overlapped the wire) — bit-
    identical to the bulk paths by construction, completion order
    included (each blob decodes independently; the concat is in layer-id
    order).
    Returns a BootResult whose ``seconds`` is the time from blob assembly
    to the first forward's output being ready (includes jit compile — the
    honest time-to-first-token a cold boot pays)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import family, quant, serde
    from ..models.llama import forward_jit

    t0 = time.monotonic()
    head_id = serde.head_blob_id(cfg)
    layer_ids, full = classify_held_blobs(cfg, layers)

    # Integrity gate: every host-readable blob verifies against its
    # expected digest before device placement (blobs the receiver's ack
    # path already verified skip in O(1) — ``digest_verified``).  A
    # mismatch raises: a corrupted model must never serve.
    if digest_lookup is not None:
        for lid in sorted(set(layer_ids) | ({head_id} & set(layers))):
            verify_blob_digest(lid, layers[lid], digest_lookup,
                               digest_verified)

    # Wire-codec holdings (docs/codec.md): a blob delivered under a
    # NEGOTIATED per-transfer codec differs in form from the run codec
    # the bulk decode paths below assume.  The streaming stager decodes
    # those per-blob under their own codec (the fast path); any such
    # blob that reaches the bulk/infill paths is normalized here to the
    # canonical raw form on host (wire codecs require a raw-canonical
    # run, core/config.py) so every downstream path sees one uniform
    # codec.  The overlay is local — the receiver's store keeps the
    # encoded holding it announced and serves.
    mixed = [lid for lid in (layer_ids
                             + ([head_id] if head_id in layers else []))
             if getattr(layers[lid].meta, "codec", "")
             and layers[lid].meta.codec != codec]
    if mixed:
        from ..core.types import LayerLocation as _Loc
        from ..core.types import LayerMeta as _Meta
        from ..core.types import LayerSrc as _Src

        layers = dict(layers)
        for lid in mixed:
            src = layers[lid]
            raw = quant.decode_to_raw(cfg, lid, src.read_bytes(),
                                      src.meta.codec)
            layers[lid] = _Src(
                inmem_data=bytearray(raw), data_size=len(raw),
                meta=_Meta(location=_Loc.INMEM))
        log.info("normalized wire-codec blobs for bulk assembly",
                 blobs=mixed)

    sharding = None
    if placement is not None and node_id in placement.node_to_stage:
        from jax.sharding import PartitionSpec as P
        from jax.sharding import NamedSharding

        sharding = NamedSharding(
            placement.stage_mesh(placement.node_to_stage[node_id]), P()
        )

    # Assembly: streamed per-layer leaves splice with one concat per
    # leaf; otherwise device blobs stay on device (donated when safe —
    # the wire blobs are consumed in place instead of doubling the
    # footprint); otherwise host blobs go up in one device_put per
    # leaf-stack.
    held = layer_ids + ([head_id] if head_id in layers else [])
    streamed: Dict[int, dict] = {}
    stream_wait_s = 0.0
    if stager is not None:
        t_w = time.monotonic()
        with trace.span("boot.wait_stream", node=node_id, blobs=len(held)):
            # The boot owns the staged leaves from here (its own dicts,
            # the stager's references dropped): assembly below pops each
            # leaf as it stacks it, so the device holds the model and ONE
            # stacked leaf kind in flight, not the model twice.
            streamed = {lid: dict(leaves)
                        for lid, leaves in stager.collect(held).items()}
            stager.release(streamed)
        stream_wait_s = time.monotonic() - t_w
    # Taken AFTER the wait: a wire blob the stager released while staging
    # must not be kept alive through assembly by a reference held here.
    dev_blobs = {lid: _device_blob(layers[lid]) for lid in held}
    stacked = None
    via = ""
    with trace.span("boot.assemble", node=node_id, blobs=len(held)) as asm:
        if streamed:
            try:
                missing = [lid for lid in layer_ids if lid not in streamed]
                for lid in missing:
                    # Infill: the stager missed this blob (a per-blob
                    # failure, or collect hit its timeout) — run the SAME
                    # per-blob staging here, so one bad blob costs one
                    # inline decode, never a whole-model host reassembly
                    # (the stager may already have released the OTHER
                    # blobs' device copies).
                    streamed[lid] = stage_blob_leaves(
                        cfg, lid, layers[lid], codec=codec, sharding=sharding)
                # One stack a kind of layer, each staged leaf taken out
                # of its blob's dict as it is stacked (``family.stack``).
                with jax.named_scope("boot.assemble"):
                    stacked = family.stack(cfg, layer_ids, streamed.get,
                                           jnp.concatenate)
                # The decoded params exist: blobs whose device copy the boot
                # may consume are released now (same bookkeeping as the
                # donated bulk decode — host fallbacks keep serving late
                # readers).
                for lid in held:
                    if lid in streamed and blob_donate_ok(layers[lid]):
                        layers[lid].device_array = None
                        dev_blobs[lid] = None
                via = ("streamed per-layer" if not missing
                       else f"streamed per-layer (+{len(missing)} infilled)")
            except Exception as e:  # noqa: BLE001 — bulk assembly still works
                log.warn("streamed assembly failed; bulk assembly instead",
                         err=repr(e))
                trace.count("device.degraded.stream_assembly")
                stacked = None
        if stacked is None and all(
                dev_blobs[lid] is not None for lid in layer_ids):
            donate = all(blob_donate_ok(layers[lid]) for lid in layer_ids)
            stacked = quant.stacked_from_device(
                cfg, [dev_blobs[lid] for lid in layer_ids], codec,
                donate=donate, layer_ids=layer_ids)
            via = "device bitcast" if codec == "raw" else f"device {codec} dequant"
            if donate:
                for lid in layer_ids:
                    layers[lid].device_array = None
                    dev_blobs[lid] = None
                via += " (donated)"
        elif stacked is None:
            blobs = {
                lid: (
                    layers[lid].inmem_data
                    if layers[lid].inmem_data is not None
                    else layers[lid].read_bytes()
                )
                for lid in layer_ids
            }
            stacked = jax.tree.map(
                lambda a: jax.device_put(a, sharding) if sharding is not None
                else jnp.asarray(a),
                quant.stacked_from_blobs_host(cfg, blobs, layer_ids, codec))
            via = VIA_HOST_ASSEMBLY

        if full:
            head_on_device = dev_blobs[head_id] is not None
            if head_id in streamed:
                head = {name: a[0]
                        for name, a in streamed.pop(head_id).items()}
                head_on_device = True  # streamed leaves are already placed
            else:
                head = decode_head(cfg, layers[head_id], codec,
                                   donate=blob_donate_ok(layers[head_id]))
            if not head_on_device:
                # Host-decoded leaves: place per the stage sharding.
                head = {
                    name: jax.device_put(a, sharding) if sharding is not None
                    else jnp.asarray(a)
                    for name, a in head.items()
                }
            params = {**head, "layers": stacked}
        asm.set(via=via, kinds=len(family.group(cfg, layer_ids)))
    if full:
        if tokens is None:
            tokens = jnp.zeros((1, 16), jnp.int32)
        # forward_jit is the module-level jitted forward: when a
        # BootHintMsg precompile already lowered this shape, the call
        # below is a cache hit and TTFT drops by the compile time.
        with trace.span("boot.first_forward", node=node_id, kind="full"):
            logits = forward_jit(params, tokens, cfg)
            jax.block_until_ready(logits)
        # TTFT stops HERE: the decode below is serving time, not boot
        # time — it must not contaminate the metric reported next to TTD.
        dt = time.monotonic() - t0
        log.info("model booted from disseminated layers", kind="full",
                 layers=len(layer_ids), via=via, ttft_ms=round(dt * 1000, 1),
                 stream_wait_ms=round(stream_wait_s * 1000, 1))
        res = BootResult("full", dt, layer_ids, logits=logits,
                         params=params, via=via)
        decode_after_boot(cfg, res, generate_tokens, tokens=tokens)
        return res

    # Stage boot: run this stage's slice on dummy activations (the
    # module-level jit, so a hint-time precompile makes this a cache hit).
    x = jnp.zeros((1, 16, cfg.d_model), cfg.dtype)
    if sharding is not None:
        x = jax.device_put(x, sharding)
    with trace.span("boot.first_forward", node=node_id, kind="stage"):
        acts = _stage_forward_jitted()(stacked, x, cfg, tuple(layer_ids))
        jax.block_until_ready(acts)
    dt = time.monotonic() - t0
    log.info("pipeline stage booted from disseminated layers", kind="stage",
             layers=len(layer_ids), via=via, ttft_ms=round(dt * 1000, 1),
             stream_wait_ms=round(stream_wait_s * 1000, 1))
    return BootResult("stage", dt, layer_ids, activations=acts,
                      params=stacked, via=via)


def precompile_boot(
    cfg,
    blob_ids: Sequence[int],
    placement=None,
    node_id=None,
    codec: str = "raw",
    device_blobs: bool = False,
    streamed: Optional[bool] = None,
) -> dict:
    """Lower + compile the boot's jitted programs for the held set
    ``blob_ids`` BEFORE the bytes arrive — XLA compiles from shapes
    alone, so a receiver that gets a ``BootHintMsg`` at distribution
    start can overlap the whole compile with the network transfer and
    the post-startup boot hits warm caches.  The compiles also WRITE
    JAX's persistent compilation cache (placed at process entry,
    ``utils.env.place_compile_cache``), so the next run's precompile —
    or boot — is a disk hit instead of an XLA compile.

    Compiles the same module-level callables ``boot_from_layers`` calls
    (``llama.forward_jit`` / ``_stage_forward_jitted`` and, for
    ``device_blobs``, the codec decode jits), so the warm-up needs no
    handle passing.  ``streamed`` (default: the ``DLD_STREAM_BOOT`` env
    gate, matching the receiver) picks which decode program to warm:
    the streaming stager decodes ONE blob per call, the bulk boot
    decodes all n under one jit — distinct cache entries.  The donated
    decode twin is warmed when the donation mode resolves on for the
    target devices (per-blob host-fallback checks at boot time can still
    demote a blob — only a cache miss).  Returns {"compiled": [...]}
    naming what was warmed (for logs and tests).  Best-effort by design:
    any mismatch with the real boot (different path, sharding, shapes)
    is only a cache miss."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models import family, quant, serde
    from ..models.llama import forward_jit

    if streamed is None:
        streamed = env_util.stream_boot_enabled()
    head_id = serde.head_blob_id(cfg)
    try:
        layer_ids, full = classify_held_blobs(cfg, blob_ids)
    except ValueError:
        return {"compiled": []}  # boot_from_layers would reject this set
    dt = cfg.dtype
    dt_name = np.dtype(dt).name

    # Sharding discipline mirrors boot_from_layers EXACTLY — jit cache
    # keys include argument shardings (committed-ness included), and the
    # two real paths differ:
    # - host assembly: every leaf is device_put with the stage sharding
    #   (committed) when a placement maps this node, else jnp.asarray
    #   (uncommitted);
    # - device (-hbm) assembly: the staged wire blobs are COMMITTED to
    #   the stage device (device_put / host-buffer adoption / the
    #   make_array gather — every ingest arm), and jit outputs inherit
    #   their inputs' commitment, so the decode outputs feeding the
    #   forward are committed to the same device.  The stage boot's
    #   dummy activations are still device_put with the stage sharding.
    stage_sharding = None
    stage_devs = None
    if placement is not None and node_id in placement.node_to_stage:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        stage_sharding = NamedSharding(
            placement.stage_mesh(placement.node_to_stage[node_id]), P()
        )
        stage_devs = list(placement.devices_for_node(node_id))
    if device_blobs:
        devs = stage_devs or [jax.devices()[0]]
        if len(devs) == 1:
            dev_sharding = jax.sharding.SingleDeviceSharding(devs[0])
        else:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from ..parallel.ingest import flat_mesh

            dev_sharding = NamedSharding(flat_mesh(devs), P())
        leaf_sharding = dev_sharding
    else:
        dev_sharding = None
        leaf_sharding = stage_sharding
    x_sharding = stage_sharding

    def sds(shape, dtype, sharding):
        if sharding is not None:
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        return jax.ShapeDtypeStruct(shape, dtype)

    compiled = []
    t0 = time.monotonic()
    # One stack, and one set of decode programs, for each KIND of layer
    # held (a uniform family: one): the programs number with the kinds,
    # not with the depth.
    groups = family.group(cfg, layer_ids)
    kind_specs = {kind: tuple(serde.layer_param_specs(cfg, ids[0]))
                  for kind, ids in groups.items()}
    stacked_abs = family.of_kinds(cfg, {
        kind: {name: sds((len(groups[kind]), *shape), dt, leaf_sharding)
               for name, shape in specs}
        for kind, specs in kind_specs.items()})

    def blob_abs(lid):
        return sds((quant.blob_nbytes_codec(cfg, lid, codec),), jnp.uint8,
                   dev_sharding)

    if device_blobs:
        # The -hbm path decodes HBM-resident wire blobs under these
        # jits.  The exact callable matters (donated and plain variants
        # are distinct executables): the STREAMING stager always runs
        # the PLAIN 1-blob program (it releases blobs by reference, not
        # donate_argnums — stream_boot._stage_one), while the bulk boot
        # runs the donated n-blob variant when donation resolves on for
        # these devices (blob_donate_ok minus the per-blob host-fallback
        # check, unknowable from shapes alone).
        if streamed:
            # One plain 1-blob program covers every layer blob of a
            # kind (else the first blob of a new kind would compile in
            # the stager, mid-wire); the head's is warmed below.
            decode = quant.device_decode_jit(codec, donate=False)
        else:
            mode = env_util.boot_donate_mode()
            donate = (mode == "force"
                      or (mode == "auto"
                          and all(d.platform != "cpu" for d in devs)))
            decode = quant.device_decode_jit(codec, donate)
        for kind, ids in groups.items():
            held = ids[:1] if streamed else ids
            decode.lower(tuple(blob_abs(lid) for lid in held),
                         kind_specs[kind], dt_name).compile()
            compiled.append(f"decode[{codec}]x{len(held)}"
                            + ("" if len(groups) == 1 else f"/{kind}"))
        if full:
            decode.lower(
                (blob_abs(head_id),), tuple(serde.head_param_specs(cfg)),
                dt_name).compile()
            compiled.append(f"decode[{codec}]head")

    if full:
        head_abs = {name: sds(shape, dt, leaf_sharding)
                    for name, shape in serde.head_param_specs(cfg)}
        params_abs = {**head_abs, "layers": stacked_abs}
        tok_abs = jax.ShapeDtypeStruct((1, 16), jnp.int32)
        forward_jit.lower(params_abs, tok_abs, cfg).compile()
        compiled.append("forward")
    else:
        x_abs = sds((1, 16, cfg.d_model), dt, x_sharding)
        _stage_forward_jitted().lower(stacked_abs, x_abs, cfg,
                                      tuple(layer_ids)).compile()
        compiled.append("stage_forward")
    return {"compiled": compiled,
            "compile_s": round(time.monotonic() - t0, 2),
            "persistent_cache": bool(jax.config.jax_compilation_cache_dir)}
