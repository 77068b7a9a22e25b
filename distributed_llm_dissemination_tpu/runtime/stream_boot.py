"""Per-layer receive-to-device streaming boot staging.

The boot used to be strictly SEQUENCED after delivery: every blob lands,
startup fires, and only then does the boot decode all n wire blobs and
place the params — at physical scale that serial tail is several times
the transfer it follows (VERDICT r5 item 4).  Redistribution work hides
that class of latency by overlapping data movement with the downstream
compute (arXiv:2112.01075, arXiv:2412.14374); this module is the boot's
version of the same move.

``StreamingBootStager`` accepts each blob THE MOMENT its interval set
completes (the receiver's completion commit — mid-wire for every blob
but the last) and immediately runs that blob's share of the boot work on
a dedicated worker thread:

- **device path** (``-hbm``): the HBM-resident wire blob is decoded
  per-blob under the same codec jits the bulk boot uses, 1-blob
  programs — one compile (or persistent-cache read) covers every layer
  of a kind (``models/family.py``; ``boot.precompile_boot`` warms each
  kind's), and each decode overlaps the remaining transfers;
- **host path**: the blob is decoded on host (numpy views) and each
  leaf ``device_put`` — asynchronous, so the host→device DMA of layer k
  rides under the receive of layer k+1.

``boot_from_layers`` then assembles the staged leaves with one
device-local concatenate per leaf of each kind of layer (HBM-bandwidth
work) — bit-identical
to the bulk assembly regardless of COMPLETION ORDER, because each blob
decodes independently and the concat is in layer-id order.

Leaves are stored with a leading length-1 axis (the decode jits'
natural output for a 1-tuple; host leaves get ``[None]``) so assembly is
a plain ``jnp.concatenate`` for both paths.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Dict, Optional

from ..utils import env as env_util, trace
from ..utils.logging import log


class StreamingBootStager:
    """Decode/stage completed blobs concurrently with the receive.

    Thread model: ``submit`` is called from receiver handler threads
    (idempotent per blob — re-plan duplicates are no-ops) and enqueues;
    ONE worker daemon drains the queue (per-blob decodes are big device
    dispatches — a pool would just thrash the link).  ``collect`` blocks
    until every submitted blob is processed and returns the staged
    leaves; the boot calls it once at startup.  Failures are per-blob
    and non-fatal: a blob that fails to stage is simply absent from
    ``collect`` and the boot falls back to bulk assembly."""

    def __init__(self, cfg, codec: str = "raw", placement=None,
                 node_id=None, digest_lookup=None, digest_verified=None):
        """``digest_lookup``/``digest_verified`` (integrity plane): a
        ``blob_id -> expected hex digest (or None)`` callable and the
        receiver's already-verified id set.  Each blob with host bytes
        re-verifies before its decode is dispatched UNLESS the ack path
        already verified it (the set) — defense in depth for bytes that
        reached the stager without crossing the ack gate, at zero cost
        on the normal path."""
        self.cfg = cfg
        self.codec = codec
        self.placement = placement
        self.node_id = node_id
        self.digest_lookup = digest_lookup
        self.digest_verified = digest_verified
        # Pod-delivery hook (docs/fabric.md): called from the worker
        # thread after every shard-gather attempt with
        # ``(blob_id, full_wire_bytes_or_None, codec)`` — the receiver
        # stores the materialized holding and acks the FULL layer (or
        # degrades loudly) the moment ITS layer gathers, in any
        # completion order across layers.
        self.on_gathered = None
        self._q: "queue.Queue[Optional[tuple]]" = queue.Queue()
        self._lock = threading.Lock()
        self._done = threading.Condition(self._lock)
        self._staged: Dict[int, dict] = {}
        self._released = 0  # staged blobs a boot took over (release)
        # Shard-gather state (docs/sharding.md): blob -> accumulated
        # shard parts, and blob -> the materialized full layer's bytes.
        self._shards: Dict[int, dict] = {}
        self._gathered: Dict[int, bytes] = {}
        self._submitted: set = set()
        self._pending = 0
        self._closed = False
        self._startup_seen = False
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------- intake

    def submit(self, blob_id: int, src) -> bool:
        """Queue a completed blob for staging; False for duplicates,
        closed stagers, or ids the boot can never use."""
        from ..models import serde

        if self.cfg is None or blob_id > serde.head_blob_id(self.cfg):
            # cfg-less stagers exist purely as shard-gather drivers
            # (pod delivery on a -boot none run): nothing boots here.
            return False
        with self._lock:
            if self._closed or blob_id in self._submitted:
                return False
            self._submitted.add(blob_id)
            self._pending += 1
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, daemon=True,
                    name=f"boot-stream-{self.node_id}")
                self._thread.start()
            # Enqueue INSIDE the lock: a racing close() must not slot
            # its None sentinel ahead of this item, or the worker exits
            # with _pending stuck > 0 and collect() waits out its whole
            # timeout.
            self._q.put((blob_id, src))
        return True

    def invalidate(self, blob_id: int) -> None:
        """Forget a blob whose bytes turned out corrupt AFTER submission
        (a digest stamp arriving late demotes the layer): drops both the
        staged leaves and the dedup marker so the redelivered copy
        re-stages.  The worker re-checks the marker before storing, so a
        stage already in flight for the corrupt bytes is discarded
        instead of landing in ``_staged``."""
        with self._lock:
            self._submitted.discard(blob_id)
            self._staged.pop(blob_id, None)

    def mark_startup(self) -> None:
        """Startup arrived: blobs staged from here on no longer overlap
        the wire (accounting only — staging itself continues)."""
        with self._lock:
            self._startup_seen = True

    @property
    def staged_count(self) -> int:
        with self._lock:
            return len(self._staged) + self._released

    # ------------------------------------------------------------ consume

    def collect(self, blob_ids, timeout: float = 300.0) -> Dict[int, dict]:
        """Wait for all in-flight staging, then return {blob_id: leaves}
        for the requested ids that staged successfully.  The returned
        leaves carry a leading length-1 axis (module docstring)."""
        with self._lock:
            self._done.wait_for(lambda: self._pending == 0, timeout=timeout)
            if self._pending:
                log.warn("streamed staging still in flight at collect; "
                         "boot falls back to bulk assembly",
                         pending=self._pending)
                return {}
            return {b: self._staged[b] for b in blob_ids
                    if b in self._staged}

    def release(self, blob_ids) -> None:
        """The boot took these blobs' staged leaves over (``collect``):
        drop this stager's references, so that each leaf frees the moment
        the boot has stacked it.  The submission markers stay — a
        released blob is not staged again."""
        with self._lock:
            for b in blob_ids:
                if self._staged.pop(b, None) is not None:
                    self._released += 1

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            started = self._thread is not None
        if started:
            self._q.put(None)

    # ------------------------------------------------------------- worker

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            if item[0] == "gather":
                self._gather_one(item[1])
                continue
            blob_id, src = item
            leaves = None
            t0 = time.monotonic()
            with trace.span("decode.stage", id=self._pair(blob_id),
                            node=self.node_id) as sp:
                try:
                    leaves = self._stage_one(blob_id, src, sp)
                except Exception as e:  # noqa: BLE001 — boot falls back to bulk
                    log.warn("streamed boot staging failed for blob; bulk "
                             "assembly will cover it", blobID=blob_id,
                             err=repr(e))
                    trace.count("device.degraded.stream_stage")
                    sp.set(error=repr(e))
                dt = time.monotonic() - t0
                with self._lock:
                    # Store only while the submission marker stands — an
                    # invalidate() (corrupt blob demoted mid-stage)
                    # discards this result; the redelivered copy
                    # re-stages.
                    if leaves is not None and blob_id not in self._submitted:
                        log.warn("discarding staged leaves for invalidated "
                                 "blob", blobID=blob_id)
                        leaves = None
                    if leaves is not None:
                        self._staged[blob_id] = leaves
                    in_wire = not self._startup_seen
                    self._pending -= 1
                    if self._pending == 0:
                        self._done.notify_all()
                # stage-overlap-achieved: the blob staged before startup
                sp.set(in_wire=in_wire)
            if leaves is not None:
                log.info("layer boot-staged (streamed)", blobID=blob_id,
                         stage_ms=round(dt * 1000, 1), in_wire=in_wire)
            # This frame waits in ``get`` next: it must not keep the last
            # blob's leaves (or its wire blob) alive meanwhile.
            item = src = leaves = None

    def _pair(self, blob_id) -> Optional[str]:
        """The blob's pair id (every span of one blob shares it)."""
        if self.node_id is None:
            return None
        from ..utils import telemetry

        return telemetry.span_id(self.node_id, blob_id)

    def _sharding(self):
        if (self.placement is not None
                and self.node_id in self.placement.node_to_stage):
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            return NamedSharding(
                self.placement.stage_mesh(
                    self.placement.node_to_stage[self.node_id]), P()
            )
        return None

    def submit_shard(self, blob_id: int, spec: str, data, total: int,
                     expected_digest: str = "", codec: str = "") -> bool:
        """Feed one completed SHARD of a layer to the shard gather
        (docs/sharding.md) — callable the moment the shard's interval
        set completes, in ANY completion order across shards.  Returns
        False for duplicates/closed stagers.  When the last shard of a
        layer arrives, the worker thread runs the on-mesh all-gather
        (``parallel.collectives.gather_byte_shards``) and the
        materialized FULL layer becomes available via
        ``collect_gathered`` — verified against ``expected_digest``
        (the stamped full-layer digest) when one is known.

        ``codec`` (docs/codec.md, docs/fabric.md): the WIRE form the
        shards slice ("" = canonical) — ``total``, the specs' ranges,
        and ``expected_digest`` then all live in encoded byte space,
        and a boot-capable stager dequants the gathered blob on device
        (``quant.device_decode_jit`` after the gather) so the decoded
        leaves stage exactly like a full codec'd delivery's would."""
        from ..core.types import parse_shard_spec

        parsed = parse_shard_spec(spec)
        n, k = parsed if parsed is not None else (1, 0)
        with self._lock:
            if self._closed:
                return False
            rec = self._shards.setdefault(
                blob_id, {"n": n, "total": int(total), "parts": {},
                          "digest": "", "codec": codec, "queued": False})
            if rec["n"] != n or rec["total"] != int(total):
                log.error("conflicting shard geometry submitted",
                          blobID=blob_id, have_n=rec["n"], got_n=n)
                return False
            if k in rec["parts"]:
                return False
            rec["parts"][k] = bytes(data)
            if expected_digest:
                rec["digest"] = expected_digest
            if codec:
                rec["codec"] = codec
            ready = len(rec["parts"]) >= n and not rec["queued"]
            if not ready:
                return True
            rec["queued"] = True
            self._pending += 1
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, daemon=True,
                    name=f"boot-stream-{self.node_id}")
                self._thread.start()
            self._q.put(("gather", blob_id))
        return True

    def collect_gathered(self, blob_ids, timeout: float = 300.0
                         ) -> Dict[int, bytes]:
        """Wait for in-flight gathers, then return {blob_id: full layer
        bytes} for the requested ids whose shard sets materialized."""
        with self._lock:
            self._done.wait_for(lambda: self._pending == 0, timeout=timeout)
            if self._pending:
                log.warn("shard gathers still in flight at collect",
                         pending=self._pending)
                return {}
            return {b: self._gathered[b] for b in blob_ids
                    if b in self._gathered}

    def _gather_one(self, blob_id: int) -> None:
        from ..parallel.collectives import gather_byte_shards

        with trace.span("decode.stage", id=self._pair(blob_id),
                        node=self.node_id, gather=True) as sp:
            self._gather_and_stage(blob_id, gather_byte_shards, sp)

    def _gather_and_stage(self, blob_id: int, gather_byte_shards,
                          sp) -> None:
        t0 = time.monotonic()
        with self._lock:
            rec = self._shards.get(blob_id)
            if rec is None:
                parts, total, digest, codec = None, 0, "", ""
            else:
                parts = sorted(rec["parts"].items())
                total, digest = rec["total"], rec["digest"]
                codec = rec.get("codec", "")
        out, leaves = None, None
        if parts is not None:
            # Boot-capable stagers decode the gathered blob in the same
            # pass (device dequant when the gather ran on-mesh) so the
            # leaves stage exactly like a full delivery's.
            decode = None
            if self.cfg is not None:
                from ..models import serde

                if blob_id <= serde.head_blob_id(self.cfg):
                    decode = (self.cfg, blob_id)
            try:
                got = gather_byte_shards(parts, total,
                                         verify_digest=digest or None,
                                         codec=codec, decode=decode)
                out, leaves = got if decode is not None else (got, None)
            except Exception as e:  # noqa: BLE001 — loud, never wedge
                log.error("on-mesh shard gather failed", blobID=blob_id,
                          err=repr(e))
        dt = time.monotonic() - t0
        with self._lock:
            if out is not None and blob_id in self._shards:
                self._gathered[blob_id] = out
                if leaves is not None:
                    # The gather's dequant already staged this blob:
                    # mark it submitted so a later full-delivery
                    # ``submit`` dedupes instead of re-decoding.
                    self._submitted.add(blob_id)
                    self._staged[blob_id] = leaves
            in_wire = not self._startup_seen
            self._pending -= 1
            if self._pending == 0:
                self._done.notify_all()
        sp.set(in_wire=in_wire)
        if out is not None:
            log.info("layer materialized from shards (on-mesh gather)",
                     blobID=blob_id, gather_ms=round(dt * 1000, 1),
                     in_wire=in_wire, bytes=len(out),
                     codec=codec or None, digest_verified=bool(digest))
        hook = self.on_gathered
        if hook is not None:
            try:
                hook(blob_id, out, codec)
            except Exception as e:  # noqa: BLE001 — the hook must not
                log.error("on_gathered hook failed", blobID=blob_id,
                          err=repr(e))  # kill the worker
        if out is None:
            trace.count("shard.gather_failed")

    def _stage_one(self, blob_id: int, src, sp) -> dict:
        """One blob's staging — ``boot.stage_blob_leaves`` verbatim, so
        the mid-wire path and the boot's infill path share programs and
        bits.  Consumable device blobs (``blob_donate_ok``: host
        fallback retained) are released by reference inside the helper
        the moment their decode is dispatched: HBM peaks at params-so-
        far + the in-flight blob, not params + every wire blob.

        Per-blob codec (docs/codec.md): a blob delivered under a
        negotiated WIRE codec decodes under ITS form, not the run's —
        this is the decode-during-staging half of the quantized wire
        path (the dequant rides the same per-blob device jit the
        global-codec runs use)."""
        from .boot import stage_blob_leaves, verify_blob_digest

        verify_blob_digest(blob_id, src, self.digest_lookup,
                           self.digest_verified)
        codec = getattr(src.meta, "codec", "") or self.codec
        return stage_blob_leaves(self.cfg, blob_id, src, codec=codec,
                                 sharding=self._sharding(), span=sp)
