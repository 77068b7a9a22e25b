"""Process-environment helpers: which backend a process may take, and
where its compiled programs are kept.  JAX-free on purpose — every
helper here must be callable before ``import jax``."""

from __future__ import annotations

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Where compiled programs persist when JAX_COMPILATION_CACHE_DIR is not
# set: ONE fixed path inside the checkout (git-ignored).  The path is
# part of JAX's cache key, so a directory that moves never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO, ".jax_compile_cache")


def place_compile_cache() -> str:
    """Process-entry hook (``cli.main``, ``cli.podrun``, the test
    session): make JAX's persistent compilation cache live from the
    first compile on — ingest splice, gather and decode programs
    included, not only the boot's.  JAX reads its cache settings from
    the environment when it is imported, so this only fills in what the
    environment left open: a ``JAX_COMPILATION_CACHE_DIR`` set from
    outside wins and no other directory is touched.  The size/time
    thresholds default to "cache everything": the delivery path is dozens
    of sub-second programs whose sum is the warm boot's compile bill.
    Returns the directory in force."""
    cache_dir = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                      DEFAULT_COMPILE_CACHE_DIR)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    return cache_dir


def pin_jax_to_cpu() -> None:
    """One process per chip: a process that neither stages, boots nor
    joins a fabric must never initialise an accelerator backend,
    whatever platform list it inherited — on a TPU host the first
    process to touch the chip owns it and the next one fails or hangs.
    Seeding still runs JAX (``serde.seeded_blob``), on the CPU."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax = sys.modules.get("jax")
    if jax is not None:  # imported before entry (an embedding process)
        jax.config.update("jax_platforms", "cpu")


def cpu_pinned_env(base: dict = None) -> dict:
    """Env for a child process that imports jax but must never take an
    accelerator: pin the CPU backend."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def boot_donate_mode() -> str:
    """The donated-staging knob (``DLD_BOOT_DONATE``): ``"off"`` (0),
    ``"force"`` (1), or ``"auto"`` (unset/anything else).  Auto donates
    only where it is both profitable and safe: non-CPU device blobs with
    a retained host fallback — the CPU backend zero-copy-ADOPTS host
    buffers as device arrays (``utils.hostmem``), and donating an adopted
    array would let XLA scribble over the very memory ``inmem_data``
    still serves retransmits from.  The consumers of this knob
    (``runtime/boot.py``, ``parallel/ingest.py``) each apply their own
    platform/aliasing checks on top of the mode."""
    v = os.environ.get("DLD_BOOT_DONATE", "")
    if v == "0":
        return "off"
    if v == "1":
        return "force"
    return "auto"


def stream_boot_enabled() -> bool:
    """Per-layer receive-to-device streaming boot staging
    (``runtime/stream_boot.py``), default ON; ``DLD_STREAM_BOOT=0``
    disables it (the boot then assembles everything after startup, the
    pre-streaming behavior)."""
    return os.environ.get("DLD_STREAM_BOOT", "1") != "0"
