"""The architecture seam: everything the harness knows of a model's
shape it asks of ``benchmark/archs/<arch>.py``, named by the key ``arch``
in the configuration's file (absent: ``llama``).

The module is found like every other piece, beside the manifest first and
in this checkout second: ``Manifest.config`` looks it up and leaves its
path in the configuration under ``arch_file`` (in memory only), and the
configuration is what every process, reader and ``kernels.py`` function
is handed; that path is the one lookup.  Top-level imports of an
architecture module are numpy at most: the benchmark's parent loads it
and must never touch JAX.

The hooks, every one called with the configuration's contents in its
source's own keys (``benchmark/README.md`` says who calls which):

``dims(config) -> dict``
    the sizes: ``layers`` (the layer blobs; the head blob is one past
    them), ``vocab`` (what prompts draw from), and whatever the module's
    own functions need.
``layout(config, blob_id) -> [(name, shape, fill)]``
    a blob's leaves in wire order, of any rank; ``fill`` is None for
    seeded random weights or the constant every element holds (a norm
    gain: 1.0).
``ref_in(jnp, dims, head, tokens)``, ``ref_layer(jnp, jax, dims, p, h)``,
``ref_out(jnp, dims, head, h)``
    the plain reference in float32 ``jax.numpy``: tokens to the hidden
    state, one block, the hidden state to logits.  ``head`` and ``p`` are
    ``{leaf: float32 array}`` of the head blob and of one layer blob.
``register(config, name) -> forward``
    build the program's configuration object under ``name`` in this
    process; ``forward(boot, tokens)`` is the program's own jitted forward
    on a boot result's delivered parameters.
``leaf(boot, blob_id, name)``
    the device array behind one delivered leaf of a boot result.

The logit tolerance is no hook: ``run.py`` holds every configuration to
the one it states.
"""

from __future__ import annotations

import functools
import os

from benchmark.manifest import REPO, ManifestError, arch_names, load_file

HOOKS = ("dims", "layout", "ref_in", "ref_layer", "ref_out", "register",
         "leaf")


@functools.lru_cache(maxsize=None)
def load(path: str):
    """The module at ``path``, held to the hooks."""
    if not os.path.exists(path):
        raise ManifestError(
            f"no architecture module {path}; known in this checkout: "
            f"{arch_names(REPO)}")
    mod = load_file(path, "benchmark_archs_"
                    + os.path.basename(path)[:-3])
    missing = [h for h in HOOKS if not callable(getattr(mod, h, None))]
    if missing:
        raise ManifestError(
            f"architecture module {path} lacks the hook(s) "
            f"{', '.join(missing)}; the harness calls each of {HOOKS}")
    return mod


def of(config: dict):
    """The architecture module of a configuration, as ``Manifest.config``
    handed it out."""
    if "arch_file" not in config:
        raise ManifestError(
            "a configuration without 'arch_file': take it from "
            "Manifest.config, which finds its architecture module")
    return load(config["arch_file"])
