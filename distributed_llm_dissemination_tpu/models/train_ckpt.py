"""Sharded training-state checkpointing (orbax).

The dissemination layer journals LAYER BYTES (``runtime/checkpoint.py``
— fsync'd fragment intervals, resume plans only the gaps).  This module
is the TRAINING side of durability: (params, AdamW state) saved and
restored WITH their shardings, so a restarted pod resumes exactly —
each process writes/reads only its own shards (orbax handles the
per-host fan-out on a real multi-host mesh).

The reference has no training loop at all; this exists because a
TPU-native framework whose dissemination feeds a training mesh needs
the other half of the crash story: weights land (dissemination resume)
AND optimization continues (state restore), without either path caring
about the other.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding

from .llama import ModelConfig
from .sharded import adamw_state_specs, param_specs


def _state_shardings(cfg: ModelConfig, mesh: Mesh):
    """NamedShardings for the (params, opt) tree — derived from the same
    specs the train step runs with, so a restored state is placed
    EXACTLY where the donated-buffer step expects it."""
    to_sharding = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    return {
        "params": jax.tree.map(to_sharding, param_specs(cfg)),
        "opt": jax.tree.map(to_sharding, adamw_state_specs(cfg)),
    }


def save_train_state(path: str, params, opt_state) -> None:
    """Write {params, opt} atomically (orbax tmp+rename).  Every leaf
    keeps its dtype; on multi-host meshes each process persists only
    its addressable shards."""
    import orbax.checkpoint as ocp

    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(path, {"params": params, "opt": opt_state}, force=True)
        ckptr.wait_until_finished()


def restore_train_state(path: str, cfg: ModelConfig, mesh: Mesh):
    """(params, opt_state) restored onto ``mesh`` with the train step's
    shardings — ready to feed ``build_adamw_train_step`` directly.

    The target tree (structure + shapes + dtypes + shardings) is built
    from the config, NOT trusted from disk: restoring under a different
    topology places shards for THIS mesh, and a checkpoint whose
    structure disagrees fails loudly instead of materializing
    mis-sharded state."""
    import numpy as np
    import orbax.checkpoint as ocp

    from . import family
    from .llama import init_params
    from .sharded import init_adamw_state

    family.only(cfg, ("llama",), "models/train_ckpt.py",
                "a training state is the sharded train step's "
                "(models/sharded.py), which knows Llama's leaves only")
    shardings = _state_shardings(cfg, mesh)
    # Abstract targets: shape/dtype from a throwaway host init (cheap at
    # config scale), sharding from the train-step specs.
    host_params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0)))
    host_opt = jax.eval_shape(
        lambda: init_adamw_state(
            init_params(cfg, jax.random.key(0))))
    target = {
        "params": jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sh),
            host_params, shardings["params"]),
        "opt": jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(
                np.shape(a), a.dtype, sharding=sh),
            host_opt, shardings["opt"]),
    }
    with ocp.StandardCheckpointer() as ckptr:
        restored = ckptr.restore(path, target)
    return restored["params"], restored["opt"]
