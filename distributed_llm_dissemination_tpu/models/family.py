"""The model-family seam: which module of ``models/`` answers for a
configuration.

A configuration object names its family (the class attribute ``family``)
and carries what every caller reads without asking — ``name``, ``vocab``,
``d_model``, ``n_layers``, ``dtype``, ``norm_eps``, ``layer_nbytes()``.
Everything that depends on the shape of a block is the family module's,
under the same names in each:

``CONFIGS``                                 its named configurations
``layer_param_specs(cfg)``                  a layer blob's leaves in wire
``head_param_specs(cfg)``                   order, and the head blob's
``init_layer_params(cfg, key)``             seeded leaves of one layer
``init_head_params(cfg, k_emb, k_out)``     and of the head
``embed(params, tokens, cfg)``              tokens to the hidden state
``layer_apply(p, x, positions, cfg)``       one block, no cache
``logits(params, x, cfg)``                  hidden state to float32 logits
``init_cache(cfg, batch, max_len)``         serving state, every leaf
                                            stacked over the layers
``layer_with_cache(p, x, positions, cache, cfg) -> (x, cache, counters)``
                                            one block through its slice of
                                            that state; ``counters`` is a
                                            dict of int32 scalars (may be
                                            empty) that the serving loop
                                            adds up per request

``serde`` and ``quant`` (blob layout), ``llama.forward`` (the scan over
the stacked layers), ``generate`` (prefill and decode) and
``runtime/boot.py`` ask here; nothing else branches on a family.  The
table imports a family module on first use, so this file imports none of
them.
"""

from __future__ import annotations

import importlib
from typing import Dict, List

import numpy as np

# family name -> module beside this file
FAMILIES: Dict[str, str] = {"llama": ".llama", "longcat": ".longcat"}


class FamilyNotSupported(ValueError):
    """An entry point was handed a configuration of a family it has not
    learnt: it says what is missing instead of running another family's
    block on the wrong leaves."""


def module(name: str):
    return importlib.import_module(FAMILIES[name], __package__)


def of(cfg):
    """The module that answers for ``cfg``."""
    return module(cfg.family)


def config(name: str):
    """The named configuration of whichever family has it (KeyError if
    none does)."""
    for fam in FAMILIES:
        found = module(fam).CONFIGS.get(name)
        if found is not None:
            return found
    raise KeyError(name)


def known() -> List[str]:
    """The configuration names of every family."""
    return sorted(n for fam in FAMILIES for n in module(fam).CONFIGS)


def only(cfg, families, here: str, missing: str) -> None:
    """Refuse, in one sentence, a configuration whose family ``here`` has
    not learnt."""
    if cfg.family not in families:
        raise FamilyNotSupported(
            f"{here} cannot run {cfg.name!r} of the {cfg.family} family "
            f"(it knows {', '.join(families)}): {missing}")


def spec_nbytes(specs, dtype) -> int:
    """Bytes of a blob with these leaves: the one sum behind
    ``serde.blob_nbytes`` and every configuration's ``layer_nbytes``."""
    return sum(int(np.prod(s)) for _, s in specs) * np.dtype(dtype).itemsize
