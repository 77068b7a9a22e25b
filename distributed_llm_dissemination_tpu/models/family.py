"""The model-family seam: which module of ``models/`` answers for a
configuration.

A configuration object names its family (the class attribute ``family``)
and carries what every caller reads without asking — ``name``, ``vocab``,
``d_model``, ``n_layers``, ``dtype``, ``norm_eps`` (and, where every
layer is alike, ``layer_nbytes()``).  Everything that depends on the
shape of a block is the family module's, under the same names in each:

``CONFIGS``                                 its named configurations
``layer_param_specs(cfg)``                  a layer blob's leaves in wire
``head_param_specs(cfg)``                   order, and the head blob's
``init_layer_params(cfg, key)``             seeded leaves of one layer
``init_head_params(cfg, k_emb, k_out)``     and of the head
``embed(params, tokens, cfg)``              tokens to the hidden state
``layer_apply(p, x, positions, cfg)``       one block, no cache
``logits(params, x, cfg)``                  hidden state to float32 logits
``init_cache(cfg, batch, max_len)``         serving state, stacked as the
                                            parameters are
``layer_with_cache(p, x, positions, cache, cfg) -> (x, cache, counters)``
                                            one block through its slice of
                                            that state; ``counters`` is a
                                            dict of int32 scalars (may be
                                            empty) that the serving loop
                                            adds up per request

**Kinds of layer.**  A family whose layers are not all alike also has
``layer_kinds(cfg)``, the kind (a name) of each layer id, and takes the
kind as the last argument of ``layer_param_specs`` and
``init_layer_params``; its block tells the kind from the leaves it is
handed.  This table is the one place that says which kind a layer id is
(``layer_kinds``), and it holds what follows from that: a blob's leaves
by its id (``layer_param_specs``), the layers of each kind (``group``),
the runs of one kind in the stack's order (``runs``), and how the
parameters and the serving state are held: stacked BY KIND,
``{kind: {leaf: [layers of the kind, ...]}}``.  A family without the
hook is the case of one kind, and its tree stays what it always was —
``{leaf: [n_layers, ...]}``, every leaf stacked over the layers
(``by_kind`` / ``of_kinds`` turn one view into the other).

**Blobs beside the stack.**  A kind may be delivered as a layer blob and
be no layer of the stack: a module the forward does not pass through (a
multi-token-prediction module, which drafts in ``generate``).  The
family names such kinds in ``side_kinds(cfg)``; their layer ids come
after the stack's.  Everything that handles BLOBS treats the kind as one
more (its leaves, its decode program, its stack of parameters and of
serving state: ``layer_kinds``, ``group``, ``stack``); what walks the
STACK leaves it out (``runs``, ``run_slices``).  A family that has such
a module and can draft with it says so in ``drafts(cfg)`` and answers
``draft(params, h, nxt, positions, cache, cfg, at)`` (``drafter``).

``serde`` and ``quant`` (blob layout), ``llama.forward`` (a scan over
each run of stacked layers), ``generate`` (prefill and decode) and
``runtime/boot.py`` ask here; nothing else branches on a family.  The
table imports a family module on first use, so this file imports none of
them.
"""

from __future__ import annotations

import importlib
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

# family name -> module beside this file
FAMILIES: Dict[str, str] = {"llama": ".llama", "longcat": ".longcat",
                            "lfm2": ".lfm2", "joyai": ".joyai"}
# The one kind of a family whose layers are all alike.
ONE_KIND = "layer"


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


# --------------------------------------------------------- kinds of layer


def _has_kinds(cfg) -> bool:
    return hasattr(of(cfg), "layer_kinds")


def layer_kinds(cfg) -> Tuple[str, ...]:
    """The kind of each layer id: the family's word, or the one kind."""
    if _has_kinds(cfg):
        return tuple(of(cfg).layer_kinds(cfg))
    return (ONE_KIND,) * cfg.n_layers


def side_kinds(cfg) -> Tuple[str, ...]:
    """The kinds that are delivered as layer blobs and are no layers of
    the stack (none, unless the family says)."""
    hook = getattr(of(cfg), "side_kinds", None)
    return tuple(hook(cfg)) if hook else ()


def drafter(cfg) -> Optional[Callable]:
    """The family's ``draft`` where ``cfg`` holds a module that drafts
    (``generate`` then decodes by draft and verify at temperature 0),
    else None."""
    fam = of(cfg)
    drafts = getattr(fam, "drafts", None)
    return fam.draft if drafts is not None and drafts(cfg) else None


def _ids(cfg, layer_ids: Optional[Sequence[int]]) -> Sequence[int]:
    return range(cfg.n_layers) if layer_ids is None else layer_ids


def _kind_args(cfg, layer_id: Optional[int]) -> tuple:
    """What a family's per-layer functions take after their own
    arguments: nothing where the layers are alike, else the kind."""
    if not _has_kinds(cfg):
        return ()
    if layer_id is None:
        raise ValueError(
            f"the layers of {cfg.name!r} ({cfg.family} family) are not all "
            "alike: ask for a layer's leaves with its id")
    return (layer_kinds(cfg)[layer_id],)


def layer_param_specs(cfg, layer_id: Optional[int] = None) -> list:
    """(name, shape) of layer ``layer_id``'s leaves in wire order; the id
    may be left out where every layer is alike."""
    return of(cfg).layer_param_specs(cfg, *_kind_args(cfg, layer_id))


def init_layer_params(cfg, key, layer_id: int) -> Dict[str, Any]:
    """Seeded leaves of layer ``layer_id``."""
    return of(cfg).init_layer_params(cfg, key, *_kind_args(cfg, layer_id))


def group(cfg, layer_ids: Optional[Sequence[int]] = None
          ) -> Dict[str, List[int]]:
    """``{kind: its layer ids, ascending}`` among ``layer_ids`` (default:
    every layer): a layer's place in its kind's stack is its place in
    that list."""
    kinds = layer_kinds(cfg)
    out: Dict[str, List[int]] = {}
    for lid in _ids(cfg, layer_ids):
        out.setdefault(kinds[lid], []).append(lid)
    return out


def runs(cfg, layer_ids: Optional[Sequence[int]] = None
         ) -> List[Tuple[str, int, int]]:
    """The stack in order as runs of one kind: ``(kind, start, stop)``,
    ``start:stop`` the run's place in its kind's stack.  A uniform family
    is one run.  A blob beside the stack (``side_kinds``) is in no run."""
    kinds = layer_kinds(cfg)
    aside = side_kinds(cfg)
    seen: Dict[str, int] = {}
    out: List[Tuple[str, int, int]] = []
    for lid in _ids(cfg, layer_ids):
        kind = kinds[lid]
        if kind in aside:
            continue
        at = seen.get(kind, 0)
        seen[kind] = at + 1
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], at + 1)
        else:
            out.append((kind, at, at + 1))
    return out


def by_kind(cfg, tree) -> Dict[str, Any]:
    """A parameter or state tree as ``{kind: {leaf: stack}}``, whatever
    the family."""
    return tree if _has_kinds(cfg) else {ONE_KIND: tree}


def of_kinds(cfg, kinds: Dict[str, Any]):
    """``by_kind``'s inverse: the tree as the family holds it."""
    return kinds if _has_kinds(cfg) else kinds[ONE_KIND]


def stack(cfg, layer_ids: Sequence[int], leaves_of: Callable[[int], Dict],
          stack_fn: Callable[[list], Any]):
    """The layers ``layer_ids`` stacked by kind, as the family holds them:
    ``leaves_of(lid)`` is one layer's ``{leaf: array}`` and
    ``stack_fn(arrays)`` joins a leaf's arrays in the order given.  Each
    leaf is taken OUT of its layer's dict as it is stacked (the dicts are
    the caller's to give away), so a staged device leaf is free the
    moment its stack exists: what is in flight is one leaf of one
    kind."""
    out = {}
    for kind, ids in group(cfg, layer_ids).items():
        per_layer = [leaves_of(lid) for lid in ids]
        out[kind] = {name: stack_fn([lp.pop(name) for lp in per_layer])
                     for name, _ in layer_param_specs(cfg, ids[0])}
    return of_kinds(cfg, out)


def run_slices(cfg, trees: tuple, layer_ids: Optional[Sequence[int]] = None
               ) -> Iterator[Tuple[str, int, int, tuple]]:
    """``(kind, start, stop, slices)`` for each run of ``runs``: the run's
    part of its kind's stack in each of ``trees`` (parameters, state; each
    as the family holds it) — the stack itself where the run is all of
    it, so a uniform family's one run is its trees untouched."""
    import jax

    kinds = [by_kind(cfg, tree) for tree in trees]
    for kind, start, stop in runs(cfg, layer_ids):
        n = jax.tree.leaves(kinds[0][kind])[0].shape[0]
        yield kind, start, stop, tuple(
            k[kind] if (start, stop) == (0, n)
            else jax.tree.map(lambda a: a[start:stop], k[kind])
            for k in kinds)
