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
the stack's order cut into stretches (``stretches``, which
``scan_stack`` walks), and how the
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
STACK leaves it out (``stretches``, ``scan_stack``).  A family that has such
a module and can draft with it says so in ``drafts(cfg)`` and answers
``draft(params, h, nxt, positions, cache, cfg, at)`` (``drafter``).

``serde`` and ``quant`` (blob layout), ``llama.forward`` and ``generate``
(prefill and decode; both through ``scan_stack``) and
``runtime/boot.py`` ask here; nothing else branches on a family.  The
table imports a family module on first use, so this file imports none of
them.
"""

from __future__ import annotations

import importlib
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

# family name -> module beside this file
FAMILIES: Dict[str, str] = {"llama": ".llama", "longcat": ".longcat",
                            "lfm2": ".lfm2", "joyai": ".joyai",
                            "trinity": ".trinity"}
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


def stretches(cfg, layer_ids: Optional[Sequence[int]] = None
              ) -> List[List[Tuple[str, int]]]:
    """The stack in order, cut into stretches that ``scan_stack`` scans
    one at a time: each a list of ``(kind, place)``, a layer's kind and
    its place in that kind's stack.  A run of one kind that is ALL of its
    kind's stack is a stretch of its own (a uniform family is one); the
    runs between such — kinds that alternate, each run a part of its
    kind's stack — are one stretch together, however many periods they
    go on for.  A blob beside the stack (``side_kinds``) is in none."""
    aside = side_kinds(cfg)
    kinds = [k for k in (layer_kinds(cfg)[lid] for lid in _ids(cfg, layer_ids))
             if k not in aside]
    seen: Dict[str, int] = {}
    out: List[List[Tuple[str, int]]] = []
    mixed = None  # the stretch of partial runs being gathered
    at = 0
    while at < len(kinds):
        kind, end = kinds[at], at
        while end < len(kinds) and kinds[end] == kind:
            end += 1
        run = [(kind, seen.get(kind, 0) + i) for i in range(end - at)]
        seen[kind] = seen.get(kind, 0) + len(run)
        if len(run) == kinds.count(kind):
            out.append(run)
            mixed = None
        else:
            if mixed is None:
                mixed = []
                out.append(mixed)
            mixed.extend(run)
        at = end
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


def scan_stack(cfg, step: Callable, x, params, state=None,
               layer_ids: Optional[Sequence[int]] = None):
    """``x`` through the blocks of ``layer_ids`` (default: the stack) in
    order: ``step(x, layer_params, layer_state) -> (x, layer_state,
    counters)`` once a layer, ``params`` and ``state`` (or None) each as
    the family holds it.  Returns (x, the state after, the counters added
    up over the layers).

    One ``lax.scan`` for each stretch of ``stretches``.  A stretch that
    is all of one kind's stack scans over that stack (a uniform family's
    one scan over its tree).  A stretch of kinds that alternate scans
    over its layers with a ``lax.switch`` on the layer's kind: one traced
    body a KIND however many runs there are.  A branch takes its layer's
    leaves out of its kind's stack by index, as a scan does with what it
    scans over, so no part of a stack is sliced out and none is copied;
    the state rows of the stretch's kinds are taken out before the
    switch and put back after it (a kind that the layer is not has its
    row put back as it was), so no branch passes a whole stack of state
    through."""
    import jax
    import jax.numpy as jnp

    held = by_kind(cfg, params)
    rows = None if state is None else dict(by_kind(cfg, state))
    total: Dict[str, Any] = {}

    def take(tree, at):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, at, 0, False), tree)

    for stretch in stretches(cfg, layer_ids):
        kinds = list(dict.fromkeys(kind for kind, _ in stretch))
        if len(kinds) == 1 and len(stretch) == jax.tree.leaves(
                held[kinds[0]])[0].shape[0]:
            def body(x, scanned):
                x, new, counted = step(x, *scanned)
                return x, (new, counted)

            x, (new, counted) = jax.lax.scan(
                body, x, (held[kinds[0]],
                          None if rows is None else rows[kinds[0]]))
            if rows is not None:
                rows[kinds[0]] = new
        else:
            # Each kind's place at every layer of the stretch: the
            # layer's own where it is of that kind, else the kind's
            # nearest (its row is read and put back unchanged).
            where = {k: [] for k in kinds}
            near = {k: next(p for kind, p in stretch if kind == k)
                    for k in kinds}
            for kind, place in stretch:
                near[kind] = place
                for k in kinds:
                    where[k].append(near[k])

            def body(carry, layer, kinds=kinds):
                x, mine = carry
                which, at = layer
                taken = (None if mine is None
                         else {k: take(mine[k], at[k]) for k in kinds})

                def one(kind, fill):
                    def branch(x, taken):
                        x, new, counted = step(
                            x, take(held[kind], at[kind]),
                            None if taken is None else taken[kind])
                        return (x, None if taken is None
                                else {**taken, kind: new},
                                {**fill, **counted})
                    return branch

                # the kinds' counters, so that every branch answers with
                # the same names (a kind that does not count one adds 0)
                names = {}
                for kind in kinds:
                    names.update(jax.eval_shape(one(kind, {}), x, taken)[2])
                fill = {n: jnp.zeros(c.shape, c.dtype)
                        for n, c in names.items()}
                x, taken, counted = jax.lax.switch(
                    which, [one(kind, fill) for kind in kinds], x, taken)
                if mine is not None:
                    mine = {k: jax.tree.map(
                        lambda a, row, k=k:
                        jax.lax.dynamic_update_index_in_dim(a, row, at[k], 0),
                        mine[k], taken[k]) for k in kinds}
                return (x, mine), counted

            mine = None if rows is None else {k: rows[k] for k in kinds}
            (x, mine), counted = jax.lax.scan(
                body, (x, mine),
                (jnp.asarray([kinds.index(kind) for kind, _ in stretch]),
                 {k: jnp.asarray(v) for k, v in where.items()}))
            if rows is not None:
                rows.update(mine)
        for name, c in counted.items():
            c = c.sum(0)
            total[name] = total[name] + c if name in total else c
    return x, (None if rows is None else of_kinds(cfg, rows)), total
