"""The sigmoid router and a rank's share of a routed block, for the
families that have them (``models/joyai.py``, ``models/trinity.py``):
by dense dispatch (``experts``) or by grouped dispatch (``grouped``),
whichever computes fewer expert rows at the call's static shape
(``dispatch``).

    s = sigmoid(float32(x)·float32(gate))     over EVERY router output
    pick = top-k of (s + bias)                the bias picks, does not weigh
    w = s[pick] / (sum of s[pick] + 1e-20) * route_scale
                                              the sum over ALL picks, held
                                              here or not
    sum over held picks of w_e * expert_e(x)  this rank's part; a slot that
                                              picked an absent expert adds
                                              nothing

A configuration gives ``n_experts`` (the router's outputs), ``top_k``,
``route_scale``, ``experts_held`` and ``expert_first`` under the same
names in both families; the leaves are
``gate`` and the selection bias (whose name the caller gives: the
checkpoints differ) and the held experts' stacks ``ew1``, ``ew3``,
``ew2``.  Products with weights are ``lfm2._mm``'s (the activations as
two ``cfg.dtype`` terms); the router and the mix of the experts' outputs
are float32 at ``highest`` precision: the picks are made among sigmoid
scores a few hundredths apart (PERF.md section 6, PR 31 and PR 33).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .lfm2 import _mm

_EXACT = jax.lax.Precision.HIGHEST


def swiglu(xn, w1, w3, w2):
    gate = jax.nn.silu(_mm("bsd,df->bsf", xn, w1))
    return _mm("bsf,fd->bsd", gate * _mm("bsd,df->bsf", xn, w3), w2)


def route(p, xn, cfg, bias: str):
    """A token's picks among ALL router outputs and their weights:
    ``(idx [b, s, top_k] int32, w [b, s, top_k] float32)``.  The bias
    (the leaf ``p[bias]``) picks and does not weigh; the weights are
    renormalised over every pick, whichever rank holds its expert."""
    scores = jax.nn.sigmoid(
        jnp.einsum("bsd,de->bse", xn.astype(jnp.float32),
                   p["gate"].astype(jnp.float32), precision=_EXACT))
    _, idx = jax.lax.top_k(scores + p[bias].astype(jnp.float32), cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.route_scale


def held(idx, cfg):
    """Which slot picked which expert of this rank: ``[b, s, top_k,
    experts_held]`` bool."""
    return (idx[..., None] - cfg.expert_first
            == jnp.arange(cfg.experts_held))


def experts(p, xn, w, held):
    """The held experts' part of the routed block's output (float32), by
    dense dispatch: every held expert runs over every token, unpicked
    pairs weigh zero."""
    gate = (w[..., None] * held).sum(-2)  # [b, s, held]
    g = jax.nn.silu(_mm("bsd,edf->besf", xn, p["ew1"]))
    out = _mm("besf,efd->besd", g * _mm("bsd,edf->besf", xn, p["ew3"]),
              p["ew2"])
    return jnp.einsum("besd,bse->bsd", out, gate, precision=_EXACT)


def _rows_an_item(n, cfg):
    """``C``, the rows of one item of ``grouped``'s loop over ``n``
    slots: the rows a held expert expects, ``n / n_experts``, rounded up
    to a multiple of 128."""
    return -(-n // (cfg.n_experts * 128)) * 128


def grouped(p, xn, idx, w, cfg):
    """``experts``' output (float32) by grouped dispatch, and the expert
    rows it computed (an int32 scalar): each held expert runs over the
    slots that picked it alone.

    The call's ``[b, s, top_k]`` slots are sorted by their local expert,
    stably, the slots of experts held elsewhere last; each expert's run
    of sorted rows is cut into items of ``C`` rows, and a ``while_loop``
    over the items gathers an item's rows of ``xn``, runs its expert's
    ``swiglu`` on them, weighs each row by its slot's ``w`` (0 past the
    run's end) and adds it into its position's row.  Every held slot is
    computed once; nothing is dropped.  ``C`` is ``_rows_an_item``; the
    loop's length is a device scalar under a static bound of
    ``ceil(b·s·top_k / C) + experts_held`` items."""
    b, s, d = xn.shape
    n, held = idx.size, cfg.experts_held
    chunk = _rows_an_item(n, cfg)
    local = idx.reshape(n) - cfg.expert_first
    group = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=held + 1)[:held]
    starts = jnp.cumsum(sizes) - sizes
    chunks = -(-sizes // chunk)
    ends = jnp.cumsum(chunks)  # one past each expert's last item
    t = jnp.arange(-(-n // chunk) + held)
    of = jnp.minimum(jnp.sum(t[:, None] >= ends, axis=1), held - 1)
    first = starts[of] + (t - ends[of] + chunks[of]) * chunk
    stop = starts[of] + sizes[of]
    # padded so that an item's C rows never run past the end
    token = jnp.pad(order // idx.shape[-1], (0, chunk))
    weight = jnp.pad(w.reshape(n)[order], (0, chunk))
    rows = xn.reshape(b * s, d)

    def item(carry):
        i, out = carry
        at = jax.lax.dynamic_slice_in_dim(token, first[i], chunk)
        live = first[i] + jnp.arange(chunk) < stop[i]
        wt = jnp.where(live, jax.lax.dynamic_slice_in_dim(
            weight, first[i], chunk), 0.0)
        y = swiglu(rows[at][None], *(
            jax.lax.dynamic_index_in_dim(p[name], of[i], keepdims=False)
            for name in ("ew1", "ew3", "ew2")))[0]
        return i + 1, out.at[at].add(y * wt[:, None])

    _, out = jax.lax.while_loop(
        lambda carry: carry[0] < ends[-1], item,
        (jnp.int32(0), jnp.zeros((b * s, d), jnp.float32)))
    return out.reshape(b, s, d), ends[-1] * chunk


def counts(idx, held):
    """What a call routed: ``moe_slots`` (positions x top_k), ``moe_held``
    (slots whose expert is here), ``moe_touched`` (distinct held experts
    that got a slot in this call — what a gathered dispatch would
    read)."""
    return {"moe_slots": jnp.asarray(idx.size, jnp.int32),
            "moe_held": jnp.sum(held, dtype=jnp.int32),
            "moe_touched": jnp.sum(held.any((0, 1, 2)), dtype=jnp.int32)}


def dispatch(p, xn, idx, w, mine, cfg):
    """The held experts' part (float32) and the expert rows it computed,
    by the dispatch that computes fewer rows at this call's static shape:
    ``experts`` (``b·s·experts_held`` rows) unless ``grouped``'s bound,
    ``(ceil(b·s·top_k / C) + experts_held)·C`` rows, is below that.  A
    call of 128 positions or fewer (a decode step, JoyAI's and Trinity's
    boot forward of 16) is always dense; at Trinity's cell (128 router
    outputs, 16 held, top-8) a call turns grouped from 265 positions on.
    ``mine`` is ``held(idx, cfg)``."""
    b, s, _ = xn.shape
    chunk = _rows_an_item(idx.size, cfg)
    dense = b * s * cfg.experts_held
    if (-(-idx.size // chunk) + cfg.experts_held) * chunk < dense:
        return grouped(p, xn, idx, w, cfg)
    return experts(p, xn, w, mine), jnp.asarray(dense, jnp.int32)


def routed_part(p, xn, idx, w, cfg):
    """``dispatch`` over the whole call, and its ``counts``."""
    mine = held(idx, cfg)
    return dispatch(p, xn, idx, w, mine, cfg)[0], counts(idx, mine)
