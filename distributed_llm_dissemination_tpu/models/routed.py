"""The sigmoid router and a rank's share of a routed block by dense
dispatch, for the families that have them (``models/joyai.py``,
``models/trinity.py``).

    s = sigmoid(float32(x)·float32(gate))     over EVERY router output
    pick = top-k of (s + bias)                the bias picks, does not weigh
    w = s[pick] / (sum of s[pick] + 1e-20) * route_scale
                                              the sum over ALL picks, held
                                              here or not
    sum over held picks of w_e * expert_e(x)  this rank's part; a slot that
                                              picked an absent expert adds
                                              nothing

A configuration gives ``top_k``, ``route_scale``, ``experts_held`` and
``expert_first`` under the same names in both families; the leaves are
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


def counts(idx, held):
    """What a call routed: ``moe_slots`` (positions x top_k), ``moe_held``
    (slots whose expert is here), ``moe_touched`` (distinct held experts
    that got a slot in this call — what a gathered dispatch would
    read)."""
    return {"moe_slots": jnp.asarray(idx.size, jnp.int32),
            "moe_held": jnp.sum(held, dtype=jnp.int32),
            "moe_touched": jnp.sum(held.any((0, 1, 2)), dtype=jnp.int32)}


def routed_part(p, xn, idx, w, cfg):
    """``experts`` over the whole call, and its ``counts``."""
    mine = held(idx, cfg)
    return experts(p, xn, w, mine), counts(idx, mine)
