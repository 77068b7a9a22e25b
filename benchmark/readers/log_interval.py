"""Seconds (times ``scale``) between two log records of a round, on
CLOCK_MONOTONIC.  ``start`` and ``end`` are ``[role, message, which]``
with ``which`` one of ``first`` / ``last``."""


def _pick(ctx, role, message, which):
    hits = [r for r in ctx["logs_by_role"].get(role, ())
            if r.get("message") == message and "mono" in r]
    if not hits:
        return None
    return hits[0 if which == "first" else -1]["mono"]


def read(ctx, start, end, scale=1.0):
    t0, t1 = _pick(ctx, *start), _pick(ctx, *end)
    if t0 is None or t1 is None:
        return None
    return (t1 - t0) * scale
