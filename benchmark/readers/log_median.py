"""Median of one numeric field over a role's log records of a round."""

import statistics


def read(ctx, role, message, field, scale=1.0):
    xs = [float(r[field]) for r in ctx["logs_by_role"].get(role, ())
          if r.get("message") == message and field in r]
    return statistics.median(xs) * scale if xs else None
