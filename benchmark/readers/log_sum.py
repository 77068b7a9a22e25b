"""Sum of numeric fields over a role's log records of a round, times
``scale``.  ``terms`` is ``[[message, field], ...]``."""


def read(ctx, role, terms, scale=1.0):
    total, seen = 0.0, False
    for rec in ctx["logs_by_role"].get(role, ()):
        for message, field in terms:
            if rec.get("message") == message and field in rec:
                total += float(rec[field])
                seen = True
    return total * scale if seen else None
