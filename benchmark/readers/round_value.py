"""A value already in the round's record: the sum of the ``plus`` fields
less the ``minus`` fields (``boot.tail_s`` = ttft_s - ttd_s)."""


def read(ctx, plus, minus=(), scale=1.0):
    rec = ctx["round"]
    if any(rec.get(k) is None for k in (*plus, *minus)):
        return None
    return (sum(float(rec[k]) for k in plus)
            - sum(float(rec[k]) for k in minus)) * scale
