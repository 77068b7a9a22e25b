"""Delivered wire bytes over the span from the first to the last received
fragment at the destination, in GB/s (10^9 bytes per second)."""

MESSAGE = "(a fraction of) layer received"


def read(ctx, role="dest"):
    frags = [r for r in ctx["logs_by_role"].get(role, ())
             if r.get("message") == MESSAGE and "mono" in r]
    if len(frags) < 2:
        return None
    # a record is written when its fragment has arrived; the first one's
    # own receive time belongs to the span
    t0 = frags[0]["mono"] - float(frags[0].get("duration_ms", 0.0)) / 1000.0
    span = frags[-1]["mono"] - t0
    nbytes = sum(int(r["layer_size"]) for r in frags)
    return nbytes / span / 1e9 if span > 0 else None
