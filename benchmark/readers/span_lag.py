"""The lag of the hop, frame by frame: the receiving role's span start
less the sending role's, joined on the frame's key.

Both ends of a frame's hop record a span that carries the delivery
pair's ``id`` and the frame's ``offset`` in its blob: the sender's
``wire.send.write`` (the header's first byte handed to the kernel) and
the destination's ``wire.recv`` (a receive thread started on the body).
CLOCK_MONOTONIC is one clock for every process of the host, so the
difference of the two starts is what lies between a sending thread and
a free receive thread: the socket, the readiness loop's parse of the
envelope, and the wait for the receive pool.

``stat`` is ``median`` (the one the metric reads) or ``p90``.  ``None``
where either role has no dump or lost spans (``span_stat.spans_of``),
and where fewer than half of the receiving role's frames find their
sender's span: the peer seeder sent them, or the program is from before
the spans.  Of a frame written more than once (a retry, a retransmit)
the k-th write meets the k-th receive.
"""

import os
import statistics

from benchmark.manifest import load_file

span_stat = load_file(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "span_stat.py"), "benchmark_readers_span_stat")


def by_frame(spans, name) -> dict:
    out = {}
    for s in sorted(spans, key=lambda s: s["t0"]):
        if s["name"] == name and "offset" in s.get("fields", ()):
            out.setdefault((s.get("id"), s["fields"]["offset"]),
                           []).append(s["t0"])
    return out


def read(ctx, send_role="leader", recv_role="dest",
         send_span="wire.send.write", recv_span="wire.recv",
         stat="median", scale=1000.0):
    sent, _ = span_stat.spans_of(ctx, send_role)
    got, _ = span_stat.spans_of(ctx, recv_role)
    if sent is None or got is None:
        return None
    writes, recvs = by_frame(sent, send_span), by_frame(got, recv_span)
    lags = [r - w for key, ws in writes.items()
            for w, r in zip(ws, recvs.get(key, ()))]
    if not lags or 2 * len(lags) < sum(len(v) for v in recvs.values()):
        return None
    if stat == "median":
        return statistics.median(lags) * scale
    if stat == "p90":
        return sorted(lags)[min(len(lags) - 1, int(0.9 * len(lags)))] * scale
    raise ValueError(f"unknown stat {stat!r}")
