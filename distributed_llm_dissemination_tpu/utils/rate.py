"""Token-bucket rate limiter for paced byte streams.

Equivalent of golang.org/x/time/rate as used by the reference's transport
(``/root/reference/distributor/transport.go:407-424``): in-memory layer
sends are chunked (256 KiB bucket) and each chunk waits for tokens so a
transfer never exceeds its source's configured bytes/sec.
"""

from __future__ import annotations

import threading
import time

from . import trace

# Reference uses a 256 KiB burst bucket (distributor/transport.go:409).
DEFAULT_BURST = 256 * 1024

# One bucket quantum must represent at least this much wall time of
# traffic: time.sleep's OS granularity is ~1 ms, so a fixed 256 KiB
# bucket silently caps ANY commanded rate at ~burst/1ms (~256 MB/s) —
# a 10 GB/s ICI-class budget would ship at 1/40th of it.  Scaling the
# burst UP for fast rates keeps the pacing overhead bounded while
# leaving slow rates (where 256 KiB already spans many ms) at exact
# reference-parity burst semantics.
MIN_QUANTUM_S = 0.005


def effective_burst(rate: float, burst: int = DEFAULT_BURST) -> int:
    if rate <= 0:
        return burst
    return max(int(burst), int(rate * MIN_QUANTUM_S))


class TokenBucket:
    """Thread-safe token bucket: ``wait_n(n)`` blocks until n tokens exist.

    ``rate`` is tokens (bytes) per second; ``rate <= 0`` means unlimited.
    """

    def __init__(self, rate: float, burst: int = DEFAULT_BURST):
        self.rate = float(rate)
        # burst must be positive when limited, or wait_n's chunking spins;
        # fast rates scale it so sleep granularity can't cap throughput.
        self.burst = (max(1, effective_burst(rate, burst))
                      if rate > 0 else 0)
        self._tokens = float(self.burst)
        self._last = time.monotonic()
        self._lock = threading.Lock()

    def wait_n(self, n: int) -> None:
        if self.rate <= 0:
            return
        if n > self.burst:
            # Split oversized requests into burst-sized waits.
            remaining = n
            while remaining > 0:
                chunk = min(remaining, self.burst)
                self.wait_n(chunk)
                remaining -= chunk
            return
        while True:
            with self._lock:
                now = time.monotonic()
                self._tokens = min(
                    float(self.burst), self._tokens + (now - self._last) * self.rate
                )
                self._last = now
                if self._tokens >= n:
                    self._tokens -= n
                    return
                deficit = n - self._tokens
            time.sleep(deficit / self.rate)


class PacedWriter:
    """Wrap a write callable so bytes flow at most at ``rate`` B/s, in
    bucket-sized chunks (transport.go:407-424)."""

    def __init__(self, write, rate: float, burst: int = DEFAULT_BURST):
        self._write = write
        self._bucket = TokenBucket(rate, burst)
        self._chunk = self._bucket.burst if rate > 0 else 1 << 20

    def write(self, data: bytes) -> int:
        view = memoryview(data)
        sent = 0
        while sent < len(view):
            chunk = view[sent : sent + self._chunk]
            self._bucket.wait_n(len(chunk))
            self._write(chunk)
            sent += len(chunk)
        return sent


class JobPacer:
    """One flow job's pacer: every fragment and every stripe of the job
    writes through the same one, and it holds the JOB to its plan —

        at no time has the job written more than
        ``burst + rate * (now - the job's first byte)``

    with ``burst = effective_burst(rate)``, which is also the write
    quantum.  That is the solver's budget stated once for the job.  A
    ``TokenBucket`` accrues budget only while its own thread is in its
    own write loop and holds 5 ms of it at most; a stripe queued for a
    transfer thread, or a fragment waiting for a pooled connection,
    forfeits what it did not send meanwhile.  Here the piece that ran
    late writes without sleeping until the job is back on its plan, and
    a job that is ahead sleeps as a bucket would.

    A quantum is booked when it is asked for and written when the plan
    reaches it: bookings are in order and each is due no earlier than
    the one before, so what has been written at any moment is a prefix
    of the bookings that fits under the ceiling.  One sleep a quantum at
    most, to a time on the job's own schedule — threads that wait
    together do not race for the same budget, and a sleep that overran
    delays nothing after it.

    Counters ``wire.pace.job_bytes`` (bytes written through a job's
    pacer) and ``wire.pace.wait_ms`` (milliseconds sending threads slept
    in one); a span ``wire.pace`` around each sleep that really happens.
    ``clock`` / ``sleep`` are the tests' (docs/transport.md)."""

    def __init__(self, rate: float, span_id=None, job: str = "",
                 clock=time.monotonic, sleep=time.sleep):
        if rate <= 0:
            raise ValueError("a job pacer needs a commanded rate")
        self.rate = float(rate)
        self.burst = effective_burst(rate)
        self._span_id, self._job = span_id, job
        self._clock, self._sleep = clock, sleep
        self._t0 = None  # the job's first byte
        self._booked = 0
        self._lock = threading.Lock()

    def wait_n(self, n: int) -> None:
        """Book ``n`` more bytes and block until the job's ceiling
        admits them."""
        with self._lock:
            now = self._clock()
            if self._t0 is None:
                self._t0 = now
            self._booked += n
            wait = (self._t0 + (self._booked - self.burst) / self.rate
                    - now)
        if wait > 0:
            with trace.span("wire.pace", id=self._span_id, job=self._job,
                            bytes=n):
                self._sleep(wait)
            trace.count("wire.pace.wait_ms",
                        round((self._clock() - now) * 1000))

    def write(self, write, data) -> int:
        """``data`` through ``write`` in quanta of ``burst`` bytes, each
        booked against the job's ceiling first."""
        view = memoryview(data)
        for off in range(0, len(view), self.burst):
            chunk = view[off : off + self.burst]
            self.wait_n(len(chunk))
            write(chunk)
        trace.count("wire.pace.job_bytes", len(view))
        return len(view)
