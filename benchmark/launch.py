"""Starting, fencing, commanding and ending the benchmark's processes.

JAX-free: the parent that runs this never touches the chip.  The
patterns (an explicit child environment, bounded waits, kill in a
``finally``) are ``chip_smoke.py``'s, copied so that a later change to
the smoke cannot change the benchmark.
"""

from __future__ import annotations

import contextlib
import json
import os
import select
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class BenchFailure(Exception):
    pass


def child_env(jax_platforms: str) -> dict:
    """A child's environment, built explicitly: the platform list is never
    inherited (this sandbox exports JAX_PLATFORMS=cpu, a chip machine may
    too), and BENCH_RUN is the driver's own business."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME", "BENCH_RUN")}
    env["JAX_PLATFORMS"] = jax_platforms
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO, env.get("PYTHONPATH", "")) if p)
    env["PYTHONUNBUFFERED"] = "1"
    return env


@contextlib.contextmanager
def held_addrs(n: int):
    """``n`` free loopback addresses, HELD while the block runs: each port
    stays bound here (never listening) under ``SO_REUSEADDR``, so a seat's
    listener (``socket.create_server`` sets the same option) can bind
    and bind again, and nothing else gets the port, neither another
    ``bind`` nor a ``connect`` in want of a source port.  Handed out
    and closed, a port of the ephemeral range was twice in 29 runs some
    seat's source port by the time the requester bound (PERF.md, PR 24)."""
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", 0))
        yield [f"127.0.0.1:{s.getsockname()[1]}" for s in socks]
    finally:
        for s in socks:
            s.close()


def fence(cores, seeder_cores: int) -> tuple:
    """``(seeders' cores, destination's cores)`` from the cores this
    process may use: the LAST ``seeder_cores`` of them for the leader,
    the peer seeder and the requester, all the others for the
    destination.  With too few cores to split (fewer than two each) both
    sides get everything: ``(None, None)``."""
    cores = sorted(cores)
    n = int(seeder_cores)
    if n < 1 or len(cores) < n + 2:
        return None, None
    return cores[-n:], cores[:-n]


class Children:
    """Every process the benchmark starts; all of them end with it."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.procs = {}
        self._files = []

    def start(self, name: str, spec: dict, jax_platforms: str) -> None:
        spec_path = os.path.join(self.out_dir, f"{name}.spec.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        err = open(os.path.join(self.out_dir, f"{name}.setup.jsonl"), "wb")
        self._files.append(err)
        self.procs[name] = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "child.py"), spec_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=err,
            env=child_env(jax_platforms), cwd=REPO, text=True, bufsize=1)

    def send(self, name: str, **cmd) -> None:
        p = self.procs[name]
        try:
            p.stdin.write(json.dumps(cmd) + "\n")
            p.stdin.flush()
        except (BrokenPipeError, OSError):
            raise BenchFailure(f"{name} is gone (rc={p.poll()}): "
                               f"{self.tail(name)}") from None

    def recv(self, name: str, timeout: float) -> dict:
        p = self.procs[name]
        ready, _, _ = select.select([p.stdout], [], [], max(0.0, timeout))
        if not ready:
            raise BenchFailure(f"{name}: no answer in {timeout:.0f}s: "
                               f"{self.tail(name)}")
        line = p.stdout.readline()
        if not line:
            raise BenchFailure(f"{name} exited rc={p.wait()}: "
                               f"{self.tail(name)}")
        rec = json.loads(line)
        if not rec.get("ok"):
            raise BenchFailure(f"{name}: {rec.get('error')}: "
                               f"{self.tail(name)}")
        return rec

    def gather(self, names, timeout: float, on_failure=None,
               grace: float = 20.0) -> dict:
        """One answer from each of ``names``: every answer is read, so
        that no seat's pipe is left a reply ahead, and only then does a
        seat that died or reported an error fail the call, with all of
        them.  A round's seats wait on one another, so when one of them
        fails (an error, or ``rc`` not 0) the rest get ``grace`` seconds
        and ``on_failure()`` is called once — a stalled round costs
        seconds, not its whole timeout."""
        pending = {self.procs[n].stdout: n for n in names}
        out, errors, failed = {}, {}, False
        deadline = time.monotonic() + timeout
        while pending:
            left = deadline - time.monotonic()
            ready, _, _ = select.select(list(pending), [], [],
                                        max(0.0, min(left, 1.0)))
            for pipe in ready:
                name = pending.pop(pipe)
                try:
                    out[name] = self.recv(name, 1.0)
                    bad = out[name].get("rc") != 0
                except BenchFailure as e:
                    errors[name], bad = str(e), True
                if bad and not failed:
                    failed = True
                    deadline = min(deadline, time.monotonic() + grace)
                    if on_failure is not None:
                        on_failure()
            if pending and time.monotonic() > deadline:
                said = dict(errors, **{n: r.get("error")
                                       for n, r in out.items()
                                       if r.get("rc") != 0})
                raise BenchFailure(
                    f"no answer from {sorted(pending.values())}"
                    + (f" after {said}" if said else
                       f" in {timeout:.0f}s"))
        if errors:
            raise BenchFailure("; ".join(errors.values()))
        return out

    def call(self, name: str, timeout: float, **cmd) -> dict:
        self.send(name, **cmd)
        return self.recv(name, timeout)

    def tail(self, name: str, n: int = 1200) -> str:
        try:
            with open(os.path.join(self.out_dir, f"{name}.setup.jsonl"),
                      errors="replace") as f:
                return f.read()[-n:]
        except OSError:
            return ""

    def end_all(self) -> None:
        """Ask, wait, kill: nothing outlives the run."""
        for p in self.procs.values():
            if p.poll() is None:
                try:
                    p.stdin.write('{"cmd": "exit"}\n')
                    p.stdin.flush()
                except (BrokenPipeError, OSError, ValueError):
                    pass
        deadline = time.monotonic() + 10
        for p in self.procs.values():
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
        for p in self.procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass
            for pipe in (p.stdin, p.stdout):
                try:
                    pipe.close()
                except OSError:
                    pass
        for f in self._files:
            f.close()
