"""Data-plane thread discipline (utils/threads.py; docs/transport.md).

Three guards:

- the bounded :class:`WorkerPool` really bounds (and names) its
  workers, and ``run_all`` keeps one guaranteed-progress slot on the
  caller while propagating the first failure;
- the static DRIFT CHECK: every ``threading.Thread(`` occurrence in the
  package source is pinned per file — a new bare spawn site fails here
  until it is either routed through the pools or deliberately
  allowlisted with a stable name (the ``cli/trace.py`` duration-rule
  guard pattern);
- the data-plane thread CEILING, end to end on both backends: K
  concurrent striped/sendfile layer transfers never use more data
  threads than the pools' budget — connection count no longer implies
  thread count.
"""

import os
import threading
import time

import pytest

from distributed_llm_dissemination_tpu.core.types import (
    LayerLocation,
    LayerMeta,
    LayerSrc,
)
from distributed_llm_dissemination_tpu.transport import reset_registry
from distributed_llm_dissemination_tpu.transport.messages import LayerMsg
from distributed_llm_dissemination_tpu.utils import threads

from test_node import make_transports

RECV_TIMEOUT = 15.0


@pytest.fixture(autouse=True)
def _clean():
    reset_registry()
    yield
    reset_registry()


# ------------------------------------------------------------ pool units


def test_worker_pool_bounds_and_names_workers():
    pool = threads.WorkerPool(3, "tpool-test")
    seen = set()
    gate = threading.Event()

    def task(i):
        seen.add(threading.current_thread().name)
        gate.wait(5.0)

    tasks = [pool.submit(task, i) for i in range(10)]
    time.sleep(0.2)
    workers = [t for t in threading.enumerate()
               if t.name.startswith("tpool-test-")]
    assert len(workers) <= 3, workers
    gate.set()
    for t in tasks:
        assert t.wait(5.0)
    assert all(name.startswith("tpool-test-") for name in seen)


def test_idle_worker_and_finished_task_pin_nothing():
    """A job's arguments die with the job: an idle worker keeps no
    reference to the last task it ran, and a finished task none to its
    callable and arguments (a transport's bound method, and through it a
    node's receive buffers, utils/buffers.py)."""
    import gc
    import weakref

    class Payload:
        pass

    pool = threads.WorkerPool(1, "tpool-idle")
    payload = Payload()
    gone = weakref.ref(payload)
    task = pool.submit(lambda p: None, payload)
    assert task.wait(5.0)
    del payload
    # the one worker is idle in ``queue.get`` again; ``task`` still lives
    deadline = time.monotonic() + 5.0
    while gone() is not None and time.monotonic() < deadline:
        time.sleep(0.01)
        gc.collect()
    assert gone() is None
    assert task.error is None


def test_worker_pool_run_all_caller_slot_and_error():
    pool = threads.WorkerPool(2, "tpool-err")
    ran = []

    def ok(i):
        ran.append(i)

    def boom(i):
        ran.append(i)
        raise ValueError(f"boom-{i}")

    with pytest.raises(ValueError):
        pool.run_all([(ok, 0), (boom, 1), (ok, 2)])
    assert sorted(ran) == [0, 1, 2]  # every call ran despite the error
    # The FIRST call runs on the calling thread (guaranteed progress
    # even with a saturated pool).
    names = []
    pool.run_all([(lambda: names.append(threading.current_thread().name),)])
    assert names == [threading.current_thread().name]


@pytest.mark.timeout(30)
def test_run_all_nested_in_pool_workers_cannot_deadlock():
    """A pool task that itself fans into run_all (a striped send inside
    a pooled fan-out send) must complete even with every worker busy:
    waiters steal queued tasks instead of parking their slot."""
    pool = threads.WorkerPool(2, "tpool-nest")
    done = []

    def leaf(i, j):
        time.sleep(0.01)
        done.append((i, j))

    def fan(i):
        pool.run_all([(leaf, i, j) for j in range(3)])

    outer = [pool.submit(fan, i) for i in range(6)]
    deadline = time.monotonic() + 20.0
    for t in outer:
        assert t.wait(max(0.0, deadline - time.monotonic())), (
            "nested run_all deadlocked the pool")
    assert sorted(done) == [(i, j) for i in range(6) for j in range(3)]


def test_census_buckets_by_name():
    t = threading.Thread(target=lambda: time.sleep(0.3), daemon=True,
                         name="data-rx-probe")
    t.start()
    counts = threads.census()
    assert counts["data"] >= 1
    assert counts["other"] >= 1  # MainThread at least
    t.join()


# ------------------------------------------------- static drift check

# Pinned ``threading.Thread(`` occurrences per package file (docstring
# mentions count too — the check is textual on purpose, like the
# cli/trace.py duration-rule guard).  A NEW bare spawn site must either
# ride utils/threads.py's pools (data plane) or be added here with a
# stable thread name (control plane) so the census stays meaningful.
THREAD_SPAWN_ALLOWLIST = {
    "cli/main.py": 3,            # telemetry-watch, lp-warm, churn-leave
    "parallel/fabric.py": 1,     # plan-window
    "parallel/spmd_fabric.py": 1,  # spmd-fabric
    "runtime/failover.py": 1,    # replicate-<standby>
    "runtime/failure.py": 2,     # heartbeat-<id>, detector
    "runtime/hierarchy.py": 1,   # subleader-redrive-<id>
    "runtime/leader.py": 8,      # digests, watchdogs (spmd + pod),
    #                              lease, swap fence
    "runtime/node.py": 1,        # msgloop
    "runtime/receiver.py": 11,   # named control/fabric daemons
    #                              (incl. pod-collect-<id>)
    "runtime/stream_boot.py": 2,  # boot-stream-<id> (both stagers)
    "runtime/swap.py": 2,        # swap-flip, swap-prepare
    "transport/faults.py": 1,    # fault-pump
    "transport/tcp.py": 2,       # tcp-evloop, tcp-stripe-sweep
    "utils/threads.py": 2,       # THE pool helper (1 spawn + docstring)
}


def test_no_new_bare_thread_spawns():
    """Tier-1 drift check: data-plane concurrency comes from the
    bounded pools; anything else must be a named, allowlisted
    control-plane thread."""
    import distributed_llm_dissemination_tpu as pkg

    pkg_dir = os.path.dirname(os.path.abspath(pkg.__file__))
    found = {}
    for root, dirs, names in os.walk(pkg_dir):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in sorted(names):
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            rel = os.path.relpath(path, pkg_dir).replace(os.sep, "/")
            with open(path) as f:
                n = f.read().count("threading.Thread(")
            if n:
                found[rel] = n
    assert found == THREAD_SPAWN_ALLOWLIST, (
        "bare threading.Thread( sites changed; route data-plane spawns "
        "through utils.threads pools, give long-lived control threads "
        "a stable name, and update THREAD_SPAWN_ALLOWLIST deliberately: "
        f"{found}")


# ------------------------------------------- data-plane thread ceiling


def _data_threads():
    return [t.name for t in threading.enumerate()
            if t.name.startswith(threads.DATA_PREFIXES)]


@pytest.mark.parametrize("kind", ["inmem", "tcp"])
def test_data_thread_ceiling_under_concurrent_transfers(kind, tmp_path,
                                                        monkeypatch):
    """K concurrent connections' transfers — striped scatter-gather RAM
    sends AND kernel-sendfile disk stripes — never use more data-plane
    threads than the pool budget (docs/transport.md)."""
    from distributed_llm_dissemination_tpu.transport import tcp as tcp_mod

    # Force striping so the tx pool is exercised hard.
    monkeypatch.setattr(tcp_mod, "STRIPE_THRESHOLD", 64 * 1024)
    monkeypatch.setattr(tcp_mod, "STRIPE_MIN", 16 * 1024)
    monkeypatch.setattr(tcp_mod, "STRIPE_COUNT", 4)
    K = 12  # concurrent transfers (> either pool's worker budget)
    ids = range(K + 1)
    ts, _ = make_transports(kind, ids)
    size = 256 * 1024
    ram_payload = bytes(range(256)) * (size // 256)
    fp = tmp_path / "disk.layer"
    fp.write_bytes(ram_payload)
    peak = {"n": 0}
    stop = threading.Event()

    def watch():
        while not stop.is_set():
            names = _data_threads()
            if len(names) > peak["n"]:
                peak["n"], peak["names"] = len(names), sorted(names)
            time.sleep(0.002)

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    senders = []
    for i in range(1, K + 1):
        if i % 2:
            src = LayerSrc(inmem_data=ram_payload, data_size=size,
                           meta=LayerMeta(location=LayerLocation.INMEM))
        else:
            src = LayerSrc(fp=str(fp), data_size=size,
                           meta=LayerMeta(location=LayerLocation.DISK))
        senders.append(threading.Thread(
            target=ts[0].send, args=(i, LayerMsg(0, i, src, size)),
            daemon=True))
    for s in senders:
        s.start()
    got = {}
    for i in range(1, K + 1):
        msg = ts[i].deliver().get(timeout=RECV_TIMEOUT)
        got[msg.layer_id] = bytes(msg.layer_src.inmem_data)
    for s in senders:
        s.join(RECV_TIMEOUT)
    stop.set()
    watcher.join(2.0)
    assert got == {i: ram_payload for i in range(1, K + 1)}
    ceiling = threads.data_thread_ceiling()
    assert peak["n"] <= ceiling, (
        f"{peak['n']} data threads for {K} concurrent transfers "
        f"exceeds the pool ceiling {ceiling}: {peak.get('names')}")
    if kind == "tcp":
        # The pools were actually exercised (non-vacuous).
        assert peak["n"] > 0
    for t in ts.values():
        t.close()
