"""Test harness: force an 8-device virtual CPU mesh.

Mirrors the reference's dual-backend test pattern
(/root/reference/distributor/transport_test.go:35-66): protocol tests run on
a process-local fake transport *and* real TCP on loopback; device-plane
tests run on a virtual 8-device CPU mesh standing in for a TPU slice.

The suite is CPU-only by definition (it must pass in a sandbox with no
accelerator), so the platform is pinned here and not left to the launch
environment; child processes the tests spawn inherit it.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

from distributed_llm_dissemination_tpu.utils.env import (  # noqa: E402
    place_compile_cache,
)

# The session is a process entry like cli.main: the persistent compile
# cache goes where JAX_COMPILATION_CACHE_DIR says, else to the one fixed
# in-checkout path — set before jax is imported, inherited by children.
place_compile_cache()

import jax  # noqa: E402

import signal  # noqa: E402
import socket  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _run_scoped_telemetry():
    """Every test starts from a CLEAN telemetry registry (utils/
    telemetry.py): the phase buckets and event counters used to be
    process-global module state, so back-to-back runs in one process —
    exactly what a test session is — double-counted each other's totals
    and a test asserting `fired > 0` could pass on a PREDECESSOR's
    events.  Reset BEFORE the test (not after), so a failed test's
    state is still inspectable post-mortem."""
    from distributed_llm_dissemination_tpu.utils import telemetry

    telemetry.reset_run()
    yield


@pytest.fixture(scope="session")
def free_port():
    """A function that returns a loopback port nothing is bound to (the
    one copy; config builders of ``cli/genconf.py`` take it as
    ``port``)."""
    def _free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]
    return _free_port


@pytest.fixture(scope="session")
def cpu_devices():
    devices = jax.devices()
    assert len(devices) >= 8, f"expected >=8 virtual devices, got {len(devices)}"
    return devices


# Boot-path tests compile real XLA programs; a wedged compile (or a cache
# deadlock) must burn one test's budget, not the suite's.  Applied here
# so EVERY test in these files gets the SIGALRM bound without each
# hand-annotating (explicit @pytest.mark.timeout markers still win).
_BOOT_TEST_FILES = ("test_boot.py", "test_stream_boot.py")
_BOOT_TEST_TIMEOUT_S = 120.0


def pytest_collection_modifyitems(items):
    for item in items:
        fname = os.path.basename(str(getattr(item, "fspath", "")))
        if (fname in _BOOT_TEST_FILES
                and item.get_closest_marker("timeout") is None):
            item.add_marker(pytest.mark.timeout(_BOOT_TEST_TIMEOUT_S))


# Tier-1 per-test wall budget (seconds): the whole tier-1 suite must fit
# a ~10-minute CI wall, so any single test past this belongs in tier 2 —
# mark it ``@pytest.mark.slow``.  The terminal summary below names
# offenders explicitly (and always prints the 10 slowest tests) so a
# creeping test can't silently eat the budget.
TIER1_TEST_BUDGET_S = 30.0
_test_durations: dict = {}  # nodeid -> [summed seconds, is_slow-marked]

# Seeded-chaos bookkeeping: tests register their fault-schedule seed (or
# whole spec) via the ``chaos_seed`` fixture; a FAILING chaos test then
# prints it in the terminal summary, so the run replays bit-for-bit from
# the seed instead of being an unreproducible flake report.
_chaos_seeds: dict = {}  # nodeid -> seed/spec
_chaos_failed: "set[str]" = set()


@pytest.fixture
def chaos_seed(request):
    """Record the deterministic fault seed/spec driving this test."""
    def _record(seed):
        _chaos_seeds[request.node.nodeid] = seed
    return _record


def pytest_runtest_logreport(report):
    # Sum ALL phases (setup + call + teardown): a test whose cost lives
    # in its fixtures must not evade the budget guard.
    rec = _test_durations.setdefault(report.nodeid, [0.0, False])
    rec[0] += report.duration
    rec[1] = rec[1] or "slow" in report.keywords
    if report.failed and report.nodeid in _chaos_seeds:
        _chaos_failed.add(report.nodeid)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if _chaos_failed:
        terminalreporter.section("failing chaos seeds (replay with these)")
        for nodeid in sorted(_chaos_failed):
            terminalreporter.write_line(
                f"CHAOS SEED  {nodeid}  ->  {_chaos_seeds[nodeid]!r}",
                red=True)
    if not _test_durations:
        return
    ranked = sorted(((d, n) for n, (d, _) in _test_durations.items()),
                    reverse=True)
    terminalreporter.section("10 slowest tests (tier-1 budget check)")
    for dur, nodeid in ranked[:10]:
        terminalreporter.write_line(f"{dur:8.2f}s  {nodeid}")
    over = [(d, n) for n, (d, is_slow) in _test_durations.items()
            if d > TIER1_TEST_BUDGET_S and not is_slow]
    for dur, nodeid in sorted(over, reverse=True):
        terminalreporter.write_line(
            f"WARNING: {nodeid} took {dur:.1f}s (> {TIER1_TEST_BUDGET_S:g}s "
            "tier-1 per-test budget) and is not marked 'slow' — mark it "
            "@pytest.mark.slow or make it faster.", red=True)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    """Per-test wall-clock bound: ``@pytest.mark.timeout(seconds)``.

    The multi-process e2e tests spawn real OS processes whose
    ``communicate(timeout=...)`` calls usually bound them — but a hang
    BEFORE those calls (a wedged subprocess spawn, a stuck collective
    in-process) would eat the whole suite budget.  SIGALRM-based, so it
    needs no plugin and fires even inside a blocking syscall; only
    armed on the main thread (signals can't interrupt workers)."""
    marker = item.get_closest_marker("timeout")
    if (marker and marker.args and hasattr(signal, "SIGALRM")
            and threading.current_thread() is threading.main_thread()):
        limit = float(marker.args[0])

        def _alarm(signum, frame):
            raise TimeoutError(
                f"{item.nodeid} exceeded its {limit:g}s timeout")

        old = signal.signal(signal.SIGALRM, _alarm)
        signal.setitimer(signal.ITIMER_REAL, limit)
        try:
            yield
        finally:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, old)
    else:
        yield
