"""The reduction from the profiler's trace to numbers."""

import os

import pytest

from bench_helpers import REPO
from benchmark import xplane

MS = 1e6  # ns


def _planes():
    """Two chips, a 100 ms annotated window starting at 1000 ms."""
    def chip(shift):
        ops = [("fusion.1", (1010 + shift) * MS, 10 * MS),
               ("fusion.1", (1015 + shift) * MS, 10 * MS),   # overlaps
               ("copy-start", (1050 + shift) * MS, 20 * MS),
               ("fusion.2", 990 * MS, 5 * MS)]               # outside
        mods = [("jit__concat_pad(123)", (1010 + shift) * MS, 15 * MS),
                ("jit__concat_pad(456)", (1050 + shift) * MS, 20 * MS),
                ("jit__decode_blobs(7)", 1080 * MS, 1 * MS)]
        return {"name": f"/device:TPU:{shift}", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods},
            {"name": "Steps", "events": [("0", 0.0, 5000 * MS)]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        (xplane.ANCHOR, 1000 * MS, 100 * MS)]}]}
    return [host, chip(0), chip(2)]


def test_union_merges_overlapping_intervals():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    red = xplane.reduce(_planes())
    assert red["devices"] == 2
    assert red["window_s"] == pytest.approx(0.100)
    # per chip: [1010,1025) and [1050,1070) = 35 ms; the op before the
    # window does not count
    assert red["busy_s"] == pytest.approx(0.035)
    assert 0 < red["busy_s"] < red["window_s"]


def test_ops_and_modules_are_summed_by_name_and_averaged_over_chips():
    red = xplane.reduce(_planes())
    ops = dict(map(tuple, red["breakdown"]["device_ops"]))
    assert ops["fusion.1"] == pytest.approx(0.020)
    assert ops["copy-start"] == pytest.approx(0.020)
    assert list(ops)[0] in ("fusion.1", "copy-start")  # largest first
    assert red["modules_s"]["jit__concat_pad"] == pytest.approx(0.035)
    assert xplane.kernel_seconds(red, module="_concat_pad") == \
        pytest.approx(0.035)
    assert xplane.kernel_seconds(red, module="_decode_") == \
        pytest.approx(0.001)
    assert xplane.kernel_seconds(red, ops=["copy"]) == pytest.approx(0.020)
    assert xplane.kernel_seconds(red, module="absent") == 0.0


def test_idle_gaps_are_attributed_to_the_hosts_phases():
    # CLOCK_MONOTONIC read 50.0 s when the annotation began; the host
    # was "delivering" for the first 40 ms and "booting" after
    phases = [["deliver", float("-inf"), 50.040],
              ["boot", 50.040, float("inf")]]
    red = xplane.reduce(_planes(), phases, anchor_mono=50.0)
    gaps = dict(map(tuple, red["breakdown"]["idle_gaps"]))
    # chip 0 idle: [1000,1010) [1025,1050) [1070,1100) = 65 ms
    assert sum(gaps.values()) == pytest.approx(0.065)
    assert gaps["deliver"] == pytest.approx(0.010 + 0.015)
    assert gaps["boot"] == pytest.approx(0.010 + 0.030)
    unanchored = xplane.reduce(_planes(), phases, anchor_mono=None)
    assert dict(map(tuple, unanchored["breakdown"]["idle_gaps"])) == {
        "unattributed": pytest.approx(0.065)}


def test_a_trace_without_a_device_plane_is_refused():
    """A profile that lost its device plane is a failed run, never a
    100% idle share."""
    host_only = [p for p in _planes() if p["name"] == "/host:CPU"]
    with pytest.raises(xplane.TraceError, match="0 /device:TPU:"):
        xplane.reduce(host_only)


def test_fewer_busy_device_planes_than_destination_chips_is_refused():
    with pytest.raises(xplane.TraceError, match="2 /device:TPU:"):
        xplane.reduce(_planes(), working=4)
    idle = _planes()
    idle[2]["lines"][0]["events"] = []  # the second chip ran nothing
    with pytest.raises(xplane.TraceError, match="1 chip"):
        xplane.reduce(idle, working=2)
    # a chip that only seeds (a pod's leader) is not averaged in
    assert xplane.reduce(idle, working=1)["busy_s"] == pytest.approx(0.035)


def test_host_threads_never_stand_in_for_a_device():
    """The PjRt CPU client's threads are not a device plane unless a
    rehearsal says so itself (``bench_helpers.CPU_STAND_IN``)."""
    from bench_helpers import CPU_STAND_IN

    cpu = [{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [(xplane.ANCHOR, 0.0, 100 * MS)]},
        {"name": "tf_XLAPjRtCpuClient/77", "events": [
            ("dot.1", 10 * MS, 30 * MS)]}]}]
    with pytest.raises(xplane.TraceError):
        xplane.reduce(cpu)
    red = xplane.reduce(cpu, working=4, select=CPU_STAND_IN)
    assert red["busy_s"] == pytest.approx(0.030)


RECORDED = os.path.join(REPO, "benchmark", "testdata", "small.xplane.pb")


def test_the_recorded_trace_reduces_to_what_was_run():
    """``testdata/small.xplane.pb`` (see ``testdata/README``): three
    rounds of a jitted matmul-and-sum with a sleep in each, inside the
    harness's annotation, traced without the Python tracer."""
    planes = xplane.load(RECORDED)
    red = xplane.reduce(planes, [["all", float("-inf"), float("inf")]], 0.0)
    assert xplane.anchor(planes) is not None
    assert red["devices"] == 1
    assert 0.03 <= red["window_s"] < 1.0         # three sleeps of >= 10 ms
    assert 0 < red["busy_s"] < red["window_s"] - 0.03
    assert len(red["breakdown"]["device_ops"]) >= 2
    assert all(s >= 0 for _, s in red["breakdown"]["device_ops"])
    gaps = dict(map(tuple, red["breakdown"]["idle_gaps"]))
    assert gaps["all"] == pytest.approx(red["window_s"] - red["busy_s"],
                                        rel=1e-6)
    # recorded on the chip: the jitted programs are on their own line
    assert len(red["modules_s"]) >= 1
    assert sum(red["modules_s"].values()) >= red["busy_s"] * 0.5
