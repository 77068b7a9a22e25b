"""The reader of the program's own interval spans, and the per-layer
metrics built on it: on a recorded span dump of one round on the v5e
(``benchmark/testdata/spans_round.jsonl``) and on small made-up rings."""

import json
import os

import pytest

from bench_helpers import REPO
from benchmark.manifest import Manifest

MAN = Manifest()
READ = MAN.reader("span_stat")
DUMP = os.path.join(REPO, "benchmark", "testdata", "spans_round.jsonl")
TRAFFIC = MAN.traffic("cold-raw")
SPAN_METRICS = [m["name"] for m in MAN.data["per_layer"]
                if MAN.metric_spec(m["name"])["reader"] == "span_stat"]


def ctx_of(log, traffic=TRAFFIC, **round_rec):
    return {"logs_by_role": {"dest": log}, "traffic": traffic,
            "round": round_rec}


def dump(spans, counters=None, dropped=0):
    log = [{"message": "spans", "spans": spans}]
    if counters is not None:
        log.append({"message": "span counters", "counters": counters,
                    "dropped": dropped})
    return log


def sp(name, t0, t1, node=None, **fields):
    rec = {"name": name, "t0": t0, "t1": t1, "thread": "t"}
    if node is not None:
        rec["node"] = node
    if fields:
        rec["fields"] = fields
    return rec


@pytest.fixture(scope="module")
def recorded():
    with open(DUMP) as f:
        log = [json.loads(line) for line in f]
    timer = next(r for r in log if r["message"] == "timer start")
    return ctx_of([r for r in log if r is not timer],
                  timer_start_mono=timer["mono"])


# --------------------------------------------------------------- the reader

def test_union_is_elapsed_and_sum_is_thread_time():
    log = dump([sp("wire.recv", 0.0, 2.0), sp("wire.recv", 1.0, 3.0),
                sp("wire.recv", 5.0, 6.0), sp("wire.crc", 0.0, 9.0)])
    args = {"role": "dest", "names": ["wire.recv"]}
    assert READ(ctx_of(log), stat="union", **args) == 4.0
    assert READ(ctx_of(log), stat="sum", **args) == 5.0
    assert READ(ctx_of(log), stat="count", **args) == 3.0
    assert READ(ctx_of(log), stat="median", scale=1000.0, **args) == 2000.0
    assert READ(ctx_of(log), stat="union", role="dest",
                names=["wire.recv", "wire.crc"]) == 9.0


def test_uncovered_of_a_fully_covered_window_is_zero():
    window = {"start": "timer_start_mono", "end_span": "boot.first_forward"}
    spans = [sp("plan.dispatch", 9.0, 10.5), sp("wire.recv", 10.0, 12.0),
             sp("ingest.finalize", 11.5, 13.0),
             sp("boot.first_forward", 13.0, 14.0),
             sp("serve.generate", 20.0, 21.0)]  # after the window
    args = {"role": "dest", "stat": "uncovered", "window": window}
    assert READ(ctx_of(dump(spans), timer_start_mono=10.0), **args) == 0.0
    holed = [s for s in spans if s["name"] != "ingest.finalize"]
    assert READ(ctx_of(dump(holed), timer_start_mono=10.0),
                **args) == pytest.approx(1.0)
    # no timer start in the round's record, or no such span: nothing to read
    assert READ(ctx_of(dump(spans)), **args) is None
    assert READ(ctx_of(dump(spans[:3]), timer_start_mono=10.0),
                **args) is None


def test_a_field_is_summed_over_the_spans_that_carry_it():
    """``wire.recv``'s ``cpu``: the thread's own seconds in the span,
    beside the span's wall time."""
    log = dump([sp("wire.recv", 0.0, 2.0, cpu=0.5),
                sp("wire.recv", 1.0, 3.0, cpu=0.25, bytes=16),
                sp("wire.recv", 5.0, 6.0),  # a dump from before the field
                sp("wire.crc", 0.0, 9.0, cpu=8.0)])
    args = {"role": "dest", "stat": "field_sum", "names": ["wire.recv"]}
    assert READ(ctx_of(log), field="cpu", **args) == 0.75
    assert READ(ctx_of(log), field="bytes", **args) == 16.0
    assert READ(ctx_of(log), field="absent", **args) is None
    spec = MAN.metric_spec("wire.recv_cpu_s")["args"]
    assert READ(ctx_of(log), **spec) == 0.75


def test_a_wrapper_that_only_waits_is_no_cover():
    """``host.unattributed_s`` leaves the spans that only wait aside:
    a second in which the destination did nothing but sit in a queue is
    not attributed by the queue's own span."""
    window = {"start": "timer_start_mono", "end_span": "boot.first_forward"}
    spans = [sp("wire.recv", 10.0, 11.0), sp("wire.queue", 10.5, 12.5),
             sp("ingest.finalize", 12.0, 13.0),
             sp("ingest.finalize.ready", 12.75, 13.0),
             sp("boot.first_forward", 13.0, 14.0)]
    args = {"role": "dest", "stat": "uncovered", "window": window}
    ctx = ctx_of(dump(spans), timer_start_mono=10.0)
    assert READ(ctx, **args) == 0.0
    assert READ(ctx, exclude=["wire.queue", "ingest.finalize"],
                **args) == pytest.approx(1.75)
    spec = MAN.metric_spec("host.unattributed_s")["args"]
    assert {"wire.queue", "ingest.finalize", "ingest.finalize.wait",
            "boot.wait_stream", "serve.queue"} <= set(spec["exclude"])
    assert READ(ctx, **spec) == pytest.approx(1.75)


def test_a_dump_that_lost_spans_is_refused_not_reported():
    """The ring is a window: once it dropped spans, a statistic over
    what is left is not the round's.  The counters are cumulative."""
    spans = [sp("wire.recv", 0.0, 1.0), sp("boot.first_forward", 1.0, 2.0)]
    whole = ctx_of(dump(spans, counters={"xla.compiles": 2}),
                   timer_start_mono=0.0)
    cut = ctx_of(dump(spans, counters={"xla.compiles": 2}, dropped=7),
                 timer_start_mono=0.0)
    for name in SPAN_METRICS:
        args = MAN.metric_spec(name)["args"]
        if args["stat"] == "counter":
            assert READ(whole, **args) == READ(cut, **args) == 2.0
        else:
            assert READ(cut, **args) is None, name
    assert READ(whole, role="dest", stat="union", names=["wire.recv"]) == 1.0
    assert READ(cut, role="dest", stat="count", names=["wire.recv"]) is None


def test_a_log_without_a_span_dump_gives_none_and_the_metric_is_left_out():
    """A program from before the spans (the parent of PR 24): every
    span metric reads None, the run does not fail."""
    old = [{"message": "layer staged to HBM", "stage_ms": 3.0, "mono": 1.0},
           {"message": "final layer placement", "mono": 2.0}]
    for name in SPAN_METRICS:
        spec = MAN.metric_spec(name)
        assert READ(ctx_of(old, timer_start_mono=0.5),
                    **spec["args"]) is None, name
    # a dump that lacks the spans a metric names reads None too (a count
    # reads 0), and the counter 0 once the counters record is there
    log = dump([sp("wire.recv", 0.0, 1.0)], counters={})
    assert READ(ctx_of(log), role="dest", stat="union",
                names=["fabric.upload"]) is None
    assert READ(ctx_of(log), role="dest", stat="count",
                names=["fabric.upload"]) == 0.0
    assert READ(ctx_of(log), role="dest", stat="counter",
                counter="xla.compiles") == 0.0
    assert READ(ctx_of(dump([])), role="dest", stat="counter",
                counter="xla.compiles") is None
    with pytest.raises(ValueError):
        READ(ctx_of(log), role="dest", stat="mean", names=["wire.recv"])


def test_a_pods_spans_are_taken_from_its_destination_seats():
    """One process, one registry: the spans carry ``node``; seat 0 leads
    and only seeds."""
    pod = MAN.traffic("pod-pp4")
    log = dump([sp("boot.first_forward", 0.0, 1.0, node=1),
                sp("boot.first_forward", 0.5, 2.0, node=3),
                sp("boot.first_forward", 5.0, 9.0, node=0),
                sp("boot.first_forward", 2.0, 2.5)])  # names no seat
    args = {"role": "dest", "stat": "union", "names": ["boot.first_forward"]}
    assert READ(ctx_of(log, traffic=pod), nodes="dest", **args) == 2.5
    assert READ(ctx_of(log, traffic=pod), **args) == 6.5
    tcp = dump([sp("wire.recv", 0.0, 1.0, node=2),
                sp("wire.recv", 0.0, 3.0, node=0)])
    assert READ(ctx_of(tcp), role="dest", stat="union", nodes="dest",
                names=["wire.recv"]) == 1.0


# --------------------------------------------- on a round recorded on the chip

def test_every_span_metric_of_the_raw_cell_reads_the_recorded_round(recorded):
    got = {}
    for m in MAN.metrics_for("cold-raw.mistral-7b", "per_layer"):
        spec = MAN.metric_spec(m["name"])
        if spec["reader"] == "span_stat":
            got[m["name"]] = READ(recorded, **spec["args"])
    assert len(got) == 14 and all(v is not None for v in got.values()), got
    assert got["boot.compiles_in_window"] == 0.0  # a counted round
    # consistent with each other, as ISSUE 24 asks
    assert got["ingest.finalize_wait_s"] <= got["ingest.finalize_elapsed_s"]
    assert got["ingest.finalize_ready_s"] <= got["ingest.finalize_elapsed_s"]
    tail = (got["boot.wait_stream_s"] + got["boot.assemble_s"]
            + got["boot.first_forward_s"])
    assert 0 < tail < 2.0
    assert 0 <= got["host.unattributed_s"] < 1.0
    assert 1.0 < got["wire.recv_elapsed_s"] < 4.0  # 4.03 GB over loopback
    # the copies' CPU seconds fit into the stripes' wall seconds
    wall = READ(recorded, role="dest", stat="sum", names=["wire.recv"])
    assert 0 < got["wire.recv_cpu_s"] <= wall
    assert got["serve.queue_ms"] < 100.0


@pytest.mark.parametrize("names", [["wire.recv"], ["wire.crc", "wire.digest"],
                                   ["ingest.finalize"], ["ingest.write"]])
def test_union_never_exceeds_sum_on_the_recorded_round(recorded, names):
    union = READ(recorded, role="dest", stat="union", names=names)
    total = READ(recorded, role="dest", stat="sum", names=names)
    assert 0 < union <= total + 1e-9


def test_the_recorded_round_is_busy_sum_against_elapsed(recorded):
    """The two readings the issue sets side by side: the outside-timed
    busy sums of the accepted metrics and the inside-timed unions."""
    log_sum = MAN.reader("log_sum")
    busy = log_sum(recorded, **MAN.metric_spec("ingest.finalize_busy_s")[
        "args"])
    elapsed = READ(recorded, **MAN.metric_spec("ingest.finalize_elapsed_s")[
        "args"])
    assert 0 < elapsed <= busy + 0.01
    spans = [s for r in recorded["logs_by_role"]["dest"]
             if r.get("message") == "spans" for s in r["spans"]]
    assert all(s["t0"] <= s["t1"] for s in spans)
    assert len({s["id"] for s in spans if s["name"] == "wire.recv"}) == 9


def test_the_manifest_with_the_span_metrics_meets_the_contract():
    import contract

    assert contract.problems(MAN) == []
    assert len(SPAN_METRICS) == 16
    for name in SPAN_METRICS:
        entry = next(m for m in MAN.data["per_layer"] if m["name"] == name)
        assert entry["workloads"], name
        assert entry["source"] in ("program_span", "program_counter")
