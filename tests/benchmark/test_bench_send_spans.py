"""The per-layer metrics of the hop (ISSUE 35): the sending side's
spans at the leader, the receive pool's at the destination, the lag
between the two ends of a frame, and the seats' CPU — each read from a
made-up dump of two seats to a value worked out by hand, and read as
nothing from a log without a dump and from a dump that lost spans."""

import pytest

from benchmark.manifest import Manifest

MAN = Manifest()
TRAFFIC = MAN.traffic("cold-raw")
TCP_CELLS = ["cold-raw.mistral-7b", "cold-int8.mistral-7b",
             "cold-raw.longcat-flash", "cold-raw.lfm2-24b-a2b",
             "cold-raw.joyai-llm-flash"]
PAIR = "2.3"
MIB = 1 << 20


def sp(name, t0, t1, node, thread="t", id=PAIR, parent=None, **fields):
    rec = {"name": name, "t0": t0, "t1": t1, "thread": thread, "id": id,
           "node": node, "fields": fields}
    if parent:
        rec["parent"] = parent
    return rec


def leader_spans():
    """One job of two fragments, two stripes each: four frames."""
    out = [sp("wire.job", 10.0, 14.0, 0, job="j", bytes=4 * MIB,
              rate=2 * MIB, fragments=2, codec="")]
    for f, (t0, t1, barrier) in enumerate([(10.0, 12.0, 0.5),
                                           (12.0, 14.0, 0.25)]):
        out.append(sp("wire.fragment", t0, t1, 0, parent="wire.job",
                      bytes=2 * MIB, offset=f * 2 * MIB, streams=2,
                      barrier_s=barrier, stolen=f))
        for k, (queued, w0, w1) in enumerate([(0.0, t0 + 0.25, t0 + 1.5),
                                              (0.125, t0 + 0.5, t1)]):
            off = (2 * f + k) * MIB
            thread = "ctl-worker-0" if k == 0 else "data-tx-0"
            out.append(sp("wire.send", w0 - 0.125, w1, 0, thread,
                          parent="wire.fragment", bytes=MIB, offset=off,
                          stripe=k, queued_s=queued, crc_s=0.01,
                          conn="pooled", attempts=1))
            out.append(sp("wire.send.write", w0, w1, 0, thread,
                          parent="wire.send", bytes=MIB, offset=off,
                          cpu=0.25))
    return out


def dest_spans(frames=4):
    """The destination reads frame k from 0.25 s (k even) or 0.5 s (k
    odd) after its write began."""
    out = []
    for k, w0 in enumerate([10.25, 10.5, 12.25, 12.5][:frames]):
        r0 = w0 + (0.25 if k % 2 == 0 else 0.5)
        thread = f"data-rx-{k % 2}"
        out.append(sp("wire.serve", r0 - 0.125, r0 + 1.0, 2, thread,
                      bytes=MIB, offset=k * MIB, queued_s=0.0625))
        out.append(sp("wire.recv", r0, r0 + 0.75, 2, thread,
                      parent="wire.serve", src=0, bytes=MIB,
                      offset=k * MIB, cpu=0.125))
        out.append(sp("wire.crc", r0 + 0.75, r0 + 0.875, 2, thread,
                      parent="wire.serve", bytes=MIB))
    return out


def dump(spans, counters=None, dropped=0):
    log = [{"message": "spans", "spans": spans}]
    if counters is not None:
        log.append({"message": "span counters", "counters": counters,
                    "dropped": dropped})
    return log


def ctx_of(leader, dest):
    return {"logs_by_role": {"leader": leader, "dest": dest},
            "traffic": TRAFFIC, "round": {}}


def read(name, ctx):
    spec = MAN.metric_spec(name)
    return MAN.reader(spec["reader"])(ctx, **spec.get("args", {}))


WHOLE = ctx_of(dump(leader_spans(), {"proc.cpu_ms": 1437,
                                     "proc.cpu_sys_ms": 600}),
               dump(dest_spans(), {"proc.cpu_ms": 3150,
                                   "proc.cpu_sys_ms": 900}))

# By hand.  Writes: [10.25, 11.5] [10.5, 12] [12.25, 13.5] [12.5, 14]:
# covered 10.25-12 and 12.25-14 = 3.5 s, summed 1.25 + 1.5 + 1.25 + 1.5.
# Serves: four of 1.125 s.  Lags: 0.25, 0.5, 0.25, 0.5 s.
BY_HAND = {
    "wire.send_elapsed_s": 3.5,
    "wire.send_busy_s": 5.5,
    "wire.send_queued_s": 0.25,
    "wire.fragment_barrier_s": 0.75,
    "wire.serve_busy_s": 4.5,
    "wire.serve_queued_s": 0.25,
    "wire.hop_lag_ms": 375.0,
    "wire.sender_cpu_s": 1.437,
    "host.dest_cpu_s": 3.15,
}


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_each_metric_reads_the_two_seat_dump_to_the_value_worked_out(name):
    assert read(name, WHOLE) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_a_log_without_a_dump_reads_as_nothing(name):
    """A program from before the spans: the metric is left out of the
    line, the run does not fail."""
    old = [{"message": "finished sending layer", "mono": 1.0},
           {"message": "final layer placement", "mono": 2.0}]
    assert read(name, ctx_of(old, old)) is None
    assert read(name, {"logs_by_role": {}, "traffic": TRAFFIC,
                       "round": {}}) is None


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_a_dump_that_dropped_spans_is_no_rounds_statistic(name):
    """The ring is a window once it dropped spans; the counters are
    cumulative and are still read (``span_stat``'s rule)."""
    cut = ctx_of(dump(leader_spans(), {"proc.cpu_ms": 1437}, dropped=3),
                 dump(dest_spans(), {"proc.cpu_ms": 3150}, dropped=1))
    if MAN.metric_spec(name)["source"] == "program_counter":
        assert read(name, cut) == pytest.approx(BY_HAND[name])
    else:
        assert read(name, cut) is None
    # one side's loss is enough to refuse the join
    half = ctx_of(dump(leader_spans(), {}),
                  dump(dest_spans(), {}, dropped=1))
    assert read("wire.hop_lag_ms", half) is None
    assert read("wire.send_busy_s", half) == pytest.approx(5.5)


def test_a_parent_that_dumps_spans_but_not_these_leaves_them_out():
    """The program before this PR: a dump and a counters record, but no
    span of the sending side, no ``wire.serve`` and no ``proc.*``."""
    recv_only = [s for s in dest_spans() if s["name"] != "wire.serve"]
    parent = ctx_of(dump([sp("wire.pace", 10.0, 10.1, 0)],
                         {"wire.pace.job_bytes": 4 * MIB}),
                    dump(recv_only, {"xla.compiles": 0}))
    for name in BY_HAND:
        if MAN.metric_spec(name)["source"] == "program_counter":
            assert read(name, parent) == 0.0  # a counter that never fired
        else:
            assert read(name, parent) is None, name


def test_span_lag_refuses_a_join_of_under_half_the_frames():
    lag = MAN.reader("span_lag")
    few = [s for s in leader_spans() if s["name"] != "wire.send.write"
           or s["fields"]["offset"] == 0]
    # one of four frames joins: the peer seeder sent the rest
    assert lag(ctx_of(dump(few), dump(dest_spans()))) is None
    # two of four do: half is enough
    half = [s for s in leader_spans() if s["name"] != "wire.send.write"
            or s["fields"]["offset"] < 2 * MIB]
    assert lag(ctx_of(dump(half), dump(dest_spans()))) == pytest.approx(375.0)
    assert lag(ctx_of(dump(half), dump(dest_spans())),
               stat="p90") == pytest.approx(500.0)
    # a frame of another pair at the same offset is another frame
    other = [dict(s, id="2.4") for s in dest_spans()]
    assert lag(ctx_of(dump(leader_spans()), dump(other))) is None
    # a frame written twice (a retry) meets its reads in order
    twice = leader_spans() + [sp("wire.send.write", 10.0, 10.125, 0,
                                 bytes=MIB, offset=0, error="reset")]
    reads = dest_spans() + [sp("wire.recv", 10.0625, 10.125, 2,
                               bytes=MIB, offset=0)]
    assert lag(ctx_of(dump(twice), dump(reads)),
               scale=1.0) == pytest.approx(0.25)
    with pytest.raises(ValueError):
        lag(WHOLE, stat="mean")


def test_the_manifest_with_the_nine_entries_meets_the_contract():
    import contract

    assert contract.problems(MAN) == []
    entries = {m["name"]: m for m in MAN.data["per_layer"]}
    assert [m["name"] for m in MAN.data["per_layer"]][-9:] == [
        "wire.send_elapsed_s", "wire.send_busy_s", "wire.send_queued_s",
        "wire.fragment_barrier_s", "wire.serve_busy_s",
        "wire.serve_queued_s", "wire.hop_lag_ms", "wire.sender_cpu_s",
        "host.dest_cpu_s"]  # appended, in the issue's order
    for name in BY_HAND:
        entry, spec = entries[name], MAN.metric_spec(name)
        assert entry["workloads"] == TCP_CELLS, name
        assert entry["moves"] == spec["moves"] == "ttft_s"
        assert entry["better"] == "lower"
        for key in ("layer", "unit", "source"):
            assert entry[key] == spec[key], (name, key)
        assert entry["layer"] == ("host" if name.startswith("host.")
                                  else "wire")
        assert len(spec["what"]) > 40
    # the pod cell is one process over the device fabric: it reports none
    pod = {m["name"] for m in MAN.metrics_for("pod-pp4.codestral-22b",
                                              "per_layer")}
    assert not pod & set(BY_HAND)
    for cell in TCP_CELLS:
        mine = {m["name"] for m in MAN.metrics_for(cell, "per_layer")}
        assert set(BY_HAND) <= mine
