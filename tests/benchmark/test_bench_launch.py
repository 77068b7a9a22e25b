"""The launcher's dealings with its children, with stand-in children."""

import subprocess
import sys
import time

import pytest

from benchmark import launch

ECHO = r"""
import json, sys, time
behaviour = json.loads(sys.argv[1])
for line in sys.stdin:
    cmd = json.loads(line)
    if cmd["cmd"] == "exit":
        print(json.dumps({"ok": True}), flush=True); break
    time.sleep(behaviour.get("sleep", 0))
    if behaviour.get("die"):
        sys.exit(3)
    print(json.dumps(dict(behaviour.get("reply", {"ok": True, "rc": 0}),
                          echo=cmd)), flush=True)
"""


@pytest.fixture
def kids(tmp_path):
    import json

    k = launch.Children(str(tmp_path))

    def start(name, **behaviour):
        k.procs[name] = subprocess.Popen(
            [sys.executable, "-c", ECHO, json.dumps(behaviour)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            bufsize=1)

    k.start_fake = start
    yield k
    k.end_all()
    assert all(p.poll() is not None for p in k.procs.values())


def test_call_sends_a_command_and_reads_its_answer(kids):
    kids.start_fake("a")
    rep = kids.call("a", 10.0, cmd="round", k=3)
    assert rep["ok"] and rep["echo"] == {"cmd": "round", "k": 3}


def test_a_child_that_reports_an_error_fails_the_run(kids):
    kids.start_fake("a", reply={"ok": False, "error": "boom"})
    with pytest.raises(launch.BenchFailure, match="boom"):
        kids.call("a", 10.0, cmd="round")


def test_a_child_that_dies_fails_the_run(kids):
    kids.start_fake("a", die=True)
    with pytest.raises(launch.BenchFailure, match="exited rc=3"):
        kids.call("a", 10.0, cmd="round")


def test_a_child_that_never_answers_is_bounded(kids):
    kids.start_fake("a", sleep=4)
    t0 = time.monotonic()
    with pytest.raises(launch.BenchFailure, match="no answer"):
        kids.call("a", 0.5, cmd="round")
    assert time.monotonic() - t0 < 5


def test_gather_collects_one_answer_from_each_seat(kids):
    for name in ("leader", "dest", "requester"):
        kids.start_fake(name)
        kids.send(name, cmd="round")
    out = kids.gather(["leader", "dest", "requester"], 10.0)
    assert set(out) == {"leader", "dest", "requester"}


def test_a_failed_seat_cuts_the_others_wait_to_the_grace(kids):
    """A round's seats wait on one another: when the destination fails,
    a leader stuck on its acks costs seconds, not the round's timeout."""
    kids.start_fake("dest", reply={"ok": True, "rc": 1, "error": "not cold"})
    kids.start_fake("leader", sleep=5)
    for name in ("dest", "leader"):
        kids.send(name, cmd="round")
    cancelled = []
    t0 = time.monotonic()
    with pytest.raises(launch.BenchFailure, match="not cold"):
        kids.gather(["dest", "leader"], 600.0, grace=1.0,
                    on_failure=lambda: cancelled.append(1))
    assert time.monotonic() - t0 < 10 and cancelled == [1]


def test_gather_reads_every_answer_before_it_fails_with_all_of_them(kids):
    """A seat that reports an error (the requester whose bind failed)
    must not leave the others' answers unread: the next command to a seat
    would be answered with this round's reply, and the run would die at
    its read-back with no result line (PERF.md, PR 24)."""
    kids.start_fake("requester", reply={
        "ok": False, "error": "OSError(98, 'Address already in use')"})
    kids.start_fake("dest", reply={"ok": False, "error": "device lost"})
    kids.start_fake("leader", sleep=0.5)
    for name in ("requester", "dest", "leader"):
        kids.send(name, cmd="round", k=1)
    cancelled = []
    with pytest.raises(launch.BenchFailure) as e:
        kids.gather(["requester", "dest", "leader"], 60.0, grace=10.0,
                    on_failure=lambda: cancelled.append(1))
    assert "Address already in use" in str(e.value)
    assert "device lost" in str(e.value) and cancelled == [1]
    # the leader's pipe is in step: its next answer is to the next command
    assert kids.call("leader", 10.0, cmd="expected")["echo"] == {
        "cmd": "expected"}


def test_a_seat_that_died_still_ends_the_run_after_the_others_are_read(kids):
    kids.start_fake("dest", die=True)
    kids.start_fake("leader")
    for name in ("dest", "leader"):
        kids.send(name, cmd="round")
    with pytest.raises(launch.BenchFailure, match="dest exited rc=3"):
        kids.gather(["dest", "leader"], 60.0, grace=5.0)
    assert kids.call("leader", 10.0, cmd="x")["echo"] == {"cmd": "x"}


def test_handed_out_ports_stay_held_until_the_round_is_over():
    """While held, a seat's listener (``socket.create_server``, as
    ``transport/tcp.py`` opens it) binds, serves, closes and binds again,
    and nobody else can take the port."""
    import socket

    with launch.held_addrs(3) as addrs:
        assert len(set(addrs)) == 3
        host, port = addrs[0].split(":")
        for _ in range(2):  # the requester binds once for every request
            with socket.create_server((host, int(port)),
                                      reuse_port=False) as srv:
                with socket.create_connection((host, int(port)),
                                              timeout=5) as c:
                    peer, _ = srv.accept()
                    c.sendall(b"x")
                    assert peer.recv(1) == b"x"
                    peer.close()
        with socket.socket() as other, pytest.raises(OSError):
            other.bind((host, int(port)))
