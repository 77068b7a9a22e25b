"""Stale-artifact gating: a measurement artifact must carry the CURRENT
harness hash or a documented ``stale`` marker — a recorded report can
not silently masquerade as evidence for code it never ran.  (The
on-chip evidence itself is not a committed file: ``chip_smoke.py`` is
re-run on the chip for every PR; ``tests/test_chip_smoke.py`` keeps its
plumbing honest on the CPU.)"""

import re

from distributed_llm_dissemination_tpu.utils.provenance import (
    artifact_is_current,
    harness_hash,
)


def test_harness_hash_is_stable_and_code_sensitive(tmp_path):
    h1 = harness_hash()
    assert re.fullmatch(r"[0-9a-f]{16}", h1)
    assert harness_hash() == h1  # deterministic


def test_artifact_gate_semantics():
    h = harness_hash()
    ok, why = artifact_is_current({"harness_hash": h})
    assert ok and why == "hash-current"
    ok, why = artifact_is_current({"harness_hash": "0" * 16})
    assert not ok
    ok, why = artifact_is_current({})
    assert not ok
    ok, why = artifact_is_current(
        {"harness_hash": "0" * 16,
         "stale": "recorded during the outage; superseded next tpu run"})
    assert ok and why.startswith("documented-stale")
    ok, _ = artifact_is_current({"stale": "   "})  # blank marker: no pass
    assert not ok
