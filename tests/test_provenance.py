"""The harness hash a run report carries (``cli/report.py``): stable for
one tree, so a report can be told from one that merely sits next to
the code.  (On-chip evidence is not a committed file: the benchmark and
``chip_smoke.py`` are re-run on the chip for every PR.)"""

import re

from distributed_llm_dissemination_tpu.utils.provenance import harness_hash


def test_harness_hash_is_stable_and_code_sensitive(tmp_path):
    h1 = harness_hash()
    assert re.fullmatch(r"[0-9a-f]{16}", h1)
    assert harness_hash() == h1  # deterministic
