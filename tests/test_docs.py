"""The documents name files that exist.

Every back-ticked word of ``README.md``, the verify skill and
``docs/*.md`` that looks like a path of this repository (``*.py``,
``*.md``, ``*.json``, ``*.jsonl``, ``*.sh``, ``*.cc``; a ``:line``, a
``:from-to`` or a ``::test`` after it is dropped) must resolve from the root, from the
package directory or from ``tests/``.  ``PERF.md``, ``ROADMAP.md`` and
``CHANGES.md`` hold history and are free to name what is gone.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOTS = (REPO, os.path.join(REPO, "distributed_llm_dissemination_tpu"),
         os.path.join(REPO, "tests"))
DOCUMENTS = (["README.md", ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, REPO)
                      for p in glob.glob(os.path.join(REPO, "docs", "*.md"))))

# Files a run writes or an operator supplies; no checkout holds them.
MADE_AT_RUN_TIME = {
    "RUN_REPORT.json", "RUN_REPORT.md",   # cli/report.py, `-report`
    "rounds.jsonl", "result.json",        # benchmark/run.py, per cell
    "conf.json", "config.json", "job.json",  # the operator's own files
}

_TICKED = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"^[\w./-]+\.(?:py|md|json|jsonl|sh|cc)$")
_SUFFIX = re.compile(r"(?:::[\w\[\]., -]+|:\d+(?:-\d+)?(?:,\s*\d+(?:-\d+)?)*)$")


def named_paths(text: str):
    for span in _TICKED.findall(text):
        for word in span.split():
            if any(c in word for c in "<>*…{}$"):
                continue  # a pattern, not a file
            word = _SUFFIX.sub("", word.strip("()[],;'\""))
            if _PATH.match(word) and not word.startswith("/"):
                yield word


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    missing = sorted({
        p for p in named_paths(text)
        if os.path.basename(p) not in MADE_AT_RUN_TIME
        and not any(os.path.exists(os.path.join(root, p)) for root in ROOTS)})
    assert not missing, f"{document} names files that are not there: {missing}"
