"""Harness provenance: tie a recorded report to the code that ran.

A recorded report vouches only for the code that produced it.  The
same content-hash discipline ``native/__init__.py`` uses for the C++
solver (rebuild when the source changed) applies to run reports:
``cli/report.py`` embeds ``harness_hash()`` in every report it builds.
"""

from __future__ import annotations

import hashlib
import os

_PKG = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_REPO = os.path.dirname(_PKG)


def harness_hash() -> str:
    """Content hash of every source file that can change a measurement:
    the package's .py and .cc files plus the repo-root ``chip_smoke.py``
    / ``__graft_entry__.py`` drivers.  Deterministic (sorted relative
    paths mixed into the digest); 16 hex chars is plenty for a
    did-the-code-change check."""
    h = hashlib.sha256()
    files = []
    for root, dirs, names in os.walk(_PKG):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for name in sorted(names):
            if name.endswith((".py", ".cc")):
                files.append(os.path.join(root, name))
    for extra in ("chip_smoke.py", "__graft_entry__.py"):
        path = os.path.join(_REPO, extra)
        if os.path.exists(path):
            files.append(path)
    for path in sorted(files):
        h.update(os.path.relpath(path, _REPO).encode())
        h.update(b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
        h.update(b"\0")
    return h.hexdigest()[:16]
