"""Fixtures of the benchmark's own tests."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_helpers import write_tiny_root  # noqa: E402


@pytest.fixture
def tiny_manifest(tmp_path, request):
    tag = "t" + str(abs(hash(request.node.nodeid)) % 10 ** 8)
    return write_tiny_root(str(tmp_path), tag), tag
