"""The benchmark's own tests: `python3 -m pytest benchmark/tests -q` on the
pinned CPU.  They are not under `tests/`, so tier-1 neither runs nor counts
them."""

import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture(scope="session")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)
