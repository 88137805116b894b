"""`benchmark/reference/raftport/` against what it was copied from: every
file equals its source under `raft_tpu/` line for line, import lines apart.
The benchmark's `correct` stands on that copy — on its `confchange/`,
`quorum/joint.py` and `tracker/` wherever a cell changes memberships — so a
port that drifted from the program's scalar oracle would be silent.  A
difference is to be REPORTED (the copy may be the one that is right): do not
edit either side to make this pass without saying which one was wrong.

The same cases as `benchmark/tests/test_reference_copy.py`, which tier-1
neither runs nor counts."""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PORT = os.path.join(ROOT, "benchmark", "reference", "raftport")
IMPORT = re.compile(r"^\s*(from|import)\s")
FILES = sorted(
    os.path.relpath(os.path.join(base, f), PORT)
    for base, _dirs, files in os.walk(PORT)
    for f in files
    if f.endswith(".py") and os.path.join(base, f) != os.path.join(PORT, "__init__.py")
)


def body(path):
    with open(path, encoding="utf-8") as f:
        return [line for line in f.read().splitlines() if not IMPORT.match(line)]


def test_the_port_has_the_packages_the_reference_leans_on():
    assert {"raft.py", "confchange/changer.py", "quorum/joint.py", "tracker/__init__.py",
            "harness/network.py", "storage.py"} <= set(FILES)


@pytest.mark.parametrize("rel", FILES)
def test_a_file_of_the_port_equals_its_source(rel):
    source = os.path.join(ROOT, "raft_tpu", rel)
    assert os.path.exists(source), f"raft_tpu/{rel} is gone: the port has no source to follow"
    assert body(os.path.join(PORT, rel)) == body(source)
