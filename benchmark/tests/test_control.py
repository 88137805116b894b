"""`correct` has to come out false when it should.

The control: the program with its lease gate weakened (weaken.py) drives
the outage cell at G = 64 through the whole of a run, chip look-up apart,
and the device's own linearizability audit (stale_read / dual_lease) must
trip, on every seed; the sound program on the same seeds is correct.  On the
chip at the cell's own size this is `control_on_chip.py`.

The control of a membership that changes: the program that commits on the
incoming voters alone while a configuration is joint
(weaken.joint_commit_on_incoming_only) drives the test deployment's
`churn-crash` mix — two outgoing-only voters down inside the joint window —
and the joint-window audit (`commit_no_quorum`) must trip on every seed; the
sound program on the same seeds is correct.

The broken paths: a segment that returns its state unchanged, and an answer
altered where it is produced.
"""

import json

import pytest

import weaken
from benchmark import run

G = 64
SEEDS = [5, 3_000_000_017, 2**31 + 11]


def drive(bench, cell, seed, seconds=0.3, n_groups=G):
    import jax

    lines = []
    text = run.run_cell(bench, cell, seed=seed, seconds=seconds, traced=False,
                        say=lines.append, n_groups=n_groups, devices=jax.devices())
    checks = {l.split()[1].rstrip(":"): l for l in lines if l.startswith("check ")}
    return json.loads(text), checks


@pytest.mark.parametrize("seed", SEEDS)
def test_control_weakened_lease_gate_is_not_correct(bench, seed):
    with weaken.lease_without_quorum_gate():
        out, checks = drive(bench, "fleet-100k-r5.outage", seed)
    assert out["correct"] is False
    assert "FAILED" in checks["safety"] and "lease" in checks["safety"]
    assert out["failed"] >= 1


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_program_is_correct_on_the_same_seeds(bench, seed):
    out, checks = drive(bench, "fleet-100k-r5.outage", seed)
    assert out["correct"] is True, checks
    assert all("FAILED" not in c for c in checks.values())


CHURN_CRASH = "fleet-64-r3of5.churn-crash"


@pytest.mark.parametrize("seed", SEEDS)
def test_control_commit_on_the_incoming_voters_alone_is_not_correct(churn_bench, seed):
    with weaken.joint_commit_on_incoming_only() as replaced:
        out, checks = drive(churn_bench, CHURN_CRASH, seed, n_groups=None)
    assert replaced[0] >= 1  # the weakening found the quorum position it replaces
    assert out["correct"] is False
    assert "FAILED" in checks["safety"] and "commit_no_quorum" in checks["safety"]
    assert out["failed"] >= 1


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_program_is_correct_under_churn_and_crashes(churn_bench, seed):
    out, checks = drive(churn_bench, CHURN_CRASH, seed, n_groups=None)
    assert out["correct"] is True, checks
    assert all("FAILED" not in c for c in checks.values())


def test_a_segment_that_returns_its_state_unchanged_is_not_correct(bench):
    with weaken.segment_returns_state_unchanged():
        out, checks = drive(bench, "fleet-100k-r5.load", 7)
    assert out["correct"] is False
    assert "FAILED" in checks["reference"]


def test_an_answer_altered_where_it_is_produced_is_not_correct(bench):
    with weaken.reads_answered_early():
        out, checks = drive(bench, "fleet-100k-r5.outage", 9)
    assert out["correct"] is False
    assert "FAILED" in checks["fires"]


def test_no_chip_no_line():
    """On a machine without the chip the command prints no line and exits
    non-zero (here: the CPU this test is pinned to)."""
    with pytest.raises(run.BenchError, match="TPU"):
        run.require_chips(1)


def _command(cwd, env_extra):
    import os
    import subprocess
    import sys

    env = {**os.environ, **env_extra}
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "fleet-100k-r5.load",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_the_command_prints_nothing_and_fails_without_a_chip():
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    done = _command(root, {"JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"})
    assert done.returncode != 0 and done.stdout == ""
    assert "TPU" in done.stderr


def test_the_command_fails_in_a_directory_with_only_the_benchmark(tmp_path):
    import os
    import shutil

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(root, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    done = _command(str(tmp_path), {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert done.returncode != 0 and done.stdout == ""
