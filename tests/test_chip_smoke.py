"""chip_smoke.py must not rot between chip runs: its legs run here at a
tiny size on the pinned CPU (Pallas interpret mode, by the one decision
point in raft_tpu.platform), and the command itself refuses to run without
a chip, pinned to the CPU or fallen back to it."""

import os
import subprocess
import sys

import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
G, P, TICK, K = 64, 5, 16, 4

LEGS = {
    "general_undamped": lambda: chip_smoke.leg_general_undamped(
        G, P, append_rounds=16
    ),
    "general_damped": lambda: chip_smoke.leg_general_damped(
        G, P, election_tick=TICK, append_rounds=8, sample=4
    ),
    "fused_kernels": lambda: chip_smoke.leg_fused_kernels(
        G, P, k=K, election_tick=TICK
    ),
    "chaos_plan": lambda: chip_smoke.leg_chaos_plan(G),
    "reconfig_plan": lambda: chip_smoke.leg_reconfig_plan(
        G, election_tick=TICK
    ),
    "reads_plan": lambda: chip_smoke.leg_reads_plan(G, election_tick=TICK),
    "embedded_driver": lambda: chip_smoke.leg_embedded_driver(16),
    "big_fleet": lambda: chip_smoke.leg_big_fleet(
        96, 3, k=K, election_tick=TICK
    ),
}


@pytest.mark.parametrize("leg", sorted(LEGS))
def test_leg_at_tiny_size(leg):
    LEGS[leg]()


def test_a_failed_check_is_fatal():
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check(False, "boom")


def test_result_line_is_exactly_what_the_driver_parses():
    import json

    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    )
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def _run(code_or_script, env):
    return subprocess.run(
        [sys.executable, *code_or_script], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_smoke_refuses_the_pinned_cpu():
    proc = _run(["chip_smoke.py"], {**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


# Without JAX_PLATFORMS the installed jax spends ~20 s failing to find a
# TPU before it falls back to the CPU, and that fallback is exactly the
# case under test.
_UNPINNED = """
import sys
import jax
if jax.default_backend() == "tpu":
    print("HAS_TPU")
    sys.exit(0)
import chip_smoke
assert chip_smoke.main() != 0
print("REFUSED")
"""


def test_nothing_runs_on_a_fallback_cpu():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = _run(["-c", _UNPINNED], env)
    if "HAS_TPU" in proc.stdout:
        pytest.skip("this machine has a TPU")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "REFUSED" in proc.stdout
    assert '"ok"' not in proc.stdout
