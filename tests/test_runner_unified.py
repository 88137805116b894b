"""Wrapper-vs-unified runner bit-identity (the runner-registry refactor).

Every legacy entry point — chaos.make_runner, reconfig.make_runner,
reconfig.make_split_runner, workload.make_runner,
workload.make_split_runner, autopilot.make_cadence_runner — is now a
thin wrapper over the one descriptor-built factory
(raft_tpu/multiraft/runner.make_runner, instantiated from the
schedules.py registry).  These tests pin the wrapper contract the hard
way: one golden scenario per schedule family, run through BOTH the
legacy symbol and the unified factory from identical fresh inputs, with
every output leaf compared bit-for-bit.  G=8 covers tier-1; the same
scenarios at G=32 are slow-marked (ISSUE 19's budget satellite).

The jaxpr-level identity is separately machine-checked (GC014 holds the
committed budgets byte-identical; GC019 pins the phase decomposition) —
this file is the end-to-end behavioral half of that argument.
"""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from raft_tpu.multiraft import SimConfig
from raft_tpu.multiraft import autopilot, chaos, kernels, reconfig, workload
from raft_tpu.multiraft import runner as runner_mod
from raft_tpu.multiraft import sim as sim_mod


def _assert_tree_equal(out1, out2, note):
    leaves1, tree1 = jax.tree_util.tree_flatten(out1)
    leaves2, tree2 = jax.tree_util.tree_flatten(out2)
    assert tree1 == tree2, f"{note}: output tree structure diverged"
    for i, (a, b) in enumerate(zip(leaves1, leaves2)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"{note}: leaf {i}"
        )


def _chaos_plan():
    return chaos.plan_from_dict(
        {
            "name": "unified-chaos",
            "peers": 3,
            "phases": [
                {"rounds": 16, "append": 1},
                {"rounds": 8, "crash": [1], "append": 1},
                {"rounds": 8, "heal": True, "append": 1},
            ],
        }
    )


def _reconfig_plan():
    return reconfig.ReconfigPlan(
        name="unified-reconfig",
        n_peers=3,
        voters=[1, 2],
        learners=[3],
        phases=[
            reconfig.ReconfigPhase(rounds=24, append=1),
            reconfig.ReconfigPhase(
                rounds=8, append=1, op={"promote_learner": 3}
            ),
            reconfig.ReconfigPhase(rounds=16, append=1),
        ],
    )


def _client_plan():
    return workload.ClientPlan(
        name="unified-client",
        n_peers=3,
        phases=[
            workload.ClientPhase(rounds=16, append=1),
            workload.ClientPhase(
                rounds=12, write_zipf=1.9, write_max=4, read_every=2,
                read_mode="lease",
            ),
            workload.ClientPhase(
                rounds=12, append=1, read_every=1, read_mode="safe"
            ),
        ],
        seed=7,
    )


# --- per-family golden scenarios -----------------------------------------


def _run_chaos(G):
    cfg = SimConfig(n_groups=G, n_peers=3, collect_health=True)
    compiled = chaos.compile_plan(_chaos_plan(), G)

    def fresh():
        return sim_mod.init_state(cfg), sim_mod.init_health(cfg)

    out_legacy = chaos.make_runner(cfg, compiled)(*fresh())
    out_unified = runner_mod.make_runner(cfg, (compiled,))(*fresh())
    _assert_tree_equal(out_legacy, out_unified, f"chaos g{G}")


def _run_reconfig(G, split):
    plan = _reconfig_plan()
    cfg = SimConfig(n_groups=G, n_peers=3, collect_health=True)
    compiled = reconfig.compile_plan(plan, G)
    ccompiled = chaos.compile_plan(
        chaos.plan_from_dict(
            {
                "name": "unified-overlay",
                "peers": 3,
                "phases": [
                    {"rounds": 32},
                    {"rounds": 8, "loss_all": 0.03},
                    {"rounds": 8},
                ],
            }
        ),
        G,
    )

    def fresh():
        st = sim_mod.init_state(cfg, *reconfig.initial_masks(plan, G))
        return st, sim_mod.init_health(cfg), reconfig.init_reconfig_state(st)

    if split:
        out_legacy = reconfig.make_split_runner(
            cfg, compiled, ccompiled, k=4, window=4
        )(*fresh())
        out_unified = runner_mod.make_runner(
            cfg, (compiled, ccompiled), split=True, k=4, window=4
        )(*fresh())
    else:
        out_legacy = reconfig.make_runner(cfg, compiled, ccompiled)(*fresh())
        out_unified = runner_mod.make_runner(cfg, (compiled, ccompiled))(
            *fresh()
        )
    tag = "split" if split else "plain"
    _assert_tree_equal(out_legacy, out_unified, f"reconfig-{tag} g{G}")


def _run_workload(G, split):
    cfg = SimConfig(n_groups=G, n_peers=3, collect_health=True)
    client = workload.compile_plan(_client_plan(), G)

    def fresh():
        st = sim_mod.init_state(cfg)
        return (
            st,
            sim_mod.init_health(cfg),
            reconfig.init_reconfig_state(st),
            workload.init_read_carry(G),
        )

    if split:
        out_legacy = workload.make_split_runner(cfg, client, k=4)(*fresh())
        out_unified = runner_mod.make_runner(
            cfg, (client,), split=True, k=4
        )(*fresh())
    else:
        out_legacy = workload.make_runner(cfg, client)(*fresh())
        out_unified = runner_mod.make_runner(cfg, (client,))(*fresh())
    tag = "split" if split else "plain"
    _assert_tree_equal(out_legacy, out_unified, f"workload-{tag} g{G}")


def _run_cadence(G):
    """One whole-horizon cadence segment with live action planes (one
    transfer target, two kicks) — the actions family's golden scenario."""
    cfg = SimConfig(
        n_groups=G, n_peers=3, collect_health=True, transfer=True
    )
    P = cfg.n_peers
    ccompiled = chaos.compile_plan(_chaos_plan(), G)
    R = ccompiled.n_rounds
    compiled = autopilot.empty_reconfig_schedule(R, P, G)

    def fresh_args():
        st = sim_mod.init_state(cfg)
        transfer = np.zeros((G,), np.int32)
        transfer[0] = 2
        kick = np.zeros((P, G), bool)
        kick[0, 1] = True
        kick[1, 2 % G] = True
        return (
            st,
            sim_mod.init_health(cfg),
            reconfig.init_reconfig_state(st),
            jnp.zeros((chaos.N_CHAOS_STATS,), jnp.int32),
            jnp.zeros((reconfig.N_RECONFIG_STATS,), jnp.int32),
            jnp.zeros((kernels.N_SAFETY,), jnp.int32),
            jnp.int32(0),
            jnp.int32(0),
            jnp.asarray(transfer, dtype=jnp.int32),
            jnp.asarray(kick, dtype=bool),
            *runner_mod.schedule_args(compiled, ccompiled),
        )

    out_legacy = autopilot.make_cadence_runner(cfg, compiled, ccompiled, R)(
        *fresh_args()
    )
    out_unified = runner_mod.make_runner(
        cfg, (compiled, ccompiled), cadence=R
    )(*fresh_args())
    _assert_tree_equal(out_legacy, out_unified, f"cadence g{G}")


# --- tier-1: G=8 ----------------------------------------------------------


def test_chaos_wrapper_bit_identical_g8():
    _run_chaos(8)


def test_reconfig_wrapper_bit_identical_g8():
    _run_reconfig(8, split=False)


def test_reconfig_split_wrapper_bit_identical_g8():
    _run_reconfig(8, split=True)


def test_workload_wrapper_bit_identical_g8():
    _run_workload(8, split=False)


def test_workload_split_wrapper_bit_identical_g8():
    _run_workload(8, split=True)


def test_cadence_wrapper_bit_identical_g8():
    _run_cadence(8)


# --- slow: the same scenarios at G=32 ------------------------------------


@pytest.mark.slow
def test_chaos_wrapper_bit_identical_g32():
    _run_chaos(32)


@pytest.mark.slow
def test_reconfig_wrapper_bit_identical_g32():
    _run_reconfig(32, split=False)


@pytest.mark.slow
def test_reconfig_split_wrapper_bit_identical_g32():
    _run_reconfig(32, split=True)


@pytest.mark.slow
def test_workload_wrapper_bit_identical_g32():
    _run_workload(32, split=False)


@pytest.mark.slow
def test_workload_split_wrapper_bit_identical_g32():
    _run_workload(32, split=True)


@pytest.mark.slow
def test_cadence_wrapper_bit_identical_g32():
    _run_cadence(32)


# --- dispatch surface -----------------------------------------------------


def test_make_runner_rejects_duplicate_family():
    cfg = SimConfig(n_groups=4, n_peers=3, collect_health=True)
    compiled = chaos.compile_plan(_chaos_plan(), 4)
    with pytest.raises(ValueError, match="chaos"):
        runner_mod.make_runner(cfg, (compiled, compiled))


def test_make_runner_rejects_empty():
    cfg = SimConfig(n_groups=4, n_peers=3, collect_health=True)
    with pytest.raises(ValueError):
        runner_mod.make_runner(cfg, ())


# --- the split runners' tail audit is one program --------------------------


@pytest.mark.parametrize("family", ["workload", "reconfig"])
def test_split_runner_tail_audit_runs_under_a_jit(family, monkeypatch):
    """Outside a jit kernels.check_safety is one XLA program per jnp op —
    its two quorum networks alone about 110 — dispatched after the last
    block of every call (PERF.md §6, PR 27).  The split runners call it
    through runner._tail_audit under a jit: every operand check_safety
    ever sees from a runner is a tracer."""
    G = 8
    cfg = SimConfig(n_groups=G, n_peers=3, collect_health=True)
    seen = []
    real = kernels.check_safety

    def spy(state, *args, **kw):
        seen.append(isinstance(state, jax.core.Tracer))
        return real(state, *args, **kw)

    monkeypatch.setattr(kernels, "check_safety", spy)
    if family == "workload":
        run = runner_mod.make_runner(
            cfg, (workload.compile_plan(_client_plan(), G),), split=True, k=4
        )
        st = sim_mod.init_state(cfg)
        args = (
            st, sim_mod.init_health(cfg), reconfig.init_reconfig_state(st),
            workload.init_read_carry(G),
        )
    else:
        plan = _reconfig_plan()
        run = runner_mod.make_runner(
            cfg, (reconfig.compile_plan(plan, G),), split=True, k=4, window=4
        )
        st = sim_mod.init_state(cfg, *reconfig.initial_masks(plan, G))
        args = (st, sim_mod.init_health(cfg), reconfig.init_reconfig_state(st))
    out = run(*args)
    assert int(jnp.sum(out[5])) == 0, "safety slots"
    assert seen and all(seen), seen
