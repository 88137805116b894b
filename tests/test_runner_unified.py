"""ClusterSim's entry points against the runner factory called directly.

``runner.make_runner`` is the one way a compiled scenario runner is built
(raft_tpu/multiraft/runner.py, instantiated from the schedules.py
registry).  What sits between a user and it is the facade: ClusterSim's
``run_plan`` / ``run_reconfig`` / ``run_reads`` and the autopilot's
cadence loop compile and place the schedules, cache the runner, thread
the carry from call to call and format the report.  These tests pin that
layer: one golden scenario per schedule family, run through the facade
and through the factory on fresh state, every state leaf compared
bit-for-bit and the report against the same formatter over the factory's
own outputs.  G=8 covers tier-1; the same scenarios at G=32 are
slow-marked.

The jaxpr-level identity of the graphs is separately machine-checked
(GC014 holds the committed budgets; GC019 pins the phase decomposition).
"""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from raft_tpu.multiraft import ClusterSim, SimConfig
from raft_tpu.multiraft import chaos, kernels, reconfig, workload
from raft_tpu.multiraft import runner as runner_mod
from raft_tpu.multiraft import sim as sim_mod
from raft_tpu.multiraft.autopilot import Autopilot, AutopilotConfig
from raft_tpu.multiraft.health import HealthMonitor


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
    """run_plan twice on one sim (the second call takes the cached
    runner from where the first left the fleet) == the factory's runner
    called twice with the carry threaded by hand."""
    cfg = SimConfig(n_groups=G, n_peers=3, collect_health=True)
    plan = _chaos_plan()
    cs = ClusterSim(cfg, chaos=plan)
    run = runner_mod.make_runner(cfg, (chaos.compile_plan(plan, G),))
    st, hl = sim_mod.init_state(cfg), sim_mod.init_health(cfg)
    for call in (1, 2):
        report = cs.run_plan()
        st, hl, stats, safety = run(st, hl)
        _assert_tree_equal((cs.state, cs._health), (st, hl), f"chaos g{G}")
        assert report == HealthMonitor.chaos_report(
            *jax.device_get((stats, safety)), plan.n_rounds
        ), f"call {call}"


def _run_reconfig(G, split):
    plan = _reconfig_plan()
    cfg = SimConfig(n_groups=G, n_peers=3, collect_health=True)
    cplan = chaos.plan_from_dict(
        {
            "name": "unified-overlay",
            "peers": 3,
            "phases": [
                {"rounds": 32},
                {"rounds": 8, "loss_all": 0.03},
                {"rounds": 8},
            ],
        }
    )
    vm, om, lm = reconfig.initial_masks(plan, G)
    cs = ClusterSim(cfg, voter_mask=vm, outgoing_mask=om, learner_mask=lm)
    report = cs.run_reconfig(
        plan, chaos_plan=cplan, split=split, split_k=4, split_window=4
    )

    run = runner_mod.make_runner(
        cfg,
        (reconfig.compile_plan(plan, G), chaos.compile_plan(cplan, G)),
        split=split, k=4, window=4,
    )
    st = sim_mod.init_state(cfg, *reconfig.initial_masks(plan, G))
    out = run(st, sim_mod.init_health(cfg), reconfig.init_reconfig_state(st))
    tag = "split" if split else "plain"
    _assert_tree_equal(
        (cs.state, cs._health, cs._reconfig_state), out[:3],
        f"reconfig-{tag} g{G}",
    )
    stats, rstats, safety, om_h, since = jax.device_get(
        out[3:6]
        + (out[0].outgoing_mask, out[1].planes[kernels.HP_SINCE_COMMIT])
    )
    want = HealthMonitor.reconfig_report(
        stats, rstats, safety, plan.n_rounds,
        *HealthMonitor.reconfig_stall_groups(
            om_h, since, cfg.election_tick, stall_timeouts=4,
            topk=min(cfg.health_topk, G),
        ),
    )
    if split:
        total = plan.n_rounds * G
        want.update(
            fused_rounds=int(out[6]), total_rounds=total,
            fused_frac=round(int(out[6]) / total, 4),
        )
        assert want["fused_rounds"] > 0
    assert report == want


def _run_workload(G, split):
    """run_reads twice on one sim: state, health, the op-protocol carry
    kept between the calls and the read carry, against the factory's
    runner with fresh reads a call, over the last call's `last_leader`
    plane."""
    cfg = SimConfig(n_groups=G, n_peers=3, collect_health=True)
    plan = _client_plan()
    cs = ClusterSim(cfg)
    run = runner_mod.make_runner(
        cfg, (workload.compile_plan(plan, G),), split=split, k=4
    )
    st, hl = sim_mod.init_state(cfg), sim_mod.init_health(cfg)
    rst = reconfig.init_reconfig_state(st)
    tag = "split" if split else "plain"
    last = None
    for call in (1, 2):
        report = cs.run_reads(plan, split=split, split_k=4)
        out = run(st, hl, rst, workload.init_read_carry(G, last))
        st, hl, rst, stats, rstats, safety, rcar, rdstats, lat_hist = out[:9]
        _assert_tree_equal(
            (cs.state, cs._health, cs._reconfig_state, cs._read_carry),
            (st, hl, rst, rcar), f"workload-{tag} g{G} call {call}",
        )
        lat_p, recover_p = workload.report_percentiles(lat_hist, stats)
        want = workload.read_report(
            *jax.device_get((rdstats, lat_p, safety, stats)), plan.n_rounds,
            *jax.device_get((recover_p, rstats)),
        )
        last = rcar.last_leader
        if split:
            total = plan.n_rounds * G
            want.update(
                fused_rounds=int(out[9]), total_rounds=total,
                fused_frac=round(int(out[9]) / total, 4),
            )
        assert report == want, f"call {call}"


def _run_cadence(G):
    """The autopilot's loop over a crash window at cadence 8 (four
    segments, the policy's kicks and transfers live) == the factory's
    cadence segment driven by hand with the action planes the policy
    chose, then the tail audit."""
    cfg = SimConfig(
        n_groups=G, n_peers=3, collect_health=True, transfer=True
    )
    P, cadence = cfg.n_peers, 8
    plan = _chaos_plan()
    cs = ClusterSim(cfg)
    ap = Autopilot(cs, AutopilotConfig(cadence=cadence))
    decided = []
    decide = ap._decide

    def spy(summary, round_idx):
        planes = decide(summary, round_idx)
        decided.append(planes[:2])
        return planes

    ap._decide = spy
    report = ap.run_plan(plan)
    assert sum(report["actions"].values()) > 0, report["actions"]

    ccompiled = chaos.compile_plan(plan, G)
    R = ccompiled.n_rounds
    compiled = reconfig.empty_reconfig_schedule(R, P, G)
    run = runner_mod.make_runner(cfg, (compiled, ccompiled), cadence=cadence)
    st = sim_mod.init_state(cfg)
    carry = (
        st,
        sim_mod.init_health(cfg),
        reconfig.init_reconfig_state(st),
        jnp.zeros((chaos.N_CHAOS_STATS,), jnp.int32),
        jnp.zeros((reconfig.N_RECONFIG_STATS,), jnp.int32),
        jnp.zeros((kernels.N_SAFETY,), jnp.int32),
        jnp.int32(0),
    )
    actions = [(np.zeros((G,), np.int32), np.zeros((P, G), bool))] + decided
    assert len(actions) == R // cadence
    for i, (transfer, kick) in enumerate(actions):
        *carry, _fused = run(
            *carry,
            jnp.int32(i * cadence),
            jnp.asarray(transfer, dtype=jnp.int32),
            jnp.asarray(kick, dtype=bool),
            *runner_mod.schedule_args(compiled, ccompiled),
        )
    st, hl, rst, stats, _rstats, safety, csr = carry
    _assert_tree_equal((cs.state, cs._health), (st, hl), f"cadence g{G}")
    safety = safety + runner_mod._tail_audit(st, rst)
    want = HealthMonitor.chaos_report(
        *jax.device_get((stats, safety)), R
    )
    assert {k: report[k] for k in want} == want
    assert report["commit_stall_group_rounds"] == int(csr)


# --- tier-1: G=8 ----------------------------------------------------------


def test_run_plan_is_the_chaos_runner_g8():
    _run_chaos(8)


def test_run_reconfig_is_the_reconfig_runner_g8():
    _run_reconfig(8, split=False)


def test_run_reconfig_split_is_the_reconfig_split_runner_g8():
    _run_reconfig(8, split=True)


def test_run_reads_is_the_workload_runner_g8():
    _run_workload(8, split=False)


def test_run_reads_split_is_the_workload_split_runner_g8():
    _run_workload(8, split=True)


def test_autopilot_loop_is_the_cadence_runner_g8():
    _run_cadence(8)


# --- slow: the same scenarios at G=32 ------------------------------------


@pytest.mark.slow
def test_run_plan_is_the_chaos_runner_g32():
    _run_chaos(32)


@pytest.mark.slow
def test_run_reconfig_is_the_reconfig_runner_g32():
    _run_reconfig(32, split=False)


@pytest.mark.slow
def test_run_reconfig_split_is_the_reconfig_split_runner_g32():
    _run_reconfig(32, split=True)


@pytest.mark.slow
def test_run_reads_is_the_workload_runner_g32():
    _run_workload(32, split=False)


@pytest.mark.slow
def test_run_reads_split_is_the_workload_split_runner_g32():
    _run_workload(32, split=True)


@pytest.mark.slow
def test_autopilot_loop_is_the_cadence_runner_g32():
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
