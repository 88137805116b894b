"""Sharded-vs-unsharded bit-identity (ISSUE 14).

The multi-chip path — ClusterSim(mesh=): sharded bootstrap, donated
run_compiled scan segments, compiled chaos/reconfig/client schedules
replayed cross-chip, the split-fused runner — must produce EXACTLY the
single-device results: every SimState plane, the health planes, the
safety/stat accumulators, and the scenario reports, bit for bit.  The
group axis is embarrassingly parallel and every accumulator is integer,
so sharding may not change one bit; these tests pin that.

Also pinned here: SimConfig.spmd (the mesh-friendly election-phase form
that keeps the steady sharded graph collective-free, graftcheck GC015)
is bit-identical to the cond form on and off campaign rounds.

Tier-1 keeps the spmd-identity unit, the plain-scan parity case, the
drain-overlap/counter parity case (the multichip CI tool replays the
corpora but not the instrumented run_compiled path), and the
total_commit overflow regression and, since PR 32, the damped
packed-carry scan at mesh-tiling width and the client-read workload (the
damped bodies every benchmark cell runs); the golden chaos AND reconfig
corpora and the split-fused production plan are slow-marked (23-40 s
each; tier-1 takes 247 s of its 1470 s limit under xdist -n 6 at PR 32) and the
corpora are replayed by the multichip CI job via
tools/sharded_parity_report.py.
"""

import functools
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raft_tpu.multiraft import ClusterSim, SimConfig
from raft_tpu.multiraft import chaos, reconfig, sharding, workload
from raft_tpu.multiraft import sim as sim_mod

TESTDATA = os.path.join(os.path.dirname(__file__), "testdata")


def assert_state_equal(a, b, tag=""):
    for name in sim_mod.SimState._fields:
        x, y = getattr(a, name), getattr(b, name)
        if x is None:
            assert y is None, f"{tag}:{name}"
            continue
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"{tag}:{name}"
        )


def assert_sim_equal(sharded, local, tag=""):
    assert_state_equal(sharded.state, local.state, tag)
    if local._health is not None:
        np.testing.assert_array_equal(
            np.asarray(sharded._health.planes),
            np.asarray(local._health.planes),
            err_msg=f"{tag}:health",
        )


def test_spmd_step_identity():
    """SimConfig.spmd (election phase unconditional) is bit-identical to
    the cond form across quiet rounds, campaign storms, and crash
    windows — the no-campaigner election() is a provable no-op."""
    cfg = SimConfig(n_groups=16, n_peers=3)
    cfg_spmd = cfg._replace(spmd=True)
    rng = np.random.RandomState(0)
    st_a, st_b = sim_mod.init_state(cfg), sim_mod.init_state(cfg_spmd)
    step_a = jax.jit(functools.partial(sim_mod.step, cfg))
    step_b = jax.jit(functools.partial(sim_mod.step, cfg_spmd))
    for r in range(40):
        crashed = jnp.asarray(rng.rand(3, 16) < (0.2 if r % 7 == 0 else 0.0))
        append = jnp.asarray((rng.rand(16) < 0.5).astype(np.int32))
        st_a = step_a(st_a, crashed, append)
        st_b = step_b(st_b, crashed, append)
    assert_state_equal(st_a, st_b, "spmd")


def test_sharded_scan_parity_plain():
    """ClusterSim(mesh=).run_compiled — the donated sharded scan — is
    bit-identical to the single-device scan, including the sharded
    bootstrap (sharded_init_state must reproduce init_state exactly)."""
    cfg = SimConfig(n_groups=32, n_peers=3)
    mesh = sharding.make_mesh()
    a = ClusterSim(cfg, mesh=mesh)
    b = ClusterSim(cfg)
    assert_state_equal(a.state, b.state, "bootstrap")
    assert a.state.term.sharding.spec == jax.sharding.PartitionSpec(
        None, "groups"
    )
    append = jnp.ones((32,), jnp.int32)
    a.run_compiled(24, append_n=append)
    b.run_compiled(24, append_n=append)
    assert_state_equal(a.state, b.state, "scan")


def test_sharded_damped_scan_parity_packed_carry():
    """The damped mesh scan: the bits_g packed recent_active carry rides
    the donated segments sharded on its group-minor word axis (G=256:
    8 words, one per device) — bit-identical to the single-device run."""
    cfg = SimConfig(
        n_groups=256, n_peers=3, check_quorum=True, pre_vote=True
    )
    mesh = sharding.make_mesh()
    a = ClusterSim(cfg, mesh=mesh)
    b = ClusterSim(cfg)
    append = jnp.ones((256,), jnp.int32)
    a.run_compiled(24, append_n=append)
    b.run_compiled(24, append_n=append)
    assert_state_equal(a.state, b.state, "damped-scan")


def test_sharded_drain_overlap_counter_parity():
    """run_compiled's drain/scan overlap on the mesh: counter totals and
    the health-summary stream are bit-identical to the single-device
    drains (the counter fold is the one registered ICI reduction of the
    instrumented scan)."""
    cfg = SimConfig(
        n_groups=32, n_peers=3, collect_counters=True, collect_health=True
    )
    mesh = sharding.make_mesh()
    a = ClusterSim(cfg, mesh=mesh)
    b = ClusterSim(cfg)
    append = jnp.ones((32,), jnp.int32)
    a.run_compiled(20, append_n=append)
    b.run_compiled(20, append_n=append)
    assert_sim_equal(a, b, "drain")
    assert a.counters() == b.counters()


@pytest.mark.slow  # 6 scenarios x 2 chaos-runner compiles
def test_sharded_golden_chaos_corpus():
    """Every golden chaos scenario replays bit-identically on the mesh:
    state + health planes + the MTTR/safety report."""
    with open(
        os.path.join(TESTDATA, "chaos", "plans.json"), encoding="utf-8"
    ) as f:
        plans = json.load(f)
    mesh = sharding.make_mesh()
    for doc in plans:
        plan = chaos.plan_from_dict(doc)
        cfg = SimConfig(
            n_groups=32, n_peers=plan.n_peers, collect_health=True
        )
        a = ClusterSim(cfg, mesh=mesh, chaos=plan)
        b = ClusterSim(cfg, chaos=plan)
        ra, rb = a.run_plan(), b.run_plan()
        assert_sim_equal(a, b, plan.name)
        assert ra == rb, f"{plan.name}: report diverged"


@pytest.mark.slow  # 5 scenarios x 2 reconfig-runner compiles
def test_sharded_golden_reconfig_corpus():
    """Every golden reconfig scenario (reconfig DURING chaos in one scan)
    replays bit-identically on the mesh, including the op-protocol
    outcome and the joint-window safety counts."""
    with open(
        os.path.join(TESTDATA, "reconfig", "plans.json"), encoding="utf-8"
    ) as f:
        plans = json.load(f)
    mesh = sharding.make_mesh()
    for doc in plans:
        plan = reconfig.plan_from_dict(doc["reconfig"])
        cplan = chaos.plan_from_dict(doc["chaos"])
        cfg = SimConfig(
            n_groups=32, n_peers=plan.n_peers, collect_health=True
        )
        vm, om, lm = reconfig.initial_masks(plan, 32)
        a = ClusterSim(
            cfg, voter_mask=vm, outgoing_mask=om, learner_mask=lm,
            mesh=mesh,
        )
        b = ClusterSim(
            cfg, voter_mask=vm, outgoing_mask=om, learner_mask=lm
        )
        ra = a.run_reconfig(plan, chaos_plan=cplan)
        rb = b.run_reconfig(plan, chaos_plan=cplan)
        assert_sim_equal(a, b, plan.name)
        assert ra == rb, f"{plan.name}: report diverged"


def test_sharded_reads_parity():
    """The compiled client workload (Zipf writes + lease/safe reads) with
    a chaos overlay in the SAME scan replays bit-identically on the
    mesh: read stats, the on-device latency histogram percentiles, and
    the linearizability safety slots."""
    G = 64
    cfg = SimConfig(
        n_groups=G, n_peers=3, collect_health=True,
        check_quorum=True, lease_read=True,
    )
    plan = workload.ClientPlan(
        name="sharded-reads",
        n_peers=3,
        seed=5,
        phases=[
            workload.ClientPhase(rounds=12, append=1),
            workload.ClientPhase(
                rounds=16, read_every=2, read_mode="lease",
                write_zipf=1.8,
            ),
            workload.ClientPhase(rounds=12, read_every=3, read_mode="safe"),
        ],
    )
    cplan = chaos.ChaosPlan(
        name="overlay",
        n_peers=3,
        phases=[
            chaos.ChaosPhase(rounds=20, loss_all=0.02),
            chaos.ChaosPhase(rounds=20),
        ],
    )
    mesh = sharding.make_mesh()
    a = ClusterSim(cfg, mesh=mesh)
    b = ClusterSim(cfg)
    ra = a.run_reads(plan, chaos_plan=cplan)
    rb = b.run_reads(plan, chaos_plan=cplan)
    assert_sim_equal(a, b, "reads")
    assert ra == rb, "read report diverged"


@pytest.mark.slow  # split-runner + settle compiles x2 at G=256/P=5
def test_sharded_split_fused_prod_plan():
    """The ISSUE 11 split-horizon runner rides per-shard: the production
    plan (health + counters + chaos overlay + cq + pv) executes its
    fused steady blocks under the mesh with the SAME measured fused
    fraction (> 0) and bit-identical state as the single-device run."""
    with open(
        os.path.join(
            os.path.dirname(__file__), "..", "examples", "reconfig",
            "prod_fused.json",
        ),
        encoding="utf-8",
    ) as f:
        doc = json.load(f)
    plan = reconfig.plan_from_dict(doc["reconfig"])
    cplan = chaos.plan_from_dict(doc["chaos"])
    G = 256
    # collect_counters stays off: ClusterSim.run_reconfig(split=True)
    # refuses plans longer than the GC008 per-window drain cap (256
    # rounds > 128) — the counters-threaded split path is pinned by
    # tests/test_reconfig_split.py, and mesh counter parity by
    # test_sharded_drain_overlap_counter_parity.
    cfg = SimConfig(
        n_groups=G, n_peers=plan.n_peers, election_tick=64,
        collect_health=True,
        check_quorum=True, pre_vote=True,
    )
    vm, om, lm = reconfig.initial_masks(plan, G)
    mesh = sharding.make_mesh()
    append = jnp.ones((G,), jnp.int32)
    sims = []
    for m in (mesh, None):
        cs = ClusterSim(
            cfg, voter_mask=vm, outgoing_mask=om, learner_mask=lm, mesh=m
        )
        # Settle the boot storm outside the plan (bench_prod_fused's
        # regime) so the steady predicate can engage the fused blocks.
        cs.run_compiled(3 * cfg.election_tick, append_n=append)
        sims.append(cs)
    a, b = sims
    ra = a.run_reconfig(plan, chaos_plan=cplan, split=True, split_k=8)
    rb = b.run_reconfig(plan, chaos_plan=cplan, split=True, split_k=8)
    assert_sim_equal(a, b, "prod-fused")
    assert ra == rb, "split report diverged"
    assert ra["fused_frac"] > 0.5, ra["fused_frac"]


def test_sharded_status_total_commit_exact_past_int32():
    """ISSUE 14 regression: global_status.total_commit is EXACT past
    2**31 (the old single int32 psum wrapped at ~1M groups x commit>2k);
    the limb psums + host recombination reproduce the true sum."""
    G = 4096
    cfg = SimConfig(n_groups=G, n_peers=3)
    mesh = sharding.make_mesh()
    st = sim_mod.init_state(cfg)
    big = 3_000_000  # 4096 * 3M = 1.2e10 >> 2**31
    from raft_tpu.multiraft.kernels import ROLE_LEADER

    st = st._replace(
        state=st.state.at[0].set(ROLE_LEADER),
        commit=st.commit.at[0].set(big),
    )
    st = sharding.shard_state(st, mesh)
    status = sharding.global_status(cfg, mesh)(st)
    want = G * big
    assert want >= 2**31
    assert status["total_commit"] == want
    assert int(status["n_leaders"]) == G
