"""A chaos plan in the workload split runner (ISSUE 51): `ClusterSim.run_reads(
client, chaos_plan, split=True)` and `runner.make_runner((client, chaos),
split=True)`.

  * split vs scan, bit for bit — every state plane, the health planes, the op
    carry, the chaos / read / safety accumulators, the read carry and the
    latency histogram — on the lease, ReadIndex, check-quorum-only and stock
    flag sets, under a crash phase, a partition phase and a lossy phase, with
    a block that straddles a chaos phase in every plan;
  * a block under a crashed follower DOES fuse where the fleet is small
    enough to be steady beside it: `crashed` != 0 reaches the kernel;
  * the `load-restart` mix (each store in turn up, then down) cut to 96
    rounds, the device's blocks against `simref.ScalarCluster` stepped round
    for round;
  * the guard's refusal counts count what `pallas_step.steady_mask` refuses,
    and the report's block counts add up;
  * a reconfig plan stays refused;
  * without a chaos plan the runner's three programs are the ones it had
    before ISSUE 51 (a sha1 of their jaxprs), and the one thing a fused block
    does under a plan and not without — it sets `last_leader` — stays there.
"""

import hashlib
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.multiraft import (
    ChaosOracle, ClusterSim, ScalarCluster, SimConfig, chaos, kernels,
    pallas_step, reconfig, sim, workload,
)
from raft_tpu.multiraft import runner as runner_mod
from test_damping_parity import assert_health_parity, assert_parity

G = 8
ROUNDS = 96
K = 4
ELECTION_TICK, HEARTBEAT_TICK = 10, 2

FLAGS = {
    "lease": dict(check_quorum=True, pre_vote=True, lease_read=True),
    "readindex": dict(check_quorum=True, pre_vote=True),
    "cq": dict(check_quorum=True),
    "stock": dict(),
}

OUTPUTS = (
    "state", "health", "rstate", "stats", "rstats", "safety", "read_carry",
    "read_stats", "lat_hist",
)


def cfg_of(flags, P, **more):
    return SimConfig(
        n_groups=G, n_peers=P, election_tick=ELECTION_TICK,
        heartbeat_tick=HEARTBEAT_TICK, collect_health=True, **FLAGS[flags], **more,
    )


def client_plan(P, flags):
    """A skewed write load; reads (lease where the fleet has leases, else
    ReadIndex) from round 20 to 44 — through the end of the first healthy
    stretch, the whole first fault and the return from it."""
    mode = "lease" if flags == "lease" else "safe"
    load = {"write_zipf": 1.6, "write_max": 3}
    return workload.plan_from_dict({"name": "c", "peers": P, "seed": 5, "phases": [
        {"rounds": 20, **load},
        {"rounds": 24, "append": 1, "read_every": 3, "read_mode": mode},
        {"rounds": ROUNDS - 44, **load}]})


# The blocks by the chaos phase they start in: rounds [0, 24), [42, 64) and
# [80, 96) are healthy; the block at round 40 straddles the boundary at 42.
HEALTHY_BLOCKS, FAULTED_BLOCKS = 15, 9


def chaos_plan(P, kind):
    """Five phases, 96 rounds; the second boundary (round 42) is no multiple
    of K, so one block straddles two chaos phases."""
    faults = {
        # a store lost, then a store cut off but alive
        "crash": ({"crash": [2]}, {"partition": [[1]]}),
        "partition": ({"partition": [[1, 2]]}, {"crash": [P]}),
        # a lossy link beside a crash, then a rate on every link of one group
        "lossy": ({"crash": [1], "loss": [{"from": 2, "to": 3, "rate": 0.4}],
                   "groups": {"mod": 2, "eq": 1}},
                  {"loss_all": 0.3, "groups": [5]}),
    }[kind]
    return chaos.plan_from_dict({"name": kind, "peers": P, "phases": [
        {"rounds": 24}, {"rounds": 18, **faults[0]}, {"rounds": 22},
        {"rounds": 16, **faults[1]}, {"rounds": 16}]})


def settled(cfg, rounds=3 * ELECTION_TICK):
    """(state, health) of a fleet booted `rounds` rounds from cold."""
    cs = ClusterSim(cfg)
    cs.run_compiled(rounds)
    cs.reset_health()
    return cs.state, cs._health


def run_both(cfg, client, plan, k=K, start=None):
    """(scan outputs, split outputs) of the two runners from one state."""
    st0, hl0 = start or settled(cfg)
    scheds = (workload.compile_plan(client, G), chaos.compile_plan(plan, G))
    outs = []
    for split in (False, True):
        run = runner_mod.make_runner(cfg, scheds, split=split, k=k)
        outs.append(run(
            jax.tree.map(jnp.copy, st0), jax.tree.map(jnp.copy, hl0),
            reconfig.init_reconfig_state(st0), workload.init_read_carry(G),
        ))
    return outs


def assert_same(scan, split):
    for name, a, b in zip(OUTPUTS, scan[:9], split[:9]):
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb), err_msg=name)


CASES = [
    ("lease", 3, "crash"), ("lease", 5, "lossy"), ("lease", 5, "partition"),
    ("readindex", 5, "crash"), ("readindex", 3, "lossy"),
    ("cq", 5, "partition"), ("cq", 3, "lossy"),
    ("stock", 5, "crash"), ("stock", 3, "lossy"), ("stock", 3, "partition"),
]


@pytest.mark.parametrize("flags,P,kind", CASES, ids=["-".join(map(str, c)) for c in CASES])
def test_split_with_a_chaos_plan_is_the_scan_bit_for_bit(flags, P, kind):
    cfg = cfg_of(flags, P)
    scan, split = run_both(cfg, client_plan(P, flags), chaos_plan(P, kind))
    assert_same(scan, split)
    fused, healthy_refused, refusals = (np.asarray(x) for x in split[9:])
    # Both arms ran: some block fused, and the straddling block alone keeps
    # the run from fusing whole.
    assert 0 < fused < ROUNDS * G, fused
    assert fused % (K * G) == 0
    assert not np.asarray(split[5]).any(), "safety slots"
    assert refusals.shape == (len(workload.GUARD_TERMS),)
    # The five blocks before the reads fuse on every fleet; those with an
    # outstanding read do not.
    assert 1 <= healthy_refused <= HEALTHY_BLOCKS - 5
    if kind == "lossy":
        # A block of the lossy stretch fused: the in-kernel draw knocked
        # out what the scan's did, from the block's own first round on.
        assert fused // (K * G) > HEALTHY_BLOCKS - healthy_refused


def test_a_block_under_a_crashed_follower_fuses():
    """Store 1 is down from before the first election on, so it leads no
    group: the survivors are a steady quorum beside it, and a block in which
    no group's crashed peer reaches its timeout fuses — the kernel's
    `crashed` operand is not zero.  Every block of the plan is faulted."""
    P, k = 3, 4
    cfg = SimConfig(n_groups=G, n_peers=P, election_tick=20, heartbeat_tick=2,
                    collect_health=True, **FLAGS["lease"])
    cs = ClusterSim(cfg)
    down = {"rounds": 80, "crash": [1], "append": 1}
    cs.run_plan(chaos.plan_from_dict({"name": "boot", "peers": P, "phases": [down]}))
    assert not (np.asarray(cs.state.state)[0] == kernels.ROLE_LEADER).any()
    cs.reset_health()
    start = (cs.state, cs._health)
    client = workload.plan_from_dict({"name": "c", "peers": P, "phases": [
        {"rounds": ROUNDS, "append": 1}]})
    plan = chaos.plan_from_dict({"name": "down", "peers": P, "phases": [
        {"rounds": ROUNDS, "crash": [1]}]})
    scan, split = run_both(cfg, client, plan, k=k, start=start)
    assert_same(scan, split)
    fused, healthy_refused, refusals = (np.asarray(x) for x in split[9:])
    assert fused >= 2 * k * G, fused
    assert healthy_refused == 0 and not refusals.any()  # no healthy block
    # What refuses the others: the crashed peer's timer, and nothing else.
    late = start[0]._replace(
        election_elapsed=start[0].election_elapsed.at[0].set(1000))
    crashed = jnp.zeros((P, G), bool).at[0].set(True)
    by = dict(zip(workload.GUARD_TERMS, np.asarray(runner_mod._guard_refusals(
        cfg, late, crashed, k, jnp.zeros((P, P, G), jnp.int32), jnp.zeros((G,), bool))).tolist()))
    assert by["no_campaign"] == G
    assert by["one_leader"] == by["terms_ok"] == by["cq_boundary"] == 0, by


def test_run_reads_reports_the_blocks_and_the_refusals():
    """`ClusterSim.run_reads(split=True)` under a chaos plan, two calls with
    state carried over, against the scan: one report but for the split
    run's own keys, whose counts add up."""
    P = 3
    cfg = cfg_of("lease", P)
    client, plan = client_plan(P, "lease"), chaos_plan(P, "crash")
    sims = [ClusterSim(cfg), ClusterSim(cfg)]
    for cs in sims:
        cs.run_compiled(3 * ELECTION_TICK)
        cs.reset_health()
    for call in range(2):
        scan = sims[0].run_reads(client, plan)
        split = sims[1].run_reads(client, plan, split=True, split_k=K)
        own = {k: split.pop(k) for k in list(split) if k not in scan}
        assert scan == split, call
        assert set(own) == {
            "fused_rounds", "total_rounds", "fused_frac", "split_blocks",
            "split_blocks_faulted", "split_blocks_healthy",
            "split_blocks_healthy_refused", "guard_refusals",
        }
        assert own["split_blocks"] == ROUNDS // K == HEALTHY_BLOCKS + FAULTED_BLOCKS
        assert own["split_blocks_faulted"] == FAULTED_BLOCKS
        assert own["split_blocks_healthy"] == HEALTHY_BLOCKS
        assert 1 <= own["split_blocks_healthy_refused"] <= HEALTHY_BLOCKS - 5
        fused_blocks = own["fused_rounds"] // (K * G)
        healthy_fused = own["split_blocks_healthy"] - own["split_blocks_healthy_refused"]
        assert 0 < healthy_fused <= fused_blocks
        assert set(own["guard_refusals"]) == set(workload.GUARD_TERMS)
        flat = workload.report_counts({**scan, **own})
        for term in workload.GUARD_TERMS:
            assert flat[f"guard_refusals.{term}"] == own["guard_refusals"][term]
        assert max(own["guard_refusals"].values()) >= 1
    for f in sims[0].state._fields:
        if getattr(sims[0].state, f) is not None:
            np.testing.assert_array_equal(
                np.asarray(getattr(sims[0].state, f)), np.asarray(getattr(sims[1].state, f)), err_msg=f)
    np.testing.assert_array_equal(
        np.asarray(sims[0]._read_carry.last_leader), np.asarray(sims[1]._read_carry.last_leader))


# --- load-restart, cut to 96 rounds, against the scalar port -------------------

UP, DOWN, SETTLE = 12, 12, 24


def restart_plan(P, append):
    """`benchmark/traffic/load-restart.json`'s chaos block cut to 12 up / 12
    down a store behind a cold fleet's settle (the mix's 240 / 80 at the
    deployment's ticks)."""
    phases = [{"rounds": SETTLE, "append": append}]
    for s in range(1, P + 1):
        phases += [{"rounds": UP, "append": append},
                   {"rounds": DOWN, "crash": [s], "append": append}]
    return chaos.plan_from_dict({"name": "load-restart", "peers": P, "phases": phases})


def test_load_restart_blocks_against_the_scalar_cluster():
    """The mix's shape at P = 3 from a cold fleet: the scalar cluster steps
    every round, the device every block of 4 through the split runner's own
    block program, and the two are compared after each block — terms, roles,
    cursors and the health planes."""
    P, k, window = 3, 4, 8
    cfg = SimConfig(n_groups=G, n_peers=P, election_tick=8, heartbeat_tick=2,
                    collect_health=True, health_window=window, **FLAGS["lease"])
    client = workload.plan_from_dict({"name": "load", "peers": P, "phases": [
        {"rounds": ROUNDS, "append": 1}]})
    run = runner_mod.make_runner(
        cfg, (workload.compile_plan(client, G), chaos.compile_plan(restart_plan(P, 0), G)),
        split=True, k=k)
    # The scalar side is offered the client's entry through its own schedule.
    sched = chaos.HostSchedule(restart_plan(P, 1), G)
    assert sched.n_rounds == ROUNDS
    scalar = ScalarCluster(G, P, election_tick=8, heartbeat_tick=2,
                           check_quorum=True, pre_vote=True, max_inflight_msgs=1 << 12)
    oracle = ChaosOracle(scalar, schedule=sched, window=window)
    st = sim.init_state(cfg)
    zeros = lambda n: jnp.zeros((n,), jnp.int32)  # noqa: E731
    carry = (
        st, sim.init_health(cfg), reconfig.init_reconfig_state(st),
        zeros(chaos.N_CHAOS_STATS), zeros(reconfig.N_RECONFIG_STATS), zeros(kernels.N_SAFETY),
        workload.init_read_carry(G), zeros(workload.N_READ_STATS), zeros(workload.N_LAT_BUCKETS),
        jnp.int32(0), jnp.int32(0), jnp.zeros((len(workload.GUARD_TERMS),), jnp.int32),
    )
    fused_at = []
    for b, block in enumerate(run.block_args):
        before = int(carry[9])
        carry = run.fused_jit(*carry, *block, *run.schedule_args)
        for _ in range(k):
            oracle.scheduled_round()
        device = types.SimpleNamespace(state=carry[0], _health=carry[1])
        r = (b + 1) * k - 1
        assert_parity(scalar, device, r, "load-restart")
        assert_health_parity(oracle, device, r, "load-restart")
        if int(carry[9]) > before:
            fused_at.append(b)
    assert not np.asarray(carry[5]).any(), "safety slots"
    # Blocks fused, all of them after the settle; a store's return is not
    # steady at once.
    assert fused_at and min(fused_at) >= 2, fused_at
    assert int(carry[10]) >= 1 and int(np.asarray(carry[11]).max()) >= 1


# --- the refusal counts ---------------------------------------------------------


def test_the_refusal_terms_are_steady_masks_own():
    """`runner._guard_refusals` decides nothing, so nothing else holds it to
    the guard: on states from the middle of a faulted run, the groups no
    term refuses are the groups `steady_mask` passes (the fleets here have
    no joint configuration, no transfer and every link among alive peers
    up, the terms it does not count)."""
    P = 3
    cfg = cfg_of("lease", P)
    st, _ = settled(cfg, rounds=12)  # elections still under way
    refusing = 0
    for crashed_peer in (None, 0, 1):
        crashed = jnp.zeros((P, G), bool)
        if crashed_peer is not None:
            crashed = crashed.at[crashed_peer].set(True)
        link = jnp.ones((P, P, G), bool)
        loss = jnp.zeros((P, P, G), jnp.int32).at[0, 1, 3].set(7)  # group 3 lossy
        pending = jnp.zeros((G,), bool).at[6].set(True)
        mask = np.asarray(pallas_step.steady_mask(
            cfg, st, crashed, horizon=K, link=link, loss_rate=loss, read_pending=pending))
        counts = np.asarray(runner_mod._guard_refusals(cfg, st, crashed, K, loss, pending))
        assert counts[-1] == 1  # read_pending: group 6
        # No term refuses a group <=> the mask passes it: a count per term
        # cannot say which groups, so hold the totals' two ends.
        assert (counts.sum() == 0) == mask.all()
        assert counts.max() <= G and counts.max() >= (~mask).sum() / len(counts)
        refusing += int((~mask).sum())
    assert refusing, "the states refuse something"


# --- the bare programs -----------------------------------------------------------

# sha1 of `str(jax.make_jaxpr(program))` (object addresses stripped) of the
# workload split runner's three programs for a BARE client plan, G = 64, as
# commit 5ed4243 (the parent of ISSUE 51) printed them: the four accepted split
# cells run the program they ran.  A PR that means to change a bare program
# re-reads `.load` x2 and `.serve` x2 as traced pairs (ROADMAP's standing rule
# for runner.py) and stores what this test then prints.
BARE_SHA1 = {
    "r5-lease": ("f46161862107bbbeec3b9b3a0f942e88ff02d1c2", "68dca2a3a3489cd5ec87f7a356e933857da9b12e",
                 "55ee18cfc0d70375c3be0321fea7252d6d82b35e"),
    "r3-lease": ("aeb72977c9bca41eb7f307ebe25039a15536efbd", "7cc94e4742a1b571d7afb34d7590ecd7ae994700",
                 "98eed02005447c29ed349a7d2d4443565f0b7d12"),
    "r5-stock": ("7784166f3e41f204eabcba7768bbf735e5548edf", "264f74392f2a480ef9e8352e60d1516b738ab9ef",
                 "55ee18cfc0d70375c3be0321fea7252d6d82b35e"),
}


def bare_carry(cfg):
    st = sim.init_state(cfg)
    zeros = lambda n: jnp.zeros((n,), jnp.int32)  # noqa: E731
    return (
        st, sim.init_health(cfg), reconfig.init_reconfig_state(st),
        zeros(chaos.N_CHAOS_STATS), zeros(reconfig.N_RECONFIG_STATS), zeros(kernels.N_SAFETY),
        workload.init_read_carry(cfg.n_groups), zeros(workload.N_READ_STATS),
        zeros(workload.N_LAT_BUCKETS), jnp.int32(0),
    )


@pytest.mark.parametrize("fleet", sorted(BARE_SHA1))
def test_the_bare_programs_are_the_parents(fleet, monkeypatch):
    P = int(fleet[1])
    flags = FLAGS["lease" if fleet.endswith("lease") else "stock"]
    cfg = SimConfig(64, P, election_tick=20, heartbeat_tick=2, collect_health=True, **flags)
    client = workload.compile_plan(workload.plan_from_dict({"name": "t", "peers": P, "seed": 1, "phases": [
        {"rounds": 24, "append": 1, "read_every": 2, "read_mode": "lease"},
        {"rounds": 20, "write_zipf": 1.5, "read_every": 3, "read_mode": "safe"}]}), 64)
    programs = {}
    real_jit = jax.jit

    def spy(fn, **kw):
        programs[fn.__name__] = fn
        return real_jit(fn, **kw)

    monkeypatch.setattr(jax, "jit", spy)
    run = runner_mod.make_runner(cfg, (client,), split=True, k=8)
    monkeypatch.undo()

    def sha(fn, *args):
        text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(*args)))
        return hashlib.sha1(text.encode()).hexdigest()

    carry = bare_carry(cfg)
    got = (
        sha(programs["block_run"], *carry, *run.block_args[0], *run.schedule_args),
        sha(programs["tail_run"], *carry, jnp.int32(40), *run.schedule_args),
        sha(programs["tables_run"], jnp.asarray([0, 24], jnp.int32), *run.schedule_args),
    )
    assert got == BARE_SHA1[fleet], got


@pytest.mark.parametrize("with_plan", [False, True], ids=["bare", "chaos"])
def test_a_fused_block_sets_last_leader_only_under_a_plan(with_plan):
    """The known gap (PERF.md section 7, PR 51 (3)): a fused block of a bare
    plan leaves `ReadCarry.last_leader` where it was — the parent's program,
    held by the sha1 above — and under a chaos plan sets it to the standing
    leader, as k general rounds do.  Whoever closes the gap edits this test
    and the sha1 together."""
    P = 3
    cfg = cfg_of("lease", P)
    st0, hl0 = settled(cfg)
    client = workload.compile_plan(workload.plan_from_dict({"name": "c", "peers": P, "phases": [
        {"rounds": K, "append": 1}]}), G)
    healthy = chaos.compile_plan(chaos.plan_from_dict({"name": "h", "peers": P, "phases": [
        {"rounds": K}]}), G)
    run = runner_mod.make_runner(cfg, (client, healthy) if with_plan else (client,), split=True, k=K)
    out = run(st0, hl0, reconfig.init_reconfig_state(st0), workload.init_read_carry(G))
    assert int(out[9]) == K * G  # the one block fused
    last = np.asarray(out[6].last_leader)
    assert (last > 0).all() if with_plan else not last.any()


def test_a_reconfig_plan_stays_refused():
    P = 3
    cfg = cfg_of("lease", P)
    client = workload.compile_plan(client_plan(P, "lease"), G)
    plan = reconfig.empty_reconfig_schedule(ROUNDS, P, G)
    with pytest.raises(ValueError, match="reconfig"):
        runner_mod.make_runner(cfg, (client, plan), split=True, k=K)
    with pytest.raises(ValueError, match="reconfig"):
        runner_mod.make_runner(
            cfg, (client, plan, chaos.compile_plan(chaos_plan(P, "crash"), G)), split=True, k=K)


def test_the_plans_must_span_the_same_rounds():
    P = 3
    cfg = cfg_of("lease", P)
    client = workload.compile_plan(client_plan(P, "lease"), G)
    short = chaos.compile_plan(chaos.plan_from_dict({
        "name": "s", "peers": P, "phases": [{"rounds": ROUNDS - K}]}), G)
    with pytest.raises(ValueError, match="rounds"):
        runner_mod.make_runner(cfg, (client, short), split=True, k=K)


def test_phase_faulted_reads_the_packed_planes():
    plan = chaos.plan_from_dict({"name": "f", "peers": 5, "phases": [
        {"rounds": 4}, {"rounds": 4, "crash": [5], "groups": [2]},
        {"rounds": 4, "partition": [[1]]}, {"rounds": 4, "heal": True},
        {"rounds": 4, "loss": [{"from": 1, "to": 2, "rate": 0.01}]},
        {"rounds": 4, "links": [{"from": 1, "to": 2, "up": True}]}]})
    got = np.asarray(chaos.phase_faulted(chaos.compile_plan(plan, G)))
    assert got.tolist() == [False, True, True, False, True, False]
