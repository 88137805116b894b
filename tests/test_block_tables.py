"""The split workload runner's block tables (workload.block_tables /
workload.BlockRows; PERF.md §6, PR 31).

What a fused block's guard needs of the client schedule is computed once
per schedule and handed to block b as operands.  Layers:
  * the tabled rows equal the per-round definitions
    (workload.reads_pending_in_horizon's schedule half,
    workload.lease_fires_in_block, the append row, same_phase) at every
    block start, on schedules that cross phases inside a block, fire in a
    block's first and last round, end in a tail, and carry stray bits past
    G in the fire words;
  * the split runner stays bit-equal to the unsplit runner — state, report
    and the exact fused count — on a schedule that mixes fused and general
    blocks, phase crossings and a tail;
  * the property the chip number rests on, held on a CPU: the block
    program's guard is the same equations at n_rounds 64 and 640, and none
    of its operands has a dimension of n_rounds or n_phases.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raft_tpu.multiraft import ClusterSim, SimConfig, sim
from raft_tpu.multiraft import chaos, kernels, reconfig, workload
from raft_tpu.multiraft import runner as runner_mod
from raft_tpu.multiraft.simref import host_pack_bits_g

P = 3
LEASE, SAFE = sim.READ_LEASE, sim.READ_SAFE


def schedule(G, phase_rounds, fires, stray_bits=False, seed=0):
    """A CompiledClient built by hand: `phase_rounds` the phases' lengths,
    `fires` = {round: (mode, groups)} on top of seeded background fires;
    every phase gives each group a seeded mode (0 / SAFE / LEASE) and a
    seeded append."""
    rng = np.random.RandomState(seed)
    R, nph = sum(phase_rounds), len(phase_rounds)
    phase_of_round = np.repeat(np.arange(nph), phase_rounds).astype(np.int32)
    read_mode = rng.choice([0, SAFE, LEASE], size=(nph, G)).astype(np.int32)
    fire = rng.random((R, G)) < 0.1
    for r, (mode, groups) in fires.items():
        fire[r, groups] = True
        read_mode[phase_of_round[r], groups] = mode
    packed = host_pack_bits_g(fire)
    if stray_bits:  # bits past G in the last word: never a group's
        packed[:, -1] |= np.uint32(0xFFFFFFFF) << np.uint32(G % 32)
    return workload.CompiledClient(
        phase_of_round=jnp.asarray(phase_of_round),
        read_fire_packed=jnp.asarray(packed, jnp.uint32),
        read_mode=jnp.asarray(read_mode),
        append=jnp.asarray(rng.randint(0, 4, size=(nph, G)), jnp.int32),
        n_peers=P,
    )


# name -> (G, phase lengths, placed fires).  Block starts for k = 8 are
# 0, 8, 16, ...; for k = 4 every fourth round.
SCHEDULES = {
    # Phase boundaries at rounds 10 and 31: inside a block for either k.
    "phases_cross_blocks": (40, [10, 21, 9], {}),
    # SAFE and LEASE fires in the first and the last round of blocks.
    "fires_at_block_edges": (
        40, [16, 16],
        {0: (SAFE, [0, 39]), 7: (LEASE, [1, 38]), 8: (LEASE, [2, 33]),
         15: (SAFE, [3, 31]), 16: (SAFE, [32]), 31: (LEASE, [0, 39])},
    ),
    # n_rounds % k != 0: the tail's rounds belong to no block.
    "ends_in_a_tail": (33, [8, 8, 13], {28: (SAFE, [5]), 23: (LEASE, [32])}),
    # One long phase: every block shares one append row.
    "one_phase": (64, [48], {47: (SAFE, [63])}),
}


@pytest.mark.parametrize("k", [4, 8])
@pytest.mark.parametrize("name", sorted(SCHEDULES))
def test_tabled_rows_equal_the_per_round_definitions(name, k):
    G, phase_rounds, fires = SCHEDULES[name]
    client = schedule(G, phase_rounds, fires, stray_bits=G % 32 != 0)
    R = client.n_rounds
    cfg = SimConfig(n_groups=G, n_peers=P, collect_health=True)
    run = runner_mod.make_runner(cfg, (client,), split=True, k=k)
    assert len(run.block_args) == R // k
    idle = workload.init_read_carry(G)

    @jax.jit
    def per_round(r0):
        n, any_lease = workload.lease_fires_in_block(client, r0, k)
        return (
            workload.reads_pending_in_horizon(client, idle, r0, k),
            any_lease, jnp.sum(n),
        )

    phase = np.asarray(client.phase_of_round)
    some_cross = False
    for b, (rows, append) in enumerate(run.block_args):
        r0 = b * k
        safe, any_lease, n_lease = per_round(jnp.int32(r0))
        where = f"{name} k={k} block {b}"
        assert int(rows.r0) == r0, where
        assert np.array_equal(
            kernels.unpack_bits_g(rows.safe_fire, G), safe), where
        assert np.array_equal(
            kernels.unpack_bits_g(rows.lease_fire, G), any_lease), where
        assert int(rows.n_lease) == int(n_lease), where
        assert bool(rows.same_phase) == (
            phase[r0] == phase[r0 + k - 1]), where
        assert np.array_equal(append, client.append[phase[r0]]), where
        some_cross |= not bool(rows.same_phase)
    if name == "phases_cross_blocks":
        assert some_cross
    # Blocks that start in one phase share one row (not a copy each).
    loads = {id(a) for _, a in run.block_args}
    assert len(loads) == len({phase[b * k] for b in range(R // k)})


@pytest.mark.parametrize(
    "phase_rounds", [[8] * 8, [64], [3] * 21 + [1]],
    ids=["a_phase_a_block", "one_phase", "short_phases"],
)
def test_tables_stay_under_the_schedules_own_bytes(phase_rounds):
    """Bit planes for the two masks, scalars for the rest, one append row
    per phase a block starts in: whatever the phases' lengths, the rows of
    all blocks are smaller than the schedule they were computed from."""
    client = schedule(96, phase_rounds, {})
    cfg = SimConfig(n_groups=96, n_peers=P, collect_health=True)
    run = runner_mod.make_runner(cfg, (client,), split=True, k=8)
    rows = {id(x): x.nbytes for x in jax.tree.leaves(run.block_args)}
    own = sum(
        x.nbytes for x in jax.tree.leaves(client._replace(n_peers=None))
    )
    assert sum(rows.values()) < own


def mixed_plan():
    """Settled fleet, heartbeat_tick 1: lease blocks fuse, a SAFE fire or a
    phase crossing inside a block rejects it, 4 rounds of tail."""
    ph = workload.ClientPhase
    return workload.ClientPlan(
        name="mixed-blocks", n_peers=P,
        phases=[
            ph(rounds=12, append=1, read_every=2, read_mode="lease"),
            ph(rounds=10, append=2, read_every=3, read_mode="lease"),
            ph(rounds=10, append=1, read_every=4, read_mode="safe"),
            ph(rounds=16, append=1),
            ph(rounds=4, append=1, read_every=1, read_mode="lease"),
        ],
    )


def test_split_equals_unsplit_on_mixed_blocks_with_the_exact_fused_count():
    G, k = 8, 8
    cfg = SimConfig(
        n_groups=G, n_peers=P, election_tick=16, collect_health=True,
        check_quorum=True, lease_read=True,
    )
    plan = mixed_plan()
    reports, states = [], []
    for split in (False, True):
        cs = ClusterSim(cfg)
        cs.run_compiled(3 * cfg.election_tick)  # every group has a leader
        cs.reset_health()
        reports.append(cs.run_reads(plan, split=split, split_k=k))
        states.append((cs.state, cs._health, cs._read_carry))
    for a, b in zip(jax.tree.leaves(states[0]), jax.tree.leaves(states[1])):
        assert np.array_equal(a, b)
    general, split = reports
    fused = {
        name: split.pop(name)
        for name in ("fused_rounds", "total_rounds", "fused_frac")
    }
    assert split == general
    assert general["served_lease"] > 0 and general["served_quorum"] > 0
    # Blocks of 8 over phases 12 / 10 / 10 / 16 / 4: block 0 fuses, 1 and
    # 2 cross a phase, 3 holds SAFE fires, 4 and 5 are quiet and fuse, the
    # last 4 rounds are the tail.
    assert fused["fused_rounds"] == 3 * k * G
    assert fused["total_rounds"] == 52 * G


def guard_equations(jaxpr, under=False):
    """(primitive, operand shapes, result shapes) of every equation under
    the scope `runner.block_guard`, sub-jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        here = under or "runner.block_guard" in str(eqn.source_info.name_stack)
        if here:
            out.append((
                eqn.primitive.name,
                tuple(getattr(v.aval, "shape", ()) for v in eqn.invars),
                tuple(v.aval.shape for v in eqn.outvars),
            ))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += guard_equations(sub, here)
    return out


def test_the_guard_does_not_grow_with_the_schedule():
    """No chip needed: a block's guard is the same equations whether the
    schedule has 64 rounds or 640, and no operand of any of them has a
    dimension of n_rounds or n_phases — the block reads its own rows, not
    the schedule's planes (on the v5e one row of an [n_phases, G] plane
    cost the whole plane: `block_guard_share` 31.7 -> PERF.md §6, PR 31)."""
    G, k, phase_len = 72, 8, 4
    cfg = SimConfig(
        n_groups=G, n_peers=P, election_tick=20, heartbeat_tick=2,
        collect_health=True, check_quorum=True, pre_vote=True,
        lease_read=True,
    )
    st = sim.init_state(cfg)
    zeros = lambda n: jnp.zeros((n,), jnp.int32)  # noqa: E731
    guards = {}
    for R in (64, 640):
        client = schedule(G, [phase_len] * (R // phase_len), {})
        run = runner_mod.make_runner(cfg, (client,), split=True, k=k)
        args = (
            st, sim.init_health(cfg), reconfig.init_reconfig_state(st),
            zeros(chaos.N_CHAOS_STATS), zeros(reconfig.N_RECONFIG_STATS),
            zeros(kernels.N_SAFETY), workload.init_read_carry(G),
            zeros(workload.N_READ_STATS), zeros(workload.N_LAT_BUCKETS),
            jnp.int32(0), *run.block_args[0], *run.schedule_args,
        )
        guards[R] = guard_equations(run.fused_jit.trace(*args).jaxpr.jaxpr)
        assert len(guards[R]) > 50, "the guard is there"
        banned = {R, R // phase_len}
        for prim, operands, _ in guards[R]:
            for shape in operands:
                assert not banned & set(shape), (R, prim, shape)
    assert guards[64] == guards[640]
