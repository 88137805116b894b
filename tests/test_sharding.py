"""Multi-chip sharding tests on the virtual 8-device CPU mesh: the sharded
step must (a) compile+run over the mesh and (b) produce bit-identical state
to the single-device sim (shard-invariance of the batch)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raft_tpu.multiraft import ClusterSim, SimConfig
from raft_tpu.multiraft import sharding
from raft_tpu.multiraft.sim import init_state
from raft_tpu.multiraft import sim
from jax.sharding import NamedSharding, PartitionSpec as P


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8


@pytest.mark.slow  # ~10s: 30 per-round sharded dispatches; its tier-1
# role moved to tests/test_sharded_parity.py's scan-parity case (ISSUE 14
# — the scan path IS the production mesh path now), and the per-round
# sharded_step graph stays covered by the GC011/GC015 trace audits plus
# this file's spec cases.
def test_sharded_step_matches_single_device():
    cfg = SimConfig(n_groups=32, n_peers=3)
    mesh = sharding.make_mesh()
    step_fn = sharding.sharded_step(cfg, mesh, donate=False)

    st_sharded = sharding.shard_state(init_state(cfg), mesh)
    sim = ClusterSim(cfg)

    crashed = jnp.zeros((cfg.n_peers, cfg.n_groups), bool)
    append = jnp.ones((cfg.n_groups,), jnp.int32)
    for r in range(30):
        st_sharded = step_fn(st_sharded, crashed, append)
        sim.run_round(crashed, append)

    for name in SimState_fields():
        a = np.asarray(getattr(st_sharded, name))
        b = np.asarray(getattr(sim.state, name))
        np.testing.assert_array_equal(a, b, err_msg=f"field {name}")


def SimState_fields():
    from raft_tpu.multiraft.sim import SimState
    return SimState._fields


def test_global_status_collectives():
    cfg = SimConfig(n_groups=16, n_peers=3)
    mesh = sharding.make_mesh()
    cs = ClusterSim(cfg, mesh=mesh)
    cs.run_compiled(30, append_n=jnp.ones((cfg.n_groups,), jnp.int32))
    status = jax.tree.map(int, sharding.global_status(cfg, mesh)(cs.state))
    # After 30 quiet rounds every group has elected a leader and committed
    # its noop + 1 append per round.
    assert status["n_leaders"] == cfg.n_groups
    assert status["min_commit"] >= 1
    assert status["max_term"] >= 1
    assert status["total_commit"] >= cfg.n_groups


@pytest.mark.slow  # ~74s of P=5 step + sharded-barrier compiles; the unsharded
# read_index semantics stay tier-1 in test_read_index_batch.py and the
# sharding mechanics in this file's shard-invariance cases.
def test_sharded_read_index_matches_local():
    cfg = SimConfig(n_groups=32, n_peers=5)
    mesh = sharding.make_mesh()
    st = sim.init_state(cfg)
    crashed = jnp.zeros((cfg.n_peers, cfg.n_groups), bool)
    append = jnp.ones((cfg.n_groups,), jnp.int32)
    for _ in range(25):
        st = sim.step(cfg, st, crashed, append)
    want = np.asarray(sim.read_index(cfg, st, crashed))
    assert (want >= 0).all()  # settled: every group serves reads
    st_sh = sharding.shard_state(st, mesh)
    fn = sharding.sharded_read_index(cfg, mesh)
    got = np.asarray(fn(st_sh, jax.device_put(
        crashed, NamedSharding(mesh, P(None, "groups")))))
    np.testing.assert_array_equal(want, got)


def test_state_sharding_flag_combinations_two_device_mesh():
    """state_sharding(damped=, transfer=) on a 2-device mesh (ISSUE 14):
    every flag combination yields specs whose optional planes appear
    exactly when flagged, with the group axis sharded and the peer axes
    local — and sharded_init_state under those specs reproduces
    init_state bit-exactly with the pairwise planes placed [P, P, G/n]
    per device."""
    mesh2 = sharding.make_mesh(2)
    for damped in (False, True):
        for transfer in (False, True):
            specs = sharding.state_sharding(
                mesh2, damped=damped, transfer=transfer
            )
            assert specs.term.spec == P(None, "groups")
            assert specs.matched.spec == P(None, None, "groups")
            if damped:
                assert specs.recent_active.spec == P(None, None, "groups")
            else:
                assert specs.recent_active is None
            if transfer:
                assert specs.transferee.spec == P(None, "groups")
            else:
                assert specs.transferee is None
            cfg = SimConfig(
                n_groups=16, n_peers=3,
                check_quorum=damped, pre_vote=damped, transfer=transfer,
            )
            st_sh = sharding.sharded_init_state(cfg, mesh2)
            st = init_state(cfg)
            for name in SimState_fields():
                a, b = getattr(st_sh, name), getattr(st, name)
                if b is None:
                    assert a is None, name
                    continue
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b), err_msg=name
                )
            # The pairwise plane really is split on G across the 2
            # devices: each shard holds [P, P, G/2].
            shard_shapes = {
                s.data.shape for s in st_sh.matched.addressable_shards
            }
            assert shard_shapes == {(3, 3, 8)}


def test_shard_client_packed_word_fallback_two_device_mesh():
    """shard_client's packed-word replication fallback (ISSUE 14 edge
    case): on a 2-device mesh a fire plane whose word count does NOT
    tile the mesh (ceil(G/32) odd) replicates, while an even word count
    shards on the word axis — contents bit-identical either way."""
    from raft_tpu.multiraft import workload

    mesh2 = sharding.make_mesh(2)
    plan = workload.ClientPlan(
        name="edge",
        n_peers=3,
        phases=[workload.ClientPhase(rounds=4, read_every=2,
                                     read_mode="safe")],
    )
    # G=96 -> 3 packed words: 3 % 2 != 0 -> replicate.
    odd = workload.compile_plan(plan, 96)
    placed_odd, _ = sharding.shard_client(
        odd, workload.init_read_carry(96), mesh2
    )
    assert placed_odd.read_fire_packed.sharding.spec == P()
    np.testing.assert_array_equal(
        np.asarray(placed_odd.read_fire_packed),
        np.asarray(odd.read_fire_packed),
    )
    # G=128 -> 4 packed words: tiles the mesh -> sharded on the word axis.
    even = workload.compile_plan(plan, 128)
    placed_even, rcar = sharding.shard_client(
        even, workload.init_read_carry(128), mesh2
    )
    assert placed_even.read_fire_packed.sharding.spec == P(None, "groups")
    assert rcar.pending_mode.sharding.spec == P("groups")
    np.testing.assert_array_equal(
        np.asarray(placed_even.read_fire_packed),
        np.asarray(even.read_fire_packed),
    )


def test_client_schedule_and_carry_shard_on_groups():
    """The workload schedule + read carry shard on G (ISSUE 13): specs
    place every [.., G] plane (incl. the PACKED fire words — the word
    axis IS the group axis / 32) on the groups mesh axis, round-indexed
    and accumulator arrays replicated, and a placed schedule feeds the
    workload scan unchanged."""
    from raft_tpu.multiraft import workload

    G = 256  # 8 packed words: the fire plane tiles the 8-device mesh
    plan = workload.ClientPlan(
        name="shard",
        n_peers=3,
        phases=[
            workload.ClientPhase(rounds=8, append=1),
            workload.ClientPhase(rounds=8, read_every=2,
                                 read_mode="lease"),
        ],
    )
    compiled = workload.compile_plan(plan, G)
    rcar = workload.init_read_carry(G)
    mesh = sharding.make_mesh()
    placed_sched, placed_rcar = sharding.shard_client(
        compiled, rcar, mesh
    )
    assert placed_sched.read_fire_packed.sharding.spec == P(None, "groups")
    assert placed_sched.read_mode.sharding.spec == P(None, "groups")
    assert placed_sched.append.sharding.spec == P(None, "groups")
    assert placed_sched.phase_of_round.sharding.spec == P()
    assert placed_rcar.pending_mode.sharding.spec == P("groups",)
    # Bit-identical contents after placement.
    np.testing.assert_array_equal(
        np.asarray(placed_sched.read_fire_packed),
        np.asarray(compiled.read_fire_packed),
    )
    # A width that does NOT tile the mesh replicates the fire words
    # instead of failing (read-only schedule data).
    small = workload.compile_plan(plan, 32)  # 1 packed word
    placed_small, _ = sharding.shard_client(
        small, workload.init_read_carry(32), mesh
    )
    assert placed_small.read_fire_packed.sharding.spec == P()
