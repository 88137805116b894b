"""Batched ReadIndex barrier parity: sim.read_index (device) and
mr_read_index (C++) must agree with the scalar oracle's actual Safe-mode
read path — MsgReadIndex at the acting leader, heartbeat broadcast with
ctx, ack quorum — on arbitrary crash states reached by storm schedules.

The scalar probe perturbs its cluster (the pump delivers real heartbeats),
so each schedule probes once, at the end (reference: read_only.rs:65-140,
raft.rs:2067-2096)."""

import numpy as np
import jax.numpy as jnp
import pytest

from raft_tpu.eraftpb import Entry, Message, MessageType
from raft_tpu.multiraft import ClusterSim, ScalarCluster, SimConfig
from raft_tpu.multiraft import sim
from raft_tpu.multiraft.native import NativeMultiRaft


def scalar_read_probe(cluster, g, crashed_row):
    """Issue a real Safe-mode read at group g's acting leader and pump.
    Returns the read index, or -1 when the read does not complete."""
    net = cluster.networks[g]
    cluster._apply_crash_mask(net, crashed_row)
    lead = cluster.acting_leader(g, crashed_row)
    if lead is None:
        return -1
    iface = net.peers[lead]
    before = len(iface.raft.read_states)
    net.send([
        Message(
            msg_type=MessageType.MsgReadIndex,
            from_=lead,
            to=lead,
            entries=[Entry(data=b"probe")],
        )
    ])
    rs = iface.raft.read_states
    if len(rs) > before:
        return rs[-1].index
    return -1


def build_trio(G, P, voters=None, outgoing=None, learners=None):
    kwargs = {}
    vm = om = lm = None
    native = NativeMultiRaft(G, P)
    if voters is not None:
        kwargs = dict(
            voters=voters,
            voters_outgoing=outgoing or [],
            learners=learners or [],
        )
        vm_np = np.zeros((P, G), bool)
        om_np = np.zeros((P, G), bool)
        lm_np = np.zeros((P, G), bool)
        for id in voters:
            vm_np[id - 1] = True
        for id in outgoing or []:
            om_np[id - 1] = True
        for id in learners or []:
            lm_np[id - 1] = True
        vm, om, lm = map(jnp.asarray, (vm_np, om_np, lm_np))
        native.set_config(
            np.ascontiguousarray(vm_np.T).astype(np.uint8),
            np.ascontiguousarray(om_np.T).astype(np.uint8),
            np.ascontiguousarray(lm_np.T).astype(np.uint8),
        )
    scalar = ScalarCluster(G, P, **kwargs)
    device = ClusterSim(SimConfig(n_groups=G, n_peers=P), vm, om, lm)
    return scalar, device, native


def run_probe_schedule(seed, G, P, rounds, **cfg):
    scalar, device, native = build_trio(G, P, **cfg)
    rng = np.random.RandomState(seed)
    crashed = np.zeros((G, P), bool)
    for r in range(rounds):
        for g in range(G):
            roll = rng.rand()
            if roll < 0.10:
                crashed[g, rng.randint(P)] ^= True
            elif roll < 0.14:
                snap = scalar.snapshot()
                leaders = np.where(snap["state"][g] == 2)[0]
                if len(leaders):
                    crashed[g, leaders[0]] = True
            elif roll < 0.16:
                crashed[g, :] = False
            if crashed[g].sum() == P:
                crashed[g, rng.randint(P)] = False
        append = rng.randint(0, 3, size=G).astype(np.int64)
        scalar.round(crashed, append)
        device.run_round(
            jnp.asarray(crashed.T), jnp.asarray(append, dtype=jnp.int32)
        )
        native.step(crashed, append)

    got_dev = np.asarray(
        sim.read_index(device.cfg, device.state, jnp.asarray(crashed.T))
    )
    got_nat = native.read_index(crashed)
    for g in range(G):
        want = scalar_read_probe(scalar, g, crashed[g])
        assert got_dev[g] == want, (
            f"seed {seed} group {g}: device {got_dev[g]} != scalar {want}"
        )
        assert got_nat[g] == want, (
            f"seed {seed} group {g}: native {got_nat[g]} != scalar {want}"
        )


def test_read_index_steady_state():
    """All alive, settled: read == leader commit everywhere, all backends."""
    scalar, device, native = build_trio(4, 3)
    crashed = np.zeros((4, 3), bool)
    append = np.ones((4,), np.int64)
    for _ in range(25):
        scalar.round(crashed, append)
        device.run_round(None, jnp.asarray(append, dtype=jnp.int32))
        native.step(crashed, append)
    got = np.asarray(
        sim.read_index(device.cfg, device.state, jnp.zeros((3, 4), bool))
    )
    nat = native.read_index(crashed)
    snap = scalar.snapshot()
    for g in range(4):
        want = scalar_read_probe(scalar, g, crashed[g])
        assert want >= 0
        lead = int(snap["state"][g].argmax())
        assert want == snap["commit"][g, lead]
        assert got[g] == want
        assert nat[g] == want


def test_read_index_quorum_dead():
    """A leader without an alive voter quorum cannot serve reads: -1."""
    scalar, device, native = build_trio(2, 5)
    crashed = np.zeros((2, 5), bool)
    append = np.ones((2,), np.int64)
    for _ in range(25):
        scalar.round(crashed, append)
        device.run_round(None, jnp.asarray(append, dtype=jnp.int32))
        native.step(crashed, append)
    # crash 3 non-leader peers in each group -> quorum of 5 unreachable
    snap = scalar.snapshot()
    for g in range(2):
        lead = int(snap["state"][g].argmax())
        others = [p for p in range(5) if p != lead]
        for p in others[:3]:
            crashed[g, p] = True
    got = np.asarray(
        sim.read_index(device.cfg, device.state, jnp.asarray(crashed.T))
    )
    nat = native.read_index(crashed)
    for g in range(2):
        want = scalar_read_probe(scalar, g, crashed[g])
        assert want == -1
        assert got[g] == -1
        assert nat[g] == -1


def test_read_index_no_leader():
    """Fresh cluster (nobody elected): -1 everywhere."""
    scalar, device, native = build_trio(2, 3)
    crashed = np.zeros((2, 3), bool)
    got = np.asarray(
        sim.read_index(device.cfg, device.state, jnp.zeros((3, 2), bool))
    )
    nat = native.read_index(crashed)
    for g in range(2):
        assert scalar_read_probe(scalar, g, crashed[g]) == -1
        assert got[g] == -1
        assert nat[g] == -1


@pytest.mark.slow  # ~18s of 3-seed lockstep storm; the mixed/joint/
# learners/even-P storm variants keep the probe-schedule shape in tier-1.
def test_read_index_storm_plain():
    for seed in (11, 23, 37):
        run_probe_schedule(seed, 3, 5, 60)


def test_read_index_storm_even_p():
    for seed in (41, 53):
        run_probe_schedule(seed, 3, 4, 60)


def test_read_index_storm_joint():
    for seed in (61, 71):
        run_probe_schedule(seed, 3, 5, 60, voters=[1, 2, 3], outgoing=[3, 4, 5])


def test_read_index_storm_learners():
    for seed in (83, 97):
        run_probe_schedule(seed, 3, 5, 60, voters=[1, 2, 3, 4], learners=[5])


@pytest.mark.slow  # ~12s: ISSUE 13 paid its tier-1 additions with this
# one (tools/tier1_budget.py top-N) — the mixed joint/learner Safe-read
# shape is now ALSO covered tier-1 by the in-step read path's replay
# parity (tests/test_workload.py) and in the slow tier by
# tests/test_read_lease.py's config fuzz matrix.
def test_read_index_storm_mixed():
    for seed in (103, 211):
        run_probe_schedule(
            seed, 2, 6, 60,
            voters=[1, 2, 3, 4], outgoing=[3, 4, 5], learners=[6],
        )


def test_read_index_higher_term_member_ignores():
    """Members at a higher term silently ignore the lower-term ctx
    heartbeat (check_quorum/pre_vote off): they neither ack nor depose, so
    the rest of the quorum still completes the read.  Seeds 4030/8008
    historically returned -1 from the batched barrier here."""
    run_probe_schedule(4030, 3, 4, 200)
    run_probe_schedule(8008, 2, 5, 160, voters=[1, 2, 3, 4, 5])


def test_read_index_joint_self_quorum_hangs():
    """A joint config whose quorum is the leader alone (incoming ==
    outgoing == {leader}) is NOT a singleton (outgoing non-empty), so Safe
    reads go through the ctx-heartbeat path — but the ack quorum is only
    evaluated on RECEIVING a response, and there are no other members to
    respond: the read hangs until leave-joint.  Seed 838435 historically
    returned the commit index from the batched barrier here."""
    run_probe_schedule(838435, 2, 2, 140, voters=[2], outgoing=[2])
