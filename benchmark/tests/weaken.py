"""Ways to break the system under test from outside, for the control and
the broken-path tests (and `control_on_chip.py`).  Each is a context manager
that patches the PROGRAM (never the benchmark) and restores it.

`lease_without_quorum_gate` is the control: the nearest weaker guarantee
that would tempt a later PR — serve a lease read without looking at the
current recent-active quorum (it saves reading a [P, P, G] plane every
round).  raft-rs's LeaseBased reads are only safe under check-quorum; with
the gate gone a deposed leader that has not yet heard of its successor still
holds a "lease" and reads stop being linearizable.

`joint_commit_on_incoming_only` is the control of a membership that changes:
the nearest weaker guarantee there — commit on the majority of the incoming
voters alone while a configuration is joint (it saves the second quorum
position every round).  raft-rs commits at the MIN of both halves
(quorum/joint.rs); with the outgoing half gone, a write is acknowledged that
a majority of the old voters never held.
"""

import contextlib
import inspect


@contextlib.contextmanager
def lease_without_quorum_gate():
    import jax.numpy as jnp

    from raft_tpu.multiraft import kernels

    real = kernels.lease_read

    sig = inspect.signature(real)

    def weak(*args, **kw):
        bound = sig.bind(*args, **kw)
        if bound.arguments.get("recent_active") is not None:
            bound.arguments["recent_active"] = jnp.ones_like(
                bound.arguments["recent_active"]
            )
        return real(*bound.args, **bound.kwargs)

    kernels.lease_read = weak
    try:
        yield
    finally:
        kernels.lease_read = real


@contextlib.contextmanager
def joint_commit_on_incoming_only():
    """Every round's commit looks at the incoming voters alone: the
    outgoing half of `min(_quorum_index(row, st.voter_mask),
    _quorum_index(row, st.outgoing_mask))` reads "no voters" (INF, as an
    empty configuration does).  The outgoing plane is known by identity: it
    is the `outgoing_mask` of the state `sim.step` was called with.  Yields
    a list that holds the number of quorum positions replaced so far — 0
    after a run means the program no longer computes them this way and the
    control controls nothing."""
    import jax.numpy as jnp

    from raft_tpu.multiraft import kernels
    from raft_tpu.multiraft import sim

    real_step, real_quorum = sim.step, sim._quorum_index
    outgoing = []  # the outgoing planes of the steps being traced, innermost last
    replaced = [0]

    def step(cfg, st, *args, **kw):
        outgoing.append(st.outgoing_mask)
        try:
            return real_step(cfg, st, *args, **kw)
        finally:
            outgoing.pop()

    def quorum(matched, voter_mask):
        if outgoing and voter_mask is outgoing[-1]:
            replaced[0] += 1
            return jnp.full(matched.shape[1:], kernels.INF, jnp.int32)
        return real_quorum(matched, voter_mask)

    sim.step, sim._quorum_index = step, quorum
    try:
        yield replaced
    finally:
        sim.step, sim._quorum_index = real_step, real_quorum


@contextlib.contextmanager
def commit_without_majority():
    """Commit at the highest index ANY voter holds (a quorum of one)."""
    import jax.numpy as jnp

    from raft_tpu.multiraft import kernels

    real = kernels.committed_index

    def weak(matched, voter_mask):
        count = jnp.sum(voter_mask, axis=-1)
        top = jnp.max(jnp.where(voter_mask, matched, 0), axis=-1)
        return jnp.where(count == 0, kernels.INF, top)

    kernels.committed_index = weak
    try:
        yield
    finally:
        kernels.committed_index = real


@contextlib.contextmanager
def segment_returns_state_unchanged():
    """The timed path broken underneath: the entry call reports a segment
    but leaves the fleet where it was."""
    from raft_tpu.multiraft import ClusterSim

    real = ClusterSim.run_reads

    def stuck(self, *args, **kw):
        state, health = self.state, self._health
        import jax

        keep = jax.tree.map(lambda x: x.copy(), (state, health))
        report = real(self, *args, **kw)
        self.state, self._health = keep
        return report

    ClusterSim.run_reads = stuck
    try:
        yield
    finally:
        ClusterSim.run_reads = real


@contextlib.contextmanager
def reads_answered_early():
    """An answer altered where it is produced: the entry call claims every
    read fire was served in its own round."""
    from raft_tpu.multiraft import ClusterSim

    real = ClusterSim.run_reads

    def eager(self, *args, **kw):
        report = real(self, *args, **kw)
        report["served_lease"] += report["dropped_fires"]
        report["dropped_fires"] = 0
        return report

    ClusterSim.run_reads = eager
    try:
        yield
    finally:
        ClusterSim.run_reads = real
