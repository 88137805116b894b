"""Ways to break the system under test from outside, for the control and
the broken-path tests (and `control_on_chip.py`).  Each is a context manager
that patches the PROGRAM (never the benchmark) and restores it.

`lease_without_quorum_gate` is the control: the nearest weaker guarantee
that would tempt a later PR — serve a lease read without looking at the
current recent-active quorum (it saves reading a [P, P, G] plane every
round).  raft-rs's LeaseBased reads are only safe under check-quorum; with
the gate gone a deposed leader that has not yet heard of its successor still
holds a "lease" and reads stop being linearizable.
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
