"""The plain reference: real scalar Raft state machines, one network per
sampled group, stepped in lockstep rounds with delivery inside the round.

Copied in substance from `raft_tpu/multiraft/simref.py` (ScalarCluster,
ReadOracle.lease_gate / read_probe, the Inflights-twin clone) and
`tests/test_workload.py::host_replay` (the one-read-in-flight retry/drop
protocol) and `simref.ReconfigOracle` (the conf-change replay), cut to what
a benchmark run compares: no health planes, no transfers.  It imports
nothing of `raft_tpu`.

A round is: answer the round's read on the round-entry state (on a
throwaway copy of the group, so the probe's traffic never perturbs the
group), tick every peer in peer order, pump to quiescence, propose the
round's appends at the acting leader, pump.

A membership-change schedule (`membership.py` walks it through the port's
Changer) is replayed by the program's own rules: an op whose phase has come
and whose predecessors have all been applied is PROPOSED as one more entry,
appended last in the round's batch at the acting leader (no alive leader:
it tries again next round); it is APPLIED at the round boundary once its
owner still leads at the term it proposed in, is not crashed, and has
committed the entry — commit under a joint configuration already needs both
majorities (`raftport/quorum/joint.py`); it is RE-PROPOSED at the next acting
leader when the owner was deposed or crashed.  Applying installs the
Changer's configuration and progress-map delta on every peer, steps down a
peer that leaves the voters, and lets a leader pick up what the smaller
quorum commits.  The one departure from raft-rs: every peer of the group
applies in the same round — delivery is inside the round, so every peer
holds the committed entry when its owner does — where raft-rs applies on
each peer as its own apply loop reaches the entry.
"""

from __future__ import annotations

import copy
import threading
from typing import List, Optional, Sequence

import numpy as np

from .membership import Step
from .raftport.confchange.changer import MapChangeType
from .raftport.config import Config
from .raftport.eraftpb import ConfState, Entry, Message, MessageType
from .raftport.harness import Interface, Network
from .raftport.raft import Raft, StateRole
from .raftport.raft_log import NO_LIMIT
from .raftport.read_only_option import ReadOnlyOption
from .raftport.storage import MemStorage
from .raftport.tracker import Configuration

READ_NONE, READ_SAFE, READ_LEASE = 0, 1, 2  # the schedule's mode codes
_TWIN_CAP = 1 << 14  # Inflights window of a throwaway probe copy

FIELDS = ("term", "state", "commit", "last_index", "voter", "outgoing", "learner")


class Group:
    """One Raft group of the fleet, by its GLOBAL id (the timeout streams
    are keyed by it, so group g here draws what group g draws on the
    device)."""

    def __init__(self, gid: int, n_peers: int, election_tick: int,
                 heartbeat_tick: int, check_quorum: bool, pre_vote: bool,
                 lease_read: bool, voters: Optional[Sequence[int]] = None,
                 learners: Sequence[int] = ()):
        """`voters` / `learners`: the peer slots (1-based) every peer boots
        with; None: every slot a voter."""
        self.gid = gid
        self.n_peers = n_peers
        self.election_tick = election_tick
        self.lease_read = lease_read
        config = Config(
            election_tick=election_tick,
            heartbeat_tick=heartbeat_tick,
            max_size_per_msg=NO_LIMIT,
            max_inflight_msgs=1 << 20,
            timeout_seed=gid,
            check_quorum=check_quorum,
            pre_vote=pre_vote,
        )
        if voters is None or (len(voters) == n_peers and not learners):
            peers: List[Optional[Interface]] = [None] * n_peers
        else:
            peers = []
            for pid in range(1, n_peers + 1):
                store = MemStorage.new_with_conf_state(
                    ConfState(voters=list(voters), learners=list(learners)))
                peers.append(Interface(Raft(Config(**{**config.__dict__, "id": pid}), store)))
        self.net = Network.new_with_config(peers, config)
        self._probe_seq = 0
        # The conf-change protocol's carry (the program's ReconfigState).
        self.chain: List[Step] = []
        self.chain_start: List[int] = []  # round at which each op becomes eligible
        self.op_ptr = 0
        self.in_flight: Optional[tuple] = None  # (owner, index, term) of the entry
        self.conf_applied = self.conf_retried = 0

    def _install_faults(self, net: Network, faults) -> None:
        """faults = (crashed bool[P], link bool[P, P]): a crashed peer is
        isolated; a down directed link drops everything sent on it."""
        crashed, link = faults
        net.recover()
        for p, down in enumerate(crashed):
            if down:
                net.isolate(p + 1)
        for a in range(self.n_peers):
            for b in range(self.n_peers):
                if a != b and not link[a][b]:
                    net.drop(a + 1, b + 1, 1.0)

    def acting_leader(self, crashed: Sequence[bool]) -> Optional[int]:
        best, best_term = None, -1
        for p in range(1, self.n_peers + 1):
            if crashed[p - 1]:
                continue
            r = self.net.peers[p].raft
            if r.state == StateRole.Leader and r.term > best_term:
                best, best_term = p, r.term
        return best

    def lease_gate(self, crashed: Sequence[bool]):
        """(acting leader or None, whether it may serve a lease read): a
        check-quorum leader naming itself, inside its lease window,
        committed in its own term, no transfer pending, a quorum active
        now."""
        lead = self.acting_leader(crashed)
        if lead is None:
            return None, False
        r = self.net.peers[lead].raft
        active = {i for i, pr in r.prs.iter() if pr.recent_active}
        active.add(r.id)
        ok = (
            self.lease_read
            and r.check_quorum
            and r.state == StateRole.Leader
            and r.leader_id == r.id
            and r.election_elapsed < self.election_tick
            and not r.lead_transferee
            and r.commit_to_current_term()
            and r.prs.has_quorum(active)
        )
        return lead, ok

    def _clone_net(self) -> Network:
        """deepcopy of the group for one probe: store locks re-made, each
        Inflights ring replaced by a small twin holding its live window
        (the only part ever read)."""
        memo: dict = {}
        for iface in self.net.peers.values():
            r = iface.raft
            lock = getattr(getattr(r.raft_log, "store", None), "_lock", None)
            if lock is not None:
                memo[id(lock)] = threading.RLock()
            for _, pr in r.prs.iter():
                ins = pr.ins
                tcap = min(ins.cap, _TWIN_CAP)
                if ins.count > tcap // 4:
                    tcap = ins.cap
                twin = type(ins)(tcap)
                twin.count = ins.count
                for i in range(ins.count):
                    twin.buffer[i] = ins.buffer[(ins.start + i) % ins.cap]
                memo[id(ins)] = twin
        return copy.deepcopy(self.net, memo)

    def read_probe(self, faults, mode: int):
        """(served index or -1, served under the lease) for a read of
        `mode` on the round-entry state."""
        if mode == READ_NONE:
            return -1, False
        crashed = faults[0]
        lead, gate = self.lease_gate(crashed)
        lease = mode == READ_LEASE and gate
        if lead is None:
            return -1, False
        net = self._clone_net()
        self._install_faults(net, faults)
        iface = net.peers[lead]
        iface.raft.read_only.option = (
            ReadOnlyOption.LeaseBased if lease else ReadOnlyOption.Safe
        )
        self._probe_seq += 1
        ctx = b"read-%d" % self._probe_seq
        before = len(iface.raft.read_states)
        net.send([
            Message(
                msg_type=MessageType.MsgReadIndex, from_=lead, to=lead,
                entries=[Entry(data=ctx)],
            )
        ])
        rs = iface.raft.read_states
        if len(rs) > before and bytes(rs[-1].request_ctx) == ctx:
            return rs[-1].index, lease
        return -1, lease

    def restart_chain(self, chain: Sequence[Step], starts: Sequence[int]) -> None:
        """A new segment: the program re-creates its carry every call."""
        self.chain, self.chain_start = list(chain), list(starts)
        self.op_ptr, self.in_flight = 0, None

    def conf_wants(self, r: int) -> bool:
        """Does the next unapplied op propose its entry in round `r`?"""
        return (self.in_flight is None and self.op_ptr < len(self.chain)
                and r >= self.chain_start[self.op_ptr])

    def conf_gate(self, crashed: Sequence[bool]) -> None:
        """After the round: apply the op in flight, or give its entry up."""
        if self.in_flight is None:
            return
        owner, index, term = self.in_flight
        r = self.net.peers[owner].raft
        leads = r.state == StateRole.Leader and r.term == term and not crashed[owner - 1]
        if leads and r.raft_log.committed >= index:
            self._apply_conf(self.chain[self.op_ptr])
            self.op_ptr += 1
            self.in_flight = None
            self.conf_applied += 1
        elif not leads:
            self.in_flight = None  # the next acting leader proposes it again
            self.conf_retried += 1

    def _apply_conf(self, step: Step) -> None:
        """The Changer's configuration and progress-map delta, on every peer
        at once."""
        for p in range(1, self.n_peers + 1):
            r = self.net.peers[p].raft
            conf = Configuration(voters=step.voters, learners=step.learners)
            conf.voters.outgoing.voters.update(step.outgoing)
            conf.learners_next = set(step.learners_next)
            changes = [(i, MapChangeType(ct)) for i, ct in step.changes]
            # A fresh row starts at raft-rs's next index; on a leader, at the
            # first index of its own term (its first probe goes out from
            # there: nothing before was ever acked by the new member).
            if r.state == StateRole.Leader:
                next_idx = r.raft_log.last_index()
                if r.raft_log.term_or(next_idx) != r.term:
                    next_idx += 1
                else:
                    while next_idx > 1 and r.raft_log.term_or(next_idx - 1) == r.term:
                        next_idx -= 1
            else:
                next_idx = r.raft_log.last_index() + 1
            r.prs.apply_conf(conf, changes, next_idx)
            for i, ct in changes:
                if ct == MapChangeType.Add:
                    r.prs.get_mut(i).paused = True  # a probe, until a heartbeat is answered
            member = conf.voters.contains(r.id)
            r.promotable = member
            if r.state != StateRole.Follower and not member:
                r.state = StateRole.Follower  # steps down; its timers run on
                r.leader_id = 0
            elif r.state == StateRole.Leader:
                r.maybe_commit()  # what the new, maybe smaller, quorum commits

    def round(self, faults, append_n: int, conf_propose: bool = False) -> None:
        """`conf_propose`: one more entry, appended last, is the conf entry
        of the op in turn; where it landed is kept in `in_flight`."""
        net = self.net
        crashed = faults[0]
        self._install_faults(net, faults)
        initial: List[Message] = []
        for p in range(1, self.n_peers + 1):
            peer = net.peers[p]
            peer.raft.tick()
            peer.persist()
            initial.extend(net.filter(peer.read_messages()))
        net.send(initial)
        total = append_n + bool(conf_propose)
        if total > 0:
            lead = self.acting_leader(crashed)
            if lead is not None:
                if conf_propose:
                    # Taken before the pump: the leader appends the batch
                    # first; what follows may depose it, never unappend.
                    r = net.peers[lead].raft
                    self.in_flight = (lead, r.raft_log.last_index() + total, r.term)
                net.send([
                    Message(
                        msg_type=MessageType.MsgPropose, from_=lead, to=lead,
                        entries=[Entry(data=b"x") for _ in range(total)],
                    )
                ])

    def row(self) -> dict:
        """Per-peer cursors, in FIELDS' order of meaning."""
        out = {k: np.zeros(self.n_peers, np.int64) for k in FIELDS}
        for p in range(self.n_peers):
            r = self.net.peers[p + 1].raft
            out["term"][p] = r.term
            out["state"][p] = int(r.state)
            out["commit"][p] = r.raft_log.committed
            out["last_index"][p] = r.raft_log.last_index()
            # Membership as peer p's own tracker has it.
            conf = r.prs.conf
            out["voter"][p] = (p + 1) in conf.voters.incoming
            out["outgoing"][p] = (p + 1) in conf.voters.outgoing
            out["learner"][p] = (p + 1) in conf.learners
        return out


class Replay:
    """Sampled groups driven through boot and one traffic segment, with the
    client's one-read-in-flight protocol: a fire that finds a read pending
    is dropped, a pending read retries every round until served."""

    def __init__(self, gids: Sequence[int], **group_kwargs):
        self.groups = [Group(int(g), **group_kwargs) for g in gids]
        n = len(self.groups)
        self.pending = np.zeros(n, np.int32)
        self.served = 0
        self.dropped = 0

    def boot(self, rounds: int) -> None:
        P = self.groups[0].n_peers
        up = ([False] * P, [[True] * P] * P)
        for g in self.groups:
            for _ in range(rounds):
                g.round(up, 0)

    def segment(self, fire, mode, append, crashed, link, chains=None) -> None:
        """fire: bool[R, n]; mode, append: int[R, n] (already gathered by
        phase); crashed: bool[R, P]; link: bool[R, P, P]; chains: per group
        (steps, start rounds) of its membership-change ops, or None."""
        for g, (steps, starts) in zip(self.groups, chains or []):
            g.restart_chain(steps, starts)
        for r in range(fire.shape[0]):
            down = (list(crashed[r]), link[r].tolist())
            for i, g in enumerate(self.groups):
                f = bool(fire[r, i]) and mode[r, i] > 0
                if f and self.pending[i] == 0:
                    self.pending[i] = mode[r, i]
                elif f:
                    self.dropped += 1
                if self.pending[i]:
                    index, _lease = g.read_probe(down, int(self.pending[i]))
                    if index >= 0:
                        self.served += 1
                        self.pending[i] = 0
                g.round(down, int(append[r, i]), g.conf_wants(r))
                g.conf_gate(down[0])

    def rows(self) -> dict:
        rows = [g.row() for g in self.groups]
        return {k: np.stack([row[k] for row in rows]) for k in FIELDS}
