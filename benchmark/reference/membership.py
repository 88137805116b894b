"""A membership-change schedule walked through the reference's own
`raftport/confchange` Changer: which configuration each op of a chain leaves
a group in, and the progress-map delta that goes with it.

A schedule is the `phases` list of the program's plan grammar (the mix's
`reconfig` key): a phase has `rounds`, and optionally one `op`
(`add_voter` / `remove_voter` / `add_learner` / `promote_learner`: peer;
`enter_joint`: [{"add" | "remove" | "learner": peer}, ...];
`leave_joint`: true), the `groups` it is for (`"all"`, `{"mod", "eq"}` or a
list of ids) and an `append` load for every group.  Copied in substance from
`raft_tpu/multiraft/reconfig.py` (`_op_ccs`, `_bootstrap_tracker`,
`_walk_chain`) and `chaos._group_mask`; it imports nothing of `raft_tpu`.

Two callers: `traffic.generate`, which refuses a schedule whose chains do
not end where the configuration starts (a mix is one segment replayed), and
`cluster.py`, whose conf-change replay installs each `Step` on its scalar
machines.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Sequence, Tuple

import numpy as np

from .raftport.confchange.changer import Changer
from .raftport.eraftpb import ConfChangeSingle, ConfChangeType
from .raftport.errors import ConfChangeError
from .raftport.tracker import ProgressTracker

_SIMPLE = {
    "add_voter": ConfChangeType.AddNode,
    "promote_learner": ConfChangeType.AddNode,
    "add_learner": ConfChangeType.AddLearnerNode,
    "remove_voter": ConfChangeType.RemoveNode,
}
_JOINT = {
    "add": ConfChangeType.AddNode,
    "remove": ConfChangeType.RemoveNode,
    "learner": ConfChangeType.AddLearnerNode,
}


class Step(NamedTuple):
    """One validated transition of one chain: the configuration the Changer
    computed (plain sets of 1-based peer ids), its progress-map delta, and
    the phase that enqueues it."""

    voters: frozenset  # incoming voters
    outgoing: frozenset
    learners: frozenset
    learners_next: frozenset
    changes: Tuple[Tuple[int, int], ...]  # (peer id, MapChangeType value)
    phase: int


def of_config(config: dict) -> Tuple[List[int], List[int]]:
    """(voters, learners) of a configuration file: 1-based peer slots of its
    `n_peers`; a file without the keys makes every slot a voter."""
    P = int(config["n_peers"])
    voters = sorted(int(p) for p in config.get("voters", range(1, P + 1)))
    learners = sorted(int(p) for p in config.get("learners", []))
    if (not voters or set(voters) & set(learners)
            or not set(voters) | set(learners) <= set(range(1, P + 1))):
        raise ValueError(f"voters {voters} / learners {learners} are not disjoint "
                         f"slots of 1..{P} with at least one voter")
    return voters, learners


def masks(members: Sequence[int], n_peers: int, n_groups: int) -> np.ndarray:
    """bool[P, G]: the same members in every group."""
    col = np.zeros((n_peers, 1), bool)
    col[[p - 1 for p in members]] = True
    return np.repeat(col, n_groups, axis=1)


def group_mask(sel, n_groups: int) -> np.ndarray:
    """bool[G] of a `groups` selector."""
    if isinstance(sel, str):
        if sel != "all":
            raise ValueError(f"unknown group selector {sel!r}")
        return np.ones(n_groups, bool)
    if isinstance(sel, dict):
        return (np.arange(n_groups) % int(sel["mod"])) == int(sel["eq"])
    mask = np.zeros(n_groups, bool)
    for g in sel:
        if not 0 <= int(g) < n_groups:
            raise ValueError(f"group id {g} out of range [0, {n_groups})")
        mask[int(g)] = True
    return mask


def op_phases(phases: Sequence[dict]) -> List[int]:
    return [i for i, ph in enumerate(phases) if ph.get("op") is not None]


def phase_starts(phases: Sequence[dict]) -> List[int]:
    out, r = [], 0
    for ph in phases:
        out.append(r)
        r += int(ph["rounds"])
    return out


def _changes(op: dict, n_peers: int, phase: int):
    """(kind, [ConfChangeSingle]) of one op document."""
    if len(op) != 1:
        raise ValueError(f"phase {phase}: an op has exactly one kind, got {op!r}")
    (kind, arg), = op.items()

    def peer(pid) -> int:
        if not 1 <= int(pid) <= n_peers:
            raise ValueError(f"phase {phase}: {kind} peer {pid} is not in [1, {n_peers}]")
        return int(pid)

    if kind == "leave_joint":
        if arg is not True:
            raise ValueError(f"phase {phase}: leave_joint must be true")
        return kind, []
    if kind == "enter_joint":
        ccs = []
        for ch in arg:
            (what, pid), = ch.items()
            if what not in _JOINT:
                raise ValueError(f"phase {phase}: unknown enter_joint change {what!r}")
            ccs.append(ConfChangeSingle(_JOINT[what], peer(pid)))
        if not ccs:
            raise ValueError(f"phase {phase}: enter_joint with no changes")
        return kind, ccs
    if kind not in _SIMPLE:
        raise ValueError(f"phase {phase}: unknown op {kind!r}")
    return kind, [ConfChangeSingle(_SIMPLE[kind], peer(arg))]


def bootstrap(voters: Sequence[int], learners: Sequence[int]) -> ProgressTracker:
    t = ProgressTracker(1 << 20)
    for kind, ids in ((ConfChangeType.AddNode, voters),
                      (ConfChangeType.AddLearnerNode, learners)):
        for p in ids:
            cfg, changes = Changer(t).simple([ConfChangeSingle(kind, int(p))])
            t.apply_conf(cfg, changes, 1)
    return t


def walk(phases: Sequence[dict], chain: Sequence[int], n_peers: int,
         voters: Sequence[int], learners: Sequence[int]) -> List[Step]:
    """The ops of the phases `chain` (indices), in order, from the
    bootstrap configuration; a transition the Changer refuses is a
    ValueError that names its phase."""
    t = bootstrap(voters, learners)
    steps: List[Step] = []
    for i in chain:
        kind, ccs = _changes(phases[i]["op"], n_peers, i)
        ch = Changer(t)
        try:
            if kind == "enter_joint":
                cfg, changes = ch.enter_joint(False, ccs)
            elif kind == "leave_joint":
                cfg, changes = ch.leave_joint()
            else:
                cfg, changes = ch.simple(ccs)
        except ConfChangeError as e:
            raise ValueError(f"phase {i}: {phases[i]['op']!r} is refused: {e}") from e
        t.apply_conf(cfg, changes, 1)
        steps.append(Step(
            voters=frozenset(cfg.voters.incoming.ids()),
            outgoing=frozenset(cfg.voters.outgoing.ids()),
            learners=frozenset(cfg.learners),
            learners_next=frozenset(cfg.learners_next),
            changes=tuple((int(p), int(ct)) for p, ct in changes),
            phase=i,
        ))
    return steps


def classes(phases: Sequence[dict], n_groups: int) -> Dict[Tuple[int, ...], np.ndarray]:
    """{chain (the op phases a group follows): bool[G] of the groups that
    follow it}, the empty chain left out."""
    ops = op_phases(phases)
    if not ops:
        return {}
    sel = np.stack([group_mask(phases[i].get("groups", "all"), n_groups) for i in ops])
    rows, inverse = np.unique(sel.T, axis=0, return_inverse=True)
    inverse = inverse.reshape(-1)
    out = {}
    for k, row in enumerate(rows):
        chain = tuple(i for i, on in zip(ops, row) if on)
        if chain:
            out[chain] = inverse == k
    return out
