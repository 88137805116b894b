"""What decides `correct`, outside the timed window.  Every number compared
is printed beside its limit; every limit is 0 (exact comparisons: the
configuration states guarantees, not tolerances).

  safety        every safety slot the device audits, summed over every round
                of warm-up and window                               limit 0
  fires         read fires scheduled - (issued + dropped), per segment
                                                                    limit 0
  reads         issued - served - outstanding, last segment         limit 0
  monotonic     (peer, group) cells whose commit index fell between the
                window's start and end                              limit 0
  durability    groups whose highest commit index is NOT held by a majority
                of the voters and, where a group has outgoing voters (a joint
                configuration), by a majority of those too (by the pairwise
                log-agreement plane)                                limit 0
  reference     cells (term, role, commit, last index, voter, outgoing voter,
                learner per peer; the read in flight per group) in which the
                device's rows for a seeded sample of groups differ, after the
                warm-up segment, from the plain reference replaying boot and
                that segment, membership changes included          limit 0
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple

import numpy as np

from . import traffic
from .reference import cluster as ref
from .reference import membership


class Finding(NamedTuple):
    name: str
    value: int
    limit: int
    detail: str = ""

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def safety(reports: List[dict]) -> Finding:
    total: Dict[str, int] = {}
    for rep in reports:
        for slot, n in rep["safety"].items():
            total[slot] = total.get(slot, 0) + int(n)
    bad = {k: v for k, v in total.items() if v}
    return Finding("safety", sum(total.values()), 0, f"{len(total)} slots, nonzero: {bad}")


def fires(reports: List[dict], seg: traffic.Segment) -> Finding:
    off = [
        seg.read_fires - rep["reads_issued"] - rep["dropped_fires"]
        for rep in reports
    ]
    return Finding("fires", int(np.abs(off).sum()), 0,
                   f"{seg.read_fires} scheduled per segment, {len(reports)} segments")


def reads(report: dict, pending_mode: np.ndarray) -> Finding:
    outstanding = int((pending_mode > 0).sum())
    served = report["served_lease"] + report["served_quorum"]
    return Finding("reads", abs(report["reads_issued"] - served - outstanding), 0,
                   f"issued {report['reads_issued']} served {served} outstanding {outstanding}")


def monotonic(commit_start: np.ndarray, commit_end: np.ndarray) -> Finding:
    fell = int((commit_end < commit_start).sum())
    moved = int((commit_end > commit_start).sum())
    return Finding("monotonic", fell, 0, f"{moved} cells advanced")


def durability(commit: np.ndarray, agree: np.ndarray, voter: np.ndarray,
               outgoing: np.ndarray) -> Finding:
    """commit int[P, G], agree int[P, P, G] (common log prefix of peers a and
    b), voter, outgoing bool[P, G].  The peer with the highest commit index
    holds its own log; peer b holds entry c of it iff agree[a, b] >= c.  A
    group is short when fewer than a majority of its voters hold it, or —
    quorum/joint.rs — fewer than a majority of its outgoing voters where it
    has any."""
    P, G = commit.shape
    a = np.argmax(commit, axis=0)
    c = commit[a, np.arange(G)]
    holds = agree[a, :, np.arange(G)].T >= c[None, :]  # [P, G]
    holds[a, np.arange(G)] = True

    def lacks(members: np.ndarray) -> np.ndarray:
        n = members.sum(axis=0)
        return (n > 0) & ((holds & members).sum(axis=0) < n // 2 + 1)

    short_in, short_out = lacks(voter), lacks(outgoing)
    joint = int(outgoing.any(axis=0).sum())
    return Finding("durability", int((short_in | short_out).sum()), 0,
                   f"{G} groups, {joint} joint, short of the voters' majority "
                   f"{int(short_in.sum())}, of the outgoing voters' {int(short_out.sum())}; "
                   f"commit index min {int(c.min())} max {int(c.max())}")


HOT_ENTRIES_PER_ROUND = 3  # the reference appends entry by entry, in python


def pick_sample(seg: traffic.Segment, seed: int, n: int) -> np.ndarray:
    """n group ids from the seed: the region with the heaviest update load
    among those offered at most HOT_ENTRIES_PER_ROUND entries in any round
    (the very hottest regions append tens of entries a round, which the
    scalar reference replays in tens of seconds), the rest uniform — with at
    least half of the sample, where the mix changes memberships, drawn from
    the groups it changes."""
    rng = np.random.Generator(np.random.PCG64([int(seed), 0x5A]))
    load = seg.append.sum(axis=0)
    load = np.where(seg.append.max(axis=0) <= HOT_ENTRIES_PER_ROUND, load, -1)
    hot = int(np.argmax(load))
    rest = rng.choice(seg.n_groups, size=min(n, seg.n_groups), replace=False)
    ids = [hot] + [int(g) for g in rest if int(g) != hot]
    if seg.reconfig:
        moved = np.zeros(seg.n_groups, bool)
        for groups in membership.classes(seg.reconfig["phases"], seg.n_groups).values():
            moved |= groups
        movers = [g for g in ids[:n] if moved[g]]
        short = max(0, (n + 1) // 2 - len(movers))
        pool = rng.permutation(np.flatnonzero(moved)).tolist()
        movers += [g for g in pool if g not in movers][:short]
        ids = movers + [g for g in ids if not moved[g]]
    return np.array(sorted(ids[:n]), dtype=np.int64)


def conf_chains(seg: traffic.Segment, gids: np.ndarray):
    """Per sampled group (the Changer-walked steps of its ops, the round at
    which each becomes eligible), or None for a mix without a schedule."""
    if not seg.reconfig:
        return None
    doc = seg.reconfig
    starts = membership.phase_starts(doc["phases"])
    by_class = membership.classes(doc["phases"], seg.n_groups)
    walked = {(): []}
    out = []
    for g in gids:
        chain = next((c for c, groups in by_class.items() if groups[g]), ())
        if chain not in walked:
            walked[chain] = membership.walk(
                doc["phases"], chain, doc["peers"], doc["voters"], doc["learners"])
        out.append((walked[chain], [starts[s.phase] for s in walked[chain]]))
    return out


def fault_rows(seg: traffic.Segment):
    """(crashed bool[R, P], link bool[R, P, P]) of the segment's chaos
    phases, round by round, the same for every group: a crashed peer is cut
    off whole; a partition takes down the links between its cells (peers in
    no cell form one more cell)."""
    R, P = seg.n_rounds, seg.n_peers
    crashed = np.zeros((R, P), bool)
    link = np.ones((R, P, P), bool)
    r = 0
    for ph in (seg.chaos or {}).get("phases", []):
        n = int(ph["rounds"])
        for p in ph.get("crash", []):
            crashed[r:r + n, p - 1] = True
        if ph.get("partition") is not None:
            cell = np.full(P, len(ph["partition"]))
            for c, ids in enumerate(ph["partition"]):
                cell[np.asarray(ids) - 1] = c
            link[r:r + n] = cell[:, None] == cell[None, :]
        r += n
    return crashed, link


def reference(config: dict, seg: traffic.Segment, gids: np.ndarray,
              device_rows: Dict[str, np.ndarray]) -> Finding:
    """device_rows: ref.FIELDS [n, P] and pending_mode int[n] of the sampled
    groups after boot and one segment."""
    voters, learners = membership.of_config(config)
    replay = ref.Replay(
        gids,
        n_peers=seg.n_peers,
        election_tick=config["election_tick"],
        heartbeat_tick=config["heartbeat_tick"],
        check_quorum=config["check_quorum"],
        pre_vote=config["pre_vote"],
        lease_read=config["lease_read"],
        voters=voters, learners=learners,
    )
    replay.boot(config["boot_rounds"])
    fire, mode, append = traffic.sample_rows(seg, gids)
    replay.segment(fire, mode, append, *fault_rows(seg), chains=conf_chains(seg, gids))
    want = replay.rows()
    want["pending_mode"] = replay.pending
    diff, where = 0, []
    for key, w in want.items():
        d = int((np.asarray(device_rows[key]) != w).sum())
        diff += d
        if d:
            where.append(f"{key}:{d}")
    return Finding(
        "reference", diff, 0,
        f"groups {gids.tolist()}, {seg.n_rounds + config['boot_rounds']} rounds, "
        f"reference served {replay.served} reads, dropped {replay.dropped}, applied "
        f"{sum(g.conf_applied for g in replay.groups)} conf ops ("
        f"{sum(g.conf_retried for g in replay.groups)} proposed again); differing {where}",
    )
