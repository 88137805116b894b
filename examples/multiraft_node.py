"""A TiKV-style multi-raft deployment: three nodes hosting 2,000 groups.

Three MultiRaft drivers (one per peer id) tick their groups with ONE device
kernel per tick each; the host only touches groups whose timers fired.
Messages route between drivers through in-memory batched inboxes (the
production analog batches per destination host over DCN).  All three
drivers share one process, so they share the chip (chip_smoke.py drives
this same `run`).

Run: python examples/multiraft_node.py
"""

import sys
import time

sys.path.insert(0, ".")

from raft_tpu import Config, MemStorage, StateRole
from raft_tpu.multiraft.driver import MultiRaft
from raft_tpu.raft_log import NO_LIMIT

G = 2_000
PEERS = [1, 2, 3]


def base_config(id):
    return Config(
        id=id,
        election_tick=10,
        heartbeat_tick=3,
        max_size_per_msg=NO_LIMIT,
        max_inflight_msgs=256,
    )


def pump(drivers):
    moved = True
    while moved:
        moved = False
        outbox = []
        for id, d in drivers.items():
            for g in d.ready_groups():
                rd = d.ready(g)
                node = d.node(g)
                store = node.raft.raft_log.store
                msgs = rd.take_messages()
                with store.wl() as core:
                    if not rd.snapshot.is_empty():
                        core.apply_snapshot(rd.snapshot.clone())
                    if rd.entries:
                        core.append(rd.entries)
                    if rd.hs is not None:
                        core.set_hardstate(rd.hs.clone())
                msgs += rd.persisted_messages()
                light = d.advance(g, rd)
                msgs += light.take_messages()
                d.advance_apply(g)
                outbox.extend((g, m) for m in msgs)
                moved = True
        by_dest = {}
        for g, m in outbox:
            by_dest.setdefault(m.to, []).append((g, m))
        for to, batch in by_dest.items():
            drivers[to].step_batch(batch)
            moved = True


def run(n_groups=G, log=print):
    """Three drivers in ONE process (a TPU belongs to one process at a
    time): elect every group, then commit one proposal per group on every
    node.  Returns {"groups", "election_ticks", "leaders", "committed"}
    where `committed` counts the (node, group) pairs whose commit index
    reached the group's proposal; raises SystemExit when a stage stalls."""
    t0 = time.monotonic()
    drivers = {}
    for id in PEERS:
        storages = [
            MemStorage.new_with_conf_state((PEERS, []))
            for _ in range(n_groups)
        ]
        drivers[id] = MultiRaft(base_config(id), storages)
    log(f"built 3 nodes x {n_groups} groups in {time.monotonic() - t0:.1f}s")

    # Tick until every group has elected a leader.
    t0 = time.monotonic()
    ticks = 0
    while True:
        for d in drivers.values():
            d.tick()
        ticks += 1
        pump(drivers)
        n_leaders = sum(d.status()["n_leaders"] for d in drivers.values())
        if n_leaders == n_groups:
            break
        if ticks > 200:
            raise SystemExit(f"elections incomplete: {n_leaders}/{n_groups}")
    log(
        f"all {n_groups} groups elected after {ticks} ticks in "
        f"{time.monotonic() - t0:.1f}s"
    )

    # One proposal per group at its leader; every node must commit it.
    want = {}
    for id, d in drivers.items():
        for g in range(n_groups):
            raft = d.node(g).raft
            if raft.state == StateRole.Leader:
                d.propose(g, b"", b"smoke-%d" % g)
                want[g] = raft.raft_log.last_index()
    if len(want) != n_groups:
        raise SystemExit(f"proposed on {len(want)}/{n_groups} leaders")

    def n_committed():
        return sum(
            d.node(g).raft.raft_log.committed >= want[g]
            for d in drivers.values()
            for g in range(n_groups)
        )

    pump(drivers)
    extra = 0
    while (committed := n_committed()) < len(PEERS) * n_groups:
        if extra == 50:
            raise SystemExit(
                f"proposals incomplete: {committed} of "
                f"{len(PEERS) * n_groups} (node, group) commits"
            )
        # Followers learn the commit index from the next heartbeat.
        for d in drivers.values():
            d.tick()
        pump(drivers)
        extra += 1
    log(f"one proposal per group committed on every node (+{extra} ticks)")
    return {
        "groups": n_groups,
        "election_ticks": ticks,
        "leaders": n_leaders,
        "committed": committed,
    }


def main():
    run()
    print("multiraft_node OK")


if __name__ == "__main__":
    main()
