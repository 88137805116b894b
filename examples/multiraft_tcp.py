"""A multi-host-shaped multi-raft deployment: three OS processes, each a
MultiRaft driver hosting the same 64 groups, exchanging group-tagged wire
messages over TCP.

This is the full TiKV topology in miniature (SURVEY.md §5.8b): per-process
device-batched ticking, per-destination message batching, and the binary
codec on the wire (frame = u32 len | u32 group | codec message).

A codec/transport demo, not a chip workload: a TPU belongs to one process
at a time, so the three children pin themselves to the CPU
(JAX_PLATFORMS=cpu) before they import jax and never open the chip.  The
on-chip shape of this topology is three drivers in ONE process
(examples/multiraft_node.py).

Run: python examples/multiraft_tcp.py
"""

import multiprocessing as mp
import os
import queue
import socket
import struct
import sys
import threading
import time

sys.path.insert(0, ".")

NUM_NODES = 3
G = 64
BASE_PORT = 42955
PROPOSALS_PER_GROUP = 3


def node_main(node_id, result_q):
    # Before anything imports jax: this child must never open the chip.
    os.environ["JAX_PLATFORMS"] = "cpu"

    from raft_tpu import Config, MemStorage, StateRole
    from raft_tpu.codec import decode_message, encode_message
    from raft_tpu.multiraft.driver import MultiRaft
    from raft_tpu.raft_log import NO_LIMIT

    peers = list(range(1, NUM_NODES + 1))
    storages = [MemStorage.new_with_conf_state((peers, [])) for _ in range(G)]
    cfg = Config(
        id=node_id,
        election_tick=10,
        heartbeat_tick=3,
        max_size_per_msg=NO_LIMIT,
        max_inflight_msgs=256,
    )
    driver = MultiRaft(cfg, storages)

    inbox = queue.Queue()

    server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    server.bind(("127.0.0.1", BASE_PORT + node_id))
    server.listen(NUM_NODES)

    def reader(conn):
        try:
            while True:
                hdr = conn.recv(8, socket.MSG_WAITALL)
                if len(hdr) < 8:
                    return
                n, g = struct.unpack("<II", hdr)
                buf = b""
                while len(buf) < n:
                    chunk = conn.recv(n - len(buf))
                    if not chunk:
                        return
                    buf += chunk
                inbox.put((g, decode_message(buf)))
        except OSError:
            pass

    def acceptor():
        while True:
            try:
                conn, _ = server.accept()
            except OSError:
                return
            threading.Thread(target=reader, args=(conn,), daemon=True).start()

    threading.Thread(target=acceptor, daemon=True).start()

    out_conns = {}

    def send_batch(to, batch):
        conn = out_conns.get(to)
        if conn is None:
            try:
                conn = socket.create_connection(
                    ("127.0.0.1", BASE_PORT + to), timeout=1
                )
                out_conns[to] = conn
            except OSError:
                return
        frames = []
        for g, m in batch:
            payload = encode_message(m)
            frames.append(struct.pack("<II", len(payload), g) + payload)
        try:
            conn.sendall(b"".join(frames))
        except OSError:
            out_conns.pop(to, None)

    applied = {}  # group -> count
    proposed = {}  # group -> count
    tick_interval = 0.02
    last_tick = time.monotonic()
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        # Drain the network inbox in one batched delivery.
        batch = []
        try:
            while True:
                batch.append(inbox.get_nowait())
        except queue.Empty:
            pass
        if batch:
            driver.step_batch(batch)

        now = time.monotonic()
        if now - last_tick >= tick_interval:
            driver.tick()
            last_tick = now

        # The leader of each group drives its workload.
        for g in range(G):
            node = driver.node(g)
            if (
                node.raft.state == StateRole.Leader
                and proposed.get(g, 0) < PROPOSALS_PER_GROUP
                and node.raft.raft_log.committed
                >= node.raft.raft_log.last_index()
            ):
                driver.propose(g, b"", b"x")
                proposed[g] = proposed.get(g, 0) + 1

        # Ready processing with per-destination outboxes.
        outbox = {}
        for g in driver.ready_groups():
            rd = driver.ready(g)
            node = driver.node(g)
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
            committed = rd.take_committed_entries()
            light = driver.advance(g, rd)
            msgs += light.take_messages()
            committed += light.take_committed_entries()
            for e in committed:
                if e.data:
                    applied[g] = applied.get(g, 0) + 1
            driver.advance_apply(g)
            for m in msgs:
                outbox.setdefault(m.to, []).append((g, m))
        for to, batch in outbox.items():
            send_batch(to, batch)

        if sum(applied.values()) >= G * PROPOSALS_PER_GROUP:
            break
        time.sleep(0.001)

    status = driver.status()
    result_q.put((node_id, sum(applied.values()), status["n_leaders"]))
    server.close()


def main():
    mp.set_start_method("spawn")
    result_q = mp.Queue()
    procs = [
        mp.Process(target=node_main, args=(i, result_q), daemon=True)
        for i in range(1, NUM_NODES + 1)
    ]
    t0 = time.monotonic()
    for p in procs:
        p.start()
    total_applied = 0
    total_leaders = 0
    for _ in range(NUM_NODES):
        node_id, applied, leaders = result_q.get(timeout=150)
        print(f"node {node_id}: applied {applied} entries, leads {leaders} groups")
        total_applied += applied
        total_leaders += leaders
    for p in procs:
        p.join(timeout=10)
    dt = time.monotonic() - t0
    assert total_leaders == G, f"leaders: {total_leaders}"
    assert total_applied >= G * PROPOSALS_PER_GROUP
    print(
        f"multiraft_tcp OK: {G} groups across 3 processes, "
        f"{G * PROPOSALS_PER_GROUP} entries committed over TCP in {dt:.1f}s"
    )


if __name__ == "__main__":
    main()
