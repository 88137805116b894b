"""MemStorage semantics (ported behaviors from reference: storage.rs:455+)."""

import pytest

from raft_tpu.eraftpb import ConfState, Entry, HardState, Snapshot, SnapshotMetadata
from raft_tpu.errors import Compacted, SnapshotOutOfDate, SnapshotTemporarilyUnavailable, Unavailable
from raft_tpu.storage import MemStorage


def new_entry(index, term):
    return Entry(index=index, term=term)


def new_storage_with_ents(ents):
    s = MemStorage()
    with s.wl() as core:
        core.entries = list(ents)
    return s


ENTS = [new_entry(3, 3), new_entry(4, 4), new_entry(5, 5)]


def test_storage_term():
    s = new_storage_with_ents(ENTS)
    with pytest.raises(Compacted):
        s.term(2)
    assert s.term(3) == 3
    assert s.term(4) == 4
    assert s.term(5) == 5
    with pytest.raises(Unavailable):
        s.term(6)


def test_storage_entries():
    s = new_storage_with_ents(ENTS)
    with pytest.raises(Compacted):
        s.entries(2, 6)
    assert [e.index for e in s.entries(3, 4)] == [3]
    assert [e.index for e in s.entries(4, 5)] == [4]
    assert [e.index for e in s.entries(4, 6)] == [4, 5]
    with pytest.raises(AssertionError):
        s.entries(4, 7)


def test_storage_entries_size_limit():
    ents = [
        Entry(index=3, term=3, data=b"x" * 100),
        Entry(index=4, term=4, data=b"x" * 100),
        Entry(index=5, term=5, data=b"x" * 100),
    ]
    s = new_storage_with_ents(ents)
    # At least one entry is always returned.
    assert len(s.entries(3, 6, max_size=0)) == 1
    assert len(s.entries(3, 6, max_size=2 * 112 + 10)) == 2


def test_storage_first_last_index():
    s = new_storage_with_ents(ENTS)
    assert s.first_index() == 3
    assert s.last_index() == 5
    with s.wl() as core:
        core.append([new_entry(6, 5)])
    assert s.last_index() == 6


def test_storage_compact():
    s = new_storage_with_ents(ENTS)
    with s.wl() as core:
        core.compact(2)  # no-op below first
    assert s.first_index() == 3
    with s.wl() as core:
        core.compact(4)
    assert s.first_index() == 4
    with pytest.raises(Compacted):
        s.term(3)


def test_storage_append():
    # overwrite conflicting suffix
    s = new_storage_with_ents(ENTS)
    with s.wl() as core:
        core.append([new_entry(4, 6), new_entry(5, 6)])
        assert [(e.index, e.term) for e in core.entries] == [(3, 3), (4, 6), (5, 6)]
    # continuous append
    s = new_storage_with_ents(ENTS)
    with s.wl() as core:
        core.append([new_entry(6, 5)])
        assert core.last_index() == 6
    # gap panics
    s = new_storage_with_ents(ENTS)
    with pytest.raises(AssertionError):
        with s.wl() as core:
            core.append([new_entry(8, 5)])


def test_storage_apply_snapshot():
    cs = ConfState(voters=[1, 2, 3])
    s = MemStorage()
    snap = Snapshot(
        metadata=SnapshotMetadata(conf_state=cs, index=4, term=4)
    )
    with s.wl() as core:
        core.apply_snapshot(snap)
        assert core.first_index() == 5
        assert core.raft_state.hard_state.commit == 4
        assert core.raft_state.hard_state.term == 4
    # stale snapshot rejected
    old = Snapshot(metadata=SnapshotMetadata(conf_state=cs, index=3, term=3))
    with pytest.raises(SnapshotOutOfDate):
        with s.wl() as core:
            core.apply_snapshot(old)


def test_storage_create_snapshot():
    s = new_storage_with_ents(ENTS)
    cs = ConfState(voters=[1, 2, 3])
    with s.wl() as core:
        core.raft_state.conf_state = cs
        core.commit_to(4)
    snap = s.snapshot(0)
    assert snap.metadata.index == 4
    assert snap.metadata.term == 4
    assert sorted(snap.metadata.conf_state.voters) == [1, 2, 3]


def test_storage_snapshot_request_index():
    s = new_storage_with_ents(ENTS)
    with s.wl() as core:
        core.commit_to(4)
    snap = s.snapshot(5)
    assert snap.metadata.index == 5


def test_storage_snapshot_unavailable():
    s = new_storage_with_ents(ENTS)
    with s.wl() as core:
        core.commit_to(4)
        core.trigger_snap_unavailable_once()
    with pytest.raises(SnapshotTemporarilyUnavailable):
        s.snapshot(0)
    # next call succeeds
    assert s.snapshot(0).metadata.index == 4


def test_initial_state():
    s = MemStorage()
    assert not s.initial_state().initialized()
    s.initialize_with_conf_state(([1, 2, 3], []))
    assert s.initial_state().initialized()
    with s.wl() as core:
        core.set_hardstate(HardState(term=2, vote=1, commit=0))
    st = s.initial_state()
    assert st.hard_state.term == 2


# --- ArrayStorage: the dense SoA twin must behave exactly like MemStorage
# through the public surface ---


def _drive(store):
    """One op sequence covering append/conflict/compact/snapshot/commit;
    returns every observable result for cross-implementation comparison."""
    from raft_tpu.eraftpb import EntryType

    out = []
    with store.wl() as core:
        core.append(
            [
                Entry(index=1, term=1, data=b"a"),
                Entry(index=2, term=2, data=b"b", context=b"ctx"),
                Entry(
                    index=3,
                    term=2,
                    entry_type=EntryType.EntryConfChange,
                    data=b"cc",
                ),
            ]
        )
    out.append((store.first_index(), store.last_index()))
    out.append([store.term(i) for i in range(1, 4)])
    out.append(store.entries(1, 4))
    # conflicting suffix overwrite
    with store.wl() as core:
        core.append([Entry(index=2, term=3, data=b"B"), Entry(index=3, term=3)])
    out.append(store.entries(1, 4))
    # byte-capped read never returns empty if an entry is in range
    out.append(store.entries(1, 4, max_size=0))
    with store.wl() as core:
        core.commit_to(3)
        out.append((core.hard_state().commit, core.hard_state().term))
        core.compact(2)
    out.append((store.first_index(), store.last_index()))
    with pytest.raises(Compacted):
        store.term(1)
    with pytest.raises(Compacted):
        store.entries(1, 3)
    with pytest.raises(Unavailable):
        store.term(9)
    with store.wl() as core:
        snap = c_snap = core.make_snapshot()
    out.append((snap.metadata.index, snap.metadata.term))
    with store.wl() as core:
        core.apply_snapshot(c_snap)
    out.append((store.first_index(), store.last_index()))
    with pytest.raises(SnapshotOutOfDate):
        with store.wl() as core:
            stale = Snapshot()
            stale.metadata.index = 1
            core.apply_snapshot(stale)
    # post-snapshot appends continue from the snapshot index
    with store.wl() as core:
        core.append([Entry(index=4, term=4, data=b"z")])
    out.append((store.first_index(), store.last_index(), store.term(4)))
    return out


def test_array_storage_matches_mem_storage():
    from raft_tpu.storage import ArrayStorage

    a = _drive(ArrayStorage.new_with_conf_state(([1, 2, 3], [])))
    m = _drive(MemStorage.new_with_conf_state(([1, 2, 3], [])))
    assert a == m  # Entry is a dataclass: deep value comparison


def test_array_storage_capacity_doubles():
    from raft_tpu.storage import ArrayStorage

    s = ArrayStorage.new_with_conf_state(([1], []))
    with s.wl() as core:
        core.append([Entry(index=i, term=1) for i in range(1, 101)])
    assert s.last_index() == 100
    assert s.term(100) == 1
    assert len(s.entries(50, 101)) == 51


def test_array_storage_initial_and_hard_state():
    from raft_tpu.storage import ArrayStorage

    s = ArrayStorage.new_with_conf_state(([1, 2], [3]))
    st = s.initial_state()
    assert st.initialized()
    assert st.conf_state.voters == [1, 2]
    with s.wl() as core:
        core.set_hardstate(HardState(term=5, vote=2, commit=0))
    assert s.initial_state().hard_state.term == 5
    with s.wl() as core:
        core.trigger_snap_unavailable_once()
    with pytest.raises(SnapshotTemporarilyUnavailable):
        s.snapshot(0)
