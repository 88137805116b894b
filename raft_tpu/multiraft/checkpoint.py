"""Checkpoint / resume for the batched MultiRaft device state
(SURVEY.md §5.4: HardState-style persistence adapted to the [P, G] planes).

The scalar path persists through the Ready protocol (HardState + entries via
the application's Storage, reference: raw_node.rs must_sync semantics).  The
device path's equivalent is a whole-batch snapshot: every SimState plane is
downloaded once and written as a single .npz; because every backend is
deterministic, a resumed run is bit-identical to an uninterrupted one
(tested in tests/test_checkpoint.py).

For the per-group HardState view (what the reference would fsync), use
`hard_states()`: {term, vote, commit}[P, G] extracted from the planes.
"""

from __future__ import annotations

import os
import tempfile
from typing import Dict

import numpy as np
import jax.numpy as jnp

from . import planes
from .sim import SimState

_FORMAT_VERSION = 1


def save_state(state: SimState, path: str) -> None:
    """Atomically write the full device state to `path` (.npz).  The field
    set is the plane registry's "state" checkpoint family (planes.py; ==
    SimState._fields, pinned by GC016).  Optional planes that are absent
    (recent_active on an undamped sim is None) are skipped; load_state
    restores them as None."""
    arrays = {
        name: np.asarray(value)
        for name in planes.checkpoint_fields("state")
        if (value := getattr(state, name)) is not None
    }
    arrays["__version__"] = np.asarray(_FORMAT_VERSION)
    dir_ = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=dir_, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **arrays)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def load_state(path: str) -> SimState:
    """Load a state written by save_state; arrays land on the default
    device."""
    with np.load(path) as data:
        version = int(data["__version__"])
        if version != _FORMAT_VERSION:
            raise ValueError(f"unsupported checkpoint version {version}")
        fields = {}
        # Only flag-gated registry rows are optional planes; a future
        # field without a gating flag must be present in every checkpoint.
        optional = set(planes.optional_sim_fields())
        for name in planes.checkpoint_fields("state"):
            if name not in data:
                if name in optional:
                    continue  # optional plane absent (undamped checkpoint)
                raise ValueError(
                    f"checkpoint {path!r} is missing required plane "
                    f"{name!r} (corrupt or truncated file)"
                )
            arr = data[name]
            # np.load arrays are strongly typed, so this dtype is the
            # checkpointed one verbatim — passed explicitly per the GC001
            # device-boundary convention, not as a behavioral change.
            fields[name] = jnp.asarray(arr, dtype=arr.dtype)
    return SimState(**fields)


_RECONFIG_FORMAT_VERSION = 1


def save_reconfig_state(rstate, path: str) -> None:
    """Atomically write a reconfig.ReconfigState (the in-flight conf-op
    carry: stage/op_ptr/pending-entry cursors + the previous round's mask
    planes) alongside a SimState checkpoint, so a membership-churn run
    resumes mid-plan bit-identically (the schedule arrays themselves are
    recompiled from the plan — only the mutable carry needs persisting)."""
    arrays = {
        name: np.asarray(getattr(rstate, name))
        for name in planes.checkpoint_fields("reconfig")
    }
    arrays["__reconfig_version__"] = np.asarray(_RECONFIG_FORMAT_VERSION)
    dir_ = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=dir_, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **arrays)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def load_reconfig_state(path: str):
    """Load a reconfig carry written by save_reconfig_state."""
    from .reconfig import ReconfigState

    with np.load(path) as data:
        if "__reconfig_version__" not in data:
            raise ValueError(
                f"{path!r} is not a reconfig-state checkpoint (missing "
                "version marker — did you pass a SimState checkpoint?)"
            )
        version = int(data["__reconfig_version__"])
        if version != _RECONFIG_FORMAT_VERSION:
            raise ValueError(
                f"unsupported reconfig checkpoint version {version}"
            )
        fields = {}
        for name in planes.checkpoint_fields("reconfig"):
            if name not in data:
                raise ValueError(
                    f"reconfig checkpoint {path!r} is missing plane "
                    f"{name!r} (corrupt or truncated file)"
                )
            arr = data[name]
            fields[name] = jnp.asarray(arr, dtype=arr.dtype)
    return ReconfigState(**fields)


_READ_FORMAT_VERSION = 2  # 2: ReadCarry.last_leader

# The persisted read-protocol planes, in registry save order: the
# outstanding-read carry (workload.ReadCarry) plus the run's accumulators,
# so a resumed client workload reproduces its latency percentiles and
# serve counts bit-identically.
_READ_FIELDS = planes.checkpoint_fields("read")


def save_read_state(rcar, read_stats, lat_hist, path: str) -> None:
    """Atomically write the client-read protocol carry (ISSUE 13):
    workload.ReadCarry's outstanding-read planes plus the
    [workload.N_READ_STATS] stats vector and the [workload.N_LAT_BUCKETS]
    latency histogram — everything a mid-plan resume needs for
    bit-identical read accounting (the schedule arrays recompile from the
    plan, like the reconfig carry)."""
    arrays = {
        "pending_mode": np.asarray(rcar.pending_mode),
        "pending_since": np.asarray(rcar.pending_since),
        "last_leader": np.asarray(rcar.last_leader),
        "read_stats": np.asarray(read_stats),
        "lat_hist": np.asarray(lat_hist),
        "__read_version__": np.asarray(_READ_FORMAT_VERSION),
    }
    dir_ = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=dir_, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **arrays)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def load_read_state(path: str):
    """Load a read-protocol carry written by save_read_state; returns
    (workload.ReadCarry, read_stats, lat_hist).  Loud ValueError on a
    missing version marker (not a read checkpoint), an unsupported
    version, or a missing plane (corrupt/truncated file)."""
    from .workload import ReadCarry

    with np.load(path) as data:
        if "__read_version__" not in data:
            raise ValueError(
                f"{path!r} is not a read-state checkpoint (missing "
                "version marker — did you pass a SimState checkpoint?)"
            )
        version = int(data["__read_version__"])
        if version != _READ_FORMAT_VERSION:
            raise ValueError(
                f"unsupported read-state checkpoint version {version}"
            )
        fields = {}
        for name in _READ_FIELDS:
            if name not in data:
                raise ValueError(
                    f"read-state checkpoint {path!r} is missing plane "
                    f"{name!r} (corrupt or truncated file)"
                )
            arr = data[name]
            fields[name] = jnp.asarray(arr, dtype=arr.dtype)
    return (
        ReadCarry(
            pending_mode=fields["pending_mode"],
            pending_since=fields["pending_since"],
            last_leader=fields["last_leader"],
        ),
        fields["read_stats"],
        fields["lat_hist"],
    )


_BLACKBOX_FORMAT_VERSION = 1

# The persisted black-box planes, in BlackboxState field order (the
# registry pins the order against the NamedTuple): the ring windows, the
# first-trip plane, and the absolute round counter — so a post-mortem can
# be extracted from a crashed run's checkpoint exactly as from the live
# sim (forensics.decode_window reads the same arrays).
_BLACKBOX_FIELDS = planes.checkpoint_fields("blackbox")


def save_blackbox_state(blackbox, path: str) -> None:
    """Atomically write the black-box flight recorder (ISSUE 15;
    sim.BlackboxState) next to a SimState checkpoint, so the forensic
    window survives the process that captured it."""
    arrays = {
        name: np.asarray(getattr(blackbox, name))
        for name in _BLACKBOX_FIELDS
    }
    arrays["__blackbox_version__"] = np.asarray(_BLACKBOX_FORMAT_VERSION)
    dir_ = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=dir_, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **arrays)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def load_blackbox_state(path: str):
    """Load a black-box recorder written by save_blackbox_state; returns
    a sim.BlackboxState.  Loud ValueError on a missing version marker, an
    unsupported version, or a missing plane."""
    from .sim import BlackboxState

    with np.load(path) as data:
        if "__blackbox_version__" not in data:
            raise ValueError(
                f"{path!r} is not a black-box checkpoint (missing "
                "version marker — did you pass a SimState checkpoint?)"
            )
        version = int(data["__blackbox_version__"])
        if version != _BLACKBOX_FORMAT_VERSION:
            raise ValueError(
                f"unsupported black-box checkpoint version {version}"
            )
        fields = {}
        for name in _BLACKBOX_FIELDS:
            if name not in data:
                raise ValueError(
                    f"black-box checkpoint {path!r} is missing plane "
                    f"{name!r} (corrupt or truncated file)"
                )
            arr = data[name]
            fields[name] = jnp.asarray(arr, dtype=arr.dtype)
    return BlackboxState(**fields)


def hard_states(state: SimState) -> Dict[str, np.ndarray]:
    """The durable per-peer raft state {term, vote, commit} (reference:
    proto/proto/eraftpb.proto:94-98), shaped [P, G]."""
    return {
        "term": np.asarray(state.term),
        "vote": np.asarray(state.vote),
        "commit": np.asarray(state.commit),
    }
