"""The plane registry IS the contract — one parameterized suite.

Every test here is driven by iterating `raft_tpu.multiraft.planes.REGISTRY`
rather than hand-listing fields, so a new plane row is covered (or loudly
uncovered) the moment it lands in the registry:

  * runtime mirror: the NamedTuple field orders (SimState, BlackboxState,
    ReconfigState, ReadCarry) match registry order exactly;
  * per-row checkpoint round-trip for all four persistence families
    ("state" / "blackbox" / "read" / "reconfig"): perturb ONE field to a
    distinct pattern, save, load, compare every field bit-exactly;
  * corruption is loud per family: missing plane, bad version, wrong
    file kind;
  * flag-off pytree identity: optional (flag-gated) planes are None,
    skipped on save, restored as None — tree structure preserved;
  * sharding specs on a REAL 2-device mesh (conftest's virtual CPUs):
    "minor-G" rows shard the trailing group axis with leading axes
    replicated, "replicate" rows place whole copies — verified both
    against the spec and against actual device_put shard shapes.

These subsume the hand-written per-plane copies that previously lived in
tests/test_checkpoint.py (damped-plane round trip, read-state round
trip) and tests/test_transfer_batched.py (transferee round trip).

Everything tier-1 here is compile-free (init + direct plane writes +
device_put); the G=64 sweep is slow-marked per the standing budget.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu.multiraft import checkpoint, planes, reconfig, sharding
from raft_tpu.multiraft import sim as sim_mod
from raft_tpu.multiraft import workload
from raft_tpu.multiraft.sim import SimConfig


G, PEERS = 4, 3

_ALL_FLAGS = dict(check_quorum=True, pre_vote=True, transfer=True)


def _distinct(arr, salt: int):
    """A deterministic, salt-dependent pattern with arr's shape/dtype —
    distinct from zeros and from any other salt, so a round-trip that
    crossed wires between planes cannot pass."""
    a = np.asarray(arr)
    if a.dtype == np.bool_:
        pat = (np.arange(a.size) + salt) % 3 == 0
        return jnp.asarray(pat.reshape(a.shape))
    vals = (np.arange(a.size, dtype=np.int64) * 7 + 11 * salt + 3) % 89
    return jnp.asarray(vals.reshape(a.shape).astype(a.dtype))


def _assert_fields_equal(expect, got, fields):
    for f in fields:
        a, b = getattr(expect, f), getattr(got, f)
        assert (a is None) == (b is None), f
        if a is not None:
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == b.dtype, f"field {f}: {a.dtype} != {b.dtype}"
            np.testing.assert_array_equal(a, b, err_msg=f"field {f}")


# --- carriers: one fresh instance per family, fields perturbable by name ----


def _state_carrier(g=G, p=PEERS):
    return sim_mod.init_state(SimConfig(n_groups=g, n_peers=p, **_ALL_FLAGS))


def _blackbox_carrier(g=G, p=PEERS):
    return sim_mod.init_blackbox(
        SimConfig(n_groups=g, n_peers=p, blackbox=True)
    )


def _read_carrier(g=G):
    """(ReadCarry, read_stats, lat_hist) — the save_read_state triple,
    exposed as one namespace so per-row perturbation is uniform."""

    class _ReadTriple:
        _fields = planes.checkpoint_fields("read")

        def __init__(self):
            rcar = workload.init_read_carry(g)
            self.pending_mode = rcar.pending_mode
            self.pending_since = rcar.pending_since
            self.last_leader = rcar.last_leader
            self.read_stats = jnp.zeros((workload.N_READ_STATS,), jnp.int32)
            self.lat_hist = jnp.zeros((workload.N_LAT_BUCKETS,), jnp.int32)

    return _ReadTriple()


def _reconfig_carrier(g=G, p=PEERS):
    return reconfig.init_reconfig_state(
        sim_mod.init_state(SimConfig(n_groups=g, n_peers=p))
    )


def _round_trip(family, carrier, path):
    """Save `carrier` through the family's checkpoint writer and load it
    back; returns an object with the family's fields as attributes."""
    if family == "state":
        checkpoint.save_state(carrier, path)
        return checkpoint.load_state(path)
    if family == "blackbox":
        checkpoint.save_blackbox_state(carrier, path)
        return checkpoint.load_blackbox_state(path)
    if family == "read":
        checkpoint.save_read_state(
            workload.ReadCarry(
                carrier.pending_mode, carrier.pending_since,
                carrier.last_leader,
            ),
            carrier.read_stats, carrier.lat_hist, path,
        )
        rcar, stats, hist = checkpoint.load_read_state(path)
        out = _read_carrier()
        out.pending_mode, out.pending_since, out.last_leader = rcar
        out.read_stats, out.lat_hist = stats, hist
        return out
    assert family == "reconfig"
    checkpoint.save_reconfig_state(carrier, path)
    return checkpoint.load_reconfig_state(path)


_FAMILIES = {
    "state": _state_carrier,
    "blackbox": _blackbox_carrier,
    "read": _read_carrier,
    "reconfig": _reconfig_carrier,
}

_CKPT_CASES = [
    (fam, name)
    for fam in _FAMILIES
    for name in planes.checkpoint_fields(fam)
]


# --- runtime mirror ---------------------------------------------------------


def test_registry_mirrors_runtime_field_order():
    """Registry order IS NamedTuple field order for every owner the
    checkpoint and sharding layers iterate — a reordered or renamed field
    fails here before it silently corrupts a checkpoint."""
    assert sim_mod.SimState._fields == planes.sim_state_fields()
    assert sim_mod.BlackboxState._fields == tuple(
        r.name for r in planes.rows(owner="BlackboxState")
    )
    assert reconfig.ReconfigState._fields == tuple(
        r.name for r in planes.rows(owner="ReconfigState")
    )
    carry_rows = tuple(r.name for r in planes.rows(family="read-carry"))
    assert carry_rows[: len(workload.ReadCarry._fields)] == (
        workload.ReadCarry._fields
    )
    assert planes.checkpoint_fields("read") == carry_rows


def test_registry_checkpoint_families_are_exhaustive():
    """Every persisted row belongs to exactly one known family, and the
    four families partition the checkpoint != "none" rows."""
    persisted = [r for r in planes.rows() if r.checkpoint != "none"]
    assert {r.checkpoint for r in persisted} == set(_FAMILIES)
    for fam in _FAMILIES:
        names = planes.checkpoint_fields(fam)
        assert len(names) == len(set(names)), f"duplicate rows in {fam}"


# --- per-row checkpoint round-trips -----------------------------------------


@pytest.mark.parametrize(
    "family,field", _CKPT_CASES, ids=[f"{f}-{n}" for f, n in _CKPT_CASES]
)
def test_checkpoint_round_trips_every_registry_row(tmp_path, family, field):
    """Perturb ONE registry row to a distinct pattern and round-trip the
    whole family: the perturbed plane AND every sibling come back
    bit-exact with dtype preserved."""
    carrier = _FAMILIES[family]()
    salt = planes.checkpoint_fields(family).index(field) + 1
    perturbed = _distinct(getattr(carrier, field), salt)
    if hasattr(carrier, "_replace"):
        carrier = carrier._replace(**{field: perturbed})
    else:
        setattr(carrier, field, perturbed)
    back = _round_trip(
        family, carrier, os.path.join(tmp_path, f"{family}.npz")
    )
    _assert_fields_equal(carrier, back, planes.checkpoint_fields(family))


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_checkpoint_corruption_is_loud(tmp_path, family):
    """Per family: a missing plane is corruption, an unknown version is
    rejected, and (for the sidecar files) a SimState checkpoint is
    refused as the wrong file kind."""
    carrier = _FAMILIES[family]()
    path = os.path.join(tmp_path, f"{family}.npz")
    _round_trip(family, carrier, path)

    # Missing plane — drop the LAST field of the family (for "state"
    # a required, never-flag-gated plane: commit).
    victim = "commit" if family == "state" else (
        planes.checkpoint_fields(family)[-1]
    )
    with np.load(path) as data:
        arrays = {k: data[k] for k in data.files if k != victim}
    trunc = os.path.join(tmp_path, "trunc.npz")
    np.savez(trunc, **arrays)
    with pytest.raises(ValueError, match="missing"):
        _round_trip_load(family, trunc)

    # Unsupported version.
    version_key = {
        "state": "__version__",
        "blackbox": "__blackbox_version__",
        "read": "__read_version__",
        "reconfig": "__reconfig_version__",
    }[family]
    with np.load(path) as data:
        arrays = {k: data[k] for k in data.files}
    if version_key in arrays:
        arrays[version_key] = np.asarray(999)
        bad = os.path.join(tmp_path, "bad.npz")
        np.savez(bad, **arrays)
        with pytest.raises(ValueError, match="999"):
            _round_trip_load(family, bad)

    # Wrong file kind: every sidecar loader refuses a SimState file.
    if family != "state":
        other = os.path.join(tmp_path, "state.npz")
        checkpoint.save_state(
            sim_mod.init_state(SimConfig(n_groups=2, n_peers=3)), other
        )
        with pytest.raises(ValueError, match="missing version marker"):
            _round_trip_load(family, other)


def _round_trip_load(family, path):
    return {
        "state": checkpoint.load_state,
        "blackbox": checkpoint.load_blackbox_state,
        "read": checkpoint.load_read_state,
        "reconfig": checkpoint.load_reconfig_state,
    }[family](path)


# --- flag-off pytree identity -----------------------------------------------


def test_flag_off_optional_planes_are_none_end_to_end(tmp_path):
    """With every gating flag off, exactly the registry's optional rows
    are None — in the live pytree, in the saved file (skipped, not
    zero-filled), and after reload (tree structure preserved)."""
    optional = set(planes.optional_sim_fields())
    assert optional, "registry lost its flag-gated rows"

    st_off = sim_mod.init_state(SimConfig(n_groups=G, n_peers=PEERS))
    for name in planes.sim_state_fields():
        present = getattr(st_off, name) is not None
        assert present == (name not in optional), name

    path = os.path.join(tmp_path, "off.npz")
    checkpoint.save_state(st_off, path)
    with np.load(path) as data:
        saved = {k for k in data.files if not k.startswith("__")}
    assert saved == set(planes.checkpoint_fields("state")) - optional

    back = checkpoint.load_state(path)
    assert jax.tree.structure(back) == jax.tree.structure(st_off)
    _assert_fields_equal(st_off, back, planes.sim_state_fields())

    # All flags on: every optional plane materializes and round-trips.
    st_on = _state_carrier()
    for name in optional:
        assert getattr(st_on, name) is not None, name


# --- sharding specs on a real 2-device mesh ---------------------------------


_SHARDED_ROWS = [
    r for r in planes.rows() if r.sharding != "none" and r.shape != "word"
]


@pytest.fixture(scope="module")
def mesh2():
    return sharding.make_mesh(n_devices=2)


@pytest.mark.parametrize(
    "row", _SHARDED_ROWS, ids=[f"{r.owner}.{r.name}" for r in _SHARDED_ROWS]
)
def test_row_sharding_spec_matches_registry(mesh2, row):
    """Per sharded row: the derived NamedSharding is exactly what the
    registry's shape string dictates — P() for "replicate", the trailing
    group axis for "minor-G" with `leading_axes` replicated axes ahead
    of it."""
    spec = sharding._row_sharding(mesh2, "groups", row)
    assert isinstance(spec, NamedSharding)
    if row.sharding == "replicate":
        assert spec.spec == P()
    else:
        lead = planes.leading_axes(row)
        assert spec.spec == P(*(None,) * lead, "groups")
        # Shape-string arity agrees with the spec arity.
        assert row.shape.count(",") == lead


def test_state_sharding_places_real_planes(mesh2):
    """device_put every real SimState plane with its registry spec on
    the 2-device mesh: minor-G rows split the trailing axis G/2 per
    shard with leading axes intact; replicate rows keep full copies."""
    st = _state_carrier()
    specs = sharding.state_sharding(
        mesh2, damped=True, transfer=True
    )
    for r in planes.rows(owner="SimState"):
        arr, spec = getattr(st, r.name), getattr(specs, r.name)
        assert spec is not None, r.name
        placed = jax.device_put(arr, spec)
        shard_shapes = {s.data.shape for s in placed.addressable_shards}
        full = np.asarray(arr).shape
        if r.sharding == "minor-G":
            assert shard_shapes == {full[:-1] + (full[-1] // 2,)}, r.name
        else:
            assert shard_shapes == {full}, r.name

    # Flag-off: the spec pytree mirrors the absent planes with None.
    specs_off = sharding.state_sharding(mesh2)
    for name in planes.sim_state_fields():
        expect_none = name in set(planes.optional_sim_fields())
        assert (getattr(specs_off, name) is None) == expect_none, name


def test_blackbox_and_reconfig_sharding_places_real_planes(mesh2):
    """Same placement check for the two sidecar carries: the blackbox
    ring/trip planes and every reconfig carry plane shard group-minor;
    the round counter is a whole-array replica."""
    bb = _blackbox_carrier()
    specs = sharding.blackbox_sharding(mesh2)
    for r in planes.rows(owner="BlackboxState"):
        placed = jax.device_put(getattr(bb, r.name), getattr(specs, r.name))
        shard_shapes = {s.data.shape for s in placed.addressable_shards}
        full = np.asarray(getattr(bb, r.name)).shape
        if r.sharding == "minor-G":
            assert shard_shapes == {full[:-1] + (full[-1] // 2,)}, r.name
        else:
            assert shard_shapes == {full}, r.name

    rc = _reconfig_carrier()
    for r in planes.rows(owner="ReconfigState"):
        spec = sharding._row_sharding(mesh2, "groups", r)
        placed = jax.device_put(getattr(rc, r.name), spec)
        shard_shapes = {s.data.shape for s in placed.addressable_shards}
        full = np.asarray(getattr(rc, r.name)).shape
        assert shard_shapes == {full[:-1] + (full[-1] // 2,)}, r.name


# --- the G=64 sweep (slow: >= G=32 per the standing tier-1 budget) ----------


@pytest.mark.slow
def test_registry_round_trip_and_sharding_at_g64(tmp_path):
    """All four families at G=64, P=5: perturb EVERY row at once,
    round-trip bit-exactly, then place the state and blackbox pytrees on
    the 2-device mesh (32 groups per shard)."""
    g, p = 64, 5
    mesh = sharding.make_mesh(n_devices=2)
    builders = {
        "state": lambda: _state_carrier(g, p),
        "blackbox": lambda: _blackbox_carrier(g, p),
        "read": lambda: _read_carrier(g),
        "reconfig": lambda: _reconfig_carrier(g, p),
    }
    for fam, build in builders.items():
        carrier = build()
        for i, name in enumerate(planes.checkpoint_fields(fam)):
            val = _distinct(getattr(carrier, name), i + 1)
            if hasattr(carrier, "_replace"):
                carrier = carrier._replace(**{name: val})
            else:
                setattr(carrier, name, val)
        back = _round_trip(
            fam, carrier, os.path.join(tmp_path, f"{fam}64.npz")
        )
        _assert_fields_equal(carrier, back, planes.checkpoint_fields(fam))

    st = jax.tree.map(
        jax.device_put,
        _state_carrier(g, p),
        sharding.state_sharding(mesh, damped=True, transfer=True),
    )
    for r in planes.rows(owner="SimState"):
        if r.sharding != "minor-G":
            continue
        shards = {
            s.data.shape for s in getattr(st, r.name).addressable_shards
        }
        assert all(shape[-1] == g // 2 for shape in shards), r.name
    bb = sharding.shard_blackbox(
        _blackbox_carrier(g, p), mesh
    )
    for r in planes.rows(owner="BlackboxState"):
        if r.sharding != "minor-G":
            continue
        shards = {
            s.data.shape for s in getattr(bb, r.name).addressable_shards
        }
        assert all(shape[-1] == g // 2 for shape in shards), r.name
