"""The op protocol's row look-ups as static selects (ISSUE 34).

`reconfig._gather_peer` / `_gather_op` and `kernels.apply_confchange`'s
transfer abort pick one row of a `[N, ..., G]` plane per group with N - 1
static selects (`kernels.select_row`).  Each is held bit-equal here to a
`take_along_axis` reference of this file's own — the form they had — on
every index the contract names: `owner == 0` ("no owner") reads row 0,
`owner > P` the last row, `op_ptr == n_ops` ("chain finished") row K - 1.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from raft_tpu.multiraft import kernels, reconfig

G = 37


def plane_of(rng, shape, dtype):
    if dtype == "bool":
        return jnp.asarray(rng.integers(0, 2, shape).astype(bool))
    return jnp.asarray(rng.integers(-5, 1 << 20, shape).astype(np.int32))


def draw(rng, lo, hi):
    """int32[G] over [lo, hi], every value of the range present."""
    vals = np.arange(lo, hi + 1)
    idx = np.concatenate([vals, rng.choice(vals, max(G - len(vals), 0))])
    return jnp.asarray(rng.permutation(idx)[:G].astype(np.int32))


def ref_gather_peer(plane, owner):
    o = jnp.clip(owner - 1, 0, plane.shape[0] - 1)
    return jnp.take_along_axis(plane, o[None, :], axis=0)[0]


def ref_gather_op(plane, op_ptr):
    k = jnp.clip(op_ptr, 0, plane.shape[0] - 1)
    idx = jnp.broadcast_to(k, plane.shape[1:])[None]
    return jnp.take_along_axis(plane, idx, axis=0)[0]


def assert_same(got, want):
    assert got.dtype == want.dtype
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("idx_shape", [(G,), (4, G)])
@pytest.mark.parametrize("N", [1, 2, 7])
def test_select_row_equals_numpy(N, idx_shape):
    """The kernel itself against numpy, with the index as wide as a row
    ([G] against [N, G]) and wider ([4, G]: apply_confchange's form)."""
    rng = np.random.default_rng(N)
    plane = plane_of(rng, (N, G), "int32")
    idx = rng.integers(0, N, idx_shape).astype(np.int32)
    want = np.take_along_axis(np.asarray(plane), idx.reshape(-1, G), axis=0)
    assert_same(kernels.select_row(plane, jnp.asarray(idx)),
                jnp.asarray(want.reshape(idx_shape)))


@pytest.mark.parametrize("dtype", ["bool", "int32"])
@pytest.mark.parametrize("P", [3, 5])
def test_gather_peer_equals_take_along_axis(P, dtype):
    rng = np.random.default_rng(P)
    plane = plane_of(rng, (P, G), dtype)
    owner = draw(rng, 0, P + 1)
    assert_same(reconfig._gather_peer(plane, owner),
                ref_gather_peer(plane, owner))


@pytest.mark.parametrize("dtype", ["bool", "int32"])
@pytest.mark.parametrize("ndim", [2, 3])
@pytest.mark.parametrize("K", [1, 6, 15])
def test_gather_op_equals_take_along_axis(K, ndim, dtype):
    rng = np.random.default_rng(K)
    shape = (K, G) if ndim == 2 else (K, 5, G)
    plane = plane_of(rng, shape, dtype)
    op_ptr = draw(rng, -1, K)
    assert_same(reconfig._gather_op(plane, op_ptr),
                ref_gather_op(plane, op_ptr))


@pytest.mark.parametrize("P", [3, 5])
def test_confchange_transfer_abort_equals_take_along_axis(P):
    """Every output of apply_confchange with a transferee plane drawn over
    {0, 1..P, P + 1}; the expected abort is the look-up it had:
    `take_along_axis(voter' | outgoing', transferee - 1)`."""
    rng = np.random.default_rng(P)
    b = lambda *s: plane_of(rng, s, "bool")  # noqa: E731
    state = jnp.asarray(rng.integers(0, 3, (P, G)).astype(np.int32))
    matched = plane_of(rng, (P, P, G), "int32")
    transferee = jnp.stack([draw(rng, 0, P + 1) for _ in range(P)])
    apply_mask = b(G)
    new_voter, new_outgoing = b(P, G), b(P, G)
    out = kernels.apply_confchange(
        state, jnp.zeros((P, G), jnp.int32), jnp.zeros((P, G), jnp.int32),
        jnp.zeros((P, G), jnp.int32), jnp.abs(matched), b(P, G), b(P, G),
        b(P, G), new_voter, new_outgoing, b(P, G), b(P, G), b(P, G),
        apply_mask, b(P, P, G), transferee,
    )
    state2, vm, om, tr = out[0], out[4], out[5], out[8]
    tgt_in = jnp.take_along_axis(
        vm | om, jnp.clip(transferee - 1, 0, P - 1), axis=0
    )
    step_down = (state2 != state)
    want = jnp.where(
        apply_mask[None, :] & ((transferee > 0) & ~tgt_in | step_down),
        0, transferee,
    )
    assert_same(tr, want)
    assert np.any(np.asarray(tr) != np.asarray(transferee)), "aborts happen"
    assert np.any((np.asarray(tr) > 0) & np.asarray(apply_mask)[None, :]), \
        "and not every pending transfer is aborted"
