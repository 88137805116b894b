"""workload.fold_latencies — one round's served reads into the latency
histogram — against np.bincount of the served latencies (ISSUE 36).  `lat`
is made as runner._runner_body makes it: clip(r - psince, 0, cap) with
the cap off the histogram's own length."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raft_tpu.multiraft import workload

CAP = workload.LAT_CAP
L = workload.N_LAT_BUCKETS


def _mixed(g, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2, g).astype(bool), rng.integers(0, 90, g)


# name -> (served[G], psince[G], round r, carried-in histogram or None)
CASES = {
    "none_served": (np.zeros(12, bool), np.arange(12), 40, None),
    "all_served_one_bucket": (np.ones(12, bool), np.full(12, 33), 40, None),
    "at_and_over_the_cap": (
        np.ones(6, bool),
        np.array([0, 1, 2, 3, 200 - CAP, 200 - CAP + 1]), 200, None),
    "negative_clips_to_bucket_0": (
        np.array([True, True, False, True]), np.array([9, 7, 8, 5]), 5, None),
    "unserved_lat_is_masked": (
        np.array([False, True, False, False]), np.array([1, 2, 3, 4]), 30, None),
    "g_not_a_multiple_of_128": (*_mixed(131, 1), 90, None),
    "g_of_one": (np.array([True]), np.array([3]), 10, None),
    "carried_in_histogram": (
        *_mixed(257, 2), 90, np.arange(L, dtype=np.int32) * 7 + 1),
}


def expected(served, psince, r, hist0):
    lat = np.clip(r - psince, 0, CAP)
    base = np.zeros(L, np.int64) if hist0 is None else hist0.astype(np.int64)
    return base + np.bincount(lat[served], minlength=L)


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_fold_equals_bincount_of_the_served_latencies(case, x64):
    served, psince, r, hist0 = CASES[case]
    with jax.enable_x64(x64):
        hist = (jnp.zeros((L,), jnp.int32) if hist0 is None
                else jnp.asarray(hist0, jnp.int32))
        lat = jnp.clip(
            jnp.int32(r) - jnp.asarray(psince, jnp.int32), 0, hist.shape[0] - 1)
        out = jax.jit(workload.fold_latencies)(hist, jnp.asarray(served), lat)
        assert out.dtype == jnp.int32 and out.shape == (L,)
        want = expected(served, psince, r, hist0)
        assert np.asarray(out).tolist() == want.tolist()
        assert int(out.sum()) - int(hist.sum()) == int(served.sum())


def test_fold_takes_the_bucket_count_from_the_histogram():
    """The cap is the carry's shape, not the module constant: a shorter
    histogram folds into its own last bucket."""
    hist = jnp.zeros((5,), jnp.int32)
    lat = jnp.clip(jnp.asarray([0, 2, 9, 9], jnp.int32), 0, 4)
    out = workload.fold_latencies(hist, jnp.asarray([True, True, True, False]), lat)
    assert np.asarray(out).tolist() == [1, 0, 1, 0, 1]
