"""Kernel-vs-scalar-oracle parity: the batched jnp kernels must agree with
the scalar quorum/tracker math bit-for-bit on identical inputs (SURVEY.md §7
phase 4 validation: same inputs as the quorum testdata, compared as ints)."""

import random

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raft_tpu.quorum import AckIndexer, Index, JointConfig, MajorityConfig, U64_MAX, VoteResult
from raft_tpu.multiraft import kernels, sim
from raft_tpu.util import deterministic_timeout


P = 7  # padded peer width


def make_case(rng):
    n_voters = rng.randint(1, P)
    voters = rng.sample(range(P), n_voters)
    mask = np.zeros(P, dtype=bool)
    mask[voters] = True
    matched = np.array([rng.randint(0, 100) for _ in range(P)], dtype=np.int32)
    return mask, matched


def scalar_committed(mask, matched, groups=None, use_gc=False):
    """quorum.MajorityConfig.committed_index over one peer row (any width)."""
    voters = [i + 1 for i in range(len(mask)) if mask[i]]
    l = AckIndexer(
        {
            i + 1: Index(
                index=int(matched[i]),
                group_id=int(groups[i]) if groups is not None else 0,
            )
            for i in range(len(mask))
        }
    )
    idx, flag = MajorityConfig(voters).committed_index(use_gc, l)
    return idx, flag


def test_committed_index_parity_randomized():
    rng = random.Random(7)
    masks, matcheds, want = [], [], []
    for _ in range(300):
        mask, matched = make_case(rng)
        masks.append(mask)
        matcheds.append(matched)
        want.append(scalar_committed(mask, matched)[0])
    got = kernels.committed_index(
        jnp.asarray(np.stack(matcheds)), jnp.asarray(np.stack(masks))
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want, dtype=np.int32))


def test_committed_index_empty_config_is_inf():
    got = kernels.committed_index(
        jnp.zeros((1, P), jnp.int32), jnp.zeros((1, P), bool)
    )
    assert int(got[0]) == 2**31 - 1


def quorum_rows(kind, width, rng, n):
    """n (mask[width], matched[width]) rows of one kind of config."""
    rows = []
    for _ in range(n):
        matched = np.array(
            [rng.randint(0, 100) for _ in range(width)], dtype=np.int32
        )
        mask = np.zeros(width, dtype=bool)
        if kind == "random":
            mask[rng.sample(range(width), rng.randint(0, width))] = True
        elif kind == "single_voter":
            mask[rng.randrange(width)] = True
        elif kind == "all_equal":
            mask[rng.sample(range(width), rng.randint(1, width))] = True
            matched[:] = rng.randint(0, 100)
        else:
            assert kind == "empty"
        rows.append((mask, matched))
    return rows


@pytest.mark.parametrize("kind", ["random", "empty", "single_voter", "all_equal"])
@pytest.mark.parametrize("layout", ["rows", "owner_planes"])
@pytest.mark.parametrize("width", [1, 3, 5, 7])
def test_committed_index_is_the_oracle_in_every_layout(width, layout, kind):
    """kernels.committed_index against the scalar oracle on [N, P] rows and
    on the callers' swapaxes([P_owner, P, G], 1, 2) planes, and equal to
    sim._quorum_index on each owner's [P, G] plane."""
    rng = random.Random(f"{width}-{layout}-{kind}")
    n_groups = 24
    n = width * n_groups if layout == "owner_planes" else 120
    rows = quorum_rows(kind, width, rng, n)
    mask = np.stack([r[0] for r in rows])
    matched = np.stack([r[1] for r in rows])
    inf = int(kernels.INF)  # the kernel's spelling of the oracle's U64_MAX
    want = np.array(
        [min(scalar_committed(m, a)[0], inf) for m, a in rows], dtype=np.int32
    )
    if kind == "empty":
        assert (want == inf).all()
    if layout == "rows":
        got = kernels.committed_index(jnp.asarray(matched), jnp.asarray(mask))
        np.testing.assert_array_equal(np.asarray(got), want)
        return
    # [P_owner, P_target, G] as the damped round and the audits hold it.
    matched3 = jnp.asarray(
        matched.reshape(width, n_groups, width).transpose(0, 2, 1)
    )
    mask3 = jnp.asarray(mask.reshape(width, n_groups, width).transpose(0, 2, 1))
    got = kernels.committed_index(
        jnp.swapaxes(matched3, 1, 2), jnp.swapaxes(mask3, 1, 2)
    )
    assert got.shape == (width, n_groups)
    np.testing.assert_array_equal(
        np.asarray(got), want.reshape(width, n_groups)
    )
    for owner in range(width):
        np.testing.assert_array_equal(
            np.asarray(sim._quorum_index(matched3[owner], mask3[owner])),
            np.asarray(got[owner]),
        )


def test_joint_committed_index_parity():
    rng = random.Random(8)
    inc, out, matcheds, want = [], [], [], []
    for _ in range(300):
        imask, matched = make_case(rng)
        n_out = rng.randint(0, P)
        omask = np.zeros(P, dtype=bool)
        omask[rng.sample(range(P), n_out)] = True
        inc.append(imask)
        out.append(omask)
        matcheds.append(matched)
        voters_i = [i + 1 for i in range(P) if imask[i]]
        voters_o = [i + 1 for i in range(P) if omask[i]]
        l = AckIndexer({i + 1: Index(index=int(matched[i])) for i in range(P)})
        joint = JointConfig.from_majorities(
            MajorityConfig(voters_i), MajorityConfig(voters_o)
        )
        w = joint.committed_index(False, l)[0]
        want.append(min(w, 2**31 - 1))
    got = kernels.joint_committed_index(
        jnp.asarray(np.stack(matcheds)),
        jnp.asarray(np.stack(inc)),
        jnp.asarray(np.stack(out)),
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want, dtype=np.int32))


def test_committed_index_grouped_parity():
    rng = random.Random(9)
    masks, matcheds, groups, want_idx, want_flag = [], [], [], [], []
    for _ in range(400):
        mask, matched = make_case(rng)
        g = np.array([rng.randint(0, 3) for _ in range(P)], dtype=np.int32)
        masks.append(mask)
        matcheds.append(matched)
        groups.append(g)
        wi, wf = scalar_committed(mask, matched, groups=g, use_gc=True)
        want_idx.append(min(wi, 2**31 - 1))
        want_flag.append(wf)
    got_idx, got_flag = kernels.committed_index_grouped(
        jnp.asarray(np.stack(matcheds)),
        jnp.asarray(np.stack(groups)),
        jnp.asarray(np.stack(masks)),
    )
    np.testing.assert_array_equal(
        np.asarray(got_idx), np.asarray(want_idx, dtype=np.int32)
    )
    np.testing.assert_array_equal(np.asarray(got_flag), np.asarray(want_flag))


def test_vote_result_parity():
    rng = random.Random(10)
    masks, gr, rj, want = [], [], [], []
    for _ in range(300):
        mask, _ = make_case(rng)
        granted = np.zeros(P, dtype=bool)
        rejected = np.zeros(P, dtype=bool)
        votes = {}
        for i in range(P):
            r = rng.random()
            if r < 0.4:
                granted[i] = True
                votes[i + 1] = True
            elif r < 0.7:
                rejected[i] = True
                votes[i + 1] = False
        masks.append(mask)
        gr.append(granted)
        rj.append(rejected)
        voters = [i + 1 for i in range(P) if mask[i]]
        want.append(int(MajorityConfig(voters).vote_result(lambda id: votes.get(id))))
    got = kernels.vote_result(
        jnp.asarray(np.stack(gr)), jnp.asarray(np.stack(rj)), jnp.asarray(np.stack(masks))
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want, dtype=np.int32))


def test_timeout_draw_parity():
    keys = np.arange(1, 257, dtype=np.uint32)
    epochs = np.arange(1, 257, dtype=np.uint32)
    lo, hi = 10, 20
    got = kernels.timeout_draw(
        jnp.asarray(keys),
        jnp.asarray(epochs),
        jnp.full(keys.shape, lo, jnp.int32),
        jnp.full(keys.shape, hi, jnp.int32),
    )
    want = [deterministic_timeout(int(k), int(e), lo, hi) for k, e in zip(keys, epochs)]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want, dtype=np.int32))


def test_majority_of_matches_scalar_quorum():
    counts = jnp.arange(1, 16, dtype=jnp.int32)
    got = kernels.majority_of(counts)
    want = [n // 2 + 1 for n in range(1, 16)]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want, np.int32))


def test_joint_vote_result_parity():
    """reference: joint.rs:56-67 — win both halves / lose either / else
    pending, checked against JointConfig.vote_result on random tallies."""
    rng = random.Random(11)
    inc, out, gr, rj, want = [], [], [], [], []
    for _ in range(300):
        imask, _ = make_case(rng)
        omask = np.zeros(P, dtype=bool)
        omask[rng.sample(range(P), rng.randint(0, P))] = True
        granted = np.zeros(P, dtype=bool)
        rejected = np.zeros(P, dtype=bool)
        votes = {}
        for i in range(P):
            r = rng.random()
            if r < 0.4:
                granted[i] = True
                votes[i + 1] = True
            elif r < 0.7:
                rejected[i] = True
                votes[i + 1] = False
        inc.append(imask)
        out.append(omask)
        gr.append(granted)
        rj.append(rejected)
        joint = JointConfig.from_majorities(
            MajorityConfig([i + 1 for i in range(P) if imask[i]]),
            MajorityConfig([i + 1 for i in range(P) if omask[i]]),
        )
        want.append(int(joint.vote_result(lambda id: votes.get(id))))
    got = kernels.joint_vote_result(
        jnp.asarray(np.stack(gr)),
        jnp.asarray(np.stack(rj)),
        jnp.asarray(np.stack(inc)),
        jnp.asarray(np.stack(out)),
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want, dtype=np.int32))


def test_append_response_update_matches_progress_maybe_update():
    """Batched Progress.maybe_update oracle check (reference:
    progress.rs:138-150): matched/next advance monotonically, only under
    the response mask."""
    from raft_tpu.tracker import Progress

    rng = random.Random(12)
    matched = np.array([rng.randint(0, 50) for _ in range(P)], np.int32)
    next_idx = matched + 1
    resp_index = np.array([rng.randint(0, 80) for _ in range(P)], np.int32)
    resp_mask = np.array([rng.random() < 0.7 for _ in range(P)], bool)
    got_m, got_n = kernels.append_response_update(
        jnp.asarray(matched),
        jnp.asarray(next_idx),
        jnp.asarray(resp_index),
        jnp.asarray(resp_mask),
    )
    for i in range(P):
        pr = Progress(int(next_idx[i]), 10)
        pr.matched = int(matched[i])
        if resp_mask[i]:
            pr.maybe_update(int(resp_index[i]))
        assert int(got_m[i]) == pr.matched
        assert int(got_n[i]) == pr.next_idx


def test_zero_counters_and_count_events_fold():
    """The device counter plane: zero_counters starts all-zero int32;
    count_events folds per-round event masks additively."""
    ctrs = kernels.zero_counters()
    assert ctrs.shape == (kernels.N_COUNTERS,)
    assert ctrs.dtype == jnp.int32
    assert int(ctrs.sum()) == 0
    campaign = jnp.asarray([[True, False], [True, True]])
    beat = jnp.asarray([[False, False], [True, False]])
    won = jnp.asarray([[True, False], [False, False]])
    delta = jnp.asarray([[2, 0], [1, 3]], jnp.int32)
    out = kernels.count_events(ctrs, campaign, beat, won, delta)
    out = kernels.count_events(out, campaign, beat, won, delta)  # additive
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray([6, 2, 2, 12], np.int32)
    )


def test_tick_kernel_matches_scalar_counters():
    """Tick a batch with mixed roles and verify the counter/mask semantics
    against hand-computed expectations (reference: raft.rs:1024-1079)."""
    state = jnp.asarray([0, 2, 0, 2, 1], jnp.int32)  # F, L, F, L, C
    ee = jnp.asarray([8, 9, 3, 2, 8], jnp.int32)
    hb = jnp.asarray([0, 1, 0, 0, 0], jnp.int32)
    rt = jnp.asarray([9, 99, 99, 99, 9], jnp.int32)
    promotable = jnp.asarray([True, True, True, True, False])
    ee2, hb2, campaign, heartbeat, checkq = kernels.tick_kernel(
        state, ee, hb, rt, promotable, election_timeout=10, heartbeat_timeout=2
    )
    # follower 0: 8->9 >= rt 9, promotable -> campaign, ee reset
    assert bool(campaign[0]) and int(ee2[0]) == 0
    # leader 1: ee 9->10 >= 10 -> check quorum, ee reset; hb 1->2 >= 2 -> beat
    assert bool(checkq[1]) and bool(heartbeat[1])
    assert int(ee2[1]) == 0 and int(hb2[1]) == 0
    # follower 2: no timeout
    assert not bool(campaign[2]) and int(ee2[2]) == 4
    # leader 3: no timeouts, hb 0->1 < 2
    assert not bool(heartbeat[3]) and int(hb2[3]) == 1
    # candidate 4: timeout but not promotable
    assert not bool(campaign[4]) and int(ee2[4]) == 9


def test_device_plane_dtypes_stay_int32():
    """Regression for the GC007 x64-widening fixes: every value a kernel
    hands back toward the planes/host boundary is int32 regardless of
    backend flags (a bare jnp.sum would widen to int64 under x64 — caught
    statically by graftcheck --engine, pinned at runtime here)."""
    import jax.numpy as jnp

    from raft_tpu.multiraft import kernels

    ctrs = kernels.zero_counters()
    mask = jnp.zeros((3, 4), bool)
    delta = jnp.zeros((3, 4), jnp.int32)
    out = kernels.count_events(ctrs, mask, mask, mask, delta)
    assert out.dtype == jnp.int32

    planes = kernels.zero_health(8)
    counts, hist, ids, scores = kernels.health_summary(planes, 2, 4, 3, 4)
    for arr in (counts, hist, ids, scores):
        assert arr.dtype == jnp.int32

    # Chaos kernels: the loss sample is bool, the safety counts int32.
    loss = jnp.zeros((2, 2, 8), jnp.int32)
    assert kernels.link_loss_draw(jnp.int32(3), loss).dtype == jnp.bool_
    pg = jnp.zeros((2, 8), jnp.int32)
    pp = jnp.zeros((2, 2, 8), jnp.int32)
    assert kernels.check_safety(pg, pg, pg, pg, pp, pg).dtype == jnp.int32

    # Packed planes (GC008 PACKED_PLANES): words are uint32, unpacking
    # restores the registered lane dtypes (bool / int32) exactly.
    bools = jnp.zeros((5, 8), bool)
    words = kernels.pack_bits(bools)
    assert words.dtype == jnp.uint32
    assert kernels.unpack_bits(words, 5).dtype == jnp.bool_
    vals = jnp.zeros((5, 8), jnp.int32)
    pw = kernels.pack_u16_pairs(vals)
    assert pw.dtype == jnp.uint32
    assert kernels.unpack_u16_pairs(pw, 5).dtype == jnp.int32

    # The compiled chaos schedule stores ONLY packed words + int32 planes.
    from raft_tpu.multiraft import chaos

    plan = chaos.plan_from_dict(
        {
            "name": "t",
            "peers": 3,
            "phases": [
                {"rounds": 2, "partition": [[1], [2, 3]], "crash": [2],
                 "loss_all": 0.25, "append": 1},
            ],
        }
    )
    compiled = chaos.compile_plan(plan, 8)
    assert compiled.phase_of_round.dtype == jnp.int32
    assert compiled.link_packed.dtype == jnp.uint32
    assert compiled.loss_packed.dtype == jnp.uint32
    assert compiled.crashed_packed.dtype == jnp.uint32
    assert compiled.append.dtype == jnp.int32


def test_pack_bits_roundtrip_and_numpy_twin():
    """pack_bits/unpack_bits: exact round-trip at widths spanning multiple
    words, bit layout pinned against the obvious numpy twin."""
    rng = np.random.RandomState(11)
    for k in (1, 5, 25, 31, 32, 33, 64):
        planes = rng.rand(k, 13) < 0.4
        words = kernels.pack_bits(jnp.asarray(planes))
        assert words.shape == ((k + 31) // 32, 13)
        # numpy twin: word w bit j <- plane 32w + j
        twin = np.zeros(((k + 31) // 32, 13), np.uint32)
        for j in range(k):
            twin[j // 32] |= planes[j].astype(np.uint32) << np.uint32(j % 32)
        assert np.array_equal(np.asarray(words), twin)
        back = kernels.unpack_bits(words, k)
        assert np.array_equal(np.asarray(back), planes)


def test_pack_bits_g_roundtrip_and_simref_twin():
    """pack_bits_g/unpack_bits_g (the recent_active scan-carry packing,
    32:1 along the GROUP axis): exact round-trip at widths spanning word
    boundaries, bit-identical to the simref numpy twins — the GC010
    oracle for the `bits_g` PACKED_PLANES family."""
    from raft_tpu.multiraft import simref

    rng = np.random.RandomState(13)
    for shape in ((3, 3, 5), (2, 31), (2, 32), (2, 33), (1, 64), (4, 95)):
        plane = rng.rand(*shape) < 0.4
        words = kernels.pack_bits_g(jnp.asarray(plane))
        g = shape[-1]
        assert words.shape == shape[:-1] + ((g + 31) // 32,)
        assert words.dtype == jnp.uint32
        twin = simref.host_pack_bits_g(plane)
        assert np.array_equal(np.asarray(words), twin), shape
        back = kernels.unpack_bits_g(words, g)
        assert back.dtype == jnp.bool_
        assert np.array_equal(np.asarray(back), plane), shape
        assert np.array_equal(
            simref.host_unpack_bits_g(twin, g), plane
        ), shape


def test_cq_boundary_safe_conditions():
    """cq_boundary_safe (the damping half of the fused steady predicate)
    against its scalar reasoning: leader-row active quorum now, alive
    voters a quorum of each half, and crashed stale leaders clear of
    their free-running boundary."""
    G, P = 4, 3
    ra = np.zeros((P, P, G), bool)
    vm = np.ones((P, G), bool)
    om = np.zeros((P, G), bool)
    state = np.zeros((P, G), np.int64)
    state[0, :] = kernels.ROLE_LEADER
    crashed = np.zeros((P, G), bool)
    ee = np.zeros((P, G), np.int64)

    def safe(**over):
        args = dict(ra=ra, vm=vm, om=om, state=state, crashed=crashed,
                    ee=ee)
        args.update(over)
        return np.asarray(
            kernels.cq_boundary_safe(
                jnp.asarray(args["ra"]), jnp.asarray(args["vm"]),
                jnp.asarray(args["om"]),
                jnp.asarray(args["state"], dtype=jnp.int32),
                jnp.asarray(args["crashed"]),
                jnp.asarray(args["ee"], dtype=jnp.int32),
                horizon=4, election_tick=10,
            )
        )

    # empty leader row: only self active -> 1 of 3 < quorum -> unsafe
    assert not safe().any()
    # one ack -> 2 of 3 >= quorum for the leader -> safe everywhere
    ra2 = ra.copy()
    ra2[0, 1, :] = True
    assert safe(ra=ra2).all()
    # alive voters below quorum (two crashed followers): the row may be
    # saturated NOW but cannot re-saturate after the next clear
    cr2 = crashed.copy()
    cr2[1:, 0] = True
    ra3 = ra2.copy()
    ra3[0, 2, :] = True
    got = safe(ra=ra3, crashed=cr2)
    assert not got[0] and got[1:].all()
    # a crashed stale role-leader near its boundary poisons its group
    st2 = state.copy()
    cr3 = crashed.copy()
    st2[2, 1] = kernels.ROLE_LEADER
    cr3[2, 1] = True
    ee2 = ee.copy()
    ee2[2, 1] = 7  # 7 + horizon(4) >= election_tick(10)
    got = safe(ra=ra2, state=st2, crashed=cr3, ee=ee2)
    assert not got[1] and got[[0, 2, 3]].all()
    # ...but a stale leader far from its boundary is fine
    ee2[2, 1] = 3
    assert safe(ra=ra2, state=st2, crashed=cr3, ee=ee2).all()
    # joint config: BOTH halves need an alive quorum
    vm2 = np.zeros((P, G), bool)
    vm2[:2] = True
    om2 = np.zeros((P, G), bool)
    om2[1:] = True
    ra4 = np.zeros((P, P, G), bool)
    ra4[0, 1, :] = True  # incoming {1,2} active; outgoing {2,3} not
    got = safe(ra=ra4, vm=vm2, om=om2)
    assert not got.any()
    ra4[0, 2, :] = True
    assert safe(ra=ra4, vm=vm2, om=om2).all()


def test_pack_u16_pairs_roundtrip_and_numpy_twin():
    rng = np.random.RandomState(12)
    for k in (1, 2, 5, 25):
        vals = rng.randint(0, 1 << 16, size=(k, 9)).astype(np.int32)
        words = kernels.pack_u16_pairs(jnp.asarray(vals))
        assert words.shape == ((k + 1) // 2, 9)
        twin = np.zeros(((k + 1) // 2, 9), np.uint32)
        for j in range(k):
            twin[j // 2] |= vals[j].astype(np.uint32) << np.uint32(
                16 * (j % 2)
            )
        assert np.array_equal(np.asarray(words), twin)
        back = kernels.unpack_u16_pairs(words, k)
        assert np.array_equal(np.asarray(back), vals)


def _stacked_unpack_bits(words, k):
    """unpack_bits as it was before ISSUE 48: k rows built one by one and
    stacked — the reference the broadcast-shift form is held to."""
    return jnp.stack([
        ((words[j // 32] >> (j % 32)) & jnp.uint32(1)) != 0 for j in range(k)
    ])


def _stacked_unpack_u16_pairs(words, k):
    """unpack_u16_pairs as it was before ISSUE 48 (stacked rows)."""
    planes = []
    for j in range(k):
        half = words[j // 2] >> (16 * (j % 2))
        planes.append((half & jnp.uint32(0xFFFF)).astype(jnp.int32))
    return jnp.stack(planes)


@pytest.mark.parametrize("k", [5, 9, 25, 32, 33, 49])
@pytest.mark.parametrize("form", ["bits", "u16_pairs"])
def test_unpack_equals_the_stacked_rows_reference(form, k):
    """One word, the word boundary, two words: the broadcast-shift unpack
    gives the stacked rows' values, shape and dtype for every k, on random
    words (every bit of the last word set or not, the bits past k too) and
    with trailing axes, eagerly and under jit — and its jaxpr stacks
    nothing."""
    unpack, ref, per = {
        "bits": (kernels.unpack_bits, _stacked_unpack_bits, 32),
        "u16_pairs": (kernels.unpack_u16_pairs, _stacked_unpack_u16_pairs, 2),
    }[form]
    rng = np.random.RandomState(48 + k)
    n_words = (k + per - 1) // per
    for tail in ((7,), (3, 7)):
        words = jnp.asarray(
            rng.randint(0, 1 << 32, size=(n_words,) + tail, dtype=np.uint64)
            .astype(np.uint32)
        )
        want = ref(words, k)
        for got in (unpack(words, k), jax.jit(unpack, static_argnums=1)(words, k)):
            assert got.shape == want.shape == (k,) + tail
            assert got.dtype == want.dtype
            assert np.array_equal(np.asarray(got), np.asarray(want))
    prims = {e.primitive.name for e in jax.make_jaxpr(
        lambda w: unpack(w, k))(words).jaxpr.eqns}
    assert "concatenate" not in prims and "gather" not in prims, prims


def test_committed_index_attribute_is_what_the_round_and_the_audit_call(monkeypatch):
    """The benchmark's commit-quorum control (benchmark/tests/weaken.py)
    swaps `kernels.committed_index` by name and `(matched[..., P],
    voter_mask[..., P])` signature: the damped round's stage folds and the
    safety audit must read that attribute with peer-last operands, and the
    network they share with sim._quorum_index sits below it."""
    import jax

    from raft_tpu.multiraft import SimConfig

    n_groups, width = 8, 3
    shapes = []
    real = kernels.committed_index

    def spy(matched, voter_mask):
        shapes.append((matched.shape, voter_mask.shape))
        return real(matched, voter_mask)

    monkeypatch.setattr(kernels, "committed_index", spy)
    cfg = SimConfig(n_groups, width, check_quorum=True, pre_vote=True)
    st = sim.init_state(cfg)
    crashed = jnp.zeros((width, n_groups), bool)
    append = jnp.ones((n_groups,), jnp.int32)
    jax.make_jaxpr(lambda s: sim.step(cfg, s, crashed, append))(st)
    owner_planes = ((width, n_groups, width),) * 2
    assert shapes == [owner_planes] * 4, "two stage folds, both majorities"
    shapes.clear()
    jax.make_jaxpr(
        lambda s: kernels.check_safety(
            s.state, s.term, s.commit, s.last_index, s.agree, s.commit,
            voter_mask=s.voter_mask, outgoing_mask=s.outgoing_mask,
            matched=s.matched,
        )
    )(st)
    assert shapes == [owner_planes] * 2
