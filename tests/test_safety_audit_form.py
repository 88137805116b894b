"""The safety audit's two forms (ISSUE 52): `kernels.check_safety` runs a
slot at a time where the pairwise planes are small and packed — a word a
peer, the nine counts in one reduction over G — where they are large, and
says, slot for slot, what the nine invariants of its docstring say either
way.

  * parity: `check_safety` (a slot at a time at this G), its packed form
    `_check_safety_packed` (counts) and `check_safety_groups` (the packed
    flags) against a plain per-group NumPy reference written from the
    docstring — loops over groups and peers, a sorted list for the quorum
    index, no code shared with the kernels — on fuzzed, mostly UNREACHABLE
    states that trip every slot, for P in {3, 5} and every optional-argument
    combination a caller uses, plus the hand-made states the packing could
    get wrong (P lease holders in one group, every voter replaced at once,
    every bit of the word set, a violation at peer P - 1 alone);
  * the packed form: the traced graph of the runner's call holds ONE
    `optimization_barrier`, at most seven reductions over a peer axis (three:
    the pair word with the ack count, `prev_high` with `max_alive_term`, the
    bit word with the count word) and exactly ONE over the group axis (a
    slot at a time: sixteen over peer axes — the three `any(axis=(0, 1))`
    among them — and nine over G), no `transpose` (a slot at a time
    transposes `matched` and both masks for its quorum networks), and nothing
    wider than 32 bits under `jax_enable_x64` (GC007);
  * the choice: by the bytes of `agree`, at the two fleet sizes that have a
    chip reading.

Fuzzed planes are non-negative, as every index and term plane of the fleet
is: the quorum index the docstring states ("the majority()-th largest
matched among voters") is what `kernels._quorum_of_rows` computes only there
(its zero padding).
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.multiraft import kernels

G = 24
LEADER, FOLLOWER = kernels.ROLE_LEADER, kernels.ROLE_FOLLOWER
BASE = ("state", "term", "commit", "last_index", "agree", "prev_commit")
JOINT = ("voter_mask", "outgoing_mask", "matched")
PREV = ("prev_voter_mask", "prev_outgoing_mask")
OPTIONAL = JOINT + ("crashed",) + PREV + ("lease_holder", "lease_fire")
# What a caller passes beside the six positional planes: the chaos-only
# runner and the plain fuzz suites; the reconfig suites; with the chaos
# plan's crash plane; runner._tail_audit / autopilot (no crash plane); the
# reconfig runner; the workload runner without and with a read firing.
COMBOS = {
    "six": (),
    "joint": JOINT,
    "joint+crashed": JOINT + ("crashed",),
    "joint+prev": JOINT + PREV,
    "joint+crashed+prev": JOINT + ("crashed",) + PREV,
    "+holder": JOINT + ("crashed",) + PREV + ("lease_holder",),
    "+holder+fire": JOINT + ("crashed",) + PREV + ("lease_holder", "lease_fire"),
}
INF = 2**31 - 1


def reference(d, names):
    """bool[N_SAFETY, G] off the docstring of kernels.check_safety: one
    group at a time, one invariant at a time."""
    has = lambda k: k in names
    P, n_groups = d["state"].shape
    out = np.zeros((kernels.N_SAFETY, n_groups), bool)
    peers = range(P)
    pairs = [(a, b) for a in peers for b in peers if a != b]
    for g in range(n_groups):
        state, term = d["state"][:, g], d["term"][:, g]
        commit, last = d["commit"][:, g], d["last_index"][:, g]
        agree, prev = d["agree"][:, :, g], d["prev_commit"][:, g]
        leaders = [p for p in peers if state[p] == LEADER]
        out[kernels.SV_DUAL_LEADER, g] = any(
            term[a] == term[b] for a in leaders for b in leaders if a != b
        )
        out[kernels.SV_COMMIT_DIVERGED, g] = any(
            min(commit[a], commit[b]) > agree[a, b] for a, b in pairs
        )
        out[kernels.SV_COMMIT_REGRESSED, g] = any(commit[p] < prev[p] for p in peers)
        out[kernels.SV_CURSOR_INVALID, g] = any(
            commit[p] > last[p] for p in peers
        ) or any(agree[a, b] > min(last[a], last[b]) for a, b in pairs)
        if has("voter_mask"):
            voter, outgoing = d["voter_mask"][:, g], d["outgoing_mask"][:, g]
            matched = d["matched"][:, :, g]
            out[kernels.SV_LEADER_NOT_IN_CONFIG, g] = any(
                state[p] != FOLLOWER and not (voter[p] or outgoing[p]) for p in peers
            )
            alive = [not d["crashed"][p, g] if has("crashed") else True for p in peers]
            alive_terms = [term[p] for p in leaders if alive[p]]
            top = max(alive_terms) if alive_terms else -1
            checked = [p for p in leaders if not alive[p] or term[p] == top]

            def quorum(owner, mask):
                acked = sorted((matched[owner, t] for t in peers if mask[t]), reverse=True)
                return acked[len(acked) // 2] if acked else INF

            out[kernels.SV_COMMIT_NO_QUORUM, g] = any(
                commit[p] > max(prev)
                and commit[p] > min(quorum(p, voter), quorum(p, outgoing))
                for p in checked
            )
        if has("prev_voter_mask"):
            was_voter, was_outgoing = d["prev_voter_mask"][:, g], d["prev_outgoing_mask"][:, g]
            was_joint, now_joint = was_outgoing.any(), outgoing.any()
            changed = int((was_voter != voter).sum())
            if not was_joint and now_joint:  # entering: outgoing = the old incoming
                bad = (outgoing != was_voter).any()
            elif was_joint and not now_joint:  # leaving: incoming untouched
                bad = changed > 0
            elif was_joint and now_joint:  # while joint nothing moves
                bad = changed > 0 or (was_outgoing != outgoing).any()
            else:  # a simple change: one voter at most
                bad = changed > 1
            out[kernels.SV_CONF_DOUBLE_CHANGE, g] = bad
        if has("lease_holder"):
            holder = d["lease_holder"][:, g]
            out[kernels.SV_DUAL_LEASE, g] = holder.sum() >= 2
            if has("lease_fire"):
                out[kernels.SV_STALE_READ, g] = d["lease_fire"][g] and any(
                    holder[p] and prev[p] < max(prev) for p in peers
                )
    return out


def fuzzed(P, seed):
    """Half the groups hold random planes (most break several invariants at
    once), the other half a sound fleet with a cell in twelve overwritten."""
    rng = np.random.default_rng([P, seed])
    hi = 3 if seed % 2 else 6
    ints = lambda *shape: rng.integers(0, hi, shape).astype(np.int32)
    joint_now = rng.random((1, G)) < 0.5
    wild = {
        "state": rng.integers(0, 4, (P, G)).astype(np.int32),
        "term": rng.integers(0, 3, (P, G)).astype(np.int32),
        "commit": ints(P, G), "last_index": ints(P, G), "agree": ints(P, P, G),
        "prev_commit": ints(P, G), "matched": ints(P, P, G),
        "voter_mask": (rng.random((P, G)) < 0.6) & (rng.random((1, G)) < 0.9),
        "outgoing_mask": (rng.random((P, G)) < 0.4) & joint_now,
        "crashed": rng.random((P, G)) < 0.2,
        "lease_holder": rng.random((P, G)) < 0.3,
        "lease_fire": rng.random((G,)) < 0.5,
    }
    wild["prev_voter_mask"] = wild["voter_mask"] ^ (rng.random((P, G)) < 0.15)
    wild["prev_outgoing_mask"] = (
        wild["outgoing_mask"] ^ (rng.random((P, G)) < 0.1)
    ) & (rng.random((1, G)) < 0.7)
    d = sound(P)
    for name, plane in d.items():
        overwrite = rng.random(plane.shape) < 1 / 12
        overwrite[..., : G // 2] = True
        d[name] = np.where(overwrite, wild[name], plane)
    return d


def sound(P):
    """A healthy fleet: peer 0 leads at term 3, everything replicated and
    committed at 5, a simple configuration, peer 0 holds the one lease."""
    full = lambda v: np.full((P, G), v, np.int32)
    state = full(FOLLOWER)
    state[0] = LEADER
    holder = np.zeros((P, G), bool)
    holder[0] = True
    return {
        "state": state, "term": full(3), "commit": full(5), "last_index": full(5),
        "agree": np.full((P, P, G), 5, np.int32), "prev_commit": full(5),
        "matched": np.full((P, P, G), 5, np.int32),
        "voter_mask": np.ones((P, G), bool), "outgoing_mask": np.zeros((P, G), bool),
        "crashed": np.zeros((P, G), bool),
        "prev_voter_mask": np.ones((P, G), bool),
        "prev_outgoing_mask": np.zeros((P, G), bool),
        "lease_holder": holder, "lease_fire": np.ones((G,), bool),
    }


def every_peer_holds_a_lease(P):
    d = sound(P)
    d["lease_holder"][:, 3] = True  # P holders: the count must not wrap a field
    d["lease_holder"][:2, 4] = True  # two
    return d, {kernels.SV_DUAL_LEASE: 2}


def every_voter_replaced(P):
    d = sound(P)
    d["prev_voter_mask"][:, 1] = False  # vm_delta = P outside joint
    d["prev_voter_mask"][:, 2] = False  # ... and while leaving joint
    d["prev_outgoing_mask"][0, 2] = True
    d["prev_voter_mask"][0, 5] = False  # one voter: legal
    return d, {kernels.SV_CONF_DOUBLE_CHANGE: 2}


def every_bit_at_once(P):
    """Group 7 breaks all nine invariants, every fact of the word true in
    it; its neighbours stay sound."""
    d = sound(P)
    g = 7
    d["state"][:, g] = LEADER  # dual leader
    d["commit"][:, g] = 9  # past last_index: invalid; past agree: diverged
    d["prev_commit"][:, g] = 9
    d["prev_commit"][1, g] = 12  # regressed at peer 1, and the fleet's high
    d["commit"][0, g] = 13  # peer 0 advances past it on acks of 5: unbacked
    d["voter_mask"][:, g] = False
    d["voter_mask"][1, g] = True  # peers 0, 2.. lead outside the config
    d["outgoing_mask"][1, g] = True  # entering joint ...
    d["prev_voter_mask"][:, g] = True  # ... with outgoing != the old incoming
    d["lease_holder"][:, g] = True  # dual lease; holders behind 12: stale
    return d, {slot: 1 for slot in range(kernels.N_SAFETY)}


def only_the_last_peer(P):
    """Each group's one violation sits at peer P - 1 (the word's last row,
    the reduce's last operand)."""
    d = sound(P)
    z = P - 1
    d["commit"][z, 0] = 4  # regressed (and nothing else: agree 5 >= min)
    d["commit"][z, 1] = d["last_index"][z, 1] = 6
    d["last_index"][z, 1] = 5  # commit > last_index at peer z alone
    d["agree"][0, z, 1] = 5
    d["voter_mask"][z, 2] = False
    d["prev_voter_mask"][z, 2] = False
    d["state"][z, 2] = 1  # a candidate outside the configuration
    d["state"][0, 3] = FOLLOWER
    d["state"][z, 3] = LEADER  # peer z leads and commits 6 on acks of 5
    d["lease_holder"][0, 3] = False
    d["commit"][z, 3] = d["last_index"][z, 3] = 6
    d["agree"][z, :, 3] = d["agree"][:, z, 3] = 5
    d["agree"][z, z, 3] = 6
    d["lease_holder"][0, 4] = False
    d["lease_holder"][z, 4] = True  # the holder is peer z, behind the fleet
    d["prev_commit"][0, 4] = 5
    d["prev_commit"][z, 4] = 4
    d["commit"][z, 4] = 5
    return d, {
        kernels.SV_COMMIT_REGRESSED: 1, kernels.SV_CURSOR_INVALID: 1,
        kernels.SV_LEADER_NOT_IN_CONFIG: 1, kernels.SV_COMMIT_NO_QUORUM: 1,
        kernels.SV_STALE_READ: 1,
    }


HAND_MADE = (
    every_peer_holds_a_lease, every_voter_replaced, every_bit_at_once, only_the_last_peer,
)


@jax.jit
def _both(pos, kw):
    return (
        kernels.check_safety(*pos, **kw),  # G is small: a slot at a time
        kernels._check_safety_packed(*pos, *(kw.get(k) for k in OPTIONAL)),
        kernels.check_safety_groups(*pos, **kw),
    )


def audit(d, names):
    pos = [jnp.asarray(d[k]) for k in BASE]
    kw = {k: jnp.asarray(d[k]) for k in names}
    by_slot, counts, groups = _both(pos, kw)
    assert by_slot.dtype == jnp.int32 and by_slot.tolist() == counts.tolist()
    assert counts.dtype == jnp.int32 and counts.shape == (kernels.N_SAFETY,)
    assert groups.dtype == jnp.bool_ and groups.shape == (kernels.N_SAFETY, G)
    return np.asarray(counts), np.asarray(groups)


@pytest.mark.parametrize("seed", (0, 1))
@pytest.mark.parametrize("combo", COMBOS)
@pytest.mark.parametrize("P", (3, 5))
def test_fuzzed_states_match_the_reference(P, combo, seed):
    names = COMBOS[combo]
    d = fuzzed(P, seed)
    want = reference(d, names)
    counts, groups = audit(d, names)
    assert np.array_equal(groups, want), [
        kernels.SAFETY_NAMES[s] for s in range(kernels.N_SAFETY)
        if not np.array_equal(groups[s], want[s])
    ]
    assert counts.tolist() == want.sum(axis=1).tolist()
    # a slot the arguments leave inactive stays zero
    active = {
        kernels.SV_LEADER_NOT_IN_CONFIG: "voter_mask", kernels.SV_COMMIT_NO_QUORUM: "voter_mask",
        kernels.SV_CONF_DOUBLE_CHANGE: "prev_voter_mask", kernels.SV_STALE_READ: "lease_fire",
        kernels.SV_DUAL_LEASE: "lease_holder",
    }
    for slot, needs in active.items():
        if needs not in names:
            assert counts[slot] == 0


@pytest.mark.parametrize("P", (3, 5))
def test_the_fuzz_trips_every_slot(P):
    """... on some group AND leaves it clear on another, in both seeds'
    union: a slot the fuzz never trips (or always trips) tests nothing."""
    names = COMBOS["+holder+fire"]
    flags = np.concatenate([reference(fuzzed(P, s), names) for s in (0, 1)], axis=1)
    assert flags.any(axis=1).all() and not flags.all(axis=1).any()


@pytest.mark.parametrize("case", HAND_MADE, ids=lambda f: f.__name__)
@pytest.mark.parametrize("P", (3, 5))
def test_hand_made_states(P, case):
    names = COMBOS["+holder+fire"]
    d, expected = case(P)
    want = reference(d, names)
    assert {s: int(n) for s, n in enumerate(want.sum(axis=1)) if n} == expected, (
        "the case is not what its docstring says"
    )
    counts, groups = audit(d, names)
    assert np.array_equal(groups, want)
    assert counts.tolist() == want.sum(axis=1).tolist()


def test_the_sound_fleet_reads_zero():
    for P in (3, 5):
        counts, groups = audit(sound(P), COMBOS["+holder+fire"])
        assert not counts.any() and not groups.any()


# --- the form ---------------------------------------------------------------


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner)


def _runner_call(fn, P=5, n_groups=16):
    """make_jaxpr of `fn` under runner._runner_body's full argument list."""
    i2 = jax.ShapeDtypeStruct((P, n_groups), jnp.int32)
    i3 = jax.ShapeDtypeStruct((P, P, n_groups), jnp.int32)
    b2 = jax.ShapeDtypeStruct((P, n_groups), jnp.bool_)
    b1 = jax.ShapeDtypeStruct((n_groups,), jnp.bool_)

    def call(state, term, commit, last, agree, prev, vm, om, matched, crashed,
             pvm, pom, holder, fire):
        return fn(
            state, term, commit, last, agree, prev, voter_mask=vm,
            outgoing_mask=om, matched=matched, crashed=crashed,
            prev_voter_mask=pvm, prev_outgoing_mask=pom, lease_holder=holder,
            lease_fire=fire,
        )

    return jax.make_jaxpr(call)(i2, i2, i2, i2, i3, i2, b2, b2, i3, b2, b2, b2, b2, b1)


def _census(closed):
    names = [e.primitive.name for e in _walk(closed.jaxpr)]
    over_peers = over_groups = 0
    for eqn in _walk(closed.jaxpr):
        name = eqn.primitive.name
        if not name.startswith("reduce") or name == "reduce_precision":
            continue
        axes = eqn.params.get("axes", eqn.params.get("dimensions"))
        if eqn.invars[0].aval.ndim - 1 in axes:
            over_groups += 1
        else:
            over_peers += 1
    return names, over_peers, over_groups


def _barriers(names):
    return names.count("optimization_barrier")


def test_packed_one_barrier_three_reduces_over_the_peers_one_over_the_groups():
    names, over_peers, over_groups = _census(_runner_call(kernels._check_safety_packed))
    assert _barriers(names) == 1
    assert over_groups == 1
    assert 1 <= over_peers <= 7
    assert "transpose" not in names
    # the per-group twin is the same core: the same barrier and peer-axis
    # reductions, and none over G (it hands the flags on)
    names_g, peers_g, groups_g = _census(_runner_call(kernels.check_safety_groups))
    assert (_barriers(names_g), peers_g, groups_g) == (1, over_peers, 0)


@pytest.mark.parametrize("P,n_groups,packed", [(5, 100_000, False), (3, 1_000_000, True)])
def test_the_form_follows_the_bytes_of_the_pairwise_plane(P, n_groups, packed):
    """The two fleet sizes with a chip reading (PERF.md section 6, PR 52):
    10 MB of `agree` keeps the program it had — no packed variant stayed
    inside the 2% bound in both `fleet-100k-r5-stock.outage` and
    `fleet-100k-r5.serve` — and 36 MB runs packed.  Whoever moves
    `_AUDIT_PACKED_MIN_BYTES` reads a cell on each side of it on the chip."""
    names, _, over_groups = _census(_runner_call(kernels.check_safety, P, n_groups))
    assert (_barriers(names), over_groups) == ((1, 1) if packed else (0, 9))


@pytest.mark.parametrize("combo", COMBOS)
def test_nothing_wider_than_32_bits_under_x64(combo):
    names = COMBOS[combo]
    d = fuzzed(3, 0)
    with jax.enable_x64(True):
        pos = [jnp.asarray(d[k], jnp.int32 if d[k].dtype != bool else bool) for k in BASE]
        kw = {k: jnp.asarray(d[k], jnp.int32 if d[k].dtype != bool else bool) for k in names}
        closed = jax.make_jaxpr(lambda pos, kw: _both.__wrapped__(pos, kw))(pos, kw)
        wide = [
            (e.primitive.name, str(v.aval))
            for e in _walk(closed.jaxpr) for v in e.outvars
            if getattr(v.aval, "dtype", None) is not None
            and v.aval.dtype.itemsize > 4
            and G in v.aval.shape  # jnp.eye's [P, P] iota is a constant
        ]
        assert not wide, wide[:5]
        by_slot, counts, groups = closed.out_avals
        assert by_slot.dtype == counts.dtype == jnp.int32 and groups.dtype == jnp.bool_
