"""Black-box forensics (ISSUE 15): device flight recorder, trigger
capture, and the trap-to-testcase pipeline.

The negative end-to-end tests replay the two injected traps with the
black box on — the PR 13 clock-pause stale-read trap and the PR 5
stale-commit-propagation class — and assert (a) the captured group ids
are EXACTLY the injected offenders, (b) the generated datadriven repro
replays RED on the one-group scalar oracle, and (c) it flips green once
the trap directives are disabled.  The kernel-level tests pin the
check_safety_groups <-> check_safety slot-for-slot equality (the twin's
drift closure), the packed-meta round trip, the first-K-stable capture
against a host argsort, and the ring/window decode.

Tier-1 keeps the G=8 commit-regress case (plain-path compile) and the
G=2 clock-pause case (one damped-wave compile); the G>=32 variants are
slow-marked (ROADMAP.md's standing constraint on G >= 32 cases;
tier-1 takes 247 s of its 1470 s limit under xdist -n 6 at PR 32).
"""

import os

import numpy as np
import jax.numpy as jnp
import pytest

from raft_tpu.datadriven import run_test, walk
from raft_tpu.multiraft import SimConfig, checkpoint, forensics, kernels
from raft_tpu.multiraft import sim as sim_mod
from raft_tpu.multiraft.health import HealthMonitor

TESTDATA = os.path.join(os.path.dirname(__file__), "testdata")


# --- kernel-level: packing, fold, mark, capture ----------------------------


def test_blackbox_meta_roundtrip():
    rng = np.random.RandomState(0)
    role = jnp.asarray(rng.randint(0, 4, size=17), jnp.int32)
    lead = jnp.asarray(rng.randint(0, 9, size=17), jnp.int32)
    bits = jnp.asarray(
        rng.randint(0, 1 << kernels.N_SAFETY, size=17), jnp.uint32
    )
    word = kernels.pack_blackbox_meta(role, lead, bits)
    r2, l2, b2 = kernels.unpack_blackbox_meta(word)
    assert np.array_equal(np.asarray(r2), np.asarray(role))
    assert np.array_equal(np.asarray(l2), np.asarray(lead))
    assert np.array_equal(np.asarray(b2), np.asarray(bits))


def test_blackbox_fold_ring_and_trip():
    G, P, W = 5, 3, 4
    meta, term_r, commit_r, trip, ridx = kernels.zero_blackbox(G, W)
    rng = np.random.RandomState(1)
    # Fold W + 2 rounds so the ring wraps; track the expected window.
    expect = []
    for r in range(W + 2):
        state = jnp.asarray(rng.randint(0, 3, size=(P, G)), jnp.int32)
        term = jnp.asarray(rng.randint(1, 9, size=(P, G)), jnp.int32)
        commit = jnp.asarray(rng.randint(0, 50, size=(P, G)), jnp.int32)
        crashed = jnp.zeros((P, G), bool)
        viol = np.zeros((kernels.N_SAFETY, G), bool)
        if r == 2:
            viol[kernels.SV_DUAL_LEADER, 3] = True
        if r == W + 1:
            viol[kernels.SV_COMMIT_REGRESSED, 0] = True
            viol[kernels.SV_COMMIT_REGRESSED, 4] = True
        meta, term_r, commit_r, trip, ridx = kernels.blackbox_fold(
            meta, term_r, commit_r, trip, ridx,
            state, term, commit, crashed, jnp.asarray(viol),
        )
        expect.append((np.asarray(term).max(axis=0),
                       np.asarray(commit).max(axis=0), viol))
    assert int(ridx) == W + 2
    # Window decode matches the last W folded rounds, per group.
    for g in range(G):
        win = forensics.decode_window(
            np.asarray(meta)[:, g], np.asarray(term_r)[:, g],
            np.asarray(commit_r)[:, g], W + 2,
        )
        assert [rec["round"] for rec in win] == list(range(2, W + 2))
        for rec in win:
            t_exp, c_exp, viol_exp = expect[rec["round"]]
            assert rec["term"] == t_exp[g]
            assert rec["commit"] == c_exp[g]
            fired = [
                kernels.SAFETY_NAMES[s]
                for s in range(kernels.N_SAFETY)
                if viol_exp[s, g]
            ]
            assert rec["fired"] == fired
    # Trip plane: first trip rounds survive the ring wrap.
    trip_h = np.asarray(trip)
    assert trip_h[kernels.SV_DUAL_LEADER, 3] == 2
    assert trip_h[kernels.SV_COMMIT_REGRESSED, 0] == W + 1
    assert trip_h[kernels.SV_COMMIT_REGRESSED, 4] == W + 1
    assert (trip_h[kernels.SV_STALE_READ] == int(kernels.INF)).all()


def test_blackbox_mark_stamps_last_round():
    """blackbox_mark (the ad-hoc audit path) ORs the fired bits onto the
    LAST folded round's ring slot and min-folds the trip plane —
    equivalent to having passed the mask to blackbox_fold."""
    G, P, W = 4, 3, 4
    meta, term_r, commit_r, trip, ridx = kernels.zero_blackbox(G, W)
    state = jnp.zeros((P, G), jnp.int32)
    term = jnp.ones((P, G), jnp.int32)
    commit = jnp.ones((P, G), jnp.int32)
    crashed = jnp.zeros((P, G), bool)
    viol = np.zeros((kernels.N_SAFETY, G), bool)
    viol[kernels.SV_DUAL_LEASE, 2] = True
    none = jnp.zeros((kernels.N_SAFETY, G), bool)
    # Path A: fold with the mask inline.
    a = kernels.blackbox_fold(
        meta, term_r, commit_r, trip, ridx, state, term, commit,
        crashed, jnp.asarray(viol),
    )
    # Path B: fold with no mask, then mark.
    b_meta, b_term, b_commit, b_trip, b_ridx = kernels.blackbox_fold(
        meta, term_r, commit_r, trip, ridx, state, term, commit,
        crashed, none,
    )
    b_meta, b_trip = kernels.blackbox_mark(
        b_meta, b_trip, b_ridx, jnp.asarray(viol)
    )
    for x, y in zip(a, (b_meta, b_term, b_commit, b_trip, b_ridx)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_blackbox_capture_first_k_stable():
    """blackbox_capture's first-K extraction must match a stable host
    argsort by (trip round, group id) — the health_summary tie-break."""
    G, K = 40, 5
    rng = np.random.RandomState(7)
    trip = np.full((kernels.N_SAFETY, G), int(kernels.INF), np.int32)
    # Slot 0: more offenders than K with heavy round ties.
    fired = rng.rand(G) < 0.5
    trip[0, fired] = rng.randint(3, 6, size=int(fired.sum()))
    # Slot 4: fewer than K.
    trip[4, [7, 31]] = [9, 2]
    counts, ids, rounds = kernels.blackbox_capture(
        jnp.asarray(trip), K
    )
    counts, ids, rounds = map(np.asarray, (counts, ids, rounds))
    for s in range(kernels.N_SAFETY):
        want_n = int((trip[s] < int(kernels.INF)).sum())
        assert counts[s] == want_n
        order = np.argsort(trip[s], kind="stable")
        want = [
            (int(g), int(trip[s][g]))
            for g in order[: min(K, want_n)]
        ]
        got = [
            (int(g), int(r))
            for g, r in zip(ids[s], rounds[s])
            if g >= 0
        ]
        assert got == want, f"slot {s}: {got} != {want}"


def _random_safety_args(rng, G, P, with_masks, with_lease):
    state = jnp.asarray(rng.randint(0, 3, size=(P, G)), jnp.int32)
    term = jnp.asarray(rng.randint(1, 5, size=(P, G)), jnp.int32)
    commit = jnp.asarray(rng.randint(0, 20, size=(P, G)), jnp.int32)
    last = commit + jnp.asarray(
        rng.randint(0, 4, size=(P, G)), jnp.int32
    )
    agree = jnp.asarray(rng.randint(0, 22, size=(P, P, G)), jnp.int32)
    prev = commit + jnp.asarray(
        rng.randint(-2, 2, size=(P, G)), jnp.int32
    )
    kw = {}
    if with_masks:
        kw["voter_mask"] = jnp.asarray(rng.rand(P, G) < 0.8, bool)
        kw["outgoing_mask"] = jnp.asarray(rng.rand(P, G) < 0.2, bool)
        kw["matched"] = jnp.asarray(
            rng.randint(0, 22, size=(P, P, G)), jnp.int32
        )
        kw["crashed"] = jnp.asarray(rng.rand(P, G) < 0.2, bool)
        kw["prev_voter_mask"] = jnp.asarray(rng.rand(P, G) < 0.8, bool)
        kw["prev_outgoing_mask"] = jnp.asarray(
            rng.rand(P, G) < 0.2, bool
        )
    if with_lease:
        kw["lease_holder"] = jnp.asarray(rng.rand(P, G) < 0.4, bool)
        kw["lease_fire"] = jnp.asarray(rng.rand(G) < 0.5, bool)
    return (state, term, commit, last, agree, prev), kw


@pytest.mark.parametrize("with_masks,with_lease", [
    (False, False), (True, False), (True, True), (False, True),
])
def test_check_safety_groups_matches_counts(with_masks, with_lease):
    """The forensics twin's slot-wise group sums must equal
    check_safety's counts on arbitrary (including violating) states —
    the machine closure of the standalone-twin drift risk."""
    rng = np.random.RandomState(42)
    for _ in range(10):
        args, kw = _random_safety_args(rng, G=6, P=3,
                                       with_masks=with_masks,
                                       with_lease=with_lease)
        counts = np.asarray(kernels.check_safety(*args, **kw))
        groups = np.asarray(kernels.check_safety_groups(*args, **kw))
        assert groups.shape == (kernels.N_SAFETY, 6)
        assert np.array_equal(groups.sum(axis=1), counts)


# --- the injected traps, end-to-end ---------------------------------------


def _assert_exact_offenders(session, slot, offenders):
    cap = session.sim.forensics()
    got = sorted(o["group"] for o in cap["offenders"][slot])
    assert got == sorted(offenders), (
        f"{slot}: captured {got}, injected {sorted(offenders)}"
    )
    assert cap["counts"][slot] == len(offenders)
    # Every OTHER group stayed clean in every slot.
    for name, offs in cap["offenders"].items():
        for o in offs:
            assert o["group"] in offenders, (
                f"uninjected group {o['group']} tripped {name}"
            )


def test_commit_regress_trap_end_to_end(tmp_path):
    """The PR 5 stale-commit-propagation trap at G=8: exact offender
    capture, a RED scalar repro, green with the trap disabled."""
    session = forensics.run_commit_regress_trap(
        n_groups=8, offenders=[1, 5]
    )
    assert session.safety[kernels.SV_COMMIT_REGRESSED] == 2
    _assert_exact_offenders(session, "commit_regressed", [1, 5])
    out = session.extract(str(tmp_path))
    assert out["slot"] == "commit_regressed"
    assert out["group"] == 1
    assert out["reproduced"], out
    # Zero manual steps: the artifacts exist and the committed-format
    # scenario replays RED standalone...
    red = forensics.replay_scenario(out["scenario_path"])
    assert red["fired"]["commit_regressed"] > 0
    assert red["outcome"] == red["expected"]
    # ...and green once the trap directives are disabled.
    green = forensics.replay_scenario(
        out["scenario_path"], disable_traps=True
    )
    assert not any(green["fired"].values()), green["fired"]
    # The incident JSON is self-contained and schema-tagged.
    import json

    with open(out["incident_path"], encoding="utf-8") as f:
        incident = json.load(f)
    assert incident["schema"] == forensics.SCHEMA
    assert incident["headline"]["group"] == 1
    assert str(out["group"]) in incident["windows"]
    win = incident["windows"][str(out["group"])]
    assert any("commit_regressed" in rec["fired"] for rec in win)


@pytest.mark.slow  # its own damped-wave compile; tier-1 keeps the
# commit-regress G=8 case (plain-path compile) as the end-to-end pin,
# and the committed clock_pause datadriven repro replays scalar-side in
# tier-1 (test_forensics_datadriven) at zero device-compile cost.  The
# CI forensics smoke (tools/forensics_smoke.py) drives this trap every
# build regardless.
def test_clock_pause_trap_end_to_end(tmp_path):
    """The PR 13 clock-pause stale-read trap with the black box on:
    both linearizability slots capture exactly the injected offender,
    and the generated repro replays RED-then-green on the scalar
    oracle."""
    session = forensics.run_clock_pause_trap(n_groups=2, offenders=[1])
    assert session.safety[kernels.SV_STALE_READ] > 0
    assert session.safety[kernels.SV_DUAL_LEASE] > 0
    _assert_exact_offenders(session, "stale_read", [1])
    _assert_exact_offenders(session, "dual_lease", [1])
    out = session.extract(str(tmp_path))
    assert out["slot"] == "stale_read"
    assert out["group"] == 1
    assert out["reproduced"], out
    assert out["fired"]["dual_lease"] > 0
    green = forensics.replay_scenario(
        out["scenario_path"], disable_traps=True
    )
    assert not any(green["fired"].values()), green["fired"]


@pytest.mark.slow  # G=32 scale variants of both traps (fresh compiles)
def test_traps_at_g32():
    offenders = [3, 17, 30]
    s = forensics.run_commit_regress_trap(n_groups=32,
                                          offenders=offenders)
    _assert_exact_offenders(s, "commit_regressed", offenders)
    s2 = forensics.run_clock_pause_trap(n_groups=32, offenders=[5, 21])
    _assert_exact_offenders(s2, "stale_read", [5, 21])
    _assert_exact_offenders(s2, "dual_lease", [5, 21])


# --- the committed golden repros ------------------------------------------


def test_forensics_datadriven():
    """The two committed trap repros (generated by extract_repro, format
    multiraft-incident-v1) replay to their recorded outcomes."""
    ran = []

    def handle(td):
        if td.cmd != "repro":
            raise ValueError(f"unknown command {td.cmd}")
        meta = forensics.meta_from_args(
            {a.key: a.vals for a in td.cmd_args}
        )
        rounds = forensics.parse_rounds(td.input, meta["peers"])
        return forensics.render_outcome(
            meta, forensics.replay(meta, rounds)
        )

    def run(path):
        run_test(path, handle)
        ran.append(path)

    walk(os.path.join(TESTDATA, "forensics"), run)
    assert ran


# --- runner integration: compiled scans fold the same counts ---------------


@pytest.mark.slow  # two chaos-runner scan compiles; the pure-observer
# claim also rides the sharded parity case below and the CI golden
# corpora (which re-run blackbox-on on any safety failure).
def test_chaos_runner_blackbox_counts_match():
    """The blackbox-on chaos scan must produce the identical safety
    counts and scenario report as the blackbox-off scan, while folding
    the trace (pure observer)."""
    from raft_tpu.multiraft import ClusterSim, chaos

    G, P = 8, 3
    plan = chaos.ChaosPlan(
        name="forensics-parity", n_peers=P,
        phases=[
            chaos.ChaosPhase(rounds=10, partition=[[1], [2, 3]],
                             append=1),
            chaos.ChaosPhase(rounds=10, append=1),
        ],
    )
    base = SimConfig(n_groups=G, n_peers=P, collect_health=True)
    off = ClusterSim(base, chaos=plan)
    rep_off = off.run_plan()
    on = ClusterSim(base._replace(blackbox=True), chaos=plan)
    rep_on = on.run_plan()
    assert rep_on == rep_off
    assert int(on._blackbox.round_idx) == plan.n_rounds
    # The golden corpus stays zero, so nothing may be captured.
    cap = on.forensics()
    assert not any(cap["counts"].values())
    # And the end states are bit-identical (the recorder is a pure
    # observer).
    for a, b in zip(off.state, on.state):
        if a is not None:
            assert np.array_equal(np.asarray(a), np.asarray(b))


# --- monitor + incident plumbing ------------------------------------------


def test_monitor_record_incident_and_rename():
    from raft_tpu.metrics import EventTracer, Metrics

    events = []
    m = Metrics(tracer=EventTracer(events))
    mon = HealthMonitor(metrics=m)
    inc = {"slot": "stale_read", "count": 2,
           "offenders": [{"group": 3, "round": 9},
                         {"group": 5, "round": 11}]}
    entry = mon.record_incident(inc)
    assert entry["incident"] is inc
    assert mon.incidents() == [inc]
    # summary_ring is the only name (the deprecated flight_recorder
    # alias was removed; the flight-recorder role belongs to the
    # device black box).
    assert mon.summary_ring()[-1] is entry
    assert not hasattr(mon, "flight_recorder")
    snap = m.registry.snapshot()
    key = 'multiraft_safety_incidents_total{slot="stale_read"}'
    assert snap[key] == 2
    # Re-reporting a grown cumulative count increments by the delta.
    mon.record_incident({"slot": "stale_read", "count": 5,
                         "offenders": []})
    assert m.registry.snapshot()[key] == 5
    traced = [e for e in events if e["event"] == "forensics.incident"]
    assert len(traced) == 2


def test_drain_reports_incidents_to_monitor():
    """ClusterSim's drain surfaces newly-captured offenders to the
    attached monitor exactly once per growth."""
    mon = HealthMonitor()
    cfg = SimConfig(n_groups=4, n_peers=3, blackbox=True)
    cs = sim_mod.ClusterSim(cfg, health_monitor=mon)
    for _ in range(3):
        cs.run_round(append_n=jnp.ones((4,), jnp.int32))
    viol = np.zeros((kernels.N_SAFETY, 4), bool)
    viol[kernels.SV_DUAL_LEADER, 2] = True
    cs.record_safety(jnp.asarray(viol))
    cs._drain()
    incs = mon.incidents()
    assert len(incs) == 1
    assert incs[0]["slot"] == "dual_leader"
    # record_safety stamps the LAST folded round (rounds 0..2 ran).
    assert incs[0]["offenders"] == [{"group": 2, "round": 2}]
    # A second drain with no new captures reports nothing new.
    cs._drain()
    assert len(mon.incidents()) == 1


def test_status_forensics_surface():
    """MultiRaft.status() surfaces recorded incidents."""
    from raft_tpu import Config, MemStorage
    from raft_tpu.config import HealthConfig
    from raft_tpu.multiraft.driver import MultiRaft
    from raft_tpu.raft_log import NO_LIMIT

    stores = [
        MemStorage.new_with_conf_state(([1], [])) for _ in range(2)
    ]
    cfg = Config(
        id=1, election_tick=10, heartbeat_tick=1,
        max_size_per_msg=NO_LIMIT, max_inflight_msgs=256,
    )
    mr = MultiRaft(cfg, stores, health=HealthConfig())
    mr.health_monitor.record_incident(
        {"slot": "dual_lease", "count": 1,
         "offenders": [{"group": 0, "round": 4}]}
    )
    status = mr.status()
    assert status["forensics"]["incidents"] == 1
    assert status["forensics"]["counts"] == {"dual_lease": 1}
    assert status["forensics"]["last"]["slot"] == "dual_lease"


def test_blackbox_checkpoint_roundtrip(tmp_path):
    cfg = SimConfig(n_groups=4, n_peers=3, blackbox=True,
                    blackbox_window=4)
    bb = sim_mod.init_blackbox(cfg)
    viol = np.zeros((kernels.N_SAFETY, 4), bool)
    viol[kernels.SV_STALE_READ, 1] = True
    bb = sim_mod.BlackboxState(*kernels.blackbox_fold(
        bb.meta, bb.term, bb.commit, bb.trip_round, bb.round_idx,
        jnp.zeros((3, 4), jnp.int32), jnp.ones((3, 4), jnp.int32),
        jnp.ones((3, 4), jnp.int32), jnp.zeros((3, 4), bool),
        jnp.asarray(viol),
    ))
    path = str(tmp_path / "bb.npz")
    checkpoint.save_blackbox_state(bb, path)
    loaded = checkpoint.load_blackbox_state(path)
    for a, b in zip(bb, loaded):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="not a black-box checkpoint"):
        sim_path = str(tmp_path / "sim.npz")
        checkpoint.save_state(
            sim_mod.init_state(SimConfig(n_groups=2, n_peers=3)),
            sim_path,
        )
        checkpoint.load_blackbox_state(sim_path)


@pytest.mark.slow  # fresh mesh compiles; the sharded drill-down claim
def test_blackbox_sharded_capture_matches_single_device():
    """The sharded blackbox fold + drain capture must equal the
    single-device run bit-for-bit (the shard-aware claim)."""
    import jax

    from raft_tpu.multiraft import ClusterSim, chaos, sharding

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    G, P = 64, 3
    plan = chaos.ChaosPlan(
        name="forensics-sharded", n_peers=P,
        phases=[
            chaos.ChaosPhase(rounds=8, partition=[[1], [2, 3]],
                             append=1),
            chaos.ChaosPhase(rounds=8, append=1),
        ],
    )
    cfg = SimConfig(n_groups=G, n_peers=P, collect_health=True,
                    blackbox=True)
    single = ClusterSim(cfg, chaos=plan)
    rep_single = single.run_plan()
    mesh = sharding.make_mesh(min(8, len(jax.devices())))
    sharded = ClusterSim(cfg, chaos=plan, mesh=mesh)
    rep_sharded = sharded.run_plan()
    assert rep_sharded == rep_single
    assert np.array_equal(
        np.asarray(single._blackbox.trip_round),
        np.asarray(sharded._blackbox.trip_round),
    )
    assert np.array_equal(
        np.asarray(single._blackbox.meta),
        np.asarray(sharded._blackbox.meta),
    )
    assert sharded.forensics() == single.forensics()
