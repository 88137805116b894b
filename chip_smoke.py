"""chip_smoke.py — the quickest proof that the fleet path starts on the chip.

Drives the system's main path once on ONE TPU chip, through the entry
points a user calls (ClusterSim and its run_* scenario runners, the
MultiRaft driver) and the fused kernels those runners dispatch
(pallas_step.steady_round behind pallas_step.steady_predicate), at the
headline deployment's size — BASELINE config 3's shape, 100 000 groups
x 5 peers, K = 32 fused rounds a block, election_tick = 64 for the chaos
and damped families (the regime in which every fused family engages) —
and checks every answer by the repo's own means:

  1. general path, undamped   == the C++ engine (NativeMultiRaft), full G
  2. general path, damped     == scalar raft-rs port (simref.ScalarCluster)
                                 on a seeded block of groups, every round
  3. fused Pallas kernels     compiled by Mosaic, the steady predicate held,
                              bit-equal to K general rounds on the chip
  4. scenario runners         chaos / reconfig (split) / reads (split)
  5. embedded driver          three MultiRaft nodes in this one process
  6. BASELINE config 5 shape  1M groups x 3 peers, general + fused damped

Any failed check is fatal.  It prints no rate: set-up and compile time are
reported per leg (and gathered in a `summary:` line), speed is a
benchmark's business.  The last line of standard output is one JSON object
with exactly two keys, {"ok": true, "device": {"platform", "kind",
"count"}}, and the exit code is 0 only then.  Without a TPU (no
accelerator, or a process pinned to the CPU) it exits non-zero before
running anything.

The legs are functions of (G, P) so tests/test_chip_smoke.py can drive
them at a tiny size on the pinned CPU; the command itself never runs
without a chip.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

G, P = 100_000, 5  # BASELINE config 3
G_BIG, P_BIG = 1_000_000, 3  # BASELINE config 5
K = 32  # fused rounds per block
TICK_FUSED = 64  # election_tick of the chaos/damped families
LOSS = 0.01  # uniform per-link loss of the chaos family
SEED = 20230  # picks the block of groups leg 2 replays on the scalar port
SAMPLE = 16  # groups in that block

FIELDS = ("term", "state", "commit", "last_index", "last_term")


class SmokeFailure(Exception):
    """A leg's check did not hold."""


def check(ok, what: str) -> None:
    if not bool(ok):
        raise SmokeFailure(what)


def settle_rounds(election_tick: int) -> int:
    """Rounds for the boot election storm to settle fleet-wide: timeouts
    are drawn from [tick, 2*tick), and split votes need a second draw."""
    return 4 * election_tick


def leaders_and_commits(st):
    """(every group has exactly one leader, min over groups of max commit)."""
    import jax.numpy as jnp

    from raft_tpu.multiraft.kernels import ROLE_LEADER

    one = jnp.all(jnp.sum(st.state == ROLE_LEADER, axis=0) == 1)
    return bool(one), int(jnp.min(jnp.max(st.commit, axis=0)))


def boundary_safety(cs, append):
    """Advance `cs` one more general round and return the safety-invariant
    counts (kernels.check_safety, joint-window slots included) over that
    round boundary, as {slot name: violating groups}."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.multiraft import kernels

    prev = jax.tree.map(
        jnp.copy,
        (cs.state.commit, cs.state.voter_mask, cs.state.outgoing_mask),
    )
    st = cs.run_round(None, append)
    counts = kernels.check_safety(
        st.state, st.term, st.commit, st.last_index, st.agree, prev[0],
        voter_mask=st.voter_mask, outgoing_mask=st.outgoing_mask,
        matched=st.matched,
        crashed=jnp.zeros(st.state.shape, bool),
        prev_voter_mask=prev[1], prev_outgoing_mask=prev[2],
    )
    return dict(zip(kernels.SAFETY_NAMES, (int(c) for c in counts)))


def check_settled(cs, append, leg: str) -> dict:
    safety = boundary_safety(cs, append)
    one, commit_min = leaders_and_commits(cs.state)
    check(one, f"{leg}: some group does not have exactly one leader")
    check(commit_min > 0, f"{leg}: a group committed nothing")
    check(not any(safety.values()), f"{leg}: safety violated: {safety}")
    return {"commit_min": commit_min, "safety": safety}


# --- leg 1 ------------------------------------------------------------------


def leg_general_undamped(G: int, P: int, append_rounds: int = 64) -> dict:
    """ClusterSim's general path through the boot storm and an append load,
    equal at full G to the C++ engine on the same schedule."""
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.multiraft import ClusterSim, SimConfig
    from raft_tpu.multiraft.native import NativeMultiRaft

    cfg = SimConfig(G, P)
    boot = settle_rounds(cfg.election_tick)
    cs = ClusterSim(cfg)
    ones = jnp.ones((G,), jnp.int32)
    cs.run_compiled(boot)
    cs.run_compiled(append_rounds, append_n=ones)
    out = check_settled(cs, ones, "general undamped")

    native = NativeMultiRaft(G, P, cfg.election_tick, cfg.heartbeat_tick)
    native.run(boot)
    native.run(append_rounds + 1, None, np.ones((G,), np.int32))
    want = native.snapshot()
    for f in FIELDS:
        got = np.asarray(getattr(cs.state, f)).T
        bad = np.argwhere(got != want[f])
        check(
            bad.size == 0,
            f"general undamped: {f} differs from the C++ engine in "
            f"{len(bad)} (group, peer) cells, first at {bad[:1].tolist()}",
        )
    return {**out, "rounds": boot + append_rounds + 1, "reference": "cpp"}


# --- leg 2 ------------------------------------------------------------------


def leg_general_damped(
    G: int, P: int, election_tick: int = TICK_FUSED, append_rounds: int = 32,
    sample: int = SAMPLE,
) -> dict:
    """The production configuration (check-quorum + pre-vote, health and
    counter planes on) on the general path, with a seeded contiguous block
    of groups equal EVERY ROUND to the scalar raft-rs port."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.multiraft import ClusterSim, SimConfig
    from raft_tpu.multiraft.simref import ScalarCluster

    cfg = SimConfig(
        G, P, election_tick=election_tick, check_quorum=True, pre_vote=True,
        collect_health=True, collect_counters=True,
    )
    sample = min(sample, G)
    g0 = int(np.random.RandomState(SEED).randint(0, G - sample + 1))
    scalar = ScalarCluster(
        sample, P, election_tick=election_tick, check_quorum=True,
        pre_vote=True, timeout_seed_base=g0,
    )
    cs = ClusterSim(cfg)
    boot = settle_rounds(election_tick)
    zeros, ones = jnp.zeros((G,), jnp.int32), jnp.ones((G,), jnp.int32)

    def compare(r):
        want = scalar.snapshot()
        block = jax.device_get(
            [getattr(cs.state, f)[:, g0:g0 + sample] for f in FIELDS]
        )
        for f, got in zip(FIELDS, block):
            check(
                np.array_equal(got.T, want[f]),
                f"general damped: round {r}: {f} of groups "
                f"[{g0}, {g0 + sample}) differs from the scalar port",
            )

    for r in range(boot + append_rounds):
        cs.run_round(None, zeros if r < boot else ones)
        scalar.round(append_n=np.full((sample,), int(r >= boot)))
        compare(r)
    # The donated scan with the bit-packed recent_active carry is the same
    # general round: one segment, then the block must still agree.
    cs.run_compiled(K, append_n=ones)
    for _ in range(K):
        scalar.round(append_n=np.ones((sample,), np.int64))
    compare(boot + append_rounds + K - 1)
    out = check_settled(cs, ones, "general damped")
    counters = cs.counters()
    check(
        counters["commit_entries"] > 0 and counters["elections_won"] >= G,
        f"general damped: counter plane did not move: {counters}",
    )
    return {
        **out,
        "rounds": boot + append_rounds + K + 1,
        "reference": f"scalar groups [{g0}, {g0 + sample})",
    }


# --- leg 3 ------------------------------------------------------------------


def settled_sim(cfg, masks=()):
    """A ClusterSim for `cfg` (bootstrapped in `masks`' membership) run
    through the boot storm under an append load: settle_rounds(tick)
    rounds in, every group steady."""
    import jax.numpy as jnp

    from raft_tpu.multiraft import ClusterSim

    cs = ClusterSim(cfg, *masks)
    cs.run_compiled(
        settle_rounds(cfg.election_tick),
        append_n=jnp.ones((cfg.n_groups,), jnp.int32),
    )
    return cs


def fused_block(
    cfg, st, round_base: int, *, k: int = K, with_health: bool = False,
    with_counters: bool = False, loss=None,
) -> None:
    """One k-round block of the fused kernel from `st`: the steady
    predicate (what the split runners' block guard reduces) must hold for
    the horizon, and pallas_step.steady_round's result is checked
    bit-equal — every state, counter and health plane — to k general
    rounds (sim.step, with kernels.link_loss_draw's masks under loss) run
    on the same device from the same state.  `loss` is an int32[P, P, G]
    rate plane (None = no chaos surface)."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.multiraft import kernels, pallas_step, sim

    Gc, Pc = cfg.n_groups, cfg.n_peers
    crashed = jnp.zeros((Pc, Gc), bool)
    append = jnp.ones((Gc,), jnp.int32)
    chaos = loss is not None
    link = jnp.ones((Pc, Pc, Gc), bool) if chaos else None
    rb = jnp.int32(round_base)
    extras = ()
    if with_counters:
        extras += (kernels.zero_counters(),)
    if with_health:
        extras += (sim.init_health(cfg),)

    predicate = jax.jit(
        lambda st: pallas_step.steady_predicate(
            cfg, st, crashed, horizon=k, link=link, loss_rate=loss
        )
    )
    check(
        predicate(st),
        f"fused block: the steady predicate does not hold for {k} rounds",
    )
    fn = pallas_step.steady_round(
        cfg, rounds=k, with_health=with_health, with_chaos=chaos,
        with_counters=with_counters,
    )
    chaos_args = (loss, rb) if chaos else ()
    got = jax.jit(fn)(st, crashed, append, *chaos_args, *extras)
    got = tuple(got) if extras else (got,)

    def general(st, *extras):
        def body(carry, r):
            kw = {}
            if with_counters:
                kw["counters"] = carry[1]
            if with_health:
                kw["health"] = carry[-1]
            if chaos:
                kw["link"] = link & ~kernels.link_loss_draw(rb + r, loss)
            res = sim.step(cfg, carry[0], crashed, append, **kw)
            return (tuple(res) if extras else (res,)), ()

        return jax.lax.scan(
            body, (st,) + extras, jnp.arange(k, dtype=jnp.int32)
        )[0]

    want = jax.jit(general)(st, *extras)
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree.leaves(want)
    check(len(flat_got) == len(flat_want), "fused block: pytree mismatch")
    for (path, a), b in zip(flat_got, flat_want):
        check(
            a.dtype == b.dtype and bool(jnp.array_equal(a, b)),
            f"fused block differs from {k} general rounds at "
            f"{jax.tree_util.keystr(path)}",
        )


def leg_fused_kernels(
    G: int, P: int, k: int = K, election_tick: int = TICK_FUSED
) -> dict:
    """Every fused kernel family, compiled (on a TPU: by Mosaic) and run
    from a state its steady predicate admits, bit-equal to the general
    path."""
    import jax
    import jax.numpy as jnp

    from raft_tpu import platform
    from raft_tpu.multiraft import SimConfig, kernels, pallas_step

    check(
        jax.default_backend() != "tpu" or not platform.pallas_interpret(),
        "interpret mode reachable on a TPU backend",
    )
    rate = int(round(LOSS * kernels.LOSS_SCALE))
    uniform = jnp.full((P, P, G), rate, jnp.int32)

    ran = []

    def block(name, cfg, st, r0, **kw):
        fused_block(cfg, st, r0, k=k, **kw)
        ran.append(name)

    cfg = SimConfig(G, P)
    st, r0 = settled_sim(cfg).state, settle_rounds(cfg.election_tick)
    block("plain", cfg, st, r0)
    block("plain+health", cfg, st, r0, with_health=True)

    cfg = SimConfig(G, P, election_tick=election_tick)
    st, r0 = settled_sim(cfg).state, settle_rounds(election_tick)
    block("chaos", cfg, st, r0, loss=uniform)

    cfg = SimConfig(
        G, P, election_tick=election_tick, check_quorum=True, pre_vote=True
    )
    st = settled_sim(cfg).state
    block("damped", cfg, st, r0)
    block(
        "damped+health+counters", cfg, st, r0,
        with_health=True, with_counters=True,
    )
    # Under loss a group whose check-quorum boundary falls inside the
    # horizon is not provably steady (steady_mask's lossy bound), so the
    # loss falls on the groups that are and the rest stay loss-free: the
    # predicate then holds fleet-wide and the damped chaos kernel draws
    # real loss on the lossy groups' links.
    lossy = jax.jit(
        lambda st: pallas_step.steady_mask(
            cfg, st, jnp.zeros((P, G), bool), horizon=k,
            link=jnp.ones((P, P, G), bool), loss_rate=uniform,
        )
    )(st)
    n_lossy = int(jnp.sum(lossy))
    check(0 < n_lossy, "damped+loss: no group is steady under loss")
    block("damped+loss", cfg, st, r0, loss=jnp.where(lossy, uniform, 0))
    print(f"  damped+loss: {n_lossy} of {G} groups lossy", flush=True)
    return {"families": ran, "lossy_groups": n_lossy, "k": k}


# --- leg 4 ------------------------------------------------------------------


def _example(*parts: str) -> str:
    return os.path.join(HERE, "examples", *parts)


def _check_report(report: dict, leg: str) -> None:
    check(
        not any(report["safety"].values()),
        f"{leg}: safety violated: {report['safety']}",
    )


def leg_chaos_plan(G: int) -> dict:
    from raft_tpu.multiraft import ClusterSim, SimConfig, chaos

    plan = chaos.load_plan(_example("chaos", "partition_heal.json"))
    cs = ClusterSim(SimConfig(G, plan.n_peers, collect_health=True))
    report = cs.run_plan(plan)
    _check_report(report, "chaos plan")
    check(report["reelections"] > 0, "chaos plan: nothing was re-elected")
    return {k: report[k] for k in ("rounds", "reelections", "mttr_rounds")}


def _settled_production_sim(
    G: int, P: int, election_tick: int, masks=(), **flags
):
    """The settled check-quorum + pre-vote fleet the split runners start
    from (the boot storm is not part of the plan)."""
    from raft_tpu.multiraft import SimConfig

    cfg = SimConfig(
        G, P, election_tick=election_tick, collect_health=True,
        check_quorum=True, pre_vote=True, **flags,
    )
    cs = settled_sim(cfg, masks)
    cs.reset_health()
    return cs


def leg_reconfig_plan(G: int, election_tick: int = TICK_FUSED) -> dict:
    from raft_tpu.multiraft import chaos, reconfig

    with open(_example("reconfig", "prod_fused.json"), encoding="utf-8") as f:
        doc = json.load(f)
    plan = reconfig.plan_from_dict(doc["reconfig"])
    cplan = chaos.plan_from_dict(doc["chaos"])
    cs = _settled_production_sim(
        G, plan.n_peers, election_tick, reconfig.initial_masks(plan, G)
    )
    report = cs.run_reconfig(plan, cplan, split=True)
    _check_report(report, "reconfig plan")
    check(report["fused_frac"] > 0, "reconfig plan: no block rode the kernel")
    check(
        report["ops_applied"] > 0, "reconfig plan: no membership op applied"
    )
    return {k: report[k] for k in ("rounds", "ops_applied", "fused_frac")}


def leg_reads_plan(G: int, election_tick: int = TICK_FUSED) -> dict:
    from raft_tpu.multiraft import workload

    plan = workload.load_plan(_example("reads", "zipf_mixed.json"))
    cs = _settled_production_sim(
        G, plan.n_peers, election_tick, lease_read=True
    )
    report = cs.run_reads(plan, split=True)
    _check_report(report, "reads plan")
    check(report["fused_frac"] > 0, "reads plan: no block rode the kernel")
    check(
        report["served_lease"] > 0 and report["served_quorum"] > 0,
        f"reads plan: a read mode served nothing: {report}",
    )
    return {
        k: report[k]
        for k in ("rounds", "reads_issued", "read_p99", "fused_frac")
    }


# --- leg 5 ------------------------------------------------------------------


def leg_embedded_driver(n_groups: int = 2_000) -> dict:
    """examples/multiraft_node.py's flow: three MultiRaft drivers in this
    one process; all groups elect, one proposal per group commits on every
    node."""
    spec = importlib.util.spec_from_file_location(
        "multiraft_node", _example("multiraft_node.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    out = module.run(n_groups, log=lambda msg: print(f"  {msg}", flush=True))
    check(out["leaders"] == n_groups, f"embedded driver: {out}")
    check(out["committed"] == 3 * n_groups, f"embedded driver: {out}")
    return out


# --- leg 6 ------------------------------------------------------------------


def leg_big_fleet(
    G: int, P: int, k: int = K, election_tick: int = TICK_FUSED
) -> dict:
    """BASELINE config 5's shape on one chip: boot, one run_compiled
    segment on the general path, one fused damped block."""
    import jax.numpy as jnp

    from raft_tpu.multiraft import SimConfig

    cfg = SimConfig(
        G, P, election_tick=election_tick, check_quorum=True, pre_vote=True
    )
    cs = settled_sim(cfg)
    ones = jnp.ones((G,), jnp.int32)
    cs.run_compiled(k, append_n=ones)
    out = check_settled(cs, ones, "big fleet")
    fused_block(
        cfg, cs.state, settle_rounds(election_tick) + k + 1, k=k,
        with_health=True, with_counters=True,
    )
    return out


# --- the command ------------------------------------------------------------


class CompileClock:
    """Seconds jax spent lowering and compiling (or fetching from the
    persistent cache), from jax.monitoring's duration events — so each leg
    reports set-up separately from the rest of its wall time."""

    EVENTS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
        "/jax/compilation_cache/cache_retrieval_time_sec",
    )

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, seconds, **_):
        if name in self.EVENTS:
            self.seconds += seconds


def result_line(device: dict) -> str:
    """The last line of standard output: exactly the keys "ok" and
    "device", the device exactly "platform", "kind" and "count" — the
    driver parses it and refuses anything more."""
    return json.dumps({
        "ok": True,
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    })


def main() -> int:
    import jax
    import jaxlib

    from raft_tpu import platform  # nothing runs without the repo around it

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu":
        print(
            f"chip_smoke: no TPU — jax reports {device}; this command "
            "only runs on the chip",
            file=sys.stderr,
        )
        return 1
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except (ImportError, AttributeError):
        libtpu_version = "unknown"
    print(
        f"device: {device['platform']} {device['kind']} x{device['count']}  "
        f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
        f"libtpu {libtpu_version}",
        flush=True,
    )

    cache_dir = platform.enable_compile_cache()
    clock = CompileClock()

    legs = [
        ("1 general undamped", lambda: leg_general_undamped(G, P)),
        ("2 general damped", lambda: leg_general_damped(G, P)),
        ("3 fused kernels", lambda: leg_fused_kernels(G, P)),
        ("4a chaos plan", lambda: leg_chaos_plan(G)),
        ("4b reconfig plan", lambda: leg_reconfig_plan(G)),
        ("4c reads plan", lambda: leg_reads_plan(G)),
        ("5 embedded driver", leg_embedded_driver),
        ("6 big fleet", lambda: leg_big_fleet(G_BIG, P_BIG)),
    ]
    results = {}
    t_start = time.monotonic()
    for name, leg in legs:
        print(f"leg {name} ...", flush=True)
        t0, c0 = time.monotonic(), clock.seconds
        detail = leg()  # any failure is fatal: nothing below catches it
        wall = time.monotonic() - t0
        compile_s = clock.seconds - c0
        results[name] = {
            "pass": True,
            "wall_s": round(wall, 1),
            "compile_s": round(compile_s, 1),
            "run_s": round(wall - compile_s, 1),
            **detail,
        }
        print(f"leg {name} ok: {json.dumps(results[name])}", flush=True)
    summary = {
        "versions": {
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "libtpu": libtpu_version,
        },
        "groups": G,
        "peers": P,
        "compile_cache": cache_dir,
        "wall_s": round(time.monotonic() - t_start, 1),
        "legs": results,
    }
    print(f"summary: {json.dumps(summary)}", flush=True)
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
