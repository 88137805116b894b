"""Benchmark: Raft ticks/sec/chip at 100k groups (BASELINE.json config 3
shape: 100k groups × 5 peers, steady append load).

Runs the fused MultiRaft round on the TPU with a lax.scan-batched dispatch,
anchors against the native C++ scalar engine running the identical protocol
(cpp/multiraft_engine.cpp, parity-tested bit-exact against both the device
sim and the scalar Python Raft core), and prints ONE JSON line:

  {"metric": ..., "value": ..., "unit": "ticks/sec", "vs_baseline": ...,
   "reps": R, "min": ..., "median": ..., "max": ..., "spread_pct": ...,
   "spread_flagged": bool, "fused_rounds": N, "total_rounds": M,
   "fused_frac": N/M, "platform": ..., "device_kind": ..., "n_devices": N}

Backend (raft_tpu.platform, the one decision point): without a TPU the
bench exits non-zero — it never falls back to the CPU on its own.  The CI
artifact jobs pin JAX_PLATFORMS=cpu explicitly (interpret-mode Pallas;
counts such as fused_frac are valid there, timings describe no device),
and every line names the platform, device kind and device count it ran on.

Fused-fraction honesty (ISSUE 11): every JSON line carries the MEASURED
fused-kernel coverage of its timed region — `fused_rounds`/`total_rounds`
in group-rounds (one group advancing one protocol round) and their ratio
`fused_frac` — threaded through the dispatchers as an in-graph int32
accumulator (pallas_step count_fused), never inferred from a predicate
log line.  The same count folds into the in-process metrics registry
(bench.METRICS) as the `multiraft_fused_rounds_total` counter.
`--fused-floor X` exits 1 when fused_frac lands below X (the CI
production-suite assertion).

Variance-aware methodology (docs/OBSERVABILITY.md): the timed region is
repeated REPS (≥5) times and the headline `value` is the MEDIAN ticks/sec,
with min/max/spread_pct reported alongside so no single number can hide
run-to-run noise.  spread_pct = (max - min) / median × 100; a spread
above SPREAD_FLAG_PCT sets `spread_flagged` and prints a warning to stderr —
treat flagged runs as unusable for cross-build comparisons and re-run on a
quieter host.

vs_baseline = median device ticks/sec ÷ median native-CPU ticks/sec, both at
the same per-group work (the reference publishes no numbers — BASELINE.md —
so the anchor is measured in-process on the same host).

Flags (all optional):

  --profile DIR   capture a jax.profiler (XLA) trace of the timed region
                  into DIR (raft_tpu.profiling.start_trace/stop_trace);
                  view with TensorBoard's profile plugin / Perfetto.
  --health        thread the device fleet-health planes through the timed
                  region (pallas_step.fast_multi_round(..., with_health))
                  — the <5% overhead claim of docs/OBSERVABILITY.md.
  --health-out F  write the end-of-run health summary JSON to F.
  --lossy RATE    chaos-on fused path: thread an all-up link plane with a
                  uniform per-directed-link loss RATE through
                  fast_multi_round(..., with_chaos) — in-kernel seeded
                  loss draws, the instrumented-fleet configuration.  Uses
                  election_tick=64 so the conservative (lossy) steady
                  bound leaves headroom for the K=32 fused horizon.
  --check-quorum  election-damping configuration (check_quorum=True): the
                  fused damped kernel (_steady_damped_kernel) since
                  ISSUE 8, same election_tick=64 regime; composes with
                  --lossy (see the metric-key note below).
  --groups N      shrink the batch (CI artifact runs; default 100000).
  --reps N        repetition count (>=5 for comparable medians).
  --skip-anchor   skip the native-CPU anchor (vs_baseline becomes null).

Each configuration gets its own metric key so records distinguish
which path was measured: the steady path keeps the historical
`raft_ticks_per_sec_100k_groups_5_peers`, --health appends `_health`,
--lossy appends `_chaos` (both when combined: `_health_chaos`), and
--check-quorum appends `_cq_fused` (the election-damping configuration
riding the ISSUE 8 fused damped kernel; the retired `_cq` series was the
pre-fusion wave-replay number).  --check-quorum composes with --lossy
(`..._chaos_cq_fused`): the lossless damped predicate proves every
check-quorum boundary passes so the fused branch engages every block;
under LOSS the boundary bound is PER GROUP (ISSUE 11 —
kernels.cq_boundary_safe lossy=, loss-free groups keep the saturation
proof) and the composed run rides the per-group hybrid split
(pallas_step.hybrid_multi_round with_chaos): only the groups whose
boundary actually falls inside the horizon take the general wave path
each block, and the JSON line's measured fused_frac says exactly how
much fused coverage the run got.  (--health with the composed config
still uses the whole-batch dispatcher — the hybrid split does not
thread health planes.)

Perf-regression gate (docs/PERF.md):

  --check F        compare this run's median against the committed
                   baseline F (BENCH_baseline.json), keyed
                   `metric@backend@gGROUPS`; exits 1 when the median
                   falls more than the entry's threshold_pct below the
                   baseline median.  A >20% spread on the current run
                   (the PR 1 validity flag) downgrades the gate to a
                   warning — a flagged run cannot assert a regression.
  --check-out F    also write the gate verdict JSON to F (CI artifact).
  --check-threshold PCT  override the baseline entry's threshold.
  --update-baseline      rewrite the baseline entry for this
                   configuration from this run's stats instead of
                   checking (commit the result).

Chaos mode (docs/OBSERVABILITY.md "Chaos") replaces the steady bench:

  --chaos F       run the chaos plan F (JSON, raft_tpu.multiraft.chaos)
                  through the link-gated step as ONE compiled lax.scan per
                  rep; the JSON line carries the scenario summary (MTTR /
                  time-to-reelect off the health planes, safety-invariant
                  counts — all zero or the run fails) instead of
                  vs_baseline.
  --chaos-out F   also write the scenario-summary JSON to F (the CI
                  artifact next to the health summary).

Reconfig mode (docs/OBSERVABILITY.md "Reconfig") likewise replaces the
steady bench — BASELINE.json config 4 (100k groups under joint-consensus
reconfig churn) measured end-to-end:

  --reconfig F    run the membership-churn plan F (JSON,
                  raft_tpu.multiraft.reconfig — either a bare
                  ReconfigPlan document or {"reconfig": ..., "chaos":
                  ...} to overlay an equal-length fault schedule) as ONE
                  compiled lax.scan per rep; the JSON line carries the
                  scenario summary (op-protocol counts, MTTR, the
                  joint-window safety counts — all zero or the run exits
                  2) under the `raft_reconfig_ticks_per_sec` metric key
                  (`_cq` appended under --check-quorum), gated by
                  --check like every other series.
  --reconfig-out F  also write the scenario-summary JSON to F (the CI
                  artifact).

Production split-fused mode (ISSUE 11) replaces the steady bench:

  --prod-fused F  run the PRODUCTION configuration — health + counters +
                  check-quorum + pre-vote + the chaos overlay + the
                  multi-op ReconfigPlan from F ({"reconfig":...,
                  "chaos":...}) — through the split-horizon runner
                  (reconfig.make_split_runner): fused steady blocks
                  between the op windows, general rounds inside them.
                  The JSON line carries the scenario summary, the
                  measured fused_frac (PR 10's unsplit runner fuses 0%
                  of this configuration), and gates under the
                  `raft_prod_fused_ticks_per_sec` metric key.
  --prod-out F    also write the scenario-summary JSON to F.
  --split-k N     fused block length (default 8).
  --split-window N  general rounds planned around each op (default 4).

Serving-workload mode (ISSUE 13; docs/OBSERVABILITY.md "Reads")
replaces the steady bench:

  --reads F       run the client read/write plan F (JSON,
                  raft_tpu.multiraft.workload — a bare ClientPlan
                  document, or {"client": ..., "chaos": ...} to overlay
                  an equal-length fault schedule) through the production
                  damped configuration (check_quorum + pre_vote +
                  lease_read).  Bare plans ride the split-fused runner
                  (pure-lease stretches fused, measured fused_frac); the
                  JSON line carries the read counters and the on-device
                  p50/p90/p99 read latency under the
                  `raft_read_ticks_per_sec` metric key, and any nonzero
                  safety count — the stale-read/dual-lease
                  linearizability slots included — exits 2.
  --reads-out F   also write the read report JSON to F (CI artifact).

Multi-chip mode (ISSUE 14; docs/PERF.md "Multi-chip") replaces the
steady bench with BASELINE config 5 on the mesh:

  --mesh N        shard the fleet over an N-device mesh
                  (sharding.make_mesh) and run the group axis scaled out:
                  groups x 3 peers bootstrapped from the leader-election
                  storm DIRECTLY onto the mesh (no global [P, P, G] plane
                  ever materializes on one host), advanced as the donated
                  run_compiled scan under jit-with-shardings — the graph
                  graftcheck GC015 proves collective-free.  The JSON line
                  carries total AND per-chip ticks/sec plus the analytic
                  per-chip HBM plane-bytes table (the [P, P, G] pairwise
                  planes broken out; the damped recent_active plane
                  reported packed vs unpacked), under the
                  `raft_ticks_per_sec_1m_groups_3_peers_sharded` metric
                  key (`_cq_sharded` with --check-quorum: the damped
                  fleet with the bits_g packed carry riding the sharded
                  scan).  On a CPU host run with JAX_PLATFORMS=cpu so the
                  virtual device mesh engages (numbers from such a run
                  are NOT comparable to TPU medians; the CI artifact runs
                  use --mesh 8 --groups 4096).  The config-5 headline run
                  is `--mesh 8 --groups 1000000 --reps 3`.

Baseline entries carrying `"retired": true` (e.g. the pre-fusion
wave-replay `_cq` series) are historical anchors: --check skips them
with a `retired-baseline` notice instead of gating on them, and
--update-baseline refuses to overwrite them.
"""

import argparse
import functools
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu import platform
from raft_tpu.metrics import Registry


G = 100_000
P = 5
ROUNDS_PER_SCAN = 64
SCANS = 6
REPS = 5
SPREAD_FLAG_PCT = 20.0
ANCHOR_GROUPS = 4096
ANCHOR_ROUNDS = 60

# Bench-process metrics registry (raft_tpu.metrics, zero-dep): the
# measured fused-kernel coverage folds in here as
# `multiraft_fused_rounds_total` so an embedding scraping the bench
# process sees the same number the JSON line carries.
METRICS = Registry()


def fused_fields(fused_rounds: int, total_rounds: int) -> dict:
    """The measured fused-fraction fields EVERY bench JSON line carries
    (ISSUE 11).  Units are GROUP-rounds — one group advancing one
    protocol round; a whole-batch fused block of k rounds at G groups
    counts k*G — so per-group dispatchers (hybrid splits) report honest
    partial coverage.  `fused_frac` = fused_rounds / total_rounds is the
    gated claim: "the production config stays fused" is this number, not
    a log line.  Also folds the count into the module METRICS registry as
    the `multiraft_fused_rounds_total` counter."""
    METRICS.counter(
        "multiraft_fused_rounds_total",
        "fused-kernel group-rounds executed in bench timed regions",
    ).inc(int(fused_rounds))
    return {
        "fused_rounds": int(fused_rounds),
        "total_rounds": int(total_rounds),
        "fused_frac": (
            round(fused_rounds / total_rounds, 4) if total_rounds else 0.0
        ),
    }


def rep_stats(samples) -> dict:
    """min/median/max/spread_pct over per-repetition ticks/sec samples."""
    lo, hi = min(samples), max(samples)
    med = statistics.median(samples)
    spread_pct = (hi - lo) / med * 100.0 if med else float("inf")
    return {
        "reps": len(samples),
        "min": round(lo, 1),
        "median": round(med, 1),
        "max": round(hi, 1),
        "spread_pct": round(spread_pct, 1),
        "spread_flagged": spread_pct > SPREAD_FLAG_PCT,
    }


def bench_device(
    groups: int = G,
    reps: int = REPS,
    health: bool = False,
    profile_dir: str = "",
    health_out: str = "",
    lossy: float = -1.0,
    check_quorum: bool = False,
) -> dict:
    from raft_tpu.multiraft import kernels, pallas_step, sim
    from raft_tpu.multiraft.sim import SimConfig

    chaos = lossy >= 0.0

    # The chaos-on path dispatches on the CONSERVATIVE steady bound (a
    # lossy link can drop any heartbeat, so timers are assumed
    # free-running): the election timeout must clear the fused horizon or
    # the fused branch would never engage — election_tick=64 > K=32.
    # --check-quorum benches the DAMPED configuration: since ISSUE 8 it
    # rides the fused damped kernel (_steady_damped_kernel) whenever the
    # steady predicate holds — damping uses the same free-running timer
    # bound as chaos, so it shares the election_tick=64 > K=32 regime —
    # and composes with --lossy (the fused damped chaos kernel).  The
    # general damped wave path (sim._damped_linked_step) remains the
    # lax.cond fallback.
    cfg = SimConfig(
        n_groups=groups, n_peers=P,
        election_tick=64 if (chaos or check_quorum) else 10,
        check_quorum=check_quorum,
    )
    state = sim.init_state(cfg)
    crashed = jnp.zeros((P, groups), bool)
    append = jnp.ones((groups,), jnp.int32)
    link = jnp.ones((P, P, groups), bool) if chaos else None
    loss = (
        jnp.full((P, P, groups), int(round(lossy * kernels.LOSS_SCALE)),
                 jnp.int32)
        if chaos
        else None
    )

    # Every protocol round executes fully; the fused pallas kernel runs K
    # rounds per VMEM residency when the steady invariant provably holds,
    # with a lax.cond fallback to the general XLA step (bit-identical
    # semantics; see raft_tpu/multiraft/pallas_step.py).  With --health the
    # per-group health planes ride through both branches
    # (fast_multi_round(..., with_health=True)); with --lossy both branches
    # additionally thread the link plane + in-kernel loss draws.  The
    # composed --lossy --check-quorum configuration (without --health)
    # rides the PER-GROUP hybrid split (ISSUE 11): spread check-quorum
    # boundary phases cost only the boundary-crossing groups, not the
    # batch.  Every dispatcher threads the fused group-round accumulator
    # (count_fused) so the JSON line's fused_frac is measured, not
    # assumed.
    K = 32
    use_hybrid = chaos and check_quorum and not health
    if use_hybrid:
        kstep = pallas_step.hybrid_multi_round(
            cfg, k=K, with_chaos=True, count_fused=True,
        )
    else:
        kstep = pallas_step.fast_multi_round(
            cfg, k=K, with_health=health, with_chaos=chaos,
            count_fused=True,
        )
    full = jax.jit(functools.partial(sim.step, cfg))
    hstate = sim.init_health(cfg) if health else None

    def block_step(s, h, rb, fz):
        """One K-round fused-dispatch block at absolute round rb."""
        args = (s, crashed, append)
        if chaos:
            args = args + (link, loss, rb)
        if health:
            s2, h2, fz = kstep(*args, h, fz)
            return s2, h2, fz
        out, fz = kstep(*args, fz)
        return out, h, fz

    # The scan carry holds the optional recent_active plane bit-packed
    # 32:1 along G (sim.pack_ra_carry — the ISSUE 8 packed-carry form);
    # identity (None words) for undamped configs, so their graphs are
    # unchanged.
    if health:

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def multi_round_h(st, ra, h, fused, rb):
            def body(carry, i):
                s, raw, hh, fz = carry
                s, hh, fz = block_step(
                    sim.unpack_ra_carry(s, raw), hh, rb + i * K, fz
                )
                s, raw = sim.pack_ra_carry(s)
                return (s, raw, hh, fz), ()

            carry, _ = jax.lax.scan(
                body, (st, ra, h, fused),
                jnp.arange(ROUNDS_PER_SCAN // K, dtype=jnp.int32),
            )
            return carry

    else:

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def multi_round(st, ra, fused, rb):
            def body(carry, i):
                s, raw, fz = carry
                s, _, fz = block_step(
                    sim.unpack_ra_carry(s, raw), None, rb + i * K, fz
                )
                return sim.pack_ra_carry(s) + (fz,), ()

            carry, _ = jax.lax.scan(
                body, (st, ra, fused),
                jnp.arange(ROUNDS_PER_SCAN // K, dtype=jnp.int32),
            )
            return carry

    round_no = 0

    def advance(stp, ra, h, fused):
        """One donated scan segment over the PACKED carry: the bit-packed
        recent_active words stay packed between segments, so the timed
        loop never materializes the bool[P, P, G] plane — unpacking is
        the caller's (out-of-timed-region) job."""
        nonlocal round_no
        rb = jnp.int32(round_no)
        round_no += ROUNDS_PER_SCAN
        if health:
            stp, ra, h, fused = multi_round_h(stp, ra, h, fused, rb)
        else:
            stp, ra, fused = multi_round(stp, ra, fused, rb)
        return stp, ra, h, fused

    # Warm up: compile + let the election storm settle into steady state
    # (the chaos/damped configs' longer election_tick needs a longer
    # settle).
    settle = 30 if not (chaos or check_quorum) else 3 * cfg.election_tick
    for _ in range(settle):
        state = full(state, crashed, append)
    round_no = settle
    stp, ra = sim.pack_ra_carry(state)
    stp, ra, hstate, _warm_fused = advance(stp, ra, hstate, jnp.int32(0))
    jax.block_until_ready(stp)
    if (chaos or check_quorum) and not use_hybrid:
        # Honesty check: the timed region must actually ride the fused
        # kernel — a rejected predicate would bench the general fallback
        # under the fused path's metric key, so it is an error, not a
        # run.  (The hybrid split needs no check: its coverage IS the
        # measured fused_frac in the JSON line.)  The unpack happens
        # here, OUTSIDE the timed region; `state`'s buffers alias the
        # carry and are donated away by the next advance, so it must not
        # be read after the timed loop starts.
        state = sim.unpack_ra_carry(stp, ra)
        pred = bool(
            pallas_step.steady_predicate(
                cfg, state, crashed, K, link, loss_rate=loss
            )
        )
        if not pred:
            print(
                "ERROR: steady predicate rejects the settled "
                f"{'lossy' if chaos else 'damped'} state; the timed region "
                "would measure the general fallback under the fused key",
                file=sys.stderr,
            )
            raise SystemExit(1)

    rounds = (ROUNDS_PER_SCAN // K) * K * SCANS
    ticks = groups * rounds
    samples = []
    fused_total = 0
    fused = jnp.int32(0)  # re-zeroed: the warm-up segment doesn't count
    if profile_dir:
        from raft_tpu import profiling

        profiling.start_trace(profile_dir)
    try:
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(SCANS):
                stp, ra, hstate, fused = advance(stp, ra, hstate, fused)
            jax.block_until_ready(stp)
            samples.append(ticks / (time.perf_counter() - t0))
            # Per-rep drain of the int32 group-round accumulator — one
            # rep accrues groups x rounds (= 384) group-rounds, within
            # int32 up to ~5.5M groups; the carry is already synced, so
            # this fetch costs the timed region nothing.
            got = int(jax.device_get(fused))
            if got < 0:
                # The same v<0 wrap backstop as the counter drain: a
                # batch large enough to wrap the per-rep window must fail
                # loudly, not report a garbage fused_frac.
                raise RuntimeError(
                    "fused group-round accumulator wrapped int32 within "
                    "one rep (groups x rounds_per_rep >= 2**31); reduce "
                    "--groups"
                )
            fused_total += got
            fused = jnp.int32(0)
    finally:
        if profile_dir:
            profiling.stop_trace()

    # Sanity: the protocol is actually running (leaders + commits advance).
    state = sim.unpack_ra_carry(stp, ra)
    commit_min = int(jnp.min(jnp.max(state.commit, axis=0)))
    assert commit_min > 0, "bench sanity: no commits on device"
    if health and health_out:
        from raft_tpu.multiraft import kernels
        from raft_tpu.multiraft.health import HealthMonitor

        counts, hist, ids, scores = jax.device_get(
            kernels.health_summary(
                hstate.planes,
                cfg.leaderless_stall_ticks,
                cfg.commit_stall_ticks,
                cfg.churn_bumps,
                min(cfg.health_topk, groups),
            )
        )
        with open(health_out, "w") as f:
            json.dump(
                HealthMonitor.summary_dict(counts, hist, ids, scores), f
            )
    return {
        **rep_stats(samples),
        **fused_fields(fused_total, groups * rounds * reps),
    }


def bench_blackbox(groups: int = G, reps: int = REPS) -> dict:
    """Measure the ISSUE 15 black-box instrumentation overhead.

    General path: the donated run_compiled scan with SimConfig.blackbox
    off vs on (the per-round ring/trip fold riding step(blackbox=)).
    Fused path: blackbox-on conservatively rejects every fused horizon
    (pallas_step.steady_mask v1), so the honest fused-path cost of
    turning forensics on is the gap between the fused dispatcher
    (blackbox off, steady predicate engaged — bench_device's timed loop)
    and the blackbox-on GENERAL scan: `blackbox_overhead_fused_pct`
    includes the defusion, which is the price a production fused
    configuration actually pays (docs/PERF.md "Black-box overhead")."""
    from raft_tpu.multiraft.sim import ClusterSim, SimConfig

    crashed = jnp.zeros((P, groups), bool)
    append = jnp.ones((groups,), jnp.int32)

    def run_general(blackbox: bool) -> dict:
        cfg = SimConfig(n_groups=groups, n_peers=P, blackbox=blackbox)
        cs = ClusterSim(cfg)
        # Settle the election storm, then warm the segment compile.
        for _ in range(30):
            cs.run_round(crashed, append)
        cs.run_compiled(ROUNDS_PER_SCAN, append_n=append)
        jax.block_until_ready(cs.state.commit)
        samples = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(SCANS):
                cs.run_compiled(ROUNDS_PER_SCAN, append_n=append)
            jax.block_until_ready(cs.state.commit)
            samples.append(
                groups * ROUNDS_PER_SCAN * SCANS
                / (time.perf_counter() - t0)
            )
        assert int(jnp.min(jnp.max(cs.state.commit, axis=0))) > 0, (
            "bench sanity: no commits on device"
        )
        return rep_stats(samples)

    general_off = run_general(False)
    general_on = run_general(True)
    fused_off = bench_device(groups, reps)

    def overhead(base: dict, instrumented: dict) -> float:
        return round(
            100.0 * (base["median"] - instrumented["median"])
            / base["median"],
            2,
        )

    return {
        "general_off": general_off,
        "general_on": general_on,
        "fused_off": fused_off,
        "blackbox_overhead_pct": overhead(general_off, general_on),
        "blackbox_overhead_fused_pct": overhead(fused_off, general_on),
    }


def bench_chaos(
    plan_path: str, groups: int, reps: int, chaos_out: str = "",
    check_quorum: bool = False,
) -> dict:
    """Run a chaos plan as one compiled scan per rep and report both the
    scenario summary and the chaos-path throughput."""
    from raft_tpu.multiraft import chaos, sim
    from raft_tpu.multiraft.health import HealthMonitor
    from raft_tpu.multiraft.sim import SimConfig

    plan = chaos.load_plan(plan_path)
    cfg = SimConfig(
        n_groups=groups, n_peers=plan.n_peers, collect_health=True,
        check_quorum=check_quorum,
    )
    compiled = chaos.compile_plan(plan, groups)
    runner = chaos.make_runner(cfg, compiled)

    def fresh():
        return sim.init_state(cfg), sim.init_health(cfg)

    st, hl = fresh()
    st, hl, stats, safety = runner(st, hl)  # compile + first run
    jax.block_until_ready(stats)
    samples = []
    for _ in range(reps):
        st, hl = fresh()
        jax.block_until_ready((st, hl))
        t0 = time.perf_counter()
        st, hl, stats, safety = runner(st, hl)
        jax.block_until_ready(stats)
        samples.append(groups * plan.n_rounds / (time.perf_counter() - t0))
    stats_h, safety_h = jax.device_get((stats, safety))
    report = HealthMonitor.chaos_report(stats_h, safety_h, plan.n_rounds)
    report["plan"] = plan.name
    report["groups"] = groups
    report["peers"] = plan.n_peers
    report["phases"] = len(plan.phases)
    if chaos_out:
        with open(chaos_out, "w") as f:
            json.dump(report, f)
    if any(report["safety"].values()):
        print(
            f"ERROR: chaos plan {plan.name} violated safety invariants: "
            f"{report['safety']}",
            file=sys.stderr,
        )
        raise SystemExit(2)
    # The chaos runner is the per-round link-gated scan — no fused blocks
    # by construction; the honest fused_frac is 0.
    return {
        "report": report,
        **rep_stats(samples),
        **fused_fields(0, groups * plan.n_rounds * reps),
    }


def bench_reconfig(
    plan_path: str, groups: int, reps: int, reconfig_out: str = "",
    check_quorum: bool = False,
) -> dict:
    """Run a membership-churn plan (optionally composed with a chaos
    plan) as one compiled scan per rep — the BASELINE config 4 shape —
    and report both the scenario summary and the reconfig-path
    throughput."""
    from raft_tpu.multiraft import chaos, reconfig, sim
    from raft_tpu.multiraft.health import HealthMonitor
    from raft_tpu.multiraft.kernels import HP_SINCE_COMMIT
    from raft_tpu.multiraft.sim import SimConfig

    with open(plan_path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    chaos_doc = None
    if "reconfig" in doc:
        chaos_doc = doc.get("chaos")
        doc = doc["reconfig"]
    plan = reconfig.plan_from_dict(doc)
    cfg = SimConfig(
        n_groups=groups, n_peers=plan.n_peers, collect_health=True,
        check_quorum=check_quorum,
    )
    compiled = reconfig.compile_plan(plan, groups)
    chaos_compiled = (
        None
        if chaos_doc is None
        else chaos.compile_plan(chaos.plan_from_dict(chaos_doc), groups)
    )
    runner = reconfig.make_runner(cfg, compiled, chaos_compiled)

    def fresh():
        # Masks rebuilt per rep: the runner donates the state carry, so a
        # shared mask buffer would be dead after the first run.
        st = sim.init_state(cfg, *reconfig.initial_masks(plan, groups))
        return st, sim.init_health(cfg), reconfig.init_reconfig_state(st)

    st, hl, rst = fresh()
    out = runner(st, hl, rst)  # compile + first run
    jax.block_until_ready(out[3])
    samples = []
    for _ in range(reps):
        st, hl, rst = fresh()
        jax.block_until_ready((st, hl, rst))
        t0 = time.perf_counter()
        st, hl, rst, stats, rstats, safety = runner(st, hl, rst)
        jax.block_until_ready(stats)
        samples.append(groups * plan.n_rounds / (time.perf_counter() - t0))
    # Reconfig-stall detection off the final rep's planes — the one
    # shared rule (HealthMonitor.reconfig_stall_groups), same as
    # ClusterSim.run_reconfig's.
    stats_h, rstats_h, safety_h, om_h, since_h = jax.device_get(
        (stats, rstats, safety, st.outgoing_mask,
         hl.planes[HP_SINCE_COMMIT])
    )
    n_stuck, worst = HealthMonitor.reconfig_stall_groups(
        om_h, since_h, cfg.election_tick
    )
    report = HealthMonitor.reconfig_report(
        stats_h, rstats_h, safety_h, plan.n_rounds, n_stuck, worst,
    )
    report["plan"] = plan.name
    report["groups"] = groups
    report["peers"] = plan.n_peers
    report["phases"] = len(plan.phases)
    report["chaos_overlay"] = chaos_doc is not None
    if reconfig_out:
        with open(reconfig_out, "w") as f:
            json.dump(report, f)
    if any(report["safety"].values()):
        print(
            f"ERROR: reconfig plan {plan.name} violated safety "
            f"invariants: {report['safety']}",
            file=sys.stderr,
        )
        raise SystemExit(2)
    # make_runner is the unsplit per-round scan (--prod-fused is the
    # split-horizon mode); the honest fused_frac here is 0.
    return {
        "report": report,
        **rep_stats(samples),
        **fused_fields(0, groups * plan.n_rounds * reps),
    }


def bench_prod_fused(
    plan_path: str,
    groups: int,
    reps: int,
    prod_out: str = "",
    k: int = 8,
    window: int = 4,
) -> dict:
    """The PRODUCTION configuration, measured honestly fused (ISSUE 11):
    health + counters + chaos overlay + check-quorum + pre-vote + a
    multi-op ReconfigPlan, executed through the split-horizon runner
    (reconfig.make_split_runner) — the steady stretches between op
    windows ride the fused Pallas kernel in k-round blocks, the op
    propose/gate/apply rounds and runtime-rejected blocks run the general
    damped wave path — reporting ticks/sec AND the measured fused
    fraction.  PR 10's unsplit runner fuses 0% of this configuration;
    the acceptance floor is fused_frac >= 0.8 (--fused-floor in CI).

    Leaders settle OUTSIDE the timed region (3x election_tick general
    rounds from the plan's bootstrap masks — the boot storm is not the
    production regime being measured); each rep replays the plan from a
    copy of the settled state because the runner donates its carry and
    plans apply absolute masks."""
    from raft_tpu.multiraft import chaos, kernels, reconfig, sim
    from raft_tpu.multiraft.health import HealthMonitor
    from raft_tpu.multiraft.kernels import HP_SINCE_COMMIT
    from raft_tpu.multiraft.sim import SimConfig

    with open(plan_path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    chaos_doc = doc.get("chaos")
    plan = reconfig.plan_from_dict(doc.get("reconfig", doc))
    # election_tick=64: the damped free-running timer bound must clear
    # the k-round fused horizon (docs/PERF.md), same regime as --lossy.
    cfg = SimConfig(
        n_groups=groups, n_peers=plan.n_peers, election_tick=64,
        collect_health=True, collect_counters=True,
        check_quorum=True, pre_vote=True,
    )
    compiled = reconfig.compile_plan(plan, groups)
    chaos_compiled = (
        None
        if chaos_doc is None
        else chaos.compile_plan(chaos.plan_from_dict(chaos_doc), groups)
    )
    runner = reconfig.make_split_runner(
        cfg, compiled, chaos_compiled, k=k, window=window,
        with_counters=True,
    )
    step = jax.jit(functools.partial(sim.step, cfg))
    crashed0 = jnp.zeros((plan.n_peers, groups), bool)
    settle_append = jnp.ones((groups,), jnp.int32)
    st0 = sim.init_state(cfg, *reconfig.initial_masks(plan, groups))
    for _ in range(3 * cfg.election_tick):
        st0 = step(st0, crashed0, settle_append)
    jax.block_until_ready(st0)

    def fresh():
        # A copy per rep: the runner donates the carry, st0 is the keeper.
        st = jax.tree.map(jnp.copy, st0)
        return (
            st, sim.init_health(cfg), reconfig.init_reconfig_state(st),
            kernels.zero_counters(),
        )

    out = runner(*fresh())  # compile + first run
    jax.block_until_ready(out[3])
    samples = []
    fused_total = 0
    for _ in range(reps):
        st, hl, rst, ctrs = fresh()
        jax.block_until_ready((st, hl, rst))
        t0 = time.perf_counter()
        st, hl, rst, stats, rstats, safety, fused, ctrs = runner(
            st, hl, rst, ctrs
        )
        jax.block_until_ready(stats)
        samples.append(
            groups * plan.n_rounds / (time.perf_counter() - t0)
        )
        fused_total += int(jax.device_get(fused))
    stats_h, rstats_h, safety_h, om_h, since_h = jax.device_get(
        (stats, rstats, safety, st.outgoing_mask,
         hl.planes[HP_SINCE_COMMIT])
    )
    n_stuck, worst = HealthMonitor.reconfig_stall_groups(
        om_h, since_h, cfg.election_tick
    )
    report = HealthMonitor.reconfig_report(
        stats_h, rstats_h, safety_h, plan.n_rounds, n_stuck, worst,
    )
    report["plan"] = plan.name
    report["groups"] = groups
    report["peers"] = plan.n_peers
    report["phases"] = len(plan.phases)
    report["chaos_overlay"] = chaos_doc is not None
    report["segments"] = [
        {"start": s.start, "rounds": s.rounds, "fused": s.fused}
        for s in runner.segments
    ]
    if prod_out:
        with open(prod_out, "w") as f:
            json.dump(report, f)
    if any(report["safety"].values()):
        print(
            f"ERROR: prod-fused plan {plan.name} violated safety "
            f"invariants: {report['safety']}",
            file=sys.stderr,
        )
        raise SystemExit(2)
    return {
        "report": report,
        **rep_stats(samples),
        **fused_fields(fused_total, groups * plan.n_rounds * reps),
    }


def bench_autopilot(
    groups: int,
    reps: int,
    chaos_path: str = "",
    cadence: int = 16,
    out: str = "",
) -> dict:
    """The closed-loop configuration (ISSUE 12): the Zipf hot-region
    workload (benches/suites.py config 3's TiKV-style skew), a
    crash-window chaos overlay, and the autopilot's kick/transfer healing
    in one run — the healthy stretches ride the fused Pallas cadence
    segments (autopilot.make_cadence_runner's fused branch), the chaos
    window and every acted-on segment take the general path, and the
    per-cadence host policy round trips are INSIDE the timed region (the
    closed loop's cost is the number being reported).

    Leaders settle outside the timed region (3x election_tick rounds);
    each rep replays from a copy of the settled state with a fresh
    Autopilot (deterministic policy: identical actions every rep)."""
    from raft_tpu.multiraft import ClusterSim, chaos
    from raft_tpu.multiraft.autopilot import Autopilot, AutopilotConfig
    from raft_tpu.multiraft.sim import SimConfig

    PEERS = 5
    if chaos_path:
        with open(chaos_path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    else:
        doc = {
            "name": "autopilot-bench",
            "peers": PEERS,
            "phases": [
                {"rounds": 192, "append": 0},
                {"rounds": 32, "crash": [2], "append": 0},
                {"rounds": 96, "heal": True, "append": 0},
            ],
        }
    plan = chaos.plan_from_dict(doc)
    # election_tick=64: the free-running steady timer bound must clear the
    # fused cadence horizon (the --lossy / prod-fused regime).
    cfg = SimConfig(
        n_groups=groups, n_peers=plan.n_peers, election_tick=64,
        collect_health=True, transfer=True, commit_stall_ticks=8,
    )
    rng = np.random.RandomState(0)
    append = jnp.asarray(
        np.minimum(rng.zipf(1.8, size=groups), 8), dtype=jnp.int32
    )
    sim_sim = ClusterSim(cfg)
    step = sim_sim._step
    crashed0 = jnp.zeros((plan.n_peers, groups), bool)
    st0 = sim_sim.state
    for _ in range(3 * cfg.election_tick):
        st0 = step(st0, crashed0, append, None, None, None, None)
    jax.block_until_ready(st0)
    st_keep = jax.tree.map(jnp.copy, st0)

    def fresh_sim():
        from raft_tpu.multiraft import sim as sim_mod

        s = ClusterSim(cfg)
        s.state = jax.tree.map(jnp.copy, st_keep)
        s._health = sim_mod.init_health(cfg)
        return s

    apcfg = AutopilotConfig(cadence=cadence)
    # Compile + policy warm-up run (jits cache inside the Autopilot; a
    # fresh Autopilot per rep reuses nothing across them, so each rep
    # carries one cold policy pass — build one runner cache to share).
    warm = Autopilot(fresh_sim(), apcfg, fused=True)
    report = warm.run_plan(plan, append=append)
    shared_runners = warm._runners
    samples = []
    for _ in range(reps):
        s = fresh_sim()
        ap = Autopilot(s, apcfg, fused=True)
        ap._runners = shared_runners
        jax.block_until_ready(s.state)
        t0 = time.perf_counter()
        report = ap.run_plan(plan, append=append)
        jax.block_until_ready(s.state)
        samples.append(groups * plan.n_rounds / (time.perf_counter() - t0))
    if any(report["safety"].values()):
        print(
            f"ERROR: autopilot bench violated safety invariants: "
            f"{report['safety']}",
            file=sys.stderr,
        )
        raise SystemExit(2)
    if out:
        with open(out, "w") as f:
            json.dump(report, f)
    return {
        "report": {
            k: report[k]
            for k in (
                "rounds", "mttr_rounds", "reelections",
                "commit_stall_group_rounds", "safety",
            )
        },
        "actions": report["actions"],
        **rep_stats(samples),
        **fused_fields(
            report.get("fused_rounds", 0) * reps,
            groups * plan.n_rounds * reps,
        ),
    }


def bench_reads(
    plan_path: str,
    groups: int,
    reps: int,
    reads_out: str = "",
    k: int = 8,
) -> dict:
    """The serving workload (ISSUE 13): a compiled client read/write plan
    (raft_tpu.multiraft.workload — Zipf write skew, per-phase Safe/Lease
    read mixes) driven through the production damped configuration
    (check_quorum + pre_vote + lease_read, election_tick=64 — the fused
    regime) with the full per-round safety audit INCLUDING the
    linearizability slots.  A bare plan runs the split-fused runner
    (workload.make_split_runner): pure-lease stretches ride the fused
    Pallas kernel with their receipts folded closed-form, quorum-round
    reads fall back honestly — the JSON line's `fused_frac` is the
    measured coverage.  A {"client": ..., "chaos": ...} document overlays
    an equal-length fault schedule through the general scan (reads during
    partitions; fused_frac honestly 0).

    The report carries the read latency percentiles (p50/p90/p99 in
    protocol rounds, reduced ON DEVICE by workload.latency_percentiles —
    the profiling.py nearest-rank rule) and the read/serve/degrade
    counters; any nonzero safety count exits 2.  Leaders settle outside
    the timed region (3x election_tick), each rep replaying the plan from
    a copy of the settled state (the runner donates its carry)."""
    from raft_tpu.multiraft import chaos, reconfig, sim, workload
    from raft_tpu.multiraft.sim import SimConfig

    with open(plan_path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    chaos_doc = doc.get("chaos")
    plan = workload.plan_from_dict(doc.get("client", doc))
    cfg = SimConfig(
        n_groups=groups, n_peers=plan.n_peers, election_tick=64,
        collect_health=True, check_quorum=True, pre_vote=True,
        lease_read=True,
    )
    compiled = workload.compile_plan(plan, groups)
    if chaos_doc is None:
        runner = workload.make_split_runner(cfg, compiled, k=k)
    else:
        chaos_compiled = chaos.compile_plan(
            chaos.plan_from_dict(chaos_doc), groups
        )
        runner = workload.make_runner(cfg, compiled, chaos_compiled)
    step = jax.jit(functools.partial(sim.step, cfg))
    crashed0 = jnp.zeros((plan.n_peers, groups), bool)
    settle_append = jnp.ones((groups,), jnp.int32)
    st0 = sim.init_state(cfg)
    for _ in range(3 * cfg.election_tick):
        st0 = step(st0, crashed0, settle_append)
    jax.block_until_ready(st0)

    def fresh():
        st = jax.tree.map(jnp.copy, st0)
        return (
            st, sim.init_health(cfg), reconfig.init_reconfig_state(st),
            workload.init_read_carry(groups),
        )

    out = runner(*fresh())  # compile + first run
    jax.block_until_ready(out[3])
    samples = []
    fused_total = 0
    for _ in range(reps):
        args = fresh()
        jax.block_until_ready(args)
        t0 = time.perf_counter()
        out = runner(*args)
        jax.block_until_ready(out[3])
        samples.append(
            groups * plan.n_rounds / (time.perf_counter() - t0)
        )
        if chaos_doc is None:
            fused_total += int(jax.device_get(out[9]))
    _st, _hl, _rst, stats, _rstats, safety, _rcar, rdstats, lat_hist = (
        out[:9]
    )
    lat_p, recover_p = workload.report_percentiles(lat_hist, stats)
    rdstats_h, lat_p_h, safety_h, stats_h, recover_p_h = jax.device_get(
        (rdstats, lat_p, safety, stats, recover_p)
    )
    report = workload.read_report(
        rdstats_h, lat_p_h, safety_h, stats_h, plan.n_rounds, recover_p_h
    )
    report["plan"] = plan.name
    report["groups"] = groups
    report["peers"] = plan.n_peers
    report["phases"] = len(plan.phases)
    report["chaos_overlay"] = chaos_doc is not None
    if reads_out:
        with open(reads_out, "w") as f:
            json.dump(report, f)
    if any(report["safety"].values()):
        print(
            f"ERROR: read plan {plan.name} violated safety invariants "
            f"(linearizability slots included): {report['safety']}",
            file=sys.stderr,
        )
        raise SystemExit(2)
    return {
        "report": report,
        "read_p50": report["read_p50"],
        "read_p90": report["read_p90"],
        "read_p99": report["read_p99"],
        **rep_stats(samples),
        **fused_fields(fused_total, groups * plan.n_rounds * reps),
    }


MESH_PEERS = 3  # BASELINE.json config 5: 1M groups x 3 peers
MESH_ROUNDS_PER_SCAN = 64
MESH_SCANS = 6


def mesh_plane_bytes(cfg, n_devices: int) -> dict:
    """Analytic per-chip HBM bytes of the sharded fleet state (ISSUE 14).

    The [P, P, G] pairwise planes are where the cost is, so they are
    broken out per plane; the damped recent_active plane reports BOTH its
    unpacked bool[P, P, G] bytes and its bits_g packed scan-carry form
    (kernels.pack_bits_g: 32 group-bits per int32 word — 8x fewer bytes
    than XLA's byte-per-bool plane, 32x fewer carried elements).  Every
    figure is per chip: the group axis divides across the mesh, the peer
    axes stay local."""
    import math

    Gs = math.ceil(cfg.n_groups / n_devices)  # groups per chip
    Pn = cfg.n_peers
    i32 = 4
    damped = cfg.check_quorum or cfg.pre_vote
    pairwise = {
        "matched": Pn * Pn * Gs * i32,
        "agree": Pn * Pn * Gs * i32,
    }
    if damped:
        pairwise["recent_active_unpacked"] = Pn * Pn * Gs  # bool = 1 byte
        pairwise["recent_active_packed"] = (
            Pn * Pn * math.ceil(Gs / 32) * i32
        )
    # Per-peer planes: 11 int32 [P, G] cursors/timers + 3 bool config
    # masks (+ the optional transferee plane).
    per_peer = 11 * Pn * Gs * i32 + 3 * Pn * Gs
    if cfg.transfer:
        per_peer += Pn * Gs * i32
    # The damped plane rides the scan carry PACKED, so the resident total
    # counts the packed words, not the unpacked bool plane.
    resident_pairwise = (
        pairwise["matched"]
        + pairwise["agree"]
        + pairwise.get("recent_active_packed", 0)
    )
    return {
        "groups_per_chip": Gs,
        "pairwise": pairwise,
        "per_peer_total": per_peer,
        "total_per_chip": resident_pairwise + per_peer,
    }


def bench_mesh(
    groups: int,
    n_devices: int,
    reps: int = REPS,
    check_quorum: bool = False,
) -> dict:
    """BASELINE config 5 on the mesh (ISSUE 14): groups x 3 peers
    bootstrapped from the leader-election storm (init_state's randomized
    election clocks), sharded over `n_devices` chips, advanced as the
    donated run_compiled lax.scan under jit-with-shardings — the
    steady graph graftcheck GC015 proves collective-free.  The
    bootstrap never materializes a global [P, P, G] plane on one host
    (sharding.sharded_init_state).  Reports total AND per-chip
    ticks/sec plus the analytic per-chip plane-bytes table."""
    from raft_tpu.multiraft import sharding, sim
    from raft_tpu.multiraft.sim import SimConfig

    if len(jax.devices()) < n_devices:
        print(
            f"ERROR: --mesh {n_devices} needs {n_devices} devices but jax "
            f"sees {len(jax.devices())} — on a CPU host run with "
            "JAX_PLATFORMS=cpu so the virtual device mesh engages",
            file=sys.stderr,
        )
        raise SystemExit(2)
    mesh = sharding.make_mesh(n_devices)
    cfg = SimConfig(
        n_groups=groups, n_peers=MESH_PEERS,
        election_tick=64 if check_quorum else 10,
        check_quorum=check_quorum, pre_vote=check_quorum,
    )
    cs = sim.ClusterSim(cfg, mesh=mesh)
    append = cs._put(jnp.ones((groups,), jnp.int32), True)

    # Settle the election storm (config 5's initial condition), then one
    # warm segment so the timed region replays a compiled executable.
    settle = 30 if not check_quorum else 3 * cfg.election_tick
    cs.run_compiled(settle, append_n=append)
    cs.run_compiled(MESH_ROUNDS_PER_SCAN, append_n=append)
    jax.block_until_ready(cs.state.term)

    rounds = MESH_ROUNDS_PER_SCAN * MESH_SCANS
    ticks = groups * rounds
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(MESH_SCANS):
            cs.run_compiled(MESH_ROUNDS_PER_SCAN, append_n=append)
        jax.block_until_ready(cs.state.term)
        samples.append(ticks / (time.perf_counter() - t0))

    # Sanity: the protocol is running on every shard (post-storm leaders
    # committing) — via the ICI status reduction, exact total_commit
    # included (the ISSUE 14 limb fix: 1M groups x thousands of commits
    # would wrap the old single int32 psum).
    status = sharding.global_status(cs.cfg, mesh)(cs.state)
    assert int(status["n_leaders"]) > 0, "mesh bench sanity: no leaders"
    assert status["total_commit"] > 0, "mesh bench sanity: no commits"
    stats = rep_stats(samples)
    per_chip = {
        k: round(stats[k] / n_devices, 1) for k in ("min", "median", "max")
    }
    return {
        **stats,
        "n_devices": n_devices,
        "per_chip_ticks_per_sec": per_chip,
        "per_chip_plane_bytes": mesh_plane_bytes(cfg, n_devices),
        "n_leaders": int(status["n_leaders"]),
        "total_commit": status["total_commit"],
    }


def bench_scalar_anchor(reps: int = REPS) -> dict:
    from raft_tpu.multiraft.native import NativeMultiRaft

    engine = NativeMultiRaft(ANCHOR_GROUPS, P)
    append = np.ones((ANCHOR_GROUPS,), dtype=np.int32)
    # Let elections settle before timing (same steady state as the device).
    engine.run(25, None, append)
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        engine.run(ANCHOR_ROUNDS, None, append)
        samples.append(
            ANCHOR_GROUPS * ANCHOR_ROUNDS / (time.perf_counter() - t0)
        )
    return rep_stats(samples)


def check_key(metric: str, groups: int) -> str:
    """Baseline key: one entry per (metric, backend, batch size) — CPU
    interpret-mode medians and TPU medians must never gate each other."""
    return f"{metric}@{platform.backend()}@g{groups}"


def check_against_baseline(
    line: dict, baseline: dict, threshold_pct=None
) -> tuple:
    """The perf-regression gate: (ok, verdict-dict).

    Fails (ok=False) iff the run's median is more than threshold_pct below
    the committed baseline median.  The PR 1 >20% spread flag is the
    validity check: a flagged run cannot assert a regression (or a
    pass) — the gate downgrades to `spread-flagged` and passes so host
    noise cannot fail CI, exactly like flagged medians are excluded from
    cross-build comparisons (docs/OBSERVABILITY.md)."""
    key = check_key(line["metric"], line.get("groups", G))
    verdict = {"key": key, "median": line["median"]}
    entry = baseline.get(key)
    if entry is None:
        verdict["status"] = "no-baseline"
        return True, verdict
    if entry.get("retired"):
        # A retired entry is a historical anchor (e.g. the pre-fusion
        # wave-replay `_cq` series), not a live gate: skip with notice
        # instead of silently thresholding against a methodology that no
        # longer exists.
        verdict["status"] = "retired-baseline"
        if entry.get("note"):
            verdict["note"] = entry["note"]
        return True, verdict
    thr = (
        threshold_pct
        if threshold_pct is not None
        else float(entry.get("threshold_pct", 25.0))
    )
    floor = float(entry["median"]) * (1.0 - thr / 100.0)
    verdict.update(
        baseline_median=entry["median"], threshold_pct=thr,
        floor=round(floor, 1),
    )
    if line.get("spread_flagged"):
        verdict["status"] = "spread-flagged"
        return True, verdict
    if line["median"] < floor:
        verdict["status"] = "regressed"
        return False, verdict
    verdict["status"] = "ok"
    return True, verdict


def run_check(args, line) -> None:
    """--check / --update-baseline handling; exits 1 on a regression."""
    import os

    baseline = {}
    if os.path.exists(args.check):
        with open(args.check, "r", encoding="utf-8") as f:
            baseline = json.load(f)
    key = check_key(line["metric"], line.get("groups", G))
    if args.update_baseline:
        if baseline.get(key, {}).get("retired"):
            print(
                f"ERROR: baseline entry {key} is marked retired (a "
                "historical anchor); refusing to overwrite it — remove "
                "the \"retired\" flag by hand if the series is being "
                "deliberately revived",
                file=sys.stderr,
            )
            raise SystemExit(1)
        if line.get("spread_flagged"):
            # The gate's own validity rule cuts both ways: a >20%-spread
            # run cannot assert a pass, a regression, OR a baseline — a
            # floor set from a noisy run would wave real regressions by.
            print(
                "ERROR: refusing to record a baseline from a "
                f"spread-flagged run (spread {line['spread_pct']}% > "
                f"{SPREAD_FLAG_PCT}%); re-run on a quieter host",
                file=sys.stderr,
            )
            raise SystemExit(1)
        baseline[key] = {
            "median": line["median"],
            "threshold_pct": (
                args.check_threshold
                if args.check_threshold is not None
                else baseline.get(key, {}).get("threshold_pct", 25.0)
            ),
            "reps": line["reps"],
            "spread_pct": line["spread_pct"],
        }
        with open(args.check, "w", encoding="utf-8") as f:
            json.dump(baseline, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"baseline updated: {key}", file=sys.stderr)
        return
    ok, verdict = check_against_baseline(line, baseline, args.check_threshold)
    if args.check_out:
        with open(args.check_out, "w", encoding="utf-8") as f:
            json.dump(verdict, f)
    print(f"perf gate: {json.dumps(verdict)}", file=sys.stderr)
    if not ok:
        print(
            f"ERROR: median {line['median']} ticks/sec is below the "
            f"regression floor {verdict['floor']} "
            f"(baseline {verdict['baseline_median']} - "
            f"{verdict['threshold_pct']}%)",
            file=sys.stderr,
        )
        raise SystemExit(1)


def warn_spread(name: str, stats: dict) -> None:
    if stats["spread_flagged"]:
        print(
            f"WARNING: {name} ticks/sec spread {stats['spread_pct']}% "
            f"exceeds {SPREAD_FLAG_PCT}% across {stats['reps']} reps "
            f"(min {stats['min']}, max {stats['max']}); medians from this "
            "run are not comparable across builds — re-run on a quieter "
            "host.",
            file=sys.stderr,
        )


def emit(line: dict) -> None:
    """Print one bench JSON line, naming the device it was produced on."""
    print(json.dumps({**line, **platform.device_fields()}))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--profile", default="", metavar="DIR")
    ap.add_argument("--health", action="store_true")
    ap.add_argument("--health-out", default="", metavar="FILE")
    ap.add_argument("--lossy", type=float, default=-1.0, metavar="RATE")
    ap.add_argument("--check-quorum", action="store_true")
    ap.add_argument("--groups", type=int, default=G)
    ap.add_argument("--reps", type=int, default=REPS)
    ap.add_argument("--skip-anchor", action="store_true")
    ap.add_argument("--chaos", default="", metavar="PLAN_JSON")
    ap.add_argument("--chaos-out", default="", metavar="FILE")
    ap.add_argument("--reconfig", default="", metavar="PLAN_JSON")
    ap.add_argument("--reconfig-out", default="", metavar="FILE")
    ap.add_argument("--prod-fused", default="", metavar="PLAN_JSON")
    ap.add_argument("--prod-out", default="", metavar="FILE")
    ap.add_argument("--autopilot", action="store_true")
    ap.add_argument("--autopilot-plan", default="", metavar="PLAN_JSON")
    ap.add_argument("--autopilot-out", default="", metavar="FILE")
    ap.add_argument("--reads", default="", metavar="PLAN_JSON")
    ap.add_argument("--reads-out", default="", metavar="FILE")
    ap.add_argument("--blackbox", action="store_true")
    ap.add_argument("--mesh", type=int, default=0, metavar="N_DEVICES")
    ap.add_argument("--cadence", type=int, default=16)
    ap.add_argument("--split-k", type=int, default=8)
    ap.add_argument("--split-window", type=int, default=4)
    ap.add_argument("--fused-floor", type=float, default=None)
    ap.add_argument("--check", default="", metavar="BASELINE_JSON")
    ap.add_argument("--check-out", default="", metavar="FILE")
    ap.add_argument("--check-threshold", type=float, default=None)
    ap.add_argument("--update-baseline", action="store_true")
    args = ap.parse_args()
    if args.health_out and not args.health:
        ap.error("--health-out requires --health")
    if args.chaos_out and not args.chaos:
        ap.error("--chaos-out requires --chaos")
    if args.reconfig_out and not args.reconfig:
        ap.error("--reconfig-out requires --reconfig")
    if args.reconfig and args.chaos:
        # A fault overlay composes INSIDE the reconfig scan — put the
        # chaos document in the plan file ({"reconfig":..., "chaos":...}).
        ap.error("--reconfig and --chaos are separate modes; overlay "
                 "chaos via the reconfig plan file's \"chaos\" key")
    if (args.check_out or args.update_baseline) and not args.check:
        ap.error("--check-out/--update-baseline require --check")
    if args.lossy > 1.0 or (args.lossy < 0.0 and args.lossy != -1.0):
        # -1.0 is the chaos-off sentinel; any OTHER negative is a typo
        # that would silently bench the plain path under the steady key.
        ap.error("--lossy rate must be in [0, 1]")
    if args.prod_fused and (args.chaos or args.reconfig):
        ap.error("--prod-fused is its own mode (overlay chaos via the "
                 "plan file's \"chaos\" key)")
    if args.prod_out and not args.prod_fused:
        ap.error("--prod-out requires --prod-fused")

    def enforce_fused_floor(line):
        if args.fused_floor is None:
            return
        if line.get("fused_frac", 0.0) < args.fused_floor:
            print(
                f"ERROR: fused_frac {line.get('fused_frac')} is below "
                f"the --fused-floor {args.fused_floor}: the production "
                "configuration fell off the fused kernel",
                file=sys.stderr,
            )
            raise SystemExit(1)

    if args.autopilot and (args.chaos or args.reconfig or args.prod_fused):
        ap.error("--autopilot is its own mode (chaos via --autopilot-plan)")
    if (args.autopilot_plan or args.autopilot_out) and not args.autopilot:
        ap.error("--autopilot-plan/--autopilot-out require --autopilot")
    if args.reads and (
        args.chaos or args.reconfig or args.prod_fused or args.autopilot
    ):
        ap.error("--reads is its own mode (overlay chaos via the plan "
                 "file's \"chaos\" key)")
    if args.reads_out and not args.reads:
        ap.error("--reads-out requires --reads")
    if args.mesh and (
        args.chaos or args.reconfig or args.prod_fused or args.autopilot
        or args.reads or args.health or args.lossy >= 0.0
    ):
        ap.error("--mesh is its own mode (the sharded config-5 bench; "
                 "--check-quorum composes for the damped/packed-carry "
                 "variant)")
    if args.mesh < 0:
        ap.error("--mesh needs a positive device count")
    if args.blackbox and (
        args.chaos or args.reconfig or args.prod_fused or args.autopilot
        or args.reads or args.mesh or args.health or args.lossy >= 0.0
        or args.check_quorum
    ):
        ap.error("--blackbox is its own mode (the ISSUE 15 "
                 "instrumented-vs-off overhead measurement)")

    if args.mesh and platform.pinned_to_cpu():
        # The virtual CPU mesh needs its device count pinned BEFORE the
        # backend initializes; only when the process explicitly targets
        # the CPU (the CI/dryrun setting), so a real TPU mesh keeps its
        # devices.
        platform.force_virtual_cpu(args.mesh)
    try:
        platform.backend()
    except RuntimeError as e:
        raise SystemExit(f"ERROR: {e}")
    platform.enable_compile_cache()

    if args.blackbox:
        bb_stats = bench_blackbox(args.groups, args.reps)
        for tag in ("general_off", "general_on", "fused_off"):
            warn_spread(f"blackbox {tag}", bb_stats[tag])
        line = {
            "metric": "raft_blackbox_overhead",
            "value": bb_stats["blackbox_overhead_pct"],
            "unit": "pct",
            "groups": args.groups,
            "blackbox": True,
            **bb_stats,
        }
        # Deliberately no --check gate: the overhead is documented in
        # docs/PERF.md, not a first-class baseline configuration (the
        # ISSUE 15 satellite's call).
        emit(line)
        return

    if args.mesh:
        mesh_stats = bench_mesh(
            args.groups, args.mesh, args.reps,
            check_quorum=args.check_quorum,
        )
        warn_spread("mesh device", mesh_stats)
        line = {
            "metric": "raft_ticks_per_sec_1m_groups_3_peers"
            + ("_cq" if args.check_quorum else "")
            + "_sharded",
            "value": mesh_stats["median"],
            "unit": "ticks/sec",
            "groups": args.groups,
            **mesh_stats,
        }
        if args.check_quorum:
            line["check_quorum"] = True
        emit(line)
        if args.check:
            run_check(args, line)
        return

    if args.reads:
        read_stats = bench_reads(
            args.reads, args.groups, args.reps, args.reads_out,
            k=args.split_k,
        )
        warn_spread("reads device", read_stats)
        line = {
            "metric": "raft_read_ticks_per_sec",
            "value": read_stats["median"],
            "unit": "ticks/sec",
            "groups": args.groups,
            "check_quorum": True,
            "pre_vote": True,
            "lease_read": True,
            **read_stats,
        }
        emit(line)
        enforce_fused_floor(line)
        if args.check:
            run_check(args, line)
        return

    if args.autopilot:
        ap_stats = bench_autopilot(
            args.groups, args.reps, args.autopilot_plan,
            cadence=args.cadence, out=args.autopilot_out,
        )
        warn_spread("autopilot device", ap_stats)
        line = {
            "metric": "raft_autopilot_ticks_per_sec",
            "value": ap_stats["median"],
            "unit": "ticks/sec",
            "groups": args.groups,
            "autopilot": True,
            **ap_stats,
        }
        emit(line)
        enforce_fused_floor(line)
        if args.check:
            run_check(args, line)
        return

    if args.prod_fused:
        prod_stats = bench_prod_fused(
            args.prod_fused, args.groups, args.reps, args.prod_out,
            k=args.split_k, window=args.split_window,
        )
        warn_spread("prod-fused device", prod_stats)
        line = {
            "metric": "raft_prod_fused_ticks_per_sec",
            "value": prod_stats["median"],
            "unit": "ticks/sec",
            "groups": args.groups,
            "check_quorum": True,
            "pre_vote": True,
            **prod_stats,
        }
        emit(line)
        enforce_fused_floor(line)
        if args.check:
            run_check(args, line)
        return

    if args.reconfig:
        reconfig_stats = bench_reconfig(
            args.reconfig, args.groups, args.reps, args.reconfig_out,
            check_quorum=args.check_quorum,
        )
        warn_spread("reconfig device", reconfig_stats)
        line = {
            "metric": "raft_reconfig_ticks_per_sec"
            + ("_cq" if args.check_quorum else ""),
            "value": reconfig_stats["median"],
            "unit": "ticks/sec",
            "groups": args.groups,
            **reconfig_stats,
        }
        if args.check_quorum:
            line["check_quorum"] = True
        emit(line)
        if args.check:
            run_check(args, line)
        return

    if args.chaos:
        chaos_stats = bench_chaos(
            args.chaos, args.groups, args.reps, args.chaos_out,
            check_quorum=args.check_quorum,
        )
        warn_spread("chaos device", chaos_stats)
        line = {
            "metric": "raft_chaos_ticks_per_sec"
            + ("_cq" if args.check_quorum else ""),
            "value": chaos_stats["median"],
            "unit": "ticks/sec",
            "groups": args.groups,
            **chaos_stats,
        }
        if args.check_quorum:
            line["check_quorum"] = True
        emit(line)
        if args.check:
            run_check(args, line)
        return

    device = bench_device(
        groups=args.groups,
        reps=args.reps,
        health=args.health,
        profile_dir=args.profile,
        health_out=args.health_out,
        lossy=args.lossy,
        check_quorum=args.check_quorum,
    )
    anchor = None if args.skip_anchor else bench_scalar_anchor(args.reps)
    # A flagged spread on EITHER side poisons vs_baseline (it is a ratio of
    # the two medians), so both are checked.
    warn_spread("device", device)
    if anchor is not None:
        warn_spread("native-CPU anchor", anchor)
    # Per-configuration metric key: steady vs health-on vs chaos-on runs
    # must never share one baseline series.
    metric = "raft_ticks_per_sec_100k_groups_5_peers"
    if args.health:
        metric += "_health"
    if args.lossy >= 0.0:
        metric += "_chaos"
    if args.check_quorum:
        # `_cq_fused` (ISSUE 8): the damped configuration rides the fused
        # damped kernel now — a different series from the retired `_cq`
        # wave-replay numbers (75.4k @ cpu@g256), kept in
        # BENCH_baseline.json as the historical anchor.
        metric += "_cq_fused"
    line = {
        "metric": metric,
        "value": device["median"],
        "unit": "ticks/sec",
        "vs_baseline": (
            None
            if anchor is None
            else round(device["median"] / anchor["median"], 2)
        ),
        **device,
        # A flagged anchor poisons vs_baseline just as much as a flagged
        # device, so the top-level flag ORs both sides.
        "spread_flagged": device["spread_flagged"]
        or (anchor is not None and anchor["spread_flagged"]),
        "anchor": anchor,
    }
    if args.groups != G:
        line["groups"] = args.groups
    if args.health:
        line["health"] = True
    if args.lossy >= 0.0:
        line["lossy"] = args.lossy
    if args.check_quorum:
        line["check_quorum"] = True
    emit(line)
    enforce_fused_floor(line)
    if args.check:
        run_check(args, line)


if __name__ == "__main__":
    main()
