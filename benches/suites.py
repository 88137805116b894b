"""Micro-benchmark suites (the Criterion-suite equivalent; reference:
benches/suites/{raft,raw_node,progress}.rs) plus the five BASELINE.json
multi-group configs.

Run: python benches/suites.py [--quick]
Prints a table of results; bench.py remains the single-line headline bench.
"""

import argparse
import sys
import time

sys.path.insert(0, ".")

import numpy as np

from raft_tpu import Config, Entry, MemStorage, Message, MessageType, Raft, RawNode
from raft_tpu.raft import CAMPAIGN_ELECTION, CAMPAIGN_PRE_ELECTION, CAMPAIGN_TRANSFER
from raft_tpu.raft_log import NO_LIMIT
from raft_tpu.tracker import Progress


def timeit(fn, iters):
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    dt = time.perf_counter() - t0
    return dt / iters


def quick_raw_node(voters, learners):
    ids = list(range(1, voters + 1))
    learner_ids = list(range(voters + 1, voters + learners + 1))
    storage = MemStorage()
    storage.initialize_with_conf_state((ids or [1], learner_ids))
    cfg = Config(
        id=1,
        election_tick=10,
        heartbeat_tick=1,
        max_size_per_msg=NO_LIMIT,
        max_inflight_msgs=256,
    )
    return RawNode(cfg, storage)


def bench_raft_new(results, iters):
    """reference: benches/suites/raft.rs:30-38"""
    for voters, learners in [(0, 0), (3, 1), (5, 2), (7, 3)]:
        if voters == 0:
            continue
        t = timeit(lambda: quick_raw_node(voters, learners).raft, iters)
        results.append((f"Raft::new ({voters}, {learners})", t * 1e6, "us/op"))


def bench_campaign(results, iters):
    """reference: benches/suites/raft.rs:40-66"""
    for voters, learners in [(3, 1), (5, 2), (7, 3)]:
        for ct, name in [
            (CAMPAIGN_PRE_ELECTION, "PreElection"),
            (CAMPAIGN_ELECTION, "Election"),
            (CAMPAIGN_TRANSFER, "Transfer"),
        ]:
            def run():
                node = quick_raw_node(voters, learners)
                node.raft.campaign(ct)

            t = timeit(run, iters)
            results.append(
                (f"campaign ({voters},{learners}) {name}", t * 1e6, "us/op")
            )


def bench_leader_propose(results, iters):
    """reference: benches/suites/raw_node.rs:35-79"""
    for size in [0, 32, 128, 512, 1024, 4096, 16384, 131072, 524288, 1048576]:
        node = quick_raw_node(1, 0)
        node.campaign()
        while node.has_ready():
            rd = node.ready()
            with node.store.wl() as core:
                core.append(rd.entries)
                if rd.hs is not None:
                    core.set_hardstate(rd.hs.clone())
            node.advance(rd)
            node.advance_apply()
        data = b"x" * size
        n = max(1, min(iters, 2_000_000 // max(size, 1)))

        def run():
            node.propose(b"", data)

        t = timeit(run, n)
        mbps = size / t / 1e6 if t > 0 and size else 0
        results.append((f"leader_propose {size}B", t * 1e6, f"us/op ({mbps:.0f} MB/s)"))


def bench_new_ready(results, iters):
    """Loaded-node ready (reference: benches/suites/raw_node.rs:81-141
    fixture: 100 appended + 100 committed 32KiB entries + messages)."""
    def setup():
        node = quick_raw_node(3, 0)
        node.raft.become_candidate()
        node.raft.become_leader()
        ents = [Entry(data=b"x" * 32 * 1024) for _ in range(100)]
        assert node.raft.append_entry(ents)
        return node

    node = setup()

    def run():
        if node.has_ready():
            rd = node.ready()
            with node.store.wl() as core:
                core.append(rd.entries)
            node.advance(rd)

    t = timeit(run, max(1, iters // 10))
    results.append(("RawNode::ready loaded", t * 1e6, "us/op"))


def bench_progress_new(results, iters):
    """reference: benches/suites/progress.rs:10-17"""
    t = timeit(lambda: Progress(9, 10), iters * 10)
    results.append(("Progress::new", t * 1e9, "ns/op"))


def bench_baseline_configs(results, quick):
    """The five BASELINE.json multi-group configs on whatever JAX device is
    active (TPU under the driver, CPU elsewhere)."""
    import functools

    import jax
    import jax.numpy as jnp

    from raft_tpu.multiraft import sim
    from raft_tpu.multiraft.sim import SimConfig

    configs = [
        ("config2: 1k x 3 uniform", 1_000, 3, "uniform"),
        ("config3: 100k x 5 zipf", 100_000, 5, "zipf"),
        ("config5: 1M x 3 storm", 1_000_000, 3, "none"),
    ]
    if quick:
        configs = configs[:1]
    rounds = 50
    for name, G, P, workload in configs:
        cfg = SimConfig(n_groups=G, n_peers=P)
        st = sim.init_state(cfg)
        crashed = jnp.zeros((P, G), bool)
        if workload == "zipf":
            # Zipf-skewed per-group append rates (TiKV-style hot regions):
            # a few groups take most of the write load.
            import numpy as _np

            rng = _np.random.RandomState(0)
            append = jnp.asarray(
                _np.minimum(rng.zipf(1.8, size=G), 8), dtype=jnp.int32
            )
        else:
            append = jnp.full((G,), 1 if workload == "uniform" else 0, jnp.int32)
        step = functools.partial(sim.step, cfg)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def multi(st, crashed=crashed, append=append, step=step):
            def body(s, _):
                return step(s, crashed, append), ()

            return jax.lax.scan(body, st, None, length=rounds)[0]

        st = multi(st)
        jax.block_until_ready(st)
        t0 = time.perf_counter()
        st = multi(st)
        jax.block_until_ready(st)
        dt = time.perf_counter() - t0
        results.append((name, G * rounds / dt / 1e6, "M ticks/s"))

    if not quick:
        results.append(bench_config4_reconfig_compiled())
        results.append(bench_config4_joint_churn())
        results.append(bench_read_barrier())
        results.append(bench_reads_workload())
        results.append(bench_fused_instrumented())
        results.append(bench_fused_damped())
        results.append(bench_prod_fused_split())


def bench_fused_instrumented(G=100_000, P=5):
    """The instrumented fused path (docs/PERF.md): health planes + an
    all-up link plane with per-link loss threaded through
    fast_multi_round(with_health, with_chaos) — the production-fleet
    configuration ISSUE 6 made the fast path.  election_tick=64 so the
    conservative lossy steady bound clears the k=32 fused horizon."""
    import functools

    import jax
    import jax.numpy as jnp

    from raft_tpu.multiraft import kernels, pallas_step, sim
    from raft_tpu.multiraft.sim import SimConfig

    cfg = SimConfig(
        n_groups=G, n_peers=P, election_tick=64, collect_health=True
    )
    k = 32
    kstep = pallas_step.fast_multi_round(
        cfg, k=k, with_health=True, with_chaos=True
    )
    st = sim.init_state(cfg)
    h = sim.init_health(cfg)
    crashed = jnp.zeros((P, G), bool)
    append = jnp.ones((G,), jnp.int32)
    link = jnp.ones((P, P, G), bool)
    loss = jnp.full(
        (P, P, G), kernels.LOSS_SCALE // 100, jnp.int32
    )  # 1% per-link loss
    step = jax.jit(functools.partial(sim.step, cfg))
    settle = 3 * cfg.election_tick
    for _ in range(settle):
        st = step(st, crashed, append)
    if not bool(pallas_step.steady_predicate(cfg, st, crashed, k, link)):
        # Same honesty check as bench.py --lossy: never report a general-
        # fallback number under the fused-instrumented label.
        print(
            "WARNING: steady predicate rejects the settled state; "
            "config3i is timing the general fallback",
            file=sys.stderr,
        )

    blocks = 4

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def multi(st, h, rb):
        def body(carry, i):
            s, hh = carry
            return kstep(s, crashed, append, link, loss, rb + i * k, hh), ()

        return jax.lax.scan(
            body, (st, h), jnp.arange(blocks, dtype=jnp.int32)
        )[0]

    st, h = multi(st, h, jnp.int32(settle))
    jax.block_until_ready(st)
    t0 = time.perf_counter()
    st, h = multi(st, h, jnp.int32(settle + blocks * k))
    jax.block_until_ready(st)
    dt = time.perf_counter() - t0
    return (
        f"config3i: {G // 1000}k x {P} fused health+chaos",
        G * blocks * k / dt / 1e6,
        "M ticks/s",
    )


def bench_fused_damped(G=100_000, P=5):
    """config3cq: the TRUE production configuration — health + counters +
    check-quorum + pre-vote (raft-rs's deployed TiKV settings) riding the
    ISSUE 8 fused damped kernel (_steady_damped_kernel with_health +
    with_counters).  election_tick=64 so the conservative free-running
    damped bound clears the k=32 fused horizon; the lossless cq predicate
    (kernels.cq_boundary_safe) proves every in-horizon check-quorum
    boundary passes, so every block fuses."""
    import functools

    import jax
    import jax.numpy as jnp

    from raft_tpu.multiraft import kernels, pallas_step, sim
    from raft_tpu.multiraft.sim import SimConfig

    cfg = SimConfig(
        n_groups=G, n_peers=P, election_tick=64, collect_health=True,
        collect_counters=True, check_quorum=True, pre_vote=True,
    )
    k = 32
    kstep = pallas_step.fast_multi_round(
        cfg, k=k, with_health=True, with_counters=True
    )
    st = sim.init_state(cfg)
    h = sim.init_health(cfg)
    ctrs = kernels.zero_counters()
    crashed = jnp.zeros((P, G), bool)
    append = jnp.ones((G,), jnp.int32)
    step = jax.jit(functools.partial(sim.step, cfg))
    settle = 3 * cfg.election_tick
    for _ in range(settle):
        st = step(st, crashed, append)
    if not bool(pallas_step.steady_predicate(cfg, st, crashed, k)):
        # Same honesty check as bench.py --check-quorum: never report a
        # general-fallback number under the fused-damped label.
        print(
            "WARNING: steady predicate rejects the settled damped state; "
            "config3cq is timing the general fallback",
            file=sys.stderr,
        )

    blocks = 4

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def multi(st, ra, ctrs, h):
        def body(carry, _):
            s, raw, cc, hh = carry
            s, cc, hh = kstep(
                sim.unpack_ra_carry(s, raw), crashed, append, cc, hh
            )
            s, raw = sim.pack_ra_carry(s)
            return (s, raw, cc, hh), ()

        return jax.lax.scan(
            body, (st, ra, ctrs, h), None, length=blocks
        )[0]

    st, ra = sim.pack_ra_carry(st)
    st, ra, ctrs, h = multi(st, ra, ctrs, h)
    jax.block_until_ready(st)
    t0 = time.perf_counter()
    st, ra, ctrs, h = multi(st, ra, ctrs, h)
    jax.block_until_ready(st)
    dt = time.perf_counter() - t0
    return (
        f"config3cq: {G // 1000}k x {P} fused health+ctrs+cq+pv",
        G * blocks * k / dt / 1e6,
        "M ticks/s",
    )


def bench_prod_fused_split(G=100_000):
    """config4f: the FULL production configuration under membership churn
    (ISSUE 11) — health + counters + check-quorum + pre-vote + a chaos
    overlay + the 3-op prod_fused ReconfigPlan — through the
    split-horizon runner, the configuration PR 10's unsplit scan fuses
    0% of.  Delegates to bench.bench_prod_fused so the production regime
    (SimConfig, settle, split knobs) is defined ONCE; the row label
    carries the measured fused fraction so the table can't quietly
    report a general-path number as fused."""
    import os

    import bench

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "examples", "reconfig", "prod_fused.json",
    )
    stats = bench.bench_prod_fused(path, groups=G, reps=2)
    return (
        f"config4f: {G // 1000}k x {stats['report']['peers']} split-fused "
        f"prod churn (fused_frac {stats['fused_frac']:.2f})",
        stats["median"] / 1e6,
        "M ticks/s",
    )


def bench_read_barrier():
    """Batched linearizable ReadIndex barrier (sim.read_index) at 100k
    groups: reads/sec the batch can answer — TiKV-style follower-read /
    lease-read traffic is orders of magnitude hotter than writes, so the
    barrier must not touch the step's critical path (it is a pure gather +
    two quorum counts per group)."""
    import functools

    import jax
    import jax.numpy as jnp

    from raft_tpu.multiraft import sim
    from raft_tpu.multiraft.sim import SimConfig

    G, P = 100_000, 5
    cfg = SimConfig(n_groups=G, n_peers=P)
    st = sim.init_state(cfg)
    crashed = jnp.zeros((P, G), bool)
    append = jnp.ones((G,), jnp.int32)
    step = jax.jit(functools.partial(sim.step, cfg))
    for _ in range(60):  # settle past the split-vote tail: all groups elect
        st = step(st, crashed, append)
    reads = 50
    ri = jax.jit(functools.partial(sim.read_index, cfg))

    @jax.jit
    def many(st, crashed):
        def body(acc, _):
            return acc + sim.read_index(cfg, st, crashed), ()

        return jax.lax.scan(
            body, jnp.zeros((G,), jnp.int32), None, length=reads
        )[0]

    out = ri(st, crashed)
    assert int(out.min()) >= 0, "read barrier returned -1 on settled batch"
    jax.block_until_ready(many(st, crashed))
    t0 = time.perf_counter()
    jax.block_until_ready(many(st, crashed))
    dt = time.perf_counter() - t0
    return ("read_index: 100k x 5 barrier", G * reads / dt / 1e6, "M reads/s")


def bench_reads_workload(G=100_000):
    """config3r: the SERVING workload (ISSUE 13) — the zipf_mixed client
    plan (Zipf-skewed writes + Safe/Lease read mixes) through the
    production damped configuration with the split-fused runner, the
    linearizability safety net live every round.  Delegates to
    bench.bench_reads so the regime (SimConfig, settle, split knobs) is
    defined ONCE; the row label carries the measured fused fraction and
    the device-reduced read p99 so the table can't hide a degraded read
    path behind a throughput number."""
    import os

    import bench

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "examples", "reads", "zipf_mixed.json",
    )
    stats = bench.bench_reads(path, groups=G, reps=2)
    return (
        f"config3r: {G // 1000}k x {stats['report']['peers']} zipf "
        f"read/write mix (fused_frac {stats['fused_frac']:.2f}, "
        f"read_p99 {stats['read_p99']}r)",
        stats["median"] / 1e6,
        "M ticks/s",
    )


def bench_config4_reconfig_compiled():
    """BASELINE config 4, the real protocol (ISSUE 10): 100k groups under
    joint-consensus reconfig churn as ONE compiled scan — the conf entry
    proposes at each group's leader, its mask swap gates on the dual-
    majority commit, and the joint-window safety invariants fold every
    round (raft_tpu.multiraft.reconfig), zero host round trips."""
    import jax

    from raft_tpu.multiraft import reconfig, sim
    from raft_tpu.multiraft.sim import SimConfig

    G, P = 100_000, 5
    plan = reconfig.ReconfigPlan(
        name="config4",
        n_peers=P,
        voters=[1, 2, 3],
        phases=[
            reconfig.ReconfigPhase(rounds=12, append=1),
            reconfig.ReconfigPhase(
                rounds=16, append=1,
                op={"enter_joint": [{"add": 4}, {"add": 5}, {"remove": 1}]},
            ),
            reconfig.ReconfigPhase(
                rounds=16, append=1, op={"leave_joint": True}
            ),
            reconfig.ReconfigPhase(
                rounds=16, append=1, op={"add_voter": 1}
            ),
        ],
    )
    cfg = SimConfig(n_groups=G, n_peers=P, collect_health=True)
    compiled = reconfig.compile_plan(plan, G)
    runner = reconfig.make_runner(cfg, compiled)

    def fresh():
        st = sim.init_state(cfg, *reconfig.initial_masks(plan, G))
        return st, sim.init_health(cfg), reconfig.init_reconfig_state(st)

    out = runner(*fresh())  # compile + settle-free first run
    jax.block_until_ready(out[3])
    args = fresh()
    jax.block_until_ready(args)
    t0 = time.perf_counter()
    st, hl, rst, stats, rstats, safety = runner(*args)
    jax.block_until_ready(stats)
    dt = time.perf_counter() - t0
    assert not int(safety.sum()), "config4 run flagged safety violations"
    return (
        "config4: 100k x 5 compiled reconfig churn",
        G * plan.n_rounds / dt / 1e6,
        "M ticks/s",
    )


def bench_config4_joint_churn():
    """BASELINE config 4, the RETIRED pre-ISSUE-10 methodology (kept as
    the before/after anchor for bench_config4_reconfig_compiled): every k
    rounds a HOST-SIDE membership barrier swaps the voter/outgoing mask
    planes (enter-joint / leave-joint) around a donated device scan —
    exercising the JointConfig commit path but paying a host round trip
    and mask re-upload per swap, with no conf-entry protocol and no
    joint-window safety audit."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.multiraft import sim
    from raft_tpu.multiraft.sim import SimConfig

    G, P = 100_000, 5
    cfg = SimConfig(n_groups=G, n_peers=P)
    # joint: incoming {1,2,3} && outgoing {3,4,5}; simple: {1,2,3}
    vm = np.zeros((P, G), bool)
    vm[:3] = True
    om_joint = np.zeros((P, G), bool)
    om_joint[2:] = True
    om_none = np.zeros((P, G), bool)
    st = sim.init_state(
        cfg, jnp.asarray(vm, dtype=bool), jnp.asarray(om_joint, dtype=bool)
    )
    crashed = jnp.zeros((P, G), bool)
    append = jnp.ones((G,), jnp.int32)
    step = functools.partial(sim.step, cfg)

    k = 10

    @functools.partial(jax.jit, donate_argnums=(0,))
    def multi(st):
        def body(s, _):
            return step(s, crashed, append), ()

        return jax.lax.scan(body, st, None, length=k)[0]

    st = multi(st)
    jax.block_until_ready(st)
    t0 = time.perf_counter()
    swaps = 10
    for i in range(swaps):
        # membership barrier: leave/enter joint — host re-uploads the mask
        # planes (donation consumes the previous buffers, like a real
        # reconfig barrier would re-materialize them)
        om = om_none if i % 2 else om_joint
        st = st._replace(outgoing_mask=jnp.asarray(om, dtype=bool))
        st = multi(st)
    jax.block_until_ready(st)
    dt = time.perf_counter() - t0
    return (
        "config4: 100k x 5 joint churn",
        G * k * swaps / dt / 1e6,
        "M ticks/s",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    iters = 50 if args.quick else 300

    # The one backend decision: no TPU and no explicit CPU pin is an
    # error, and the table names the device its numbers came from.
    from raft_tpu import platform

    try:
        device = platform.device_fields()
    except RuntimeError as e:
        raise SystemExit(f"ERROR: {e}")
    platform.enable_compile_cache()
    print(
        f"device: {device['platform']} {device['device_kind']} "
        f"x{device['n_devices']}"
    )

    results = []
    bench_raft_new(results, iters)
    bench_campaign(results, max(10, iters // 10))
    bench_leader_propose(results, iters)
    bench_new_ready(results, iters)
    bench_progress_new(results, iters)
    bench_baseline_configs(results, args.quick)

    width = max(len(n) for n, _, _ in results)
    print(f"{'benchmark':<{width}}  value")
    print("-" * (width + 24))
    for name, value, unit in results:
        print(f"{name:<{width}}  {value:>12.2f} {unit}")


if __name__ == "__main__":
    main()
