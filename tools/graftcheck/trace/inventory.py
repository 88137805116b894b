"""The canonical graph inventory: every jitted hot-path entry point, as data.

Each ``GraphSpec`` names one compiled artifact of the production system —
entry point + flag combination + the donation structure its production
wrapper declares — and a builder that constructs it EXACTLY the way the
production wrapper does (``ClusterSim``'s jits, ``runner.make_runner``,
``sharding.sharded_step``), at a tiny audit shape
(G=8, P=3: jaxpr size and donation structure are shape-independent, so
the audit shape only has to be cheap).  ``trace/analysis.py`` runs
GC011-GC014 over the built artifacts; ``jaxpr_budget.json`` is keyed by
``GraphSpec.name``.

This registry is deliberately declarative — the flag matrix
(plain/counters/health/chaos x undamped/cq/cq+pv) and each graph's
expected donate_argnums live HERE, not scattered through the builders —
so a new plane or flag lands as one more row, and the trace gates come
for free.

Builders import jax/raft_tpu lazily so this module (and the rule
registry that imports it) stays importable in jax-less environments;
nothing here traces until ``trace.run_trace`` calls ``build()``.

GC011's escape hatch is the registry below, not line markers (violations
anchor at machine-chosen lines, so inline markers would be brittle):
``DONATION_ALLOW[(graph_name, param_path)] = "<why XLA declines this and
why that is acceptable>"``.  A stale entry — one matching no currently
declined donation — is itself a violation, exactly like a typo'd
allow-marker (GC000's discipline).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, NamedTuple, Tuple

# The audit shape: tiny on purpose (see module docstring).
G = 8
P = 3
SCAN_ROUNDS = 4  # run_compiled segment length in the audit graphs
DISPATCH_K = 4  # the split runners' fused block length in the audit graphs

# Per-graph jaxpr-const byte budget (GC012).  The healthy graphs carry
# only scalar/iota-sized consts (<= 64B observed across the whole
# inventory); anything larger is a closed-over plane — schedule arrays,
# masks, workloads — that bloats HBM at production G and defeats the
# compile cache (a new closure value is a new executable).  The budget
# must sit BELOW the smallest per-group plane at the audit shape or the
# rule cannot catch its own quarry: bool[P, P, G] is 72B and
# int32[P, G] is 96B at G=8/P=3, so 64B is the largest budget that
# still flags every accidentally-closed-over G-shaped plane.
DEFAULT_CONST_BYTES = 64

# GC011 allow-registry: (graph name, flattened param path) -> justification.
# Empty today — every declared donation in the inventory is accepted by
# XLA (the alias-map audit proves it); add entries here, with a reason,
# only for donations XLA genuinely cannot honor.
DONATION_ALLOW: Dict[Tuple[str, str], str] = {}

# The sharded-row audit shape (ISSUE 14).  Unlike the jaxpr-size rows,
# the GC015 collective audit inspects the PARTITIONED executable, so the
# shape must be large enough that every sharded axis actually tiles the
# 8-device audit mesh — in particular the packed bits_g recent_active
# carry's word axis (G/32 words needs G >= 32 * 8) — or the partitioner
# would legitimately insert gathers a production shape never sees.
G_SHARDED = 256

# GC015 allow-registry: (graph name, HLO collective opcode) ->
# justification.  A graph row with audit_collectives=True must contain
# EXACTLY the opcodes registered for it — an unregistered collective in
# the compiled module fails the build (the steady step/scan rows register
# none: that is the machine-checked "embarrassingly parallel across G"
# claim of sharding.py), and a registered opcode that no longer appears
# is rot, exactly like a stale DONATION_ALLOW entry.
COLLECTIVE_ALLOW: Dict[Tuple[str, str], str] = {
    (
        "sharded_status@spmd", "all-reduce",
    ): "the status reduction IS the cross-chip contract: psum(n_leaders)/"
       "psum(total_commit limbs)/pmin(commit)/pmax(term) all lower to "
       "all-reduce over ICI (sharding.global_status)",
    (
        "sharded_drain@health", "all-reduce",
    ): "the health-summary drain reduces threshold counts and the "
       "commit-lag histogram across shards (kernels.health_summary under "
       "the mesh) — the fixed-size summary is the only thing that leaves "
       "the device",
    (
        "sharded_drain@health", "all-gather",
    ): "health_summary's lax.top_k worst-offender extraction gathers the "
       "per-shard score vector before the global sort — O(topk + G) "
       "bytes once per drain cadence, never per round",
    (
        "sharded_scan@counters+spmd", "all-reduce",
    ): "the event-counter fold (kernels.count_events) psums per-round "
       "event counts into the [N_COUNTERS] replicated plane — the "
       "instrumented configuration's documented ICI cost, off by default",
}


class Built(NamedTuple):
    """One constructed artifact: the (jitted) callable, example args at
    the audit shape, and the donate_argnums its production wrapper
    declares — the registry's expectation, checked against the actual
    lowering by GC011."""

    fn: Callable
    args: tuple
    donate: Tuple[int, ...] = ()


@dataclass(frozen=True)
class GraphSpec:
    name: str  # budget key, e.g. "step@health+cq"
    anchor: str  # repo-relative module the entry point lives in
    build: Callable[[], Built]
    # GC011 lowers every audited graph (bidirectional drift check); the
    # compile (alias map) runs only when either side declares a donation.
    audit_donation: bool = True
    const_budget: int = DEFAULT_CONST_BYTES
    # GC015 (ISSUE 14): compile the graph over the multi-device audit
    # mesh and require its collective-op set to equal EXACTLY the opcodes
    # registered for it in COLLECTIVE_ALLOW (none registered = the
    # zero-collectives proof).  Only meaningful for graphs built over a
    # mesh; needs >= 2 devices (trace_inventory pins the virtual
    # 8-device CPU mesh).
    audit_collectives: bool = False


# --- builders ---------------------------------------------------------------


def _sim():
    from raft_tpu.multiraft import sim

    return sim


def _schedules_mod():
    """raft_tpu/multiraft/schedules.py loaded standalone by file path —
    the registry is stdlib-only by contract (GC018 leg (a) re-verifies
    that on every engine run), and loading it this way keeps this module
    importable in jax-less environments: going through the package would
    pull ``raft_tpu.multiraft.__init__`` and with it jax."""
    import importlib.util
    from pathlib import Path

    path = (
        Path(__file__).resolve().parents[3]
        / "raft_tpu" / "multiraft" / "schedules.py"
    )
    spec = importlib.util.spec_from_file_location(
        "_graftcheck_schedules", path
    )
    assert spec is not None and spec.loader is not None
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _base_args(cfg):
    import jax.numpy as jnp

    sim = _sim()
    st = sim.init_state(cfg)
    crashed = jnp.zeros((P, G), bool)
    append_n = jnp.zeros((G,), jnp.int32)
    return st, crashed, append_n


def _full_link():
    import jax.numpy as jnp

    return jnp.ones((P, P, G), bool)


def _step_builder(flags: dict, damping: dict, chaos: bool):
    def build() -> Built:
        sim = _sim()
        cfg = sim.SimConfig(n_groups=G, n_peers=P, **flags, **damping)
        cs = sim.ClusterSim(cfg)
        st, crashed, append_n = _base_args(cfg)
        link = _full_link() if chaos else None
        cc, ch = cfg.collect_counters, cfg.collect_health
        if cc and ch:
            return Built(
                cs._step_both,
                (st, crashed, append_n, cs._counters, cs._health, link),
                (0, 3, 4),
            )
        if cc:
            return Built(
                cs._step_counted,
                (st, crashed, append_n, cs._counters, link),
                (0, 3),
            )
        if ch:
            return Built(
                cs._step_health,
                (st, crashed, append_n, cs._health, link),
                (0, 3),
            )
        return Built(
            cs._step,
            (st, crashed, append_n, None, None, None, link),
            (0,),
        )

    return build


def _run_compiled_builder(flags: dict, damping: dict):
    def build() -> Built:
        sim = _sim()
        cfg = sim.SimConfig(n_groups=G, n_peers=P, **flags, **damping)
        cs = sim.ClusterSim(cfg)
        st, crashed, append_n = _base_args(cfg)
        runner = cs._compiled_runner(SCAN_ROUNDS, has_link=False)
        args: tuple = (st, crashed, append_n)
        donate: Tuple[int, ...] = (0,)
        if cfg.collect_counters:
            args = args + (cs._counters,)
            donate = donate + (len(args) - 1,)
        if cfg.collect_health:
            args = args + (cs._health,)
            donate = donate + (len(args) - 1,)
        return Built(runner, args, donate)

    return build


def _read_index_builder(chaos: bool):
    def build() -> Built:
        import functools

        import jax

        sim = _sim()
        cfg = sim.SimConfig(n_groups=G, n_peers=P)
        st, crashed, _ = _base_args(cfg)
        fn = jax.jit(functools.partial(sim.read_index, cfg))
        args = (st, crashed) + ((_full_link(),) if chaos else ())
        return Built(fn, args)

    return build


def _chaos_runner_builder(blackbox: bool = False):
    def build() -> Built:
        from raft_tpu.multiraft import chaos
        from raft_tpu.multiraft import runner as runner_mod

        sim = _sim()
        cfg = sim.SimConfig(
            n_groups=G, n_peers=P, collect_health=True,
            blackbox=blackbox,
        )
        st, _, _ = _base_args(cfg)
        plan = chaos.ChaosPlan(
            name="graftcheck-inventory",
            n_peers=P,
            phases=[
                chaos.ChaosPhase(
                    rounds=6, partition=[[1], [2, 3]], loss_all=0.05
                ),
                chaos.ChaosPhase(rounds=6, append=1),
            ],
        )
        compiled = chaos.compile_plan(plan, G)
        runner = runner_mod.make_runner(cfg, (compiled,))
        # make_runner exposes its underlying jit and full argument list
        # (state, health, *schedule arrays) precisely for this audit.
        bb = (sim.init_blackbox(cfg),) if blackbox else ()
        return Built(
            runner.jitted,
            (st, sim.init_health(cfg)) + bb + runner.schedule_args,
            (0, 1, 2) if blackbox else (0, 1),
        )

    return build


def _blackbox_step_builder():
    def build() -> Built:
        sim = _sim()
        cfg = sim.SimConfig(
            n_groups=G, n_peers=P, collect_health=True, blackbox=True
        )
        cs = sim.ClusterSim(cfg)
        st, crashed, append_n = _base_args(cfg)
        # The wrapper declares donate_argnums=(0, 3, 4, 5); argnum 3
        # (the counter plane) is None in this health+blackbox combo, so
        # the lowering donates (0, 4, 5) — declare what lowers.
        return Built(
            cs._step_blackbox,
            (st, crashed, append_n, None, cs._health, cs._blackbox,
             None),
            (0, 4, 5),
        )

    return build


def _reconfig_runner_builder(
    with_chaos: bool = False, damping: bool = False
):
    def build() -> Built:
        from raft_tpu.multiraft import chaos, reconfig
        from raft_tpu.multiraft import runner as runner_mod

        sim = _sim()
        dflags = (
            {"check_quorum": True, "pre_vote": True} if damping else {}
        )
        cfg = sim.SimConfig(
            n_groups=G, n_peers=P, collect_health=True, **dflags
        )
        plan = reconfig.ReconfigPlan(
            name="graftcheck-inventory",
            n_peers=P,
            phases=[
                reconfig.ReconfigPhase(rounds=4, append=1),
                reconfig.ReconfigPhase(
                    rounds=4,
                    op={"enter_joint": [{"add": 3}]},
                ),
                reconfig.ReconfigPhase(
                    rounds=4, op={"leave_joint": True}
                ),
            ],
            voters=[1, 2],
        )
        compiled = reconfig.compile_plan(plan, G)
        chaos_compiled = None
        if with_chaos:
            cplan = chaos.ChaosPlan(
                name="graftcheck-inventory",
                n_peers=P,
                phases=[
                    chaos.ChaosPhase(
                        rounds=8, partition=[[1], [2, 3]], loss_all=0.05
                    ),
                    chaos.ChaosPhase(rounds=4, append=1),
                ],
            )
            chaos_compiled = chaos.compile_plan(cplan, G)
        vm, om, lm = reconfig.initial_masks(plan, G)
        st = sim.init_state(cfg, vm, om, lm)
        runner = runner_mod.make_runner(cfg, (compiled, chaos_compiled))
        # make_runner exposes its underlying jit and full argument list
        # (state, health, rstate, *schedule arrays) for this audit.
        return Built(
            runner.jitted,
            (
                st, sim.init_health(cfg),
                reconfig.init_reconfig_state(st),
            ) + runner.schedule_args,
            (0, 1, 2),
        )

    return build


def _split_runner_builder():
    def build() -> Built:
        import jax.numpy as jnp

        from raft_tpu.multiraft import chaos, kernels, reconfig
        from raft_tpu.multiraft import runner as runner_mod

        sim = _sim()
        cfg = sim.SimConfig(
            n_groups=G, n_peers=P, collect_health=True,
            collect_counters=True, check_quorum=True, pre_vote=True,
        )
        plan = reconfig.ReconfigPlan(
            name="graftcheck-inventory",
            n_peers=P,
            phases=[
                reconfig.ReconfigPhase(rounds=8, append=1),
                reconfig.ReconfigPhase(
                    rounds=8, op={"add_voter": 3}, append=1
                ),
            ],
            voters=[1, 2],
        )
        cplan = chaos.ChaosPlan(
            name="graftcheck-inventory",
            n_peers=P,
            phases=[chaos.ChaosPhase(rounds=16, loss_all=0.01)],
        )
        compiled = reconfig.compile_plan(plan, G)
        chaos_compiled = chaos.compile_plan(cplan, G)
        vm, om, lm = reconfig.initial_masks(plan, G)
        st = sim.init_state(cfg, vm, om, lm)
        runner = runner_mod.make_runner(
            cfg, (compiled, chaos_compiled), split=True, k=DISPATCH_K,
            window=4, with_counters=True,
        )
        # The fused-block jit is the split runner's hot graph: the
        # steady-predicate + pending guard, the fused kernel, AND the
        # k-round general fallback all under one cond; the carry
        # (state, health, rstate, counters) is donated end to end.
        args = (
            st, sim.init_health(cfg), reconfig.init_reconfig_state(st),
            jnp.zeros((chaos.N_CHAOS_STATS,), jnp.int32),
            jnp.zeros((reconfig.N_RECONFIG_STATS,), jnp.int32),
            jnp.zeros((kernels.N_SAFETY,), jnp.int32),
            kernels.zero_counters(),
            jnp.int32(0),
            jnp.int32(0),
        ) + runner.schedule_args
        return Built(runner.fused_jit, args, (0, 1, 2, 6))

    return build


def _transfer_step_builder():
    def build() -> Built:
        import functools

        import jax

        sim = _sim()
        cfg = sim.SimConfig(
            n_groups=G, n_peers=P, collect_health=True, transfer=True
        )
        st, crashed, append_n = _base_args(cfg)
        fn = jax.jit(functools.partial(sim.step, cfg))
        import jax.numpy as jnp

        # Positional tail: (group_ids, counters, health, link,
        # reconfig_propose, transfer_propose, campaign_kick) — the
        # transfer-enabled production round with both action planes live.
        args = (
            st, crashed, append_n, None, None, sim.init_health(cfg),
            None, None,
            jnp.zeros((G,), jnp.int32),
            jnp.zeros((P, G), bool),
        )
        return Built(fn, args)

    return build


def _autopilot_runner_builder():
    def build() -> Built:
        import jax.numpy as jnp

        from raft_tpu.multiraft import chaos, kernels, reconfig
        from raft_tpu.multiraft import runner as runner_mod

        sim = _sim()
        cfg = sim.SimConfig(
            n_groups=G, n_peers=P, collect_health=True, transfer=True
        )
        cplan = chaos.ChaosPlan(
            name="graftcheck-inventory",
            n_peers=P,
            phases=[
                chaos.ChaosPhase(
                    rounds=SCAN_ROUNDS * 2, partition=[[1], [2, 3]],
                    append=1,
                ),
            ],
        )
        chaos_compiled = chaos.compile_plan(cplan, G)
        compiled = reconfig.empty_reconfig_schedule(
            SCAN_ROUNDS * 2, P, G
        )
        runner = runner_mod.make_runner(
            cfg, (compiled, chaos_compiled), cadence=SCAN_ROUNDS
        )
        st, _, _ = _base_args(cfg)
        # The flat schedule tail comes from the registry
        # (runner.schedule_args) — never hand-listed (GC018).
        args = (
            st, sim.init_health(cfg), reconfig.init_reconfig_state(st),
            jnp.zeros((chaos.N_CHAOS_STATS,), jnp.int32),
            jnp.zeros((reconfig.N_RECONFIG_STATS,), jnp.int32),
            jnp.zeros((kernels.N_SAFETY,), jnp.int32),
            jnp.int32(0),
            jnp.int32(0),
            jnp.zeros((G,), jnp.int32),
            jnp.zeros((P, G), bool),
        ) + runner_mod.schedule_args(compiled, chaos_compiled)
        return Built(runner, args, (0, 1, 2, 3, 4, 5, 6))

    return build


def _read_step_builder():
    def build() -> Built:
        import functools

        import jax
        import jax.numpy as jnp

        sim = _sim()
        cfg = sim.SimConfig(
            n_groups=G, n_peers=P, collect_health=True,
            check_quorum=True, lease_read=True,
        )
        st, crashed, append_n = _base_args(cfg)
        fn = jax.jit(functools.partial(sim.step, cfg))
        # Positional tail: (group_ids, counters, health, link,
        # reconfig_propose, transfer_propose, campaign_kick,
        # read_propose) — the damped round with the client-read phase
        # live (lease gate + nudge-cutoff ReadIndex fallback).
        args = (
            st, crashed, append_n, None, None, sim.init_health(cfg),
            jnp.ones((P, P, G), bool), None, None, None,
            jnp.full((G,), sim.READ_LEASE, jnp.int32),
        )
        return Built(fn, args)

    return build


def _client_plan():
    from raft_tpu.multiraft import workload

    return workload.ClientPlan(
        name="graftcheck-inventory",
        n_peers=P,
        phases=[
            workload.ClientPhase(rounds=SCAN_ROUNDS, append=1),
            workload.ClientPhase(
                rounds=SCAN_ROUNDS, read_every=2, read_mode="lease",
                write_zipf=1.8,
            ),
            workload.ClientPhase(
                rounds=SCAN_ROUNDS, read_every=2, read_mode="safe"
            ),
        ],
    )


def _workload_runner_builder():
    def build() -> Built:
        from raft_tpu.multiraft import reconfig, workload
        from raft_tpu.multiraft import runner as runner_mod

        sim = _sim()
        cfg = sim.SimConfig(
            n_groups=G, n_peers=P, collect_health=True,
            check_quorum=True, lease_read=True,
        )
        compiled = workload.compile_plan(_client_plan(), G)
        runner = runner_mod.make_runner(cfg, (compiled,))
        st, _, _ = _base_args(cfg)
        return Built(
            runner.jitted,
            (
                st, sim.init_health(cfg),
                reconfig.init_reconfig_state(st),
                workload.init_read_carry(G),
            ) + runner.schedule_args,
            (0, 1, 2, 3),
        )

    return build


def _workload_split_builder():
    def build() -> Built:
        import jax.numpy as jnp

        from raft_tpu.multiraft import chaos, kernels, reconfig, workload
        from raft_tpu.multiraft import runner as runner_mod

        sim = _sim()
        cfg = sim.SimConfig(
            n_groups=G, n_peers=P, collect_health=True,
            check_quorum=True, lease_read=True,
        )
        compiled = workload.compile_plan(_client_plan(), G)
        runner = runner_mod.make_runner(
            cfg, (compiled,), split=True, k=DISPATCH_K
        )
        st, _, _ = _base_args(cfg)
        # The fused-block jit is the split runner's hot graph: the
        # steady/read-pending/lease-provable predicate, the fused damped
        # kernel with the closed-form receipt fold, AND the k-round
        # general fallback (full read machinery) under one cond.
        args = (
            st, sim.init_health(cfg), reconfig.init_reconfig_state(st),
            jnp.zeros((chaos.N_CHAOS_STATS,), jnp.int32),
            jnp.zeros((reconfig.N_RECONFIG_STATS,), jnp.int32),
            jnp.zeros((kernels.N_SAFETY,), jnp.int32),
            workload.init_read_carry(G),
            jnp.zeros((workload.N_READ_STATS,), jnp.int32),
            jnp.zeros((workload.N_LAT_BUCKETS,), jnp.int32),
            jnp.int32(0),
        ) + runner.block_args[0] + runner.schedule_args
        return Built(runner.fused_jit, args, (0, 1, 2, 6))

    return build


def _sharded_mesh():
    """The GC015 audit mesh: up to 8 devices (the virtual CPU mesh
    trace_inventory pins; a 1-device fallback keeps the non-collective
    checks runnable anywhere, with GC015 skipped loudly)."""
    import jax

    from raft_tpu.multiraft import sharding

    return sharding.make_mesh(min(8, len(jax.devices())))


def _sharded_args(cfg, mesh):
    """Mesh-placed (state, crashed, append_n) at the sharded audit shape."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from raft_tpu.multiraft import sharding

    st = sharding.sharded_init_state(cfg, mesh)
    crashed = jax.device_put(
        jnp.zeros((P, G_SHARDED), bool),
        NamedSharding(mesh, PartitionSpec(None, "groups")),
    )
    append_n = jax.device_put(
        jnp.zeros((G_SHARDED,), jnp.int32),
        NamedSharding(mesh, PartitionSpec("groups")),
    )
    return st, crashed, append_n


def _sharded_builder(kind: str):
    def build() -> Built:
        from raft_tpu.multiraft import sharding

        sim = _sim()
        # The production mesh config (ClusterSim(mesh=) sets it the same
        # way): spmd=True swaps the election cond's global-any predicate
        # — the one collective the plain step graph would otherwise
        # carry — for its bit-identical masked form.
        cfg = sim.SimConfig(n_groups=G_SHARDED, n_peers=P, spmd=True)
        mesh = _sharded_mesh()
        st, crashed, append_n = _sharded_args(cfg, mesh)
        if kind == "step":
            return Built(
                sharding.sharded_step(cfg, mesh), (st, crashed, append_n),
                (0,),
            )
        if kind == "status":
            return Built(sharding.global_status(cfg, mesh).jitted, (st,))
        return Built(
            sharding.sharded_read_index(cfg, mesh), (st, crashed)
        )

    return build


def _sharded_scan_builder(flags: dict, damping: dict):
    """ClusterSim(mesh=).run_compiled's donated scan segment — the ISSUE
    14 steady mesh path, exactly as the production wrapper builds it
    (sharded init, placed planes, whole carry donated)."""

    def build() -> Built:
        sim = _sim()
        cfg = sim.SimConfig(
            n_groups=G_SHARDED, n_peers=P, **flags, **damping
        )
        mesh = _sharded_mesh()
        cs = sim.ClusterSim(cfg, mesh=mesh)
        _, crashed, append_n = _sharded_args(cs.cfg, mesh)
        runner = cs._compiled_runner(SCAN_ROUNDS, has_link=False)
        args: tuple = (cs.state, crashed, append_n)
        donate: Tuple[int, ...] = (0,)
        if cfg.collect_counters:
            args = args + (cs._counters,)
            donate = donate + (len(args) - 1,)
        if cfg.collect_health:
            args = args + (cs._health,)
            donate = donate + (len(args) - 1,)
        return Built(runner, args, donate)

    return build


def _sharded_drain_builder():
    """The mesh drain reduction: kernels.health_summary over the sharded
    health planes (what _begin_drain dispatches device-side) — the
    fixed-size summary is the only cross-chip product."""

    def build() -> Built:
        sim = _sim()
        cfg = sim.SimConfig(
            n_groups=G_SHARDED, n_peers=P, collect_health=True, spmd=True
        )
        mesh = _sharded_mesh()
        cs = sim.ClusterSim(cfg, mesh=mesh)
        return Built(cs._summary_fn, (cs._health.planes,))

    return build


# --- the registry -----------------------------------------------------------

# builder key (schedules.RunnerVariant.builder) -> the local builder
# factory.  The compiled-runner GraphSpec rows below are DERIVED from
# raft_tpu/multiraft/schedules.py's RUNNER_VARIANTS through this map —
# GC018 forbids hand-listing a runner graph here (no string literal in
# this module may equal a runner-variant name), so a new runner variant
# lands as one registry row and its trace gates (GC011-GC014, GC019)
# come for free.
_RUNNER_BUILDERS: Dict[str, Callable[..., Callable[[], Built]]] = {
    "chaos": _chaos_runner_builder,
    "reconfig": _reconfig_runner_builder,
    "reconfig_split": _split_runner_builder,
    "workload": _workload_runner_builder,
    "workload_split": _workload_split_builder,
    "autopilot": _autopilot_runner_builder,
}

# Every runner variant is built by runner.make_runner.
_RUNNER_ANCHOR = "raft_tpu/multiraft/runner.py"


def _runner_specs() -> List[GraphSpec]:
    """One GraphSpec per schedules.RUNNER_VARIANTS row: names, builder
    selection, and builder options all come from the schedule registry."""
    schedules = _schedules_mod()
    return [
        GraphSpec(
            name=variant.name,
            anchor=_RUNNER_ANCHOR,
            build=_RUNNER_BUILDERS[variant.builder](
                **dict(variant.options)
            ),
        )
        for variant in schedules.runner_variants()
    ]


_INSTRUMENT_FLAGS: List[Tuple[str, dict, bool]] = [
    # (label, SimConfig flags, link plane threaded)
    ("plain", {}, False),
    ("counters", {"collect_counters": True}, False),
    ("health", {"collect_health": True}, False),
    ("chaos", {}, True),
]

_DAMPING_FLAGS: List[Tuple[str, dict]] = [
    ("", {}),
    ("cq", {"check_quorum": True}),
    ("cq+pv", {"check_quorum": True, "pre_vote": True}),
]


def _specs() -> List[GraphSpec]:
    sim_py = "raft_tpu/multiraft/sim.py"
    out: List[GraphSpec] = []
    for ilabel, iflags, chaos in _INSTRUMENT_FLAGS:
        for dlabel, dflags in _DAMPING_FLAGS:
            name = f"step@{ilabel}" + (f"+{dlabel}" if dlabel else "")
            out.append(
                GraphSpec(
                    name=name,
                    anchor=sim_py,
                    build=_step_builder(iflags, dflags, chaos),
                )
            )
    out.append(
        GraphSpec(
            name="run_compiled@plain",
            anchor=sim_py,
            build=_run_compiled_builder({}, {}),
        )
    )
    out.append(
        GraphSpec(
            # The chunked counter-drain segment (docs/PERF.md "Donated
            # scan carries"): the whole carry — state + counter + health
            # planes — must stay donated, or run_compiled doubles its HBM.
            name="run_compiled@counters+health",
            anchor=sim_py,
            build=_run_compiled_builder(
                {"collect_counters": True, "collect_health": True}, {}
            ),
        )
    )
    out.append(
        GraphSpec(
            # The packed recent_active carry (ISSUE 8): donated bool plane
            # in, packed words inside, unpacked plane out — the aliasing
            # across the pack boundary is exactly what GC011 verifies.
            name="run_compiled@plain+cq+pv",
            anchor=sim_py,
            build=_run_compiled_builder(
                {}, {"check_quorum": True, "pre_vote": True}
            ),
        )
    )
    out.append(
        GraphSpec(
            # The transfer-enabled round (ISSUE 12): the pre-tick
            # transfer pump + both autopilot action planes live; the
            # transfer-OFF graphs are the bit-identical step@* rows
            # above (the pinned-unchanged claim).
            name="step@health+transfer",
            anchor=sim_py,
            build=_transfer_step_builder(),
            audit_donation=False,
        )
    )
    out.append(
        GraphSpec(
            name="read_index@plain", anchor=sim_py,
            build=_read_index_builder(False),
        )
    )
    out.append(
        GraphSpec(
            name="read_index@chaos", anchor=sim_py,
            build=_read_index_builder(True),
        )
    )
    out.append(
        GraphSpec(
            # The read-enabled damped round (ISSUE 13): the client-read
            # phase (lease gate + nudge-cutoff ReadIndex fallback) live
            # via read_propose; the read-OFF graphs are the bit-identical
            # step@* rows above (the pinned-unchanged claim).
            name="step@health+reads+cq",
            anchor=sim_py,
            build=_read_step_builder(),
            audit_donation=False,
        )
    )
    out.append(
        GraphSpec(
            # The forensics-instrumented round (ISSUE 15): health + the
            # black-box trace fold riding step(blackbox=) — the
            # blackbox-OFF graphs are the bit-identical step@* rows
            # above (the pinned-unchanged claim).
            name="step@health+blackbox",
            anchor=sim_py,
            build=_blackbox_step_builder(),
        )
    )
    # The compiled-runner rows (chaos/reconfig/split/workload/autopilot
    # scans — ISSUE 9/10/11/12/13/15) are derived from the schedule
    # registry, never hand-listed here (GC018).
    out.extend(_runner_specs())
    sharding_py = "raft_tpu/multiraft/sharding.py"
    out.append(
        GraphSpec(
            # The steady sharded step: ZERO collectives registered — this
            # row IS the machine-checked "embarrassingly parallel across
            # G" claim of sharding.py's docstring (SimConfig.spmd removes
            # the election cond's global-any predicate).
            name="sharded_step@spmd", anchor=sharding_py,
            build=_sharded_builder("step"),
            audit_collectives=True,
        )
    )
    out.append(
        GraphSpec(
            # The ICI status reduction: exactly its psum/pmin set
            # (COLLECTIVE_ALLOW) — including the ISSUE 14 total_commit
            # limb psums that replaced the wrapping single int32 sum.
            name="sharded_status@spmd", anchor=sharding_py,
            build=_sharded_builder("status"),
            audit_collectives=True,
        )
    )
    out.append(
        GraphSpec(
            name="sharded_read_index@spmd", anchor=sharding_py,
            build=_sharded_builder("read_index"),
            audit_collectives=True,
        )
    )
    out.append(
        GraphSpec(
            # ClusterSim(mesh=).run_compiled's donated steady scan
            # segment (ISSUE 14): whole carry donated under
            # jit-with-shardings, zero collectives.
            name="sharded_scan@spmd", anchor=sharding_py,
            build=_sharded_scan_builder({}, {}),
            audit_collectives=True,
        )
    )
    out.append(
        GraphSpec(
            # The damped mesh scan: the packed bits_g recent_active carry
            # sharded on its group-minor word axis (G_SHARDED/32 words
            # tile the 8-device mesh), donated through the pack/unpack
            # boundary, still zero collectives.
            name="sharded_scan@spmd+cq+pv", anchor=sharding_py,
            build=_sharded_scan_builder(
                {}, {"check_quorum": True, "pre_vote": True}
            ),
            audit_collectives=True,
        )
    )
    out.append(
        GraphSpec(
            # The instrumented mesh scan: the event-counter fold psums
            # per round (registered) — the documented ICI cost of
            # collect_counters on a mesh.
            name="sharded_scan@counters+spmd", anchor=sharding_py,
            build=_sharded_scan_builder({"collect_counters": True}, {}),
            audit_collectives=True,
        )
    )
    out.append(
        GraphSpec(
            # The drain-cadence health reduction under the mesh: its
            # registered all-reduce/all-gather set and nothing else.
            name="sharded_drain@health", anchor=sharding_py,
            build=_sharded_drain_builder(),
            audit_collectives=True,
        )
    )
    return out


REGISTRY: List[GraphSpec] = _specs()


def graph_names() -> List[str]:
    return [spec.name for spec in REGISTRY]
