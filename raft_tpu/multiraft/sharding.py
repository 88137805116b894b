"""Multi-chip scale-out: shard the group axis over a device mesh.

The MultiRaft batch is embarrassingly parallel across groups — every [G, P]
plane shards on G ('groups' mesh axis), the peer axis stays local to a chip
(P <= 8; a group's whole quorum computation is a few lanes of one VPU
register).  XLA therefore inserts NO collectives in the steady-state step
graph — a claim that is machine-checked, not assumed, since ISSUE 14: the
graftcheck GC015 collective audit compiles the sharded step/scan rows of
the trace inventory over a multi-device mesh and fails the build on ANY
collective op in them (SimConfig.spmd replaces the one offender, the
election-phase cond's global-any predicate, with its bit-identical masked
form).  The only cross-chip traffic is the status/drain reductions (leader
counts, commit mins, health summaries), which ride ICI via psum/pmin
inside shard_map — exactly the reduction set registered in the GC015
allow-registry (tools/graftcheck/trace/inventory.py COLLECTIVE_ALLOW).

The production mesh path is `ClusterSim(cfg, mesh=...)` (ISSUE 14): the
bootstrap builds each shard device-resident (sharded_init_state — the
global [P, P, G] planes never materialize on one host), every run_*
entry point places its schedule arrays with the *_sharding specs below,
and the donated run_compiled scan segments, the split-fused runners, and
the drain/scan overlap all execute under jit-with-shardings unchanged —
bit-identical to the single-device path on the golden chaos and reconfig
corpora (tests/test_sharded_parity.py, tools/sharded_parity_report.py).

This is the direct analog of data parallelism for consensus (SURVEY.md §2
parallelism checklist item (a)); peer-axis vectorization is item (b); the
metrics collectives are item (c)'s intra-pod half.  Cross-host real Raft
traffic (DCN) terminates in the host driver, not here.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import planes, sim
from .kernels import ROLE_LEADER
from .sim import SimConfig, SimState


def make_mesh(
    n_devices: Optional[int] = None, axis: str = "groups", devices=None
) -> Mesh:
    """1-D device mesh over the group axis.  Pass `devices` explicitly to
    pin the backend (e.g. jax.devices("cpu") for a virtual dryrun mesh).

    The axis is an Auto axis: every graph here is written for the
    partitioner to split along G from the operand shardings (jit with
    shardings), not for sharding-in-types — under jax's default Explicit
    axis a pallas_call refuses to trace outside shard_map."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return jax.make_mesh(
        (len(devices),), (axis,), devices=list(devices),
        axis_types=(jax.sharding.AxisType.Auto,),
    )


def _row_sharding(mesh: Mesh, axis: str, row) -> NamedSharding:
    """The registry row's NamedSharding: "minor-G" shards the trailing
    group axis with every leading axis replicated ("[P, G]" -> P(None,
    axis), "[P, P, G]" -> P(None, None, axis), "[G]" -> P(axis));
    "replicate" is a whole-array replica (scalars, fixed-size
    accumulators)."""
    if row.sharding == "replicate":
        return NamedSharding(mesh, P())
    assert row.sharding == "minor-G", row
    return NamedSharding(
        mesh, P(*(None,) * planes.leading_axes(row), axis)
    )


def state_sharding(
    mesh: Mesh, axis: str = "groups", damped: bool = False,
    transfer: bool = False,
) -> SimState:
    """PartitionSpecs for every SimState field, built from the plane
    registry (planes.py): the group axis (minor, the vector-lane axis of
    the peer-major [P, G] layout) is sharded; the peer axis stays local
    to the chip.  Flag-gated rows get a spec only when their flag maps to
    an enabled argument — `damped` covers the check_quorum/pre_vote rows
    (recent_active [P, P, G], sharded on G like the other pairwise
    planes), `transfer` the lead_transferee [P, G] row — and None
    otherwise, matching the absent plane."""
    enabled = {"check_quorum": damped, "pre_vote": damped,
               "transfer": transfer}
    specs = {}
    for row in planes.rows(owner="SimState"):
        if row.flag and not any(enabled.get(f, False) for f in row.flag):
            specs[row.name] = None
        else:
            specs[row.name] = _row_sharding(mesh, axis, row)
    return SimState(**specs)


def shard_state(state: SimState, mesh: Mesh, axis: str = "groups") -> SimState:
    shardings = state_sharding(
        mesh, axis, damped=state.recent_active is not None,
        transfer=state.transferee is not None,
    )
    return jax.tree.map(jax.device_put, state, shardings)


def sharded_init_state(
    cfg: SimConfig,
    mesh: Mesh,
    voter_mask=None,
    outgoing_mask=None,
    learner_mask=None,
    axis: str = "groups",
) -> SimState:
    """Bootstrap a fleet DIRECTLY onto the mesh: init_state under jit with
    out_shardings, so every plane — including the [P, P, G] pairwise
    matched/agree/recent_active planes, the HBM cost at production G —
    materializes as per-chip shards and the global arrays never exist on
    one host (the ISSUE 14 1M-group bootstrap requirement).  The iota node
    keys stay GLOBAL group ids (jit sees the global shapes), so the
    per-(group, term) timeout PRNG draws exactly the single-device
    streams.  Optional config masks are small [P, G] host arrays; None
    keeps init_state's uniform bootstrap."""
    shardings = state_sharding(
        mesh, axis, damped=cfg.check_quorum or cfg.pre_vote,
        transfer=cfg.transfer,
    )
    mask_sh = NamedSharding(mesh, P(None, axis))

    init = jax.jit(
        functools.partial(sim.init_state, cfg),
        in_shardings=(mask_sh, mask_sh, mask_sh),
        out_shardings=shardings,
    )
    G, Pn = cfg.n_groups, cfg.n_peers
    if voter_mask is None:
        voter_mask = jnp.ones((Pn, G), bool)
    if outgoing_mask is None:
        outgoing_mask = jnp.zeros((Pn, G), bool)
    if learner_mask is None:
        learner_mask = jnp.zeros((Pn, G), bool)
    return init(voter_mask, outgoing_mask, learner_mask)


def health_sharding(mesh: Mesh, axis: str = "groups"):
    """NamedShardings for the HealthState pytree: the [H, G] planes shard
    on the group axis, the scalar churn-window cursor is replicated."""
    from .sim import HealthState

    return HealthState(
        planes=NamedSharding(mesh, P(None, axis)),
        window_pos=NamedSharding(mesh, P()),
    )


def shard_health(health, mesh: Mesh, axis: str = "groups"):
    """Place a HealthState on the mesh (device_put mirror of shard_state)."""
    return jax.tree.map(jax.device_put, health, health_sharding(mesh, axis))


def blackbox_sharding(mesh: Mesh, axis: str = "groups"):
    """NamedShardings for the BlackboxState pytree (ISSUE 15): every
    plane is group-minor — the [W, G] ring rows and the [N_SAFETY, G]
    first-trip plane shard on their last axis, the round counter is
    replicated.  The per-round fold (kernels.blackbox_fold) is purely
    elementwise along G plus a replicated-axis ring write, so the steady
    sharded graphs stay collective-free; only the drain-cadence
    kernels.blackbox_capture top_k gathers per-shard candidates — the
    same registered-gather shape as the sharded health drain."""
    from .sim import BlackboxState

    return BlackboxState(**{
        row.name: _row_sharding(mesh, axis, row)
        for row in planes.rows(owner="BlackboxState")
    })


def shard_blackbox(blackbox, mesh: Mesh, axis: str = "groups"):
    """Place a BlackboxState on the mesh (device_put mirror of
    shard_state)."""
    return jax.tree.map(
        jax.device_put, blackbox, blackbox_sharding(mesh, axis)
    )


def chaos_sharding(mesh: Mesh, axis: str = "groups"):
    """NamedShardings for a compiled chaos schedule (chaos.CompiledChaos):
    every packed per-phase plane is group-minor ([NPH, W, G] — the packed
    word axis covers the P*P link pairs, NOT groups, so the planes shard
    cleanly on their last axis), the per-phase append workload is
    [NPH, G], and the round-indexed phase_of_round is replicated
    (group-free).  Per-link loss draws are keyed by GLOBAL (round, src,
    dst, group) counters computed from the global iota under
    jit-with-shardings, so the sharded replay is bit-identical."""
    from .chaos import CompiledChaos

    rep = NamedSharding(mesh, P())
    xg = NamedSharding(mesh, P(None, axis))
    xxg = NamedSharding(mesh, P(None, None, axis))
    return CompiledChaos(
        phase_of_round=rep, link_packed=xxg, loss_packed=xxg,
        crashed_packed=xxg, append=xg, n_peers=None, lossless=None,
    )


def shard_chaos(compiled, mesh: Mesh, axis: str = "groups"):
    """Place a compiled chaos schedule on the mesh (the device_put mirror
    of shard_state for the fault-injection arrays)."""
    sched_sh = chaos_sharding(mesh, axis)
    return compiled._replace(
        **{
            name: jax.device_put(getattr(compiled, name), sh)
            for name, sh in sched_sh._asdict().items()
            if sh is not None  # the trailing statics ride as they are
        }
    )


def sharded_step(
    cfg: SimConfig, mesh: Mesh, axis: str = "groups", donate: bool = True
):
    """Compile the full sim step under group-axis sharding.

    Node keys must stay GLOBAL group ids (parity with the scalar oracle), so
    the step runs under jit-with-shardings rather than shard_map: XLA sees
    the global shapes, the iota node keys stay global, and every op
    partitions trivially along G.
    """
    shardings = state_sharding(
        mesh, axis, damped=cfg.check_quorum or cfg.pre_vote,
        transfer=cfg.transfer,
    )
    crashed_sh = NamedSharding(mesh, P(None, axis))
    append_sh = NamedSharding(mesh, P(axis))
    return jax.jit(
        functools.partial(sim.step, cfg),
        in_shardings=(shardings, crashed_sh, append_sh),
        out_shardings=shardings,
        donate_argnums=(0,) if donate else (),
    )


def global_status(cfg: SimConfig, mesh: Mesh, axis: str = "groups"):
    """MultiRaftStatus reduction (SURVEY.md §5.5): per-shard partial
    aggregates combined across chips with XLA collectives over ICI.

    Returns a callable: SimState -> dict
      n_leaders:   groups currently led (device scalar)
      min_commit:  minimum commit index across groups (device scalar)
      max_term:    maximum term across groups (device scalar)
      total_commit: sum of per-group leader commit indices — an EXACT
                   host python int (see below)

    total_commit overflow (ISSUE 14): with x64 off the old single int32
    psum wrapped at ~1M groups x commit > 2k.  The device side now psums
    FOUR int32 limb sums — each group's leader commit split into its 8-bit
    bytes, so limb i's global sum is bounded by n_groups * 255 < 2**31 for
    any fleet under ~8.4M groups (asserted at build) — and the host
    recombines them in unbounded python ints: total = sum(limb_i << 8*i).
    The recombination is the only host-side arithmetic; the reduction
    itself stays on ICI.  The underlying jitted fn is exposed as `.jitted`
    for the graftcheck trace audit (GC015 pins this graph's collective
    set to exactly its psum/pmin reductions)."""
    from jax import shard_map

    if cfg.n_groups * 255 >= 2**31:
        raise ValueError(
            f"global_status limb sums can wrap int32 at n_groups="
            f"{cfg.n_groups} (needs n_groups * 255 < 2**31, ~8.4M groups);"
            " widen the limb split to 4-bit nibbles for larger fleets"
        )

    state_specs = jax.tree.map(
        lambda s: s.spec,
        state_sharding(
            mesh, axis, damped=cfg.check_quorum or cfg.pre_vote,
            transfer=cfg.transfer,
        ),
    )

    def local(st: SimState):
        is_leader = st.state == ROLE_LEADER
        has_leader = jnp.any(is_leader, axis=0)
        lead_commit = jnp.max(jnp.where(is_leader, st.commit, 0), axis=0)
        group_commit = jnp.max(st.commit, axis=0)
        n_leaders = jax.lax.psum(
            jnp.sum(has_leader.astype(jnp.int32), dtype=jnp.int32),
            axis_name=axis,
        )
        min_commit = jax.lax.pmin(jnp.min(group_commit), axis_name=axis)
        max_term = jax.lax.pmax(jnp.max(st.term), axis_name=axis)
        # 8-bit limb decomposition of each nonneg int32 commit: limb 3 is
        # the sign-free top 7 bits, so every limb value is <= 255 and the
        # global limb sum is provably < 2**31 (the build-time assert).
        limbs = jnp.stack(
            [
                jnp.sum(
                    (lead_commit >> (8 * i)) & 0xFF, dtype=jnp.int32
                )
                for i in range(4)
            ]
        )
        total_commit_limbs = jax.lax.psum(limbs, axis_name=axis)
        return {
            "n_leaders": n_leaders,
            "min_commit": min_commit,
            "max_term": max_term,
            "total_commit_limbs": total_commit_limbs,
        }

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(state_specs,),
        out_specs={
            "n_leaders": P(),
            "min_commit": P(),
            "max_term": P(),
            "total_commit_limbs": P(),
        },
    )
    jitted = jax.jit(fn)

    def status(st: SimState) -> dict:
        out = dict(jitted(st))
        limb_vals = jax.device_get(out.pop("total_commit_limbs"))
        out["total_commit"] = sum(
            int(v) << (8 * i) for i, v in enumerate(limb_vals)
        )
        return out

    status.jitted = jitted  # type: ignore[attr-defined]
    return status


def sharded_read_index(cfg: SimConfig, mesh: Mesh, axis: str = "groups"):
    """Compile the ReadIndex barrier (sim.read_index) under group-axis
    sharding: each chip answers reads for its own group shard with zero
    cross-chip traffic — the consensus analog of a data-parallel inference
    step.  Returns a jitted fn (SimState, crashed[P, G]) -> int32[G]."""
    shardings = state_sharding(
        mesh, axis, damped=cfg.check_quorum or cfg.pre_vote,
        transfer=cfg.transfer,
    )
    crashed_sh = NamedSharding(mesh, P(None, axis))
    return jax.jit(
        functools.partial(sim.read_index, cfg),
        in_shardings=(shardings, crashed_sh),
        out_shardings=NamedSharding(mesh, P(axis)),
    )


def reconfig_sharding(mesh: Mesh, axis: str = "groups"):
    """NamedShardings for a reconfig run's arrays: the compiled schedule
    (reconfig.CompiledReconfig) and the op-protocol carry
    (reconfig.ReconfigState) both shard on the group axis like every
    other [.., G] plane — per-group op chains are independent, so the
    compiled scan partitions trivially with no collectives.  Returns
    (schedule_shardings, state_shardings) as matching NamedTuples
    (CompiledReconfig.n_peers and the round-indexed phase_of_round are
    replicated: they are group-free)."""
    from .reconfig import CompiledReconfig, ReconfigState

    rep = NamedSharding(mesh, P())
    g = NamedSharding(mesh, P(axis))
    xg = NamedSharding(mesh, P(None, axis))
    kpg = NamedSharding(mesh, P(None, None, axis))
    sched = CompiledReconfig(
        phase_of_round=rep, append=xg, op_start=xg, n_ops=g,
        tgt_voter=kpg, tgt_outgoing=kpg, tgt_learner=kpg,
        added=kpg, removed=kpg, n_peers=None,
    )
    rstate = ReconfigState(
        stage=g, op_ptr=g, prop_owner=g, prop_index=g, prop_term=g,
        prev_voter=xg, prev_outgoing=xg,
    )
    return sched, rstate


def shard_reconfig(compiled, rstate, mesh: Mesh, axis: str = "groups"):
    """Place a compiled reconfig schedule + carry on the mesh (the
    device_put mirror of shard_state for the reconfig arrays).  `rstate`
    may be None (schedule-only placement: ClusterSim(mesh=) derives the
    op-protocol carry from the already-sharded state each run)."""
    sched_sh, rstate_sh = reconfig_sharding(mesh, axis)
    placed_sched = compiled._replace(
        **{
            name: jax.device_put(
                getattr(compiled, name), getattr(sched_sh, name)
            )
            for name in compiled._fields
            if name != "n_peers"
        }
    )
    placed_rstate = (
        None
        if rstate is None
        else jax.tree.map(jax.device_put, rstate, rstate_sh)
    )
    return placed_sched, placed_rstate


def client_sharding(mesh: Mesh, axis: str = "groups"):
    """NamedShardings for a client-workload run's arrays (ISSUE 13): the
    compiled schedule (workload.CompiledClient) and the outstanding-read
    carry (workload.ReadCarry) shard on the group axis like every other
    [.., G] plane — per-group read protocols are independent, so the
    compiled scan partitions trivially.  The packed read-fire plane's
    word axis IS the group axis / 32 (kernels.pack_bits_g keeps words
    group-minor), so it shards on the same mesh axis; the round-indexed
    phase_of_round and the fixed-size stats/latency accumulators are
    replicated (group-free; XLA reduces the per-shard partials over
    ICI).  Returns (schedule_shardings, carry_shardings,
    accumulator_sharding)."""
    from .workload import CompiledClient, ReadCarry

    rep = NamedSharding(mesh, P())
    g = NamedSharding(mesh, P(axis))
    xg = NamedSharding(mesh, P(None, axis))
    sched = CompiledClient(
        phase_of_round=rep,
        read_fire_packed=xg,
        read_mode=xg,
        append=xg,
        n_peers=None,
    )
    rcar = ReadCarry(pending_mode=g, pending_since=g, last_leader=g)
    return sched, rcar, rep


def shard_client(compiled, rcar, mesh: Mesh, axis: str = "groups"):
    """Place a compiled client schedule + read carry on the mesh (the
    device_put mirror of shard_state for the workload arrays).  `rcar`
    may be None (schedule-only placement, like shard_reconfig's).

    The packed fire plane's word axis is the group axis / 32, so it
    shards only when the word count tiles the mesh (ceil(G/32) divisible
    by the axis size — always true at the production shapes where
    sharding matters); otherwise it is REPLICATED, which is merely an
    HBM cost on read-only schedule data, never a correctness one."""
    sched_sh, rcar_sh, rep = client_sharding(mesh, axis)
    n_dev = mesh.shape[axis]
    if compiled.read_fire_packed.shape[1] % n_dev != 0:
        sched_sh = sched_sh._replace(read_fire_packed=rep)
    placed_sched = compiled._replace(
        **{
            name: jax.device_put(
                getattr(compiled, name), getattr(sched_sh, name)
            )
            for name in compiled._fields
            if name != "n_peers"
        }
    )
    placed_rcar = (
        None
        if rcar is None
        else jax.tree.map(jax.device_put, rcar, rcar_sh)
    )
    return placed_sched, placed_rcar
