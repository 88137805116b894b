"""Compiled client workloads: Zipf-skewed read/write mixes driven through
the batched sim as ONE jitted lax.scan.

A :class:`ClientPlan` is the client-side twin of a chaos.ChaosPlan: a list
of phases, each covering a round range and a group selector, declaring the
phase's WRITE load (uniform `append` or a seeded Zipf draw per group — the
TiKV-style hot-region skew) and its READ traffic (a read issued every
`read_every` rounds per selected group, in `read_mode` "safe" — the
ReadIndex quorum round — or "lease" — the LeaseBased local serve under the
check-quorum leader lease).  :func:`compile_plan` lowers it host-side into
dense schedule arrays (per-round read-fire masks bit-packed 32:1 along G —
GC008 PACKED_PLANES `bits_g`); ``runner.make_runner`` then executes the
whole scenario inside one ``lax.scan`` with zero host round trips,
composable with a ``chaos.CompiledChaos`` AND a
``reconfig.CompiledReconfig`` in the SAME scan (reads during partitions,
reads during joint config — ``runner._runner_body`` is the shared round
body).

Each round: outstanding reads retry through ``sim.step(read_propose=)``
(one read in flight per group; a fire landing on an outstanding read is
dropped and counted), a served read folds its latency-in-rounds into an
on-device histogram (`N_LAT_BUCKETS` buckets, overflow-capped), and
``kernels.check_safety``'s linearizability slots (SV_STALE_READ /
SV_DUAL_LEASE) audit the lease-holder mask every round.  The histogram
reduces ON DEVICE to p50/p90/p99 via :func:`latency_percentiles` — the
nearest-rank rule of :func:`nearest_rank` — so only a fixed-size report
ever crosses to the host.

Plan JSON (see docs/OBSERVABILITY.md "Reads" and examples/reads/)::

    {"name": "zipf-mixed", "peers": 5, "seed": 7, "phases": [
        {"rounds": 64, "append": 1},                       # settle, no reads
        {"rounds": 128, "write_zipf": 1.8, "write_max": 8,
         "read_every": 2, "read_mode": "lease"},
        {"rounds": 64, "read_every": 1, "read_mode": "safe",
         "groups": {"mod": 2, "eq": 0}}]}

The scalar twin is simref.ReadOracle (per-round receipt parity on the real
LeaseBased/Safe pumps); :class:`HostClientSchedule` is the numpy half the
oracle-driven tests walk — built by the SAME `_compile_arrays` walk as the
device schedule, so the two cannot drift.

The compiled runners are built by ``runner.make_runner`` from the
schedules.py registry; this module knows nothing of the runner.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from . import chaos as chaos_mod
from . import kernels
from . import sim as sim_mod
from .. import profiling
from .chaos import GroupSel, _group_mask


_MODE_CODES = {"safe": sim_mod.READ_SAFE, "lease": sim_mod.READ_LEASE}

# Read-stats accumulator indices ([N_READ_STATS] int32; each slot grows by
# at most G per round and compile_plan bounds rounds x G < 2**31 — the
# GC008 no-wrap argument, derived in docs/STATIC_ANALYSIS.md).
RS_ISSUED = 0  # fresh reads accepted (fires finding no outstanding read)
RS_SERVED_LEASE = 1  # reads served locally under the lease gate
RS_SERVED_QUORUM = 2  # reads served through the ReadIndex quorum round
RS_DEGRADED_SERVES = 3  # lease requests that served via the fallback
RS_RETRY_ROUNDS = 4  # (group, round) pairs an outstanding read waited
RS_DROPPED_FIRES = 5  # fires dropped because a read was already in flight
N_READ_STATS = 6

READ_STAT_NAMES = (
    "reads_issued",
    "served_lease",
    "served_quorum",
    "degraded_serves",
    "retry_group_rounds",
    "dropped_fires",
)

# Latency histogram: bucket i counts reads served i rounds after issue;
# the last bucket accumulates every latency >= LAT_CAP.  int32 counts,
# bounded by the same rounds x G < 2**31 compile-time assert.
LAT_CAP = 64
N_LAT_BUCKETS = LAT_CAP + 1


@dataclass
class ClientPhase:
    """One contiguous stretch of rounds with a fixed client traffic mix.

    rounds:     phase length in protocol rounds (>= 1).
    append:     uniform per-round write load at each selected group's
                leader (ignored when write_zipf > 0).
    write_zipf: Zipf skew parameter (> 1); when set, each selected group
                draws its per-round write load once for the phase from
                numpy's zipf(a), clipped to write_max — TiKV-style
                hot-region skew.
    write_max:  clip bound for the Zipf draw.
    read_every: issue a read every N rounds per selected group (0 = no
                reads this phase).
    read_mode:  "safe" (ReadIndex quorum round) or "lease" (LeaseBased
                local serve; degrades to safe where the gate fails).
    stagger:    offset each group's fire cadence by its group id so the
                fleet's reads spread across rounds (True, the default)
                instead of firing in lockstep.
    groups:     which groups the phase's traffic applies to.
    """

    rounds: int
    append: int = 0
    write_zipf: float = 0.0
    write_max: int = 8
    read_every: int = 0
    read_mode: str = "safe"
    stagger: bool = True
    groups: GroupSel = "all"


@dataclass
class ClientPlan:
    """A named multi-phase client workload (host-side, declarative)."""

    name: str
    n_peers: int
    phases: List[ClientPhase] = field(default_factory=list)
    seed: int = 0

    @property
    def n_rounds(self) -> int:
        return sum(ph.rounds for ph in self.phases)


def plan_from_dict(doc: Dict[str, object]) -> ClientPlan:
    """Build a ClientPlan from its JSON document form (see module doc)."""
    phases: List[ClientPhase] = []
    for i, ph in enumerate(doc["phases"]):  # type: ignore[index]
        if not isinstance(ph, dict):
            raise ValueError(f"phase {i} is not an object: {ph!r}")
        mode = str(ph.get("read_mode", "safe"))
        if mode not in _MODE_CODES:
            raise ValueError(
                f"phase {i}: read_mode {mode!r} is not one of "
                f"{sorted(_MODE_CODES)}"
            )
        phases.append(
            ClientPhase(
                rounds=int(ph["rounds"]),  # type: ignore[arg-type]
                append=int(ph.get("append", 0)),  # type: ignore[arg-type]
                write_zipf=float(ph.get("write_zipf", 0.0)),  # type: ignore[arg-type]
                write_max=int(ph.get("write_max", 8)),  # type: ignore[arg-type]
                read_every=int(ph.get("read_every", 0)),  # type: ignore[arg-type]
                read_mode=mode,
                stagger=bool(ph.get("stagger", True)),
                groups=ph.get("groups", "all"),  # type: ignore[arg-type]
            )
        )
    return ClientPlan(
        name=str(doc.get("name", "unnamed")),
        n_peers=int(doc["peers"]),  # type: ignore[arg-type]
        phases=phases,
        seed=int(doc.get("seed", 0)),  # type: ignore[arg-type]
    )


def load_plan(path: str) -> ClientPlan:
    """Load a ClientPlan from a JSON file (examples/reads/)."""
    with open(path, "r", encoding="utf-8") as f:
        return plan_from_dict(json.load(f))


class CompiledClient(NamedTuple):
    """Device schedule arrays for one client plan at one batch shape.

    phase_of_round:   int32[R]           round -> phase index
    read_fire_packed: uint32[R, Wg]      per-round read-issue mask,
                                         bit-packed 32:1 along the GROUP
                                         axis (kernels.pack_bits_g —
                                         GC008 PACKED_PLANES `bits_g`;
                                         Wg = ceil(G/32))
    read_mode:        int32[NPH, G]      sim.READ_* code per phase (0
                                         where the phase reads nothing)
    append:           int32[NPH, G]      per-phase per-group write load
                                         (the seeded Zipf draw baked in)
    n_peers:          static python int
    """

    phase_of_round: jnp.ndarray  # gc: int32[R]
    read_fire_packed: jnp.ndarray  # gc: uint32[R, WG]
    read_mode: jnp.ndarray  # gc: int32[NPH, G]
    append: jnp.ndarray  # gc: int32[NPH, G]
    n_peers: int

    @property
    def n_rounds(self) -> int:
        return int(self.phase_of_round.shape[0])


def _compile_arrays(plan: ClientPlan, n_groups: int):
    """The numpy schedule (shared by the device path and the oracle-side
    HostClientSchedule — one walk, so the twins cannot drift).  The Zipf
    write draws come from ONE RandomState(plan.seed) consumed in phase
    order: replaying the same plan always produces the same skew."""
    G = n_groups
    nph = len(plan.phases)
    if nph == 0:
        raise ValueError("plan has no phases")
    R = plan.n_rounds
    phase_of_round = np.zeros(R, dtype=np.int32)
    read_fire = np.zeros((R, G), dtype=bool)
    read_mode = np.zeros((nph, G), dtype=np.int32)
    append = np.zeros((nph, G), dtype=np.int32)
    rng = np.random.RandomState(plan.seed)
    gid = np.arange(G)
    r0 = 0
    for i, ph in enumerate(plan.phases):
        if ph.rounds < 1:
            raise ValueError(f"phase {i}: rounds must be >= 1")
        phase_of_round[r0 : r0 + ph.rounds] = i
        gsel = _group_mask(ph.groups, G)
        if ph.write_zipf > 0.0:
            if ph.write_zipf <= 1.0:
                raise ValueError(
                    f"phase {i}: write_zipf must be > 1 (numpy zipf)"
                )
            draws = np.minimum(
                rng.zipf(ph.write_zipf, size=G), ph.write_max
            ).astype(np.int32)
        else:
            draws = np.full(G, ph.append, dtype=np.int32)
        append[i] = np.where(gsel, draws, 0)
        if ph.read_every > 0:
            read_mode[i] = np.where(gsel, _MODE_CODES[ph.read_mode], 0)
            off = gid % ph.read_every if ph.stagger else np.zeros(G, int)
            for o in range(ph.rounds):
                read_fire[r0 + o] = gsel & (
                    (o + off) % ph.read_every == 0
                )
        r0 += ph.rounds
    # The read stats / latency histogram sum per-group indicators over the
    # run in int32; bound the schedule so they provably cannot wrap (the
    # GC008 discipline, derived in docs/STATIC_ANALYSIS.md "Read planes").
    if R * max(1, G) >= 2**31:
        raise ValueError(
            f"plan spans {R} rounds x {G} groups >= 2**31 (group, round) "
            "pairs; the int32 read-stats/latency accumulators could wrap "
            "— split the plan"
        )
    return phase_of_round, read_fire, read_mode, append


def compile_plan(plan: ClientPlan, n_groups: int) -> CompiledClient:
    """Lower a ClientPlan to device schedule arrays for `n_groups` groups
    (fire masks packed along G — see CompiledClient)."""
    phase_of_round, read_fire, read_mode, append = _compile_arrays(
        plan, n_groups
    )
    return CompiledClient(
        phase_of_round=jnp.asarray(phase_of_round, dtype=jnp.int32),
        read_fire_packed=kernels.pack_bits_g(
            jnp.asarray(read_fire, dtype=bool)
        ),
        read_mode=jnp.asarray(read_mode, dtype=jnp.int32),
        append=jnp.asarray(append, dtype=jnp.int32),
        n_peers=plan.n_peers,
    )


class HostClientSchedule:
    """The compiled client schedule kept in numpy — what the oracle-driven
    parity tests walk.  Round r's traffic is exactly what the runner's
    scan body gathers: the round's fire row, the phase's mode row, and the
    phase's append row."""

    def __init__(self, plan: ClientPlan, n_groups: int):
        (
            self.phase_of_round,
            self.read_fire,
            self.read_mode,
            self.append,
        ) = _compile_arrays(plan, n_groups)
        self.n_rounds = plan.n_rounds
        self.n_peers = plan.n_peers
        self.n_groups = n_groups

    def masks(self, round_idx: int):
        """(fire[G] bool, mode[G] int32, append[G] int32) for one round."""
        ph = int(self.phase_of_round[round_idx])
        return (
            self.read_fire[round_idx],
            self.read_mode[ph],
            self.append[ph],
        )


class ReadCarry(NamedTuple):
    """The runner's per-group read carry: `pending_mode` is the sim.READ_*
    code of the read in flight (0 = none — one read per group at a time;
    new fires drop), `pending_since` the absolute round it was issued
    (latency = serve round - pending_since), `last_leader` the 1-based id
    of the last acting leader the group had (0 = none seen yet; what the
    report's `leader_changes` compares a round's end with —
    chaos.update_leader_stats; a fused block proves a standing leader and leaves
    the plane as it is).  Persisted by checkpoint.save_read_state; values
    bounded by the mode codes, the plan's round count and n_peers (GC008
    READ_PLANES registry)."""

    pending_mode: jnp.ndarray  # gc: int32[G]
    pending_since: jnp.ndarray  # gc: int32[G]
    last_leader: jnp.ndarray  # gc: int32[G]


class LearnerLagCarry(NamedTuple):
    """What ClusterSim.run_reads hands the workload scan in the read
    carry's place for a fleet that BOOTS with learners: the ReadCarry and
    the call's count of (group, round) pairs that ended with a learner
    behind its leader's commit (chaos.fold_learner_lag, the report's
    `learner_behind_group_rounds`).  runner._runner_body folds the count
    where it finds this type and nowhere else, so a fleet that boots
    without learners runs the round it always ran; the runners pass the
    carry through as they pass a ReadCarry."""

    reads: ReadCarry
    behind: jnp.ndarray  # gc: int32[]


def init_read_carry(n_groups: int, last_leader=None) -> ReadCarry:
    """Fresh no-reads-outstanding carry over `last_leader` (default: no
    leader seen yet) — ClusterSim.run_reads hands the plane from call to
    call, so a change across a call boundary counts."""
    if last_leader is None:
        last_leader = jnp.zeros((n_groups,), jnp.int32)
    return ReadCarry(
        pending_mode=jnp.zeros((n_groups,), jnp.int32),
        pending_since=jnp.zeros((n_groups,), jnp.int32),
        last_leader=last_leader,
    )


@profiling.scope("read_fold")
def fold_latencies(
    lat_hist: jnp.ndarray,  # gc: int32[L]
    served: jnp.ndarray,  # gc: bool[G]
    lat: jnp.ndarray,  # gc: int32[G]
) -> jnp.ndarray:
    """One round's served reads into the latency histogram: bucket i grows
    by the groups with `served` and `lat == i` (`lat` already clipped to
    [0, L - 1] by the caller; `served` masks whatever the others hold).
    Compare-and-sum over [buckets, G], the idiom of
    chaos.update_chaos_stats' recover_hist — no scatter: a scatter-add of
    G elements was the largest op of every general round on the chip
    (PERF.md §6, PR 36)."""
    bucket = jnp.arange(lat_hist.shape[0], dtype=jnp.int32)[:, None]
    # dtype= on the sum: a bare bool reduction widens under x64 (GC007).
    return lat_hist + jnp.sum(
        served[None, :] & (lat[None, :] == bucket), axis=1, dtype=jnp.int32
    )


@profiling.scope("read_latency")
def latency_percentiles(
    hist: jnp.ndarray,  # gc: int32[L]
    qs: Tuple[int, ...] = (50, 90, 99),
) -> jnp.ndarray:
    """Nearest-rank percentiles of a histogram of rounds (read latency;
    the lengths of leaderless episodes — chaos.recover_hist), ON DEVICE:
    the smallest bucket with at least ceil(q/100 * N) of the N samples
    at or below it — exactly :func:`nearest_rank`'s rule lifted from a
    sorted sample list to the histogram.  Returns int32[len(qs)], -1
    everywhere when the histogram is empty.

    The rank math decomposes n = 100a + b so a*q + ceil(b*q/100) never
    leaves int32 (n < 2**31 by compile_plan's bound, q <= 100; a naive
    n*q would wrap for n > ~21M served reads)."""
    n = jnp.sum(hist)
    cum = jnp.cumsum(hist)
    out = []
    for q in qs:
        a, b = n // 100, n % 100
        rank = a * jnp.int32(q) + (b * jnp.int32(q) + 99) // 100
        idx = jnp.sum(cum < rank, dtype=jnp.int32)
        out.append(jnp.where(n == 0, jnp.int32(-1), idx))
    return jnp.stack(out)


@jax.jit
def report_percentiles(
    lat_hist: jnp.ndarray,  # gc: int32[L]
    stats: jnp.ndarray,  # gc: int32[S]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(read p50/p90/p99, recover p50/p90/p99) of a run's accumulators as
    ONE program: the report's second histogram must not add some twenty
    eager dispatches to every call."""
    return (
        latency_percentiles(lat_hist),
        latency_percentiles(chaos_mod.recover_hist(stats)),
    )


def nearest_rank(xs, q: float):
    """Nearest-rank percentile (the smallest sample with at least q of
    the distribution at or below it): xs sorted, 0 < q <= 1."""
    return xs[math.ceil(q * len(xs)) - 1]


def host_latency_percentile(samples, q: int) -> int:
    """Host twin of latency_percentiles for the tests: THE nearest-rank
    rule (:func:`nearest_rank`) over the raw sample list, so the device
    reduction is pinned against the single source of the formula."""
    xs = sorted(samples)
    if not xs:
        return -1
    return nearest_rank(xs, q / 100)


def _validate(cfg, client, chaos_compiled, reconfig_compiled):
    if client.n_peers != cfg.n_peers:
        raise ValueError(
            f"client plan is for {client.n_peers} peers but the sim has "
            f"{cfg.n_peers}"
        )
    R = client.n_rounds
    if chaos_compiled is not None and chaos_compiled.n_rounds != R:
        raise ValueError(
            f"chaos schedule spans {chaos_compiled.n_rounds} rounds but "
            f"the client plan spans {R} — compose equal-length plans"
        )
    if reconfig_compiled is not None and reconfig_compiled.n_rounds != R:
        raise ValueError(
            f"reconfig schedule spans {reconfig_compiled.n_rounds} rounds "
            f"but the client plan spans {R} — compose equal-length plans"
        )


def reads_pending_in_horizon(
    client: CompiledClient,
    rcar: ReadCarry,
    r0: jnp.ndarray,  # gc: int32[]
    horizon: int,
) -> jnp.ndarray:
    """bool[G]: the group has quorum-round read work somewhere inside
    [r0, r0 + horizon) — an OUTSTANDING read (any mode: it must retry
    every round) or a scheduled SAFE-mode fire.  This is the fused
    horizon's read rejection mask (pallas_step.steady_mask's
    `read_pending=`): the fused kernel can serve neither arm of the
    quorum round, while pure LEASE fires are NOT pending — on a steady
    horizon the lease gate provably holds and the serve touches no
    message planes, so those fold closed-form (the workload split
    runner).  The per-round DEFINITION: the split runner
    computes the carry half itself and takes the schedule half from
    :func:`block_tables`, which tests/test_block_tables.py holds equal
    to this."""
    G = rcar.pending_mode.shape[0]
    pending = rcar.pending_mode > 0
    safe_fire = jnp.zeros((G,), bool)
    R = client.n_rounds
    for o in range(horizon):
        r = jnp.clip(r0 + o, 0, R - 1)
        fire = kernels.unpack_bits_g(client.read_fire_packed[r], G)
        mode = client.read_mode[client.phase_of_round[r]]
        safe_fire = safe_fire | (
            fire & (mode == sim_mod.READ_SAFE) & ((r0 + o) < R)
        )
    return pending | safe_fire


def lease_fires_in_block(
    client: CompiledClient,
    r0: jnp.ndarray,  # gc: int32[]
    horizon: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(n_lease int32[G], any bool[G]): scheduled LEASE-mode fires per
    group inside [r0, r0 + horizon) — the closed-form serve count a fused
    block folds into the latency histogram's zero bucket (a lease serve
    on a steady horizon completes the round it fires).  The per-round
    DEFINITION of BlockRows.n_lease / .lease_fire (see
    reads_pending_in_horizon)."""
    G = client.read_mode.shape[1]
    n = jnp.zeros((G,), jnp.int32)
    R = client.n_rounds
    for o in range(horizon):
        r = jnp.clip(r0 + o, 0, R - 1)
        fire = kernels.unpack_bits_g(client.read_fire_packed[r], G)
        mode = client.read_mode[client.phase_of_round[r]]
        n = n + (
            fire & (mode == sim_mod.READ_LEASE) & ((r0 + o) < R)
        ).astype(jnp.int32)
    return n, n > 0


class BlockRows(NamedTuple):
    """What ONE fused k-round block needs of the schedules: every field is
    a function of the schedules and the block's number alone, so
    :func:`block_tables` computes them once per schedule and the split
    runner hands block b its rows as operands — a block's guard reads no
    [R, ...] or [NPH, ...] plane of the client schedule (on the v5e those
    planes are laid out phase-minor, so ONE row read costs the whole plane:
    PERF.md, PR 31).

    r0:         int32[]       the block's first round
    same_phase: bool[]        its first and last round share a client phase
    n_lease:    int32[]       LEASE-mode fires inside it, all groups
                              (sum of lease_fires_in_block's count)
    safe_fire:  uint32[Wg]    groups with a SAFE-mode fire inside it
                              (reads_pending_in_horizon's schedule half),
                              bit-packed along G like read_fire_packed
    lease_fire: uint32[Wg]    groups with a LEASE-mode fire inside it
                              (lease_fires_in_block's `any`), packed alike

    Only where the runner has a chaos plan (None, and no leaf of the
    pytree, where it has none):

    same_chaos_phase: bool[]  its first and last round share a chaos phase
                              (the fused kernel takes the planes of the
                              block's first round for all k)
    faulted:          bool[]  that phase has a fault (chaos.phase_faulted)
    """

    r0: jnp.ndarray  # gc: int32[]
    same_phase: jnp.ndarray  # gc: bool[]
    n_lease: jnp.ndarray  # gc: int32[]
    safe_fire: jnp.ndarray  # gc: uint32[WG]
    lease_fire: jnp.ndarray  # gc: uint32[WG]
    same_chaos_phase: Optional[jnp.ndarray] = None  # gc: bool[]
    faulted: Optional[jnp.ndarray] = None  # gc: bool[]


def block_tables(
    client: CompiledClient,
    k: int,
    chaos_compiled: Optional[chaos_mod.CompiledChaos] = None,
) -> BlockRows:
    """The :class:`BlockRows` of every whole k-round block of `client`
    (and of `chaos_compiled`, a plan of the same length, where the runner
    has one), stacked along a leading [n_rounds // k] axis.  One pass over
    the schedule in PACKED space — a round's fire words AND its phase's
    packed mode mask — so nothing of [n_blocks, k, G] is ever built."""
    n_blocks = client.n_rounds // k
    n = n_blocks * k
    phase = client.phase_of_round[:n]
    fire = client.read_fire_packed[:n]

    def fires(mode: int) -> jnp.ndarray:
        of_phase = kernels.pack_bits_g(client.read_mode == mode)
        return (fire & of_phase[phase]).reshape(n_blocks, k, fire.shape[1])

    lease = fires(sim_mod.READ_LEASE)
    by_block = phase.reshape(n_blocks, k)
    rows = BlockRows(
        r0=jnp.arange(n_blocks, dtype=jnp.int32) * k,
        same_phase=by_block[:, 0] == by_block[:, k - 1],
        n_lease=jnp.sum(
            jax.lax.population_count(lease), axis=(1, 2), dtype=jnp.int32
        ),
        safe_fire=jnp.bitwise_or.reduce(fires(sim_mod.READ_SAFE), axis=1),
        lease_fire=jnp.bitwise_or.reduce(lease, axis=1),
    )
    if chaos_compiled is None:
        return rows
    chaos_by_block = chaos_compiled.phase_of_round[:n].reshape(n_blocks, k)
    return rows._replace(
        same_chaos_phase=chaos_by_block[:, 0] == chaos_by_block[:, k - 1],
        faulted=chaos_mod.phase_faulted(chaos_compiled)[chaos_by_block[:, 0]],
    )


def read_report(
    rdstats, lat_p, safety, stats, rounds: int, recover_p=(-1, -1, -1),
    rstats=(0, 0, 0, 0), conf_unfinished: int = 0, learner_behind=None,
) -> dict:
    """The per-scenario read-workload summary off the device accumulators
    (host-side formatter; ClusterSim.run_reads emits it).  `lat_p` is
    latency_percentiles' (p50, p90, p99) vector of the read histogram,
    `recover_p` the same of chaos.recover_hist(stats) — the lengths in
    rounds of the leaderless episodes that ended (-1: none did).  `rstats`
    is the op protocol's [reconfig.N_RECONFIG_STATS] vector (conf entries
    proposed / ops applied / entries given up with their owner /
    group-rounds in a joint configuration) and `conf_unfinished` the groups
    with ops of their chain left at the end; all zero with no reconfig plan.
    `learner_behind` is the LearnerLagCarry's count, and
    `learner_behind_group_rounds` is in the report only where the run
    carried one (a fleet that boots with learners, through the scan)."""
    from .chaos import (
        CS_APPENDS_DROPPED,
        CS_APPENDS_OFFERED,
        CS_HEALED_ROUNDS,
        CS_LEADER_CHANGES,
        CS_LEADERLESS_ROUNDS,
        CS_MAX_STREAK,
        CS_REELECTIONS,
        CS_TERM_BUMPS,
        recover_hist,
    )
    from .kernels import SAFETY_NAMES
    from .reconfig import READ_REPORT_CONF_NAMES

    reelections = int(stats[CS_REELECTIONS])
    healed = int(stats[CS_HEALED_ROUNDS])
    return {
        "rounds": int(rounds),
        **{name: int(v) for name, v in zip(READ_STAT_NAMES, rdstats)},
        "read_p50": int(lat_p[0]),
        "read_p90": int(lat_p[1]),
        "read_p99": int(lat_p[2]),
        "mttr_rounds": (
            round(healed / reelections, 3) if reelections else None
        ),
        "reelections": reelections,
        "healed_rounds": healed,
        "max_leaderless_streak": int(stats[CS_MAX_STREAK]),
        "leaderless_group_rounds": int(stats[CS_LEADERLESS_ROUNDS]),
        "appends_offered": int(stats[CS_APPENDS_OFFERED]),
        "appends_dropped": int(stats[CS_APPENDS_DROPPED]),
        "leader_changes": int(stats[CS_LEADER_CHANGES]),
        "term_bumps": int(stats[CS_TERM_BUMPS]),
        **(
            {} if learner_behind is None
            else {"learner_behind_group_rounds": int(learner_behind)}
        ),
        "recover_hist": [int(v) for v in recover_hist(stats)],
        "recover_p50_rounds": int(recover_p[0]),
        "recover_p90_rounds": int(recover_p[1]),
        "recover_p99_rounds": int(recover_p[2]),
        **{name: int(v) for name, v in zip(READ_REPORT_CONF_NAMES, rstats)},
        "conf_unfinished": int(conf_unfinished),
        "safety": {
            name: int(v) for name, v in zip(SAFETY_NAMES, safety)
        },
    }


# The guard terms the workload split runner counts under a chaos plan, in
# the order of `guard_refusals`' columns (runner._guard_refusals).
GUARD_TERMS = (
    "no_campaign", "one_leader", "terms_ok", "cq_boundary", "read_pending",
)


def split_chaos_report(
    n_blocks: int, blocks_faulted: int, healthy_refused, refusals
) -> dict:
    """What a split run under a chaos plan adds to its report: the blocks
    by kind — `faulted` where the block's chaos phase has a crash, a cut or
    a loss rate, `healthy` where it has none — the healthy ones that did
    not fuse (the fleet was not steady yet: the re-fuse delay after a
    fault), and `refusals` int[len(GUARD_TERMS)], the groups each guard
    term refused summed over those."""
    return {
        "split_blocks": int(n_blocks),
        "split_blocks_faulted": int(blocks_faulted),
        "split_blocks_healthy": int(n_blocks - blocks_faulted),
        "split_blocks_healthy_refused": int(healthy_refused),
        "guard_refusals": {
            name: int(v) for name, v in zip(GUARD_TERMS, refusals)
        },
    }


def report_counts(report: dict) -> Dict[str, int]:
    """Every integer of a report, flat — what the `raft.run_reads.report`
    span is closed with (the safety slots as `safety.<name>`, the guard's
    refusals as `guard_refusals.<term>`)."""
    out = {
        k: v for k, v in report.items()
        if isinstance(v, int) and not isinstance(v, bool)
    }
    for group in ("safety", "guard_refusals"):
        for name, v in report.get(group, {}).items():
            out[f"{group}.{name}"] = v
    return out
