"""Device-resident chaos engine: declarative fault plans compiled into
on-device schedules for the batched sim.

The fault surface is the pairwise link plane `link[P, P, G]` threaded
through ``sim.step`` (see ``sim._linked_step``): a whole-peer crash is the
special case ``link[p, :, g] = link[:, p, g] = False``, an asymmetric
partition is a directed subset, and per-link message loss is a seeded
per-round draw (``kernels.link_loss_draw``, keyed ``(round, src, dst,
group)`` so every run replays bit-exactly).

A :class:`ChaosPlan` is a list of phases — partitions, directed link
overrides, loss rates, crashes, heals — each covering a round range and an
optional group selector.  :func:`compile_plan` lowers it host-side into
dense per-phase schedule arrays; ``runner.make_runner`` then executes the
whole multi-phase scenario inside ONE jitted ``lax.scan`` with zero host
round trips: per-round masks are gathered from the schedule by phase index,
the loss plane is drawn on device, the link-gated step advances every group,
``kernels.check_safety`` folds the safety invariants (election safety,
committed-prefix agreement, commit monotonicity) into a violation
accumulator, and the health planes feed a time-to-reelect / MTTR accumulator
(``health.chaos_report`` formats the host-side summary).

Plan JSON (see docs/OBSERVABILITY.md "Chaos" and tests/testdata/chaos/)::

    {"name": "split-brain", "peers": 5, "phases": [
        {"rounds": 30},                                   # settle
        {"rounds": 40, "partition": [[1, 2], [3, 4, 5]],  # symmetric split
         "append": 1},
        {"rounds": 20, "links": [{"from": 1, "to": 2, "up": false}],
         "loss": [{"from": 3, "to": 4, "rate": 0.5}],
         "crash": [5], "groups": {"mod": 2, "eq": 0}},
        {"rounds": 30, "heal": true}]}

The scalar twin is ``simref.ChaosOracle``: it replays the SAME compiled
schedule through real Raft state machines and the harness Network's
per-edge drops — :func:`host_masks` / :func:`host_loss_draw` are the numpy
mirrors of the device schedule and must stay bit-identical
(tests/test_chaos_parity.py).

The compiled runner is built by ``runner.make_runner`` from the
schedules.py registry rows; this module knows nothing of the runner.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from .. import profiling
from . import kernels


# Group selectors: "all", an explicit id list, or {"mod": m, "eq": r}.
GroupSel = Union[str, Sequence[int], Dict[str, int]]


@dataclass
class ChaosPhase:
    """One contiguous stretch of rounds with a fixed fault topology.

    rounds:    phase length in protocol rounds (>= 1).
    partition: list of peer-id cells; links BETWEEN cells are down, links
               within a cell stay up.  Peers in no cell form one implicit
               extra cell.  None = no partition.
    links:     directed overrides [{"from": a, "to": b, "up": bool}],
               applied after the partition.
    loss:      directed loss rates [{"from": a, "to": b, "rate": 0..1}];
               "rate" is sampled per (round, link, group).
    loss_all:  uniform loss rate applied to every directed link first.
    crash:     peer ids crashed (fully isolated) for the phase.
    groups:    which groups the phase's faults apply to; non-selected
               groups run fault-free for the phase.
    append:    per-round append workload proposed at each group's leader.
    """

    rounds: int
    partition: Optional[List[List[int]]] = None
    links: List[Dict[str, object]] = field(default_factory=list)
    loss: List[Dict[str, object]] = field(default_factory=list)
    loss_all: float = 0.0
    crash: List[int] = field(default_factory=list)
    groups: GroupSel = "all"
    append: int = 0


@dataclass
class ChaosPlan:
    """A named multi-phase fault scenario (host-side, declarative)."""

    name: str
    n_peers: int
    phases: List[ChaosPhase]

    @property
    def n_rounds(self) -> int:
        return sum(ph.rounds for ph in self.phases)


def plan_from_dict(doc: Dict[str, object]) -> ChaosPlan:
    """Build a ChaosPlan from its JSON document form (see module doc)."""
    phases: List[ChaosPhase] = []
    for ph in doc["phases"]:  # type: ignore[index]
        if not isinstance(ph, dict):
            raise ValueError(f"phase is not an object: {ph!r}")
        if ph.get("heal"):
            ph = {"rounds": ph["rounds"], "append": ph.get("append", 0)}
        phases.append(
            ChaosPhase(
                rounds=int(ph["rounds"]),  # type: ignore[arg-type]
                partition=ph.get("partition"),  # type: ignore[arg-type]
                links=list(ph.get("links", [])),  # type: ignore[arg-type]
                loss=list(ph.get("loss", [])),  # type: ignore[arg-type]
                loss_all=float(ph.get("loss_all", 0.0)),  # type: ignore[arg-type]
                crash=[int(p) for p in ph.get("crash", [])],  # type: ignore[union-attr]
                groups=ph.get("groups", "all"),  # type: ignore[arg-type]
                append=int(ph.get("append", 0)),  # type: ignore[arg-type]
            )
        )
    return ChaosPlan(
        name=str(doc.get("name", "unnamed")),
        n_peers=int(doc["peers"]),  # type: ignore[arg-type]
        phases=phases,
    )


def load_plan(path: str) -> ChaosPlan:
    """Load a ChaosPlan from a JSON file (examples/chaos/)."""
    with open(path, "r", encoding="utf-8") as f:
        return plan_from_dict(json.load(f))


def _group_mask(sel: GroupSel, n_groups: int) -> np.ndarray:
    if isinstance(sel, str):
        if sel != "all":
            raise ValueError(f"unknown group selector {sel!r}")
        return np.ones(n_groups, dtype=bool)
    if isinstance(sel, dict):
        m, r = int(sel["mod"]), int(sel["eq"])
        return (np.arange(n_groups) % m) == r
    mask = np.zeros(n_groups, dtype=bool)
    for g in sel:
        if not 0 <= int(g) < n_groups:
            raise ValueError(
                f"group id {g} out of range [0, {n_groups})"
            )
        mask[int(g)] = True
    return mask


def _peer_index(pid: object, n_peers: int, what: str, phase: int) -> int:
    """Validate a 1-based peer id from a plan document -> 0-based index
    (a 0 or negative id would otherwise silently wrap into the wrong
    peer's link row)."""
    p = int(pid)  # type: ignore[call-overload]
    if not 1 <= p <= n_peers:
        raise ValueError(
            f"phase {phase}: {what} peer id {p} out of range [1, {n_peers}]"
        )
    return p - 1


def _rate_to_fp(rate: float) -> int:
    if not 0.0 <= rate <= 1.0:
        raise ValueError(f"loss rate {rate} outside [0, 1]")
    return int(round(rate * kernels.LOSS_SCALE))


class CompiledChaos(NamedTuple):
    """Device schedule arrays for one plan at one batch shape.

    The bool/sub-int32 planes are stored PACKED (kernels.pack_bits /
    pack_u16_pairs — GC008 PACKED_PLANES): the per-round schedule gather
    in the jitted scan reads the packed words from HBM and unpacks them
    with a handful of VPU shift/mask ops, so the hot loop's schedule
    traffic shrinks ~6x at P = 5 (byte-per-bool [P, P, G] planes become
    ceil(P*P/32) uint32 words per group).  schedule_masks returns the
    planes UNPACKED — the step sees bit-identical masks either way
    (pinned by tests/test_chaos_parity.py's run_plan-vs-stepping case,
    ClusterSim.run_plan).

    phase_of_round: int32[R]                round -> phase index
    link_packed:    uint32[NPH, Wl, G]      per-phase base link plane,
                                            bit (s*P + d) of the word
                                            stack (Wl = ceil(P*P/32))
    loss_packed:    uint32[NPH, Wr, G]      per-phase loss rates
                                            (1/LOSS_SCALE <= 2**16, two
                                            halfwords per word, Wr =
                                            ceil(P*P/2))
    crashed_packed: uint32[NPH, 1, G]       per-phase crash masks, bit p
    append:         int32[NPH, G]           per-phase append workload
    n_peers:        static python int, the unpack shape
    lossless:       static python bool, true where no phase of the plan
                    has a loss rate (compile_plan reads it off the numpy
                    loss array before anything is lowered).  It selects
                    schedule_masks' program at trace time: a lossless
                    plan neither unpacks loss_packed nor draws the loss
                    sample (the draw would compare a non-negative sample
                    with 0 and knock out nothing), a plan with any rate
                    keeps the draw.  The masks are bit-identical either
                    way; like n_peers it rides the runner's closure
                    template and is never a jit argument.
    """

    phase_of_round: jnp.ndarray  # gc: int32[R]
    link_packed: jnp.ndarray  # gc: uint32[NPH, WL, G]
    loss_packed: jnp.ndarray  # gc: uint32[NPH, WR, G]
    crashed_packed: jnp.ndarray  # gc: uint32[NPH, 1, G]
    append: jnp.ndarray  # gc: int32[NPH, G]
    n_peers: int
    lossless: bool

    @property
    def n_rounds(self) -> int:
        return int(self.phase_of_round.shape[0])


def _compile_arrays(
    plan: ChaosPlan, n_groups: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """The numpy schedule (shared by the device path and the oracle)."""
    P, G = plan.n_peers, n_groups
    nph = len(plan.phases)
    if nph == 0:
        raise ValueError("plan has no phases")
    phase_of_round = np.zeros(plan.n_rounds, dtype=np.int32)
    link = np.ones((nph, P, P, G), dtype=bool)
    loss = np.zeros((nph, P, P, G), dtype=np.int32)
    crashed = np.zeros((nph, P, G), dtype=bool)
    append = np.zeros((nph, G), dtype=np.int32)
    r0 = 0
    for i, ph in enumerate(plan.phases):
        if ph.rounds < 1:
            raise ValueError(f"phase {i}: rounds must be >= 1")
        phase_of_round[r0 : r0 + ph.rounds] = i
        r0 += ph.rounds
        gsel = _group_mask(ph.groups, G)
        lk = np.ones((P, P), dtype=bool)
        if ph.partition is not None:
            cell = np.full(P, -1, dtype=np.int64)
            for c, ids in enumerate(ph.partition):
                for pid in ids:
                    cell[_peer_index(pid, P, "partition", i)] = c
            cell[cell < 0] = len(ph.partition)  # implicit last cell
            lk = cell[:, None] == cell[None, :]
        for ov in ph.links:
            a = _peer_index(ov["from"], P, "link", i)
            b = _peer_index(ov["to"], P, "link", i)
            lk[a, b] = bool(ov.get("up", False))
        ls = np.full((P, P), _rate_to_fp(ph.loss_all), dtype=np.int32)
        for ov in ph.loss:
            a = _peer_index(ov["from"], P, "loss", i)
            b = _peer_index(ov["to"], P, "loss", i)
            ls[a, b] = _rate_to_fp(float(ov["rate"]))  # type: ignore[arg-type]
        link[i] = np.where(gsel[None, None, :], lk[:, :, None], True)
        loss[i] = np.where(gsel[None, None, :], ls[:, :, None], 0)
        for pid in ph.crash:
            crashed[i, _peer_index(pid, P, "crash", i)] = gsel
        append[i] = np.where(gsel, ph.append, 0)
    # The chaos-stats accumulator sums per-group indicators over the run in
    # int32 (update_chaos_stats); bound the schedule so it provably cannot
    # wrap (the GC008 discipline, derived in docs/STATIC_ANALYSIS.md).
    if plan.n_rounds * max(1, G) >= 2**31:
        raise ValueError(
            f"plan spans {plan.n_rounds} rounds x {G} groups >= 2**31 "
            "(group, round) pairs; the int32 chaos-stats accumulator "
            "could wrap — split the plan"
        )
    return phase_of_round, link, loss, crashed, append


def compile_plan(plan: ChaosPlan, n_groups: int) -> CompiledChaos:
    """Lower a ChaosPlan to device schedule arrays for `n_groups` groups
    (bool/loss planes packed — see CompiledChaos)."""
    phase_of_round, link, loss, crashed, append = _compile_arrays(
        plan, n_groups
    )
    P, G = plan.n_peers, n_groups
    nph = link.shape[0]
    return CompiledChaos(
        phase_of_round=jnp.asarray(phase_of_round, dtype=jnp.int32),
        link_packed=kernels.pack_bits(
            jnp.asarray(link, dtype=bool).reshape(nph, P * P, G).swapaxes(
                0, 1
            )
        ).swapaxes(0, 1),
        loss_packed=kernels.pack_u16_pairs(
            jnp.asarray(loss, dtype=jnp.int32).reshape(nph, P * P, G).swapaxes(
                0, 1
            )
        ).swapaxes(0, 1),
        crashed_packed=kernels.pack_bits(
            jnp.asarray(crashed, dtype=bool).swapaxes(0, 1)
        ).swapaxes(0, 1),
        append=jnp.asarray(append, dtype=jnp.int32),
        n_peers=P,
        lossless=not loss.any(),
    )


def _base_planes(
    compiled: CompiledChaos,
    ph: jnp.ndarray,  # gc: int32[]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Phase `ph`'s (base_link bool[P, P, G], crashed bool[P, G]),
    gathered and unpacked."""
    P = compiled.n_peers
    G = compiled.append.shape[1]
    link = kernels.unpack_bits(compiled.link_packed[ph], P * P).reshape(
        P, P, G
    )
    return link, kernels.unpack_bits(compiled.crashed_packed[ph], P)


def _loss_plane(
    compiled: CompiledChaos,
    ph: jnp.ndarray,  # gc: int32[]
) -> jnp.ndarray:
    """Phase `ph`'s loss rates int32[P, P, G], gathered and unpacked."""
    P = compiled.n_peers
    G = compiled.append.shape[1]
    return kernels.unpack_u16_pairs(compiled.loss_packed[ph], P * P).reshape(
        P, P, G
    )


def schedule_planes(
    compiled: CompiledChaos,
    round_idx: jnp.ndarray,  # gc: int32[]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Device-side (base_link, loss_rate, crashed, append) for one round:
    the round's phase row gathered and unpacked WITHOUT the loss sample
    knocked out.  schedule_masks is the per-round consumer; the split
    fused dispatch (the reconfig split runner) needs the base plane for
    its steady predicate and the raw rates for the in-kernel draw — both
    constant across a phase, so one gather covers a whole fused block."""
    ph = compiled.phase_of_round[round_idx]
    link, crashed = _base_planes(compiled, ph)
    return link, _loss_plane(compiled, ph), crashed, compiled.append[ph]


def phase_faulted(compiled: CompiledChaos) -> jnp.ndarray:
    """bool[NPH]: the phase has a fault in some group — a crashed peer, a
    link that is down or a loss rate — read off the packed planes.  What
    the workload split runner tables per block (workload.BlockRows.faulted)
    and counts (`blocks_faulted`): a fused block inside such a phase runs
    beside the fault, one outside it between two."""
    P = compiled.n_peers
    healed = kernels.pack_bits(jnp.ones((P * P, 1), bool))  # [Wl, 1]
    return (
        jnp.any(compiled.link_packed != healed[None], axis=(1, 2))
        | jnp.any(compiled.crashed_packed != 0, axis=(1, 2))
        | jnp.any(compiled.loss_packed != 0, axis=(1, 2))
    )


@profiling.scope("runner.chaos_masks")
def schedule_masks(
    compiled: CompiledChaos,
    round_idx: jnp.ndarray,  # gc: int32[]
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Device-side (link, crashed, append) for one round of the schedule:
    gather the round's (packed) phase row, unpack it on device, and —
    only where the plan has a loss rate (CompiledChaos.lossless) — knock
    out the seeded loss sample.

    link and crashed leave through one optimization_barrier: the round
    reads FINISHED planes.  Unbarriered, the unpack (one cheap elementwise
    expression) is copied into every consumer's fusion of the round."""
    ph = compiled.phase_of_round[round_idx]
    link, crashed = _base_planes(compiled, ph)
    if not compiled.lossless:
        drop = kernels.link_loss_draw(round_idx, _loss_plane(compiled, ph))
        link = link & ~drop
    link, crashed = jax.lax.optimization_barrier((link, crashed))
    return link, crashed, compiled.append[ph]


# --- host twins (the ChaosOracle side; must stay bit-identical) -----------


def host_loss_draw(round_idx: int, loss_rate: np.ndarray) -> np.ndarray:
    """Numpy twin of kernels.link_loss_draw (same counter PRNG, same key
    layout); tests/test_chaos_parity.py pins bit-equality."""
    P = loss_rate.shape[0]
    G = loss_rate.shape[2]
    g = np.arange(G, dtype=np.uint32)[None, None, :]
    s = np.arange(P, dtype=np.uint32)[:, None, None]
    d = np.arange(P, dtype=np.uint32)[None, :, None]
    lane = s * np.uint32(P) + d + np.uint32(1)

    def mix(x: np.ndarray) -> np.ndarray:
        x = x.astype(np.uint32)
        x ^= x >> np.uint32(16)
        x = (x * np.uint32(0x85EBCA6B)).astype(np.uint32)
        x ^= x >> np.uint32(13)
        x = (x * np.uint32(0xC2B2AE35)).astype(np.uint32)
        x ^= x >> np.uint32(16)
        return x

    x = mix(
        (g * np.uint32(0x9E3779B1) + np.uint32(round_idx)).astype(np.uint32)
    )
    x = mix(x ^ (lane * np.uint32(0x85EBCA6B)).astype(np.uint32))
    return (x % np.uint32(kernels.LOSS_SCALE)).astype(np.int32) < loss_rate


class HostSchedule:
    """The compiled schedule kept in numpy — what simref.ChaosOracle walks.

    Round r's effective masks are exactly what schedule_masks hands the
    device step: base link plane of the round's phase, minus the seeded
    loss sample, plus the phase crash mask and append workload.
    """

    def __init__(self, plan: ChaosPlan, n_groups: int):
        (
            self.phase_of_round,
            self.link,
            self.loss,
            self.crashed,
            self.append,
        ) = _compile_arrays(plan, n_groups)
        self.n_rounds = plan.n_rounds
        self.n_peers = plan.n_peers
        self.n_groups = n_groups

    def masks(
        self, round_idx: int
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(link[P, P, G], crashed[P, G], append[G]) for one round."""
        ph = int(self.phase_of_round[round_idx])
        drop = host_loss_draw(round_idx, self.loss[ph])
        return self.link[ph] & ~drop, self.crashed[ph], self.append[ph]


# --- the compiled-run harness ---------------------------------------------

# Chaos-stats accumulator indices ([N_CHAOS_STATS] int32; time-to-reelect /
# MTTR off the PR 3 health planes — health.chaos_report formats them).
# Every slot grows by at most G per round (CS_MAX_STREAK is a max), and
# every compiled plan bounds rounds x G < 2**31: no slot can wrap.
CS_REELECTIONS = 0  # leaderless episodes that ended (leader regained)
CS_HEALED_ROUNDS = 1  # summed length of ended episodes (MTTR numerator)
CS_MAX_STREAK = 2  # longest leaderless streak observed anywhere
CS_LEADERLESS_ROUNDS = 3  # total leaderless (group, round) pairs
CS_APPENDS_OFFERED = 4  # (group, round) pairs the schedule offered entries
CS_APPENDS_DROPPED = 5  # ... of which no acting leader took them
# What `reelections` cannot see — a hand-over with no leaderless round
# between, and whether the leader regained is the one that was lost — and
# what a campaign costs the rest of the group.  Folded (update_leader_stats)
# by the runners that carry each group's last acting leader
# (workload.ReadCarry.last_leader: the client-workload runners); 0 in
# every other runner's vector.
CS_LEADER_CHANGES = 6  # (group, round) pairs that ended under a NEW leader
CS_TERM_BUMPS = 7  # summed growth of each group's highest term
# The ended episodes by length: slot CS_RECOVER_HIST + i counts episodes
# that lasted i rounds, the last slot every length >= RECOVER_CAP (capped
# like workload.lat_hist) — so recovery has a p99, not only a mean.
CS_RECOVER_HIST = 8
RECOVER_CAP = 64
N_RECOVER_BUCKETS = RECOVER_CAP + 1
N_CHAOS_STATS = CS_RECOVER_HIST + N_RECOVER_BUCKETS

CHAOS_STAT_NAMES = (
    "reelections",
    "healed_rounds",
    "max_leaderless_streak",
    "leaderless_group_rounds",
    "appends_offered",
    "appends_dropped",
    "leader_changes",
    "term_bumps",
)


def recover_hist(stats):
    """The [N_RECOVER_BUCKETS] histogram of ended leaderless episodes by
    length in rounds, out of a chaos-stats vector (device or host)."""
    return stats[CS_RECOVER_HIST:CS_RECOVER_HIST + N_RECOVER_BUCKETS]


def update_chaos_stats(
    stats: jnp.ndarray,  # gc: int32[S]
    prev_leaderless: jnp.ndarray,  # gc: int32[G]
    new_leaderless: jnp.ndarray,  # gc: int32[G]
    offered: Optional[jnp.ndarray] = None,  # gc: bool[G]
    dropped: Optional[jnp.ndarray] = None,  # gc: bool[G]
    rounds: int = 1,
) -> jnp.ndarray:
    """Fold one round's leaderless-plane transition into the stats, and
    the round's append offers: `offered` marks the groups the schedule
    offered entries, `dropped` those of them whose batch no acting leader
    took (sim.ReconfigProposal.dropped).  A fused block folds its `rounds`
    rounds at once: the plane is 0 at every round's end (a leader held),
    every offer was taken (dropped=None), and `offered` counts `rounds`
    times.  The two leadership slots are update_leader_stats' to fold."""
    healed = (prev_leaderless > 0) & (new_leaderless == 0)
    # dtype= on the sums: bare reductions widen to int64 under x64 (GC007).
    n_healed = jnp.sum(healed, dtype=jnp.int32)

    def count(mask):
        if mask is None:
            return jnp.int32(0)
        return jnp.sum(mask, dtype=jnp.int32)

    # The ended episodes by length: compare-and-sum over [buckets, G] —
    # no scatter, and no cond around it (PERF.md §6, PR 26, has what a
    # conditional in the scan body did to the round on the chip).
    length = jnp.minimum(prev_leaderless, RECOVER_CAP)
    bucket = jnp.arange(N_RECOVER_BUCKETS, dtype=jnp.int32)[:, None]
    hist = jnp.sum(
        healed[None, :] & (length[None, :] == bucket),
        axis=1, dtype=jnp.int32,
    )
    delta = jnp.concatenate([
        jnp.stack(
            [
                n_healed,
                jnp.sum(
                    jnp.where(healed, prev_leaderless, 0), dtype=jnp.int32
                ),
                jnp.int32(0),
                jnp.sum(new_leaderless > 0, dtype=jnp.int32),
                count(offered) * jnp.int32(rounds),
                count(dropped),
                jnp.int32(0),  # CS_LEADER_CHANGES: update_leader_stats'
                jnp.int32(0),  # CS_TERM_BUMPS: likewise
            ]
        ),
        hist,
    ])
    out = stats + delta
    return out.at[CS_MAX_STREAK].set(
        jnp.maximum(stats[CS_MAX_STREAK], jnp.max(new_leaderless))
    )


def update_leader_stats(
    stats: jnp.ndarray,  # gc: int32[S]
    last_leader: jnp.ndarray,  # gc: int32[G]
    prev_health,  # gc: HealthState
    bumps: jnp.ndarray,  # gc: int32[G]
    state: jnp.ndarray,  # gc: int32[P, G]
    term: jnp.ndarray,  # gc: int32[P, G]
    crashed: jnp.ndarray,  # gc: bool[P, G]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold one round's end into the two leadership slots: a group counts
    a leader change when its acting leader at the round's end
    (kernels.acting_leader_id: the alive leader of the highest term, off
    the planes sim.step returned — before the op protocol's apply) is a
    peer other than `last_leader`, the last acting leader it had (0 = none
    seen yet, which counts nothing) — leaderless rounds between the two or
    not.  The term bumps come off the planes the round already reduced:
    kernels.update_health adds each group's growth of its highest term to
    HP_TERM_BUMPS after zeroing the plane where the churn window is fresh,
    so the round's growth is `bumps` (the plane after the round) less the
    plane `prev_health` (the round-entry HealthState) carried into it.
    Returns (stats', last_leader').  A fused block never calls this: its
    predicate proves one standing leader and no campaign, so both slots
    and the plane stand."""
    lead = kernels.acting_leader_id(state, term, crashed)
    changed = (lead > 0) & (last_leader > 0) & (lead != last_leader)
    carried = jnp.where(
        prev_health.window_pos == 0, 0,
        prev_health.planes[kernels.HP_TERM_BUMPS],
    )
    # dtype= on the sums: bare reductions widen to int64 under x64 (GC007).
    delta = jnp.stack([
        jnp.sum(changed, dtype=jnp.int32),
        jnp.sum(bumps - carried, dtype=jnp.int32),
    ])
    # The two slots are neighbours: a static pad, no scatter.
    stats = stats + jnp.pad(
        delta, (CS_LEADER_CHANGES, N_CHAOS_STATS - CS_TERM_BUMPS - 1)
    )
    return stats, jnp.where(lead > 0, lead, last_leader)


@profiling.scope("runner.learner_lag")
def fold_learner_lag(
    behind: jnp.ndarray,  # gc: int32[]
    state: jnp.ndarray,  # gc: int32[P, G]
    term: jnp.ndarray,  # gc: int32[P, G]
    commit: jnp.ndarray,  # gc: int32[P, G]
    learner_mask: jnp.ndarray,  # gc: bool[P, G]
    crashed: jnp.ndarray,  # gc: bool[P, G]
) -> jnp.ndarray:
    """`behind` plus the groups in which, at this round's end, some member
    that is a learner holds a commit index below its acting leader's — the
    alive leader of the highest term, as kernels.acting_leader_id and
    ScalarCluster.acting_leader have it (election safety, audited every
    round, leaves no tie).  A group with no alive leader counts nothing; a
    crashed learner counts like any other.  A healthy round ends with every
    member at its leader's commit (the commit-advance re-broadcast is in the
    round), so this is the lag a learner's absence leaves WHILE ITS GROUP
    COMMITS, and the catch-up after it: a group that is offered no entry
    while its learner is away counts nothing.

    The planes are read behind an optimization barrier, as finished arrays
    in kernels of the count's own: fused into the round's producers the same
    arithmetic regrouped the tally's and the apply's kernels and cost 2.9%
    of the rate on the chip (PERF.md section 6, PR 47).  Two kernels on the
    TPU: ONE reduce over the peer axis that carries three values (a
    reduction a kernel cost 0.67%), and the sum.  Folded by
    runner._runner_body only where the carry asks for it
    (workload.LearnerLagCarry: a fleet that BOOTS with learners); every
    other fleet's round is the one it was."""
    state, term, commit, learner_mask, crashed = jax.lax.optimization_barrier(
        (state, term, commit, learner_mask, crashed)
    )
    is_lead = (state == kernels.ROLE_LEADER) & ~crashed

    def fold(a, b):
        # (term, commit) of the leader of the higher term; the lowest commit
        # a learner holds.
        (ta, ca, la), (tb, cb, lb) = a, b
        first = (ta > tb) | ((ta == tb) & (ca >= cb))
        return (
            jnp.where(first, ta, tb), jnp.where(first, ca, cb),
            jnp.minimum(la, lb),
        )

    # One pass over the peer axis.  No alive leader: commit -1, below which
    # no index is; no learner: INT32_MAX, which is below none.
    top = jnp.iinfo(jnp.int32).max
    _, lead_commit, learner_commit = jax.lax.reduce(
        (
            jnp.where(is_lead, term, -1), jnp.where(is_lead, commit, -1),
            jnp.where(learner_mask, commit, top),
        ),
        (jnp.int32(-1), jnp.int32(-1), jnp.int32(top)), fold, (0,),
    )
    # dtype= on the sum: a bare bool sum widens to int64 under x64 (GC007).
    return behind + jnp.sum(learner_commit < lead_commit, dtype=jnp.int32)
