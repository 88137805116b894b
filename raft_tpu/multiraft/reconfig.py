"""Device-resident membership churn: declarative joint-consensus reconfig
plans compiled into on-device schedules for the batched sim (BASELINE
config 4), the way chaos.py compiles fault schedules.

A :class:`ReconfigPlan` is a list of phases; a phase may carry ONE
conf-change op (add/remove voter, add/promote learner, explicit
joint-entry/joint-exit) that is ENQUEUED for the selected groups at the
phase's first round.  :func:`compile_plan` lowers the plan host-side by
driving the scalar ``confchange.Changer`` — every transition is validated
and its target masks computed by the reference's own rules (one voter per
simple step, outgoing := old incoming on joint-entry, ``learners_next``
staging, materialized on leave) — into dense per-op schedule arrays;
``runner.make_runner`` then executes the whole multi-phase scenario
inside ONE jitted ``lax.scan`` with zero host round trips, composable
with a compiled :class:`chaos.ChaosPlan` of equal length in the SAME scan
(reconfig *during* partition/loss/crash — the Jepsen-style killer
scenario).

The in-scan op protocol per group (the scalar twin is
``simref.ReconfigOracle``, which replays the identical rules through real
Raft state machines and applies the identical surgery — exact per-round
state+health parity in tests/test_reconfig_parity.py):

  propose   an eligible op (its phase reached, all earlier ops applied)
            appends one conf entry at the group's acting leader — the
            step reports where it landed (sim.ReconfigProposal: owner,
            index, term); no alive leader -> retry next round;
  wait      the swap is GATED on the entry committing under BOTH
            majorities of the (possibly joint) config: commit itself
            requires the dual quorum (quorum/joint.rs min-of-halves), so
            the gate is `owner still leader at its propose term (and not
            crashed) AND owner.commit >= entry index`;
  retry     a deposed/crashed owner invalidates the pending entry (it may
            be overwritten, and a frozen owner can never advance) — the
            op re-proposes at the next acting leader, exactly like an
            operator re-submitting a conf change that fell into a
            leadership change;
  apply     ``kernels.apply_confchange`` swaps the
            voter/outgoing/learner mask planes at the round boundary for
            every peer of the group at once and runs the reference's
            apply-time reactions (leader-step-down when the leader leaves
            the config, fresh tracker rows for added members,
            quorum-shrink commit pickup) — raft.rs post_conf_change
            semantics on the batched planes.

Every scan round also folds ``kernels.check_safety`` WITH the
joint-window invariants (election safety under dual majorities, no
commit lacking either majority, no single-step double-membership change
— the masks-transition pair is checked one round later, with a tail
check after the scan covering the final apply) into a violation
accumulator, plus the chaos MTTR stats and a reconfig stats vector
(proposals/applies/retries/joint-group-rounds).

Plan JSON (see docs/OBSERVABILITY.md "Reconfig" and
tests/testdata/reconfig/)::

    {"name": "joint-churn", "peers": 5, "voters": [1, 2, 3],
     "learners": [4],
     "phases": [
        {"rounds": 30},                                     # settle
        {"rounds": 40, "op": {"enter_joint": [{"add": 5}, {"remove": 1}]},
         "groups": {"mod": 2, "eq": 0}, "append": 1},
        {"rounds": 20, "op": {"leave_joint": true}},
        {"rounds": 10, "op": {"promote_learner": 4}}]}

Op forms: ``{"add_voter": p}``, ``{"remove_voter": p}``,
``{"add_learner": p}``, ``{"promote_learner": p}`` (single-step simple
changes), ``{"enter_joint": [{"add": p} | {"remove": p} | {"learner": p},
...]}`` and ``{"leave_joint": true}`` (explicit joint window).  Ops queue
strictly in phase order per group; an op whose phase arrives while an
earlier op is still pending waits its turn.

Schedule arrays stay small (ops-per-group x [P, G] masks, not
per-round), and the stats accumulators count at most one event per
(group, round): ``compile_plan`` asserts rounds x groups < 2**31 so the
int32 accumulators provably cannot wrap (the GC008 discipline,
docs/STATIC_ANALYSIS.md).

The compiled runners are built by ``runner.make_runner`` from the
schedules.py registry; this module knows nothing of the runner, nor of
the client workload.  The per-round scan body that RUNS the op protocol
above is ``runner._runner_body``; what it reads of this module is the
compiled schedule, ``ReconfigState`` and the two row look-ups
(``_gather_peer`` / ``_gather_op``).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .. import profiling
from . import chaos as chaos_mod
from . import kernels
from . import sim as sim_mod
from ..confchange import Changer
from ..confchange.changer import MapChangeType
from ..eraftpb import ConfChangeSingle, ConfChangeType
from ..tracker import ProgressTracker

# Padding sentinel for op_start: far beyond any legal plan (compile_plan
# bounds rounds x groups < 2**31, so rounds < 2**30 whenever G >= 2).
NO_ROUND = 1 << 30

_SIMPLE_OPS = ("add_voter", "remove_voter", "add_learner", "promote_learner")


@dataclass
class ReconfigPhase:
    """One contiguous stretch of rounds, optionally enqueuing ONE op.

    rounds: phase length in protocol rounds (>= 1).
    op:     the op document ({"add_voter": p}, {"enter_joint": [...]},
            {"leave_joint": true}, ...) enqueued for the selected groups
            at the phase's FIRST round; None = settle/wait phase.
    groups: which groups the op applies to (chaos.py group selectors);
            non-selected groups skip this op entirely.
    append: per-round append workload proposed at each group's leader
            for the phase (all groups — the background write load the
            reconfig must ride along with).
    """

    rounds: int
    op: Optional[Dict[str, object]] = None
    groups: chaos_mod.GroupSel = "all"
    append: int = 0


@dataclass
class ReconfigPlan:
    """A named multi-phase membership-churn scenario (host-side,
    declarative).  `voters`/`learners` (1-based peer ids) are the
    bootstrap configuration of every group — they must match the sim
    state the runner is applied to (use :func:`initial_masks`)."""

    name: str
    n_peers: int
    phases: List[ReconfigPhase]
    voters: List[int] = field(default_factory=list)
    learners: List[int] = field(default_factory=list)

    @property
    def n_rounds(self) -> int:
        return sum(ph.rounds for ph in self.phases)


def plan_from_dict(doc: Dict[str, object]) -> ReconfigPlan:
    """Build a ReconfigPlan from its JSON document form (module doc)."""
    n_peers = int(doc["peers"])  # type: ignore[arg-type]
    phases: List[ReconfigPhase] = []
    for ph in doc["phases"]:  # type: ignore[index]
        if not isinstance(ph, dict):
            raise ValueError(f"phase is not an object: {ph!r}")
        phases.append(
            ReconfigPhase(
                rounds=int(ph["rounds"]),  # type: ignore[arg-type]
                op=ph.get("op"),  # type: ignore[arg-type]
                groups=ph.get("groups", "all"),  # type: ignore[arg-type]
                append=int(ph.get("append", 0)),  # type: ignore[arg-type]
            )
        )
    voters = [int(p) for p in doc.get("voters", [])]  # type: ignore[union-attr]
    return ReconfigPlan(
        name=str(doc.get("name", "unnamed")),
        n_peers=n_peers,
        phases=phases,
        voters=voters or list(range(1, n_peers + 1)),
        learners=[int(p) for p in doc.get("learners", [])],  # type: ignore[union-attr]
    )


def load_plan(path: str) -> ReconfigPlan:
    """Load a ReconfigPlan from a JSON file (examples/reconfig/)."""
    with open(path, "r", encoding="utf-8") as f:
        return plan_from_dict(json.load(f))


# --- host-side compilation: drive the scalar confchange path ---------------


class _OpSlot(NamedTuple):
    """One validated transition of one group chain: the Changer-computed
    target configuration (as plain sets), the progress-map delta, and the
    member delta the device kernel applies."""

    voters_inc: frozenset
    voters_out: frozenset
    learners: frozenset
    learners_next: frozenset
    changes: Tuple[Tuple[int, int], ...]  # (peer id, MapChangeType value)
    added: frozenset  # fresh members (fresh tracker rows + ra grace)
    removed: frozenset  # ex-members (tracker rows cleared)
    phase: int  # the enqueuing phase index (start-round lookup)


def _peer(pid: object, n_peers: int, what: str, phase: int) -> int:
    p = int(pid)  # type: ignore[call-overload]
    if not 1 <= p <= n_peers:
        raise ValueError(
            f"phase {phase}: {what} peer id {p} out of range [1, {n_peers}]"
        )
    return p


def _op_ccs(
    op: Dict[str, object], n_peers: int, phase: int
) -> Tuple[str, List[ConfChangeSingle]]:
    """Normalize one op document -> (kind, ConfChangeSingle list)."""
    kinds = [k for k in op if k in _SIMPLE_OPS + ("enter_joint", "leave_joint")]
    if len(kinds) != 1 or len(op) != 1:
        raise ValueError(
            f"phase {phase}: op must have exactly one kind, got {op!r}"
        )
    kind = kinds[0]
    V, L, R = (
        ConfChangeType.AddNode,
        ConfChangeType.AddLearnerNode,
        ConfChangeType.RemoveNode,
    )
    if kind == "leave_joint":
        # {"leave_joint": false} would otherwise still leave (the value
        # was never read) — an edited-to-disable plan must fail loudly;
        # delete the op to make a phase a settle phase.
        if not op[kind]:
            raise ValueError(
                f"phase {phase}: leave_joint must be true — remove the "
                "op to disable the phase"
            )
        return kind, []
    if kind == "enter_joint":
        ccs = []
        for ch in op[kind]:  # type: ignore[attr-defined]
            if not isinstance(ch, dict) or len(ch) != 1:
                raise ValueError(
                    f"phase {phase}: enter_joint change must be one of "
                    f'{{"add"|"remove"|"learner": peer}}, got {ch!r}'
                )
            (what, pid), = ch.items()
            p = _peer(pid, n_peers, f"enter_joint {what}", phase)
            t = {"add": V, "remove": R, "learner": L}.get(what)
            if t is None:
                raise ValueError(
                    f"phase {phase}: unknown enter_joint change {what!r}"
                )
            ccs.append(ConfChangeSingle(t, p))
        if not ccs:
            raise ValueError(f"phase {phase}: enter_joint with no changes")
        return kind, ccs
    p = _peer(op[kind], n_peers, kind, phase)
    t = {"add_voter": V, "promote_learner": V, "add_learner": L,
         "remove_voter": R}[kind]
    return kind, [ConfChangeSingle(t, p)]


def _bootstrap_tracker(plan: ReconfigPlan) -> ProgressTracker:
    t = ProgressTracker(1 << 20)
    for v in plan.voters:
        _peer(v, plan.n_peers, "initial voter", -1)
        cfg, changes = Changer(t).simple(
            [ConfChangeSingle(ConfChangeType.AddNode, int(v))]
        )
        t.apply_conf(cfg, changes, 1)
    for l in plan.learners:
        _peer(l, plan.n_peers, "initial learner", -1)
        cfg, changes = Changer(t).simple(
            [ConfChangeSingle(ConfChangeType.AddLearnerNode, int(l))]
        )
        t.apply_conf(cfg, changes, 1)
    return t


def _member(t: ProgressTracker) -> frozenset:
    c = t.conf
    return frozenset(
        c.voters.incoming.ids() | c.voters.outgoing.ids() | c.learners
    )


def _walk_chain(
    plan: ReconfigPlan, sig: Tuple[int, ...]
) -> List[_OpSlot]:
    """Apply the op sequence `sig` (phase indices) through the scalar
    Changer, recording each validated transition."""
    t = _bootstrap_tracker(plan)
    slots: List[_OpSlot] = []
    for phase_idx in sig:
        op = plan.phases[phase_idx].op
        assert op is not None
        kind, ccs = _op_ccs(op, plan.n_peers, phase_idx)
        # Plan-typo guards beyond the Changer's own invariants: a no-op
        # simple change (adding an existing voter, promoting a non-
        # learner, removing a non-voter) would propose+commit an entry
        # that changes nothing — almost certainly a plan mistake.
        inc = t.conf.voters.incoming.ids()
        if kind == "add_voter" and ccs[0].node_id in inc:
            raise ValueError(
                f"phase {phase_idx}: add_voter {ccs[0].node_id} is "
                "already a voter"
            )
        if kind == "promote_learner" and ccs[0].node_id not in t.conf.learners:
            raise ValueError(
                f"phase {phase_idx}: promote_learner {ccs[0].node_id} is "
                "not currently a learner"
            )
        if kind == "remove_voter" and ccs[0].node_id not in inc:
            raise ValueError(
                f"phase {phase_idx}: remove_voter {ccs[0].node_id} is "
                "not currently a voter"
            )
        if kind == "add_learner" and ccs[0].node_id in t.conf.learners:
            raise ValueError(
                f"phase {phase_idx}: add_learner {ccs[0].node_id} is "
                "already a learner"
            )
        old_member = _member(t)
        ch = Changer(t)
        if kind == "enter_joint":
            cfg, changes = ch.enter_joint(False, ccs)
        elif kind == "leave_joint":
            cfg, changes = ch.leave_joint()
        else:
            cfg, changes = ch.simple(ccs)
        t.apply_conf(cfg, changes, 1)
        new_member = _member(t)
        slots.append(
            _OpSlot(
                voters_inc=frozenset(cfg.voters.incoming.ids()),
                voters_out=frozenset(cfg.voters.outgoing.ids()),
                learners=frozenset(cfg.learners),
                learners_next=frozenset(cfg.learners_next),
                changes=tuple((int(i), int(ct)) for i, ct in changes),
                added=new_member - old_member,
                removed=old_member - new_member,
                phase=phase_idx,
            )
        )
    return slots


def _compile_schedule(plan: ReconfigPlan, n_groups: int):
    """The shared numpy schedule (device compile AND the oracle's host
    twin): phase timing, per-group op chains (Changer-validated), and the
    dense per-slot target masks."""
    P, G = plan.n_peers, n_groups
    nph = len(plan.phases)
    if nph == 0:
        raise ValueError("plan has no phases")
    if plan.n_rounds * max(1, G) >= 2**31:
        raise ValueError(
            f"plan spans {plan.n_rounds} rounds x {G} groups >= 2**31 "
            "(group, round) pairs; the int32 reconfig/safety accumulators "
            "could wrap — split the plan"
        )
    phase_of_round = np.zeros(plan.n_rounds, dtype=np.int32)
    phase_start = np.zeros(nph, dtype=np.int32)
    append = np.zeros((nph, G), dtype=np.int32)
    r0 = 0
    op_phases: List[int] = []
    gsel_by_phase: Dict[int, np.ndarray] = {}
    for i, ph in enumerate(plan.phases):
        if ph.rounds < 1:
            raise ValueError(f"phase {i}: rounds must be >= 1")
        phase_of_round[r0 : r0 + ph.rounds] = i
        phase_start[i] = r0
        r0 += ph.rounds
        append[i] = ph.append
        if ph.op is not None:
            op_phases.append(i)
            gsel_by_phase[i] = chaos_mod._group_mask(ph.groups, G)
    if not op_phases:
        raise ValueError("plan has no reconfig ops (use a ChaosPlan for "
                         "pure fault scenarios)")
    # Per-group op signature -> Changer chain (validated once per
    # distinct sequence, shared across the groups that follow it).  The
    # signatures are the distinct columns of the ops' [n_op_phases, G]
    # selection plane — numbered by refining the classes one op at a
    # time, so the ids stay below G however many ops there are — and
    # every plane below is filled per signature: a plan has a handful of
    # them whatever G is.
    sel = np.stack([gsel_by_phase[i] for i in op_phases])
    sig_idx = np.zeros(G, dtype=np.int64)
    for row in sel:
        _, sig_idx = np.unique(2 * sig_idx + row, return_inverse=True)
    firsts = np.unique(sig_idx, return_index=True)[1]
    sigs: List[Tuple[int, ...]] = [
        tuple(i for i, on in zip(op_phases, sel[:, g]) if on)
        for g in firsts
    ]
    sig_of_group = [sigs[i] for i in sig_idx.tolist()]
    chains: Dict[Tuple[int, ...], List[_OpSlot]] = {
        sig: _walk_chain(plan, sig) for sig in sigs
    }
    K = max(1, max(len(s) for s in sigs))
    op_start = np.full((K, G), NO_ROUND, dtype=np.int32)
    n_ops = np.zeros(G, dtype=np.int32)
    tgt_voter = np.zeros((K, P, G), dtype=bool)
    tgt_outgoing = np.zeros((K, P, G), dtype=bool)
    tgt_learner = np.zeros((K, P, G), dtype=bool)
    added = np.zeros((K, P, G), dtype=bool)
    removed = np.zeros((K, P, G), dtype=bool)
    pids = np.arange(1, P + 1)

    def column(members: frozenset) -> np.ndarray:
        """bool[P, 1]: the peers of one set, to broadcast over a mask."""
        return np.isin(pids, list(members))[:, None]

    for i, sig in enumerate(sigs):
        mask = sig_idx == i
        n_ops[mask] = len(sig)
        for k, slot in enumerate(chains[sig]):
            op_start[k, mask] = phase_start[slot.phase]
            tgt_voter[k][:, mask] = column(slot.voters_inc)
            tgt_outgoing[k][:, mask] = column(slot.voters_out)
            # learners_next stay outgoing voters until leave-joint
            # materializes them (tracker.rs:50-83) — the device
            # learner plane carries only the ACTIVE learners.
            tgt_learner[k][:, mask] = column(slot.learners)
            added[k][:, mask] = column(slot.added)
            removed[k][:, mask] = column(slot.removed)
    return (
        phase_of_round, append, op_start, n_ops,
        tgt_voter, tgt_outgoing, tgt_learner, added, removed,
        sig_of_group, chains,
    )


class CompiledReconfig(NamedTuple):
    """Device schedule arrays for one plan at one batch shape.

    phase_of_round: int32[R]       round -> phase index
    append:         int32[NPH, G]  per-phase append workload
    op_start:       int32[K, G]    round at which op k becomes eligible
                                   (NO_ROUND padding past n_ops)
    n_ops:          int32[G]       ops in the group's chain
    tgt_voter:      bool[K, P, G]  post-apply incoming-voter mask
    tgt_outgoing:   bool[K, P, G]  post-apply outgoing mask
    tgt_learner:    bool[K, P, G]  post-apply learner mask
    added:          bool[K, P, G]  fresh members (tracker-row reset + ra)
    removed:        bool[K, P, G]  ex-members (tracker rows cleared)
    n_peers:        static python int
    """

    phase_of_round: jnp.ndarray  # gc: int32[R]
    append: jnp.ndarray  # gc: int32[NPH, G]
    op_start: jnp.ndarray  # gc: int32[K, G]
    n_ops: jnp.ndarray  # gc: int32[G]
    tgt_voter: jnp.ndarray  # gc: bool[K, P, G]
    tgt_outgoing: jnp.ndarray  # gc: bool[K, P, G]
    tgt_learner: jnp.ndarray  # gc: bool[K, P, G]
    added: jnp.ndarray  # gc: bool[K, P, G]
    removed: jnp.ndarray  # gc: bool[K, P, G]
    n_peers: int

    @property
    def n_rounds(self) -> int:
        return int(self.phase_of_round.shape[0])


def compile_plan(plan: ReconfigPlan, n_groups: int) -> CompiledReconfig:
    """Lower a ReconfigPlan to device schedule arrays for `n_groups`
    groups; every transition is Changer-validated host-side."""
    (
        phase_of_round, append, op_start, n_ops,
        tgt_voter, tgt_outgoing, tgt_learner, added, removed,
        _, _,
    ) = _compile_schedule(plan, n_groups)
    return CompiledReconfig(
        phase_of_round=jnp.asarray(phase_of_round, dtype=jnp.int32),
        append=jnp.asarray(append, dtype=jnp.int32),
        op_start=jnp.asarray(op_start, dtype=jnp.int32),
        n_ops=jnp.asarray(n_ops, dtype=jnp.int32),
        tgt_voter=jnp.asarray(tgt_voter, dtype=bool),
        tgt_outgoing=jnp.asarray(tgt_outgoing, dtype=bool),
        tgt_learner=jnp.asarray(tgt_learner, dtype=bool),
        added=jnp.asarray(added, dtype=bool),
        removed=jnp.asarray(removed, dtype=bool),
        n_peers=plan.n_peers,
    )


def empty_reconfig_schedule(
    n_rounds: int, n_peers: int, n_groups: int
) -> CompiledReconfig:
    """A no-op CompiledReconfig spanning `n_rounds`: zero ops, zero extra
    append — composing it with a chaos schedule through _runner_body
    reproduces the plain chaos runner's protocol exactly (the op-protocol
    carry provably never moves).  The workload runners run it when no
    reconfig plan is given; the autopilot starts every horizon on it and
    swaps in a real evacuation schedule only when the policy fires."""
    P, G = n_peers, n_groups
    return CompiledReconfig(
        phase_of_round=jnp.zeros((n_rounds,), jnp.int32),
        append=jnp.zeros((1, G), jnp.int32),
        op_start=jnp.full((1, G), NO_ROUND, jnp.int32),
        n_ops=jnp.zeros((G,), jnp.int32),
        tgt_voter=jnp.zeros((1, P, G), bool),
        tgt_outgoing=jnp.zeros((1, P, G), bool),
        tgt_learner=jnp.zeros((1, P, G), bool),
        added=jnp.zeros((1, P, G), bool),
        removed=jnp.zeros((1, P, G), bool),
        n_peers=P,
    )


def initial_masks(
    plan: ReconfigPlan, n_groups: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(voter_mask, outgoing_mask, learner_mask) [P, G] matching the
    plan's bootstrap configuration — hand these to sim.init_state so the
    sim starts in the config the compiled chains transition FROM."""
    P, G = plan.n_peers, n_groups
    vm = np.zeros((P, G), dtype=bool)
    lm = np.zeros((P, G), dtype=bool)
    for v in plan.voters:
        vm[_peer(v, P, "initial voter", -1) - 1] = True
    for l in plan.learners:
        lm[_peer(l, P, "initial learner", -1) - 1] = True
    return (
        jnp.asarray(vm, dtype=bool),
        jnp.zeros((P, G), dtype=bool),
        jnp.asarray(lm, dtype=bool),
    )


class HostReconfigSchedule:
    """The compiled reconfig schedule kept in numpy + python — what
    simref.ReconfigOracle walks.  Carries the SAME timing/eligibility
    arrays the device gathers (phase_of_round, append, op_start, n_ops)
    plus, per (group, op-slot), the Changer-computed transition record
    (_OpSlot: target config sets, progress-map delta, member delta) the
    oracle's scalar surgery installs — both sides derive from ONE
    _compile_schedule walk, so they cannot drift."""

    def __init__(self, plan: ReconfigPlan, n_groups: int):
        (
            self.phase_of_round, self.append, self.op_start, self.n_ops,
            self.tgt_voter, self.tgt_outgoing, self.tgt_learner,
            self.added, self.removed,
            self._sig_of_group, self._chains,
        ) = _compile_schedule(plan, n_groups)
        self.n_rounds = plan.n_rounds
        self.n_peers = plan.n_peers
        self.n_groups = n_groups
        self.voters = list(plan.voters)
        self.learners = list(plan.learners)

    def slot(self, group: int, op_idx: int) -> _OpSlot:
        """The validated transition record for the group's op `op_idx`."""
        return self._chains[self._sig_of_group[group]][op_idx]


class ReconfigState(NamedTuple):
    """The runner's per-group op-protocol carry.

    stage:         0 = next op (if any) needs proposing, 1 = a conf entry
                   is in flight awaiting its dual-majority commit.
    op_ptr:        index of the next unapplied op in the group's chain.
    prop_owner:    proposing leader's peer id (1-based; 0 = none).
    prop_index:    the in-flight conf entry's log index.
    prop_term:     the proposing leader's term (the entry's term).
    prev_voter/prev_outgoing: the mask planes that governed the PREVIOUS
                   round's step — the double-change safety check compares
                   each round's step masks against these, so every apply
                   transition is audited exactly once (one round later;
                   the post-scan tail check covers a final-round apply).
    """

    stage: jnp.ndarray  # gc: int32[G]
    op_ptr: jnp.ndarray  # gc: int32[G]
    prop_owner: jnp.ndarray  # gc: int32[G]
    prop_index: jnp.ndarray  # gc: int32[G]
    prop_term: jnp.ndarray  # gc: int32[G]
    prev_voter: jnp.ndarray  # gc: bool[P, G]
    prev_outgoing: jnp.ndarray  # gc: bool[P, G]


def init_reconfig_state(st: sim_mod.SimState) -> ReconfigState:
    """Fresh op-protocol state for a run starting from `st`.  Every field
    is a DISTINCT buffer (the mask planes are copied): the runner donates
    both the sim state and this carry, and an aliased buffer would be
    donated twice."""
    G = st.term.shape[1]
    return ReconfigState(
        stage=jnp.zeros((G,), jnp.int32),
        op_ptr=jnp.zeros((G,), jnp.int32),
        prop_owner=jnp.zeros((G,), jnp.int32),
        prop_index=jnp.zeros((G,), jnp.int32),
        prop_term=jnp.zeros((G,), jnp.int32),
        prev_voter=jnp.array(st.voter_mask, dtype=bool),
        prev_outgoing=jnp.array(st.outgoing_mask, dtype=bool),
    )


def resume_state(
    rst: ReconfigState,
    n_ops: jnp.ndarray,  # gc: int32[G]
) -> ReconfigState:
    """The carry a call starts from, given the carry the last call of the
    SAME schedule ended with (or a fresh one: for it this is the
    identity).  A replayed schedule is a cycle: a group whose chain is
    complete (every op applied, nothing in flight) starts again at op 0;
    a group with an op in flight or ops left keeps its pointer and its
    pending entry, and finishes its chain in order — late, by `op_start`,
    never from op 0.  Everything else goes on as if the two calls were
    one scan: the transition-audit anchors are the last round's step
    masks, so the first round audits the last call's final apply once
    more (its tail audit already did)."""
    done = (rst.op_ptr >= n_ops) & (rst.stage == 0)
    return rst._replace(op_ptr=jnp.where(done, 0, rst.op_ptr))


@jax.jit
def unfinished_groups(
    op_ptr: jnp.ndarray,  # gc: int32[G]
    n_ops: jnp.ndarray,  # gc: int32[G]
) -> jnp.ndarray:
    """int32[]: groups with ops of their chain still to apply."""
    return jnp.sum(op_ptr < n_ops, dtype=jnp.int32)


# Reconfig stats accumulator indices ([N_RECONFIG_STATS] int32; each slot
# grows by at most G per round, and compile_plan bounds rounds x G < 2**31
# — the GC008 no-wrap argument).
RC_PROPOSED = 0  # conf entries appended (retries re-count)
RC_APPLIED = 1  # mask swaps committed
RC_RETRIES = 2  # pending entries invalidated by owner deposition/crash
RC_JOINT_ROUNDS = 3  # (group, round) pairs spent inside a joint config
N_RECONFIG_STATS = 4

RECONFIG_STAT_NAMES = (
    "proposals",
    "ops_applied",
    "retries",
    "joint_group_rounds",
)

# The same four under the names ClusterSim.run_reads reports them by
# (workload.read_report), beside `conf_unfinished`.
READ_REPORT_CONF_NAMES = (
    "conf_proposals",
    "conf_applied",
    "conf_retries",
    "joint_group_rounds",
)


@profiling.scope("op_gather")
def _gather_peer(plane: jnp.ndarray, owner: jnp.ndarray) -> jnp.ndarray:
    """plane[P, G], owner int32[G] (1-based, 0-safe) -> plane[owner-1, g]."""
    o = jnp.clip(owner - 1, 0, plane.shape[0] - 1)
    return kernels.select_row(plane, o)


@profiling.scope("op_gather")
def _gather_op(plane: jnp.ndarray, op_ptr: jnp.ndarray) -> jnp.ndarray:
    """plane[K, ..., G], op_ptr int32[G] -> plane[op_ptr[g], ..., g]."""
    k = jnp.clip(op_ptr, 0, plane.shape[0] - 1)
    return kernels.select_row(plane, k)


def pending_in_horizon(
    compiled: CompiledReconfig,
    rst: ReconfigState,
    round_idx: jnp.ndarray,  # gc: int32[]
    horizon: int,
) -> jnp.ndarray:
    """bool[G]: groups with a conf entry in flight OR an op scheduled to
    become eligible within the next `horizon` rounds — the mask
    pallas_step.steady_mask must reject (a fused horizon cannot propose,
    gate, or apply a conf change).

    Since ISSUE 11 this per-group runtime check is the GUARD of the
    split-horizon machinery, not its whole story: `split_plan` is the
    host-side split-point planner that places the scheduled op rounds in
    general segments up front (so the common case never pays a rejected
    fused block), and this mask catches the dynamic tail — an op whose
    retry chain outlives its planned window keeps its group's fused
    blocks honestly on the general path until the op applies."""
    start = _gather_op(compiled.op_start, rst.op_ptr)
    has_op = rst.op_ptr < compiled.n_ops
    return (rst.stage > 0) | (
        has_op & (start < round_idx + jnp.int32(horizon))
    )


# --- split-horizon planning (ISSUE 11) --------------------------------------


class HorizonSegment(NamedTuple):
    """One planned stretch of a runner horizon (host-side python ints).

    start:  absolute round index of the segment's first round.
    rounds: segment length (>= 1).
    fused:  True = the segment is a whole number of k-round fused-dispatch
            blocks (each still guarded at runtime by the steady predicate
            + pending_in_horizon, so the plan is a performance hint, never
            a correctness assumption); False = per-round general rounds
            (the op propose/gate/apply windows, phase-cut remainders, and
            fused spans shorter than one block).
    """

    start: int
    rounds: int
    fused: bool


def plan_split_points(
    n_rounds: int,
    windows: Sequence[Tuple[int, int]],
    cuts: Sequence[int] = (),
    k: int = 8,
) -> List[HorizonSegment]:
    """Lower op windows + schedule-phase cuts to an ordered segment list.

    windows: half-open (start, end) GENERAL intervals — where scheduled
             conf-change ops propose/gate/apply (overlaps are merged).
    cuts:    round indices a fused block may not span (phase starts: the
             append workload and fault masks change there, and a fused
             block needs them constant).
    k:       fused block length in rounds.

    Returns segments covering [0, n_rounds) exactly, in order.  Fused
    segments always have rounds % k == 0 — remainders degrade to general
    segments — and an empty `windows` with no interior cuts yields ONE
    full fused segment (plus a general remainder when n_rounds % k != 0).
    """
    R = int(n_rounds)
    if R < 1:
        raise ValueError("n_rounds must be >= 1")
    if k < 1:
        raise ValueError("k must be >= 1")
    ivs = sorted(
        (max(0, int(a)), min(R, int(b)))
        for a, b in windows
        if int(b) > 0 and int(a) < R and int(b) > int(a)
    )
    merged: List[Tuple[int, int]] = []
    for a, b in ivs:
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    cutset = sorted({int(c) for c in cuts if 0 < int(c) < R})
    segs: List[HorizonSegment] = []

    def emit_fused_span(a: int, b: int) -> None:
        points = [a] + [c for c in cutset if a < c < b] + [b]
        for lo, hi in zip(points, points[1:]):
            nb = (hi - lo) // k
            if nb:
                segs.append(HorizonSegment(lo, nb * k, True))
            rem = (hi - lo) - nb * k
            if rem:
                segs.append(HorizonSegment(lo + nb * k, rem, False))

    pos = 0
    for a, b in merged:
        if a > pos:
            emit_fused_span(pos, a)
        segs.append(HorizonSegment(a, b - a, False))
        pos = b
    if pos < R:
        emit_fused_span(pos, R)
    # Coalesce adjacent general segments (fewer jit shapes to compile).
    out: List[HorizonSegment] = []
    for s in segs:
        if (
            out
            and not s.fused
            and not out[-1].fused
            and out[-1].start + out[-1].rounds == s.start
        ):
            out[-1] = HorizonSegment(
                out[-1].start, out[-1].rounds + s.rounds, False
            )
        else:
            out.append(s)
    return out


def split_plan(
    compiled: CompiledReconfig,
    k: int = 8,
    chaos_compiled: Optional[chaos_mod.CompiledChaos] = None,
    window: int = 4,
) -> List[HorizonSegment]:
    """The split-point planner: where the compiled schedule's horizon
    splits into fused steady blocks vs general op rounds (ISSUE 11 — the
    host-side evolution of `pending_in_horizon`, which remains the
    per-block runtime guard).

    Each scheduled op start round opens a `window`-round general window
    (propose + dual-majority gate + apply complete in one round on a
    steady fleet; the window absorbs short retry tails).  A JOINT-entering
    op (its target config has outgoing voters) extends its window to the
    selected groups' NEXT op start + window — the joint interval is
    steady-rejected (not-joint condition) anyway, so planning it fused
    would only buy rejected blocks — or to the horizon end when a
    selected group's chain ends joint.  Fused spans additionally split at
    every reconfig/chaos phase start (`plan_split_points` cuts): the
    per-phase append workload and fault masks must be constant across a
    fused block.
    """
    R = compiled.n_rounds
    op_start = np.asarray(compiled.op_start)  # [K, G]
    n_ops = np.asarray(compiled.n_ops)  # [G]
    tgt_out = np.asarray(compiled.tgt_outgoing)  # [K, P, G]
    phase_of_round = np.asarray(compiled.phase_of_round)
    K = op_start.shape[0]
    windows: List[Tuple[int, int]] = []
    for ki in range(K):
        valid = (ki < n_ops) & (op_start[ki] < NO_ROUND)
        if not valid.any():
            continue
        for s in np.unique(op_start[ki][valid]):
            sel = valid & (op_start[ki] == s)
            end = int(s) + window
            if tgt_out[ki][:, sel].any():
                # Joint-entering op: general until the leave applies.
                if ki + 1 < K:
                    nxt = op_start[ki + 1][sel]
                    has_next = (n_ops[sel] > ki + 1) & (nxt < NO_ROUND)
                    if bool(has_next.all()):
                        end = int(nxt.max()) + window
                    else:
                        end = R
                else:
                    end = R
            windows.append((int(s), min(end, R)))
    cuts = set((np.flatnonzero(np.diff(phase_of_round)) + 1).tolist())
    if chaos_compiled is not None:
        cph = np.asarray(chaos_compiled.phase_of_round)
        cuts |= set((np.flatnonzero(np.diff(cph)) + 1).tolist())
    return plan_split_points(R, windows, sorted(cuts), k)
