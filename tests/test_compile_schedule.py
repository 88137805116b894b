"""reconfig._compile_schedule fills its [K, P, G] planes per distinct op
signature; pinned here against the per-group loops it replaced (kept below
as the reference) on the benchmark's membership-change mixes and a plan
with learners, at a G that is and one that is not a multiple of the
selectors' moduli."""

import json
import os
from typing import Dict, List, Tuple

import numpy as np
import pytest

from benchmark import traffic
from raft_tpu.multiraft import chaos as chaos_mod
from raft_tpu.multiraft import reconfig
from raft_tpu.multiraft.reconfig import NO_ROUND, _walk_chain

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARRAYS = (
    "phase_of_round", "append", "op_start", "n_ops", "tgt_voter",
    "tgt_outgoing", "tgt_learner", "added", "removed",
)


def compile_schedule_by_group(plan, n_groups):
    """The loops over all G groups (x K x P assignments) that
    reconfig._compile_schedule ran until PR 29, its input checks left
    out: the nine arrays, then the signature of every group and the
    chains."""
    P, G = plan.n_peers, n_groups
    nph = len(plan.phases)
    phase_of_round = np.zeros(plan.n_rounds, dtype=np.int32)
    phase_start = np.zeros(nph, dtype=np.int32)
    append = np.zeros((nph, G), dtype=np.int32)
    r0 = 0
    op_phases: List[int] = []
    gsel_by_phase: Dict[int, np.ndarray] = {}
    for i, ph in enumerate(plan.phases):
        phase_of_round[r0 : r0 + ph.rounds] = i
        phase_start[i] = r0
        r0 += ph.rounds
        append[i] = ph.append
        if ph.op is not None:
            op_phases.append(i)
            gsel_by_phase[i] = chaos_mod._group_mask(ph.groups, G)
    sig_of_group: List[Tuple[int, ...]] = []
    for g in range(G):
        sig_of_group.append(
            tuple(i for i in op_phases if gsel_by_phase[i][g])
        )
    chains = {}
    for sig in set(sig_of_group):
        chains[sig] = _walk_chain(plan, sig)
    K = max(1, max(len(s) for s in sig_of_group))
    op_start = np.full((K, G), NO_ROUND, dtype=np.int32)
    n_ops = np.zeros(G, dtype=np.int32)
    tgt_voter = np.zeros((K, P, G), dtype=bool)
    tgt_outgoing = np.zeros((K, P, G), dtype=bool)
    tgt_learner = np.zeros((K, P, G), dtype=bool)
    added = np.zeros((K, P, G), dtype=bool)
    removed = np.zeros((K, P, G), dtype=bool)
    for g in range(G):
        sig = sig_of_group[g]
        n_ops[g] = len(sig)
        for k, slot in enumerate(chains[sig]):
            op_start[k, g] = phase_start[slot.phase]
            for p in range(P):
                pid = p + 1
                tgt_voter[k, p, g] = pid in slot.voters_inc
                tgt_outgoing[k, p, g] = pid in slot.voters_out
                tgt_learner[k, p, g] = pid in slot.learners
                added[k, p, g] = pid in slot.added
                removed[k, p, g] = pid in slot.removed
    return (
        phase_of_round, append, op_start, n_ops,
        tgt_voter, tgt_outgoing, tgt_learner, added, removed,
        sig_of_group, chains,
    )


def mix_plan(path, n_groups):
    with open(os.path.join(ROOT, path), encoding="utf-8") as f:
        mix = json.load(f)
    doc, _ = traffic.reconfig_document(mix, n_groups, 5, "t", [1, 2, 3], [])
    return reconfig.plan_from_dict(doc)


def learner_plan(_n_groups):
    """Learners at boot, staged (`learner` in a joint entry: learners_next)
    and promoted; three classes of groups with chains of 4, 2 and 1 ops,
    and groups that follow none."""
    return reconfig.plan_from_dict({
        "name": "learners", "peers": 5, "voters": [1, 2, 3], "learners": [4],
        "phases": [
            {"rounds": 3, "append": 2},
            {"rounds": 2, "op": {"promote_learner": 4},
             "groups": {"mod": 3, "eq": 0}},
            {"rounds": 5, "op": {"enter_joint": [{"learner": 1}, {"add": 5}]},
             "groups": {"mod": 3, "eq": 0}, "append": 1},
            {"rounds": 1, "op": {"add_learner": 5},
             "groups": {"mod": 3, "eq": 1}},
            {"rounds": 4, "op": {"leave_joint": True},
             "groups": {"mod": 3, "eq": 0}},
            {"rounds": 2, "op": {"remove_voter": 2}, "groups": [0, 1, 5]},
        ],
    })


PLANS = {
    "churn": lambda g: mix_plan("benchmark/tests/data/churn.json", g),
    "churn-crash": lambda g: mix_plan(
        "benchmark/tests/data/churn-crash.json", g),
    "rebalance": lambda g: mix_plan("benchmark/traffic/rebalance.json", g),
    "learners": learner_plan,
}


@pytest.mark.parametrize("n_groups", [64, 97])
@pytest.mark.parametrize("name", sorted(PLANS))
def test_planes_filled_per_signature_equal_the_loops(name, n_groups):
    plan = PLANS[name](n_groups)
    got = reconfig._compile_schedule(plan, n_groups)
    want = compile_schedule_by_group(plan, n_groups)
    for key, a, b in zip(ARRAYS, got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, key
        assert np.array_equal(a, b), key
    # ... and what HostReconfigSchedule reads: the same walk per group.
    assert got[9] == want[9]
    assert got[10] == want[10]
    host = reconfig.HostReconfigSchedule(plan, n_groups)
    for g in (0, 1, n_groups - 1):
        for k in range(int(host.n_ops[g])):
            assert host.slot(g, k) == want[10][want[9][g]][k]
