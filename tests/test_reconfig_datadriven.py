"""Golden-file tests for the reconfig corpus using the datadriven runner.

Each case replays one named scenario from
tests/testdata/reconfig/plans.json — a ReconfigPlan paired with the
ChaosPlan it rides through (host-materialized schedule masks, the
propose/gate/apply protocol of runner._runner_body applied eagerly —
bit-identical to the compiled scan, tests/test_reconfig_parity.py) — and
records the end-state health planes, consensus cursors, final config
masks, op-protocol outcome, and the per-round safety counts.  The five
scenarios are the corpus the ISSUE names: joint-entry during symmetric
split, remove-leader under asymmetric link, promote-learner with lossy
majority, joint-exit blocked by a downed outgoing majority, rolling
add/remove churn.

Every case shares one (G=8, P=3, window=8) jitted step — the harness
keeps ONE link-path compile by threading every schedule through
`sim.step(..., health=, link=, reconfig_propose=)` directly — while the
gate/apply tail runs as cheap eager kernel calls per round.  Regenerate
with RAFT_TPU_REWRITE=1."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.datadriven import TestData, parse_file, run_test, walk
from raft_tpu.multiraft import SimConfig
from raft_tpu.multiraft import chaos, kernels, reconfig
from raft_tpu.multiraft import sim as sim_mod

TESTDATA = os.path.join(os.path.dirname(__file__), "testdata")

G, P, WINDOW = 8, 3, 8


class ReconfigHarness:
    def __init__(self):
        self.cfg = SimConfig(
            n_groups=G, n_peers=P, collect_health=True,
            health_window=WINDOW,
        )
        self._step = jax.jit(
            functools.partial(sim_mod.step, self.cfg)
        )
        with open(
            os.path.join(TESTDATA, "reconfig", "plans.json"),
            encoding="utf-8",
        ) as f:
            self.plans = {d["name"]: d for d in json.load(f)}

    def handle(self, td: TestData) -> str:
        if td.cmd != "run":
            raise ValueError(f"unknown command {td.cmd}")
        arg = td.arg("plan")
        if arg is None:
            raise ValueError(f"{td.pos}: run needs plan=<name>")
        doc = self.plans[arg.value]
        plan = reconfig.plan_from_dict(doc["reconfig"])
        cplan = chaos.plan_from_dict(doc["chaos"])
        if plan.n_peers != P or cplan.n_peers != P:
            raise ValueError(f"{td.pos}: corpus plans must use peers={P}")
        sched = reconfig.HostReconfigSchedule(plan, G)
        csched = chaos.HostSchedule(cplan, G)
        if csched.n_rounds != sched.n_rounds:
            raise ValueError(f"{td.pos}: plan/chaos round mismatch")
        vm, om, lm = reconfig.initial_masks(plan, G)
        st = sim_mod.init_state(self.cfg, vm, om, lm)
        hl = sim_mod.init_health(self.cfg)
        rst = reconfig.init_reconfig_state(st)
        compiled = reconfig.compile_plan(plan, G)
        safety = np.zeros(kernels.N_SAFETY, np.int64)
        rstats = np.zeros(reconfig.N_RECONFIG_STATS, np.int64)
        for r in range(sched.n_rounds):
            link, crashed, capp = csched.masks(r)
            append = sched.append[int(sched.phase_of_round[r])] + capp
            k = np.clip(np.asarray(rst.op_ptr), 0,
                        sched.op_start.shape[0] - 1)
            start = sched.op_start[k, np.arange(G)]
            active = (np.asarray(rst.op_ptr) < sched.n_ops) & (r >= start)
            want = active & (np.asarray(rst.stage) == 0)
            want_j = jnp.asarray(want)
            st2, hl, prop = self._step(
                st, jnp.asarray(crashed),
                jnp.asarray(append + want, dtype=jnp.int32),
                None, None, hl, jnp.asarray(link), want_j,
            )
            got = want & (np.asarray(prop.owner) > 0)
            stage = np.where(got, 1, np.asarray(rst.stage))
            powner = np.where(got, np.asarray(prop.owner),
                              np.asarray(rst.prop_owner))
            pindex = np.where(got, np.asarray(prop.index),
                              np.asarray(rst.prop_index))
            pterm = np.where(got, np.asarray(prop.term),
                             np.asarray(rst.prop_term))
            o = np.clip(powner - 1, 0, P - 1)
            gi = np.arange(G)
            own_lead = (
                (np.asarray(st2.state)[o, gi] == kernels.ROLE_LEADER)
                & (np.asarray(st2.term)[o, gi] == pterm)
                & ~crashed[o, gi]
            )
            committed = np.asarray(st2.commit)[o, gi] >= pindex
            apply_mask = (stage == 1) & own_lead & committed
            retry = (stage == 1) & ~own_lead
            stage = np.where(apply_mask | retry, 0, stage)
            safety += np.asarray(
                kernels.check_safety(
                    st2.state, st2.term, st2.commit, st2.last_index,
                    st2.agree, st.commit,
                    voter_mask=st2.voter_mask,
                    outgoing_mask=st2.outgoing_mask,
                    matched=st2.matched,
                    crashed=jnp.asarray(crashed),
                    prev_voter_mask=rst.prev_voter,
                    prev_outgoing_mask=rst.prev_outgoing,
                )
            )
            op_ptr = np.asarray(rst.op_ptr)
            (
                state3, leader3, commit3, matched3, vm3, om3, lm3, _, _,
            ) = kernels.apply_confchange(
                st2.state, st2.leader_id, st2.commit,
                st2.term_start_index, st2.matched, st2.voter_mask,
                st2.outgoing_mask, st2.learner_mask,
                reconfig._gather_op(compiled.tgt_voter, jnp.asarray(op_ptr, jnp.int32)),
                reconfig._gather_op(compiled.tgt_outgoing, jnp.asarray(op_ptr, jnp.int32)),
                reconfig._gather_op(compiled.tgt_learner, jnp.asarray(op_ptr, jnp.int32)),
                reconfig._gather_op(compiled.added, jnp.asarray(op_ptr, jnp.int32)),
                reconfig._gather_op(compiled.removed, jnp.asarray(op_ptr, jnp.int32)),
                jnp.asarray(apply_mask), None,
            )
            rstats += np.asarray([
                got.sum(), apply_mask.sum(), retry.sum(),
                int(np.asarray(jnp.any(om3, axis=0)).sum()),
            ])
            rst = reconfig.ReconfigState(
                stage=jnp.asarray(stage, jnp.int32),
                op_ptr=jnp.asarray(
                    np.where(apply_mask, op_ptr + 1, op_ptr), jnp.int32
                ),
                prop_owner=jnp.asarray(powner, jnp.int32),
                prop_index=jnp.asarray(pindex, jnp.int32),
                prop_term=jnp.asarray(pterm, jnp.int32),
                prev_voter=st2.voter_mask,
                prev_outgoing=st2.outgoing_mask,
            )
            st = st2._replace(
                state=state3, leader_id=leader3, commit=commit3,
                matched=matched3, voter_mask=vm3, outgoing_mask=om3,
                learner_mask=lm3,
            )
        # tail audit (the scan's post-loop fold)
        safety += np.asarray(
            kernels.check_safety(
                st.state, st.term, st.commit, st.last_index, st.agree,
                st.commit,
                voter_mask=st.voter_mask,
                outgoing_mask=st.outgoing_mask, matched=st.matched,
                prev_voter_mask=rst.prev_voter,
                prev_outgoing_mask=rst.prev_outgoing,
            )
        )
        planes = np.asarray(hl.planes)
        out = [
            f"{name}: {' '.join(str(v) for v in planes[i])}"
            for i, name in enumerate(kernels.HEALTH_PLANE_NAMES)
        ]
        leaders = (np.asarray(st.state) == kernels.ROLE_LEADER).sum(
            axis=0
        )
        out.append("leaders: " + " ".join(str(v) for v in leaders))
        out.append(
            "max_term: "
            + " ".join(str(v) for v in np.asarray(st.term).max(axis=0))
        )
        out.append(
            "commit: "
            + " ".join(str(v) for v in np.asarray(st.commit).max(axis=0))
        )
        out.append(
            "voters: "
            + " ".join(
                "".join(
                    str(int(v)) for v in np.asarray(st.voter_mask)[:, g]
                )
                for g in range(G)
            )
        )
        out.append(
            "learners: "
            + " ".join(
                "".join(
                    str(int(v))
                    for v in np.asarray(st.learner_mask)[:, g]
                )
                for g in range(G)
            )
        )
        out.append(
            "joint: "
            + " ".join(
                str(int(v))
                for v in np.asarray(st.outgoing_mask).any(axis=0)
            )
        )
        out.append(
            "op_ptr: "
            + " ".join(str(v) for v in np.asarray(rst.op_ptr))
        )
        out.append(
            "reconfig: "
            + " ".join(
                f"{k}={v}"
                for k, v in zip(reconfig.RECONFIG_STAT_NAMES, rstats)
            )
        )
        out.append(
            "safety: "
            + " ".join(
                f"{k}={v}"
                for k, v in zip(kernels.SAFETY_NAMES, safety)
            )
        )
        assert not safety.any(), (
            f"{td.pos}: joint-window safety violations: {safety}"
        )
        return "\n".join(out) + "\n"


def test_reconfig_datadriven():
    harness = ReconfigHarness()  # shared: one link-path jit total
    ran = []

    def run(path):
        run_test(path, harness.handle)
        ran.append(path)

    walk(os.path.join(TESTDATA, "reconfig"), run)
    assert ran


def test_corpus_covers_required_scenarios():
    """The ISSUE's five scenario families must stay present by name."""
    harness = ReconfigHarness()
    want = {
        "joint_entry_split", "remove_leader_asym",
        "promote_learner_lossy", "joint_exit_blocked", "rolling_churn",
    }
    assert want <= set(harness.plans)
    # and the golden walker exercises each of them
    path = os.path.join(TESTDATA, "reconfig", "scenarios.txt")
    seen = set()
    for td in parse_file(path):
        if td.cmd == "run":
            arg = td.arg("plan")
            if arg is not None:
                seen.add(arg.value)
    assert want <= seen
