"""Before/after election-damping churn report over the chaos golden corpus.

Runs every scenario in tests/testdata/chaos/plans.json twice — undamped
and fully damped (SimConfig check_quorum + pre_vote) — through the
compiled chaos scan (ClusterSim.run_plan) and writes one JSON document
comparing the runs per plan:

    {"groups": 128, "plans": {
        "asymmetric-link": {
            "undamped": {"mttr_rounds": ..., "reelections": ...,
                         "max_term": ..., "peak_term_bumps": ...,
                         "vote_splits": ..., "safety": {...}},
            "damped":   {...},
            "term_growth_ratio": 0.12}, ...}}

`max_term` is the fleet max term at scenario end (every run starts from a
fresh term-0 boot, so it IS the cumulative term growth), and
`peak_term_bumps` / `vote_splits` are end-of-run maxima over groups of
the PR 3 health planes.  The CI chaos step uploads the report next to
the scenario summaries; any safety-invariant count in EITHER
configuration exits non-zero, and so does a damped run whose term growth
fails to undercut the undamped run on the asymmetric-link scenario — the
churn collapse this PR exists to demonstrate.

With `--fused` (the CI setting since ISSUE 8) each scenario's damped
half ALSO replays through the split-horizon runner
(ClusterSim.run_reconfig(split=True) with the no-op membership schedule:
runner.make_runner's fused blocks where the steady predicate holds, the
general round elsewhere — both arms covered) and the run exits non-zero if
any churn stat diverges from the scan-damped run, pinning that fusion
cannot change churn results.

On a nonzero safety count the step no longer fails with bare counts
(ISSUE 15): the offending scenario re-runs with the device black box on
(`SimConfig(blackbox=True)` — a pure observer, bit-identical protocol
evolution), and the incident JSON (per-slot offender groups + their
decoded ring windows) plus the generated one-group datadriven repro are
written next to the report as CI artifacts
(forensics.capture_chaos_incident).

Usage:  python tools/chaos_churn_report.py [--groups N] [--fused]
        [--out FILE] [--artifacts-dir DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# Fused block length of the --fused replay.  The corpus plans are tens of
# rounds from a cold boot at election_tick 10: a block is fused only when
# the WHOLE fleet is steady for it, and at the runner's default of 8 no
# block of the corpus is.
SPLIT_K = 2


def run_config(
    doc: dict, groups: int, damped: bool, split: bool = False
) -> dict:
    """One corpus scenario from a fresh boot, through the compiled chaos
    scan or — `split` — through the split-horizon runner
    (runner.make_runner(..., split=True) under ClusterSim.run_reconfig,
    with the no-op membership schedule): every block is the fused kernel
    where the steady predicate holds for it — healed or merely lossy
    phases — and the general round otherwise, so BOTH arms get
    golden-corpus coverage.  The caller diffs the split run's churn stats
    against the scan's to pin that fusion cannot change churn results."""
    from raft_tpu.multiraft import ClusterSim, SimConfig, chaos, kernels
    from raft_tpu.multiraft import reconfig

    plan = chaos.plan_from_dict(doc)
    cfg = SimConfig(
        n_groups=groups,
        n_peers=plan.n_peers,
        collect_health=True,
        check_quorum=damped,
        pre_vote=damped,
    )
    if split:
        sim = ClusterSim(cfg)
        report = sim.run_reconfig(
            reconfig.empty_reconfig_schedule(
                plan.n_rounds, plan.n_peers, groups
            ),
            plan,
            split=True,
            split_k=SPLIT_K,
        )
    else:
        sim = ClusterSim(cfg, chaos=plan)
        report = sim.run_plan()
    planes = np.asarray(sim._health.planes)
    term = np.asarray(sim.state.term)
    out = {
        "mttr_rounds": report["mttr_rounds"],
        "reelections": report["reelections"],
        "max_leaderless_streak": report["max_leaderless_streak"],
        "max_term": int(term.max()),
        "peak_term_bumps": int(planes[kernels.HP_TERM_BUMPS].max()),
        "vote_splits": int(planes[kernels.HP_VOTE_SPLITS].max()),
        "safety": report["safety"],
    }
    if split:
        out["fused_rounds"] = report["fused_rounds"]
        out["total_rounds"] = report["total_rounds"]
    return out


FUSED_COMPARE_KEYS = (
    "mttr_rounds", "reelections", "max_leaderless_streak", "max_term",
    "peak_term_bumps", "vote_splits",
)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--groups", type=int, default=128)
    ap.add_argument(
        "--fused",
        action="store_true",
        help="also run each scenario's damped half through the split "
        "runner (fused blocks where the steady predicate holds) and fail "
        "if any churn stat diverges from the scan-damped run",
    )
    ap.add_argument("--out", default="chaos-churn-report.json")
    ap.add_argument(
        "--artifacts-dir",
        default="",
        help="directory for on-failure forensics artifacts (incident "
        "JSON + generated repro scenario); default: the --out directory",
    )
    ap.add_argument(
        "--plans",
        default=os.path.join(
            os.path.dirname(__file__), "..", "tests", "testdata", "chaos",
            "plans.json",
        ),
    )
    args = ap.parse_args()
    with open(args.plans, "r", encoding="utf-8") as f:
        docs = json.load(f)
    out = {"groups": args.groups, "plans": {}}
    failed = []
    to_capture: dict = {}
    total_fused = 0
    for doc in docs:
        name = doc["name"]
        undamped = run_config(doc, args.groups, damped=False)
        damped = run_config(doc, args.groups, damped=True)
        ratio = (
            damped["max_term"] / undamped["max_term"]
            if undamped["max_term"]
            else None
        )
        out["plans"][name] = {
            "undamped": undamped,
            "damped": damped,
            "term_growth_ratio": round(ratio, 3) if ratio is not None else None,
        }
        checked = (("undamped", undamped), ("damped", damped))
        if args.fused:
            fused = run_config(doc, args.groups, damped=True, split=True)
            out["plans"][name]["damped_fused"] = fused
            checked = checked + (("damped_fused", fused),)
            total_fused += fused["fused_rounds"]
            for key in FUSED_COMPARE_KEYS:
                if fused[key] != damped[key]:
                    failed.append(
                        f"{name}: fused-damped {key} {fused[key]} != "
                        f"scan-damped {damped[key]} — fusion changed the "
                        "churn result"
                    )
        for tag, rep in checked:
            if any(rep["safety"].values()):
                failed.append(f"{name}/{tag}: safety {rep['safety']}")
                to_capture[name] = (doc, tag != "undamped")
        print(
            f"{name}: max_term {undamped['max_term']} -> "
            f"{damped['max_term']}, peak bumps "
            f"{undamped['peak_term_bumps']} -> {damped['peak_term_bumps']}"
        )
    if args.fused and total_fused == 0:
        failed.append(
            "no golden-corpus block engaged the fused damped arm; the "
            "both-arms coverage claim is vacuous (predicate rot?)"
        )
    # The headline claim: damping collapses the asymmetric-partition term
    # inflation (the PR 5 pinned pathology).  The scenario MUST be in the
    # corpus — a rename would otherwise skip the gate vacuously.
    asym = out["plans"].get("asymmetric-link")
    if asym is None:
        failed.append(
            "golden corpus has no 'asymmetric-link' scenario; the churn "
            "collapse gate cannot run (renamed plan?)"
        )
    elif asym["damped"]["max_term"] >= asym["undamped"]["max_term"]:
        failed.append(
            "asymmetric-link: damped term growth "
            f"{asym['damped']['max_term']} did not undercut undamped "
            f"{asym['undamped']['max_term']}"
        )
    if to_capture:
        # Nonzero safety: attach the drill-down artifacts (ISSUE 15) —
        # the incident JSON and the generated one-group repro — instead
        # of failing with bare counts.
        from raft_tpu.multiraft import forensics

        art_dir = args.artifacts_dir or (
            os.path.dirname(os.path.abspath(args.out))
        )
        forensics.report_failures(
            to_capture, out,
            lambda name, doc, damped: forensics.capture_chaos_incident(
                doc, args.groups, art_dir, damped=damped,
                stem=f"incident-{name}",
            ),
        )
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")
    if failed:
        for msg in failed:
            print(f"ERROR: {msg}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
