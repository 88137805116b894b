"""GC014: the committed jaxpr-size budget (tools/graftcheck/jaxpr_budget.json).

The budget file is a committed baseline for compile time: one
committed equation count per inventoried graph, checked on every trace run
and regenerated only deliberately (``--update-budget`` / ``make
jaxpr-budget``), so jaxpr growth — which is compile time, which is tier-1
budget (docs/PERF.md) — is paid visibly in review instead of silently in
compile seconds.  ISSUE 6 bought the link path down 2716 -> 356 eqns;
this file is what holds that class of line.

Pure stdlib on purpose: the check/diff logic must be unit-testable (and
the budget replayable in CI artifacts) without importing jax — only the
MEASUREMENT (trace/analysis.py) needs jax.

File format::

    {
      "format": 1,
      "versions": {"jax": "0.4.37", "jaxlib": "0.4.36"},
      "tolerance_pct": 15.0,
      "graphs": {"step@plain": {"eqns": 1567}, ...}
    }

Failure modes (each a GC014 violation): a measured graph above its entry
by more than ``tolerance_pct``; an inventoried graph with no entry (new
graphs must be budgeted in the same PR); a budget entry naming no
inventoried graph (stale — regenerate).  Shrinkage never fails (only
regressions are gated) but is recorded in the diff
artifact so an intentional reduction can be re-baselined.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from ..core import Violation

BUDGET_NAME = "jaxpr_budget.json"
BUDGET_FORMAT = 1
DEFAULT_TOLERANCE_PCT = 15.0

GC014 = "GC014"
GC014_SLUG = "jaxpr-budget"

GC019 = "GC019"
GC019_SLUG = "phase-budget"
DEFAULT_PHASE_TOLERANCE_PCT = 2.0


def budget_path(repo_root: Path) -> Path:
    return repo_root / "tools" / "graftcheck" / BUDGET_NAME


def load_budget(path: Path) -> Optional[dict]:
    """The parsed budget document, or None when missing/unreadable (the
    caller reports that as a violation — a missing budget must not read
    as green)."""
    try:
        doc = json.loads(path.read_text(encoding="utf-8"))
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(doc, dict) or doc.get("format") != BUDGET_FORMAT:
        return None
    if not isinstance(doc.get("graphs"), dict):
        return None
    return doc


def render_budget(
    measured: Dict[str, int], versions: Dict[str, str],
    tolerance_pct: float = DEFAULT_TOLERANCE_PCT,
    phase_doc: Optional[dict] = None,
) -> str:
    doc = {
        "format": BUDGET_FORMAT,
        "versions": versions,
        "tolerance_pct": tolerance_pct,
        "graphs": {
            name: {"eqns": int(n)} for name, n in sorted(measured.items())
        },
    }
    if phase_doc:
        doc.update(phase_doc)
    return json.dumps(doc, indent=2, sort_keys=True) + "\n"


# --- GC019: the phase-budget decomposition -----------------------------------
#
# Each runner variant's eqn count must decompose (within tolerance) into
# eqns(base graph) + sum(registered phase-kernel budgets) — so a phase
# accidentally lowered TWICE into one runner variant (a duplicated chaos
# gather, a re-traced client arm) fails the build even when the total
# still clears GC014's 15% growth gate.  `variants` rows are
# schedules.RunnerVariant-shaped (name/base/phases/probe_for); the logic
# stays stdlib so the unit tests and the negative fixture run jax-less.


def derive_phase_doc(
    measured: Dict[str, int],
    variants,
    tolerance_pct: float = DEFAULT_PHASE_TOLERANCE_PCT,
) -> dict:
    """The committed GC019 sections, derived at regen time: each phase's
    eqn budget is defined by its unique probe variant (phase =
    eqns(probe) - eqns(base) - other registered phases, clamped at 0),
    in registry declaration order — GC018 pins exactly one probe per
    phase, and probes for composite variants come after the probes of
    the phases they ride on.  Every variant's residual (measured vs
    base + sum(phases)) is recorded so the check can gate GROWTH of the
    residual rather than its absolute value (base graphs and runner
    graphs share lowering that never decomposes exactly)."""
    phases: Dict[str, int] = {}
    runners: Dict[str, dict] = {}
    for v in variants:
        if not v.probe_for:
            continue
        base = measured.get(v.base)
        own = measured.get(v.name)
        if base is None or own is None:
            continue
        others = sum(
            phases.get(p, 0) for p in v.phases if p != v.probe_for
        )
        phases[v.probe_for] = max(0, own - base - others)
    for v in variants:
        base = measured.get(v.base)
        own = measured.get(v.name)
        if base is None or own is None:
            continue
        predicted = base + sum(phases.get(p, 0) for p in v.phases)
        residual = (
            (own - predicted) * 100.0 / predicted if predicted else 0.0
        )
        runners[v.name] = {
            "base": v.base,
            "phases": list(v.phases),
            "predicted": int(predicted),
            "residual_pct": round(residual, 2),
        }
    return {
        "phases": phases,
        "runners": runners,
        "phase_tolerance_pct": tolerance_pct,
    }


def check_phase_budget(
    measured: Dict[str, int],
    doc: Optional[dict],
    anchor_path: str,
    variants,
    full_registry: bool = True,
) -> Tuple[List[Violation], dict]:
    """GC019 over one measurement: recompute each variant's residual
    against the committed phase budgets and fail any variant whose
    residual GREW past the recorded one by more than the committed
    tolerance (percentage points).  Shrinkage never fails (the GC014
    convention).  On a partial run (fixture specs, --rule subsets)
    variants whose graphs were not traced are skipped, and stale
    `runners` entries are only reported on the full-registry run."""

    def v(line_msg: str) -> Violation:
        return Violation(anchor_path, 1, GC019, GC019_SLUG, line_msg)

    violations: List[Violation] = []
    diff: dict = {"runners": {}}
    if doc is None:
        return violations, diff  # GC014 already reports the missing budget
    phases = doc.get("phases")
    runners = doc.get("runners")
    if not isinstance(phases, dict) or not isinstance(runners, dict):
        violations.append(
            v(
                "committed budget has no GC019 phase decomposition "
                "('phases'/'runners' sections) — regenerate with "
                "`make jaxpr-budget` and commit it"
            )
        )
        return violations, diff
    tolerance = float(
        doc.get("phase_tolerance_pct", DEFAULT_PHASE_TOLERANCE_PCT)
    )
    diff["phase_tolerance_pct"] = tolerance
    diff["phases"] = dict(phases)
    for var in variants:
        own = measured.get(var.name)
        base = measured.get(var.base)
        if own is None or base is None:
            continue  # partial run: the variant's graphs were not traced
        predicted = base + sum(int(phases.get(p, 0)) for p in var.phases)
        residual = (
            (own - predicted) * 100.0 / predicted if predicted else 0.0
        )
        entry = runners.get(var.name)
        if not isinstance(entry, dict) or "residual_pct" not in entry:
            violations.append(
                v(
                    f"runner variant {var.name!r} has no recorded GC019 "
                    "residual — every variant's phase decomposition must "
                    "be committed in the PR that adds it "
                    "(`make jaxpr-budget`)"
                )
            )
            diff["runners"][var.name] = {
                "recorded": None,
                "residual_pct": round(residual, 2),
                "status": "new",
            }
            continue
        recorded = float(entry["residual_pct"])
        status = "ok"
        if residual > recorded + tolerance:
            status = "over"
            violations.append(
                v(
                    f"runner variant {var.name!r} traced to {own} eqns "
                    f"but its phase decomposition predicts {predicted} "
                    f"(base {var.base!r} = {base} + phases "
                    f"{list(var.phases)}): residual {residual:+.2f}% vs "
                    f"recorded {recorded:+.2f}% (tolerance "
                    f"{tolerance:.1f} pts) — a phase is lowered more "
                    "than once into this variant (or a phase kernel "
                    "grew without its probe moving); deduplicate the "
                    "lowering or pay for it visibly with "
                    "`make jaxpr-budget`"
                )
            )
        elif residual < recorded - tolerance:
            status = "shrunk"
        diff["runners"][var.name] = {
            "recorded": recorded,
            "residual_pct": round(residual, 2),
            "status": status,
        }
    if full_registry:
        # Stale = names no REGISTERED variant (a variant whose build
        # failed is a GC000 finding, not a stale entry).
        registered = {var.name for var in variants}
        for name in sorted(set(runners) - registered):
            violations.append(
                v(
                    f"GC019 `runners` entry {name!r} names no registered "
                    "runner variant — stale after a registry change; "
                    "regenerate with `make jaxpr-budget`"
                )
            )
            diff["runners"][name] = {"status": "stale"}
    return violations, diff


def check_budget(
    measured: Dict[str, int],
    doc: Optional[dict],
    anchor_path: str,
    measured_versions: Optional[Dict[str, str]] = None,
) -> Tuple[List[Violation], dict]:
    """(violations, diff document) for a measurement against the committed
    budget.  ``anchor_path`` is where violations anchor (the budget file's
    repo-relative path).  ``measured_versions`` is the measuring
    environment's jax/jaxlib versions: when they differ from the budget's
    recorded stamp, an over-budget finding may be an upstream lowering
    change rather than a repo change, so the mismatch is recorded in the
    diff (``version_mismatch``) and appended to every over-budget message
    — the gate still fails (growth is growth), but the verdict says where
    to look."""

    def v(message: str) -> Violation:
        return Violation(anchor_path, 1, GC014, GC014_SLUG, message)

    violations: List[Violation] = []
    diff: dict = {"graphs": {}, "versions": {}}
    if doc is None:
        violations.append(
            v(
                "committed jaxpr budget is missing or unreadable; "
                "regenerate with `make jaxpr-budget` and commit it"
            )
        )
        for name, eqns in sorted(measured.items()):
            diff["graphs"][name] = {
                "budget": None, "measured": eqns, "status": "new",
            }
        return violations, diff
    tolerance = float(doc.get("tolerance_pct", DEFAULT_TOLERANCE_PCT))
    diff["tolerance_pct"] = tolerance
    diff["versions"] = doc.get("versions", {})
    mismatch = bool(
        measured_versions
        and diff["versions"]
        and measured_versions != diff["versions"]
    )
    diff["version_mismatch"] = mismatch
    version_note = (
        (
            f" [NOTE: installed {measured_versions} differ from the "
            f"budget's recorded {diff['versions']} — this may be an "
            "upstream jax lowering change, not a repo change; re-baseline "
            "with `make jaxpr-budget` at the new versions if so]"
        )
        if mismatch
        else ""
    )
    graphs = doc["graphs"]
    for name, eqns in sorted(measured.items()):
        entry = graphs.get(name)
        if not isinstance(entry, dict) or "eqns" not in entry:
            violations.append(
                v(
                    f"graph {name!r} has no budget entry — every inventoried "
                    "graph must be budgeted in the PR that adds it "
                    "(`make jaxpr-budget`)"
                )
            )
            diff["graphs"][name] = {
                "budget": None, "measured": eqns, "status": "new",
            }
            continue
        budget = int(entry["eqns"])
        delta_pct = (
            (eqns - budget) * 100.0 / budget if budget else float(eqns > 0)
        )
        status = "ok"
        if eqns > budget * (1.0 + tolerance / 100.0):
            status = "over"
            violations.append(
                v(
                    f"graph {name!r} traced to {eqns} eqns, "
                    f"{delta_pct:+.1f}% over its budget of {budget} "
                    f"(tolerance {tolerance:.0f}%) — jaxpr growth is compile "
                    "time is tier-1 budget (docs/PERF.md); shrink the graph "
                    "or pay for it visibly with `make jaxpr-budget`"
                    + version_note
                )
            )
        elif eqns < budget * (1.0 - tolerance / 100.0):
            # An improvement never fails, but a
            # stale high baseline hands the next regression free headroom —
            # the diff artifact flags it for re-baselining.
            status = "shrunk"
        diff["graphs"][name] = {
            "budget": budget,
            "measured": eqns,
            "delta_pct": round(delta_pct, 2),
            "status": status,
        }
    for name in sorted(set(graphs) - set(measured)):
        violations.append(
            v(
                f"budget entry {name!r} names no inventoried graph — stale "
                "after an inventory change; regenerate with "
                "`make jaxpr-budget`"
            )
        )
        diff["graphs"][name] = {
            "budget": int(graphs[name].get("eqns", 0))
            if isinstance(graphs[name], dict)
            else None,
            "measured": None,
            "status": "stale",
        }
    return violations, diff
