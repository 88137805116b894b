"""The import order of raft_tpu/multiraft, read off the source (AST: no jax).

    planes, schedules <- kernels <- sim <- pallas_step, chaos
        <- workload, reconfig <- runner <- autopilot

A module's module-level imports go only to tiers strictly below its own; an
import deferred into a function may also stay inside its tier, never go up.
The modules the order above does not name sit in the lowest tier their
imports allow.  The one exception is named below and goes with ROADMAP
C12 (b), not the test."""

import ast
import glob
import os

import pytest

PKG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "raft_tpu", "multiraft"
)
TIERS = (
    ("planes", "schedules", "native", "simref"),
    ("kernels",),
    ("sim",),
    ("pallas_step", "chaos"),
    ("workload", "reconfig", "forensics"),
    ("runner", "health", "checkpoint", "sharding"),
    ("autopilot", "driver"),
    ("__init__",),
)
TIER = {name: i for i, tier in enumerate(TIERS) for name in tier}
# C12 (b): `ClusterSim`, the facade over every layer, still lives in sim.py and
# reaches the layers above it through imports deferred into its methods.  The
# PR that moves the class out deletes this entry.
UPWARD_DEFERRED = {
    "sim": {"chaos", "forensics", "health", "reconfig", "runner", "sharding", "workload"},
}
MODULES = sorted(os.path.basename(p)[:-3] for p in glob.glob(os.path.join(PKG, "*.py")))


def tree_of(module):
    with open(os.path.join(PKG, module + ".py"), encoding="utf-8") as f:
        return ast.parse(f.read())


def siblings(node):
    """The modules of this package one import statement names."""
    if not isinstance(node, ast.ImportFrom) or node.level != 1:
        return set()
    if node.module is None:
        return {alias.name for alias in node.names}
    return {node.module.split(".")[0]}


def imports_of(module):
    """(module-level, deferred) sibling imports."""
    tree = tree_of(module)
    top = set().union(*(siblings(n) for n in tree.body))
    every = set().union(*(siblings(n) for n in ast.walk(tree)))
    return top, every - top


def test_every_module_has_a_tier():
    assert sorted(TIER) == MODULES


@pytest.mark.parametrize("module", MODULES)
def test_imports_point_down(module):
    top, deferred = imports_of(module)
    up = {m for m in top if TIER[m] >= TIER[module]}
    assert not up, f"{module} imports {sorted(up)} at module level: not below it"
    up = {m for m in deferred if TIER[m] > TIER[module]}
    assert up == UPWARD_DEFERRED.get(module, set()), (module, sorted(up))


def test_sim_reaches_up_only_from_cluster_sim():
    """C12 (b)'s exception is the facade's and nobody else's in sim.py."""
    tree = tree_of("sim")
    inside = set()
    for node in tree.body:
        if isinstance(node, ast.ClassDef) and node.name == "ClusterSim":
            inside = {id(n) for n in ast.walk(node)}
    assert inside, "ClusterSim left sim.py: delete UPWARD_DEFERRED['sim'] (C12 (b))"
    outside = set().union(*(
        siblings(n) for n in ast.walk(tree) if id(n) not in inside
    ))
    assert not {m for m in outside if TIER[m] > TIER["sim"]}


def test_reconfig_knows_neither_the_workload_nor_the_runner():
    top, deferred = imports_of("reconfig")
    assert not (top | deferred) & {"workload", "runner", "autopilot"}
    names = {n.name for n in tree_of("reconfig").body if isinstance(n, ast.FunctionDef)}
    assert "_runner_body" not in names
    assert "_runner_body" in {
        n.name for n in tree_of("runner").body if isinstance(n, ast.FunctionDef)
    }


def test_pallas_step_holds_no_dispatcher():
    """Choosing between a fused kernel and the general round is
    runner.make_runner(..., split=True)'s business: no function of
    pallas_step branches (`lax.cond` / `lax.switch`) or calls `sim.step`."""
    tree = tree_of("pallas_step")
    called = {
        n.func.attr for n in ast.walk(tree)
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
    }
    assert not called & {"cond", "switch", "step"}, sorted(called & {"cond", "switch", "step"})
    # ... and `sim` is there for its types alone.
    assert all(
        "sim" not in {alias.name for alias in n.names}
        for n in tree.body if isinstance(n, ast.ImportFrom) and n.module is None
    )
