"""The general round's map (ISSUE 42): names on every op of a
`runner._runner_body` round, and nothing but names.

  * the names change no equation: with `profiling.scope` patched to a null
    context before `raft_tpu.multiraft` is imported (a child process: the
    module-level decorators are applied at import), `jax.make_jaxpr` of
    `sim.step` on the damped fleets and of the client scan runners prints
    the text it prints with the scopes;
  * every equation a round runs carries a scope of `profiling.SCOPES`: the
    CPU-side twin of the benchmark's `unscoped_share`.  The walk is over
    the jaxpr — an equation's `source_info.name_stack` under its
    containers' is the name stack its device ops get; the lowered text
    spells the inside of an outlined function relative to its call.  A
    later PR that adds un-named work to the round fails here, at no chip
    time;
  * off the pinned CPU the names are part of a program's compile-cache key.
"""

import contextlib
import functools
import hashlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from raft_tpu import profiling
from raft_tpu.multiraft import SimConfig, chaos, kernels, reconfig, sim, workload
from raft_tpu.multiraft import runner as runner_mod

HERE = os.path.dirname(os.path.abspath(__file__))
G = 8
ROUNDS = 48


def damped_cfg(P, lease=True, pre_vote=True):
    return SimConfig(
        G, P, election_tick=10, heartbeat_tick=2, check_quorum=True,
        pre_vote=pre_vote, collect_health=True, lease_read=lease,
    )


# Check-quorum WITHOUT pre-vote, reads through ReadIndex (`fleet-100k-r5-cq`,
# ISSUE 44): the `not pv` arms of the damped round — the campaign that raises
# the term at once, the real tally in wave 2, a re-broadcast's commit.
CQ_ONLY = {"lease": False, "pre_vote": False}


def client_of(P):
    return workload.compile_plan(workload.plan_from_dict({
        "name": "t", "peers": P, "seed": 1, "phases": [
            {"rounds": ROUNDS, "append": 1, "read_every": 2, "read_mode": "lease"}]}), G)


def chaos_of(P):
    return chaos.compile_plan(chaos.plan_from_dict({"name": "c", "peers": P, "phases": [
        {"rounds": 4}, {"rounds": 14, "crash": [1]}, {"rounds": 30, "crash": [2]}]}), G)


def step_program(P, lease, pre_vote=True):
    cfg = damped_cfg(P, lease, pre_vote)

    def fn(st, crashed, app, link, rd):
        return sim.step(cfg, st, crashed, app, link=link, read_propose=rd,
                        health=sim.init_health(cfg))

    app = jnp.ones((G,), jnp.int32)
    return fn, (sim.init_state(cfg), jnp.zeros((P, G), bool), app,
                jnp.ones((P, P, G), bool), app)


def scan_program(with_chaos, P=3, learners=False, **flags):
    """The client scan runner — the shape `.outage`, `.netsplit` and
    `.rebalance` run — without and with a chaos plan; with `learners`, as
    `ClusterSim.run_reads` calls it for a fleet that boots voters {1, 2, 3}
    and learners {4, 5} (`fleet-100k-r3l2`, ISSUE 47): the learners' lag
    rides beside the read carry."""
    cfg = damped_cfg(P, **flags)
    scheds = ((chaos_of(P),) if with_chaos else ()) + (client_of(P),)
    run = runner_mod.make_runner(cfg, scheds)
    rcar = workload.init_read_carry(G)
    if learners:
        rows = jnp.broadcast_to(jnp.arange(P)[:, None], (P, G))
        st = sim.init_state(cfg, rows < 3, None, rows >= 3)
        rcar = workload.LearnerLagCarry(rcar, jnp.int32(0))
    else:
        st = sim.init_state(cfg)
    return run.jitted, (st, sim.init_health(cfg), reconfig.init_reconfig_state(st),
                        rcar, *run.schedule_args)


def block_program(P=3, with_chaos=False):
    """The split runner's block program: guard, both arms, the damped
    round with reads and health in the general arm; with a chaos plan
    (ISSUE 51), the block's planes, the refusal counts and the chaos
    schedule's masks in the general arm's rounds."""
    cfg = damped_cfg(P)
    scheds = (client_of(P),) + ((chaos_of(P),) if with_chaos else ())
    run = runner_mod.make_runner(cfg, scheds, split=True, k=8)
    st = sim.init_state(cfg)
    zeros = lambda n: jnp.zeros((n,), jnp.int32)  # noqa: E731
    counts = (jnp.int32(0), jnp.zeros((len(workload.GUARD_TERMS),), jnp.int32))
    return run.fused_jit, (
        st, sim.init_health(cfg), reconfig.init_reconfig_state(st),
        zeros(chaos.N_CHAOS_STATS), zeros(reconfig.N_RECONFIG_STATS),
        zeros(kernels.N_SAFETY), workload.init_read_carry(G),
        zeros(workload.N_READ_STATS), zeros(workload.N_LAT_BUCKETS),
        jnp.int32(0), *(counts if with_chaos else ()), *run.block_args[0],
        *run.schedule_args,
    )


def segment_program(P=3, with_chaos=False):
    """A short split call (ISSUE 53): the accumulators' fills, the block
    program scanned over the stacked rows (48 rounds in blocks of 13: three
    and a tail of 9), the tail's rounds and the tail audit, in one program."""
    cfg = damped_cfg(P)
    scheds = (client_of(P),) + ((chaos_of(P),) if with_chaos else ())
    run = runner_mod.make_runner(cfg, scheds, split=True, k=13)
    st = sim.init_state(cfg)
    return run.jitted, (
        st, sim.init_health(cfg), reconfig.init_reconfig_state(st),
        workload.init_read_carry(G), *run.segment_args,
    )


PROGRAMS = {
    **{f"step-P{P}-{'lease' if lease else 'readindex'}": (step_program, (P, lease))
       for P in (3, 5) for lease in (True, False)},
    "step-P5-cq": (functools.partial(step_program, 5, **CQ_ONLY), ()),
    "client-scan": (scan_program, (False,)),
    "client-chaos-scan": (scan_program, (True,)),
    "client-chaos-scan-cq": (functools.partial(scan_program, True, 5, **CQ_ONLY), ()),
    "client-chaos-scan-learners": (functools.partial(scan_program, True, 5, learners=True), ()),
    "client-chaos-split": (block_program, (3, True)),
    "client-chaos-segment": (segment_program, (3, True)),
}


def jaxpr_digests():
    out = {}
    for name, (build, args) in PROGRAMS.items():
        fn, operands = build(*args)
        # A `reduce` with a combiner of its own prints the Python function's
        # address beside its jaxpr (chaos.fold_learner_lag): not an equation.
        text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(*operands)))
        out[name] = hashlib.sha1(text.encode()).hexdigest()
    return out


# --- the names change no equation ---------------------------------------------

NULL_SCOPES = (
    "import contextlib, json, sys\n"
    "import raft_tpu.profiling as profiling\n"
    "class Null(contextlib.ContextDecorator):\n"
    "    def __enter__(self): return self\n"
    "    def __exit__(self, *exc): return False\n"
    "profiling.scope = lambda name: Null()\n"
    "sys.path.insert(0, sys.argv[1])\n"
    "import test_round_map\n"
    "print(json.dumps(test_round_map.jaxpr_digests()))\n"
)


@pytest.fixture(scope="module")
def digests():
    """(with the scopes, with `profiling.scope` a null context)."""
    done = subprocess.run(
        [sys.executable, "-c", NULL_SCOPES, HERE], cwd=os.path.dirname(HERE),
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return jaxpr_digests(), json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_names_change_no_equation(digests, program):
    named, bare = digests
    assert named[program] == bare[program]


def test_a_null_scope_is_what_the_child_patched_in():
    """The patch the child applies does what it says here too: no scope
    lands in a traced function's name stacks."""
    class Null(contextlib.ContextDecorator):
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    @Null()
    def f(x):
        with Null():
            return x + 1

    stacks = {str(e.source_info.name_stack) for e in jax.make_jaxpr(f)(1).jaxpr.eqns}
    assert stacks == {""}


def test_off_the_pinned_cpu_a_programs_names_are_part_of_its_cache_key(monkeypatch):
    """jax leaves a program's metadata out of its persistent-cache key, so a
    source that differs from a cached one by names alone would be handed
    that executable and show the OLD names in a trace (it did, on the chip:
    PERF.md §6, PR 42).  `enable_compile_cache` puts the metadata into the
    key wherever a device can be traced, and leaves the pinned CPU alone."""
    from raft_tpu import platform

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    try:
        assert platform.pinned_to_cpu()
        platform.enable_compile_cache()
        assert getattr(jax.config, flag) == before
        monkeypatch.setattr(platform, "pinned_to_cpu", lambda: False)
        platform.enable_compile_cache()
        assert getattr(jax.config, flag) is True
    finally:
        jax.config.update(flag, before)


# --- every equation of a round carries a catalogue scope ------------------------

# Containers that run their body once per call of the program.  An equation
# under these alone runs once per `run_reads` call — the carry's zeros (the
# scan runner's and the split runner's segment program's),
# `reconfig.resume_state`, the tail audit's fold — and is allowed without a
# name; inside a `scan`, `while` or `cond` (a block of the segment program,
# a round, an arm, a loop trip) every equation has one.
ONCE = {"jit", "pjit", "closed_call"}


def sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in (value if isinstance(value, (tuple, list)) else (value,)):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def leaves(jaxpr, scopes=(), containers=()):
    """(primitive, name stack components, containing primitives) of every
    equation that holds no jaxpr of its own."""
    for eqn in jaxpr.eqns:
        own = tuple(c for c in str(eqn.source_info.name_stack).split("/") if c)
        inner = list(sub_jaxprs(eqn))
        for sub in inner:
            yield from leaves(sub, scopes + own, containers + (eqn.primitive.name,))
        if not inner:
            yield eqn.primitive.name, scopes + own, containers


ROUND_PROGRAMS = {
    "block": (block_program, ()),
    "client-scan": (scan_program, (False,)),
    "client-chaos-scan": (scan_program, (True,)),
    "client-chaos-scan-cq": PROGRAMS["client-chaos-scan-cq"],
    "client-chaos-scan-learners": PROGRAMS["client-chaos-scan-learners"],
    "client-chaos-split": PROGRAMS["client-chaos-split"],
    "segment": (segment_program, ()),
    "client-chaos-segment": PROGRAMS["client-chaos-segment"],
}


# `lax.cond` casts its predicate to an index itself: one scalar equation of a
# block that no scope of block_run can reach (a scope around the cond would
# put both arms under it, and `block_guard_share` reads any component of a
# name stack).  In the block program it is once a call; in the segment
# program it is the one bare equation of the scan over the blocks.
COND_INDEX = ("convert_element_type", "", "jit/scan")


@pytest.mark.parametrize("program", sorted(ROUND_PROGRAMS))
def test_every_equation_of_a_round_carries_a_catalogue_scope(program):
    build, args = ROUND_PROGRAMS[program]
    fn, operands = build(*args)
    found = list(leaves(jax.make_jaxpr(fn)(*operands).jaxpr))
    assert len(found) > 1000, "the walk reached the round body"
    bare = [f for f in found if not set(f[1]) & set(profiling.SCOPES)]
    in_a_round = sorted({(prim, "/".join(stack), "/".join(inside))
                         for prim, stack, inside in bare if not set(inside) <= ONCE})
    if program.endswith("segment"):
        assert COND_INDEX in in_a_round
        in_a_round.remove(COND_INDEX)
    assert not in_a_round, in_a_round
    assert len(bare) <= 20, "the once-per-call set-up stays a handful of equations"


def test_the_learners_lag_is_in_the_learner_fleets_round_alone():
    """`runner.learner_lag` names equations of the round only where the carry
    asks for the count (`workload.LearnerLagCarry`); the same fleet's round
    under a plain read carry — what every fleet that boots without learners
    runs — holds none, and is the program it was."""
    def scopes(learners):
        fn, operands = scan_program(True, 5, learners=learners)
        return {c for _prim, stack, _inside in leaves(jax.make_jaxpr(fn)(*operands).jaxpr)
                for c in stack}

    assert "runner.learner_lag" in scopes(True)
    assert "runner.learner_lag" not in scopes(False)
