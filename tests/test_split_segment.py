"""The workload split runner's segment program (ISSUE 53): a
`run_reads(split=True)` call of at most `runner._SEGMENT_MAX_BLOCKS` blocks
is ONE dispatch of `runner.jitted` — the accumulators' fills, the blocks a
`lax.scan` over the stacked `workload.BlockRows` (unrolled: no `while`), the
tail's rounds and the tail audit; a longer call dispatches a program a
block, as every call did before (`PERF.md` section 6, PR 53 has the chip's
reasons for the two).

  * the segment program against the blocks walked by hand — a Python loop
    of `runner.fused_jit` over `runner.block_args`, then the tail program,
    then the tail audit — every output bit for bit, for a bare plan and a
    chaos plan, with and without a tail, P = 3 and P = 5;
  * a segment shorter than a block is its tail alone;
  * the structure, so that the loop cannot come back unnoticed in a short
    call: `runner.jitted`'s jaxpr holds one outer `scan` of length
    `n_blocks` whose body holds the block's `cond`, and a call of
    `runner()` dispatches that one program and runs no `jnp` op of
    `runner.py` beside it; and which calls are short.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.multiraft import chaos, kernels, reconfig, workload
from raft_tpu.multiraft import runner as runner_mod
from test_workload_split_chaos import G, cfg_of, settled

K = 8


def zeros(n):
    return jnp.zeros((n,), jnp.int32)


def schedules(plan, P, flags, rounds):
    """One quiet block (it fuses), then reads — and, under the chaos plan, a
    store lost behind a lossy link — through the rest (general rounds)."""
    mode = "lease" if flags == "lease" else "safe"
    client = workload.plan_from_dict({"name": "c", "peers": P, "seed": 5, "phases": [
        {"rounds": K, "append": 1},
        {"rounds": rounds - K, "append": 1, "read_every": 3, "read_mode": mode}]})
    scheds = (workload.compile_plan(client, G),)
    if plan == "chaos":
        scheds += (chaos.compile_plan(chaos.plan_from_dict({"name": "x", "peers": P, "phases": [
            {"rounds": K},
            {"rounds": K // 2, "crash": [1], "loss": [{"from": 2, "to": 3, "rate": 0.4}]},
            {"rounds": rounds - K - K // 2}]}), G),)
    return scheds


def fresh(st0, hl0):
    return (jax.tree.map(jnp.copy, st0), jax.tree.map(jnp.copy, hl0),
            reconfig.init_reconfig_state(st0), workload.init_read_carry(G))


# (plan, P, rounds, flags): 16 rounds are two blocks, 21 leave a tail of 5.
CASES = [
    ("bare", 3, 16, "lease"), ("bare", 5, 21, "stock"),
    ("chaos", 3, 21, "readindex"), ("chaos", 5, 16, "stock"),
]


@pytest.mark.parametrize("plan,P,rounds,flags", CASES, ids=["-".join(map(str, c)) for c in CASES])
def test_the_segment_program_is_the_blocks_walked_by_hand(plan, P, rounds, flags):
    """One call of `runner()` returns what the per-block form returns: the
    nine outputs, the fused count and, under a chaos plan, `healthy_refused`
    and the refusal counts."""
    cfg = cfg_of(flags, P)
    run = runner_mod.make_runner(cfg, schedules(plan, P, flags, rounds), split=True, k=K)
    assert run.n_blocks == rounds // K == len(run.block_args) <= runner_mod._SEGMENT_MAX_BLOCKS
    assert run.jitted is not None and (run.tail_jit is None) == (rounds % K == 0)
    st0, hl0 = settled(cfg)

    got = run(*fresh(st0, hl0))

    st, hl, rst, rcar = fresh(st0, hl0)
    carry = (st, hl, rst, zeros(chaos.N_CHAOS_STATS), zeros(reconfig.N_RECONFIG_STATS),
             zeros(kernels.N_SAFETY), rcar, zeros(workload.N_READ_STATS),
             zeros(workload.N_LAT_BUCKETS), jnp.int32(0))
    if plan == "chaos":
        carry += (jnp.int32(0), zeros(len(workload.GUARD_TERMS)))
    for block in run.block_args:
        carry = run.fused_jit(*carry, *block, *run.schedule_args)
    if run.tail_jit is not None:
        carry = run.tail_jit(*carry, jnp.int32(run.n_blocks * K), *run.schedule_args)
    want = carry[:5] + (carry[5] + runner_mod._tail_audit(carry[0], carry[2]),) + carry[6:]

    assert len(got) == len(want) == (12 if plan == "chaos" else 10)
    for i, (a, b) in enumerate(zip(got, want)):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        assert len(la) == len(lb), i
        for x, y in zip(la, lb):
            assert x.dtype == y.dtype and x.shape == y.shape, i
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=f"output {i}")
    # Both arms ran inside the program, and reads were issued.
    assert 0 < int(got[9]) < run.n_blocks * K * G and int(got[9]) % (K * G) == 0
    assert np.asarray(got[7])[workload.RS_ISSUED] > 0
    assert not np.asarray(got[5]).any(), "safety slots"


def test_a_segment_shorter_than_a_block_is_its_tail():
    """n_rounds < k: no block, no scan over blocks — the tail's rounds and
    the audit alone, and still the scan runner's outputs."""
    P, rounds = 3, 3
    cfg = cfg_of("lease", P)
    client = workload.compile_plan(workload.plan_from_dict({
        "name": "short", "peers": P, "phases": [{"rounds": rounds, "append": 1}]}), G)
    st0, hl0 = settled(cfg)
    run = runner_mod.make_runner(cfg, (client,), split=True, k=4)
    assert run.n_blocks == 0 and run.block_args == [] and run.tail_jit is not None
    split = run(*fresh(st0, hl0))
    scan = runner_mod.make_runner(cfg, (client,))(*fresh(st0, hl0))
    assert int(split[9]) == 0
    for a, b in zip(jax.tree.leaves(scan[:9]), jax.tree.leaves(split[:9])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def primitives(jaxpr):
    """Primitive names of a jaxpr's own equations and of every jaxpr inside."""
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += primitives(sub)
    return out


def counting_jit(monkeypatch, dispatched):
    """Patch `jax.jit` so that every program built counts its calls by name."""
    real_jit = jax.jit

    class Counted:
        def __init__(self, fn, **kw):
            self.name, self.jitted = fn.__name__, real_jit(fn, **kw)

        def __call__(self, *args):
            dispatched[self.name] += 1
            return self.jitted(*args)

        def __getattr__(self, attr):
            return getattr(self.jitted, attr)

    monkeypatch.setattr(jax, "jit", Counted)


@pytest.mark.parametrize("plan,rounds", [("bare", 16), ("chaos", 21)], ids=["bare", "chaos-tail"])
def test_a_short_segment_is_one_scan_over_the_blocks_and_one_dispatch(plan, rounds, monkeypatch):
    P = 3
    cfg = cfg_of("lease", P)
    dispatched = collections.Counter()
    counting_jit(monkeypatch, dispatched)
    run = runner_mod.make_runner(cfg, schedules(plan, P, "lease", rounds), split=True, k=K)
    monkeypatch.undo()
    n_blocks, tail = rounds // K, rounds % K
    assert run.n_blocks == n_blocks

    # The program: one scan of length n_blocks at the top (unrolled where it
    # is lowered), the cond in its body; beside it only the tail's scan of
    # the general round.
    st0, hl0 = settled(cfg)
    jaxpr = run.jitted.trace(*fresh(st0, hl0), *run.segment_args).jaxpr.jaxpr
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [n_blocks] + ([tail] if tail else [])
    assert scans[0].params["unroll"] == n_blocks
    body = scans[0].params["jaxpr"].jaxpr
    assert [e.primitive.name for e in body.eqns].count("cond") == 1
    assert "cond" not in [e.primitive.name for e in jaxpr.eqns]
    assert "pallas_call" in primitives(body)  # the fused arm is in there
    # The scanned rows lead with n_blocks; nothing of the program's own grows
    # with anything else.
    tables, loads, row_of = run.segment_args[:3]
    for leaf in jax.tree.leaves((tables, row_of)):
        assert leaf.shape[0] == n_blocks
    assert loads.shape[1:] == (G,) and loads.shape[0] <= n_blocks

    # The call: set-up dispatched the two table programs, a run dispatches
    # the segment program and nothing else...
    assert dict(dispatched) == {"stacked_tables": 1, "tables_run": 1}
    dispatched.clear()
    run(*fresh(st0, hl0))
    assert dict(dispatched) == {"segment_run": 1}
    # ... and on a warm call no line of runner.py touches jnp: an eager op
    # (a zeros carry, a scalar, an add after the program) is a dispatch too.

    class Forbidden:
        def __getattr__(self, attr):
            raise AssertionError(f"runner.py ran jnp.{attr} outside the segment program")

    args = fresh(st0, hl0)
    monkeypatch.setattr(runner_mod, "jnp", Forbidden())
    out = run(*args)
    monkeypatch.undo()
    assert dict(dispatched) == {"segment_run": 2}
    assert len(out) == (12 if plan == "chaos" else 10)


def test_a_long_segment_dispatches_a_program_a_block(monkeypatch):
    """More blocks than `_SEGMENT_MAX_BLOCKS`: no segment program is built
    (unrolled it would compile the general round once a block; rolled it
    costs every fused block, `PERF.md` section 6, PR 53), and a call is the
    block program once a block, the tail's and the audit's."""
    P, rounds = 3, 5 * K + 3
    assert rounds // K > runner_mod._SEGMENT_MAX_BLOCKS
    cfg = cfg_of("lease", P)
    dispatched = collections.Counter()
    counting_jit(monkeypatch, dispatched)
    run = runner_mod.make_runner(cfg, schedules("bare", P, "lease", rounds), split=True, k=K)
    monkeypatch.undo()
    assert run.jitted is None and run.segment_args is None
    assert dict(dispatched) == {"tables_run": 1}
    dispatched.clear()
    st0, hl0 = settled(cfg)
    out = run(*fresh(st0, hl0))
    assert dict(dispatched) == {"block_run": rounds // K, "tail_run": 1, "_tail_audit": 1}
    assert 0 < int(out[9]) < rounds * G
