"""What the program names in a trace (ISSUE 26; raft_tpu/profiling.py).

Layers:
  * host spans: a traced `ClusterSim.run_reads` call yields the span tree
    `raft.run_reads` > prepare | dispatch (> `raft.runner.blocks`) | report
    (> download) on one thread, the report span closed with the returned
    report's counts; a call with no trace running returns the same report;
  * the catalogue is closed both ways: every span, scope and kernel name
    used in raft_tpu/ is in profiling's catalogue, and every catalogue
    name is used;
  * device names: each scope is in the debug text of a lowered program
    that runs it, each pallas_call carries its kernel name;
  * the counts added beside them: `leaderless_group_rounds`,
    `appends_offered` / `appends_dropped` and `recover_hist` against a
    numpy replay of the per-round leaderless plane under a crash plan, and
    the histogram's invariants.
"""

import ast
import glob
import os
import re
import shutil

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from raft_tpu import profiling
from raft_tpu.multiraft import ClusterSim, SimConfig, sim
from raft_tpu.multiraft import chaos, kernels, pallas_step, reconfig, workload
from raft_tpu.multiraft import runner as runner_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
G, P = 8, 3
BOOT = 40
ROUNDS = 48


def damped_cfg(**kw):
    kw = {"lease_read": True, **kw}
    return SimConfig(
        G, P, election_tick=10, heartbeat_tick=2, check_quorum=True,
        pre_vote=True, collect_health=True, **kw,
    )


def client_plan():
    return workload.plan_from_dict({"name": "t", "peers": P, "seed": 1, "phases": [
        {"rounds": ROUNDS, "append": 1, "read_every": 2, "read_mode": "lease"},
    ]})


def crash_plan():
    """Every peer down in turn: whoever leads is lost once."""
    return chaos.plan_from_dict({"name": "c", "peers": P, "phases": [
        {"rounds": 4},
        {"rounds": 14, "crash": [1]},
        {"rounds": 14, "crash": [2]},
        {"rounds": 16, "crash": [3]},
    ]})


def booted():
    s = ClusterSim(damped_cfg())
    s.run_compiled(BOOT)
    s.reset_health()
    return s


# --- host spans ---------------------------------------------------------------


def read_spans(trace_dir):
    """[(name, start_ns, end_ns, stats)] of the `raft.` spans of a capture,
    with the thread line each is on."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(max(paths, key=os.path.getmtime)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(profiling.SPAN_PREFIX):
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                dict(ev.stats), line.name))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def traced_calls(tmp_path, split, n_calls=2):
    s = booted()
    plan = client_plan()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    shutil.rmtree(tmp_path, ignore_errors=True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        reports = [s.run_reads(plan, split=split) for _ in range(n_calls)]
    finally:
        jax.profiler.stop_trace()
    return reports, read_spans(str(tmp_path))


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("split", [False, True], ids=["scan", "split"])
def test_traced_call_yields_the_span_tree(tmp_path, split):
    reports, spans = traced_calls(tmp_path, split)
    assert len({s[4] for s in spans}) == 1, "all spans on the calling thread"
    calls = [s for s in spans if s[0] == "raft.run_reads"]
    assert [c[3]["call"] for c in calls] == [1, 2]
    for call, report in zip(calls, reports):
        assert call[3]["rounds"] == ROUNDS and call[3]["groups"] == G
        assert call[3]["loss_draw"] == 0, "no chaos plan, no loss draw"
        kids = [s for s in spans if s is not call and inside(s, call)]
        by_name = {s[0]: s for s in kids}
        want = {"raft.run_reads.prepare", "raft.run_reads.dispatch",
                "raft.run_reads.report", "raft.run_reads.download"}
        if split:
            want.add("raft.runner.blocks")
        assert set(by_name) == want
        order = [by_name[n] for n in ("raft.run_reads.prepare",
                                      "raft.run_reads.dispatch",
                                      "raft.run_reads.report")]
        for a, b in zip(order, order[1:]):
            assert a[2] <= b[1], "prepare, dispatch, report do not overlap"
        assert inside(by_name["raft.run_reads.download"],
                      by_name["raft.run_reads.report"])
        if split:
            blocks = by_name["raft.runner.blocks"]
            assert inside(blocks, by_name["raft.run_reads.dispatch"])
            assert blocks[3] == {"blocks": ROUNDS // 8, "tail": ROUNDS % 8,
                                 "chaos": 0, "blocks_faulted": 0}
        # The report span is closed with the report's counts, and `call`
        # ties it to its call.
        stats = by_name["raft.run_reads.report"][3]
        assert stats.pop("call") == call[3]["call"]
        assert stats.pop("groups") == G
        assert stats == workload.report_counts(report)
    assert calls[0][2] <= calls[1][1]


def test_prepare_span_marks_the_runner_cache_miss(tmp_path):
    _reports, spans = traced_calls(tmp_path, split=False)
    prepares = [s[3] for s in spans if s[0] == "raft.run_reads.prepare"]
    assert prepares == [{"miss": 1}, {}]


@pytest.mark.parametrize("loss_all, want", [(0.0, 0), (0.25, 1)],
                         ids=["crash-only", "lossy"])
def test_run_reads_span_says_whether_its_rounds_draw_the_loss_sample(
    tmp_path, loss_all, want
):
    """`loss_draw` of the `raft.run_reads` span is the chaos plan's compiled
    fact (`chaos.CompiledChaos.lossless`), on the miss and on the cache hit."""
    doc = {"name": "c", "peers": P, "phases": [
        {"rounds": 4}, {"rounds": ROUNDS - 4, "crash": [1], "loss_all": loss_all}]}
    plan, cplan = client_plan(), chaos.plan_from_dict(doc)
    assert chaos.compile_plan(cplan, G).lossless is (not want)
    s = booted()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            s.run_reads(plan, cplan)
    finally:
        jax.profiler.stop_trace()
    calls = [sp[3] for sp in read_spans(str(tmp_path)) if sp[0] == "raft.run_reads"]
    assert [c["loss_draw"] for c in calls] == [want, want]
    assert [c["call"] for c in calls] == [1, 2]


def test_split_call_under_a_chaos_plan_says_so_in_its_spans(tmp_path):
    """ISSUE 51: `raft.run_reads` carries `split` and `chaos`,
    `raft.runner.blocks` `chaos` and `blocks_faulted` (the blocks whose
    chaos phase has a fault: static), and the report span the split run's
    block counts and the guard's refusals by term."""
    plan, cplan = client_plan(), crash_plan()
    s = booted()
    jax.profiler.start_trace(str(tmp_path))
    try:
        report = s.run_reads(plan, cplan, split=True, split_k=4)
    finally:
        jax.profiler.stop_trace()
    spans = {sp[0]: sp[3] for sp in read_spans(str(tmp_path))}
    assert spans["raft.run_reads"]["split"] == 1
    assert spans["raft.run_reads"]["chaos"] == 1
    # crash_plan: 4 healthy rounds, then a store down to the end.
    assert spans["raft.runner.blocks"] == {
        "blocks": ROUNDS // 4, "tail": 0, "chaos": 1, "blocks_faulted": ROUNDS // 4 - 1}
    stats = spans["raft.run_reads.report"]
    assert stats["split_blocks"] == ROUNDS // 4
    assert stats["split_blocks_faulted"] == ROUNDS // 4 - 1
    assert stats["split_blocks_healthy"] == 1
    assert stats["split_blocks_healthy_refused"] == report["split_blocks_healthy_refused"]
    for term in workload.GUARD_TERMS:
        assert stats[f"guard_refusals.{term}"] == report["guard_refusals"][term]


def test_untraced_report_equals_traced(tmp_path):
    plan, cplan = client_plan(), crash_plan()
    plain = booted().run_reads(plan, cplan)
    s = booted()
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = s.run_reads(plan, cplan)
    finally:
        jax.profiler.stop_trace()
    assert traced == plain
    assert plain["reelections"] > 0, "the plan must lose leaders"


def test_report_counts_are_every_integer_of_the_report():
    report = booted().run_reads(client_plan())
    counts = workload.report_counts(report)
    assert all(type(v) is int for v in counts.values())
    flat = {k for k, v in report.items() if type(v) is int}
    assert set(counts) == flat | {f"safety.{k}" for k in report["safety"]}
    assert "recover_hist" not in counts and "mttr_rounds" not in counts


# --- the catalogue is closed both ways ----------------------------------------

CALLS = {"span": "SPANS", "scope": "SCOPES", "at": "SCOPES", "kernel": "KERNELS"}


def names_used():
    """{catalogue: {name: [file:line]}} of the literal first arguments of
    profiling.span / scope / kernel and Sections.at calls in raft_tpu/."""
    used = {c: {} for c in set(CALLS.values())}
    for path in glob.glob(os.path.join(ROOT, "raft_tpu", "**", "*.py"), recursive=True):
        if os.path.basename(path) == "profiling.py":
            continue
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)):
                continue
            if node.func.attr not in CALLS or not node.args:
                continue
            recv = node.func.value
            is_ours = (
                isinstance(recv, ast.Name) and recv.id in ("profiling", "sec", "guard")
            )
            if not is_ours:
                continue
            arg = node.args[0]
            assert isinstance(arg, ast.Constant) and isinstance(arg.value, str), (
                f"{path}:{node.lineno}: a profiling name must be a literal")
            used[CALLS[node.func.attr]].setdefault(arg.value, []).append(
                f"{os.path.relpath(path, ROOT)}:{node.lineno}")
    return used


@pytest.mark.parametrize("catalogue", ["SPANS", "SCOPES", "KERNELS"])
def test_catalogue_is_closed_both_ways(catalogue):
    known = getattr(profiling, catalogue)
    used = names_used()[catalogue]
    assert not set(used) - set(known), "used but not in the catalogue"
    assert not set(known) - set(used), "in the catalogue but used nowhere"
    assert all(isinstance(v, str) and v for v in known.values())


def test_no_name_bypasses_the_catalogue():
    """raft_tpu/ spells no TraceAnnotation, named_scope or pallas name= of
    its own: profiling.py is the one home."""
    for path in glob.glob(os.path.join(ROOT, "raft_tpu", "**", "*.py"), recursive=True):
        if os.path.basename(path) == "profiling.py":
            continue
        with open(path, encoding="utf-8") as f:
            text = f.read()
        for banned in ("TraceAnnotation(", "named_scope(", "StepTraceAnnotation("):
            assert banned not in text, f"{path} uses {banned}"
    with pytest.raises(KeyError):
        profiling.span("raft.not_in_the_catalogue")
    with pytest.raises(KeyError):
        profiling.scope("not_in_the_catalogue")
    with pytest.raises(KeyError):
        profiling.kernel("not_in_the_catalogue")
    assert not hasattr(profiling, "RoundTimer")
    assert not hasattr(profiling, "annotate")


# --- names on the device --------------------------------------------------------


@pytest.fixture(scope="module")
def lowered_text():
    """Debug text of the lowered programs that, between them, run every
    scope: the split block program of the damped fleet (guard, both arms,
    the damped round with reads and health, the safety audit), the
    undamped round without a link plane and, serving a read, with one, the
    percentile fold, the reconfig scan and the client-workload scan
    without and with a chaos plan."""
    cfg = damped_cfg()
    client = workload.compile_plan(client_plan(), G)
    run = runner_mod.make_runner(cfg, (client,), split=True, k=8)
    st = sim.init_state(cfg)
    rst = reconfig.init_reconfig_state(st)
    zeros = lambda n: jnp.zeros((n,), jnp.int32)  # noqa: E731
    args = (
        st, sim.init_health(cfg), rst, zeros(chaos.N_CHAOS_STATS),
        zeros(reconfig.N_RECONFIG_STATS), zeros(kernels.N_SAFETY),
        workload.init_read_carry(G), zeros(workload.N_READ_STATS),
        zeros(workload.N_LAT_BUCKETS), jnp.int32(0), *run.block_args[0],
        *run.schedule_args,
    )
    texts = {"block": run.fused_jit.lower(*args).as_text(debug_info=True)}
    plain = SimConfig(G, P, collect_health=True)
    pst = sim.init_state(plain)
    crashed = jnp.zeros((P, G), bool)
    app = jnp.ones((G,), jnp.int32)
    step = jax.jit(lambda s, c, a: sim.step(plain, s, c, a))
    texts["plain"] = step.lower(pst, crashed, app).as_text(debug_info=True)
    linked = jax.jit(lambda s, c, a, l, rd: sim.step(
        plain, s, c, a, link=l, read_propose=rd))
    texts["linked"] = linked.lower(
        pst, crashed, app, jnp.ones((P, P, G), bool), app
    ).as_text(debug_info=True)
    # Damping on, lease reads off: the ReadIndex round on the damped body,
    # its per-peer gate in the arm of the rounds that can need it.
    safe = damped_cfg(lease_read=False)
    texts["readindex"] = jax.jit(lambda s, c, a, l, rd: sim.step(
        safe, s, c, a, link=l, read_propose=rd)).lower(
        sim.init_state(safe), crashed, app, jnp.ones((P, P, G), bool), app
    ).as_text(debug_info=True)
    texts["latency"] = jax.jit(workload.latency_percentiles).lower(
        zeros(workload.N_LAT_BUCKETS)).as_text(debug_info=True)
    # The op protocol with K = 6 op slots (the block program's no-op
    # schedule has one): a voter leaves and comes back, twice.
    churn = reconfig.compile_plan(reconfig.plan_from_dict({
        "name": "k6", "peers": P, "phases": [
            {"rounds": 4, "op": op} for p in (3, 2) for op in (
                {"remove_voter": p}, {"add_learner": p},
                {"promote_learner": p})
        ]}), G)
    assert churn.tgt_voter.shape == (6, P, G)
    scan = runner_mod.make_runner(cfg, (churn,))
    texts["reconfig"] = scan.jitted.lower(
        st, sim.init_health(cfg), rst, *scan.schedule_args
    ).as_text(debug_info=True)
    # The scan program WITH a client plan (the shape `.outage` and
    # `.rebalance` run): the read fold is in the scan's body.
    cscan = runner_mod.make_runner(cfg, (client,))
    texts["client_scan"] = cscan.jitted.lower(
        st, sim.init_health(cfg), rst, workload.init_read_carry(G),
        *cscan.schedule_args,
    ).as_text(debug_info=True)
    # ... and under a chaos plan (the shape `.outage` runs): the round's
    # masks are cut out of the chaos schedule in the scan's body.
    xscan = runner_mod.make_runner(
        cfg, (chaos.compile_plan(crash_plan(), G), client))
    texts["client_chaos_scan"] = xscan.jitted.lower(
        st, sim.init_health(cfg), rst, workload.init_read_carry(G),
        *xscan.schedule_args,
    ).as_text(debug_info=True)
    # ... as ClusterSim.run_reads calls it for a fleet that boots with
    # learners: their lag is counted beside the read carry.
    # The split block program under a chaos plan (ISSUE 51): the block's
    # planes and the guard's refusal counts.
    xrun = runner_mod.make_runner(
        cfg, (client, chaos.compile_plan(crash_plan(), G)), split=True, k=8)
    texts["chaos_block"] = xrun.fused_jit.lower(
        *args[:10], jnp.int32(0),
        jnp.zeros((len(workload.GUARD_TERMS),), jnp.int32),
        *xrun.block_args[0], *xrun.schedule_args,
    ).as_text(debug_info=True)
    texts["learner_scan"] = xscan.jitted.lower(
        st, sim.init_health(cfg), rst,
        workload.LearnerLagCarry(workload.init_read_carry(G), jnp.int32(0)),
        *xscan.schedule_args,
    ).as_text(debug_info=True)
    return texts


WHERE = {"round": "plain", "round.linked": "linked", "read_latency": "latency",
         "damped.read_holders": "readindex",
         "runner.chaos_masks": "client_chaos_scan",
         "runner.learner_lag": "learner_scan",
         "runner.block_planes": "chaos_block",
         "runner.guard_refusals": "chaos_block",
         **{s: "linked" for s in profiling.SCOPES if s.startswith("linked.")}}


@pytest.mark.parametrize("scope", sorted(profiling.SCOPES))
def test_scope_is_in_the_lowered_program(lowered_text, scope):
    text = lowered_text[WHERE.get(scope, "block")]
    # A whole component of a name stack: `"jit(f)/.../<scope>/op"`.
    assert re.search(rf'["/]{re.escape(scope)}["/]', text), scope


@pytest.mark.parametrize(
    "banned", ["gather", "sort", "take_along_axis", "dynamic_slice", "transpose"]
)
def test_quorum_commit_is_static_slices_and_selects(lowered_text, banned):
    """The quorum position is P static slices, a compare-exchange network
    and P selects (kernels._quorum_of_rows): no op under `quorum_commit` in
    any lowered program is a sort, a gather or a relayout.  The v5e ran the
    sort + take_along_axis form at 58 of a general round's 98 ms (PERF.md
    §6, PR 27); a CPU run would not notice it coming back.  A jitted
    helper's ops carry only their own name, so the name stack to read is
    the call's: `.../quorum_commit/jit(take_along_axis)`."""
    tails = []
    for text in lowered_text.values():
        tails += re.findall(r'"[^"]*/quorum_commit/([^"]*)"', text)
    assert any(t in ("max", "min") for t in tails), "the network is there"
    assert re.search(r'"[^"]*/round\.damped/[^"]*/quorum_commit/max"',
                     lowered_text["block"]), "and in the damped round"
    hits = sorted({t for t in tails if banned in t})
    assert not hits, hits


@pytest.mark.parametrize("program", ["block", "reconfig"], ids=["K1", "K6"])
@pytest.mark.parametrize(
    "banned", ["gather", "sort", "take_along_axis", "dynamic_slice", "transpose"]
)
def test_op_gather_is_static_slices_and_selects(lowered_text, program, banned):
    """The op protocol's row look-ups (reconfig._gather_peer / _gather_op)
    are static slices and N - 1 selects (kernels.select_row), with one op
    slot (every fixture without a reconfig plan, `.serve`) and with six: no
    op under `op_gather` is a gather or a relayout.  The v5e ran the
    take_along_axis form at 29 of a general round's 35.7 ms (PERF.md §6,
    PR 34); a CPU run would not notice it coming back."""
    text = lowered_text[program]
    tails = re.findall(r'"[^"]*/op_gather/([^"]*)"', text)
    assert "select_n" in tails, "the selects are there"
    hits = sorted({t for t in tails if banned in t})
    assert not hits, hits


@pytest.mark.parametrize("program", ["block", "client_scan"], ids=["split", "scan"])
@pytest.mark.parametrize(
    "banned", ["scatter", "gather", "dynamic_update_slice", "while"]
)
def test_read_fold_is_a_compare_and_reduce(lowered_text, program, banned):
    """The round's served reads go into the latency histogram
    (workload.fold_latencies) by a compare against the static buckets and
    a sum over G, in the split program's general arm and in the scan's
    body: no op under `read_fold` is a scatter, a gather or a loop.  The
    v5e ran the `lat_hist.at[lat].add(served)` form at 0.875 of a general
    round's 6.5 ms at 100k x 5 and 8.75 of 54.5 at 1M x 3, the largest op
    of every general round (PERF.md §6, PR 36); a CPU run would not notice
    it coming back."""
    text = lowered_text[program]
    tails = re.findall(r'"(?:[^"]*/)?read_fold/([^"]*)"', text)
    assert "reduce_sum" in tails and "eq" in tails, "the compare and the sum are there"
    hits = sorted({t for t in tails if banned in t})
    assert not hits, hits


@pytest.mark.parametrize("kernel,kw", [
    ("raft_steady", {}),
    ("raft_steady_chaos", {"with_chaos": True}),
    ("raft_steady_damped", {"damped": True}),
])
def test_pallas_call_carries_its_name(kernel, kw):
    damped = kw.pop("damped", False)
    cfg = damped_cfg() if damped else SimConfig(G, P, collect_health=True)
    fn = pallas_step.steady_round(cfg, rounds=2, with_health=True, **kw)
    st = sim.init_state(cfg)
    args = [st, jnp.zeros((P, G), bool), jnp.ones((G,), jnp.int32)]
    if kw.get("with_chaos"):
        args += [jnp.zeros((P, P, G), jnp.int32), jnp.int32(0)]
    args.append(sim.init_health(cfg))
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    assert names == [kernel]


# --- the counts, against a numpy replay ------------------------------------------


@pytest.fixture(scope="module")
def crash_run():
    """(report of run_reads under the crash plan, the leaderless plane
    [ROUNDS + 1, G] of the same scenario stepped round by round, the
    append schedule [ROUNDS, G])."""
    plan, cplan = client_plan(), crash_plan()
    report = booted().run_reads(plan, cplan)

    s = booted()
    cfg = s.cfg
    client = workload.compile_plan(plan, G)
    cc = chaos.compile_plan(cplan, G)
    body = jax.jit(runner_mod._runner_body(
        cfg, reconfig.empty_reconfig_schedule(ROUNDS, P, G), cc,
        client=client))
    zeros = lambda n: jnp.zeros((n,), jnp.int32)  # noqa: E731
    carry = (
        s.state, s._health, reconfig.init_reconfig_state(s.state),
        zeros(chaos.N_CHAOS_STATS), zeros(reconfig.N_RECONFIG_STATS),
        zeros(kernels.N_SAFETY), workload.init_read_carry(G),
        zeros(workload.N_READ_STATS), zeros(workload.N_LAT_BUCKETS),
    )
    planes = [np.asarray(carry[1].planes[kernels.HP_LEADERLESS])]
    for r in range(ROUNDS):
        carry, _ = body(carry, jnp.int32(r))
        planes.append(np.asarray(carry[1].planes[kernels.HP_LEADERLESS]))
    host = workload.HostClientSchedule(plan, G)
    append = np.stack([host.masks(r)[2] for r in range(ROUNDS)])
    return report, np.stack(planes), append


def test_counts_equal_a_numpy_replay_of_the_leaderless_plane(crash_run):
    report, planes, append = crash_run
    prev, new = planes[:-1], planes[1:]
    healed = (prev > 0) & (new == 0)
    assert report["leaderless_group_rounds"] == int((new > 0).sum())
    assert report["reelections"] == int(healed.sum())
    assert report["healed_rounds"] == int(prev[healed].sum())
    assert report["max_leaderless_streak"] == int(new.max())
    hist = np.bincount(
        np.minimum(prev[healed], chaos.RECOVER_CAP),
        minlength=chaos.N_RECOVER_BUCKETS,
    )
    assert report["recover_hist"] == hist.tolist()
    # Under crashes alone (every link up) a group takes the round's batch
    # exactly when it ends the round with a leader.
    offered = append > 0
    assert report["appends_offered"] == int(offered.sum())
    assert report["appends_dropped"] == int((offered & (new > 0)).sum())
    assert report["appends_dropped"] > 0
    lengths = np.sort(prev[healed])
    for q in (50, 90, 99):
        assert report[f"recover_p{q}_rounds"] == workload.host_latency_percentile(
            lengths.tolist(), q)


def test_recover_hist_invariants(crash_run):
    report, _planes, _append = crash_run
    hist = np.asarray(report["recover_hist"])
    assert len(hist) == chaos.N_RECOVER_BUCKETS
    assert hist.sum() == report["reelections"] > 0
    assert hist[-1] == 0, "no episode passed the cap"
    assert (np.arange(len(hist)) * hist).sum() == report["healed_rounds"]
    assert report["leaderless_group_rounds"] >= report["healed_rounds"]
    assert report["recover_p50_rounds"] <= report["recover_p90_rounds"] \
        <= report["recover_p99_rounds"] <= report["max_leaderless_streak"]


def test_recover_hist_caps_long_episodes():
    stats = jnp.zeros((chaos.N_CHAOS_STATS,), jnp.int32)
    prev = jnp.asarray([0, 3, chaos.RECOVER_CAP, chaos.RECOVER_CAP + 9, 5], jnp.int32)
    new = jnp.asarray([0, 0, 0, 0, 6], jnp.int32)
    out = np.asarray(chaos.update_chaos_stats(stats, prev, new))
    hist = np.asarray(chaos.recover_hist(out))
    assert hist[3] == 1 and hist[-1] == 2 and hist.sum() == 3
    assert out[chaos.CS_REELECTIONS] == 3
    assert out[chaos.CS_HEALED_ROUNDS] == 3 + 2 * chaos.RECOVER_CAP + 9
    assert out[chaos.CS_LEADERLESS_ROUNDS] == 1
    assert out[chaos.CS_MAX_STREAK] == 6


def test_fused_block_folds_its_offers_closed_form():
    """The split runner's stats equal the scan's slot for slot — the fused
    arm's `offered x k, dropped 0` is what k general rounds count."""
    plan = workload.plan_from_dict({"name": "w", "peers": P, "seed": 3, "phases": [
        {"rounds": 32, "append": 1}]})
    scan = booted().run_reads(plan)
    split = booted().run_reads(plan, split=True)
    assert split["fused_frac"] == 1.0
    for key in ("appends_offered", "appends_dropped", "leaderless_group_rounds",
                "recover_hist", "reelections"):
        assert split[key] == scan[key], key
    assert scan["appends_offered"] == 32 * G and scan["appends_dropped"] == 0


def test_record_reads_passes_the_new_counts_on():
    from raft_tpu.multiraft.health import HealthMonitor

    events = []

    class Sink:
        def trace(self, name, **fields):
            events.append((name, fields))

    mon = HealthMonitor(metrics=Sink())
    report = booted().run_reads(client_plan(), crash_plan())
    mon.record_reads(report)
    (name, fields), = [e for e in events if e[0] == "reads.scenario"]
    for key in ("leaderless_group_rounds", "appends_offered", "appends_dropped",
                "recover_p50_rounds", "recover_p90_rounds", "recover_p99_rounds"):
        assert fields[key] == report[key]
