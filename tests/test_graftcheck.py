"""Unit tests for tools/graftcheck: every GC rule has known-bad and
known-good fixtures, plus the allow-marker escape hatch and its
justification/typo enforcement (GC000).

Fixtures are written under tmp_path with repo-shaped relative paths because
rule scoping matches on path suffixes (docs/STATIC_ANALYSIS.md)."""

import textwrap

import pytest

from tools.graftcheck import Context, all_rules, run_paths


# Deliberately-bad fixture content is assembled at runtime: graftcheck scans
# THIS file too (it is under tests/), and must not trip on literals that
# only exist to be written into tmp fixtures.
MARK = "# graftcheck: " + "allow-"


def cite(name, rng):
    return name + ":" + rng


def run_on(tmp_path, relpath, source, tests_root=None):
    f = tmp_path / relpath
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    ctx = Context(
        repo_root=tmp_path, tests_root=tests_root, reference_root=None
    )
    return run_paths([str(f)], all_rules(), ctx)


def ids(violations):
    return [v.rule_id for v in violations]


# --- GC001 no-implicit-dtype ---


def test_gc001_flags_missing_dtype(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/mod.py",
        """\
        import jax.numpy as jnp
        x = jnp.zeros((4, 4))
        y = jnp.arange(8)
        """,
    )
    assert ids(vs) == ["GC001", "GC001"]


def test_gc001_accepts_explicit_dtype(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/mod.py",
        """\
        import jax.numpy as jnp
        a = jnp.zeros((4,), jnp.int32)
        b = jnp.ones((4,), dtype=bool)
        c = jnp.full((4,), 7, jnp.int32)
        d = jnp.arange(8, dtype=jnp.uint32)
        e = jnp.asarray([1, 2], dtype=jnp.int32)
        """,
    )
    assert vs == []


def test_gc001_out_of_scope_module_is_ignored(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/scalar_only.py",
        """\
        import jax.numpy as jnp
        x = jnp.zeros((4,))
        """,
    )
    assert vs == []


# --- GC002 no-host-sync-in-jit ---


def test_gc002_flags_host_sync_primitives(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/sim.py",
        """\
        import jax
        import numpy as np

        def step(st):
            vals = jax.device_get(st)
            n = st.sum().item()
            arr = np.asarray(st)
            return int(st[0])
        """,
    )
    assert ids(vs) == ["GC002"] * 4


def test_gc002_class_bodies_may_coerce_but_not_sync(tmp_path):
    # int() on downloaded values in a host wrapper class is fine; a raw
    # device_get still is not (it needs the allow marker).
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/sim.py",
        """\
        import jax

        class HostWrapper:
            def drain(self, vals):
                return int(vals[0])

            def bad(self, x):
                return jax.device_get(x)
        """,
    )
    assert ids(vs) == ["GC002"]
    assert "device_get" in vs[0].message


# --- GC003 no-python-branch-on-traced ---


def test_gc003_flags_branch_on_traced(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/sim.py",
        '''\
        """doc"""

        def f(x):
            if x > 0:
                return x
            assert x.sum() == 0
            while x:
                pass
        ''',
    )
    assert ids(vs) == ["GC003"] * 3


def test_gc003_static_tests_pass(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/sim.py",
        '''\
        """doc"""
        BLOCK = 8

        def f(cfg, x, rounds: int, group_ids=None):
            if group_ids is None:
                pass
            if cfg.heartbeat_tick == 1:
                pass
            n = x.shape[0]
            if n > BLOCK or rounds > 2:
                pass
            for p in range(n):
                if p % 2 == 0:
                    pass
            assert rounds >= 1
        ''',
    )
    assert vs == []


def test_gc003_rebinding_drops_staticness(tmp_path):
    # Tuple-unpack, AugAssign, and non-range for loops rebind names to
    # traced values; branches on them must flag.
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/sim.py",
        '''\
        """doc"""

        def f(x):
            n = 1
            n, m = x.nonzero()
            if n:
                pass
            k = 0
            k += x.sum()
            while k:
                pass
            for v in x:
                if v > 0:
                    pass
        ''',
    )
    assert ids(vs) == ["GC003"] * 3


def test_gc003_item_with_args_still_flags_gc002(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/kernels.py",
        '"""majority_of <-> util"""\n\ndef majority_of(x):\n    return x.item(0)\n',
    )
    assert "GC002" in ids(vs)


# --- GC004 metrics-guarded ---


def test_gc004_flags_unguarded_metrics_call(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/raft.py",
        """\
        class Raft:
            def send(self, m):
                self.metrics.on_send(m)
        """,
    )
    assert ids(vs) == ["GC004"]


def test_gc004_guard_idioms_pass(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/raft.py",
        """\
        class Raft:
            def direct(self, m):
                if self.metrics is not None:
                    self.metrics.on_send(m)

            def nested(self, m):
                if m.kind == 1:
                    if self.metrics is not None:
                        self.metrics.on_beat()

            def alias(self):
                mm = self.metrics
                if mm is not None:
                    mm.on_tick(n=1)

            def early_return(self):
                if self.metrics is None:
                    return {}
                return self.metrics.registry.snapshot()
        """,
    )
    assert vs == []


def test_gc004_aliased_unguarded_is_flagged(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/driver.py",
        """\
        class MultiRaft:
            def tick(self):
                m = self.metrics
                m.on_driver_tick(n_active=1)
        """,
    )
    assert ids(vs) == ["GC004"]


# --- GC005 citation-check ---


def test_gc005_flags_malformed_citation(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/anywhere.py",
        f"# see {cite('majority.rs', '124-70')} for the scan\n"
        f"# and {cite('raft.rs', '0-5')} for ticks\n",
    )
    assert ids(vs) == ["GC005", "GC005"]


def test_gc005_well_formed_citation_passes(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/anywhere.py",
        """\
        # see majority.rs:70-124 and joint.rs:47
        """,
    )
    assert vs == []


def test_gc005_repo_local_citation_resolves(tmp_path):
    (tmp_path / "mod.py").write_text("a = 1\nb = 2\nc = 3\n")
    ok = run_on(tmp_path, "raft_tpu/ok.py", "# cites mod.py:1-3\n")
    assert ok == []
    stale = run_on(tmp_path, "raft_tpu/stale.py", "# cites mod.py:2-99\n")
    assert ids(stale) == ["GC005"]
    assert "stale" in stale[0].message


def test_gc005_checks_markdown_too(tmp_path):
    vs = run_on(
        tmp_path, "docs/NOTES.md", f"See {cite('raft.rs', '90-10')}.\n"
    )
    assert ids(vs) == ["GC005"]


# --- GC006 kernel-parity-map ---

_KERNELS_FIXTURE = '''\
"""Map:

  mapped_kernel <-> oracle.fn (reference: x.rs:1-2)
"""

def mapped_kernel(x):
    return x

def unmapped_kernel(x):
    return x

def _private(x):
    return x
'''


def test_gc006_docstring_map_and_test_coverage(tmp_path):
    tests_root = tmp_path / "tests"
    tests_root.mkdir()
    (tests_root / "test_k.py").write_text(
        "def test_mapped():\n    assert mapped_kernel is not None\n"
    )
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/kernels.py",
        _KERNELS_FIXTURE,
        tests_root=tests_root,
    )
    # unmapped_kernel: missing from docstring AND untested; _private exempt.
    assert ids(vs) == ["GC006", "GC006"]
    assert all("unmapped_kernel" in v.message for v in vs)


def test_gc006_fully_mapped_and_tested_passes(tmp_path):
    tests_root = tmp_path / "tests"
    tests_root.mkdir()
    (tests_root / "test_k.py").write_text(
        "def test_it():\n    assert kernels.mapped_kernel(1) == 1\n"
    )
    fixture = '"""Map: mapped_kernel <-> oracle"""\n\ndef mapped_kernel(x):\n    return x\n'
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/kernels.py",
        fixture,
        tests_root=tests_root,
    )
    assert vs == []


def test_gc006_comment_mention_does_not_count_as_tested(tmp_path):
    # A kernel named only in a comment/docstring is NOT exercised; the
    # coverage scan looks at code identifiers, not text.
    tests_root = tmp_path / "tests"
    tests_root.mkdir()
    (tests_root / "test_k.py").write_text(
        '"""talks about mapped_kernel"""\n# uses mapped_kernel\n'
    )
    fixture = '"""Map: mapped_kernel <-> oracle"""\n\ndef mapped_kernel(x):\n    return x\n'
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/kernels.py",
        fixture,
        tests_root=tests_root,
    )
    assert ids(vs) == ["GC006"]
    assert "not exercised" in vs[0].message


# --- allow markers + GC000 meta enforcement ---


def test_allow_marker_same_line_suppresses(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/mod.py",
        """\
        import jax.numpy as jnp
        x = jnp.zeros((4,))  # graftcheck: allow-no-implicit-dtype — fixture wants weak typing
        """,
    )
    assert vs == []


def test_allow_marker_standalone_covers_next_code_line(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/sim.py",
        """\
        import jax

        def drain(c):
            # graftcheck: allow-no-host-sync-in-jit — deliberate host-side
            # drain, runs outside the jitted step
            return jax.device_get(c)
        """,
    )
    assert vs == []


def test_allow_marker_by_rule_id(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/mod.py",
        """\
        import jax.numpy as jnp
        x = jnp.zeros((4,))  # graftcheck: allow-GC001 — fixture
        """,
    )
    assert vs == []


def test_allow_marker_without_justification_is_gc000(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/mod.py",
        "import jax.numpy as jnp\n"
        f"x = jnp.zeros((4,))  {MARK}no-implicit-dtype\n",
    )
    # The unjustified marker suppresses nothing and is itself flagged.
    assert sorted(ids(vs)) == ["GC000", "GC001"]


def test_allow_marker_unknown_rule_is_gc000(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/scalar.py",
        f"{MARK}no-such-rule — because\n",
    )
    assert ids(vs) == ["GC000"]


def test_allow_marker_wrong_rule_does_not_suppress(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/mod.py",
        """\
        import jax.numpy as jnp
        x = jnp.zeros((4,))  # graftcheck: allow-metrics-guarded — wrong rule
        """,
    )
    assert ids(vs) == ["GC001"]


def test_syntax_error_reports_parse_error_not_crash(tmp_path):
    vs = run_on(tmp_path, "raft_tpu/broken.py", "def f(:\n")
    assert ids(vs) == ["GC000"]
    assert vs[0].slug == "parse-error"


# --- PR 3 rule-list extensions: health-plane code paths are in scope ---


def test_gc002_covers_health_module(tmp_path):
    # The HealthMonitor sits on the drain boundary: a device sync creeping
    # into its record path must trip GC002 like any kernel module.
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/health.py",
        """\
        import jax

        class HealthMonitor:
            def record(self, summary):
                return jax.device_get(summary)
        """,
    )
    assert ids(vs) == ["GC002"]


def test_gc004_covers_health_module(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/health.py",
        """\
        class HealthMonitor:
            def record(self, summary):
                self.metrics.on_health_summary(summary)

            def record_guarded(self, summary):
                m = self.metrics
                if m is not None:
                    m.on_health_summary(summary)
        """,
    )
    assert ids(vs) == ["GC004"]


def test_gc003_accepts_health_config_fields(tmp_path):
    # The new SimConfig health fields are compile-time static.
    vs = run_on(
        tmp_path,
        "raft_tpu/multiraft/sim.py",
        """\
        def step(cfg, st):
            if cfg.collect_health:
                w = cfg.health_window
            if cfg.churn_bumps > cfg.health_topk:
                pass
            return st
        """,
    )
    assert ids(vs) == []


# --- PR 4 engine rules (GC007-GC010): cross-module abstract interpretation


from tools.graftcheck.engine import run_engine  # noqa: E402


def run_engine_on(tmp_path, files, with_suite_stub=True):
    """Write a repo-shaped fixture tree and run the engine over it.

    `files` maps repo-relative paths to (dedented) sources.  A stub
    tests/test_sim_parity.py is created by default so GC010's
    suite-must-exist check doesn't fire on fixtures about OTHER rules."""
    if with_suite_stub and "tests/test_sim_parity.py" not in files:
        files = dict(files)
        files["tests/test_sim_parity.py"] = "# parity suite stub\n"
    for rel, src in files.items():
        f = tmp_path / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text(textwrap.dedent(src))
    tests_root = tmp_path / "tests"
    ctx = Context(
        repo_root=tmp_path,
        tests_root=tests_root if tests_root.is_dir() else None,
        reference_root=None,
    )
    return run_engine([str(tmp_path / "raft_tpu")], ctx)


# --- GC007 shape-dtype ---


def test_gc007_bare_reduction_flags(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/kernels.py": '''\
            """m <-> o"""
            import jax.numpy as jnp

            def m(x):  # gc: int32[P, G]
                return jnp.sum(x, axis=0)
            ''',
        },
    )
    gc7 = [v for v in vs if v.rule_id == "GC007"]
    assert len(gc7) == 1
    assert "dtype=jnp.int32" in gc7[0].message


def test_gc007_reduction_with_dtype_or_astype_passes(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/kernels.py": '''\
            """a <-> o; b <-> o; c <-> o"""
            import jax.numpy as jnp

            def a(x):  # gc: int32[P, G]
                return jnp.sum(x, axis=0, dtype=jnp.int32)

            def b(x):  # gc: bool[P, G]
                return jnp.sum(x, axis=0).astype(jnp.int32)

            def c(x):  # gc: int32[P, G]
                return jnp.sum(x, axis=0) == 1
            ''',
        },
    )
    assert [v.rule_id for v in vs if v.rule_id == "GC007"] == []


def test_gc007_signed_unsigned_mix_flags(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/kernels.py": '''\
            """m <-> o"""
            import jax.numpy as jnp

            def m(
                x,  # gc: int32[G]
                y,  # gc: uint32[G]
            ):
                return x + y
            ''',
        },
    )
    gc7 = [v for v in vs if v.rule_id == "GC007"]
    assert len(gc7) == 1 and "int64" in gc7[0].message


def test_gc007_bool_scalar_arithmetic_flags(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/kernels.py": '''\
            """m <-> o"""
            import jax.numpy as jnp

            def m(x):  # gc: bool[G]
                return x + 1
            ''',
        },
    )
    gc7 = [v for v in vs if v.rule_id == "GC007"]
    assert len(gc7) == 1 and "bool array" in gc7[0].message


def test_gc007_call_boundary_dtype_and_rank(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/kernels.py": '''\
            """helper <-> o; bad_dtype <-> o; bad_rank <-> o; ok <-> o"""
            import jax.numpy as jnp

            def helper(x):  # gc: int32[G]
                return x

            def bad_dtype(y):  # gc: uint32[G]
                return helper(y)

            def bad_rank(y):  # gc: int32[P, G]
                return helper(y)

            def ok(y):  # gc: int32[G]
                return helper(y)
            ''',
        },
    )
    gc7 = [v for v in vs if v.rule_id == "GC007"]
    assert len(gc7) == 2
    assert any("dtype mixing across a call boundary" in v.message for v in gc7)
    assert any("rank drift" in v.message for v in gc7)


def test_gc007_struct_field_mismatch(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/sim.py": '''\
            """doc"""
            from typing import NamedTuple
            import jax.numpy as jnp

            class St(NamedTuple):
                term: jnp.ndarray  # gc: int32[P, G]

            def make(
                x,  # gc: bool[P, G]
                y,  # gc: int32[P, G]
            ):
                bad = St(term=x)
                good = St(term=y)
                return bad, good
            ''',
        },
    )
    gc7 = [v for v in vs if v.rule_id == "GC007"]
    assert len(gc7) == 1 and "St.term" in gc7[0].message


def test_gc007_allow_marker_suppresses(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/kernels.py": (
                '"""m <-> o"""\n'
                "import jax.numpy as jnp\n\n"
                "def m(x):  # gc: int32[P, G]\n"
                f"    return jnp.sum(x, axis=0)  {MARK}GC007 — fixture "
                "wants the widening\n"
            ),
        },
    )
    assert [v.rule_id for v in vs if v.rule_id == "GC007"] == []


# --- GC008 plane-overflow ---

_GC008_KERNELS_OK = '''\
"""zero_health <-> o; update_health <-> o"""
import jax.numpy as jnp

HP_LEADERLESS = 0
HP_SINCE_COMMIT = 1
HP_TERM_BUMPS = 2
HP_VOTE_SPLITS = 3
N_HEALTH_PLANES = 4

def zero_health(n_groups: int):
    return jnp.zeros((N_HEALTH_PLANES, n_groups), jnp.int32)

def update_health(planes, window_pos, window: int, has_leader,
                  commit_advanced, term_bump, vote_split):
    leaderless = jnp.where(has_leader, 0, planes[HP_LEADERLESS] + 1)
    since = jnp.where(commit_advanced, 0, planes[HP_SINCE_COMMIT] + 1)
    fresh = window_pos == 0
    bumps = jnp.where(fresh, 0, planes[HP_TERM_BUMPS]) + term_bump
    splits = planes[HP_VOTE_SPLITS] + vote_split.astype(jnp.int32)
    return jnp.stack([leaderless, since, bumps, splits]), window_pos
'''


def test_gc008_registered_planes_pass(tmp_path):
    vs = run_engine_on(
        tmp_path, {"raft_tpu/multiraft/kernels.py": _GC008_KERNELS_OK}
    )
    assert [v.rule_id for v in vs if v.rule_id == "GC008"] == []


def test_gc008_unregistered_plane_flags(tmp_path):
    src = _GC008_KERNELS_OK.replace(
        "N_HEALTH_PLANES = 4", "HP_NOVEL = 4\nN_HEALTH_PLANES = 5"
    )
    vs = run_engine_on(tmp_path, {"raft_tpu/multiraft/kernels.py": src})
    gc8 = [v for v in vs if v.rule_id == "GC008"]
    assert len(gc8) == 1 and "HP_NOVEL" in gc8[0].message


def test_gc008_growth_bound_violation_flags(tmp_path):
    src = _GC008_KERNELS_OK.replace(
        "planes[HP_LEADERLESS] + 1", "planes[HP_LEADERLESS] + 2"
    )
    vs = run_engine_on(tmp_path, {"raft_tpu/multiraft/kernels.py": src})
    gc8 = [v for v in vs if v.rule_id == "GC008"]
    assert len(gc8) == 1 and "grows by up to 2" in gc8[0].message


def test_gc008_unprovable_increment_flags(tmp_path):
    src = _GC008_KERNELS_OK.replace(
        "planes[HP_VOTE_SPLITS] + vote_split.astype(jnp.int32)",
        "planes[HP_VOTE_SPLITS] + mystery_rate",
    )
    vs = run_engine_on(tmp_path, {"raft_tpu/multiraft/kernels.py": src})
    gc8 = [v for v in vs if v.rule_id == "GC008"]
    assert len(gc8) == 1 and "cannot prove" in gc8[0].message


_GC008_SIM = '''\
"""doc"""

class ClusterSim:
    _DRAIN_MAX = 128

    def __init__(self, cfg):
        self._drain_cap = max(
            1, min(self._DRAIN_MAX, ({cap}) // (256 * cfg.n_groups))
        )

    def _drain_counters(self):
        v = -1
        if v < 0:
            raise RuntimeError("wrapped")
'''


def test_gc008_drain_cap_within_wrap_bound_passes(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {"raft_tpu/multiraft/sim.py": _GC008_SIM.format(cap="1 << 31")},
    )
    assert [v.rule_id for v in vs if v.rule_id == "GC008"] == []


def test_gc008_drain_cadence_beyond_wrap_bound_flags(tmp_path):
    # THE acceptance fixture: stretching the drain window budget past the
    # int32 wrap bound (2**40 events per window) must fail the build.
    vs = run_engine_on(
        tmp_path,
        {"raft_tpu/multiraft/sim.py": _GC008_SIM.format(cap="1 << 40")},
    )
    gc8 = [v for v in vs if v.rule_id == "GC008"]
    assert len(gc8) == 1 and "wraps at 2**31" in gc8[0].message


def test_gc008_backstop_in_settle_drain_passes(tmp_path):
    # ISSUE 11 moved the wrap backstop into the split drain's host half
    # (_settle_drain); the rule accepts either home.
    src = _GC008_SIM.format(cap="1 << 31").replace(
        "_drain_counters", "_settle_drain"
    )
    vs = run_engine_on(tmp_path, {"raft_tpu/multiraft/sim.py": src})
    assert [v.rule_id for v in vs if v.rule_id == "GC008"] == []


def test_gc008_missing_wrap_backstop_flags(tmp_path):
    # The backstop check must look for the v<0 raise INSIDE
    # _drain_counters: an unrelated raise elsewhere in the class (the
    # "disabled" RuntimeErrors) must not satisfy it.
    src = _GC008_SIM.format(cap="1 << 31").replace(
        '        if v < 0:\n            raise RuntimeError("wrapped")\n',
        "        return v\n",
    )
    src += (
        "\n    def counters(self):\n"
        '        raise RuntimeError("counters disabled")\n'
    )
    vs = run_engine_on(tmp_path, {"raft_tpu/multiraft/sim.py": src})
    gc8 = [v for v in vs if v.rule_id == "GC008"]
    assert len(gc8) == 1 and "backstop" in gc8[0].message


# --- GC009 traced-escape ---


def test_gc009_traced_into_static_param_flags(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/sim.py": '''\
            """doc"""

            def helper(x, n: int):
                return x * n

            def step(cfg, x):
                return helper(x, x.sum())
            ''',
        },
    )
    gc9 = [v for v in vs if v.rule_id == "GC009"]
    assert len(gc9) == 1 and "`n` of helper()" in gc9[0].message


def test_gc009_static_args_pass(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/sim.py": '''\
            """doc"""

            def helper(x, n: int):
                return x * n

            def step(cfg, x):
                a = helper(x, cfg.n_groups)
                b = helper(x, x.shape[0])
                sub_cfg = cfg._replace(n_groups=4)
                c = helper(x, n=sub_cfg.n_groups)
                return a, b, c
            ''',
        },
    )
    assert [v.rule_id for v in vs if v.rule_id == "GC009"] == []


def test_gc009_closure_statics_seen_in_nested_defs(tmp_path):
    # GC003's per-body pass cannot see that `cfg` is static inside the
    # nested fn; the call-graph-aware pass must (no false positive), while
    # still catching the traced escape in the second nested fn.
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/sim.py": '''\
            """doc"""

            def helper(x, rounds: int):
                return x * rounds

            def factory(cfg, k: int):
                def good(st):
                    return helper(st, k)

                def bad(st):
                    return helper(st, st.sum())

                return good, bad
            ''',
        },
    )
    gc9 = [v for v in vs if v.rule_id == "GC009"]
    assert len(gc9) == 1 and "`rounds` of helper()" in gc9[0].message


def test_gc009_cross_module_call_checked(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/kernels.py": '''\
            """tick <-> o"""

            def tick(state, election_timeout: int):
                return state + election_timeout
            ''',
            "raft_tpu/multiraft/sim.py": '''\
            """doc"""
            from . import kernels

            def step(cfg, st):
                return kernels.tick(st, st.max())
            ''',
        },
    )
    gc9 = [v for v in vs if v.rule_id == "GC009"]
    assert len(gc9) == 1 and "`election_timeout` of tick()" in gc9[0].message


# --- GC010 parity-obligations ---


def test_gc010_unresolvable_oracle_symbol_flags(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/kernels.py": '''\
            """Map:

              mapped <-> quorum.Missing.thing
            """

            def mapped(x):
                return x
            ''',
            "raft_tpu/quorum/__init__.py": "",
        },
    )
    gc10 = [v for v in vs if v.rule_id == "GC010"]
    assert len(gc10) == 1 and "does not resolve" in gc10[0].message


def test_gc010_resolvable_oracle_passes_and_unmachine_checkable_flags(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/kernels.py": '''\
            """Map:

              good <-> quorum.MajorityConfig.committed_index
              cited <-> scalar walk (reference: majority.rs:70-124)
              vague <-> something handwavy with no anchor at all
            """

            def good(x):
                return x

            def cited(x):
                return x

            def vague(x):
                return x
            ''',
            "raft_tpu/quorum/__init__.py": (
                "from .majority import MajorityConfig\n"
            ),
            "raft_tpu/quorum/majority.py": (
                "class MajorityConfig:\n"
                "    def committed_index(self, l):\n"
                "        return 0\n"
            ),
        },
    )
    gc10 = [v for v in vs if v.rule_id == "GC010"]
    assert len(gc10) == 1
    assert "vague" in gc10[0].message
    assert "no machine-checkable oracle" in gc10[0].message


def test_gc010_stale_baseline_flags(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/kernels.py": '''\
            """Map:

              mapped <-> scalar walk (reference: x.rs:1-2)
            """

            def mapped(x):
                return x
            ''',
            "tools/graftcheck/parity_obligations.json": (
                '{"version": 1, "obligations": '
                '[{"kernel": "dropped_kernel"}]}\n'
            ),
        },
    )
    gc10 = [v for v in vs if v.rule_id == "GC010"]
    assert len(gc10) == 1
    assert "drifted" in gc10[0].message
    assert "dropped_kernel" in gc10[0].message


def test_engine_rules_listed_and_markers_validate(tmp_path):
    # allow-GC007..GC010 markers must be KNOWN to the per-file run (a
    # marker naming them is not a GC000 unknown-rule violation).
    vs = run_on(
        tmp_path,
        "raft_tpu/scalar.py",
        f"{MARK}GC008 — engine rule marker is legal\n",
    )
    assert vs == []
    from tools.graftcheck import all_rules as _all

    ids_ = {r.id for r in _all()}
    assert {"GC007", "GC008", "GC009", "GC010"} <= ids_


# --- run cache + --changed-only (tools.graftcheck.__main__) ---


def test_run_cache_replays_unchanged_tree(tmp_path, monkeypatch, capsys):
    import tools.graftcheck.__main__ as gm

    f = tmp_path / "raft_tpu" / "multiraft" / "mod.py"
    f.parent.mkdir(parents=True)
    f.write_text("import jax.numpy as jnp\nx = jnp.zeros((4,))\n")
    monkeypatch.chdir(tmp_path)
    rc1 = gm.main(["raft_tpu"])
    out1 = capsys.readouterr().out
    assert rc1 == 1 and "GC001" in out1
    # Second run must replay from cache: run_paths must not execute.
    monkeypatch.setattr(
        gm, "run_paths", lambda *a, **k: (_ for _ in ()).throw(AssertionError)
    )
    rc2 = gm.main(["raft_tpu"])
    out2 = capsys.readouterr().out
    assert rc2 == 1 and out2 == out1
    # Touching the file misses the cache (mtime key) and re-runs.
    monkeypatch.undo()
    monkeypatch.chdir(tmp_path)
    f.write_text("import jax.numpy as jnp\nx = jnp.zeros((4,), jnp.int32)\n")
    assert gm.main(["raft_tpu"]) == 0


def test_changed_only_scans_only_changed_files(tmp_path, monkeypatch, capsys):
    import subprocess

    import tools.graftcheck.__main__ as gm

    def git(*args):
        return subprocess.run(
            ["git", *args], cwd=tmp_path, capture_output=True, text=True
        )

    if git("init", "-q").returncode != 0:
        import pytest

        pytest.skip("git unavailable")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    clean = tmp_path / "raft_tpu" / "multiraft" / "clean.py"
    clean.parent.mkdir(parents=True)
    # A violation in a COMMITTED, unchanged file must not be reported.
    clean.write_text("import jax.numpy as jnp\nx = jnp.zeros((4,))\n")
    git("add", "-A")
    git("commit", "-qm", "seed")
    dirty = tmp_path / "raft_tpu" / "multiraft" / "dirty.py"
    dirty.write_text("import jax.numpy as jnp\ny = jnp.ones((4,))\n")
    monkeypatch.chdir(tmp_path)
    rc = gm.main(["--changed-only", "raft_tpu"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "dirty.py" in out and "clean.py" not in out
    # A DELETION falls back to the full scan: violations for a vanished
    # file anchor in unchanged files, so filtering would miss them.
    clean.unlink()
    rc = gm.main(["--changed-only", "raft_tpu"])
    captured = capsys.readouterr()
    assert rc == 1
    assert "full scan" in captured.err


def test_rule_filter_on_engine_rule_requires_engine(tmp_path, monkeypatch, capsys):
    import tools.graftcheck.__main__ as gm

    f = tmp_path / "raft_tpu" / "multiraft" / "mod.py"
    f.parent.mkdir(parents=True)
    f.write_text("x = 1\n")
    monkeypatch.chdir(tmp_path)
    # `--rule GC008` without --engine would otherwise exit 0 having run
    # NOTHING (engine rules never apply per-file) — a silent green.
    rc = gm.main(["--rule", "GC008", "raft_tpu"])
    assert rc == 2
    assert "--engine" in capsys.readouterr().err


# --- PR 9 trace rules (GC011-GC014): analysis of the LOWERED artifacts ----
# Fixture graphs are TINY jitted fns (one or two eqns, sub-second CPU
# compiles) driven through the same trace_inventory() driver as the real
# inventory; the full flag-matrix run lives in `make lint` and the
# graftcheck-trace CI job, not in tier-1 (it is ~60s of XLA compiles).


def _trace_spec(name, build, const_budget=256):
    from tools.graftcheck.trace.inventory import GraphSpec

    return GraphSpec(
        name=name,
        anchor="raft_tpu/multiraft/sim.py",
        build=build,
        const_budget=const_budget,
    )


def _trace_run(specs):
    from tools.graftcheck.trace.analysis import trace_inventory

    return trace_inventory(specs)


def _declined_build():
    # A donated input whose shape matches NO output: XLA cannot alias it
    # and silently declines the donation — exactly GC011's quarry.
    import jax
    import jax.numpy as jnp

    from tools.graftcheck.trace.inventory import Built

    fn = jax.jit(lambda x: x.sum(), donate_argnums=(0,))
    return Built(fn, (jnp.zeros((8, 8), jnp.int32),), (0,))


def test_gc011_declined_donation_flags():
    vs, measured = _trace_run([_trace_spec("declined@fixture", _declined_build)])
    assert ids(vs) == ["GC011"]
    assert "alias map" in vs[0].message and "[0][0]" in vs[0].message
    # The measurement side still records the graph for GC014.
    assert measured["declined@fixture"] >= 1


def test_gc011_accepted_donation_passes():
    import jax
    import jax.numpy as jnp

    from tools.graftcheck.trace.inventory import Built

    def build():
        fn = jax.jit(lambda x: x + 1, donate_argnums=(0,))
        return Built(fn, (jnp.zeros((8, 8), jnp.int32),), (0,))

    vs, _ = _trace_run([_trace_spec("accepted@fixture", build)])
    assert vs == []


def test_gc011_registry_drift_flags():
    # The inventory declares donate=(0,) but the production wrapper jits
    # WITHOUT donation: the registry and the lowering disagree.
    import jax
    import jax.numpy as jnp

    from tools.graftcheck.trace.inventory import Built

    def build():
        return Built(
            jax.jit(lambda x: x + 1), (jnp.zeros((8,), jnp.int32),), (0,)
        )

    vs, _ = _trace_run([_trace_spec("drift@fixture", build)])
    assert ids(vs) == ["GC011"]
    assert "disagree" in vs[0].message


def test_gc011_allow_registry_accepts_decline(monkeypatch):
    from tools.graftcheck.trace import analysis

    monkeypatch.setitem(
        analysis.DONATION_ALLOW,
        ("declined@fixture", "[0][0]"),
        "fixture: reduction output cannot alias its input",
    )
    vs, _ = _trace_run([_trace_spec("declined@fixture", _declined_build)])
    assert vs == []


def test_gc011_stale_allow_entry_flags(monkeypatch):
    import jax
    import jax.numpy as jnp

    from tools.graftcheck.trace import analysis
    from tools.graftcheck.trace.inventory import Built

    def build():
        fn = jax.jit(lambda x: x + 1, donate_argnums=(0,))
        return Built(fn, (jnp.zeros((8,), jnp.int32),), (0,))

    # XLA ACCEPTS this donation, so an allow entry for it is rot.
    monkeypatch.setitem(
        analysis.DONATION_ALLOW,
        ("stale@fixture", "[0][0]"),
        "obsolete justification",
    )
    vs, _ = _trace_run([_trace_spec("stale@fixture", build)])
    assert ids(vs) == ["GC011"]
    assert "matches no declined" in vs[0].message


def test_gc011_allow_entry_without_reason_flags(monkeypatch):
    from tools.graftcheck.trace import analysis

    monkeypatch.setitem(
        analysis.DONATION_ALLOW, ("declined@fixture", "[0][0]"), "  "
    )
    vs, _ = _trace_run([_trace_spec("declined@fixture", _declined_build)])
    # An unjustified entry suppresses nothing (the decline still fires)
    # AND is itself a violation — the GC000 discipline.
    assert ids(vs) == ["GC011", "GC011"]
    assert any("no justification" in v.message for v in vs)


def test_gc011_allow_entry_for_unknown_graph_flags(monkeypatch):
    # A typo'd (or removed-graph) entry matches nothing traced; it would
    # suppress nothing and rot forever if the stale check skipped it.
    from tools.graftcheck.trace import analysis

    monkeypatch.setitem(
        analysis.DONATION_ALLOW,
        ("declinedX@fixture", "[0][0]"),
        "typo'd graph name",
    )
    vs, _ = _trace_run([_trace_spec("declined@fixture", _declined_build)])
    assert ids(vs) == ["GC011", "GC011"]
    assert any("names no inventoried graph" in v.message for v in vs)


def test_gc011_allow_entry_for_non_donating_graph_flags(monkeypatch):
    # The named graph exists but declares no donations, so the entry can
    # never match a decline — rot of a different flavor.
    import jax
    import jax.numpy as jnp

    from tools.graftcheck.trace import analysis
    from tools.graftcheck.trace.inventory import Built

    def build():
        return Built(jax.jit(lambda x: x + 1), (jnp.zeros((8,), jnp.int32),))

    monkeypatch.setitem(
        analysis.DONATION_ALLOW,
        ("nodonate@fixture", "[0][0]"),
        "graph stopped donating",
    )
    vs, _ = _trace_run([_trace_spec("nodonate@fixture", build)])
    assert ids(vs) == ["GC011"]
    assert "matches no declined" in vs[0].message


def test_gc011_allow_entry_for_unaudited_graph_flags(monkeypatch):
    # audit_donation=False rows run no donation audit at all, so an allow
    # entry pointed at one can never match.
    import jax
    import jax.numpy as jnp

    from tools.graftcheck.trace import analysis
    from tools.graftcheck.trace.inventory import Built, GraphSpec

    def build():
        return Built(jax.jit(lambda x: x + 1), (jnp.zeros((8,), jnp.int32),))

    spec = GraphSpec(
        name="unaudited@fixture",
        anchor="raft_tpu/multiraft/sim.py",
        build=build,
        audit_donation=False,
    )
    monkeypatch.setitem(
        analysis.DONATION_ALLOW,
        ("unaudited@fixture", "[0][0]"),
        "points at an unaudited row",
    )
    vs, _ = _trace_run([spec])
    assert ids(vs) == ["GC011"]
    assert "audit_donation=False" in vs[0].message


def test_gc011_reverse_drift_flags():
    # The wrapper DONATES but the registry row declares none: the drift
    # check must be bidirectional, or a donation added without updating
    # the inventory is invisible (and its decline unauditable).
    import jax
    import jax.numpy as jnp

    from tools.graftcheck.trace.inventory import Built

    def build():
        fn = jax.jit(lambda x: x + 1, donate_argnums=(0,))
        return Built(fn, (jnp.zeros((8,), jnp.int32),))

    vs, _ = _trace_run([_trace_spec("reverse-drift@fixture", build)])
    assert ids(vs) == ["GC011"]
    assert "disagree" in vs[0].message


def test_gc012_oversized_closure_const_flags():
    import jax
    import jax.numpy as jnp

    from tools.graftcheck.trace.inventory import Built

    def build():
        big = jnp.arange(512, dtype=jnp.int32)  # 2048B > any sane budget
        return Built(
            jax.jit(lambda x: x + big), (jnp.zeros((512,), jnp.int32),)
        )

    vs, _ = _trace_run([_trace_spec("const@fixture", build)])
    assert ids(vs) == ["GC012"]
    assert "2048-byte const" in vs[0].message
    # The same graph under a budget that admits the const passes: the
    # threshold, not the existence of consts, is the rule.
    vs, _ = _trace_run(
        [_trace_spec("const@fixture", build, const_budget=4096)]
    )
    assert vs == []


def test_gc012_catches_small_g_plane_at_default_budget():
    # The audit shape is tiny (G=8, P=3), so a closed-over bool[P, P, G]
    # is only 72B there — the DEFAULT budget must still catch it, or the
    # rule misses its stated quarry at exactly the shape it audits.
    import jax
    import jax.numpy as jnp

    from tools.graftcheck.trace.inventory import (
        Built,
        DEFAULT_CONST_BYTES,
    )

    def build():
        plane = jnp.ones((3, 3, 8), bool)  # the smallest per-group plane
        return Built(
            jax.jit(lambda x: x & plane), (jnp.zeros((3, 3, 8), bool),)
        )

    assert DEFAULT_CONST_BYTES < 72
    vs, _ = _trace_run(
        [
            _trace_spec(
                "plane@fixture", build, const_budget=DEFAULT_CONST_BYTES
            )
        ]
    )
    assert ids(vs) == ["GC012"]
    assert "72-byte const" in vs[0].message


def test_gc013_io_callback_in_graph_flags():
    import jax
    import jax.numpy as jnp
    from jax.experimental import io_callback

    from tools.graftcheck.trace.inventory import Built

    def build():
        def fn(x):
            io_callback(lambda v: None, None, x)
            jax.debug.print("s={s}", s=x.sum())
            return x + 1

        return Built(jax.jit(fn), (jnp.zeros((8,), jnp.int32),))

    vs, _ = _trace_run([_trace_spec("callback@fixture", build)])
    assert ids(vs) == ["GC013", "GC013"]
    prims = " ".join(v.message for v in vs)
    assert "io_callback" in prims and "debug_print" in prims


def test_trace_build_failure_is_a_finding():
    def build():
        raise ValueError("fixture build exploded")

    vs, measured = _trace_run([_trace_spec("broken@fixture", build)])
    assert ids(vs) == ["GC000"]
    assert "failed to build/trace" in vs[0].message
    assert measured == {}


# --- GC015 collective-audit (ISSUE 14): the partitioned executables ------


def _coll_spec(name, build, audit=True):
    from tools.graftcheck.trace.inventory import GraphSpec

    return GraphSpec(
        name=name,
        anchor="raft_tpu/multiraft/sharding.py",
        build=build,
        const_budget=256,
        audit_collectives=audit,
    )


def _sharded_input():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = jax.make_mesh((8,), ("g",))
    return jax.device_put(
        jnp.zeros((64,), jnp.int32),
        NamedSharding(mesh, PartitionSpec("g")),
    )


def _psum_build():
    # A global reduction over the sharded axis: GSPMD must lower it as an
    # all-reduce — exactly GC015's quarry in a zero-collective graph.
    import jax

    from tools.graftcheck.trace.inventory import Built

    return Built(jax.jit(lambda x: x.sum()), (_sharded_input(),))


def _elementwise_build():
    import jax

    from tools.graftcheck.trace.inventory import Built

    return Built(jax.jit(lambda x: x + 1), (_sharded_input(),))


def test_gc015_unregistered_collective_flags():
    vs, _ = _trace_run([_coll_spec("coll@fixture", _psum_build)])
    assert ids(vs) == ["GC015"]
    assert "all-reduce" in vs[0].message
    assert "NOT registered" in vs[0].message


def test_gc015_zero_collective_graph_passes():
    vs, _ = _trace_run([_coll_spec("clean@fixture", _elementwise_build)])
    assert vs == []


def test_gc015_allow_registry_accepts(monkeypatch):
    from tools.graftcheck.trace import analysis

    monkeypatch.setitem(
        analysis.COLLECTIVE_ALLOW,
        ("coll@fixture", "all-reduce"),
        "fixture: the reduction is the graph's whole point",
    )
    vs, _ = _trace_run([_coll_spec("coll@fixture", _psum_build)])
    assert vs == []


def test_gc015_stale_allow_entry_flags(monkeypatch):
    from tools.graftcheck.trace import analysis

    # The graph has NO collectives, so an allow entry for it is rot.
    monkeypatch.setitem(
        analysis.COLLECTIVE_ALLOW,
        ("clean@fixture", "all-reduce"),
        "obsolete justification",
    )
    vs, _ = _trace_run([_coll_spec("clean@fixture", _elementwise_build)])
    assert ids(vs) == ["GC015"]
    assert "matches no collective" in vs[0].message


def test_gc015_allow_entry_for_unaudited_graph_flags(monkeypatch):
    from tools.graftcheck.trace import analysis

    monkeypatch.setitem(
        analysis.COLLECTIVE_ALLOW,
        ("clean@fixture", "all-gather"),
        "never matched",
    )
    vs, _ = _trace_run(
        [_coll_spec("clean@fixture", _elementwise_build, audit=False)]
    )
    assert ids(vs) == ["GC015"]
    assert "audit_collectives" in vs[0].message


# --- GC014 jaxpr-budget (stdlib: the committed file + the check logic) ---


def _committed_budget():
    from pathlib import Path

    from tools.graftcheck.trace.budget import budget_path, load_budget

    repo = Path(__file__).resolve().parents[1]
    return load_budget(budget_path(repo))


def test_gc014_committed_budget_parses_and_replays_green():
    from tools.graftcheck.trace.budget import check_budget

    doc = _committed_budget()
    assert doc is not None and doc["graphs"], (
        "committed jaxpr_budget.json must parse (regenerate with "
        "`make jaxpr-budget`)"
    )
    measured = {n: e["eqns"] for n, e in doc["graphs"].items()}
    vs, diff = check_budget(measured, doc, "tools/graftcheck/jaxpr_budget.json")
    assert vs == []
    assert all(g["status"] == "ok" for g in diff["graphs"].values())


def test_gc014_budget_regression_replay_fails():
    # The bench-gate negative test, for jaxprs: replay the committed
    # budget with ONE measurement inflated past tolerance — the gate
    # must fail, or it gates nothing.
    from tools.graftcheck.trace.budget import check_budget

    doc = _committed_budget()
    measured = {n: e["eqns"] for n, e in doc["graphs"].items()}
    name = sorted(measured)[0]
    tolerance = doc["tolerance_pct"] / 100.0
    measured[name] = int(measured[name] * (1 + tolerance)) + 2
    vs, diff = check_budget(measured, doc, "tools/graftcheck/jaxpr_budget.json")
    assert ids(vs) == ["GC014"] and name in vs[0].message
    assert diff["graphs"][name]["status"] == "over"


def test_gc014_missing_entry_and_stale_entry_flag():
    from tools.graftcheck.trace.budget import check_budget

    doc = {
        "format": 1,
        "tolerance_pct": 15.0,
        "graphs": {"gone@flags": {"eqns": 10}},
    }
    vs, diff = check_budget({"new@flags": 7}, doc, "b.json")
    assert ids(vs) == ["GC014", "GC014"]
    msgs = " ".join(v.message for v in vs)
    assert "no budget entry" in msgs and "stale" in msgs
    assert diff["graphs"]["new@flags"]["status"] == "new"
    assert diff["graphs"]["gone@flags"]["status"] == "stale"


def test_gc014_missing_budget_file_is_a_violation(tmp_path):
    from tools.graftcheck.trace.budget import budget_path, check_budget, load_budget

    doc = load_budget(budget_path(tmp_path))  # no file there
    assert doc is None
    vs, _ = check_budget({"g@f": 5}, doc, "b.json")
    assert ids(vs) == ["GC014"]
    assert "missing or unreadable" in vs[0].message


def test_gc014_shrink_never_fails_but_shows_in_diff():
    from tools.graftcheck.trace.budget import check_budget

    doc = {"format": 1, "tolerance_pct": 15.0, "graphs": {"g@f": {"eqns": 100}}}
    vs, diff = check_budget({"g@f": 40}, doc, "b.json")
    assert vs == []
    assert diff["graphs"]["g@f"]["status"] == "shrunk"


def test_gc014_version_mismatch_recorded_and_noted():
    # The graftcheck-trace CI job installs unpinned jax, so an upstream
    # lowering change can blow a budget with zero repo changes; the gate
    # still fails (growth is growth) but the verdict must say where to
    # look: mismatch in the diff artifact + a note on the violation.
    from tools.graftcheck.trace.budget import check_budget

    doc = {
        "format": 1,
        "tolerance_pct": 15.0,
        "versions": {"jax": "0.1.0", "jaxlib": "0.1.0"},
        "graphs": {"g@f": {"eqns": 100}},
    }
    newer = {"jax": "9.9.9", "jaxlib": "9.9.9"}
    vs, diff = check_budget({"g@f": 100}, doc, "b.json", measured_versions=newer)
    assert vs == [] and diff["version_mismatch"] is True
    vs, diff = check_budget({"g@f": 200}, doc, "b.json", measured_versions=newer)
    assert len(vs) == 1 and "upstream jax lowering change" in vs[0].message
    # Matching versions: no mismatch, no note.
    same = {"jax": "0.1.0", "jaxlib": "0.1.0"}
    vs, diff = check_budget({"g@f": 200}, doc, "b.json", measured_versions=same)
    assert diff["version_mismatch"] is False
    assert "upstream" not in vs[0].message


def test_trace_rules_listed_and_markers_validate(tmp_path):
    vs = run_on(
        tmp_path,
        "raft_tpu/scalar.py",
        f"{MARK}GC013 — trace rule marker is legal\n",
    )
    assert vs == []
    from tools.graftcheck import all_rules as _all

    ids_ = {r.id for r in _all()}
    assert {"GC011", "GC012", "GC013", "GC014"} <= ids_


# --- the --trace CLI: run cache + jax-version keying ---------------------


def test_trace_cache_replays_and_keys_on_jax_version(tmp_path, monkeypatch, capsys):
    import tools.graftcheck.__main__ as gm
    import tools.graftcheck.trace as trace_pkg
    from tools.graftcheck import Violation

    f = tmp_path / "raft_tpu" / "multiraft" / "mod.py"
    f.parent.mkdir(parents=True)
    f.write_text("x = 1\n")
    monkeypatch.chdir(tmp_path)
    calls = []

    def fake_run_trace(ctx, update_budget=False, diff_out=None):
        calls.append(1)
        return [
            Violation(
                "raft_tpu/multiraft/sim.py", 1, "GC013",
                "host-sync-in-graph", "fixture finding",
            )
        ]

    monkeypatch.setattr(trace_pkg, "run_trace", fake_run_trace)
    rc1 = gm.main(["--trace", "raft_tpu"])
    out1 = capsys.readouterr().out
    assert rc1 == 1 and "GC013" in out1 and len(calls) == 1
    # Unchanged tree + same jax: the cached trace result replays without
    # re-tracing (the 60s full-inventory run must not re-run per commit).
    rc2 = gm.main(["--trace", "raft_tpu"])
    out2 = capsys.readouterr().out
    assert rc2 == 1 and out2 == out1 and len(calls) == 1
    # A jax upgrade changes every jaxpr WITHOUT touching one repo file:
    # the version key must miss the cache (the v2 invalidation gap).
    monkeypatch.setattr(
        gm, "_trace_versions", lambda: "jax=99.0.0,jaxlib=99.0.0"
    )
    rc3 = gm.main(["--trace", "raft_tpu"])
    capsys.readouterr()
    assert rc3 == 1 and len(calls) == 2
    # And a raft_tpu source change misses it too (mtime fingerprint).
    monkeypatch.setattr(gm, "_trace_versions", lambda: "jax=1,jaxlib=1")
    gm.main(["--trace", "raft_tpu"])
    assert len(calls) == 3
    f.write_text("x = 2\n")
    gm.main(["--trace", "raft_tpu"])
    assert len(calls) == 4


def test_update_budget_bypasses_trace_cache(tmp_path, monkeypatch):
    # --update-budget must ACTUALLY trace (regen is a side effect a
    # cache replay would skip), even on an unchanged tree.
    import tools.graftcheck.__main__ as gm
    import tools.graftcheck.trace as trace_pkg

    f = tmp_path / "raft_tpu" / "multiraft" / "mod.py"
    f.parent.mkdir(parents=True)
    f.write_text("x = 1\n")
    monkeypatch.chdir(tmp_path)
    calls = []

    def fake_run_trace(ctx, update_budget=False, diff_out=None):
        calls.append(update_budget)
        return []

    monkeypatch.setattr(trace_pkg, "run_trace", fake_run_trace)
    assert gm.main(["--trace", "raft_tpu"]) == 0
    assert gm.main(["--update-budget", "raft_tpu"]) == 0
    assert gm.main(["--update-budget", "raft_tpu"]) == 0
    assert calls == [False, True, True]


def test_rule_filter_on_trace_rule_requires_trace(tmp_path, monkeypatch, capsys):
    import tools.graftcheck.__main__ as gm

    f = tmp_path / "raft_tpu" / "multiraft" / "mod.py"
    f.parent.mkdir(parents=True)
    f.write_text("x = 1\n")
    monkeypatch.chdir(tmp_path)
    # `--rule GC014` without --trace would exit 0 having run NOTHING
    # (trace rules never apply per-file) — the same silent-green hazard
    # as the engine rules.
    rc = gm.main(["--rule", "GC014", "raft_tpu"])
    assert rc == 2
    assert "--trace" in capsys.readouterr().err


def test_rule_filter_keeps_trace_build_errors(tmp_path, monkeypatch, capsys):
    # A graph that fails to BUILD yields only a GC000 trace-build-error;
    # `--trace --rule GC011` must not filter it out (the broken row found
    # nothing for GC011, so dropping the build error reads as green).
    import tools.graftcheck.__main__ as gm
    import tools.graftcheck.trace as trace_pkg
    from tools.graftcheck import Violation

    f = tmp_path / "raft_tpu" / "multiraft" / "mod.py"
    f.parent.mkdir(parents=True)
    f.write_text("x = 1\n")
    monkeypatch.chdir(tmp_path)

    def fake_run_trace(ctx, update_budget=False, diff_out=None):
        return [
            Violation(
                "raft_tpu/multiraft/sim.py", 1, "GC000",
                "trace-build-error", "graph 'x' failed to build/trace",
            )
        ]

    monkeypatch.setattr(trace_pkg, "run_trace", fake_run_trace)
    rc = gm.main(["--trace", "--rule", "GC011", "raft_tpu"])
    assert rc == 1
    assert "trace-build-error" in capsys.readouterr().out


# --- PR 17 registry rules: GC016 registry-closure + GC017 stale-marker


# A minimal-but-complete fixture registry: GC016 standalone-loads the
# SCANNED planes.py, so every accessor check_registry calls must exist.
# `{ghost_extra}` lets tests vary the gated row (oracle, etc).
_FIXTURE_PLANES = '''\
from typing import NamedTuple, Optional, Tuple


class PlaneSpec(NamedTuple):
    name: str
    owner: str
    family: str
    shape: str
    dtype: str
    flag: Tuple[str, ...] = ()
    bound_bits: Optional[int] = None
    bound: str = ""
    packing: str = "none"
    checkpoint: str = "none"
    sharding: str = "none"
    steady: str = "fusable"
    oracle: Optional[str] = None


REGISTRY = (
    PlaneSpec("term", "SimState", "core", "[P, G]", "int32",
              checkpoint="state", sharding="minor-G"),
    PlaneSpec("ghost", "SimState", "core", "[P, G]", "bool",
              flag=("damp",), checkpoint="state",
              sharding="minor-G"{ghost_extra}),
)


def rows(owner=None, family=None):
    return tuple(
        r for r in REGISTRY
        if (owner is None or r.owner == owner)
        and (family is None or r.family == family)
    )


def row(owner, name):
    for r in REGISTRY:
        if r.owner == owner and r.name == name:
            return r
    raise KeyError((owner, name))


def sim_state_fields():
    return tuple(r.name for r in rows(owner="SimState"))


def optional_sim_fields():
    return tuple(r.name for r in rows(owner="SimState") if r.flag)


def checkpoint_fields(policy):
    return tuple(r.name for r in REGISTRY if r.checkpoint == policy)


def packed_carry_fields():
    return tuple(
        r.name for r in rows(owner="SimState") if r.packing == "bits_g"
    )


def steady_defuse_flags():
    out = []
    for r in REGISTRY:
        if r.steady == "defuse":
            for f in r.flag:
                if f not in out:
                    out.append(f)
    return tuple(out)


def gating_flags():
    out = []
    for r in REGISTRY:
        for f in r.flag:
            if f not in out:
                out.append(f)
    return tuple(out)


def leading_axes(r):
    return r.shape.count(",")
'''

_FIXTURE_SIM = '''\
"""fixture sim"""
from typing import NamedTuple, Optional

import jax.numpy as jnp


class SimConfig(NamedTuple):
    n_groups: int = 1
    damp: bool = False


class SimState(NamedTuple):
    term: jnp.ndarray  # gc: int32[P, G]
    ghost: Optional[jnp.ndarray] = None  # gc: bool[P, G]


# carry packing derives from planes.packed_carry_fields (consumption pin)
'''


def planes_fixture(ghost_extra=""):
    return _FIXTURE_PLANES.format(ghost_extra=ghost_extra)


def gc016(vs):
    return [v for v in vs if v.rule_id == "GC016"]


def test_gc016_matching_tree_passes(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/planes.py": planes_fixture(),
            "raft_tpu/multiraft/sim.py": _FIXTURE_SIM,
        },
    )
    assert gc016(vs) == []


def test_gc016_simstate_field_order_mismatch_flags(tmp_path):
    # Dropping the gated field desyncs SimState from the registry rows.
    sim = _FIXTURE_SIM.replace(
        "    ghost: Optional[jnp.ndarray] = None  # gc: bool[P, G]\n", ""
    )
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/planes.py": planes_fixture(),
            "raft_tpu/multiraft/sim.py": sim,
        },
    )
    assert any("SimState fields" in v.message for v in gc016(vs))


def test_gc016_anchor_dtype_mismatch_flags(tmp_path):
    sim = _FIXTURE_SIM.replace("# gc: int32[P, G]", "# gc: bool[P, G]")
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/planes.py": planes_fixture(),
            "raft_tpu/multiraft/sim.py": sim,
        },
    )
    assert any("anchor" in v.message for v in gc016(vs))


def test_gc016_gated_field_must_be_optional(tmp_path):
    sim = _FIXTURE_SIM.replace(
        "term: jnp.ndarray  # gc: int32[P, G]\n"
        "    ghost: Optional[jnp.ndarray] = None  # gc: bool[P, G]",
        "term: jnp.ndarray  # gc: int32[P, G]\n"
        "    ghost: jnp.ndarray  # gc: bool[P, G]",
    )
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/planes.py": planes_fixture(),
            "raft_tpu/multiraft/sim.py": sim,
        },
    )
    assert any("flag-gated" in v.message for v in gc016(vs))


def test_gc016_gating_flag_must_exist_in_simconfig(tmp_path):
    sim = _FIXTURE_SIM.replace("    damp: bool = False\n", "")
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/planes.py": planes_fixture(),
            "raft_tpu/multiraft/sim.py": sim,
        },
    )
    assert any("not a SimConfig field" in v.message for v in gc016(vs))


def test_gc016_oracle_must_resolve(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/planes.py": planes_fixture(
                ghost_extra=', oracle="simref.NoSuchOracle"'
            ),
            "raft_tpu/multiraft/sim.py": _FIXTURE_SIM,
            "raft_tpu/multiraft/simref.py": '"""x"""\n\nclass Other:\n    pass\n',
        },
    )
    assert any("does not resolve" in v.message for v in gc016(vs))


def test_gc016_overflow_drift_flags(tmp_path):
    # A fixture linter checkout whose overflow.py regrew a local dict:
    # the drift check reads repo_root/tools/..., which run_engine_on
    # points at tmp_path.
    bad = tmp_path / "tools" / "graftcheck" / "engine" / "overflow.py"
    bad.parent.mkdir(parents=True)
    bad.write_text('COUNTER_PLANES = {"CTR_X"}\n')
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/planes.py": planes_fixture(),
            "raft_tpu/multiraft/sim.py": _FIXTURE_SIM,
        },
    )
    msgs = [v.message for v in gc016(vs)]
    assert any("local literal" in m for m in msgs)
    assert any("no longer binds" in m for m in msgs)


def test_gc016_checkpoint_literal_family_flags(tmp_path):
    ckpt = (
        '"""fixture checkpoint"""\n'
        "from . import planes\n\n"
        "_STATE = planes.checkpoint_fields(\"state\")\n"
        "_OPT = planes.optional_sim_fields()\n"
        'BYPASS = ["ghost"]\n'
    )
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/planes.py": planes_fixture(),
            "raft_tpu/multiraft/sim.py": _FIXTURE_SIM,
            "raft_tpu/multiraft/checkpoint.py": ckpt,
        },
    )
    assert any("re-enumerates" in v.message for v in gc016(vs))


def gc017(vs):
    return [v for v in vs if v.rule_id == "GC017"]


def test_gc017_stale_marker_flags(tmp_path):
    # The dtype IS explicit, so the GC001 suppression earns nothing.
    src = (
        '"""m <-> o"""\n'
        "import jax.numpy as jnp\n\n"
        f"x = jnp.zeros((4,), dtype=jnp.int32)  {MARK}no-implicit-dtype — obsolete\n"
    )
    vs = run_engine_on(tmp_path, {"raft_tpu/multiraft/kernels.py": src})
    assert any(v.line == 4 for v in gc017(vs))


def test_gc017_live_marker_passes(tmp_path):
    src = (
        '"""m <-> o"""\n'
        "import jax.numpy as jnp\n\n"
        f"x = jnp.zeros((4,))  {MARK}no-implicit-dtype — fixture wants weak typing\n"
    )
    vs = run_engine_on(tmp_path, {"raft_tpu/multiraft/kernels.py": src})
    assert gc017(vs) == []


def test_gc017_trace_rule_marker_exempt(tmp_path):
    # GC011-GC015 liveness needs the lowered graphs (jax); the engine run
    # must not call their markers stale.
    src = (
        '"""m <-> o"""\n'
        "import jax.numpy as jnp\n\n"
        f"{MARK}GC014 — budget exception justified elsewhere\n"
        "x = jnp.zeros((4,), dtype=jnp.int32)\n"
    )
    vs = run_engine_on(tmp_path, {"raft_tpu/multiraft/kernels.py": src})
    assert gc017(vs) == []


def test_gc017_marker_in_string_literal_ignored(tmp_path):
    src = (
        '"""m <-> o"""\n'
        "import jax.numpy as jnp\n\n"
        f'FIXTURE = """y = 1  {MARK}no-implicit-dtype — embedded fixture"""\n'
    )
    vs = run_engine_on(tmp_path, {"raft_tpu/multiraft/kernels.py": src})
    assert gc017(vs) == []


def test_gc017_unconsulted_anchor_flags(tmp_path):
    # A module-level assignment's anchor is never read by the engine
    # interpreter — the claim is decorative.
    src = (
        '"""fixture sim"""\n'
        "import jax.numpy as jnp\n\n"
        "X = 4  # gc" + ": int32[P, G]\n"
    )
    vs = run_engine_on(tmp_path, {"raft_tpu/multiraft/sim.py": src})
    assert any("anchor" in v.message for v in gc017(vs))


def test_gc017_consulted_anchor_passes(tmp_path):
    src = (
        '"""fixture sim"""\n'
        "import jax.numpy as jnp\n\n\n"
        "def f(x):  # gc" + ": int32[P, G]\n"
        "    y = x  # gc" + ": int32[P, G]\n"
        "    return y\n"
    )
    vs = run_engine_on(tmp_path, {"raft_tpu/multiraft/sim.py": src})
    assert gc017(vs) == []


def test_gc017_fix_markers_rewrites_files(tmp_path):
    from tools.graftcheck.engine import run_stale_scan
    from tools.graftcheck.engine.stale import fix_files

    src = (
        '"""m <-> o"""\n'
        "import jax.numpy as jnp\n\n"
        f"x = jnp.zeros((4,), dtype=jnp.int32)  {MARK}no-implicit-dtype — obsolete\n"
        f"{MARK}no-host-sync-in-jit — a standalone stale marker whose\n"
        "# justification wraps onto this second comment line\n"
        "y = jnp.zeros((2,), dtype=jnp.int32)\n"
    )
    f = tmp_path / "raft_tpu" / "multiraft" / "kernels.py"
    f.parent.mkdir(parents=True)
    f.write_text(src)
    stub = tmp_path / "tests" / "test_sim_parity.py"
    stub.parent.mkdir(parents=True)
    stub.write_text("# parity suite stub\n")
    ctx = Context(
        repo_root=tmp_path, tests_root=tmp_path / "tests",
        reference_root=None,
    )
    items = run_stale_scan([str(tmp_path / "raft_tpu")], ctx)
    assert len(items) == 2
    fix_files(items)
    out = f.read_text()
    assert "graftcheck" not in out
    assert "justification wraps" not in out
    assert "x = jnp.zeros((4,), dtype=jnp.int32)\n" in out
    assert "y = jnp.zeros((2,), dtype=jnp.int32)\n" in out


# --- PR 19 runner registry: GC018 runner-closure + GC019 phase-budget


# A minimal-but-complete fixture schedule registry: GC018 standalone-loads
# the SCANNED schedules.py (the GC016 discipline), so every accessor
# check_runners calls must exist.  `{extra_row}` lets tests inject an
# orphan registry row.
_FIXTURE_SCHEDULES = '''\
from typing import NamedTuple, Tuple


class ScheduleSpec(NamedTuple):
    name: str
    family: str
    shape: str
    dtype: str
    packing: str = ""
    gather: str = "phase"
    flag: Tuple[str, ...] = ()

    @property
    def anchor_text(self):
        return self.dtype + self.shape


class ScheduleFamily(NamedTuple):
    name: str
    compiled: str
    host_twin: str
    phase: str


class RunnerVariant(NamedTuple):
    name: str
    base: str
    phases: Tuple[str, ...]
    builder: str
    options: Tuple = ()
    probe_for: str = ""


PHASES = ("chaos",)
PHASE_TOLERANCE_PCT = 2.0

SCHEDULES = (
    ScheduleSpec("phase_of_round", "chaos", "[R]", "int32", gather="round"),
    ScheduleSpec("link_packed", "chaos", "[S, W, G]", "uint32"),
    ScheduleSpec("append", "chaos", "[S, G]", "int32"),{extra_row}
)

FAMILIES = (
    ScheduleFamily(
        "chaos", "chaos.CompiledChaos", "chaos.HostSchedule", "chaos"
    ),
)

RUNNER_VARIANTS = (
    RunnerVariant(
        "chaos_runner", "step", ("chaos",), "chaos", probe_for="chaos"
    ),
)


def rows(family=None):
    return tuple(
        r for r in SCHEDULES if family is None or r.family == family
    )


def row(family_name, name):
    for r in SCHEDULES:
        if r.family == family_name and r.name == name:
            return r
    raise KeyError((family_name, name))


def families():
    return FAMILIES


def family(name):
    for f in FAMILIES:
        if f.name == name:
            return f
    raise KeyError(name)


def array_fields(family_name):
    return tuple(r.name for r in rows(family_name))


def runner_variants():
    return RUNNER_VARIANTS


def variant(name):
    for v in RUNNER_VARIANTS:
        if v.name == name:
            return v
    raise KeyError(name)


def phases():
    return PHASES


def gating_flags():
    out = []
    for r in SCHEDULES:
        for f in r.flag:
            if f not in out:
                out.append(f)
    return tuple(out)


def packing_families():
    out = []
    for r in SCHEDULES:
        if r.packing and r.packing not in out:
            out.append(r.packing)
    return tuple(out)
'''

_FIXTURE_CHAOS = '''\
"""fixture chaos"""
from typing import NamedTuple

import jax.numpy as jnp


class CompiledChaos(NamedTuple):
    phase_of_round: jnp.ndarray  # gc: int32[R]
    link_packed: jnp.ndarray  # gc: uint32[S, W, G]
    append: jnp.ndarray  # gc: int32[S, G]
    n_rounds: int = 0


class HostSchedule:
    pass
'''


def schedules_fixture(extra_row=""):
    return _FIXTURE_SCHEDULES.format(extra_row=extra_row)


def gc018(vs):
    return [v for v in vs if v.rule_id == "GC018"]


def test_gc018_matching_tree_passes(tmp_path):
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/schedules.py": schedules_fixture(),
            "raft_tpu/multiraft/chaos.py": _FIXTURE_CHAOS,
        },
    )
    assert gc018(vs) == []


def test_gc018_orphan_registry_row_flags(tmp_path):
    # A registry row with no compiled-tuple field desyncs the family.
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/schedules.py": schedules_fixture(
                extra_row='\n    ScheduleSpec('
                '"loss_packed", "chaos", "[S, W, G]", "uint32"),'
            ),
            "raft_tpu/multiraft/chaos.py": _FIXTURE_CHAOS,
        },
    )
    assert any("orphan registry row" in v.message for v in gc018(vs))


def test_gc018_closure_const_schedule_flags(tmp_path):
    # A nested (traced) def reading a schedule array off an enclosing-
    # scope object is the source-level GC012 constant-capture hazard.
    runner = (
        '"""fixture runner"""\n'
        "from . import schedules\n\n\n"
        "def make_runner(cfg, compiled):\n"
        '    fields = schedules.array_fields("chaos")\n\n'
        "    def run(st):\n"
        "        return st + compiled.link_packed.sum()\n\n"
        "    return run\n"
    )
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/schedules.py": schedules_fixture(),
            "raft_tpu/multiraft/chaos.py": _FIXTURE_CHAOS,
            "raft_tpu/multiraft/runner.py": runner,
        },
    )
    assert any("closure variable" in v.message for v in gc018(vs))


def test_gc018_runtime_arg_schedule_in_nested_def_passes(tmp_path):
    # The same read is fine when the schedule object is the nested
    # function's OWN parameter — a runtime jit arg, not a closure const.
    runner = (
        '"""fixture runner"""\n'
        "from . import schedules\n\n\n"
        "def make_runner(cfg, compiled):\n"
        '    fields = schedules.array_fields("chaos")\n\n'
        "    def run(st, sched):\n"
        "        return st + sched.link_packed.sum()\n\n"
        "    return run\n"
    )
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/schedules.py": schedules_fixture(),
            "raft_tpu/multiraft/chaos.py": _FIXTURE_CHAOS,
            "raft_tpu/multiraft/runner.py": runner,
        },
    )
    assert gc018(vs) == []


_FACTORY_RUNNER = (
    '"""fixture runner"""\n'
    "from . import schedules\n\n\n"
    "def _body_of(cfg, sched):\n"
    "    def body(st, r):\n"
    "        return st + sched.link_packed.sum()\n\n"
    "    return body\n\n\n"
    "def make_runner(cfg, compiled):\n"
    '    fields = schedules.array_fields("chaos")\n\n'
    "    def run(st, *args):\n"
    "        sched = compiled._replace(**dict(zip(fields, args)))\n"
    "        return _body_of(cfg, {passed})(st, 0)\n\n"
    "    return run\n"
)


@pytest.mark.parametrize("passed, clean", [("sched", True), ("compiled", False)])
def test_gc018_body_factory_parameter_is_judged_at_its_call_sites(
    tmp_path, passed, clean
):
    # A private top-level body factory (runner._runner_body) may read
    # schedule arrays off its parameter when every call in the module hands
    # it a schedule rebuilt inside a traced def; handed the constructor's
    # compiled template, the same read is the closure const it always was.
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/schedules.py": schedules_fixture(),
            "raft_tpu/multiraft/chaos.py": _FIXTURE_CHAOS,
            "raft_tpu/multiraft/runner.py": _FACTORY_RUNNER.format(passed=passed),
        },
    )
    assert (gc018(vs) == []) == clean
    assert clean or any("closure variable" in v.message for v in gc018(vs))


def test_gc018_hand_listed_schedule_tuple_flags(tmp_path):
    # Re-enumerating three family arrays off one object in a Load-context
    # display is the drift the registry exists to delete.
    runner = (
        '"""fixture runner"""\n'
        "from . import schedules\n\n\n"
        "def make_runner(cfg, compiled):\n"
        '    fields = schedules.array_fields("chaos")\n'
        "    flat = (\n"
        "        compiled.phase_of_round,\n"
        "        compiled.link_packed,\n"
        "        compiled.append,\n"
        "    )\n"
        "    return flat\n"
    )
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/schedules.py": schedules_fixture(),
            "raft_tpu/multiraft/chaos.py": _FIXTURE_CHAOS,
            "raft_tpu/multiraft/runner.py": runner,
        },
    )
    assert any("hand-listed schedule tuple" in v.message for v in gc018(vs))


def test_gc018_hand_listed_inventory_row_flags(tmp_path):
    # A fixture linter checkout whose inventory.py regrew a hand-listed
    # runner row (and dropped the runner_variants() derivation): the
    # check reads repo_root/tools/..., which run_engine_on points at
    # tmp_path.
    bad = tmp_path / "tools" / "graftcheck" / "trace" / "inventory.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(
        'GRAPHS = [("chaos_runner", "raft_tpu/multiraft/chaos.py")]\n'
    )
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/schedules.py": schedules_fixture(),
            "raft_tpu/multiraft/chaos.py": _FIXTURE_CHAOS,
        },
    )
    msgs = [v.message for v in gc018(vs)]
    assert any("does not call runner_variants()" in m for m in msgs)
    assert any("hand-listed runner graph row" in m for m in msgs)


def test_gc018_derived_inventory_passes(tmp_path):
    good = tmp_path / "tools" / "graftcheck" / "trace" / "inventory.py"
    good.parent.mkdir(parents=True)
    good.write_text(
        "def _runner_specs(schedules):\n"
        "    return [v.name for v in schedules.runner_variants()]\n"
    )
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/schedules.py": schedules_fixture(),
            "raft_tpu/multiraft/chaos.py": _FIXTURE_CHAOS,
        },
    )
    assert gc018(vs) == []


def test_gc018_missing_probe_flags(tmp_path):
    sched = schedules_fixture().replace('probe_for="chaos"', 'probe_for=""')
    vs = run_engine_on(
        tmp_path,
        {
            "raft_tpu/multiraft/schedules.py": sched,
            "raft_tpu/multiraft/chaos.py": _FIXTURE_CHAOS,
        },
    )
    assert any("probe" in v.message for v in gc018(vs))


# --- GC019 phase-budget (stdlib unit tests over check_phase_budget) ---


from tools.graftcheck.trace import budget as budget_mod  # noqa: E402


def _gc019_fixture():
    from raft_tpu.multiraft import schedules

    var = schedules.RunnerVariant(
        name="chaos_runner", base="step", phases=("chaos",),
        builder="chaos", probe_for="chaos",
    )
    doc = {
        "phases": {"chaos": 90},
        "runners": {
            "chaos_runner": {
                "base": "step", "phases": ["chaos"], "predicted": 190,
                "residual_pct": 0.0,
            },
        },
        "phase_tolerance_pct": 2.0,
    }
    return var, doc


def test_gc019_within_tolerance_passes():
    var, doc = _gc019_fixture()
    measured = {"step": 100, "chaos_runner": 192}  # +1.05% residual
    vs, diff = budget_mod.check_phase_budget(
        measured, doc, "jaxpr_budget.json", [var]
    )
    assert vs == []
    assert diff["runners"]["chaos_runner"]["status"] == "ok"


def test_gc019_phase_overrun_flags():
    # The duplicated-lowering failure mode: the variant's eqn count
    # outgrows base + phase budgets past the recorded residual.
    var, doc = _gc019_fixture()
    measured = {"step": 100, "chaos_runner": 240}  # +26.3% residual
    vs, diff = budget_mod.check_phase_budget(
        measured, doc, "jaxpr_budget.json", [var]
    )
    assert len(vs) == 1
    assert vs[0].rule_id == "GC019"
    assert "lowered more than once" in vs[0].message
    assert diff["runners"]["chaos_runner"]["status"] == "over"


def test_gc019_shrinkage_never_fails():
    var, doc = _gc019_fixture()
    measured = {"step": 100, "chaos_runner": 150}  # well under predicted
    vs, diff = budget_mod.check_phase_budget(
        measured, doc, "jaxpr_budget.json", [var]
    )
    assert vs == []


def test_gc019_unrecorded_variant_flags():
    var, doc = _gc019_fixture()
    doc = dict(doc, runners={})
    measured = {"step": 100, "chaos_runner": 192}
    vs, _ = budget_mod.check_phase_budget(
        measured, doc, "jaxpr_budget.json", [var]
    )
    assert any("no recorded GC019 residual" in v.message for v in vs)


def test_gc019_missing_sections_flag():
    var, _ = _gc019_fixture()
    vs, _ = budget_mod.check_phase_budget(
        {"step": 100, "chaos_runner": 192}, {"graphs": {}},
        "jaxpr_budget.json", [var],
    )
    assert any("phase decomposition" in v.message for v in vs)


def test_gc019_stale_entry_only_on_full_registry():
    var, doc = _gc019_fixture()
    doc["runners"]["ghost_runner"] = dict(doc["runners"]["chaos_runner"])
    measured = {"step": 100, "chaos_runner": 192}
    vs_full, _ = budget_mod.check_phase_budget(
        measured, doc, "jaxpr_budget.json", [var], full_registry=True
    )
    assert any("ghost_runner" in v.message for v in vs_full)
    vs_part, _ = budget_mod.check_phase_budget(
        measured, doc, "jaxpr_budget.json", [var], full_registry=False
    )
    assert not any("ghost_runner" in v.message for v in vs_part)
