"""Golden-file tests for fleet-health schedules using the datadriven
runner: each case drives a crash/append schedule DSL through ClusterSim
(collect_health=True) and records the end-state health planes + summary.

Case format::

    run rounds=N [append=A] [stall=S] [commit_stall=C] [churn=B] [topk=K]
    <schedule lines>
    ----
    <planes + summary>

Schedule lines (applied in order, one sim round per `step` unit):

    step N [append=A]     N rounds with the current crash mask
    crash peers=(1,2) [groups=(0,1)]   isolate peers (all groups if omitted)
    recover [groups=(...)]             clear crash state

Every case shares one (G=8, P=3, window=8) ClusterSim — state is reset
between cases and per-case thresholds only parameterize the (eager)
summary reduction — so the whole file pays for exactly one jit compile.
Regenerate with RAFT_TPU_REWRITE=1."""

import os

import jax.numpy as jnp
import numpy as np

from raft_tpu.datadriven import TestData, run_test, walk
from raft_tpu.multiraft import ClusterSim, SimConfig
from raft_tpu.multiraft import sim as sim_mod
from raft_tpu.multiraft.kernels import (
    HEALTH_COUNT_NAMES,
    HEALTH_PLANE_NAMES,
    health_summary,
)

TESTDATA = os.path.join(os.path.dirname(__file__), "testdata")

G, P, WINDOW = 8, 3, 8


class HealthHarness:
    """One ClusterSim (and ONE compile of its jitted step) for every case:
    thresholds vary per case, but they only parameterize the summary
    reduction, which runs eagerly here — so cases just reset sim state."""

    def __init__(self):
        self.cfg = SimConfig(
            n_groups=G, n_peers=P, collect_health=True, health_window=WINDOW
        )
        self.sim = ClusterSim(self.cfg)

    def handle(self, td: TestData) -> str:
        if td.cmd != "run":
            raise ValueError(f"unknown command {td.cmd}")

        def intarg(key, default):
            a = td.arg(key)
            return int(a.value) if a else default

        sim = self.sim
        sim.state = sim_mod.init_state(self.cfg)
        sim.reset_health()
        crashed = np.zeros((P, G), dtype=bool)

        def step(n, append):
            a = jnp.full((G,), append, jnp.int32)
            # A copy: on the CPU `jnp.asarray` may alias the numpy buffer,
            # the rounds are dispatched without waiting, and the schedule's
            # next `crash` / `recover` line writes `crashed` in place — on a
            # loaded machine (tier-1 under xdist) rounds still queued then
            # read the NEXT mask (ROADMAP C10: the failure that came and
            # went with the machine's load).
            mask = jnp.asarray(crashed.copy())
            for _ in range(n):
                sim.run_round(mask, a)

        for line in td.input.splitlines():
            toks = line.split()
            if not toks or toks[0].startswith("#"):
                continue
            cmd, args = toks[0], toks[1:]
            kv = dict(t.split("=", 1) for t in args if "=" in t)
            pos = [t for t in args if "=" not in t]

            def ids(key, default):
                v = kv.get(key)
                if v is None:
                    return list(default)
                return [int(x) for x in v.strip("()").split(",") if x]

            if cmd == "step":
                step(int(pos[0]), int(kv.get("append", 0)))
            elif cmd == "crash":
                for g in ids("groups", range(G)):
                    for p in ids("peers", []):
                        crashed[p - 1, g] = True
            elif cmd == "recover":
                for g in ids("groups", range(G)):
                    crashed[:, g] = False
            else:
                raise ValueError(f"{td.pos}: unknown schedule line {line!r}")

        planes = np.asarray(sim._health.planes)
        out = [
            f"{name}: {' '.join(str(v) for v in planes[i])}"
            for i, name in enumerate(HEALTH_PLANE_NAMES)
        ]
        # Per-case thresholds: run the summary reduction eagerly (tiny at
        # G=8) instead of through a per-case jitted ClusterSim.
        counts, hist, ids_, scores = health_summary(
            jnp.asarray(planes),
            intarg("stall", 6),
            intarg("commit_stall", 8),
            intarg("churn", 3),
            intarg("topk", 4),
        )
        out.append(
            " ".join(
                f"{k}={v}"
                for k, v in zip(HEALTH_COUNT_NAMES, np.asarray(counts))
            )
        )
        out.append(
            "lag_hist: " + " ".join(str(v) for v in np.asarray(hist))
        )
        out.append(
            "worst: "
            + " ".join(
                f"{g}:{s}"
                for g, s in zip(np.asarray(ids_), np.asarray(scores))
            )
        )
        return "\n".join(out)


def test_health_datadriven():
    harness = HealthHarness()  # shared: one jitted-step compile total
    ran = []

    def run(path):
        run_test(path, harness.handle)
        ran.append(path)

    walk(os.path.join(TESTDATA, "health"), run)
    assert ran
