"""Batched MultiRaft: the per-group Raft hot loop on TPU.

This package is the new thing this framework adds over the reference
(BASELINE.json north star): instead of G independent `RawNode` event loops,
per-group integer state lives in dense `[G]` / `[G, P]` device arrays and the
hot paths — tick timers, quorum commit indices, vote tallies, progress
updates — run as fused XLA kernels advancing every group in lockstep.

Modules:
  kernels   — pure jnp kernel functions (the scalar oracle lives in
              raft_tpu.quorum / raft_tpu.tracker)
  sim       — ClusterSim: closed-loop on-device simulation of G groups × P
              peers (what the benchmark drives; BASELINE configs 2-5)
  simref    — ScalarCluster: the same lockstep protocol driven through real
              scalar Raft instances (the parity oracle)
  sharding  — mesh construction + shard_map'd step for multi-chip scale-out
  driver    — MultiRaftNode: device-resident tick/commit for this node's G
              groups with host-side message materialization (sparse)
"""

from .kernels import (
    committed_index,
    committed_index_grouped,
    joint_committed_index,
    tick_kernel,
    timeout_draw,
    vote_result,
)
from .sim import (
    BlackboxState,
    ClusterSim,
    HealthState,
    SimConfig,
    SimState,
    init_blackbox,
    init_health,
    read_index,
)
from .simref import (
    ChaosOracle,
    HealthOracle,
    ReadOracle,
    ReconfigOracle,
    ScalarCluster,
    TransferOracle,
)

__all__ = [
    "ChaosOracle",
    "ReadOracle",
    "ReconfigOracle",
    "TransferOracle",
    "committed_index",
    "committed_index_grouped",
    "joint_committed_index",
    "vote_result",
    "tick_kernel",
    "timeout_draw",
    "ClusterSim",
    "SimConfig",
    "SimState",
    "HealthState",
    "init_health",
    "BlackboxState",
    "init_blackbox",
    "ScalarCluster",
    "HealthOracle",
    "read_index",
    # submodules imported lazily to keep jax-light paths cheap:
    #   .chaos     fault-plan compiler + compiled-schedule runner
    #   .forensics black-box incident extraction + one-group scalar repro
    #   .reconfig  membership-churn plan compiler + compiled-schedule runner
    #   .autopilot closed-loop control plane (kick/transfer/evacuate)
    #   .workload  client read/write plan compiler + compiled-schedule runner
    #   .driver    MultiRaft host driver
    #   .native    NativeMultiRaft C++ engine bindings
    #   .pallas_step  fused steady-round kernels
    #   .checkpoint   save/load device state
    #   .sharding     mesh + sharded step + global status
]
