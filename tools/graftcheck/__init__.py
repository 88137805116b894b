"""graftcheck: repo-specific static analysis for the TPU-kernel and parity
invariants (docs/STATIC_ANALYSIS.md).

Usage:  python -m tools.graftcheck raft_tpu tests docs README.md CHANGES.md

Rules (each with a `# graftcheck: allow-<rule> — <why>` escape hatch):

  GC001 no-implicit-dtype          explicit dtypes in device modules
  GC002 no-host-sync-in-jit        no host syncs in sim/kernels/pallas_step
  GC003 no-python-branch-on-traced no Python control flow on traced values
  GC004 metrics-guarded            metrics hooks behind the enabled-check
  GC005 citation-check             file:line cites well-formed + resolvable
  GC006 kernel-parity-map          kernels mapped to oracles and tested

Engine rules (cross-module abstract interpretation; run with --engine):

  GC007 shape-dtype                whole-program shape/dtype inference
  GC008 plane-overflow             int32 planes cannot wrap between drains
  GC009 traced-escape              no traced values into static-claimed params
  GC010 parity-obligations         kernel obligations extracted + baselined
"""

from .core import Context, Rule, SourceFile, Violation, run_paths
from .rules import all_rules

__all__ = [
    "Context",
    "Rule",
    "SourceFile",
    "Violation",
    "all_rules",
    "run_paths",
]
