"""Test configuration.

Multi-chip sharding is tested on a virtual 8-device CPU mesh: the env vars
MUST be set before jax is imported anywhere in the test process.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from raft_tpu.platform import (  # noqa: E402
    enable_compile_cache,
    force_virtual_cpu,
    require_virtual_cpu,
)

force_virtual_cpu(8)
require_virtual_cpu(8)
# Persistent XLA compile cache (JAX_COMPILATION_CACHE_DIR, else the fixed
# .jax_cache/ in the checkout; CI caches the directory between runs):
# compile seconds are tier-1 budget.
enable_compile_cache()
