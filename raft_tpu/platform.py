"""Platform selection: the ONE place that decides which backend a process
runs on, whether Pallas kernels are interpreted, and where the persistent
compile cache lives.

The rule: the program runs on a TPU.  XLA's CPU backend and Pallas interpret
mode are chosen only when the process was *explicitly* pinned to the CPU
(`JAX_PLATFORMS=cpu` in the environment, or `force_virtual_cpu()` — what the
tests, every CI step and the graftcheck audits do).  A CPU backend reached by
JAX's own "no TPU found" fallback is an error, never a run: a timing or a
result from it would carry a device metric's name without a device.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def force_virtual_cpu(n_devices: int) -> None:
    """Pin this process to the CPU platform with `n_devices` virtual devices.

    Mutates process-global state (env vars + jax.config) and does NOT restore
    it: the caller owns the whole process (pytest session, driver dryrun
    subprocess).  Do not call from a process that later needs the real TPU.

    Env vars cover the fresh-process case; jax.config covers jax already
    being imported with no live backend.  If a backend is already
    initialized the config updates raise RuntimeError, which we swallow —
    callers must check with require_virtual_cpu() if they need a hard
    guarantee.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    # Replace any pre-existing device-count flag (whatever its value) rather
    # than skipping: a stale count would silently survive into the backend.
    kept = [
        f
        for f in flags.split()
        if not f.startswith("--xla_force_host_platform_device_count")
    ]
    kept.append(f"--xla_force_host_platform_device_count={n_devices}")
    os.environ["XLA_FLAGS"] = " ".join(kept)

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n_devices)
    except RuntimeError:
        pass  # backend already initialized; caller checks require_virtual_cpu


def pinned_to_cpu() -> bool:
    """True iff this process was explicitly pinned to the CPU platform
    (JAX_PLATFORMS / jax_platforms begins with `cpu`)."""
    import jax

    platforms = jax.config.jax_platforms or ""
    return platforms.split(",")[0].strip().lower() == "cpu"


def backend() -> str:
    """The live backend, checked against the rule in the module docstring:
    "tpu", or "cpu" when (and only when) the process was explicitly pinned
    there.  Anything else — notably the CPU backend JAX falls back to when
    it finds no TPU — raises."""
    import jax

    live = jax.default_backend()
    if live == "tpu" or (live == "cpu" and pinned_to_cpu()):
        return live
    raise RuntimeError(
        f"no TPU: jax initialized the {live!r} backend without being "
        "asked to.  This program runs on a TPU; to run on the CPU "
        "(tests, CI, counts — never timings) pin the process explicitly "
        "with JAX_PLATFORMS=cpu."
    )


def pallas_interpret() -> bool:
    """Whether pl.pallas_call sites build in interpret mode: exactly when
    the process is explicitly pinned to the CPU (no Mosaic there).  On a
    TPU every kernel is compiled by Mosaic."""
    return backend() == "cpu"


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set jax already uses it and no
    directory is set in code; otherwise the cache lives at one fixed path
    inside the checkout.  Off the pinned CPU a program's metadata — its
    profiling names and its source positions — is part of its cache key
    (below), so there a checkout that moves never hits.  Call before the
    process's first compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # The multi-second compiles worth caching here are the link-path /
    # fused-kernel jits; sub-second ones would only bloat the cache.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not pinned_to_cpu():
        # The names a trace shows (raft_tpu.profiling's scopes) live in the
        # compiled program's metadata, which jax leaves out of the cache
        # key by default: a source that differs from a cached one by names
        # alone is handed that executable, and its trace the OLD names
        # (seen on the chip: PERF.md §6, PR 42).  On the chip the names are
        # part of the program.  CPU-pinned processes (the tests) never
        # trace a device, and keep sharing entries across call sites.
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", True
        )
    return path


def require_virtual_cpu(n_devices: int) -> list:
    """Hard guarantee that the live backend is CPU with >= n_devices virtual
    devices; returns the device list.  Raises one actionable RuntimeError for
    both failure modes (non-CPU backend already initialized, or too few
    virtual devices) instead of jax's opaque 'unknown backend'."""
    import jax

    try:
        devices = jax.devices("cpu")
        backend = jax.default_backend()
    except RuntimeError as e:
        raise RuntimeError(
            "a non-CPU backend was already initialized in this process; "
            "call force_virtual_cpu() before any jax backend use, or run "
            "in a fresh process."
        ) from e
    if len(devices) < n_devices or backend != "cpu":
        raise RuntimeError(
            f"need a virtual {n_devices}-device CPU backend but got "
            f"{backend} x{len(devices)}; call force_virtual_cpu() before "
            "any jax backend use, or run in a fresh process."
        )
    return devices
