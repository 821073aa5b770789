"""Computation-environment configuration for launches and host simulation.

One place owns the XLA process flags every multi-device entry point needs,
instead of each test/benchmark hand-rolling its own ``XLA_FLAGS`` string:

- ``host_sim_flags(n)`` / ``host_sim_env(n)`` — simulate an N-device
  (multi-host-shaped) platform on CPU via
  ``--xla_force_host_platform_device_count``. Subprocess-based tests
  (``tests/test_distributed.py``, ``bench/tests/test_bench_sharded.py``)
  build their child environment here, so the flag — which must be set
  before the child's first jax init — is spelled once.
- ``gpu_comm_flags()`` — the GPU latency-hiding / async-collective flag
  set (XLA GPU performance-tips guidance): overlaps the hierarchical
  superstep's cross-host collectives with compute instead of serializing
  on them. Harmless to request on CPU; only an XLA:GPU backend reads them.
- ``configure(...)`` — compose both into ``os.environ`` for a process
  that has NOT yet initialized jax (flags are read at first init; calling
  after is a silent no-op, so this raises instead).
- ``enable_compile_cache()`` — JAX's persistent compilation cache for the
  entry points (``chip_smoke.py``, ``launch.serve``):
  ``JAX_COMPILATION_CACHE_DIR`` when it is set, else one fixed directory
  of the checkout (``.jax_cache``, git-ignored). The path is part of the
  cache key, so it never comes from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import sys

HOST_SIM_FLAG = "--xla_force_host_platform_device_count"
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: src/repro/launch/env.py is three levels down
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

# XLA:GPU flags that keep the sharded superstep's collectives off the
# critical path (async collectives + latency-hiding scheduler) and enable
# the fusion paths the per-round kernels benefit from. See
# https://jax.readthedocs.io/en/latest/gpu_performance_tips.html
GPU_COMM_FLAGS = (
    "--xla_gpu_enable_triton_softmax_fusion=true",
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def host_sim_flags(n_devices: int) -> str:
    """The flag forcing ``n_devices`` fake host-platform devices."""
    return f"{HOST_SIM_FLAG}={int(n_devices)}"


def gpu_comm_flags() -> str:
    return " ".join(GPU_COMM_FLAGS)


def xla_flags(n_devices: int = 0, *, gpu_comm: bool = False,
              base: str | None = None) -> str:
    """Compose an ``XLA_FLAGS`` value: optional host-device simulation +
    optional GPU comm flags, appended to ``base`` (defaults to the current
    process's ``XLA_FLAGS``) without duplicating flags already present."""
    parts = (base if base is not None
             else os.environ.get("XLA_FLAGS", "")).split()
    if n_devices > 1 and not any(p.startswith(HOST_SIM_FLAG) for p in parts):
        parts.append(host_sim_flags(n_devices))
    if gpu_comm:
        parts.extend(f for f in GPU_COMM_FLAGS if f not in parts)
    return " ".join(parts)


def host_sim_env(n_devices: int, *, src_path: str | None = None,
                 gpu_comm: bool = False) -> dict:
    """Child-process environment for an ``n_devices``-simulated run.

    The standard subprocess idiom of the multi-device tests: inherit
    the parent environment, force the fake-device flag (and optionally the
    GPU comm set), and put ``src_path`` on ``PYTHONPATH`` so ``-c``
    scripts can import ``repro``.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = xla_flags(n_devices, gpu_comm=gpu_comm,
                                 base=env.get("XLA_FLAGS", ""))
    if src_path is not None:
        env["PYTHONPATH"] = src_path
    return env


def configure(n_devices: int = 0, *, gpu_comm: bool = False) -> str:
    """Set ``XLA_FLAGS`` for THIS process, before jax initializes.

    Raises if jax already initialized a backend — the flags would be
    silently ignored, which is exactly the failure mode this module
    exists to prevent. Returns the flags it set.
    """
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            initialized = jax._src.xla_bridge._backends  # noqa: SLF001
        except AttributeError:  # pragma: no cover - jax internals moved
            initialized = None
        if initialized:
            raise RuntimeError(
                "launch.env.configure() called after jax backend init; "
                "XLA_FLAGS would be ignored. Call before importing/using "
                "jax, or launch a subprocess with host_sim_env().")
    flags = xla_flags(n_devices, gpu_comm=gpu_comm)
    os.environ["XLA_FLAGS"] = flags
    return flags


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other; otherwise the cache goes to ``REPO_CACHE_DIR``.
    Every compile is cached, however short: kernels compile in well under
    JAX's default one-second threshold."""
    import jax
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
