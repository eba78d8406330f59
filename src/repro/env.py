"""Backend-aware XLA environment setup (applied *before* jax initializes).

XLA reads ``XLA_FLAGS`` once, at backend initialization — flags appended
after the first ``import jax`` touch are silently ignored, and *unknown*
flags can abort process startup. This module therefore

  * never imports jax at module level (``repro`` is a namespace package,
    so ``from repro import env`` stays jax-free);
  * gates every flag on the resolved backend: GPU gets the
    async-collective / latency-hiding scheduler flags that let the
    interior/boundary-split ``dist_spmv`` actually run its interior SpMV
    while the halo ``ppermute`` is in flight, CPU gets only the
    forced-host-device-count flag (the SPMD test/bench harness);
  * merges with any caller-set ``XLA_FLAGS``, replacing only the flags it
    manages — a user's unrelated flags pass through untouched;
  * keeps JAX's persistent compile cache in ``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` already names a directory (then
    that one is used, and no other is set).

Entry points (``examples/hpcg_solve.py``, ``bench/run.py``,
``repro.launch.serve``) call :func:`apply` first thing::

    from repro import env
    env.apply(host_devices=8)      # CPU SPMD: 8 forced host devices
    import jax                     # now initializes with the flags set

:func:`describe` reports what was applied.
"""
from __future__ import annotations

import os
import sys
import warnings
from typing import Dict, List, Optional

# Flags this module owns; merge replaces exactly these, nothing else.
_MANAGED_PREFIXES = (
    "--xla_force_host_platform_device_count",
    "--xla_gpu_enable_async_collectives",
    "--xla_gpu_enable_latency_hiding_scheduler",
    "--xla_gpu_enable_highest_priority_async_stream",
)

# The async-collective set: the GPU scheduler only overlaps a collective
# with independent compute when these are on (bayespec's env pattern).
_GPU_FLAGS = (
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)

_applied: Optional[Dict[str, object]] = None

# <checkout>/.jax_cache: this file is <checkout>/src/repro/env.py
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def resolve_backend(backend: Optional[str] = None) -> str:
    """Resolve the target backend without importing jax.

    Priority: explicit argument > ``JAX_PLATFORMS``/``JAX_PLATFORM_NAME``
    env > ``REPRO_BACKEND`` env > ``"cpu"``.
    """
    if backend:
        return backend.lower()
    for var in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME", "REPRO_BACKEND"):
        v = os.environ.get(var)
        if v:
            return v.split(",")[0].strip().lower()
    return "cpu"


def _merge_flags(existing: str, managed: List[str]) -> str:
    """Union of the caller's XLA_FLAGS and ours; ours win on overlap."""
    kept = [f for f in existing.split()
            if not any(f.startswith(p) for p in _MANAGED_PREFIXES)]
    return " ".join(kept + managed).strip()


def apply(backend: Optional[str] = None,
          host_devices: Optional[int] = None) -> Dict[str, object]:
    """Set ``XLA_FLAGS`` for ``backend`` (resolved per :func:`resolve_backend`).

    ``host_devices`` forces N host (CPU) devices — the SPMD harness for
    distributed tests/benches on machines without N accelerators. On GPU
    backends the async-collective/latency-hiding flags are added; on CPU
    they are *not* (unknown or inapplicable flags can abort XLA startup,
    so every flag is backend-gated).

    ``JAX_COMPILATION_CACHE_DIR`` is set to :data:`COMPILE_CACHE_DIR`
    when unset; a caller's value is left alone.

    Idempotent and safe to call multiple times; warns (but still sets the
    environment for child processes) when jax already initialized in this
    process, since the running backend will not see the change.
    """
    global _applied
    bk = resolve_backend(backend)
    managed: List[str] = []
    if host_devices is not None and int(host_devices) > 0:
        managed.append(
            f"--xla_force_host_platform_device_count={int(host_devices)}")
    if bk in ("gpu", "cuda", "rocm"):
        managed.extend(_GPU_FLAGS)

    if "jax" in sys.modules and managed:
        warnings.warn(
            "repro.env.apply() called after jax was imported: the current "
            "process's XLA backend is already initialized and will not see "
            "these flags (child processes will).", RuntimeWarning,
            stacklevel=2)

    flags = _merge_flags(os.environ.get("XLA_FLAGS", ""), managed)
    if flags:
        os.environ["XLA_FLAGS"] = flags
    cache_dir = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                      COMPILE_CACHE_DIR)
    _applied = {"backend": bk, "host_devices": host_devices,
                "managed_flags": list(managed), "xla_flags": flags,
                "compile_cache_dir": cache_dir}
    return dict(_applied)


def describe() -> Dict[str, object]:
    """What :func:`apply` last did; reads the live environment when apply
    was never called in this process."""
    if _applied is not None:
        return dict(_applied)
    return {"backend": resolve_backend(), "host_devices": None,
            "managed_flags": [], "xla_flags": os.environ.get("XLA_FLAGS", ""),
            "compile_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")}
