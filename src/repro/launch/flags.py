"""Serving-grade XLA latency flags (SNIPPETS.md §1/§3).

Decode latency on real hardware is dominated by exposed communication:
the weight all-gathers and activation all-reduces of the decode mesh sit
on the critical path unless XLA's latency-hiding scheduler overlaps them
with compute and the collectives themselves run asynchronously on a
prioritized stream.  These are process-level options, not per-jit ones,
so they must reach the environment *before the backend initializes* —
the launch entry points apply them first thing, gated behind
``RunConfig.latency_flags`` / ``--latency-flags``.  The TPU set belongs
to libtpu and goes to ``LIBTPU_INIT_ARGS``; the others go to
``XLA_FLAGS``.

:func:`apply_latency_flags` is additive and idempotent: it appends only
the flags not already present, preserving whatever the environment set
(e.g. ``--xla_force_host_platform_device_count`` for host meshes, or the
machine's own ``LIBTPU_INIT_ARGS``), and returns the resulting flag
string so a dryrun test can assert the flags actually reach the
options.
"""
from __future__ import annotations

import os
import warnings
from typing import Mapping, MutableMapping, Optional, Tuple

# Async collectives + latency-hiding scheduler per platform (the
# serving sets of SNIPPETS.md §1/§3, pruned to options current XLA
# still registers — collectives are async by default since the
# xla_gpu_enable_async_collectives removal).  These MUST be applied
# only for the platform that will actually run: XLA's flag parser
# aborts the process on options its build doesn't register (the TPU
# set exists only in libtpu builds).
LATENCY_FLAGS: Mapping[str, Tuple[str, ...]] = {
    "gpu": (
        "--xla_gpu_enable_latency_hiding_scheduler=true",
        "--xla_gpu_enable_highest_priority_async_stream=true",
    ),
    "tpu": (
        "--xla_tpu_enable_async_collective_fusion=true",
        "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
        "--xla_tpu_overlap_compute_collective_tc=true",
    ),
    # the CPU container has no collective streams to hide latency on —
    # nothing to set, but the entry remains so launchers can gate
    # uniformly on any platform
    "cpu": (),
}


def latency_flags(platform: str) -> Tuple[str, ...]:
    """The flag set for ``platform`` (unknown platforms → none)."""
    return LATENCY_FLAGS.get(platform, ())


def flags_variable(platform: str) -> str:
    """The environment variable ``platform``'s flags are read from."""
    return "LIBTPU_INIT_ARGS" if platform == "tpu" else "XLA_FLAGS"


def resolve_platform(env: Mapping[str, str]) -> str:
    """Which platform this process will run on, *without* initializing
    the backend: the ``JAX_PLATFORMS``/``JAX_PLATFORM_NAME`` hint if
    set, the live backend if one already exists (too late to flag, but
    the right answer), else '' (unknown)."""
    for var in ("JAX_PLATFORMS", "JAX_PLATFORM_NAME"):
        val = env.get(var, "")
        if val:
            return val.split(",")[0].strip().lower()
    if env is os.environ and _backend_initialized():
        import jax
        return jax.default_backend()
    return ""


def _backend_initialized() -> bool:
    """Has any XLA backend already been created?  Read-only: must never
    itself trigger initialization (``jax.extend.backend.backends()``
    would), so it peeks at the bridge's registry of live clients."""
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:
        return False


def apply_latency_flags(platform: Optional[str] = None, *,
                        env: Optional[MutableMapping[str, str]] = None
                        ) -> str:
    """Append the latency flags to the platform's flag variable
    (:func:`flags_variable`; idempotent).

    Must run before the XLA backend initializes; once a backend exists
    the options are baked and this warns instead of silently having no
    effect.  ``platform`` defaults to :func:`resolve_platform` — only
    the running platform's flags are ever applied, because XLA aborts
    on options its build doesn't register.  Returns the resulting value
    of that variable.
    """
    if env is None:
        env = os.environ
        if _backend_initialized():
            warnings.warn(
                "apply_latency_flags: the XLA backend is already "
                "initialized — the appended flags will not take effect "
                "until the next process",
                RuntimeWarning, stacklevel=2)
    if platform is None:
        platform = resolve_platform(env)
        if not platform:
            warnings.warn(
                "apply_latency_flags: cannot determine the platform "
                "before backend init (set JAX_PLATFORMS or pass "
                "platform=...) — applying no flags",
                RuntimeWarning, stacklevel=2)
    var = flags_variable(platform)
    current = env.get(var, "")
    present = set(current.split())
    added = [f for f in latency_flags(platform) if f not in present]
    merged = " ".join(filter(None, [current.strip()] + added))
    env[var] = merged
    return merged
