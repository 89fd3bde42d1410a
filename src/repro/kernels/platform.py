"""Where the Pallas kernels run: compiled by Mosaic, or interpreted.

The one platform check of the repo.  Mosaic compiles kernels for the TPU
only; on any other backend (the CPU test container) the kernels run in
``interpret=True`` mode, where their bodies execute as plain jnp ops.
Every ``interpret=None`` entry point resolves through
:func:`resolve_interpret`, so a test that has to steer the choice — for
example to compile a kernel for a described TPU topology from a CPU
process — patches :func:`interpret_default` and nothing else.

The device table holds what the kernels assume about a chip.  A TPU whose
``device_kind`` is not in it is an error, not a default
(:func:`check_tpu`).
"""
from __future__ import annotations

from typing import Optional

import jax

# Per-core VMEM capacity by ``device_kind``.  TPU v5e: 128 MiB; measured
# by AOT-compiling a kernel whose double-buffered blocks need 74 MiB
# (accepted) and 148 MiB (refused: "would exceed memory (size=134217728)")
# for a described v5e:2x2 topology.
VMEM_CAPACITY = {
    "TPU v5 lite": 128 * 2 ** 20,
}


def interpret_default() -> bool:
    """True unless JAX's default backend is a TPU."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret=None`` → the platform default; explicit values pass."""
    return interpret_default() if interpret is None else bool(interpret)


def check_tpu() -> dict:
    """The attached accelerator, or an error.

    Returns ``{"platform", "kind", "count"}`` as JAX reports them.  Raises
    ``RuntimeError`` when the first device is not a TPU, or when its
    ``device_kind`` is not in :data:`VMEM_CAPACITY` — the kernels' VMEM
    budget (:data:`repro.sparse.plan.VMEM_BYTES`) is only known to fit
    the chips listed there.
    """
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    if d.platform != "tpu":
        raise RuntimeError(f"no TPU attached: {info}")
    if d.device_kind not in VMEM_CAPACITY:
        raise RuntimeError(
            f"unknown TPU device_kind {d.device_kind!r}: add its VMEM "
            f"capacity to repro.kernels.platform.VMEM_CAPACITY "
            f"(known: {sorted(VMEM_CAPACITY)})")
    return info
