"""Production mesh construction.

A FUNCTION (not module-level state) so importing never touches jax device
state; the dry-run sets XLA_FLAGS for 512 host devices before any import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the model code places activations with sharding
    # constraints and lets the partitioner propagate (GSPMD); explicit
    # axes — jax.make_mesh's default — would type every array with its
    # sharding and refuse ops whose operands disagree.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 (one v5e pod slice) or 2×16×16 (two pods) device mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """(data, model) mesh over the devices present: ``model_parallel``
    on the model axis, the rest on data."""
    n = len(jax.devices())
    dp = max(n // model_parallel, 1)
    return _mesh((dp, model_parallel), ("data", "model"))
