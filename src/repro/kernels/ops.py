"""Jit'd public wrappers for the Pallas kernels.

Off the TPU kernels run in ``interpret=True`` mode — the kernel body
executes as jnp ops, which is the validation path; on the TPU they
compile to Mosaic.  ``interpret=None`` resolves through
:func:`repro.kernels.platform.resolve_interpret`.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import bitmap as bmod
from repro.core import im2col as i2c
from repro.kernels.bitmap_encode import bitmap_encode_pallas
from repro.kernels.platform import resolve_interpret
from repro.kernels.bitmap_spgemm import (  # noqa: F401  (re-exports)
    bitmap_spgemm,
    bitmap_spgemm_kcondensed,
    bitmap_spgemm_kfused,
    bitmap_spgemm_kfused_planned,
    bitmap_spgemm_planned,
    kcondense,
    plan_slices,
)
from repro.kernels.sparse_im2col import sparse_im2col_pallas


def bitmap_encode(x: jax.Array, interpret: Optional[bool] = None):
    """(C, H, W) dense → (packed bits, row-condensed values)."""
    return bitmap_encode_pallas(x, interpret=resolve_interpret(interpret))


def rowpacked_to_flat(low_bits: jax.Array, low_vals: jax.Array,
                      ow: int, p: int) -> i2c.LoweredBitmap:
    """Kernel output layout → flat-P :class:`~repro.core.im2col.LoweredBitmap`.

    The im2col kernels emit the lowered bitmap per-output-row packed —
    (KKC, OH, ceil(OW/32)), each feature row starting a fresh word for
    lane alignment — while the planner/dispatch layout packs over the
    flat P axis.  This is the one place that conversion lives (and the
    round-trip the property tests pin): unpack each row to its OW bits,
    concatenate to (KKC, P), repack.  Values/counts are layout-invariant.
    """
    mask = bmod.unpack_bits(low_bits, axis=-1)[..., :ow]   # (KKC, OH, OW)
    flat = mask.reshape(-1, p)
    packed = bmod.pack_bits(jnp.pad(flat, ((0, 0), (0, (-p) % bmod.WORD))),
                            axis=1)
    counts = jnp.sum(flat, axis=1, dtype=jnp.int32)
    return i2c.LoweredBitmap(bitmap=packed, values=low_vals, counts=counts)


def sparse_im2col(
    x: jax.Array, kh: int, kw: int, stride: int = 1,
    interpret: Optional[bool] = None,
) -> i2c.LoweredBitmap:
    """Implicit bitmap im2col of an (H, W, C) feature map.

    The encode kernel condenses each feature-map row; the im2col kernel
    lowers every (dy, dx, c) window row from that form, any stride.
    """
    interp = resolve_interpret(interpret)
    h, w, c = x.shape
    oh, ow = i2c.out_size(h, kh, stride), i2c.out_size(w, kw, stride)
    p = oh * ow
    xc = jnp.moveaxis(x, -1, 0)                        # (C, H, W)
    bits, cond = bitmap_encode_pallas(xc, interpret=interp)
    low_bits, low_vals = sparse_im2col_pallas(
        cond, bits, kh=kh, kw=kw, stride=stride, interpret=interp)
    return rowpacked_to_flat(low_bits, low_vals, ow, p)
