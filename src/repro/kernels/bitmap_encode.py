"""Pallas TPU kernel: dense → bitmap encode (paper Fig. 2b / Fig. 11 S0).

Per channel, packs the non-zero mask of each feature-map row into uint32
words and front-packs ("condenses") the non-zero values with one-hot
selection matmuls — the MXU-friendly gather (DESIGN.md §2): for row x
with exclusive popcount prefix c(i), the selector S[i,t] = [c(i)=t ∧
x(i)≠0] satisfies (x @ S)[t] = t-th non-zero of x.  The selector is
built one 128 x 128 tile at a time — output lanes [128T, 128T+128) can
only come from input lanes i >= 128T, because c(i) <= i — so no
(W, W) matrix is ever materialised.

Everything is lane-shaped for Mosaic: the row is padded to a lane
multiple, the prefix count is a log-step scan of lane rotations
(:func:`lane_prefix_sum`; Mosaic has no cumsum), and the bit packing is
two small matmuls against power-of-two weights.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitmap import WORD

LANES = 128
_EXACT = jax.lax.Precision.HIGHEST


def lane_prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum along the last (lane) axis.

    Hillis–Steele scan: log2(W) lane rotations, each masked so nothing
    wraps around.  W must be a lane multiple on the TPU.
    """
    n = x.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    shift = 1
    while shift < n:
        rolled = pltpu.roll(x, shift, x.ndim - 1)
        x = x + jnp.where(lane >= shift, rolled, 0)
        shift *= 2
    return x


def pack_words(mask: jax.Array) -> jax.Array:
    """(H, W) bool → (H, W/32) uint32, LSB-first (``core.bitmap``).

    Two matmuls against power-of-two weights, one per 16-bit half, so
    every partial sum stays below 2^16 and is exact in f32."""
    h, w = mask.shape
    ww = w // WORD
    i = jax.lax.broadcasted_iota(jnp.int32, (w, ww), 0)
    word = jax.lax.broadcasted_iota(jnp.int32, (w, ww), 1)
    bit = i % WORD
    own = (i // WORD) == word
    m = mask.astype(jnp.float32)

    def half(lo):
        pw = jnp.where(own & (bit >= lo) & (bit < lo + 16),
                       jnp.left_shift(1, jnp.maximum(bit - lo, 0)), 0)
        return jnp.dot(m, pw.astype(jnp.float32), precision=_EXACT,
                       preferred_element_type=jnp.float32
                       ).astype(jnp.int32)

    packed = jnp.left_shift(half(16), 16) | half(0)
    return jax.lax.bitcast_convert_type(packed, jnp.uint32)


def condense_rows(x_ref, c_ref, out_ref) -> None:
    """Front-pack the non-zeros of each row into out_ref.

    x_ref: (H, W) f32 values, c_ref: (H, W) int32 exclusive prefix
    counts, out_ref: (H, W) f32, zeroed here.  Row r, output tile T
    gathers from input tiles I >= T with one (1, 128) x (128, 128)ᵀ
    matmul each.
    """
    h, w = x_ref.shape
    nt = w // LANES
    t_iota = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    out_ref[...] = jnp.zeros_like(out_ref)
    for r in range(h):
        def tile(t, _, r=r):
            def gather(i, acc):
                cols = pl.ds(pl.multiple_of(i * LANES, LANES), LANES)
                xs = x_ref[r:r + 1, cols]
                cs = c_ref[r:r + 1, cols]
                # selᵀ[t, i] = [c(i) = 128T + t ∧ x(i) ≠ 0]
                sel_t = (cs == t * LANES + t_iota) & (xs != 0)
                return acc + jax.lax.dot_general(
                    xs, sel_t.astype(jnp.float32), (((1,), (1,)), ((), ())),
                    precision=_EXACT, preferred_element_type=jnp.float32)

            acc = jax.lax.fori_loop(t, nt, gather,
                                    jnp.zeros((1, LANES), jnp.float32))
            out_ref[r:r + 1, pl.ds(pl.multiple_of(t * LANES, LANES),
                                   LANES)] = acc
            return 0

        jax.lax.fori_loop(0, nt, tile, 0)


def _encode_kernel(x_ref, bits_ref, cond_ref, xf_ref, cum_ref, acc_ref):
    x = x_ref[0]                               # (H, Wp)
    mask = x != 0
    m = mask.astype(jnp.int32)
    bits_ref[0] = pack_words(mask)
    xf_ref[...] = x.astype(jnp.float32)
    cum_ref[...] = lane_prefix_sum(m) - m      # exclusive prefix counts
    condense_rows(xf_ref, cum_ref, acc_ref)
    cond_ref[0] = acc_ref[...].astype(cond_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitmap_encode_pallas(
    x: jax.Array, *, interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """x: (C, H, W) dense → (bits (C,H,ceil(W/32)) uint32, cond (C,H,W))."""
    c, h, w = x.shape
    wp = -(-w // LANES) * LANES
    xp = jnp.pad(x, ((0, 0), (0, 0), (0, wp - w)))
    bits, cond = pl.pallas_call(
        _encode_kernel,
        grid=(c,),
        in_specs=[pl.BlockSpec((1, h, wp), lambda ci: (ci, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, h, wp // WORD), lambda ci: (ci, 0, 0)),
            pl.BlockSpec((1, h, wp), lambda ci: (ci, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((c, h, wp // WORD), jnp.uint32),
            jax.ShapeDtypeStruct((c, h, wp), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((h, wp), jnp.float32),
                        pltpu.VMEM((h, wp), jnp.int32),
                        pltpu.VMEM((h, wp), jnp.float32)],
        interpret=interpret,
    )(xp)
    return bits[:, :, :-(-w // WORD)], cond[:, :, :w]
