"""Pallas TPU kernel: bitmap-based implicit sparse im2col (paper Fig. 11).

One grid program per lowered row k = (dy, dx, c).  The program reads the
packed bitmap words and the row-condensed values of channel c (already in
VMEM — the "registers" of the paper's S1), then:

  S2  expands the bitmap words to lanes and decodes the condensed values
      back to their columns (one-hot selection by cumulative popcount —
      the accumulated shifted-out bits),
  S3  selects the window: feature rows ``oy*stride + dy`` and columns
      ``ox*stride + dx``, by one-hot selection matmuls,
  S4  emits the lowered row directly in (bitmap, condensed values) form:
      the window bits packed per output row, the values front-packed by
      their running popcount offset.

The lowered matrix never exists in HBM (implicit im2col); the outputs are
exactly the (bitmap, condensed values) operand the SpGEMM kernel's planner
consumes.  Every data-dependent gather is a one-hot matmul on 128 x 128
tiles and every prefix count a lane-rotation scan, because Mosaic lowers
neither gathers at data-dependent lane offsets nor cumsum; all strides
share the one kernel.

Output bitmap layout: per-output-row packed words, i.e. shape
(KKC, OH, ceil(OW/32)) — each feature row's window bits start a fresh word
(lane alignment); ``ops.py`` provides the conversion to the flat-P layout.
Values/counts layouts are identical to the jnp reference.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitmap import WORD
from repro.kernels.bitmap_encode import (LANES, lane_prefix_sum,
                                         pack_words)

_EXACT = jax.lax.Precision.HIGHEST


def _mm(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=_EXACT,
                               preferred_element_type=jnp.float32)


def _unpack_words(words: jax.Array, w: int) -> jax.Array:
    """(H, W/32) uint32 → (H, W) bool, LSB-first: each word is spread to
    its 32 lanes by a 0/1 matmul, one 16-bit half at a time (exact)."""
    ww = words.shape[1]
    wi = jax.lax.bitcast_convert_type(words, jnp.int32)
    own = (jax.lax.broadcasted_iota(jnp.int32, (ww, w), 1) // WORD
           == jax.lax.broadcasted_iota(jnp.int32, (ww, w), 0))
    spread = own.astype(jnp.float32)
    bit = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1) % WORD
    lo = _mm((wi & 0xFFFF).astype(jnp.float32), spread).astype(jnp.int32)
    hi = _mm(((wi >> 16) & 0xFFFF).astype(jnp.float32),
             spread).astype(jnp.int32)
    half = jnp.where(bit < 16, lo >> bit, hi >> jnp.maximum(bit - 16, 0))
    return (half & 1) == 1


def _im2col_kernel(vals_ref, bits_ref, out_bits_ref, out_vals_ref,
                   pos_in_ref, dense_ref, low_ref, pos_ref, acc_ref, *,
                   h: int,
                   oh: int, ow: int, oww: int, stride: int):
    dy = pl.program_id(1)
    dx = pl.program_id(2)
    wp = vals_ref.shape[2]
    owp = low_ref.shape[1]
    n_in, n_out, n_p = wp // LANES, owp // LANES, acc_ref.shape[1] // LANES
    t_rows = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    t_cols = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)

    # ---- S2: bitmap words → lanes; condensed values → their columns ----
    m = _unpack_words(bits_ref[0], wp).astype(jnp.int32)    # (H, Wp)
    # offset of each set bit's value in its row (-1: no value)
    pos_in_ref[...] = jnp.where(m == 1, lane_prefix_sum(m) - m, -1)
    for y in range(h):
        def decode(j, _, y=y):
            cols = pl.ds(pl.multiple_of(j * LANES, LANES), LANES)

            def gather(t, acc):
                src = vals_ref[0, y:y + 1, pl.ds(
                    pl.multiple_of(t * LANES, LANES), LANES)]
                # sel[t', j'] = [c(j) = 128T + t' ∧ m(j)]
                sel = pos_in_ref[y:y + 1, cols] == t * LANES + t_rows
                return acc + _mm(src.astype(jnp.float32),
                                 sel.astype(jnp.float32))

            dense_ref[y:y + 1, cols] = jax.lax.fori_loop(
                0, j + 1, gather, jnp.zeros((1, LANES), jnp.float32))
            return 0

        jax.lax.fori_loop(0, n_in, decode, 0)

    # ---- S3: window rows oy*stride + dy, then columns ox*stride + dx ----
    row_sel = (jax.lax.broadcasted_iota(jnp.int32, (oh, h), 0) * stride
               + dy == jax.lax.broadcasted_iota(jnp.int32, (oh, h), 1))
    rows = _mm(row_sel.astype(jnp.float32), dense_ref[...])  # (OH, Wp)
    dense_ref[0:oh, :] = rows

    def columns(x, _):
        def gather(j, acc):
            src = dense_ref[0:oh, pl.ds(pl.multiple_of(j * LANES, LANES),
                                        LANES)]
            ox = x * LANES + t_cols
            sel = (j * LANES + t_rows == ox * stride + dx) & (ox < ow)
            return acc + _mm(src, sel.astype(jnp.float32))

        lo = x * stride
        hi = jnp.minimum(lo + stride + 1, n_in)
        low_ref[:, pl.ds(pl.multiple_of(x * LANES, LANES), LANES)] = (
            jax.lax.fori_loop(lo, hi, gather,
                              jnp.zeros((oh, LANES), jnp.float32)))
        return 0

    jax.lax.fori_loop(0, n_out, columns, 0)

    # ---- S4: window bits per output row; values by popcount offset ----
    low = low_ref[...]                                       # (OH, OWp)
    active = low != 0
    out_bits_ref[0] = pack_words(active)[:, :oww]
    a = active.astype(jnp.int32)
    # non-zeros in the rows above each output row
    before = (jax.lax.broadcasted_iota(jnp.int32, (oh, oh), 1)
              < jax.lax.broadcasted_iota(jnp.int32, (oh, oh), 0))
    row_off = jnp.sum(_mm(before.astype(jnp.float32),
                          a.astype(jnp.float32)), axis=1,
                      keepdims=True).astype(jnp.int32)       # (OH, 1)
    pos_ref[...] = jnp.where(active, row_off + lane_prefix_sum(a) - a, -1)

    def emit(t, _):
        def row(oy, acc):
            def gather(x, acc):
                cols = pl.ds(pl.multiple_of(x * LANES, LANES), LANES)
                src = low_ref[pl.ds(oy, 1), cols]
                # selᵀ[t', x'] = [pos(oy, x) = 128T + t']
                sel_t = pos_ref[pl.ds(oy, 1), cols] == t * LANES + t_rows
                return acc + _mm(src, sel_t.astype(jnp.float32),
                                 contract=((1,), (1,)))

            return jax.lax.fori_loop(0, n_out, gather, acc)

        acc_ref[:, pl.ds(pl.multiple_of(t * LANES, LANES), LANES)] = (
            jax.lax.fori_loop(0, oh, row,
                              jnp.zeros((1, LANES), jnp.float32)))
        return 0

    jax.lax.fori_loop(0, n_p, emit, 0)
    out_vals_ref[0] = acc_ref[...].astype(out_vals_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("kh", "kw", "stride", "interpret"))
def sparse_im2col_pallas(
    cond_vals: jax.Array,   # (C, H, W) row-condensed values
    bits: jax.Array,        # (C, H, ceil(W/32)) packed uint32
    *, kh: int, kw: int, stride: int = 1, interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (lowered_bits (KKC, OH, OWw) uint32, lowered_vals (KKC, P))."""
    c, h, w = cond_vals.shape
    oh, ow = (h - kh) // stride + 1, (w - kw) // stride + 1
    oww = -(-ow // WORD)
    p = oh * ow
    wp = -(-w // LANES) * LANES
    owp = -(-ow // LANES) * LANES
    p_cap = -(-p // LANES) * LANES
    vals_p = jnp.pad(cond_vals, ((0, 0), (0, 0), (0, wp - w)))
    bits_p = jnp.pad(bits, ((0, 0), (0, 0), (0, wp // WORD - bits.shape[2])))
    kkc = kh * kw * c

    kernel = functools.partial(_im2col_kernel, h=h, oh=oh, ow=ow, oww=oww,
                               stride=stride)
    out_bits, out_vals = pl.pallas_call(
        kernel,
        grid=(c, kh, kw),
        in_specs=[
            pl.BlockSpec((1, h, wp), lambda ci, dy, dx: (ci, 0, 0)),
            pl.BlockSpec((1, h, wp // WORD), lambda ci, dy, dx: (ci, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, oh, oww),
                         lambda ci, dy, dx: ((dy * kw + dx) * c + ci, 0, 0)),
            pl.BlockSpec((1, 1, p_cap),
                         lambda ci, dy, dx: ((dy * kw + dx) * c + ci, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kkc, oh, oww), jnp.uint32),
            jax.ShapeDtypeStruct((kkc, 1, p_cap), cond_vals.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((h, wp), jnp.int32),
                        pltpu.VMEM((h, wp), jnp.float32),
                        pltpu.VMEM((oh, owp), jnp.float32),
                        pltpu.VMEM((oh, owp), jnp.int32),
                        pltpu.VMEM((1, p_cap), jnp.float32)],
        interpret=interpret,
    )(vals_p, bits_p)
    return out_bits, out_vals[:, 0, :p]
