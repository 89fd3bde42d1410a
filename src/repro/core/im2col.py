"""im2col variants (paper §IV): dense, outer-product-friendly, bitmap-sparse.

Conventions.  Feature maps are NHWC.  For a (KH, KW) kernel with stride S
and VALID padding, the *lowered* feature map in inner-product layout is
``L: (P, KH*KW*C)`` with P = OH*OW output positions (one row per sliding
window, paper Fig. 1 / Fig. 10a).  The outer-product-friendly layout
(paper Fig. 10b) is its transpose ``L^T: (KH*KW*C, P)`` generated a
*column at a time* by a 1×B zig-zag sliding window, B = (R−K+S)/S; GEMM is
then ``out(F, P) = W_flat(F, KH*KW*C) @ L^T`` so that each row k of L^T is
a B-operand row for the outer-product SpGEMM (condensed row-major).

The bitmap sparse im2col (paper Fig. 11, steps S0–S4) never touches the
dense lowered matrix: it masks/shifts the packed *bitmap* of each feature
map row, accumulates shifted-out bits (cumulative popcount) as offsets into
the row's condensed values, and emits each lowered row directly in the
condensed (bitmap, values) form that :mod:`repro.core.spgemm` consumes.
The Pallas realisation is ``repro.kernels.sparse_im2col``; the functions
here are the jnp dataflow-faithful references.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm


def out_size(h: int, k: int, s: int) -> int:
    return (h - k) // s + 1


# ---------------------------------------------------------------------------
# dense im2col (inner- and outer-product layouts)
# ---------------------------------------------------------------------------

def extract_patches(x: jax.Array, kh: int, kw: int, stride: int) -> jax.Array:
    """x: (H, W, C) → patches (OH, OW, KH, KW, C), VALID padding."""
    h, w, _ = x.shape
    oh, ow = out_size(h, kh, stride), out_size(w, kw, stride)
    rows = jnp.arange(oh)[:, None] * stride + jnp.arange(kh)[None, :]
    cols = jnp.arange(ow)[:, None] * stride + jnp.arange(kw)[None, :]
    return x[rows[:, None, :, None], cols[None, :, None, :], :]


def im2col_dense(x: jax.Array, kh: int, kw: int, stride: int) -> jax.Array:
    """Inner-product friendly lowered map: (P, KH*KW*C)."""
    p = extract_patches(x, kh, kw, stride)
    oh, ow, _, _, c = p.shape
    return p.reshape(oh * ow, kh * kw * c)


def im2col_outer(x: jax.Array, kh: int, kw: int, stride: int) -> jax.Array:
    """Outer-product friendly lowered map L^T: (KH*KW*C, P).

    Row k = (dy, dx, c) of L^T is the feature map sampled at offset
    (dy, dx) channel c over all output positions — the column-at-a-time
    zig-zag generation of paper Fig. 10b lands rows in exactly this order.
    """
    p = extract_patches(x, kh, kw, stride)
    oh, ow, _, _, c = p.shape
    return p.transpose(2, 3, 4, 0, 1).reshape(kh * kw * c, oh * ow)


# ---------------------------------------------------------------------------
# bitmap sparse im2col (paper Fig. 11)
# ---------------------------------------------------------------------------

class LoweredBitmap(NamedTuple):
    """Lowered feature map in condensed bitmap encoding (B operand).

    bitmap : (KKC, ceil(P/32)) packed uint32 — the lowered bitmap (S2).
    values : (KKC, P) row-condensed non-zeros (left-pushed), zero padded.
    counts : (KKC,) int32 non-zeros per lowered row (S4 popcount output).
    """
    bitmap: jax.Array
    values: jax.Array
    counts: jax.Array

    def decode(self) -> jax.Array:
        p = self.values.shape[1]
        padded = bm.decode(bm.BitmapMatrix(
            values=jnp.pad(self.values,
                           ((0, 0), (0, self.bitmap.shape[1] * bm.WORD - p))),
            bitmap=self.bitmap, counts=self.counts, order="row"))
        return padded[:, :p]


def im2col_bitmap(x: jax.Array, kh: int, kw: int, stride: int
                  ) -> LoweredBitmap:
    """Bitmap-based sparse im2col, dataflow-faithful to paper Fig. 11.

    S0  encode each feature-map row as (bitmap, condensed values).
    S1  take the bitmap row + its condensed values.
    S2  mask/shift the bitmap row per output column → lowered bitmap bits.
    S3  accumulated shifted-out bits (cumulative popcount) → value offsets.
    S4  popcount inside the mask → segment lengths; gather condensed values.

    Requires P = OH*OW to be a multiple of 32 only for the packed output;
    inputs are padded internally.  x: (H, W, C).
    """
    h, w, c = x.shape
    oh, ow = out_size(h, kh, stride), out_size(w, kw, stride)
    p = oh * ow

    # channel-first working layout: (C, H, W)
    xc = jnp.moveaxis(x, -1, 0)
    maskc = bm.nonzero(xc)                            # S0 bitmap
    # cumulative popcount per feature-map row: offset of each position's
    # value inside the row's condensed value list (S3 shifted-out bits).
    cumc = jnp.cumsum(maskc, axis=2) - maskc          # exclusive prefix
    # condensed values per (channel, row) fiber (S0 value field)
    condc = bm._condense(xc, maskc, axis=2)           # (C, H, W)

    # For lowered row k=(dy, dx, ch) and output position (oy, ox):
    #   source pixel = (ch, oy*S + dy, ox*S + dx)
    ys = jnp.arange(kh)[:, None] + jnp.arange(oh)[None, :] * stride  # (KH,OH)
    xs = jnp.arange(kw)[:, None] + jnp.arange(ow)[None, :] * stride  # (KW,OW)
    idx_c = jnp.arange(c)[None, None, :, None, None]
    idx_y = ys[:, None, None, :, None]
    idx_x = xs[None, :, None, None, :]

    # lowered bitmap bits[k, p]  (S2: mask + shift on the bitmap row)
    bits = maskc[idx_c, idx_y, idx_x]                 # (KH,KW,C,OH,OW)
    # offsets[k, p] into the row-condensed values (S3)
    offs = cumc[idx_c, idx_y, idx_x]
    # gather values via (row, accumulated-popcount offset)  (S4)
    vals = condc[idx_c, idx_y, offs]
    vals = jnp.where(bits, vals, 0)

    # (KH,KW,C,OH,OW) → (KKC, P) outer-friendly order
    bits = bits.reshape(kh * kw * c, p)
    vals = vals.reshape(kh * kw * c, p)

    pad = (-p) % bm.WORD
    bits_p = jnp.pad(bits, ((0, 0), (0, pad)))
    packed = bm.pack_bits(bits_p, axis=1)
    counts = jnp.sum(bits, axis=1, dtype=jnp.int32)
    cond_vals = bm._condense(vals, bits, axis=1)
    return LoweredBitmap(bitmap=packed, values=cond_vals, counts=counts)


# ---------------------------------------------------------------------------
# CSR im2col (comparison baseline of paper Table III)
# ---------------------------------------------------------------------------

class CSRMatrix(NamedTuple):
    data: jax.Array      # (nnz_cap,)
    indices: jax.Array   # (nnz_cap,) column index per non-zero
    indptr: jax.Array    # (rows+1,)
    shape: Tuple[int, int]


def csr_encode(x: jax.Array) -> CSRMatrix:
    """Dense (R, C) → CSR with static capacity R*C (JAX static shapes)."""
    r, c = x.shape
    mask = (x != 0).reshape(-1)
    order = jnp.argsort(~mask, stable=True)
    data = jnp.where(mask, x.reshape(-1), 0)[order]
    cols = jnp.where(mask, jnp.tile(jnp.arange(c), r), 0)[order]
    row_nnz = jnp.sum(x != 0, axis=1)
    indptr = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(row_nnz).astype(jnp.int32)])
    return CSRMatrix(data=data, indices=cols.astype(jnp.int32),
                     indptr=indptr, shape=(r, c))


def im2col_csr(x: jax.Array, kh: int, kw: int, stride: int) -> jax.Array:
    """CSR-based im2col: decode through indptr/indices (two data-dependent
    reads per non-zero — the overhead Table III quantifies), then lower.

    Returns the dense L^T for correctness comparison; the *cost* of this
    path is measured by ``benchmarks/bench_im2col.py``.
    """
    h, w, c = x.shape
    flat = x.reshape(h, w * c)
    csr = csr_encode(flat)
    # reconstruct via CSR traversal (scatter), then dense im2col.
    rows = jnp.searchsorted(csr.indptr, jnp.arange(csr.data.shape[0]),
                            side="right") - 1
    dense = jnp.zeros((h, w * c), x.dtype).at[rows, csr.indices].set(csr.data)
    dense = dense.reshape(h, w, c)
    return im2col_outer(dense, kh, kw, stride)
