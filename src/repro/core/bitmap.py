"""Bitmap sparse encoding (paper §III-A, Fig. 2b / Fig. 9).

A sparse matrix is represented by a two-tuple *(bitmap, condensed values)*:
the bitmap holds 1-bits at non-zero positions, and the value buffer holds
the non-zeros condensed ("pushed") along the contraction-friendly axis —
column-major for the left operand A, row-major for the right operand B
(paper Fig. 4c).  The two-level variant (paper Fig. 9) additionally stores
a *tile bitmap* ("warp-bitmap") with one bit per (tile_m × tile_k) tile so
that all-zero tiles can be skipped wholesale and partial-matrix addressing
stays tile-local.

JAX needs static shapes, so condensed buffers are allocated at full
capacity and zero-padded; the *speedup* of the scheme is carried by the
counts/bitmaps (consumed by the Pallas kernels and the skip-cost models in
``repro.core.stats``), not by shrinking buffers.

Bitmaps are packed into ``uint32`` words, 32 positions per word, LSB =
lowest index — the layout the Pallas kernels consume directly.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

WORD = 32  # bits per packed bitmap word


# ---------------------------------------------------------------------------
# packing / popcount primitives
# ---------------------------------------------------------------------------

def pack_bits(mask: jax.Array, axis: int = -1) -> jax.Array:
    """Pack a boolean mask into uint32 words along ``axis``.

    The axis length must be a multiple of 32. Bit i of word w corresponds to
    position w*32+i (LSB-first).
    """
    mask = jnp.moveaxis(mask, axis, -1)
    *lead, n = mask.shape
    if n % WORD:
        raise ValueError(f"bitmap axis ({n}) must be a multiple of {WORD}")
    m = mask.reshape(*lead, n // WORD, WORD).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(WORD, dtype=jnp.uint32))
    packed = jnp.sum(m * weights, axis=-1, dtype=jnp.uint32)
    return jnp.moveaxis(packed, -1, axis)


def pack_bits_padded(mask: jax.Array, axis: int = -1) -> jax.Array:
    """:func:`pack_bits` with the axis zero-padded to a WORD multiple.

    The one place the pad-then-pack rule lives — activation bitmaps and
    KV-cache occupancy bitmaps both use it, so the packed layout can
    never diverge between them.
    """
    mask = jnp.moveaxis(mask, axis, -1)
    pad = (-mask.shape[-1]) % WORD
    if pad:
        mask = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, pad)])
    return jnp.moveaxis(pack_bits(mask, axis=-1), -1, axis)


def unpack_bits(words: jax.Array, axis: int = -1) -> jax.Array:
    """Inverse of :func:`pack_bits` — uint32 words → boolean mask."""
    words = jnp.moveaxis(words, axis, -1)
    *lead, nw = words.shape
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    out = bits.reshape(*lead, nw * WORD).astype(bool)
    return jnp.moveaxis(out, -1, axis)


def popcount(words: jax.Array) -> jax.Array:
    """Per-word population count (the paper's POPC)."""
    return jax.lax.population_count(words)


def row_nnz(words: jax.Array, axis: int = -1) -> jax.Array:
    """Total number of set bits along a packed-word axis."""
    return jnp.sum(popcount(words).astype(jnp.int32), axis=axis)


# ---------------------------------------------------------------------------
# single-level bitmap encoding  (paper Fig. 2b)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BitmapMatrix:
    """Bitmap-encoded 2-D matrix.

    values    : (rows, cols) condensed non-zeros, zero padded.  For
                ``order='col'`` non-zeros of each *column* are pushed to the
                top (condensed along rows); for ``order='row'`` non-zeros of
                each *row* are pushed to the left.
    bitmap    : packed uint32 bitmap of the ORIGINAL positions.  For
                order='col' it is packed along rows: shape (rows//32, cols);
                for order='row' packed along cols: shape (rows, cols//32).
    counts    : per-column (order='col') / per-row (order='row') non-zero
                counts, int32.
    order     : 'col' (operand A) | 'row' (operand B).
    """
    values: jax.Array
    bitmap: jax.Array
    counts: jax.Array
    order: str = dataclasses.field(metadata=dict(static=True))

    @property
    def shape(self) -> Tuple[int, int]:
        if self.order == "col":
            return (self.bitmap.shape[0] * WORD, self.bitmap.shape[1])
        return (self.bitmap.shape[0], self.bitmap.shape[1] * WORD)

    @property
    def dtype(self):
        return self.values.dtype

    @property
    def nnz(self) -> jax.Array:
        return jnp.sum(self.counts)


def nonzero(x: jax.Array) -> jax.Array:
    """Exact non-zero mask, subnormals included.

    XLA flushes subnormal floats to zero in float compares on CPU and
    TPU, so ``x != 0`` drops them while numpy keeps them.  Testing the
    bit pattern without the sign bit keeps every non-zero value, which
    is what the bitmap promises.
    """
    if not jnp.issubdtype(x.dtype, jnp.floating):
        return x != 0
    bits = jax.lax.bitcast_convert_type(
        x, jnp.dtype(f"uint{jnp.dtype(x.dtype).itemsize * 8}"))
    return (bits << 1) != 0


def _condense(x: jax.Array, mask: jax.Array, axis: int) -> jax.Array:
    """Stable-push the masked elements of ``x`` to the front along ``axis``.

    Equivalent to, per 1-D fiber: ``fiber[mask]`` zero-padded to full length.
    Implemented as a stable argsort on (!mask) — O(n log n) but fully
    vectorised and differentiable-free (used at inference/encode time only).
    """
    x = jnp.moveaxis(x, axis, -1)
    mask = jnp.moveaxis(mask, axis, -1)
    # stable sort: zeros (mask False) sink to the back, order preserved.
    order = jnp.argsort(~mask, axis=-1, stable=True)
    cond = jnp.take_along_axis(jnp.where(mask, x, 0), order, axis=-1)
    return jnp.moveaxis(cond, -1, axis)


def encode(x: jax.Array, order: str) -> BitmapMatrix:
    """Encode a dense (M, N) matrix into bitmap + condensed values."""
    if x.ndim != 2:
        raise ValueError(f"encode expects 2-D, got {x.shape}")
    if order not in ("col", "row"):
        raise ValueError(f"order must be 'col'|'row', got {order!r}")
    mask = x != 0
    if order == "col":  # condense each column upward; bitmap packed over rows
        values = _condense(x, mask, axis=0)
        bitmap = pack_bits(mask, axis=0)
        counts = jnp.sum(mask, axis=0, dtype=jnp.int32)
    else:  # condense each row leftward; bitmap packed over cols
        values = _condense(x, mask, axis=1)
        bitmap = pack_bits(mask, axis=1)
        counts = jnp.sum(mask, axis=1, dtype=jnp.int32)
    return BitmapMatrix(values=values, bitmap=bitmap, counts=counts, order=order)


def decode(bm: BitmapMatrix) -> jax.Array:
    """Reconstruct the dense matrix from a :class:`BitmapMatrix`."""
    if bm.order == "col":
        mask = unpack_bits(bm.bitmap, axis=0)  # (M, N)
        # position of each original element inside the condensed column
        pos = jnp.cumsum(mask, axis=0) - 1
        gathered = jnp.take_along_axis(bm.values, jnp.maximum(pos, 0), axis=0)
        return jnp.where(mask, gathered, 0).astype(bm.values.dtype)
    mask = unpack_bits(bm.bitmap, axis=1)
    pos = jnp.cumsum(mask, axis=1) - 1
    gathered = jnp.take_along_axis(bm.values, jnp.maximum(pos, 0), axis=1)
    return jnp.where(mask, gathered, 0).astype(bm.values.dtype)


# ---------------------------------------------------------------------------
# two-level bitmap encoding  (paper §III-C, Fig. 9)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TwoLevelBitmap:
    """Tiled two-level encoding of a dense (M, K) matrix.

    values       : dense values laid out tile-major: (Mt, Kt, tm, tk).
                   (Intra-tile condensation is done *inside* the SpGEMM
                   kernel per (i,j) pair — see DESIGN.md §2 — so the tile
                   payload stays positionally addressed here.)
    elem_bitmap  : packed element bitmap per tile: (Mt, Kt, tm, tk//32).
    tile_bitmap  : "warp-bitmap" — one bit per tile: (Mt, Kt) bool.
    slice_counts : per-tile, per-k-slice-group activity used for k-slice
                   condensation: (Mt, Kt, tk // slice) int32 — number of
                   non-zero *columns* (k positions) in each 128-wide group.
    tile_m/tile_k/slice : static tiling parameters.
    """
    values: jax.Array
    elem_bitmap: jax.Array
    tile_bitmap: jax.Array
    slice_counts: jax.Array
    tile_m: int = dataclasses.field(metadata=dict(static=True))
    tile_k: int = dataclasses.field(metadata=dict(static=True))
    slice: int = dataclasses.field(metadata=dict(static=True))

    @property
    def grid(self) -> Tuple[int, int]:
        return self.tile_bitmap.shape

    @property
    def shape(self) -> Tuple[int, int]:
        mt, kt = self.tile_bitmap.shape
        return (mt * self.tile_m, kt * self.tile_k)


def encode_two_level(
    x: jax.Array, tile_m: int, tile_k: int, slice: int = 128
) -> TwoLevelBitmap:
    """Tile a dense (M, K) matrix and build both bitmap levels."""
    m, k = x.shape
    if m % tile_m or k % tile_k or tile_k % WORD or tile_k % slice:
        raise ValueError(
            f"shape {x.shape} not tileable by ({tile_m},{tile_k},{slice})")
    mt, kt = m // tile_m, k // tile_k
    tiles = x.reshape(mt, tile_m, kt, tile_k).transpose(0, 2, 1, 3)
    mask = tiles != 0
    elem_bitmap = pack_bits(mask, axis=-1)  # (Mt,Kt,tm,tk//32)
    tile_bitmap = jnp.any(mask, axis=(-1, -2))  # (Mt,Kt)
    # k-slice activity: a k column is active if any row in the tile uses it.
    col_active = jnp.any(mask, axis=-2)  # (Mt,Kt,tk)
    groups = col_active.reshape(mt, kt, tile_k // slice, slice)
    slice_counts = jnp.sum(groups, axis=-1, dtype=jnp.int32)
    return TwoLevelBitmap(
        values=tiles.astype(x.dtype),
        elem_bitmap=elem_bitmap,
        tile_bitmap=tile_bitmap,
        slice_counts=slice_counts,
        tile_m=tile_m,
        tile_k=tile_k,
        slice=slice,
    )


def decode_two_level(enc: TwoLevelBitmap) -> jax.Array:
    mt, kt = enc.grid
    mask = unpack_bits(enc.elem_bitmap, axis=-1)
    tiles = jnp.where(mask, enc.values, 0)
    return tiles.transpose(0, 2, 1, 3).reshape(mt * enc.tile_m, kt * enc.tile_k)


# ---------------------------------------------------------------------------
# bitmap outer product ("multiply-bitmap" / BOHMMA analogue, paper §III-A)
# ---------------------------------------------------------------------------

def bitmap_outer(col_bits_a: jax.Array, row_bits_b: jax.Array) -> jax.Array:
    """1-bit outer product of an A-column bitmap and a B-row bitmap.

    col_bits_a: packed uint32 over M (shape (M//32,));
    row_bits_b: packed uint32 over N (shape (N//32,)).
    Returns the packed (M, N//32) bitmap of the partial matrix D = a ⊗ b —
    the BOHMMA instruction of paper Fig. 14, done with word-level ANDs.
    """
    a = unpack_bits(col_bits_a, axis=0)  # (M,) bool
    return jnp.where(a[:, None], row_bits_b[None, :], jnp.uint32(0))


def tile_activity_outer(a_tiles: jax.Array, b_tiles: jax.Array) -> jax.Array:
    """Level-2 activity: which (i, j, kb) block products are non-trivial.

    a_tiles: (Mt, Kt) bool; b_tiles: (Kt, Nt) bool.
    Returns (Mt, Nt, Kt) bool — True where A tile (i,kb) AND B tile (kb,j)
    are both non-empty.  This drives the scalar-prefetch index list of the
    Pallas kernel (the paper's warp-bitmap skip).
    """
    return a_tiles[:, None, :] & b_tiles.T[None, :, :]
