"""Pallas TPU kernel: two-level bitmap outer-product SpGEMM.

TPU-native realisation of the paper's dual-side sparse Tensor Core
(DESIGN.md §2).  C = A @ B is tiled into (block_m × block_n) output blocks;
the contraction dimension is cut into 128-wide *k-slices* (the MXU-aligned
analogue of the paper's 8×16×1 OHMMA step).  A k-slice is **active** for
output block (i, j) iff some column of A rows-block i uses it AND some row
of B cols-block j uses it — the bitmap AND of the paper's condensing step
(Fig. 4c).  The host-side :func:`plan_slices` front-packs active slice
indices per output block ("condensing"), and the kernel walks only that
list via scalar-prefetch index maps:

* level-2 skip (warp-bitmap, Fig. 9): blocks whose slice list is empty do
  zero MXU work and — because skipped grid steps repeat the previous block
  index — zero extra DMA;
* level-1 skip (OHMMA predication, Fig. 15): inactive k-slices never appear
  in the list, so the contraction is *condensed* to the active slices,
  quantised at 128 granularity.
* merge (gather–accumulate–scatter, Fig. 7): the partial products of all
  visited slices accumulate into a float32 VMEM scratch tile — the TPU
  analogue of the paper's accumulation buffer; tile-locality is guaranteed
  by construction, so no operand collector is needed.

The kernel computes exactly A @ B for any sparsity pattern.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import platform

SLICE_K = 128  # MXU-native contraction depth = unit of sparsity skip


# Scalar-prefetched schedule words per pallas_call.  The schedule lives in
# SMEM (1 MiB on v5e); a call whose schedule would not fit is split into
# calls over sub-rectangles of the output-block grid (each call re-reads
# only the operand blocks its own output blocks need, exactly as the one
# big grid would).  Prefetch arrays are passed flattened to 1-D: SMEM pads
# a multi-dimensional array's minor dimension, so a (E, Mt, Nt, 2)
# schedule took 64x its size.
SMEM_SCHEDULE_WORDS = 96 * 1024


def _compiler_params(dimension_semantics):
    from repro.sparse import plan as pln
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=pln.VMEM_BYTES)


def mxu_dot(a: jax.Array, b: jax.Array, interpret: bool,
            contract=((1,), (0,))) -> jax.Array:
    """f32-accumulating product of two operand tiles.

    On the TPU the operands reach the MXU in their own dtype (bf16 stays
    bf16) with f32 accumulation.  Interpret mode upcasts them to f32
    first: the CPU backend has no bf16 x bf16 -> f32 dot.  Both forms
    are exact products summed in f32 — bf16 values are exact in f32 —
    so only the summation order can differ.
    """
    if interpret:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def grid_split(n_outer: int, n_inner: int, words_per_block: int
               ) -> Tuple[int, int]:
    """(outer, inner) block counts per call so that one call's flattened
    schedule stays within :data:`SMEM_SCHEDULE_WORDS`."""
    inner = max(1, min(n_inner, SMEM_SCHEDULE_WORDS // words_per_block))
    outer = max(1, min(n_outer,
                       SMEM_SCHEDULE_WORDS // (inner * words_per_block)))
    return outer, inner


def over_blocks(call, a, b, *schedule, block_m: int, block_n: int,
                words_per_block: int):
    """Run ``call(a_rows, b_cols, *schedule_blocks)`` over rectangles of
    the (Mt, Nt) output-block grid whose schedules fit SMEM
    (:func:`grid_split`) and stitch the output blocks back together."""
    mt, nt = schedule[0].shape[:2]
    rows, cols = grid_split(mt, nt, words_per_block)
    out = []
    for i0 in range(0, mt, rows):
        i1 = min(i0 + rows, mt)
        out.append(jnp.concatenate([
            call(a[i0 * block_m:i1 * block_m],
                 b[:, j0 * block_n:min(j0 + cols, nt) * block_n],
                 *(x[i0:i1, j0:j0 + cols] for x in schedule))
            for j0 in range(0, nt, cols)], axis=1))
    return jnp.concatenate(out, axis=0)


def chunk_ranges(gk: jax.Array, slice_k: int) -> Tuple[jax.Array,
                                                        jax.Array]:
    """First and last ``slice_k``-wide k-chunk each condensed step reads.

    gk (..., S, slice_k) packed schedule.  A step's head lanes (the
    block's active k's) ascend, so they span chunks
    ``gk[..., 0] // slice_k`` to the chunk of the last head lane; the
    tail of the last step restarts at the smallest inactive k, which
    shows as the first descent.  Lanes outside the range gather nothing
    (zero), lanes inside it are exact — tail lanes read k's whose outer
    product is zero either way.  Returns (qlo, qhi), each (..., S).
    """
    desc = gk[..., 1:] < gk[..., :-1]
    tail = jnp.cumsum(desc, axis=-1, dtype=jnp.int32) > 0
    head = jnp.concatenate([jnp.ones_like(tail[..., :1]), ~tail], axis=-1)
    qlo = gk[..., 0] // slice_k
    qhi = jnp.max(jnp.where(head, gk, 0), axis=-1) // slice_k
    return qlo.astype(jnp.int32), qhi.astype(jnp.int32)


def gather_step(a_chunk, bt_chunk, idx: jax.Array, qlo, qhi, *,
                bm: int, bn: int, slice_k: int, a_dtype, b_dtype):
    """Pack one condensed step's operands out of resident panels.

    ``a_chunk(q)`` → the (bm, slice_k) A columns of k-chunk q,
    ``bt_chunk(q)`` → the (bn, slice_k) transposed B rows of chunk q.
    Lane l of the packed step reads k = idx[l]: chunk ``idx // slice_k``,
    offset ``idx % slice_k``.  Each chunk in [qlo, qhi] is gathered with
    one lane gather (Mosaic gathers 32-bit data within 128 lanes, so the
    chunk is upcast to f32) and merged into the lanes that belong to it.
    B is gathered transposed because Mosaic gathers along lanes, not
    across sublane tiles.  Returns (a_pack (bm, sk), bt_pack (bn, sk))
    cast back to the panels' dtypes.
    """
    chunk = idx // slice_k                       # (1, sk)
    off = idx % slice_k

    def body(q, carry):
        a_pack, bt_pack = carry
        a_c = a_chunk(q).astype(jnp.float32)
        bt_c = bt_chunk(q).astype(jnp.float32)
        sel = chunk == q
        a_g = jnp.take_along_axis(a_c, jnp.broadcast_to(off, a_c.shape),
                                  axis=1)
        bt_g = jnp.take_along_axis(bt_c, jnp.broadcast_to(off, bt_c.shape),
                                   axis=1)
        return (jnp.where(sel, a_g, a_pack), jnp.where(sel, bt_g, bt_pack))

    init = (jnp.zeros((bm, slice_k), jnp.float32),
            jnp.zeros((bn, slice_k), jnp.float32))
    a_pack, bt_pack = jax.lax.fori_loop(qlo, qhi + 1, body, init)
    return a_pack.astype(a_dtype), bt_pack.astype(b_dtype)


# ---------------------------------------------------------------------------
# host-side planning (the two-level bitmap metadata)
# ---------------------------------------------------------------------------

def plan_slices(
    a: jax.Array, b: jax.Array, block_m: int, block_n: int,
    slice_k: int = SLICE_K,
) -> Tuple[jax.Array, jax.Array]:
    """Build the condensed active-slice schedule from operand bitmaps.

    Thin wrapper over the unified planner in :mod:`repro.sparse.plan`
    (slice activity → block reduction → front-pack with repeat-last tail);
    kept as the kernel-local name because the schedule layout is the
    kernel's scalar-prefetch contract.

    Returns:
      ks:     (Mt, Nt, S) int32 — front-packed active k-slice indices for
              each output block; inactive tail repeats the last active
              entry so skipped grid steps re-map to an already-resident
              block (no DMA).
      counts: (Mt, Nt) int32 — number of active slices per output block.
    Fully jittable; cost is a cheap reduction over the operands (in the
    serving path the activation-side activity comes cached from the
    previous layer's :class:`repro.sparse.SparseActivation`).
    """
    from repro.sparse import plan as pln
    return pln.plan_operands(a, b, block_m, block_n, slice_k)


# ---------------------------------------------------------------------------
# kernel body
# ---------------------------------------------------------------------------

def _spgemm_kernel(idx_ref, cnt_ref, a_ref, b_ref, out_ref, acc_ref, *,
                   nt: int, s: int, interpret: bool):
    i, j, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    blk = i * nt + j

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # level-1/2 skip: only active, condensed slices contribute (the
    # paper's POPC-driven OHMMA predication).
    @pl.when(t < cnt_ref[blk])
    def _mac():
        acc_ref[...] += mxu_dot(a_ref[...], b_ref[...], interpret)

    @pl.when(t == s - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------

def _spgemm_call(a, b, ks, counts, *, block_m, block_n, slice_k,
                 interpret, out_dtype):
    """One pallas_call over an (mt, nt) output-block rectangle."""
    mt, nt, s = ks.shape
    kernel = functools.partial(_spgemm_kernel, nt=nt, s=s,
                               interpret=interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(mt, nt, s),
        in_specs=[
            pl.BlockSpec((block_m, slice_k),
                         lambda i, j, t, idx, cnt:
                         (i, idx[(i * nt + j) * s + t])),
            pl.BlockSpec((slice_k, block_n),
                         lambda i, j, t, idx, cnt:
                         (idx[(i * nt + j) * s + t], j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, t, idx, cnt: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mt * block_m, nt * block_n),
                                       out_dtype),
        compiler_params=_compiler_params(("parallel", "parallel",
                                          "arbitrary")),
        interpret=interpret,
    )(ks.reshape(-1), counts.reshape(-1), a, b)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "slice_k", "interpret",
                     "out_dtype"))
def bitmap_spgemm_planned(
    a: jax.Array,
    b: jax.Array,
    ks: jax.Array,
    counts: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    slice_k: int = SLICE_K,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Run the kernel with an externally supplied slice schedule."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    mt, nt, s = ks.shape
    out_dtype = out_dtype or jnp.promote_types(a.dtype, b.dtype)

    pad_m = mt * block_m - m
    pad_n = nt * block_n - n
    pad_k = s * slice_k - k
    a = jnp.pad(a, ((0, pad_m), (0, pad_k)))
    b = jnp.pad(b, ((0, pad_k), (0, pad_n)))

    call = functools.partial(_spgemm_call, block_m=block_m,
                             block_n=block_n, slice_k=slice_k,
                             interpret=interpret, out_dtype=out_dtype)
    out = over_blocks(call, a, b, ks, counts, block_m=block_m,
                      block_n=block_n, words_per_block=s + 1)
    return out[:m, :n]


def bitmap_spgemm(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 256,       # kept for API symmetry; slices are the unit
    slice_k: int = SLICE_K,
    interpret: Optional[bool] = None,
    out_dtype=None,
) -> jax.Array:
    """Dual-side sparse C = A @ B with on-the-fly bitmap planning."""
    from repro.sparse import plan as pln
    del block_k
    interpret = platform.resolve_interpret(interpret)
    block_m, block_n, slice_k = pln.clamp_geometry(
        a.shape[0], b.shape[1], a.shape[1], block_m, block_n, slice_k,
        interpret)
    ks, counts = plan_slices(a, b, block_m, block_n, slice_k)
    return bitmap_spgemm_planned(
        a, b, ks, counts, block_m=block_m, block_n=block_n, slice_k=slice_k,
        interpret=bool(interpret), out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# element-granular K-condensation (paper Fig. 4c, TPU-exact variant)
# ---------------------------------------------------------------------------

def kcondense(a: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array,
                                                   jax.Array]:
    """Condense the contraction dimension at *element* granularity.

    k is active iff column k of A and row k of B both contain a non-zero
    (the bitmap AND of the paper's condensing, Fig. 4c).  Active k's are
    front-packed by a stable gather — an exact transform: the product of
    the condensed operands equals A @ B, because dropped k's contribute
    a zero outer product.  Unlike the paper's M/N-side condensation this
    needs no output scatter (DESIGN.md §2/§8): the TPU has no MXU-path
    scatter, so K-side condensation is the scatter-free equivalent.

    Returns (a_cond, b_cond, n_active).  Static shapes: buffers keep
    capacity K; the *schedule* savings come from running the block-skip
    kernel on them (only ceil(n_active/slice_k) leading slices are
    active).

    This whole-operand pre-pass costs two dense HBM round-trips (the
    gathered copies of A and B) and condenses on the *global* AND only;
    it is kept as the reference implementation that the fused planner
    level (:func:`bitmap_spgemm_kfused_planned`, DESIGN.md §12) is
    tested against.
    """
    act = jnp.any(a != 0, axis=0) & jnp.any(b != 0, axis=1)   # (K,)
    from repro.sparse import plan as pln
    order, nact = pln.stable_partition(act)
    return jnp.take(a, order, axis=1), jnp.take(b, order, axis=0), nact


def bitmap_spgemm_kcondensed(
    a: jax.Array, b: jax.Array, *, block_m: int = 256, block_n: int = 256,
    slice_k: int = SLICE_K, interpret: Optional[bool] = None,
    out_dtype=None,
) -> jax.Array:
    """Dual-side SpGEMM with element-granular K condensation + block skip.

    Reference implementation of fused K-condensation (DESIGN.md §12):
    the dense :func:`kcondense` pre-pass followed by the block-skip
    kernel.  Model paths use :func:`bitmap_spgemm_kfused` instead, which
    executes the same condensation inside the kernel's schedule.
    """
    a_c, b_c, _ = kcondense(a, b)
    return bitmap_spgemm(a_c, b_c, block_m=block_m, block_n=block_n,
                         slice_k=slice_k, interpret=interpret,
                         out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# fused K-condensation (DESIGN.md §12): the schedule gathers, not a pre-pass
# ---------------------------------------------------------------------------

def _spgemm_kfused_kernel(cnt_ref, qlo_ref, qhi_ref, gk_ref, a_ref, b_ref,
                          out_ref, acc_ref, *, nt: int, s: int,
                          slice_k: int, interpret: bool):
    i, j, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    blk = i * nt + j

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # element-granular condensation: condensed step t gathers the k's
    # the packed schedule routes to it — from the VMEM-resident operand
    # panels, so the gather rides the block DMAs that already happened.
    # Lanes past the block's nnz reference *inactive* k's (zero outer
    # products), so the last partial step needs no lane predication.
    @pl.when(t < cnt_ref[blk])
    def _mac():
        step = blk * s + t
        bm, bn = acc_ref.shape

        def a_chunk(q):
            return a_ref[:, pl.ds(pl.multiple_of(q * slice_k, slice_k),
                                  slice_k)]

        def bt_chunk(q):
            return b_ref[pl.ds(pl.multiple_of(q * slice_k, slice_k),
                               slice_k), :].T

        a_pack, bt_pack = gather_step(
            a_chunk, bt_chunk, gk_ref[0, 0, pl.ds(t, 1), :],
            qlo_ref[step], qhi_ref[step], bm=bm, bn=bn, slice_k=slice_k,
            a_dtype=a_ref.dtype, b_dtype=b_ref.dtype)
        acc_ref[...] += mxu_dot(a_pack, bt_pack, interpret,
                                contract=((1,), (1,)))

    @pl.when(t == s - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _kfused_call(a, b, gk, counts, *, block_m, block_n, slice_k, interpret,
                 out_dtype):
    """One pallas_call over an (mt, nt) output-block rectangle."""
    mt, nt, s, _ = gk.shape
    kp = s * slice_k
    qlo, qhi = chunk_ranges(gk, slice_k)
    kernel = functools.partial(_spgemm_kfused_kernel, nt=nt, s=s,
                               slice_k=slice_k, interpret=interpret)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(mt, nt, s),
        in_specs=[
            # the block's whole packed schedule (S, slice_k), resident
            # across its condensed steps; step t reads row t
            pl.BlockSpec((1, 1, s, slice_k),
                         lambda i, j, t, *_: (i, j, 0, 0)),
            # operand panels: full contraction depth, resident per (i, j)
            pl.BlockSpec((block_m, kp), lambda i, j, t, *_: (i, 0)),
            pl.BlockSpec((kp, block_n), lambda i, j, t, *_: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, t, *_: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mt * block_m, nt * block_n),
                                       out_dtype),
        compiler_params=_compiler_params(("parallel", "parallel",
                                          "arbitrary")),
        interpret=interpret,
    )(counts.reshape(-1), qlo.reshape(-1), qhi.reshape(-1), gk, a, b)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "slice_k", "interpret",
                     "out_dtype"))
def bitmap_spgemm_kfused_planned(
    a: jax.Array,
    b: jax.Array,
    gk: jax.Array,
    counts: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    slice_k: int = SLICE_K,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Run the kernel with an element-condensed packed-k schedule.

    gk (Mt, Nt, S, slice_k) / counts (Mt, Nt) from
    :func:`repro.sparse.plan.plan_kcondensed`.  Per output block only
    ``counts[i, j] == ceil(nnz_AND / slice_k)`` grid steps do MXU work —
    element-granular skips instead of whole-k-slice quantisation.
    Operand panels stay VMEM-resident across the condensed steps
    ((block_m, K) of A per block-row, (K, block_n) of B per block-col),
    so the packed-k gather (:func:`gather_step`) costs no HBM traffic
    beyond the block DMAs the dense schedule already performs
    (DESIGN.md §12 discusses the VMEM budget).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    mt, nt, s, sk = gk.shape
    assert sk == slice_k, (gk.shape, slice_k)
    out_dtype = out_dtype or jnp.promote_types(a.dtype, b.dtype)
    kp = s * slice_k

    a = jnp.pad(a, ((0, mt * block_m - m), (0, kp - k)))
    b = jnp.pad(b, ((0, kp - k), (0, nt * block_n - n)))

    call = functools.partial(_kfused_call, block_m=block_m,
                             block_n=block_n, slice_k=slice_k,
                             interpret=interpret, out_dtype=out_dtype)
    out = over_blocks(call, a, b, gk, counts, block_m=block_m,
                      block_n=block_n, words_per_block=2 * s + 1)
    return out[:m, :n]


def bitmap_spgemm_kfused(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    slice_k: int = SLICE_K,
    interpret: Optional[bool] = None,
    out_dtype=None,
) -> jax.Array:
    """Fused-K-condensed C = A @ B with on-the-fly element planning."""
    from repro.sparse import plan as pln
    interpret = platform.resolve_interpret(interpret)
    block_m, block_n, slice_k = pln.clamp_geometry(
        a.shape[0], b.shape[1], a.shape[1], block_m, block_n, slice_k,
        interpret)
    kp = pln.plan_kcondensed(
        pln.element_activity_lhs(a, block_m),
        pln.element_activity_rhs(b, block_n), slice_k)
    return bitmap_spgemm_kfused_planned(
        a, b, kp.gk, kp.counts, block_m=block_m, block_n=block_n,
        slice_k=slice_k, interpret=bool(interpret), out_dtype=out_dtype)
