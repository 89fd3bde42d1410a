"""Pallas TPU kernel: ragged grouped SpGEMM over stacked experts.

The MoE expert-FFN matmul — ``C[e] = A[e] @ B[e]`` for stacked operands
``A (E, C, K)`` and ``B (E, K, N)`` — is the most extreme dynamic-sparsity
case the repo has: each expert's capacity buffer fills to a *different*
row count (ragged occupancy), and every empty slot is a whole zero row
born from the gating itself (DESIGN.md §3, §9).  The 2-D
:mod:`~repro.kernels.bitmap_spgemm` kernel cannot express the expert axis,
so PR 1's dispatch only *counted* the skips; this kernel executes them.

One grid ``(E, Mt, Nt, S)`` covers all experts.  Per expert, the
scalar-prefetched schedule ``ks (E, Mt, Nt, S)`` / ``counts (E, Mt, Nt)``
is the same two-level bitmap plan as the 2-D kernel
(:func:`repro.sparse.plan.plan_grouped_activity`): front-packed active
k-slice indices per output block, inactive tails repeating the last
active index.  Raggedness needs no special casing — an expert with fewer
occupied rows simply has more all-zero block-rows, whose slice lists are
empty (``counts == 0``) and whose grid steps re-map to already-resident
blocks: zero MXU work, zero DMA.  The grid stays rectangular because the
repeat-last tails pad every per-expert slice list to the shared S.

The kernel computes exactly ``einsum("eck,ekn->ecn", A, B)`` for any
sparsity pattern — scheduling changes, math doesn't.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import platform
from repro.kernels.bitmap_spgemm import (SLICE_K, _compiler_params,
                                         chunk_ranges, gather_step,
                                         grid_split, mxu_dot)


# ---------------------------------------------------------------------------
# host-side planning (per-expert two-level bitmap metadata)
# ---------------------------------------------------------------------------

def plan_grouped(
    a: jax.Array, b: jax.Array, block_m: int, block_n: int,
    slice_k: int = SLICE_K,
) -> Tuple[jax.Array, jax.Array]:
    """Build the per-expert condensed slice schedule from dense operands.

    a: (E, C, K), b: (E, K, N).  Returns (ks (E, Mt, Nt, S),
    counts (E, Mt, Nt)) — the kernel's scalar-prefetch contract.  Thin
    wrapper over the unified planner (slice activity → block reduction →
    front-pack with repeat-last tails), vmapped over the expert axis.
    """
    from repro.sparse import plan as pln
    cols = jax.vmap(lambda ai: pln.block_reduce_lhs(
        pln.slice_activity_lhs(ai, slice_k), block_m))(a)
    rows = jax.vmap(lambda bi: pln.block_reduce_rhs(
        pln.slice_activity_rhs(bi, slice_k), block_n))(b)
    return pln.plan_grouped_activity(cols, rows)


# ---------------------------------------------------------------------------
# kernel body
# ---------------------------------------------------------------------------

def _grouped_kernel(idx_ref, cnt_ref, a_ref, b_ref, out_ref, acc_ref, *,
                    mt: int, nt: int, s: int, interpret: bool):
    e = pl.program_id(0)
    i, j, t = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    blk = (e * mt + i) * nt + j

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # level-1/2 skip: only this expert's active, condensed slices
    # contribute; ragged-empty blocks have cnt == 0 and do no MXU work.
    @pl.when(t < cnt_ref[blk])
    def _mac():
        acc_ref[...] += mxu_dot(a_ref[0], b_ref[0], interpret)

    @pl.when(t == s - 1)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def _by_experts(call, e: int, words_per_expert: int, *arrays):
    """Run ``call`` over expert ranges whose schedules fit SMEM and
    concatenate the (E, ...) outputs."""
    per_call, _ = grid_split(e, 1, words_per_expert)
    return jnp.concatenate([call(*(x[e0:e0 + per_call] for x in arrays))
                            for e0 in range(0, e, per_call)], axis=0)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "slice_k", "interpret",
                     "out_dtype"))
def grouped_spgemm_planned(
    a: jax.Array,
    b: jax.Array,
    ks: jax.Array,
    counts: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    slice_k: int = SLICE_K,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Run the grouped kernel with an externally supplied slice schedule.

    a: (E, C, K), b: (E, K, N), ks/counts from
    :func:`repro.sparse.plan.plan_grouped_activity` (or
    :func:`plan_grouped`).  Returns (E, C, N).
    """
    e, c, k = a.shape
    e2, k2, n = b.shape
    assert (e, k) == (e2, k2), (a.shape, b.shape)
    e3, mt, nt, s = ks.shape
    assert e3 == e, (ks.shape, a.shape)
    out_dtype = out_dtype or jnp.promote_types(a.dtype, b.dtype)

    pad_m = mt * block_m - c
    pad_n = nt * block_n - n
    pad_k = s * slice_k - k
    a = jnp.pad(a, ((0, 0), (0, pad_m), (0, pad_k)))
    b = jnp.pad(b, ((0, 0), (0, pad_k), (0, pad_n)))

    def call(a, b, ks, counts):
        ec = ks.shape[0]
        kernel = functools.partial(_grouped_kernel, mt=mt, nt=nt, s=s,
                                   interpret=interpret)

        def step(g, i, j, t, idx):
            return idx[((g * mt + i) * nt + j) * s + t]

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(ec, mt, nt, s),
            in_specs=[
                pl.BlockSpec((1, block_m, slice_k),
                             lambda g, i, j, t, idx, cnt:
                             (g, i, step(g, i, j, t, idx))),
                pl.BlockSpec((1, slice_k, block_n),
                             lambda g, i, j, t, idx, cnt:
                             (g, step(g, i, j, t, idx), j)),
            ],
            out_specs=pl.BlockSpec((1, block_m, block_n),
                                   lambda g, i, j, t, idx, cnt: (g, i, j)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (ec, mt * block_m, nt * block_n), out_dtype),
            compiler_params=_compiler_params(
                ("parallel", "parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(ks.reshape(-1), counts.reshape(-1), a, b)

    out = _by_experts(call, e, mt * nt * (s + 1), a, b, ks, counts)
    return out[:, :c, :n]


def grouped_spgemm(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    slice_k: int = SLICE_K,
    interpret: Optional[bool] = None,
    out_dtype=None,
) -> jax.Array:
    """Ragged grouped SpGEMM with on-the-fly per-expert planning."""
    from repro.sparse import plan as pln
    interpret = platform.resolve_interpret(interpret)
    e, c, k = a.shape
    n = b.shape[-1]
    block_m, block_n, slice_k = pln.clamp_geometry(
        c, n, k, block_m, block_n, slice_k, interpret)
    ks, counts = plan_grouped(a, b, block_m, block_n, slice_k)
    return grouped_spgemm_planned(
        a, b, ks, counts, block_m=block_m, block_n=block_n,
        slice_k=slice_k, interpret=bool(interpret), out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# fused K-condensation (DESIGN.md §12): per-expert packed-k schedules
# ---------------------------------------------------------------------------

def _grouped_kfused_kernel(cnt_ref, qlo_ref, qhi_ref, gk_ref, a_ref, b_ref,
                           out_ref, acc_ref, *, mt: int, nt: int, s: int,
                           slice_k: int, interpret: bool):
    e = pl.program_id(0)
    i, j, t = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    blk = (e * mt + i) * nt + j

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # element-granular condensation per expert: step t gathers its
    # packed k's from the expert's VMEM-resident operand panels; lanes
    # past the block's nnz reference inactive k's (zero outer products).
    @pl.when(t < cnt_ref[blk])
    def _mac():
        step = blk * s + t
        bm, bn = acc_ref.shape

        def a_chunk(q):
            return a_ref[0, :, pl.ds(pl.multiple_of(q * slice_k, slice_k),
                                     slice_k)]

        def bt_chunk(q):
            return b_ref[0, pl.ds(pl.multiple_of(q * slice_k, slice_k),
                                  slice_k), :].T

        a_pack, bt_pack = gather_step(
            a_chunk, bt_chunk, gk_ref[0, 0, 0, pl.ds(t, 1), :],
            qlo_ref[step], qhi_ref[step], bm=bm, bn=bn, slice_k=slice_k,
            a_dtype=a_ref.dtype, b_dtype=b_ref.dtype)
        acc_ref[...] += mxu_dot(a_pack, bt_pack, interpret,
                                contract=((1,), (1,)))

    @pl.when(t == s - 1)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "slice_k", "interpret",
                     "out_dtype"))
def grouped_spgemm_kfused_planned(
    a: jax.Array,
    b: jax.Array,
    gk: jax.Array,
    counts: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    slice_k: int = SLICE_K,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """Grouped kernel with per-expert element-condensed schedules.

    a: (E, C, K), b: (E, K, N); gk (E, Mt, Nt, S, slice_k) /
    counts (E, Mt, Nt) from
    :func:`repro.sparse.plan.plan_grouped_kcondensed`.  Same prefetch
    contract as :func:`repro.kernels.bitmap_spgemm.
    bitmap_spgemm_kfused_planned`, with the expert axis as the leading
    parallel grid dimension; raggedness needs no special casing — an
    idle expert's blocks have ``counts == 0`` and do zero MXU work.
    """
    e, c, k = a.shape
    e2, k2, n = b.shape
    assert (e, k) == (e2, k2), (a.shape, b.shape)
    e3, mt, nt, s, sk = gk.shape
    assert e3 == e and sk == slice_k, (gk.shape, a.shape, slice_k)
    out_dtype = out_dtype or jnp.promote_types(a.dtype, b.dtype)
    kp = s * slice_k

    a = jnp.pad(a, ((0, 0), (0, mt * block_m - c), (0, kp - k)))
    b = jnp.pad(b, ((0, 0), (0, kp - k), (0, nt * block_n - n)))

    def call(a, b, gk, counts):
        ec = gk.shape[0]
        qlo, qhi = chunk_ranges(gk, slice_k)
        kernel = functools.partial(_grouped_kfused_kernel, mt=mt, nt=nt,
                                   s=s, slice_k=slice_k,
                                   interpret=interpret)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(ec, mt, nt, s),
            in_specs=[
                pl.BlockSpec((1, 1, 1, s, slice_k),
                             lambda g, i, j, t, *_: (g, i, j, 0, 0)),
                pl.BlockSpec((1, block_m, kp),
                             lambda g, i, j, t, *_: (g, i, 0)),
                pl.BlockSpec((1, kp, block_n),
                             lambda g, i, j, t, *_: (g, 0, j)),
            ],
            out_specs=pl.BlockSpec((1, block_m, block_n),
                                   lambda g, i, j, t, *_: (g, i, j)),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (ec, mt * block_m, nt * block_n), out_dtype),
            compiler_params=_compiler_params(
                ("parallel", "parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(counts.reshape(-1), qlo.reshape(-1), qhi.reshape(-1), gk, a, b)

    out = _by_experts(call, e, mt * nt * (2 * s + 1), a, b, gk, counts)
    return out[:, :c, :n]


def grouped_spgemm_kfused(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    slice_k: int = SLICE_K,
    interpret: Optional[bool] = None,
    out_dtype=None,
) -> jax.Array:
    """Fused-K-condensed grouped SpGEMM with on-the-fly planning."""
    from repro.sparse import plan as pln
    interpret = platform.resolve_interpret(interpret)
    e, c, k = a.shape
    n = b.shape[-1]
    block_m, block_n, slice_k = pln.clamp_geometry(
        c, n, k, block_m, block_n, slice_k, interpret)
    kp = pln.plan_grouped_kcondensed(
        jax.vmap(lambda ai: pln.element_activity_lhs(ai, block_m))(a),
        jax.vmap(lambda bi: pln.element_activity_rhs(bi, block_n))(b),
        slice_k)
    return grouped_spgemm_kfused_planned(
        a, b, kp.gk, kp.counts, block_m=block_m, block_n=block_n,
        slice_k=slice_k, interpret=bool(interpret), out_dtype=out_dtype)
