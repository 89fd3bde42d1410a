"""The unified two-level bitmap planner (DESIGN.md §4.1).

Every sparse matmul in the repo schedules work from the same three-step
recipe:

1. *slice activity* — reduce each operand's non-zero mask to k-slice
   granularity (``slice_k`` contraction positions per slice, the MXU-depth
   analogue of the paper's OHMMA step);
2. *block reduction* — reduce slice activity to output-block granularity
   (``block_m`` rows of A / ``block_n`` cols of B per block);
3. *front-pack* — for each output block, stably push the indices of
   active slices (A-side AND B-side, the paper's condensing bitmap AND,
   Fig. 4c) to the front of the schedule, repeating the last active index
   in the inactive tail so that skipped grid steps re-map to an
   already-resident block and cost no DMA.

Historically ``kernels/bitmap_spgemm.plan_slices`` and
``core/spgemm.plan_blocks`` each implemented their own copy of this (and
``plan_blocks`` padded the tail with whatever ``argsort`` left behind,
causing spurious DMA on skipped steps).  Both now delegate here.

The functions are pure jnp on the last axes, so they are vmap-safe and
jit-friendly; the activation side can be cached in a
:class:`repro.sparse.activation.SparseActivation` and the weight side in a
:class:`repro.sparse.weights.PlannedWeight`, reducing per-step planning to
the AND in :func:`plan_from_activity`.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import stats

SLICE_K = 128  # MXU-native contraction depth = unit of sparsity skip


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


# ---------------------------------------------------------------------------
# step 1: slice activity
# ---------------------------------------------------------------------------

def slice_activity_lhs(a: jax.Array, slice_k: int) -> jax.Array:
    """Per-row k-slice activity of a left operand.

    a: (..., K) values (or bool mask).  Returns (..., S) bool with
    S = ceil(K / slice_k): slice s is active for a row iff the row has a
    non-zero in columns [s*slice_k, (s+1)*slice_k).
    """
    *lead, k = a.shape
    s = _cdiv(k, slice_k)
    mask = jnp.pad(a != 0, [(0, 0)] * len(lead) + [(0, s * slice_k - k)])
    return jnp.any(mask.reshape(*lead, s, slice_k), axis=-1)


def slice_activity_rhs(b: jax.Array, slice_k: int) -> jax.Array:
    """Per-column k-slice activity of a right operand.

    b: (K, N) values (or bool mask).  Returns (S, N) bool: slice s is
    active for a column iff the column has a non-zero in rows
    [s*slice_k, (s+1)*slice_k).
    """
    k, n = b.shape
    s = _cdiv(k, slice_k)
    mask = jnp.pad(b != 0, ((0, s * slice_k - k), (0, 0)))
    return jnp.any(mask.reshape(s, slice_k, n), axis=1)


# ---------------------------------------------------------------------------
# step 2: block reduction
# ---------------------------------------------------------------------------

def block_reduce_lhs(row_act: jax.Array, block_m: int) -> jax.Array:
    """(M, S) per-row activity → (Mt, S) per-block-row activity."""
    m, s = row_act.shape
    mt = _cdiv(m, block_m)
    padded = jnp.pad(row_act, ((0, mt * block_m - m), (0, 0)))
    return jnp.any(padded.reshape(mt, block_m, s), axis=1)


def block_reduce_rhs(col_act: jax.Array, block_n: int) -> jax.Array:
    """(S, N) per-column activity → (S, Nt) per-block-col activity."""
    s, n = col_act.shape
    nt = _cdiv(n, block_n)
    padded = jnp.pad(col_act, ((0, 0), (0, nt * block_n - n)))
    return jnp.any(padded.reshape(s, nt, block_n), axis=2)


# ---------------------------------------------------------------------------
# step 3: front-pack ("condensing")
# ---------------------------------------------------------------------------

def stable_partition(act: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Cumsum/scatter stable partition of indices along the last axis.

    act: (..., S) bool.  Returns (order (..., S) int32, counts (...)
    int32): per fiber, the active indices in ascending order followed by
    the *inactive* indices in ascending order — exactly
    ``argsort(~act, stable=True)``, but built from two cumsums and one
    permutation-inverting scatter (O(S) per fiber instead of the sort's
    O(S log S)).  Every condensing schedule in the repo derives from
    this: :func:`front_pack` overwrites the inactive tail with the
    repeat-last index (the slice/block schedules, where tails must
    re-map to a resident block), while :func:`plan_kcondensed` keeps the
    inactive tail as-is (the element schedules, where tail lanes must
    gather k's whose outer product is zero).
    """
    s = act.shape[-1]
    act = act.astype(bool)
    counts = jnp.sum(act, axis=-1, dtype=jnp.int32)
    rank_active = jnp.cumsum(act, axis=-1, dtype=jnp.int32) - 1
    rank_inactive = jnp.cumsum(~act, axis=-1, dtype=jnp.int32) - 1
    # destination of each source index under the partition…
    pos = jnp.where(act, rank_active, counts[..., None] + rank_inactive)
    # …inverted (dest → source) with one batched scatter.  ``pos`` is a
    # permutation per fiber, so indices are unique and none drop.
    flat = pos.reshape(-1, s)
    rows = jnp.arange(flat.shape[0], dtype=jnp.int32)[:, None]
    src = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), flat.shape)
    order = jnp.zeros(flat.shape, jnp.int32).at[rows, flat].set(
        src, unique_indices=True)
    return order.reshape(act.shape), counts


def front_pack(act: jax.Array, cap: Optional[int] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """Stable-front-pack active indices along the last axis.

    act: (..., S) bool.  Returns (indices (..., cap), counts (...)): the
    active indices of each fiber pushed to the front in ascending order
    (:func:`stable_partition`, cumsum-based — no argsort); the inactive
    tail repeats the last active index (all-zeros for fibers with no
    active entry) so skipped grid steps re-map to an already-resident
    block and trigger no DMA.
    """
    s = act.shape[-1]
    order, counts = stable_partition(act)
    arange = jnp.arange(s, dtype=jnp.int32)
    last = jnp.maximum(counts - 1, 0)[..., None]
    idx = jnp.where(arange < counts[..., None],
                    order, jnp.take_along_axis(order, last, axis=-1))
    if cap is not None:
        idx = idx[..., :cap]
    return idx, counts


def plan_from_activity(col: jax.Array, row: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """Combine the two sides' block-level activity into a schedule.

    col: (Mt, S) A-side block-row slice activity;
    row: (S, Nt) B-side block-col slice activity.
    Returns (ks (Mt, Nt, S), counts (Mt, Nt)) for
    :func:`repro.kernels.bitmap_spgemm.bitmap_spgemm_planned`.  This AND +
    front-pack is the *entire* per-step planning cost when both sides'
    activities are cached.
    """
    act = col[:, None, :] & row.T[None, :, :]   # (Mt, Nt, S)
    return front_pack(act)


def plan_grouped_activity(cols: jax.Array, rows: jax.Array
                          ) -> Tuple[jax.Array, jax.Array]:
    """Batched (per-expert) schedule over stacked operands.

    cols: (E, Mt, S) per-expert A-side block-row slice activity;
    rows: (E, S, Nt) per-expert B-side block-col slice activity.
    Returns (ks (E, Mt, Nt, S), counts (E, Mt, Nt)) for
    :func:`repro.kernels.grouped_spgemm.grouped_spgemm_planned`.

    Experts whose capacity buffers fill to different row counts (ragged
    occupancy) simply have more inactive block-rows; :func:`front_pack`'s
    repeat-last tail pads every per-expert slice list out to the shared
    S, so the (E, Mt, Nt, S) grid stays rectangular and the kernel's
    skipped steps re-map to already-resident blocks (no DMA).
    """
    act = cols[:, :, None, :] & rows.transpose(0, 2, 1)[:, None, :, :]
    return front_pack(act)               # (E, Mt, Nt, S)


def grouped_counts_from_activity(cols: jax.Array, rows: jax.Array
                                 ) -> jax.Array:
    """Per-expert per-block active-slice counts, schedule-free.

    Same AND as :func:`plan_grouped_activity` but a plain sum — the
    stats-only grouped path, sparing the front-pack's argsort."""
    act = cols[:, :, None, :] & rows.transpose(0, 2, 1)[:, None, :, :]
    return jnp.sum(act, axis=-1, dtype=jnp.int32)


def counts_from_activity(col: jax.Array, row: jax.Array) -> jax.Array:
    """Per-block active-slice counts without building the schedule.

    Same AND as :func:`plan_from_activity` but a plain sum — for
    stats-only callers that never feed a kernel, sparing the
    front-pack's argsort/gather.
    """
    act = col[:, None, :] & row.T[None, :, :]   # (Mt, Nt, S)
    return jnp.sum(act, axis=-1, dtype=jnp.int32)


def plan_operands(a: jax.Array, b: jax.Array, block_m: int, block_n: int,
                  slice_k: int = SLICE_K) -> Tuple[jax.Array, jax.Array]:
    """Plan directly from dense 2-D operands (on-the-fly path).

    Exactly equivalent to planning from cached
    ``SparseActivation``/``PlannedWeight`` activities at the same
    geometry — the caches are bit-identical reformulations, not
    approximations.
    """
    col = block_reduce_lhs(slice_activity_lhs(a, slice_k), block_m)
    row = block_reduce_rhs(slice_activity_rhs(b, slice_k), block_n)
    return plan_from_activity(col, row)


# ---------------------------------------------------------------------------
# element-granular K-condensation schedules (DESIGN.md §12)
# ---------------------------------------------------------------------------

def element_activity_lhs(a: jax.Array, block_m: int) -> jax.Array:
    """Per-block-row *element* k-activity of a left operand.

    a: (M, K) values or bool mask.  Returns (Mt, K) bool: k is active
    for block-row i iff some row of the block has a non-zero at column
    k.  The element-granular analogue of
    :func:`slice_activity_lhs` + :func:`block_reduce_lhs` — no slice
    quantisation, so unstructured (k-fiber) sparsity survives.
    """
    m, k = a.shape
    mt = _cdiv(m, block_m)
    mask = jnp.pad(a != 0, ((0, mt * block_m - m), (0, 0)))
    return jnp.any(mask.reshape(mt, block_m, k), axis=1)


def element_activity_rhs(b: jax.Array, block_n: int) -> jax.Array:
    """Per-block-col element k-activity of a right operand.

    b: (K, N) values or bool mask.  Returns (K, Nt) bool: k is active
    for block-col j iff some column of the block has a non-zero at row
    k.
    """
    k, n = b.shape
    nt = _cdiv(n, block_n)
    mask = jnp.pad(b != 0, ((0, 0), (0, nt * block_n - n)))
    return jnp.any(mask.reshape(k, nt, block_n), axis=2)


class KPlan(NamedTuple):
    """A per-output-block packed active-k schedule (``plan_kcondensed``).

    gk     : (..., Mt, Nt, S, slice_k) int32 — for condensed step t,
             lane l gathers contraction index ``gk[..., t, l]``.  Heads
             (the first ``nnz`` lanes across steps) are exactly the
             block's element-AND active k's in ascending order; tail
             lanes continue with the *inactive* k's in ascending order,
             whose outer products are identically zero, so a partial
             last step needs no lane predication (DESIGN.md §12).
    counts : (..., Mt, Nt) int32 — executed condensed steps per output
             block, ``ceil(nnz / slice_k)``.
    nnz    : (..., Mt, Nt) int32 — element-AND active k's per block.
    """
    gk: jax.Array
    counts: jax.Array
    nnz: jax.Array


def _kpack(act: jax.Array, slice_k: int) -> KPlan:
    """(..., K) element activity → packed-k schedule at ``slice_k``."""
    *lead, k = act.shape
    s = _cdiv(k, slice_k)
    act = jnp.pad(act, [(0, 0)] * len(lead) + [(0, s * slice_k - k)])
    order, nnz = stable_partition(act)
    counts = -(-nnz // slice_k)      # ceil: executed condensed steps
    return KPlan(gk=order.reshape(*lead, s, slice_k),
                 counts=counts.astype(jnp.int32), nnz=nnz)


def plan_kcondensed(col: jax.Array, row: jax.Array,
                    slice_k: int = SLICE_K) -> KPlan:
    """Element-granular condensed schedule from the two sides' element
    activities.

    col: (Mt, K) A-side block-row element activity
    (:func:`element_activity_lhs`); row: (K, Nt) B-side
    (:func:`element_activity_rhs`).  Returns the :class:`KPlan` the
    fused kernels (:func:`repro.kernels.bitmap_spgemm.
    bitmap_spgemm_kfused_planned`) consume: the bitmap AND of the
    paper's condensing step (Fig. 4c), stable-front-packed per output
    block by :func:`stable_partition` — executed slices become
    ``ceil(nnz_AND / slice_k)`` instead of quantising at whole k-slices.

    The intermediate AND is materialised at (Mt, Nt, K) — fine for the
    repo's planning shapes; the compact carrier for larger problems is
    the factorized (col, row) bitmap pair itself (DESIGN.md §12).
    """
    act = col[:, None, :] & row.T[None, :, :]        # (Mt, Nt, K)
    return _kpack(act, slice_k)


def plan_grouped_kcondensed(cols: jax.Array, rows: jax.Array,
                            slice_k: int = SLICE_K) -> KPlan:
    """Batched (per-expert) element-condensed schedule.

    cols: (E, Mt, K); rows: (E, K, Nt).  Returns a :class:`KPlan` with
    leading expert axis — gk (E, Mt, Nt, S, slice_k) — for
    :func:`repro.kernels.grouped_spgemm.grouped_spgemm_kfused_planned`.
    """
    act = cols[:, :, None, :] & rows.transpose(0, 2, 1)[:, None, :, :]
    return _kpack(act, slice_k)


def kcondensed_counts(col: jax.Array, row: jax.Array,
                      slice_k: int = SLICE_K) -> jax.Array:
    """Condensed-step counts without building the gather maps.

    Same AND as :func:`plan_kcondensed` but only ``ceil(nnz/slice_k)``
    per block — the stats-only path (XLA fallback), sparing the pack.
    """
    nnz = jnp.sum(col[:, None, :] & row.T[None, :, :], axis=-1,
                  dtype=jnp.int32)
    return (-(-nnz // slice_k)).astype(jnp.int32)


def grouped_kcondensed_counts(cols: jax.Array, rows: jax.Array,
                              slice_k: int = SLICE_K) -> jax.Array:
    """(E, Mt, Nt) condensed-step counts, schedule-free."""
    act = cols[:, :, None, :] & rows.transpose(0, 2, 1)[:, None, :, :]
    nnz = jnp.sum(act, axis=-1, dtype=jnp.int32)
    return (-(-nnz // slice_k)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# shard-local plans (DESIGN.md §11)
# ---------------------------------------------------------------------------

def shard_plan(ks: jax.Array, counts: jax.Array, start: int, size: int,
               axis: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Restrict a front-packed schedule to a contiguous fiber range.

    ks (..., S) / counts (...) along a leading fiber axis (expert axis of
    a grouped plan, or block-row axis of a 2-D plan).  Because
    :func:`front_pack` is independent per fiber, slicing the *plan* along
    a fiber axis is exactly the plan of the sliced *activity* — the
    identity the shard_map MoE path rests on: each device's in_spec slice
    of the global plan is its local plan, no re-planning needed
    (pinned by ``tests/test_plan_properties.py``).
    """
    return (jax.lax.slice_in_dim(ks, start, start + size, axis=axis),
            jax.lax.slice_in_dim(counts, start, start + size, axis=axis))


def kplan_shardable(k: int, n_shards: int, slice_k: int = SLICE_K) -> bool:
    """Can a cached k-side slice activity be viewed per-shard?

    When a weight's contraction axis of depth ``k`` is split ``n_shards``
    ways (tensor-parallel ``w_down``), the cached ``(…, S, N)`` activity
    can be sliced along S into valid per-shard plans only if shard
    boundaries align with slice boundaries *and* the dispatch clamps to
    the same granularity locally as globally (``effective_slice_k``).
    Fibers along S are **not** independent under :func:`front_pack`
    (indices shift), so unlike :func:`shard_plan` this slices the
    *activity*, never a packed schedule — callers re-run the front-pack
    on the shard-local activity.  Returns False when the view would be
    invalid; callers then drop the cache and re-plan from the local
    weight shard (bit-identical, just unbuffered).
    """
    if n_shards <= 1:
        return True
    if k % n_shards:
        return False
    k_loc = k // n_shards
    sk = effective_slice_k(k, slice_k)
    return effective_slice_k(k_loc, slice_k) == sk and k_loc % sk == 0


# ---------------------------------------------------------------------------
# decode-path KV-cache planning (DESIGN.md §10)
# ---------------------------------------------------------------------------

def kv_slot_visibility(kpos: jax.Array, qpos: jax.Array,
                       window: Optional[int]) -> jax.Array:
    """Which cache slots the query at ``qpos`` may attend to.

    kpos: (T,) absolute position held by each slot (-1 = never written);
    qpos: scalar query position.  Mirrors the mask arithmetic of
    ``attention._attend_block`` exactly: causal (kpos <= qpos) AND, for
    sliding-window configs, kpos > qpos - window.  Unwritten slots
    (kpos < 0) are never visible.
    """
    valid = (kpos >= 0) & (kpos <= qpos)
    if window is not None:
        valid &= kpos > (qpos - window)
    return valid


def slot_block_reduce(mask: jax.Array, block_t: int) -> jax.Array:
    """(..., T) per-slot mask → (..., NB) per-block any-reduction."""
    *lead, t = mask.shape
    nb = _cdiv(t, block_t)
    padded = jnp.pad(mask, [(0, 0)] * len(lead)
                     + [(0, nb * block_t - t)])
    return jnp.any(padded.reshape(*lead, nb, block_t), axis=-1)


def kv_decode_slots(occ_slots: jax.Array, kpos: jax.Array,
                    qpos: jax.Array, window: Optional[int]) -> jax.Array:
    """Slot-level decode schedule: occupancy AND causal/window mask.

    The level ``attention.attend_sparse`` consumes directly — the
    dispatch layer re-derives block schedules (and their front-pack)
    from the operand metadata built on top of this mask, so no argsort
    runs here.  Because occupancy ≡ ``kpos >= 0`` (a property-test
    invariant), the result also equals the dense path's softmax validity
    mask bit-for-bit.
    """
    return occ_slots & kv_slot_visibility(kpos, qpos, window)


class KVDecodePlan(NamedTuple):
    """One decode step's cache schedule (``plan_kv_decode``).

    slots  : (T,) bool — scheduled slots (:func:`kv_decode_slots`); the
             operand builders in :mod:`repro.sparse.kvcache` consume
             this level.
    blocks : (NB,) bool — the same schedule at cache-block granularity.
    idx    : (NB,) int32 — front-packed scheduled block indices with a
             repeat-last tail (the scalar-prefetch layout a
             block-granular cache kernel consumes; pinned today by the
             property tests).
    count  : scalar int32 — number of scheduled blocks.
    """
    slots: jax.Array
    blocks: jax.Array
    idx: jax.Array
    count: jax.Array


def plan_kv_decode(occ_slots: jax.Array, kpos: jax.Array, qpos: jax.Array,
                   window: Optional[int], block_t: int) -> KVDecodePlan:
    """Front-packed cache-block schedule for one decode step.

    occ_slots: (T,) bool slot occupancy from the cache's incrementally
    maintained bitmap (:mod:`repro.sparse.kvcache`) — never re-derived
    from the dense K/V values.  A block is *scheduled* iff it holds at
    least one occupied slot that the causal/window mask lets the query
    see; everything else (zero-padded, ring/window-evicted, or
    never-written blocks) is skipped.  The head of ``idx`` only ever
    references occupied blocks — the invariant the property tests pin
    down.
    """
    sched_slots = kv_decode_slots(occ_slots, kpos, qpos, window)
    blocks = slot_block_reduce(sched_slots, block_t)
    idx, count = front_pack(blocks)
    return KVDecodePlan(slots=sched_slots, blocks=blocks, idx=idx,
                        count=count)


def kv_blocks_reclaimable(pos: int, window: Optional[int], block_t: int,
                          n_blocks: int):
    """Which cache blocks no future query can ever attend (host-side).

    For a full-history cache (no ring wrap: logical slot i holds token
    i), block b spans slots [b·block_t, (b+1)·block_t); once every slot
    in it falls out of the sliding window of the *current* cursor —
    ``(b+1)·block_t - 1 < pos - window + 1`` — it is out for all later
    queries too (the window only moves forward).  This is the paged
    engine's page-reclaim predicate: a reclaimable block's physical page
    can return to the pool, because the decode schedule
    (:func:`kv_decode_slots`) already excludes every slot in it.
    Returns a python list of bools, length ``n_blocks``; all-False
    without a window.
    """
    if not window:
        return [False] * n_blocks
    horizon = pos - window  # slots <= horizon are invisible forever
    return [(b + 1) * block_t - 1 <= horizon for b in range(n_blocks)]


# ---------------------------------------------------------------------------
# step-count accounting (shared by all dispatch modes)
# ---------------------------------------------------------------------------

def counts_to_steps(counts: jax.Array, n_slices: int) -> stats.StepCounts:
    """Schedule counts → the repo's machine-independent StepCounts.

    counts: (Mt, Nt) active slices per output block; dense work is
    Mt · Nt · S slice-matmuls.
    """
    mt, nt = counts.shape
    return stats.StepCounts(
        dense=jnp.asarray(mt * nt * n_slices),
        sparse=jnp.sum(counts),
        tiles_skipped=jnp.sum(counts == 0))


def grouped_counts_to_steps(counts: jax.Array, n_slices: int
                            ) -> stats.StepCounts:
    """(E, Mt, Nt) grouped schedule counts → summed StepCounts.

    Dense work is E · Mt · Nt · S slice-matmuls; the per-expert tallies
    collapse into one entry because the grouped kernel runs all experts
    under a single grid."""
    e, mt, nt = counts.shape
    return stats.StepCounts(
        dense=jnp.asarray(e * mt * nt * n_slices),
        sparse=jnp.sum(counts),
        tiles_skipped=jnp.sum(counts == 0))


def effective_slice_k(k: int, slice_k: int = SLICE_K) -> int:
    """The slice granularity the dispatch will actually use for a
    contraction of depth ``k`` (cached plans must be built at this
    granularity to hit the fast path)."""
    return min(slice_k, max(8, k))


# ---------------------------------------------------------------------------
# knob validity (autotuner contract, DESIGN.md §13)
# ---------------------------------------------------------------------------

# Scoped VMEM every kernel requests (``CompilerParams.vmem_limit_bytes``)
# and the budget :func:`knobs_valid` holds a tile to.  Mosaic's default
# scoped limit on v5e is 16 MiB; the chips listed in
# ``repro.kernels.platform.VMEM_CAPACITY`` have 128 MiB per core, and
# the rest is left to Mosaic's own scratch.
VMEM_BYTES = 96 * 2 ** 20
SUBLANE = 8     # second-minor tile unit
LANE = 128      # minor (lane) tile unit
F32_BYTES = 4   # accumulator scratch dtype
BUFFERS = 2     # the Pallas pipeline double-buffers every blocked operand


def _round_up(x: int, unit: int) -> int:
    return _cdiv(max(x, 1), unit) * unit


def kfused_panel_bytes(block_m: int, block_n: int, k: int, slice_k: int,
                       dtype_bytes: int = 4) -> int:
    """VMEM footprint of the kfused kernels.

    ``bitmap_spgemm_kfused_planned`` keeps the full-K operand panels
    VMEM-resident so the packed-k gathers never leave the core: a
    (block_m, Kp) A panel, a (Kp, block_n) B panel and the block's
    (S, slice_k) int32 schedule, each double-buffered, plus the
    (block_m, block_n) output block (double-buffered, counted at f32)
    and f32 accumulator, where Kp = S · slice_k = ceil(K / slice_k) ·
    slice_k.
    """
    kp = _cdiv(max(k, 1), slice_k) * slice_k
    return (BUFFERS * ((block_m * kp + kp * block_n) * dtype_bytes
                       + kp * 4 + block_m * block_n * F32_BYTES)
            + block_m * block_n * F32_BYTES)


def slice_panel_bytes(block_m: int, block_n: int, slice_k: int,
                      dtype_bytes: int = 4) -> int:
    """VMEM footprint of the slice-granular kernel: a (block_m, slice_k)
    A block, a (slice_k, block_n) B block and the output block, each
    double-buffered, plus the f32 accumulator."""
    return (BUFFERS * ((block_m * slice_k + slice_k * block_n) * dtype_bytes
                       + block_m * block_n * F32_BYTES)
            + block_m * block_n * F32_BYTES)


def knobs_valid(m: int, n: int, k: int, block_m: int, block_n: int,
                slice_k: int, *, use_kernel: bool = False,
                condense: Optional[str] = None, interpret: bool = False,
                dtype_bytes: int = 4) -> bool:
    """Is a (block_m, block_n, slice_k) knob vector valid for an
    (m, n, k) problem?

    The predicate every cache-served knob vector must satisfy before the
    dispatch applies it (a stale cache must degrade to the config
    fallback, never to a mis-tiled kernel):

    * tile divisibility — block_m a multiple of the 8-sublane unit,
      block_n a multiple of the 128-lane unit (8 under interpret, where
      lanes are emulated), slice_k a multiple of 8 and, compiled, of
      the 128-lane unit unless one slice spans all of K (the slice is
      the minor dimension of the A block);
    * no over-tiling — each knob at most the problem dimension rounded
      up to its tile unit (``clamp_geometry`` would silently shrink
      anything larger, so the served vector would not be the one that
      was tuned);
    * slice_k ≤ K (rounded up to the sublane unit);
    * VMEM panel fit for the kernel backends — the kfused kernels hold
      full-K operand panels resident, the slice-granular kernel one
      slice per step (:func:`kfused_panel_bytes` /
      :func:`slice_panel_bytes` ≤ :data:`VMEM_BYTES`).
    """
    if min(m, n, k, block_m, block_n, slice_k) <= 0:
        return False
    lane = SUBLANE if interpret else LANE
    if block_m % SUBLANE or block_n % lane or slice_k % SUBLANE:
        return False
    if not interpret and slice_k % LANE and slice_k < k:
        return False
    if block_m > _round_up(m, SUBLANE) or block_n > _round_up(n, lane):
        return False
    if slice_k > _round_up(k, SUBLANE):
        return False
    if use_kernel:
        if condense == "k":
            if kfused_panel_bytes(block_m, block_n, k, slice_k,
                                  dtype_bytes) > VMEM_BYTES:
                return False
        elif slice_panel_bytes(block_m, block_n, slice_k,
                               dtype_bytes) > VMEM_BYTES:
            return False
    return True


def clamp_geometry(m: int, n: int, k: int, block_m: int, block_n: int,
                   slice_k: int, interpret: bool) -> Tuple[int, int, int]:
    """Clamp block sizes for small problems, keeping lane alignment.

    The one geometry every kernel entry point and every externally built
    plan uses, so plans agree with the kernel's grid.  Compiled kernels
    also need the slice — the minor dimension of the A block — to be a
    lane multiple or all of K: a narrower slice (the KV decode's 32-slot
    value tile) widens to the next lane multiple.
    """
    block_m = min(block_m, max(8, m))
    block_n = min(block_n, max(8 if interpret else LANE, n))
    slice_k = effective_slice_k(k, slice_k)
    if not interpret and slice_k < k and slice_k % LANE:
        slice_k = min(_round_up(slice_k, LANE), k)
    return block_m, block_n, slice_k
