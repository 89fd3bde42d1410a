"""The single dispatch point for sparse matmuls (DESIGN.md §4.4).

Every projection in the model stack — MLP up/down, attention QKV/output,
MoE expert FFNs, the LM head, and ``DualSparseLinear`` — routes through
:func:`matmul` (2-D weights) or :func:`grouped_matmul` (stacked per-expert
weights).  The dispatch

* accepts any leading batch shape ``(..., K)`` and flattens it for the
  kernel (vmap-free: the flattened matmul *is* the batched matmul);
* accepts a :class:`~repro.sparse.activation.SparseActivation` on the
  activation side and a :class:`~repro.sparse.weights.PlannedWeight` on
  the weight side, in which case per-step planning is the cached-metadata
  AND of :func:`repro.sparse.plan.plan_from_activity`;
* falls back to on-the-fly planning from dense operands (bit-identical —
  see :func:`repro.sparse.plan.plan_operands`) when metadata is absent;
* records per-call :class:`~repro.core.stats.StepCounts` to the active
  :mod:`repro.sparse.tape` so serving/benchmarks can report per-layer
  skipped work.

Modes mirror ``DualSparseLinear``:

* ``dense``  — plain matmul, dense schedule accounting.
* ``weight`` — static weight-side skips only (activation assumed dense).
* ``dual``   — weight AND activation skips; with ``use_kernel`` the
  Pallas kernels execute the condensed schedule (2-D block-skip for
  :func:`matmul`, ragged grouped for :func:`grouped_matmul` —
  DESIGN.md §9).

Orthogonally, ``condense="k"`` (``ModelConfig.sparse_kcondense``) plans
at *element* granularity instead of whole k-slices: the bitmap AND is
taken per contraction index, stable-front-packed per output block, and
the fused kernels gather the packed k's out of their resident operand
panels — executed slices become ``ceil(nnz_AND / slice_k)`` rather than
quantising at ``slice_k`` (DESIGN.md §12).  The stats tape counts the
same element-granular schedule, so executed == counted stays the proof
of real elided work.

All modes compute exactly ``x @ w`` — sparsity changes the schedule, not
the math.
"""
from __future__ import annotations

import contextlib
import inspect
import warnings
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import stats
from repro.kernels.platform import resolve_interpret
from repro.sparse import plan as pln
from repro.sparse import tape
from repro.sparse import validate
from repro.sparse.activation import SparseActivation
from repro.sparse.weights import PlannedWeight

Operand = Union[jax.Array, SparseActivation]
Weight = Union[jax.Array, PlannedWeight]

MODES = ("dense", "weight", "dual")
CONDENSE = (None, "k")

# keys already warned about — configuration mismatches (a kernel that
# cannot run, a cached plan that cannot be sliced) must be *audible*, but
# once per process, not once per matmul
_WARNED: set = set()
_SUPPRESS_WARNINGS = False


def warn_once(key: str, message: str) -> None:
    """Emit ``message`` as a RuntimeWarning the first time ``key`` fires.

    The dispatch layer's contract is that an unsupported combination
    never *silently* changes what the stats tape reports — it either
    raises or warns here (ISSUE 4 / DESIGN.md §11)."""
    if key not in _WARNED and not _SUPPRESS_WARNINGS:
        _WARNED.add(key)
        warnings.warn(message, RuntimeWarning, stacklevel=3)


@contextlib.contextmanager
def warnings_suppressed():
    """Silence :func:`warn_once` within a region.

    For passes whose *purpose* is to hit the fallback paths — e.g.
    ``Engine.autotune_keys`` discovering cache keys by running with an
    unpopulated cache, where every miss is expected, not a
    misconfiguration.  Suppressed keys are not marked warned, so a real
    later miss stays audible.
    """
    global _SUPPRESS_WARNINGS
    prev = _SUPPRESS_WARNINGS
    _SUPPRESS_WARNINGS = True
    try:
        yield
    finally:
        _SUPPRESS_WARNINGS = prev


def kwargs_from_config(cfg, out_dtype=None) -> dict:
    """Dispatch kwargs from a ``ModelConfig``'s sparse_* fields.

    The raw config-constant tier.  Model/serving call sites no longer
    call this directly — they construct an :class:`~repro.sparse.site.
    OpSite` and let :func:`repro.sparse.site.resolve` run the cache →
    costmodel → config chain (DESIGN.md §16); this helper remains for
    direct dispatch users (tests, benches) that want the hand-set
    constants plus the in-dispatch ``autotune`` consultation.

    ``out_dtype`` (optional) rides along to the dispatch entry points
    for callers that need a pinned accumulation dtype.

    With ``cfg.sparse_autotune`` the returned kwargs also carry the
    per-call tuning-cache consultation (DESIGN.md §13): at each dispatch
    the cache is probed for the call's bucketed key, and on a hit the
    served knob vector overrides the config geometry/backend.  The
    config constants above stay in the dict as the fallback tier — a
    miss (or stale entry) executes exactly what an untuned run would.
    """
    kw = dict(mode=cfg.sparse_mode, block_m=cfg.sparse_block_m,
              block_n=cfg.sparse_block_n, slice_k=cfg.sparse_slice_k,
              use_kernel=cfg.sparse_use_kernel,
              condense="k" if cfg.sparse_kcondense else None)
    if out_dtype is not None:
        kw["out_dtype"] = out_dtype
    if getattr(cfg, "sparse_autotune", False):
        kw["autotune"] = True
        ts = getattr(cfg, "sparse_tune_sparsity", -1.0)
        if ts is not None and ts >= 0:
            kw["tune_sparsity"] = float(ts)
    return kw


def _consult_autotune(op: str, m: int, n: int, k: int, dtype,
                      tune_sparsity, interp: bool, extra: str = ""):
    """Probe the tuning cache for one call site (autotune=True paths).

    Returns the served :class:`~repro.sparse.autotune.Knobs` or None;
    a miss is audible once per bucketed key and falls back to the
    caller's config constants — the cache can change the schedule only,
    so numerics are untouched either way.
    """
    from repro.sparse import autotune as atn
    kn = atn.lookup(op, m, n, k, dtype=dtype, sparsity=tune_sparsity,
                    interpret=interp, extra=extra)
    if kn is None:
        key = atn.make_key(op, m, n, k, dtype=dtype,
                           sparsity=tune_sparsity, extra=extra)
        warn_once(
            f"autotune:miss:{key}",
            f"sparse.{op}: no tuning-cache entry for {key} — falling "
            "back to the config constants (run `bench_models --tune` "
            "to populate the cache)")
    return kn


def _kfused_fits(condense, use_kernel: bool, interp: bool, m: int, n: int,
                 k: int, block_m: int, block_n: int, slice_k: int,
                 dtype) -> Optional[str]:
    """``condense`` unless compiled kfused panels cannot fit VMEM.

    The kfused kernels keep full-K operand panels resident
    (:func:`repro.sparse.plan.kfused_panel_bytes`); where a call's
    geometry needs more than :data:`repro.sparse.plan.VMEM_BYTES` the
    slice-granular kernel runs instead (audibly, once per geometry).
    Interpret mode has no VMEM and keeps the requested schedule.
    """
    if condense != "k" or not use_kernel or interp:
        return condense
    need = pln.kfused_panel_bytes(block_m, block_n, k, slice_k,
                                  jnp.dtype(dtype).itemsize)
    if need <= pln.VMEM_BYTES:
        return condense
    warn_once(
        f"kfused:vmem:{m}x{n}x{k}:{block_m}:{block_n}:{slice_k}",
        f"sparse dispatch: kfused panels for ({m}, {n}, {k}) at "
        f"({block_m}, {block_n}, {slice_k}) need {need / 2 ** 20:.1f} MiB "
        f"of VMEM > {pln.VMEM_BYTES / 2 ** 20:.0f} MiB; running the "
        "slice-granular kernel for this geometry")
    return None


def _values(x: Operand) -> jax.Array:
    return x.values if isinstance(x, SparseActivation) else x


def _weight_array(w: Weight) -> jax.Array:
    return w.w if isinstance(w, PlannedWeight) else w


def _lhs_activity(x: Operand, x2: jax.Array, block_m: int, slice_k: int,
                  mode: str) -> jax.Array:
    """(Mt, S) block-row slice activity of the activation side."""
    mt = pln._cdiv(x2.shape[0], block_m)
    s = pln._cdiv(x2.shape[1], slice_k)
    if mode == "weight":  # activation treated as dense
        return jnp.ones((mt, s), dtype=bool)
    if isinstance(x, SparseActivation):
        rows = x.flatten_leading().row_slice_activity(slice_k)
    else:
        rows = pln.slice_activity_lhs(x2, slice_k)
    return pln.block_reduce_lhs(rows, block_m)


def _rhs_activity(w: Weight, block_n: int, slice_k: int) -> jax.Array:
    """(S, Nt) block-col slice activity of the weight side."""
    if isinstance(w, PlannedWeight):
        cols = w.col_slice_activity(slice_k)
    else:
        cols = pln.slice_activity_rhs(w, slice_k)
    return pln.block_reduce_rhs(cols, block_n)


def _lhs_element(x: Operand, x2: jax.Array, block_m: int,
                 mode: str) -> jax.Array:
    """(Mt, K) block-row *element* k-activity of the activation side.

    The ``condense="k"`` planning input (DESIGN.md §12): from the packed
    bitmap when the operand carries one (never from the values), from
    ``x != 0`` otherwise; all-true in weight mode.

    Exactness contract for *claimed* masks: the fused kernels' tail
    lanes gather k's this AND declares inactive, relying on their raw
    outer products being zero.  A SparseActivation whose bitmap declares
    a position zero while the value is non-zero is therefore only valid
    when the discrepancy is K-uniform per row (the KV score operand:
    whole slots masked ⇒ a block is either fully scheduled along k or
    fully skipped) or the values really are zero (the KV value operand:
    softmax-masked probabilities).  Masks that vary along K over
    non-zero values would make tail lanes add garbage — don't build
    such operands (pinned by test_kcondense_fused's KV decode parity).
    """
    mt = pln._cdiv(x2.shape[0], block_m)
    if mode == "weight":  # activation treated as dense
        return jnp.ones((mt, x2.shape[1]), dtype=bool)
    if isinstance(x, SparseActivation):
        return pln.element_activity_lhs(
            x.flatten_leading().element_mask(), block_m)
    return pln.element_activity_lhs(x2, block_m)


def _rhs_element(w: Weight, w_arr: jax.Array, block_n: int) -> jax.Array:
    """(K, Nt) block-col element k-activity of the weight side.

    ``PlannedWeight`` stores its pruning mask applied to the values, so
    ``w != 0`` is the exact static element structure on either operand
    form; a plan built with ``block_n`` serves the memoized activity
    instead of re-reducing it per call.
    """
    if isinstance(w, PlannedWeight):
        return w.col_element_activity(block_n)
    return pln.element_activity_rhs(w_arr, block_n)


def matmul(
    x: Operand,
    w: Weight,
    *,
    mode: str = "dense",
    block_m: int = 128,
    block_n: int = 128,
    slice_k: int = pln.SLICE_K,
    use_kernel: bool = False,
    condense: Optional[str] = None,
    interpret: Optional[bool] = None,
    collect_stats: bool = False,
    name: str = "matmul",
    out_dtype=None,
    autotune: bool = False,
    tune_sparsity: Optional[float] = None,
    op: str = "matmul",
) -> Tuple[jax.Array, Optional[stats.StepCounts]]:
    """y = x @ w with mode-selectable dual-side sparse scheduling.

    x: (..., K) array or SparseActivation; w: (K, N) array or
    PlannedWeight.  Returns (y (..., N), StepCounts or None).  Stats are
    computed when ``collect_stats`` or a stats tape is active.
    ``out_dtype`` sets the accumulation/output dtype on every compute
    path (``preferred_element_type`` on XLA, the f32-scratch flush dtype
    on the kernels) — the sparse KV decode path uses f32 here to match
    the dense attention's accumulation exactly.
    ``condense="k"`` plans (and with ``use_kernel`` executes) the
    schedule at element granularity — the fused K-condensation of
    DESIGN.md §12 — so unstructured sparsity inside k-slices is skipped,
    not just counted.
    ``autotune`` consults the persistent tuning cache
    (:mod:`repro.sparse.autotune`) for this call's bucketed
    (platform, dtype, M/N/K, sparsity) key; a hit overrides the
    geometry *and* backend knobs above, a miss warns once per key and
    keeps them — schedule-only either way, so outputs are unchanged.
    ``tune_sparsity`` is the static activation-sparsity hint the key is
    bucketed under (None → the 'any' bucket).  ``op`` names the tuning
    namespace the key lives in — :mod:`repro.sparse.conv` passes
    ``op="conv"`` so conv-lowered GEMM shapes tune independently of LM
    projections with the same bucketed geometry.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if condense not in CONDENSE:
        raise ValueError(
            f"condense must be one of {CONDENSE}, got {condense!r}")
    if validate.enabled():              # opt-in debug mode (DESIGN.md §17)
        validate.check_operands(x, w)
    w_arr = _weight_array(w)
    if w_arr.ndim != 2:
        raise ValueError(f"matmul expects 2-D weights, got {w_arr.shape}; "
                         "use grouped_matmul for stacked experts")
    xv = _values(x)
    lead = xv.shape[:-1]
    k = xv.shape[-1]
    x2 = xv.reshape(-1, k)
    t = x2.shape[0]
    n = w_arr.shape[1]
    w_arr = w_arr.astype(xv.dtype)

    interp = resolve_interpret(interpret)
    if autotune and mode != "dense":
        kn = _consult_autotune(op, t, n, k, x2.dtype,
                               tune_sparsity, interp)
        if kn is not None:
            tuned = kn.kwargs()
            block_m, block_n, slice_k = (tuned["block_m"],
                                         tuned["block_n"],
                                         tuned["slice_k"])
            use_kernel = tuned["use_kernel"]
            condense = tuned["condense"]
    block_m, block_n, slice_k = pln.clamp_geometry(
        t, n, k, block_m, block_n, slice_k, interp)
    if mode != "dense":
        condense = _kfused_fits(condense, use_kernel, interp, t, n, k,
                                block_m, block_n, slice_k, x2.dtype)
    mt, nt, s = (pln._cdiv(t, block_m), pln._cdiv(n, block_n),
                 pln._cdiv(k, slice_k))

    def _xla_matmul():
        if out_dtype is None:
            return x2 @ w_arr
        return jnp.matmul(x2, w_arr, preferred_element_type=out_dtype)

    want_stats = collect_stats or tape.active()
    steps = None
    if mode == "dense":
        if use_kernel:
            warn_once(
                "matmul:dense+use_kernel",
                "sparse.matmul: use_kernel has no effect in dense mode — "
                "the block-skip kernel only runs a condensed schedule; "
                "executing the XLA matmul (executed == dense steps)")
        if condense:
            warn_once(
                "matmul:dense+condense",
                "sparse.matmul: condense='k' has no effect in dense mode "
                "— there is no schedule to condense; executing the XLA "
                "matmul (executed == dense steps)")
        y = _xla_matmul()
        if want_stats:
            dense = jnp.asarray(mt * nt * s)
            steps = stats.StepCounts(dense=dense, sparse=dense,
                                     tiles_skipped=jnp.asarray(0))
    else:
        # plan only when something consumes it: the kernel's schedule or
        # the stats accounting (under jit XLA would DCE a dead plan, but
        # eager callers would pay the pack for nothing)
        if use_kernel or want_stats:
            if condense == "k":
                # element granularity: the fused kernel gathers packed
                # k's, so both the schedule and the accounting are
                # ceil(nnz_AND / slice_k) per block (DESIGN.md §12)
                col_e = _lhs_element(x, x2, block_m, mode)
                row_e = _rhs_element(w, w_arr, block_n)
                if use_kernel:
                    kplan = pln.plan_kcondensed(col_e, row_e, slice_k)
                    counts = kplan.counts
                else:  # stats only: skip the schedules' pack
                    counts = pln.kcondensed_counts(col_e, row_e, slice_k)
            else:
                col = _lhs_activity(x, x2, block_m, slice_k, mode)
                row = _rhs_activity(w, block_n, slice_k)
                if use_kernel:
                    ks, counts = pln.plan_from_activity(col, row)
                else:  # stats only: skip the schedule's pack
                    counts = pln.counts_from_activity(col, row)
            if want_stats:
                steps = pln.counts_to_steps(counts, s)
        if use_kernel:
            from repro.kernels import bitmap_spgemm as bsk
            if condense == "k":
                y = bsk.bitmap_spgemm_kfused_planned(
                    x2, w_arr, kplan.gk, kplan.counts, block_m=block_m,
                    block_n=block_n, slice_k=slice_k, interpret=interp,
                    out_dtype=out_dtype)
            else:
                y = bsk.bitmap_spgemm_planned(
                    x2, w_arr, ks, counts, block_m=block_m,
                    block_n=block_n, slice_k=slice_k, interpret=interp,
                    out_dtype=out_dtype)
        else:
            y = _xla_matmul()
    if steps is not None:
        # kernel path executes the condensed schedule; XLA computes dense
        tape.record(name, steps,
                    steps.sparse if mode != "dense" and use_kernel
                    else None)
    return y.reshape(*lead, n), steps


def _grouped_lhs_activity(x: Operand, xv: jax.Array, block_m: int,
                          slice_k: int, mode: str) -> jax.Array:
    """(E, Mt, S) per-expert block-row slice activity (activation side)."""
    e, c, k = xv.shape
    mt = pln._cdiv(c, block_m)
    s = pln._cdiv(k, slice_k)
    if mode == "weight":  # activation treated as dense
        return jnp.ones((e, mt, s), dtype=bool)
    if isinstance(x, SparseActivation):
        rows = x.row_slice_activity(slice_k)
    else:
        rows = pln.slice_activity_lhs(xv, slice_k)
    return jax.vmap(lambda r: pln.block_reduce_lhs(r, block_m))(rows)


def _grouped_rhs_activity(w: Weight, w_arr: jax.Array, block_n: int,
                          slice_k: int) -> jax.Array:
    """(E, S, Nt) per-expert block-col slice activity (weight side)."""
    if isinstance(w, PlannedWeight):
        cols = w.col_slice_activity(slice_k)
    else:
        cols = jax.vmap(
            lambda wi: pln.slice_activity_rhs(wi, slice_k))(w_arr)
    return jax.vmap(lambda a: pln.block_reduce_rhs(a, block_n))(cols)


def _grouped_lhs_element(x: Operand, xv: jax.Array, block_m: int,
                         mode: str) -> jax.Array:
    """(E, Mt, K) per-expert block-row element k-activity."""
    e, c, k = xv.shape
    mt = pln._cdiv(c, block_m)
    if mode == "weight":  # activation treated as dense
        return jnp.ones((e, mt, k), dtype=bool)
    mask = x.element_mask() if isinstance(x, SparseActivation) else xv
    return jax.vmap(
        lambda mi: pln.element_activity_lhs(mi, block_m))(mask)


def _grouped_rhs_element(w: Weight, w_arr: jax.Array,
                         block_n: int) -> jax.Array:
    """(E, K, Nt) per-expert block-col element k-activity (memoized on
    a ``block_n``-planned :class:`PlannedWeight`)."""
    if isinstance(w, PlannedWeight):
        return w.col_element_activity(block_n)
    return jax.vmap(
        lambda wi: pln.element_activity_rhs(wi, block_n))(w_arr)


def grouped_matmul(
    x: Operand,
    w: Weight,
    *,
    mode: str = "dense",
    block_m: int = 128,
    block_n: int = 128,
    slice_k: int = pln.SLICE_K,
    use_kernel: bool = False,
    condense: Optional[str] = None,
    interpret: Optional[bool] = None,
    collect_stats: bool = False,
    name: str = "grouped_matmul",
    out_dtype=None,
    autotune: bool = False,
    tune_sparsity: Optional[float] = None,
) -> Tuple[jax.Array, Optional[stats.StepCounts]]:
    """Batched-weights matmul: x (E, C, K) @ w (E, K, N) → (E, C, N).

    The MoE expert-FFN pattern: each expert has its own weight matrix and
    its own capacity buffer (whose empty slots are genuine zero rows —
    dynamic sparsity from the gating itself), filled to a *different* row
    count per expert (ragged occupancy).  With ``use_kernel`` the ragged
    grouped Pallas kernel runs one (E, Mt, Nt, S) grid over all experts
    and executes the per-expert condensed schedules — the blocks the tape
    counts as skipped are never scheduled (DESIGN.md §9).  Without it,
    compute falls back to one XLA einsum with the same schedule
    accounting.  ``condense="k"`` plans (and with ``use_kernel``
    executes) per-expert schedules at element granularity
    (DESIGN.md §12), same contract as :func:`matmul` — as are
    ``autotune``/``tune_sparsity`` (the grouped key additionally carries
    the expert-count bucket).
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if condense not in CONDENSE:
        raise ValueError(
            f"condense must be one of {CONDENSE}, got {condense!r}")
    if validate.enabled():              # opt-in debug mode (DESIGN.md §17)
        validate.check_operands(x, w)
    w_arr = _weight_array(w)
    xv = _values(x)
    if xv.ndim != 3 or w_arr.ndim != 3:
        raise ValueError(f"grouped_matmul expects (E,C,K)×(E,K,N), got "
                         f"{xv.shape} × {w_arr.shape}")
    e, c, k = xv.shape
    n = w_arr.shape[-1]
    w_arr = w_arr.astype(xv.dtype)

    interp = resolve_interpret(interpret)
    if autotune and mode != "dense":
        from repro.sparse import autotune as atn
        kn = _consult_autotune("grouped", c, n, k, xv.dtype,
                               tune_sparsity, interp,
                               extra=f"e{atn.bucket_dim(e)}")
        if kn is not None:
            tuned = kn.kwargs()
            block_m, block_n, slice_k = (tuned["block_m"],
                                         tuned["block_n"],
                                         tuned["slice_k"])
            use_kernel = tuned["use_kernel"]
            condense = tuned["condense"]
    block_m, block_n, slice_k = pln.clamp_geometry(
        c, n, k, block_m, block_n, slice_k, interp)
    if mode != "dense":
        condense = _kfused_fits(condense, use_kernel, interp, c, n, k,
                                block_m, block_n, slice_k, xv.dtype)
    s = pln._cdiv(k, slice_k)

    def _xla_grouped():
        if out_dtype is None:
            return jnp.einsum("eck,ekn->ecn", xv, w_arr)
        return jnp.einsum("eck,ekn->ecn", xv, w_arr,
                          preferred_element_type=out_dtype)

    want_stats = collect_stats or tape.active()
    run_kernel = use_kernel and mode != "dense"
    steps = None
    if use_kernel and not run_kernel:
        warn_once(
            "grouped_matmul:dense+use_kernel",
            "sparse.grouped_matmul: use_kernel has no effect in dense "
            "mode — the ragged grouped kernel only runs a condensed "
            "schedule; executing the XLA einsum (executed == dense steps)")
    if condense and mode == "dense":
        warn_once(
            "grouped_matmul:dense+condense",
            "sparse.grouped_matmul: condense='k' has no effect in dense "
            "mode — there is no schedule to condense; executing the XLA "
            "einsum (executed == dense steps)")
    if mode == "dense":
        y = _xla_grouped()
        if want_stats:
            dense = jnp.asarray(
                e * pln._cdiv(c, block_m) * pln._cdiv(n, block_n) * s)
            steps = stats.StepCounts(dense=dense, sparse=dense,
                                     tiles_skipped=jnp.asarray(0))
            tape.record(name, steps)
    else:
        if run_kernel or want_stats:
            if condense == "k":
                cols_e = _grouped_lhs_element(x, xv, block_m, mode)
                rows_e = _grouped_rhs_element(w, w_arr, block_n)
                if run_kernel:
                    kplan = pln.plan_grouped_kcondensed(cols_e, rows_e,
                                                        slice_k)
                    counts = kplan.counts
                else:  # stats only: skip the schedules' pack
                    counts = pln.grouped_kcondensed_counts(cols_e, rows_e,
                                                           slice_k)
            else:
                cols = _grouped_lhs_activity(x, xv, block_m, slice_k,
                                             mode)
                rows = _grouped_rhs_activity(w, w_arr, block_n, slice_k)
                if run_kernel:
                    ks, counts = pln.plan_grouped_activity(cols, rows)
                else:  # stats only: skip the schedule's pack
                    counts = pln.grouped_counts_from_activity(cols, rows)
            if want_stats:
                steps = pln.grouped_counts_to_steps(counts, s)
        if run_kernel:
            from repro.kernels import grouped_spgemm as gsk
            if condense == "k":
                y = gsk.grouped_spgemm_kfused_planned(
                    xv, w_arr, kplan.gk, kplan.counts, block_m=block_m,
                    block_n=block_n, slice_k=slice_k, interpret=interp,
                    out_dtype=out_dtype)
            else:
                y = gsk.grouped_spgemm_planned(
                    xv, w_arr, ks, counts, block_m=block_m,
                    block_n=block_n, slice_k=slice_k, interpret=interp,
                    out_dtype=out_dtype)
        else:
            y = _xla_grouped()
        if steps is not None:
            tape.record(name, steps,
                        steps.sparse if run_kernel else None)
    return y, steps


# every knob project may forward to matmul — a resolved OpSite dict or a
# hand-written call site must fail loudly on a typo'd knob name instead
# of silently dropping it into **kwargs
_MATMUL_KNOBS = frozenset(
    p for p in inspect.signature(matmul).parameters if p not in ("x", "w"))


def project(
    x: Operand,
    w: Weight,
    *,
    n_contract: int = 1,
    plan_act: Optional[jax.Array] = None,
    **kwargs,
) -> Tuple[jax.Array, Optional[stats.StepCounts]]:
    """Tensor projection through :func:`matmul`.

    Contracts the last ``n_contract`` axes of ``x`` with the first
    ``n_contract`` axes of ``w`` and restores the remaining weight axes on
    the output — the attention einsums ``bsd,dhk->bshk`` (n_contract=1)
    and ``bshk,hkd->bsd`` (n_contract=2) without hand-reshaping at the
    call sites.  ``plan_act`` is an optional cached weight-side slice
    activity over the *flattened* contraction axis (shape (S, prod(out
    dims))); without it the weight side is re-reduced on the fly.
    ``kwargs`` must name real :func:`matmul` knobs — unknown names raise
    rather than vanish.
    """
    unknown = set(kwargs) - _MATMUL_KNOBS
    if unknown:
        raise TypeError(
            f"sparse.project: unknown dispatch knob(s) {sorted(unknown)}; "
            f"valid knobs: {sorted(_MATMUL_KNOBS)}")
    w_arr = _weight_array(w)
    k_dims = w_arr.shape[:n_contract]
    out_dims = w_arr.shape[n_contract:]
    kflat = 1
    for d in k_dims:
        kflat *= d
    if isinstance(x, SparseActivation):
        if n_contract != 1:
            raise ValueError("SparseActivation carries metadata over one "
                             "contraction axis only")
        x_in: Operand = x
    else:
        x_in = x.reshape(*x.shape[:x.ndim - n_contract], kflat)
    if isinstance(w, PlannedWeight) and n_contract == 1 and not out_dims[1:]:
        w_in: Weight = w
    else:
        w_in = w_arr.reshape(kflat, -1)
        if plan_act is not None:
            w_in = PlannedWeight(
                w=w_in, slice_act=plan_act,
                slice_k=pln.effective_slice_k(
                    kflat, kwargs.get("slice_k", pln.SLICE_K)))
    y, steps = matmul(x_in, w_in, **kwargs)
    return y.reshape(*y.shape[:-1], *out_dims), steps
