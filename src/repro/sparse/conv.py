"""``repro.sparse.conv`` — dual-side sparse convolution through the
dispatch layer (DESIGN.md §15).

The paper's SpCONV (§IV) composes a bitmap implicit im2col with the
outer-product SpGEMM so the lowered matrix never exists in HBM.  This
module is its dispatch-layer realisation:

* :func:`im2col_sparse` lowers an NHWC feature map with the bitmap
  im2col (Pallas kernels on the ``use_kernel`` path, the jnp reference
  otherwise) and emits a genuine
  :class:`~repro.sparse.activation.SparseActivation` — the packed
  element bitmap and per-row slice activity ride straight out of the
  im2col's lowered bitmap, *never* re-derived from a ``values != 0``
  compare.  Layout is inner-product ``(..., P, KH·KW·C)``: rows are
  output positions, the contraction axis is the lowered k — exactly the
  unstructured-K case ``condense="k"`` was built for (DESIGN.md §12).
* :class:`PlannedConv` / :func:`plan_conv` cache conv weights as
  :class:`~repro.sparse.weights.PlannedWeight` ``(KH·KW·C, F)`` fibers
  (with the memoized "@elem" element activity when ``block_n`` is
  given), built once at init/load like every other layer plan.
* :func:`conv2d` routes the lowered GEMM through
  :func:`repro.sparse.dispatch.matmul` with the full
  ``use_kernel``/``condense="k"``/``autotune=True`` surface — conv
  shapes are first-class ``op="conv"`` TuningCache keys — and every
  executed/counted step lands on the :mod:`repro.sparse.tape` under the
  call's ``name`` (``conv.*`` in the model frontends), same
  executed == counted contract as the LM paths.

Orientation note.  The paper generates ``L^T (KKC, P)`` a column at a
time and computes ``out(F, P) = W_flat(F, KKC) @ L^T``; the dispatch
layer's canonical form is activation-major, so we hand it the transpose
pair — ``L (P, KKC) @ W_flat (KKC, F)`` — which is the same set of
(k-fiber × output-position) products under the same two-level bitmap
schedule.  The metadata is bitmap-borne end to end; the dense-layout
``values`` tensor the dispatch consumes is the positionally-addressed
operand every kernel in this repo takes (the condensed buffers stay an
encode-side representation, as in DESIGN.md §2).

``repro.core.spconv`` keeps the dense oracles and a thin wrapper over
this module for parity tests.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm
from repro.core import im2col as i2c
from repro.core import stats
from repro.kernels.platform import resolve_interpret
from repro.sparse import dispatch as dsp
from repro.sparse import plan as pln
from repro.sparse import tape
from repro.sparse.activation import SparseActivation
from repro.sparse.weights import PlannedWeight, plan_weight


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PlannedConv:
    """Cached conv-layer plan: ``(KH·KW·C, F)`` fibers + static geometry.

    weight : the reshaped conv kernel as a :class:`PlannedWeight` —
             per-column slice activity (and optionally the "@elem"
             element activity) memoized at build time.
    kh/kw  : static spatial kernel extent (recovers the 4-D view).
    site   : optional static :class:`~repro.sparse.site.OpSite` — the
             declarative call-site descriptor this plan belongs to
             (DESIGN.md §16).
    """
    weight: PlannedWeight
    kh: int = dataclasses.field(metadata=dict(static=True))
    kw: int = dataclasses.field(metadata=dict(static=True))
    site: Optional[object] = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def shape(self) -> Tuple[int, int, int, int]:
        kkc, f = self.weight.w.shape
        c = kkc // (self.kh * self.kw)
        return (self.kh, self.kw, c, f)

    @property
    def dtype(self):
        return self.weight.dtype

    def w4d(self) -> jax.Array:
        """The (KH, KW, C, F) view (for the dense-mode lax.conv path)."""
        kh, kw, c, f = self.shape
        return self.weight.w.reshape(kh, kw, c, f)


def plan_conv(w: jax.Array, mask: Optional[jax.Array] = None,
              slice_k: int = pln.SLICE_K,
              block_n: Optional[int] = None) -> PlannedConv:
    """Build the static conv weight plan (call once per layer).

    w: (KH, KW, C, F); mask (same shape, optional) is the pruning mask.
    The kernel is reshaped to ``(KH·KW·C, F)`` — row k = (dy, dx, c) in
    the same order the im2col lowers — and planned at the effective
    slice granularity the dispatch will clamp to.  ``block_n``
    additionally memoizes the ``condense="k"`` element activity.
    """
    if w.ndim != 4:
        raise ValueError(f"plan_conv expects (KH,KW,C,F), got {w.shape}")
    kh, kw, c, f = w.shape
    kkc = kh * kw * c
    w2 = w.reshape(kkc, f)
    m2 = mask.reshape(kkc, f) if mask is not None else None
    pw = plan_weight(w2, m2, slice_k=pln.effective_slice_k(kkc, slice_k),
                     block_n=block_n)
    return PlannedConv(weight=pw, kh=kh, kw=kw)


def lowered_to_activation(lb: i2c.LoweredBitmap,
                          slice_k: int = pln.SLICE_K) -> SparseActivation:
    """``LoweredBitmap`` → inner-product-layout :class:`SparseActivation`.

    Leading-dim safe (a vmapped im2col yields ``(N, KKC, ·)`` fields).
    The element mask comes from the lowered *bitmap* (unpack, transpose,
    repack over the new trailing axis) and the slice activity is reduced
    from that mask — metadata never round-trips through a dense
    ``values != 0`` compare.  The values tensor is scattered back to
    positional (…, P, KKC) layout, which is the operand form every
    kernel in this repo consumes (DESIGN.md §2).
    """
    vals = lb.values                                      # (..., KKC, P)
    p = vals.shape[-1]
    mask = bm.unpack_bits(lb.bitmap, axis=-1)[..., :p]    # (..., KKC, P)
    # decode the row-condensed values by popcount offset (bm.decode for
    # arbitrary leading dims)
    pos = jnp.cumsum(mask, axis=-1) - 1
    dense = jnp.where(
        mask, jnp.take_along_axis(vals, jnp.maximum(pos, 0), axis=-1), 0
    ).astype(vals.dtype)
    mask_t = jnp.swapaxes(mask, -1, -2)                   # (..., P, KKC)
    vals_t = jnp.swapaxes(dense, -1, -2)
    kkc = vals_t.shape[-1]
    sk = pln.effective_slice_k(kkc, slice_k)
    return SparseActivation(
        values=vals_t,
        bitmap=bm.pack_bits_padded(mask_t, axis=-1),
        slice_act=pln.slice_activity_lhs(mask_t, sk),
        slice_k=sk)


def im2col_sparse(x: jax.Array, kh: int, kw: int, stride: int = 1, *,
                  slice_k: int = pln.SLICE_K, use_kernel: bool = False,
                  interpret: Optional[bool] = None) -> SparseActivation:
    """Bitmap implicit im2col emitting a :class:`SparseActivation`.

    x: (N, H, W, C) or (H, W, C), VALID padding.  Returns the lowered
    activation in inner-product layout ``(N, P, KH·KW·C)`` (or
    ``(P, KKC)`` unbatched).  ``use_kernel`` runs the Pallas
    encode + im2col kernels (stride-1 fast path and the strided
    variant); otherwise the jnp reference — identical outputs.
    """
    single = x.ndim == 3
    xb = x[None] if single else x
    if xb.ndim != 4:
        raise ValueError(f"im2col_sparse expects NHWC, got {x.shape}")
    if use_kernel:
        from repro.kernels import ops as kops

        def lower(img):
            return kops.sparse_im2col(img, kh, kw, stride,
                                      interpret=interpret)
    else:
        def lower(img):
            return i2c.im2col_bitmap(img, kh, kw, stride)

    lb = jax.vmap(lower)(xb)
    act = lowered_to_activation(lb, slice_k)
    if single:
        return SparseActivation(
            values=act.values[0], bitmap=act.bitmap[0],
            slice_act=act.slice_act[0], slice_k=act.slice_k)
    return act


ConvWeight = Union[jax.Array, PlannedConv]


def conv2d(
    x: jax.Array,
    w: ConvWeight,
    stride: int = 1,
    *,
    mode: str = "dense",
    block_m: int = 128,
    block_n: int = 128,
    slice_k: int = pln.SLICE_K,
    use_kernel: bool = False,
    condense: Optional[str] = None,
    interpret: Optional[bool] = None,
    collect_stats: bool = False,
    name: str = "conv",
    out_dtype=None,
    autotune: bool = False,
    tune_sparsity: Optional[float] = None,
) -> Tuple[jax.Array, Optional[stats.StepCounts]]:
    """2-D convolution with dual-side sparse scheduling (VALID padding).

    x: (N, H, W, C); w: (KH, KW, C, F) array or :class:`PlannedConv`.
    Returns ``(y (N, OH, OW, F), StepCounts or None)``.  All modes
    compute exactly the convolution — sparsity changes the schedule,
    not the math:

    * ``dense``  — ``lax.conv_general_dilated`` (no lowering at all),
      dense GEMM-equivalent schedule on the tape.
    * ``weight``/``dual`` — bitmap implicit im2col
      (:func:`im2col_sparse`) feeding :func:`repro.sparse.matmul` with
      the dispatch's full surface: ``use_kernel`` executes the
      condensed schedule, ``condense="k"`` plans/executes at element
      granularity, ``autotune`` consults the TuningCache under
      first-class ``op="conv"`` keys.  The batch dimension flattens
      into the GEMM's rows (one GEMM covers all N images).

    Step accounting lands on the active tape under ``name`` with the
    same executed == counted contract as the LM projections.
    """
    if x.ndim != 4:
        raise ValueError(f"conv2d expects NHWC input, got {x.shape}")
    if mode not in dsp.MODES:
        raise ValueError(f"mode must be one of {dsp.MODES}, got {mode!r}")
    if isinstance(w, PlannedConv):
        kh, kw, c_w, f = w.shape
        w_gemm: Union[jax.Array, PlannedWeight] = w.weight
        w4 = w.w4d()
    else:
        if w.ndim != 4:
            raise ValueError(f"conv2d expects (KH,KW,C,F) weights, got "
                             f"{w.shape}")
        kh, kw, c_w, f = w.shape
        w_gemm = w.reshape(kh * kw * c_w, f)
        w4 = w
    n_im, h, wd, c = x.shape
    if c != c_w:
        raise ValueError(f"channel mismatch: input {c} vs weight {c_w}")
    oh, ow = i2c.out_size(h, kh, stride), i2c.out_size(wd, kw, stride)
    p = oh * ow
    kkc = kh * kw * c

    if mode == "dense":
        if use_kernel:
            dsp.warn_once(
                "conv:dense+use_kernel",
                "sparse.conv2d: use_kernel has no effect in dense mode — "
                "executing lax.conv (executed == dense steps)")
        if condense:
            dsp.warn_once(
                "conv:dense+condense",
                "sparse.conv2d: condense='k' has no effect in dense mode "
                "— there is no schedule to condense; executing lax.conv "
                "(executed == dense steps)")
        kwargs = {}
        if out_dtype is not None:
            kwargs["preferred_element_type"] = out_dtype
        y = jax.lax.conv_general_dilated(
            x, w4.astype(x.dtype), window_strides=(stride, stride),
            padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            **kwargs)
        steps = None
        if collect_stats or tape.active():
            # the GEMM-equivalent dense schedule, mirroring matmul's
            # dense branch so conv and LM entries are summable
            interp = resolve_interpret(interpret)
            bm_, bn_, sk_ = pln.clamp_geometry(
                n_im * p, f, kkc, block_m, block_n, slice_k, interp)
            dense = jnp.asarray(
                pln._cdiv(n_im * p, bm_) * pln._cdiv(f, bn_)
                * pln._cdiv(kkc, sk_))
            steps = stats.StepCounts(dense=dense, sparse=dense,
                                     tiles_skipped=jnp.asarray(0))
            tape.record(name, steps)
        return y, steps

    act = im2col_sparse(x, kh, kw, stride, slice_k=slice_k,
                        use_kernel=use_kernel, interpret=interpret)
    y2, steps = dsp.matmul(
        act, w_gemm, mode=mode, block_m=block_m, block_n=block_n,
        slice_k=slice_k, use_kernel=use_kernel, condense=condense,
        interpret=interpret, collect_stats=collect_stats, name=name,
        out_dtype=out_dtype, autotune=autotune,
        tune_sparsity=tune_sparsity, op="conv")
    return y2.reshape(n_im, oh, ow, f), steps
