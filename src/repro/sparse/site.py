"""``repro.sparse.site`` — declarative per-call-site dispatch resolution
(DESIGN.md §16).

Every sparse matmul in the model stack used to re-thread the dispatch
knob vector (mode/block_m/block_n/slice_k/use_kernel/condense/out_dtype)
by hand via ``dispatch.kwargs_from_config``.  This module replaces that
plumbing with a declarative descriptor:

* :class:`OpSite` names one call site — its op kind (the TuningCache
  namespace: ``matmul``/``grouped``/``conv``/``attn.score``/
  ``attn.value``), its tape name, the logical axes of its weight (the
  sharding-spec source, see :func:`repro.distributed.sharding.
  plan_specs_from_sites`), and optional dtype/sparsity hints.  Layers
  build sites **once at plan time** via the memoized :func:`make` and
  attach them to their cached plans
  (:class:`~repro.sparse.weights.PlannedWeight` /
  :class:`~repro.sparse.conv.PlannedConv` carry a static ``site``
  field).
* :func:`resolve` turns a site + ``ModelConfig`` + concrete call
  geometry into the dispatch kwargs through the three-tier chain that
  previously lived inline in ``dispatch.matmul``:

  1. **TuningCache** (``cfg.sparse_autotune``) — the bucketed
     (platform, dtype, op, M/N/K, sparsity) key, served knobs
     re-validated by :func:`repro.sparse.plan.knobs_valid`;
  2. **costmodel** (``cfg.sparse_costmodel``) — the top
     :func:`repro.sparse.autotune.candidates` pick (sparse roofline +
     step-fraction scorer) when the cache has no measurement;
  3. **config constants** — the hand-set ``sparse_*`` fields, with the
     attention-aware twist that ``attn.score`` reads its row tile and
     ``attn.value`` its contraction tile from ``cfg.sparse_block_t``
     (the KV decode slot tile).

  Resolution runs host-side at trace time, so the served knobs are
  jit-constants: a cache hit changes the *schedule* of the traced
  program, never its math, and adds zero extra traces (the PR 7
  one-decode-trace contract is untouched).
* :func:`matmul` / :func:`grouped_matmul` / :func:`project` /
  :func:`conv2d` are the call-site entry points: they derive the call
  geometry from the operands exactly as the dispatch layer does (so
  cache keys are identical to the ones ``autotune=True`` dispatch calls
  record), resolve the site, and forward to
  :mod:`repro.sparse.dispatch` / :mod:`repro.sparse.conv` with
  ``autotune=False`` — the consultation already happened here, exactly
  once.

The attention decode sites are the point of the exercise: ``attn.score``
is keyed on (M=T slots, N=G heads-per-group, K=head_dim) so the tuned
``block_m`` *is* the tuned score tile, and ``attn.value`` on
(M=G, N=head_dim, K=T slots) so the tuned ``slice_k`` *is* the tuned
value tile — ``sparse_block_t`` becomes a measured, cache-keyed knob
(swept by :func:`repro.sparse.autotune.tune_attn`) instead of a config
constant.  Both carry the ``e``-bucket extra (E = batch·KV heads), so
batched serving geometries tune independently of single-slot decode.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import im2col as i2c
from repro.kernels.platform import resolve_interpret
from repro.sparse import conv as scv
from repro.sparse import dispatch as dsp
from repro.sparse.activation import SparseActivation

OPS = ("matmul", "grouped", "conv", "attn.score", "attn.value")


@dataclasses.dataclass(frozen=True)
class OpSite:
    """One declarative sparse call site (hashable, jit-static).

    op       : TuningCache namespace — one of :data:`OPS`.
    name     : stats-tape entry name (``mlp.up``, ``attn.score``, …).
    axes     : logical names of the weight's axes (``("embed", "mlp")``,
               ``("experts", "mlp", "embed")``, …) — what sharding specs
               are derived from, instead of per-call-site PartitionSpec
               tables.
    shape    : optional logical weight shape (documentation; the
               resolver keys on the *call* geometry).
    dtype    : optional compute-dtype name ("" → follow the operands).
    out_dtype: optional accumulation/output dtype name ("" → dispatch
               default).  The KV decode sites pin "float32" here so the
               XLA fallback matches dense attention bit-for-bit.
    sparsity : static activation-sparsity hint for the cache key
               (-1 → ``cfg.sparse_tune_sparsity`` / the 'any' bucket).
    """
    op: str
    name: str
    axes: Tuple[str, ...] = ()
    shape: Tuple[int, ...] = ()
    dtype: str = ""
    out_dtype: str = ""
    sparsity: float = -1.0


@functools.lru_cache(maxsize=None)
def make(op: str, name: str, *, axes: Tuple[str, ...] = (),
         shape: Tuple[int, ...] = (), dtype: str = "",
         out_dtype: str = "", sparsity: float = -1.0) -> OpSite:
    """Memoized :class:`OpSite` constructor — "once at plan time" for
    free: every trace/call returns the same descriptor object."""
    if op not in OPS:
        raise ValueError(f"OpSite op must be one of {OPS}, got {op!r}")
    return OpSite(op=op, name=name, axes=tuple(axes), shape=tuple(shape),
                  dtype=dtype, out_dtype=out_dtype,
                  sparsity=float(sparsity))


# ---------------------------------------------------------------------------
# per-site quarantine (DESIGN.md §17): a kernel/kfused backend that
# raised at a site is degraded to the XLA arm for the rest of the
# session.  Numerics are untouched — the XLA arm computes the same
# contraction — so a Pallas lowering failure costs a warn-once and the
# kernel speedup at that one site, never the request or the process.

_QUARANTINED: dict = {}          # (op, name) -> first failure reason


def quarantined(st: OpSite) -> bool:
    return (st.op, st.name) in _QUARANTINED


def quarantine(st: OpSite, reason: str) -> None:
    _QUARANTINED.setdefault((st.op, st.name), reason)


def clear_quarantine() -> None:
    """Lift all quarantines (tests / new process epoch)."""
    _QUARANTINED.clear()


def quarantine_report() -> dict:
    """``{"op:name": reason}`` — part of ``Engine.health()``."""
    return {f"{op}:{name}": r
            for (op, name), r in sorted(_QUARANTINED.items())}


def _degrade(st: OpSite, kw: dict) -> dict:
    """Force the XLA arm of a quarantined site's resolved knobs."""
    if kw.get("use_kernel") and quarantined(st):
        kw = dict(kw, use_kernel=False, condense=None)
    return kw


def _guarded(st: OpSite, kw: dict, call):
    """Run ``call(kw)``; a kernel-arm failure retries on the XLA arm
    inside the same trace and quarantines the site.

    Kernel backends are invoked at trace time (dispatch imports them
    lazily inside its function bodies), so a lowering/backend exception
    surfaces here whether the caller is eager or jitted.  If the XLA
    retry *also* fails the error was never the kernel's — it
    propagates untouched.
    """
    if not kw.get("use_kernel"):
        return call(kw)
    try:
        return call(kw)
    except Exception as e:  # noqa: BLE001 — backend failures are varied
        fallback = dict(kw, use_kernel=False, condense=None)
        out = call(fallback)          # raises if the fault wasn't the kernel's
        quarantine(st, f"{type(e).__name__}: {e}")
        dsp.warn_once(
            f"quarantine:{st.op}:{st.name}",
            f"sparse.site: kernel backend failed at {st.op}:{st.name} "
            f"({type(e).__name__}: {e}); site degraded to the XLA arm "
            "for the rest of the session (numerics preserved)")
        return out


def _base_kwargs(st: OpSite, cfg) -> dict:
    """Tier 3: the hand-set config constants for this site."""
    kw = dict(mode=cfg.sparse_mode, block_m=cfg.sparse_block_m,
              block_n=cfg.sparse_block_n, slice_k=cfg.sparse_slice_k,
              use_kernel=cfg.sparse_use_kernel,
              condense="k" if cfg.sparse_kcondense else None)
    # the KV decode slot tile: score tiles block-rows of slots,
    # value slices the slot contraction axis (DESIGN.md §16)
    if st.op == "attn.score":
        kw["block_m"] = cfg.sparse_block_t
    elif st.op == "attn.value":
        kw["slice_k"] = cfg.sparse_block_t
    if st.out_dtype:
        kw["out_dtype"] = jnp.dtype(st.out_dtype)
    return kw


@functools.lru_cache(maxsize=None)
def _costmodel_knobs(op: str, m: int, n: int, k: int, e: int,
                     dtype_name: str, sparsity: float, interp: bool):
    """Tier 2: best analytic candidate (memoized — host-side resolution
    must stay cheap on the trace path)."""
    from repro.sparse import autotune as atn
    cands = atn.candidates(
        m, n, k, a_sparsity=max(sparsity, 0.0),
        dtype_bytes=atn._DTYPE_BYTES.get(dtype_name, 4),
        interpret=interp, n_groups=max(e, 1), max_candidates=1)
    return cands[0] if cands else None


def resolve(st: OpSite, cfg, *, m: int, n: int, k: int, e: int = 1,
            dtype=jnp.float32, interpret: Optional[bool] = None) -> dict:
    """Site + config + call geometry → concrete dispatch kwargs.

    The cache → costmodel → config chain (module docstring).  Dense mode
    short-circuits to the config constants (there is no schedule to
    tune).  The returned dict never carries ``autotune`` — consultation
    happens here, once, and the dispatch is invoked with the resolved
    knobs as plain constants.
    """
    kw = _base_kwargs(st, cfg)
    if cfg.sparse_mode == "dense":
        return _degrade(st, kw)
    interp = resolve_interpret(interpret)
    dt = jnp.dtype(st.dtype) if st.dtype else jnp.dtype(dtype)
    hint = st.sparsity if st.sparsity >= 0 else float(
        getattr(cfg, "sparse_tune_sparsity", -1.0))
    hint = hint if hint >= 0 else None
    extra = ""
    if st.op in ("grouped", "attn.score", "attn.value"):
        from repro.sparse import autotune as atn
        extra = f"e{atn.bucket_dim(e)}"
    if getattr(cfg, "sparse_autotune", False):
        kn = dsp._consult_autotune(st.op, m, n, k, dt, hint, interp,
                                   extra=extra)
        if kn is not None:
            kw.update(kn.kwargs())
            return _degrade(st, kw)
    if getattr(cfg, "sparse_costmodel", False):
        kn = _costmodel_knobs(st.op, int(m), int(n), int(k), int(e),
                              dt.name, -1.0 if hint is None else hint,
                              interp)
        if kn is not None:
            kw.update(kn.kwargs())
    return _degrade(st, kw)


def _operand_values(x) -> jax.Array:
    return x.values if isinstance(x, SparseActivation) else x


def _weight_array(w) -> jax.Array:
    return w.w if hasattr(w, "w") else w


def _site_of(w, site: Optional[OpSite]) -> OpSite:
    st = site if site is not None else getattr(w, "site", None)
    if st is None:
        raise ValueError(
            "sparse.site: no OpSite — pass one explicitly or attach it "
            "to the weight plan (PlannedWeight/PlannedConv.site)")
    return st


def matmul(x, w, site: Optional[OpSite], cfg, *,
           interpret: Optional[bool] = None, collect_stats: bool = False,
           resolved: Optional[dict] = None):
    """Site-resolved :func:`repro.sparse.dispatch.matmul`.

    ``resolved`` (optional) injects an already-resolved knob dict so a
    caller that needed the knobs *before* operand construction (the KV
    value path builds its operands at the tuned tile) doesn't consult
    the cache twice.
    """
    st = _site_of(w, site)
    xv = _operand_values(x)
    m = 1
    for d in xv.shape[:-1]:
        m *= d
    kw = resolved if resolved is not None else resolve(
        st, cfg, m=m, n=_weight_array(w).shape[-1], k=xv.shape[-1],
        dtype=xv.dtype, interpret=interpret)
    return _guarded(st, _degrade(st, kw), lambda kw2: dsp.matmul(
        x, w, name=st.name, op=st.op, interpret=interpret,
        collect_stats=collect_stats, **kw2))


def grouped_matmul(x, w, site: Optional[OpSite], cfg, *,
                   interpret: Optional[bool] = None,
                   collect_stats: bool = False,
                   resolved: Optional[dict] = None):
    """Site-resolved :func:`repro.sparse.dispatch.grouped_matmul`."""
    st = _site_of(w, site)
    xv = _operand_values(x)
    e, c, k = xv.shape
    kw = resolved if resolved is not None else resolve(
        st, cfg, m=c, n=_weight_array(w).shape[-1], k=k, e=e,
        dtype=xv.dtype, interpret=interpret)
    return _guarded(st, _degrade(st, kw), lambda kw2: dsp.grouped_matmul(
        x, w, name=st.name, interpret=interpret,
        collect_stats=collect_stats, **kw2))


def project(x, w, site: Optional[OpSite], cfg, *, n_contract: int = 1,
            plan_act=None, interpret: Optional[bool] = None,
            collect_stats: bool = False):
    """Site-resolved :func:`repro.sparse.dispatch.project` (the
    attention/LM-head tensor projections)."""
    st = _site_of(w, site)
    w_arr = _weight_array(w)
    kflat = 1
    for d in w_arr.shape[:n_contract]:
        kflat *= d
    n = 1
    for d in w_arr.shape[n_contract:]:
        n *= d
    xv = _operand_values(x)
    lead = (xv.shape[:-1] if isinstance(x, SparseActivation)
            else xv.shape[:xv.ndim - n_contract])
    m = 1
    for d in lead:
        m *= d
    kw = resolve(st, cfg, m=m, n=n, k=kflat, dtype=xv.dtype,
                 interpret=interpret)
    return _guarded(st, kw, lambda kw2: dsp.project(
        x, w, n_contract=n_contract, plan_act=plan_act, name=st.name,
        op=st.op, interpret=interpret, collect_stats=collect_stats,
        **kw2))


def conv2d(x, w, stride: int = 1, *, site: Optional[OpSite] = None,
           cfg=None, interpret: Optional[bool] = None,
           collect_stats: bool = False):
    """Site-resolved :func:`repro.sparse.conv.conv2d`.

    Keys the resolution on the lowered GEMM geometry — M = N·OH·OW
    output positions, K = KH·KW·C lowered fibers, N = F filters — which
    is exactly the (m, n, k) the inner ``dispatch.matmul(op="conv")``
    would have keyed on.
    """
    st = _site_of(w, site)
    kh, kw_sp, c, f = w.shape
    xs = x.shape if x.ndim == 4 else (1,) + tuple(x.shape)
    nb, h, wid = xs[0], xs[1], xs[2]
    m = nb * i2c.out_size(h, kh, stride) * i2c.out_size(wid, kw_sp, stride)
    kw = resolve(st, cfg, m=m, n=f, k=kh * kw_sp * c, dtype=x.dtype,
                 interpret=interpret)
    return _guarded(st, kw, lambda kw2: scv.conv2d(
        x, w, stride, name=st.name, interpret=interpret,
        collect_stats=collect_stats, **kw2))
