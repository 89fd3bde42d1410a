"""Per-(arch × shape) sparse autotuner with a persistent cache (DESIGN.md §13).

The paper's dual-side speedups are strongly shape-sensitive: the tile
sizes, slice granularity, and condensation mode that win on one
(M, N, K, sparsity) regime lose on another, and the kernel-vs-XLA
crossover moves with all of them.  This module turns those knobs from
config constants into a measured, cached decision:

* a **tuning cache** maps a bucketed call-site key —
  ``platform|dtype|op|M/N/K buckets|sparsity bucket`` — to the winning
  :class:`Knobs` vector (backend + block_m/block_n/slice_k) and its
  measured wall-clock;
* **candidate generation** enumerates the valid knob lattice
  (:func:`repro.sparse.plan.knobs_valid`: tile divisibility, slice_k ≤ K,
  VMEM panel fit) and prunes it with the analytic scorer —
  :func:`repro.launch.costmodel.sparse_step_fraction` for the
  StepCounts-predicted executed steps, folded into
  :func:`repro.launch.roofline.sparse_matmul`'s sparse
  arithmetic-intensity term;
* **timed sweeps** (:func:`tune_matmul` / :func:`tune_grouped`) validate
  the survivors against the hand-set baseline with a shared timer, so
  "tuned ≤ baseline" holds by construction (the baseline is itself a
  candidate in the same sweep);
* the **dispatch layer** consults :func:`lookup` per call; a miss (or a
  stale entry that fails re-validation) falls back to the config
  constants — the cache can only ever change the schedule, never the
  math, so numerics are identical on hit, miss, and stale.

Every lookup is also *recorded* (:data:`OBSERVED`), which closes the
loop for key discovery: run a profile with ``sparse_autotune`` on and
the prefill **and** decode shapes the model actually dispatches — e.g.
the M=1 decode matmuls of the PR 3 KV path — fall out as first-class
keys for ``bench_models --tune`` to sweep.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax

from repro.kernels.platform import resolve_interpret
from repro.launch import costmodel, roofline
from repro.sparse import plan as pln

CACHE_VERSION = 1

# Backends the tuner chooses between, in dispatch terms:
#   xla    — use_kernel=False (dense-schedule XLA fallback)
#   kernel — use_kernel=True, condense=None (slice-granular block-skip)
#   kfused — use_kernel=True, condense="k" (element-granular condensation)
BACKENDS = ("xla", "kernel", "kfused")

# Sparsity-bucket bin edges (fraction of zeros); lookups with no hint
# use the "any" bucket.
SPARSITY_BINS = (0.0, 0.25, 0.5, 0.75, 0.9, 0.99)
ANY = "any"

# Per-executed-grid-step overhead charged by the candidate scorer under
# interpret mode, where each step is a Python-level emulation rather
# than a hardware grid iteration.  This is what keeps CPU smoke sweeps
# honest: on hardware the term is zero and the roofline decides.
INTERPRET_STEP_OVERHEAD_S = 2e-4

_DTYPE_BYTES = {"float64": 8, "float32": 4, "bfloat16": 2, "float16": 2,
                "int8": 1, "float8_e4m3fn": 1, "float8_e5m2": 1}


class Knobs(NamedTuple):
    """One tunable dispatch decision: backend + geometry."""
    backend: str
    block_m: int
    block_n: int
    slice_k: int

    def kwargs(self) -> dict:
        """The dispatch kwargs this vector denotes (see BACKENDS)."""
        return dict(block_m=self.block_m, block_n=self.block_n,
                    slice_k=self.slice_k,
                    use_kernel=self.backend != "xla",
                    condense="k" if self.backend == "kfused" else None)

    def valid_for(self, m: int, n: int, k: int, *,
                  interpret: bool = False, dtype_bytes: int = 4) -> bool:
        kw = self.kwargs()
        return self.backend in BACKENDS and pln.knobs_valid(
            m, n, k, self.block_m, self.block_n, self.slice_k,
            use_kernel=kw["use_kernel"], condense=kw["condense"],
            interpret=interpret, dtype_bytes=dtype_bytes)


def knobs_from_config(cfg) -> Knobs:
    """The hand-set config constants as a Knobs vector (the fallback
    tier, and the sweep baseline)."""
    if cfg.sparse_use_kernel:
        backend = "kfused" if cfg.sparse_kcondense else "kernel"
    else:
        backend = "xla"
    return Knobs(backend=backend, block_m=cfg.sparse_block_m,
                 block_n=cfg.sparse_block_n, slice_k=cfg.sparse_slice_k)


def clamp_knobs(kn: Knobs, m: int, n: int, k: int,
                interpret: bool = False) -> Knobs:
    """Clamp a knob vector to a problem exactly as the dispatch would
    (:func:`repro.sparse.plan.clamp_geometry`) — the *effective*
    hand-set config for small shapes."""
    bm, bn, sk = pln.clamp_geometry(m, n, k, kn.block_m, kn.block_n,
                                    kn.slice_k, interpret)
    return Knobs(kn.backend, bm, bn, sk)


# ---------------------------------------------------------------------------
# cache keys
# ---------------------------------------------------------------------------

def bucket_dim(x: int) -> int:
    """Next power of two ≥ x (shape bucket)."""
    x = max(int(x), 1)
    b = 1
    while b < x:
        b <<= 1
    return b


def bucket_sparsity(sparsity: Optional[float]) -> str:
    """Nearest bin label for a zero-fraction hint; None → 'any'."""
    if sparsity is None or sparsity < 0:
        return ANY
    s = min(max(float(sparsity), 0.0), 1.0)
    best = min(SPARSITY_BINS, key=lambda b: abs(b - s))
    return f"{best:g}"


def make_key(op: str, m: int, n: int, k: int, *, dtype,
             sparsity: Optional[float] = None,
             platform: Optional[str] = None, extra: str = "") -> str:
    """The persistent cache key for one bucketed call site.

    ``op`` distinguishes matmul from grouped_matmul (grouped adds the
    expert-count bucket via ``extra``); M buckets separate decode (M=1)
    from prefill (M=seq) naturally, which is what makes decode shapes
    first-class keys.
    """
    platform = platform or jax.default_backend()
    dt = jax.numpy.dtype(dtype).name
    key = (f"{platform}|{dt}|{op}|m{bucket_dim(m)}|n{bucket_dim(n)}"
           f"|k{bucket_dim(k)}|s{bucket_sparsity(sparsity)}")
    if extra:
        key += f"|{extra}"
    return key


# ---------------------------------------------------------------------------
# the persistent cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TuningCache:
    """key → winning knob vector + its measurement (JSON-persistable).

    Entry schema (the on-disk format, documented in
    ``benchmarks/run.py --help``)::

        {"backend": "xla|kernel|kfused", "block_m": int, "block_n": int,
         "slice_k": int, "us": float, "baseline_us": float,
         "source": "tuned"}
    """
    entries: Dict[str, dict] = dataclasses.field(default_factory=dict)
    path: Optional[str] = None

    def get(self, key: str) -> Optional[Knobs]:
        e = self.entries.get(key)
        if e is None:
            return None
        return Knobs(backend=e["backend"], block_m=int(e["block_m"]),
                     block_n=int(e["block_n"]), slice_k=int(e["slice_k"]))

    def put(self, key: str, kn: Knobs, us: float,
            baseline_us: Optional[float] = None) -> None:
        self.entries[key] = {
            "backend": kn.backend, "block_m": kn.block_m,
            "block_n": kn.block_n, "slice_k": kn.slice_k,
            "us": float(us),
            "baseline_us": None if baseline_us is None
            else float(baseline_us),
            "source": "tuned"}

    def save(self, path: Optional[str] = None) -> str:
        """Atomic persist: write a sibling temp file, then rename — a
        killed benchmark can truncate the temp, never the cache."""
        path = path or self.path
        if not path:
            raise ValueError("TuningCache.save: no path")
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"version": CACHE_VERSION, "entries": self.entries},
                      f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        self.path = path
        return path

    def load(self, path: str, merge: bool = True) -> "TuningCache":
        """Merge a persisted cache.

        Truncated/corrupt JSON degrades to an empty document with a
        warn-once (a damaged cache must never take the process down —
        every lookup just falls back to config constants).  A *valid*
        document with a foreign schema version still raises: that is a
        deliberate mismatch, not damage.
        """
        from repro.sparse.dispatch import warn_once
        try:
            with open(path) as f:
                doc = json.load(f)
            if not isinstance(doc, dict):
                raise json.JSONDecodeError(
                    "top-level document is not an object", "", 0)
        except (json.JSONDecodeError, UnicodeDecodeError) as e:
            warn_once(f"tunecache-corrupt:{path}",
                      f"tuning cache {path} is truncated or corrupt "
                      f"({e}); continuing with an empty cache "
                      "(dispatch falls back to config constants)")
            doc = {"version": CACHE_VERSION, "entries": {}}
        if doc.get("version") != CACHE_VERSION:
            raise ValueError(
                f"tuning cache {path}: version {doc.get('version')!r} "
                f"!= {CACHE_VERSION}")
        entries = doc.get("entries", {})
        if not isinstance(entries, dict):
            warn_once(f"tunecache-corrupt:{path}",
                      f"tuning cache {path}: 'entries' is not an "
                      "object; ignoring it")
            entries = {}
        if not merge:
            self.entries.clear()
        self.entries.update(entries)
        self.path = path
        return self


# process-global cache + telemetry (tests reset via reset())
_CACHE = TuningCache()
HITS = 0
MISSES = 0
STALE = 0
# every dispatch lookup, hit or miss: key → {op, m, n, k, dtype,
# sparsity, count}.  The closed-loop key-discovery surface.
OBSERVED: Dict[str, dict] = {}


def get_cache() -> TuningCache:
    return _CACHE


def load_cache(path: str, merge: bool = True) -> TuningCache:
    """Load (by default merge) a persisted cache into the process-global
    one consulted by the dispatch layer."""
    return _CACHE.load(path, merge=merge)


def save_cache(path: str) -> str:
    return _CACHE.save(path)


def reset() -> None:
    """Clear the global cache and telemetry (test isolation)."""
    global HITS, MISSES, STALE
    _CACHE.entries.clear()
    _CACHE.path = None
    HITS = MISSES = STALE = 0
    OBSERVED.clear()


def lookup(op: str, m: int, n: int, k: int, *, dtype,
           sparsity: Optional[float] = None, interpret: bool = False,
           extra: str = "") -> Optional[Knobs]:
    """Consult the cache for one call site; None ⇒ fall back to config.

    Tries the exact sparsity bucket, then the 'any' bucket.  A hit is
    re-validated against :func:`repro.sparse.plan.knobs_valid` for the
    *actual* (m, n, k) — buckets are ranges, and a stale or
    foreign-shape entry must degrade to the fallback, never reach a
    kernel.  Records the observation either way.
    """
    global HITS, MISSES, STALE
    dt = jax.numpy.dtype(dtype)
    key = make_key(op, m, n, k, dtype=dt, sparsity=sparsity, extra=extra)
    obs = OBSERVED.setdefault(key, {
        "op": op, "m": int(m), "n": int(n), "k": int(k), "dtype": dt.name,
        "sparsity": None if sparsity is None else float(sparsity),
        "extra": extra, "count": 0})
    obs["count"] += 1
    tried = [key]
    if bucket_sparsity(sparsity) != ANY:
        tried.append(make_key(op, m, n, k, dtype=dt, sparsity=None,
                              extra=extra))
    for key_i in tried:
        kn = _CACHE.get(key_i)
        if kn is None:
            continue
        if kn.valid_for(m, n, k, interpret=interpret,
                        dtype_bytes=_DTYPE_BYTES.get(dt.name, 4)):
            HITS += 1
            return kn
        STALE += 1
    MISSES += 1
    return None


def record(op: str, m: int, n: int, k: int, *, dtype, sparsity,
           knobs: Knobs, us: float, baseline_us: Optional[float] = None,
           extra: str = "", also_any: bool = True,
           cache: Optional[TuningCache] = None) -> str:
    """Store a sweep winner under its bucketed key.

    ``also_any`` mirrors the entry into the 'any' sparsity bucket when
    it is empty or slower — so call sites without a sparsity hint (the
    default model path) still hit.
    """
    cache = cache or _CACHE
    key = make_key(op, m, n, k, dtype=dtype, sparsity=sparsity,
                   extra=extra)
    cache.put(key, knobs, us, baseline_us)
    if also_any and bucket_sparsity(sparsity) != ANY:
        any_key = make_key(op, m, n, k, dtype=dtype, sparsity=None,
                           extra=extra)
        prev = cache.entries.get(any_key)
        if prev is None or float(prev.get("us", float("inf"))) > us:
            cache.put(any_key, knobs, us, baseline_us)
    return key


# ---------------------------------------------------------------------------
# candidate generation + cost-model pruning
# ---------------------------------------------------------------------------

_BLOCK_M_CHOICES = (8, 16, 32, 64, 128, 256)
_BLOCK_N_CHOICES = (128, 256, 512)
_BLOCK_N_INTERP = (8, 32, 128, 256)
_SLICE_K_CHOICES = (32, 64, 128, 256)


def score(kn: Knobs, m: int, n: int, k: int, *,
          a_density: float = 1.0, w_density: float = 1.0,
          dtype_bytes: int = 4, interpret: bool = False,
          n_groups: int = 1) -> float:
    """Predicted seconds for one candidate (lower is better)."""
    kw = kn.kwargs()
    frac = costmodel.sparse_step_fraction(
        kn.block_m, kn.block_n, kn.slice_k, k, a_density=a_density,
        w_density=w_density, condense=kw["condense"])
    terms = roofline.sparse_matmul(
        m, n, k, executed_fraction=frac, block_m=kn.block_m,
        block_n=kn.block_n, dtype_bytes=dtype_bytes, backend=kn.backend,
        step_overhead_s=INTERPRET_STEP_OVERHEAD_S if interpret else 0.0)
    return terms["predict_s"] * max(n_groups, 1)


def candidates(m: int, n: int, k: int, *, a_sparsity: float = 0.0,
               w_sparsity: float = 0.0, dtype_bytes: int = 4,
               interpret: bool = False, n_groups: int = 1,
               max_candidates: int = 8,
               include: Tuple[Knobs, ...] = ()) -> List[Knobs]:
    """Valid knob vectors for an (m, n, k) problem, cost-model ranked.

    Enumerates the backend × block lattice, drops everything
    :func:`repro.sparse.plan.knobs_valid` rejects, scores the rest with
    the sparse roofline, and keeps the ``max_candidates`` best — always
    retaining at least one ``xla`` candidate (the crossover must stay
    measurable) and everything in ``include`` (the sweep baseline).
    """
    a_d = 1.0 - min(max(a_sparsity, 0.0), 1.0)
    w_d = 1.0 - min(max(w_sparsity, 0.0), 1.0)
    lane = 8 if interpret else pln.LANE
    # clamp the lattice to the problem exactly as clamp_geometry would —
    # for small dims every un-clamped choice can overshoot the round-up
    # bound, and the sweep must never come back empty
    bm_choices = sorted({min(bm, pln._round_up(m, 8))
                         for bm in _BLOCK_M_CHOICES})
    bn_choices = sorted({min(bn, pln._round_up(n, lane)) for bn in
                         (_BLOCK_N_INTERP if interpret
                          else _BLOCK_N_CHOICES)})
    sk_choices = sorted({min(sk, pln._round_up(k, 8))
                         for sk in _SLICE_K_CHOICES})
    pool: List[Knobs] = []
    for backend in BACKENDS:
        for bm in bm_choices:
            for bn in bn_choices:
                for sk in sk_choices:
                    kn = Knobs(backend, bm, bn, sk)
                    if kn.valid_for(m, n, k, interpret=interpret,
                                    dtype_bytes=dtype_bytes):
                        pool.append(kn)
        if backend == "xla" and pool:
            # geometry only changes xla's *accounting*, not its compute
            # — one representative is enough
            pool = [max(pool, key=lambda c: (c.block_m, c.block_n,
                                             c.slice_k))]
    ranked = sorted(pool, key=lambda c: score(
        c, m, n, k, a_density=a_d, w_density=w_d, dtype_bytes=dtype_bytes,
        interpret=interpret, n_groups=n_groups))
    out: List[Knobs] = [kn for kn in include
                        if kn.valid_for(m, n, k, interpret=interpret,
                                        dtype_bytes=dtype_bytes)]
    for kn in ranked:
        if len(out) >= max_candidates + len(include):
            break
        if kn not in out:
            out.append(kn)
    if not any(c.backend == "xla" for c in out):
        xla = [c for c in ranked if c.backend == "xla"]
        if xla:
            out.append(xla[0])
    return out


# ---------------------------------------------------------------------------
# timed sweeps
# ---------------------------------------------------------------------------

def _default_timer(fn: Callable[[], None], warmup: int = 1,
                   repeat: int = 3) -> float:
    """Median wall-clock µs of fn() (compile excluded by warmup)."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e6)
    ts.sort()
    return ts[len(ts) // 2]


def _sweep(run: Callable[[Knobs], Callable[[], None]],
           cands: List[Knobs], baseline: Knobs,
           timer: Optional[Callable] = None) -> Tuple[Knobs, float, float,
                                                      List[dict]]:
    """Time baseline + candidates with one shared timer; argmin wins.

    The baseline is measured in the same sweep, so the winner is ≤ the
    hand-set config by construction.
    """
    timer = timer or _default_timer
    rows: List[dict] = []
    best: Optional[Knobs] = None
    best_us = float("inf")
    baseline_us = float("inf")
    seen = []
    for kn in [baseline] + [c for c in cands if c != baseline]:
        if kn in seen:
            continue
        seen.append(kn)
        us = float(timer(run(kn)))
        rows.append({"backend": kn.backend, "block_m": kn.block_m,
                     "block_n": kn.block_n, "slice_k": kn.slice_k,
                     "us": us, "is_baseline": kn == baseline})
        if kn == baseline:
            baseline_us = us
        if us < best_us:
            best, best_us = kn, us
    return best, best_us, baseline_us, rows


def tune_matmul(x, w, *, mode: str = "dual",
                sparsity: Optional[float] = None,
                w_sparsity: float = 0.0, baseline: Optional[Knobs] = None,
                interpret: Optional[bool] = None,
                timer: Optional[Callable] = None, max_candidates: int = 8,
                out_dtype=None, cache: Optional[TuningCache] = None,
                platform: Optional[str] = None) -> dict:
    """Sweep one 2-D dispatch call site and cache the winner.

    ``x``/``w`` are exactly what :func:`repro.sparse.dispatch.matmul`
    takes (arrays, SparseActivation, PlannedWeight).  ``sparsity`` is
    the activation-side zero fraction the key is bucketed under (and
    the cost model prunes with); ``baseline`` defaults to the repo's
    config constants, clamped as the dispatch would.  Returns a
    JSON-ready summary row (key, baseline/tuned µs, the full sweep).
    """
    from repro.sparse import dispatch as dsp
    xv = x.values if hasattr(x, "values") else x
    w_arr = w.w if hasattr(w, "w") else w
    k = xv.shape[-1]
    m = 1
    for d in xv.shape[:-1]:
        m *= d
    n = w_arr.shape[-1]
    interp = resolve_interpret(interpret)
    dt = jax.numpy.dtype(xv.dtype)
    if baseline is None:
        baseline = Knobs("kernel", 128, 128, pln.SLICE_K)
    baseline = clamp_knobs(baseline, m, n, k, interp)
    cands = candidates(
        m, n, k, a_sparsity=sparsity or 0.0, w_sparsity=w_sparsity,
        dtype_bytes=_DTYPE_BYTES.get(dt.name, 4), interpret=interp,
        max_candidates=max_candidates, include=(baseline,))

    def run(kn: Knobs) -> Callable[[], None]:
        kw = kn.kwargs()

        def fn():
            y, _ = dsp.matmul(x, w, mode=mode, interpret=interp,
                              out_dtype=out_dtype, **kw)
            jax.block_until_ready(y)
        return fn

    best, best_us, baseline_us, rows = _sweep(run, cands, baseline, timer)
    key = record("matmul", m, n, k, dtype=dt, sparsity=sparsity,
                 knobs=best, us=best_us, baseline_us=baseline_us,
                 cache=cache)
    return {"key": key, "op": "matmul", "m": m, "n": n, "k": k,
            "dtype": dt.name, "sparsity": sparsity,
            "baseline": {"backend": baseline.backend,
                         "block_m": baseline.block_m,
                         "block_n": baseline.block_n,
                         "slice_k": baseline.slice_k, "us": baseline_us},
            "tuned": {"backend": best.backend, "block_m": best.block_m,
                      "block_n": best.block_n, "slice_k": best.slice_k,
                      "us": best_us},
            "speedup": baseline_us / best_us if best_us else 0.0,
            "sweep": rows}


def tune_grouped(x, w, *, mode: str = "dual",
                 sparsity: Optional[float] = None, w_sparsity: float = 0.0,
                 baseline: Optional[Knobs] = None,
                 interpret: Optional[bool] = None,
                 timer: Optional[Callable] = None,
                 max_candidates: int = 8, out_dtype=None,
                 cache: Optional[TuningCache] = None) -> dict:
    """Grouped (stacked-expert) analogue of :func:`tune_matmul`."""
    from repro.sparse import dispatch as dsp
    xv = x.values if hasattr(x, "values") else x
    w_arr = w.w if hasattr(w, "w") else w
    e, c, k = xv.shape
    n = w_arr.shape[-1]
    interp = resolve_interpret(interpret)
    dt = jax.numpy.dtype(xv.dtype)
    extra = f"e{bucket_dim(e)}"
    if baseline is None:
        baseline = Knobs("kernel", 128, 128, pln.SLICE_K)
    baseline = clamp_knobs(baseline, c, n, k, interp)
    cands = candidates(
        c, n, k, a_sparsity=sparsity or 0.0, w_sparsity=w_sparsity,
        dtype_bytes=_DTYPE_BYTES.get(dt.name, 4), interpret=interp,
        n_groups=e, max_candidates=max_candidates, include=(baseline,))

    def run(kn: Knobs) -> Callable[[], None]:
        kw = kn.kwargs()

        def fn():
            y, _ = dsp.grouped_matmul(x, w, mode=mode, interpret=interp,
                                      out_dtype=out_dtype, **kw)
            jax.block_until_ready(y)
        return fn

    best, best_us, baseline_us, rows = _sweep(run, cands, baseline, timer)
    key = record("grouped", c, n, k, dtype=dt, sparsity=sparsity,
                 knobs=best, us=best_us, baseline_us=baseline_us,
                 extra=extra, cache=cache)
    return {"key": key, "op": "grouped", "m": c, "n": n, "k": k, "e": e,
            "dtype": dt.name, "sparsity": sparsity,
            "baseline": {"backend": baseline.backend,
                         "block_m": baseline.block_m,
                         "block_n": baseline.block_n,
                         "slice_k": baseline.slice_k, "us": baseline_us},
            "tuned": {"backend": best.backend, "block_m": best.block_m,
                      "block_n": best.block_n, "slice_k": best.slice_k,
                      "us": best_us},
            "speedup": baseline_us / best_us if best_us else 0.0,
            "sweep": rows}


# The occupancy-block granularities the attention sweep always times —
# the tuned replacement for the hand-set ``ModelConfig.sparse_block_t``.
_BLOCK_T_CHOICES = (8, 16, 32, 64, 128)


def _attn_operands(cfg, *, batch: int, capacity: int, fill: int,
                   seed: int, dtype):
    """Synthetic batched-decode operands, shaped exactly like
    ``attend_sparse``'s (E = batch × kv_heads stacked problems).

    Slots beyond ``fill`` are genuinely zero in K/V and in the
    probability tensor — the same contract the real decode path
    guarantees (unwritten cache slots, softmax-masked rows), so the
    sweep's sparsity is the sparsity the kernels will actually see.
    """
    import jax.numpy as jnp
    kvh = cfg.n_kv_heads
    hd = cfg.hd
    g = max(cfg.n_heads // kvh, 1)
    t = capacity
    ne = batch * kvh
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    occ = jnp.arange(t) < fill
    occ_e = jnp.broadcast_to(occ[None, :], (ne, t))
    kd_e = jnp.where(occ[None, :, None],
                     jax.random.normal(ks[0], (ne, t, hd), dtype), 0)
    vd_e = jnp.where(occ[None, :, None],
                     jax.random.normal(ks[1], (ne, t, hd), dtype), 0)
    qw = jax.random.normal(ks[2], (ne, hd, g), dtype)
    p_e = jnp.where(occ_e[:, None, :],
                    jax.random.uniform(ks[3], (ne, g, t), dtype), 0)
    return dict(t=t, g=g, hd=hd, ne=ne, occ_e=occ_e, sched_e=occ_e,
                kd_e=kd_e, vd_e=vd_e, qw=qw, p_e=p_e)


def tune_attn(cfg, *, batch: int = 1, capacity: int = 64,
              fill: Optional[int] = None, sparsity: Optional[float] = None,
              interpret: Optional[bool] = None,
              timer: Optional[Callable] = None, max_candidates: int = 6,
              cache: Optional[TuningCache] = None, seed: int = 0,
              dtype=None) -> List[dict]:
    """Sweep the decode attention's two grouped matmuls; cache winners.

    The sites (DESIGN.md §16) are keyed on their true matmul geometry:

    * ``attn.score`` — ``scores[e] = K[e] @ q[e]``: (m, n, k) =
      (capacity, group, head_dim), E = batch × kv_heads.  The tuned
      ``block_m`` *is* the score-side occupancy tile, so the hand-set
      ``ModelConfig.sparse_block_t`` becomes this key's baseline and
      rides the same sweep (tuned ≤ hand-set by construction).
    * ``attn.value`` — ``out[e] = p[e] @ V[e]``: (m, n, k) =
      (group, head_dim, capacity).  The tuned ``slice_k`` is the value
      block_t; the (p, V) operands are **rebuilt per candidate** because
      the occupancy-block metadata granularity must track it.

    ``fill`` is the occupied prefix of the cache (default capacity/2);
    the sparsity hint defaults to the empty-slot fraction.  ``dtype``
    defaults to bfloat16 — the decode activation dtype, i.e. the dtype
    bucket the engine's lookups actually consult.  Returns two
    JSON-ready rows shaped like :func:`tune_grouped`'s.
    """
    import jax.numpy as jnp

    from repro.sparse import dispatch as dsp
    from repro.sparse import kvcache as skvc
    interp = resolve_interpret(interpret)
    mode = cfg.sparse_mode if cfg.sparse_mode != "dense" else "dual"
    fill = capacity // 2 if fill is None else fill
    fill = min(max(int(fill), 1), capacity)
    dt = jax.numpy.dtype(dtype or jax.numpy.bfloat16)
    ops = _attn_operands(cfg, batch=batch, capacity=capacity, fill=fill,
                         seed=seed, dtype=dt)
    t, g, hd, ne = ops["t"], ops["g"], ops["hd"], ops["ne"]
    if sparsity is None:
        sparsity = 1.0 - fill / t
    base = knobs_from_config(cfg)
    extra = f"e{bucket_dim(ne)}"
    dtb = _DTYPE_BYTES.get(dt.name, 4)
    rows: List[dict] = []

    def _include(m, n, k, mk):
        """Baseline-backend variants over the block_t lattice (dedup'd,
        baseline first) — the granularities the hand-set knob chooses
        between must all be in the sweep."""
        out = [mk(cfg.sparse_block_t)]
        for bt in _BLOCK_T_CHOICES:
            kn = mk(bt)
            if kn not in out:
                out.append(kn)
        return [clamp_knobs(kn, m, n, k, interp) for kn in out]

    def _row(op, m, n, k, baseline, baseline_us, best, best_us, sweep):
        return {"key": record(op, m, n, k, dtype=dt, sparsity=sparsity,
                              knobs=best, us=best_us,
                              baseline_us=baseline_us, extra=extra,
                              cache=cache),
                "op": op, "m": m, "n": n, "k": k, "e": ne,
                "dtype": dt.name, "sparsity": sparsity,
                "baseline": {"backend": baseline.backend,
                             "block_m": baseline.block_m,
                             "block_n": baseline.block_n,
                             "slice_k": baseline.slice_k,
                             "us": baseline_us},
                "tuned": {"backend": best.backend,
                          "block_m": best.block_m,
                          "block_n": best.block_n,
                          "slice_k": best.slice_k, "us": best_us},
                "speedup": baseline_us / best_us if best_us else 0.0,
                "sweep": sweep}

    # --- attn.score: block_m is the score tile over cache slots -------
    inc_s = _include(t, g, hd,
                     lambda bt: Knobs(base.backend, bt, base.block_n,
                                      base.slice_k))
    baseline_s = inc_s[0]
    cands_s = candidates(t, g, hd, a_sparsity=sparsity, dtype_bytes=dtb,
                         interpret=interp, n_groups=ne,
                         max_candidates=max_candidates,
                         include=tuple(inc_s))

    def run_score(kn: Knobs) -> Callable[[], None]:
        kw = kn.kwargs()
        sk = pln.effective_slice_k(hd, kw["slice_k"])
        x_k = skvc.score_operand(ops["kd_e"], ops["sched_e"], sk)

        def fn():
            y, _ = dsp.grouped_matmul(x_k, ops["qw"], mode=mode,
                                      interpret=interp,
                                      out_dtype=jnp.float32,
                                      **{**kw, "slice_k": sk})
            jax.block_until_ready(y)
        return fn

    best, best_us, baseline_us, sweep = _sweep(run_score, cands_s,
                                               baseline_s, timer)
    rows.append(_row("attn.score", t, g, hd, baseline_s, baseline_us,
                     best, best_us, sweep))

    # --- attn.value: slice_k is the value-side occupancy block_t ------
    inc_v = _include(g, hd, t,
                     lambda bt: Knobs(base.backend, base.block_m,
                                      base.block_n, bt))
    baseline_v = inc_v[0]
    cands_v = candidates(g, hd, t, a_sparsity=sparsity, dtype_bytes=dtb,
                         interpret=interp, n_groups=ne,
                         max_candidates=max_candidates,
                         include=tuple(inc_v))

    def run_value(kn: Knobs) -> Callable[[], None]:
        kw = kn.kwargs()
        bt = pln.effective_slice_k(t, kw["slice_k"])
        x_p, w_v = skvc.value_operands(ops["occ_e"], ops["p_e"],
                                       ops["vd_e"], ops["sched_e"], bt)

        def fn():
            y, _ = dsp.grouped_matmul(x_p, w_v, mode=mode,
                                      interpret=interp,
                                      out_dtype=jnp.float32,
                                      **{**kw, "slice_k": bt})
            jax.block_until_ready(y)
        return fn

    best, best_us, baseline_us, sweep = _sweep(run_value, cands_v,
                                               baseline_v, timer)
    rows.append(_row("attn.value", g, hd, t, baseline_v, baseline_us,
                     best, best_us, sweep))
    return rows


def default_cache_path(root: Optional[str] = None) -> str:
    """Where ``bench_models --tune`` persists the cache by default."""
    return os.path.join(root or os.getcwd(), "BENCH_autotune_cache.json")
