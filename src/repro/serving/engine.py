"""Continuous-batching serving engine (paged, batched, vLLM-lite).

The host-side control plane around three jitted cores (DESIGN.md §14):

* **prefill** — admitted requests pack into shape-bucketed batches and
  run one jitted prefill per (batch, padded_len) bucket into contiguous
  full-history caches (compiled once per bucket, in ``__init__``-hoisted
  jit — never re-traced per admission);
* **insert** — each prefilled row scatters into the shared
  :class:`~repro.sparse.kvcache.PagedSparseKVCache` page pool at the
  physical pages the host allocator backed for its slot;
* **decode** — ONE jitted step per engine tick advances every slot
  together: tokens (B, 1), per-slot positions (B, 1), and with a
  non-dense sparse mode both attention matmuls route through
  ``grouped_matmul`` with a single E = B·KV grouped grid spanning slots.

Slots share one physical cache; pages freed by retired (or preempted)
requests recycle across requests through :class:`PageAllocator`, with
per-page occupancy doubling as the level-2 bitmap of the sparse decode
planner.  Admission order and preemption victims come from
:class:`repro.serving.scheduler.Scheduler` — under the ``cost`` policy
the per-request signal is the StepCounts tape (scheduled MXU steps of
one eager prefill).

Encoder-decoder / cross-attention stacks (whisper, llama-vision) fall
back to the legacy per-slot sequential control plane — their memory K/V
are per-request and fixed-size, so there is nothing to page.

Degradation contract (DESIGN.md §17): a request whose decode produces
non-finite logits retires with ``status="error"`` without perturbing its
batch siblings (the rows are independent through attention/MLP/LM-head);
page-allocation failures self-preempt with bounded exponential backoff
instead of crashing admission; ``run_to_completion`` watches for
progress and raises :class:`EngineStalled` carrying an
:meth:`Engine.health` snapshot plus the unfinished requests rather than
silently dropping in-flight work.

Tracing: each tick and its phases open ``engine.*`` host spans
(``jax.profiler.TraceAnnotation``: ``engine.step`` around ``admit``,
``prefill``, ``insert``, ``ensure_pages``, ``push_table``, ``decode``,
``sync``, ``retire`` and ``validate``), and each jitted core opens
``engine.trace.<core>`` while it is being traced, so a recompile shows
on the profiler's timeline.  The spans cost about a microsecond each
while no profiler runs.  Requests carry two wall-clock stamps on
``time.perf_counter()``, the clock of the benchmark harness, which reads
them: ``t_prefill`` (start of the request's own first prefill call) and
``t_first`` (its first token on the host).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import sys
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from repro import sparse
from repro.configs.base import ModelConfig, RunConfig, ServeConfig
from repro.models import model_zoo as zoo
from repro.models import nn
from repro.models import ssm as ssmm
from repro.models import transformer as tfm
from repro.serving.scheduler import PageAllocator, Scheduler, pack_prefills
from repro.testing import faults


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # lifecycle: queued → active → done | error (terminal; ``error``
    # holds the reason: "nonfinite_logits" | "deadline")
    status: str = "queued"
    error: Optional[str] = None
    # optional wall budget in engine ticks from submission; exceeded →
    # terminal error retirement (queued or active alike)
    deadline_ticks: Optional[int] = None
    # recompute-preemption resume point: prompt + output at eviction time
    # (the user-visible ``prompt`` is never mutated)
    resume_prompt: Optional[List[int]] = dataclasses.field(
        default=None, repr=False)
    # robustness bookkeeping (DESIGN.md §17)
    submit_tick: int = dataclasses.field(default=0, repr=False)
    not_before: int = dataclasses.field(default=0, repr=False)
    preempt_retries: int = dataclasses.field(default=0, repr=False)
    # wall-clock stamps (``time.perf_counter()``): start of its own first
    # prefill call, first token on the host
    t_prefill: Optional[float] = dataclasses.field(default=None,
                                                   repr=False)
    t_first: Optional[float] = dataclasses.field(default=None, repr=False)


class EngineStalled(RuntimeError):
    """``run_to_completion`` gave up: no progress within the watchdog
    window, or the tick budget ran out with work still in flight.

    Carries the evidence instead of dropping it: ``health`` is the
    :meth:`Engine.health` JSON snapshot at raise time and ``unfinished``
    the queued + active requests that did not complete.
    """

    def __init__(self, message: str, health: dict, unfinished):
        super().__init__(message)
        self.health = health
        self.unfinished = list(unfinished)


def _round_up(x: int, unit: int) -> int:
    return -(-x // unit) * unit


def _traced(impl):
    """``impl`` inside an ``engine.trace.<core>`` span (``_decode_impl`` →
    ``engine.trace.decode``).  Jitted, the span opens only while the
    program is being traced: a recompile shows on the timeline."""
    name = "engine.trace." + impl.__name__[1:-len("_impl")]

    @functools.wraps(impl)
    def traced(*args):
        with TraceAnnotation(name):
            return impl(*args)
    return traced


class Engine:
    def __init__(self, params, cfg: ModelConfig, *, slots: int = 4,
                 capacity: int = 256, rc: Optional[RunConfig] = None,
                 eos_id: int = -1, serve: Optional[ServeConfig] = None,
                 scheduler: Optional[Scheduler] = None):
        if serve is None:
            serve = ServeConfig(slots=slots, capacity=capacity,
                                eos_id=eos_id)
        self.params = params
        self.cfg = cfg
        self.rc = rc
        self.serve = serve
        self.slots = serve.slots
        self.capacity = serve.capacity      # retire bound (user-visible)
        self.eos_id = serve.eos_id
        self.quantized = bool(rc and rc.kv_quant)

        # page geometry: page size == the sparse planner's block_t, so a
        # page's occupied count is the level-2 bitmap entry (§14)
        self.page = serve.page_size or cfg.sparse_block_t
        self.cap_pages = _round_up(self.capacity, self.page)
        self.n_blocks = self.cap_pages // self.page
        self.n_pages = serve.pages or self.slots * self.n_blocks
        kinds = [cfg.layer_kind(p) for p in range(cfg.period)]
        # exact-length, unpacked prefill where padding or co-batching
        # perturbs per-request numerics: MoE expert capacity scales with
        # the token count, SSM recurrent state integrates padded steps
        self._exact_prefill = cfg.n_experts > 0 or "mamba" in kinds
        self.bucket = 1 if self._exact_prefill else (
            serve.prefill_bucket or self.page)

        # per-request accounting
        self.active: Dict[int, Optional[Request]] = {
            i: None for i in range(self.slots)}
        self.pos = [0] * self.slots
        self.last_tok = np.zeros((self.slots,), np.int32)
        self.pages_held: Dict[int, List[int]] = {}
        self.admitted_tick: Dict[int, int] = {}
        self._early: List[Request] = []
        self.allocator = PageAllocator(self.n_pages)
        if scheduler is None:
            cost_fn = (self._request_cost
                       if serve.policy == "cost" else None)
            scheduler = Scheduler(serve.policy, cost_fn=cost_fn)
        self.scheduler = scheduler

        # control-plane counters (trace counters increment as a python
        # side effect inside the jitted bodies — once per compile)
        self.ticks = 0
        self.evictions = 0
        self.prefill_traces = 0
        self.prefill_calls = 0
        self.prefill_tokens_true = 0     # prompt tokens prefilled
        self.prefill_tokens_padded = 0   # rows x padded length dispatched
        self.insert_traces = 0
        self.decode_traces = 0
        self.decode_calls = 0
        self.tokens_emitted = 0        # progress signal for the watchdog
        self.errored = 0               # terminal error retirements

        # robustness (DESIGN.md §17): invariant validators per tick when
        # RunConfig.validate (or REPRO_VALIDATE=1) is set; the nan_logits
        # fault is captured once here so the decode jit is poison-aware
        # for the engine's whole life (one trace either way — the poison
        # mask is a traced operand, never a recompile)
        self._validate = bool(rc and getattr(rc, "validate", False))
        self._logit_fault = faults.spec("nan_logits")

        # static weight-side sparse plans: built exactly once per engine
        # (weights don't change at inference), reused by every prefill
        # and decode step (DESIGN.md §4.3).
        self.weight_plans = tfm.plan_weight_activities(params, cfg)
        # per-call autotuning (DESIGN.md §13): make the persisted tuning
        # cache available before the first trace — lookups happen at
        # trace time, so the cache must be loaded, not lazily discovered
        if cfg.sparse_autotune and cfg.sparse_tune_cache:
            sparse.autotune.load_cache(cfg.sparse_tune_cache)

        # jitted cores, hoisted here so admissions never re-jit: the jit
        # cache is keyed by operand shapes, so every same-bucket prefill
        # and every tick's decode reuse one executable.  Parameters and
        # weight plans are operands, never closure constants: a captured
        # array is baked into the program as a literal, which at
        # published widths means gigabytes of HLO.
        self._prefill = jax.jit(_traced(self._prefill_impl))
        self._insert = jax.jit(_traced(self._insert_impl))
        self._decode = jax.jit(_traced(self._decode_impl))
        self._decode_one = jax.jit(_traced(self._decode_one_impl))

        try:
            self.caches = tfm.init_paged_caches(
                cfg, self.slots, self.n_pages, self.page, self.cap_pages,
                quantized=self.quantized)
            mesh = nn.current_mesh()
            if mesh is not None:
                # the pool is shared by every slot: one full copy per
                # device of the serving mesh, not everything on device 0
                self.caches = jax.device_put(
                    self.caches, NamedSharding(mesh, PartitionSpec()))
            self.paged = True
            self.table_host = np.zeros((self.slots, self.n_blocks),
                                       np.int32)
            self._table_dirty = False
        except ValueError:
            # legacy per-slot control plane (enc-dec / cross-attention)
            self.paged = False
            self.caches = [
                tfm.init_caches(cfg, 1, self.capacity,
                                quantized=self.quantized)
                for _ in range(self.slots)]

    # -- jitted cores ------------------------------------------------
    # Every core returns an extra per-row ``ok = all(isfinite(logits))``
    # flag — the jit-compatible poison guard (DESIGN.md §17).  A request
    # whose row goes non-finite (kernel garbage, injected NaN) retires
    # with status="error" on the host; sibling rows are untouched (rows
    # are independent through attention/MLP/LM-head).  The reduction is
    # one fused pass over logits the step already materialised — far
    # cheaper than the argmax — so the guard is always on.

    def _prefill_impl(self, params, plans, tokens, true_len, caches):
        """Batched bucket prefill; logits gathered at each true length."""
        self.prefill_traces += 1
        s = tokens.shape[1]
        out = tfm.forward(params, {"tokens": tokens}, self.cfg,
                          mode="prefill", caches=caches,
                          positions=jnp.arange(s, dtype=jnp.int32),
                          rc=self.rc, weight_plans=plans)
        idx = jnp.clip(true_len - 1, 0, s - 1)
        logits = jnp.take_along_axis(out.logits, idx[:, None, None],
                                     axis=1)[:, 0]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ok = jnp.all(jnp.isfinite(logits), axis=-1)
        return out.caches, nxt, ok

    def _insert_impl(self, caches, pre, row, slot, pages, true_len):
        """Lift one prefilled row into the paged pool / per-slot state."""
        self.insert_traces += 1
        new = {}
        for posk, c in caches.items():
            nc = dict(c)
            if "kv" in c:
                nc["kv"] = sparse.kvcache.insert_prefill(
                    c["kv"], pre[posk]["kv"], row, slot, pages, true_len)
            if "ssm" in c:
                st, old = pre[posk]["ssm"], c["ssm"]
                nc["ssm"] = ssmm.SSMState(
                    state=old.state.at[:, slot].set(
                        jnp.take(st.state, row, axis=1)),
                    conv=old.conv.at[:, slot].set(
                        jnp.take(st.conv, row, axis=1)))
            new[posk] = nc
        return new

    def _decode_impl(self, params, plans, toks, pos, caches, poison):
        """One batched decode step over every serving slot.

        ``poison`` NaNs the logits of flagged rows *inside* the trace
        (all-False in production — the ``where`` fuses into the logits
        pass, costing nothing).  It is a traced operand on every call,
        not just under faults: a fault-only operand would compile a
        *second* decode executable whose reassociated float sums can
        flip argmax near-ties on rows the fault never touched.  Keeping
        one executable is what makes chaos-run tokens bit-identical to
        fault-free runs (DESIGN.md §17).
        """
        self.decode_traces += 1
        out = tfm.forward(params, {"tokens": toks[:, None]},
                          self.cfg, mode="decode", caches=caches,
                          positions=pos[:, None], rc=self.rc,
                          weight_plans=plans)
        logits = out.logits[:, -1]
        if poison is not None:
            logits = jnp.where(poison[:, None], jnp.float32(jnp.nan),
                               logits)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ok = jnp.all(jnp.isfinite(logits), axis=-1)
        return out.caches, nxt, ok

    def _decode_one_impl(self, params, plans, tok, pos, caches):
        out = tfm.forward(params, {"tokens": tok[None, None]},
                          self.cfg, mode="decode", caches=caches,
                          positions=pos[None], rc=self.rc,
                          weight_plans=plans)
        logits = out.logits[0, 0]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ok = jnp.all(jnp.isfinite(logits))
        return out.caches, nxt, ok

    # -- sparsity accounting ------------------------------------------
    def profile_sparsity(self, tokens, decode_steps: int = 0
                         ) -> List[dict]:
        """Per-layer MXU StepCounts for one forward over ``tokens``.

        Runs a single eager, scan-unrolled prefill with the stats tape
        active, so every dispatch-routed projection (QKV/out, MLP up/
        down, MoE FFNs, LM head) reports its dense vs. scheduled step
        counts — and, per entry, the ``executed_steps`` of the compute
        path that actually ran: equal to ``sparse_steps`` on the Pallas
        kernel paths (``cfg.sparse_use_kernel``, incl. the ragged
        grouped MoE kernel, DESIGN.md §9), equal to ``dense_steps`` on
        the XLA fallbacks.

        Runs under an active mesh too: the shard_map MoE path collects
        its StepCounts inside the block with the tape suppressed, psums
        them across the mesh, and records the totals outside the traced
        region (DESIGN.md §11) — so on N devices the ``moe.*`` entries
        report mesh-total executed-vs-counted steps, comparable
        entry-for-entry with the single-device run.

        ``decode_steps > 0`` additionally greedy-decodes that many
        tokens eagerly, so with ``cfg.sparse_kv`` the bitmap-scheduled
        decode path (DESIGN.md §10) records its ``attn.score`` /
        ``attn.value`` entries — scheduled vs skipped *cache blocks* per
        layer — and the report ends with one ``kvcache.posN.layerI``
        occupancy entry per sparse cache (written fraction, ring/window
        evicted fraction, quantized flag).  Diagnostic path — the jitted
        serving steps are untouched.  Returns ``[]`` in dense mode
        (nothing is routed).
        """
        if self.cfg.sparse_mode == "dense":
            return []
        toks = jnp.asarray(tokens, jnp.int32)
        if toks.ndim == 1:
            toks = toks[None]
        rc = dataclasses.replace(self.rc or RunConfig(), scan_unroll=True)
        caches = tfm.init_caches(self.cfg, toks.shape[0], self.capacity,
                                 quantized=self.quantized)
        # conv frontends consume raw modality inputs at prefill — feed
        # synthetic zero-heavy ones so the conv.* stem entries land on
        # the tape alongside the projection entries (DESIGN.md §15)
        batch = {"tokens": toks,
                 **zoo.frontend_inputs(self.cfg, toks.shape[0])}
        with sparse.tape.collect() as entries:
            out = tfm.forward(self.params, batch, self.cfg,
                              mode="prefill", caches=caches,
                              positions=jnp.arange(toks.shape[1],
                                                   dtype=jnp.int32),
                              rc=rc, weight_plans=self.weight_plans)
            caches = out.caches
            pos = toks.shape[1]
            nxt = jnp.argmax(out.logits[:, -1], axis=-1).astype(jnp.int32)
            for _ in range(decode_steps):
                out = tfm.forward(
                    self.params, {"tokens": nxt[:, None]}, self.cfg,
                    mode="decode", caches=caches,
                    positions=jnp.asarray([pos], jnp.int32),
                    rc=rc, weight_plans=self.weight_plans)
                caches = out.caches
                pos += 1
                nxt = jnp.argmax(out.logits[:, 0],
                                 axis=-1).astype(jnp.int32)
        report = sparse.tape.summarize(entries)
        report.extend(self._cache_occupancy_entries(caches))
        return report

    def autotune_keys(self, prompt_len: int = 8,
                      decode_steps: int = 1) -> List[str]:
        """Discover the tuning-cache keys this engine's forwards consult.

        Runs one eager prefill over a synthetic prompt plus
        ``decode_steps`` greedy decode steps with ``sparse_autotune``
        forced on, and returns the cache keys the dispatch layer looked
        up (hit or miss) during that window — the closed-loop surface
        for ``bench_models --tune``: because M buckets differ, the M=1
        decode matmuls of the PR 3 KV path appear as their own
        first-class keys, separate from the M=prompt_len prefill ones,
        so prefill and decode tune independently (DESIGN.md §13).
        Returns ``[]`` in dense mode (nothing is routed).
        """
        if self.cfg.sparse_mode == "dense":
            return []
        cfg = dataclasses.replace(self.cfg, sparse_autotune=True)
        rc = dataclasses.replace(self.rc or RunConfig(), scan_unroll=True)
        before = set(sparse.autotune.OBSERVED)
        toks = jnp.ones((1, prompt_len), jnp.int32)
        caches = tfm.init_caches(cfg, 1, self.capacity,
                                 quantized=self.quantized)
        batch = {"tokens": toks, **zoo.frontend_inputs(cfg, 1)}
        with sparse.dispatch.warnings_suppressed():
            out = tfm.forward(self.params, batch, cfg,
                              mode="prefill", caches=caches,
                              positions=jnp.arange(prompt_len,
                                                   dtype=jnp.int32),
                              rc=rc, weight_plans=self.weight_plans)
            caches, pos = out.caches, prompt_len
            nxt = jnp.argmax(out.logits[:, -1], axis=-1).astype(jnp.int32)
            for _ in range(decode_steps):
                out = tfm.forward(self.params, {"tokens": nxt[:, None]},
                                  cfg, mode="decode", caches=caches,
                                  positions=jnp.asarray([pos], jnp.int32),
                                  rc=rc, weight_plans=self.weight_plans)
                caches, pos = out.caches, pos + 1
                nxt = jnp.argmax(out.logits[:, 0],
                                 axis=-1).astype(jnp.int32)
        return sorted(set(sparse.autotune.OBSERVED) - before)

    def _cache_occupancy_entries(self, caches) -> List[dict]:
        """Per-layer sparse-cache occupancy, from the maintained bitmaps."""
        out: List[dict] = []
        if caches is None:
            return out
        mask_w = self.cfg.sliding_window or None
        for posname in sorted(caches):
            c = caches[posname].get("kv")
            if not isinstance(c, sparse.SparseKVCache):
                continue
            rep = sparse.kvcache.occupancy_report(c, mask_window=mask_w)
            for i, (wf, ef) in enumerate(zip(rep["written_frac"],
                                             rep["evicted_frac"])):
                out.append({
                    "name": f"kvcache.{posname}.layer{i}",
                    "written_frac": wf,
                    "evicted_frac": ef,
                    "quantized": rep["quantized"],
                    "capacity": rep["capacity"],
                    "block_t": rep["block_t"],
                    "n_blocks": rep["n_blocks"],
                })
        return out

    def _request_cost(self, req: Request) -> float:
        """StepCounts-tape admission cost: scheduled MXU steps of one
        eager prefill over the request's (resume) prompt.  Dense mode
        routes nothing through the dispatch, so cost degrades to prompt
        length there."""
        prompt = req.resume_prompt or req.prompt
        if self.cfg.sparse_mode == "dense":
            return float(len(prompt))
        rc = dataclasses.replace(self.rc or RunConfig(), scan_unroll=True)
        toks = jnp.asarray(prompt, jnp.int32)[None]
        batch = {"tokens": toks, **zoo.frontend_inputs(self.cfg, 1)}
        with sparse.tape.collect() as entries:
            tfm.forward(self.params, batch, self.cfg,
                        mode="prefill", caches=None,
                        positions=jnp.arange(len(prompt),
                                             dtype=jnp.int32),
                        rc=rc, weight_plans=self.weight_plans)
        steps = sum(e["sparse_steps"]
                    for e in sparse.tape.summarize(entries))
        return float(steps) if steps else float(len(prompt))

    # -- paged control plane ------------------------------------------
    def stats(self) -> Dict[str, int]:
        """Control-plane counters.  Read by the benchmark harness
        (compile evidence, errored requests, prefill padding) and by the
        watchdog's stall report (through :meth:`health`)."""
        return {
            "ticks": self.ticks,
            "evictions": self.evictions,
            "prefill_traces": self.prefill_traces,
            "prefill_calls": self.prefill_calls,
            "prefill_tokens_true": self.prefill_tokens_true,
            "prefill_tokens_padded": self.prefill_tokens_padded,
            "insert_traces": self.insert_traces,
            "decode_traces": self.decode_traces,
            "decode_calls": self.decode_calls,
            "tokens_emitted": self.tokens_emitted,
            "errored": self.errored,
            "pages_free": self.allocator.available if self.paged else 0,
            "pages_total": self.n_pages if self.paged else 0,
        }

    def pool_stats(self) -> Optional[dict]:
        """Per-slot paged-cache occupancy report (first attn position)."""
        if not self.paged:
            return None
        for c in self.caches.values():
            if "kv" in c:
                return sparse.kvcache.paged_occupancy_report(
                    c["kv"], mask_window=self.cfg.sliding_window or None)
        return None

    def health(self) -> dict:
        """JSON-serialisable control-plane snapshot (DESIGN.md §17).

        This is what :class:`EngineStalled` carries and what the chaos
        bench archives — enough to diagnose a stall post-mortem without
        a debugger: who holds which slot, who is backed off until when,
        which sparse sites degraded, and how the pool looks."""
        from repro.sparse import autotune as atn
        from repro.sparse import site as ssite
        slots = {}
        for i in range(self.slots):
            req = self.active.get(i)
            if req is None:
                slots[str(i)] = None
                continue
            slots[str(i)] = {
                "uid": req.uid, "status": req.status,
                "pos": int(self.pos[i]),
                "generated": len(req.output),
                "max_new_tokens": req.max_new_tokens,
                "admitted_tick": self.admitted_tick.get(i),
            }
        queue = [{"uid": r.uid, "status": r.status,
                  "not_before": r.not_before,
                  "preempt_retries": r.preempt_retries,
                  "deadline_ticks": r.deadline_ticks}
                 for r in self.scheduler.queue]
        return {
            "stats": self.stats(),
            "tick": self.ticks,
            "slots": slots,
            "queue": queue,
            "request_costs": {str(k): v
                              for k, v in self.scheduler._cost.items()},
            "quarantines": ssite.quarantine_report(),
            "autotune": {"hits": atn.HITS, "misses": atn.MISSES,
                         "stale": atn.STALE,
                         "observed": len(atn.OBSERVED)},
            "pool": self.pool_stats(),
        }

    def validate_state(self) -> None:
        """Run the §17 serving invariants against live engine state:
        allocator free-list integrity, page-ownership disjointness, and
        paged-cache occupancy == popcount.  Raises
        :class:`repro.sparse.validate.ValidationError` on violation."""
        val = sparse.validate
        val.check_allocator(self.allocator)
        if not self.paged:
            return
        free = set(self.allocator._free)
        held_all: List[int] = []
        for slot, held in self.pages_held.items():
            held_all.extend(held)
            if free & set(held):
                raise val.ValidationError(
                    f"engine: slot {slot} holds pages that are also on "
                    f"the free list: {sorted(free & set(held))}")
            row = {int(p) for p in self.table_host[slot] if p > 0}
            if not row <= set(held):
                raise val.ValidationError(
                    f"engine: slot {slot} block table references pages "
                    f"it does not hold: {sorted(row - set(held))}")
        if len(held_all) != len(set(held_all)):
            raise val.ValidationError(
                "engine: a physical page is held by two slots")
        for c in self.caches.values():
            if "kv" in c:
                val.check_paged_kv(c["kv"], table=self.table_host)
                break

    def _maybe_validate(self) -> None:
        if self._validate or sparse.validate.enabled():
            with TraceAnnotation("engine.validate"):
                self.validate_state()

    def _prompt_of(self, req: Request) -> List[int]:
        return req.resume_prompt or req.prompt

    def _prefill_pages(self, req: Request) -> int:
        return -(-len(self._prompt_of(req)) // self.page)

    def _push_table(self) -> None:
        tbl = jnp.asarray(self.table_host)
        for c in self.caches.values():
            if "kv" in c:
                kv = c["kv"]
                # keep the leaf's placement: a table with another
                # sharding would re-trace the decode step
                c["kv"] = kv._replace(table=jax.device_put(
                    jnp.broadcast_to(tbl[None], kv.table.shape),
                    kv.table.sharding))
        self._table_dirty = False

    def _retire(self, slot: int) -> None:
        self.allocator.free(self.pages_held.pop(slot, []))
        self.table_host[slot, :] = 0
        self.active[slot] = None
        self.admitted_tick.pop(slot, None)
        self._table_dirty = True

    def _evict_one(self) -> bool:
        """Recompute-preemption: kick one active request back to the
        queue (resuming later from prompt + generated-so-far)."""
        rows = [(i, r, self.admitted_tick.get(i, 0))
                for i, r in self.active.items() if r is not None]
        victim = self.scheduler.pick_victim(rows)
        if victim is None:
            return False
        req = self.active[victim]
        # resume point: the full generated stream so far — ``output``
        # accumulates across preemptions, so original prompt + output is
        # exactly the token history a re-prefill must replay
        req.resume_prompt = req.prompt + req.output
        req.status = "queued"
        self._retire(victim)
        self.scheduler.requeue(req)
        self.evictions += 1
        return True

    def _requeue_with_backoff(self, req: Request) -> None:
        """Self-preemption after a failed page allocation: requeue with
        bounded exponential backoff so transient pool pressure cannot
        livelock admission (every eligible tick retries a strictly
        bounded amount of work, and the backoff window keeps the
        starved request from monopolising the admission loop)."""
        req.resume_prompt = req.prompt + req.output
        req.status = "queued"
        req.preempt_retries += 1
        backoff = self.serve.backoff_ticks * (
            2 ** min(req.preempt_retries - 1, 5))
        req.not_before = self.ticks + backoff
        self.scheduler.requeue(req)

    def _error_retire(self, req: Request, reason: str,
                      slot: Optional[int] = None) -> Request:
        """Terminal error retirement (poisoned logits, blown deadline)."""
        req.done = True
        req.status = "error"
        req.error = reason
        self.errored += 1
        if slot is not None:
            if self.paged:
                self._retire(slot)
            else:
                self.active[slot] = None
        return req

    def _append_token(self, req: Request, tok: int) -> None:
        req.output.append(tok)
        self.tokens_emitted += 1
        if req.t_first is None:
            req.t_first = time.perf_counter()

    def _deadline_blown(self, req: Request) -> bool:
        return (req.deadline_ticks is not None
                and self.ticks - req.submit_tick >= req.deadline_ticks)

    def _expire_queued_deadlines(self) -> List[Request]:
        """Retire queued requests whose tick deadline passed while they
        waited — they must not consume a prefill."""
        expired: List[Request] = []
        q = self.scheduler.queue
        if not any(r.deadline_ticks is not None for r in q):
            return expired
        keep = [r for r in q if not self._deadline_blown(r)]
        if len(keep) != len(q):
            expired = [self._error_retire(r, "deadline")
                       for r in q if self._deadline_blown(r)]
            q.clear()
            q.extend(keep)
        return expired

    def _reclaim_swa(self) -> int:
        """Free pages whose whole block fell behind the sliding window
        of every future query — the visibility mask already excludes
        them, so the pool can recycle the memory."""
        win = self.cfg.sliding_window
        if not win:
            return 0
        freed = 0
        for i, req in self.active.items():
            if req is None:
                continue
            dead = sparse.plan.kv_blocks_reclaimable(
                self.pos[i], win, self.page, self.n_blocks)
            held = self.pages_held.get(i, [])
            for b, is_dead in enumerate(dead):
                pg = int(self.table_host[i, b])
                if is_dead and pg > 0:
                    self.table_host[i, b] = 0
                    if pg in held:
                        held.remove(pg)
                    self.allocator.free([pg])
                    freed += 1
                    self._table_dirty = True
        return freed

    def _ensure_pages(self) -> None:
        """Back the next decode write of every active slot with a real
        page, reclaiming window-dead pages first and preempting (LIFO /
        max-cost) when the pool is truly exhausted.

        Retries are bounded (``ServeConfig.alloc_retries``): when
        reclaim + eviction still can't produce a page — e.g. an
        injected allocator fault, or a pool smaller than one slot's
        next write — the starved slot self-preempts with backoff
        instead of raising, so one bad tick never takes the engine
        down and admission cannot livelock."""
        for i in range(self.slots):
            if self.active[i] is None:
                continue
            lb = (self.pos[i] % self.cap_pages) // self.page
            if self.table_host[i, lb] != 0:
                continue
            got = self.allocator.alloc(1)
            attempts = 0
            while got is None and attempts < max(
                    1, self.serve.alloc_retries):
                attempts += 1
                self._reclaim_swa()
                if self.allocator.available == 0:
                    self._evict_one()
                if self.active[i] is None:
                    break              # this very request was the victim
                got = self.allocator.alloc(1)
            if self.active[i] is None:
                continue
            if got is None:
                # bounded retries exhausted: self-preempt with backoff
                req = self.active[i]
                self._retire(i)
                self._requeue_with_backoff(req)
                self.evictions += 1
                continue
            self.table_host[i, lb] = got[0]
            self.pages_held.setdefault(i, []).append(got[0])
            self._table_dirty = True

    # -- control plane ------------------------------------------------
    def submit(self, req: Request):
        if not req.prompt:
            raise ValueError("empty prompt")
        if len(req.prompt) > self.capacity - 1:
            raise ValueError(
                f"prompt length {len(req.prompt)} exceeds capacity "
                f"{self.capacity} (one slot must remain for decode)")
        if self.paged and self._prefill_pages(req) > self.n_pages:
            raise ValueError("prompt cannot fit the page pool")
        req.submit_tick = self.ticks
        if req.max_new_tokens <= 0:
            # nothing to generate: retire at admission with no compute
            req.done = True
            req.status = "done"
            self._early.append(req)
            return
        self.scheduler.submit(req)

    def _admit(self) -> List[Request]:
        if not self.paged:
            return self._admit_legacy()
        finished: List[Request] = []
        with TraceAnnotation("engine.admit"):
            free_slots = [i for i in range(self.slots)
                          if self.active[i] is None]
            admitted: List[Request] = []
            reserved = 0
            while len(admitted) < len(free_slots) and len(self.scheduler):
                req = self.scheduler.pop_next(
                    max_pages=self.allocator.available - reserved,
                    pages_of=self._prefill_pages,
                    now=self.ticks)
                if req is None:
                    break
                admitted.append(req)
                reserved += self._prefill_pages(req)
            if not admitted:
                return finished
            groups = pack_prefills(
                admitted, bucket=self.bucket,
                max_batch=max(1, self.serve.max_prefill_batch),
                pack=not self._exact_prefill,
                length_of=lambda r: len(self._prompt_of(r)))
        for lpad, group in groups:
            lpad = min(max(lpad, 1), self.cap_pages)
            n = len(group)
            lens = np.asarray([len(self._prompt_of(r)) for r in group],
                              np.int32)
            with TraceAnnotation("engine.prefill"):
                self._stamp_prefill(group, int(lens.sum()), n * lpad)
                toks = np.zeros((n, lpad), np.int32)
                for r_i, req in enumerate(group):
                    toks[r_i, :lens[r_i]] = self._prompt_of(req)
                pre = tfm.init_caches(self.cfg, n, lpad, sparse=False,
                                      full_history=True,
                                      quantized=self.quantized)
                pre, nxt, ok = self._prefill(
                    self.params, self.weight_plans, jnp.asarray(toks),
                    jnp.asarray(lens), pre)
                nxt = np.asarray(nxt)
                ok = np.asarray(ok)
            for r_i, req in enumerate(group):
                if not bool(ok[r_i]):
                    # poisoned prompt: its logits went non-finite — the
                    # request retires terminally and never touches a
                    # slot, so its batch siblings are unaffected
                    finished.append(
                        self._error_retire(req, "nonfinite_logits"))
                    continue
                tok = int(nxt[r_i])
                self._append_token(req, tok)
                if (len(req.output) >= req.max_new_tokens
                        or tok == self.eos_id):
                    # admission-retired: first token already finishes
                    # the request — it never occupies a slot or pages
                    req.done = True
                    req.status = "done"
                    finished.append(req)
                    continue
                nbr = self._prefill_pages(req)
                pages = self.allocator.alloc(nbr)
                if pages is None:
                    # the reserve was computed before this prefill ran;
                    # an injected allocator fault (or a concurrent
                    # _ensure_pages grab) can still starve us here —
                    # requeue with backoff rather than crash
                    self._requeue_with_backoff(req)
                    continue
                slot = free_slots.pop(0)
                self.table_host[slot, :] = 0
                self.table_host[slot, :nbr] = pages
                self.pages_held[slot] = list(pages)
                with TraceAnnotation("engine.insert"):
                    self.caches = self._insert(
                        self.caches, pre, jnp.int32(r_i), jnp.int32(slot),
                        jnp.asarray(pages, jnp.int32),
                        jnp.int32(int(lens[r_i])))
                self.pos[slot] = int(lens[r_i])
                self.last_tok[slot] = tok
                self.active[slot] = req
                req.status = "active"
                self.admitted_tick[slot] = self.ticks
                self._table_dirty = True
        return finished

    def _admit_legacy(self) -> List[Request]:
        finished: List[Request] = []
        for i in range(self.slots):
            if self.active[i] is None and len(self.scheduler):
                req = self.scheduler.pop_next(now=self.ticks)
                if req is None:
                    break
                prompt = self._prompt_of(req)
                with TraceAnnotation("engine.prefill"):
                    self._stamp_prefill([req], len(prompt), len(prompt))
                    toks = jnp.asarray(prompt, jnp.int32)[None]
                    self.caches[i] = tfm.init_caches(
                        self.cfg, 1, self.capacity,
                        quantized=self.quantized)
                    caches, nxt, ok = self._prefill(
                        self.params, self.weight_plans, toks,
                        jnp.asarray([len(prompt)], jnp.int32),
                        self.caches[i])
                    nxt, ok = np.asarray(nxt), np.asarray(ok)
                self.caches[i] = caches
                if not bool(ok[0]):
                    finished.append(
                        self._error_retire(req, "nonfinite_logits"))
                    continue
                tok = int(nxt[0])
                self._append_token(req, tok)
                if (len(req.output) >= req.max_new_tokens
                        or tok == self.eos_id):
                    req.done = True
                    req.status = "done"
                    finished.append(req)
                    continue
                self.pos[i] = len(prompt)
                self.last_tok[i] = tok
                self.active[i] = req
                req.status = "active"
        return finished

    def _stamp_prefill(self, group: List[Request], true_len: int,
                       padded_len: int) -> None:
        """Count one prefill call and stamp the start of each request's
        first prefill."""
        self.prefill_calls += 1
        self.prefill_tokens_true += true_len
        self.prefill_tokens_padded += padded_len
        now = time.perf_counter()
        for req in group:
            if req.t_prefill is None:
                req.t_prefill = now

    def step(self) -> List[Request]:
        """One engine tick: admit, one batched decode, retire."""
        with TraceAnnotation("engine.step"):
            return self._tick()

    def _tick(self) -> List[Request]:
        self.ticks += 1
        finished = self._early
        self._early = []
        finished.extend(self._expire_queued_deadlines())
        finished.extend(self._admit())
        if not self.paged:
            out = finished + self._step_legacy()
            self._maybe_validate()
            return out
        storm = faults.spec("preemption_storm")
        if storm is not None and storm.fire():
            self._evict_one()
        if all(r is None for r in self.active.values()):
            self._maybe_validate()
            return finished
        with TraceAnnotation("engine.ensure_pages"):
            self._ensure_pages()
        if all(r is None for r in self.active.values()):
            self._maybe_validate()
            return finished
        if self._table_dirty:
            with TraceAnnotation("engine.push_table"):
                self._push_table()
        # The poison mask is ALWAYS passed (all-False when no nan_logits
        # fault is installed): binding it only under faults would give
        # the fault runs a different compiled executable than production
        # decodes, and XLA is free to re-order float accumulations per
        # program — enough to flip an argmax near-tie on rows the fault
        # never touched.  One operand, one executable, bit-identical
        # tokens with the harness on or off (DESIGN.md §17).
        with TraceAnnotation("engine.decode"):
            if self._logit_fault is not None:
                poison = np.array(
                    [r is not None and self._logit_fault.poisons(r.uid)
                     for r in (self.active[i] for i in range(self.slots))],
                    bool)
            else:
                poison = np.zeros(self.slots, bool)
            self.caches, nxt, ok = self._decode(
                self.params, self.weight_plans, jnp.asarray(self.last_tok),
                jnp.asarray(self.pos, jnp.int32), self.caches,
                jnp.asarray(poison))
        self.decode_calls += 1
        with TraceAnnotation("engine.sync"):
            nxt = np.asarray(nxt)
            ok = np.asarray(ok)
        with TraceAnnotation("engine.retire"):
            for i, req in self.active.items():
                if req is None:
                    continue
                if not bool(ok[i]):
                    # poisoned decode: retire this row terminally;
                    # sibling rows keep their (finite) tokens
                    finished.append(
                        self._error_retire(req, "nonfinite_logits", i))
                    continue
                self.pos[i] += 1
                tok = int(nxt[i])
                self._append_token(req, tok)
                self.last_tok[i] = tok
                if (len(req.output) >= req.max_new_tokens
                        or tok == self.eos_id
                        or self.pos[i] >= self.capacity - 1):
                    req.done = True
                    req.status = "done"
                    finished.append(req)
                    self._retire(i)
                elif self._deadline_blown(req):
                    finished.append(self._error_retire(req, "deadline", i))
        self._maybe_validate()
        return finished

    def _step_legacy(self) -> List[Request]:
        finished = []
        for i, req in self.active.items():
            if req is None:
                continue
            caches, nxt, ok = self._decode_one(
                self.params, self.weight_plans,
                jnp.asarray(self.last_tok[i], jnp.int32),
                jnp.asarray(self.pos[i], jnp.int32), self.caches[i])
            self.caches[i] = caches
            self.decode_calls += 1
            if not bool(np.asarray(ok)):
                finished.append(
                    self._error_retire(req, "nonfinite_logits", i))
                continue
            self.pos[i] += 1
            tok = int(nxt)
            self._append_token(req, tok)
            self.last_tok[i] = tok
            if (len(req.output) >= req.max_new_tokens
                    or tok == self.eos_id
                    or self.pos[i] >= self.capacity - 1):
                req.done = True
                req.status = "done"
                finished.append(req)
                self.active[i] = None
            elif self._deadline_blown(req):
                finished.append(self._error_retire(req, "deadline", i))
        return finished

    def _idle(self) -> bool:
        return (not len(self.scheduler) and not self._early
                and all(v is None for v in self.active.values()))

    def _unfinished(self) -> List[Request]:
        live = [r for r in self.active.values() if r is not None]
        live.extend(self.scheduler.queue)
        live.extend(self._early)
        return live

    def run_to_completion(self, max_ticks: int = 10_000) -> List[Request]:
        """Drive ticks until the engine drains.

        A no-progress watchdog (``ServeConfig.watchdog_ticks``, 0
        disables) guards against livelock: if neither the finished
        count nor ``tokens_emitted`` moves for that many consecutive
        ticks — or ``max_ticks`` runs out with work still pending —
        the health snapshot is dumped and :class:`EngineStalled`
        raised, instead of silently dropping unfinished requests."""
        done: List[Request] = []
        watchdog = self.serve.watchdog_ticks
        stamp = (len(done), self.tokens_emitted)
        stale = 0
        for _ in range(max_ticks):
            done.extend(self.step())
            if self._idle():
                return done
            now = (len(done), self.tokens_emitted)
            stale = stale + 1 if now == stamp else 0
            stamp = now
            if watchdog and stale >= watchdog:
                self._stall("no progress for "
                            f"{watchdog} consecutive ticks")
        if not self._idle():
            self._stall(f"max_ticks={max_ticks} exhausted with "
                        "unfinished requests")
        return done

    def _stall(self, why: str) -> None:
        health = self.health()
        unfinished = self._unfinished()
        print("[engine] STALLED: " + why, file=sys.stderr)
        print(json.dumps(health, indent=2, default=str),
              file=sys.stderr)
        raise EngineStalled(
            f"engine stalled: {why} "
            f"({len(unfinished)} unfinished requests)",
            health, unfinished)

    # legacy attribute: tests/tools that poked ``engine.queue`` keep
    # working against the scheduler's deque
    @property
    def queue(self):
        return self.scheduler.queue
