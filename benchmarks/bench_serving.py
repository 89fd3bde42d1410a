"""Continuous-batching engine checks: compile evidence vs slot count.

A fixed workload of requests with mixed prompt lengths runs through the
paged ``repro.serving.engine.Engine`` at increasing slot counts.  Each
configuration does one warmup wave (compiles the bucketed prefill, the
insert scatter, and the single batched decode step) and then a second
wave on the same engine.  Times are the chip benchmark's
(``chipbench/``); on the CPU this asserts and emits counts only.

Per configuration we emit ``serving.slots{N}.{mode}`` with the compile
evidence from ``Engine.stats()``: ``decode_traces`` must stay 1 per
engine regardless of slot count (the decode step is traced once for the
``(slots,)`` batch and reused every tick) and ``prefill_traces`` stays
at the number of distinct bucket geometries, not the number of
admissions.  The second wave must add zero traces and run one batched
decode call per tick.

``--sparse`` routes decode through the bitmap-scheduled sparse KV path
(grouped_matmul with one E=B*KV grid spanning slots) instead of dense
attention over the paged pool.

``--tune`` sweeps the engine's own decode geometry through
``autotune.tune_attn`` (first-class ``attn.score``/``attn.value``
TuningCache keys, DESIGN.md §16) and replays the batched sparse decode
tick untuned vs tuned — the tuned engine consumes the cached
``sparse_block_t`` replacement at trace time, so the one-decode-trace
contract is asserted on both arms and the tuned arm adds zero traces.
"""
import argparse
import dataclasses

import jax
import numpy as np

from benchmarks.bench_utils import dump_json, emit
from repro.configs import smoke_config
from repro.configs.base import ServeConfig
from repro.models import transformer as tfm
from repro.serving.engine import Engine, Request

RNG = np.random.default_rng(0)


def _workload(n_req: int, lens, vocab: int, max_new: int, uid0: int = 0):
    reqs = []
    for i in range(n_req):
        length = lens[i % len(lens)]
        prompt = [int(t) for t in RNG.integers(1, vocab, size=length)]
        reqs.append(Request(uid=uid0 + i, prompt=prompt,
                            max_new_tokens=max_new))
    return reqs


def _drive(eng: Engine, reqs) -> None:
    """Submit + run to completion."""
    for r in reqs:
        eng.submit(r)
    done = eng.run_to_completion()
    assert len(done) == len(reqs)


def run(smoke: bool = False, sparse: bool = False) -> None:
    cfg = smoke_config("qwen1.5-110b")
    if sparse:
        cfg = dataclasses.replace(cfg, sparse_mode="dual", sparse_kv=True,
                                  sparse_block_t=8)
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    mode = "sparse" if sparse else "dense"

    slot_counts = (1, 2, 4) if smoke else (1, 2, 4, 8)
    n_req = 6 if smoke else 16
    max_new = 6 if smoke else 16
    lens = (3, 5, 8, 12)           # mixed prompt lengths (two buckets)

    print(f"# bench_serving [{mode}]: {n_req} requests, prompt lens "
          f"{lens}, {max_new} new tokens each")
    for slots in slot_counts:
        sv = ServeConfig(slots=slots, capacity=64)
        eng = Engine(params, cfg, serve=sv)
        # warmup wave: compiles prefill (per bucket), insert, decode
        _drive(eng, _workload(n_req, lens, cfg.vocab_size, max_new))
        warm = eng.stats()
        # second wave on the same engine: must hit the jit caches only
        reqs = _workload(n_req, lens, cfg.vocab_size, max_new,
                         uid0=n_req)
        _drive(eng, reqs)
        st = eng.stats()
        new_traces = (st["prefill_traces"] - warm["prefill_traces"]
                      + st["decode_traces"] - warm["decode_traces"])
        assert st["decode_traces"] == 1, st
        assert new_traces == 0, (warm, st)
        ticks = st["ticks"] - warm["ticks"]
        decode_calls = st["decode_calls"] - warm["decode_calls"]
        assert decode_calls <= ticks      # one batched decode per tick
        toks = sum(len(r.output) for r in reqs)
        emit(f"serving.slots{slots}.{mode}", None,
             f"tokens={toks};ticks={ticks};"
             f"decode_calls={decode_calls};"
             f"decode_traces={st['decode_traces']};"
             f"prefill_traces={st['prefill_traces']};"
             f"evictions={st['evictions']};"
             f"pages_free={st['pages_free']};"
             f"pages_total={st['pages_total']}")
    print(f"# OK [{mode}]: decode traced once per engine, second wave "
          "added zero traces, one batched decode call per tick")


def run_tune(smoke: bool = False) -> dict:
    """Tuned vs untuned batched sparse decode ticks (DESIGN.md §16).

    Sweeps the engine's exact decode geometry — t = page-rounded
    capacity, E = slots × kv_heads — into the global TuningCache, then
    drives two engines over the same workload: one on the hand-set
    config constants, one with ``sparse_autotune`` consuming the tuned
    ``attn.score``/``attn.value`` knobs at trace time.  Both must keep
    ``decode_traces == 1`` with a zero-trace second wave (the PR 7
    contract: tuned knobs are jit-constants, never extra traces).
    """
    from repro.sparse import autotune as atn
    from repro.sparse import dispatch as dsp

    cfg = dataclasses.replace(smoke_config("qwen1.5-110b"),
                              sparse_mode="dual", sparse_kv=True,
                              sparse_block_t=8)
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    slots = 2 if smoke else 4
    capacity = 32 if smoke else 64
    n_req = 4 if smoke else 8
    max_new = 4 if smoke else 12
    lens = (3, 5, 8)

    atn.reset()
    page = cfg.sparse_block_t
    cap_pages = -(-capacity // page) * page
    rows = atn.tune_attn(cfg, batch=slots, capacity=cap_pages,
                         max_candidates=2 if smoke else 4)
    for r in rows:
        assert r["tuned"]["us"] <= r["baseline"]["us"], r

    print(f"# bench_serving [tune]: slots={slots} capacity={capacity}, "
          f"attn sites swept at t={cap_pages} E={slots * cfg.n_kv_heads}")
    hits0 = atn.HITS
    for arm, c in (("untuned", cfg),
                   ("tuned",
                    dataclasses.replace(cfg, sparse_autotune=True))):
        eng = Engine(params, c,
                     serve=ServeConfig(slots=slots, capacity=capacity))
        with dsp.warnings_suppressed():
            _drive(eng, _workload(n_req, lens, cfg.vocab_size, max_new))
            warm = eng.stats()
            reqs = _workload(n_req, lens, cfg.vocab_size, max_new,
                             uid0=n_req)
            _drive(eng, reqs)
        st = eng.stats()
        # one decode trace per engine, tuned included; second wave adds 0
        assert st["decode_traces"] == 1, st
        assert st["decode_traces"] == warm["decode_traces"], (warm, st)
        ticks = st["ticks"] - warm["ticks"]
        emit(f"serving.tune.{arm}", None,
             f"ticks={ticks};decode_traces={st['decode_traces']}")
    assert atn.HITS > hits0, \
        "tuned decode was not served from the attention sites"
    print(f"# OK [tune]: tuned decode served {atn.HITS - hits0} cache "
          "hit(s) in one decode trace")
    return {"attn_sweep": rows, "hits": atn.HITS - hits0}


def run_chaos(smoke: bool = False, seed: int = 0) -> dict:
    """Seeded chaos smoke over the full fault matrix (DESIGN.md §17).

    One workload, two arms:

    * **reference** — XLA arm, no faults, ample pages;
    * **chaos** — kernel backends raising on every call (→ per-site
      quarantine onto the XLA arm), page allocations failing at 25%,
      one forced preemption per ~5 ticks, an under-provisioned page
      pool, a corrupted on-disk tuning cache, and one uid with poisoned
      decode logits.

    The acceptance contract asserted here: every non-poisoned request
    completes with a token stream *identical* to the reference arm, the
    poisoned request retires ``status="error"``, the engine neither
    crashes nor livelocks, keeps its one-decode-trace contract, and the
    §17 invariant validators come back clean at exit.
    """
    import os
    import tempfile

    from repro.sparse import autotune as atn
    from repro.sparse import dispatch as dsp
    from repro.sparse import site as ssite
    from repro.testing import faults

    cfg = dataclasses.replace(smoke_config("qwen1.5-110b"),
                              sparse_mode="dual", sparse_kv=True,
                              sparse_block_t=8)
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    n_req = 6 if smoke else 12
    max_new = 6 if smoke else 10
    lens = (3, 5, 8)
    poisoned = {1}

    base = _workload(n_req, lens, cfg.vocab_size, max_new)
    clone = lambda: [Request(uid=r.uid, prompt=list(r.prompt),
                             max_new_tokens=r.max_new_tokens)
                     for r in base]

    # reference arm: XLA knobs, no faults, ample pool
    ssite.clear_quarantine()
    atn.reset()
    ref_eng = Engine(params, cfg, serve=ServeConfig(slots=2, capacity=32))
    ref_reqs = clone()
    with dsp.warnings_suppressed():
        _drive(ref_eng, ref_reqs)
    ref = {r.uid: tuple(r.output) for r in ref_reqs}

    # a corrupted persisted tuning cache the chaos arm must tolerate
    fd, cache_path = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    atn.record("matmul", 8, 8, 8, dtype=jax.numpy.float32, sparsity=None,
               knobs=atn.Knobs("xla", 8, 8, 8), us=1.0)
    atn.save_cache(cache_path)
    atn.reset()
    faults.corrupt_json(cache_path, "truncate")

    chaos_cfg = dataclasses.replace(cfg, sparse_use_kernel=True,
                                    sparse_autotune=True)
    ssite.clear_quarantine()
    print(f"# bench_serving [chaos]: seed={seed}, {n_req} requests, "
          f"poisoned uids {sorted(poisoned)}, kernel faults always-on, "
          "alloc faults 25%, preemption storm 20%, pages=6 of a "
          "4-page/slot demand, corrupted tuning cache")
    with dsp.warnings_suppressed():
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("ignore")
            atn.load_cache(cache_path)      # degrades to empty, no raise
        assert atn.get_cache().entries == {}
        with faults.chaos(seed=seed, alloc_rate=0.25, storm_rate=0.2,
                          poisoned_uids=poisoned):
            # engine built INSIDE the fault context: the nan_logits
            # poison mask rides the (single) decode trace from tick one
            eng = Engine(params, chaos_cfg,
                         serve=ServeConfig(slots=2, capacity=32,
                                           page_size=8, pages=6))
            reqs = clone()
            for r in reqs:
                eng.submit(r)
            done = {r.uid: r for r in eng.run_to_completion()}
            eng.validate_state()            # invariants clean at exit
    os.unlink(cache_path)

    assert sorted(done) == sorted(ref), (sorted(done), sorted(ref))
    mismatches = []
    for uid, r in sorted(done.items()):
        if uid in poisoned:
            assert r.status == "error" and r.error == "nonfinite_logits", \
                (uid, r.status, r.error)
        else:
            assert r.status == "done", (uid, r.status, r.error)
            if tuple(r.output) != ref[uid]:
                mismatches.append(uid)
    assert not mismatches, f"token drift under chaos: uids {mismatches}"
    st = eng.stats()
    assert st["decode_traces"] == 1, st     # poison ride-along adds none
    quarantines = ssite.quarantine_report()
    assert quarantines, "kernel faults never hit a site"
    assert st["errored"] == len(poisoned), st

    emit("serving.chaos", None,
         f"requests={n_req};errored={st['errored']};"
         f"evictions={st['evictions']};ticks={st['ticks']};"
         f"decode_traces={st['decode_traces']};"
         f"quarantined_sites={len(quarantines)}")
    print(f"# OK [chaos]: {n_req - len(poisoned)} request(s) "
          "token-identical to the fault-free arm, "
          f"{len(poisoned)} poisoned retired as errors, "
          f"{len(quarantines)} site(s) quarantined to XLA, "
          f"{st['evictions']} eviction(s), validators clean")
    ssite.clear_quarantine()
    atn.reset()
    return {"seed": seed, "requests": n_req, "errored": st["errored"],
            "evictions": st["evictions"], "ticks": st["ticks"],
            "decode_traces": st["decode_traces"],
            "quarantined_sites": sorted(quarantines),
            "health": eng.health()}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced shapes for CI")
    ap.add_argument("--sparse", action="store_true",
                    help="also run the bitmap-scheduled sparse KV decode "
                         "path (in addition to dense)")
    ap.add_argument("--tune", action="store_true",
                    help="also sweep the attn.score/attn.value decode "
                         "sites and replay the batched tick tuned vs "
                         "untuned (DESIGN.md §16)")
    ap.add_argument("--chaos", action="store_true",
                    help="run the seeded fault-matrix chaos smoke "
                         "(kernel/alloc/preemption/nan-logits faults + "
                         "corrupted tuning cache) and assert graceful "
                         "degradation (DESIGN.md §17)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write machine-readable results to PATH")
    args = ap.parse_args()
    doc = {"bench": "bench_serving", "smoke": args.smoke}
    if not args.chaos:
        run(smoke=args.smoke)
        if args.sparse:
            run(smoke=args.smoke, sparse=True)
    if args.tune:
        doc["tune"] = run_tune(smoke=args.smoke)
    if args.chaos:
        doc["chaos"] = run_chaos(smoke=args.smoke)
    dump_json(args.json, doc)
