#!/usr/bin/env python3
"""Smoke run of the served path on a TPU.  Not a benchmark.

    python chip_smoke.py                # nemotron-4-340b on one chip
    python chip_smoke.py --four-chips   # mixtral-8x7b, experts over 4 chips

One chip: ``nemotron-4-340b`` at its published widths (one layer, one
chip's 32000-row share of the vocabulary), seeded random bf16 weights
pruned to 50 % block sparsity, serves 8 seeded requests through
``serving.Engine`` twice — a dense arm on XLA and a dual arm on the
Pallas kernels with the sparse KV decode — inside the mesh and axis
rules ``repro.launch.serve`` sets up.  Four chips: ``mixtral-8x7b`` (two
layers, all 8 experts) through ``Engine`` on a (1, 4) data x model mesh
with 2 experts per chip, against the same requests on one chip without
a mesh.

Every check raises on failure, so the process exits non-zero; only a
run that passed every check prints the final JSON line.  Without a TPU
the script fails before any work.  Times printed are wall and compile
seconds of one cold run, for orientation only.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import sparse  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import RunConfig, ServeConfig  # noqa: E402
from repro.core import pruning  # noqa: E402
from repro.kernels import platform  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import transformer as tfm  # noqa: E402
from repro.serving.engine import Engine, Request  # noqa: E402

N_REQUESTS = 8
NEW_TOKENS = 16
PROMPT_LEN = (128, 1024)
SLOTS = 4
CAPACITY = 2048
SPARSITY = 0.5
# Kernel tiles of the dual arm.  block_m 512 bounds how often a prefill
# re-reads each weight panel (once per 512 prompt rows); block_n 256
# divides 18432, 73728, 1536 and 32000; slice_k is the MXU depth.
TILES = dict(sparse_block_m=512, sparse_block_n=256, sparse_slice_k=128)
# Prefill logits of two arms on the same weights must agree within this
# share of the dense arm's largest |logit|.  Both arms feed bf16 operands
# to the MXU and accumulate in f32, but in different orders, and round
# each projection's output to bf16 (relative step 2^-8 = 0.4 %); about
# eight such roundings compound through one layer and the head.
LOGIT_RTOL = 0.05


class CompileClock:
    """Seconds spent in XLA backend compiles while installed."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def peak_bytes() -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def make_requests(seed: int, vocab: int, n: int = N_REQUESTS):
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, size=n)
    return [rng.integers(0, vocab, size=int(length)).tolist()
            for length in lens]


def prune(params, cfg):
    """50 % block sparsity on every matrix the dual arm dispatches.

    Tiles are the kernels' skip unit — (slice_k rows of K) x (block_n
    columns of N) of each weight in its dispatched 2-D form — so a pruned
    tile is one whole entry of the schedule.  Each leaf is pruned in its
    own jit with its buffer donated: no second copy of a weight."""
    block = (cfg.sparse_slice_k, cfg.sparse_block_n)

    def masked(w, k_axes, stacked):
        w3 = w if stacked else w[None]
        k = int(np.prod(w3.shape[1:1 + k_axes]))
        w3 = w3.reshape(w3.shape[0], k, -1)
        keep = jax.vmap(
            lambda x: pruning.block_mask(x, SPARSITY, block))(w3)
        return jnp.where(keep, w3, 0).reshape(w.shape)

    run = jax.jit(masked, static_argnums=(1, 2), donate_argnums=0)
    lp = params["layers"]["pos0"]
    for name, k_axes in (("wq", 1), ("wk", 1), ("wv", 1), ("wo", 2)):
        lp["attn"][name] = run(lp["attn"][name], k_axes, True)
    for name in ("w_up", "w_down"):
        lp["mlp"][name] = run(lp["mlp"][name], 1, True)
    params["lm_head"] = run(params["lm_head"], 1, False)
    return params


def probe(params, plans, cfg, prompt):
    """Last-position logits of one prompt, plus the StepCounts tape.

    One jitted prefill: the tape records inside the trace and its
    counts come back as outputs."""
    names = []

    def fwd(params, plans, toks):
        with sparse.tape.collect() as entries:
            out = tfm.forward(params, {"tokens": toks}, cfg,
                              mode="prefill", caches=None,
                              positions=jnp.arange(toks.shape[1],
                                                   dtype=jnp.int32),
                              rc=RunConfig(scan_unroll=True),
                              weight_plans=plans)
        names[:] = [e[0] for e in entries]
        return (out.logits[0, -1].astype(jnp.float32),
                [(e[1], e[2]) for e in entries])

    logits, tape = jax.jit(fwd)(params, plans,
                                jnp.asarray(prompt, jnp.int32)[None])
    entries = [(n, sc, ex) for n, (sc, ex) in zip(names, tape)]
    return np.asarray(logits), sparse.tape.summarize(entries)


def serve_arm(label, params, cfg, prompts, clock, *, slots=SLOTS,
              capacity=CAPACITY, bucket=None):
    """Serve ``prompts`` through one Engine; check and report the arm.

    Prompts pad to one bucket (the longest prompt length by default),
    so each arm compiles one prefill."""
    sparse.site.clear_quarantine()
    c0, t0 = clock.seconds, time.perf_counter()
    engine = Engine(params, cfg, rc=RunConfig(), serve=ServeConfig(
        slots=slots, capacity=capacity,
        prefill_bucket=bucket or PROMPT_LEN[1], max_prefill_batch=slots))
    for uid, prompt in enumerate(prompts):
        engine.submit(Request(uid=uid, prompt=prompt,
                              max_new_tokens=NEW_TOKENS))
    done = engine.run_to_completion()
    wall, compile_s = time.perf_counter() - t0, clock.seconds - c0
    bad = [(r.uid, r.status, r.error, len(r.output)) for r in done
           if r.status != "done" or len(r.output) != NEW_TOKENS]
    check(len(done) == len(prompts) and not bad,
          f"{label}: requests not all ok with {NEW_TOKENS} tokens: {bad}")
    check(not sparse.site.quarantine_report(),
          f"{label}: quarantined sites {sparse.site.quarantine_report()}")
    check(engine.decode_traces == 1,
          f"{label}: {engine.decode_traces} decode traces, expected 1")
    logits, tape = probe(engine.params, engine.weight_plans, cfg,
                         prompts[0])
    check(bool(np.all(np.isfinite(logits))),
          f"{label}: non-finite prefill logits")
    print(f"[{label}] smoke run, not a benchmark: serving wall {wall:.1f} s "
          f"of which compile {compile_s:.1f} s, "
          f"{sum(len(r.output) for r in done)} tokens, "
          f"decode traces {engine.decode_traces}, "
          f"peak_bytes_in_use so far {peak_bytes()}", flush=True)
    tokens = {r.uid: r.output for r in done}
    return engine, logits, tape, tokens


def compare_logits(ref, got, what):
    err = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    agree = int(np.argmax(ref) == np.argmax(got))
    print(f"[compare] {what}: max |diff| {err:.5f} vs max |logit| "
          f"{scale:.5f} (limit {LOGIT_RTOL} x), argmax agrees: {agree}",
          flush=True)
    check(err <= LOGIT_RTOL * scale,
          f"{what}: prefill logits differ by {err} > {LOGIT_RTOL} x {scale}")


def one_chip(seed: int) -> None:
    base = get_config("nemotron-4-340b")
    cfg = dataclasses.replace(base, n_layers=1, vocab_size=32000, **TILES)
    print(f"reduced: n_layers {base.n_layers} -> 1 (all layers alike: one "
          f"layer is a whole period); vocab_size {base.vocab_size} -> "
          f"32000 (one chip's share of an 8-way vocabulary-parallel "
          f"embedding and head; request ids drawn from it)", flush=True)
    print(f"kept: d_model {cfg.d_model}, heads {cfg.n_heads}, kv heads "
          f"{cfg.n_kv_heads}, head dim {cfg.hd}, d_ff {cfg.d_ff}, "
          f"{cfg.mlp_type}, rope {cfg.rope_style}", flush=True)
    clock = CompileClock()
    mesh = make_host_mesh(1)
    prompts = make_requests(seed, cfg.vocab_size)
    print(f"requests: {len(prompts)} prompts of "
          f"{[len(p) for p in prompts]} tokens, {NEW_TOKENS} new each; "
          f"{SLOTS} slots x {CAPACITY} positions", flush=True)
    with serve.serving(mesh):
        params = prune(serve.init_params(cfg, mesh, seed=seed), cfg)
        n = sum(x.size for x in jax.tree_util.tree_leaves(params))
        print(f"params: {n} bf16 ({n * 2 / 1e9:.2f} GB); pruning: "
              f"core.pruning.block_mask at {SPARSITY:.0%} block sparsity, "
              f"tiles {cfg.sparse_slice_k} (K) x {cfg.sparse_block_n} (N), "
              f"on attn q/k/v/o, mlp up/down and lm_head; embedding and "
              f"norms dense; peak_bytes_in_use {peak_bytes()}", flush=True)

        dense = dataclasses.replace(cfg, sparse_mode="dense")
        eng, ref, _, _ = serve_arm("dense", params, dense, prompts, clock)
        del eng

        dual = dataclasses.replace(cfg, sparse_mode="dual", sparse_kv=True,
                                   sparse_use_kernel=True,
                                   sparse_kcondense=True)
        for site, (k, n_) in (("mlp.up", (cfg.d_model, cfg.d_ff)),
                              ("mlp.down", (cfg.d_ff, cfg.d_model))):
            for m in (SLOTS, SLOTS * PROMPT_LEN[1]):
                bm, bn, sk = sparse.plan.clamp_geometry(
                    m, n_, k, cfg.sparse_block_m, cfg.sparse_block_n,
                    cfg.sparse_slice_k, False)
                fits = sparse.plan.knobs_valid(
                    m, n_, k, bm, bn, sk, use_kernel=True, condense="k",
                    dtype_bytes=2)
                print(f"kcondense {site} M={m}: "
                      f"{'kfused' if fits else 'slice kernel'} "
                      f"({sparse.plan.kfused_panel_bytes(bm, bn, k, sk, 2)}"
                      f" B of VMEM for kfused panels)", flush=True)
        eng, got, tape, _ = serve_arm("dual", params, dual, prompts, clock)
        args = (eng.params, eng.weight_plans, jnp.asarray(eng.last_tok),
                jnp.asarray(eng.pos, jnp.int32), eng.caches,
                jnp.zeros(eng.slots, bool))
        text = eng._decode.lower(*args).as_text()
        check("tpu_custom_call" in text,
              "dual: decode program holds no Pallas kernel")
        print(f"[dual] decode program: {text.count('tpu_custom_call')} "
              f"tpu_custom_call sites", flush=True)
        for e in tape:
            print(f"[dual] tape {e['name']}: dense {e['dense_steps']} "
                  f"executed {e['executed_steps']}", flush=True)
        for site in ("mlp.up", "mlp.down"):
            rows = [e for e in tape if e["name"] == site]
            check(bool(rows) and all(e["executed_steps"] < e["dense_steps"]
                                     for e in rows),
                  f"dual: {site} executed steps not below dense: {rows}")
        del eng
    compare_logits(ref, got, "dual vs dense")


def four_chips(seed: int) -> None:
    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    base = get_config("mixtral-8x7b")
    cfg = dataclasses.replace(base, n_layers=2)
    print(f"reduced: n_layers {base.n_layers} -> 2; kept all "
          f"{cfg.n_experts} experts top-{cfg.n_experts_active}, sliding "
          f"window {cfg.sliding_window}, vocab {cfg.vocab_size}, d_model "
          f"{cfg.d_model}, d_ff {cfg.d_ff}", flush=True)
    clock = CompileClock()
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (256, 128, 256, 128)]
    mesh = make_host_mesh(4)
    print(f"mesh: {dict(mesh.shape)}", flush=True)
    with serve.serving(mesh):
        params = serve.init_params(cfg, mesh, seed=seed)
        w_up = params["layers"]["pos0"]["moe"]["w_up"]
        per_dev = {s.device.id: s.data.shape
                   for s in w_up.addressable_shards}
        print(f"expert w_up {w_up.shape} shards: {per_dev}", flush=True)
        check(len(per_dev) == 4 and all(
            shp[1] == cfg.n_experts // 4 for shp in per_dev.values()),
            f"experts not split 2 per chip: {per_dev}")
        eng, sharded, _, tok_ep = serve_arm(
            "ep 1x4", params, cfg, prompts, clock, slots=2, capacity=512,
            bucket=1)
        pool = eng.caches["pos0"]["kv"].k
        print(f"KV pool {pool.shape} on devices "
              f"{sorted(d.id for d in pool.sharding.device_set)}",
              flush=True)
        check(len(pool.sharding.device_set) == 4,
              "KV pool does not span the mesh")
        del eng
    one = jax.device_put(params, jax.devices()[0])
    del params
    eng, local, _, tok_one = serve_arm("one chip", one, cfg, prompts, clock,
                                       slots=2, capacity=512, bucket=1)
    del eng
    same = sum(tok_ep[u] == tok_one[u] for u in tok_ep)
    print(f"[compare] greedy streams identical: {same}/{len(tok_ep)}",
          flush=True)
    compare_logits(local, sharded, "expert-parallel vs one chip")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mixtral expert-parallel phase on "
                    "4 chips and its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    device = platform.check_tpu()
    d0 = jax.devices()[0]
    print(f"platform {d0.platform}, device_kind {d0.device_kind}, "
          f"devices {len(jax.devices())}", flush=True)
    check(not platform.resolve_interpret(None),
          "kernels would run in interpret mode")
    print(f"compile cache: {serve.enable_compile_cache()}", flush=True)
    if args.four_chips:
        four_chips(args.seed)
        device["count"] = 4
    else:
        one_chip(args.seed)
        device["count"] = 1
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
