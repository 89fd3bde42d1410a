"""One run of one cell: set-up, a measured window, the check, a result.

Everything that belongs to one configuration, traffic mix, cell, model
family, arrival process or per-layer metric is a file found by name
(``configs/``, ``traffic/``, ``cells/``, ``models/``, ``references/``,
``arrivals/``, ``metrics/``); this module is the same for every cell.

From the program the benchmark takes the system under test
(``serving.Engine`` with the served path's set-up in
``launch.serve``), its counters (``Engine.stats()``) and the names of its
programs and kernels (``program.json``).  Traffic, weights, the
reference, the work counts and the trace reduction are its own.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import pathlib
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import generator
import plugins

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<traffic>.json
    spec: dict            # cells/<workload>.json
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str) -> Cell:
    bench = _json(ROOT / "BENCHMARK.json")
    work = [w for w in bench["workloads"] if w["name"] == name]
    if not work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[0]
    conf = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if ("workloads" in m and name in m["workloads"])
                 or ("workloads" not in m and m["moves"] in names)]
    return Cell(name=name, chips=int(w["chips"]),
                config=_json(ROOT / conf["file"]),
                traffic=_json(HERE / "traffic" / f"{w['traffic']}.json"),
                spec=_json(HERE / "cells" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str) -> Callable:
    return plugins.load("metrics", name).read


def family(cell: Cell):
    """The configuration's model family module (``models/<family>.py``)."""
    return plugins.load("models", cell.config["family"])


# ---------------------------------------------------------------------------
# compile evidence
# ---------------------------------------------------------------------------

class CompileClock:
    """Counts JAX tracing and compile events, and backend-compile seconds,
    while installed (JAX's monitoring events)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.events += 1
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


# ---------------------------------------------------------------------------
# set-up: weights, engine, warm-up of exactly the cell's shapes
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Setup:
    cell: Cell
    seed: int
    engine: object
    params: object
    kept: Dict[str, List[int]]
    clock: CompileClock
    mesh_ctx: object


def model_config(cell: Cell):
    """The program's ModelConfig for the configuration file, checked
    against the sizes the file states (and the reference reads)."""
    from repro.configs import get_config
    c = cell.config
    cfg = dataclasses.replace(get_config(c["zoo"]), **c["zoo_overrides"],
                              **c["serving"])
    bad = family(cell).program_mismatches(cfg, c["model"])
    if bad:
        raise ValueError(f"program config differs from {c['name']}: {bad}")
    return cfg


def prefill_shapes(cell: Cell, page: int) -> Dict[tuple, List[int]]:
    """(batch, padded length) of every prefill the traffic can produce,
    each with the page counts of its prompts."""
    lo, hi = cell.traffic["prompt"]["min"], cell.traffic["prompt"]["max"]
    bucket = cell.spec["engine"]["prefill_bucket"]
    batches = range(1, min(cell.spec["engine"]["max_prefill_batch"],
                           cell.spec["engine"]["slots"]) + 1)
    out: Dict[tuple, List[int]] = {}
    for lpad in range(bucket * math.ceil(lo / bucket), hi + bucket, bucket):
        first = max(lo, lpad - bucket + 1)
        last = min(hi, lpad)
        if first > last:
            continue
        pages = list(range(math.ceil(first / page),
                           math.ceil(last / page) + 1))
        for n in batches:
            out[(n, lpad)] = pages
    return out


def set_up(cell: Cell, seed: int, clock: CompileClock, *,
           engine_hook=None, log=None) -> Setup:
    import jax
    import jax.numpy as jnp
    from repro.configs.base import RunConfig, ServeConfig
    from repro.launch import serve
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tfm
    from repro.serving.engine import Engine, Request

    import weights

    t_last = [time.perf_counter()]

    def phase(what):
        now = time.perf_counter()
        if log is not None:
            log(f"set-up: {what} {now - t_last[0]:.3f} s "
                f"(compile {clock.seconds:.3f} s so far)")
        t_last[0] = now

    cfg = model_config(cell)
    c = cell.config
    mesh = make_host_mesh(1)
    eng_knobs = cell.spec["engine"]
    ctx = serve.serving(mesh)
    ctx.__enter__()
    params, kept = weights.make(c["family"], c["model"], c["sparsity"],
                                seed)
    jax.block_until_ready(params)
    kept = {k: [int(x) for x in np.asarray(v)] for k, v in kept.items()}
    phase("weights")
    engine = Engine(params, cfg, rc=RunConfig(), serve=ServeConfig(
        slots=eng_knobs["slots"], capacity=eng_knobs["capacity"],
        prefill_bucket=eng_knobs["prefill_bucket"],
        max_prefill_batch=eng_knobs["max_prefill_batch"]))
    if engine_hook is not None:
        engine_hook(engine)
    phase("engine")

    # every prefill (batch, length) and insert (batch, length, pages)
    for (n, lpad), pages in prefill_shapes(cell, engine.page).items():
        pre = tfm.init_caches(cfg, n, lpad, sparse=False, full_history=True,
                              quantized=engine.quantized)
        pre, nxt, ok = engine._prefill(
            engine.params, engine.weight_plans,
            jnp.asarray(np.zeros((n, lpad), np.int32)),
            jnp.asarray(np.full((n,), lpad, np.int32)), pre)
        np.asarray(nxt), np.asarray(ok)
        for nbr in pages:
            out = engine._insert(engine.caches, pre, jnp.int32(0),
                                 jnp.int32(0),
                                 jnp.asarray(np.arange(1, nbr + 1),
                                             jnp.int32),
                                 jnp.int32(nbr * engine.page))
            jax.block_until_ready(out)
        del pre, out
        phase(f"prefill {n}x{lpad} and {len(pages)} inserts")
    # the decode step, block-table pushes and the admission path, through
    # the engine's own loop
    lo = cell.traffic["prompt"]["min"]
    for uid in range(2):
        engine.submit(Request(uid=-1 - uid, prompt=[1] * lo,
                              max_new_tokens=3))
    engine.run_to_completion()
    phase("decode")
    return Setup(cell=cell, seed=seed, engine=engine, params=params,
                 kept=kept, clock=clock, mesh_ctx=ctx)


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    """What the window saw of one request (times on the host clock, s)."""
    uid: int
    due: float
    prompt: List[int]
    max_new_tokens: int
    request: object = None
    submitted: Optional[float] = None
    admitted_step: Optional[float] = None   # start of the admitting step
    token_times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Tick:
    start: float
    end: float
    prefill_lengths: List[int]     # prompts admitted (first token here)
    decode_keys: List[int]         # keys each decoded token attended to


@dataclasses.dataclass
class Window:
    t0: float
    seconds: float
    served: List[Served]
    ticks: List[Tick]
    compile_events: int
    traces_before: dict
    traces_after: dict


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation("chipbench." + name)


def drive(setup: Setup, seconds: float, *, drain_s: float = 60.0,
          want_tokens: Optional[int] = None,
          trace_handle: Optional[dict] = None) -> Window:
    """Offer the cell's traffic for ``seconds``.

    Requests are submitted when due; a step runs whenever the engine has
    work, else the loop sleeps until the next arrival.  After the close
    only requests due inside the window are submitted (those that came
    due during the last step), and the engine steps on until its finished
    requests hold the ``want_tokens`` the check compares (the cell's, by
    default) and, under an open-loop arrival process, every request due
    in the window has its first token: at most ``drain_s`` more.  Tokens
    are stamped with the end of the step that returned them; what comes
    after the close counts for the time to first token of requests due in
    the window and for the check, never for gaps or throughput."""
    from repro.serving.engine import Request

    cell, engine = setup.cell, setup.engine
    plan = generator.schedule(
        cell.traffic, seed=setup.seed, seconds=seconds,
        vocab=cell.config["model"]["vocab_size"],
        rate=cell.spec.get("rate_per_s", 0.0))
    served = [Served(uid=p.uid, due=p.due, prompt=p.prompt,
                     max_new_tokens=p.max_new_tokens) for p in plan]
    by_uid = {s.uid: s for s in served}
    traces_before = dict(engine.stats())
    ev0 = setup.clock.events
    ticks: List[Tick] = []
    nxt = 0
    open_loop = generator.process(cell.traffic).OPEN_LOOP
    want = (cell.spec["check"]["tokens"] if want_tokens is None
            else want_tokens)
    finished_tokens = 0
    window_span = _span("window")
    t0 = time.perf_counter()
    window_span.__enter__()
    end, closed = t0 + seconds, False
    while True:
        now = time.perf_counter()
        if now >= end and not closed:
            closed = True
            window_span.__exit__(None, None, None)
        with _span("submit"):
            while (nxt < len(served) and t0 + served[nxt].due <= now
                   and served[nxt].due < seconds):
                s = served[nxt]
                s.request = Request(uid=s.uid, prompt=s.prompt,
                                    max_new_tokens=s.max_new_tokens)
                s.submitted = now
                engine.submit(s.request)
                nxt += 1
        if closed and (now >= end + drain_s or (
                finished_tokens >= want and not (open_loop and any(
                    not s.token_times for s in served
                    if s.request is not None)))):
            break
        if engine._idle():
            if nxt >= len(served) or closed:
                if not closed:
                    with _span("wait"):
                        time.sleep(max(0.0, end - time.perf_counter()))
                    continue
                break
            with _span("wait"):
                time.sleep(max(0.0, min(t0 + served[nxt].due, end)
                               - time.perf_counter()))
            continue
        start = time.perf_counter()
        with _span("step"):
            finished = engine.step()
        stop = time.perf_counter()
        touched = [r for r in engine.active.values() if r is not None]
        touched += finished
        pre, keys = [], []
        for r in touched:
            s = by_uid.get(r.uid)
            if s is None:
                continue
            new = len(r.output) - len(s.token_times)
            if new <= 0:
                continue
            first = len(s.token_times)
            if first == 0:
                s.admitted_step = start
                pre.append(len(s.prompt))
            for j in range(first, len(r.output)):
                if j >= 1:
                    keys.append(len(s.prompt) + j)
            s.token_times.extend([stop] * new)
            if r.done and r.status == "done":
                finished_tokens += len(r.output)
        ticks.append(Tick(start, stop, pre, keys))
    if trace_handle is not None:
        # stopped only now: writing the profile pauses the host, which
        # inside the drain would stall the requests still in flight
        import tracefile
        tracefile.stop(trace_handle)
    return Window(t0=t0, seconds=seconds, served=served,
                  ticks=ticks,
                  compile_events=setup.clock.events - ev0,
                  traces_before=traces_before,
                  traces_after=dict(engine.stats()))


# ---------------------------------------------------------------------------
# end-to-end numbers
# ---------------------------------------------------------------------------

def due_in_window(w: Window) -> List[Served]:
    return [s for s in w.served if s.due < w.seconds]


def end_to_end(w: Window, setup_s: float) -> dict:
    """Every end-to-end number a cell may report, by metric name; a cell
    reports those that BENCHMARK.json lists for it.

    Time to first token counts from the due time, over the requests due
    in the window; one that got no token by the end of the drain counts
    as waiting until the last step.  Inter-token gaps are every gap
    between two tokens of a request whose later token came inside the
    window; tokens per second are the tokens returned inside it."""
    end = w.t0 + w.seconds
    out = {"setup_s": setup_s}
    reqs = due_in_window(w)
    last = max((t.end for t in w.ticks), default=end)
    ttft = [((s.token_times[0] if s.token_times else last)
             - (w.t0 + s.due)) * 1e3 for s in reqs]
    for q in (50, 90):
        out[f"ttft_p{q}_ms"] = float(np.percentile(ttft, q))
    gaps = []
    for s in w.served:
        times = [t for t in s.token_times if t <= end]
        gaps += [b - a for a, b in zip(times, times[1:])]
    for q in (50, 90, 95, 99):
        out[f"itl_p{q}_ms"] = float(np.percentile(np.asarray(gaps) * 1e3, q))
    out["output_tokens_per_s"] = sum(
        1 for s in w.served for t in s.token_times if t <= end) / w.seconds
    return out


def attempted(w: Window, open_loop: bool) -> List[Served]:
    """Requests attempted: those due in the window (open loop), or those
    the window started (a backlog)."""
    if open_loop:
        return due_in_window(w)
    end = w.t0 + w.seconds
    return [s for s in w.served if s.token_times and s.token_times[0] <= end]


# ---------------------------------------------------------------------------
# the check against the plain reference
# ---------------------------------------------------------------------------

def sample(w: Window, seed: int, want_tokens: int) -> List[Served]:
    """Finished requests drawn from the seed, the longest among them,
    until they hold ``want_tokens`` served tokens."""
    done = [s for s in w.served if s.request is not None
            and s.request.status == "done" and s.request.output]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 7])
    longest = max(done, key=lambda s: (len(s.request.output),
                                       len(s.prompt), -s.uid))
    rest = [s for s in done if s is not longest]
    order = rng.permutation(len(rest))
    picked, total = [longest], len(longest.request.output)
    for i in order:
        if total >= want_tokens:
            break
        picked.append(rest[i])
        total += len(rest[i].request.output)
    return picked


def reference_module(cell: Cell):
    return plugins.load("references", cell.config["reference"])


def compare(cell: Cell, seed: int, picked: List[Served],
            controls=()) -> dict:
    """Regenerate the weights from the seed and run the reference over
    each picked request's prompt and served tokens (and, for the limits,
    the lower-precision ``controls`` at the same positions)."""
    import jax
    import weights
    ref = reference_module(cell)
    c = cell.config
    params, _ = weights.make(c["family"], c["model"], c["sparsity"], seed)
    out_max = cell.traffic["output"]["max"]
    pad_to = cell.spec["engine"]["capacity"]
    rows = []
    with jax.default_matmul_precision("highest"):
        for s in picked:
            rows.append(ref.served_gaps(cell.config["model"], params,
                                        s.prompt, s.request.output, pad_to,
                                        out_max, controls=controls))
    del params
    tokens = sum(r["tokens"] for r in rows)
    res = {"widest_logit_gap": max((r["gap"] for r in rows),
                                   default=float("inf")),
           "mean_logit_gap": (sum(r["gap_sum"] for r in rows) / tokens
                              if tokens else float("inf")),
           "compared_tokens": tokens,
           "argmax_agree": sum(r["agree"] for r in rows),
           "requests": len(rows)}
    for q in controls:
        res[f"{q}_widest_gap"] = max(r[f"{q}_gap"] for r in rows)
        res[f"{q}_mean_gap"] = sum(r[f"{q}_gap_sum"] for r in rows) / tokens
    return res


# ---------------------------------------------------------------------------
# a whole run
# ---------------------------------------------------------------------------

def device_info(require_tpu: bool, chips: int) -> dict:
    import jax
    if require_tpu:
        from repro.kernels import platform
        info = platform.check_tpu()
        if info["count"] < chips:
            raise RuntimeError(f"cell needs {chips} chips, found "
                               f"{info['count']}")
        return {"platform": info["platform"], "kind": info["kind"],
                "count": chips}
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def enable_cache() -> str:
    import jax
    from repro.launch import serve
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    path = serve.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def free(setup: Setup) -> None:
    """Drop every array of the program's state, and leave its mesh."""
    setup.engine = None
    setup.params = None
    setup.mesh_ctx.__exit__(None, None, None)
    gc.collect()


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        started: float, require_tpu: bool = True, engine_hook=None,
        log=print) -> dict:
    """One run; returns the result line's object."""
    import jax
    device = device_info(require_tpu, cell.chips)
    chip_peaks = peaks(device["kind"]) if require_tpu else None
    enable_cache()
    clock = CompileClock()
    setup = set_up(cell, seed, clock, engine_hook=engine_hook, log=log)
    trace_handle = None
    if trace:
        import tracefile
        trace_handle = tracefile.start()
    setup_s = time.perf_counter() - started
    w = drive(setup, seconds, trace_handle=trace_handle)
    open_loop = generator.process(cell.traffic).OPEN_LOOP
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    e2e = end_to_end(w, setup_s)
    tried = attempted(w, open_loop)
    unanswered = sum(1 for s in tried if not s.token_times)
    failed = unanswered + sum(1 for s in tried if s.request is not None
                              and s.request.status == "error")
    counters = {"before": w.traces_before, "after": w.traces_after}
    kept, cfg_tile = setup.kept, tuple(cell.config["sparsity"]["tile"])
    free(setup)
    picked = sample(w, seed, cell.spec["check"]["tokens"])
    t_ref = time.perf_counter()
    cmp = compare(cell, seed, picked)
    log(f"reference: {time.perf_counter() - t_ref:.3f} s")
    retraced = sum(w.traces_after[k] - w.traces_before[k]
                   for k in ("prefill_traces", "insert_traces",
                             "decode_traces"))
    checks = {
        "widest_logit_gap": {"value": _finite(cmp["widest_logit_gap"]),
                             "limit": cell.spec["check"]["widest_logit_gap"]},
        "compiles_in_window": {"value": w.compile_events + retraced,
                               "limit": 0},
        # the engine retires a request whose logits are not finite (or
        # whose deadline passed) as an error, and serves the others on
        "errored_requests": {"value": w.traces_after["errored"]
                             - w.traces_before["errored"], "limit": 0},
        # an attempted request that got no token by the end of the drain
        "unanswered_requests": {"value": unanswered, "limit": 0},
    }
    correct = bool(picked) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    log(f"end-to-end: {e2e}")
    log(f"window {seconds} s: {len(tried)} attempted, {failed} failed, "
        f"{len(w.ticks)} steps, compared {cmp['compared_tokens']} tokens of "
        f"{cmp['requests']} requests, argmax agrees on "
        f"{cmp['argmax_agree']}, mean logit gap {cmp['mean_logit_gap']!r}; "
        f"late submissions "
        f"{_lateness(w):.6f} s at most; counters {counters}")
    result = {"correct": correct, "attempted": len(tried), "failed": failed}
    if trace:
        import tracefile
        t_read = time.perf_counter()
        prog = _json(HERE / "program.json")
        events = tracefile.extract(trace_handle["path"],
                                   prog["kernel_custom_call_target"])
        tracefile.discard(trace_handle)
        red = tracefile.reduce(events, prog["programs"])
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = Context(cell=cell, window=w, trace=red, kept=kept,
                      tile=cfg_tile, peaks=chip_peaks,
                      program=prog)
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        log(f"trace: {len(events)} events read in "
            f"{time.perf_counter() - t_read:.3f} s; programs "
            f"{red['programs']}")
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = checks
    return result


def _finite(x: float) -> Optional[float]:
    """JSON has no infinity: a number that never came is null."""
    return x if math.isfinite(x) else None


def _lateness(w: Window) -> float:
    late = [s.submitted - (w.t0 + s.due) for s in w.served
            if s.submitted is not None]
    return max(late, default=0.0)


def peaks(kind: str) -> dict:
    table = _json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets."""
    cell: Cell
    window: Window
    trace: dict
    kept: Dict[str, List[int]]
    tile: tuple
    peaks: dict
    program: dict

    def work(self):
        return family(self.cell).Work(self.cell.config["model"], self.kept,
                                      self.tile)
