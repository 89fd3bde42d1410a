"""Shared benchmark helpers: timing, CSV emission, JSON persistence.

Every ``emit`` both prints the legacy ``name,us_per_call,derived`` CSV
line and appends a machine-readable record to the module-level
``RESULTS`` list; ``dump_json`` writes the collected records (plus
environment metadata) to a file, so CI can upload per-run artifacts and
the perf trajectory across PRs is diffable instead of buried in logs.
"""
import json
import os
import platform
import time
from typing import List, Optional

import jax
import numpy as np

# machine-readable mirror of everything emit() printed in this process
RESULTS: List[dict] = []


def time_fn(fn, *args, warmup: int = 2, repeat: int = 5) -> float:
    """Median wall-time in microseconds of jitted fn(*args)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(times))


def tune_timer(warmup: int = 1, repeat: int = 3):
    """A ``fn -> median µs`` adapter for the autotune sweeps.

    ``repro.sparse.autotune.tune_matmul/tune_grouped`` take a
    ``timer(fn)`` callable; this closes :func:`time_fn` over a
    warmup/repeat budget so every bench's sweep shares the same
    measurement discipline as its other numbers.
    """
    def timer(fn):
        return time_fn(fn, warmup=warmup, repeat=repeat)
    return timer


def _parse_derived(derived: str) -> dict:
    """``k=v;k=v`` → dict with numeric coercion (raw string fallback)."""
    out = {}
    for part in derived.split(";"):
        if "=" not in part:
            out.setdefault("notes", []).append(part)
            continue
        key, val = part.split("=", 1)
        try:
            num = float(val)
            out[key] = int(num) if num == int(num) and "." not in val \
                and "e" not in val.lower() else num
        except ValueError:
            out[key] = val
    return out


def emit(name: str, us: Optional[float], derived: str = "") -> None:
    """One record; ``us`` None for a record that times nothing (an empty
    CSV field, no ``us_per_call`` key)."""
    print(f"{name},{'' if us is None else f'{us:.1f}'},{derived}",
          flush=True)
    entry = {"name": name}
    if us is not None:
        entry["us_per_call"] = round(us, 1)
    if derived:
        entry.update(_parse_derived(derived))
    RESULTS.append(entry)


def dump_json(path: Optional[str], extra_meta: Optional[dict] = None
              ) -> None:
    """Write the collected RESULTS (+ run metadata) to ``path``.

    No-op when ``path`` is falsy, so benches can pass their ``--json``
    argument through unconditionally.
    """
    if not path:
        return
    dirname = os.path.dirname(path)
    if dirname:
        os.makedirs(dirname, exist_ok=True)
    meta = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "python": platform.python_version(),
        "platform": platform.platform(),
    }
    if extra_meta:
        meta.update(extra_meta)
    with open(path, "w") as f:
        json.dump({"meta": meta, "results": RESULTS}, f, indent=2)
    print(f"# wrote {len(RESULTS)} bench records to {path}", flush=True)


def sparse(rng, shape, sparsity, dtype=np.float32):
    x = rng.normal(size=shape).astype(dtype)
    x[rng.random(shape) < sparsity] = 0
    return x


def kfiber_sparse(rng, shape, sparsity, axis=-1, dtype=np.float32):
    """Dense values with a random fraction of whole k-fibers zeroed.

    The unstructured-K regime of DESIGN.md §12: sparsity is element-
    granular along the contraction axis (no slice/block alignment) but
    fiber-aligned across the other axis — magnitude-pruned input
    channels, Griffin-style flocked ReLU features.  Slice-granular
    planning barely skips it; element condensation recovers it.
    """
    x = rng.normal(size=shape).astype(dtype)
    n = shape[axis]
    dead = rng.random(n) < sparsity
    idx = [slice(None)] * len(shape)
    idx[axis] = dead
    x[tuple(idx)] = 0
    return x
