"""The one traffic generator: a mix file of parameters → requests.

A mix (``traffic/<name>.json``) names an arrival process, found by name
in ``arrivals/<process>.py``, and two length distributions; a cell
(``cells/<workload>.json``) may set the rate.  The sizes and gaps are
drawn once from the mix's own ``shape_seed``, so every run seed serves
the same multiset of prompt lengths, output lengths and inter-arrival
gaps; the run seed only permutes them (within blocks of
``shuffle_block`` where the mix sets one) and draws the token ids.  Two
seeds therefore offer the same work in another order, and the spread
between seeds is the system's, not the generator's.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

import plugins


@dataclasses.dataclass
class Planned:
    """One request of the schedule; ``due`` in seconds from window start."""
    uid: int
    due: float
    prompt: List[int]
    max_new_tokens: int


def _lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, size=n)
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def process(mix: dict):
    """The mix's arrival process module."""
    return plugins.load("arrivals", mix["arrival"])


def schedule(mix: dict, *, seed: int, seconds: float, vocab: int,
             rate: float = 0.0) -> List[Planned]:
    """Requests due in a window of ``seconds``, sorted by due time."""
    arrivals = process(mix)
    n = arrivals.count(mix, rate, seconds)
    shape = np.random.default_rng(int(mix["shape_seed"]))
    prompts = _lengths(shape, mix["prompt"], n)
    outputs = _lengths(shape, mix["output"], n)
    gaps = arrivals.gaps(shape, mix, n, seconds)
    order = np.random.default_rng(int(seed))
    block = int(mix.get("shuffle_block", n))

    def permute(x):
        return np.concatenate([order.permutation(x[i:i + block])
                               for i in range(0, n, block)])
    prompts = permute(prompts)
    outputs = permute(outputs)
    due = np.cumsum(permute(gaps))
    return [Planned(uid=i, due=float(due[i]),
                    prompt=order.integers(0, vocab, size=int(prompts[i]))
                    .tolist(),
                    max_new_tokens=int(outputs[i]))
            for i in range(n)]
