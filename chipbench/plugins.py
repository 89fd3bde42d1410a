"""Files the benchmark finds by name: ``chipbench/<kind>/<name>.py``.

Kinds: ``models`` (a model family: its parameter tree, pruned matrices
and needed work), ``references`` (a family's plain reference),
``arrivals`` (an arrival process of the traffic generator) and
``metrics`` (one per-layer metric reader).  A later cell that brings a
new family, process or metric adds a file here and edits none.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


@functools.lru_cache(maxsize=None)
def load(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} file named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + "_".join((kind, name)).replace(".", "_")
        .replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
