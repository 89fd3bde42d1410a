"""Spans, scopes and stamps inside the served path.

On nemotron's smoke config with the chip cell's serving knobs (dual
mode, sparse KV, kernels in interpret mode, K-condensation): the
engine's ``engine.*`` host spans nest in ``engine.step``; every sparse
site's device ops carry ``<site>/plan``, ``<site>/kernel`` or
``<site>/xla`` in their ``op_name``, and those scopes leave the compiled
program unchanged; requests carry ordered wall-clock stamps; the prefill
token counters match the dispatched shapes.
"""
import collections
import contextlib
import dataclasses
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.configs.base import ServeConfig
from repro.models import transformer as tfm
from repro.serving.engine import Engine, Request

PHASES = ("engine.admit", "engine.prefill", "engine.insert",
          "engine.ensure_pages", "engine.push_table", "engine.decode",
          "engine.sync", "engine.retire")


@pytest.fixture(scope="module")
def served():
    cfg = dataclasses.replace(
        smoke_config("nemotron-4-340b"), sparse_mode="dual",
        sparse_kv=True, sparse_use_kernel=True, sparse_kcondense=True,
        sparse_block_m=16, sparse_block_n=16, sparse_slice_k=16)
    params, _ = tfm.init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(served):
    cfg, params = served
    return Engine(params, cfg, serve=ServeConfig(
        slots=2, capacity=64, prefill_bucket=16))


@pytest.fixture(scope="module")
def engine(served):
    return _engine(served)


def _requests(uid0, lengths, max_new=3):
    return [Request(uid=uid0 + i, prompt=[1 + (7 * i + j) % 500
                                          for j in range(n)],
                    max_new_tokens=max_new)
            for i, n in enumerate(lengths)]


def _decode_args(eng):
    return (eng.params, eng.weight_plans,
            jnp.zeros((eng.slots,), jnp.int32),
            jnp.zeros((eng.slots,), jnp.int32), eng.caches,
            jnp.zeros((eng.slots,), bool))


def _program(hlo: str) -> str:
    """Compiled HLO text without ``metadata={...}`` and the source-location
    tables above the first computation, with instruction names numbered
    in order of appearance (the CPU backend numbers some of the copies
    it adds by their metadata)."""
    lines = hlo.splitlines()
    first = next(i for i, line in enumerate(lines)
                 if line.startswith(("%", "ENTRY")))
    text = re.sub(r', metadata=\{(?:[^{}"]|"[^"]*")*\}', "",
                  "\n".join(lines[:1] + lines[first:]))
    names = {}

    def renumber(m):
        base = re.sub(r"[.-]\d+$", "", m.group(0))
        return names.setdefault(m.group(0), f"{base}#{len(names)}")
    return re.sub(r"%[A-Za-z_][\w.-]*", renumber, text)


def test_request_stamps_and_prefill_tokens(engine):
    """More requests than slots: every request's stamps are ordered and
    lie inside the run, and the prefill counters equal what was
    dispatched."""
    dispatched = []
    prefill = engine._prefill

    def counting(params, plans, tokens, true_len, caches):
        dispatched.append((tokens.shape[0] * tokens.shape[1],
                           int(np.asarray(true_len).sum())))
        return prefill(params, plans, tokens, true_len, caches)

    before = engine.stats()
    reqs = _requests(0, (5, 9, 17, 12))
    engine._prefill = counting
    try:
        start = time.perf_counter()
        for r in reqs:
            engine.submit(r)
        done = engine.run_to_completion()
        end = time.perf_counter()
    finally:
        engine._prefill = prefill
    assert sorted(r.uid for r in done) == [r.uid for r in reqs]
    for r in reqs:
        assert r.status == "done"
        assert start <= r.t_prefill <= r.t_first <= end, r
    after = engine.stats()
    true = after["prefill_tokens_true"] - before["prefill_tokens_true"]
    padded = (after["prefill_tokens_padded"]
              - before["prefill_tokens_padded"])
    assert true == sum(len(r.prompt) for r in reqs)
    assert true == sum(t for _, t in dispatched)
    assert padded == sum(p for p, _ in dispatched)
    assert padded >= true
    assert after["decode_traces"] == 1


def test_profile_shows_phases_nested_in_step(engine, tmp_path):
    """A profile of three ticks: one ``engine.step`` each, every phase
    span inside one of them, one prefill call for the two prompts and
    one decode call a tick."""
    from jax.profiler import ProfileData
    engine.run_to_completion()
    for r in _requests(100, (6, 11), max_new=8):
        engine.submit(r)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            engine.step()
    finally:
        jax.profiler.stop_trace()
    engine.run_to_completion()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("engine.")]
    steps = [s for s in spans if s[0] == "engine.step"]
    assert len(steps) == 3
    names = {s[0] for s in spans}
    assert set(PHASES) <= names, names
    for name, start, end in spans:
        if name != "engine.step":
            assert any(s <= start and end <= e for _, s, e in steps), name
    # both prompts pad to one 16-token bucket: one call of two rows
    count = collections.Counter(name for name, _, _ in spans)
    assert count["engine.prefill"] == 1
    assert count["engine.decode"] == 3
    assert engine.stats()["decode_traces"] == 1


def test_decode_hlo_carries_site_scopes(served):
    """The decode program's ops name their site and its part."""
    eng = _engine(served)
    hlo = eng._decode.lower(*_decode_args(eng)).as_text(
        dialect="hlo", debug_info=True)
    paths = set(re.findall(r'op_name="([^"]*)"', hlo))
    for want in ("mlp.up/plan/", "mlp.up/kernel/", "mlp.down/plan/",
                 "lm_head/", "attn.q/plan/", "attn.out/kernel/",
                 "attn.kv/", "attn.score/kernel/", "attn.value/plan/"):
        assert any(want in p for p in paths), want


def test_scopes_leave_compiled_decode_unchanged(served, monkeypatch):
    """With every named scope removed the compiled decode program differs
    in metadata and instruction numbering only."""
    eng = _engine(served)
    args = _decode_args(eng)

    def compiled():
        # a new function each time, so nothing comes from the trace cache
        return jax.jit(lambda *a: eng._decode_impl(*a)).lower(
            *args).compile().as_text()

    scoped = compiled()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled()
    assert "mlp.up/plan/" in scoped and "mlp.up/plan/" not in plain
    assert _program(scoped) == _program(plain)
