"""The per-layer readers of the engine's own stamps and counters, on a
smoke-size window on the CPU, and on a program that has neither."""
import types

import numpy as np
import pytest

import harness
import smoke
import work

READERS = ("engine.admit_wait_p50_ms", "engine.first_token_hold_p50_ms",
           "engine.prefill_pad_share")


@pytest.fixture(scope="module")
def window():
    cell = smoke.smoke_cell("nemotron-4-340b.chat")
    setup = harness.set_up(cell, 2**31 + 5, harness.CompileClock())
    w = harness.drive(setup, 2.0)
    harness.free(setup)
    return cell, w


def _ctx(cell, w):
    return harness.Context(cell=cell, window=w, trace={}, kept={},
                           tile=(), peaks=None, program={})


def test_readers_on_a_window(window):
    cell, w = window
    got = {name: harness.metric_reader(name)(_ctx(cell, w))
           for name in READERS}
    due = [s for s in w.served if s.due < w.seconds]
    for s in due:
        r = s.request
        # the harness submits a request after its due time, the step that
        # admits it starts after that, and its prefill inside that step
        assert w.t0 + s.due <= s.submitted <= s.admitted_step
        assert s.admitted_step <= r.t_prefill <= r.t_first
        assert r.t_first <= s.token_times[0]
    assert got["engine.admit_wait_p50_ms"] == pytest.approx(float(
        np.median([(s.request.t_prefill - (w.t0 + s.due)) * 1e3
                   for s in due])))
    # never below the queue wait, which ends where the admitting step
    # starts
    assert got["engine.admit_wait_p50_ms"] >= harness.metric_reader(
        "engine.queue_wait_p50_ms")(_ctx(cell, w))
    assert got["engine.first_token_hold_p50_ms"] >= 0
    # padding recounted from the prompts each step admitted
    eng = cell.spec["engine"]
    calls = [c for t in w.ticks
             for c in work.prefill_calls(t.prefill_lengths,
                                         eng["prefill_bucket"],
                                         eng["max_prefill_batch"])]
    padded = sum(n * lpad for n, lpad in calls)
    true = sum(n for t in w.ticks for n in t.prefill_lengths)
    assert got["engine.prefill_pad_share"] == pytest.approx(
        100.0 * (padded - true) / padded)


def test_readers_without_stamps_or_counters(window):
    """A program with no stamps and no prefill counters reads None."""
    cell, w = window
    bare = types.SimpleNamespace(
        t0=w.t0, seconds=w.seconds, ticks=w.ticks,
        served=[types.SimpleNamespace(
            due=s.due, token_times=s.token_times,
            request=types.SimpleNamespace(output=s.request.output))
            for s in w.served if s.request is not None],
        traces_before={"decode_traces": 1},
        traces_after={"decode_traces": 1})
    for name in READERS:
        assert harness.metric_reader(name)(_ctx(cell, bare)) is None
