"""A whole run at smoke size on the CPU: the harness without its look for
a chip, driving the program's engine; with the timed path broken
underneath, ``correct`` has to come out false.

Faults a served cell can have: a decode step that returns its cache
state unchanged; half of the serving batch left out (its rows fed the
other half's tokens); a token altered where it is produced; a row whose
logits are not finite, which the engine retires as an error while it
serves the others on.  There is no exchange between chips in a one-chip
cell.
"""
import time

import jax.numpy as jnp
import pytest

import harness
import smoke


def _run(workload, hook=None):
    cell = smoke.smoke_cell(workload)
    return harness.run(cell, 2**31 + 17, 2.0, False,
                       started=time.perf_counter(), require_tpu=False,
                       engine_hook=hook, log=lambda *a: None)


def altered_token(engine):
    decode = engine._decode
    vocab = engine.cfg.vocab_size

    def broken(*args):
        caches, nxt, ok = decode(*args)
        return caches, (nxt + 1) % vocab, ok
    engine._decode = broken


def unchanged_state(engine):
    decode = engine._decode

    def broken(params, plans, toks, pos, caches, poison):
        _, nxt, ok = decode(params, plans, toks, pos, caches, poison)
        return caches, nxt, ok
    engine._decode = broken


def half_batch(engine):
    decode = engine._decode

    def broken(params, plans, toks, pos, caches, poison):
        half = toks.shape[0] // 2
        toks = jnp.concatenate([toks[:half], toks[:toks.shape[0] - half]])
        return decode(params, plans, toks, pos, caches, poison)
    engine._decode = broken


def nonfinite_row(engine):
    """The third decode call reports row 0's logits as not finite."""
    decode = engine._decode
    calls = [0]

    def broken(*args):
        caches, nxt, ok = decode(*args)
        calls[0] += 1
        if calls[0] == 3:
            ok = ok.at[0].set(False)
        return caches, nxt, ok
    engine._decode = broken


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_cache", lambda: None)


def test_sound_run_is_correct():
    res = _run("nemotron-4-340b.chat")
    assert res["correct"], res["checks"]
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert res["checks"]["errored_requests"]["value"] == 0
    assert res["checks"]["unanswered_requests"]["value"] == 0
    assert set(res["metrics"]) == {
        m["name"] for m in harness.load_cell("nemotron-4-340b.chat")
        .end_to_end}
    assert list(res)[-1] == "checks"


def test_backlog_run_is_correct():
    """A backlog through the same harness: only the requests the window
    starts are attempted, and none of them fails."""
    cell = smoke.smoke_cell("nemotron-4-340b.chat", backlog=40)
    res = harness.run(cell, 5, 2.0, False, started=time.perf_counter(),
                      require_tpu=False, log=lambda *a: None)
    assert res["correct"], res["checks"]
    assert 0 < res["attempted"] < 40 and res["failed"] == 0


@pytest.mark.parametrize("fault", [altered_token, unchanged_state,
                                   half_batch, nonfinite_row])
def test_broken_step_is_not_correct(fault):
    res = _run("nemotron-4-340b.chat", fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_control_is_not_correct(seed):
    """The plain reference in fp8 put in the program's place: at the
    served positions, the token it puts first lies further below the
    float32 reference's best than the limit allows."""
    cell = smoke.smoke_cell("nemotron-4-340b.chat")
    setup = harness.set_up(cell, seed, harness.CompileClock())
    w = harness.drive(setup, 2.0)
    harness.free(setup)
    picked = harness.sample(w, seed, cell.spec["check"]["tokens"])
    res = harness.compare(cell, seed, picked, controls=("fp8",))
    limit = cell.spec["check"]["widest_logit_gap"]
    assert res["widest_logit_gap"] <= limit
    assert res["fp8_widest_gap"] > limit, res


def test_compile_in_window_is_not_correct():
    def late_compile(engine):
        decode = engine._decode

        def recompiling(*args):
            jnp.arange(engine.ticks + 1000).sum()   # a new shape each tick
            return decode(*args)
        engine._decode = recompiling
    res = _run("nemotron-4-340b.chat", late_compile)
    assert res["checks"]["compiles_in_window"]["value"] > 0
    assert not res["correct"]
