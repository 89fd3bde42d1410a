"""The traffic generator's schedule from a seed."""
import numpy as np

import generator
import harness

CHAT = harness._json(harness.HERE / "traffic" / "chat.json")
# a backlog mix: short prompts, long answers, all due at the start
OFFLINE = {"arrival": "backlog", "count": 1500, "shuffle_block": 32,
           "shape_seed": 20240612,
           "prompt": {"dist": "uniform", "min": 64, "max": 256},
           "output": {"dist": "uniform", "min": 256, "max": 512}}


def test_same_seed_same_schedule():
    a = generator.schedule(CHAT, seed=2**31 + 5, seconds=30, vocab=32000,
                           rate=4.0)
    b = generator.schedule(CHAT, seed=2**31 + 5, seconds=30, vocab=32000,
                           rate=4.0)
    assert [(r.due, r.prompt, r.max_new_tokens) for r in a] == \
        [(r.due, r.prompt, r.max_new_tokens) for r in b]


def test_seeds_reorder_the_same_work():
    a = generator.schedule(CHAT, seed=1, seconds=30, vocab=32000, rate=4.0)
    b = generator.schedule(CHAT, seed=2, seconds=30, vocab=32000, rate=4.0)
    assert len(a) == len(b) == 120
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in b)
    assert sorted(r.max_new_tokens for r in a) == sorted(
        r.max_new_tokens for r in b)
    gaps = [np.diff([0.0] + [r.due for r in s]) for s in (a, b)]
    assert np.allclose(sorted(gaps[0]), sorted(gaps[1]))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_open_loop_arrivals_inside_window_and_in_range():
    s = generator.schedule(CHAT, seed=3, seconds=20, vocab=100, rate=6.0)
    due = [r.due for r in s]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 20
    assert all(128 <= len(r.prompt) <= 2048 for r in s)
    assert all(16 <= r.max_new_tokens <= 512 for r in s)
    assert all(0 <= t < 100 for r in s for t in r.prompt)
    # the published medians of the conversation trace
    assert 800 < np.median([len(r.prompt) for r in s]) < 1250
    assert 90 < np.median([r.max_new_tokens for r in s]) < 180


def test_backlog_is_due_at_start():
    s = generator.schedule(OFFLINE, seed=4, seconds=30, vocab=32000)
    assert len(s) == OFFLINE["count"]
    assert all(r.due == 0.0 for r in s)
    assert all(64 <= len(r.prompt) <= 256 and 256 <= r.max_new_tokens <= 512
               for r in s)


def test_backlog_head_holds_the_same_sizes_for_every_seed():
    block = OFFLINE["shuffle_block"]
    a = generator.schedule(OFFLINE, seed=7, seconds=30, vocab=32000)
    b = generator.schedule(OFFLINE, seed=8, seconds=30, vocab=32000)
    for i in range(0, 3 * block, block):
        assert sorted(len(r.prompt) for r in a[i:i + block]) == sorted(
            len(r.prompt) for r in b[i:i + block])
        assert sorted(r.max_new_tokens for r in a[i:i + block]) == sorted(
            r.max_new_tokens for r in b[i:i + block])
    assert [len(r.prompt) for r in a[:block]] != [len(r.prompt)
                                                  for r in b[:block]]


def test_unknown_arrival_process_is_an_error():
    import pytest
    with pytest.raises(KeyError, match="arrivals"):
        generator.schedule(dict(CHAT, arrival="bursty"), seed=1, seconds=5,
                           vocab=10, rate=1.0)
