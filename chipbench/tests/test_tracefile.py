"""The reduction from trace events to the numbers the readers use."""
import gzip
import json
import pathlib

import pytest

import tracefile

PROGRAMS = {"prefill": "_prefill_impl", "decode": "_decode_impl",
            "insert": "_insert_impl"}
DEV = "/device:TPU:0"
SLICE = pathlib.Path(__file__).resolve().parent / "data" / \
    "trace_slice.json.gz"


def op(name, start, dur, kernel=False):
    return {"plane": DEV, "line": tracefile.OPS_LINE, "name": name,
            "kernel": kernel, "start_ns": float(start),
            "dur_ns": float(dur)}


def module(name, start, dur):
    return {"plane": DEV, "line": tracefile.MODULES_LINE, "name": name,
            "kernel": False, "start_ns": float(start), "dur_ns": float(dur)}


def host(name, start, dur):
    return {"plane": "/host:CPU", "line": "python", "name": name,
            "kernel": False, "start_ns": float(start), "dur_ns": float(dur)}


def test_hand_made_window():
    events = [
        host("chipbench.window", 1000, 10000),
        host("chipbench.step", 1000, 4000),
        host("chipbench.wait", 5000, 4000),
        host("chipbench.step", 9000, 2000),
        # decode program 1000..4000: a kernel and an XLA fusion
        module("jit__decode_impl(3)", 1000, 3000),
        op("bitmap_spgemm_planned.1", 1000, 1500, kernel=True),
        op("fusion.2", 2000, 1000),           # overlaps the kernel
        # prefill program 9000..12000, half outside the window
        module("jit__prefill_impl(7)", 9000, 3000),
        op("grouped_spgemm_planned.3", 9000, 3000, kernel=True),
        # an op before the window is not counted
        op("fusion.9", 0, 500),
    ]
    r = tracefile.reduce(events, PROGRAMS)
    assert r["window_s"] == pytest.approx(10e-6)
    # busy: 1000..3000 and 9000..11000 inside the window
    assert r["busy_s"] == pytest.approx(4e-6)
    assert r["programs"]["decode"] == {"calls": 1, "device_s": 3e-6,
                                       "kernel_s": 1.5e-6,
                                       "kernel_calls": 1}
    assert r["programs"]["prefill"]["calls"] == 1
    assert r["programs"]["prefill"]["kernel_s"] == pytest.approx(3e-6)
    # the longest idle gap (3000..9000) fell mostly in the host's wait
    assert r["idle_gaps"][0] == ["wait", pytest.approx(6e-6)]
    assert dict(r["device_ops"])["bitmap_spgemm_planned"] == \
        pytest.approx(1.5e-6)


def test_window_span_required():
    with pytest.raises(ValueError):
        tracefile.reduce([op("fusion.1", 0, 10)], PROGRAMS)


def test_recorded_slice():
    """A slice of a TPU v5e trace of nemotron-4-340b.chat (3 prefills,
    3 inserts, 2 decode steps), against a plain recount."""
    with gzip.open(SLICE, "rt") as f:
        events = json.load(f)
    r = tracefile.reduce(events, PROGRAMS)
    mods = [e for e in events if e["line"] == tracefile.MODULES_LINE]
    for role, key in PROGRAMS.items():
        mine = [m for m in mods if key in m["name"]]
        assert r["programs"][role]["calls"] == len(mine)
        assert r["programs"][role]["device_s"] == pytest.approx(
            sum(m["dur_ns"] for m in mine) / 1e9)
        kernel_ns = sum(
            e["dur_ns"] for e in events
            if e["kernel"] and any(
                m["start_ns"] <= e["start_ns"] + e["dur_ns"] / 2
                < m["start_ns"] + m["dur_ns"] for m in mine))
        assert r["programs"][role]["kernel_s"] == pytest.approx(
            kernel_ns / 1e9)
    assert [r["programs"][k]["calls"] for k in PROGRAMS] == [3, 2, 3]
    assert r["programs"]["decode"]["kernel_calls"] == 18
    # busy time: a plain sweep over the op intervals
    ops = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                 for e in events if e["line"] == tracefile.OPS_LINE)
    busy, end = 0.0, float("-inf")
    for s, e in ops:
        if e > end:
            busy += e - max(s, end)
            end = e
    assert r["busy_s"] == pytest.approx(busy / 1e9)
    assert 0 < r["busy_s"] < r["window_s"]
