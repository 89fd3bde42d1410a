"""Device trace: capture around the window, extract, reduce to numbers.

Two stages, so the reduction can be checked on a small recorded trace:

* :func:`extract` reads the profiler's ``.xplane.pb`` into plain event
  dicts — every event of the device planes' op and module lines, and the
  benchmark's own host spans (``chipbench.*`` annotations);
* :func:`reduce` turns those events into what the metric readers use:
  the traced window, device busy time (the union of op intervals, averaged
  over the devices), device time and calls per engine program, Pallas
  kernel time per program, the ops that took most time, and the longest
  idle gaps labelled by the host span they fell in.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def start() -> dict:
    """Start the profiler into a fresh temporary directory."""
    import jax
    handle = {"dir": tempfile.mkdtemp(prefix="chipbench-trace-"),
              "path": None, "running": True}
    jax.profiler.start_trace(handle["dir"])
    return handle


def stop(handle: dict) -> None:
    """Stop the profiler (once) and find the ``.xplane.pb`` it wrote."""
    import jax
    if not handle["running"]:
        return
    jax.profiler.stop_trace()
    handle["running"] = False
    found = glob.glob(os.path.join(handle["dir"], "plugins", "profile",
                                   "*", "*.xplane.pb"))
    handle["path"] = found[0] if found else None


def discard(handle: dict) -> None:
    stop(handle)
    shutil.rmtree(handle["dir"], ignore_errors=True)


def extract(path: str, kernel_target: str) -> List[dict]:
    """Events of an ``.xplane.pb``: device ops and modules, host spans.

    A device op's event name is its HLO instruction text; the event keeps
    the instruction's name and whether it is a custom call to
    ``kernel_target`` (a Pallas kernel)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    marker = f'custom_call_target="{kernel_target}"'
    events: List[dict] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(SPAN_PREFIX):
                    continue
                events.append({
                    "plane": plane.name, "line": line.name,
                    "name": name.split(" = ")[0].lstrip("%"),
                    "kernel": device and marker in name,
                    "start_ns": float(ev.start_ns),
                    "dur_ns": float(ev.duration_ns)})
    return events


def _union(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _op_group(name: str) -> str:
    """An op's name without its instance number (fusion.12 → fusion)."""
    return re.sub(r"[.\-_]\d+$", "", name)


def reduce(events: List[dict], programs: Dict[str, str],
           window: Optional[Tuple[float, float]] = None) -> dict:
    """Numbers of one traced window.

    ``programs`` maps a role (decode, prefill, ...) to the substring that
    names its module.  The window is the ``chipbench.window`` host span
    unless given (in ns).
    """
    if window is None:
        spans = [e for e in events if e["name"] == WINDOW_SPAN]
        if not spans:
            raise ValueError("trace has no chipbench.window span")
        w = spans[0]
        window = (w["start_ns"], w["start_ns"] + w["dur_ns"])
    lo, hi = window
    ops = [e for e in events if e["line"] == OPS_LINE]
    modules = [e for e in events if e["line"] == MODULES_LINE]
    devices = sorted({e["plane"] for e in ops})
    busy_ns = 0.0
    busy_by_device = {}
    for dev in devices:
        iv = [c for e in ops if e["plane"] == dev
              for c in [_clip(e["start_ns"], e["start_ns"] + e["dur_ns"],
                              lo, hi)] if c]
        busy_by_device[dev] = _union(iv)
        busy_ns += sum(e - s for s, e in busy_by_device[dev])
    busy_ns /= max(len(devices), 1)

    per_program: Dict[str, dict] = {
        role: {"calls": 0, "device_s": 0.0, "kernel_s": 0.0,
               "kernel_calls": 0} for role in programs}
    spans: Dict[str, List[Tuple[float, float, str]]] = {}
    for m in modules:
        if not lo <= m["start_ns"] < hi:
            continue
        for role, key in programs.items():
            if key in m["name"]:
                per_program[role]["calls"] += 1
                per_program[role]["device_s"] += m["dur_ns"] / 1e9
                spans.setdefault(m["plane"], []).append(
                    (m["start_ns"], m["start_ns"] + m["dur_ns"], role))
                break
    starts = {p: [s for s, _, _ in sorted(v)] for p, v in spans.items()}
    spans = {p: sorted(v) for p, v in spans.items()}
    for e in ops:
        if e["plane"] not in spans or not e["kernel"]:
            continue
        mid = e["start_ns"] + e["dur_ns"] / 2
        i = bisect.bisect_right(starts[e["plane"]], mid) - 1
        if i >= 0 and mid < spans[e["plane"]][i][1]:
            role = spans[e["plane"]][i][2]
            per_program[role]["kernel_s"] += e["dur_ns"] / 1e9
            per_program[role]["kernel_calls"] += 1

    by_op: Dict[str, float] = {}
    for e in ops:
        c = _clip(e["start_ns"], e["start_ns"] + e["dur_ns"], lo, hi)
        if c:
            key = _op_group(e["name"])
            by_op[key] = by_op.get(key, 0.0) + (c[1] - c[0]) / 1e9
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]

    host = [e for e in events if e["name"].startswith(SPAN_PREFIX)
            and e["name"] != WINDOW_SPAN]
    gaps = []
    if devices:
        busy = busy_by_device[devices[0]]
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        best, best_overlap = "none", 0.0
        for h in host:
            c = _clip(h["start_ns"], h["start_ns"] + h["dur_ns"], s, e)
            if c and c[1] - c[0] > best_overlap:
                best, best_overlap = h["name"][len(SPAN_PREFIX):], c[1] - c[0]
        labelled.append([best, (e - s) / 1e9])
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9,
            "devices": len(devices), "programs": per_program,
            "device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": labelled}
