#!/usr/bin/env python3
"""Where the served path spends device time and device idle time, by the
program's own names: the named scopes on its device ops (``<site>/plan``,
``<site>/kernel``, ``<site>/xla``, ``attn.kv``) and its ``engine.*`` host
spans over the device's idle gaps.

It extends :mod:`tracefile` and changes nothing that module returns:

* :func:`extract` gives tracefile's events plus, on each device op, its
  ``scope`` (the op's ``op_name`` path), and the program's ``engine.*``
  host spans; :func:`tracefile.reduce` reads these events as it reads
  its own.  A TPU trace names each op by its instruction text without
  metadata (its stats hold only times), so the scope comes from the
  compiled module that ran it, which the profiler stores in the trace
  (:func:`hlo_scopes`);
* :func:`reduce` gives ``scopes`` (device seconds per program role and
  ``<site>/<part>`` of the innermost ops, and the unscoped remainder of
  the role's device time), ``idle_by_phase`` (device-idle seconds in
  the window by the harness span and the innermost engine span over
  them, such as ``step/engine.push_table``) and ``idle_gaps``
  (tracefile's ten longest idle gaps, labelled the same way).

    python3 chipbench/phases.py --workload nemotron-4-340b.chat \\
        --seed 7 --seconds 51 --out phases.json

runs one traced window of a cell on the chip (set-up and traffic as
``run.py``, no reference check) and prints both reductions, the share of
each program's device time that scopes name, and the window's end-to-end
and per-layer numbers as one JSON line.  ``--slice PATH`` also
writes the events from the window's first prefill to the end of the
second decode step after it, for the tests.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterator, List, Optional, Tuple

import tracefile

ENGINE_PREFIX = "engine."
PARTS = ("plan", "kernel", "xla")
# the scope of the paged-cache gather and operand build of decode
# attention, beside the program's kernel sites (program.json)
CACHE_SCOPE = "attn.kv"
# where the profiler stores each compiled module it saw (an HloProto)
HLO_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
_PROGRAM = re.compile(r"^jit\([^/]*\)/")


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a protobuf message's wire form: an int
    for a varint, a memoryview for a length-delimited or fixed field."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _ints(values) -> List[int]:
    """A repeated integer field, packed or not."""
    out: List[int] = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
        else:
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


def _one(buf, wanted) -> Dict[int, list]:
    """The fields of a message numbered in ``wanted``, each as a list."""
    out: Dict[int, list] = {f: [] for f in wanted}
    for f, v in _fields(buf):
        if f in out:
            out[f].append(v)
    return out


def module_scopes(hlo_proto) -> Dict[str, str]:
    """Instruction name → scope (its ``op_name`` below the program's own
    ``jit(<program>)/``) of one compiled module, from its serialized
    ``HloProto``.  The TPU compiler drops the metadata of many
    instructions it makes (layout copies, the fusions and sorts a large
    scatter becomes): such an instruction takes the scope of the ops it
    calls, else of its nearest named neighbour along the data flow,
    producers before users.  So does one whose ``op_name`` lies outside
    the program's ``jit(...)``: a parameter's is its argument's path,
    and some lowerings name an op by its primitive alone
    (``reduce_window_sum`` inside a cumsum)."""
    module = _one(hlo_proto, (1,))[1][0]                    # hlo_module
    rows = []                   # (key, name, own scope, operands, calls)
    for comp in _one(module, (3,))[3]:                      # computations
        c = _one(comp, (2, 5))                              # instructions, id
        cid = c[5][0] if c[5] else None
        for ins in c[2]:
            f = _one(ins, (1, 7, 35, 36, 38))
            op = _one(f[7][0], (2,))[2] if f[7] else []     # op_name
            op = bytes(op[0]).decode() if op else ""
            scope = _PROGRAM.sub("", op) if _PROGRAM.match(op) else ""
            # instruction ids are unique within a computation
            rows.append(((cid, f[35][0] if f[35] else None),
                         bytes(f[1][0]).decode(), scope,
                         [(cid, o) for o in _ints(f[36])], _ints(f[38])))
    inside: Dict[int, str] = {}         # computation id -> a scope in it
    for (cid, _), _, scope, _, _ in reversed(rows):         # root last
        if scope:
            inside.setdefault(cid, scope)
    scope_of = {key: scope or next((inside[c] for c in calls
                                    if c in inside), "")
                for key, _, scope, _, calls in rows}
    users: Dict[tuple, list] = {}
    for key, _, _, operands, _ in rows:
        for o in operands:
            users.setdefault(o, []).append(key)
    todo = [(key, operands) for key, _, _, operands, _ in rows
            if not scope_of[key]]
    while todo:                 # one step along the data flow a round
        found = {}
        for key, operands in todo:
            s = next((scope_of[o] for o in operands
                      if scope_of.get(o)), "") or next(
                (scope_of[u] for u in users.get(key, ())
                 if scope_of[u]), "")
            if s:
                found[key] = s
        if not found:
            break
        scope_of.update(found)
        todo = [t for t in todo if t[0] not in found]
    return {name: scope_of[key] for key, name, _, _, _ in rows}


def hlo_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """Module name as its ``XLA Modules`` events give it
    (``jit__decode_impl(<program id>)``) → :func:`module_scopes` of the
    compiled module the profiler stored with the trace: the executable
    that ran, whatever compiled it."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for plane in _one(space, (1,))[1]:                      # planes
        p = _one(plane, (2, 4, 5))          # name, event/stat metadata
        if not p[2] or bytes(p[2][0]).decode() != HLO_PLANE:
            continue
        stat_ids = set()
        for entry in p[5]:
            meta = _one(_one(entry, (2,))[2][0], (1, 2))
            if meta[2] and bytes(meta[2][0]).decode() == HLO_STAT:
                stat_ids.add(meta[1][0] if meta[1] else 0)
        for entry in p[4]:
            meta = _one(_one(entry, (2,))[2][0], (2, 5))    # name, stats
            for stat in meta[5]:
                st = _one(stat, (1, 6))                     # id, bytes
                if st[6] and (st[1][0] if st[1] else 0) in stat_ids:
                    out[bytes(meta[2][0]).decode()] = module_scopes(
                        st[6][0])
    return out


def _holder(events: List[dict], lo: float = float("-inf"),
            hi: float = float("inf")):
    """A function giving the module (an ``XLA Modules`` event started in
    [lo, hi)) that holds an op's midpoint, or None."""
    mods: Dict[str, List[dict]] = {}
    for m in events:
        if m["line"] == tracefile.MODULES_LINE and lo <= m["start_ns"] < hi:
            mods.setdefault(m["plane"], []).append(m)
    for v in mods.values():
        v.sort(key=lambda m: m["start_ns"])
    starts = {p: [m["start_ns"] for m in v] for p, v in mods.items()}

    def holder(e: dict) -> Optional[dict]:
        mid = e["start_ns"] + e["dur_ns"] / 2
        i = bisect.bisect_right(starts.get(e["plane"], []), mid) - 1
        if i >= 0 and mid < (mods[e["plane"]][i]["start_ns"]
                             + mods[e["plane"]][i]["dur_ns"]):
            return mods[e["plane"]][i]
        return None
    return holder


def name_ops(events: List[dict],
             scopes: Dict[str, Dict[str, str]]) -> None:
    """Give each device op its ``scope`` from the module that ran it
    (``scopes``: :func:`hlo_scopes`); "" where none is known."""
    holder = _holder(events)
    for e in events:
        if e["line"] == tracefile.OPS_LINE:
            m = holder(e)
            e["scope"] = scopes.get(m["name"], {}).get(e["name"], "") \
                if m is not None else ""


def extract(path: str, kernel_target: str) -> List[dict]:
    """tracefile's events of an ``.xplane.pb``, each device op with its
    ``scope`` (:func:`name_ops`), and the ``engine.*`` host spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    marker = f'custom_call_target="{kernel_target}"'
    events: List[dict] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (tracefile.OPS_LINE,
                                            tracefile.MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if not device and not name.startswith(
                        (tracefile.SPAN_PREFIX, ENGINE_PREFIX)):
                    continue
                events.append({"plane": plane.name, "line": line.name,
                               "name": name.split(" = ")[0].lstrip("%"),
                               "kernel": device and marker in name,
                               "start_ns": float(ev.start_ns),
                               "dur_ns": float(ev.duration_ns)})
    name_ops(events, hlo_scopes(path))
    return events


def site_part(scope: str, sites) -> Tuple[Optional[str], str]:
    """(site, part) of an op's scope path: the innermost segment that
    names a site, and the segment under it when that is one of
    :data:`PARTS`, else ``other``; (None, "") outside every site."""
    segs = scope.split("/")
    for i in range(len(segs) - 1, -1, -1):
        if segs[i] in sites:
            below = segs[i + 1] if i + 1 < len(segs) else ""
            return segs[i], below if below in PARTS else "other"
    return None, ""


def _window(events: List[dict], window) -> Tuple[float, float]:
    if window is not None:
        return window
    spans = [e for e in events if e["name"] == tracefile.WINDOW_SPAN]
    if not spans:
        raise ValueError("trace has no chipbench.window span")
    return spans[0]["start_ns"], spans[0]["start_ns"] + spans[0]["dur_ns"]


def _leaves(ops: List[dict]) -> List[dict]:
    """The ops no other op of their device runs inside (a ``while`` op
    spans the ops of its body)."""
    by_plane: Dict[str, List[dict]] = {}
    for e in ops:
        by_plane.setdefault(e["plane"], []).append(e)
    out: List[dict] = []
    for group in by_plane.values():
        group.sort(key=lambda e: (e["start_ns"], -e["dur_ns"]))
        stack: List[list] = []              # [op, runs another inside]
        for e in group:
            while stack and e["start_ns"] >= (stack[-1][0]["start_ns"]
                                              + stack[-1][0]["dur_ns"]):
                top, parent = stack.pop()
                if not parent:
                    out.append(top)
            if stack:
                stack[-1][1] = True
            stack.append([e, False])
        out.extend(top for top, parent in stack if not parent)
    return out


def _scopes(events, programs, sites, lo, hi) -> Dict[str, dict]:
    """Device seconds of each role's innermost ops by ``<site>/<part>``,
    and of its ten longest ops outside every site by name and scope; an
    op belongs to the program module (started in the window) that holds
    its midpoint, as tracefile counts kernel time."""
    out = {role: {"device_s": 0.0, "sites": {}, "unscoped_s": 0.0,
                  "unscoped_ops": {}} for role in programs}

    def role_of(m):
        return next((r for r, key in programs.items()
                     if key in m["name"]), None)
    for m in events:
        if m["line"] == tracefile.MODULES_LINE and lo <= m["start_ns"] < hi \
                and role_of(m):
            out[role_of(m)]["device_s"] += m["dur_ns"] / 1e9
    holder = _holder(events, lo, hi)
    for e in _leaves([e for e in events
                      if e["line"] == tracefile.OPS_LINE]):
        m = holder(e)
        site, part = site_part(e.get("scope", ""), sites)
        if m is None or not role_of(m):
            continue
        r = out[role_of(m)]
        table, key = ((r["sites"], f"{site}/{part}") if site is not None
                      else (r["unscoped_ops"],
                            f"{e['name']} [{e.get('scope', '')}]"))
        table[key] = table.get(key, 0.0) + e["dur_ns"] / 1e9
    for r in out.values():
        r["unscoped_s"] = r["device_s"] - sum(r["sites"].values())
        r["unscoped_ops"] = dict(sorted(r["unscoped_ops"].items(),
                                        key=lambda kv: -kv[1])[:10])
    return out


def _host_timeline(events, lo, hi) -> List[Tuple[float, float, str]]:
    """What the host was in over [lo, hi), piece by piece: the harness
    span (``chipbench.*`` without its prefix) and the innermost
    ``engine.*`` span open there, as ``step/engine.decode``, ``wait``,
    ``engine.sync`` or ``none``."""
    spans = [(e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
             for e in events if e["line"] not in (tracefile.OPS_LINE,
                                                  tracefile.MODULES_LINE)
             and e["name"] != tracefile.WINDOW_SPAN
             and e["name"].startswith((tracefile.SPAN_PREFIX,
                                       ENGINE_PREFIX))]
    marks = sorted([(max(s, lo), 1, i) for i, (s, e, _) in enumerate(spans)
                    if e > lo and s < hi]
                   + [(min(e, hi), 0, i) for i, (s, e, _)
                      in enumerate(spans) if e > lo and s < hi])
    pieces: List[Tuple[float, float, str]] = []
    open_: Dict[int, Tuple[float, float, str]] = {}
    t = lo
    for when, opening, i in marks + [(hi, 0, -1)]:
        if when > t:
            pieces.append((t, when, _label(open_.values())))
            t = when
        if i < 0:
            break
        if opening:
            open_[i] = spans[i]
        else:
            open_.pop(i, None)
    return pieces


def _label(open_spans) -> str:
    def innermost(prefix):
        inside = [s for s in open_spans if s[2].startswith(prefix)]
        # spans of one thread nest: the innermost started last
        return max(inside, key=lambda s: (s[0], -s[1]))[2] if inside \
            else None
    outer = innermost(tracefile.SPAN_PREFIX)
    inner = innermost(ENGINE_PREFIX)
    parts = [outer[len(tracefile.SPAN_PREFIX):] if outer else None, inner]
    return "/".join(p for p in parts if p) or "none"


def _overlaps(pieces, starts, s, e):
    """(label, ns) of the timeline pieces over [s, e)."""
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    while i < len(pieces) and pieces[i][0] < e:
        c = tracefile._clip(pieces[i][0], pieces[i][1], s, e)
        if c:
            yield pieces[i][2], c[1] - c[0]
        i += 1


def reduce(events: List[dict], programs: Dict[str, str], sites,
           window: Optional[Tuple[float, float]] = None) -> dict:
    """``scopes``, ``idle_by_phase`` and labelled ``idle_gaps`` of one
    traced window (``sites``: the scope names that are sites)."""
    lo, hi = _window(events, window)
    sites = set(sites)
    ops = [e for e in events if e["line"] == tracefile.OPS_LINE]
    devices = sorted({e["plane"] for e in ops})
    gaps: List[Tuple[float, float]] = []
    if devices:
        busy = tracefile._union(
            c for e in ops if e["plane"] == devices[0]
            for c in [tracefile._clip(e["start_ns"],
                                      e["start_ns"] + e["dur_ns"], lo, hi)]
            if c)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    pieces = _host_timeline(events, lo, hi)
    starts = [p[0] for p in pieces]
    by_phase: Dict[str, float] = {}
    for s, e in gaps:
        for label, ns in _overlaps(pieces, starts, s, e):
            by_phase[label] = by_phase.get(label, 0.0) + ns / 1e9
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        share: Dict[str, float] = {}
        for label, ns in _overlaps(pieces, starts, s, e):
            share[label] = share.get(label, 0.0) + ns
        labelled.append([max(share, key=share.get) if share else "none",
                         (e - s) / 1e9])
    return {"scopes": _scopes(events, programs, sites, lo, hi),
            "idle_by_phase": dict(sorted(by_phase.items(),
                                         key=lambda kv: -kv[1])),
            "idle_gaps": labelled}


def plan_s(scopes: dict, role: str) -> float:
    """Device seconds under every site's ``plan`` scope in one role."""
    return sum(v for k, v in scopes[role]["sites"].items()
               if k.endswith("/plan"))


def step_idle_s(idle_by_phase: Dict[str, float]) -> float:
    """Device-idle seconds while the host was inside an engine span."""
    return sum(v for k, v in idle_by_phase.items()
               if k.split("/")[-1].startswith(ENGINE_PREFIX))


def trace_slice(events: List[dict], programs: Dict[str, str],
                decodes: int = 2, margin_ns: float = 1e6) -> List[dict]:
    """The events from the window's first prefill module to the end of
    the ``decodes``-th decode module after it, under a window span of
    their own."""
    lo, hi = _window(events, None)
    mods = sorted((e for e in events
                   if e["line"] == tracefile.MODULES_LINE
                   and lo <= e["start_ns"] < hi),
                  key=lambda e: e["start_ns"])
    first = next(i for i, m in enumerate(mods)
                 if programs["prefill"] in m["name"])
    ends = [m["start_ns"] + m["dur_ns"] for m in mods[first:]
            if programs["decode"] in m["name"]]
    a = mods[first]["start_ns"] - margin_ns
    b = ends[decodes - 1] + margin_ns
    out = [e for e in events if e["name"] != tracefile.WINDOW_SPAN
           and e["start_ns"] < b and e["start_ns"] + e["dur_ns"] > a]
    return [{"plane": "/host:CPU", "line": "python",
             "name": tracefile.WINDOW_SPAN, "kernel": False,
             "start_ns": a, "dur_ns": b - a}] + out


def scoped_share(scopes: dict, role: str) -> Optional[float]:
    """Share (%) of a role's device time that its ops' scopes name."""
    r = scopes[role]
    return (100.0 * (1.0 - r["unscoped_s"] / r["device_s"])
            if r["device_s"] else None)


def main() -> int:
    import argparse
    import gzip
    import json
    import pathlib
    import sys
    import time

    started = time.perf_counter()
    here = pathlib.Path(__file__).resolve().parent
    sys.path.insert(0, str(here.parent / "src"))
    import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--slice", default=None)
    args = ap.parse_args()

    def log(*a):
        print(*a, file=sys.stderr, flush=True)

    cell = harness.load_cell(args.workload)
    device = harness.device_info(True, cell.chips)
    harness.enable_cache()
    clock = harness.CompileClock()
    setup = harness.set_up(cell, args.seed, clock, log=log)
    handle = tracefile.start()
    setup_s = time.perf_counter() - started
    w = harness.drive(setup, args.seconds, trace_handle=handle)
    e2e = harness.end_to_end(w, setup_s)
    prog = json.loads((here / "program.json").read_text())
    events = extract(handle["path"], prog["kernel_custom_call_target"])
    tracefile.discard(handle)
    sites = {s for v in prog["kernel_sites"].values() for s in v}
    sites.add(CACHE_SCOPE)
    base = tracefile.reduce(events, prog["programs"])
    mine = reduce(events, prog["programs"], sites)
    # the cell's per-layer metrics, read as run.py reads them
    ctx = harness.Context(
        cell=cell, window=w, trace=base, kept=setup.kept,
        tile=tuple(cell.config["sparsity"]["tile"]),
        peaks=harness.peaks(device["kind"]), program=prog)
    metrics = {m["name"]: harness.metric_reader(m["name"])(ctx)
               for m in cell.per_layer}
    decode_calls = base["programs"]["decode"]["calls"]
    prefill_s = base["programs"]["prefill"]["device_s"]
    out = {"workload": args.workload, "seed": args.seed,
           "device": device, "end_to_end": e2e, "metrics": metrics,
           "window_s": base["window_s"], "busy_s": base["busy_s"],
           "programs": base["programs"], **mine,
           "scoped_share": {role: scoped_share(mine["scopes"], role)
                            for role in mine["scopes"]},
           "decode_plan_ms": (plan_s(mine["scopes"], "decode")
                              / decode_calls * 1e3
                              if decode_calls else None),
           "prefill_plan_share": (100.0 * plan_s(mine["scopes"],
                                                 "prefill") / prefill_s
                                  if prefill_s else None),
           "step_idle_ms": (step_idle_s(mine["idle_by_phase"])
                            / decode_calls * 1e3
                            if decode_calls else None),
           "compile_events": w.compile_events}
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    if args.slice:
        with gzip.open(args.slice, "wt") as f:
            json.dump(trace_slice(events, prog["programs"]), f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
