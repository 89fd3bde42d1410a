#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop cell sustains, on the chip.

    python3 chipbench/sweep.py --workload nemotron-4-340b.chat \\
        --rates 2,4,6,8 --seconds 20 --seed 5

One set-up, then one window per rate.  After each window the engine
steps until every request due in it has its first token (at most
``--drain`` seconds), and then retires whatever it still holds, so the
next rate starts on an empty engine.  A rate is sustained when the queue
does not grow through the window: the median time to first token of the
last quarter of arrivals is under twice that of the first quarter, plus
50 ms.  One JSON line per rate, with every end-to-end number the harness
computes; the cell file then takes 0.8 x the highest rate sustained, as
a number.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))


def reading(w) -> dict:
    import harness
    reqs = sorted(harness.due_in_window(w), key=lambda s: s.due)
    last = max(t.end for t in w.ticks)
    ttft = np.array([((s.token_times[0] if s.token_times else last)
                      - (w.t0 + s.due)) for s in reqs])
    q = max(1, len(reqs) // 4)
    first, final = np.median(ttft[:q]), np.median(ttft[-q:])
    e2e = harness.end_to_end(w, 0.0)
    del e2e["setup_s"]
    return {"requests": len(reqs), **e2e, "steps": len(w.ticks),
            "prefill_steps": sum(1 for t in w.ticks if t.prefill_lengths),
            "ttft_first_quarter_ms": float(first) * 1e3,
            "ttft_last_quarter_ms": float(final) * 1e3,
            "sustained": bool(final < 2 * first + 0.05)}


def empty(engine) -> None:
    """Retire every request the engine still holds (as deadline errors)."""
    for r in list(engine.queue) + list(engine.active.values()):
        if r is not None:
            r.deadline_ticks = 0
    while not engine._idle():
        engine.step()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--drain", type=float, default=15.0)
    args = ap.parse_args()

    import harness
    cell = harness.load_cell(args.workload)
    harness.device_info(True, cell.chips)
    harness.enable_cache()
    clock = harness.CompileClock()
    setup = harness.set_up(cell, args.seed, clock,
                           log=lambda *a: print(*a, file=sys.stderr))
    for rate in (float(r) for r in args.rates.split(",")):
        setup.cell = copy.copy(cell)
        setup.cell.spec = dict(cell.spec, rate_per_s=rate)
        w = harness.drive(setup, args.seconds, drain_s=args.drain,
                          want_tokens=0)
        print(json.dumps({"rate_per_s": rate, **reading(w)}), flush=True)
        empty(setup.engine)
    harness.free(setup)
    return 0


if __name__ == "__main__":
    sys.exit(main())
