#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload nemotron-4-340b.chat --seed 7 \\
        --seconds 30 --trace 0

The cell is an entry of ``workloads`` in BENCHMARK.json; everything it
needs is found by name under ``chipbench/``.  ``--trace 0`` prints the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics read from
a profile of the window.  Without a TPU, or with fewer chips than the
cell asks for, the run fails before any work and prints no result.
The last lines on standard error, and the ``checks`` key of the result,
give each number compared with its limit.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    import harness
    cell = harness.load_cell(args.workload)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         started=STARTED,
                         log=lambda *a: print(*a, file=sys.stderr,
                                              flush=True))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
