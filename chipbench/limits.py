#!/usr/bin/env python3
"""Readings a cell's correctness limit is set from, on the chip.

    python3 chipbench/limits.py --workload nemotron-4-340b.chat \\
        --seeds 11,12,13 --seconds 10

For each seed, in one process: set the cell up, serve a short window at
the cell's own load, then compare the sample a run compares with the
plain reference: the program's served tokens (the lower reading: what
sound runs give), and the tokens that the reference computed in int8
and in fp8 puts first at the same positions (the upper reading: what a
lower precision gives).  One JSON line per seed.  The benchmark's own
runs never run the controls.
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


def readings(cell, seed: int, seconds: float, clock) -> dict:
    import harness
    setup = harness.set_up(cell, seed, clock)
    w = harness.drive(setup, seconds)
    harness.free(setup)
    picked = harness.sample(w, seed, cell.spec["check"]["tokens"])
    res = harness.compare(cell, seed, picked, controls=("int8", "fp8"))
    res["seed"] = seed
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated run seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    import harness
    cell = harness.load_cell(args.workload)
    device = harness.device_info(True, cell.chips)
    harness.enable_cache()
    clock = harness.CompileClock()
    for seed in (int(s) for s in args.seeds.split(",")):
        res = readings(cell, seed, args.seconds, clock)
        res["device"] = device["kind"]
        print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
