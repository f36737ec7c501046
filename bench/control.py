#!/usr/bin/env python3
"""Readings that set the limits of a cell's comparison.

    python3 bench/control.py --workload <cell> --seconds <s>
        --seeds <n> [<n> ...]

For each seed, in one process: set the cell up as a run does, serve a
short window at the cell's own load, then print one JSON line holding
the numbers the comparison gives for the program (``program``) and for
the control — the plain reference in the nearest lower precision
(float32 for float64 decisions, float8 operands for the bfloat16
model) put in the program's place over the same inputs (``control``).
A limit lies above the program's readings and below the control's.
The benchmark's own runs never run the control.
"""

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import harness  # noqa: E402


def main() -> int:
    """Program and control readings for each seed."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(harness.ROOT, args.workload)
    try:
        devs = harness.find_chips(cell.entry["chips"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.enable_cache(harness.ROOT)
    for seed in args.seeds:
        ctx = harness.Context(False, 0.0)
        drv = cell.driver.Driver(cell, seed, ctx, devs)
        drv.setup()
        drv.window(args.seconds)
        units = drv.record()["units"]
        drv.release()
        t0 = time.perf_counter()
        prog = drv.check()
        t1 = time.perf_counter()
        ctrl = drv.control()
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "units": units, "program": prog, "control": ctrl,
                          "check_s": t1 - t0,
                          "control_s": time.perf_counter() - t1}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
