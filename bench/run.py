#!/usr/bin/env python3
"""The benchmark's one command; run it from the root of a checkout.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

It prints the result as one JSON object, the last line of standard
output, and every number its comparison checked beside its limit as the
last lines of standard error.  Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The checkout's root (for the ``bench`` package) and the program's
# sources, in place of this script's own directory.
sys.path[0:1] = [_ROOT, os.path.join(_ROOT, "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
