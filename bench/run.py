#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON result as the last line of standard output and the numbers
that decide ``correct``, each beside its limit, as the last lines of
standard error.  Exits non-zero, printing no result, where JAX finds no TPU
or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT                      # keep bench/ itself off the path
sys.path.insert(1, os.path.join(ROOT, "src"))

if __name__ == "__main__":
    from bench import harness
    sys.exit(harness.main(sys.argv[1:], T_START))
