#!/usr/bin/env python3
"""Benchmark entry point (see bench/harness.py):

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The last line of standard output is one
JSON object; a run that cannot measure (no TPU, an unknown device kind,
fewer chips than the cell needs, no program beside the benchmark) exits
non-zero and prints no result."""
import time

START = time.perf_counter()   # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], START))
