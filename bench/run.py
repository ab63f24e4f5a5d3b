#!/usr/bin/env python3
"""One run of one benchmark cell, as ``BENCHMARK.json`` defines it.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs only on a TPU: without one, or with fewer chips than the cell asks
for, it exits non-zero and prints no result. The last line of standard
output is the result, one JSON object; progress and the numbers that
decide ``correct`` go to standard error.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

from lib.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
