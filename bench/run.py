#!/usr/bin/env python3
"""Runs one benchmark cell once, on the chips of the machine it starts on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, traffic mixes and metrics are listed in
BENCHMARK.json at the checkout's root.  The last line of standard output
is the result, one JSON object; the numbers compared with the plain
reference are the last lines of standard error, each beside its limit.
Without a TPU, with fewer chips than the cell asks for, or without the
program beside it, the run exits non-zero and prints no result.
"""
import os
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import harness
    sys.exit(harness.main(t_start=T_START))
