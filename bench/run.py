#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the run's result as one JSON object;
the numbers compared with the reference, each beside its limit, are the
last lines of standard error. ``--rehearse`` runs the cell at its
configuration's rehearsal size on any backend and prints no result.
"""
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T0))
