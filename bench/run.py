#!/usr/bin/env python3
"""Run one benchmark cell once (see ``isla_bench/harness.py``):

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from isla_bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
