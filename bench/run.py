#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip:

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations and metrics are listed in BENCHMARK.json at the root
of the checkout; see bench/harness.py. Without a TPU (or with fewer chips
than the cell asks for) the run exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t0=T0))
