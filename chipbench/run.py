#!/usr/bin/env python3
"""Run one benchmark cell once, from the root of a checkout:

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result (see ``harness``).  Without
a TPU, or with fewer chips than the cell asks for, it exits 2 and prints
no result.
"""

import time

START = time.time()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(start=START))
