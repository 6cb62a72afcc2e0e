"""Run one cell of the cascade server's chip benchmark.

    python3 bench/run.py --workload stage0.chat --seed 7 --seconds 40 --trace 0

From the root of a checkout, on a machine with the chips the cell asks
for.  The last line of standard output is the result as one JSON object;
the numbers that decided ``correct`` are the last lines of standard
error.  Without a TPU, or with fewer chips than the cell needs, it exits
non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T0))
