"""Run one benchmark cell once and print its result as the last stdout line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names come from ``BENCHMARK.json`` at the repository root; the
system under test is imported from ``src/``.  Exits non-zero, with no
result line, when JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
