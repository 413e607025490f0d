#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  It refuses to measure unless JAX's first
device is a TPU (and the cell's chips are there): it then exits non-zero
and prints no result.  The last line of standard output is the result as
one JSON object; the numbers the output check compared, each with its
limit, are the last lines of standard error and the ``compared`` key of
the result.  See bench/harness.py.
"""

import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

try:
    import repro.serving  # noqa: F401,E402  the system under test
    from bench import harness  # noqa: E402
except ImportError as e:
    sys.exit(f"bench: cannot import the program or the benchmark ({e}); "
             f"run from the root of a checkout")

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T0))
