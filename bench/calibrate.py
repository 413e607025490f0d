#!/usr/bin/env python3
"""Readings that the output check's limit is set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seconds <s> SEED [SEED ...]

For each seed, one run of the cell at its own load and sizes (no
profiler), then the output check of the served tokens against the float32
reference and the same check of the control, the reference in the
configuration's lower precision (``check.control``, int8 for the cells
here) put in the program's place, over the same requests and held to the
configuration's limit: each line says whether each came out correct.  All
seeds run in one process.  The benchmark's own runs never run the control.
"""

import argparse
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args(argv)
    try:
        harness.calibrate(harness.ROOT, args.workload, args.seeds,
                          args.seconds, log=lambda *a: print(*a, flush=True))
    except harness.NoAccelerator as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
