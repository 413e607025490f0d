#!/usr/bin/env python3
"""Knee sweep of an open-loop cell on the chip: the same node served at
several fixed rates, one after another, in one process.

    python3 bench/sweep.py --workload <name> --seconds 30 --warm 10 R1 R2 ...

For each rate: the cell's mix at that rate (its warm segment shortened to
``--warm``), then the window; printed: requests due, TTFT p50/p90, ITL
p95, output tokens/s and the backlog (requests due and not yet admitted)
at the window's start and end and its largest value.  The knee is the
highest rate whose backlog does not grow over the window; a cell offers
0.8 of it.  Rates run in the order given, each straight after the last:
requests the last one never admitted are dropped, the admitted ones run on
into the next rate's warm segment.
"""

import argparse
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import numpy as np  # noqa: E402

from bench import harness, readings, stats  # noqa: E402
from bench.traffic import Traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--warm", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=20260)
    ap.add_argument("rates", type=float, nargs="+")
    args = ap.parse_args(argv)
    cell = harness.load_cell(harness.ROOT, args.workload)
    try:
        devices = harness.accelerator(cell.chips)
    except harness.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    harness.enable_cache(cell.root)
    node = harness.build_node(cell, args.seed, devices[0])
    pump = harness.Pump(node)
    rng = np.random.default_rng(5)
    harness.warm_shapes(pump, cell.config, cell.mix,
                        lambda n: rng.integers(0, node.sizes.vocab, n,
                                               dtype=np.int64
                                               ).astype(np.int32))
    for rate in args.rates:
        mix = dict(cell.mix, rate_per_s=rate, warm_s=args.warm,
                   steady_start=0)
        traffic = Traffic(mix, args.seed + int(rate * 1000), args.seconds,
                          node.sizes.vocab, node.cfg.eos_id)
        pump.requests.clear()
        pump.steps.clear()
        backlog = []
        lo, hi, end, snap, _ = harness.serve(pump, traffic, args.seconds,
                                             grace_s=0.0, backlog=backlog)
        run = harness.Run(cell=cell, seconds=args.seconds, lo=lo, hi=hi,
                          end=end, requests=list(pump.requests),
                          steps=list(pump.steps),
                          stats=stats.deltas(snap["hi"], snap["lo"]),
                          sizes=node.sizes, setup_s=0.0, preemptions=0,
                          compiles_in_window=0, cache_hits_in_window=0)
        inside = [b for t, b in backlog if lo <= t < hi]
        first = next((b for t, b in backlog if t >= lo), 0)
        last = inside[-1] if inside else 0
        ttft = readings.ttft_values(run)
        print(f"rate {rate}: {len(run.due_in_window())} due; ttft p50 "
              f"{stats.percentile(ttft, 50)} p90 {stats.percentile(ttft, 90)}"
              f"; itl p95 {stats.percentile(readings.itl_values(run), 95)}; "
              f"output tok/s {readings.output_tokens_per_s(run)}; occupancy "
              f"{readings.occupancy(run)}; decode step "
              f"{readings.decode_step_s(run)}; backlog start {first} end "
              f"{last} max {max(inside, default=0)}", flush=True)
        pump.holding.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
