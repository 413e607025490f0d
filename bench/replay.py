#!/usr/bin/env python3
"""Host replay of a closed-loop mix over many schedule seeds, to choose
the one a cell serves.

    python3 bench/replay.py --mix batch --first 3000 --count 400

Touches no device.  It replays the closed loop as the engine serves it
with ``max_pending_tokens`` 0: one queued request at a time, a prefill
into a free row, one token per resident row per step, a finished row's
client sending its next request at once.  Each step's decode block table
is as wide as its longest row's pages, rounded up to a power of two.  Step
times are a model (``--decode-ms``, per table width in pages; host work;
a prefill), so the tokens/s it prints only rank schedules; the widths and
contexts follow from the lengths alone.  It prints, for the schedule at
the median tokens/s (the lowest such seed), the window's share of steps
by table width and its longest context.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
from typing import Dict, Tuple

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT]

from bench.traffic import Traffic  # noqa: E402


def replay(mix: Dict, schedule: int, seconds: float, decode_s: Dict[int, float],
           host_s: float, prefill_s: float, rows: int = 32,
           page_size: int = 16) -> Tuple[Dict[int, float], int, float]:
    """(share of window steps by table width, longest context in the
    window, tokens/s) of one schedule seed."""
    traffic = Traffic(dict(mix, schedule_seed=schedule), 0, seconds, 2, 1)
    holding = collections.deque(traffic.first())
    queued: list = []
    resident: list = []                     # [prompt, budget, emitted, client]
    lo = float(mix.get("warm_s", 0.0))
    hi = lo + seconds
    t, tokens, longest = 0.0, 0, 0
    widths: collections.Counter = collections.Counter()

    def admit() -> int:
        if queued and len(resident) < rows:
            it = queued.pop()
            resident.append([it.prompt_len, it.max_new, 0, it.client])
            return 1
        return 0

    while t < hi:
        if not queued and holding:
            queued.append(holding.popleft())
        prefills = admit()
        for r in resident:
            r[2] += 1
        done = [r for r in resident if r[2] >= r[1]]
        for r in done:
            resident.remove(r)
        if done:
            prefills += admit()
        need = max(((r[0] + r[2] - 1) // page_size + 1 for r in resident),
                   default=1)
        width = 1 << (need - 1).bit_length()
        t += (decode_s[min(k for k in decode_s if k >= width)] + host_s
              + prefill_s * prefills)
        if lo <= t < hi:
            widths[width] += 1
            tokens += len(resident) + len(done)
            longest = max([longest] + [r[0] + r[2] for r in resident])
        for r in done:
            holding.append(traffic.next_for(r[3], t))
    n = sum(widths.values()) or 1
    return ({w: c / n for w, c in sorted(widths.items())}, longest,
            tokens / seconds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mix", required=True)
    ap.add_argument("--first", type=int, default=3000)
    ap.add_argument("--count", type=int, default=400)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--decode-ms", default="128:51,256:105,512:200,1024:380",
                    help="decode step time by table width, width:ms,...")
    ap.add_argument("--host-ms", type=float, default=14.0)
    ap.add_argument("--prefill-ms", type=float, default=250.0)
    args = ap.parse_args(argv)
    with open(os.path.join(_ROOT, "bench", "traffic", f"{args.mix}.json")) as f:
        mix = json.load(f)
    decode_s = {int(w): float(ms) / 1000.0 for w, ms in
                (kv.split(":") for kv in args.decode_ms.split(","))}
    out = [(replay(mix, s, args.seconds, decode_s, args.host_ms / 1000.0,
                   args.prefill_ms / 1000.0), s)
           for s in range(args.first, args.first + args.count)]
    rates = sorted(r[2] for r, _ in out)
    median = rates[len(rates) // 2]
    (widths, longest, rate), seed = min((o for o in out if o[0][2] == median),
                                        key=lambda o: o[1])
    mixes = collections.Counter(tuple(r[0]) for r, _ in out)
    print(f"{len(out)} schedules; tokens/s (model) quartiles "
          f"{[round(rates[int(q * (len(rates) - 1))], 1) for q in (0, .25, .5, .75, 1)]}")
    print(f"window table widths (pages) and how many schedules: "
          f"{dict(mixes.most_common(4))}")
    print(f"median schedule_seed {seed}: steps by width "
          f"{ {w: round(s, 3) for w, s in widths.items()} }, longest context "
          f"{longest}, {rate:.1f} tokens/s (model)")
    rate_of = {s: r[2] for r, s in out}
    mine = mix.get("schedule_seed")
    if mine in rate_of:
        rank = sum(1 for x in rates if x < rate_of[mine]) / len(rates)
        print(f"the mix's schedule_seed {mine}: percentile {rank:.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
