#!/usr/bin/env python3
"""Where the engine step's time goes, on the chip: one run of a cell with
the program's tracer on and its wall spans on the profiler's timeline.

    python3 bench/phases.py --workload <name> --seed <n> --seconds <s>

The run is the harness's: the same node, warm-up, traffic, window and
device trace of the window's last seconds.  It differs in one thing: an
enabled ``repro.obs.Tracer(annotate=jax.profiler.TraceAnnotation)`` is
installed around the serving loop, so every ``engine.*`` wall span (one
``engine.step`` a step, a span per phase inside it) is also a host event
of the device trace.  Printed, over the traced seconds:

* the device's idle time split by the innermost host span it falls in
  (``engine.*`` phases inside ``bench.step``), in ms per engine step; the
  parts sum to the window's idle;
* the idle inside ``engine.decode_step`` spans, per span: what the chip
  waits while the host uploads the tables and dispatches the forward;
* the idle gaps the harness's breakdown would list with these spans;
* the offset of the device's clock from the host's: the forward program
  (``jit_paged_decode_step``) runs inside its ``engine.decode_step``
  span, and the sampler's last program ends inside ``engine.sample``,
  since both spans end after a host sync on what those programs made; so
  (program start - span start) and (program end - span end) bound it;
* the split and the forward's idle again, with the device times moved by
  the median (forward program end - span end);

and over the whole window, from the tracer's spans: each phase's mean ms
per step and the step's host time outside its forwards.  The benchmark's
own runs leave the tracer off; this is a diagnostic, run by hand.
"""

from __future__ import annotations

import argparse
import bisect
import os
import shutil
import sys
import tempfile
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import trace as tracemod  # noqa: E402

HOST_PREFIXES = ("bench.", "engine.")
FORWARD = "engine.decode_step"
SYNCED = "engine.sample"           # ends after a host sync on its ops
FORWARDS = ("engine.decode_step", "engine.prefill")
MODULES_LINE = "XLA Modules"
# the device programs the forward span dispatches (named jitted steps)
FORWARD_MODULES = ("jit_paged_decode_step", "jit_decode_step")


@dataclass
class PhaseTrace(tracemod.Trace):
    # device plane name -> [(program name, start ns, end ns)]: the jitted
    # programs the device ran, ``jit_paged_decode_step(<hash>)`` read as
    # ``jit_paged_decode_step``
    modules: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)


def load(path: str) -> PhaseTrace:
    """``bench.trace.load``, keeping the program's ``engine.`` host spans
    beside the benchmark's ``bench.`` ones, and the device's programs."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = PhaseTrace()
    for plane in pd.planes:
        if plane.name.startswith(tracemod.DEVICE_PREFIX):
            ops = tr.ops.setdefault(plane.name, [])
            mods = tr.modules.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == tracemod.OPS_LINE:
                    ops.extend((tracemod.op_name(ev.name), ev.start_ns,
                                ev.start_ns + ev.duration_ns)
                               for ev in line.events)
                elif line.name == MODULES_LINE:
                    mods.extend((ev.name.split("(", 1)[0], ev.start_ns,
                                 ev.start_ns + ev.duration_ns)
                                for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans.extend((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns)
                                for ev in line.events
                                if ev.name.startswith(HOST_PREFIXES))
    return tr


class Busy:
    """Device busy time between two instants, from the union of a
    device's op intervals clipped to the window."""

    def __init__(self, ops: Sequence[Tuple[str, float, float]], lo: float,
                 hi: float) -> None:
        merged = tracemod.union([(a, b) for _, a, b in ops], lo, hi)
        self.starts = [a for a, _ in merged]
        self.ends = [b for _, b in merged]
        self.cum = [0.0]
        for a, b in merged:
            self.cum.append(self.cum[-1] + b - a)

    def upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0:
            return 0.0
        return self.cum[i] + min(t, self.ends[i]) - self.starts[i]

    def between(self, a: float, b: float) -> float:
        return self.upto(b) - self.upto(a)


def _busy(tr: tracemod.Trace) -> Tuple[Tuple[float, float], List[Busy]]:
    win = tracemod.window(tr)
    if win is None or not tr.ops:
        raise ValueError("the trace holds no device operation")
    return win, [Busy(ops, *win) for ops in tr.ops.values()]


def idle_split(tr: tracemod.Trace) -> Dict[str, float]:
    """Idle seconds of the window under each host span label (the
    innermost span, as ``bench.trace.label`` names it), averaged over
    devices.  Gaps are cut at every span edge, so a gap that runs from
    one phase into the next counts in both, and the parts sum to the
    window's idle time."""
    (lo, hi), busy = _busy(tr)
    cuts = sorted({lo, hi} | {min(max(x, lo), hi)
                              for _, a, b in tr.spans for x in (a, b)})
    out: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        if b <= a:
            continue
        idle = sum((b - a) - d.between(a, b) for d in busy) / len(busy)
        if idle > 0:
            name = tracemod.label(tr, (a + b) / 2)
            out[name] = out.get(name, 0.0) + idle / 1e9
    return out


def idle_inside(tr: tracemod.Trace, name: str) -> Tuple[float, int]:
    """(idle seconds inside the window's spans called ``name``, averaged
    over devices; the number of those spans)."""
    (lo, hi), busy = _busy(tr)
    total, n = 0.0, 0
    for s, a, b in tr.spans:
        a, b = max(a, lo), min(b, hi)
        if s != name or b <= a:
            continue
        n += 1
        total += sum((b - a) - d.between(a, b) for d in busy) / len(busy)
    return total / 1e9, n


def _in_window(tr: tracemod.Trace, name: str):
    lo, hi = tracemod.window(tr)
    return [(a, b) for s, a, b in tr.spans
            if s == name and lo <= a and b <= hi]


def sample_skew_ms(tr: PhaseTrace) -> List[float]:
    """For each ``engine.sample`` span in the window: the end of the
    sampler's last program (the last program other than the forward
    that starts before the first forward program starting after the
    span's start) less the span's end, in ms.  The span ends after a host
    sync on the sampled tokens, so in truth this is at most 0: a reading
    above 0 is device time running ahead of the host's."""
    progs = sorted(m for ms in tr.modules.values() for m in ms)
    fwd = [(a, b) for n, a, b in progs if n in FORWARD_MODULES]
    other = [(a, b) for n, a, b in progs if n not in FORWARD_MODULES]
    starts = [a for a, _ in other]
    out = []
    for a, b in _in_window(tr, SYNCED):
        nxt = next((fa for fa, _ in fwd if fa >= a), None)
        if nxt is None:
            continue
        i = bisect.bisect_left(starts, nxt) - 1
        if i >= 0 and other[i][0] >= a - (b - a):
            out.append((other[i][1] - b) / 1e6)
    return out


def forward_skew_ms(tr: PhaseTrace) -> List[Tuple[float, float]]:
    """For each ``engine.decode_step`` span in the window and the forward
    program that overlaps it most: (program start - span start, program
    end - span end) in ms.  The span dispatches the program and ends
    after a host sync on its result, so in truth the first is at least 0
    and the second at most 0: the device clock's offset from the host's
    lies between the second reading and the first."""
    fwd = [(a, b) for ms in tr.modules.values() for n, a, b in ms
           if n in FORWARD_MODULES]
    out = []
    for a, b in _in_window(tr, FORWARD):
        best = max(fwd, key=lambda m: min(m[1], b) - max(m[0], a),
                   default=None)
        if best is not None and min(best[1], b) > max(best[0], a):
            out.append(((best[0] - a) / 1e6, (best[1] - b) / 1e6))
    return out


def shifted(tr: tracemod.Trace, ns: float) -> tracemod.Trace:
    """The trace with every device op moved by ``ns``."""
    return tracemod.Trace(
        ops={p: [(n, a + ns, b + ns) for n, a, b in ops]
             for p, ops in tr.ops.items()},
        spans=tr.spans)


def step_phases(spans: Sequence, lo: float, hi: float
                ) -> Tuple[int, Dict[str, float], float]:
    """From the tracer's wall spans (``repro.obs`` ``Span``) of the steps
    that started in [lo, hi): (steps, mean ms per step of each phase,
    mean ms per step of the step's own host time: ``engine.step`` less
    its forwards)."""
    steps = [s for s in spans if s.name == "engine.step"
             and lo <= s.t0 < hi]
    if not steps:
        return 0, {}, 0.0
    first, last = steps[0].t0, steps[-1].t1
    per: Dict[str, float] = {}
    for s in spans:
        if s.name.startswith("engine.") and first <= s.t0 and s.t1 <= last:
            per[s.name] = per.get(s.name, 0.0) + s.dur
    n = len(steps)
    host = per["engine.step"] - sum(per.get(f, 0.0) for f in FORWARDS)
    return n, {k: 1000.0 * v / n for k, v in sorted(per.items())}, \
        1000.0 * host / n


def _quantiles(xs: List[float]) -> str:
    if not xs:
        return "none"
    xs = sorted(xs)
    q = [xs[min(len(xs) - 1, int(p * len(xs)))] for p in (0.1, 0.5, 0.9)]
    return (f"n {len(xs)}, min {xs[0]}, p10 {q[0]}, median {q[1]}, p90 "
            f"{q[2]}, max {xs[-1]} ms")


def _median(xs: List[float]) -> Optional[float]:
    return sorted(xs)[len(xs) // 2] if xs else None


def _device_part(tr: tracemod.Trace, log, note: str) -> None:
    red = tracemod.reduce(tr)
    wl, wh = tracemod.window(tr)
    starts = sorted(a for s, a, _ in tr.spans
                    if s == "engine.step" and wl <= a < wh)
    # steps the window holds: its length over the mean step period
    steps = (red.window_s * 1e9 * (len(starts) - 1)
             / (starts[-1] - starts[0]) if len(starts) > 1 else 1.0)
    idle = red.window_s - red.busy_s
    log(f"{note}: window {red.window_s} s, busy {red.busy_s} s, idle "
        f"{idle} s ({100 * red.idle_share}%), {steps} engine steps")
    split = idle_split(tr)
    for name, s in sorted(split.items(), key=lambda kv: -kv[1]):
        log(f"  idle under {name}: {s} s, {1000 * s / steps} ms a step")
    log(f"  sum of the parts {sum(split.values())} s against the "
        f"window's idle {idle} s")
    fidle, n = idle_inside(tr, FORWARD)
    log(f"  forward_idle_ms {1000 * fidle / n if n else None} ({n} {FORWARD}"
        f" spans, {fidle} s idle inside them)")
    log("  idle gaps as the breakdown would label them: "
        + ", ".join(f"{n} {s * 1000} ms" for n, s in red.idle_gaps))


def report(tr: PhaseTrace, spans: Sequence, lo: float, hi: float,
           log=print) -> None:
    if tracemod.reduce(tr) is None:
        log("device trace: no device operation in the window")
    else:
        _device_part(tr, log, "traced window, device times as recorded")
        ss, fs = sample_skew_ms(tr), forward_skew_ms(tr)
        log(f"skew, sampler's last program end - {SYNCED} end: "
            f"{_quantiles(ss)}")
        log(f"skew, forward program start - {FORWARD} start: "
            f"{_quantiles([a for a, _ in fs])}")
        log(f"skew, forward program end - {FORWARD} end: "
            f"{_quantiles([b for _, b in fs])}")
        off = _median([b for _, b in fs])
        if off is not None:
            _device_part(shifted(tr, -off * 1e6), log,
                         f"device times moved by {-off} ms (the median "
                         f"forward end skew)")
    n, phase_ms, host = step_phases(spans, lo, hi)
    log(f"window, from the tracer's spans: {n} steps; step_host_ms {host}")
    for name, ms in phase_ms.items():
        log(f"  {name}: {ms} ms a step")


def main(argv=None) -> int:
    import jax
    import numpy as np

    from bench import harness, readings, stats
    from bench.traffic import Traffic
    from repro.obs import Tracer, set_tracer

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(harness.ROOT, args.workload)
    try:
        devices = harness.accelerator(cell.chips)
    except harness.NoAccelerator as e:
        print(f"phases: {e}", file=sys.stderr)
        return 3
    harness.enable_cache(cell.root)
    node = harness.build_node(cell, args.seed, devices[0])
    pump = harness.Pump(node)
    rng = np.random.default_rng(12345)
    harness.warm_shapes(pump, cell.config, cell.mix,
                        lambda n: rng.integers(0, node.sizes.vocab, n,
                                               dtype=np.int64
                                               ).astype(np.int32))
    pump.requests.clear()
    pump.steps.clear()
    traffic = Traffic(cell.mix, args.seed, args.seconds, node.sizes.vocab,
                      node.cfg.eos_id)
    trace_dir = tempfile.mkdtemp(prefix="bench_phases_")
    tracer = Tracer(annotate=jax.profiler.TraceAnnotation)
    old = set_tracer(tracer)
    try:
        lo, hi, end, snap, _ = harness.serve(pump, traffic, args.seconds,
                                             trace_dir)
    finally:
        set_tracer(old)
    try:
        tr = load(tracemod.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = harness.Run(cell=cell, seconds=args.seconds, lo=lo, hi=hi,
                      end=end, requests=list(pump.requests),
                      steps=list(pump.steps),
                      stats=stats.deltas(snap["hi"], snap["lo"]),
                      sizes=node.sizes, setup_s=0.0, preemptions=0,
                      compiles_in_window=0, cache_hits_in_window=0)
    print(f"{cell.name}, seed {args.seed}, tracer on: {len(run.window_steps)}"
          f" window steps; itl_p50_ms "
          f"{readings.ms(stats.percentile(readings.itl_values(run), 50))}; "
          f"output_tok_s {readings.output_tokens_per_s(run)}; decode step "
          f"{readings.ms(readings.decode_step_s(run))} ms")
    print(f"window held: {harness.window_shape(run)}")
    report(tr, tracer.spans, lo, hi)
    return 0


if __name__ == "__main__":
    sys.exit(main())
