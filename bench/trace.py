"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device busy
time, idle share, the operations that took most time and the longest
idle gaps, each labelled by what the host was doing.

* Device operations are the events on the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane.  Busy time is the union of their intervals
  inside the window, so overlapping operations count once.
* The window is the host span ``bench.traced`` that the benchmark opens
  around the traced seconds; without it, the first to the last device
  operation.
* Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``
  events (names starting ``bench.``) on the host plane.  An idle gap is
  labelled by the innermost such span that covers its middle.
* Per-op device seconds inside the window are kept for every op, keyed
  by the op's base name (``%paged_decode.10`` reads ``paged_decode``), so
  a kernel's reader finds its time however the compiler numbered it.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"

Interval = Tuple[float, float]          # start, end in ns


@dataclass
class Trace:
    # device plane name -> [(op name, start ns, end ns)]
    ops: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    # [(span name, start ns, end ns)] of the benchmark's host spans
    spans: List[Tuple[str, float, float]] = field(default_factory=list)


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    return paths[-1]


def op_name(name: str) -> str:
    """An XLA op's event name up to its HLO text: ``%while.1 = (...)``
    reads ``%while.1``."""
    return name.split(" = ", 1)[0]


_SUFFIX = re.compile(r"(\.\d+)+$")


def base_name(op: str) -> str:
    """An op's name without the ``%`` and the HLO number: ``%fusion.149``
    and ``%fusion`` read ``fusion``."""
    return _SUFFIX.sub("", op.lstrip("%"))


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = tr.ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((op_name(ev.name), ev.start_ns,
                                ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        tr.spans.append((ev.name, ev.start_ns,
                                         ev.start_ns + ev.duration_ns))
    return tr


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """The union of ``intervals`` clipped to [lo, hi], as sorted disjoint
    intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def window(tr: Trace) -> Optional[Interval]:
    spans = [(a, b) for n, a, b in tr.spans if n == WINDOW_SPAN]
    if spans:
        return min(a for a, _ in spans), max(b for _, b in spans)
    ends = [(a, b) for ops in tr.ops.values() for _, a, b in ops]
    if not ends:
        return None
    return min(a for a, _ in ends), max(b for _, b in ends)


def label(tr: Trace, t: float) -> str:
    """The innermost benchmark span covering time ``t``."""
    best = None
    for n, a, b in tr.spans:
        if n != WINDOW_SPAN and a <= t < b and (best is None
                                                or b - a < best[1]):
            best = (n, b - a)
    return best[0] if best else "outside any bench span"


@dataclass
class Reduced:
    busy_s: float          # device busy seconds, averaged over devices
    window_s: float        # length of the traced window
    top_ops: List[Tuple[str, float]]      # (op name, seconds), summed
    idle_gaps: List[Tuple[str, float]]    # (host span label, seconds)
    # seconds of every op in the window, summed by base_name()
    op_seconds: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(tr: Trace, top: int = 10) -> Optional[Reduced]:
    """Busy seconds, window, top operations and longest idle gaps; None
    when the trace holds no device operation in the window."""
    win = window(tr)
    if win is None or not tr.ops:
        return None
    lo, hi = win
    busy, per_op = [], {}
    gaps: List[Tuple[str, float]] = []
    for ops in tr.ops.values():
        merged = union([(a, b) for _, a, b in ops], lo, hi)
        busy.append(sum(b - a for a, b in merged))
        for n, a, b in ops:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                per_op[n] = per_op.get(n, 0.0) + (b - a)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((label(tr, (a + b) / 2), (b - a) / 1e9))
    if not any(busy):
        return None
    ops_sorted = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    by_base: Dict[str, float] = {}
    for n, s in per_op.items():
        by_base[base_name(n)] = by_base.get(base_name(n), 0.0) + s / 1e9
    return Reduced(
        busy_s=sum(busy) / len(busy) / 1e9,
        window_s=(hi - lo) / 1e9,
        top_ops=[(n, s / 1e9) for n, s in ops_sorted],
        idle_gaps=sorted(gaps, key=lambda g: -g[1])[:top],
        op_seconds=by_base)
