"""Percentiles and the request-timeline arithmetic of the end-to-end
metrics.  Pure functions of numbers, so a test can check them on a
timeline built by hand."""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile by nearest rank (the smallest value with at
    least ``q`` percent of the values at or below it); None when empty."""
    xs = sorted(values)
    if not xs:
        return None
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]


def ttfts(due: Sequence[float], first: Sequence[Optional[float]],
          end: float) -> List[float]:
    """Time to first token of each request, from its due time.  A request
    that has no first token (it failed or never finished its prefill) by
    ``end`` counts as missing: its time is at least ``end - due``, and that
    lower bound is what it contributes."""
    return [(f if f is not None else end) - d for d, f in zip(due, first)]


def inter_token_gaps(emits: Iterable[Sequence[float]], lo: float,
                     hi: float) -> List[float]:
    """Gaps between consecutive tokens of each request whose later token
    was emitted in ``[lo, hi)``."""
    out: List[float] = []
    for ts in emits:
        for a, b in zip(ts, ts[1:]):
            if lo <= b < hi:
                out.append(b - a)
    return out


def tokens_in(emits: Iterable[Sequence[float]], lo: float, hi: float) -> int:
    """Tokens emitted in ``[lo, hi)`` over all requests."""
    return sum(1 for ts in emits for t in ts if lo <= t < hi)


def deltas(after: Dict[str, float], before: Dict[str, float]
           ) -> Dict[str, float]:
    return {k: after[k] - before.get(k, 0) for k in after}
