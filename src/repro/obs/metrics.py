"""Labeled counters (DESIGN.md §Observability).

The unified sink the repo's ad-hoc accumulators feed through: routing
message counts and drop/give-up events from ``core.network``, preemption
and prefix-cache counters from the engines.  Series are identified by a
metric name plus a sorted label set (``counter("net.msg", kind="probe")``),
so one metric fans out into per-kind/per-node series without string
mangling at the call sites.  ``snapshot()`` renders everything as a
JSON-able dict for bench payloads and test assertions.

A counter is deliberately minimal — one float and an ``inc`` — because it
sits on the simulator's hot paths (every routed message); anything
cleverer (rates, windows, distributions) belongs in the consumer.
"""

from __future__ import annotations

from typing import Any, Dict


class Counter:
    """Monotonically increasing count."""

    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        self.value += n


def _series_key(name: str, labels: Dict[str, Any]) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return f"{name}{{{inner}}}"


class MetricsRegistry:
    """A namespace of labeled counters, lazily created on first touch.

    Re-requesting a series with the same name+labels returns the same
    counter, so call sites may cache it or not.
    """

    def __init__(self) -> None:
        self._series: Dict[str, Counter] = {}

    def counter(self, name: str, **labels: Any) -> Counter:
        key = _series_key(name, labels)
        inst = self._series.get(key)
        if inst is None:
            inst = self._series[key] = Counter()
        return inst

    def snapshot(self) -> Dict[str, Any]:
        """Everything recorded so far as a JSON-able dict, keyed by the
        rendered series name (``name{label=value,...}``)."""
        return {"counters": {key: self._series[key].value
                             for key in sorted(self._series)}}

    def value(self, name: str, **labels: Any) -> float:
        """Current value of a counter series (0.0 if never touched) — the
        test-friendly read path."""
        inst = self._series.get(_series_key(name, labels))
        return inst.value if inst is not None else 0.0

    def clear(self) -> None:
        self._series.clear()


_REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-wide default registry; instrumented objects resolve it
    at construction when not handed an explicit one."""
    return _REGISTRY


def set_registry(reg: MetricsRegistry) -> MetricsRegistry:
    """Install ``reg`` as the process-wide default; returns the old one."""
    global _REGISTRY
    old, _REGISTRY = _REGISTRY, reg
    return old
