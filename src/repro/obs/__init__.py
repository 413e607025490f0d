"""repro.obs — the observability plane (DESIGN.md §Observability).

One home for the three telemetry primitives every layer shares:

* :mod:`repro.obs.tracer` — per-request lifecycle spans (``route.decide``,
  ``executor.queue``/``admit``/``preempt``, ``engine.prefill`` /
  ``decode_step`` / ``spec_verify``, ``disagg.handoff``) and the engine
  step's phase spans, recorded against either the simulator clock or the
  wall clock, cheap no-op when disabled; wall spans can also go onto a
  profiler's timeline through a caller-supplied annotation factory.
* :mod:`repro.obs.metrics` — labeled counters the ad-hoc accumulators
  (``Network.msg_counts``, drop events, preemptions, prefix hit rates)
  feed through, snapshotable as JSON.
* :mod:`repro.obs.export` — Chrome/Perfetto ``trace_event`` JSON writer
  and the plain-text per-request latency-breakdown report.

Instrumented layers (network/node/executor/engine) never touch
``time.perf_counter`` or construct ``Span`` directly — they call
:func:`wall_now` / :meth:`Tracer.wall` / :meth:`Tracer.span`, which is
what the ``obs-lint`` checker (DESIGN.md §7) enforces.
"""

from repro.obs.export import (breakdown_report, latency_breakdown,
                              to_chrome_trace, write_chrome_trace)
from repro.obs.metrics import (Counter, MetricsRegistry, get_registry,
                               set_registry)
from repro.obs.tracer import (SIM, WALL, Span, Tracer, WallSpan, get_tracer,
                              set_tracer, wall_now)

__all__ = [
    "SIM", "WALL", "Span", "Tracer", "WallSpan", "get_tracer", "set_tracer",
    "wall_now",
    "Counter", "MetricsRegistry", "get_registry", "set_registry",
    "to_chrome_trace", "write_chrome_trace", "latency_breakdown",
    "breakdown_report",
]
