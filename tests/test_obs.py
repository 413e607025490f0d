"""repro.obs: tracer, metrics registry, exporters, latency partition.

Four layers:

1.  ``Tracer``/``WallSpan`` unit tests — a disabled tracer is a no-op,
    ``Tracer.wall`` ALWAYS measures (the ``EngineStats`` accumulators
    depend on ``dt`` with tracing off) but only records when enabled,
    and only an enabled tracer opens the caller's profiler annotation.
2.  ``MetricsRegistry`` unit tests — labeled series, snapshot shape —
    plus the ``core.network`` event accounting
    (satellite of DESIGN.md §Observability): queued-request drops feed
    both ``msg_counts["dropped"]`` and the labeled registry counter.
3.  Export tests — Chrome ``trace_event`` structure (two clock-domain
    processes, complete vs instant phases) and the latency breakdown.
4.  The end-to-end partition: a traced sim run's merged per-request
    sim spans reconstruct ``CompletedRequest.latency`` (the ``--trace``
    acceptance invariant), plus the ``MetricsCollector`` aggregate
    regressions that rode along with this plane.

Note: ``Span`` is deliberately never constructed here — the
``obs-lint/span-construction`` rule covers tests/ too, so spans are made
the idiomatic way, through the ``Tracer`` recording API.
"""

import json

import pytest

from repro.obs import (SIM, WALL, MetricsRegistry, Tracer,
                       breakdown_report, get_registry, get_tracer,
                       latency_breakdown, set_registry, set_tracer,
                       to_chrome_trace, wall_now, write_chrome_trace)
from repro.sim.metrics import CompletedRequest, MetricsCollector


# ---------------------------------------------------------------------------
# 1. tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_disabled_tracer_records_nothing(self):
        tr = Tracer(enabled=False)
        tr.span("route.decide", "r1", "n0", 0.0, 1.0)
        tr.event("executor.admit", "r1", "n0", 1.0)
        with tr.wall("engine.decode_step", who="n0"):
            pass
        assert tr.spans == []

    def test_enabled_tracer_records_spans_and_events(self):
        tr = Tracer()
        tr.span("route.decide", "r1", "n0", 0.5, 1.5, mode="gossip",
                target="n2")
        tr.event("executor.admit", "r1", "n2", 1.5, active=3)
        a, b = tr.spans
        assert (a.name, a.rid, a.who, a.t0, a.t1) == \
            ("route.decide", "r1", "n0", 0.5, 1.5)
        assert a.clock == SIM and a.attrs["target"] == "n2"
        assert a.dur == 1.0
        assert b.t0 == b.t1 == 1.5 and b.attrs == {"active": 3}

    def test_wall_span_always_measures_records_only_when_enabled(self):
        # dt must be a real measurement even with tracing off: the
        # serving layer's EngineStats accumulators are fed from it
        for enabled in (False, True):
            tr = Tracer(enabled=enabled)
            with tr.wall("engine.prefill", who="node1", rows=2) as sp:
                x = sum(range(1000))
            assert x == 499500
            assert sp.dt > 0.0
            if enabled:
                (s,) = tr.spans
                assert s.clock == WALL and s.name == "engine.prefill"
                assert (s.t0, s.t1) == (sp.t0, sp.t1)
                assert s.attrs == {"rows": 2}
            else:
                assert tr.spans == []

    def test_disabled_tracer_never_calls_the_annotation_factory(self):
        calls = []
        tr = Tracer(enabled=False, annotate=lambda name: calls.append(name))
        for _ in range(3):
            with tr.wall("engine.step") as sp:
                with tr.wall("engine.sample"):
                    pass
        tr.span("route.decide", "r1", "n0", 0.0, 1.0)
        assert calls == [] and tr.spans == [] and sp.dt > 0.0

    def test_enabled_tracer_annotates_each_wall_block_around_it(self):
        log = []

        class Ann:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                log.append(("open", self.name))

            def __exit__(self, *exc):
                log.append(("close", self.name, exc[0]))

        tr = Tracer(annotate=Ann)
        with tr.wall("engine.step"):
            with tr.wall("engine.sample"):
                pass
            with tr.wall("engine.decode_step", rows=2) as sp:
                sp.note(width=8)
        # sim-clock spans and events are never annotated
        tr.span("route.decide", "r1", "n0", 0.0, 1.0)
        tr.event("executor.admit", "r1", "n0", 1.0)
        assert log == [("open", "engine.step"), ("open", "engine.sample"),
                       ("close", "engine.sample", None),
                       ("open", "engine.decode_step"),
                       ("close", "engine.decode_step", None),
                       ("close", "engine.step", None)]
        walls = [s for s in tr.spans if s.clock == WALL]
        assert [s.name for s in walls] == [
            "engine.sample", "engine.decode_step", "engine.step"]
        assert walls[1].attrs == {"rows": 2, "width": 8}

    def test_annotation_closes_when_the_block_raises(self):
        log = []

        class Ann:
            def __init__(self, name):
                pass

            def __enter__(self):
                log.append("open")

            def __exit__(self, *exc):
                log.append(exc[0])

        tr = Tracer(annotate=Ann)
        with pytest.raises(ValueError):
            with tr.wall("engine.sample"):
                raise ValueError("boom")
        assert log == ["open", ValueError]
        assert [s.name for s in tr.spans] == ["engine.sample"]

    def test_by_request_groups_sorts_and_drops_batch_spans(self):
        tr = Tracer()
        tr.span("engine.decode", "r1", "n0", 2.0, 3.0)
        tr.span("route.decide", "r1", "n0", 0.0, 1.0)
        tr.span("engine.decode_step", "", "n0", 0.0, 0.1)   # batch-scoped
        by = tr.by_request()
        assert list(by) == ["r1"]
        assert [s.name for s in by["r1"]] == ["route.decide", "engine.decode"]

    def test_set_tracer_swaps_and_restores_process_default(self):
        assert not get_tracer().enabled      # process default starts off
        mine = Tracer()
        old = set_tracer(mine)
        try:
            assert get_tracer() is mine
        finally:
            assert set_tracer(old) is mine
        assert get_tracer() is old

    def test_wall_now_is_monotonic(self):
        a = wall_now()
        assert wall_now() >= a


# ---------------------------------------------------------------------------
# 2. metrics registry
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counter_labels_fan_out_into_series(self):
        reg = MetricsRegistry()
        reg.counter("net.messages", kind="probe").inc()
        reg.counter("net.messages", kind="probe").inc(2)
        reg.counter("net.messages", kind="gossip").inc()
        assert reg.value("net.messages", kind="probe") == 3.0
        assert reg.value("net.messages", kind="gossip") == 1.0
        assert reg.value("net.messages", kind="bounce") == 0.0

    def test_snapshot_shape_and_series_keys(self):
        reg = MetricsRegistry()
        reg.counter("net.dropped", reason="offline").inc()
        reg.counter("engine.preempted").inc(2)
        snap = reg.snapshot()
        assert snap == {"counters": {"engine.preempted": 2.0,
                                     "net.dropped{reason=offline}": 1.0}}
        json.dumps(snap)                     # JSON-able end to end

    def test_set_registry_swaps_and_restores(self):
        mine = MetricsRegistry()
        old = set_registry(mine)
        try:
            assert get_registry() is mine
        finally:
            set_registry(old)
        assert get_registry() is old

    def test_queued_drop_feeds_msg_counts_and_registry(self):
        # satellite (DESIGN.md §Observability): a churn-dropped queued
        # request was previously invisible; it must now show up both in
        # the "dropped" key next to msg_counts and as a labeled counter
        from repro.core import DuelParams, Network, Node, NodePolicy
        from repro.core.node import QueuedRequest
        from repro.sim import make_profile
        from repro.sim.workload import Request
        net = Network(mode="decentralized", seed=0,
                      duel=DuelParams(p_d=0.0, k_judges=0))
        for nid in ("n0", "n1"):
            net.add_node(Node(nid, make_profile(quality=0.5),
                              policy=NodePolicy()))
        net.nodes["n0"].online = False
        req = Request(rid="r0", origin="n1", arrival=0.0, prompt_tokens=8,
                      output_tokens=4, slo_s=30.0)
        net.nodes["n0"].enqueue(
            QueuedRequest(req, 0.0, delegated=True, origin_node="n1"))
        assert net.msg_counts["dropped"] == 1
        assert net.registry.value("net.dropped", reason="offline") == 1.0
        # the other routing kinds flow through the same registry
        net._count_msg("probe", 2)
        net._count_giveup("gossip")
        assert net.registry.value("net.messages", kind="probe") == 2.0
        assert net.msg_counts["giveup"] == 1
        assert net.registry.value("net.giveup", path="gossip") == 1.0


# ---------------------------------------------------------------------------
# 3. export
# ---------------------------------------------------------------------------

def _two_domain_tracer():
    tr = Tracer()
    tr.span("route.decide", "r1", "n0", 0.0, 0.1, mode="gossip")
    tr.event("executor.admit", "r1", "n1", 0.1)
    tr.span("engine.decode", "r1", "n1", 0.1, 1.1)
    tr.span("engine.decode_step", "", "node1", 100.0, 100.25, clock=WALL,
            batch=2)
    return tr


class TestChromeExport:
    def test_clock_domains_become_processes(self):
        payload = to_chrome_trace(_two_domain_tracer().spans)
        evs = payload["traceEvents"]
        procs = {e["args"]["name"]: e["pid"] for e in evs
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert procs == {"sim-time": 1, "wall-time": 2}
        assert payload["displayTimeUnit"] == "ms"

    def test_intervals_are_complete_events_instants_are_instants(self):
        evs = to_chrome_trace(_two_domain_tracer().spans)["traceEvents"]
        by_name = {e["name"]: e for e in evs if e["ph"] in ("X", "i")}
        dec = by_name["route.decide"]
        assert dec["ph"] == "X" and dec["dur"] == pytest.approx(1e5)
        assert dec["ts"] == 0.0 and dec["args"]["rid"] == "r1"
        assert by_name["executor.admit"]["ph"] == "i"
        assert by_name["executor.admit"]["s"] == "t"
        # wall timestamps are rebased to the earliest wall span
        step = by_name["engine.decode_step"]
        assert step["pid"] == 2 and step["ts"] == 0.0
        assert step["dur"] == pytest.approx(0.25e6)

    def test_threads_are_named_per_who(self):
        evs = to_chrome_trace(_two_domain_tracer().spans)["traceEvents"]
        threads = {(e["pid"], e["args"]["name"]) for e in evs
                   if e["ph"] == "M" and e["name"] == "thread_name"}
        assert (1, "n0") in threads and (1, "n1") in threads
        assert (2, "node1") in threads

    def test_write_chrome_trace_round_trips(self, tmp_path):
        p = tmp_path / "trace.json"
        payload = write_chrome_trace(_two_domain_tracer().spans, str(p))
        assert json.loads(p.read_text()) == payload


class TestBreakdown:
    def test_latency_breakdown_sums_stages_and_covers_total(self):
        bd = latency_breakdown(_two_domain_tracer().spans)
        assert list(bd) == ["r1"]            # batch-scoped "" excluded
        entry = bd["r1"]
        assert entry["spans"] == 3
        assert entry["stages"]["route.decide"] == pytest.approx(0.1)
        assert entry["stages"]["engine.decode"] == pytest.approx(1.0)
        assert entry["total"] == pytest.approx(1.1)

    def test_breakdown_report_orders_and_limits(self):
        tr = _two_domain_tracer()
        tr.span("engine.decode", "r2", "n0", 0.0, 5.0)
        text = breakdown_report(tr.spans)
        assert text.index("r2:") < text.index("r1:")   # slowest first
        only = breakdown_report(tr.spans, limit=1)
        assert "r2:" in only and "r1:" not in only


# ---------------------------------------------------------------------------
# 4. the latency partition, end to end
# ---------------------------------------------------------------------------

class TestLatencyPartition:
    """The --trace acceptance invariant (DESIGN.md §Observability), on the
    same traced sim the bench harness drives."""

    @pytest.fixture(scope="class")
    def traced_run(self):
        from benchmarks.run import _traced_sim_mix
        return _traced_sim_mix(n_requests=10)

    def test_merged_sim_spans_reconstruct_latency(self, traced_run):
        from benchmarks.run import _span_coverage_errors
        m, tr, _net = traced_run
        assert len(m.completed) == 10
        errs = _span_coverage_errors(m, tr.spans)
        assert errs and max(errs.values()) <= 0.05, errs

    def test_every_request_carries_the_lifecycle_chain(self, traced_run):
        m, tr, _net = traced_run
        by = tr.by_request()
        for c in m.completed:
            names = {s.name for s in by[c.rid]}
            assert {"route.decide", "executor.queue", "executor.admit",
                    "engine.prefill", "engine.decode"} <= names, \
                f"{c.rid}: {sorted(names)}"

    def test_spans_nest_inside_the_request_lifetime(self, traced_run):
        m, tr, _net = traced_run
        by = tr.by_request()
        for c in m.completed:
            for s in by[c.rid]:
                if s.clock == SIM:
                    assert c.arrival - 1e-9 <= s.t0 <= s.t1 <= \
                        c.finish + 1e-9, (c.rid, s.name)

    def test_process_tracer_restored_after_run(self, traced_run):
        _m, tr, _net = traced_run
        assert get_tracer() is not tr


# ---------------------------------------------------------------------------
# 4b. MetricsCollector aggregate regressions (satellites)
# ---------------------------------------------------------------------------

def _cr(rid, executor="n0", arrival=0.0, finish=1.0, slo=2.0, duel=False):
    return CompletedRequest(rid=rid, origin="n0", executor=executor,
                            arrival=arrival, finish=finish, slo_s=slo,
                            delegated=False, is_duel_extra=duel)


class TestMetricsCollectorAggregates:
    def test_per_executor_counts_excludes_duel_extras_by_default(self):
        m = MetricsCollector()
        m.record(_cr("u1", executor="n0"))
        m.record(_cr("u2", executor="n1"))
        m.record(_cr("d1", executor="n0", duel=True))   # duel challenger
        m.record(_cr("d2", executor="n0", duel=True))   # duel judge
        # the regression: duel extras used to inflate duel-heavy nodes
        assert m.per_executor_counts() == {"n0": 1, "n1": 1}
        # raw count stays available for duel accounting
        assert m.per_executor_counts(user_only=False) == {"n0": 3, "n1": 1}

    def test_windowed_latency_empty_collector(self):
        assert MetricsCollector().windowed_latency(1.0, 10.0) == []

    def test_windowed_latency_skips_empty_windows(self):
        m = MetricsCollector()
        m.record(_cr("a", finish=0.5))
        m.record(_cr("b", finish=8.5, arrival=8.0))
        out = m.windowed_latency(1.0, 10.0)
        assert [t for t, _ in out] == [0.5, 8.5]       # midpoints only
        assert out[0][1] == pytest.approx(0.5)
        assert out[1][1] == pytest.approx(0.5)

    def test_windowed_latency_window_larger_than_t_end(self):
        m = MetricsCollector()
        m.record(_cr("a", finish=3.0))
        out = m.windowed_latency(10.0, 4.0)
        # one window [0, 10) starting inside [0, t_end) catches the finish
        assert len(out) == 1 and out[0][1] == pytest.approx(3.0)

    def test_latency_cdf_single_request(self):
        m = MetricsCollector()
        m.record(_cr("a", finish=2.5))
        assert m.latency_cdf(n=1) == [(2.5, 0.0)]
        cdf = m.latency_cdf()
        assert cdf[0] == (2.5, 0.0) and cdf[-1] == (2.5, 1.0)
        assert MetricsCollector().latency_cdf() == []

    def test_slo_curve_is_monotonic_in_scale(self):
        m = MetricsCollector()
        for i, lat in enumerate((0.5, 1.0, 1.5, 3.0, 6.0)):
            m.record(_cr(f"r{i}", finish=lat, slo=2.0))
        scales = (0.25, 0.5, 1.0, 2.0, 4.0)
        curve = m.slo_curve(scales)
        assert [s for s, _ in curve] == list(scales)
        atts = [a for _, a in curve]
        assert all(b >= a for a, b in zip(atts, atts[1:]))
        assert atts[-1] == 1.0
