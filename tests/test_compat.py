"""Compat layer: gated-symbol hygiene, the mesh-environment layer, and
Pallas interpret mode.

Three families of tests:

1.  Hygiene guards proving that no module outside ``repro.compat``
    resolves version-gated ``jax.sharding`` / pallas symbols, and that
    the executor-layer state boundaries hold.  These are now thin
    wrappers over the AST checkers in ``repro.analysis`` (DESIGN.md §7)
    — the historical test names stay so the contract's history stays
    greppable, while the string greps they once were (with their
    docstring false positives and whole-file allowlists) are gone.
2.  Unit tests for ``repro.compat.meshenv``: on the installed JAX's real
    mesh context, and against a monkeypatched abstract mesh.
3.  ``repro.compat.pallascompat.resolve_interpret``: the interpreter runs
    only off the TPU.
"""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis import run_analysis
from repro.compat import meshenv

REPO = pathlib.Path(__file__).resolve().parents[1]


def _new_findings(rule):
    """New findings for one (sub-)rule over this repo, baseline disabled
    so grandfathering can never mask a regression in tier-1."""
    report = run_analysis(REPO, rules=[rule.split("/")[0]],
                          baseline_path="")
    return [f.format() for f in report.new
            if f.rule_id == rule or rule == f.rule_id.split("/")[0]]


# ---------------------------------------------------------------------------
# 1. version-gate hygiene
# ---------------------------------------------------------------------------

class TestVersionGateHygiene:
    def test_no_version_gated_symbols_outside_compat(self):
        offenders = _new_findings("compat-boundary")
        assert not offenders, (
            "version-gated mesh/pallas symbols outside repro.compat "
            "(route through meshenv/pallascompat instead):\n  "
            + "\n  ".join(offenders))


class TestExecutorLayerHygiene:
    """Frozen-share scheduling must not creep back: only the executor layer
    may call the analytic ``BackendProfile.service_time`` directly.  Nodes
    route execution through ``Executor.admit``/``load``/``estimate``
    (DESIGN.md §6.1)."""

    def test_service_time_only_called_from_executor_layer(self):
        offenders = _new_findings("layering/service-time")
        assert not offenders, (
            "direct service_time calls outside the executor layer "
            "(route through Executor.admit/load/estimate instead):\n  "
            + "\n  ".join(offenders))

    # the paged engine's page-pool bookkeeping is private to the engine;
    # everything else reads Engine.load_snapshot() / Executor.load()
    # (pages_used / pages_total / free_pages / page_size)
    def test_page_pool_state_private_to_engine(self):
        offenders = _new_findings("layering/private-state")
        assert not offenders, (
            "private page-pool state accessed outside the paged engine "
            "(read Engine.load_snapshot()/Executor.load() instead):\n  "
            + "\n  ".join(offenders))


class TestBenchSchema:
    """BENCH_scheduling.json drift is caught in tier-1: the checked-in
    artifact must satisfy the pinned schema that ``benchmarks/run.py
    --bench`` also validates at write time."""

    def test_checked_in_bench_matches_schema(self):
        import json

        from benchmarks.run import check_bench_schema
        path = REPO / "BENCH_scheduling.json"
        assert path.exists(), "BENCH_scheduling.json missing (run --bench)"
        payload = json.loads(path.read_text())
        check_bench_schema(payload)

    def test_schema_checker_rejects_drift(self):
        import json

        from benchmarks.run import check_bench_schema
        payload = json.loads((REPO / "BENCH_scheduling.json").read_text())
        del payload["engine"]["paged"]["decode_tokens_per_s"]
        with pytest.raises(AssertionError):
            check_bench_schema(payload)

    def test_schema_checker_rejects_mix_drift(self):
        """Schema 6 keeps pinning the disagg-vs-colocated mixed-workload
        section (incl. the surfaced transfer pipeline depth)."""
        import json

        from benchmarks.run import check_bench_schema
        payload = json.loads((REPO / "BENCH_scheduling.json").read_text())
        assert payload["schema"] == 9
        assert "ttft_speedup_prompt_heavy" in payload["mix"]
        for key in ("handoffs", "transfer_inflight_peak"):
            broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
            del broken["mix"]["disagg"][key]
            with pytest.raises(AssertionError):
                check_bench_schema(broken)
        broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
        del broken["mix"]["slot"]["avg_ttft_prompt_heavy_s"]
        with pytest.raises(AssertionError):
            check_bench_schema(broken)

    def test_schema_checker_rejects_kernel_drift(self):
        """Schema 6 pins the kernel microbench: slot/paged/quantized-paged
        timings, the autotuned pages_per_step, and the int8 admission demo
        whose >= 2x concurrency bar is a hard assert — a capacity
        regression in the quantized page pool fails tier-1, not just the
        artifact diff."""
        import json

        from benchmarks.run import check_bench_schema
        payload = json.loads((REPO / "BENCH_scheduling.json").read_text())
        kern = payload["kernel"]
        assert kern["admission"]["paged_quant"] >= 2 * kern["admission"]["paged"]
        assert kern["tuning"]["pages_per_step"] >= 1
        for key in ("slot", "paged", "paged_quant"):
            broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
            del broken["kernel"]["decode"][key]
            with pytest.raises(AssertionError):
                check_bench_schema(broken)
        broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
        del broken["kernel"]["spec_verify"]["paged_quant"]
        with pytest.raises(AssertionError):
            check_bench_schema(broken)
        broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
        broken["kernel"]["admission"]["paged_quant"] = \
            2 * broken["kernel"]["admission"]["paged"] - 1
        with pytest.raises(AssertionError):
            check_bench_schema(broken)
        broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
        broken["mix"]["paged"]["decode_tokens_per_s"] = \
            broken["mix"]["slot"]["decode_tokens_per_s"] - 1.0
        with pytest.raises(AssertionError):
            check_bench_schema(broken)

    def test_schema_checker_rejects_lint_drift(self):
        """Schema 6 pins the static-analysis snapshot: rule list, counts
        by disposition, and a hard zero on new violations — a baseline
        or suppression creep shows up in the artifact diff."""
        import json

        from benchmarks.run import check_bench_schema
        payload = json.loads((REPO / "BENCH_scheduling.json").read_text())
        assert payload["lint"]["new"] == 0
        assert payload["lint"]["rules"], "no checkers ran?"
        for key in ("rules", "suppressed", "baselined", "wall_s"):
            broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
            del broken["lint"][key]
            with pytest.raises(AssertionError):
                check_bench_schema(broken)
        broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
        broken["lint"]["new"] = 3
        with pytest.raises(AssertionError):
            check_bench_schema(broken)

    def test_schema_checker_rejects_spec_drift(self):
        """Schema 6 pins the speculative-vs-paged decode-heavy section:
        accepted-length distribution + effective decode tokens/s."""
        import json

        from benchmarks.run import check_bench_schema
        payload = json.loads((REPO / "BENCH_scheduling.json").read_text())
        spec = payload["spec"]
        assert "speedup_decode_tokens_per_s" in spec
        assert len(spec["spec"]["accept_hist"]) == spec["spec_k"] + 1
        for key in ("accept_hist", "alpha_ema", "expected_tokens_per_step"):
            broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
            del broken["spec"]["spec"][key]
            with pytest.raises(AssertionError):
                check_bench_schema(broken)
        broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
        del broken["spec"]["paged"]["decode_tokens_per_s"]
        with pytest.raises(AssertionError):
            check_bench_schema(broken)

    def test_schema_checker_rejects_prefix_cache_drift(self):
        """Schema 8 pins the prefix-cache section (DESIGN.md §6.1-prefix):
        engine cached-vs-cold TTFT, the simulated zipf hit rate, and the
        affinity-vs-blind routing comparison — with hard bars (cached TTFT
        strictly below cold, hit rate >= 0.5, affinity above blind) so a
        cache regression fails tier-1, not just the artifact diff."""
        import json

        from benchmarks.run import check_bench_schema
        payload = json.loads((REPO / "BENCH_scheduling.json").read_text())
        pc = payload["prefix_cache"]
        assert pc["engine"]["cached_ttft_s"] < pc["engine"]["cold_ttft_s"]
        assert pc["sim"]["hit_rate"] >= 0.5
        assert (pc["routing"]["affinity"]["hit_rate"]
                > pc["routing"]["blind"]["hit_rate"])
        for key in ("cold_ttft_s", "cached_ttft_s", "ttft_speedup",
                    "hit_tokens", "cached_pages"):
            broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
            del broken["prefix_cache"]["engine"][key]
            with pytest.raises(AssertionError):
                check_bench_schema(broken)
        for mode in ("affinity", "blind"):
            broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
            del broken["prefix_cache"]["routing"][mode]["hit_rate"]
            with pytest.raises(AssertionError):
                check_bench_schema(broken)
        # hard-bar violations are rejected, not just missing keys
        broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
        broken["prefix_cache"]["engine"]["cached_ttft_s"] = \
            broken["prefix_cache"]["engine"]["cold_ttft_s"] + 1.0
        with pytest.raises(AssertionError):
            check_bench_schema(broken)
        broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
        broken["prefix_cache"]["sim"]["hit_rate"] = 0.3
        with pytest.raises(AssertionError):
            check_bench_schema(broken)
        broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
        broken["prefix_cache"]["routing"]["affinity"]["hit_rate"] = \
            broken["prefix_cache"]["routing"]["blind"]["hit_rate"]
        with pytest.raises(AssertionError):
            check_bench_schema(broken)

    def test_schema_checker_rejects_obs_drift(self):
        """Schema 9 pins the tracing-overhead section (DESIGN.md
        §Observability): mix decode tok/s with the tracer on vs off, with
        a hard >= 0.95x bound — making spans expensive fails tier-1, not
        just the artifact diff."""
        import json

        from benchmarks.run import check_bench_schema
        payload = json.loads((REPO / "BENCH_scheduling.json").read_text())
        obs = payload["obs"]
        assert obs["overhead_ratio"] >= 0.95
        assert obs["spans"] > 0
        for key in ("untraced", "traced", "overhead_ratio", "spans",
                    "metrics"):
            broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
            del broken["obs"][key]
            with pytest.raises(AssertionError):
                check_bench_schema(broken)
        for arm in ("untraced", "traced"):
            broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
            del broken["obs"][arm]["decode_tokens_per_s"]
            with pytest.raises(AssertionError):
                check_bench_schema(broken)
        broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
        broken["obs"]["overhead_ratio"] = 0.8
        with pytest.raises(AssertionError):
            check_bench_schema(broken)

    def test_schema_checker_rejects_gossip_drift(self):
        """Schema 7 pins the gossip scale-out section (DESIGN.md
        §6.2-gossip): both routing modes at the 100- and 1k-node points,
        plus hard bars — at 1k nodes the digest plane must route with at
        least 3x fewer messages per request than the power-of-two probe
        baseline while holding SLO attainment within 2 points."""
        import json

        from benchmarks.run import check_bench_schema
        payload = json.loads((REPO / "BENCH_scheduling.json").read_text())
        gos = payload["gossip"]
        big = gos["points"]["1000"]
        assert (big["gossip"]["routing_msgs_per_req"]
                < big["probe"]["routing_msgs_per_req"])
        assert big["msgs_ratio"] >= 3.0
        assert big["slo_gap"] <= 0.02
        for pt in ("100", "1000"):
            for mode in ("gossip", "probe"):
                broken = json.loads(
                    (REPO / "BENCH_scheduling.json").read_text())
                del broken["gossip"]["points"][pt][mode]["routing_msgs_per_req"]
                with pytest.raises(AssertionError):
                    check_bench_schema(broken)
        broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
        broken["gossip"]["points"]["1000"]["msgs_ratio"] = 2.5
        with pytest.raises(AssertionError):
            check_bench_schema(broken)
        broken = json.loads((REPO / "BENCH_scheduling.json").read_text())
        broken["gossip"]["points"]["1000"]["slo_gap"] = 0.1
        with pytest.raises(AssertionError):
            check_bench_schema(broken)


# ---------------------------------------------------------------------------
# 2. meshenv — the installed JAX's mesh context
# ---------------------------------------------------------------------------

class TestMeshEnvLegacy:
    """The mesh-environment contract on the installed JAX's real
    ``jax.set_mesh`` context."""

    def test_no_mesh_is_none(self):
        assert meshenv.current_mesh() is None
        assert meshenv.axis_names() == ()
        assert meshenv.axis_sizes() == {}

    def test_mesh_context_sets_ambient_mesh(self):
        m = meshenv.make_mesh((1, 1), ("data", "model"))
        with meshenv.mesh_context(m):
            got = meshenv.current_mesh()
            assert got is not None
            assert tuple(got.axis_names) == ("data", "model")
            assert meshenv.axis_sizes() == {"data": 1, "model": 1}
        assert meshenv.current_mesh() is None

    def test_with_sharding_constraint_under_jit(self):
        m = meshenv.make_mesh((1, 1), ("data", "model"))
        x = jnp.arange(16.0).reshape(4, 4)
        with meshenv.mesh_context(m):
            y = jax.jit(lambda a: meshenv.with_sharding_constraint(
                a, P("data", None)))(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))

    def test_constraint_is_noop_without_mesh(self):
        x = jnp.ones((2, 2))
        assert meshenv.with_sharding_constraint(x, P(None, None)) is x

    def test_shard_map_runs(self):
        m = meshenv.make_mesh((1, 1), ("data", "model"))
        f = meshenv.shard_map(lambda a: a * 2, mesh=m,
                              in_specs=P(None, None),
                              out_specs=P(None, None))
        np.testing.assert_array_equal(np.asarray(f(jnp.ones((2, 2)))),
                                      2 * np.ones((2, 2)))

    def test_logical_spec_resolution(self):
        """models.common routes through meshenv: batch -> present axes."""
        from repro.models import common as cm
        m = meshenv.make_mesh((1, 1), ("data", "model"))
        with meshenv.mesh_context(m):
            assert cm.logical("batch", None, "model") == \
                P(("data",), None, "model")
            assert cm.logical("absent_axis") == P(None)
        assert cm.logical("batch") == P(None)     # unmeshed: everything drops


# ---------------------------------------------------------------------------
# 2b. meshenv — against a simulated abstract mesh
# ---------------------------------------------------------------------------

class _FakeAbstractMesh:
    def __init__(self, sizes, empty=False):
        self._sizes = dict(sizes)
        self.empty = empty

    @property
    def axis_names(self):
        return tuple(self._sizes)

    @property
    def shape(self):
        return dict(self._sizes)


class _FakeAxisType:
    Auto = "auto"


class TestMeshEnvModern:
    def _install(self, monkeypatch, mesh):
        monkeypatch.setattr(jax.sharding, "get_abstract_mesh",
                            lambda: mesh, raising=False)
        monkeypatch.setattr(jax.sharding, "AxisType", _FakeAxisType,
                            raising=False)

    def test_modern_detection_and_current_mesh(self, monkeypatch):
        fake = _FakeAbstractMesh({"data": 2, "model": 4})
        self._install(monkeypatch, fake)
        assert meshenv.current_mesh() is fake
        assert meshenv.axis_names() == ("data", "model")
        assert meshenv.axis_sizes() == {"data": 2, "model": 4}
        assert meshenv.mesh_size(fake, ("data", "model")) == 8
        assert meshenv.mesh_size(fake, "model") == 4

    def test_empty_abstract_mesh_is_none(self, monkeypatch):
        self._install(monkeypatch, _FakeAbstractMesh({}, empty=True))
        assert meshenv.current_mesh() is None
        assert meshenv.axis_names() == ()

    def test_make_mesh_passes_axis_types(self, monkeypatch):
        seen = {}

        def fake_make_mesh(shapes, names, **kw):
            seen.update(kw, shapes=shapes, names=names)
            return "mesh"

        monkeypatch.setattr(jax, "make_mesh", fake_make_mesh)
        monkeypatch.setattr(jax.sharding, "AxisType", _FakeAxisType,
                            raising=False)
        assert meshenv.make_mesh((2, 2), ("data", "model")) == "mesh"
        assert seen["axis_types"] == ("auto", "auto")

    def test_modern_constraint_uses_bare_spec(self, monkeypatch):
        """With an (abstract, non-concrete) mesh active, the constraint is
        passed through as a bare PartitionSpec — the modern contract."""
        fake = _FakeAbstractMesh({"data": 1, "model": 1})
        self._install(monkeypatch, fake)
        captured = {}

        def fake_wsc(x, sharding):
            captured["sharding"] = sharding
            return x

        monkeypatch.setattr(jax.lax, "with_sharding_constraint", fake_wsc)
        x = jnp.ones((2, 2))
        meshenv.with_sharding_constraint(x, P("data", None))
        assert captured["sharding"] == P("data", None)
        assert not isinstance(captured["sharding"], Mesh)



# ---------------------------------------------------------------------------
# 3. pallascompat — interpret mode follows the backend
# ---------------------------------------------------------------------------

class TestResolveInterpret:
    def test_follows_the_backend(self, monkeypatch):
        from repro.compat import pallascompat as pc
        assert pc.resolve_interpret() is (jax.default_backend() != "tpu")
        monkeypatch.setattr(pc, "on_tpu", lambda: True)
        assert pc.resolve_interpret() is False
        # an explicit False compiles for the chip from any process
        assert pc.resolve_interpret(False) is False

    def test_interpreter_refused_on_tpu(self, monkeypatch):
        from repro.compat import pallascompat as pc
        monkeypatch.setattr(pc, "on_tpu", lambda: False)
        assert pc.resolve_interpret(True) is True
        monkeypatch.setattr(pc, "on_tpu", lambda: True)
        with pytest.raises(ValueError, match="interpret"):
            pc.resolve_interpret(True)
