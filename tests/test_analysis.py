"""repro.analysis: the invariant linter, tested on fixture repos + live.

Two layers:

1.  Per-checker fixture tests — each rule gets a seeded tmp_path repo
    with a positive case (the violation fires), a negative case (the
    idiomatic form stays silent), plus shared suppression and
    baseline-round-trip mechanics.  The fixtures are also what the CLI
    exit-code test seeds, so ``python -m repro.analysis`` failing on a
    seeded violation is asserted per rule.
2.  The live pass (tier-1 acceptance, DESIGN.md §7): the full analyzer
    over this repository's src/ + tests/ + benchmarks/ must come back
    with zero NEW findings in under 10s, and the committed baseline must
    be empty — violations get fixed or carry an inline
    ``# repro: allow[...]`` justification, they do not accumulate.
"""

import json
import pathlib
import subprocess
import sys
import textwrap

from repro.analysis import (BASELINE_FILE, all_checkers, load_baseline,
                            run_analysis, save_baseline)
from repro.analysis.framework import Finding, RepoIndex, rule_matches

REPO = pathlib.Path(__file__).resolve().parents[1]

ALL_RULES = ("compat-boundary", "docs-anchors", "kernel-lint", "layering",
             "obs-lint", "twin-drift")


def mk_repo(tmp_path, files):
    for rel, content in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(content))
    return tmp_path


def analyze(root, rule=None):
    report = run_analysis(root, rules=[rule] if rule else None,
                          baseline_path="")
    return report


def rule_ids(report):
    return [f.rule_id for f in report.new]


# the DESIGN.md skeleton fixtures share: defines every pinned anchor
DESIGN_OK = """\
    # DESIGN
    ## §6.1 Executors
    ### §6.1-paged Paged
    ### §6.1-disagg Disagg
    ### §6.1-prefix Prefix cache
    ### §6.1-spec Spec
    ## §Perf-kernels Speed
    ## §6.2 Duels
    ### §6.2-gossip Load dissemination
    ## §6.3 Ledger
    ## §7 Analysis
    ## §Arch-applicability
    ## §Observability
"""
MD_STUBS = {"DESIGN.md": DESIGN_OK, "ROADMAP.md": "roadmap\n",
            "CHANGES.md": "changes\n", "README.md": "readme\n"}

# per-rule seeded violations; each MUST produce >= 1 finding of its rule
# (the CLI test below runs python -m repro.analysis against each of these)
SEEDED = {
    "compat-boundary": {
        **MD_STUBS,
        "src/repro/serving/x.py": """\
            from jax.sharding import set_mesh

            def f(m):
                with set_mesh(m):
                    return 1
        """,
    },
    "layering": {
        **MD_STUBS,
        "src/repro/core/x.py": """\
            from repro.serving.engine import Engine
        """,
    },
    "kernel-lint": {
        **MD_STUBS,
        "src/repro/kernels/x.py": """\
            import functools
            from jax.experimental import pallas as pl

            def _k(x_ref, o_ref, *, b):
                print(x_ref)
                o_ref[...] = x_ref[...]

            def run(x, b):
                kernel = functools.partial(_k, b=b)
                return pl.pallas_call(kernel, grid=(x.shape[0] // b,))(x)
        """,
    },
    "twin-drift": {
        **MD_STUBS,
        "src/repro/sim/servicemodel.py": "SPEC_K = 4\n",
        "src/repro/serving/engine.py": "SPEC_K = 4\n",
    },
    "docs-anchors": {
        **MD_STUBS,
        "ROADMAP.md": "see §no-such-section\n",
    },
    "obs-lint": {
        **MD_STUBS,
        # a governed module reading a raw clock (and no longer resolving
        # the tracer) trips both wall-clock and emission
        "src/repro/core/network.py": """\
            import time

            def now():
                return time.perf_counter()
        """,
    },
}


class TestSeededFixtures:
    """Every rule fires on its seeded fixture — the same repos the CLI
    exit-code test uses."""

    def test_each_seeded_repo_trips_its_rule(self, tmp_path):
        for rule, files in SEEDED.items():
            root = mk_repo(tmp_path / rule.replace("/", "_"), files)
            report = analyze(root, rule)
            assert any(rule_matches(rule, r) for r in rule_ids(report)), \
                f"{rule} fixture produced {rule_ids(report)}"


class TestCompatBoundary:
    def test_import_attribute_and_kwarg_forms_fire(self, tmp_path):
        root = mk_repo(tmp_path, {**MD_STUBS, "src/repro/serving/x.py": """\
            import jax

            def f(m):
                jax.sharding.set_mesh(m)
                return jax.make_mesh((1,), ("d",), axis_types=(1,))
        """})
        ids = rule_ids(analyze(root, "compat-boundary"))
        assert ids.count("compat-boundary") == 2

    def test_compat_package_and_docstrings_are_silent(self, tmp_path):
        root = mk_repo(tmp_path, {**MD_STUBS, "src/repro/compat/x.py": """\
            from jax.sharding import get_abstract_mesh, set_mesh
        """, "src/repro/serving/y.py": '''\
            """Mentions get_abstract_mesh and AxisType only in prose."""
            # a comment about set_mesh is fine too
            X = 1
        '''})
        assert rule_ids(analyze(root, "compat-boundary")) == []


class TestLayering:
    def test_import_dag_violation_and_unknown_subpackage(self, tmp_path):
        root = mk_repo(tmp_path, {
            **MD_STUBS,
            "src/repro/core/x.py": "import repro.serving\n",
            "src/repro/mystery/y.py": "X = 1\n",
        })
        ids = rule_ids(analyze(root, "layering"))
        assert ids.count("layering/import-dag") == 2

    def test_sanctioned_edges_are_silent(self, tmp_path):
        root = mk_repo(tmp_path, {
            **MD_STUBS,
            "src/repro/serving/x.py": "from repro.sim import executor\n",
            "src/repro/core/y.py": "from repro.sim import workload\n",
        })
        assert rule_ids(analyze(root, "layering")) == []

    def test_executor_contract_missing_surface(self, tmp_path):
        root = mk_repo(tmp_path, {**MD_STUBS, "src/repro/sim/x.py": """\
            class Executor:
                pass

            class Partial(Executor):
                def admit(self, r):
                    return True

            class Full(Executor):
                def admit(self, r):
                    return True
                def load(self):
                    return None
                def estimate(self, r):
                    return 0.0
                @property
                def n_active(self):
                    return 0

            class Inheriting(Full):
                pass
        """})
        findings = analyze(root, "layering").new
        bad = [f for f in findings
               if f.rule_id == "layering/executor-contract"]
        assert len(bad) == 1 and "'Partial'" in bad[0].msg
        for m in ("load", "estimate", "n_active"):
            assert m in bad[0].msg

    def test_service_time_and_private_state_boundaries(self, tmp_path):
        root = mk_repo(tmp_path, {**MD_STUBS, "src/repro/core/x.py": """\
            def f(profile, eng):
                t = profile.service_time(10)
                return t + len(eng._free_pages)
        """})
        ids = rule_ids(analyze(root, "layering"))
        assert "layering/service-time" in ids
        assert "layering/private-state" in ids

    def test_digest_construction_confined_to_executor_layer(self, tmp_path):
        root = mk_repo(tmp_path, {
            **MD_STUBS,
            # hand-rolled digest outside the executor layer: flagged
            "src/repro/core/x.py": """\
            from repro.sim.executor import LoadDigest

            def fake(now):
                return LoadDigest(now, 1.0, 1.0, 0, 0, 1.0, 0)
        """,
            # the sanctioned projection home constructs freely
            "src/repro/sim/executor.py": """\
            class LoadDigest:
                pass

            def make_load_digest(load, now):
                return LoadDigest()
        """,
            # obtaining a digest via the projection helper is silent
            "src/repro/core/y.py": """\
            from repro.sim.executor import make_load_digest

            def ok(load, now):
                return make_load_digest(load, now)
        """})
        findings = analyze(root, "layering").new
        bad = [f for f in findings
               if f.rule_id == "layering/digest-construction"]
        assert len(bad) == 1
        assert bad[0].path == "src/repro/core/x.py"
        assert "make_load_digest" in bad[0].msg


class TestKernelLint:
    def test_nested_kernel_closure_capture(self, tmp_path):
        root = mk_repo(tmp_path, {**MD_STUBS, "src/repro/kernels/x.py": """\
            from jax.experimental import pallas as pl

            def run(x):
                scale = float(x.shape[0])

                def _k(x_ref, o_ref):
                    o_ref[...] = x_ref[...] * scale

                return pl.pallas_call(_k, grid=(1,))(x)
        """})
        findings = analyze(root, "kernel-lint").new
        closure = [f for f in findings if f.rule_id == "kernel-lint/closure"]
        assert len(closure) == 1 and "scale" in closure[0].msg

    def test_partial_bound_statics_are_silent(self, tmp_path):
        root = mk_repo(tmp_path, {**MD_STUBS, "src/repro/kernels/x.py": """\
            import functools
            from jax.experimental import pallas as pl

            def _k(x_ref, o_ref, *, b):
                o_ref[...] = x_ref[...] * b

            def run(x, b):
                pad = (-x.shape[0]) % b
                kernel = functools.partial(_k, b=b)
                return pl.pallas_call(kernel, grid=(x.shape[0] // b,))(x)
        """})
        assert rule_ids(analyze(root, "kernel-lint")) == []

    def test_grid_division_without_evidence(self, tmp_path):
        root = mk_repo(tmp_path, {**MD_STUBS, "src/repro/kernels/x.py": """\
            import functools
            from jax.experimental import pallas as pl

            def _k(x_ref, o_ref, *, b):
                o_ref[...] = x_ref[...]

            def run(x, b):
                kernel = functools.partial(_k, b=b)
                return pl.pallas_call(kernel, grid=(x.shape[0] // b,))(x)
        """})
        ids = rule_ids(analyze(root, "kernel-lint"))
        assert "kernel-lint/grid-divisibility" in ids

    def test_index_map_purity(self, tmp_path):
        root = mk_repo(tmp_path, {**MD_STUBS, "src/repro/kernels/x.py": """\
            from jax.experimental import pallas as pl

            def _k(x_ref, o_ref):
                o_ref[...] = x_ref[...]

            def helper(i):
                return i

            def run(x):
                return pl.pallas_call(
                    _k,
                    in_specs=[pl.BlockSpec((8,), lambda i: helper(i)),
                              pl.BlockSpec((8,), lambda i: pl.ds(i, 1))],
                    grid=(1,))(x)
        """})
        ids = rule_ids(analyze(root, "kernel-lint"))
        assert ids.count("kernel-lint/index-map") == 1

    def test_tunable_attribute_divisor_needs_evidence(self, tmp_path):
        # a grid axis divided by a tuning ATTRIBUTE (not a bare name) must
        # carry the same % evidence; the bare-name check alone misses it
        bad = {**MD_STUBS, "src/repro/kernels/x.py": """\
            import functools
            from jax.experimental import pallas as pl

            def _k(x_ref, o_ref, *, b):
                o_ref[...] = x_ref[...]

            def run(x, tuning):
                kernel = functools.partial(_k, b=tuning.pages_per_step)
                return pl.pallas_call(
                    kernel, grid=(x.shape[0] // tuning.pages_per_step,))(x)
        """}
        ids = rule_ids(analyze(mk_repo(tmp_path / "bad", bad), "kernel-lint"))
        assert "kernel-lint/grid-divisibility" in ids
        good = {**MD_STUBS, "src/repro/kernels/x.py": """\
            import functools
            from jax.experimental import pallas as pl

            def _k(x_ref, o_ref, *, b):
                o_ref[...] = x_ref[...]

            def run(x, tuning):
                pad = (-x.shape[0]) % tuning.pages_per_step
                kernel = functools.partial(_k, b=tuning.pages_per_step)
                return pl.pallas_call(
                    kernel,
                    grid=((x.shape[0] + pad) // tuning.pages_per_step,))(x)
        """}
        assert rule_ids(analyze(mk_repo(tmp_path / "good", good),
                                "kernel-lint")) == []

    def test_dequant_helper_redefined_in_pallas_module(self, tmp_path):
        root = mk_repo(tmp_path, {**MD_STUBS, "src/repro/kernels/x.py": """\
            from jax.experimental import pallas as pl

            def kv_dequantize(q, scale, dtype):
                return q.astype(dtype) * scale

            def _k(x_ref, s_ref, o_ref):
                o_ref[...] = kv_dequantize(x_ref[...], s_ref[...], float)

            def run(x, s):
                return pl.pallas_call(_k, grid=(1,))(x, s)
        """})
        ids = rule_ids(analyze(root, "kernel-lint"))
        # both the local re-definition and the call resolving to it fire
        assert ids.count("kernel-lint/dequant-import") == 2

    def test_dequant_imported_from_attention_is_silent(self, tmp_path):
        root = mk_repo(tmp_path, {**MD_STUBS, "src/repro/kernels/x.py": """\
            from jax.experimental import pallas as pl
            from repro.models.attention import kv_dequantize

            def _k(x_ref, s_ref, o_ref):
                o_ref[...] = kv_dequantize(x_ref[...], s_ref[...], float)

            def run(x, s):
                return pl.pallas_call(_k, grid=(1,))(x, s)
        """})
        assert rule_ids(analyze(root, "kernel-lint")) == []


class TestTwinDrift:
    def test_redefining_shared_constant_and_predicate(self, tmp_path):
        root = mk_repo(tmp_path, {
            **MD_STUBS,
            "src/repro/sim/servicemodel.py": "SPEC_K = 4\nKV = {}\n",
            "src/repro/serving/engine.py": """\
                SPEC_K = 4

                def paged_admit_ok(load, req):
                    return True
            """,
        })
        ids = rule_ids(analyze(root, "twin-drift"))
        assert ids.count("twin-drift/shared-name") == 2

    def test_importing_shared_names_is_silent(self, tmp_path):
        root = mk_repo(tmp_path, {
            **MD_STUBS,
            "src/repro/sim/servicemodel.py": "SPEC_K = 4\n",
            "src/repro/serving/engine.py":
                "from repro.sim.servicemodel import SPEC_K\n"
                "LOCAL_ONLY = 3\n",
        })
        assert rule_ids(analyze(root, "twin-drift")) == []

    def test_redefining_prefix_predicates_flagged(self, tmp_path):
        """The §6.1-prefix hit rule is a registered shared predicate: a
        local re-implementation in an engine or benchmark module is drift,
        both as a function def and as a shadowing assignment."""
        root = mk_repo(tmp_path, {
            **MD_STUBS,
            "src/repro/serving/engine.py": """\
                def prefix_hit_pages(prompt, page, matched):
                    return matched // page
            """,
            "benchmarks/run.py": "prefix_fingerprint_id = hash\n",
        })
        ids = rule_ids(analyze(root, "twin-drift"))
        assert ids.count("twin-drift/shared-name") == 2

    def test_importing_prefix_predicates_is_silent(self, tmp_path):
        root = mk_repo(tmp_path, {
            **MD_STUBS,
            "src/repro/serving/engine.py":
                "from repro.sim.executor import prefix_hit_pages\n",
            "src/repro/core/network.py":
                "from repro.sim.executor import prefix_fingerprint_id\n",
        })
        assert rule_ids(analyze(root, "twin-drift")) == []

    def test_duplicate_constant_across_modules(self, tmp_path):
        root = mk_repo(tmp_path, {
            **MD_STUBS,
            "src/repro/kernels/a.py": "NEG_INF = -1e30\n",
            "src/repro/kernels/b.py": "NEG_INF = -1e30\n",
            "src/repro/models/c.py": "_PRIVATE = 1.0\n",
            "src/repro/models/d.py": "_PRIVATE = 1.0\n",
        })
        ids = rule_ids(analyze(root, "twin-drift"))
        # both public copies flagged; private (_-prefixed) ones exempt
        assert ids.count("twin-drift/duplicate-const") == 2


class TestObsLint:
    def test_span_ctor_outside_obs_fires(self, tmp_path):
        root = mk_repo(tmp_path, {**MD_STUBS, "src/repro/core/x.py": """\
            from repro.obs.tracer import Span

            def f(spans):
                spans.append(Span("route.decide", "r1", "n0", 0.0, 1.0))
        """})
        findings = analyze(root, "obs-lint").new
        bad = [f for f in findings
               if f.rule_id == "obs-lint/span-construction"]
        assert len(bad) == 1 and bad[0].path == "src/repro/core/x.py"

    def test_obs_home_and_tracer_api_are_silent(self, tmp_path):
        root = mk_repo(tmp_path, {
            **MD_STUBS,
            # the sanctioned home constructs Span freely
            "src/repro/obs/tracer.py": """\
            class Span:
                pass

            def span(name):
                return Span()
        """,
            # recording through the Tracer API is the idiomatic form
            "src/repro/core/x.py": """\
            from repro.obs import get_tracer

            def f(rid):
                tr = get_tracer()
                if tr.enabled:
                    tr.span("route.decide", rid, "n0", 0.0, 1.0)
        """})
        assert rule_ids(analyze(root, "obs-lint")) == []

    def test_raw_clock_in_governed_module_fires(self, tmp_path):
        root = mk_repo(tmp_path, {
            **MD_STUBS,
            "src/repro/serving/engine.py": """\
            import time
            from time import perf_counter

            from repro.obs import get_tracer

            def step():
                get_tracer()
                return perf_counter() - time.time() + time.monotonic()
        """})
        ids = rule_ids(analyze(root, "obs-lint"))
        # perf_counter(), time.time(), time.monotonic() — three reads
        assert ids.count("obs-lint/wall-clock") == 3
        assert "obs-lint/emission" not in ids

    def test_wall_now_and_ungoverned_clocks_are_silent(self, tmp_path):
        root = mk_repo(tmp_path, {
            **MD_STUBS,
            # governed module stamping through the sanctioned API
            "src/repro/serving/engine.py": """\
            from repro.obs import get_tracer, wall_now

            def step(self):
                with get_tracer().wall("engine.decode_step") as sp:
                    t = wall_now()
                return t
        """,
            # raw clocks outside the governed set (drivers, benches) are
            # not obs-lint's business
            "src/repro/launch/serve.py": """\
            import time

            def main():
                return time.perf_counter()
        """})
        assert rule_ids(analyze(root, "obs-lint")) == []

    def test_governed_module_without_tracer_fires_emission(self, tmp_path):
        root = mk_repo(tmp_path, {**MD_STUBS, "src/repro/core/node.py": """\
            def enqueue(qr):
                return qr
        """})
        ids = rule_ids(analyze(root, "obs-lint"))
        assert ids == ["obs-lint/emission"]


class TestDocAnchors:
    def test_missing_required_heading(self, tmp_path):
        files = dict(MD_STUBS)
        files["DESIGN.md"] = DESIGN_OK.replace("## §7 Analysis\n", "")
        root = mk_repo(tmp_path, files)
        findings = analyze(root, "docs-anchors").new
        assert any(f.rule_id == "docs-anchors/required" and "§7" in f.msg
                   for f in findings)

    def test_python_attribution_window(self, tmp_path):
        # the anchor sign is spelled as an escape so THIS file's source
        # carries no attributed dangling anchors for the live pass to see
        sec = "§"
        body = (f'"""Paged admission (DESIGN.md\n'
                f'{sec}6.1-paged) vs dangling (DESIGN.md {sec}9.9); the '
                f'paper\'s {sec}5 and\n'
                f'EXPERIMENTS.md {sec}Roofline have no attribution."""\n'
                f'X = 1\n')
        root = mk_repo(tmp_path, {**MD_STUBS, "src/repro/sim/x.py": body})
        findings = analyze(root, "docs-anchors").new
        # §6.1-paged resolves (wrapped attribution); §9.9 dangles; §5 and
        # §Roofline sit after another anchor / other files — unattributed
        assert [f.rule_id for f in findings] == ["docs-anchors/python"]
        assert f"{sec}9.9" in findings[0].msg


class TestSuppression:
    def test_inline_and_comment_above_suppress(self, tmp_path):
        root = mk_repo(tmp_path, {**MD_STUBS, "src/repro/serving/x.py": """\
            from jax.sharding import get_abstract_mesh  # repro: allow[compat-boundary]

            # justified exception:  # repro: allow[compat-boundary]
            from jax.sharding import set_mesh
        """})
        report = analyze(root, "compat-boundary")
        assert report.new == []
        assert len(report.suppressed) == 2

    def test_suppression_is_rule_scoped(self, tmp_path):
        root = mk_repo(tmp_path, {**MD_STUBS, "src/repro/serving/x.py": """\
            from jax.sharding import get_abstract_mesh  # repro: allow[layering]
        """})
        report = analyze(root, "compat-boundary")
        assert rule_ids(report) == ["compat-boundary"]


class TestBaseline:
    def test_round_trip_grandfathers_then_empties(self, tmp_path):
        root = mk_repo(tmp_path, SEEDED["compat-boundary"])
        strict = analyze(root, "compat-boundary")
        assert strict.new

        bl = root / BASELINE_FILE
        save_baseline(bl, strict.new)
        assert [tuple(k) for k in load_baseline(bl)] == \
            [f.key() for f in sorted(strict.new)]

        # default pickup: run_analysis finds <root>/analysis_baseline.json
        graced = run_analysis(root, rules=["compat-boundary"])
        assert graced.new == []
        assert [f.key() for f in graced.baselined] == \
            [f.key() for f in sorted(strict.new)]

        # a NEW violation still fails even with the baseline in place
        (root / "src/repro/serving/y.py").write_text(
            "from jax.sharding import set_mesh\n")
        report = run_analysis(root, rules=["compat-boundary"])
        assert len(report.new) == 1
        assert report.new[0].path == "src/repro/serving/y.py"

    def test_parse_error_becomes_finding(self, tmp_path):
        root = mk_repo(tmp_path, {**MD_STUBS,
                                  "src/repro/sim/x.py": "def broken(:\n"})
        report = analyze(root)
        assert any(f.rule_id == "parse-error" for f in report.new)


class TestCLI:
    """python -m repro.analysis: exit codes and --json over seeded repos."""

    def _run(self, *args, cwd):
        env = {"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"}
        return subprocess.run(
            [sys.executable, "-m", "repro.analysis", *args],
            capture_output=True, text=True, cwd=cwd, env=env, timeout=60)

    def test_exits_nonzero_on_each_seeded_violation(self, tmp_path):
        for rule, files in SEEDED.items():
            root = mk_repo(tmp_path / rule.replace("/", "_"), files)
            res = self._run("--root", str(root), "--json", cwd=REPO)
            assert res.returncode == 1, f"{rule}: {res.stdout}\n{res.stderr}"
            payload = json.loads(res.stdout)
            assert payload["counts"]["new"] >= 1
            assert any(rule_matches(rule, f["rule_id"])
                       for f in payload["new"]), rule

    def test_exits_zero_on_this_repo(self):
        res = self._run("--root", str(REPO), cwd=REPO)
        assert res.returncode == 0, res.stdout + res.stderr

    def test_list_rules_names_all_six(self):
        res = self._run("--list-rules", cwd=REPO)
        assert res.returncode == 0
        for rule in ALL_RULES:
            assert rule in res.stdout


class TestLivePass:
    """Tier-1 acceptance: the analyzer over THIS repository."""

    def test_all_six_checkers_registered(self):
        assert [c.rule_id for c in all_checkers()] == sorted(ALL_RULES)

    def test_repo_is_clean_and_fast(self):
        report = run_analysis(REPO)
        assert sorted(report.rules) == sorted(ALL_RULES)
        assert report.new == [], "new findings:\n  " + "\n  ".join(
            f.format() for f in report.new)
        assert report.wall_s < 10.0, f"analysis took {report.wall_s:.1f}s"

    def test_committed_baseline_is_empty(self):
        # the goal state (DESIGN.md §7): fix or justify inline, never
        # accumulate grandfathered debt
        assert load_baseline(REPO / BASELINE_FILE) == []

    def test_repo_index_sees_all_scan_dirs(self):
        repo = RepoIndex(REPO)
        files = repo.py_files()
        assert any(f.startswith("src/repro/") for f in files)
        assert any(f.startswith("tests/") for f in files)
        assert any(f.startswith("benchmarks/") for f in files)
        assert repo.module_name("src/repro/sim/executor.py") == \
            "repro.sim.executor"

    def test_finding_format_and_ordering(self):
        a = Finding("r", "a.py", 3, "m")
        b = Finding("r", "a.py", 9, "m")
        assert a.format() == "a.py:3: [r] m"
        assert sorted([b, a]) == [a, b]
        assert a.key() == ("r", "a.py", "m")
