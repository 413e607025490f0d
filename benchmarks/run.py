"""Benchmark harness: one entry per paper table/figure + substrate benches.

Prints ``name,us_per_call,derived`` CSV (one row per artifact).  Roofline
numbers come from ``repro.launch.dryrun`` (see EXPERIMENTS.md §Roofline) —
that path needs 512 host devices and therefore runs as its own process.

``--smoke`` instead runs a <60s end-to-end sanity pass (model forward,
prefill/decode consistency, real engine generation, Pallas kernel vs
oracle, mesh-context sharding) so regressions in the tier-1 command are
caught before a full pytest run::

    PYTHONPATH=src python benchmarks/run.py --smoke

``--bench`` emits a machine-readable ``BENCH_scheduling.json`` (SLO
attainment per mode, avg/p95 latency, simulated requests/s, real-engine
decode tokens/s and admitted concurrency for paged vs slot vs wave
batching, the disagg-vs-colocated TTFT mix, the speculative-vs-paged
decode-heavy comparison with its accepted-length distribution, the pinned
kernel microbench — slot vs paged vs quantized-paged decode/spec-verify
timings at fixed shapes, the autotuned ``pages_per_step``, and the int8
admission 2x demo — the schema-7 ``gossip`` scale-out section:
gossip-digest vs power-of-two probe routing at 100 and 1k sim nodes with
SLO attainment and routing messages-per-request, whose >=3x message cut
at matched SLO is asserted by ``check_bench_schema`` — and, new in
schema 8, the ``prefix_cache`` section (DESIGN.md §6.1-prefix): real-
engine cached-vs-cold TTFT on a shared prefix (cached must be faster),
the simulated zipf-shared-prefix hit rate (>= 0.5), and cache-affinity
vs affinity-blind gossip routing on a hot-origin zipf workload
(affinity must win on aggregate hit rate), and, new in schema 9, the
``obs`` tracing-overhead section (DESIGN.md §Observability): mix-bench
decode tokens/s with the span tracer enabled vs disabled, whose
>= 0.95x ratio is asserted by ``check_bench_schema``) so the
performance trajectory is tracked PR over PR::

    PYTHONPATH=src python benchmarks/run.py --bench

The payload shape is pinned by ``check_bench_schema`` (validated here at
write time and against the checked-in file by ``tests/test_compat.py``, so
schema drift is caught in tier-1).

``--lint`` runs the AST invariant linter (``repro.analysis``,
DESIGN.md §7) over src/tests/benchmarks — a <10s jax-free pass that is
also the first check of ``--smoke`` and whose rule/violation counts are
recorded in the ``lint`` section of the --bench payload::

    PYTHONPATH=src python benchmarks/run.py --lint

``--trace <path>`` runs the traced sim mix (DESIGN.md §Observability):
a small decentralized network with the span tracer live, writing a
Perfetto/Chrome ``trace_event`` JSON to <path> and printing the
per-request latency breakdown.  It asserts the latency partition: for
every completed request, the union of its merged sim-clock span
intervals (route.decide / executor.queue / engine.prefill /
engine.decode / route.return, plus the nested disagg.handoff) must
reconstruct ``CompletedRequest.latency`` within 5%::

    PYTHONPATH=src python benchmarks/run.py --trace out.json
"""

from __future__ import annotations

import argparse
import pathlib
import sys
import time
from typing import List

# allow `python benchmarks/run.py` without the repo root on PYTHONPATH
# (the sibling benchmark modules import as the ``benchmarks`` package,
# and repro imports from src/)
_REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_REPO))
sys.path.insert(0, str(_REPO / "src"))

BENCH_SCHEMA_VERSION = 9

# required keys per payload section; engine modes each carry ENGINE_MODE_KEYS
SIM_MODE_KEYS = ("slo_attainment", "avg_latency_s", "p95_latency_s",
                 "delegation_rate", "n")
ENGINE_MODE_KEYS = ("decode_tokens", "decode_steps", "decode_tokens_per_s",
                    "wall_s", "admitted_concurrency", "max_batch",
                    "kv_budget_tokens")
ENGINE_MODES = ("slot", "wave", "paged")
# schema 3: mixed prompt-heavy/decode-heavy workload, disagg vs colocated
# (DESIGN.md §6.1-disagg) — TTFT per request class and decode throughput
MIX_MODES = ("slot", "paged", "disagg")
MIX_MODE_KEYS = ("avg_ttft_prompt_heavy_s", "avg_ttft_decode_heavy_s",
                 "decode_tokens_per_s", "wall_s", "served")
# schema 4: decode-heavy workload, speculative vs plain paged (DESIGN.md
# §6.1-spec) — accepted-length distribution and effective decode tokens/s
SPEC_MODES = ("paged", "spec")
SPEC_MODE_KEYS = ("decode_tokens", "decode_tokens_per_s", "wall_s", "served")
SPEC_ONLY_KEYS = ("accept_hist", "alpha_ema", "expected_tokens_per_step",
                  "draft_wall_s", "verify_steps")
# schema 5: static-analysis snapshot (DESIGN.md §7) — which rules ran and
# the violation counts by disposition, so a silently growing baseline or
# suppression set shows up in the PR-over-PR artifact diff
LINT_KEYS = ("rules", "new", "suppressed", "baselined", "wall_s")
# schema 6: pinned kernel microbench (DESIGN.md §Perf-kernels) — paged vs
# slot vs quantized-paged decode and spec-verify timings at fixed shapes,
# the pages_per_step the autotune sweep recorded, and the int8 admission
# demo (same page budget, fp vs kv_quant engine) whose 2x is asserted here
KERNEL_DECODE_MODES = ("slot", "paged", "paged_quant")
KERNEL_VERIFY_MODES = ("paged", "paged_quant")
KERNEL_TUNING_KEYS = ("page_size", "head_dim", "hkv", "pages_per_step")
KERNEL_ADMISSION_KEYS = ("num_pages", "page_size", "paged", "paged_quant")
# schema 7: gossip load-dissemination scale-out (DESIGN.md §6.2-gossip) —
# gossip-digest vs power-of-two probe routing at 100 and 1k sim nodes;
# the 10k point stays out of tier-1 behind `-m slow` (tests/test_scaling.py)
GOSSIP_POINTS = ("100", "1000")
GOSSIP_ROUTING_MODES = ("gossip", "probe")
GOSSIP_MODE_KEYS = ("slo_attainment", "p95_latency_s",
                    "routing_msgs_per_req", "gossip_msgs", "probes",
                    "dispatches", "bounces", "delegation_rate", "n",
                    "wall_s")
# schema 8: cross-request prefix caching (DESIGN.md §6.1-prefix) — real
# engine cached-vs-cold TTFT on a shared prefix, the simulated
# zipf-shared-prefix hit rate, and cache-affinity vs affinity-blind
# gossip dispatch on a hot-origin zipf workload
PREFIX_ENGINE_KEYS = ("cold_ttft_s", "cached_ttft_s", "ttft_speedup",
                      "hit_tokens", "cached_pages", "prefix_tokens",
                      "suffix_tokens")
PREFIX_SIM_KEYS = ("hit_rate", "hit_tokens", "lookup_tokens", "served")
PREFIX_ROUTING_MODES = ("affinity", "blind")
PREFIX_ROUTING_KEYS = ("hit_rate", "hit_tokens", "lookup_tokens", "n")
# schema 9: tracing overhead (DESIGN.md §Observability) — mix-workload
# paged decode throughput with the span tracer enabled vs disabled;
# check_bench_schema hard-asserts traced >= 0.95x untraced
OBS_ARMS = ("untraced", "traced")
OBS_ARM_KEYS = ("decode_tokens", "decode_tokens_per_s", "wall_s")
OBS_KEYS = ("workload", "overhead_ratio", "spans", "metrics")


def check_bench_schema(payload: dict) -> None:
    """Raise AssertionError when ``payload`` drifts from the pinned shape."""
    assert payload.get("schema") == BENCH_SCHEMA_VERSION, (
        f"schema {payload.get('schema')} != {BENCH_SCHEMA_VERSION}")
    assert payload.get("bench") == "scheduling"
    sim = payload["sim"]
    for k in ("setting", "wall_s", "requests_per_s", "modes"):
        assert k in sim, f"sim.{k} missing"
    for mode in ("single", "centralized", "decentralized"):
        for k in SIM_MODE_KEYS:
            assert k in sim["modes"][mode], f"sim.modes.{mode}.{k} missing"
    eng = payload["engine"]
    assert "model" in eng, "engine.model missing"
    for mode in ENGINE_MODES:
        assert mode in eng, f"engine.{mode} missing"
        for k in ENGINE_MODE_KEYS:
            assert k in eng[mode], f"engine.{mode}.{k} missing"
    for k in ("page_size", "num_pages", "preempted"):
        assert k in eng["paged"], f"engine.paged.{k} missing"
    mix = payload["mix"]
    for k in ("workload", "ttft_speedup_prompt_heavy"):
        assert k in mix, f"mix.{k} missing"
    for mode in MIX_MODES:
        assert mode in mix, f"mix.{mode} missing"
        for k in MIX_MODE_KEYS:
            assert k in mix[mode], f"mix.{mode}.{k} missing"
    for k in ("handoffs", "handoff_bytes", "transfer_inflight_peak"):
        assert k in mix["disagg"], f"mix.disagg.{k} missing"
    # schema 6 perf bar: the tuned paged engine (carry-borne pools, donated
    # buffers, device-resident width-trimmed tables — DESIGN.md
    # §Perf-kernels) must not decode slower than slot batching on the mix
    assert (mix["paged"]["decode_tokens_per_s"]
            >= mix["slot"]["decode_tokens_per_s"]), (
        f"mix paged decode {mix['paged']['decode_tokens_per_s']} tok/s "
        f"regressed below slot {mix['slot']['decode_tokens_per_s']}")
    spec = payload["spec"]
    for k in ("workload", "spec_k", "speedup_decode_tokens_per_s"):
        assert k in spec, f"spec.{k} missing"
    for mode in SPEC_MODES:
        assert mode in spec, f"spec.{mode} missing"
        for k in SPEC_MODE_KEYS:
            assert k in spec[mode], f"spec.{mode}.{k} missing"
    for k in SPEC_ONLY_KEYS:
        assert k in spec["spec"], f"spec.spec.{k} missing"
    assert len(spec["spec"]["accept_hist"]) == spec["spec_k"] + 1
    lint = payload["lint"]
    for k in LINT_KEYS:
        assert k in lint, f"lint.{k} missing"
    assert lint["new"] == 0, "lint.new must be 0 in a committed artifact"
    kern = payload["kernel"]
    for k in ("shapes", "tuning", "decode", "spec_verify", "admission"):
        assert k in kern, f"kernel.{k} missing"
    for mode in KERNEL_DECODE_MODES:
        assert mode in kern["decode"], f"kernel.decode.{mode} missing"
        assert "us_per_call" in kern["decode"][mode], \
            f"kernel.decode.{mode}.us_per_call missing"
    for mode in KERNEL_VERIFY_MODES:
        assert mode in kern["spec_verify"], f"kernel.spec_verify.{mode} missing"
        assert "us_per_call" in kern["spec_verify"][mode], \
            f"kernel.spec_verify.{mode}.us_per_call missing"
    for k in KERNEL_TUNING_KEYS:
        assert k in kern["tuning"], f"kernel.tuning.{k} missing"
    adm = kern["admission"]
    for k in KERNEL_ADMISSION_KEYS:
        assert k in adm, f"kernel.admission.{k} missing"
    gos = payload["gossip"]
    for k in ("workload", "slo_s", "points"):
        assert k in gos, f"gossip.{k} missing"
    for pt in GOSSIP_POINTS:
        assert pt in gos["points"], f"gossip.points.{pt} missing"
        entry = gos["points"][pt]
        for k in ("msgs_ratio", "slo_gap"):
            assert k in entry, f"gossip.points.{pt}.{k} missing"
        for mode in GOSSIP_ROUTING_MODES:
            assert mode in entry, f"gossip.points.{pt}.{mode} missing"
            for k in GOSSIP_MODE_KEYS:
                assert k in entry[mode], \
                    f"gossip.points.{pt}.{mode}.{k} missing"
    # schema 7 scale-out bar (ROADMAP item 1 / DESIGN.md §6.2-gossip): at
    # 1k nodes the digest plane must cut routing messages-per-request at
    # least 3x while holding SLO attainment within 2 points of the
    # power-of-two probe baseline
    big = gos["points"]["1000"]
    assert (big["gossip"]["routing_msgs_per_req"]
            < big["probe"]["routing_msgs_per_req"]), (
        f"gossip routing msgs/req {big['gossip']['routing_msgs_per_req']} "
        f"not below probe {big['probe']['routing_msgs_per_req']} at 1k nodes")
    assert big["msgs_ratio"] >= 3.0, (
        f"gossip message cut {big['msgs_ratio']}x < 3x at 1k nodes")
    assert big["slo_gap"] <= 0.02, (
        f"gossip-vs-probe SLO gap {big['slo_gap']} > 0.02 at 1k nodes")
    # schema 6 capacity bar: int8 KV pages halve bytes per token, so on the
    # same page budget the kv_quant engine must keep at least twice the
    # concurrent residents of the fp paged engine (DESIGN.md §6.1-paged)
    assert adm["paged_quant"] >= 2 * adm["paged"], (
        f"quantized admission {adm['paged_quant']} < "
        f"2x fp admission {adm['paged']}")
    # schema 8: cross-request prefix caching (DESIGN.md §6.1-prefix)
    pc = payload["prefix_cache"]
    for k in ("workload", "engine", "sim", "routing"):
        assert k in pc, f"prefix_cache.{k} missing"
    for k in PREFIX_ENGINE_KEYS:
        assert k in pc["engine"], f"prefix_cache.engine.{k} missing"
    for k in PREFIX_SIM_KEYS:
        assert k in pc["sim"], f"prefix_cache.sim.{k} missing"
    for mode in PREFIX_ROUTING_MODES:
        assert mode in pc["routing"], f"prefix_cache.routing.{mode} missing"
        for k in PREFIX_ROUTING_KEYS:
            assert k in pc["routing"][mode], \
                f"prefix_cache.routing.{mode}.{k} missing"
    # hard bars: a prefix hit must serve its first token faster than the
    # cold prefill of the same prompt; the zipf workload must actually
    # exercise the cache; and cache-affinity dispatch must beat
    # affinity-blind gossip routing on aggregate hit rate
    assert pc["engine"]["cached_ttft_s"] < pc["engine"]["cold_ttft_s"], (
        f"cached TTFT {pc['engine']['cached_ttft_s']} not below cold "
        f"{pc['engine']['cold_ttft_s']}")
    assert pc["sim"]["hit_rate"] >= 0.5, (
        f"zipf-shared-prefix sim hit rate {pc['sim']['hit_rate']} < 0.5")
    assert (pc["routing"]["affinity"]["hit_rate"]
            > pc["routing"]["blind"]["hit_rate"]), (
        f"cache-affinity hit rate {pc['routing']['affinity']['hit_rate']} "
        f"not above blind {pc['routing']['blind']['hit_rate']}")
    # schema 9: tracing overhead (DESIGN.md §Observability)
    obs = payload["obs"]
    for k in OBS_KEYS:
        assert k in obs, f"obs.{k} missing"
    for arm in OBS_ARMS:
        assert arm in obs, f"obs.{arm} missing"
        for k in OBS_ARM_KEYS:
            assert k in obs[arm], f"obs.{arm}.{k} missing"
    assert obs["spans"] > 0, "traced arm recorded no spans"
    # hard bar: spans are cheap enough to leave on — traced mix decode
    # throughput must hold >= 0.95x of the untraced arm
    assert obs["overhead_ratio"] >= 0.95, (
        f"tracing overhead: traced decode "
        f"{obs['traced']['decode_tokens_per_s']} tok/s is "
        f"{obs['overhead_ratio']}x untraced "
        f"{obs['untraced']['decode_tokens_per_s']} (< 0.95x)")


def _lint(verbose: bool = True) -> int:
    """Run the AST invariant linter (DESIGN.md §7); jax-free and <10s."""
    from repro.analysis import run_analysis
    report = run_analysis(_REPO)
    if verbose:
        for f in report.new:
            print(f"  {f.format()}", flush=True)
        print(f"lint: {len(report.rules)} checkers, {len(report.new)} new "
              f"/ {len(report.suppressed)} suppressed / "
              f"{len(report.baselined)} baselined in {report.wall_s:.2f}s",
              flush=True)
    return 0 if report.ok else 1


def _traced_sim_mix(n_requests: int = 30, seed: int = 0):
    """Small decentralized sim mix with the span tracer live (jax-free).

    Duels, churn, and rebalancing are off, so each completed request's
    lifecycle spans — route.decide, executor.queue, engine.prefill,
    engine.decode, route.return (plus the nested disagg.handoff on the
    disagg node) — tile [arrival, finish] exactly (DESIGN.md
    §Observability).  Returns (metrics, tracer, network).
    """
    from repro.core import DuelParams, Network, Node, NodePolicy
    from repro.obs import Tracer, set_tracer
    from repro.sim import DisaggTokenBucketExecutor, make_profile
    from repro.sim.workload import Request
    net = Network(mode="decentralized", seed=seed,
                  duel=DuelParams(p_d=0.0, k_judges=0), init_balance=100.0)
    # offload-eager policy (low utilization knee) so the trace actually
    # carries delegation legs (route.decide dispatch spans + route.return)
    # rather than an everything-local run
    pol = NodePolicy(accept_freq=1.0, offload_freq=1.0,
                     offload_queue_threshold=0, offload_util_threshold=0.3)
    for i in range(4):
        # one disagg backend so traces carry disagg.handoff spans nested
        # inside engine.decode (exercises the merged-interval coverage)
        factory = ((lambda node: DisaggTokenBucketExecutor(node.profile))
                   if i == 3 else None)
        net.add_node(Node(f"n{i}",
                          make_profile("qwen3-8b", "RTX3090", "sglang",
                                       quality=0.5),
                          policy=pol, executor_factory=factory))
    reqs = []
    for i in range(n_requests):       # mixed prompt-heavy / decode-heavy,
        heavy = i % 3 == 0            # all hot on n0 so it must delegate
        reqs.append(Request(rid=f"t{i:03d}", origin="n0",
                            arrival=0.15 * i,
                            prompt_tokens=512 if heavy else 48,
                            output_tokens=16 if heavy else 96,
                            slo_s=120.0))
    tr = Tracer()
    old = set_tracer(tr)
    try:
        m = net.run(reqs, until=10_000.0, rebalance_interval=0.0)
    finally:
        set_tracer(old)
    return m, tr, net


def _span_coverage_errors(metrics, spans) -> dict:
    """Per-rid relative error of the span-reconstructed latency.

    For each completed request, merge its sim-clock span intervals and
    compare the union's length to ``CompletedRequest.latency`` — the
    lifecycle partition of DESIGN.md §Observability says they match
    (spans may nest, e.g. disagg.handoff inside engine.decode, so a
    plain sum over-counts; the merged union does not).
    """
    from repro.obs import SIM
    by = {}
    for s in spans:
        if s.rid and s.clock == SIM:
            by.setdefault(s.rid, []).append((s.t0, s.t1))
    errs = {}
    for c in metrics.completed:
        covered, hi = 0.0, None
        for t0, t1 in sorted(by.get(c.rid, ())):
            if hi is None or t0 > hi:
                covered += t1 - t0
                hi = t1
            elif t1 > hi:
                covered += t1 - hi
                hi = t1
        errs[c.rid] = abs(covered - c.latency) / max(c.latency, 1e-9)
    return errs


def _trace(out_path: str) -> int:
    """Write a Perfetto trace of the sim mix; assert the latency partition."""
    from repro.obs import breakdown_report, write_chrome_trace
    t0 = time.perf_counter()
    m, tr, _net = _traced_sim_mix()
    payload = write_chrome_trace(tr.spans, out_path)
    print(breakdown_report(tr.spans, limit=5))
    errs = _span_coverage_errors(m, tr.spans)
    worst = max(errs.values()) if errs else 1.0
    print(f"trace: {len(m.completed)} requests, {len(tr.spans)} spans, "
          f"{len(payload['traceEvents'])} events -> {out_path} "
          f"({time.perf_counter() - t0:.1f}s)")
    print(f"trace: worst span-coverage error {worst:.4f} "
          f"(merged sim spans vs CompletedRequest.latency)")
    assert worst <= 0.05, (
        f"span partition broken: worst relative coverage error {worst:.4f} "
        f"> 0.05 (DESIGN.md §Observability)")
    return 0


def _smoke() -> int:
    """End-to-end sanity: fail fast and loudly, return a shell exit code."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    t_start = time.perf_counter()
    failures: List[str] = []

    def check(name, fn):
        t0 = time.perf_counter()
        try:
            fn()
            print(f"  ok   {name} ({time.perf_counter() - t0:.1f}s)",
                  flush=True)
        except Exception as e:  # noqa: BLE001 — collect, report all
            failures.append(name)
            print(f"  FAIL {name}: {e!r}", flush=True)

    def model_roundtrip():
        from repro.configs import get_config
        from repro.models import registry
        cfg = get_config("qwen3-8b").smoke().replace(dtype="float32")
        params = registry.init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                  cfg.vocab_size)
        fam = registry.get_family(cfg)
        lg, cache = fam.prefill(params, cfg, {"tokens": toks}, q_chunk=32,
                                kv_chunk=32, capacity=48)
        assert not bool(jnp.isnan(lg).any())
        nt = jnp.argmax(lg, -1).astype(jnp.int32)
        lg2, _ = fam.decode_step(params, cfg, cache, nt)
        full = jnp.concatenate([toks, nt], axis=1)
        ref = registry.apply_logits(params, cfg, {"tokens": full},
                                    q_chunk=32, kv_chunk=32)
        np.testing.assert_allclose(np.asarray(lg2),
                                   np.asarray(ref[:, -1:]),
                                   atol=2e-4, rtol=2e-3)

    def engine_generates():
        from repro.configs import get_config
        from repro.models import registry
        from repro.serving import Engine, GenRequest
        cfg = get_config("qwen3-8b").smoke().replace(dtype="float32")
        params = registry.init(jax.random.PRNGKey(0), cfg)
        eng = Engine(cfg, params, max_batch=2, bucket=16)
        prompt = np.arange(2, 14).astype(np.int32)
        done = eng.serve([GenRequest(rid="a", tokens=prompt, max_new=4),
                          GenRequest(rid="b", tokens=prompt, max_new=2,
                                     temperature=1.0)])
        assert len(done[0].result) <= 4 and len(done[1].result) <= 2

    def paged_engine_matches_slot():
        from repro.configs import get_config
        from repro.models import registry
        from repro.serving import Engine, GenRequest
        cfg = get_config("qwen3-8b").smoke().replace(dtype="float32")
        params = registry.init(jax.random.PRNGKey(0), cfg)

        def mk():
            prompts = [np.random.default_rng(i).integers(2, 400, size=8 + 4 * i)
                       .astype(np.int32) for i in range(3)]
            return [GenRequest(rid=f"r{i}", tokens=prompts[i],
                               max_new=[4, 10, 4][i]) for i in range(3)]

        slot = Engine(cfg, params, max_batch=2, bucket=16)
        paged = Engine(cfg, params, max_batch=2, bucket=16, paged=True,
                       page_size=16, num_pages=5)   # tight: preempts
        rs, rp = slot.serve(mk()), paged.serve(mk())
        for a, b in zip(rs, rp):
            np.testing.assert_array_equal(a.result, b.result)
        snap = paged.load_snapshot()
        assert snap["pages_used"] == 0 and snap["free_pages"] == 5

    def disagg_matches_colocated_paged():
        from repro.configs import get_config
        from repro.models import registry
        from repro.serving import DisaggEngineExecutor, Engine, GenRequest
        cfg = get_config("qwen3-8b").smoke().replace(dtype="float32")
        params = registry.init(jax.random.PRNGKey(0), cfg)

        def mk():
            prompts = [np.random.default_rng(i).integers(2, 400, size=6 + 5 * i)
                       .astype(np.int32) for i in range(3)]
            return [GenRequest(rid=f"r{i}", tokens=prompts[i],
                               max_new=[6, 9, 4][i]) for i in range(3)]

        ref = Engine(cfg, params, max_batch=3, bucket=16, paged=True,
                     page_size=16)
        rs = {r.rid: r.result for r in ref.serve(mk())}
        ex = DisaggEngineExecutor(
            Engine(cfg, params, max_batch=2, bucket=16, paged=True,
                   page_size=16),
            Engine(cfg, params, max_batch=3, bucket=16, paged=True,
                   page_size=16))
        ex.bind(None, lambda r, st, ft: None)
        for r in mk():
            assert ex.admit(r)
        done = {r.rid: r.result for r in ex.drain()}
        for rid in rs:
            np.testing.assert_array_equal(rs[rid], done[rid])
        assert ex.prefill.stats.handoffs == 3
        assert ex.prefill.load_snapshot()["pages_used"] == 0
        assert ex.decode.load_snapshot()["pages_used"] == 0

    def spec_engine_matches_paged():
        from repro.configs import get_config
        from repro.models import registry
        from repro.serving import Engine, GenRequest
        cfg = get_config("qwen3-8b").smoke().replace(dtype="float32")
        params = registry.init(jax.random.PRNGKey(0), cfg)
        draft_cfg = cfg.draft()
        draft_params = registry.init(jax.random.PRNGKey(9), draft_cfg)

        def mk():
            prompts = [np.random.default_rng(i).integers(2, 400, size=6 + 3 * i)
                       .astype(np.int32) for i in range(3)]
            return [GenRequest(rid=f"r{i}", tokens=prompts[i],
                               max_new=[6, 9, 4][i]) for i in range(3)]

        ref = Engine(cfg, params, max_batch=3, bucket=16, paged=True,
                     page_size=16)
        rs = {r.rid: r.result for r in ref.serve(mk())}
        spec = Engine(cfg, params, max_batch=3, bucket=16, paged=True,
                      page_size=16, spec_draft=(draft_cfg, draft_params),
                      spec_k=3)
        rp = {r.rid: r.result for r in spec.serve(mk())}
        for rid in rs:
            np.testing.assert_array_equal(rs[rid], rp[rid])
        assert spec.stats.spec_steps > 0
        assert spec.load_snapshot()["pages_used"] == 0

    def prefix_cache_parity():
        # cached-vs-cold bit parity + hit-rate sanity (DESIGN.md
        # §6.1-prefix): serving the same shared prefix twice must produce
        # bit-identical greedy output to a cache-less engine while actually
        # hitting the cache, with the page pool reconciling exactly
        from repro.configs import get_config
        from repro.models import registry
        from repro.serving import Engine, GenRequest
        cfg = get_config("qwen3-8b").smoke().replace(dtype="float32")
        params = registry.init(jax.random.PRNGKey(0), cfg)
        prefix = np.random.default_rng(5).integers(2, 400, size=35) \
            .astype(np.int32)

        def mk(rid, sufseed):
            suf = np.random.default_rng(sufseed).integers(2, 400, size=7) \
                .astype(np.int32)
            return GenRequest(rid=rid,
                              tokens=np.concatenate([prefix, suf]),
                              max_new=4)

        cold = Engine(cfg, params, max_batch=2, bucket=16, paged=True,
                      page_size=16, num_pages=32)
        ref = {r.rid: np.asarray(r.result)
               for r in cold.serve([mk("a", 1), mk("b", 2)])}
        warm = Engine(cfg, params, max_batch=2, bucket=16, paged=True,
                      page_size=16, num_pages=32, prefix_cache=True)
        got = {}
        for rid, ss in (("a", 1), ("b", 2)):   # sequential: b hits a's pages
            got.update({r.rid: np.asarray(r.result)
                        for r in warm.serve([mk(rid, ss)])})
        for rid in ref:
            np.testing.assert_array_equal(ref[rid], got[rid])
        assert warm.prefix_hit_tokens > 0, "no prefix-cache hits"
        acct = warm.debug_page_accounting()
        assert acct["cold"] > 0 and acct["held"] == 0

    def pallas_kernel_matches_oracle():
        from repro.kernels.flash_attention import flash_attention_tpu
        from repro.kernels.ref import reference_attention
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (1, 128, 4, 64))
        k = jax.random.normal(ks[1], (1, 128, 2, 64))
        v = jax.random.normal(ks[2], (1, 128, 2, 64))
        out = flash_attention_tpu(q, k, v, causal=True, block_q=64,
                                  block_k=64)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=1e-3)

    def mesh_context_sharding():
        from repro.compat import meshenv
        from repro.launch.mesh import make_host_mesh
        from repro.models import common as cm
        mesh = make_host_mesh()
        with meshenv.mesh_context(mesh):
            assert meshenv.axis_names() == ("data", "model")
            y = jax.jit(lambda a: cm.shard(a, "batch", "model"))(
                jnp.ones((2, 4)))
            assert y.shape == (2, 4)
        assert meshenv.current_mesh() is None

    def protocol_sim():
        from repro.core import DuelParams, Network, Node, NodePolicy
        from repro.sim import make_profile
        from repro.sim.workload import Request
        net = Network(mode="decentralized", seed=0,
                      duel=DuelParams(p_d=0.1, k_judges=1),
                      init_balance=100.0)
        for i in range(3):
            net.add_node(Node(f"n{i}", make_profile("qwen3-8b", "RTX3090",
                                                    "sglang", quality=0.5),
                              policy=NodePolicy()))
        reqs = [Request(rid=f"r{i}", origin="n0", arrival=0.05 * i,
                        prompt_tokens=16, output_tokens=8, slo_s=30.0)
                for i in range(20)]
        m = net.run(reqs, until=300.0)
        assert len(m.completed) >= 20

    def gossip_probe_parity():
        # fast scale-out parity (DESIGN.md §6.2-gossip): on a small pool the
        # digest plane must complete the same workload as probe routing
        # without spending more routing messages per request
        from benchmarks.scaling import run_scale_point
        point = dict(hot=2, hot_ia=1.0, bg_ia=16.0, t_end=15.0,
                     gossip_interval=1.0, view_cap=None)
        res = {r: run_scale_point(20, r, point=point)
               for r in ("gossip", "probe")}
        g, p = res["gossip"], res["probe"]
        assert g["n"] == g["n_submitted"], \
            f"gossip dropped requests: {g['n']}/{g['n_submitted']}"
        assert p["n"] == p["n_submitted"], \
            f"probe dropped requests: {p['n']}/{p['n_submitted']}"
        assert g["routing_msgs_per_req"] <= p["routing_msgs_per_req"], (
            f"gossip routing cost {g['routing_msgs_per_req']} msgs/req "
            f"above probe {p['routing_msgs_per_req']}")

    def analysis_clean():
        assert _lint(verbose=False) == 0, \
            "repro.analysis found new violations (run --lint for details)"

    def trace_roundtrip():
        # <10s jax-free trace round-trip (DESIGN.md §Observability): run a
        # small traced sim, write the Chrome trace to a temp file, and check
        # that the JSON parses, spans nest inside their request's lifetime,
        # every completed request carries the route->admit->prefill chain,
        # and the merged sim spans reconstruct its measured latency
        import json
        import tempfile

        from repro.obs import SIM, write_chrome_trace
        m, tr, _net = _traced_sim_mix(n_requests=12)
        assert m.completed, "traced sim completed nothing"
        with tempfile.TemporaryDirectory() as td:
            p = pathlib.Path(td) / "trace.json"
            write_chrome_trace(tr.spans, p)
            evs = json.loads(p.read_text())["traceEvents"]
        assert any(e["ph"] == "X" for e in evs), "no complete events"
        by_rid = {}
        for s in tr.spans:
            if s.rid:
                by_rid.setdefault(s.rid, []).append(s)
        errs = _span_coverage_errors(m, tr.spans)
        for c in m.completed:
            names = {s.name for s in by_rid.get(c.rid, ())}
            for need in ("route.decide", "executor.queue", "executor.admit",
                         "engine.prefill", "engine.decode"):
                assert need in names, f"{c.rid} missing {need} span"
            for s in by_rid[c.rid]:     # nesting: inside [arrival, finish]
                if s.clock == SIM:
                    assert (s.t0 >= c.arrival - 1e-9
                            and s.t1 <= c.finish + 1e-9), \
                        f"{c.rid} span {s.name} outside its lifecycle"
            assert errs[c.rid] <= 0.05, \
                f"{c.rid} span coverage error {errs[c.rid]:.4f} > 0.05"

    print("smoke: end-to-end sanity pass", flush=True)
    check("static analysis (repro.analysis)", analysis_clean)
    check("trace round-trip (spans nest, latency partition)",
          trace_roundtrip)
    check("model forward + prefill/decode consistency", model_roundtrip)
    check("serving engine generation", engine_generates)
    check("paged engine greedy-matches slot engine", paged_engine_matches_slot)
    check("disagg KV handoff greedy-matches colocated paged",
          disagg_matches_colocated_paged)
    check("speculative engine greedy-matches paged engine",
          spec_engine_matches_paged)
    check("prefix cache cached-vs-cold parity + hit rate",
          prefix_cache_parity)
    check("pallas flash kernel vs oracle", pallas_kernel_matches_oracle)
    check("mesh context + sharding constraint", mesh_context_sharding)
    check("decentralized protocol sim", protocol_sim)
    check("gossip-vs-probe routing parity (20-node pool)",
          gossip_probe_parity)
    dt = time.perf_counter() - t_start
    if failures:
        print(f"smoke FAILED ({len(failures)}): {failures} in {dt:.1f}s",
              flush=True)
        return 1
    print(f"smoke OK in {dt:.1f}s", flush=True)
    return 0


def _bench(out_path: str) -> int:
    """Machine-readable perf snapshot: scheduling sim + real-engine decode."""
    import json

    import jax
    import numpy as np

    payload = {"schema": BENCH_SCHEMA_VERSION, "bench": "scheduling"}

    # --- simulated scheduling (paper Fig 4 / Table 2, setting1) -------------
    from benchmarks.scheduling import run_setting
    t0 = time.perf_counter()
    r = run_setting("setting1")
    sim_wall = time.perf_counter() - t0
    n_total = sum(r[m]["n"] for m in ("single", "centralized", "decentralized"))
    payload["sim"] = {
        "setting": "setting1",
        "wall_s": round(sim_wall, 3),
        "requests_per_s": round(n_total / max(sim_wall, 1e-9), 1),
        "modes": {
            mode: {
                "slo_attainment": round(r[mode]["slo"], 4),
                "avg_latency_s": round(r[mode]["avg_latency"], 2),
                "p95_latency_s": round(r[mode]["p95_latency"], 2),
                "delegation_rate": round(r[mode]["delegation_rate"], 3),
                "n": r[mode]["n"],
            } for mode in ("single", "centralized", "decentralized")
        },
    }

    # --- real engine: slot-based continuous batching vs wave batching ------
    from repro.configs import get_config
    from repro.models import registry
    from repro.serving import Engine, GenRequest
    cfg = get_config("qwen3-8b").smoke().replace(dtype="float32")
    params = registry.init(jax.random.PRNGKey(0), cfg)
    prompts = [np.random.default_rng(i).integers(2, 400, size=12 + i)
               .astype(np.int32) for i in range(6)]
    budgets = [4, 32, 4, 32, 4, 16]

    def mk():
        return [GenRequest(rid=f"r{i}", tokens=prompts[i], max_new=budgets[i])
                for i in range(len(prompts))]

    # slot/wave reserve pad(prompt)+pad(max_new) tokens per slot; the paged
    # engine gets the slot engine's MEASURED kv budget as pages but admits
    # on prompt pages only, so more requests are resident concurrently
    # (admitted_concurrency) on the same memory
    page_size = 16
    engine_kw = {
        "slot": dict(max_batch=2, continuous=True),
        "wave": dict(max_batch=2, continuous=False),
        "paged": dict(max_batch=4, paged=True, page_size=page_size),
    }
    engine_out = {}
    for label in ("slot", "wave", "paged"):
        from repro.serving.engine import EngineStats
        eng = Engine(cfg, params, bucket=16, **engine_kw[label])
        eng.serve(mk())          # warm the per-instance jit caches
        eng.stats = EngineStats()
        t0 = time.perf_counter()
        eng.serve(mk())          # timed run reuses the compiled steps
        wall = time.perf_counter() - t0
        snap = eng.load_snapshot()
        engine_out[label] = {
            "max_batch": engine_kw[label]["max_batch"],
            "kv_budget_tokens": snap["kv_budget"],
            "decode_tokens": eng.stats.decode_tokens,
            "decode_steps": eng.stats.decode_steps,
            # decode throughput over wall time spent inside decode_step, so
            # prefill batching differences don't pollute the metric
            "decode_tokens_per_s": round(
                eng.stats.decode_tokens / max(eng.stats.decode_wall_s, 1e-9),
                1),
            "wall_s": round(wall, 3),
            "admitted_concurrency": eng.stats.peak_resident,
        }
        if label == "slot":
            # hand the paged engine exactly the slot engine's KV budget
            engine_kw["paged"]["num_pages"] = snap["kv_budget"] // page_size
        elif label == "paged":
            engine_out[label].update(page_size=page_size,
                                     num_pages=engine_kw[label]["num_pages"],
                                     preempted=eng.stats.preempted)
    payload["engine"] = {"model": cfg.name, **engine_out}

    # --- mixed prompt-heavy/decode-heavy workload: disagg vs colocated ------
    # (DESIGN.md §6.1-disagg) Decode-heavy requests are submitted first and
    # monopolize a colocated engine's two slots for their long decode, so
    # the prompt-heavy requests behind them wait ~the whole decode for their
    # first token.  A disaggregated pair prefills them immediately on the
    # idle prefill engine (which serves the first token), so their TTFT
    # collapses to ~prefill time even while the decode engine is saturated.
    from repro.serving import DisaggEngineExecutor, EngineExecutor
    from repro.serving.engine import EngineStats as _ES

    def mk_mix():
        rng = np.random.default_rng(7)
        reqs = [GenRequest(rid=f"dec{i}",
                           tokens=rng.integers(2, 400, size=8)
                           .astype(np.int32), max_new=48) for i in range(2)]
        reqs += [GenRequest(rid=f"pro{i}",
                            tokens=rng.integers(2, 400, size=96)
                            .astype(np.int32), max_new=4) for i in range(3)]
        return reqs

    def mk_executor(label):
        kw = dict(bucket=16, max_batch=2)
        if label == "slot":
            return EngineExecutor(Engine(cfg, params, **kw))
        if label == "paged":
            return EngineExecutor(Engine(cfg, params, paged=True,
                                         page_size=page_size, num_pages=64,
                                         **kw))
        return DisaggEngineExecutor(
            Engine(cfg, params, paged=True, page_size=page_size, **kw),
            Engine(cfg, params, paged=True, page_size=page_size,
                   num_pages=64, **kw))

    def run_mix(ex, track_inflight=False):
        done = []
        ex.bind(None, lambda r, st_, ft: done.append(r))
        for r in mk_mix():
            assert ex.admit(r)
        # optionally sample the executor-side load report while stepping:
        # disagg surfaces its in-flight KV transfers there (ExecutorLoad
        # .transfer_inflight / .handoff_bytes), so the mix section can
        # record how deep the handoff pipeline actually ran — only done on
        # an UNTIMED pass, so the per-step snapshot cost never perturbs
        # the wall/TTFT numbers tracked PR over PR
        peak_inflight = 0
        while ex.has_work():
            ex.step()
            if track_inflight:
                peak_inflight = max(peak_inflight,
                                    ex.load().transfer_inflight)
        return done, peak_inflight

    mix_out = {}
    for label in MIX_MODES:
        ex = mk_executor(label)
        # warm the per-instance jit caches TWICE: the slot engine's cache
        # capacity grows during the first pass, so only the second pass
        # compiles the shapes the timed run will hit.  The second (warm,
        # untimed, same deterministic workload) pass also records the
        # disagg transfer-pipeline peak.
        run_mix(ex)
        _, peak_inflight = run_mix(ex, track_inflight=(label == "disagg"))
        engines = ([ex.prefill, ex.decode] if label == "disagg"
                   else [ex.engine])
        for e in engines:
            e.stats = _ES()
        t0 = time.perf_counter()
        done, _ = run_mix(ex)            # timed run reuses compiled steps
        wall = time.perf_counter() - t0
        st = ex.engine_stats()
        ttft = {r.rid: r.first_token_at - r.enqueued_at for r in done}
        mix_out[label] = {
            "served": len(done),
            "avg_ttft_prompt_heavy_s": round(float(np.mean(
                [v for k, v in ttft.items() if k.startswith("pro")])), 4),
            "avg_ttft_decode_heavy_s": round(float(np.mean(
                [v for k, v in ttft.items() if k.startswith("dec")])), 4),
            "decode_tokens_per_s": round(
                st.decode_tokens / max(st.decode_wall_s, 1e-9), 1),
            "wall_s": round(wall, 3),
        }
        if label == "disagg":
            mix_out[label].update(handoffs=st.handoffs,
                                  handoff_bytes=st.handoff_bytes,
                                  transfer_inflight_peak=peak_inflight)
    payload["mix"] = {
        "workload": "2 decode-heavy (prompt 8, out 48) then "
                    "3 prompt-heavy (prompt 96, out 4), max_batch 2",
        "ttft_speedup_prompt_heavy": round(
            mix_out["paged"]["avg_ttft_prompt_heavy_s"]
            / max(mix_out["disagg"]["avg_ttft_prompt_heavy_s"], 1e-9), 2),
        **mix_out,
    }

    # --- decode-heavy workload: speculative vs plain paged (§6.1-spec) ------
    # The draft here IS the target (same params), the regime where drafts
    # always agree, so every verify forward emits spec_k + 1 tokens.
    # decode_tokens_per_s is EFFECTIVE target-side decode throughput:
    # emitted tokens over wall time inside target decode/verify jits — the
    # draft's own (stand-in, full-size) cost is reported separately as
    # spec.draft_wall_s, since a production draft is ~10x smaller.
    from repro.serving import SpecEngineExecutor
    from repro.sim.executor import spec_expected_tokens
    spec_k = 4

    def mk_spec():
        rng = np.random.default_rng(11)
        return [GenRequest(rid=f"s{i}",
                           tokens=rng.integers(2, 400, size=10)
                           .astype(np.int32), max_new=40) for i in range(3)]

    def run_spec(ex):
        done = []
        ex.bind(None, lambda r, st_, ft: done.append(r))
        for r in mk_spec():
            assert ex.admit(r)
        while ex.has_work():
            ex.step()
        return done

    spec_out = {}
    for label in SPEC_MODES:
        # ample page pool (num_pages=64) on BOTH engines: recompute
        # preemption would replay tokens and pollute the throughput
        # comparison with recompute work
        if label == "paged":
            ex = EngineExecutor(Engine(cfg, params, bucket=16, max_batch=3,
                                       paged=True, page_size=page_size,
                                       num_pages=64))
        else:
            ex = SpecEngineExecutor(Engine(
                cfg, params, bucket=16, max_batch=3, paged=True,
                page_size=page_size, num_pages=64,
                spec_draft=(cfg, params), spec_k=spec_k))
        run_spec(ex)
        run_spec(ex)                     # warm the per-instance jit caches
        eng = ex.engine
        eng.stats = _ES()
        if label == "spec":
            eng.spec_accept_hist = [0] * (spec_k + 1)
        t0 = time.perf_counter()
        done = run_spec(ex)              # timed run reuses compiled steps
        wall = time.perf_counter() - t0
        st = ex.engine_stats()
        spec_out[label] = {
            "served": len(done),
            "decode_tokens": st.decode_tokens,
            "decode_tokens_per_s": round(
                st.decode_tokens / max(st.decode_wall_s, 1e-9), 1),
            "wall_s": round(wall, 3),
        }
        if label == "spec":
            spec_out[label].update(
                accept_hist=list(eng.spec_accept_hist),
                alpha_ema=round(eng.spec_alpha, 4),
                expected_tokens_per_step=round(
                    spec_expected_tokens(eng.spec_alpha, spec_k), 3),
                draft_wall_s=round(st.draft_wall_s, 3),
                verify_steps=st.spec_steps)
    payload["spec"] = {
        "workload": "3 decode-heavy requests (prompt 10, out 40), "
                    "max_batch 3; draft = target (always agrees)",
        "spec_k": spec_k,
        "speedup_decode_tokens_per_s": round(
            spec_out["spec"]["decode_tokens_per_s"]
            / max(spec_out["paged"]["decode_tokens_per_s"], 1e-9), 2),
        **spec_out,
    }

    # --- pinned kernel microbench (DESIGN.md §Perf-kernels) -----------------
    # Fixed shapes, forced Pallas path (interpreted off the TPU): slot
    # (contiguous cache) vs paged (block tables) vs quantized-paged (int8
    # pools + scale pools) decode, plus the multi-token spec-verify pair.
    # The fp paged entries are bit-exactness-tested elsewhere
    # (tests/test_kernels.py); here the timings and the autotuned
    # pages_per_step are tracked PR over PR so a grid/tuning regression
    # shows up in the artifact diff.
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.ref import paged_decode_ref, paged_decode_quant_ref
    from repro.kernels.tuning import autotune_paged_decode
    from repro.models.attention import kv_quantize

    kb, kh, khkv, kd = 2, 8, 2, 64
    kpage, kmaxp, kpool, spec_k = 16, 4, 16, 3
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    kq1 = jax.random.normal(ks[0], (kb, 1, kh, kd), jnp.float32)
    kqv = jax.random.normal(ks[1], (kb, spec_k + 1, kh, kd), jnp.float32)
    kp = jax.random.normal(ks[2], (kpool, kpage, khkv, kd), jnp.float32)
    vp = jax.random.normal(ks[3], (kpool, kpage, khkv, kd), jnp.float32)
    kbt = jnp.arange(kb * kmaxp, dtype=jnp.int32).reshape(kb, kmaxp)
    klens = jnp.asarray([40, 57], jnp.int32)
    kq_i8, k_scale = kv_quantize(kp)
    vq_i8, v_scale = kv_quantize(vp)
    kcache = kp[:kb * kmaxp].reshape(kb, kmaxp * kpage, khkv, kd)
    vcache = vp[:kb * kmaxp].reshape(kb, kmaxp * kpage, khkv, kd)
    kcl = jnp.asarray(57, jnp.int32)

    def _us(fn, *args, iters=3, **kw):
        jax.block_until_ready(fn(*args, **kw))       # warm / trace
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn(*args, **kw))
        return round((time.perf_counter() - t0) / iters * 1e6, 1)

    tuned = autotune_paged_decode(kq1, kp, vp, kbt, klens,
                                  candidates=(1, 2, 4))
    pps = tuned.pages_per_step
    out_paged = ops.paged_decode(kq1, kp, vp, kbt, klens, backend="pallas",
                                 pages_per_step=pps)
    err_paged = float(jnp.max(jnp.abs(
        out_paged - paged_decode_ref(kq1, kp, vp, kbt, klens))))
    out_quant = ops.paged_decode_quant(kq1, kq_i8, vq_i8, k_scale, v_scale,
                                       kbt, klens, backend="pallas",
                                       pages_per_step=pps)
    err_quant = float(jnp.max(jnp.abs(
        out_quant - paged_decode_quant_ref(kq1, kq_i8, vq_i8, k_scale,
                                           v_scale, kbt, klens))))
    payload["kernel"] = {
        "shapes": {"batch": kb, "heads": kh, "kv_heads": khkv,
                   "head_dim": kd, "page_size": kpage, "pages_per_row": kmaxp,
                   "pool_pages": kpool, "spec_k": spec_k},
        "tuning": {"page_size": kpage, "head_dim": kd, "hkv": khkv,
                   "pages_per_step": pps},
        "decode": {
            "slot": {"us_per_call": _us(
                ops.decode, kq1, kcache, vcache, kcl, backend="pallas")},
            "paged": {"us_per_call": _us(
                ops.paged_decode, kq1, kp, vp, kbt, klens,
                backend="pallas", pages_per_step=pps),
                "max_err_vs_oracle": round(err_paged, 8)},
            "paged_quant": {"us_per_call": _us(
                ops.paged_decode_quant, kq1, kq_i8, vq_i8, k_scale, v_scale,
                kbt, klens, backend="pallas", pages_per_step=pps),
                "max_err_vs_oracle": round(err_quant, 8)},
        },
        "spec_verify": {
            "paged": {"us_per_call": _us(
                ops.paged_verify, kqv, kp, vp, kbt, klens,
                backend="pallas", pages_per_step=pps)},
            "paged_quant": {"us_per_call": _us(
                ops.paged_verify_quant, kqv, kq_i8, vq_i8, k_scale, v_scale,
                kbt, klens, backend="pallas", pages_per_step=pps)},
        },
    }

    # int8 admission demo: same tight page budget, 8 queued one-page
    # requests (prompt 15 + 1 new token stays inside one 16-token page) —
    # the kv_quant engine's doubled pool (repro.sim.executor
    # .quantized_pages) must keep >= 2x the concurrent residents
    adm_pages = 4
    adm_out = {}
    for label, quant in (("paged", False), ("paged_quant", True)):
        acfg = cfg.replace(kv_quant=True) if quant else cfg
        eng = Engine(acfg, params, max_batch=8, bucket=16, paged=True,
                     page_size=page_size, num_pages=adm_pages)
        reqs = [GenRequest(rid=f"adm{i}",
                           tokens=np.arange(2, 17).astype(np.int32),
                           max_new=1) for i in range(8)]
        eng.serve(reqs)
        adm_out[label] = eng.stats.peak_resident
    payload["kernel"]["admission"] = {
        "num_pages": adm_pages, "page_size": page_size, **adm_out}

    # --- gossip scale-out: digest vs probe routing (DESIGN.md §6.2-gossip) --
    from benchmarks.scaling import gossip_scaling_section
    payload["gossip"] = gossip_scaling_section()

    # --- cross-request prefix caching (DESIGN.md §6.1-prefix) ---------------
    # (a) real engine: cold prefill of a long shared prefix vs a cached hit
    # on the same prefix with a fresh suffix.  Both prompt shapes are
    # identical; the jit caches for BOTH the cold-prefill and warm-prefill
    # paths are compiled untimed on a throwaway prefix first, so the timed
    # TTFTs compare page reuse, not compilation.
    pfx_tokens, sfx_tokens = 192, 8
    rngp = np.random.default_rng(23)
    bench_prefix = rngp.integers(2, 400, size=pfx_tokens).astype(np.int32)
    jit_prefix = rngp.integers(2, 400, size=pfx_tokens).astype(np.int32)

    def pfx_req(rid, prefix, sufseed):
        suf = np.random.default_rng(sufseed).integers(
            2, 400, size=sfx_tokens).astype(np.int32)
        return GenRequest(rid=rid, tokens=np.concatenate([prefix, suf]),
                          max_new=4)

    peng = Engine(cfg, params, bucket=16, max_batch=2, paged=True,
                  page_size=page_size, num_pages=96, prefix_cache=True)
    peng.serve([pfx_req("jit-cold", jit_prefix, 1)])   # compiles cold path
    peng.serve([pfx_req("jit-warm", jit_prefix, 2)])   # compiles warm path
    cold_done = peng.serve([pfx_req("cold", bench_prefix, 3)])
    hit_before = peng.prefix_hit_tokens
    warm_done = peng.serve([pfx_req("hit", bench_prefix, 4)])
    cold_ttft = cold_done[0].first_token_at - cold_done[0].enqueued_at
    cached_ttft = warm_done[0].first_token_at - warm_done[0].enqueued_at
    psnap = peng.load_snapshot()

    # (b) simulated zipf-shared-prefix workload on one prefix-cache backend
    from repro.core.node import QueuedRequest
    from repro.sim import TokenBucketExecutor, make_profile
    from repro.sim.events import EventLoop
    from repro.sim.workload import make_zipf_prefix_requests
    zloop = EventLoop()
    zex = TokenBucketExecutor(make_profile(quality=0.6),
                              page_size=page_size, prefix_cache=True)
    zserved = []
    zex.bind(zloop, lambda qr, st_, ft: zserved.append(qr))

    def zsubmit(qr):
        if not zex.admit(qr):
            zloop.schedule(0.5, lambda: zsubmit(qr))

    for zr in make_zipf_prefix_requests(300, ["n0"], seed=23, n_prefixes=8):
        zloop.schedule(zr.arrival, lambda zr=zr: zsubmit(
            QueuedRequest(zr, zr.arrival, False, "n0")))
    zloop.run(until=10000.0)
    zhit_rate = zex.prefix_hit_tokens / max(1, zex.prefix_lookup_tokens)

    # (c) cache-affinity vs affinity-blind gossip dispatch on a hot-origin
    # zipf workload: every request lands on one node, which must offload
    # most of them — with more live prefixes (24) than one node's
    # fingerprint window (PREFIX_FINGERPRINT_K), where the dispatch choice
    # decides the aggregate hit rate
    from repro.core import Network, Node, NodePolicy
    from repro.core.duel import DuelParams
    from repro.sim import BackendProfile

    def _affinity_point(affinity):
        net = Network(mode="decentralized", seed=0, init_balance=100.0,
                      duel=DuelParams(p_d=0.0, k_judges=0),
                      gossip_interval=0.25, cache_affinity=affinity)
        pol = NodePolicy(accept_freq=1.0, offload_freq=1.0,
                         offload_queue_threshold=0)
        prof = BackendProfile(prefill_tps=1e4, decode_tps=300.0,
                              saturation=2, max_concurrency=8, quality=0.6,
                              kv_token_budget=16384)
        for i in range(8):
            net.add_node(Node(
                f"n{i}", prof, policy=pol,
                executor_factory=lambda node: TokenBucketExecutor(
                    node.profile, page_size=page_size, prefix_cache=True)))
        reqs = make_zipf_prefix_requests(
            500, ["n0"], seed=100, n_prefixes=24, prefix_tokens=512,
            suffix_mean=24, mean_interarrival=0.05, output_mean=48)
        net.run(list(reqs), until=400.0)
        hit = sum(n.executor.prefix_hit_tokens for n in net.nodes.values())
        look = sum(n.executor.prefix_lookup_tokens
                   for n in net.nodes.values())
        return {"hit_rate": round(hit / max(1, look), 4),
                "hit_tokens": hit, "lookup_tokens": look, "n": len(reqs)}

    payload["prefix_cache"] = {
        "workload": f"engine: prefix {pfx_tokens} + suffix {sfx_tokens}, "
                    "cold then cached; sim: 300 zipf requests over 8 "
                    "prefixes; routing: 500 hot-origin zipf requests over "
                    "24 prefixes, 8 nodes",
        "engine": {
            "cold_ttft_s": round(cold_ttft, 4),
            "cached_ttft_s": round(cached_ttft, 4),
            "ttft_speedup": round(cold_ttft / max(cached_ttft, 1e-9), 2),
            "hit_tokens": peng.prefix_hit_tokens - hit_before,
            "cached_pages": psnap["cached_pages"],
            "prefix_tokens": pfx_tokens,
            "suffix_tokens": sfx_tokens,
        },
        "sim": {
            "hit_rate": round(zhit_rate, 4),
            "hit_tokens": zex.prefix_hit_tokens,
            "lookup_tokens": zex.prefix_lookup_tokens,
            "served": len(zserved),
        },
        "routing": {
            "affinity": _affinity_point(True),
            "blind": _affinity_point(False),
        },
    }

    # --- tracing overhead: mix decode throughput, traced vs untraced --------
    # (DESIGN.md §Observability) Same paged executor and deterministic mix
    # workload as the mix section; the traced arm runs under a live Tracer
    # so every engine.prefill/engine.decode_step wall span is recorded.
    # Best-of-two decode tok/s per arm so a one-off GC/scheduler hiccup
    # doesn't trip the pinned >= 0.95x bound.
    from repro.obs import MetricsRegistry, Tracer, set_registry, set_tracer

    def obs_arm(traced):
        ex = mk_executor("paged")
        run_mix(ex)
        run_mix(ex)                  # warm the per-instance jit caches twice
        tr = Tracer()
        old_tr = set_tracer(tr) if traced else None
        try:
            best = None
            for _ in range(2):
                ex.engine.stats = _ES()
                t0 = time.perf_counter()
                run_mix(ex)          # timed run reuses compiled steps
                wall = time.perf_counter() - t0
                st = ex.engine_stats()
                tps = st.decode_tokens / max(st.decode_wall_s, 1e-9)
                if best is None or tps > best["decode_tokens_per_s"]:
                    best = {"decode_tokens": st.decode_tokens,
                            "decode_tokens_per_s": round(tps, 1),
                            "wall_s": round(wall, 3)}
        finally:
            if traced:
                set_tracer(old_tr)
        return best, len(tr.spans)

    obs_reg = MetricsRegistry()
    old_reg = set_registry(obs_reg)
    try:
        obs_untraced, _ = obs_arm(False)
        obs_traced, obs_spans = obs_arm(True)
    finally:
        set_registry(old_reg)
    # the engine counters only fire under pressure (preemption, prefix
    # hits) and the mix fits in budget, so fold in the routing-plane
    # counters from the traced sim mix too — the artifact then shows
    # the labeled series (net.messages{kind=...}) the registry carries
    _sim_m, _sim_tr, sim_net = _traced_sim_mix(n_requests=12)
    obs_counters = dict(obs_reg.snapshot()["counters"])
    obs_counters.update(sim_net.registry.snapshot()["counters"])
    payload["obs"] = {
        "workload": "mix workload on the paged executor, best-of-two "
                    "decode tok/s per arm, tracer off vs on",
        "untraced": obs_untraced,
        "traced": obs_traced,
        "overhead_ratio": round(
            obs_traced["decode_tokens_per_s"]
            / max(obs_untraced["decode_tokens_per_s"], 1e-9), 4),
        "spans": obs_spans,
        "metrics": obs_counters,
    }

    # --- static-analysis snapshot (DESIGN.md §7) ----------------------------
    from repro.analysis import run_analysis
    lint_report = run_analysis(_REPO)
    payload["lint"] = {
        "rules": lint_report.rules,
        "new": len(lint_report.new),
        "suppressed": len(lint_report.suppressed),
        "baselined": len(lint_report.baselined),
        "wall_s": round(lint_report.wall_s, 3),
    }

    check_bench_schema(payload)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {out_path}")
    print(json.dumps(payload, indent=2, sort_keys=True))
    return 0


def _full() -> int:
    rows: List[str] = ["name,us_per_call,derived"]
    from benchmarks import (duel_overhead, dynamic, gametheory, kernels,
                            policies, protocol, quality, scheduling)
    for mod, label in ((scheduling, "scheduling (Fig4/Tab2)"),
                       (dynamic, "dynamic participation (Fig5)"),
                       (quality, "quality incentivization (Fig6)"),
                       (duel_overhead, "duel overhead (Fig7)"),
                       (policies, "user-level policies (Fig8)"),
                       (gametheory, "game theory (Sec5)"),
                       (protocol, "protocol: ledger ablation + gossip (AppA2/C)"),
                       (kernels, "pallas kernels")):
        t0 = time.perf_counter()
        mod.main(rows)
        dt = time.perf_counter() - t0
        print(f"# {label}: {dt:.1f}s", file=sys.stderr, flush=True)
    print("\n".join(rows))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="benchmark harness")
    ap.add_argument("--smoke", action="store_true",
                    help="<60s end-to-end sanity pass instead of the full "
                         "benchmark sweep")
    ap.add_argument("--bench", action="store_true",
                    help="emit machine-readable BENCH_scheduling.json "
                         "(SLO/latency per mode, sim req/s, engine decode "
                         "tokens/s)")
    ap.add_argument("--bench-out", default="BENCH_scheduling.json",
                    help="output path for --bench")
    ap.add_argument("--lint", action="store_true",
                    help="run the AST invariant linter (repro.analysis) "
                         "only; <10s, no jax import")
    ap.add_argument("--trace", metavar="PATH",
                    help="run the traced sim mix and write a "
                         "Perfetto/Chrome trace_event JSON to PATH; "
                         "prints the per-request latency breakdown and "
                         "asserts the span latency partition; <10s, no "
                         "jax import")
    args = ap.parse_args(argv)
    if args.lint:
        return _lint()
    if args.trace:
        return _trace(args.trace)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        return _smoke()
    if args.bench:
        return _bench(args.bench_out)
    return _full()


if __name__ == "__main__":
    sys.exit(main())
