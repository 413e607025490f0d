"""Paged KV-cache executor (DESIGN.md §6.1, paged backend).

Four families of tests:

1.  Engine parity — the paged engine produces bit-identical greedy outputs
    to the contiguous slot engine (incl. under preemption from a tight
    pool), while admitting strictly more concurrent requests on the same
    KV budget, and random admit/evict/preempt churn keeps that true for
    random page/pool sizes (property-based; deeper sweep behind ``-m
    slow``).
2.  EOS regression — ``Engine`` reads EOS from ``ModelConfig.eos_id``; a
    prompt-configured EOS terminates decode in both paged and slot paths.
3.  Executor-layer invariants — headroom never negative, ``estimate()``
    monotone in queue depth, page accounting conserved through churny
    stepped serving.
4.  Sim-vs-engine agreement — the simulated ``TokenBucketExecutor`` in
    page mode and the real paged engine admit/deny identically on
    identical page budgets (both route through ``paged_admit_ok``), and
    ``go_offline`` churn drains paged nodes with their pages reclaimed.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import Network, Node, NodePolicy
from repro.core.node import QueuedRequest
from repro.sim import (BackendProfile, EventLoop, TokenBucketExecutor,
                       make_profile)
from repro.sim.executor import paged_admit_ok, pages_for
from repro.sim.workload import Request


def _qr(rid, prompt, output, t=0.0):
    return QueuedRequest(
        Request(rid=rid, origin="n", arrival=t, prompt_tokens=prompt,
                output_tokens=output, slo_s=600.0),
        enqueue_time=t, delegated=False, origin_node="n")


class _Harness:
    """A TokenBucketExecutor on a bare event loop, recording completions."""

    def __init__(self, profile, page_size=None):
        self.loop = EventLoop()
        self.ex = TokenBucketExecutor(profile, page_size=page_size)
        self.done = {}
        self.ex.bind(self.loop, self._cb)

    def _cb(self, qr, started_at, first_token_at):
        self.done[qr.req.rid] = dict(finish=self.loop.now,
                                     started=started_at,
                                     first_token=first_token_at)


# ---------------------------------------------------------------------------
# shared pure-rule unit tests (no model, no loop)
# ---------------------------------------------------------------------------

class TestPagedAdmissionRule:
    def test_pages_for(self):
        assert pages_for(1, 16) == 1
        assert pages_for(16, 16) == 1
        assert pages_for(17, 16) == 2
        assert pages_for(0, 16) == 1          # every sequence owns >= 1 page

    @given(free=st.integers(0, 64), prompt=st.integers(1, 2048),
           page=st.sampled_from([8, 16, 32, 64]))
    @settings(max_examples=40, deadline=None)
    def test_rule_properties(self, free, prompt, page):
        # an empty backend always admits; a resident one admits iff the
        # prompt's pages fit the free pool
        assert paged_admit_ok(free, prompt, page, resident=False)
        assert paged_admit_ok(free, prompt, page, resident=True) == (
            pages_for(prompt, page) <= free)


# ---------------------------------------------------------------------------
# real-engine parity
# ---------------------------------------------------------------------------

_MODEL_CACHE = {}


def _smoke_model():
    """Memoized smoke model, shared by the ``setup`` fixture and the
    @given property tests that call it directly."""
    if "cp" not in _MODEL_CACHE:
        import jax
        from repro.configs import get_config
        from repro.models import registry
        cfg = get_config("qwen3-8b").smoke().replace(dtype="float32")
        _MODEL_CACHE["cp"] = (cfg, registry.init(jax.random.PRNGKey(0), cfg))
    return _MODEL_CACHE["cp"]


@pytest.fixture(scope="module")
def setup():
    return _smoke_model()


def _mk_reqs(seed, n=4, max_prompt=24, max_new_hi=10):
    from repro.serving import GenRequest
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        plen = int(rng.integers(5, max_prompt + 1))
        out.append(GenRequest(
            rid=f"r{i}",
            tokens=rng.integers(2, 400, size=plen).astype(np.int32),
            max_new=int(rng.integers(2, max_new_hi + 1))))
    return out


def _results_by_rid(reqs):
    return {r.rid: np.asarray(r.result) for r in reqs}


class TestPagedEngineParity:
    def test_paged_matches_slot_under_preemption(self, setup):
        """A pool too small for the offered load forces preempt-and-requeue
        mid-decode; greedy outputs must still be bit-identical."""
        from repro.serving import Engine
        cfg, params = setup
        slot = Engine(cfg, params, max_batch=2, bucket=16)
        paged = Engine(cfg, params, max_batch=4, bucket=16, paged=True,
                       page_size=16, num_pages=4)
        rs = slot.serve(_mk_reqs(7, n=5, max_new_hi=16))
        rp = paged.serve(_mk_reqs(7, n=5, max_new_hi=16))
        a, b = _results_by_rid(rs), _results_by_rid(rp)
        for rid in a:
            np.testing.assert_array_equal(a[rid], b[rid])
        assert paged.stats.preempted > 0          # the tight pool actually bit
        snap = paged.load_snapshot()
        assert snap["pages_used"] == 0            # everything reclaimed

    def test_paged_admits_more_concurrency_same_kv_budget(self, setup):
        """Acceptance: same KV token budget, bit-identical greedy outputs,
        strictly more concurrently admitted requests under paging (admission
        charges prompt pages, not prompt+max_new reservations)."""
        from repro.serving import Engine
        cfg, params = setup
        reqs = _mk_reqs(3, n=6, max_prompt=14, max_new_hi=10)
        slot = Engine(cfg, params, max_batch=2, bucket=16)
        rs = slot.serve([r for r in reqs])
        # slot engine reserved pad(prompt)+pad(max_new) per slot; hand the
        # paged engine the same total KV as pages
        budget = slot.load_snapshot()["kv_budget"]
        paged = Engine(cfg, params, max_batch=6, bucket=16, paged=True,
                       page_size=16, num_pages=budget // 16)
        rp = paged.serve(_mk_reqs(3, n=6, max_prompt=14, max_new_hi=10))
        a, b = _results_by_rid(rs), _results_by_rid(rp)
        for rid in a:
            np.testing.assert_array_equal(a[rid], b[rid])
        assert paged.stats.peak_resident > slot.stats.peak_resident
        assert slot.stats.peak_resident == 2

    @given(page_size=st.sampled_from([8, 16]), pool=st.integers(4, 8),
           seed=st.integers(0, 10**6))
    @settings(max_examples=3, deadline=None)
    def test_random_churn_parity_paged_vs_slot(self, page_size, pool, seed):
        """Random page/pool sizes and workloads: admit/evict/preempt churn
        in the paged engine never changes greedy outputs vs slot batching."""
        from repro.serving import Engine
        cfg, params = _smoke_model()
        slot = Engine(cfg, params, max_batch=2, bucket=16)
        paged = Engine(cfg, params, max_batch=3, bucket=16, paged=True,
                       page_size=page_size, num_pages=pool)
        rs = slot.serve(_mk_reqs(seed))
        rp = paged.serve(_mk_reqs(seed))
        a, b = _results_by_rid(rs), _results_by_rid(rp)
        for rid in a:
            np.testing.assert_array_equal(a[rid], b[rid])
        assert paged.load_snapshot()["pages_used"] == 0

    @pytest.mark.slow
    @given(page_size=st.sampled_from([8, 16, 32]), pool=st.integers(3, 10),
           seed=st.integers(0, 10**6), max_batch=st.integers(2, 5))
    @settings(max_examples=8, deadline=None)
    def test_random_churn_parity_three_way_deep(self, page_size, pool,
                                                seed, max_batch):
        """Deeper sweep (``-m slow``): paged == slot == wave greedy outputs
        across random pool geometries and batch widths."""
        from repro.serving import Engine
        cfg, params = _smoke_model()
        slot = Engine(cfg, params, max_batch=2, bucket=16)
        wave = Engine(cfg, params, max_batch=2, bucket=16, continuous=False)
        paged = Engine(cfg, params, max_batch=max_batch, bucket=16,
                       paged=True, page_size=page_size, num_pages=pool)
        outs = [_results_by_rid(e.serve(_mk_reqs(seed, n=5, max_new_hi=14)))
                for e in (slot, wave, paged)]
        for rid in outs[0]:
            np.testing.assert_array_equal(outs[0][rid], outs[1][rid])
            np.testing.assert_array_equal(outs[0][rid], outs[2][rid])


class TestConfiguredEos:
    """Engine.eos_id comes from ModelConfig (regression for the hard-coded
    ``eos_id = 1``): a prompt-configured EOS terminates decode early in both
    the paged and the contiguous slot path."""

    @pytest.mark.parametrize("paged", [False, True])
    def test_configured_eos_terminates_decode(self, setup, paged):
        from repro.serving import Engine, GenRequest
        cfg, params = setup
        prompt = np.random.default_rng(11).integers(2, 400, size=12) \
            .astype(np.int32)

        def run(cfg_run, max_new=10):
            kw = dict(paged=True, page_size=16) if paged else {}
            eng = Engine(cfg_run, params, max_batch=2, bucket=16, **kw)
            assert eng.eos_id == cfg_run.eos_id
            (r,) = eng.serve([GenRequest(rid="a", tokens=prompt.copy(),
                                         max_new=max_new)])
            return list(r.result)

        base = run(cfg)
        assert len(base) == 10                   # ran to budget, no EOS hit
        # pick an emitted token whose first occurrence is not at step 0 and
        # declare it EOS; decode must now stop right before it
        tok = next(t for t in base[1:] if base.index(t) >= 1)
        cut = base.index(tok)
        early = run(cfg.replace(eos_id=int(tok)))
        assert early == base[:cut]
        assert len(early) < len(base)


# ---------------------------------------------------------------------------
# int8 KV pages (DESIGN.md §6.1-paged, quantized pools)
# ---------------------------------------------------------------------------

class TestQuantizedPages:
    """The int8 page pools must be invisible to the paging machinery:
    quantized-paged generations match quantized-slot bit-for-bit (the
    rounding is pinned by kernel tolerance oracles; THESE tests pin the
    block-table indirection), the shared ``quantized_pages`` rule doubles
    every capacity report, and preemption round-trips reproduce the same
    quantized tokens."""

    def test_quant_paged_matches_quant_slot_bitwise(self, setup):
        from repro.serving import Engine
        cfg, params = setup
        qcfg = cfg.replace(kv_quant=True)
        slot = Engine(qcfg, params, max_batch=2, bucket=16)
        paged = Engine(qcfg, params, max_batch=3, bucket=16, paged=True,
                       page_size=16, num_pages=8)
        rs = slot.serve(_mk_reqs(7, n=4, max_new_hi=10))
        rp = paged.serve(_mk_reqs(7, n=4, max_new_hi=10))
        a, b = _results_by_rid(rs), _results_by_rid(rp)
        for rid in a:
            np.testing.assert_array_equal(a[rid], b[rid])
        assert paged.load_snapshot()["pages_used"] == 0

    def test_quant_preemption_roundtrips_same_tokens(self, setup):
        """LIFO preempt-and-requeue on an int8 pool: the greedy restart
        re-quantizes the same prompt through the same pipeline, so the
        reproduced tokens are bit-identical to the quantized-slot run."""
        from repro.serving import Engine
        cfg, params = setup
        qcfg = cfg.replace(kv_quant=True)
        slot = Engine(qcfg, params, max_batch=2, bucket=16)
        # num_pages=2 doubles to 4 usable pages — tight enough to preempt
        paged = Engine(qcfg, params, max_batch=4, bucket=16, paged=True,
                       page_size=16, num_pages=2)
        rs = slot.serve(_mk_reqs(7, n=5, max_new_hi=16))
        rp = paged.serve(_mk_reqs(7, n=5, max_new_hi=16))
        a, b = _results_by_rid(rs), _results_by_rid(rp)
        for rid in a:
            np.testing.assert_array_equal(a[rid], b[rid])
        assert paged.stats.preempted > 0          # the tight pool actually bit
        assert paged.load_snapshot()["pages_used"] == 0

    def test_quantized_pages_rule_shared_by_sim_and_engine(self, setup):
        """THE capacity rule: the same nominal pool reports 2x pages on
        both backends when quantized — sim and engine must agree or their
        admission decisions drift."""
        from repro.serving import Engine
        from repro.sim.executor import quantized_pages
        assert quantized_pages(8, False) == 8
        assert quantized_pages(8, True) == 16
        cfg, params = setup
        eng = Engine(cfg.replace(kv_quant=True), params, max_batch=2,
                     bucket=16, paged=True, page_size=16, num_pages=8)
        sim = TokenBucketExecutor(BackendProfile(
            prefill_tps=1e4, decode_tps=100.0, saturation=2,
            max_concurrency=8, quality=0.5, kv_token_budget=16 * 8),
            page_size=16, kv_quant=True)
        assert sim.pages_total == 16 == eng.load_snapshot()["pages_total"]

    def test_quant_page_accounting_conserved_under_churn(self, setup):
        """Stepped churny serving on int8 pools: the one free list covers
        page and scale pools alike, so pages_used + free_pages ==
        pages_total at every step and the pool fully drains."""
        from repro.serving import Engine
        cfg, params = setup
        eng = Engine(cfg.replace(kv_quant=True), params, max_batch=3,
                     bucket=16, paged=True, page_size=8, num_pages=5)
        for r in _mk_reqs(23, n=6, max_new_hi=12):
            eng.submit(r)
        while eng.has_work():
            eng.step()
            snap = eng.load_snapshot()
            assert snap["pages_used"] + snap["free_pages"] \
                == snap["pages_total"]
            assert snap["kv_used"] == snap["pages_used"] * snap["page_size"]
        assert eng.load_snapshot()["pages_used"] == 0


# ---------------------------------------------------------------------------
# executor-layer invariants
# ---------------------------------------------------------------------------

PAGED_PROF = BackendProfile(prefill_tps=1e4, decode_tps=100.0, saturation=2,
                            max_concurrency=8, quality=0.5,
                            kv_token_budget=1024)


class TestExecutorInvariants:
    @given(ops=st.lists(st.integers(1, 400), min_size=1, max_size=12),
           page=st.sampled_from([16, 32, 64]),
           dt=st.floats(0.0, 3.0))
    @settings(max_examples=25, deadline=None)
    def test_headroom_never_negative(self, ops, page, dt):
        """Random admit sequences + time advancement: every load() snapshot
        keeps both headrooms in [0, 1] and the counts non-negative."""
        h = _Harness(PAGED_PROF, page_size=page)
        t = 0.0
        for prompt in ops:
            h.ex.admit(_qr(f"p{t}-{prompt}", prompt, prompt, t=t))
            t += dt
            h.loop.run(until=t)
            ld = h.ex.load()
            assert 0.0 <= ld.kv_headroom <= 1.0
            assert 0.0 <= ld.page_headroom <= 1.0
            assert ld.pages_used >= 0 and ld.kv_used >= 0
            assert ld.pending_prefill_tokens >= 0
            assert ld.pending_decode_tokens >= 0
        h.loop.run()
        ld = h.ex.load()
        assert ld.pages_used == 0 and ld.kv_used == 0   # all reclaimed

    @pytest.mark.parametrize("page", [None, 32])
    def test_estimate_monotone_in_queue_depth(self, page):
        """estimate() must be weakly increasing in the number of admitted
        streams — more co-residents can only slow a hypothetical request."""
        h = _Harness(make_profile(), page_size=page)
        prev = 0.0
        for i in range(12):
            est = h.ex.estimate(256, 512)
            assert est >= prev
            prev = est
            assert h.ex.admit(_qr(f"r{i}", 64, 64))

    def test_engine_page_accounting_conserved(self, setup):
        """Stepped churny serving: pages_used + free_pages == pages_total at
        every engine step, and the pool fully drains."""
        from repro.serving import Engine
        cfg, params = setup
        eng = Engine(cfg, params, max_batch=3, bucket=16, paged=True,
                     page_size=8, num_pages=9)
        for r in _mk_reqs(23, n=6, max_new_hi=12):
            eng.submit(r)
        while eng.has_work():
            eng.step()
            snap = eng.load_snapshot()
            assert snap["pages_used"] + snap["free_pages"] \
                == snap["pages_total"]
            assert snap["pages_used"] >= 0
            assert snap["kv_used"] == snap["pages_used"] * snap["page_size"]
        assert eng.load_snapshot()["pages_used"] == 0


# ---------------------------------------------------------------------------
# sim-vs-engine agreement + churn
# ---------------------------------------------------------------------------

class TestSimEngineAgreement:
    def test_admission_decisions_agree_on_identical_page_budget(self, setup):
        """The simulated page-mode executor and the real paged engine (via
        the page-gated EngineExecutor) must produce the same admit/deny
        sequence for the same page budget — they share paged_admit_ok."""
        from repro.serving import Engine, EngineExecutor, GenRequest
        cfg, params = setup
        page, pool = 16, 8
        prof = BackendProfile(prefill_tps=1e4, decode_tps=100.0, saturation=2,
                              max_concurrency=8, quality=0.5,
                              kv_token_budget=page * pool)
        sim = _Harness(prof, page_size=page)
        eng = Engine(cfg, params, max_batch=8, bucket=16, paged=True,
                     page_size=page, num_pages=pool)
        ex = EngineExecutor(eng, gate_on_pages=True)
        ex.bind(None, lambda r, st_, ft: None)
        rng = np.random.default_rng(5)
        sim_dec, eng_dec = [], []
        for i, plen in enumerate((40, 30, 50, 20)):     # pages 3, 2, 4, 2
            sim_dec.append(sim.ex.admit(_qr(f"s{i}", plen, 64)))
            ok = ex.admit(GenRequest(
                rid=f"e{i}", tokens=rng.integers(2, 400, size=plen)
                .astype(np.int32), max_new=64))
            eng_dec.append(ok)
            if ok:
                ex.step()         # prefill claims the prompt pages for real
        assert sim_dec == eng_dec == [True, True, False, True]
        assert ex.load().pages_used == sim.ex.load().pages_used == 7
        assert ex.load().pages_total == sim.ex.load().pages_total == pool

    def test_go_offline_reclaims_doubled_quantized_pool(self):
        """Churn on an int8 page pool: the doubled capacity is visible in
        every load snapshot and every page (and with it its scale-pool
        row — one free list covers both) is reclaimed after the node
        drains offline."""
        net = Network(mode="single", seed=0, init_balance=100.0)
        prof = BackendProfile(prefill_tps=1e4, decode_tps=50.0, saturation=2,
                              max_concurrency=8, quality=0.5,
                              kv_token_budget=4096)
        net.add_node(Node(
            "n1", prof, policy=NodePolicy(),
            executor_factory=lambda node: TokenBucketExecutor(
                node.profile, page_size=64, kv_quant=True)))
        net.add_node(Node("n2", make_profile(), policy=NodePolicy()))
        reqs = [Request(rid=f"r{i}", origin="n1", arrival=0.1 * i,
                        prompt_tokens=500, output_tokens=1000, slo_s=600.0)
                for i in range(10)]
        net.loop.schedule(5.0, lambda: net.nodes["n1"].go_offline())
        m = net.run(reqs, until=500.0)
        user = [c for c in m.completed if not c.is_duel_extra]
        assert len(user) == 10                          # nothing stranded
        ld = net.nodes["n1"].executor.load()
        assert ld.pages_total == 2 * (4096 // 64)       # quantized_pages rule
        assert ld.pages_used == 0 and ld.page_headroom == 1.0

    def test_go_offline_drains_paged_node_with_pages_reclaimed(self):
        """Churn: a paged node going offline hands queued requests back to
        the network; its in-flight streams drain and every page returns to
        the pool."""
        net = Network(mode="single", seed=0, init_balance=100.0)
        prof = BackendProfile(prefill_tps=1e4, decode_tps=50.0, saturation=2,
                              max_concurrency=8, quality=0.5,
                              kv_token_budget=4096)
        net.add_node(Node(
            "n1", prof, policy=NodePolicy(),
            executor_factory=lambda node: TokenBucketExecutor(
                node.profile, page_size=64)))
        net.add_node(Node("n2", make_profile(), policy=NodePolicy()))
        reqs = [Request(rid=f"r{i}", origin="n1", arrival=0.1 * i,
                        prompt_tokens=500, output_tokens=1000, slo_s=600.0)
                for i in range(10)]
        net.loop.schedule(5.0, lambda: net.nodes["n1"].go_offline())
        m = net.run(reqs, until=500.0)
        user = [c for c in m.completed if not c.is_duel_extra]
        assert len(user) == 10                          # nothing stranded
        assert net.nodes["n1"].queue_len == 0
        assert any(c.executor == "n2" for c in user)    # drained to the peer
        ld = net.nodes["n1"].executor.load()
        assert ld.pages_used == 0 and ld.page_headroom == 1.0
