"""The serving engine's phase spans and step counters (DESIGN.md
§Observability).

Each slot/paged ``Engine.step`` records one ``engine.step`` wall span and,
inside it, a span per phase: ``engine.admit`` (prefills nest in it),
``engine.sample``, ``engine.retire``, a second ``engine.admit`` when rows
retired, ``engine.pages`` (paged), ``engine.decode_step`` and
``engine.carry``.  ``EngineStats`` counts the steps, their wall time and
the real prompt tokens the prefills computed for beside the padded ones.
"""

import re

import numpy as np
import pytest

from repro.obs import WALL, Tracer, set_tracer

PHASES = re.compile(r"^admit( sample retire( admit)?( pages)?"
                    r"( decode_step carry)?)?$")


@pytest.fixture(scope="module")
def setup():
    import jax
    from repro.configs import get_config
    from repro.models import registry
    cfg = get_config("qwen3-8b").smoke().replace(dtype="float32")
    return cfg, registry.init(jax.random.PRNGKey(0), cfg)


def _reqs(seed, n=6, lo=5, hi=40, max_new=(2, 9)):
    from repro.serving import GenRequest
    rng = np.random.default_rng(seed)
    return [GenRequest(rid=f"r{i}",
                       tokens=rng.integers(2, 400, size=int(
                           rng.integers(lo, hi + 1))).astype(np.int32),
                       max_new=int(rng.integers(*max_new)))
            for i in range(n)]


def _traced(engine, reqs, annotate=None):
    tr = Tracer(annotate=annotate)
    old = set_tracer(tr)
    try:
        engine.serve(reqs)
    finally:
        set_tracer(old)
    return tr.spans


def _paged(cfg, params, **kw):
    from repro.serving import Engine
    return Engine(cfg, params, max_batch=3, bucket=16, paged=True,
                  page_size=16, num_pages=kw.pop("num_pages", 64), **kw)


def _inside(s, outer):
    return outer.t0 <= s.t0 and s.t1 <= outer.t1


def _children(spans, step):
    return sorted((s for s in spans if s is not step and s.clock == WALL
                   and s.name != "engine.prefill" and _inside(s, step)),
                  key=lambda s: s.t0)


def test_every_paged_step_holds_its_phases_in_order(setup):
    cfg, params = setup
    eng = _paged(cfg, params)
    spans = _traced(eng, _reqs(1))
    steps = [s for s in spans if s.name == "engine.step"]
    assert len(steps) == eng.stats.steps > 0
    assert sum(s.dur for s in steps) == pytest.approx(eng.stats.step_wall_s)
    seen = set()
    for st in steps:
        kids = _children(spans, st)
        names = " ".join(k.name.split(".", 1)[1] for k in kids)
        assert PHASES.match(names), names
        seen.update(names.split())
        for a, b in zip(kids, kids[1:]):
            assert a.t1 <= b.t0                    # siblings do not overlap
    assert seen == {"admit", "sample", "retire", "pages", "decode_step",
                    "carry"}
    # every span of the step path lies inside one engine.step, and every
    # prefill inside an engine.admit
    assert all(any(_inside(s, st) for st in steps) for s in spans
               if s.name != "engine.step")
    admits = [s for s in spans if s.name == "engine.admit"]
    prefills = [s for s in spans if s.name == "engine.prefill"]
    assert prefills and all(any(_inside(p, a) for a in admits)
                            for p in prefills)


def test_decode_span_reports_rows_and_the_table_width(setup, monkeypatch):
    cfg, params = setup
    eng = _paged(cfg, params)
    widths = []
    real = eng._table_width

    def recording(lookahead=1):
        w = real(lookahead)
        widths.append(w)
        return w

    monkeypatch.setattr(eng, "_table_width", recording)
    spans = _traced(eng, _reqs(2, hi=70, max_new=(8, 30)))
    dec = [s for s in spans if s.name == "engine.decode_step"]
    assert [s.attrs["width"] for s in dec] == widths
    assert len(set(widths)) > 1                    # tables grew in the run
    assert sum(s.attrs["rows"] for s in dec) == eng.stats.decode_tokens
    assert sum(s.dur for s in dec) == pytest.approx(eng.stats.decode_wall_s)


def test_prefill_counts_real_prompt_tokens_beside_padded_ones(setup):
    cfg, params = setup
    eng = _paged(cfg, params)
    reqs = _reqs(3)
    spans = _traced(eng, reqs)
    real = sum(len(r.tokens) for r in reqs)
    pre = [s for s in spans if s.name == "engine.prefill"]
    assert eng.stats.preempted == 0
    assert eng.stats.prefill_prompt_tokens == real
    assert sum(s.attrs["prompt_tokens"] for s in pre) == real
    # padded positions: every prefill row pads to a page-multiple bucket
    assert eng.stats.prefill_tokens == sum(s.attrs["tokens"] for s in pre)
    assert eng.stats.prefill_tokens > real
    assert all(s.attrs["tokens"] % 16 == 0 for s in pre)


def test_warm_prefill_counts_the_uncached_suffix(setup):
    from repro.serving import GenRequest
    cfg, params = setup
    eng = _paged(cfg, params, prefix_cache=True)
    rng = np.random.default_rng(4)
    head = rng.integers(2, 400, size=32).astype(np.int32)
    first = GenRequest("a", np.concatenate([head, [7, 8, 9]]), max_new=3)
    second = GenRequest("b", np.concatenate([head, [5, 6]]), max_new=3)
    spans = _traced(eng, [first])
    spans += _traced(eng, [second])
    warm = [s for s in spans if s.name == "engine.prefill"
            and s.attrs["path"] == "warm"]
    assert len(warm) == 1 and warm[0].attrs["prompt_tokens"] == 2
    assert eng.stats.prefill_prompt_tokens == len(first.tokens) + 2


def test_slot_engine_steps_have_no_page_phase(setup):
    from repro.serving import Engine
    cfg, params = setup
    eng = Engine(cfg, params, max_batch=3, bucket=16)
    spans = _traced(eng, _reqs(5))
    names = {s.name for s in spans}
    assert "engine.pages" not in names
    assert {"engine.step", "engine.admit", "engine.sample", "engine.retire",
            "engine.decode_step", "engine.carry"} <= names
    for st in (s for s in spans if s.name == "engine.step"):
        kids = " ".join(k.name.split(".", 1)[1]
                        for k in _children(spans, st))
        assert PHASES.match(kids), kids
    assert all("width" not in s.attrs for s in spans
               if s.name == "engine.decode_step")


def test_counters_run_with_the_tracer_off(setup):
    cfg, params = setup
    eng = _paged(cfg, params)
    eng.serve(_reqs(6))                    # process tracer: disabled
    st = eng.stats
    assert st.steps > st.decode_steps > 0
    assert st.step_wall_s > st.decode_wall_s + st.prefill_wall_s > 0.0


def test_one_annotation_per_wall_span(setup):
    cfg, params = setup
    eng = _paged(cfg, params)
    opened = []

    class Ann:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    spans = _traced(eng, _reqs(7), annotate=Ann)
    assert opened and sorted(opened) == sorted(s.name for s in spans
                                               if s.clock == WALL)


def test_jitted_steps_are_named(setup):
    import jax.numpy as jnp
    cfg, params = setup
    eng = _paged(cfg, params, prefix_cache=True)
    assert eng._prefill.__name__ == "prefill_step"
    assert eng._decode.__name__ == "decode_step"
    assert eng._decode_paged.__name__ == "paged_decode_step"
    assert eng._verify.__name__ == "paged_verify_step"
    text = eng._prefill.lower(params, {"tokens": jnp.zeros((1, 16), jnp.int32)},
                              16, jnp.zeros((1,), jnp.int32)).as_text()
    assert "jit_prefill_step" in text and "jit__lambda" not in text
