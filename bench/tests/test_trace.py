"""bench/trace.py on a small profiler trace recorded on a TPU v5e
(``fixtures/small.xplane.pb``): three host steps, each running a jitted
matmul-tanh-matmul and a reduction, inside a ``bench.traced`` span."""

from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "small.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return trace.load(str(FIXTURE))


def test_loads_device_ops_and_bench_spans(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    names = [n for n, _, _ in recorded.ops["/device:TPU:0"]]
    assert names.count("%convolution_tanh_fusion") == 3
    assert all(" = " not in n for n in names)     # HLO text cut off
    spans = [n for n, _, _ in recorded.spans]
    assert spans.count("bench.traced") == 1
    assert spans.count("bench.step") == 3
    assert spans.count("bench.bookkeeping") == 3


def test_busy_union_idle_share_and_top_ops(recorded):
    red = trace.reduce(recorded)
    # the window is the bench.traced span; the ops inside it, by hand
    # from the recorded events (ns): no two overlap, two touch
    lo, hi = 48010578.0, 71776207.0
    inside = [4815, 14, 3156, 14848, 12617, 5057, 13, 3109, 14662, 12617,
              4906]
    assert red.window_s == pytest.approx((hi - lo) / 1e9)
    assert red.busy_s == pytest.approx(sum(inside) / 1e9)
    assert red.idle_share == pytest.approx(1 - sum(inside) / (hi - lo))
    assert [n for n, _ in red.top_ops] == [
        "%convolution_tanh_fusion", "%fusion", "%multiply_reduce_fusion",
        "%copy-done", "%copy-start"]
    assert red.top_ops[0][1] == pytest.approx((14848 + 14662) / 1e9)
    gaps = [s for _, s in red.idle_gaps]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= 10
    assert {n for n, _ in red.idle_gaps} <= {
        "bench.step", "bench.bookkeeping", "outside any bench span"}


def test_union_merges_overlaps_and_clips():
    ivs = [(0, 10), (5, 12), (20, 30), (29, 31), (40, 50)]
    assert trace.union(ivs, 2, 45) == [(2, 12), (20, 31), (40, 45)]
    assert trace.union([], 0, 1) == []


def test_idle_gaps_labelled_by_innermost_span():
    tr = trace.Trace(
        ops={"/device:TPU:0": [("a", 0, 100), ("b", 50, 150),
                               ("a", 400, 500)]},
        spans=[("bench.traced", 0, 1000), ("bench.step", 0, 600),
               ("bench.submit", 150, 300)])
    red = trace.reduce(tr)
    assert red.busy_s == pytest.approx(250e-9)
    assert red.window_s == pytest.approx(1000e-9)
    assert red.idle_gaps == [("outside any bench span", pytest.approx(5e-7)),
                             ("bench.submit", pytest.approx(2.5e-7))]
    assert red.top_ops == [("a", pytest.approx(2e-7)),
                           ("b", pytest.approx(1e-7))]


def test_no_device_op_reads_nothing():
    assert trace.reduce(trace.Trace(spans=[("bench.traced", 0, 10)])) is None
