"""The reader of ``paged_decode_roofline``: nothing to read in an empty
window, and its arithmetic on windows built by hand.  (The time-to-first-
token reader this file also tested left with the cell that reported it.)"""

from types import SimpleNamespace

import pytest

from bench import harness, trace
from bench.harness import Step
from bench.work import Sizes

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SIZES = Sizes(layers=2, d_model=64, heads=4, kv_heads=2, head_dim=16,
              d_ff=128, vocab=500, padded_vocab=512, tied=False)


def reader(name):
    return harness.reader(harness.ROOT, name)


def _run(steps=(), trace_=None, traced=None):
    return SimpleNamespace(steps=list(steps), sizes=SIZES, peaks=PEAKS,
                           trace=trace_, traced=traced)


def _reduced(kernel_s):
    return trace.Reduced(busy_s=1.0, window_s=4.0, top_ops=[], idle_gaps=[],
                         op_seconds={"paged_decode": kernel_s,
                                     "while": 2.0})


def test_empty_windows_read_nothing():
    for name in ("paged_decode_roofline.chat", "paged_decode_roofline.batch"):
        assert reader(name)(_run()) is None
        assert reader(name)(_run(trace_=_reduced(0.5),
                                 traced=(16.0, 20.0))) is None


def test_roofline_counts_the_steps_inside_the_traced_span():
    steps = [Step(15.0, 2, 2, 1000), Step(16.5, 2, 2, 1002),
             Step(17.0, 3, 2, 2000), Step(18.0, 1, 0, 0),
             Step(19.5, 2, 2, 3000), Step(20.5, 2, 2, 5000)]
    kernel_s = 1e-6
    run = _run(steps=steps, trace_=_reduced(kernel_s), traced=(16.0, 20.0))
    # steps 3 and 5 began (previous step ended) and ended inside the span;
    # step 2 began before it, step 6 ended after it
    positions = (2000 + 2) + (3000 + 2)
    want = 100.0 * positions * SIZES.kv_bytes_per_token / kernel_s / 819e9
    got = reader("paged_decode_roofline.chat")(run)
    assert got == pytest.approx(want)
    assert reader("paged_decode_roofline.batch")(run) == got
    # without the kernel in the trace, or without a trace: nothing
    no_kernel = trace.Reduced(1.0, 4.0, [], [], {"while": 2.0})
    assert reader("paged_decode_roofline.chat")(
        _run(steps=steps, trace_=no_kernel, traced=(16.0, 20.0))) is None
    assert reader("paged_decode_roofline.chat")(
        _run(steps=steps, traced=(16.0, 20.0))) is None
