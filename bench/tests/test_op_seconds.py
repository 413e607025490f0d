"""Per-op device seconds in the trace reduction (``Reduced.op_seconds``):
every op in the window, summed by base name, beside the top ops and idle
gaps the ledger's breakdown reads, which stay as they were."""

from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "small.xplane.pb"


def test_base_names():
    assert trace.base_name("%paged_decode.10") == "paged_decode"
    assert trace.base_name("%fusion.149") == "fusion"
    assert trace.base_name("%fusion") == "fusion"
    assert trace.base_name("%copy-done") == "copy-done"
    assert trace.base_name("%constant_dynamic-slice_fusion.8") == \
        "constant_dynamic-slice_fusion"


def test_fixture_op_seconds_beside_unchanged_top_ops():
    red = trace.reduce(trace.load(str(FIXTURE)))
    assert [n for n, _ in red.top_ops] == [
        "%convolution_tanh_fusion", "%fusion", "%multiply_reduce_fusion",
        "%copy-done", "%copy-start"]
    assert red.top_ops[0][1] == pytest.approx((14848 + 14662) / 1e9)
    # no two ops overlap in this window: the ops' seconds are the busy time
    assert sum(red.op_seconds.values()) == pytest.approx(red.busy_s)
    assert red.op_seconds == {trace.base_name(n): pytest.approx(s)
                              for n, s in red.top_ops}


def test_ops_of_one_base_name_sum_and_are_clipped_to_the_window():
    tr = trace.Trace(
        ops={"/device:TPU:0": [("%paged_decode.10", 0, 100),
                               ("%paged_decode.11", 200, 260),
                               ("%paged_decode", 900, 1100),
                               ("%while.5", 0, 400)]},
        spans=[("bench.traced", 0, 1000)])
    red = trace.reduce(tr)
    assert red.op_seconds == {"paged_decode": pytest.approx(260e-9),
                              "while": pytest.approx(400e-9)}
    # the top ops keep the compiler's full names
    assert [n for n, _ in red.top_ops] == [
        "%while.5", "%paged_decode.10", "%paged_decode", "%paged_decode.11"]
