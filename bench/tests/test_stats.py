"""Percentile and timeline arithmetic on a hand-built timeline."""

import pytest

from bench import stats


def test_percentile_nearest_rank():
    xs = [5, 1, 4, 2, 3, 10, 9, 8, 7, 6]
    assert stats.percentile(xs, 50) == 5
    assert stats.percentile(xs, 90) == 9
    assert stats.percentile(xs, 95) == 10
    assert stats.percentile(xs, 100) == 10
    assert stats.percentile([], 90) is None
    assert stats.percentile([3.5], 90) == 3.5


def test_ttft_counts_from_due_and_missing_as_at_least_the_wait():
    due = [10.0, 10.5, 11.0, 11.5]
    # the generator ran late for the second request and the node stalled:
    # the wait before submission still counts; the fourth never started
    first = [10.2, 11.4, 11.3, None]
    got = stats.ttfts(due, first, end=20.0)
    assert got == pytest.approx([0.2, 0.9, 0.3, 8.5])
    # the missing request sits at the top of the tail
    assert stats.percentile(got, 90) == pytest.approx(8.5)


def test_inter_token_gaps_inside_the_window():
    emits = [[1.0, 1.1, 1.3, 2.0],      # gaps 0.1 (t=1.1), 0.2, 0.7 (t=2.0)
             [0.5, 1.05],               # gap 0.55 ends at 1.05: in
             [2.5, 2.6]]                # ends at 2.6: outside [1, 2.5)
    gaps = stats.inter_token_gaps(emits, 1.0, 2.5)
    assert sorted(gaps) == pytest.approx([0.1, 0.2, 0.55, 0.7])
    assert stats.percentile(gaps, 95) == pytest.approx(0.7)
    assert stats.tokens_in(emits, 1.0, 2.5) == 5


def test_deltas():
    assert stats.deltas({"a": 5.0, "b": 2.0}, {"a": 3.0}) == \
        {"a": 2.0, "b": 2.0}
