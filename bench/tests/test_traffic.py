"""The traffic generator is seeded, deterministic, serves every seed the
same work in another order, and matches each mix's medians and clips."""

import json
import statistics
from pathlib import Path

import numpy as np
import pytest

from bench.traffic import Traffic, quantile_gaps, quantile_lengths

MIXES = Path(__file__).resolve().parents[1] / "traffic"
VOCAB, EOS = 151936, 151645


def mixes():
    return sorted(p.stem for p in MIXES.glob("*.json"))


def load(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def window_items(t: Traffic):
    lo, hi = t.warm_s, t.warm_s + t.seconds
    return [i for i in t.items if lo <= i.due < hi]


def sample(mix, seed, seconds=40.0):
    t = Traffic(mix, seed, seconds, VOCAB, EOS)
    if t.kind == "open_poisson":
        return window_items(t)
    return t.first() + [t.next_for(c, 1.0) for c in range(200)]


@pytest.mark.parametrize("name", mixes())
def test_same_seed_same_traffic(name):
    a, b = sample(load(name), 2 ** 33 + 7), sample(load(name), 2 ** 33 + 7)
    assert [(i.due, i.prompt_len, i.max_new) for i in a] == \
        [(i.due, i.prompt_len, i.max_new) for i in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))


@pytest.mark.parametrize("name", mixes())
def test_tokens_in_vocab_and_never_eos(name):
    for it in sample(load(name), 5)[:20]:
        assert len(it.tokens) == it.prompt_len
        assert it.tokens.min() >= 0 and it.tokens.max() < VOCAB
        assert not (it.tokens == EOS).any()


def schedule(items):
    return [(i.due, i.prompt_len, i.max_new) for i in items]


@pytest.mark.parametrize("name", mixes())
def test_every_seed_serves_the_mix_schedule(name):
    mix = load(name)
    a, b = sample(mix, 1), sample(mix, 2 ** 40 + 3)
    assert "schedule_seed" in mix
    assert schedule(a) == schedule(b)
    assert not np.array_equal(a[0].tokens, b[0].tokens)


@pytest.mark.parametrize("name", [m for m in mixes()
                                  if load(m)["kind"] == "open_poisson"])
def test_without_a_schedule_seed_the_same_work_in_another_order(name):
    mix = dict(load(name))
    del mix["schedule_seed"]
    a, b = sample(mix, 1), sample(mix, 2 ** 40 + 3)
    n = round(mix["rate_per_s"] * 40.0)
    assert len(a) == len(b) == n
    assert schedule(a) != schedule(b)
    assert sorted(i.prompt_len for i in a) == sorted(i.prompt_len for i in b)
    assert sorted(i.max_new for i in a) == sorted(i.max_new for i in b)
    # the window's gaps are its segment's, whatever their order
    for items in (a, b):
        dues = [i.due for i in items]
        assert dues[-1] - dues[0] < 40.0 <= dues[-1] - dues[0] + 40.0 / n * 6


@pytest.mark.parametrize("name", mixes())
@pytest.mark.parametrize("what", ["prompt", "output"])
def test_lengths_match_medians_and_clips(name, what):
    dist = load(name)[what]
    xs = quantile_lengths(dist, 1001)
    assert xs.min() >= dist["min"] and xs.max() <= dist["max"]
    med = statistics.median(xs.tolist())
    assert abs(med - dist["median"]) <= 1
    # a lognormal of this sigma: its quartiles lie at exp(+-0.674 sigma)
    q3 = np.quantile(xs, 0.75)
    want = min(dist["max"], dist["median"] * np.exp(0.6745 * dist["sigma"]))
    assert abs(q3 - want) / want < 0.02


def test_gaps_sum_to_the_segment():
    g = quantile_gaps(50, 20.0)
    assert g.sum() == pytest.approx(20.0)
    assert np.all(g > 0) and np.all(np.diff(g) > 0)


@pytest.mark.parametrize("name", [m for m in mixes()
                                  if load(m)["kind"] == "closed_loop"])
def test_closed_loop_steady_start(name):
    mix = load(name)
    t = Traffic(mix, 77, 30.0, VOCAB, EOS)
    first = t.first()
    assert len(first) == mix["clients"]
    assert sorted(i.client for i in first) == list(range(mix["clients"]))
    assert all(i.due == 0.0 for i in first)
    lo, hi = mix["output"]["min"], mix["output"]["max"]
    assert all(1 <= i.max_new <= hi for i in first)
    # residual budgets: shorter on the whole than fresh draws of the mix
    fresh = quantile_lengths(mix["output"], 256)
    assert lo <= np.median([i.max_new for i in first]) < np.median(fresh) * 1.2
    nxt = t.next_for(3, 12.5)
    assert nxt.client == 3 and nxt.due == 12.5
