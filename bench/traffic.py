"""Traffic generation from a mix file (``bench/traffic/<mix>.json``).

One general generator reads every mix.  A mix names its kind and its
length distributions::

    {"kind": "open_poisson", "rate_per_s": 2.0, "warm_s": 20,
     "prompt": {"median": 1020, "sigma": 0.8, "min": 64, "max": 4096},
     "output": {"median": 129, "sigma": 0.8, "min": 8, "max": 1024}}

    {"kind": "closed_loop", "clients": 64, "warm_s": 10,
     "steady_start": true, "prompt": {...}, "output": {...}}

Lengths are lognormal (median, sigma) clipped to [min, max].  A segment of
``n`` requests takes its prompt and output lengths from the ``n``
quantiles ``(i + 0.5) / n`` of the distribution and its Poisson gaps from
the quantiles of the exponential, each list permuted.  The permutations
come from the mix's ``schedule_seed`` where it has one, else from the
run's seed; the prompt tokens always come from the run's seed.  With a
``schedule_seed`` every seed serves the same lengths at the same times:
on a node whose step time follows the longest resident row, the order of
the requests moves the tails by more than the system's own noise, and
the spread between runs must be the system's, not the draw's.

Open loop: arrivals follow a schedule fixed in advance, whatever the node
does; the warm segment, the measured window and a tail after it are
segments of their own.  With ``steady_start: n`` the first ``n`` requests
of the warm segment are due at once, with residual output budgets (as
below), standing for the requests a node at this rate has in flight.  Closed loop: ``clients`` callers each send their
next request the moment the previous one completes.  With
``steady_start`` the first request of each client draws its output budget
from the residual-life distribution of the outputs (a length-biased
length, a uniform share of it left), so completions are spread from the
start as in a queue that has run for a long time.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

KINDS = ("open_poisson", "closed_loop")


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one use (``stream``) of a run's seed; any whole
    number, however large, is a valid seed."""
    return np.random.default_rng([int(seed) % (1 << 64), int(stream)])


def quantile_lengths(dist: Dict, n: int) -> np.ndarray:
    """The ``n`` stratified lengths of a clipped lognormal, ascending."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def quantile_gaps(n: int, duration: float) -> np.ndarray:
    """``n`` exponential gaps from stratified quantiles, scaled so that
    they sum to ``duration`` exactly."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (duration / g.sum())


@dataclass
class Item:
    """One request of the traffic: when it is due (seconds after the start
    of the run's traffic), its lengths and its prompt tokens."""

    index: int
    due: float
    prompt_len: int
    max_new: int
    tokens: np.ndarray
    client: int = -1


def _segment(dist_p: Dict, dist_o: Dict, n: int,
             rng: np.random.Generator) -> List[Tuple[int, int]]:
    p = rng.permutation(quantile_lengths(dist_p, n))
    o = rng.permutation(quantile_lengths(dist_o, n))
    return list(zip(p.tolist(), o.tolist()))


def _residual(mix: Dict, n: int, rng: np.random.Generator
              ) -> List[Tuple[int, int]]:
    """``n`` requests caught in flight: a length-biased pick (a request in
    flight is long with odds proportional to its output length), with a
    uniform share of its output left as its budget."""
    if n <= 0:
        return []
    sizes = _segment(mix["prompt"], mix["output"], 256, rng)
    outs = np.array([o for _, o in sizes], np.float64)
    pick = rng.choice(len(sizes), size=n, p=outs / outs.sum())
    left = rng.permutation((np.arange(n) + 0.5) / n)
    return [(sizes[j][0], max(1, int(math.ceil(left[i] * sizes[j][1]))))
            for i, j in enumerate(pick)]


class Traffic:
    """The requests of one run, drawn from the mix and the seed.

    ``vocab`` bounds the prompt token ids (``avoid`` is never drawn: the
    model's end-of-sequence id).  ``warm_s`` and ``seconds`` are the warm
    segment and the measured window; open loops draw ``tail_s`` more."""

    def __init__(self, mix: Dict, seed: int, seconds: float, vocab: int,
                 avoid: int, tail_s: float = 60.0) -> None:
        if mix["kind"] not in KINDS:
            raise ValueError(f"unknown traffic kind {mix['kind']!r}; "
                             f"known: {KINDS}")
        self.mix = mix
        self.kind = mix["kind"]
        self.seed = seed
        self.warm_s = float(mix.get("warm_s", 0.0))
        self.seconds = float(seconds)
        self.vocab = vocab
        self.avoid = avoid
        self._tok_rng = seed_rng(seed, 1)
        self._count = 0
        sched = int(mix.get("schedule_seed", seed))
        if self.kind == "open_poisson":
            self.items = self._open(seed_rng(sched, 0), tail_s)
        else:
            self._closed_rng = seed_rng(sched, 2)
            self._pool: List[Tuple[int, int]] = []

    # ------------------------------------------------------------ tokens
    def _prompt(self, n: int) -> np.ndarray:
        t = self._tok_rng.integers(0, self.vocab, size=n, dtype=np.int64)
        t[t == self.avoid] = (self.avoid + 1) % self.vocab
        return t.astype(np.int32)

    def _item(self, due: float, p: int, o: int, client: int = -1) -> Item:
        it = Item(self._count, due, int(p), int(o), self._prompt(int(p)),
                  client)
        self._count += 1
        return it

    # --------------------------------------------------------- open loop
    def _open(self, rng: np.random.Generator, tail_s: float) -> List[Item]:
        rate = float(self.mix["rate_per_s"])
        items: List[Item] = [
            self._item(0.0, p, o)
            for p, o in _residual(self.mix, int(self.mix.get("steady_start",
                                                            0)), rng)]
        t0 = 0.0
        for dur in (self.warm_s, self.seconds, tail_s):
            n = int(round(rate * dur))
            if n == 0:
                t0 += dur
                continue
            gaps = rng.permutation(quantile_gaps(n, dur))
            due = t0 + np.cumsum(gaps) - gaps
            for t, (p, o) in zip(due, _segment(self.mix["prompt"],
                                               self.mix["output"], n, rng)):
                items.append(self._item(float(t), p, o))
            t0 += dur
        return items

    # ------------------------------------------------------- closed loop
    @property
    def clients(self) -> int:
        return int(self.mix["clients"])

    def first(self) -> List[Item]:
        """The closed loop's first request of every client, due at 0."""
        rng = self._closed_rng
        n = self.clients
        if self.mix.get("steady_start"):
            sizes = _residual(self.mix, n, rng)
        else:
            sizes = _segment(self.mix["prompt"], self.mix["output"], n, rng)
        return [self._item(0.0, p, o, client=i)
                for i, (p, o) in enumerate(sizes)]

    def next_for(self, client: int, due: float) -> Item:
        """The next request of ``client``, due when its last one ended."""
        if not self._pool:
            self._pool = _segment(self.mix["prompt"], self.mix["output"],
                                  256, self._closed_rng)
        p, o = self._pool.pop()
        return self._item(due, p, o, client=client)


def prompt_range(mix: Dict) -> Tuple[int, int]:
    return int(mix["prompt"]["min"]), int(mix["prompt"]["max"])

