"""The one traffic generator: a traffic mix's data file in, operations out.

A mix (`traffic/<name>.json`) states the p values and their integer
weights, `k`, the share of inserts, the query-pool size and the loop that
offers the load (`loops/<kind>.py`). Every seed gets the same multiset of
work in another order: p values come from shuffled decks that hold each p
`weight` times, inserts from shuffled decks of operations, and open-loop
gaps from a fixed set of quantiles of the arrival law, shuffled.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from fractions import Fraction

import numpy as np

QUERY, INSERT = "query", "insert"


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent NumPy generator per (seed, stream)."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


@dataclass
class Op:
    kind: str          # QUERY | INSERT
    index: int         # row of the query pool or of the insert pool
    p: float = 0.0
    k: int = 0


class Deck:
    """Endless draws from shuffled copies of a fixed card list."""

    def __init__(self, cards: list, rng: np.random.Generator):
        self.cards, self.rng, self.hand = list(cards), rng, []

    def draw(self):
        if not self.hand:
            self.hand = [self.cards[i]
                         for i in self.rng.permutation(len(self.cards))]
        return self.hand.pop()


class Stream:
    """The operation sequence of one run, drawn from the seed."""

    def __init__(self, traffic: dict, seed: int, pool: int,
                 insert_pool: int = 0):
        ps, weights = traffic["p"], traffic["weights"]
        if len(ps) != len(weights) or min(weights) < 1:
            raise ValueError("traffic: one positive integer weight per p")
        self.k = int(traffic["k"])
        self.pool, self.insert_pool = int(pool), int(insert_pool)
        self._p = Deck([float(p) for p, w in zip(ps, weights)
                        for _ in range(int(w))], rng_for(seed, 1))
        share = Fraction(traffic.get("insert_share", 0)).limit_denominator(100)
        self._ops = Deck([INSERT] * share.numerator
                         + [QUERY] * (share.denominator - share.numerator),
                         rng_for(seed, 2))
        self._rng = rng_for(seed, 3)
        self._inserts = 0

    def next(self) -> Op:
        if self._ops.draw() == INSERT and self._inserts < self.insert_pool:
            self._inserts += 1
            return Op(INSERT, self._inserts - 1)
        return Op(QUERY, int(self._rng.integers(self.pool)),
                  self._p.draw(), self.k)


def lp_lanes(ps, cutoff: float) -> list[float]:
    """One p per engine lane (base graph x exact/verify) the mix uses."""
    lanes = {}
    for p in ps:
        base = 1.0 if p <= cutoff else 2.0
        lanes.setdefault((base, p == base), float(p))
    return list(lanes.values())


def arrival_offsets(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Open-loop due times (s from the window's start), one per request.

    Bursts of `burst` requests arrive with gaps of mean burst / rate_qps.
    The gaps are the midpoint quantiles of the arrival law ("poisson":
    exponential gaps), so every seed offers the same gaps in another order
    and the same number of requests in the window.
    """
    rate, burst = float(traffic["rate_qps"]), int(traffic["burst"])
    mean = burst / rate
    m = max(1, int(math.ceil(seconds / mean)))
    law = traffic.get("arrivals", "poisson")
    if law != "poisson":
        raise ValueError(f"unknown arrival law {law!r}")
    gaps = -mean * np.log1p(-(np.arange(m) + 0.5) / m)
    gaps = gaps[rng_for(seed, 4).permutation(m)]
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    starts = starts[starts < seconds]
    return np.repeat(starts, burst)


@dataclass
class Record:
    """What the harness knows of one operation of the window."""

    op: Op
    due: float                     # submission (closed) or due time (open)
    admitted: float = 0.0          # when the generator actually handed it in
    visible: int = 0               # rows searchable when it was admitted
    finish: float | None = None
    ids: np.ndarray | None = None
    dists: np.ndarray | None = None
    error: str | None = None


@dataclass
class Recorder:
    """Per-operation records of a window, keyed by request id."""

    records: dict[int, Record] = field(default_factory=dict)
    inserted: list[int] = field(default_factory=list)  # insert-pool rows
    window: tuple[float, float] = (0.0, 0.0)
    closed: float = 0.0            # when the drain after the window ended

    def add(self, rid: int, rec: Record) -> None:
        self.records[rid] = rec

    def finish(self, results: dict, failures: dict, now: float) -> list[int]:
        """Record answers and failures; returns the request ids closed."""
        done = []
        for rid, (ids, dists) in results.items():
            rec = self.records.get(rid)
            if rec is None:
                continue
            rec.finish, rec.ids, rec.dists = now, np.asarray(ids), \
                np.asarray(dists)
            done.append(rid)
        for rid, err in failures.items():
            rec = self.records.get(rid)
            if rec is None:
                continue
            rec.error = err
            done.append(rid)
        return done
