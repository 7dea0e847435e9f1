"""The one general traffic generator: reads a mix file (``bench/traffic/
<mix>.json``) and yields query specs from a seed.

A mix file holds:

* ``loop``: ``"closed"`` -- ``clients`` that each send their next query
  ``think_ms`` after their last answer;
* ``queries``: ``{"kind": "range", ...}`` -- an aggregate (``aggs``) of the
  measure over a random ``[start, start + length)`` range of one
  ``column``, ``length`` one of ``lengths``, grouped by ``group_by`` with
  probability ``group_p``;
* ``warmup`` and ``check``: how the harness warms up and how many answers
  it compares with the reference.

Every query asks for the configuration's ``(e, beta)``.  With
``"balanced": true`` the categorical choices (aggregate, length, grouping)
come from shuffled decks that hold every combination once, and, over
``start_strata`` decks, each combination gets a start in each of that many
equal strata of the start range: every seed sends the same mix in another
order.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Iterator, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """One query as the traffic sends it (the harness maps it onto the
    program's query type)."""

    agg: str
    column: Optional[str]        # WHERE column (None: no WHERE)
    lo: Optional[float]          # WHERE column >= lo
    hi: Optional[float]          # WHERE column < hi
    group_by: Optional[str]
    e: float
    beta: float

    @property
    def key(self) -> tuple:
        """What the served path shares state on: WHERE and GROUP BY."""
        return (self.column, self.lo, self.hi, self.group_by)


class Mix:
    """A traffic mix bound to a configuration's precision."""

    def __init__(self, spec: dict, e: float, beta: float):
        self.spec = spec
        self.e = float(e)
        self.beta = float(beta)
        if spec["loop"] != "closed":
            raise ValueError(f"unknown loop {spec['loop']!r}")
        self.queries_spec = spec["queries"]
        if self.queries_spec["kind"] != "range":
            raise ValueError(f"unknown query kind "
                             f"{self.queries_spec['kind']!r}")

    @property
    def clients(self) -> int:
        return int(self.spec.get("clients", 1))

    @property
    def think_s(self) -> float:
        return float(self.spec.get("think_ms", 0.0)) / 1e3

    def queries(self, rng: np.random.Generator) -> Iterator[QuerySpec]:
        """An endless stream of queries drawn from ``rng``."""
        q = self.queries_spec
        start_lo, start_hi = (int(q["start"][0]), int(q["start"][1]))
        if q.get("balanced"):
            yield from self._balanced(q, rng, start_lo, start_hi)
        aggs = list(q["aggs"])
        lengths = [float(x) for x in q["lengths"]]
        p_group = float(q.get("group_p", 0.0))
        while True:
            agg = aggs[int(rng.integers(len(aggs)))]
            length = lengths[int(rng.integers(len(lengths)))]
            grouped = bool(rng.random() < p_group)
            yield self._range_query(rng, q, agg, length, grouped,
                                    start_lo, start_hi)

    def deck(self) -> list:
        """The (aggregate, length, grouped) combinations of a balanced range
        mix: ``group_deck`` grouping slots, round(group_p * group_deck) of
        them grouped."""
        q = self.queries_spec
        n_g = int(q.get("group_deck", 2))
        k = int(round(float(q.get("group_p", 0.0)) * n_g))
        grp = [True] * k + [False] * (n_g - k)
        return list(itertools.product(q["aggs"],
                                      [float(x) for x in q["lengths"]], grp))

    def _balanced(self, q, rng, start_lo, start_hi):
        deck = self.deck()
        n_s = int(q.get("start_strata", 1))
        width = (start_hi - start_lo + 1) / n_s
        while True:
            # Over n_s decks every combination starts once in each stratum.
            strata = np.stack([rng.permutation(n_s) for _ in deck])
            for r in range(n_s):
                for i in rng.permutation(len(deck)):
                    agg, length, grouped = deck[int(i)]
                    lo = start_lo + int(strata[i, r] * width)
                    hi = start_lo + int((strata[i, r] + 1) * width) - 1
                    yield self._range_query(rng, q, agg, length, grouped,
                                            lo, max(hi, lo))

    def _range_query(self, rng, q, agg, length, grouped, start_lo,
                     start_hi, e_factor: float = 1.0) -> QuerySpec:
        start = float(rng.integers(start_lo, start_hi + 1))
        return QuerySpec(agg=agg, column=q["column"], lo=start,
                         hi=start + length,
                         group_by=q.get("group_by") if grouped else None,
                         e=self.e * e_factor, beta=self.beta)

    def sweep(self, rng: np.random.Generator, e_factors) -> list:
        """Every combination of the deck at each precision factor (random
        starts): the warm-up that reaches the sample counts, and so the
        device shapes, the mix reaches at its extremes."""
        q = self.queries_spec
        start_lo, start_hi = (int(q["start"][0]), int(q["start"][1]))
        deck = self.deck()
        out = []
        for i in rng.permutation(len(deck)):
            agg, length, grouped = deck[int(i)]
            for f in e_factors:
                out.append(self._range_query(rng, q, agg, length, grouped,
                                             start_lo, start_hi,
                                             e_factor=float(f)))
        return out
