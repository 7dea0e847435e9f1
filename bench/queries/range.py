"""Query kind ``range``: an aggregate of the measure over a ``[start, start +
length)`` range of one whole-numbered column (a day number), grouped or not.

The mix's ``queries`` entry holds ``aggs``, the range ``column``, the
``start`` range, the ``lengths``, ``group_by`` and ``group_p`` (the share of
grouped queries).  With ``"balanced": true`` the categorical choices
(aggregate, length, grouping) come from shuffled decks that hold every
combination once, and, over ``start_strata`` decks, each combination gets a
start in each of that many equal strata of the start range: every seed sends
the same mix in another order.

The exact reference (``ExactTruth``) is per-(day, group) prefix sums of the
count and the measure, built once from the table.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Iterator, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """One query as the traffic sends it."""

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


def queries(q: dict, e: float, beta: float,
            rng: np.random.Generator) -> Iterator[QuerySpec]:
    """An endless stream of queries drawn from ``rng``."""
    start_lo, start_hi = (int(q["start"][0]), int(q["start"][1]))
    if q.get("balanced"):
        yield from _balanced(q, e, beta, rng, start_lo, start_hi)
    aggs = list(q["aggs"])
    lengths = [float(x) for x in q["lengths"]]
    p_group = float(q.get("group_p", 0.0))
    while True:
        agg = aggs[int(rng.integers(len(aggs)))]
        length = lengths[int(rng.integers(len(lengths)))]
        grouped = bool(rng.random() < p_group)
        yield _range_query(rng, q, e, beta, agg, length, grouped, start_lo,
                           start_hi)


def deck(q: dict) -> list:
    """The (aggregate, length, grouped) combinations of a balanced mix:
    ``group_deck`` grouping slots, round(group_p * group_deck) of them
    grouped."""
    n_g = int(q.get("group_deck", 2))
    k = int(round(float(q.get("group_p", 0.0)) * n_g))
    grp = [True] * k + [False] * (n_g - k)
    return list(itertools.product(q["aggs"],
                                  [float(x) for x in q["lengths"]], grp))


def _balanced(q, e, beta, rng, start_lo, start_hi):
    combos = deck(q)
    n_s = int(q.get("start_strata", 1))
    width = (start_hi - start_lo + 1) / n_s
    while True:
        # Over n_s decks every combination starts once in each stratum.
        strata = np.stack([rng.permutation(n_s) for _ in combos])
        for r in range(n_s):
            for i in rng.permutation(len(combos)):
                agg, length, grouped = combos[int(i)]
                lo = start_lo + int(strata[i, r] * width)
                hi = start_lo + int((strata[i, r] + 1) * width) - 1
                yield _range_query(rng, q, e, beta, agg, length, grouped,
                                   lo, max(hi, lo))


def _range_query(rng, q, e, beta, agg, length, grouped, start_lo, start_hi,
                 e_factor: float = 1.0) -> QuerySpec:
    start = float(rng.integers(start_lo, start_hi + 1))
    return QuerySpec(agg=agg, column=q["column"], lo=start,
                     hi=start + length,
                     group_by=q.get("group_by") if grouped else None,
                     e=float(e) * e_factor, beta=float(beta))


def sweep(q: dict, e: float, beta: float, rng: np.random.Generator,
          e_factors) -> list:
    """Every combination of the deck at each precision factor (random
    starts): the warm-up that reaches the sample counts, and so the device
    shapes, the mix reaches at its extremes."""
    start_lo, start_hi = (int(q["start"][0]), int(q["start"][1]))
    combos = deck(q)
    out = []
    for i in rng.permutation(len(combos)):
        agg, length, grouped = combos[int(i)]
        for f in e_factors:
            out.append(_range_query(rng, q, e, beta, agg, length, grouped,
                                    start_lo, start_hi,
                                    e_factor=float(f)))
    return out


def where(spec: QuerySpec) -> Optional[dict]:
    if spec.column is None:
        return None
    return {"column": spec.column, "lo": spec.lo, "hi": spec.hi}


def match(spec: QuerySpec, columns: dict) -> np.ndarray:
    if spec.column is None:
        return np.ones(next(iter(columns.values())).shape, bool)
    col = columns[spec.column]
    m = np.ones(col.shape, bool)
    if spec.lo is not None:
        m &= col >= spec.lo
    if spec.hi is not None:
        m &= col < spec.hi
    return m


def values(cfg: dict, columns: dict) -> np.ndarray:
    return columns[cfg["measure"]]


def truth(cfg: dict, q: dict, table) -> "ExactTruth":
    group = q["group_by"]
    return ExactTruth(table, values(cfg, table.columns), q["column"], group,
                      cfg["group_domains"][group])


class ExactTruth:
    """Exact COUNT / SUM / AVG over ``[lo, hi)`` ranges of one whole-numbered
    column, overall and per group, and the blocks that hold matching
    rows."""

    def __init__(self, table, price: np.ndarray, column: str,
                 group_col: str, n_groups: int):
        self.column = column
        day = table.columns[column].astype(np.int64)
        grp = table.columns[group_col].astype(np.int64)
        self.n_days = int(day.max()) + 1 if day.size else 0
        self.n_groups = int(n_groups)
        key = day * self.n_groups + grp
        size = self.n_days * self.n_groups
        cnt = np.bincount(key, minlength=size).astype(np.float64)
        s1 = np.bincount(key, weights=price, minlength=size)
        del key
        shape = (self.n_days, self.n_groups)
        z = np.zeros((1, self.n_groups))
        self._cnt = np.concatenate([z, np.cumsum(cnt.reshape(shape), 0)])
        self._s1 = np.concatenate([z, np.cumsum(s1.reshape(shape), 0)])
        # Per block: prefix counts over days, to tell which blocks hold a
        # matching row.
        hist = np.stack([np.bincount(day[table.block_slice(b)],
                                     minlength=self.n_days)
                         for b in range(table.n_blocks)])
        self._block_prefix = np.concatenate(
            [np.zeros((table.n_blocks, 1), np.int64),
             np.cumsum(hist, axis=1)], axis=1)

    def _days(self, spec):
        """The spec's range as prefix indices (no WHERE: every day)."""
        lo = spec.lo if spec.column is not None and spec.lo is not None else 0
        hi = (spec.hi if spec.column is not None and spec.hi is not None
              else math.inf)
        a = math.ceil(min(max(lo, 0), self.n_days))
        b = math.ceil(min(max(hi, 0), self.n_days))
        return a, max(a, b)

    def answer(self, spec: QuerySpec, group: int | None = None) -> float:
        a, b = self._days(spec)
        cnt, s1 = self._cnt[b] - self._cnt[a], self._s1[b] - self._s1[a]
        if group is None:
            c, s = float(cnt.sum()), float(s1.sum())
        else:
            c, s = float(cnt[group]), float(s1[group])
        if spec.agg == "COUNT":
            return c
        if spec.agg == "SUM":
            return s
        if spec.agg == "AVG":
            return s / c if c > 0 else float("nan")
        raise ValueError(f"no exact reference for {spec.agg!r}")

    def live_blocks(self, spec: QuerySpec) -> Optional[np.ndarray]:
        """Indices of blocks that hold at least one row in the spec's range;
        None for a spec with no WHERE (nothing to prune)."""
        if spec.column != self.column:
            return None
        a, b = self._days(spec)
        return np.flatnonzero(self._block_prefix[:, b]
                              - self._block_prefix[:, a] > 0)
