"""Query kind ``q6``: TPC-H Q6 (clause 2.4.6), the revenue a discount band
brought over a year of shipments,

    SELECT SUM(l_extendedprice * l_discount) FROM lineitem
    WHERE l_shipdate >= DATE AND l_shipdate < DATE + days
      AND l_discount BETWEEN DISCOUNT - band AND DISCOUNT + band
      AND l_quantity < QUANTITY

as SUM, or as COUNT of the same WHERE.  The mix's ``queries`` entry holds
``aggs``, the ``start`` range of DATE (a day number), ``days``, the
``discounts`` and the ``band`` (in units of 0.01), the ``quantities``, and
``start_strata``.  The categorical choices (aggregate, discount, quantity)
come from shuffled decks that hold every combination once, and over
``start_strata`` decks each combination gets a start in each of that many
equal strata of the start range: every seed sends the same mix in another
order.  Discounts are two-decimal values, so the band is the half-open
``[DISCOUNT - band - 0.005, DISCOUNT + band + 0.005)``, whose edges no
value sits on.

The exact reference (``Cube``) is a prefix cube over (ship day x discount
x quantity) of the count and the revenue, built once from the table, and a
per-block presence map over (ship day x discount x quantity class) for the
blocks that hold a matching row.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Iterator, Optional

import numpy as np

SHIP, DISCOUNT, QUANTITY, PRICE = ("l_shipdate", "l_discount", "l_quantity",
                                   "l_extendedprice")
DISCOUNT_CODES = 11          # 0.00 .. 0.10
MAX_QUANTITY = 50


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """One query as the traffic sends it; discounts in hundredths."""

    agg: str
    start: float                 # l_shipdate >= start
    days: float                  # l_shipdate < start + days
    discount: int                # DISCOUNT
    band: int                    # |l_discount - DISCOUNT| <= band
    quantity: float              # l_quantity < quantity
    e: float
    beta: float

    @property
    def group_by(self) -> Optional[str]:
        return None

    @property
    def key(self) -> tuple:
        """What the served path shares state on: the WHERE."""
        return (self.start, self.days, self.discount, self.band,
                self.quantity)

    @property
    def discount_edges(self) -> tuple:
        """The band as a half-open range of l_discount values."""
        return ((self.discount - self.band - 0.5) / 100.0,
                (self.discount + self.band + 0.5) / 100.0)


def _codes(q: dict) -> list:
    return [int(round(100 * d)) for d in q["discounts"]]


def deck(q: dict) -> list:
    """The (aggregate, discount, quantity) combinations of the mix."""
    return list(itertools.product(q["aggs"], _codes(q),
                                  [float(x) for x in q["quantities"]]))


def _query(rng, q, e, beta, combo, start_lo, start_hi,
           e_factor: float = 1.0) -> QuerySpec:
    agg, discount, quantity = combo
    start = float(rng.integers(start_lo, start_hi + 1))
    return QuerySpec(agg=agg, start=start, days=float(q["days"]),
                     discount=discount, band=int(round(100 * q["band"])),
                     quantity=quantity, e=float(e) * e_factor,
                     beta=float(beta))


def queries(q: dict, e: float, beta: float,
            rng: np.random.Generator) -> Iterator[QuerySpec]:
    """An endless stream of queries drawn from ``rng``."""
    start_lo, start_hi = int(q["start"][0]), int(q["start"][1])
    combos = deck(q)
    n_s = int(q.get("start_strata", 1))
    width = (start_hi - start_lo + 1) / n_s
    while True:
        # Over n_s decks every combination starts once in each stratum.
        strata = np.stack([rng.permutation(n_s) for _ in combos])
        for r in range(n_s):
            for i in rng.permutation(len(combos)):
                lo = start_lo + int(strata[i, r] * width)
                hi = start_lo + int((strata[i, r] + 1) * width) - 1
                yield _query(rng, q, e, beta, combos[int(i)], lo,
                             max(hi, lo))


def sweep(q: dict, e: float, beta: float, rng: np.random.Generator,
          e_factors) -> list:
    """Every combination of the deck at each precision factor (random
    starts): the warm-up that reaches the sample counts, and so the device
    shapes, the mix reaches at its extremes."""
    start_lo, start_hi = int(q["start"][0]), int(q["start"][1])
    combos = deck(q)
    return [_query(rng, q, e, beta, combos[int(i)], start_lo, start_hi,
                   e_factor=float(f))
            for i in rng.permutation(len(combos)) for f in e_factors]


def where(spec: QuerySpec) -> dict:
    d_lo, d_hi = spec.discount_edges
    return {"terms": [
        {"column": SHIP, "lo": spec.start, "hi": spec.start + spec.days},
        {"column": DISCOUNT, "lo": d_lo, "hi": d_hi},
        {"column": QUANTITY, "hi": spec.quantity}]}


def match(spec: QuerySpec, columns: dict) -> np.ndarray:
    ship, disc = columns[SHIP], columns[DISCOUNT]
    d_lo, d_hi = spec.discount_edges
    return ((ship >= spec.start) & (ship < spec.start + spec.days)
            & (disc >= d_lo) & (disc < d_hi)
            & (columns[QUANTITY] < spec.quantity))


def values(cfg: dict, columns: dict) -> np.ndarray:
    """The revenue of each row: l_extendedprice * l_discount, in
    float64."""
    return (np.asarray(columns[PRICE], np.float64)
            * np.asarray(columns[DISCOUNT], np.float64))


def truth(cfg: dict, q: dict, table) -> "Cube":
    return Cube(table, q["quantities"])


class Cube:
    """Exact COUNT / SUM / AVG of the revenue for any Q6 WHERE, and the
    blocks that hold a matching row.

    Prefix sums over ship days of a (discount code x quantity) grid of
    counts and revenue; per block, which (ship day, discount code,
    quantity class) cells hold a row, where a quantity's class is how many
    of the mix's ``quantities`` it reaches (so ``l_quantity < Q`` is a
    prefix of the classes for every Q of the mix)."""

    def __init__(self, table, quantities):
        cols = table.columns
        day = cols[SHIP].astype(np.int64)
        disc = np.rint(cols[DISCOUNT] * 100).astype(np.int64)
        qty = cols[QUANTITY].astype(np.int64)
        self.n_days = int(day.max()) + 1 if day.size else 0
        shape = (self.n_days, DISCOUNT_CODES, MAX_QUANTITY)
        key = (day * DISCOUNT_CODES + disc) * MAX_QUANTITY + qty - 1
        size = int(np.prod(shape))
        cnt = np.bincount(key, minlength=size).astype(np.float64)
        rev = np.bincount(key, weights=values(None, cols), minlength=size)
        del key
        z = np.zeros((1,) + shape[1:])
        self._cnt = np.concatenate([z, np.cumsum(cnt.reshape(shape), 0)])
        self._rev = np.concatenate([z, np.cumsum(rev.reshape(shape), 0)])
        del cnt, rev
        self.caps = sorted(float(x) for x in quantities)
        qclass = np.searchsorted(self.caps, qty, side="right")
        n_cls = len(self.caps) + 1
        self._present = np.zeros((table.n_blocks, self.n_days,
                                  DISCOUNT_CODES, n_cls), bool)
        cell = (day * DISCOUNT_CODES + disc) * n_cls + qclass
        for b in range(table.n_blocks):
            self._present[b].reshape(-1)[cell[table.block_slice(b)]] = True

    def _box(self, spec: QuerySpec) -> tuple:
        """The spec's (day, discount code) prefix and range bounds."""
        a = int(np.clip(np.ceil(spec.start), 0, self.n_days))
        b = int(np.clip(np.ceil(spec.start + spec.days), a, self.n_days))
        d0 = max(spec.discount - spec.band, 0)
        d1 = min(spec.discount + spec.band, DISCOUNT_CODES - 1) + 1
        return a, b, d0, max(d1, d0)

    def answer(self, spec: QuerySpec, group: int | None = None) -> float:
        a, b, d0, d1 = self._box(spec)
        nq = int(np.clip(np.ceil(spec.quantity) - 1, 0, MAX_QUANTITY))
        c = float((self._cnt[b] - self._cnt[a])[d0:d1, :nq].sum())
        s = float((self._rev[b] - self._rev[a])[d0:d1, :nq].sum())
        if spec.agg == "COUNT":
            return c
        if spec.agg == "SUM":
            return s
        if spec.agg == "AVG":
            return s / c if c > 0 else float("nan")
        raise ValueError(f"no exact reference for {spec.agg!r}")

    def live_blocks(self, spec: QuerySpec) -> np.ndarray:
        """Indices of blocks that hold at least one matching row."""
        if spec.quantity not in self.caps:
            raise ValueError(f"quantity {spec.quantity} is not one of the "
                             f"mix's {self.caps}")
        a, b, d0, d1 = self._box(spec)
        n_cls = self.caps.index(spec.quantity) + 1
        return np.flatnonzero(
            self._present[:, a:b, d0:d1, :n_cls].any(axis=(1, 2, 3)))
