"""Generator ``tpch_lineitem``: TPC-H ``lineitem`` columns, generated from
a seed.

The rules are those of TPC-H v3 clause 4.2.3 (the ``dbgen`` population
rules), vectorised with numpy:

* ``O_ORDERDATE`` uniform over [1992-01-01, 1998-08-02]; 1..7 lines an order;
* ``L_SHIPDATE = O_ORDERDATE + U[1, 121]``, ``L_RECEIPTDATE = L_SHIPDATE +
  U[1, 30]``;
* ``L_RETURNFLAG`` R or A (even odds) where the receipt date is on or before
  CURRENTDATE (1995-06-17), else N;
* ``L_LINESTATUS`` O where the ship date is after CURRENTDATE, else F;
* ``L_EXTENDEDPRICE = L_QUANTITY * P_RETAILPRICE`` with ``L_QUANTITY`` in
  U[1, 50] and ``P_RETAILPRICE = (90000 + (partkey / 10) mod 20001 + 100 *
  (partkey mod 1000)) / 100`` for a uniform partkey in [1, SF * 200 000].

The table is not dbgen's byte stream: the same distributions from another
generator.  Dates are day numbers counted from 1992-01-01.  By default
three columns are kept, as float64 (what the served path samples):

* ``l_extendedprice`` -- the measure;
* ``l_shipdate`` -- the day number the date predicates filter on;
* ``rf_ls`` -- the code of ``(l_returnflag, l_linestatus)`` in Q1's order:
  0 A-F, 1 N-F, 2 N-O, 3 R-F (R-O and A-O cannot occur).

Rows come in order-key order (each block mixes every date) or, where the
configuration says ``"sort_by": "l_shipdate"``, sorted on the ship date.
"""
from __future__ import annotations

import numpy as np

from isla_bench.deployment import Table, seed_rng

ORDER_LAST_DAY = 2405       # 1998-08-02
CURRENT_DAY = 1263          # 1995-06-17

STREAM_DATA = 1
DEFAULT_COLUMNS = ("l_extendedprice", "l_shipdate", "rf_ls")


def _lines_per_order(rng: np.random.Generator, rows: int) -> np.ndarray:
    """Lines per order, 1..7 uniform, cut so they add up to ``rows``."""
    n = rows // 4 + 4096
    while True:
        nl = rng.integers(1, 8, size=n, dtype=np.int64)
        csum = np.cumsum(nl)
        if csum[-1] >= rows:
            break
        n *= 2
    k = int(np.searchsorted(csum, rows))
    nl = nl[:k + 1]
    nl[-1] -= int(csum[k]) - rows
    return nl


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE in cents (clause 4.2.3); int32 holds it and its
    product with a quantity (at most 50 * 209 899)."""
    pk = partkey.astype(np.int32, copy=False)
    return 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)


def generate(cfg: dict, seed: int) -> Table:
    """The configuration's ``lineitem`` columns for ``seed``.

    ``cfg`` keys: ``scale_factor``, ``rows``, ``block_rows`` and optionally
    ``sort_by`` (``"l_shipdate"``) and ``columns`` (which to keep, of
    ``l_extendedprice``, ``l_quantity``, ``l_shipdate``, ``rf_ls``).
    """
    rows = int(cfg["rows"])
    sf = float(cfg["scale_factor"])
    rng = seed_rng(seed, STREAM_DATA)
    nl = _lines_per_order(rng, rows)
    odate = rng.integers(0, ORDER_LAST_DAY + 1, size=nl.size,
                         dtype=np.int16)
    ship = np.repeat(odate, nl)
    del odate, nl
    ship += rng.integers(1, 122, size=rows, dtype=np.int16)
    receipt = ship + rng.integers(1, 31, size=rows, dtype=np.int16)
    returned = receipt <= CURRENT_DAY
    del receipt
    r_not_a = rng.integers(0, 2, size=rows, dtype=np.int8)
    late = (ship > CURRENT_DAY).view(np.int8)          # linestatus O
    # A-F 0, N-F 1, N-O 2, R-F 3: a returned line shipped before CURRENT.
    code = np.where(returned, 3 * r_not_a, 1 + late).astype(np.float64)
    del returned, r_not_a, late
    qty = rng.integers(1, 51, size=rows, dtype=np.int32)
    partkey = rng.integers(1, int(round(sf * 200_000)) + 1, size=rows,
                           dtype=np.int32)
    price = (qty * retail_price_cents(partkey)).astype(np.float64) / 100.0
    del partkey
    order = None
    if cfg.get("sort_by") == "l_shipdate":
        order = np.argsort(ship, kind="stable")
    elif cfg.get("sort_by") is not None:
        raise ValueError(f"unknown sort_by {cfg['sort_by']!r}")
    cols = {"l_extendedprice": price, "l_quantity": qty.astype(np.float64),
            "l_shipdate": ship.astype(np.float64), "rf_ls": code}
    del ship, qty
    keep = cfg.get("columns", DEFAULT_COLUMNS)
    cols = {k: cols[k] for k in keep}
    if order is not None:
        cols = {k: v[order] for k, v in cols.items()}
    return Table(columns=cols, block_rows=int(cfg["block_rows"]))
