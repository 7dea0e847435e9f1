"""The lineitem generator keeps TPC-H clause 4.2.3's rules and is
deterministic in its seed."""
import numpy as np
import pytest

import bench_tiny

tpch = bench_tiny.load("generators", "tpch_lineitem")

CFG = {"scale_factor": 0.1, "rows": 200_003, "block_rows": 20_000,
       "columns": ["l_extendedprice", "l_quantity", "l_shipdate", "rf_ls"]}


@pytest.fixture(scope="module")
def table():
    return tpch.generate(CFG, 2 ** 31 + 11)


def test_rows_and_blocks(table):
    assert table.rows == CFG["rows"]
    assert table.n_blocks == 11
    sizes = table.block_sizes()
    assert sizes[:-1] == [20_000] * 10 and sizes[-1] == 3
    assert sum(len(b["rf_ls"]) for b in table.blocks()) == CFG["rows"]


def test_seed_determinism():
    a = tpch.generate(CFG, 5)
    b = tpch.generate(CFG, 5)
    c = tpch.generate(CFG, 6)
    for k in a.columns:
        np.testing.assert_array_equal(a.columns[k], b.columns[k])
    assert not np.array_equal(a.columns["l_extendedprice"],
                              c.columns["l_extendedprice"])


def test_dates_and_flags(table):
    ship = table.columns["l_shipdate"]
    code = table.columns["rf_ls"]
    assert ship.min() >= 1 and ship.max() <= tpch.ORDER_LAST_DAY + 121
    assert set(np.unique(code)) == {0.0, 1.0, 2.0, 3.0}
    # Linestatus F (A-F, N-F, R-F) iff shipped on or before CURRENTDATE.
    f = np.isin(code, (0.0, 1.0, 3.0))
    np.testing.assert_array_equal(f, ship <= tpch.CURRENT_DAY)
    # Returned lines (R or A) were received on or before CURRENTDATE, so
    # shipped at least a day earlier; R and A come at even odds.
    returned = np.isin(code, (0.0, 3.0))
    assert ship[returned].max() <= tpch.CURRENT_DAY - 1
    r, a = np.sum(code == 3.0), np.sum(code == 0.0)
    assert abs(r - a) < 5 * np.sqrt(r + a)
    # N-F: shipped before CURRENTDATE, received after it (<= 30 days).
    nf = code == 1.0
    assert ship[nf].min() >= tpch.CURRENT_DAY - 30


def test_price_formula(table):
    assert tpch.retail_price_cents(np.array([1]))[0] == 90100   # 901.00
    assert tpch.retail_price_cents(np.array([1000]))[0] == 90100
    qty = table.columns["l_quantity"]
    price = table.columns["l_extendedprice"]
    assert qty.min() == 1 and qty.max() == 50
    cents = np.round(price * 100).astype(np.int64)
    assert np.all(cents % qty.astype(np.int64) == 0)
    retail = cents // qty.astype(np.int64)
    pk_max = int(CFG["scale_factor"] * 200_000)
    possible = np.unique(tpch.retail_price_cents(np.arange(1, pk_max + 1)))
    assert np.all(np.isin(retail, possible))


def test_sorted_variant_is_a_permutation():
    base = tpch.generate(CFG, 9)
    srt = tpch.generate(dict(CFG, sort_by="l_shipdate"), 9)
    assert np.all(np.diff(srt.columns["l_shipdate"]) >= 0)
    for k in base.columns:
        np.testing.assert_array_equal(np.sort(base.columns[k]),
                                      np.sort(srt.columns[k]))


def test_default_columns():
    t = tpch.generate({k: v for k, v in CFG.items() if k != "columns"}, 1)
    assert set(t.columns) == set(tpch.DEFAULT_COLUMNS)
