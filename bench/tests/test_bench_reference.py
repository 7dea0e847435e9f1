"""The plain reference: the range kind's prefix-sum exact answers and
drawn-row statistics against brute-force numpy passes, and the bfloat16
control."""
import math

import numpy as np
import pytest

import bench_tiny
from isla_bench import reference

tpch = bench_tiny.load("generators", "tpch_lineitem")
range_kind = bench_tiny.load("queries", "range")

CFG = {"scale_factor": 0.1, "rows": 120_000, "block_rows": 10_000}


def spec(agg, lo, hi, column="l_shipdate"):
    return range_kind.QuerySpec(agg, column, lo, hi, None, 1.0, 0.95)


@pytest.fixture(scope="module")
def table():
    return tpch.generate(CFG, 4242)


@pytest.fixture(scope="module")
def truth(table):
    return range_kind.truth(
        {"measure": "l_extendedprice", "group_domains": {"rf_ls": 4}},
        {"column": "l_shipdate", "group_by": "rf_ls"}, table)


@pytest.mark.parametrize("lo,hi", [(0, 3000), (100, 107), (1263, 1628),
                                   (2500, 2900), (5.5, 40.2)])
def test_exact_answers_match_brute_force(table, truth, lo, hi):
    day = table.columns["l_shipdate"]
    price = table.columns["l_extendedprice"]
    grp = table.columns["rf_ls"]
    m = (day >= lo) & (day < hi)
    for agg in ("COUNT", "SUM", "AVG"):
        want = {"COUNT": m.sum(), "SUM": price[m].sum(),
                "AVG": price[m].mean() if m.any() else math.nan}[agg]
        got = truth.answer(spec(agg, lo, hi))
        assert got == pytest.approx(want, rel=1e-12, nan_ok=True)
        for g in range(4):
            mg = m & (grp == g)
            want = {"COUNT": mg.sum(), "SUM": price[mg].sum(),
                    "AVG": price[mg].mean() if mg.any() else math.nan}[agg]
            assert truth.answer(spec(agg, lo, hi), g) == pytest.approx(
                want, rel=1e-12, nan_ok=True)
    live = [b for b in range(table.n_blocks)
            if np.any(m[table.block_slice(b)])]
    assert truth.live_blocks(spec("COUNT", lo, hi)).tolist() == live
    assert truth.live_blocks(spec("COUNT", None, None, None)) is None
    assert truth.answer(spec("SUM", None, None, None)) == pytest.approx(
        price.sum(), rel=1e-12)


def _draw(table, n, seed):
    rng = np.random.default_rng(seed)
    sizes = np.asarray(table.block_sizes())
    bids = np.repeat(np.arange(table.n_blocks), n)
    idx = np.concatenate([b * table.block_rows
                          + rng.integers(0, sizes[b], n)
                          for b in range(table.n_blocks)])
    return bids, idx, sizes.astype(np.float64)


def test_sample_stats_match_brute_force(table):
    bids, idx, sizes = _draw(table, 700, 1)
    day = table.columns["l_shipdate"][idx]
    val = table.columns["l_extendedprice"][idx]
    grp = table.columns["rf_ls"][idx].astype(np.int64)
    match = (day >= 300) & (day < 1400)
    st = reference.sample_stats(bids, val, match, grp, 4, sizes)
    for g in range(4):
        sel = match & (grp == g)
        n = sel.sum()
        assert st["groups"][g]["n"] == n
        assert st["groups"][g]["mean"] == pytest.approx(val[sel].mean(),
                                                        rel=1e-12)
        assert st["groups"][g]["sigma"] == pytest.approx(
            val[sel].std(ddof=1), rel=1e-9)
        w = sum(sizes[b] * np.sum(sel & (bids == b)) / 700
                for b in range(table.n_blocks))
        assert st["groups"][g]["w"] == pytest.approx(w, rel=1e-12)
    assert st["n"] == match.sum()
    assert st["total_drawn"] == len(idx)
    plain = reference.sample_stats(bids, val, match, None, 1, sizes)
    assert plain["groups"] is None
    assert plain["mean"] == pytest.approx(val[match].mean(), rel=1e-12)


def test_bfloat16_control_departs_from_float64(table):
    bids, idx, sizes = _draw(table, 200, 2)
    day = table.columns["l_shipdate"][idx]
    val = table.columns["l_extendedprice"][idx]
    grp = table.columns["rf_ls"][idx].astype(np.int64)
    match = (day >= 0) & (day < 800)
    f64 = reference.answer_fields(
        "COUNT", 0.95, reference.sample_stats(bids, val, match, grp, 4,
                                              sizes))
    bf = reference.answer_fields(
        "COUNT", 0.95, reference.sample_stats(bids, val, match, grp, 4,
                                              sizes, "bfloat16"))
    assert f64["counts"] == bf["counts"]
    gap = max(reference.rel_gap(bf["rel"][k], v)
              for k, v in f64["rel"].items())
    assert 1e-6 < gap < 1e-2


def test_bf16_rounding():
    x = np.array([1.0, 1.00390625, 3.14159, -2.5e4], np.float32)
    y = reference._to_bf16(x)
    assert y[0] == 1.0 and y[1] == 1.0   # 1 + 2^-8 ties to even
    assert np.all(np.abs(y - x) <= np.abs(x) * 2.0 ** -8)


def test_unbacked_bounds():
    z = reference.z_score(0.95)
    st = {"n": 100.0, "sigma": 10.0, "groups": [
        {"n": 50.0, "sigma": 10.0}, {"n": 1.0, "sigma": math.nan}]}
    e_ok = z * 10.0 / math.sqrt(100.0) * 1.0001
    answer = {"bound": e_ok, "groups": [
        {"group": 0, "bound": e_ok}, {"group": 1, "bound": None}]}
    # The total is backed at m = 100 rows; group 0 has only 50 of them.
    assert reference.unbacked_bounds("AVG", e_ok, 0.95, st, answer) == 1
    assert reference.unbacked_bounds("COUNT", e_ok, 0.95, st, answer) == 0
    answer["groups"][1]["bound"] = 1.0
    assert reference.unbacked_bounds("AVG", e_ok, 0.95, st, answer) == 2


EST = bench_tiny.tiny_config()["estimator"]


def _program_params():
    from repro.core import IslaParams
    from isla_bench.service import ISLA_PARAMS

    return IslaParams(e=1.0, beta=0.95, **{k: EST[k] for k in ISLA_PARAMS})


def test_calibrated_partials_match_the_program_host_engine():
    """The reference's calibrated cell estimate against the program's
    float64 host Phase 2 (a test may import the program; the reference
    does not)."""
    from repro.core import engine

    rng = np.random.default_rng(3)
    sizes = rng.integers(0, 40, size=(2, 500))
    ms, ml = np.zeros((500, 4)), np.zeros((500, 4))
    for i in range(500):
        xs = rng.uniform(5.0, 8.0, sizes[0, i])
        ys = rng.uniform(12.0, 15.0, sizes[1, i])
        ms[i] = [xs.size, xs.sum(), (xs * xs).sum(), (xs ** 3).sum()]
        ml[i] = [ys.size, ys.sum(), (ys * ys).sum(), (ys ** 3).sum()]
    lo, hi, empty = reference.calibrated_partials(ms, ml, 10.0, EST)
    host = engine.phase2_iteration_batch(ms, ml, 10.0, _program_params(),
                                         mode="calibrated").avg
    assert np.array_equal(empty, (ms[:, 0] < 1) | (ml[:, 0] < 1))
    assert np.all(lo <= hi)
    assert np.all(lo == hi)   # no k near nought among these cells
    np.testing.assert_allclose(lo, host, rtol=1e-12)
    # One row in each region: k is nought, the cell answers c.
    one = reference.calibrated_partials(ms[:1] * 0 + [1, 6, 36, 216],
                                        ml[:1] * 0 + [1, 14, 196, 2744],
                                        10.0, EST)
    assert one[0][0] <= 10.0 <= one[1][0]


def test_anchors_match_the_program():
    from repro.core import Predicate
    from repro.core.preestimation import PilotResult
    from repro.core.types import Anchor

    rng = np.random.default_rng(4)

    def call(b, n):
        return (b, {"v": rng.gamma(2.0, 10.0, n), "d": rng.integers(
            0, 100, n).astype(np.float64)})

    boot = [call(b, 64) for b in range(0, 12, 3)]
    pilot = [call(b, 9) for b in range(12)]
    anchors = reference.Anchors(boot, pilot, lambda cols: cols["v"], EST)
    pv = np.concatenate([r["v"] for _, r in pilot])
    pr = PilotResult(sketch0=float(pv.mean()), sigma=float(pv.std(ddof=1)),
                     pilot_size=pv.size, shift=0.0, values=pv)
    params = _program_params()
    g = Anchor.from_pilot(pr, params)
    cols = {k: np.concatenate([r[k] for _, r in boot + pilot])
            for k in ("v", "d")}
    for lo, hi in [(0, 30), (10, 12), (0, 100), (40, 90)]:
        want = g.refine_for_predicate(cols, Predicate(column="d", lo=lo,
                                                      hi=hi),
                                      params, measure="v",
                                      min_support=EST["anchor_min_support"])
        got = anchors.for_mask(range_kind.match(
            spec("AVG", lo, hi, column="d"), anchors.rows))
        assert got["sketch0"] == pytest.approx(want.sketch0, rel=1e-12)
        assert got["sigma"] == pytest.approx(want.sigma, rel=1e-12)
        assert got["cuts"] == pytest.approx(want.boundaries.as_tuple(),
                                            rel=1e-12)


def test_split_first_tick():
    rows = {"v": np.zeros(1)}
    boot = [(b, rows) for b in range(5)]
    pilot = [(b, rows) for b in range(5)]
    tick = [(b, rows) for b in (0, 2, 3)]
    assert reference.split_first_tick(boot + pilot + tick, 5) == (boot,
                                                                 pilot)
    strided = [(b, rows) for b in (0, 2, 4)]
    assert reference.split_first_tick(strided + pilot + pilot, 5) == (
        strided, pilot)
    with pytest.raises(ValueError):
        reference.split_first_tick(strided + tick, 5)


def test_a_row_on_a_cut_counts_on_both_sides():
    frame = {"sketch0": 100.0, "shift": 0.0, "sigma": 20.0,
             "cuts": (60.0, 90.0, 110.0, 140.0)}
    bids = np.zeros(6, np.int64)
    sizes = np.array([1000.0])

    def stats(vals):
        return reference.calibrated_stats(
            bids, np.asarray(vals, np.float64), np.ones(6, bool), None, 1,
            sizes, frame, EST)["mean"]

    lo, hi = stats([70.0, 80.0, 120.0, 130.0, 125.0, 90.0])
    assert lo < hi
    lo, hi = stats([70.0, 80.0, 120.0, 130.0, 125.0, 95.0])
    assert lo == hi
