"""TPC-H Q6 (``tpch_sf10_q6.rolling_year``) as files: the generator adds
``l_discount`` and leaves ``tpch_lineitem``'s draws alone, the ``q6``
kind's exact reference equals a scan, the mix is balanced, the tiny cell
runs through the program's conjunctive WHERE and product measure and
comes out correct, and the check catches a discount band shifted by one
code and the bfloat16 control."""
import copy
import hashlib
import itertools
import json
from collections import Counter
from types import SimpleNamespace

import numpy as np
import pytest

import bench_tiny
from isla_bench import harness
from isla_bench.deployment import seed_rng
from isla_bench.traffic import Mix

SEED = 2 ** 31 + 61
CELL = "tiny_q6.rolling_year"


def _data(**over):
    return dict(bench_tiny.tiny_config("tpch_sf10_q6")["data"], **over)


@pytest.fixture(scope="module")
def table():
    gen = bench_tiny.load("generators", "tpch_lineitem_q6")
    return gen.generate(_data(rows=60_000, block_rows=1_500), SEED)


@pytest.fixture(scope="module")
def mix_spec():
    return json.loads((bench_tiny.BENCH / "traffic" / "rolling_year.json")
                      .read_text())


def _sha(table) -> str:
    h = hashlib.sha256()
    for k in sorted(table.columns):
        h.update(k.encode())
        h.update(table.columns[k].tobytes())
    return h.hexdigest()


def test_generator_adds_the_discount_and_keeps_lineitem_bits():
    lineitem = bench_tiny.load("generators", "tpch_lineitem")
    q6 = bench_tiny.load("generators", "tpch_lineitem_q6")
    before = _sha(lineitem.generate(_data(), SEED))
    t = q6.generate(_data(), SEED)
    assert _sha(lineitem.generate(_data(), SEED)) == before
    same = lineitem.generate(
        _data(columns=["l_extendedprice", "l_quantity", "l_shipdate"]), SEED)
    assert sorted(t.columns) == ["l_discount", "l_extendedprice",
                                 "l_quantity", "l_shipdate"]
    for k, v in same.columns.items():
        assert np.array_equal(t.columns[k], v)
    disc = t.columns["l_discount"]
    codes = np.rint(disc * 100)
    assert np.array_equal(disc, codes / 100.0)
    assert set(np.unique(codes).tolist()) == set(range(11))
    assert np.all(np.abs(np.bincount(codes.astype(int)) / disc.size
                         - 1 / 11) < 0.01)
    # Its own stream: another seed draws other discounts.
    assert not np.array_equal(q6.generate(_data(), SEED + 1)
                              .columns["l_discount"], disc)


def test_truth_and_live_blocks_match_a_scan(table, mix_spec):
    kind = bench_tiny.load("queries", "q6")
    q = dict(mix_spec["queries"], start=[1, 2300], days=30)
    truth = kind.truth({}, q, table)
    cols = table.columns
    specs = list(itertools.islice(kind.queries(q, 95.6, 0.95,
                                               np.random.default_rng(3)), 48))
    specs += [kind.QuerySpec("AVG", 0.0, 2600.0, 0, 1, 25.0, 1.0, 0.95),
              kind.QuerySpec("SUM", 2520.5, 365.0, 10, 1, 24.0, 1.0, 0.95)]
    for spec in specs:
        m = kind.match(spec, cols)
        v = kind.values(None, cols)[m]
        want = {"COUNT": float(m.sum()), "SUM": float(v.sum()),
                "AVG": float(v.mean()) if v.size else float("nan")}
        got = truth.answer(spec)
        assert got == pytest.approx(want[spec.agg], rel=1e-9, nan_ok=True)
        live = [b for b in range(table.n_blocks)
                if m[table.block_slice(b)].any()]
        assert truth.live_blocks(spec).tolist() == live
    # Short windows leave some blocks without a matching row.
    assert any(len(truth.live_blocks(s)) < table.n_blocks for s in specs)
    with pytest.raises(ValueError):
        truth.live_blocks(kind.QuerySpec("SUM", 1.0, 30.0, 5, 1, 30.0, 1.0,
                                         0.95))


def test_mix_is_balanced_and_where_is_the_match(table, mix_spec):
    cfg = bench_tiny.tiny_config("tpch_sf10_q6")
    mix = Mix(mix_spec, cfg["e"], cfg["beta"])
    combos = mix.kind.deck(mix_spec["queries"])
    assert len(combos) == 2 * 8 * 2
    qs = list(itertools.islice(mix.queries(seed_rng(SEED, 3)),
                               4 * len(combos)))
    assert Counter((s.agg, s.discount, s.quantity) for s in qs) == \
        {c: 4 for c in combos}
    edges = [1 + int(r * 2162 / 4) for r in range(5)]
    for c in combos:
        starts = sorted(s.start for s in qs
                        if (s.agg, s.discount, s.quantity) == c)
        assert np.searchsorted(edges, starts, side="right").tolist() == \
            [1, 2, 3, 4]
    assert all(s.group_by is None and s.days == 365.0 for s in qs)
    from repro.core import Predicate

    for s in qs[:16]:
        where = Predicate(**mix.kind.where(s))
        assert len(where.terms) == 3
        assert np.array_equal(where.mask(table.columns),
                              mix.kind.match(s, table.columns))
    sweep = mix.sweep(seed_rng(SEED, 4), [0.5, 1.0, 2.0])
    assert len(sweep) == 3 * len(combos)


def test_range_where_is_the_one_column_predicate():
    """The range kind's WHERE builds the same one-column ``Predicate`` as
    before conjunctions: equal, and hashed as its four fields."""
    from repro.core import Predicate

    kind = bench_tiny.load("queries", "range")
    spec = json.loads((bench_tiny.BENCH / "traffic" / "adhoc.json")
                      .read_text())["queries"]
    for s in itertools.islice(kind.queries(spec, 1911.0, 0.95,
                                           np.random.default_rng(5)), 24):
        fields = kind.where(s)
        p = Predicate(**fields)
        assert (p.column, p.lo, p.hi, p.eq, p.terms) == (
            s.column, s.lo, s.hi, None, ())
        assert hash(p) == hash((s.column, s.lo, s.hi, None))


@pytest.fixture(scope="module")
def root(tmp_path_factory, mix_spec):
    cfg = bench_tiny.tiny_config("tpch_sf10_q6")
    mix = bench_tiny.tiny_mix("rolling_year")
    q6 = (bench_tiny.BENCH / "queries" / "q6.py").read_text()
    # The reference's band one discount code higher than the WHERE's.
    shifted = q6.replace("    d_lo, d_hi = spec.discount_edges\n"
                         "    return ((ship",
                         "    d_lo, d_hi = spec.discount_edges\n"
                         "    d_lo, d_hi = d_lo + 0.01, d_hi + 0.01\n"
                         "    return ((ship")
    assert shifted != q6
    mix_shifted = copy.deepcopy(mix)
    mix_shifted["queries"]["kind"] = "q6_shifted"
    cells = [{"name": CELL, "config": "tiny_q6", "traffic": "rolling_year",
              "chips": 1, "why": "test"},
             {"name": "tiny_q6.shifted", "config": "tiny_q6",
              "traffic": "shifted", "chips": 1, "why": "test"}]
    return bench_tiny.make_root(
        tmp_path_factory.mktemp("bench"), configs={"tiny_q6": cfg},
        mixes={"rolling_year": mix, "shifted": mix_shifted}, cells=cells,
        queries={"q6_shifted": shifted})


def test_tiny_q6_cell_is_correct(root):
    out = bench_tiny.run(root, CELL, seed=SEED, seconds=4.0, trace=True)
    res = out["result"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert out["notes"]["checked_answers"] >= 2
    held, earned = out["notes"]["bounds_held"]
    assert earned > 0
    for name in ("stage.measure_ms", "stage.zone_ms"):
        assert res["metrics"][name]["value"] > 0.0


def test_shifted_discount_band_is_caught(root):
    out = bench_tiny.run(root, "tiny_q6.shifted", seed=SEED + 1,
                         seconds=2.0)
    assert out["result"]["correct"] is False
    assert out["result"]["checks"]["count_gap"]["value"] > 0


def test_bfloat16_control_is_not_correct(root):
    out = bench_tiny.run(root, CELL, seed=SEED + 2, seconds=2.0,
                         control=True)
    limits = bench_tiny.tiny_config("tpch_sf10_q6")["checks"]
    checks = out["result"]["checks"]
    assert out["result"]["correct"] is False
    assert (checks["moment_gap"]["value"] > 3 * limits["moment_gap"]
            or checks["estimate_gap"]["value"] > 3 * limits["estimate_gap"])
    program = out["notes"]["program_checks"]
    assert program["moment_gap"] < limits["moment_gap"]
    assert program["estimate_gap"] < limits["estimate_gap"]


STAGE_OF = {"stage.measure_ms": "draw.measure", "stage.zone_ms": "plan.zone"}


@pytest.mark.parametrize("case", ["value", "absent"])
@pytest.mark.parametrize("name", sorted(STAGE_OF))
def test_new_stage_readers(name, case):
    read = harness.load_reader(bench_tiny.BENCH.parent, name)
    other = {"draw": 9.0, "plan": 3.0}
    ticks = ([harness.TickRecord([("SUM",)], 100)] * 4
             + [harness.TickRecord([], 0)])
    s1 = dict(other, **{STAGE_OF[name]: 1.5}) if case == "value" else other
    s0 = dict(other, **{STAGE_OF[name]: 1.0}) if case == "value" else other
    counters = dict(answered=0, subsumed=0, deduped=0, samples_drawn=0)
    ctx = harness.metric_context(
        SimpleNamespace(sent=[], ticks=ticks), {"group_domains": {}},
        dict(counters, stage_seconds=s0), dict(counters, stage_seconds=s1),
        (0.0, 1.0, 1.0), 0, None, 0.0, "cpu", 8)
    if case == "value":
        assert read(ctx) == pytest.approx(125.0)
    else:
        # A program that books no such stage reports nothing.
        assert read(ctx) is None
