"""Conjunctive WHEREs and derived measures (TPC-H Q6's shape).

A ``Predicate`` is an AND of one-column terms in one canonical, hashable
form; its mask is the AND of the terms' masks, and the zone map judges it
per term (EMPTY if any term is provably empty, FULL only if every term is
provably full), soundly even where a term's column is untracked.  A
one-column predicate compares and hashes exactly as before conjunctions
existed.  A ``Measure`` is one column or a product of columns, evaluated in
float64 wherever the executor reads values; the executor's answers under
a three-column WHERE over the product measure are held to a float64 plain
reference over the rows it drew, on the host and device routes.
"""
import dataclasses
import math
from typing import Optional

import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core.boundaries import make_boundaries
from repro.core.engine import IslaQuery
from repro.core.multiquery import MultiQueryExecutor, table_sampler
from repro.core.preestimation import z_score
from repro.core.types import (ZONE_EMPTY, ZONE_FULL, ZONE_PARTIAL, Anchor,
                              IslaParams, Measure, Predicate, ZoneMap)
from repro.launch.serve import IslaAdmissionLoop

PRICE, DISC, QTY, SHIP = "price", "disc", "qty", "ship"
MEASURE = [PRICE, DISC]


@dataclasses.dataclass(frozen=True)
class _OneColumn:
    """``types.Predicate``'s fields and dataclass semantics as they were
    before conjunctions: what a one-column predicate must still equal and
    hash as."""

    column: str = "value"
    lo: Optional[float] = None
    hi: Optional[float] = None
    eq: Optional[float] = None


def _q6_tables(n_blocks=6, rows=3000, seed=0):
    """Blocks of Q6-shaped rows: a price, a two-decimal discount in
    0.00-0.10, a quantity in 1..50 and a ship day in 0..99."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_blocks):
        qty = rng.integers(1, 51, rows).astype(np.float64)
        out.append({PRICE: qty * rng.uniform(900.0, 2100.0, rows),
                    DISC: rng.integers(0, 11, rows) / 100.0,
                    QTY: qty,
                    SHIP: rng.integers(0, 100, rows).astype(np.float64)})
    return out


def _q6_where(start=20.0, days=40.0, disc=0.05, qty=30.0) -> Predicate:
    return Predicate(terms=[
        {"column": SHIP, "lo": start, "hi": start + days},
        {"column": DISC, "lo": disc - 0.015, "hi": disc + 0.015},
        {"column": QTY, "hi": qty}])


# ---------------------------------------------------------------------------
# The predicate.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fields", [
    dict(column="day", lo=3.0, hi=9.0),
    dict(column="flag", eq=1.0),
    dict(column="value", lo=100.0),
    dict(),
])
def test_one_column_predicate_keeps_eq_and_hash(fields):
    p, old = Predicate(**fields), _OneColumn(**fields)
    assert hash(p) == hash(old)
    assert (p.column, p.lo, p.hi, p.eq) == dataclasses.astuple(old)
    assert p == Predicate(**fields) and p.terms == ()
    assert p != Predicate(**dict(fields, hi=1e9))


def test_conjunction_normal_form():
    a = {"column": SHIP, "lo": 1.0, "hi": 366.0}
    b = {"column": QTY, "hi": 24.0}
    c = Predicate(DISC, lo=0.045, hi=0.075)
    p = Predicate(terms=[a, b, c])
    # Any order, Predicate or mapping terms, duplicates, nesting and a
    # bound-free term give one form, in column order.
    assert p == Predicate(terms=[c, b, Predicate(terms=[a, b]), a,
                                 {"column": "x"}])
    assert hash(p) == hash(Predicate(terms=(b, c, a)))
    assert [t.column for t in p.terms] == [DISC, QTY, SHIP]
    assert p.columns == (DISC, QTY, SHIP)
    assert (p.column, p.lo, p.hi, p.eq) == (None, None, None, None)
    assert p.describe() == ("disc >= 0.045 AND disc < 0.075 AND qty < 24 "
                            "AND ship >= 1 AND ship < 366")
    # A conjunction of one term is that one-column predicate.
    one = Predicate(terms=[b])
    assert one == Predicate(QTY, hi=24.0) and hash(one) == hash(
        _OneColumn(QTY, hi=24.0))
    assert len({p: 1, Predicate(terms=[a, b, c]): 2}) == 1
    with pytest.raises(TypeError):
        Predicate(terms=[("qty", None, 24.0)])


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10 ** 6), n_terms=st.integers(1, 4))
def test_conjunction_mask_is_the_and_of_its_terms(seed, n_terms):
    rng = np.random.default_rng(seed)
    cols = {c: rng.integers(0, 8, 200).astype(np.float64)
            for c in ("a", "b", "c")}
    terms, want = [], np.ones(200, bool)
    for _ in range(n_terms):
        c = ("a", "b", "c")[int(rng.integers(3))]
        lo, hi, eq = (float(x) if rng.random() < 0.5 else None
                      for x in rng.integers(0, 8, 3))
        terms.append({"column": c, "lo": lo, "hi": hi, "eq": eq})
        x = cols[c]
        if lo is not None:
            want &= x >= lo
        if hi is not None:
            want &= x < hi
        if eq is not None:
            want &= x == eq
    assert np.array_equal(Predicate(terms=terms).mask(cols), want)


@settings(max_examples=60, deadline=None)
@given(
    blocks=st.lists(st.lists(st.tuples(st.integers(0, 4), st.integers(0, 4),
                                       st.integers(0, 4)),
                             min_size=1, max_size=8),
                    min_size=1, max_size=6),
    bounds=st.lists(st.tuples(st.none() | st.integers(-1, 5),
                              st.none() | st.integers(-1, 5),
                              st.none() | st.integers(-1, 5)),
                    min_size=3, max_size=3),
)
def test_conjunctive_zone_verdicts_are_sound(blocks, bounds):
    """For any data and any conjunction over a tracked pair of columns
    and one the zone map never saw: EMPTY means no row of the block
    matches, FULL means every row does, and the executor's zone mask is
    bit-identical to the plain mask."""
    tables = [{"value": np.asarray(b, np.float64)[:, 0] + 50.0,
               "a": np.asarray(b, np.float64)[:, 0],
               "b": np.asarray(b, np.float64)[:, 1],
               "u": np.asarray(b, np.float64)[:, 2]} for b in blocks]
    where = Predicate(terms=[
        {"column": c, "lo": None if lo is None else float(lo),
         "hi": None if hi is None else float(hi),
         "eq": None if eq is None else float(eq)}
        for c, (lo, hi, eq) in zip(("a", "b", "u"), bounds)])
    zm = ZoneMap.from_tables([{k: v for k, v in t.items() if k != "u"}
                              for t in tables])
    status = zm.status(where)
    assert status.shape == (len(tables),)
    for b, t in enumerate(tables):
        m = where.mask(t)
        if status[b] == ZONE_EMPTY:
            assert not m.any()
        if status[b] == ZONE_FULL:
            assert m.all()
    ex = MultiQueryExecutor([table_sampler(t) for t in tables],
                            [len(t["a"]) for t in tables], zone_map=zm)
    cols = {k: np.concatenate([t[k] for t in tables]) for k in tables[0]}
    ids = np.concatenate([np.full(len(t["a"]), i)
                          for i, t in enumerate(tables)])
    assert np.array_equal(ex._zone_mask(where, cols, ids), where.mask(cols))


def test_conjunctive_interval_status_per_term():
    """Hand-checked: EMPTY if any term is, FULL only if every term is, a
    term on a column without bounds PARTIAL for itself only."""
    lo = {"a": [0.0, 2.0, 2.0, 0.0], "b": [0.0, 0.0, 5.0, 9.0]}
    hi = {"a": [1.0, 2.0, 3.0, 9.0], "b": [1.0, 1.0, 6.0, 9.0]}
    both = Predicate(terms=[{"column": "a", "eq": 2.0},
                            {"column": "b", "hi": 5.0}])
    assert both.interval_status(lo, hi).tolist() == [
        ZONE_EMPTY, ZONE_FULL, ZONE_EMPTY, ZONE_EMPTY]
    with_u = Predicate(terms=[{"column": "a", "eq": 2.0},
                              {"column": "u", "eq": 0.0}])
    assert with_u.interval_status(lo, hi).tolist() == [
        ZONE_EMPTY, ZONE_PARTIAL, ZONE_PARTIAL, ZONE_PARTIAL]
    # Blocks with no rows are EMPTY under every term that has bounds.
    assert with_u.interval_status(lo, hi, count=[5, 0, 5, 5]).tolist() == [
        ZONE_EMPTY, ZONE_EMPTY, ZONE_PARTIAL, ZONE_PARTIAL]
    only_u = Predicate(terms=[{"column": "u", "eq": 0.0},
                              {"column": "v", "lo": 1.0}])
    assert only_u.interval_status(lo, hi, count=[5, 0, 5, 5]).tolist() == [
        ZONE_PARTIAL] * 4


# ---------------------------------------------------------------------------
# The measure.
# ---------------------------------------------------------------------------


def test_measure_forms():
    rows = {PRICE: np.array([100.0, 250.5]), DISC: np.array([0.07, 0.1]),
            "n": np.array([3, 4], np.int32)}
    one = Measure.of(PRICE)
    assert not one.derived and one(rows) is rows[PRICE]
    prod = Measure.of(MEASURE)
    assert prod.derived and prod == Measure.of((PRICE, DISC))
    assert np.array_equal(prod(rows), rows[PRICE] * rows[DISC])
    assert Measure.of(["n", PRICE])(rows).dtype == np.float64
    assert Measure.of(prod) is prod
    for bad in ([], [PRICE, 3], None):
        with pytest.raises((ValueError, TypeError)):
            Measure.of(bad)


def test_missing_measure_column_raises():
    tables = _q6_tables(n_blocks=2, rows=200)
    with pytest.raises(KeyError, match="nope"):
        ZoneMap.from_tables(tables, measure=[PRICE, "nope"])
    ex = MultiQueryExecutor([table_sampler(t) for t in tables], [200, 200],
                            measure=[PRICE, "nope"])
    with pytest.raises(KeyError, match="nope"):
        ex.run([IslaQuery(e=5.0, agg="SUM", where=_q6_where())],
               np.random.default_rng(0))
    g = Anchor(make_boundaries(100.0, 10.0, IslaParams()), 100.0, 0.0, 10.0)
    with pytest.raises(KeyError, match="nope"):
        g.refine_for_predicate(tables[0], _q6_where(), IslaParams(),
                               measure=[PRICE, "nope"])
    # Bare-array samplers carry one column: no derived measure.
    bare = MultiQueryExecutor(
        [lambda n, r: r.normal(100.0, 10.0, n)] * 2, [200, 200],
        measure=MEASURE)
    with pytest.raises(ValueError, match="row dicts"):
        bare.run([IslaQuery(e=5.0, agg="AVG")], np.random.default_rng(0))


def test_zone_map_moments_hold_the_derived_measure():
    tables = _q6_tables(n_blocks=3, rows=500)
    zm = ZoneMap.from_tables(tables, measure=MEASURE)
    for b, t in enumerate(tables):
        v = t[PRICE] * t[DISC]
        assert zm.moments[b].tolist() == [v.size, float(v.sum()),
                                          float((v * v).sum())]


def test_refine_for_predicate_reads_the_derived_measure():
    """The refined anchor over the product equals the one over a column
    holding the product's values: refinement reads the measure, it does
    not fall back to the global anchor."""
    rows = {k: np.concatenate([t[k] for t in _q6_tables(rows=2000)])
            for k in (PRICE, DISC, QTY, SHIP)}
    rev = dict(rows, rev=rows[PRICE] * rows[DISC])
    where = Predicate(terms=[{"column": QTY, "hi": 24.0},
                             {"column": DISC, "lo": 0.035}])
    g = Anchor(make_boundaries(1000.0, 900.0, IslaParams()), 1000.0, 0.0,
               900.0, support=10_000)
    a = g.refine_for_predicate(rows, where, IslaParams(), measure=MEASURE)
    b = g.refine_for_predicate(rev, where, IslaParams(), measure="rev")
    assert a.source == "refined" and a == b
    vals = rev["rev"][where.mask(rows)]
    assert a.sigma == pytest.approx(float(np.std(vals, ddof=1)), rel=1e-12)


# ---------------------------------------------------------------------------
# The executor against a float64 plain reference.
# ---------------------------------------------------------------------------


def _logged(tables, log):
    def wrap(b, inner):
        def sample(n, rng):
            rows = inner(n, rng)
            log.append((b, rows))
            return rows
        return sample
    return [wrap(b, table_sampler(t)) for b, t in enumerate(tables)]


@pytest.mark.parametrize("route", ["host", "device"])
def test_conjunction_and_product_measure_match_a_plain_reference(route):
    """A three-column WHERE over the product measure, served warm
    (incremental) after a first tick fixed the pilot: the answers' matched
    counts equal the drawn rows' (exact), the population is the
    Horvitz-Thompson sum of block size x matched / drawn, and COUNT's mean
    and SUM's half-width are the plain float64 moments of the matching
    products.  Tolerance: 1e-9 on the host (float64, summation order
    only); 4e-5 on the device, the benchmark's limit for float32 moments
    (``moment_gap``)."""
    tables = _q6_tables()
    sizes = [10 ** 6] * len(tables)
    log = []
    ex = MultiQueryExecutor(_logged(tables, log), sizes,
                            params=IslaParams(e=2.0), measure=MEASURE,
                            zone_map=ZoneMap.from_tables(tables,
                                                         measure=MEASURE))
    rng = np.random.default_rng(7)
    ex.run([IslaQuery(e=2.0, agg="AVG")], rng, route=route,
           incremental=True)
    where = _q6_where()
    log.clear()
    count, total = ex.run([IslaQuery(e=2.0, agg="COUNT", where=where),
                           IslaQuery(e=2.0, agg="SUM", where=where)],
                          rng, route=route, incremental=True)
    assert log, "the warm tick drew no rows"
    drawn = np.zeros(len(tables))
    matched = np.zeros(len(tables))
    vals = []
    for b, rows in log:
        m = ((rows[SHIP] >= 20.0) & (rows[SHIP] < 60.0)
             & (rows[DISC] >= 0.035) & (rows[DISC] < 0.065)
             & (rows[QTY] < 30.0))
        drawn[b] += m.size
        matched[b] += m.sum()
        vals.append((rows[PRICE] * rows[DISC])[m])
    vals = np.concatenate(vals)
    w = float(np.sum(np.asarray(sizes) * matched / drawn))
    rel = 1e-9 if route == "host" else 4e-5
    for ans in (count, total):
        assert ans.n_matched == vals.size > 100
        assert ans.sample_size == int(drawn.sum())
        assert ans.est_population == pytest.approx(w, rel=rel)
    assert count.value == pytest.approx(w, rel=rel)
    assert count.mean == pytest.approx(float(vals.mean()), rel=rel)
    hw = w * z_score(0.95) * float(np.std(vals, ddof=1)) / math.sqrt(
        vals.size)
    assert total.half_width == pytest.approx(hw, rel=rel)
    assert total.value == pytest.approx(total.est_population * total.mean,
                                        rel=1e-12)


def test_new_spans_open_only_for_conjunctions_and_derived_measures(
        monkeypatch):
    """``plan.zone`` opens inside ``plan`` once for a conjunction the zone
    map has not judged, ``draw.measure`` inside ``draw`` for each chunk of
    a derived measure; both are booked on the loop's stage clock."""
    opened = []

    class Span:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            pass

    tables = _q6_tables()
    ex = MultiQueryExecutor([table_sampler(t) for t in tables],
                            [10 ** 6] * len(tables),
                            params=IslaParams(e=2.0), measure=MEASURE,
                            zone_map=ZoneMap.from_tables(tables,
                                                         measure=MEASURE))
    loop = IslaAdmissionLoop(ex, np.random.default_rng(3), route="device",
                             incremental=True)
    loop.submit(IslaQuery(e=2.0, agg="SUM", where=_q6_where()))
    loop.tick()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Span)
    before = dict(loop.stats["stage_seconds"])
    loop.submit(IslaQuery(e=2.0, agg="COUNT", where=_q6_where(start=5.0)))
    loop.submit(IslaQuery(e=2.0, agg="SUM", where=_q6_where(start=5.0)))
    assert len(loop.tick()) == 2
    after = loop.stats["stage_seconds"]
    assert opened.count("isla:plan.zone") == 1
    assert opened.index("isla:plan") < opened.index("isla:plan.zone") \
        < opened.index("isla:draw")
    assert opened.count("isla:draw.measure") == opened.count(
        "isla:draw.sample") >= 1
    for part in ("plan.zone", "draw.measure"):
        assert after[part] > before.get(part, 0.0)
    assert ex.zone_map.judged(_q6_where(start=5.0))
