"""The admission tier: PlanCache, answer subsumption, dedupe fan-out,
priority-weighted budget scheduling, and progressive (OLA) streaming."""
import dataclasses

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import IslaParams, IslaQuery, Predicate
from repro.core.moment_store import split_budget
from repro.core.multiquery import MultiQueryExecutor, table_sampler
from repro.core.types import AnswerKey, StoreKey, ZoneMap, demand_dominates
from repro.launch.serve import IslaAdmissionLoop, _synthetic_grouped_blocks

N_BLOCKS = 6


def _tables(n_blocks=N_BLOCKS, rows=4000, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for b in range(n_blocks):
        g = rng.integers(0, 3, size=rows)
        out.append({
            "value": rng.normal(100.0 + 4.0 * g, 10.0, rows),
            "region": g.astype(np.float64),
            "flag": rng.integers(0, 2, size=rows).astype(np.float64),
            "day": np.full(rows, float(b % 3)),
        })
    return out


def _executor(tables=None, **kw):
    tables = _tables() if tables is None else tables
    return MultiQueryExecutor(
        [table_sampler(t) for t in tables], [10 ** 6] * len(tables),
        params=IslaParams(e=0.5), group_domains={"region": 3}, **kw)


def _loop(**kw):
    samplers = _synthetic_grouped_blocks(n_blocks=N_BLOCKS, n_groups=3,
                                         rows=4000, seed=0)
    ex = MultiQueryExecutor(samplers, [10 ** 6] * N_BLOCKS,
                            params=IslaParams(e=0.5),
                            group_domains={"region": 3})
    return IslaAdmissionLoop(ex, np.random.default_rng(1), **kw)


FLAG1 = Predicate(column="flag", eq=1.0)
DAY0 = Predicate(column="day", eq=0.0)


# ---------------------------------------------------------------- PlanCache

def test_plan_cache_hits_on_repeated_warm_batches():
    """Tick 1 is the cold pilot path; tick 2 caches the warm plan; from
    tick 3 on a steady batch re-plans zero times in Python."""
    ex = _executor()
    rng = np.random.default_rng(3)
    batch = [IslaQuery(e=0.5, agg="AVG"),
             IslaQuery(e=0.5, agg="AVG", where=FLAG1)]
    for _ in range(4):
        ex.run(batch, rng, incremental=True)
    assert ex.plan_cache_misses == 1
    assert ex.plan_cache_hits == 2
    assert ex.plan_cache_evictions == 0


def test_plan_cache_key_strips_priorities():
    """Re-weighting a steady workload must not fault the PlanCache: the
    key is the priority-stripped batch."""
    ex = _executor()
    rng = np.random.default_rng(3)
    q = IslaQuery(e=0.5, agg="AVG", where=FLAG1)
    ex.run([q], rng, incremental=True)
    ex.run([q], rng, incremental=True)
    ex.run([dataclasses.replace(q, priority=7.0)], rng, incremental=True)
    assert ex.plan_cache_misses == 1
    assert ex.plan_cache_hits == 1


def test_cached_plan_answers_match_fresh_plans_bitwise():
    """A plan-cache hit and a fresh re-plan are stream-identical: warm
    planning consumes no RNG, so two executors with identical draws —
    one caching, one with the cache disabled — answer bit-identically."""
    answers = {}
    for size in (256, 0):  # plan_cache_size=0 disables caching
        ex = _executor(plan_cache_size=size)
        rng = np.random.default_rng(3)
        batch = [IslaQuery(e=0.5, agg="AVG", where=FLAG1),
                 IslaQuery(e=0.5, agg="VAR")]
        for _ in range(3):
            out = ex.run(batch, rng, incremental=True)
        answers[size] = out
    assert answers[0][0].value == answers[256][0].value
    assert answers[0][1].value == answers[256][1].value


def test_drift_reset_evicts_only_the_affected_keys_plans():
    """Satellite 1: a neighbor's per-key drift reset must evict exactly
    the cached plans touching that key's predicate — an unrelated key's
    cached plan (and cached answer) survives."""
    ex = _executor()
    rng = np.random.default_rng(3)
    qa = IslaQuery(e=0.5, agg="AVG", where=FLAG1)
    qb = IslaQuery(e=0.5, agg="AVG", where=DAY0)
    for _ in range(2):  # separate batches -> separate cache entries
        ex.run([qa], rng, incremental=True)
        ex.run([qb], rng, incremental=True)
    assert len(ex._plan_cache) == 2
    ex._reset_key(StoreKey(where=DAY0, mode="calibrated"))
    # DAY0's plan and answer are gone; FLAG1's both survive.
    assert len(ex._plan_cache) == 1
    (entry,) = ex._plan_cache.values()
    assert FLAG1 in entry.wheres and DAY0 not in entry.wheres
    assert ex.lookup_answer(qa) is not None
    assert ex.lookup_answer(qb) is None
    # The survivor still serves as a hit.
    hits = ex.plan_cache_hits
    ex.run([qa], rng, incremental=True)
    assert ex.plan_cache_hits == hits + 1


def test_zone_refresh_keeps_plans_whose_verdicts_held():
    """A zone-map refresh bumps the version; cached plans re-validate
    against the fresh verdicts and survive when no verdict they pruned
    under changed.  A refresh that flips a verdict evicts."""
    tables = _tables()
    zm = ZoneMap.from_tables(tables)
    ex = _executor(tables, zone_map=zm)
    rng = np.random.default_rng(3)
    q = IslaQuery(e=0.5, agg="AVG", where=DAY0)
    for _ in range(2):
        ex.run([q], rng, incremental=True)
    assert len(ex._plan_cache) == 1
    # Refresh that changes nothing day-wise: verdicts hold, plan stays.
    zm.refresh(1, {"value": np.array([100.0]), "day": np.array([1.0])})
    hits = ex.plan_cache_hits
    ex.run([q], rng, incremental=True)
    assert ex.plan_cache_hits == hits + 1
    # Refresh that turns a day!=0 block into a day-0 overlap: the EMPTY
    # verdict this plan pruned under flips, so the entry must go.
    zm.refresh(1, {"value": np.array([100.0]), "day": np.array([0.0])})
    misses = ex.plan_cache_misses
    ex.run([q], rng, incremental=True)
    assert ex.plan_cache_misses == misses + 1


# ------------------------------------------------- subsumption + dedupe

def test_subsumption_serves_weaker_demand_with_zero_samples():
    ex = _executor()
    rng = np.random.default_rng(3)
    strong = IslaQuery(e=0.5, beta=0.95, agg="AVG", where=FLAG1)
    (full,) = ex.run([strong], rng, incremental=True)
    assert full.error_bound is not None
    weak = IslaQuery(e=1.0, beta=0.90, agg="AVG", where=FLAG1)
    served = ex.lookup_answer(weak)
    assert served is not None
    assert served.new_samples == 0
    assert served.served == "subsumed"
    assert served.value == full.value
    # Bound no looser than asked: the dominator's bound satisfies the
    # weaker (e, beta) with room to spare.
    assert served.error_bound <= weak.e + 1e-12
    assert served.query is weak  # metadata re-targeted to the ask


def test_incomparable_demands_are_not_served():
    """Tighter e at LOWER beta is incomparable in the dominance lattice
    — serving it would overclaim confidence."""
    ex = _executor()
    rng = np.random.default_rng(3)
    ex.run([IslaQuery(e=0.5, beta=0.95, agg="AVG", where=FLAG1)], rng,
           incremental=True)
    assert ex.lookup_answer(
        IslaQuery(e=0.4, beta=0.90, agg="AVG", where=FLAG1)) is None
    assert ex.lookup_answer(
        IslaQuery(e=1.0, beta=0.99, agg="AVG", where=FLAG1)) is None
    assert not demand_dominates(0.5, 0.95, 0.4, 0.90)
    assert not demand_dominates(0.5, 0.95, 1.0, 0.99)


def test_answer_cache_invalidates_on_new_samples():
    """A top-up on the answer's store moves the ledger stamp: the stale
    cached answer must NOT be served afterwards."""
    ex = _executor()
    rng = np.random.default_rng(3)
    q = IslaQuery(e=0.5, agg="AVG", where=FLAG1)
    ex.run([q], rng, incremental=True)
    assert ex.lookup_answer(q) is not None
    # A strictly tighter ask forces a real top-up on the same store.
    ex.run([dataclasses.replace(q, e=0.3)], rng, incremental=True)
    tighter = ex.lookup_answer(q)
    # Either served from the FRESH (e=0.3) answer or not at all — never
    # from the stale pre-top-up one.
    if tighter is not None:
        assert tighter.error_bound <= 0.3 + 1e-12


def test_loop_dedupes_identical_same_tick_queries():
    """Satellite 2: N identical same-tick queries execute once and fan
    out N answers, counted in metadata."""
    loop = _loop(incremental=True)
    q = IslaQuery(e=0.5, agg="VAR")  # VAR: never answer-cacheable
    tids = [loop.submit(dataclasses.replace(q)) for _ in range(4)]
    done = loop.tick()
    assert [t.tid for t in done] == tids
    assert loop.deduped == 3
    byserved = sorted(t.answer.served or "computed" for t in done)
    assert byserved == ["computed", "dedupe", "dedupe", "dedupe"]
    assert all(t.answer.dedupe_fanout == 4 for t in done)
    values = {t.answer.value for t in done}
    assert len(values) == 1
    # One shared pass total: the dedupe mates drew nothing new.
    assert all(t.answer.new_samples == 0 or t.answer.served is None
               for t in done)


def test_loop_serves_same_tick_weaker_demand_from_dominator():
    """A weaker ask admitted in the SAME tick as its dominator holds one
    tick and is served from the dominator's freshly-cached answer —
    zero extra executions."""
    loop = _loop(incremental=True)
    strong = IslaQuery(e=0.5, beta=0.95, agg="AVG", where=FLAG1)
    weak = IslaQuery(e=1.0, beta=0.90, agg="AVG", where=FLAG1)
    t0 = loop.submit(strong)
    t1 = loop.submit(weak)
    done = loop.run_until_drained()
    assert {t.tid for t in done} == {t0, t1}
    assert loop.subsumed == 1
    by_tid = {t.tid: t for t in done}
    assert by_tid[t1].answer.served == "subsumed"
    assert by_tid[t1].answer.new_samples == 0
    assert by_tid[t1].answer.value == by_tid[t0].answer.value


def test_loop_stats_expose_admission_counters():
    loop = _loop(incremental=True)
    q = IslaQuery(e=0.5, agg="AVG", where=FLAG1)
    loop.submit(q)
    loop.tick()
    loop.submit(dataclasses.replace(q, e=1.0, beta=0.90))
    loop.tick()
    s = loop.stats
    assert s["subsumed"] == 1
    assert s["answered"] == 2
    for key in ("plan_cache_hits", "plan_cache_misses", "deduped",
                "samples_drawn", "in_flight", "answers_cached"):
        assert key in s


@pytest.mark.parametrize("route", ["device", "mesh"])
def test_store_builds_count_cold_keys_only(route):
    """One device store is built per cold key served; none for a warm
    repeat on a known key, nor for an answer served from the cache."""
    loop = _loop(incremental=True, route=route)
    q = IslaQuery(e=0.5, agg="AVG", where=FLAG1)

    def tick(query):
        before = loop.stats["store_builds"]
        loop.submit(query)
        (done,) = loop.tick()
        return done.answer, loop.stats["store_builds"] - before

    answer, built = tick(q)
    assert built == 1 and answer.served is None
    answer, built = tick(dataclasses.replace(q, e=1.0, beta=0.90))
    assert built == 0 and answer.served == "subsumed"
    answer, built = tick(dataclasses.replace(q, e=0.3))
    assert built == 0 and answer.served is None and answer.new_samples > 0
    answer, built = tick(IslaQuery(e=0.5, agg="AVG", where=DAY0))
    assert built == 1 and answer.served is None


def test_admission_off_is_fifo():
    """``admission=False`` (and any non-incremental loop) is the plain
    FIFO route: no dedupe, no subsumption, every query executes."""
    loop = _loop(incremental=True, admission=False)
    q = IslaQuery(e=0.5, agg="AVG", where=FLAG1)
    loop.submit(q)
    loop.submit(dataclasses.replace(q))
    loop.submit(dataclasses.replace(q, e=1.0, beta=0.90))
    done = loop.run_until_drained()
    assert len(done) == 3
    assert loop.deduped == 0 and loop.subsumed == 0
    assert all(t.answer.served is None for t in done)


# -------------------------------------------- priority-weighted budgeting

def test_split_budget_weights_shift_samples_to_priority():
    """At equal deficit and sigma, a higher weight receives weakly more
    of a scarce budget; unit weights reproduce the unweighted split."""
    n_now = [1000.0, 1000.0]
    sig = [10.0, 10.0]
    deficits = [800, 800]
    base = split_budget(n_now, sig, deficits, 600)
    assert base[0] == base[1]
    tilted = split_budget(n_now, sig, deficits, 600, weights=[4.0, 1.0])
    assert tilted[0] > tilted[1]
    assert int(tilted.sum()) == 600
    unit = split_budget(n_now, sig, deficits, 600, weights=[1.0, 1.0])
    assert np.array_equal(unit, base)


def test_split_budget_weights_validate():
    with pytest.raises(ValueError):
        split_budget([10.0], [1.0], [5], 5, weights=[0.0])
    with pytest.raises(ValueError):
        split_budget([10.0], [1.0], [5], 5, weights=[np.nan])
    with pytest.raises(ValueError):
        split_budget([10.0, 10.0], [1.0, 1.0], [5, 5], 5, weights=[1.0])


def test_split_budget_floors_are_weight_independent():
    """QoS floors outrank priority: even a 100x weight cannot starve a
    low-priority store below its floor."""
    out = split_budget([1000.0, 1000.0], [10.0, 10.0], [500, 500], 220,
                       min_per_store=100, weights=[100.0, 1.0])
    assert out[1] >= 100
    assert int(out.sum()) == 220


@settings(max_examples=60, deadline=None)
@given(w_hi=st.floats(1.0, 50.0), w_lo=st.floats(0.02, 1.0),
       sigma=st.floats(0.5, 50.0), deficit=st.integers(1, 2000),
       budget=st.integers(1, 3000))
def test_split_budget_priority_monotone_property(w_hi, w_lo, sigma,
                                                 deficit, budget):
    """Hypothesis property (satellite 3): at equal deficit and sigma the
    higher-priority store gets weakly more samples, totals never exceed
    min(budget, total deficit), and quotas never exceed the deficit."""
    out = split_budget([500.0, 500.0], [sigma, sigma],
                       [deficit, deficit], budget, weights=[w_hi, w_lo])
    assert out[0] >= out[1]
    assert out.min() >= 0
    assert out.max() <= deficit
    assert int(out.sum()) <= min(budget, 2 * deficit)


def test_loop_priority_orders_admission():
    """Priorities reorder a tick's admitted batch (high first) without
    changing any answer's value."""
    loop = _loop(incremental=True, max_batch=2)
    lo = loop.submit(IslaQuery(e=0.5, agg="AVG", priority=1.0))
    hi = loop.submit(IslaQuery(e=0.5, agg="AVG", where=FLAG1,
                               priority=8.0))
    done = loop.tick()
    assert [t.tid for t in done] == [lo, hi]
    assert loop.answered[0].query.priority == 8.0  # hi ran first
    assert loop.answered[0].tid == hi


def test_validate_rejects_bad_priority():
    ex = _executor()
    with pytest.raises(ValueError):
        ex.run([IslaQuery(e=0.5, priority=0.0)], np.random.default_rng(0))
    with pytest.raises(ValueError):
        ex.run([IslaQuery(e=0.5, priority=float("nan"))],
               np.random.default_rng(0))


# ------------------------------------------------------ progressive (OLA)

def test_progressive_streams_shrinking_half_width():
    """Under a tight per-tick budget a progressive ticket stays in
    flight, streaming (value, half_width) snapshots that shrink, and
    completes once the bound is earned."""
    loop = _loop(incremental=True, deadline_samples=300, progressive=True)
    loop.submit(IslaQuery(e=0.4, beta=0.95, agg="AVG", where=FLAG1))
    assert loop.tick() == []  # not earned yet: in flight, not answered
    assert loop.in_flight == 1
    done = loop.run_until_drained(max_ticks=300)
    assert len(done) == 1
    t = done[0]
    widths = [hw for (_, _, hw, _) in t.progress if hw is not None]
    assert len(widths) >= 2
    assert widths[-1] < widths[0]
    assert t.answer.error_bound is not None
    assert t.answer.error_bound <= 0.4 + 1e-9


def test_progressive_requires_incremental():
    with pytest.raises(ValueError):
        _loop(progressive=True)


# ------------------------------------------------------------- AnswerKey

def test_answer_key_identity_and_dominance():
    q = IslaQuery(e=0.5, beta=0.95, agg="AVG", where=FLAG1,
                  group_by="region")
    k = AnswerKey.from_query(q, default_mode="calibrated")
    assert k.agg == "AVG"
    assert k.store == StoreKey(where=FLAG1, group_by="region",
                               mode="calibrated")
    # Same demand dominates itself; dominance is a partial order.
    assert demand_dominates(0.5, 0.95, 0.5, 0.95)
    assert demand_dominates(0.5, 0.95, 0.6, 0.90)
    assert not demand_dominates(0.6, 0.90, 0.5, 0.95)
