"""Multi-query executor: shared-pass planning, SUM/COUNT/VAR estimators
against exact answers within the (e, beta) guarantee, device-route parity."""
import numpy as np
import pytest

from conftest import normal_samplers
from repro.core.engine import IslaQuery
from repro.core.multiquery import (AGGREGATES, MultiQueryExecutor,
                                   multi_aggregate)
from repro.core.preestimation import sampling_rate, z_score
from repro.core.types import IslaParams

B = 10
M = 10 ** 10
SIZES = [M // B] * B
MU, SIGMA = 100.0, 20.0


def _executor():
    return MultiQueryExecutor(normal_samplers(b=B), SIZES,
                              params=IslaParams())


def test_avg_within_guarantee():
    errs = []
    for seed in range(6):
        (a,) = _executor().run([IslaQuery(e=0.1, agg="AVG")],
                               np.random.default_rng(seed))
        errs.append(abs(a.value - MU))
    assert np.mean(errs) <= 0.1


def test_sum_scales_mean_and_bound():
    errs = []
    for seed in range(6):
        (a,) = _executor().run([IslaQuery(e=0.2, agg="SUM")],
                               np.random.default_rng(seed))
        assert a.value == pytest.approx(M * a.mean)
        assert a.error_bound == pytest.approx(M * 0.2)
        errs.append(abs(a.value - MU * M))
    # beta=0.95 bound: a single seed may exceed it; the average must not.
    assert np.mean(errs) <= a.error_bound


def test_count_exact():
    (a,) = _executor().run([IslaQuery(e=0.5, agg="COUNT")],
                           np.random.default_rng(0))
    assert a.value == float(M)
    assert a.error_bound == 0.0


@pytest.mark.parametrize("n_drawn", [2_000, 12_000])
def test_estimated_count_bound_holds_its_confidence(n_drawn):
    """An estimated COUNT's bound, held to the exact binomial law of the
    matched rows: averaged over match fractions that leave 20-400 of the
    drawn rows matching (a TPC-H Q6 WHERE matches ~200 of ~12,000), the
    chance that the estimate misses the true count by more than its bound
    is at most ``1 - beta``.  The normal (Wald) half-width misses ~5.2-5.3%
    there; no tolerance, the law is computed exactly.  At p = 0 the bound
    is not 0 (the draw cannot rule out a few matching rows)."""
    from scipy import stats

    ex = _executor()
    z = z_score(0.95)
    k = np.arange(n_drawn + 1)
    bound = np.array([ex._count_bound(float(M) * j / n_drawn, n_drawn, z)
                      for j in k])
    misses = []
    for p in np.linspace(20.0, 400.0, 96) / n_drawn:
        far = np.abs(M * k / n_drawn - M * p) > bound
        misses.append(float(stats.binom.pmf(k[far], n_drawn, p).sum()))
    assert np.mean(misses) <= 0.05
    assert bound[0] == pytest.approx(M * z * z / (n_drawn + z * z))


def test_var_close_to_truth():
    vals = []
    for seed in range(6):
        (a,) = _executor().run([IslaQuery(e=0.1, agg="VAR")],
                               np.random.default_rng(seed))
        vals.append(a.value)
    # E[X^2] - mu^2 with both terms from the shared pass: a few percent.
    assert np.mean(vals) == pytest.approx(SIGMA ** 2, rel=0.1)


def test_var_shift_invariance():
    """VAR composes on the shifted stream; the shift must cancel."""
    samplers = [(lambda n, rng: rng.normal(0.0, 5.0, size=n))
                for _ in range(4)]
    ex = MultiQueryExecutor(samplers, [10 ** 8] * 4, params=IslaParams())
    (a,) = ex.run([IslaQuery(e=0.1, agg="VAR")], np.random.default_rng(1))
    assert a.value == pytest.approx(25.0, rel=0.15)


def test_shared_pass_uses_strictest_rate():
    queries = [IslaQuery(e=0.5, beta=0.9, agg="AVG"),
               IslaQuery(e=0.1, beta=0.99, agg="SUM"),
               IslaQuery(e=1.0, beta=0.95, agg="VAR")]
    ans = _executor().run(queries, np.random.default_rng(0))
    rates = {a.sampling_rate for a in ans}
    assert len(rates) == 1  # one shared sample
    # the shared rate satisfies the strictest query's Eq. 1 rate
    shared = rates.pop()
    ex = _executor()
    for q in queries:
        assert shared >= sampling_rate(
            q.e, 19.0, q.beta, ex.data_size) * 0.5  # sigma-hat wiggle room


def test_answers_share_one_rng_pass():
    """All aggregates in one batch derive from the same mean estimate."""
    queries = [IslaQuery(e=0.1, agg="AVG"), IslaQuery(e=0.1, agg="SUM"),
               IslaQuery(e=0.1, agg="VAR"), IslaQuery(e=0.1, agg="COUNT")]
    ans = _executor().run(queries, np.random.default_rng(5))
    means = {a.mean for a in ans}
    assert len(means) == 1
    assert ans[1].value == pytest.approx(M * ans[0].value)


def test_sample_size_reports_actual_draw():
    """Under a deadline cap, sample_size is what was drawn, not the plan."""
    ans = _executor().run([IslaQuery(e=0.1)], np.random.default_rng(0),
                          deadline_samples=7)
    assert ans[0].sample_size == 7 * B


def test_truncated_draw_degrades_bound_to_best_effort():
    """deadline/rate_override below Eq. 1's sample size: the (e, beta)
    guarantee is not earned, so error_bound must not claim it."""
    full = _executor().run([IslaQuery(e=0.1, agg="AVG")],
                           np.random.default_rng(0))
    assert full[0].error_bound == 0.1
    capped = _executor().run([IslaQuery(e=0.1, agg="AVG"),
                              IslaQuery(e=0.1, agg="SUM")],
                             np.random.default_rng(0), deadline_samples=5)
    assert capped[0].error_bound is None
    assert capped[1].error_bound is None


def test_multi_aggregate_convenience():
    ans = multi_aggregate(normal_samplers(b=4), [10 ** 8] * 4,
                          [IslaQuery(e=0.2, agg="AVG")],
                          np.random.default_rng(0))
    assert abs(ans[0].value - MU) < 1.0


def test_count_does_not_inflate_shared_rate():
    """COUNT is exact — a strict-e COUNT must not drive the sampling rate."""
    loose = _executor().run([IslaQuery(e=0.5, agg="AVG")],
                            np.random.default_rng(0))
    with_count = _executor().run(
        [IslaQuery(e=0.5, agg="AVG"), IslaQuery(e=0.0001, agg="COUNT")],
        np.random.default_rng(0))
    assert with_count[0].sampling_rate == pytest.approx(
        loose[0].sampling_rate, rel=0.2)
    # all-exact batch still answers, at a minimal probe rate
    only_count = _executor().run([IslaQuery(e=0.0001, agg="COUNT")],
                                 np.random.default_rng(0))
    assert only_count[0].value == float(M)
    assert only_count[0].sampling_rate < 1e-3


def test_device_route_close_to_host():
    queries = [IslaQuery(e=0.1, agg="AVG"), IslaQuery(e=0.1, agg="VAR")]
    host = _executor().run(queries, np.random.default_rng(3), route="host")
    dev = _executor().run(queries, np.random.default_rng(3), route="device")
    # identical samples (same RNG stream); fp32 phase 2 vs float64 host
    assert dev[0].value == pytest.approx(host[0].value, rel=1e-4)
    assert dev[1].value == pytest.approx(host[1].value, rel=1e-2)


def test_device_route_provenance_consistent():
    """blocks.avg on the device route holds the device partials the answer
    was summarized from."""
    from repro.core.summarize import summarize
    ex = _executor()
    sp = ex._shared_pass([IslaQuery(e=0.1)], np.random.default_rng(4),
                         "calibrated", "device", None, None, None)
    assert summarize(np.asarray(sp.result.blocks.avg), SIZES) == \
        pytest.approx(sp.mean_shifted)


def test_validation_errors():
    ex = _executor()
    with pytest.raises(ValueError, match="at least one"):
        ex.run([], np.random.default_rng(0))
    with pytest.raises(ValueError, match="unknown aggregate"):
        ex.run([IslaQuery(agg="MEDIAN")], np.random.default_rng(0))
    with pytest.raises(ValueError, match="precision"):
        ex.run([IslaQuery(e=-1.0)], np.random.default_rng(0))
    with pytest.raises(ValueError, match="unknown route"):
        ex.run([IslaQuery()], np.random.default_rng(0), route="moon")
    with pytest.raises(ValueError, match="unknown mode"):
        ex.run([IslaQuery()], np.random.default_rng(0), mode="calibratd")
    with pytest.raises(ValueError, match="one sampler per block"):
        MultiQueryExecutor(normal_samplers(b=3), [1, 2])
    assert set(AGGREGATES) == {"AVG", "SUM", "COUNT", "VAR",
                               "count_distinct"}
