"""What decides ``correct`` has to fail when it should: the bfloat16
control, put in the program's place, comes out not correct, and a run whose
timed path is broken underneath comes out not correct, once for each fault
this cell can have.  (The exchange between chips does not exist on one
chip.)"""
import numpy as np
import pytest

import bench_tiny

LIMITS = bench_tiny.tiny_config()["checks"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def repeat_root(tmp_path_factory):
    """A mix of few keys, so answers repeat and are served from the
    answer cache."""
    mix = bench_tiny.tiny_mix()
    mix["queries"].update(start=[1, 2], lengths=[365], aggs=["COUNT", "AVG"])
    return bench_tiny.make_root(tmp_path_factory.mktemp("bench"),
                                mixes={"adhoc": mix})


def test_control_fails_the_moment_limit(root):
    out = bench_tiny.run(root, seed=31, seconds=2.0, control=True)
    checks = out["result"]["checks"]
    assert out["result"]["correct"] is False
    assert (checks["moment_gap"]["value"] > 3 * LIMITS["moment_gap"]
            or checks["estimate_gap"]["value"] > 3 * LIMITS["estimate_gap"])
    program = out["notes"]["program_checks"]
    assert program["moment_gap"] < LIMITS["moment_gap"]
    assert program["estimate_gap"] < LIMITS["estimate_gap"]


def _broken_tick(monkeypatch, keep):
    """Patch the fused dense tick so only the pane rows ``keep(pad)``
    leaves valid reach the moments."""
    from repro.core import distributed

    orig = distributed.fused_tick_dense

    def tick(mom_s, mom_l, totals, n_sampled, values2d, pad_valid, *a, **kw):
        return orig(mom_s, mom_l, totals, n_sampled, values2d,
                    keep(pad_valid), *a, **kw)

    monkeypatch.setattr(distributed, "fused_tick_dense", tick)


def test_state_returned_unchanged_is_caught(root, monkeypatch):
    _broken_tick(monkeypatch, lambda pad: pad * 0.0)
    out = bench_tiny.run(root, seed=32, seconds=1.5)
    assert out["result"]["correct"] is False
    assert out["result"]["checks"]["count_gap"]["value"] > 0


def test_half_the_batch_left_out_is_caught(root, monkeypatch):
    def half(pad):
        keep = np.ones(pad.shape[0], np.float32)
        keep[pad.shape[0] // 2:] = 0.0
        return pad * keep[:, None]
    _broken_tick(monkeypatch, half)
    out = bench_tiny.run(root, seed=33, seconds=1.5)
    assert out["result"]["correct"] is False
    assert out["result"]["checks"]["count_gap"]["value"] > 0


def _altered_compose(monkeypatch, alter):
    from repro.core.multiquery import MultiQueryExecutor

    orig = MultiQueryExecutor._compose_keyed

    def compose(self, q, *a, **kw):
        ans = orig(self, q, *a, **kw)
        alter(q, ans)
        return ans

    monkeypatch.setattr(MultiQueryExecutor, "_compose_keyed", compose)


def test_answer_altered_where_produced_is_caught(root, monkeypatch):
    def alter(q, ans):
        ans.value *= 1.001
    _altered_compose(monkeypatch, alter)
    out = bench_tiny.run(root, seed=34, seconds=1.5)
    assert out["result"]["correct"] is False
    checks = out["result"]["checks"]
    assert max(checks["moment_gap"]["value"],
               checks["estimate_gap"]["value"]) > LIMITS["moment_gap"]


def _avg_total(q, ans):
    if q.agg == "AVG":
        ans.value *= 1.001


def _sum_groups(q, ans):
    if q.agg == "SUM":
        for g in ans.groups or []:
            g.value *= 1.001


@pytest.mark.parametrize("alter", [_avg_total, _sum_groups],
                         ids=["avg_total", "sum_groups"])
def test_estimate_altered_is_caught(root, monkeypatch, alter):
    """An ISLA estimate (AVG or SUM) altered by 1e-3 where it is composed,
    every other field left as it was: only the estimate check sees it."""
    _altered_compose(monkeypatch, alter)
    out = bench_tiny.run(root, seed=35, seconds=2.0)
    checks = out["result"]["checks"]
    assert out["result"]["correct"] is False
    assert checks["estimate_gap"]["value"] > 3 * LIMITS["estimate_gap"]
    assert checks["moment_gap"]["value"] <= LIMITS["moment_gap"]


def test_missed_guarantee_is_caught(root, monkeypatch):
    """Earned bounds that do not hold: the (e, beta) check fails."""
    def alter(q, ans):
        for row in [ans] + list(ans.groups or []):
            if row.error_bound:
                row.value += 2.0 * row.error_bound
    _altered_compose(monkeypatch, alter)
    out = bench_tiny.run(root, seed=36, seconds=2.0)
    miss = out["result"]["checks"]["bound_miss_share"]
    assert out["result"]["correct"] is False
    assert miss["value"] > miss["limit"]


def test_served_answers_are_checked(repeat_root):
    out = bench_tiny.run(repeat_root, seed=37, seconds=1.5)
    assert out["notes"]["served_answers"] > 0
    assert out["result"]["checks"]["served_gap"]["value"] == 0.0


def test_served_answer_altered_is_caught(repeat_root, monkeypatch):
    from repro.core.multiquery import MultiQueryExecutor

    orig = MultiQueryExecutor.lookup_answer

    def lookup(self, *a, **kw):
        ans = orig(self, *a, **kw)
        if ans is not None:
            ans.value *= 1.001
        return ans

    monkeypatch.setattr(MultiQueryExecutor, "lookup_answer", lookup)
    out = bench_tiny.run(repeat_root, seed=38, seconds=1.5)
    assert out["notes"]["served_answers"] > 0
    assert out["result"]["checks"]["served_gap"]["value"] > 0
    assert out["result"]["correct"] is False
