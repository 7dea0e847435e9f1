"""Zone-map block pruning: interval verdicts, planner block rates, the
pruned/unpruned agreement properties, and the compacted device launch.

The soundness contract under test is three-way: ``ZONE_EMPTY`` and
``ZONE_FULL`` are *proofs* over exact per-block bounds (never
estimates), so

 * a provably-empty block contributes a deterministic zero — rated 0 by
   the planner, never drawn, no RNG consumed;
 * the residual blocks' per-cell moments are BIT-IDENTICAL between the
   compacted device launch and the full-axis launch (x64);
 * pruned and unpruned executions of the same WHERE answer within the
   shared (e, beta) contract, on the host AND device routes.
"""
import warnings

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core.boundaries import make_boundaries
from repro.core.engine import IslaQuery
from repro.core.moment_store import DeviceMomentStore, DeviceStack
from repro.core.multiquery import (MIN_PLANNED_SELECTIVITY,
                                   MultiQueryExecutor,
                                   PlannedSelectivityFloorWarning,
                                   table_sampler)
from repro.core.types import (ZONE_EMPTY, ZONE_FULL, ZONE_PARTIAL,
                              IslaParams, Predicate, ZoneMap)

MU, SIGMA = 100.0, 12.0


def _clustered_tables(n_blocks, rows, seed=0, n_days=None):
    """Block-clustered predicate column: block b holds day == b % n_days
    only, so ``day == d`` provably matches 1/n_days of the blocks."""
    rng = np.random.default_rng(seed)
    n_days = n_days or n_blocks
    return [{"value": rng.normal(MU, SIGMA, rows),
             "day": np.full(rows, float(b % n_days))}
            for b in range(n_blocks)]


def _executor(tables, zone=True, **kw):
    rows = len(tables[0]["value"])
    zm = ZoneMap.from_tables(tables) if zone else None
    return MultiQueryExecutor([table_sampler(t) for t in tables],
                              [rows] * len(tables), zone_map=zm, **kw)


# ---------------------------------------------------------------------------
# Interval verdicts: Predicate.interval_status / ZoneMap.status.
# ---------------------------------------------------------------------------


def test_interval_status_three_way_verdicts():
    """Hand-checked verdicts for eq / range / half-open-hi clauses."""
    lo, hi = [0.0, 2.0, 1.0, 5.0], [1.0, 2.0, 3.0, 9.0]
    assert (Predicate("c", eq=2.0).interval_status(lo, hi).tolist()
            == [ZONE_EMPTY, ZONE_FULL, ZONE_PARTIAL, ZONE_EMPTY])
    assert (Predicate("c", lo=2.0).interval_status(lo, hi).tolist()
            == [ZONE_EMPTY, ZONE_FULL, ZONE_PARTIAL, ZONE_FULL])
    # hi is exclusive but block bounds are inclusive: a block whose max
    # EQUALS the cut is only PARTIAL-provable from bounds when its min
    # is below, EMPTY when its min reaches the cut.
    assert (Predicate("c", hi=2.0).interval_status(lo, hi).tolist()
            == [ZONE_FULL, ZONE_EMPTY, ZONE_PARTIAL, ZONE_EMPTY])
    assert (Predicate("c", lo=1.0, hi=3.0).interval_status(lo, hi).tolist()
            == [ZONE_PARTIAL, ZONE_FULL, ZONE_PARTIAL, ZONE_EMPTY])


def test_interval_status_zero_count_is_empty():
    """count == 0 proves EMPTY regardless of (stale infinite) bounds."""
    out = Predicate("c", eq=1.0).interval_status(
        [np.inf, 1.0], [-np.inf, 1.0], count=[0, 5])
    assert out.tolist() == [ZONE_EMPTY, ZONE_FULL]


def test_zone_map_status_and_untracked_column():
    tables = _clustered_tables(4, rows=8)
    zm = ZoneMap.from_tables(tables)
    assert (zm.status(Predicate("day", eq=2.0)).tolist()
            == [ZONE_EMPTY, ZONE_EMPTY, ZONE_FULL, ZONE_EMPTY])
    # no WHERE: everything provably matches
    assert (zm.status(None) == ZONE_FULL).all()
    # a column the map never saw proves nothing — sound fallback
    assert (zm.status(Predicate("untracked", eq=0.0))
            == ZONE_PARTIAL).all()


def test_zone_map_refresh_widens_and_invalidates():
    """Bounds only widen on refresh, and the (predicate, version) verdict
    cache invalidates: a block that gains matching rows flips EMPTY ->
    PARTIAL."""
    zm = ZoneMap.from_tables(_clustered_tables(3, rows=8))
    p = Predicate("day", eq=2.0)
    assert zm.status(p)[0] == ZONE_EMPTY
    zm.refresh(0, {"value": np.array([MU]), "day": np.array([2.0])})
    assert zm.status(p)[0] == ZONE_PARTIAL  # mixed {0.0, 2.0} bounds
    lo, hi = zm.columns["day"]
    assert lo[0] == 0.0 and hi[0] == 2.0


@settings(max_examples=60, deadline=None)
@given(
    blocks=st.lists(
        st.lists(st.integers(0, 4), min_size=1, max_size=8),
        min_size=1, max_size=6),
    lo=st.none() | st.integers(-1, 5),
    hi=st.none() | st.integers(-1, 5),
    eq=st.none() | st.integers(-1, 5),
)
def test_zone_verdicts_are_sound(blocks, lo, hi, eq):
    """Property (zone soundness): for ANY data and ANY predicate, an
    EMPTY verdict means no row of the block matches and a FULL verdict
    means every row matches — and the executor's ``_zone_mask`` shortcut
    is bit-identical to the plain ``where.mask``."""
    tables = [{"value": np.asarray(b, dtype=np.float64) + 50.0,
               "day": np.asarray(b, dtype=np.float64)}
              for b in blocks]
    where = Predicate("day",
                      lo=None if lo is None else float(lo),
                      hi=None if hi is None else float(hi),
                      eq=None if eq is None else float(eq))
    zm = ZoneMap.from_tables(tables)
    status = zm.status(where)
    for b, t in enumerate(tables):
        m = where.mask(t)
        if status[b] == ZONE_EMPTY:
            assert not m.any()
        elif status[b] == ZONE_FULL:
            assert m.all()
    ex = _executor(tables)
    columns = {k: np.concatenate([t[k] for t in tables])
               for k in tables[0]}
    block_ids = np.repeat(np.arange(len(tables)),
                          [len(b) for b in blocks])
    np.testing.assert_array_equal(
        ex._zone_mask(where, columns, block_ids), where.mask(columns))


# ---------------------------------------------------------------------------
# Planner: pruned block rates, floor warning.
# ---------------------------------------------------------------------------


def test_plan_rates_empty_blocks_exactly_zero(rng):
    """The mode-group's ``block_rates`` plan is exactly 0 on every
    provably-empty block (deterministic-zero contribution, no draw) and
    shared across the active ones; its quotas draw nothing there."""
    tables = _clustered_tables(10, rows=400)
    ex = _executor(tables)
    q = IslaQuery(e=1.0, beta=0.95, where=Predicate("day", eq=3.0))
    plan = ex.plan([q], rng)
    (mg,) = plan.mode_groups
    assert mg.block_rates is not None
    status = ex.zone_map.status(q.where)
    assert (mg.block_rates[status == ZONE_EMPTY] == 0.0).all()
    assert (mg.block_rates[status != ZONE_EMPTY] > 0.0).all()
    quotas = ex._target_quotas(mg, None)
    assert (quotas[status == ZONE_EMPTY] == 0).all()
    assert quotas[3] > 0


def test_zone_selectivity_counts_full_mass_exactly():
    """``zone_selectivity`` = (full mass + clipped residual estimate) /
    active mass — empty blocks leave both sides of the ratio."""
    tables = _clustered_tables(3, rows=100)  # day: 0 / 1 / 2
    tables.append({"value": np.full(100, MU),
                   "day": np.repeat([1.0, 3.0], 50)})  # PARTIAL for day==1
    ex = _executor(tables)
    pilot = {k: np.concatenate([t[k] for t in tables])
             for k in tables[0]}
    # status for day==1: [EMPTY, FULL, EMPTY, PARTIAL]; pilot sel = 150/400
    sel = ex.zone_selectivity(Predicate("day", eq=1.0), pilot)
    assert sel == pytest.approx((100.0 + 50.0) / 200.0)


def test_selectivity_floor_warns_without_zones(rng):
    """Scalar plan below MIN_PLANNED_SELECTIVITY: the capped rate cannot
    promise (e, beta), so planning warns."""
    tables = _clustered_tables(128, rows=64)
    assert 1.0 / 128 < MIN_PLANNED_SELECTIVITY
    q = IslaQuery(e=8.0, beta=0.9, where=Predicate("day", eq=3.0))
    with pytest.warns(PlannedSelectivityFloorWarning):
        _executor(tables, zone=False).plan([q], rng)


def test_zone_plan_avoids_selectivity_floor(rng):
    """The same sub-floor predicate with a helpful zone map re-weights
    over the active mass only (zone selectivity ~1), so no floor warning
    and a fraction of the samples."""
    tables = _clustered_tables(128, rows=64)
    ex = _executor(tables)
    q = IslaQuery(e=8.0, beta=0.9, where=Predicate("day", eq=3.0))
    with warnings.catch_warnings():
        warnings.simplefilter("error", PlannedSelectivityFloorWarning)
        plan = ex.plan([q], rng)
    (mg,) = plan.mode_groups
    assert int(np.sum(mg.block_rates > 0.0)) == 1


# ---------------------------------------------------------------------------
# Pruned vs unpruned agreement, host and device routes.
# ---------------------------------------------------------------------------


def _counting_tables(tables):
    """table_samplers wrapped with a per-block drawn-row counter."""
    drawn = np.zeros(len(tables), dtype=np.int64)

    def wrap(sampler, b):
        def f(n, rng):
            drawn[b] += n
            return sampler(n, rng)
        return f

    return [wrap(table_sampler(t), b) for b, t in enumerate(tables)], drawn


def test_pruned_run_skips_empty_blocks_host_route():
    """End to end on the host route: with the zone map the main pass
    draws NOTHING from provably-empty blocks (only the block-proportional
    pilot touches them), both answers meet (e, beta) against the ground
    truth, and the pruned run spends a fraction of the samples."""
    tables = _clustered_tables(12, rows=3000)
    truth = float(np.mean(tables[3]["value"]))
    q = IslaQuery(e=0.5, beta=0.95, where=Predicate("day", eq=3.0))
    outs = {}
    for zone in (True, False):
        samplers, drawn = _counting_tables(tables)
        rows = len(tables[0]["value"])
        zm = ZoneMap.from_tables(tables) if zone else None
        ex = MultiQueryExecutor(samplers, [rows] * len(tables),
                                zone_map=zm)
        pilot_only = None
        orig_plan = ex.plan

        def spy_plan(*a, _ex=ex, **kw):
            nonlocal pilot_only
            out = orig_plan(*a, **kw)
            pilot_only = drawn.copy()  # pilot draws all happen in plan()
            return out
        ex.plan = spy_plan
        ans = ex.run([q], np.random.default_rng(7))[0]
        main = drawn - pilot_only
        outs[zone] = (ans, main)
        assert abs(ans.value - truth) <= q.e
    empty = np.asarray([b for b in range(12) if b != 3])
    assert (outs[True][1][empty] == 0).all()      # pruned: zero main draws
    assert (outs[False][1][empty] > 0).all()      # masked: samples + drops
    savings = outs[False][0].new_samples / outs[True][0].new_samples
    assert savings > 5.0


def test_pruned_device_route_matches_host(rng):
    """The pruned plan threads through the device tier: incremental
    ``route="device"`` (the DeviceStack tick, where the compacted launch
    lives) agrees with the host route on the same seeds and spends the
    same pruned sample budget."""
    tables = _clustered_tables(12, rows=3000)
    q = IslaQuery(e=0.5, beta=0.95, where=Predicate("day", eq=3.0))
    ans = {}
    for route in ("host", "device"):
        ex = _executor(tables)
        ans[route] = ex.run([q], np.random.default_rng(7), route=route,
                            incremental=True)[0]
    assert np.isclose(ans["device"].value, ans["host"].value, rtol=1e-4)
    assert ans["device"].new_samples == ans["host"].new_samples
    truth = float(np.mean(tables[3]["value"]))
    assert abs(ans["device"].value - truth) <= q.e


# ---------------------------------------------------------------------------
# Compacted device launch: bit parity, warm re-activation.
# ---------------------------------------------------------------------------


def _stack(n_blocks, n_groups, compaction):
    params = IslaParams()
    b = make_boundaries(MU, SIGMA, params)
    sizes = np.full(n_blocks, 10.0 ** 6)
    stack = DeviceStack(
        [DeviceMomentStore.fresh_device(n_blocks, b, MU, sizes,
                                        n_groups=g)
         for g in (1, n_groups)])
    stack.block_compaction = compaction
    return stack, params


def _pruned_draw(rng, n_blocks, n_groups, active, quota=32):
    quotas = np.zeros(n_blocks, dtype=np.int64)
    quotas[np.asarray(active)] = quota
    vals = rng.normal(MU, SIGMA, len(active) * quota)
    gids = rng.integers(0, n_groups, vals.size)
    return vals, gids, quotas


def test_compacted_launch_bit_identical_x64(rng):
    """Acceptance: the compacted dense launch (gather active blocks,
    scatter the delta) reproduces the full-axis launch BIT-IDENTICALLY
    on the resident x64 state — active cells see the same adds in the
    same order, pruned cells are never addressed."""
    from jax import enable_x64

    with enable_x64():
        n_blocks, n_groups = 24, 3
        outs = []
        for compaction in (True, False):
            r = np.random.default_rng(5)
            stack, params = _stack(n_blocks, n_groups, compaction)
            for active in ([3, 17], [3, 17], [5]):
                vals, gids, quotas = _pruned_draw(r, n_blocks, n_groups,
                                                  active)
                stack.tick(params, values=vals, quotas=quotas,
                           dense=([None, gids], [None, None]))
            assert bool(stack._active_cache) is compaction  # engaged
            outs.append([np.asarray(a) for a in stack._state])
        assert all(np.array_equal(a, b) for a, b in zip(*outs))


def test_pruned_cells_stay_resident_and_reactivate_warm(rng):
    """Pruned cells keep their resident rows untouched through compacted
    ticks and re-activate warm: drawing block 5 after rounds that never
    touched it merges onto block 5's ORIGINAL state, bit-identically to
    the never-compacted stack (x64)."""
    from jax import enable_x64

    with enable_x64():
        n_blocks, n_groups = 24, 3
        stack, params = _stack(n_blocks, n_groups, True)
        vals, gids, quotas = _pruned_draw(np.random.default_rng(1),
                                          n_blocks, n_groups, [5])
        stack.tick(params, values=vals, quotas=quotas,
                   dense=([None, gids], [None, None]))
        baseline5 = [np.asarray(a).copy() for a in stack._state]
        for _ in range(3):  # block 5 pruned from every one of these
            vals, gids, quotas = _pruned_draw(rng, n_blocks, n_groups,
                                              [3, 17])
            stack.tick(params, values=vals, quotas=quotas,
                       dense=([None, gids], [None, None]))
        # the ledger/moment rows of block-5 cells never moved
        mom, n_sampled = (np.asarray(stack._state[0]),
                          np.asarray(stack._state[3]))
        for k, st_ in enumerate(stack.stores):
            cells = (int(stack.offsets[k])
                     + np.arange(st_.n_groups) * n_blocks + 5)
            np.testing.assert_array_equal(mom[cells], baseline5[0][cells])
        ns2 = n_sampled.reshape(len(stack.stores), n_blocks)
        assert (ns2[:, 5] == np.asarray(baseline5[3]).reshape(
            len(stack.stores), n_blocks)[:, 5]).all()
        # warm re-activation: a later draw lands on the preserved rows
        vals, gids, quotas = _pruned_draw(np.random.default_rng(9),
                                          n_blocks, n_groups, [5, 17])
        stack.tick(params, values=vals, quotas=quotas,
                   dense=([None, gids], [None, None]))
        assert (np.asarray(stack._state[3]).reshape(
            len(stack.stores), n_blocks)[:, 5] > ns2[:, 5]).all()


def test_compaction_falls_back_on_dense_active_sets(rng):
    """A draw touching (nearly) every block skips compaction — the padded
    compact axis would not be smaller — and still lands correctly."""
    n_blocks, n_groups = 12, 3
    stack, params = _stack(n_blocks, n_groups, True)
    vals, gids, quotas = _pruned_draw(rng, n_blocks, n_groups,
                                      list(range(n_blocks)))
    assert stack._compact_plan(quotas) is None
    stack.tick(params, values=vals, quotas=quotas,
               dense=([None, gids], [None, None]))
    assert not stack._active_cache
    ns = np.asarray(stack._state[3]).reshape(len(stack.stores), n_blocks)
    assert (ns == 32).all()
