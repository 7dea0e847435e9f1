"""Device-resident ISLA: DeviceMomentStore / DeviceStack / fused ticks.

Covers the PR-4 acceptance contract: fp32 tolerance parity against the
host float64 path (bit-exact when jax runs in x64), zero host<->device
moment transfers on the steady-state tick (transfer-guard + sanctioned-
upload counting), donated in-place state, the stacked multi-store launch,
the drift guard, and the shared chunked-draw-loop contract.
"""
import numpy as np
import pytest

from repro.core import IslaParams, IslaQuery, Predicate
from repro.core.boundaries import make_boundaries
from repro.core.moment_store import (DeviceMomentStore, DeviceStack,
                                     MomentStore, iter_chunked_draws)
from repro.core.multiquery import MultiQueryExecutor, table_sampler

PARAMS = IslaParams()
MU, SIGMA = 100.0, 20.0


def _tagged_pass(rng, n_blocks, n_groups, quota, masked=True):
    vals = rng.normal(MU, SIGMA, n_blocks * quota)
    bids = np.repeat(np.arange(n_blocks), quota)
    gids = rng.integers(0, n_groups, vals.size)
    mask = (rng.random(vals.size) < 0.8) if masked else None
    quotas = np.full(n_blocks, quota, dtype=np.int64)
    return vals, bids, gids, mask, quotas


def _host_and_device(n_blocks=5, n_groups=3):
    b = make_boundaries(MU, SIGMA, PARAMS)
    host = MomentStore.fresh(n_blocks, b, MU, n_groups=n_groups)
    dev = DeviceMomentStore.fresh_device(n_blocks, b, MU,
                                         [10 ** 6] * n_blocks,
                                         n_groups=n_groups)
    return host, dev


def test_device_store_matches_host_fp32(rng):
    """Two merged ticks: device moments/partials track the host float64
    path within fp32 tolerance; ledgers identical."""
    host, dev = _host_and_device()
    for _ in range(2):
        vals, bids, gids, mask, quotas = _tagged_pass(rng, 5, 3, 3000)
        host.ingest(vals, bids, quotas, group_ids=gids, mask=mask)
        dev.ingest_tick(vals, bids, quotas, PARAMS, group_ids=gids,
                        mask=mask)
    res = host.solve(PARAMS, mode="calibrated")
    dh = dev.to_host()
    np.testing.assert_allclose(dh.mom_s, host.mom_s, rtol=5e-6,
                               atol=1e-3)
    np.testing.assert_allclose(dh.mom_l, host.mom_l, rtol=5e-6,
                               atol=1e-3)
    np.testing.assert_allclose(dev.partials_host(), res.avg, rtol=2e-4)
    assert np.array_equal(dh.n_sampled, host.n_sampled)
    assert dh.rounds == host.rounds == 2
    assert dev.sample_sigma() == pytest.approx(host.sample_sigma(),
                                               rel=1e-4)


def test_device_store_bit_exact_x64(rng):
    """The float64 device store (tagged carry-prepend scatter) is
    BIT-IDENTICAL to the host bincount fold — moments, totals and the
    solved partials."""
    from jax import enable_x64

    host, _ = _host_and_device()
    passes = [_tagged_pass(rng, 5, 3, 2000) for _ in range(2)]
    for vals, bids, gids, mask, quotas in passes:
        host.ingest(vals, bids, quotas, group_ids=gids, mask=mask)
    res = host.solve(PARAMS, mode="calibrated")
    with enable_x64():
        b = make_boundaries(MU, SIGMA, PARAMS)
        dev = DeviceMomentStore.fresh_device(5, b, MU, [10 ** 6] * 5,
                                             n_groups=3)
        assert dev.scale == 1.0  # x64 runs unscaled for bit parity
        for vals, bids, gids, mask, quotas in passes:
            dev.ingest_tick(vals, bids, quotas, PARAMS, group_ids=gids,
                            mask=mask)
        dh = dev.to_host()
        assert np.array_equal(dh.mom_s, host.mom_s)
        assert np.array_equal(dh.mom_l, host.mom_l)
        assert np.array_equal(dh.totals, host.totals)
        assert np.array_equal(dev.partials_host(), res.avg)


def test_dense_and_tagged_layouts_agree(rng):
    """The dense batched-contraction Phase 1 and the tagged scatter fold
    the same pass to the same moments (fp32 summation-order tolerance)."""
    _, dev_a = _host_and_device()
    _, dev_b = _host_and_device()
    vals, bids, gids, mask, quotas = _tagged_pass(rng, 5, 3, 3000)
    dev_a.ingest_tick(vals, bids, quotas, PARAMS, group_ids=gids,
                      mask=mask, layout="dense")
    dev_b.ingest_tick(vals, bids, quotas, PARAMS, group_ids=gids,
                      mask=mask, layout="tagged")
    np.testing.assert_allclose(np.asarray(dev_a.mom_s),
                               np.asarray(dev_b.mom_s), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(dev_a.totals),
                               np.asarray(dev_b.totals), rtol=1e-5,
                               atol=1e-4)


def _counting_h2d(calls):
    from repro.core import distributed as D
    real = D.h2d

    def h2d(x, dtype=None):
        calls.append(np.asarray(x).nbytes)
        return real(x, dtype)
    return h2d


@pytest.mark.transfer_guard
def test_steady_tick_zero_moment_transfers(rng, monkeypatch):
    """Acceptance: the steady-state device tick runs under
    ``jax.transfer_guard("disallow")`` with ONE sanctioned sample upload
    crossing (the packed quotas, values and GROUP BY pane — sample-sized;
    the pad mask is derived in the launch), booked as one ``h2d_calls``,
    and the resident moments never ship."""
    import jax

    from repro.core import distributed as D

    _, dev = _host_and_device()
    vals, bids, gids, mask, quotas = _tagged_pass(rng, 5, 3, 1000,
                                                  masked=False)
    dev.ingest_tick(vals, bids, quotas, PARAMS, group_ids=gids)  # warm

    calls = []
    monkeypatch.setattr(D, "h2d", _counting_h2d(calls))
    vals, bids, gids, _, quotas = _tagged_pass(rng, 5, 3, 1000,
                                               masked=False)
    with jax.transfer_guard("disallow"):
        dev.ingest_tick(vals, bids, quotas, PARAMS, group_ids=gids)
    assert len(calls) == 1  # quotas, values, group codes: one buffer
    # The crossing is sample-sized (<= 2x bucket padding, within the
    # bound each float64 pane was once held to) — nothing remotely
    # moment-shaped ships.
    assert calls[0] <= 8 * 2 * vals.size
    # The run's counter books the same single upload.
    clock = D.StageClock(("h2d", "h2d.pane", "h2d.put", "launch",
                          "readback"), ("h2d_calls", "h2d_bytes"))
    with jax.transfer_guard("disallow"):
        dev._own_stack().tick(PARAMS, values=vals - dev.shift,
                              quotas=quotas, dense=([gids], [None]),
                              timings=clock)
    assert clock.counts["h2d_calls"] == 1
    assert clock.counts["h2d_bytes"] == calls[1] == calls[0]
    # Zero-draw warm repeat: answered from the stats cache — NO h2d,
    # no launch, not even a transfer-guard scope entered.
    calls.clear()
    with jax.transfer_guard("disallow"):
        dev.solve_device(PARAMS)
    assert calls == []


def test_donation_consumes_previous_state(rng):
    """The fused tick donates the resident buffers: after a continuation
    round the previous round's moment buffer is dead (in-place launch),
    not a lingering copy."""
    _, dev = _host_and_device()
    vals, bids, gids, mask, quotas = _tagged_pass(rng, 5, 3, 1000)
    dev.ingest_tick(vals, bids, quotas, PARAMS, group_ids=gids, mask=mask)
    before = np.asarray(dev.mom_s).copy()
    stacked_before = dev._owner._state[0]
    vals, bids, gids, mask, quotas = _tagged_pass(rng, 5, 3, 1000)
    dev.ingest_tick(vals, bids, quotas, PARAMS, group_ids=gids, mask=mask)
    assert stacked_before.is_deleted()
    assert not np.array_equal(before, np.asarray(dev.mom_s))


def test_from_host_to_host_roundtrip(rng):
    """Warm-store promotion uploads once and round-trips the state."""
    host, _ = _host_and_device()
    vals, bids, gids, mask, quotas = _tagged_pass(rng, 5, 3, 2000)
    host.ingest(vals, bids, quotas, group_ids=gids, mask=mask)
    dev = DeviceMomentStore.from_host(host, [10 ** 6] * 5)
    dh = dev.to_host()
    np.testing.assert_allclose(dh.mom_s, host.mom_s, rtol=1e-6)
    np.testing.assert_allclose(dh.totals, host.totals, rtol=1e-6)
    assert np.array_equal(dh.n_sampled, host.n_sampled)
    assert dh.rounds == host.rounds


def test_stack_release_and_regroup(rng):
    """A store leaving its stack (new warm key arrives -> stack rebuilt)
    keeps its state: release materializes the slices back."""
    _, dev_a = _host_and_device()
    _, dev_b = _host_and_device()
    stack = DeviceStack([dev_a, dev_b])
    vals, bids, gids, mask, quotas = _tagged_pass(rng, 5, 3, 2000)
    seg_a = dev_a.build_seg(bids, gids, mask, offset=0)
    seg_b = dev_b.build_seg(bids, gids, mask, offset=dev_a.n_cells)
    mvals = vals[mask]
    stack.tick(PARAMS, values=np.concatenate([mvals, mvals]),
               seg=np.concatenate([seg_a, seg_b]), quotas=quotas)
    snap = np.asarray(dev_a.mom_s).copy()
    # Regroup: dev_a joins a fresh stack with a new cold store.
    _, dev_c = _host_and_device()
    stack2 = DeviceStack([dev_a, dev_c])
    assert stack._released
    np.testing.assert_array_equal(np.asarray(dev_a.mom_s), snap)
    with pytest.raises(ValueError, match="released"):
        stack.tick(PARAMS)
    assert stack2.stores[0] is dev_a


def _bits(a):
    a = np.asarray(a)
    return a.shape, a.dtype, a.tobytes()


@pytest.mark.parametrize("x64", [False, True])
@pytest.mark.parametrize("has_sketch", [False, True])
@pytest.mark.parametrize("n_groups", [1, 4])
def test_cold_store_build_matches_eager_construction(monkeypatch, n_groups,
                                                     has_sketch, x64):
    """A cold store and its one-store stack come from one launch over one
    upload of the six anchor constants, and hold the same bits as the
    eager construction (an op or upload per array): zeros, cuts and their
    row, block sizes, the sketch scalar and vector, the inverse scale."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from repro.core import distributed as D
    from repro.core import sketch as SK

    n_blocks = 7
    sizes = [122880 + 17 * b for b in range(n_blocks)]
    bounds = make_boundaries(MU + 0.37, SIGMA + 0.11, PARAMS)
    sketch0 = MU + 0.731
    with jax.enable_x64() if x64 else contextlib.nullcontext():
        first = DeviceMomentStore.fresh_device(
            n_blocks, bounds, sketch0, sizes, n_groups=n_groups,
            has_sketch=has_sketch)
        uploads = []
        real = D.h2d

        def h2d(x, dtype=None):
            uploads.append(np.asarray(x))
            return real(x, dtype)
        monkeypatch.setattr(D, "h2d", h2d)
        st = DeviceMomentStore.fresh_device(
            n_blocks, bounds, sketch0, sizes, n_groups=n_groups,
            has_sketch=has_sketch, sizes=first._sizes)
        monkeypatch.undo()
        dtype = st.dtype
        assert dtype == (jnp.float64 if x64 else jnp.float32)
        assert (st.scale == 1.0) == x64
        # One upload: the six constants, cast on the host.
        packed = (np.asarray(bounds.as_tuple() + (sketch0, 1.0))
                  / st.scale).astype(dtype)
        assert len(uploads) == 1
        assert _bits(uploads[0]) == _bits(packed)

        n_cells = n_groups * n_blocks
        ref_bounds = jnp.asarray(np.asarray(bounds.as_tuple()) / st.scale,
                                 dtype)
        ref_sketch0 = jnp.asarray(sketch0 / st.scale, dtype)
        ref = {
            "_mom_s": jnp.zeros((n_cells, 4), dtype),
            "_mom_l": jnp.zeros((n_cells, 4), dtype),
            "_totals": jnp.zeros((n_cells, 3), dtype),
            "_ns_dev": jnp.zeros((n_blocks,), dtype),
            "_bounds": ref_bounds,
            "_bound_row": ref_bounds.reshape(1, 4),
            "_sizes": jnp.asarray(np.asarray(sizes, dtype=np.float64),
                                  dtype),
            "_sketch0_dev": ref_sketch0,
            "_sk_cells": jnp.broadcast_to(ref_sketch0, (n_cells,)),
            "_inv_scale": jnp.asarray(np.full(n_cells, 1.0 / st.scale),
                                      dtype),
        }
        for name, want in ref.items():
            assert _bits(getattr(st, name)) == _bits(want), name
        assert st._sizes is first._sizes
        ref_regs = jnp.zeros((n_cells, SK.M), jnp.uint8)
        if has_sketch:
            assert _bits(st._regs) == _bits(ref_regs)
        else:
            assert st._regs is None

        # The one-store stack adopts all of it and uploads nothing.
        uploads.clear()
        monkeypatch.setattr(D, "h2d", h2d)
        stack = DeviceStack([st])
        monkeypatch.undo()
        assert uploads == []
        for got, name in zip(stack._state,
                             ("_mom_s", "_mom_l", "_totals", "_ns_dev")):
            assert _bits(got) == _bits(ref[name]), name
        assert _bits(stack._bounds) == _bits(ref["_bound_row"])
        assert _bits(stack._bound_rows) == _bits(ref["_bound_row"])
        assert _bits(stack._sizes) == _bits(ref["_sizes"])
        assert _bits(stack._sketch0_cells()) == _bits(ref["_sk_cells"])
        assert _bits(stack._inv_scale) == _bits(ref["_inv_scale"])
        if has_sketch:
            assert _bits(stack._regs_state) == _bits(ref_regs)


def test_device_group_evicts_only_released_stacks():
    """The executor's stack cache: a cold key's build releases no stack,
    so every cached stack stays live; a batch that stacks a warm key
    with a new one releases exactly the warm key's old stack, which
    leaves the cache with its state freed, while an unrelated stack
    stays."""
    rng = np.random.default_rng(0)
    tables = [{"value": rng.normal(MU, SIGMA, 3000),
               "flag": rng.integers(0, 2, 3000).astype(np.float64)}
              for _ in range(4)]
    ex = MultiQueryExecutor([table_sampler(t) for t in tables],
                            [10 ** 6] * 4, params=IslaParams(e=1.0))
    q_a = IslaQuery(e=1.0, agg="AVG", where=Predicate(column="flag",
                                                      eq=1.0))
    q_b = IslaQuery(e=1.0, agg="AVG", where=Predicate(column="flag",
                                                      eq=0.0))
    q_c = IslaQuery(e=1.0, agg="AVG")

    def run(*queries):
        ex.run(list(queries), rng, incremental=True, route="device")
        return list(ex._device_stacks.values())

    (stack_a,) = run(q_a)
    stacks = run(q_b)
    assert len(stacks) == 2 and stack_a in stacks
    assert not any(s._released for s in stacks)
    (stack_b,) = [s for s in stacks if s is not stack_a]
    stacks = run(q_a, q_c)
    assert stack_a._released and stack_a._state is None
    assert stack_a not in stacks
    assert stack_b in stacks and not stack_b._released
    assert len(stacks) == 2 and not any(s._released for s in stacks)
    (stack_ac,) = [s for s in stacks if s is not stack_b]
    assert stack_ac.stores[0] is stack_a.stores[0]
    assert set(ex._stack_keys) == set(stacks)


def test_multiquery_device_resident_matches_host(rng):
    """run(incremental=True, route='device'): answers match the host
    route within fp32 tolerance, identical draw ledgers, warm repeats
    draw zero."""
    n_blocks, n_groups = 4, 3
    tables = []
    for _ in range(n_blocks):
        g = rng.integers(0, n_groups, size=3000)
        tables.append({
            "value": rng.normal(MU - 8.0 + 2.0 * g, SIGMA),
            "region": g.astype(np.float64),
            "flag": rng.integers(0, 2, size=3000).astype(np.float64),
        })
    sizes = [10 ** 7] * n_blocks
    queries = [
        IslaQuery(e=1.0, agg="AVG"),
        IslaQuery(e=1.0, agg="AVG", group_by="region"),
        IslaQuery(e=1.0, agg="SUM",
                  where=Predicate(column="flag", eq=1.0)),
        IslaQuery(e=1.0, agg="COUNT", group_by="region",
                  where=Predicate(column="flag", eq=1.0)),
        IslaQuery(e=1.0, agg="VAR"),
    ]

    def mk():
        return MultiQueryExecutor(
            [table_sampler(t) for t in tables], sizes,
            params=IslaParams(e=1.0), group_domains={"region": n_groups})

    host_ex, dev_ex = mk(), mk()
    ah = host_ex.run(queries, np.random.default_rng(5), incremental=True)
    ad = dev_ex.run(queries, np.random.default_rng(5), incremental=True,
                    route="device")
    for h, d in zip(ah, ad):
        assert d.value == pytest.approx(h.value, rel=2e-3)
        assert d.new_samples == h.new_samples
        assert d.sample_size == h.sample_size
        if h.groups is not None:
            for gh, gd in zip(h.groups, d.groups):
                assert gd.n_samples == gh.n_samples
                assert gd.value == pytest.approx(gh.value, rel=5e-3)
    # Warm repeat: zero new samples on both routes, answers unchanged.
    ad2 = dev_ex.run(queries, np.random.default_rng(7), incremental=True,
                     route="device")
    assert all(a.new_samples == 0 for a in ad2)
    for d, d2 in zip(ad, ad2):
        assert d2.value == pytest.approx(d.value, rel=1e-9)
    # A tighter demand tops up the same deficit as the host route.
    tight = [IslaQuery(e=0.5, agg="AVG", group_by="region")]
    (h3,) = host_ex.run(tight, np.random.default_rng(9), incremental=True)
    (d3,) = dev_ex.run(tight, np.random.default_rng(9), incremental=True,
                       route="device")
    assert d3.new_samples == h3.new_samples > 0
    assert d3.value == pytest.approx(h3.value, rel=2e-3)


def test_drift_guard_resets_on_table_change(rng):
    """Satellite: drift_check= re-pilots and resets warm stores when the
    table's distribution moved, instead of refining a stale anchor."""
    tables = [{"value": rng.normal(MU, SIGMA, 3000)} for _ in range(4)]
    sizes = [10 ** 6] * 4
    ex = MultiQueryExecutor([table_sampler(t) for t in tables], sizes,
                            params=IslaParams(e=1.0))
    q = [IslaQuery(e=1.0, agg="AVG")]
    ex.run(q, np.random.default_rng(1), incremental=True)
    # Stable table: the guard keeps the warm store (zero new samples).
    (a,) = ex.run(q, np.random.default_rng(2), incremental=True,
                  drift_check=6.0)
    assert a.new_samples == 0
    # The table shifts by many sigma: guard drops the stores, answers
    # re-converge to the new mean with fresh samples.
    new = [{"value": rng.normal(MU + 150.0, SIGMA, 3000)}
           for _ in range(4)]
    ex.block_samplers = [table_sampler(t) for t in new]
    (b,) = ex.run(q, np.random.default_rng(3), incremental=True,
                  drift_check=6.0)
    assert b.new_samples > 0
    assert abs(b.value - (MU + 150.0)) < 5.0
    assert not ex._stores or all(
        st.rounds <= 1 for st in ex._stores.values())


def test_drift_check_requires_incremental(rng):
    tables = [{"value": rng.normal(MU, SIGMA, 500)} for _ in range(2)]
    ex = MultiQueryExecutor([table_sampler(t) for t in tables],
                            [10 ** 5] * 2, params=IslaParams(e=1.0))
    with pytest.raises(ValueError, match="drift_check"):
        ex.run([IslaQuery(e=1.0)], rng, drift_check=3.0)


# -- shared chunked-draw-loop contract (satellite) -------------------------


class _RecordingSampler:
    """Sampler that logs (block, n) calls and draws from the rng."""

    def __init__(self, block, log):
        self.block = block
        self.log = log

    def __call__(self, n, rng):
        self.log.append((self.block, int(n)))
        return rng.normal(MU, SIGMA, size=n)


def test_iter_chunked_draws_contract():
    """Quota padding, zero-quota skip (no RNG consumed), one first=True
    chunk, block order."""
    log = []
    samplers = [_RecordingSampler(j, log) for j in range(6)]
    quotas = np.array([3, 0, 2, 0, 0, 4], dtype=np.int64)
    rng = np.random.default_rng(0)
    chunks = list(iter_chunked_draws(samplers, quotas, rng,
                                     chunk_blocks=2))
    assert log == [(0, 3), (2, 2), (5, 4)]  # zero-quota blocks skipped
    assert [c.first for c in chunks] == [True, False, False]
    total = np.zeros(6, dtype=np.int64)
    for c in chunks:
        assert c.chunk_quotas.shape == (6,)
        assert c.chunk_quotas[:c.start].sum() == 0
        assert c.chunk_quotas[c.end:].sum() == 0
        total += c.chunk_quotas
    assert np.array_equal(total, quotas)
    # An all-zero pass yields nothing (no round counted anywhere).
    assert list(iter_chunked_draws(samplers, np.zeros(6, np.int64),
                                   rng)) == []


def test_draw_loops_lockstep_parity():
    """The two serving draw paths — ``MomentStore.continue_rounds`` and
    the executor's ``_draw_and_ingest`` — consume IDENTICAL sampler-call
    sequences and RNG streams for the same quotas/chunking (they share
    ``iter_chunked_draws``), so their accumulated moments agree bit-
    for-bit."""
    n_blocks = 5
    sizes = [1000] * n_blocks
    rate = 0.1  # -> 100 per block via block_quotas
    b = make_boundaries(MU, SIGMA, PARAMS)

    log_a, log_b = [], []
    store_a = MomentStore.fresh(n_blocks, b, MU)
    store_a.continue_rounds([_RecordingSampler(j, log_a)
                             for j in range(n_blocks)],
                            sizes, rate, PARAMS,
                            np.random.default_rng(42), chunk_blocks=2)

    ex = MultiQueryExecutor([_RecordingSampler(j, log_b)
                             for j in range(n_blocks)], sizes,
                            params=IslaParams(e=1.0))
    store_b = MomentStore.fresh(n_blocks, b, MU)
    from repro.core.engine import block_quotas
    quotas = np.asarray(block_quotas(sizes, rate), dtype=np.int64)
    ex._draw_and_ingest({(None, None): store_b}, quotas,
                        np.random.default_rng(42), chunk_blocks=2)

    assert log_a == log_b  # identical call sequence -> identical RNG use
    assert np.array_equal(store_a.mom_s, store_b.mom_s)
    assert np.array_equal(store_a.mom_l, store_b.mom_l)
    assert np.array_equal(store_a.n_sampled, store_b.n_sampled)
    assert store_a.rounds == store_b.rounds == 1


def test_zero_draw_solve_respects_mode_change(rng):
    """The stats cache is keyed by the solve configuration: a zero-draw
    re-solve under a different Phase 2 mode must not return the previous
    mode's cached answers."""
    _, dev = _host_and_device()
    vals, bids, gids, mask, quotas = _tagged_pass(rng, 5, 3, 3000)
    dev.ingest_tick(vals, bids, quotas, PARAMS, mode="calibrated",
                    group_ids=gids, mask=mask)
    cal = dev.partials_host().copy()
    dev.solve_device(PARAMS, mode="faithful")
    faith = dev.partials_host()
    assert not np.allclose(cal, faith)  # the case-table answer differs
    # And re-solving under the original config serves the fresh solve.
    dev.solve_device(PARAMS, mode="calibrated")
    np.testing.assert_allclose(dev.partials_host(), cal, rtol=1e-6)


def test_scaled_phase2_iterates_to_host_depth(rng):
    """thr rides the scale normalization: large-magnitude data (anchor
    scale >> 1) must not stop the Phase 2 shrink log2(scale) rounds
    early on the fp32 device path.  A coarse thr makes the truncation
    error dominate the fp32 floor: left unscaled the residual is ~2e-5
    relative here, vs ~1e-7 with thr scaled."""
    big = 2.0e4  # anchor scale ~ 4.4e4
    coarse = PARAMS.replace(thr=1e-3)
    b = make_boundaries(big, SIGMA, coarse)
    host = MomentStore.fresh(5, b, big, n_groups=3)
    dev = DeviceMomentStore.fresh_device(5, b, big, [10 ** 6] * 5,
                                         n_groups=3)
    vals = rng.normal(big, SIGMA, 5 * 3000) + 0.4  # skewed off-anchor
    bids = np.repeat(np.arange(5), 3000)
    gids = rng.integers(0, 3, vals.size)
    quotas = np.full(5, 3000, dtype=np.int64)
    host.ingest(vals, bids, quotas, group_ids=gids)
    res = host.solve(coarse, mode="calibrated")
    dev.ingest_tick(vals, bids, quotas, coarse, mode="calibrated",
                    group_ids=gids)
    np.testing.assert_allclose(dev.partials_host(), res.avg, rtol=2e-6)
