"""Mesh-sharded ISLA cell axis (``MeshDeviceStack`` / ``route="mesh"``).

Covers the mesh tier's parity contracts against the single-device
``DeviceStack``: tagged and dense fused ticks (fp32 tolerance), warm
donated continuation ticks, hetero-anchor stacks, the zero-draw
re-solve, x64 bit parity of the resident state and per-cell partials
(psum'd stat rows are allclose only — float association), the
release/reset round trip that gathers rows back from EVERY shard, the
executor route parity (``route="mesh"`` vs ``route="device"``), the
shard-aware per-key reset path, the ``isla_cell_specs`` placement
table, and the collective-footprint audit: the only cross-device
traffic a compiled mesh tick may contain is the O(groups) stat-row
psum — never per-cell moment state.

Single-shard cases run on a stock 1-device CPU runtime; multi-shard
cases need ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set
before jax imports (the CI mesh job uses N=8) and skip otherwise.
"""
import numpy as np
import pytest

import jax

from repro.core import distributed as D
from repro.core.moment_store import (DeviceMomentStore, DeviceStack,
                                     MeshDeviceStack, _bucket)
from repro.core.multiquery import (IslaQuery, MultiQueryExecutor,
                                   Predicate)
from repro.core.types import Boundaries, IslaParams
from repro.launch.mesh import make_cell_mesh
from repro.sharding.specs import ISLA_CELL_AXIS, isla_cell_specs

PARAMS = IslaParams()
N_DEV = jax.device_count()

multi_shard = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count>=2 "
           "set before jax import")

# B deliberately NOT divisible by typical shard counts (2/4/8), so every
# multi-shard run exercises the inert trailing pad blocks.
B, G = 10, 3
SIZES = [100 + 7 * i for i in range(B)]


def _mk(shift=0.0, sketch0=3.0, bounds=(0.5, 2.0, 2.0, 8.0)):
    return DeviceMomentStore.fresh_device(
        B, Boundaries(*bounds), sketch0=sketch0, shift=shift,
        block_sizes=SIZES, n_groups=G)


def _pair(mk_a=_mk, mk_b=_mk):
    """(single-device stack, mesh stack) over two fresh two-store sets."""
    a1, b1, a2, b2 = mk_a(), mk_b(), mk_a(), mk_b()
    return (DeviceStack([a1, b1]), MeshDeviceStack([a2, b2],
                                                   make_cell_mesh()),
            (a1, b1), (a2, b2))


def _draw(rng, lo=3, hi=9):
    quotas = rng.integers(lo, hi, size=B)
    n = int(quotas.sum())
    vals = rng.lognormal(1.0, 0.7, size=n)
    block_ids = np.repeat(np.arange(B), quotas)
    gids = rng.integers(0, G, size=n)
    return vals, block_ids, gids, quotas


def _tick_both(single, msh, singles, meshes, vals, bids, gids, quotas,
               **kw):
    """Run the same tagged pass through both stacks via each stack's
    ``key_seg`` placement contract; returns (out_single, out_mesh)."""
    seg_s = np.concatenate([single.key_seg(k, st, bids, gids)
                            for k, st in enumerate(singles)])
    seg_m = np.concatenate([msh.key_seg(k, st, bids, gids)
                            for k, st in enumerate(meshes)])
    v2 = np.concatenate([(vals + st.shift) / st.scale for st in singles])
    return (single.tick(PARAMS, values=v2, seg=seg_s, quotas=quotas, **kw),
            msh.tick(PARAMS, values=v2, seg=seg_m, quotas=quotas, **kw))


def _assert_stats_close(out_s, out_m, rtol=1e-5):
    for (ps, rs), (pm, rm) in zip(out_s, out_m):
        np.testing.assert_allclose(np.asarray(pm), np.asarray(ps),
                                   rtol=rtol)
        np.testing.assert_allclose(np.asarray(rm), np.asarray(rs),
                                   rtol=rtol)


# ---------------------------------------------------------------------------
# Tick parity: single-device DeviceStack vs MeshDeviceStack.
# ---------------------------------------------------------------------------


def test_mesh_tagged_tick_matches_single_device(rng):
    """The sharded tagged fused tick reproduces the single-device stack
    (per-cell partials and psum'd stat rows, fp32 tolerance), and a
    warm SECOND tick through the donated resident state still agrees —
    the block-run layout, drop-row retagging and pad blocks are all
    invisible in the answers."""
    single, msh, singles, meshes = _pair()
    for _ in range(2):
        out_s, out_m = _tick_both(single, msh, singles, meshes,
                                  *_draw(rng))
        _assert_stats_close(out_s, out_m)


def test_mesh_dense_tick_matches_single_device(rng):
    """Dense-layout parity: the block axis IS the sharded axis, so the
    mesh body is ``_dense_core`` verbatim on each shard's block run."""
    single, msh, _, _ = _pair()
    vals, _, gids, quotas = _draw(rng)
    dense = ([gids, None], [None, None])
    out_s = single.tick(PARAMS, values=vals, quotas=quotas, dense=dense)
    out_m = msh.tick(PARAMS, values=vals, quotas=quotas, dense=dense)
    _assert_stats_close(out_s, out_m)


def test_mesh_zero_draw_solve_matches_single_device(rng):
    """A zero-draw re-solve (mode flip, no new samples) launches
    ``mesh_solve_fn`` against the resident shards and matches the
    single-device ``fused_solve``."""
    single, msh, singles, meshes = _pair()
    _tick_both(single, msh, singles, meshes, *_draw(rng))
    out_s = single.tick(PARAMS, mode="faithful")
    out_m = msh.tick(PARAMS, mode="faithful")
    _assert_stats_close(out_s, out_m)


def test_mesh_hetero_anchor_tick_matches_single_device(rng):
    """Per-key refined anchors (different Boundaries / shift / sketch0
    per store -> per-cell cuts table, sharded with the cells) agree
    with the single-device hetero stack."""
    other = lambda: _mk(shift=0.5, sketch0=1.5,  # noqa: E731
                        bounds=(0.2, 1.0, 1.0, 4.0))
    single, msh, singles, meshes = _pair(mk_b=other)
    out_s, out_m = _tick_both(single, msh, singles, meshes, *_draw(rng))
    _assert_stats_close(out_s, out_m)


def test_mesh_release_round_trip(rng):
    """``MeshDeviceStack.release`` gathers each store's rows back from
    EVERY shard (one d2h of the four mesh arrays + inverse
    permutation): the released stores match their single-device twins,
    including the ledger."""
    single, msh, (a1, b1), (a2, b2) = _pair()
    _tick_both(single, msh, (a1, b1), (a2, b2), *_draw(rng))
    msh.release()
    assert a2._owner is None and msh._released
    np.testing.assert_allclose(np.asarray(a2.mom_s), np.asarray(a1.mom_s),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(b2.totals),
                               np.asarray(b1.totals), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(a2._n_sampled_dev),
                               np.asarray(a1._n_sampled_dev))
    np.testing.assert_allclose(np.asarray(b2._n_sampled_dev),
                               np.asarray(b1._n_sampled_dev))


def test_mesh_x64_state_and_partials_bit_exact(rng):
    """The x64 bit-parity contract for the mesh tier: resident moments,
    totals and per-cell partials are BIT-IDENTICAL to the single-device
    stack (each shard's fold order is the single-device fold on its own
    cells; non-owned samples retag to the drop row without touching the
    accumulation order).  The psum'd stat rows are only allclose — the
    cross-shard reduction order is the one thing that legitimately
    differs."""
    from jax import enable_x64

    with enable_x64():
        single, msh, singles, meshes = _pair()
        assert singles[0].scale == 1.0  # x64 runs unscaled
        out_s, out_m = _tick_both(single, msh, singles, meshes,
                                  *_draw(rng))
        for st_s, st_m in zip(singles, meshes):
            assert np.array_equal(np.asarray(st_m.mom_s),
                                  np.asarray(st_s.mom_s))
            assert np.array_equal(np.asarray(st_m.mom_l),
                                  np.asarray(st_s.mom_l))
            assert np.array_equal(np.asarray(st_m.totals),
                                  np.asarray(st_s.totals))
        for (ps, rs), (pm, rm) in zip(out_s, out_m):
            assert np.array_equal(np.asarray(pm), np.asarray(ps))
            np.testing.assert_allclose(np.asarray(rm), np.asarray(rs),
                                       rtol=1e-12)


# ---------------------------------------------------------------------------
# Placement + transfer audit.
# ---------------------------------------------------------------------------


def test_isla_cell_specs_match_stack_placement(rng):
    """``sharding.specs.isla_cell_specs`` is the stack's actual
    placement table: per-cell matrices shard as ``cell_rows``, per-cell
    vectors as ``cells``, the stat rows come back replicated."""
    from jax.sharding import NamedSharding

    _, msh, _, meshes = _pair()
    specs = isla_cell_specs(msh.mesh)
    assert D.cell_axis(msh.mesh) == ISLA_CELL_AXIS

    def placed(arr, spec):
        return arr.sharding.is_equivalent_to(
            NamedSharding(msh.mesh, spec), arr.ndim)

    mom_s, mom_l, totals, ns = msh._state
    for a in (mom_s, mom_l, totals):
        assert placed(a, specs["cell_rows"])
    assert placed(ns, specs["cells"])
    assert placed(msh._sizes, specs["cells"])
    assert placed(msh._inv_scale, specs["cells"])
    vals, bids, gids, quotas = _draw(rng)
    seg = np.concatenate([msh.key_seg(k, st, bids, gids)
                          for k, st in enumerate(meshes)])
    v2 = np.concatenate([vals / st.scale for st in meshes])
    out = msh.tick(PARAMS, values=v2, seg=seg, quotas=quotas)
    # Rows land on the host (psum'd, replicated) sliced per store.
    assert all(rows.shape == (G, 9) for _, rows in out)


@multi_shard
def test_mesh_tick_collectives_are_stat_rows_only(rng):
    """Acceptance: the compiled mesh tick's ONLY cross-device
    collectives are the O(groups) stat-row psum — every entry in the
    HLO collective footprint is bounded by n_rows * 9 elements, so no
    per-cell moment state ever crosses devices (the mesh analogue of
    the device tier's ``transfer_guard`` audit)."""
    from jax.sharding import PartitionSpec as P

    mesh = make_cell_mesh()
    a = _mk()
    msh = MeshDeviceStack([a], mesh)
    vals, bids, gids, quotas = _draw(rng)
    seg = msh.key_seg(0, a, bids, gids)
    n = vals.size
    bucket = _bucket(n)
    v_pad = np.zeros(bucket)
    v_pad[:n] = vals / a.scale
    s_pad = np.full(bucket, msh.n_cells_mesh, np.int32)
    s_pad[:n] = seg
    fn = D.mesh_tick_fn(mesh, PARAMS, "calibrated", None, (G,), False)
    args = (*msh._state,
            D.mesh_h2d(mesh, v_pad, P(), msh.dtype),
            D.mesh_h2d(mesh, s_pad, P(), jax.numpy.int32),
            D.mesh_h2d(mesh,
                       np.zeros(msh.n_shards * msh.blocks_local),
                       P(ISLA_CELL_AXIS), msh.dtype),
            msh._bounds, msh._sketch0_cells(), msh._sizes,
            msh._inv_scale, None, None)
    footprint = D.collective_footprint(fn.lower(*args).compile().as_text())
    assert footprint, "expected at least the stat-row psum"
    cap = G * 9  # one store, G group-stat rows of 9 columns
    assert all(elements <= cap for _, elements in footprint), footprint
    n_cells_resident = msh.n_cells_mesh * 4
    assert all(elements < n_cells_resident
               for _, elements in footprint), footprint


# ---------------------------------------------------------------------------
# Executor route parity + shard-aware per-key reset.
# ---------------------------------------------------------------------------


def _region_executor(seed, n_blocks=40, rows=400):
    rng = np.random.default_rng(seed)
    blocks = [{"value": rng.lognormal(1.0, 0.8, rows),
               "region": rng.integers(0, 4, rows)}
              for _ in range(n_blocks)]

    def sampler(blk):
        def draw(n, rng2):
            idx = rng2.integers(0, rows, n)
            return {"value": blk["value"][idx],
                    "region": blk["region"][idx]}
        return draw

    return MultiQueryExecutor([sampler(b) for b in blocks],
                              [rows] * n_blocks,
                              group_domains={"region": 4})


_REGION_QUERIES = [IslaQuery(agg="AVG"),
                   IslaQuery(agg="AVG", group_by="region"),
                   IslaQuery(agg="SUM",
                             where=Predicate(column="region", eq=1)),
                   IslaQuery(agg="VAR")]


def test_executor_route_mesh_matches_device():
    """End to end, ``route="mesh"`` answers the same batch as
    ``route="device"`` across two incremental runs (same RNG stream,
    warm second tick) — values and per-group rows within fp32
    tolerance, and the warm tick tops up zero new samples on both
    routes."""
    outs = {}
    for route in ("device", "mesh"):
        ex = _region_executor(7)
        rng = np.random.default_rng(11)
        a1 = ex.run(_REGION_QUERIES, rng, mode="calibrated", route=route,
                    incremental=True)
        a2 = ex.run(_REGION_QUERIES, rng, mode="calibrated", route=route,
                    incremental=True)
        assert all(a.new_samples == 0 for a in a2)
        outs[route] = (a1, a2)
    for tick in (0, 1):
        for dev, msh in zip(outs["device"][tick], outs["mesh"][tick]):
            if dev.value is not None:
                assert np.isclose(dev.value, msh.value, rtol=1e-4)
            if dev.groups is not None:
                np.testing.assert_allclose(
                    [g.value for g in msh.groups],
                    [g.value for g in dev.groups], rtol=1e-4)


def test_mesh_per_key_reset_is_shard_aware():
    """Dropping ONE key's warm state on the mesh route releases its
    stack through ``MeshDeviceStack.release`` — the surviving keys'
    stores get their rows back from EVERY shard (bit-identical to the
    pre-release gather), and the next run rebuilds the stack, re-draws
    only the dropped key and answers unchanged for the survivors."""
    ex = _region_executor(7)
    rng = np.random.default_rng(11)
    for _ in range(2):  # second run converges: survivors fully warm
        pre = ex.run(_REGION_QUERIES, rng, mode="calibrated",
                     route="mesh", incremental=True)
    assert ex._device_stores, "mesh route should build device mirrors"
    keys = list(ex._device_stores)
    grouped = next(k for k in keys if k.group_by == "region")
    survivors = [k for k in keys if k is not grouped]
    snap = {k: (np.asarray(ex._device_stores[k].mom_s),
                np.asarray(ex._device_stores[k].totals))
            for k in survivors}
    ex._drop_key_state(grouped)
    assert grouped not in ex._device_stores
    for k in survivors:
        st = ex._device_stores[k]
        assert st._owner is None  # stack dissolved, state handed back
        assert np.array_equal(np.asarray(st.mom_s), snap[k][0])
        assert np.array_equal(np.asarray(st.totals), snap[k][1])
    answers = ex.run(_REGION_QUERIES, rng, mode="calibrated",
                     route="mesh", incremental=True)
    regrouped = answers[1]
    assert regrouped.new_samples > 0  # dropped key re-accumulates
    np.testing.assert_allclose([g.value for g in regrouped.groups],
                               [g.value for g in pre[1].groups],
                               rtol=0.05)
    # Survivors answer on their preserved (now topped-up) state: the
    # ungrouped AVG/VAR stay consistent with the pre-drop converged run.
    for i in (0, 3):
        assert np.isclose(answers[i].value, pre[i].value, rtol=0.05)


# ---------------------------------------------------------------------------
# Zone-pruned compacted launch (block pruning through the mesh tier).
# ---------------------------------------------------------------------------


def test_mesh_pruned_compacted_tick_matches_device(rng):
    """Zone-pruned quotas through the mesh tier: the shard-aware
    compacted launch (each shard's local active-block run padded to the
    shared width) reproduces the single-device FULL-AXIS launch, across
    warm re-activation rounds that change the active set.  Uses its own
    wider store — the module's B=10 under 8 shards leaves runs too short
    for compaction to ever engage, which is exactly the fallback the
    plan's size guard takes."""
    B2 = 64  # divisible by 1/2/4/8 shards: every shard owns a real run
    sizes = [1000 + 3 * i for i in range(B2)]

    def mk():
        return DeviceMomentStore.fresh_device(
            B2, Boundaries(0.5, 2.0, 2.0, 8.0), sketch0=3.0,
            block_sizes=sizes, n_groups=G)

    a1, b1, a2, b2 = mk(), mk(), mk(), mk()
    single = DeviceStack([a1, b1])
    single.block_compaction = False  # uncompacted reference
    msh = MeshDeviceStack([a2, b2], make_cell_mesh())
    for active in ([3, B2 - 5], [3, B2 - 5], [7, 20, B2 - 5]):
        quotas = np.zeros(B2, dtype=np.int64)
        quotas[np.asarray(active)] = 24
        n = int(quotas.sum())
        vals = rng.lognormal(1.0, 0.7, size=n)
        gids = rng.integers(0, G, size=n)
        dense = ([gids, None], [None, None])
        out_s = single.tick(PARAMS, values=vals, quotas=quotas,
                            dense=dense)
        out_m = msh.tick(PARAMS, values=vals, quotas=quotas, dense=dense)
        _assert_stats_close(out_s, out_m)
    assert msh._active_cache, "mesh compaction should have engaged"
    assert not single._active_cache
