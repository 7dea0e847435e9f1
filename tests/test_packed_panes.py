"""The dense tick's one packed upload (``distributed.PaneLayout``: ``pack``
on the host through ``moment_store._packed_panes``, ``unpack`` in the
launch).

The single-device dense tick ships every pane and each block's quota in ONE
buffer, a block's row of each beside its quota, and derives the pad mask
in-graph.  These tests hold the packing to
what the separate uploads carried: the same words for every pane (values
and masks as the device-dtype cast of the float64 panes, int32 codes and
uint32 hash limbs bit for bit), a pad mask equal to the host's ragged-quota
mask, and stacked ticks that agree with the host ``MomentStore``.
"""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import distributed as D
from repro.core import sketch as SK
from repro.core.boundaries import make_boundaries
from repro.core.moment_store import (DeviceMomentStore, DeviceStack,
                                     MomentStore, _dense_panes, _hash_panes,
                                     _key_panes, _packed_panes)
from repro.core.types import IslaParams

PARAMS = IslaParams()
MU, SIGMA = 100.0, 20.0
unpack = jax.jit(lambda buf, layout: layout.unpack(buf), static_argnums=1)


def _x64(dtype):
    return (jax.enable_x64() if dtype == np.float64
            else contextlib.nullcontext())


def _bits(a):
    a = np.asarray(a)
    return a.shape, a.dtype, a.tobytes()


def _keys(rng, m, n_groups):
    """Three keys over one stream: two share a GROUP BY array and a mask,
    the third has its own mask and no GROUP BY."""
    gids = rng.integers(0, n_groups, m).astype(np.int32)
    gids[:2] = [0, n_groups - 1]       # the smallest and largest codes
    wide = gids.copy()
    wide[2] = np.iinfo(np.int32).max   # every bit of the word survives
    mask_a = rng.random(m) < 0.7
    mask_b = rng.random(m) < 0.3
    return [gids, gids, None, wide], [mask_a, mask_a, mask_b, None]


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_pack_unpack_gives_back_every_pane(rng, dtype):
    quotas = np.array([5, 0, 17, 1, 32, 9], dtype=np.int64)
    m = int(quotas.sum())
    vals = rng.normal(MU, SIGMA, m) / 7.0
    key_gids, key_valids = _keys(rng, m, 11)
    with _x64(dtype):
        buf, layout, gid_slots, valid_slots = _packed_panes(
            vals, quotas, key_gids, key_valids, dtype, hash_values=vals)
        assert buf.dtype == dtype
        assert layout == D.PaneLayout(6, 32, n_valid=2, n_gid=2, n_hash=2)
        out = unpack(jnp.asarray(buf), layout)
        values2d, pad, q, gids, valids, hashes = out

        # The reference: the separate panes the tick used to upload, each
        # cast to its device dtype on the way up.
        v2d, pad_ref, vmask = _dense_panes(vals, quotas)
        gid_ref, gslots, valid_ref, vslots = _key_panes(vmask, key_gids,
                                                        key_valids)
        assert (gid_slots, valid_slots) == (gslots, vslots) \
            == ((0, 0, -1, 1), (0, 0, 1, -1))
        assert _bits(values2d) == _bits(jnp.asarray(v2d, dtype))
        assert _bits(pad) == _bits(jnp.asarray(pad_ref, dtype))
        assert _bits(q) == _bits(jnp.asarray(quotas, dtype))
        assert len(gids) == len(gid_ref) == 2
        for got, want in zip(gids, gid_ref):
            assert _bits(got) == _bits(want)
        assert np.asarray(gids[0])[0, 0] == 0
        assert np.asarray(gids[0])[0, 1] == 10
        assert np.asarray(gids[1])[0, 2] == np.iinfo(np.int32).max
        assert len(valids) == len(valid_ref) == 2
        for got, want in zip(valids, valid_ref):
            assert _bits(got) == _bits(jnp.asarray(want, dtype))
        for got, want in zip(hashes, _hash_panes(vals, vmask)):
            assert _bits(got) == _bits(want)


def _pad_cases():
    ragged = np.array([3, 0, 8, 1, 5, 0, 0, 64, 2], dtype=np.int64)
    pruned = np.zeros(40, dtype=np.int64)
    pruned[[4, 9, 31]] = [7, 1, 12]
    return {"ragged": (ragged, False), "zero_quota_block": (
        np.array([0, 0, 4], dtype=np.int64), False),
        "compacted": (pruned, True)}


@pytest.mark.parametrize("case", sorted(_pad_cases()))
def test_in_graph_pad_matches_host_mask(case):
    quotas, compacted = _pad_cases()[case]
    if compacted:
        # The launch sees the compact quota row of the active blocks.
        b = make_boundaries(MU, SIGMA, PARAMS)
        stack = DeviceStack([DeviceMomentStore.fresh_device(
            quotas.size, b, MU, [10 ** 6] * quotas.size)])
        plan = stack._compact_plan(quotas)
        assert plan is not None
        quotas = plan[0]
        assert quotas.size < stack.n_blocks and (quotas == 0).any()
    m = int(quotas.sum())
    buf, layout, _, _ = _packed_panes(np.ones(m), quotas, [None], [None],
                                      np.float32)
    _, pad, q, *_ = unpack(jnp.asarray(buf), layout)
    _, pad_ref, vmask = _dense_panes(np.ones(m), quotas)
    assert np.array_equal(np.asarray(pad) > 0, vmask)
    assert _bits(pad) == _bits(pad_ref.astype(np.float32))
    assert np.array_equal(np.asarray(q), quotas)


def test_a_blocks_row_holds_its_samples_and_quota(rng):
    """Zeroing block rows of the buffer drops exactly those blocks: their
    values, masks, codes, quotas and so their pad, and nothing of the
    other blocks."""
    quotas = np.array([5, 3, 17, 1, 32, 9], dtype=np.int64)
    m = int(quotas.sum())
    vals = rng.normal(MU, SIGMA, m)
    key_gids, key_valids = _keys(rng, m, 11)
    buf, layout, _, _ = _packed_panes(vals, quotas, key_gids, key_valids,
                                      np.float32)
    assert buf.shape[0] == quotas.size
    kept = np.arange(quotas.size) < quotas.size // 2
    whole = unpack(jnp.asarray(buf), layout)
    half = unpack(jnp.asarray(buf * kept[:, None]), layout)
    for got, want in zip(jax.tree_util.tree_leaves(half),
                         jax.tree_util.tree_leaves(whole)):
        got, want = np.asarray(got), np.asarray(want)
        assert np.array_equal(got[kept], want[kept])
        assert not got[~kept].any()


def _hetero_stores(n_blocks, n_groups, has_sketch=False):
    """Two grouped keys with different anchors: different cuts, sketches,
    shifts and so scales (a non-uniform ``key_affine``)."""
    anchors = [(make_boundaries(MU, SIGMA, PARAMS), MU, 0.0),
               (make_boundaries(5.0, 1.5 * SIGMA, PARAMS), 5.0, -MU)]
    host = [MomentStore.fresh(n_blocks, bd, sk, shift=sh,
                              n_groups=n_groups, has_sketch=has_sketch)
            for bd, sk, sh in anchors]
    dev = [DeviceMomentStore.fresh_device(
        n_blocks, bd, sk, [10 ** 6] * n_blocks, shift=sh,
        n_groups=n_groups, has_sketch=has_sketch)
        for bd, sk, sh in anchors]
    return host, dev, DeviceStack(dev)


def _tick_both(rng, host, stack, quotas, n_groups):
    # Quarter-offset integers: exact under every shift (so the host hashes
    # the same raw bits) and never on an anchor's (integer) cut.
    raw = np.round(rng.normal(MU, SIGMA, int(quotas.sum()))) + 0.25
    bids = np.repeat(np.arange(quotas.size), quotas)
    gids = rng.integers(0, n_groups, raw.size)
    masks = [rng.random(raw.size) < 0.6, rng.random(raw.size) < 0.4]
    for st, mask in zip(host, masks):
        st.ingest(raw + st.shift, bids, quotas, group_ids=gids, mask=mask)
    return stack.tick(PARAMS, values=raw, quotas=quotas,
                      dense=([gids, gids], masks))


def _tick_quotas(n_blocks, pruned):
    quotas = np.array([37 + 13 * b for b in range(n_blocks)],
                      dtype=np.int64)
    quotas[1] = 0
    if pruned:
        quotas[n_blocks // 2:] = 0
    return quotas


@pytest.mark.parametrize("pruned", [False, True])
def test_hetero_grouped_masked_stack_matches_host(rng, pruned):
    n_blocks, n_groups = 20, 3
    host, dev, stack = _hetero_stores(n_blocks, n_groups)
    assert not stack._uniform
    assert stack._key_affine[0] != stack._key_affine[1]
    for _ in range(2):
        quotas = _tick_quotas(n_blocks, pruned)
        out = _tick_both(rng, host, stack, quotas, n_groups)
    assert bool(stack._active_cache) is pruned   # compaction engaged
    for h, d, (_, rows) in zip(host, dev, out):
        dh = d.to_host()
        np.testing.assert_allclose(dh.mom_s, h.mom_s, rtol=5e-6, atol=1e-3)
        np.testing.assert_allclose(dh.mom_l, h.mom_l, rtol=5e-6, atol=1e-3)
        np.testing.assert_allclose(dh.totals, h.totals, rtol=5e-6,
                                   atol=1e-3)
        assert np.array_equal(dh.n_sampled, h.n_sampled)
        # Group rows: matched count and plain sums per group, in the
        # store's scaled units (fp32 sums of a centered stream cancel, so
        # the sum of values keeps the moments' absolute tolerance).
        rows = np.asarray(rows)
        per = h.totals.reshape(n_groups, n_blocks, 3).sum(axis=1)
        assert np.array_equal(rows[:, 0], per[:, 0])
        np.testing.assert_allclose(rows[:, 4], per[:, 1] / d.scale,
                                   rtol=1e-5, atol=1e-3)
        np.testing.assert_allclose(rows[:, 5], per[:, 2] / d.scale ** 2,
                                   rtol=1e-5)
        np.testing.assert_allclose(
            d.partials_host(), h.solve(PARAMS, mode="calibrated").avg,
            rtol=2e-4, atol=1e-3 * d.scale)


@pytest.mark.parametrize("pruned", [False, True])
def test_hetero_grouped_masked_sketch_stack_matches_host(rng, pruned):
    n_blocks, n_groups = 20, 3
    host, dev, stack = _hetero_stores(n_blocks, n_groups, has_sketch=True)
    for _ in range(2):
        _tick_both(rng, host, stack, _tick_quotas(n_blocks, pruned),
                   n_groups)
    for h, d in zip(host, dev):
        assert np.array_equal(np.asarray(d.regs), h.regs)
        assert np.array_equal(d.group_registers(), h.group_registers())
        assert d.group_registers().shape == (n_groups, SK.M)
