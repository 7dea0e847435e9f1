"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles
(interpret=True executes the kernel body on CPU)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.isla_moments import (isla_moments_batched_pallas,
                                        isla_moments_grouped_pallas,
                                        isla_moments_pallas,
                                        pilot_stats_pallas)

BOUNDS = (60.0, 90.0, 110.0, 140.0)
BOUNDS_ARR = jnp.asarray(BOUNDS, jnp.float32)


@pytest.mark.parametrize("shape", [(64, 128), (256, 128), (64 * 7, 128),
                                   (512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moments_kernel_sweep(shape, dtype, rng):
    x = jnp.asarray(rng.normal(100, 20, size=shape), dtype)
    got = isla_moments_pallas(x, BOUNDS_ARR, tm=64, interpret=True)
    want = ref.isla_moments_ref(x, *BOUNDS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-3 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("stride", [1, 2, 4])
def test_moments_kernel_strided(stride, rng):
    x = jnp.asarray(rng.normal(100, 20, size=(64 * 8, 128)), jnp.float32)
    got = isla_moments_pallas(x, BOUNDS_ARR, tm=64, stride=stride,
                              interpret=True)
    sel = x.reshape(8, 64, 128)[::stride].reshape(-1, 128)
    want = ref.isla_moments_ref(sel, *BOUNDS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize("n", [100, 8192, 64 * 128 + 17, 200_000])
def test_ops_isla_moments_any_shape(n, rng):
    """ops wrapper: arbitrary sizes via N-region padding; == oracle."""
    x = jnp.asarray(rng.normal(100, 20, size=(n,)), jnp.float32)
    got = ops.isla_moments(x, BOUNDS_ARR, tm=64, interpret=True)
    want = ref.isla_moments_ref(x, *BOUNDS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4)


@pytest.mark.parametrize("n_blocks", [1, 3, 8])
def test_moments_batched_kernel(n_blocks, rng):
    """Batched multi-block kernel == per-block kernel == oracle."""
    x = jnp.asarray(rng.normal(100, 20, size=(n_blocks, 64 * 3, 128)),
                    jnp.float32)
    got = isla_moments_batched_pallas(x, BOUNDS_ARR, tm=64, interpret=True)
    assert got.shape == (n_blocks, 2, 4)
    for b in range(n_blocks):
        want = ref.isla_moments_ref(x[b], *BOUNDS)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want),
                                   rtol=1e-5)


@pytest.mark.parametrize("stride", [2, 4])
def test_moments_batched_kernel_strided(stride, rng):
    x = jnp.asarray(rng.normal(100, 20, size=(4, 64 * 8, 128)), jnp.float32)
    got = isla_moments_batched_pallas(x, BOUNDS_ARR, tm=64, stride=stride,
                                      interpret=True)
    for b in range(4):
        sel = x[b].reshape(8, 64, 128)[::stride].reshape(-1, 128)
        want = ref.isla_moments_ref(sel, *BOUNDS)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want),
                                   rtol=1e-5)


def test_batched_kernel_feeds_batched_phase2(rng):
    """(n, 2, 4) kernel moments flow straight into the stacked jnp Phase 2 —
    the device route of the multi-query executor."""
    from repro.core.distributed import phase2
    from repro.core.types import IslaParams
    params = IslaParams()
    x = jnp.asarray(rng.normal(100, 20, size=(5, 64 * 4, 128)), jnp.float32)
    mom = isla_moments_batched_pallas(x, BOUNDS_ARR, tm=64, interpret=True)
    avgs = phase2(mom[:, 0], mom[:, 1], jnp.float32(100.0), params,
                  mode="calibrated")
    assert avgs.shape == (5,)
    for b in range(5):
        one = phase2(mom[b, 0], mom[b, 1], jnp.float32(100.0), params,
                     mode="calibrated")
        assert float(avgs[b]) == pytest.approx(float(one), rel=1e-6)


def test_moments_grouped_kernel(rng):
    """(group, block) kernel == per-cell oracle, and its output reshapes
    straight onto the stacked Phase 2 (the relational device route)."""
    from repro.core.distributed import phase2
    from repro.core.types import IslaParams
    x = jnp.asarray(rng.normal(100, 20, size=(3, 4, 64 * 2, 128)),
                    jnp.float32)
    got = isla_moments_grouped_pallas(x, BOUNDS_ARR, tm=64, interpret=True)
    assert got.shape == (3, 4, 2, 4)
    for g in range(3):
        for b in range(4):
            want = ref.isla_moments_ref(x[g, b], *BOUNDS)
            np.testing.assert_allclose(np.asarray(got[g, b]),
                                       np.asarray(want), rtol=1e-5)
    avgs = phase2(got[..., 0, :], got[..., 1, :], jnp.float32(100.0),
                  IslaParams(), mode="calibrated")
    assert avgs.shape == (3, 4)
    with pytest.raises(ValueError, match="n_groups"):
        isla_moments_grouped_pallas(x[0], BOUNDS_ARR, tm=64,
                                    interpret=True)


def test_moments_kernel_prior_accumulator(rng):
    """The prior operand seeds the accumulator: two rounds through the
    kernel == one kernel pass over the concatenated data (§VII-A merge on
    device)."""
    x1 = jnp.asarray(rng.normal(100, 20, size=(64 * 2, 128)), jnp.float32)
    x2 = jnp.asarray(rng.normal(100, 20, size=(64 * 3, 128)), jnp.float32)
    round1 = isla_moments_pallas(x1, BOUNDS_ARR, tm=64, interpret=True)
    merged = isla_moments_pallas(x2, BOUNDS_ARR, tm=64, interpret=True,
                                 prior=round1)
    whole = isla_moments_pallas(jnp.concatenate([x1, x2]), BOUNDS_ARR,
                                tm=64, interpret=True)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(whole),
                               rtol=1e-6)


def test_moments_batched_kernel_prior_accumulator(rng):
    """Per-block prior cells merge independently on the batched route."""
    x1 = jnp.asarray(rng.normal(100, 20, size=(3, 64 * 2, 128)),
                     jnp.float32)
    x2 = jnp.asarray(rng.normal(100, 20, size=(3, 64 * 2, 128)),
                     jnp.float32)
    round1 = isla_moments_batched_pallas(x1, BOUNDS_ARR, tm=64,
                                         interpret=True)
    merged = isla_moments_batched_pallas(x2, BOUNDS_ARR, tm=64,
                                         interpret=True, prior=round1)
    whole = isla_moments_batched_pallas(
        jnp.concatenate([x1, x2], axis=1), BOUNDS_ARR, tm=64,
        interpret=True)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(whole),
                               rtol=1e-6)
    with pytest.raises(ValueError, match="prior"):
        isla_moments_batched_pallas(x2, BOUNDS_ARR, tm=64, interpret=True,
                                    prior=round1[:2])


def test_moments_grouped_kernel_prior_accumulator(rng):
    x = jnp.asarray(rng.normal(100, 20, size=(2, 3, 64, 128)), jnp.float32)
    round1 = isla_moments_grouped_pallas(x, BOUNDS_ARR, tm=64,
                                         interpret=True)
    merged = isla_moments_grouped_pallas(x, BOUNDS_ARR, tm=64,
                                         interpret=True, prior=round1)
    np.testing.assert_allclose(np.asarray(merged), 2 * np.asarray(round1),
                               rtol=1e-6)


def test_pilot_stats_kernel(rng):
    x = jnp.asarray(rng.normal(100, 20, size=(256, 128)), jnp.float32)
    got = pilot_stats_pallas(x, tm=64, interpret=True)
    want = ref.pilot_stats_ref(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize("n", [500, 64 * 128 * 3 + 5])
def test_ops_pilot_stats_padding_correction(n, rng):
    x = jnp.asarray(rng.normal(-5, 3, size=(n,)), jnp.float32)
    got = ops.pilot_stats(x, tm=64, interpret=True)
    want = ref.pilot_stats_ref(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-2)


def test_kernel_feeds_phase2(rng):
    """Kernel moments plug into the distributed phase 2 and give the same
    answer as the host engine on the same data."""
    from repro.core.distributed import phase2
    from repro.core.engine import phase2_iteration
    from repro.core.types import Boundaries, IslaParams, RegionMoments
    params = IslaParams()
    vals = rng.normal(100, 20, size=(64 * 128 * 4,))
    x = jnp.asarray(vals, jnp.float32)
    mom = ops.isla_moments(x, BOUNDS_ARR, tm=64, interpret=True)
    dev_avg = float(phase2(mom[0], mom[1], jnp.float32(100.0), params,
                           mode="calibrated"))
    b = Boundaries(*BOUNDS)
    from repro.core.engine import phase1_sampling
    ps, pl = phase1_sampling(vals, b)
    host = phase2_iteration(ps, pl, 100.0, params, mode="calibrated")
    assert dev_avg == pytest.approx(host.avg, rel=1e-4)


def test_grouped_kernel_prior_ragged_cells(rng):
    """Prior operand on ragged shapes: G*B = 15 cells (not a multiple of
    any tile/lane width) each merge their own prior cell, including an
    all-zero prior row (a cold cell merged into a warm launch)."""
    g_n, b_n = 3, 5
    x1 = jnp.asarray(rng.normal(100, 20, size=(g_n, b_n, 64 * 2, 128)),
                     jnp.float32)
    x2 = jnp.asarray(rng.normal(100, 20, size=(g_n, b_n, 64 * 3, 128)),
                     jnp.float32)
    round1 = isla_moments_grouped_pallas(x1, BOUNDS_ARR, tm=64,
                                         interpret=True)
    # Cold cell inside a warm launch: zero out one prior row entirely.
    prior = np.asarray(round1).copy()
    prior[1, 2] = 0.0
    merged = isla_moments_grouped_pallas(
        x2, BOUNDS_ARR, tm=64, interpret=True,
        prior=jnp.asarray(prior))
    whole = isla_moments_grouped_pallas(
        jnp.concatenate([x1, x2], axis=2), BOUNDS_ARR, tm=64,
        interpret=True)
    for g in range(g_n):
        for b in range(b_n):
            if (g, b) == (1, 2):
                # The zeroed cell must equal x2's moments alone.
                want = ref.isla_moments_ref(x2[g, b], *BOUNDS)
            else:
                want = whole[g, b]
            np.testing.assert_allclose(np.asarray(merged[g, b]),
                                       np.asarray(want), rtol=1e-5)


def test_grouped_kernel_prior_shape_guard(rng):
    x = jnp.asarray(rng.normal(100, 20, size=(2, 3, 64, 128)),
                    jnp.float32)
    with pytest.raises(ValueError, match="prior"):
        isla_moments_grouped_pallas(x, BOUNDS_ARR, tm=64, interpret=True,
                                    prior=jnp.zeros((3, 2, 2, 4)))


def test_fused_pallas_one_launch_matches_split(rng):
    """isla_fused_pallas == (batched moments kernel + branchless Phase 2)
    with the prior merged — and the donated prior is consumed."""
    from repro.core.distributed import phase2
    from repro.core.types import IslaParams
    from repro.kernels.isla_moments import isla_fused_pallas

    params = IslaParams()
    cells = 7  # not a multiple of any tile width
    x = jnp.asarray(rng.normal(100, 20, size=(cells, 64 * 3, 128)),
                    jnp.float32)
    prior = jnp.asarray(rng.uniform(0, 10, size=(cells, 2, 4)),
                        jnp.float32)
    prior_copy = jnp.array(prior)
    mom, partials = isla_fused_pallas(x, BOUNDS_ARR, prior,
                                      jnp.float32(100.0), params,
                                      tm=64, interpret=True)
    want = isla_moments_batched_pallas(x, BOUNDS_ARR, tm=64,
                                       interpret=True, prior=prior_copy)
    np.testing.assert_allclose(np.asarray(mom), np.asarray(want),
                               rtol=1e-6)
    want_p = phase2(want[:, 0], want[:, 1], jnp.float32(100.0), params,
                    mode="calibrated")
    np.testing.assert_allclose(np.asarray(partials), np.asarray(want_p),
                               rtol=1e-6)
    assert prior.is_deleted()  # donated: the launch was in-place


@pytest.mark.parametrize("n_blocks", [1, 4])
def test_moments_batched_kernel_per_cell_bounds(n_blocks, rng):
    """(n_blocks, 4) bounds: every cell classifies under its OWN anchor
    cuts (the per-key refined-anchor launch) == per-block oracle runs."""
    x = jnp.asarray(rng.normal(100, 20, size=(n_blocks, 64 * 2, 128)),
                    jnp.float32)
    rows = np.stack([np.asarray(BOUNDS) + 7.0 * b
                     for b in range(n_blocks)])
    got = isla_moments_batched_pallas(x, jnp.asarray(rows, jnp.float32),
                                      tm=64, interpret=True)
    for b in range(n_blocks):
        want = ref.isla_moments_ref(x[b], *rows[b])
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want),
                                   rtol=1e-5)
    with pytest.raises(ValueError, match="per-cell bounds"):
        isla_moments_batched_pallas(x, jnp.zeros((n_blocks + 1, 4)),
                                    tm=64, interpret=True)


def test_fused_pallas_per_cell_bounds_and_inv_scale(rng):
    """The fused kernel under hetero anchors: per-cell bounds rows plus
    the inv_scale vector scaling the stopping threshold per cell — each
    cell's partial equals a standalone phase2 run in that cell's frame."""
    from repro.core.distributed import phase2
    from repro.core.types import IslaParams
    from repro.kernels.isla_moments import isla_fused_pallas

    params = IslaParams()
    cells = 3
    scales = np.array([1.0, 2.0, 4.0], dtype=np.float32)
    x = jnp.asarray(rng.normal(100, 20, size=(cells, 64 * 2, 128)),
                    jnp.float32) / scales[:, None, None]
    rows = jnp.asarray(np.asarray(BOUNDS)[None, :] / scales[:, None],
                       jnp.float32)
    sk = jnp.asarray(100.0 / scales, jnp.float32)
    inv = jnp.asarray(1.0 / scales, jnp.float32)
    mom, partials = isla_fused_pallas(
        x, rows, jnp.zeros((cells, 2, 4), jnp.float32), sk, params,
        tm=64, interpret=True, inv_scale=inv)
    for c in range(cells):
        want_m = ref.isla_moments_ref(x[c], *np.asarray(rows[c]))
        np.testing.assert_allclose(np.asarray(mom[c]),
                                   np.asarray(want_m), rtol=1e-5)
        want_p = phase2(mom[c, 0], mom[c, 1], sk[c],
                        params.replace(thr=params.thr / float(scales[c])),
                        mode="calibrated")
        np.testing.assert_allclose(np.asarray(partials[c]),
                                   np.asarray(want_p), rtol=1e-5)


def _host_regs(vals_per_cell):
    """One-pass host register plane for a list of per-cell value arrays."""
    from repro.core import sketch as SK
    regs = np.zeros((len(vals_per_cell), SK.M), np.uint8)
    for c, v in enumerate(vals_per_cell):
        j, rho = SK.encode(SK.hash_values(np.asarray(v, np.float64)))
        SK.scatter_max(regs, np.full(len(v), c), j, rho)
    return regs


def test_sketch_kernel_matches_host_twin_and_merges(rng):
    """The HLL scatter kernel: bit-identical to the host numpy twin on a
    masked pane, and two prior-seeded rounds fold to the one-pass plane
    (merge = elementwise max inside the launch)."""
    from repro.core import sketch as SK
    from repro.kernels.isla_moments import (LANE, REG_ROWS,
                                            isla_sketch_pallas)

    n_cells, rows = 3, 256
    vals = np.round(rng.normal(0, 50, (n_cells, rows * LANE)))
    valid = rng.random((n_cells, rows * LANE)) < 0.9
    host = _host_regs([vals[c][valid[c]] for c in range(n_cells)])

    hi, lo = SK.value_limbs(vals.reshape(-1))
    hi3 = jnp.asarray(hi.reshape(n_cells, rows, LANE))
    lo3 = jnp.asarray(lo.reshape(n_cells, rows, LANE))
    v3 = jnp.asarray(valid.reshape(n_cells, rows, LANE).astype(np.uint32))
    got = isla_sketch_pallas(hi3, lo3, v3, tm=64, interpret=True)
    assert got.shape == (n_cells, REG_ROWS, LANE) and got.dtype == jnp.uint8
    assert np.array_equal(np.asarray(got).reshape(n_cells, SK.M), host)

    half = rows // 2
    r1 = isla_sketch_pallas(hi3[:, :half], lo3[:, :half], v3[:, :half],
                            tm=64, interpret=True)
    r2 = isla_sketch_pallas(hi3[:, half:], lo3[:, half:], v3[:, half:],
                            tm=64, interpret=True, prior=r1)
    assert np.array_equal(np.asarray(r2), np.asarray(got))


def test_fused_sketch_kernel_rides_the_launch_unchanged(rng):
    """The fused moments+sketch kernel returns the plain fused kernel's
    exact moments and phase-2 partials (the register pane must not
    perturb the fp32 pipeline) while its uint8 registers match the host
    twin bit for bit."""
    from repro.core import sketch as SK
    from repro.core.types import IslaParams
    from repro.kernels.isla_moments import (LANE, isla_fused_pallas,
                                            isla_fused_sketch_pallas)

    params = IslaParams(e=0.5)
    n_cells, rows = 2, 128
    vals = np.round(rng.normal(100, 20, (n_cells, rows, LANE)))
    prior = jnp.zeros((n_cells, 2, 4), jnp.float32)
    prior_regs = jnp.zeros((n_cells, 32, LANE), jnp.uint8)
    hi, lo = SK.value_limbs(vals.reshape(-1))
    hi3 = jnp.asarray(hi.reshape(n_cells, rows, LANE))
    lo3 = jnp.asarray(lo.reshape(n_cells, rows, LANE))
    v3 = jnp.ones((n_cells, rows, LANE), jnp.uint32)
    mom, regs, partials = isla_fused_sketch_pallas(
        jnp.asarray(vals, jnp.float32), BOUNDS_ARR, prior, prior_regs,
        hi3, lo3, v3, jnp.float32(100.0), params, tm=64, interpret=True)
    mom2, partials2 = isla_fused_pallas(
        jnp.asarray(vals, jnp.float32), BOUNDS_ARR,
        jnp.zeros((n_cells, 2, 4), jnp.float32), jnp.float32(100.0),
        params, tm=64, interpret=True)
    assert np.array_equal(np.asarray(mom), np.asarray(mom2))
    assert np.array_equal(np.asarray(partials), np.asarray(partials2))
    host = _host_regs([vals[c].reshape(-1) for c in range(n_cells)])
    assert np.array_equal(np.asarray(regs).reshape(n_cells, SK.M), host)
