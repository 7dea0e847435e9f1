"""Device-side ISLA: phase2 parity with host, isla_mean under shard_map,
O(1) moment communication."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.boundaries import choose_q as choose_q_host
from repro.core.boundaries import deviation_degree
from repro.core.distributed import (choose_q, exact_mean, isla_mean, moments,
                                    phase2, subsample, theorem3_kc)
from repro.core.engine import phase2_iteration
from repro.core.estimator import moments_from_values
from repro.core.estimator import theorem3_kc as t3_host
from repro.core.types import IslaParams, RegionMoments

P = IslaParams()


def _mom_pair(rng, u, v):
    xs = rng.uniform(0.5, 0.9, size=u)
    ys = rng.uniform(1.1, 1.5, size=v)
    return xs, ys


@pytest.mark.parametrize("mode", ["faithful", "calibrated"])
def test_phase2_matches_host(mode, rng):
    for trial in range(10):
        u = int(rng.integers(5, 200))
        v = int(rng.integers(5, 200))
        xs, ys = _mom_pair(rng, u, v)
        ms = moments_from_values(xs)
        ml = moments_from_values(ys)
        host = phase2_iteration(ms, ml, 1.0, P,
                                mode="faithful_cf" if mode == "faithful"
                                else mode)
        mS = jnp.array([ms.count, ms.s1, ms.s2, ms.s3], jnp.float32)
        mL = jnp.array([ml.count, ml.s1, ml.s2, ml.s3], jnp.float32)
        dev = float(phase2(mS, mL, jnp.float32(1.0), P, mode=mode))
        assert dev == pytest.approx(host.avg, rel=2e-4), \
            f"trial {trial} (u={u}, v={v})"


@pytest.mark.parametrize("mode", ["faithful", "calibrated"])
def test_phase2_single_sample_regions_match_host(mode, rng):
    """One sample in S and one in L: k is identically zero, so the host
    answers c = (x + y) / 2 — faithful's balanced case included, since
    the k guard takes precedence.  The fp32 device must agree on every
    such cell, not only where its rounding noise happens to cancel."""
    x = rng.uniform(0.5, 0.9, size=400)
    y = rng.uniform(1.1, 1.5, size=400)
    ms = np.stack([np.ones_like(x), x, x * x, x * x * x], axis=1)
    ml = np.stack([np.ones_like(y), y, y * y, y * y * y], axis=1)
    from repro.core.engine import phase2_iteration_batch
    host = phase2_iteration_batch(ms, ml, 1.0, P,
                                  mode="faithful_cf" if mode == "faithful"
                                  else mode).avg
    np.testing.assert_allclose(host, (x + y) / 2, rtol=1e-12)
    dev = phase2(jnp.asarray(ms, jnp.float32), jnp.asarray(ml, jnp.float32),
                 jnp.float32(1.0), P, mode=mode)
    np.testing.assert_allclose(np.asarray(dev), host, rtol=1e-6)


def test_choose_q_matches_host():
    for dev_val in [0.5, 0.95, 0.98, 1.0, 1.02, 1.05, 2.0]:
        got = float(choose_q(jnp.float32(dev_val), P))
        want = choose_q_host(dev_val, P)
        assert got == pytest.approx(want)


def test_moments_match_engine(rng):
    from repro.core.engine import phase1_sampling
    from repro.core.types import Boundaries
    vals = rng.normal(100, 20, size=5000)
    bounds = (60.0, 90.0, 110.0, 140.0)
    mS, mL = moments(jnp.asarray(vals, jnp.float32), bounds)
    ps, pl = phase1_sampling(vals, Boundaries(*bounds))
    assert float(mS[0]) == ps.count and float(mL[0]) == pl.count
    assert float(mS[3]) == pytest.approx(ps.s3, rel=1e-4)


def test_moments_prior_merges_rounds(rng):
    """The accumulator operand: moments(round2, prior=round1) == moments of
    the concatenated stream (device-side §VII-A continuation), and the
    merged vectors feed phase2 unchanged."""
    bounds = (60.0, 90.0, 110.0, 140.0)
    v1 = jnp.asarray(rng.normal(100, 20, size=3000), jnp.float32)
    v2 = jnp.asarray(rng.normal(100, 20, size=5000), jnp.float32)
    r1 = moments(v1, bounds)
    mS, mL = moments(v2, bounds, prior=r1)
    wS, wL = moments(jnp.concatenate([v1, v2]), bounds)
    np.testing.assert_allclose(np.asarray(mS), np.asarray(wS), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(mL), np.asarray(wL), rtol=1e-5)
    merged = float(phase2(mS, mL, jnp.float32(100.0), P, mode="calibrated"))
    whole = float(phase2(wS, wL, jnp.float32(100.0), P, mode="calibrated"))
    assert merged == pytest.approx(whole, rel=1e-5)


def test_isla_mean_jit_accuracy(rng):
    x = jnp.asarray(rng.normal(100, 20, size=(512, 512)), jnp.float32)
    got = float(jax.jit(lambda v: isla_mean(v, P, rate=0.1))(x))
    assert got == pytest.approx(float(x.mean()), abs=0.5)


def test_exact_mean(rng):
    x = jnp.asarray(rng.normal(3.0, 1.0, size=(100, 7)), jnp.float32)
    assert float(exact_mean(x)) == pytest.approx(float(x.mean()), rel=1e-5)


def test_subsample_rate():
    x = jnp.arange(10000, dtype=jnp.float32)
    s = subsample(x, 0.05)
    assert abs(s.shape[0] - 500) <= 1
    s2 = subsample(x, 0.05, key=jax.random.key(0))
    assert abs(s2.shape[0] - 500) <= 1


def test_scale_invariance_distributed(rng):
    """isla_mean(s*x) == s*isla_mean(x) (exact equivariance, fp32 lever)."""
    x = jnp.asarray(rng.normal(10, 2, size=(64, 256)), jnp.float32)
    a = float(isla_mean(x, P, rate=0.2))
    b = float(isla_mean(x * 1000.0, P, rate=0.2))
    assert b == pytest.approx(a * 1000.0, rel=1e-3)
