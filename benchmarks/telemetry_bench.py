"""Framework-level benchmarks: ISLA telemetry vs exact reduction, and the
Pallas Phase-1 kernel (interpret mode on CPU — correctness-grade timing; the
collective-payload numbers are exact and platform-independent).
"""
from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import exact_mean, isla_mean
from repro.core.types import IslaParams

Row = Tuple[str, float, float]


def _time_jit(fn, *args, iters=20) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def telemetry_collective_payload() -> List[Row]:
    """Collective payload of the loss-stats aggregation across a mesh:
    exact mean needs a full-width reduction of B*S values; ISLA psums
    13 floats.  derived = payload ratio (exact / isla)."""
    rows: List[Row] = []
    for (bsz, seq) in [(256, 4096), (32, 32768)]:
        exact_bytes = 4 * 2  # (sum, n) — exact mean after local reduce
        exact_full = bsz * seq * 4  # naive all-gather of per-token losses
        isla_bytes = (3 + 10) * 4
        rows.append((f"telemetry/payload_ratio_gather_B{bsz}xS{seq}",
                     0.0, exact_full / isla_bytes))
        rows.append((f"telemetry/payload_ratio_reduced_B{bsz}xS{seq}",
                     0.0, exact_bytes / isla_bytes))
    return rows


def telemetry_accuracy_speed() -> List[Row]:
    """Wall time + accuracy of isla_mean vs exact_mean on one device."""
    rng = np.random.default_rng(0)
    p = IslaParams(e=0.01)
    x = jnp.asarray(rng.normal(5.5, 1.5, size=(256, 4096)), jnp.float32)
    f_isla = jax.jit(lambda v: isla_mean(v, p, rate=0.02))
    f_exact = jax.jit(exact_mean)
    t_isla = _time_jit(f_isla, x)
    t_exact = _time_jit(f_exact, x)
    err = abs(float(f_isla(x)) - float(f_exact(x)))
    return [
        ("telemetry/isla_mean_us", t_isla, err),
        ("telemetry/exact_mean_us", t_exact, 0.0),
    ]


def kernel_bench() -> List[Row]:
    """isla_moments Pallas kernel (interpret mode) vs jnp reference —
    derived = max abs rel error vs oracle."""
    from repro.kernels import ops, ref
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(100, 20, size=(512, 128)), jnp.float32)
    bounds = jnp.asarray([60., 90., 110., 140.], jnp.float32)
    got = ops.isla_moments(x, bounds, tm=64, interpret=True)
    want = ref.isla_moments_ref(x, 60., 90., 110., 140.)
    rel = float(jnp.max(jnp.abs(got - want) / (jnp.abs(want) + 1e-9)))
    t = _time_jit(lambda v: ops.isla_moments(v, bounds, tm=64,
                                               interpret=True), x, iters=5)
    return [("kernel/isla_moments_interp_us", t, rel)]
