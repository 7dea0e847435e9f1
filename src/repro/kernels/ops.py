"""jit'd public wrappers around the Pallas kernels.

Handles: flattening/padding to the (rows, 128) tile layout, the N-region
padding trick (pad values land strictly inside N so they are invisible to
both masks), and a pure-jnp path for shapes too small to tile.  The caller
says how the kernel runs: ``interpret=False`` compiles it for the TPU,
``interpret=True`` executes the same body in the Pallas interpreter (the
CPU test tier).  Nothing here looks at the backend.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from . import ref
from .isla_moments import (DEFAULT_TM, LANE, isla_moments_pallas,
                           pilot_stats_pallas)


def _pad_to_tiles(v: jnp.ndarray, tm: int, pad_value) -> jnp.ndarray:
    """Flatten and pad to (k * tm, 128)."""
    flat = v.reshape(-1)
    n = flat.shape[0]
    per_tile = tm * LANE
    padded = ((n + per_tile - 1) // per_tile) * per_tile
    flat = jnp.pad(flat, (0, padded - n), constant_values=pad_value)
    return flat.reshape(-1, LANE)


@functools.partial(jax.jit, static_argnames=("tm", "stride", "interpret"))
def isla_moments(values: jnp.ndarray, bounds: jnp.ndarray,
                 tm: int = DEFAULT_TM, stride: int = 1, *,
                 interpret: bool) -> jnp.ndarray:
    """ISLA Phase-1 moments of an arbitrary-shaped value tensor.

    bounds: (4,) = (s_lo, s_hi, l_lo, l_hi).  Returns (2, 4) fp32:
    rows (S, L) x cols (count, s1, s2, s3).

    stride > 1 = fused tile sampling: only every stride-th tile is read
    (sampling rate 1/stride), the kernel's HBM traffic drops accordingly.
    """
    n = values.size
    if n < tm * LANE:  # too small to tile — jnp path (same contract)
        return ref.isla_moments_ref(values, bounds[0], bounds[1], bounds[2],
                                    bounds[3])
    pad = (bounds[1] + bounds[2]) * 0.5  # strictly inside N
    v2d = _pad_to_tiles(values, tm, pad)
    return isla_moments_pallas(v2d, bounds, tm=tm, stride=stride,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def pilot_stats(values: jnp.ndarray, tm: int = DEFAULT_TM, *,
                interpret: bool) -> jnp.ndarray:
    """(count, sum, sumsq, min) of a value tensor (fp32).

    NOTE: padding uses the first element so min() stays honest; count/sum are
    corrected for the pad afterwards.
    """
    n = values.size
    if n < tm * LANE:
        return ref.pilot_stats_ref(values)
    flat = values.reshape(-1)
    first = flat[0]
    v2d = _pad_to_tiles(flat, tm, 0.0)
    # overwrite zero-padding correction: count/sum/sumsq of pads are zero
    # already (pad=0), min needs guarding: replace pads with first element.
    per_tile = tm * LANE
    padded = v2d.size
    n_pad = padded - n
    stats = pilot_stats_pallas(
        jnp.where(
            (jnp.arange(padded).reshape(-1, LANE) < n), v2d,
            first.astype(v2d.dtype)),
        tm=tm, interpret=interpret)
    # count includes pads (they were counted as elements): subtract; sum/sumsq
    # include n_pad copies of `first`: subtract.
    f32 = jnp.float32
    first32 = first.astype(f32)
    return jnp.stack([
        stats[0] - f32(n_pad),
        stats[1] - f32(n_pad) * first32,
        stats[2] - f32(n_pad) * first32 * first32,
        stats[3],
    ])


def moments_split(m: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(2,4) -> (mom_S, mom_L) 4-vectors for core.distributed.phase2."""
    return m[0], m[1]
