"""Distributed / jit-safe ISLA.

This is the device-side mirror of ``engine.py``: everything is branchless
(jnp.where over the modulation cases), fp32-safe (values are pre-scaled by a
static normalizer; ISLA is exactly scale-equivariant), and communication is
O(1): a block's entire contribution is a 10-float vector.

Two aggregation semantics, both faithful to the paper:
 * "blocks"  — each device is a block: local Phase 1 + Phase 2, then the
               Summarization psum of (avg * n, n)  (paper §II-B).
 * "merged"  — moments are psum'd first, one global Phase 2 (the online/
               continuation view: all devices form one block).

``isla_mean`` is the drop-in for "mean of a big distributed tensor" telemetry:
it samples its input at ``rate``, so the HBM traffic is rate-proportional and
the collective payload is constant.
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .types import IslaParams

# ---------------------------------------------------------------------------
# Phase 1: classification + moments (vectorized; the Pallas kernel in
# repro.kernels implements the same contract for the TPU hot path).
# ---------------------------------------------------------------------------


def region_masks(v: jnp.ndarray, b: Tuple) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """S and L masks per §IV-A1 (bounds as a (s_lo, s_hi, l_lo, l_hi) tuple)."""
    s_lo, s_hi, l_lo, l_hi = b
    ms = (v > s_lo) & (v < s_hi)
    ml = (v > l_lo) & (v < l_hi)
    return ms, ml


def moments(values: jnp.ndarray, bounds: Tuple, valid=None, prior=None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Masked (count, s1, s2, s3) for S and L as two 4-vectors (fp32).

    ``prior`` is the online-continuation accumulator operand: a previous
    round's ``(mom_s, mom_l)`` pair, merged into this round's sums on
    device (moments are additive — §VII-A; fp32 vector adds here, the
    bit-exact carry merge lives on the host ``MomentStore`` path).
    """
    v = values.astype(jnp.float32).reshape(-1)
    ms, ml = region_masks(v, bounds)
    if valid is not None:
        valid = valid.astype(bool).reshape(-1)
        ms, ml = ms & valid, ml & valid

    def mom(mask):
        m = mask.astype(jnp.float32)
        vm = v * m
        return jnp.stack([jnp.sum(m), jnp.sum(vm), jnp.sum(vm * v),
                          jnp.sum(vm * v * v)])

    mom_s, mom_l = mom(ms), mom(ml)
    if prior is not None:
        prior_s, prior_l = prior
        mom_s = mom_s + jnp.asarray(prior_s, jnp.float32)
        mom_l = mom_l + jnp.asarray(prior_l, jnp.float32)
    return mom_s, mom_l


# ---------------------------------------------------------------------------
# Phase 2 pieces (branchless).
# ---------------------------------------------------------------------------


def choose_q(dev: jnp.ndarray, params: IslaParams) -> jnp.ndarray:
    """§IV-A4 q schedule as nested where."""
    qp = jnp.where(
        (dev >= 0.97) & (dev <= 1.03), 1.0,
        jnp.where((dev >= params.mild_lo) & (dev <= params.mild_hi),
                  params.q_mild, params.q_strong))
    return jnp.where(dev > 1.0, 1.0 / qp, qp)


def theorem3_kc(mom_s: jnp.ndarray, mom_l: jnp.ndarray, q: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Closed-form (k, c) from moment vectors; safe for u=0 / v=0 (the
    caller masks those out).  Accepts a single (4,) vector or any stack of
    them (..., 4) — the batched multi-query route feeds (n_blocks, 4)."""
    u, sx, sx2, sx3 = (mom_s[..., 0], mom_s[..., 1], mom_s[..., 2],
                       mom_s[..., 3])
    v, sy, sy2, sy3 = (mom_l[..., 0], mom_l[..., 1], mom_l[..., 2],
                       mom_l[..., 3])
    eps = jnp.float32(1e-30)
    t2 = sx2 + sy2
    denom_s = (1.0 + v / (q * jnp.maximum(u, 1.0))) * (u * t2 - sx2)
    term_s = (t2 * sx - sx3) / jnp.maximum(denom_s, eps)
    term_l = v * sy3 / jnp.maximum((q * u + v) * sy2, eps)
    c = (sx + sy) / jnp.maximum(u + v, 1.0)
    k = term_s + term_l - c
    return k, c


def n_iterations(d0: jnp.ndarray, thr: float, eta: float) -> jnp.ndarray:
    ad = jnp.abs(d0)
    t = jnp.ceil(jnp.log(jnp.maximum(ad / thr, 1.0)) / jnp.log(1.0 / eta))
    return t.astype(jnp.float32)


def _lambda_star(p1: float, p2: float) -> float:
    from .modulation import lambda_star
    return lambda_star(p1, p2)


def phase2(mom_s: jnp.ndarray, mom_l: jnp.ndarray, sketch0: jnp.ndarray,
           params: IslaParams, mode: str = "calibrated",
           geometry=None, thr=None) -> jnp.ndarray:
    """Branchless Phase 2.  Returns the block's partial answer.

    Fully elementwise: feed one (4,) moment pair for a scalar answer, or
    any stacked (..., 4) pairs for a batch of partial answers in one call —
    the device route of ``multiquery.MultiQueryExecutor``.  The relational
    (group, block) moments axis rides this unchanged: segment id =
    ``group * n_blocks + block`` (``engine.flat_segments``) flattens onto
    the batch dim, so grouped/predicated cells cost the same one launch as
    plain blocks — feed (n_groups * n_blocks, 4) or (n_groups, n_blocks, 4)
    stacks, both work.

    mode="calibrated" — ISLA-C fixed point (geometry-correct lambda*).
    mode="empirical"  — ISLA-E: geometry=(kappa, b0) measured from the pilot.
    mode="faithful"   — §V-C case table, algebraic form (== host closed form).
    Falls back to sketch0 when u or v is 0, to c when k ~ 0.

    ``thr`` optionally overrides ``params.thr`` with an array broadcast
    against the cell axis — the per-cell stopping threshold of stacks
    whose cells run at different anchor scales (thr is ABSOLUTE on the
    value axis, so each cell's normalized frame needs its own).  The
    ISLA-E ``b0`` may likewise be per-cell.
    """
    eta, lam = params.eta, params.lam
    thr = params.thr if thr is None else thr
    u, v = mom_s[..., 0], mom_l[..., 0]
    q = choose_q(u / jnp.maximum(v, 1.0), params)
    k, c = theorem3_kc(mom_s, mom_l, q)
    # One sample in each region: dev = 1, so q = 1, and Theorem 3's k is
    # identically zero — the closed form cancels to (q x + y)/(q + 1) - c.
    # In float64 the cancellation leaves noise far under the 1e-12 guard;
    # in fp32 (and with a TPU's approximate division) it does not, so the
    # zero is set exactly, as the host finds it.
    k = jnp.where((u == 1.0) & (v == 1.0), 0.0, k)
    d0 = c - sketch0
    t = n_iterations(d0, thr, eta)
    total_shrink = (1.0 - eta ** t) * jnp.abs(d0)

    if mode == "empirical":
        kappa, b0 = geometry
        c_adj = c - b0
        d0 = c_adj - sketch0
        t = n_iterations(d0, thr, eta)
        shrink = (1.0 - eta ** t) * jnp.abs(d0)
        avg = c_adj - jnp.sign(d0) * kappa * shrink / (1.0 + kappa)
        balanced = jnp.zeros_like(d0, dtype=bool)
    elif mode == "calibrated":
        lam_c = _lambda_star(params.p1, params.p2)
        s_sk = total_shrink / (1.0 + lam_c)
        mu_move = -jnp.sign(d0) * lam_c * s_sk
        avg = c + mu_move
        balanced = jnp.zeros_like(d0, dtype=bool)  # calibrated always modulates
    elif mode == "faithful":
        sgn_k = jnp.where(k >= 0, 1.0, -1.0)
        case1 = (d0 < 0) & (u < v)
        case2 = (d0 < 0) & (u >= v)
        case3 = (d0 >= 0) & (u < v)
        # case4 = (d0 >= 0) & (u >= v)
        # mu-dominant cases (1/4): dmu = +-shrink/(1-lam)
        mu_dom_move = jnp.where(case1, total_shrink / (1.0 - lam),
                                -total_shrink / (1.0 - lam))
        # sketch-dominant cases (2/3): gain = |sgn_k*lam -+ (-1/+1)|
        gain2 = 1.0 + sgn_k * lam
        gain3 = 1.0 - sgn_k * lam
        sk_dom_move = jnp.where(case2,
                                sgn_k * lam * total_shrink / gain2,
                                sgn_k * lam * total_shrink / gain3)
        # cases 2/3 are sketch-dominant, cases 1/4 mu-dominant:
        mu_move = jnp.where(case2 | case3, sk_dom_move, mu_dom_move)
        avg = c + mu_move
        dev = u / jnp.maximum(v, 1.0)
        balanced = (dev > params.balanced_lo) & (dev < params.balanced_hi)
    else:
        raise ValueError(f"unknown mode {mode}")

    # Fallback precedence as in the host engine: empty region > k ~ 0 >
    # the faithful balanced case.
    avg = jnp.where(balanced, sketch0, avg)
    avg = jnp.where(jnp.abs(k) < 1e-12, c, avg)
    avg = jnp.where((u < params.min_region_count) |
                    (v < params.min_region_count), sketch0, avg)
    return avg


# ---------------------------------------------------------------------------
# Device-resident tick: tagged Phase 1 + totals + Phase 2 + group stats in
# ONE jitted launch, continuing from donated resident moment buffers.
# ---------------------------------------------------------------------------


def h2d(x, dtype=None) -> jnp.ndarray:
    """The single sanctioned host->device upload of the device-resident
    serving path.  Every array the steady-state tick ships to the device
    (fresh sample values and their segment tags — never moments) goes
    through here, so tests can count crossings and wrap the rest of the
    tick in a ``jax.transfer_guard("disallow")``.  Each call and its bytes
    are booked under the ``h2d_calls`` and ``h2d_bytes`` counters of the
    open stage's run (``count``)."""
    with jax.transfer_guard("allow"):
        out = jnp.asarray(x, dtype=dtype)
    count("h2d_calls", 1)
    count("h2d_bytes", out.nbytes)
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def cold_store(consts: jnp.ndarray, n_cells: int, n_blocks: int,
               has_sketch: bool):
    """A cold device store's resident state and anchor constants, built
    by ONE launch instead of an eager device op (or upload) apiece.

    ``consts`` is the store's six anchor constants, already in its dtype:
    the four cuts of ``Boundaries.as_tuple``, ``sketch0`` and 1, each
    divided by the store's scale.  Returns ``(state, consts_out)``:
    ``state`` is the zero ``(mom_s, mom_l, totals, n_sampled, regs)``
    (``regs`` the uint8 HLL plane, None without a sketch plane), fresh
    buffers the first fused tick donates; ``consts_out`` is ``(bounds,
    bound_row, sketch0, sketch0_cells, inv_scale_cells)`` — the (4,) cuts
    and their (1, 4) row, the scalar sketch and its per-cell vector, and
    the per-cell inverse scale, which no launch donates."""
    from . import sketch as SK

    dt = consts.dtype
    regs = jnp.zeros((n_cells, SK.M), jnp.uint8) if has_sketch else None
    state = (jnp.zeros((n_cells, 4), dt), jnp.zeros((n_cells, 4), dt),
             jnp.zeros((n_cells, 3), dt), jnp.zeros((n_blocks,), dt), regs)
    bounds = consts[:4]
    return state, (bounds, bounds.reshape(1, 4), consts[4],
                   jnp.broadcast_to(consts[4], (n_cells,)),
                   jnp.broadcast_to(consts[5], (n_cells,)))


def d2h_async(x):
    """Launch/readback decoupling: start the device->host copy of ``x``
    (the tick's O(groups) stat rows) WITHOUT blocking, and return ``x``.

    The pipelined tick dispatches a mode-group's fused launch, calls this
    on the returned rows handle, and keeps staging the next group's
    samples; the bytes stream back concurrently and the eventual
    ``np.asarray`` at compose time finds them already landed (or blocks
    only for the remainder).  This is an EXPLICIT transfer — sanctioned
    under ``jax.transfer_guard("disallow")``, like the ``np.asarray``
    readout it front-runs.  Arrays without an async copy path (e.g.
    tracers, or sharded layouts that must gather first) pass through
    untouched — the later materialization just pays the full sync."""
    try:
        x.copy_to_host_async()
    except (AttributeError, RuntimeError, ValueError):
        pass
    return x


_launch_pool = None


def launch_pool():
    """The pipelined tick's single launch worker (lazy, process-wide).

    One worker thread runs every fused launch in submission order —
    exactly the serial launch order, so per-cell merge order (and with
    it bit parity) is untouched — while the MAIN thread keeps drawing
    and pane-building the next chunk.  The overlap is real even on
    runtimes whose dispatch executes synchronously: jax releases the
    GIL inside the native XLA execute (and device_put copy), which is
    where the launch wall time lives.  ONE worker globally also
    serializes ticks against the same stack's donated state."""
    global _launch_pool
    if _launch_pool is None:
        from concurrent.futures import ThreadPoolExecutor
        _launch_pool = ThreadPoolExecutor(max_workers=1,
                                          thread_name_prefix="isla-launch")
    return _launch_pool


class StageClock(dict):
    """One run's stage clock: stage key -> seconds (the mapping itself),
    plus ``counts``, the run's counters, which ``count`` books while one
    of the run's stages is open on the calling thread."""

    def __init__(self, stages=(), counters=()):
        super().__init__(dict.fromkeys(stages, 0.0))
        self.counts = dict.fromkeys(counters, 0)


class _OpenStage:
    __slots__ = ("timings", "part", "nested")

    def __init__(self, timings, part: bool) -> None:
        self.timings = timings
        self.part = part
        self.nested = 0.0   # seconds of top-level stages nested inside


_open_stages = threading.local()
_book_lock = threading.Lock()   # the pipelined worker books concurrently


def _stage_frames() -> list:
    frames = getattr(_open_stages, "frames", None)
    if frames is None:
        frames = _open_stages.frames = []
    return frames


@contextlib.contextmanager
def stage_trace(name: str, timings=None, key: Optional[str] = None):
    """One stage of the served tick, on the profiler's clock and on the
    stage clock at once.

    Opens ``jax.profiler.TraceAnnotation(f"isla:{name}")`` — a host span
    on the clock the device trace shares — and books the same interval
    into ``timings[key or name]``:

     * a top-level stage (``draw``) books its SELF time: the interval
       minus the top-level stages nested in it on this thread;
     * a part (a dotted name, ``draw.sample``) books its inclusive
       interval and is not subtracted from its parent.

    Nesting is tracked per thread, so a stage the pipelined launch worker
    runs is never subtracted from the main thread's: the stage sum then
    exceeds the wall clock by exactly the overlap.  ``timings=None``
    emits the span and books nothing."""
    frames = _stage_frames()
    frame = _OpenStage(timings, "." in name)
    frames.append(frame)
    with jax.profiler.TraceAnnotation("isla:" + name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            spent = time.perf_counter() - t0
            frames.pop()
    if not frame.part:
        for outer in reversed(frames):
            if not outer.part:
                outer.nested += spent
                break
        spent = max(spent - frame.nested, 0.0)
    if timings is not None:
        key = name if key is None else key
        with _book_lock:
            timings[key] = timings.get(key, 0.0) + spent


def count(name: str, n: int) -> None:
    """Add ``n`` to counter ``name`` of the run whose stage is innermost
    on this thread (dropped when that stage's clock keeps no counts, or
    no stage is open)."""
    for frame in reversed(_stage_frames()):
        if frame.timings is not None:
            counts = getattr(frame.timings, "counts", None)
            if counts is not None:
                with _book_lock:
                    counts[name] = counts.get(name, 0) + int(n)
            return


def _segment_carry_sum(prior: jnp.ndarray, cols, seg: jnp.ndarray,
                       n_segments: int) -> jnp.ndarray:
    """Carry-prepend segmented sum: each segment's resident total is
    prepended to the scatter stream as one extra weight row, so the fold
    is ``((carry + a1) + a2) + ...`` — the identical left fold the host
    ``np.bincount`` carry performs (``engine._segment_moment_rows``).
    XLA's sequential scatter-add makes this bit-identical to the host
    path when the store runs float64.  All columns ride ONE 2-D scatter
    (row-wide updates) — an order of magnitude cheaper than per-column
    scatters on CPU XLA, with the same per-column fold order."""
    ids2 = jnp.concatenate([jnp.arange(n_segments, dtype=seg.dtype), seg])
    data = jnp.concatenate([prior, jnp.stack(cols, axis=1)])
    return jax.ops.segment_sum(data, ids2, num_segments=n_segments)


def group_row_stats(mom_s: jnp.ndarray, mom_l: jnp.ndarray,
                    totals: jnp.ndarray, partials: jnp.ndarray,
                    n_sampled: jnp.ndarray, sizes: jnp.ndarray,
                    n_groups_list, min_region_count: float) -> jnp.ndarray:
    """Per-group statistics rows, reduced on device so the host never
    reads per-cell moments.  One row per (store, group); columns:

      0 n_g            matching samples
      1 w_g            estimated matching population (size * cnt / drawn)
      2 sum p*w        partials weighted by w (group leverage mean num.)
      3 sum ex2*w      per-cell E[x^2] weighted by w
      4 s1_g           plain sample sum
      5 s2_g           plain sample square sum
      6 degraded       #populated cells that hit the empty-region fallback
      7 sum ex2*size   catalog-weighted E[x^2] numerator (visited cells)
      8 sum size       catalog-weighted denominator (visited cells)

    Cells are (group, block)-contiguous per stacked store
    (``n_groups_list`` gives each store's static cardinality), so every
    reduction is a plain reshape-sum over the block axis — no scatter.
    """
    cnt, s1, s2 = totals[:, 0], totals[:, 1], totals[:, 2]
    per_ex2 = s2 / jnp.maximum(cnt, 1.0)
    visited = (cnt > 0).astype(cnt.dtype)
    fallback = ((mom_s[:, 0] < min_region_count)
                | (mom_l[:, 0] < min_region_count)
                ).astype(cnt.dtype) * visited
    n_b = n_sampled.shape[0] // len(n_groups_list)
    out = []
    o = 0
    for k, g in enumerate(n_groups_list):
        sl = slice(o, o + g * n_b)
        shape = (g, n_b)
        drawn = n_sampled[k * n_b:(k + 1) * n_b][None, :]
        bsize = sizes[k * n_b:(k + 1) * n_b][None, :]
        cnt_k = cnt[sl].reshape(shape)
        w = bsize * cnt_k / jnp.maximum(drawn, 1.0)
        ex2_k = per_ex2[sl].reshape(shape)
        vis_k = visited[sl].reshape(shape)
        out.append(jnp.stack([
            cnt_k.sum(1), w.sum(1),
            (partials[sl].reshape(shape) * w).sum(1), (ex2_k * w).sum(1),
            s1[sl].reshape(shape).sum(1), s2[sl].reshape(shape).sum(1),
            fallback[sl].reshape(shape).sum(1),
            (ex2_k * bsize * vis_k).sum(1), (bsize * vis_k).sum(1),
        ], axis=1))
        o += g * n_b
    return jnp.concatenate(out) if len(out) > 1 else out[0]


def _scaled_solve_args(params: IslaParams, geometry, inv_scale):
    """Per-cell Phase 2 stopping threshold and ISLA-E geometry.

    ``thr`` (and the empirical ``b0``) are ABSOLUTE quantities on the
    value axis; cells normalized by their own anchor scale need them
    divided by that scale.  ``inv_scale`` is the per-cell 1/scale vector
    (all-ones for float64 stores — exact passthrough); ``None`` keeps the
    scalar params (pre-scaled by the caller, the legacy contract).
    """
    if inv_scale is None:
        return params.thr, geometry
    thr = params.thr * inv_scale
    if geometry is not None:
        geometry = (geometry[0], geometry[1] * inv_scale)
    return thr, geometry


def _sample_bounds(bounds: jnp.ndarray, seg: jnp.ndarray):
    """Region cuts aligned with a tagged sample stream.

    ``bounds`` is either one broadcast row ((4,) or (1, 4) — every cell
    shares the anchor) or a per-cell table ((n_cells + 1, 4), the per-key
    anchor path; the +1 pad row holds +inf cuts so bucket-padding drop
    samples match no region).  Returns the four cut operands, scalar or
    per-sample."""
    b = bounds.reshape(-1, 4)
    if b.shape[0] == 1:
        return b[0, 0], b[0, 1], b[0, 2], b[0, 3]
    bs = b[seg]
    return bs[:, 0], bs[:, 1], bs[:, 2], bs[:, 3]


def _solve_rows(mom_s: jnp.ndarray, mom_l: jnp.ndarray,
                totals: jnp.ndarray, n_sampled: jnp.ndarray,
                sketch0: jnp.ndarray, sizes: jnp.ndarray,
                inv_scale: jnp.ndarray, *, params: IslaParams, mode: str,
                geometry, n_groups_list):
    """Phase 2 over the resident state and its group stat rows: the
    common tail of every tick program, ``(partials, rows)``."""
    thr, geometry = _scaled_solve_args(params, geometry, inv_scale)
    partials = phase2(mom_s, mom_l, sketch0, params, mode=mode,
                      geometry=geometry, thr=thr)
    rows = group_row_stats(mom_s, mom_l, totals, partials, n_sampled,
                           sizes, n_groups_list,
                           float(params.min_region_count))
    return partials, rows


def _tick_core(mom_s: jnp.ndarray, mom_l: jnp.ndarray,
               totals: jnp.ndarray, n_sampled: jnp.ndarray,
               values: jnp.ndarray, seg: jnp.ndarray, quotas: jnp.ndarray,
               bounds: jnp.ndarray, sketch0: jnp.ndarray,
               sizes: jnp.ndarray, inv_scale: jnp.ndarray, regs=None,
               hashes=None, *, params: IslaParams, mode: str, geometry,
               n_groups_list):
    """The tagged tick body shared by the single-device ``fused_tick``
    and the per-shard program of the mesh launch (``mesh_tick_fn``) —
    the rows come back UNREDUCED across shards (the mesh wrapper psums
    them; single-device they already cover every cell).  A register
    plane ``regs`` takes each sample's ``hashes`` limbs at its cell id
    (pad ids drop).  Returns ``(mom_s', mom_l', totals', n_sampled',
    regs', partials, rows)``."""
    n_cells = mom_s.shape[0]
    # One 11-column carry-prepend scatter folds the whole pass: S and L
    # region moments plus the plain totals, each column's fold order
    # identical to the host bincount carry (bit-exact in float64).  The
    # extra pad row is the bucket-padding drop segment.
    v = values
    s_lo, s_hi, l_lo, l_hi = _sample_bounds(bounds, seg)
    m_s = ((v > s_lo) & (v < s_hi)).astype(v.dtype)
    m_l = ((v > l_lo) & (v < l_hi)).astype(v.dtype)
    v2 = v * v
    v3 = v2 * v
    ones = jnp.ones_like(v)
    pad = jnp.zeros((1, 11), mom_s.dtype)
    prior = jnp.concatenate(
        [jnp.concatenate([mom_s, mom_l, totals], axis=1), pad])
    merged = _segment_carry_sum(
        prior, [m_s, v * m_s, v2 * m_s, v3 * m_s,
                m_l, v * m_l, v2 * m_l, v3 * m_l,
                ones, v, v2], seg, n_cells + 1)[:n_cells]
    mom_s, mom_l = merged[:, 0:4], merged[:, 4:8]
    totals = merged[:, 8:11]
    n_sampled = n_sampled + jnp.tile(quotas, len(n_groups_list))
    if regs is not None:
        j, rho = _sketch_encode(*hashes)
        regs = regs.at[seg, j].max(rho, mode="drop")
    partials, rows = _solve_rows(
        mom_s, mom_l, totals, n_sampled, sketch0, sizes, inv_scale,
        params=params, mode=mode, geometry=geometry,
        n_groups_list=n_groups_list)
    return mom_s, mom_l, totals, n_sampled, regs, partials, rows


# The tick state: the moment quadruple and the optional register plane,
# donated by name so the plane can ride as a keyword operand.
_STATE = ("mom_s", "mom_l", "totals", "n_sampled", "regs")


@functools.partial(
    jax.jit,
    static_argnames=("params", "mode", "geometry", "n_groups_list"),
    donate_argnames=_STATE)
def fused_tick(mom_s: jnp.ndarray, mom_l: jnp.ndarray,
               totals: jnp.ndarray, n_sampled: jnp.ndarray,
               values: jnp.ndarray, seg: jnp.ndarray, quotas: jnp.ndarray,
               bounds: jnp.ndarray, sketch0: jnp.ndarray,
               sizes: jnp.ndarray, inv_scale: jnp.ndarray = None,
               regs=None, hashes=None, *, params: IslaParams,
               mode: str = "calibrated", geometry=None,
               n_groups_list=(1,)):
    """One device-resident continuation round as a single fused launch.

    The state operands are DONATED: the tick consumes the resident
    buffers and returns their successors, so steady state never
    re-ships moments host<->device — the fresh ``values``/``seg``/
    ``quotas`` sample upload is the only h2d crossing, and only the
    per-group stats rows and per-cell partial answers come back.

    ``values`` are pre-scaled/shifted on the host into each cell's anchor
    frame (sample prep, not moments); ``seg`` may contain ``n_cells`` as
    a drop segment for bucket padding (``n_cells + 1`` segments are
    reduced, the overflow row discarded) so the jit does not retrace on
    every tick's matched-sample count.  ``sketch0`` is per-cell, so
    stacked stores that re-anchored independently still solve in one
    launch; ``bounds`` is one broadcast row for a shared-anchor stack or
    a per-cell (+pad) table for per-key anchors, and ``inv_scale`` is the
    per-cell anchor-scale vector the stopping threshold rides.

    ``regs`` is the optional (n_cells, m) uint8 HLL register plane and
    ``hashes`` the samples' (hi, lo) raw-bit limbs, aligned with
    ``values`` / ``seg`` (see ``_sketch_encode``).

    Returns ``(mom_s', mom_l', totals', n_sampled', regs', partials, rows,
    group_regs)`` — ``rows`` per ``group_row_stats``, ``group_regs`` the
    folded per-group register rows (the only register bytes that ever
    read back); both register entries are None without a plane.
    """
    state = _tick_core(mom_s, mom_l, totals, n_sampled, values, seg,
                       quotas, bounds, sketch0, sizes, inv_scale, regs,
                       hashes, params=params, mode=mode, geometry=geometry,
                       n_groups_list=n_groups_list)
    return (*state, _sketch_fold(state[4], n_groups_list))


def _dense_core(mom_s: jnp.ndarray, mom_l: jnp.ndarray,
                totals: jnp.ndarray, n_sampled: jnp.ndarray,
                values2d: jnp.ndarray, pad_valid: jnp.ndarray,
                quotas: jnp.ndarray, gid_panes, valid_panes,
                bounds: jnp.ndarray, sketch0: jnp.ndarray,
                sizes: jnp.ndarray, inv_scale: jnp.ndarray, *,
                params: IslaParams, mode: str, geometry,
                n_groups_list, gid_slots, valid_slots, key_affine,
                bound_slots, active_cells=None, regs=None, hashes=None):
    """The dense tick body shared by the single-device
    ``fused_tick_dense`` and the per-shard program of the mesh launch
    (``mesh_tick_dense_fn``); rows come back unreduced across shards.

    ``active_cells`` is the zone-map pruning contract: when the planner
    rates blocks at 0 (provably filtered out), the launch runs over a
    COMPACTED block axis — ``values2d`` / ``pad_valid`` / ``quotas`` and
    every pane cover only the active blocks — and ``active_cells =
    (cell_idx, ns_idx)`` scatters the compacted delta back onto the full
    resident state (``cell_idx`` maps compacted (key, group, block) rows
    to resident cell rows, ``ns_idx`` maps compacted (key, block) quota
    rows to the draw ledger; out-of-bounds pad entries drop).  Pruned
    cells' resident rows are left untouched — x + 0 never happens, the
    rows simply aren't addressed — so a predicate change re-activates
    them warm.  Phase 2 and the group stat rows still run over the FULL
    state: skipped cells keep contributing their resident moments.

    The serving draw is per-block contiguous, so the tick's samples pack
    into a (n_blocks, quota_max) pane (``pad_valid`` zeroes the ragged
    tail).  The 11 weight columns (S/L region moments + plain totals)
    contract against a per-key (group one-hot x predicate) matrix in one
    ``dot_general`` over the quota axis — the MXU-shaped form of Alg. 1,
    and ~4x faster than the scatter on CPU XLA too.  The contraction runs
    at ``Precision.HIGHEST``: at default precision a TPU rounds f32
    operands to bf16 (~3 significant digits), and the v, v^2, v^3 weight
    columns feed the moment sums directly.  The delta is added
    onto the donated resident moments (plain vector add, not the
    bit-exact carry fold — the float64 bit-parity contract belongs to
    the tagged ``fused_tick``; this is the fp32 serving hot path).

    Pane sharing is STATIC: ``gid_panes`` / ``valid_panes`` hold each
    distinct uploaded (n_blocks, quota_max) GROUP BY / predicate pane
    once, and the per-store ``gid_slots`` / ``valid_slots`` index into
    them (-1 = ungrouped / unpredicated).  Keys sharing a GROUP BY slot
    ride ONE contraction — their (predicate-masked) weight columns
    concatenate along the moment axis, so k such keys cost one batched
    GEMM, not k (identity of traced operands cannot be detected inside
    jit, hence the static slots).  ``n_groups_list`` gives each store's
    static cardinality.

    Per-key anchors ride the same static-slot idiom: the value pane is
    uploaded ONCE on a reference axis, ``key_affine[k] = (ratio, offset)``
    recovers key k's own scaled-shifted frame as ``v * ratio + offset``,
    and ``bound_slots[k]`` picks its anchor's row out of the deduplicated
    ``bounds`` table ((n_distinct_anchors, 4)).  Keys sharing an anchor
    slot AND affine share one weight pane (python-level CSE), so a
    uniform-anchor stack traces the identical graph as before;
    ``inv_scale`` is the per-cell anchor-scale vector the stopping
    threshold and ISLA-E ``b0`` ride.

    A register plane ``regs`` takes the (hi, lo) ``hashes`` panes
    through ``_sketch_dense_scatter``, compacted like the moments.
    Returns ``(mom_s', mom_l', totals', n_sampled', regs', partials,
    rows)``.
    """
    dt = mom_s.dtype
    n_keys = len(n_groups_list)
    if key_affine is None:
        key_affine = ((1.0, 0.0),) * n_keys
    if bound_slots is None:
        bound_slots = (0,) * n_keys
    brows = bounds.reshape(-1, 4)
    n_b = values2d.shape[0]
    w_cache = {}  # (affine, bound slot) -> shared weight pane

    def w_for(i):
        ck = (key_affine[i], bound_slots[i])
        if ck not in w_cache:
            ratio, off = key_affine[i]
            v = (values2d if ratio == 1.0 and off == 0.0
                 else values2d * dt.type(ratio) + dt.type(off))
            row = brows[bound_slots[i]]
            ms = ((v > row[0]) & (v < row[1])).astype(dt) * pad_valid
            ml = ((v > row[2]) & (v < row[3])).astype(dt) * pad_valid
            v2 = v * v
            v3 = v2 * v
            w_cache[ck] = jnp.stack(
                [ms, v * ms, v2 * ms, v3 * ms,
                 ml, v * ml, v2 * ml, v3 * ml,
                 pad_valid, v * pad_valid, v2 * pad_valid], axis=-1)
        return w_cache[ck]

    parts = [None] * n_keys
    shared = {}  # gid slot -> [(key index, valid slot), ...]
    for i, (gslot, vslot, g) in enumerate(zip(gid_slots, valid_slots,
                                              n_groups_list)):
        if g == 1:
            # Ungrouped key: a plain quota-axis reduction, no one-hot.
            vk = pad_valid if vslot < 0 else valid_panes[vslot]
            parts[i] = (w_for(i) * vk[..., None]).sum(axis=1)  # (B, 11)
        else:
            shared.setdefault(gslot, []).append((i, vslot))
    for gslot, members in shared.items():
        g = n_groups_list[members[0][0]]
        oh = jax.nn.one_hot(gid_panes[gslot], g, dtype=dt)
        w_cat = jnp.concatenate(
            [w_for(i) if vslot < 0
             else w_for(i) * valid_panes[vslot][..., None]
             for i, vslot in members], axis=2)          # (B, q, 11k)
        blk = jax.lax.dot_general(
            w_cat, oh, (((1,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=dt)                  # (B, 11k, G)
        for j, (i, _) in enumerate(members):
            sub = blk[:, 11 * j:11 * (j + 1), :]
            parts[i] = jnp.transpose(sub, (2, 0, 1)).reshape(g * n_b, 11)
    delta = jnp.concatenate(parts, axis=0)              # (C, 11)
    if active_cells is None:
        mom_s = mom_s + delta[:, 0:4]
        mom_l = mom_l + delta[:, 4:8]
        totals = totals + delta[:, 8:11]
        n_sampled = n_sampled + jnp.tile(quotas, len(n_groups_list))
    else:
        cell_idx, ns_idx = active_cells
        mom_s = mom_s.at[cell_idx].add(delta[:, 0:4], mode="drop")
        mom_l = mom_l.at[cell_idx].add(delta[:, 4:8], mode="drop")
        totals = totals.at[cell_idx].add(delta[:, 8:11], mode="drop")
        n_sampled = n_sampled.at[ns_idx].add(
            jnp.tile(quotas, len(n_groups_list)), mode="drop")
    if regs is not None:
        regs = _sketch_dense_scatter(
            regs, *hashes, pad_valid, gid_panes, valid_panes,
            n_groups_list=n_groups_list, gid_slots=gid_slots,
            valid_slots=valid_slots,
            cell_idx=None if active_cells is None else active_cells[0])
    partials, rows = _solve_rows(
        mom_s, mom_l, totals, n_sampled, sketch0, sizes, inv_scale,
        params=params, mode=mode, geometry=geometry,
        n_groups_list=n_groups_list)
    return mom_s, mom_l, totals, n_sampled, regs, partials, rows


class PaneLayout(NamedTuple):
    """Where each operand of a dense tick sits in its ONE packed upload,
    and the host packing and in-graph unpacking of that buffer.

    The packed buffer is ``(pane_rows, width)`` in the stack's dtype: row
    ``b`` holds block ``b``'s row of every pane side by side, in order the
    value pane, each distinct predicate-mask pane, each distinct GROUP BY
    code pane, each uint32 hash-limb pane, and then the block's quota in
    one word; the row is zero up to whole lane tiles.  Float panes take
    ``quota_max`` words; code and hash panes take ``quota_max`` 4-byte
    words, stored bit for bit (``.view`` on the host,
    ``bitcast_convert_type`` in-graph).  A block's samples and its quota
    share its row.  The layout follows from shapes the compiled
    programs already key on, so as a static argument it adds no
    programs."""

    pane_rows: int      # blocks in the launch (compact under pruning)
    quota_max: int      # the pane bucket
    n_valid: int = 0    # distinct predicate-mask panes
    n_gid: int = 0      # distinct GROUP BY code panes
    n_hash: int = 0     # hash-limb panes: (hi, lo) on a sketch stack

    def spans(self, itemsize: int):
        """Each pane's ``(first, stop)`` columns in order, then the quota
        word's, for a buffer of ``itemsize``-byte words."""
        fw, iw = self.quota_max, self.quota_max * 4 // itemsize
        edges = [0]
        for w in ([fw] * (1 + self.n_valid)
                  + [iw] * (self.n_gid + self.n_hash) + [1]):
            edges.append(edges[-1] + w)
        return tuple(zip(edges[:-1], edges[1:]))

    def shape(self, itemsize: int):
        """Whole 128-word lane tiles: a TPU lays a narrower or ragged
        row out column-major, and the upload would transpose it."""
        return self.pane_rows, -(-self.spans(itemsize)[-1][1] // 128) * 128

    def pack(self, dtype, quotas, values, valids=(), gids=(), hashes=()):
        """The host buffer: each block-major stream written straight into
        its pane in ``dtype``.  Row-major assignment through the
        ragged-quota mask preserves stream order; numpy's cast on
        assignment rounds as a device-dtype conversion does.  ``valids``
        are predicate masks, ``gids`` int32 codes, ``hashes`` uint32
        limbs, one stream each."""
        itemsize = np.dtype(dtype).itemsize
        buf = np.zeros(self.shape(itemsize), dtype=dtype)
        cols = [buf[:, a:b] for a, b in self.spans(itemsize)]
        vmask = (np.arange(self.quota_max)[None, :]
                 < np.asarray(quotas)[:, None])
        streams = ([(values, None)] + [(v, None) for v in valids]
                   + [(g, np.int32) for g in gids]
                   + [(h, np.uint32) for h in hashes])
        for col, (src, kind) in zip(cols, streams):
            pane = col if kind is None else col.view(kind)
            pane[vmask] = np.asarray(src).reshape(-1)
        cols[-1][:, 0] = quotas
        return buf

    def unpack(self, panes: jnp.ndarray):
        """In-graph inverse of ``pack``: ``(values2d, pad_valid, quotas,
        gid_panes, valid_panes, hash_panes)``.  The pad pane is not
        uploaded: it is ``iota(quota_max) < quotas``, and under
        zone-pruned compaction the quota column is already compact."""
        dt = panes.dtype
        n, q = self.pane_rows, self.quota_max
        cols = [panes[:, a:b] for a, b in self.spans(dt.itemsize)]

        def bits(block, to):
            return jax.lax.bitcast_convert_type(block, to).reshape(n, q)

        o, h = 1 + self.n_valid, 1 + self.n_valid + self.n_gid
        pad_valid = (jnp.arange(q, dtype=dt)[None, :]
                     < cols[-1]).astype(dt)
        return (cols[0], pad_valid, cols[-1].reshape(n),
                tuple(bits(c, jnp.int32) for c in cols[o:h]),
                tuple(cols[1:o]),
                tuple(bits(c, jnp.uint32) for c in cols[h:-1]))


@functools.partial(
    jax.jit,
    static_argnames=("params", "mode", "geometry", "n_groups_list",
                     "gid_slots", "valid_slots", "key_affine",
                     "bound_slots", "pane_layout"),
    donate_argnames=_STATE)
def fused_tick_dense(mom_s: jnp.ndarray, mom_l: jnp.ndarray,
                     totals: jnp.ndarray, n_sampled: jnp.ndarray,
                     bounds: jnp.ndarray, panes: jnp.ndarray,
                     sketch0: jnp.ndarray, sizes: jnp.ndarray,
                     inv_scale: jnp.ndarray = None,
                     active_cells=None, regs=None, *,
                     params: IslaParams, pane_layout: PaneLayout,
                     mode: str = "calibrated", geometry=None,
                     n_groups_list=(1,), gid_slots=(-1,),
                     valid_slots=(-1,), key_affine=None,
                     bound_slots=None):
    """``fused_tick`` on the dense block-major layout (see
    ``_dense_core`` for the batched-contraction Phase 1, the static-slot
    pane sharing, and the ``active_cells`` compacted-launch contract;
    this wrapper owns the jit + donation and unpacks ``panes``, the
    tick's one upload, per ``pane_layout``).  A register plane's hash
    panes ride in ``panes``.  Returns ``fused_tick``'s shape."""
    values2d, pad_valid, quotas, gid_panes, valid_panes, hashes = \
        pane_layout.unpack(panes)
    state = _dense_core(mom_s, mom_l, totals, n_sampled, values2d,
                        pad_valid, quotas, gid_panes, valid_panes, bounds,
                        sketch0, sizes, inv_scale, params=params, mode=mode,
                        geometry=geometry, n_groups_list=n_groups_list,
                        gid_slots=gid_slots, valid_slots=valid_slots,
                        key_affine=key_affine, bound_slots=bound_slots,
                        active_cells=active_cells, regs=regs, hashes=hashes)
    return (*state, _sketch_fold(state[4], n_groups_list))


@functools.partial(
    jax.jit,
    static_argnames=("params", "mode", "geometry", "n_groups_list"))
def fused_solve(mom_s: jnp.ndarray, mom_l: jnp.ndarray,
                totals: jnp.ndarray, n_sampled: jnp.ndarray,
                sketch0: jnp.ndarray, sizes: jnp.ndarray,
                inv_scale: jnp.ndarray = None, regs=None, *,
                params: IslaParams, mode: str = "calibrated",
                geometry=None, n_groups_list=(1,)):
    """The zero-draw tick: re-solve resident moments without touching the
    state (a warm repeat whose deficit is <= 0).  No donation — the
    resident buffers stay live — and no h2d operand at all.
    ``inv_scale`` is the per-cell anchor-scale vector (see
    ``fused_tick``).  Returns ``(partials, rows, group_regs)``: a
    register plane is re-folded so a warm repeat serves distinct answers
    from the same O(groups) readback."""
    return (*_solve_rows(mom_s, mom_l, totals, n_sampled, sketch0, sizes,
                         inv_scale, params=params, mode=mode,
                         geometry=geometry, n_groups_list=n_groups_list),
            _sketch_fold(regs, n_groups_list))


# ---------------------------------------------------------------------------
# The HLL register plane: optional state of every tick program.
# ---------------------------------------------------------------------------
#
# COUNT DISTINCT state is a per-cell HyperLogLog register row whose merge
# is an elementwise max — associative, commutative, idempotent — so the
# tick-merge parity story is STRUCTURAL: any partition of a stream folds
# to the bit-identical one-pass plane, on every route.  An absent plane
# is None, an empty pytree: it adds no operand, no donation and no op,
# so a stack without a sketch traces the same graph as a moment-only
# tick.
#
# Hash operands arrive as (hi, lo) uint32 limb pairs of the splitmix64'd
# raw value bits (``sketch.value_limbs`` — computed on host, mixed
# in-graph; sample-sized h2d like the value vector).  rho == 0 is the
# scatter's neutral element, so masked lanes ride with a zeroed rank, and
# bucket-pad / pruned rows drop through ``mode="drop"`` — pruned cells'
# registers are never addressed and re-activate warm, exactly like the
# moment rows.


def _sketch_encode(hash_hi: jnp.ndarray, hash_lo: jnp.ndarray):
    """In-graph hash mix + register encode: limb pairs -> (j, rho)."""
    from . import sketch as SK
    return SK.encode_graph(*SK.splitmix64_graph(hash_hi, hash_lo))


def _sketch_fold(regs, n_groups_list):
    """Fold the (n_cells, m) register plane to one (store, group) register
    row each — max over every store's block axis (the register analogue of
    ``group_row_stats``: the host reads O(groups) rows, never per-cell
    registers).  Cells are (group, block)-contiguous per stacked store, so
    the fold is a reshape-max, no scatter.  No plane folds to None."""
    if regs is None:
        return None
    n_b = regs.shape[0] // sum(n_groups_list)
    out = []
    o = 0
    for g in n_groups_list:
        out.append(regs[o:o + g * n_b].reshape(g, n_b, -1).max(axis=1))
        o += g * n_b
    return jnp.concatenate(out) if len(out) > 1 else out[0]


def _mesh_fold_max(regs, n_groups_list, ax):
    """The shard's ``_sketch_fold`` rows, max-reduced across the mesh
    (None without a plane).

    The collective runs on int32: a TPU packs four uint8 rows into each
    32-bit sublane word, and its all-reduce maxes those whole words — on
    a v5e a uint8 ``pmax`` kept only the top row of every four correct.
    """
    if regs is None:
        return None
    folded = _sketch_fold(regs, n_groups_list).astype(jnp.int32)
    return jax.lax.pmax(folded, ax).astype(jnp.uint8)


def _sketch_dense_scatter(regs: jnp.ndarray, hash_hi2d: jnp.ndarray,
                          hash_lo2d: jnp.ndarray, pad_valid: jnp.ndarray,
                          gid_panes, valid_panes, *, n_groups_list,
                          gid_slots, valid_slots, cell_idx=None):
    """The dense-layout register merge: hash panes are block-major like
    the value pane, each key's (block, group) lane maps to its resident
    cell row, and invalid lanes (ragged pad / predicate miss) scatter the
    neutral rho = 0.  ``cell_idx`` routes compacted pane rows onto the
    full register plane (pads out-of-bounds -> drop), same contract as
    ``_dense_core``'s ``active_cells``.  Unlike the moment delta — whose
    dense fold is a float vector add — the register merge is an integer
    max, so the dense route keeps the tagged route's bit-parity contract
    for the plane even in fp32 serving."""
    j, rho0 = _sketch_encode(hash_hi2d, hash_lo2d)
    n_rows = hash_hi2d.shape[0]
    biota = jnp.arange(n_rows, dtype=jnp.int32)[:, None]
    o = 0
    for i, (gslot, vslot, g) in enumerate(zip(gid_slots, valid_slots,
                                              n_groups_list)):
        valid = pad_valid if vslot < 0 else pad_valid * valid_panes[vslot]
        ok = valid > 0
        rho = jnp.where(ok, rho0, jnp.uint8(0))
        if g == 1:
            row = jnp.broadcast_to(o + biota, j.shape)
        else:
            gid = jnp.where(ok, gid_panes[gslot].astype(jnp.int32), 0)
            row = o + gid * n_rows + biota
        cell = row if cell_idx is None else cell_idx[row]
        regs = regs.at[cell, j].max(rho, mode="drop")
        o += g * n_rows
    return regs


# ---------------------------------------------------------------------------
# Mesh launch: the fused tick sharded over the (group, block) cell axis.
# ---------------------------------------------------------------------------
#
# The cell axis is the natural unit to distribute (partition-level summary
# state, a la partitioned AQP): each shard owns a contiguous run of blocks
# for EVERY (store, group), keeps its moment / total / ledger rows resident,
# and runs the identical ``_tick_core`` / ``_dense_core`` program on its
# local slice.  The only cross-device traffic the steady state permits is
#
#   * the replicated sample upload (``mesh_h2d`` -- the sanctioned h2d of
#     the device tier, now placed once per device), and
#   * one ``psum`` of the O(groups) stat rows (9 columns per (store,
#     group) -- never per-cell moments).
#
# ``group_row_stats`` columns are all plain sums over the block axis, so
# per-shard rows psum to exactly the full-table rows (up to float
# association -- the x64 bit-parity contract for the mesh tier covers the
# resident state and per-cell partials, not the psum'd rows).
#
# A register plane is sharded with the moment rows and merged shard-local
# (samples retag to the local cell window exactly like the moment
# scatter), so per-cell registers never cross devices: its one collective
# is a ``pmax`` of the O(groups) FOLDED rows (``_mesh_fold_max``) —
# bit-identical to the single-device fold, since max is associative.  The
# optional operands (the plane, its hashes, the compacted scatter pair)
# sit last with a ``PartitionSpec`` each; None broadcasts to no leaves.


def cell_axis(mesh) -> str:
    """Name of the (single) mesh axis the cell dimension shards over."""
    return mesh.axis_names[0]


def mesh_h2d(mesh, x, spec, dtype=None) -> jnp.ndarray:
    """``h2d`` for the mesh tier: the single sanctioned host->mesh upload.

    ``spec`` is the ``PartitionSpec`` placing the array — ``P(ax, ...)``
    for cell-sharded operands, ``P()`` for the replicated sample stream.
    Everything the steady-state mesh tick ships to devices goes through
    here so tests can wrap the rest in ``jax.transfer_guard``.  The
    call and the array's global bytes are booked like ``h2d``'s.
    """
    from jax.sharding import NamedSharding
    with jax.transfer_guard("allow"):
        out = jax.device_put(jnp.asarray(x, dtype=dtype),
                             NamedSharding(mesh, spec))
    count("h2d_calls", 1)
    count("h2d_bytes", out.nbytes)
    return out


@functools.lru_cache(maxsize=64)
def mesh_tick_fn(mesh, params: IslaParams, mode: str, geometry,
                 n_groups_list, per_cell_bounds: bool):
    """Compiled mesh launch of the tagged fused tick.

    Returns a jitted function with the ``fused_tick`` operand order and
    return shape, ``regs`` and ``hashes`` passed positionally (None
    without a plane); the state is donated.  ``seg`` carries GLOBAL mesh
    cell ids and is replicated; each shard keeps the samples whose id
    falls in its own ``[s*L, (s+1)*L)`` window and retags the rest to its
    local drop row, so the per-cell fold order matches the single-device
    launch bit-for-bit in float64.  ``per_cell_bounds`` picks the
    hetero-anchor layout: a cell-sharded (N, 4) cuts table whose +inf pad
    row is appended per shard inside the body (uniform stacks replicate
    one row).  Rows are psum'd across the axis and come back replicated.
    """
    from jax.sharding import PartitionSpec as P
    ax = cell_axis(mesh)
    row, vec, rep = P(ax, None), P(ax), P()
    bspec = P(ax, None) if per_cell_bounds else P(None, None)

    def body(mom_s, mom_l, totals, ns, values, seg, quotas, bounds,
             sketch0, sizes, inv_scale, regs, hashes):
        n_local = mom_s.shape[0]
        lo = jax.lax.axis_index(ax).astype(seg.dtype) * n_local
        own = (seg >= lo) & (seg < lo + n_local)
        lseg = jnp.where(own, seg - lo, n_local).astype(seg.dtype)
        if per_cell_bounds:
            bounds = jnp.concatenate(
                [bounds, jnp.full((1, 4), jnp.inf, bounds.dtype)])
        mom_s, mom_l, totals, ns, regs, partials, rows = _tick_core(
            mom_s, mom_l, totals, ns, values, lseg, quotas, bounds,
            sketch0, sizes, inv_scale, regs, hashes, params=params,
            mode=mode, geometry=geometry, n_groups_list=n_groups_list)
        return (mom_s, mom_l, totals, ns, regs, partials,
                jax.lax.psum(rows, ax),
                _mesh_fold_max(regs, n_groups_list, ax))

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(row, row, row, vec, rep, rep, vec, bspec, vec, vec,
                  vec, row, rep),
        out_specs=(row, row, row, vec, row, vec, P(None, None),
                   P(None, None)))
    return jax.jit(sharded, donate_argnums=(0, 1, 2, 3, 11))


@functools.lru_cache(maxsize=64)
def mesh_tick_dense_fn(mesh, params: IslaParams, mode: str, geometry,
                       n_groups_list, gid_slots, valid_slots, key_affine,
                       bound_slots, n_gid_panes: int, n_valid_panes: int):
    """Compiled mesh launch of the dense fused tick.

    The block axis IS the sharded axis in the dense layout: the value
    pane, pad mask, quotas, GROUP BY / predicate panes and a plane's
    hash panes are all block-major, so every operand shards as
    ``P(ax, ...)`` and the body is ``_dense_core`` verbatim on the local
    slice — no retagging at all.  Group ids stay global (every shard
    holds all groups; only blocks split).  ``n_gid_panes`` /
    ``n_valid_panes`` fix the static pytree arity of the shared pane
    tuples.  The trailing operands are ``active_cells``, ``regs`` and
    ``hashes``, each None when absent.

    ``active_cells`` makes it the shard-aware zone-pruned launch: the
    pane operands cover each shard's ACTIVE blocks only (every shard
    padded to the same bucketed active count, so block runs stay
    contiguous and the global pane layout remains shard-major), and the
    two ``P(ax)`` index vectors — local cell / ledger scatter targets
    per shard, pads out-of-bounds — route the compacted delta onto the
    resident shards (see ``_dense_core``).
    """
    from jax.sharding import PartitionSpec as P
    ax = cell_axis(mesh)
    row, vec = P(ax, None), P(ax)

    def body(mom_s, mom_l, totals, ns, values2d, pad_valid, quotas,
             gid_panes, valid_panes, bounds, sketch0, sizes, inv_scale,
             active_cells, regs, hashes):
        mom_s, mom_l, totals, ns, regs, partials, rows = _dense_core(
            mom_s, mom_l, totals, ns, values2d, pad_valid, quotas,
            gid_panes, valid_panes, bounds, sketch0, sizes, inv_scale,
            params=params, mode=mode, geometry=geometry,
            n_groups_list=n_groups_list, gid_slots=gid_slots,
            valid_slots=valid_slots, key_affine=key_affine,
            bound_slots=bound_slots, active_cells=active_cells,
            regs=regs, hashes=hashes)
        return (mom_s, mom_l, totals, ns, regs, partials,
                jax.lax.psum(rows, ax),
                _mesh_fold_max(regs, n_groups_list, ax))

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(row, row, row, vec, row, row, vec,
                  (vec,) * n_gid_panes, (row,) * n_valid_panes,
                  P(None, None), vec, vec, vec, vec, row, row),
        out_specs=(row, row, row, vec, row, vec, P(None, None),
                   P(None, None)))
    return jax.jit(sharded, donate_argnums=(0, 1, 2, 3, 14))


@functools.lru_cache(maxsize=64)
def mesh_solve_fn(mesh, params: IslaParams, mode: str, geometry,
                  n_groups_list):
    """Compiled mesh launch of the zero-draw re-solve (``fused_solve``,
    ``regs`` positional and None without a plane).

    No donation — the resident shards stay live — and the only
    collectives are the stat-row psum and a plane's folded-row pmax.
    """
    from jax.sharding import PartitionSpec as P
    ax = cell_axis(mesh)
    row, vec = P(ax, None), P(ax)

    def body(mom_s, mom_l, totals, ns, sketch0, sizes, inv_scale, regs):
        partials, rows = _solve_rows(
            mom_s, mom_l, totals, ns, sketch0, sizes, inv_scale,
            params=params, mode=mode, geometry=geometry,
            n_groups_list=n_groups_list)
        return (partials, jax.lax.psum(rows, ax),
                _mesh_fold_max(regs, n_groups_list, ax))

    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(row, row, row, vec, vec, vec, vec, row),
        out_specs=(vec, P(None, None), P(None, None)))
    return jax.jit(sharded)


_COLLECTIVE_OPS = ("all-reduce", "all-gather", "all-to-all",
                   "collective-permute", "reduce-scatter")


def collective_footprint(hlo_text: str):
    """Cross-device collectives in a compiled module, as a list of
    ``(op_name, total_elements)``.

    Parsed from the optimized HLO text (``lowered.compile().as_text()``)
    — the transfer-audit analogue of the device tier's
    ``transfer_guard``: the zero-moment-traffic contract holds iff every
    entry's element count is O(groups) stat rows, never O(cells) moment
    state.
    """
    import re
    shape = re.compile(r"\w+\[([0-9,]*)\]")
    head = re.compile(
        r"=\s*((?:\([^)]*\))|(?:\S+))\s+(%s)" %
        "|".join(_COLLECTIVE_OPS))
    out = []
    for m in head.finditer(hlo_text):
        total = 0
        for dims in shape.findall(m.group(1)):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n
        out.append((m.group(2), total))
    return out


# ---------------------------------------------------------------------------
# Pilot + end-to-end distributed mean.
# ---------------------------------------------------------------------------


def pilot_stats_device(values) -> Tuple[float, float, float]:
    """Pre-estimation moment accumulation on device: ``(sketch0, sigma,
    min)`` of a host pilot array via the same jnp reduction path Phase 2
    runs on (a ``stats_fn`` for ``preestimation.run_pilot``).

    fp32-safe by the usual lever: values are pre-scaled by a host-side
    normalizer (the pilot's max |value|) so the device sums are O(n), and
    the three statistics are exactly scale-equivariant.  sigma uses ddof=1
    to match the host pilot.  The served routes do not use it: their
    anchors come from the float64 host pilot (``MultiQueryExecutor``).
    """
    v_host = np.asarray(values, dtype=np.float64).reshape(-1)
    if v_host.size == 0:
        raise ValueError("pilot must be non-empty")
    scale = float(max(np.max(np.abs(v_host)), 1e-12))
    v = jnp.asarray(v_host / scale, jnp.float32)
    n = v.shape[0]
    mean = jnp.sum(v) / n
    var = jnp.sum(jnp.square(v - mean)) / max(n - 1, 1)
    sigma = jnp.sqrt(jnp.maximum(var, 0.0))
    lo = jnp.min(v)
    return float(mean) * scale, float(sigma) * scale, float(lo) * scale


def local_pilot(values: jnp.ndarray, pilot_size: int = 256
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Cheap local sketch/sigma from a strided slice: (sum, sumsq, n)."""
    v = values.astype(jnp.float32).reshape(-1)
    n = v.shape[0]
    take = min(pilot_size, n)
    stride = max(n // take, 1)
    pv = jax.lax.slice(v, (0,), (take * stride,), (stride,))
    return jnp.sum(pv), jnp.sum(pv * pv), jnp.float32(pv.shape[0])


def pilot_band_geometry(pilot_vals: jnp.ndarray, sketch0, sigma,
                        params: IslaParams, axis_names=None):
    """Device-side ISLA-E geometry: (kappa, b0) from the pilot slice.

    Evaluates the S∪L band mean at three centers (sketch0, sketch0 -+ h) via
    masked sums — a (3, 2) psum, still O(1) collective payload.  b0 =
    band-mean offset at delta=0 (skew signal); kappa = central-difference
    slope (the Theorem-1 deviation ratio).
    """
    v = pilot_vals.astype(jnp.float32).reshape(-1)
    h = 0.25 * sigma
    centers = jnp.stack([sketch0, sketch0 - h, sketch0 + h])

    def band_sum(center):
        lo1, hi1 = center - params.p2 * sigma, center - params.p1 * sigma
        lo2, hi2 = center + params.p1 * sigma, center + params.p2 * sigma
        m = (((v > lo1) & (v < hi1)) | ((v > lo2) & (v < hi2))
             ).astype(jnp.float32)
        return jnp.stack([jnp.sum(v * m), jnp.sum(m)])

    sums = jax.vmap(band_sum)(centers)              # (3, 2)
    sums = _psum(sums, axis_names)
    means = sums[:, 0] / jnp.maximum(sums[:, 1], 1.0)
    means = jnp.where(sums[:, 1] > 0, means, centers)
    kappa_hat = jnp.clip((means[1] - means[2]) / (2.0 * h), -0.9, 0.9)
    b0_hat = means[0] - sketch0                      # sketch0 == pilot mean
    # Shrink toward the analytic normal prior (kappa*, b0=0) by pilot mass:
    # a small pilot's measured geometry is noise-dominated; N0 ~ the pilot
    # size at which measurement and prior carry equal weight.
    n0 = jnp.float32(1024.0)
    w = sums[0, 1] / (sums[0, 1] + n0)
    kappa = w * kappa_hat + (1.0 - w) * _lambda_star(params.p1, params.p2)
    b0 = w * b0_hat
    return kappa, b0


def _psum(x, axis_names):
    return jax.lax.psum(x, axis_names) if axis_names else x


def subsample(values: jnp.ndarray, rate: float,
              key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Uniform sample of ~rate*n elements.

    Strided when key is None (cheap, good for i.i.d.-positioned data);
    PRNG gather otherwise.
    """
    v = values.reshape(-1)
    n = v.shape[0]
    m = max(1, int(round(n * rate)))
    if key is None:
        stride = max(n // m, 1)
        return jax.lax.slice(v, (0,), (m * stride,), (stride,))
    idx = jax.random.randint(key, (m,), 0, n)
    return v[idx]


def isla_mean(values: jnp.ndarray,
              params: IslaParams,
              axis_names=None,
              rate: float = 0.05,
              key: Optional[jax.Array] = None,
              scale_hint: Optional[float] = None,
              semantics: str = "blocks",
              mode: str = "calibrated",
              pilot_size: int = 256) -> jnp.ndarray:
    """Approximate distributed mean of ``values`` (local shard view).

    Must be called inside shard_map/jit with ``axis_names`` naming the mesh
    axes to aggregate over (None = single device).  Cross-device traffic:
    one psum of 3 floats (pilot) + one psum of 10 floats (moments/partials),
    regardless of tensor size or mesh size.
    """
    v = values.astype(jnp.float32).reshape(-1)

    # --- Pre-estimation (pilot): relaxed sketch0 + sigma, hierarchical psum.
    ps, pss, pn = local_pilot(v, pilot_size)
    ps, pss, pn = _psum(jnp.stack([ps, pss, pn]), axis_names)
    sketch0 = ps / jnp.maximum(pn, 1.0)
    var = jnp.maximum(pss / jnp.maximum(pn, 1.0) - sketch0 * sketch0, 1e-12)
    sigma = jnp.sqrt(var)

    # --- fp32 safety: scale so values are O(1).  Exact equivariance.
    scale = (jnp.float32(scale_hint) if scale_hint is not None
             else jnp.maximum(jnp.abs(sketch0), sigma))
    scale = jnp.maximum(scale, 1e-12)
    vs = v / scale
    sk = sketch0 / scale
    sg = sigma / scale

    bounds = (sk - params.p2 * sg, sk - params.p1 * sg,
              sk + params.p1 * sg, sk + params.p2 * sg)

    # --- ISLA-E geometry from the pilot slice (O(1): one (3,2) psum).
    geometry = None
    if mode == "empirical":
        n_loc = v.shape[0]
        take = min(max(pilot_size, 2048), n_loc)  # geometry needs more mass
        stride = max(n_loc // take, 1)
        pv = jax.lax.slice(vs, (0,), (take * stride,), (stride,))
        geometry = pilot_band_geometry(pv, sk, sg, params, axis_names)

    # --- Phase 1 on a sampled subset.
    samp = subsample(vs, rate, key)
    mom_s, mom_l = moments(samp, bounds)

    if semantics == "merged":
        mom = _psum(jnp.concatenate([mom_s, mom_l]), axis_names)
        avg = phase2(mom[:4], mom[4:], sk, params, mode=mode,
                     geometry=geometry)
        return avg * scale
    elif semantics == "blocks":
        avg = phase2(mom_s, mom_l, sk, params, mode=mode, geometry=geometry)
        n_local = jnp.float32(samp.shape[0])
        acc = _psum(jnp.stack([avg * n_local, n_local]), axis_names)
        return (acc[0] / jnp.maximum(acc[1], 1.0)) * scale
    raise ValueError(f"unknown semantics {semantics}")


def exact_mean(values: jnp.ndarray, axis_names=None) -> jnp.ndarray:
    """The exact competitor: full local reduction + psum (for benchmarks)."""
    s = jnp.sum(values.astype(jnp.float32))
    n = jnp.float32(values.size)
    acc = _psum(jnp.stack([s, n]), axis_names)
    return acc[0] / acc[1]
