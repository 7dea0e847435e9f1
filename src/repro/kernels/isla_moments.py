"""Pallas TPU kernels for ISLA Phase 1: classify + masked moment reduction.

The paper's Alg. 1 is a scalar loop over samples; the TPU-native version is a
tiled, vectorized reduction: each grid step streams one (TM, 128) tile
HBM -> VMEM, computes the S/L masks on the VPU, and folds the tile's
sublane axis into a lane-dense (8, 128) accumulator block — one row per
moment (S: count, s1, s2, s3; L: the same), one partial sum per lane —
that every tile step of a cell maps to (sequential-grid accumulation, the
standard TPU reduction idiom).  The 128 lane partials are summed outside
the kernel.  Lane-dense rows keep every store a full-width vector store;
the region cuts are scalars and live in SMEM.

The *strided* variant is the fused "sample while reducing" path: the input
index_map selects every ``stride``-th tile, so HBM traffic is cut by the
sampling rate instead of gathering a sample first (which would read the full
tensor once AND write the sample).  Tile-granular sampling of i.i.d.-
positioned data is statistically equivalent to element sampling at the same
rate; see DESIGN.md §3.

Padding contract: callers pad the tail with any value strictly inside the N
region ((s_hi + l_lo)/2 is always safe) — N-region values contribute to
neither S nor L, so no validity mask is needed.

Every kernel takes ``interpret``: ``True`` runs the same body through the
Pallas interpreter (any backend, the CPU test tier), ``False`` compiles it
with Mosaic for the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128          # TPU vector lane width
DEFAULT_TM = 512    # rows per tile -> tile = 512*128*4B = 256 KiB VMEM
MOMENT_ROWS = 8     # (S, L) x (count, s1, s2, s3): one accumulator row each


def _moments_kernel(bounds_ref, prior_ref, x_ref, o_ref):
    """Grid (cell, tile): fold one tile into the cell's (1, 8, 128)
    lane-partial moment block.

    The accumulator is seeded from ``prior_ref`` instead of zeros — the
    online continuation (§VII-A): passing a previous round's moments as the
    prior operand merges the rounds on device without a second pass.
    ``bounds_ref`` is the cell's (1, 1, 4) row of region cuts in SMEM
    (one shared row, or the cell's own row of a per-cell table).
    """
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = prior_ref[...]

    x = x_ref[0].astype(jnp.float32)                      # (tm, 128)
    s_lo, s_hi = bounds_ref[0, 0, 0], bounds_ref[0, 0, 1]
    l_lo, l_hi = bounds_ref[0, 0, 2], bounds_ref[0, 0, 3]
    ms = ((x > s_lo) & (x < s_hi)).astype(jnp.float32)
    ml = ((x > l_lo) & (x < l_hi)).astype(jnp.float32)
    xs = x * ms
    xl = x * ml
    # rows: S (count, s1, s2, s3), then L (count, s1, s2, s3)
    for k, col in enumerate((ms, xs, xs * x, xs * x * x,
                             ml, xl, xl * x, xl * x * x)):
        o_ref[0, k:k + 1, :] += jnp.sum(col, axis=0, keepdims=True)


def _lane_prior(prior: jnp.ndarray) -> jnp.ndarray:
    """(n, 2, 4) moments -> the kernel's (n, 8, 128) lane-partial layout
    (each moment in lane 0, zeros elsewhere)."""
    n = prior.shape[0]
    return jnp.zeros((n, MOMENT_ROWS, LANE), jnp.float32).at[:, :, 0].set(
        prior.reshape(n, MOMENT_ROWS).astype(jnp.float32))


@functools.partial(jax.jit,
                   static_argnames=("tm", "stride", "interpret"))
def isla_moments_pallas(values2d: jnp.ndarray, bounds: jnp.ndarray,
                        tm: int = DEFAULT_TM, stride: int = 1,
                        interpret: bool = False,
                        prior: jnp.ndarray = None) -> jnp.ndarray:
    """Tiled ISLA moments.

    values2d: (rows, 128), rows % tm == 0; bounds: (4,) fp32
    (s_lo, s_hi, l_lo, l_hi).  stride > 1 reads every stride-th tile only.
    ``prior`` optionally seeds the accumulator with a previous round's
    (2, 4) moments (the §VII-A continuation merged in the same launch).
    Returns (2, 4) fp32 moments — the one-cell case of
    ``isla_moments_batched_pallas``.
    """
    if values2d.ndim != 2:
        raise ValueError(f"need (rows, {LANE}), got shape {values2d.shape}")
    if prior is not None and prior.shape != (2, 4):
        raise ValueError(f"prior must be (2, 4), got {prior.shape}")
    return isla_moments_batched_pallas(
        values2d[None], bounds, tm=tm, stride=stride, interpret=interpret,
        prior=None if prior is None else prior[None])[0]


@functools.partial(jax.jit,
                   static_argnames=("tm", "stride", "interpret"))
def isla_moments_batched_pallas(values3d: jnp.ndarray, bounds: jnp.ndarray,
                                tm: int = DEFAULT_TM, stride: int = 1,
                                interpret: bool = False,
                                prior: jnp.ndarray = None) -> jnp.ndarray:
    """Batched multi-block ISLA moments — Phase 1 for the batched engine.

    values3d: (n_blocks, rows, 128), rows % tm == 0; bounds: (4,) fp32 —
    or (n_blocks, 4) for PER-CELL anchor cuts (the per-key boundary-
    refinement path: each cell classifies under its own anchor's
    boundaries, pre-scaled into its frame, in the same single launch).
    Returns (n_blocks, 2, 4) fp32 moments — one launch feeds every block's
    8 scalars straight into the vectorized Phase 2
    (``repro.core.distributed.phase2`` on stacked rows).  ``stride`` is the
    fused sample-while-reducing path, per block.  ``prior`` optionally
    seeds every block's accumulator with its previous-round (n_blocks,
    2, 4) moments — the merge-capable online route: one launch both folds
    the fresh round and merges it into the store's state.
    """
    n_blocks, rows, lane = values3d.shape
    if lane != LANE:
        raise ValueError(f"last dim must be {LANE}, got {lane}")
    if rows % tm != 0:
        raise ValueError(f"rows {rows} not a multiple of tile rows {tm}")
    n_tiles = rows // tm
    n_sel = max(1, n_tiles // stride) if stride > 1 else n_tiles
    if prior is None:
        prior = jnp.zeros((n_blocks, 2, 4), jnp.float32)
    if prior.shape != (n_blocks, 2, 4):
        raise ValueError(f"prior must be ({n_blocks}, 2, 4), got "
                         f"{prior.shape}")

    per_cell = bounds.ndim == 2
    if per_cell and bounds.shape != (n_blocks, 4):
        raise ValueError(f"per-cell bounds must be ({n_blocks}, 4), got "
                         f"{bounds.shape}")
    grid_spec = pl.GridSpec(
        grid=(n_blocks, n_sel),
        in_specs=[
            # region cuts: one SMEM row per cell — the shared row for
            # every cell, or the cell's own row of a per-cell table (a
            # (1, 1, 4) block: its trailing dims equal the array's, which
            # satisfies the TPU block-tiling rule).
            pl.BlockSpec((1, 1, 4), ((lambda b, i: (b, 0, 0)) if per_cell
                                     else (lambda b, i: (0, 0, 0))),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, MOMENT_ROWS, LANE), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, tm, LANE), lambda b, i: (b, i * stride, 0)),
        ],
        out_specs=pl.BlockSpec((1, MOMENT_ROWS, LANE),
                               lambda b, i: (b, 0, 0)),
    )
    acc = pl.pallas_call(
        _moments_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, MOMENT_ROWS, LANE),
                                       jnp.float32),
        interpret=interpret,
    )(bounds.astype(jnp.float32).reshape(-1, 1, 4), _lane_prior(prior),
      values3d)
    return acc.sum(axis=-1).reshape(n_blocks, 2, 4)


def isla_moments_grouped_pallas(values4d: jnp.ndarray, bounds: jnp.ndarray,
                                tm: int = DEFAULT_TM, stride: int = 1,
                                interpret: bool = False,
                                prior: jnp.ndarray = None) -> jnp.ndarray:
    """Relational (group, block) ISLA moments — Phase 1 for the grouped
    engine axis.

    values4d: (n_groups, n_blocks, rows, 128), rows % tm == 0; bounds: (4,)
    fp32.  Returns (n_groups, n_blocks, 2, 4) fp32 moments.

    The segment mapping is the engine's ``flat_segments`` contract —
    segment id = ``group * n_blocks + block`` — realized as a plain reshape:
    the flattened leading axis IS the batched kernel's block axis, so the
    grouped axis reuses ``isla_moments_batched_pallas`` unchanged (one
    launch, one grid) and its output reshapes straight back to the
    (group, block) cells the vectorized Phase 2 consumes.  ``prior``
    ((n_groups, n_blocks, 2, 4)) seeds each cell's accumulator with its
    previous-round moments — the merge-capable online route.
    """
    if values4d.ndim != 4:
        raise ValueError(f"need (n_groups, n_blocks, rows, {LANE}), got "
                         f"shape {values4d.shape}")
    n_groups, n_blocks, rows, lane = values4d.shape
    flat = values4d.reshape(n_groups * n_blocks, rows, lane)
    if bounds.ndim == 3:  # per-cell anchor cuts on the (group, block) axis
        if bounds.shape != (n_groups, n_blocks, 4):
            raise ValueError(f"per-cell bounds must be ({n_groups}, "
                             f"{n_blocks}, 4), got {bounds.shape}")
        bounds = bounds.reshape(n_groups * n_blocks, 4)
    if prior is not None:
        if prior.shape != (n_groups, n_blocks, 2, 4):
            raise ValueError(f"prior must be ({n_groups}, {n_blocks}, 2, "
                             f"4), got {prior.shape}")
        prior = prior.reshape(n_groups * n_blocks, 2, 4)
    out = isla_moments_batched_pallas(flat, bounds, tm=tm, stride=stride,
                                      interpret=interpret, prior=prior)
    return out.reshape(n_groups, n_blocks, 2, 4)


@functools.partial(
    jax.jit,
    static_argnames=("params", "mode", "geometry", "tm", "stride",
                     "interpret"),
    donate_argnums=(2,))
def isla_fused_pallas(values3d: jnp.ndarray, bounds: jnp.ndarray,
                      prior: jnp.ndarray, sketch0: jnp.ndarray,
                      params, mode: str = "calibrated", geometry=None,
                      tm: int = DEFAULT_TM, stride: int = 1,
                      interpret: bool = False,
                      inv_scale: jnp.ndarray = None,
                      active_cells: jnp.ndarray = None):
    """Fused Phase 1 + Phase 2: one launch from samples to answers.

    Chains the batched Pallas moment accumulation (seeded from the
    DONATED ``prior`` accumulator — the device-resident continuation)
    straight into the branchless Phase 2 solve
    (``repro.core.distributed.phase2``) inside one jit, so a dense-layout
    continuation round costs a single launch instead of
    moments -> host -> phase2.

    values3d: (n_cells, rows, 128) — the flattened (group, block) cell
    axis; bounds (4,) — or (n_cells, 4) for per-key refined anchors —
    and ``sketch0`` (scalar or (n_cells,)) on the same (pre-scaled) value
    axis as ``values3d``; ``prior`` (n_cells, 2, 4) is consumed and
    replaced by the merged moments.  ``inv_scale`` is the per-cell
    anchor-scale vector: each cell's Phase 2 stopping threshold (and the
    ISLA-E ``b0``) is divided into that cell's normalized frame, exactly
    as in ``distributed.fused_tick``.

    ``active_cells`` is the zone-pruned compacted launch: an (n_active,)
    int32 vector of resident cell ids (pads out-of-bounds), with
    ``values3d`` covering ONLY those cells.  The kernel grid runs over
    the compact axis — seeded from the gathered prior rows — and the
    merged rows scatter back (``mode="drop"``); pruned cells' rows are
    never addressed, so they stay warm, while Phase 2 still solves the
    FULL cell axis.  Pad rows must honor the in-N padding contract.

    Returns ``(moments, partials)``: the merged (n_cells, 2, 4) state —
    feed it back as the next round's ``prior`` — and the (n_cells,)
    Phase 2 partial answers.
    """
    from repro.core.distributed import _scaled_solve_args, phase2

    if active_cells is None:
        mom = isla_moments_batched_pallas(values3d, bounds, tm=tm,
                                          stride=stride,
                                          interpret=interpret, prior=prior)
    else:
        b = bounds if bounds.ndim == 1 else bounds[active_cells]
        mom_c = isla_moments_batched_pallas(
            values3d, b, tm=tm, stride=stride, interpret=interpret,
            prior=prior[active_cells])
        mom = prior.at[active_cells].set(mom_c, mode="drop")
    if geometry is not None:
        geometry = (jnp.float32(geometry[0]), jnp.float32(geometry[1]))
    thr, geometry = _scaled_solve_args(params, geometry, inv_scale)
    partials = phase2(mom[:, 0, :], mom[:, 1, :], sketch0, params,
                      mode=mode, geometry=geometry, thr=thr)
    return mom, partials


REG_ROWS = 32       # HLL register block: 4096 registers = (32, 128) tile
                    # — exactly the int8 minimum TPU tile, so one cell's
                    # registers are one native uint8 VMEM block.


def _sketch_kernel(hi_ref, lo_ref, valid_ref, prior_ref, o_ref, key_ref,
                   regs_ref):
    """Grid (cell, tile): merge one (tm, 128) hash-limb tile into the
    cell's HLL registers (elementwise max accumulation).

    The hash and the (j, rho) encoding are the shared in-graph uint32-limb
    twins from ``repro.core.sketch`` — the SAME traced arithmetic the
    fused jnp tick runs, so the Pallas route is bit-identical by
    construction.  The scatter is realized as a one-hot lane-max on the
    VPU: for register row ``rr`` and each sample row of the tile, a
    (128 target lanes, 128 samples) one-hot of the samples landing on
    that row is max-folded into a register-resident (128, 128) int32
    accumulator; one transpose and a sublane max turn it into the row's
    128 lanes.  The live intermediate is one (128, 128) block, whatever
    ``tm`` is.

    Registers stay int32 in the ``regs_ref`` scratch across the cell's
    tiles (seeded from ``prior_ref``) and are cast to uint8 once, at the
    store after the cell's last tile.  ``valid_ref`` masks pad lanes to
    rho = 0 — the merge's neutral element — because unlike the moments'
    in-N padding contract a pad value's hash would otherwise hit a real
    register.
    """
    from repro.core import sketch as _sk

    tile = pl.program_id(1)

    @pl.when(tile == 0)
    def _init():
        regs_ref[...] = prior_ref[0].astype(jnp.int32)

    j, rho = _sk.encode_graph(*_sk.splitmix64_graph(hi_ref[0], lo_ref[0]),
                              rho_dtype=jnp.int32)
    rho = jnp.where(valid_ref[0] != 0, rho, 0)
    key_ref[...] = (j << 8) | rho              # j < 2^12, rho < 2^6
    target = jax.lax.broadcasted_iota(jnp.int32, (LANE, LANE), 0)

    def reg_row(rr, carry):
        def sample_row(r, acc):
            key = key_ref[pl.ds(r, 1), :]      # (1, 128) samples
            jj = key >> 8
            lane = jnp.where((jj >> 7) == rr, jj & (LANE - 1), -1)
            hit = target == jnp.broadcast_to(lane, (LANE, LANE))
            return jnp.maximum(acc, jnp.where(
                hit, jnp.broadcast_to(key & 0xFF, (LANE, LANE)), 0))

        acc = jax.lax.fori_loop(0, key_ref.shape[0], sample_row,
                                jnp.zeros((LANE, LANE), jnp.int32))
        row = jnp.max(acc.T, axis=0, keepdims=True)          # (1, 128)
        regs_ref[pl.ds(rr, 1), :] = jnp.maximum(
            regs_ref[pl.ds(rr, 1), :], row)
        return carry

    jax.lax.fori_loop(0, REG_ROWS, reg_row, 0)

    @pl.when(tile == pl.num_programs(1) - 1)
    def _store():
        o_ref[0] = regs_ref[...].astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def isla_sketch_pallas(hash_hi3d: jnp.ndarray, hash_lo3d: jnp.ndarray,
                       valid3d: jnp.ndarray, tm: int = DEFAULT_TM,
                       interpret: bool = False,
                       prior: jnp.ndarray = None) -> jnp.ndarray:
    """Tiled HLL register merge — the sketch plane's Phase 1 twin.

    hash_hi3d / hash_lo3d: (n_cells, rows, 128) uint32 — the raw measure
    bits as ``sketch.value_limbs`` panes, rows % tm == 0; valid3d: same
    shape, nonzero on real samples (pad lanes scatter the neutral
    rho = 0).  ``prior`` optionally seeds each cell's register block with
    its previous-round (n_cells, 32, 128) uint8 state — like the moments
    prior, one launch both folds the fresh round and merges it into the
    store's plane (merge = max makes ANY tick partition bit-identical).
    Returns (n_cells, 32, 128) uint8 registers; ``.reshape(n_cells,
    4096)`` is the ``MomentStore.regs`` layout.
    """
    n_cells, rows, lane = hash_hi3d.shape
    if lane != LANE:
        raise ValueError(f"last dim must be {LANE}, got {lane}")
    if rows % tm != 0:
        raise ValueError(f"rows {rows} not a multiple of tile rows {tm}")
    n_tiles = rows // tm
    if prior is None:
        prior = jnp.zeros((n_cells, REG_ROWS, LANE), jnp.uint8)
    if prior.shape != (n_cells, REG_ROWS, LANE):
        raise ValueError(f"prior must be ({n_cells}, {REG_ROWS}, {LANE}), "
                         f"got {prior.shape}")

    grid_spec = pl.GridSpec(
        grid=(n_cells, n_tiles),
        in_specs=[
            pl.BlockSpec((1, tm, LANE), lambda c, i: (c, i, 0)),
            pl.BlockSpec((1, tm, LANE), lambda c, i: (c, i, 0)),
            pl.BlockSpec((1, tm, LANE), lambda c, i: (c, i, 0)),
            pl.BlockSpec((1, REG_ROWS, LANE), lambda c, i: (c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, REG_ROWS, LANE), lambda c, i: (c, 0, 0)),
        scratch_shapes=[pltpu.VMEM((tm, LANE), jnp.int32),
                        pltpu.VMEM((REG_ROWS, LANE), jnp.int32)],
    )
    return pl.pallas_call(
        _sketch_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_cells, REG_ROWS, LANE),
                                       jnp.uint8),
        interpret=interpret,
    )(hash_hi3d, hash_lo3d, valid3d, prior)


@functools.partial(
    jax.jit,
    static_argnames=("params", "mode", "geometry", "tm", "stride",
                     "interpret"),
    donate_argnums=(2, 3))
def isla_fused_sketch_pallas(values3d: jnp.ndarray, bounds: jnp.ndarray,
                             prior: jnp.ndarray, prior_regs: jnp.ndarray,
                             hash_hi3d: jnp.ndarray,
                             hash_lo3d: jnp.ndarray,
                             valid3d: jnp.ndarray, sketch0: jnp.ndarray,
                             params, mode: str = "calibrated",
                             geometry=None, tm: int = DEFAULT_TM,
                             stride: int = 1, interpret: bool = False,
                             inv_scale: jnp.ndarray = None):
    """``isla_fused_pallas`` with the register pane riding the launch:
    Phase 1 moments, the HLL register merge, and the branchless Phase 2
    solve chained in ONE jit over the same donated accumulators — the
    kernel-route twin of ``distributed.fused_tick_dense`` with its
    register plane (``regs``).

    ``prior`` (n_cells, 2, 4) and ``prior_regs`` (n_cells, 32, 128) are
    both consumed and replaced.  The hash panes carry the RAW measure
    bits (``sketch.value_limbs``), never the scaled/shifted pane values.
    Returns ``(moments, regs, partials)``.
    """
    from repro.core.distributed import _scaled_solve_args, phase2

    mom = isla_moments_batched_pallas(values3d, bounds, tm=tm,
                                      stride=stride, interpret=interpret,
                                      prior=prior)
    regs = isla_sketch_pallas(hash_hi3d, hash_lo3d, valid3d, tm=tm,
                              interpret=interpret, prior=prior_regs)
    if geometry is not None:
        geometry = (jnp.float32(geometry[0]), jnp.float32(geometry[1]))
    thr, geometry = _scaled_solve_args(params, geometry, inv_scale)
    partials = phase2(mom[:, 0, :], mom[:, 1, :], sketch0, params,
                      mode=mode, geometry=geometry, thr=thr)
    return mom, regs, partials


def _pilot_kernel(x_ref, o_ref):
    """Grid (tile,): fold one tile into the (4, 128) lane-partial
    (count, sum, sumsq, min) block."""
    x = x_ref[...].astype(jnp.float32)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[0:3, :] = jnp.zeros((3, LANE), jnp.float32)
        o_ref[3:4, :] = jnp.full((1, LANE), jnp.inf, jnp.float32)

    o_ref[0:1, :] += jnp.full((1, LANE), x.shape[0], jnp.float32)
    o_ref[1:2, :] += jnp.sum(x, axis=0, keepdims=True)
    o_ref[2:3, :] += jnp.sum(x * x, axis=0, keepdims=True)
    o_ref[3:4, :] = jnp.minimum(o_ref[3:4, :],
                                jnp.min(x, axis=0, keepdims=True))


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def pilot_stats_pallas(values2d: jnp.ndarray, tm: int = DEFAULT_TM,
                       interpret: bool = False) -> jnp.ndarray:
    """Fused pre-estimation statistics: (count, sum, sumsq, min)."""
    rows, lane = values2d.shape
    if lane != LANE:
        raise ValueError(f"last dim must be {LANE}, got {lane}")
    if rows % tm != 0:
        raise ValueError(f"rows {rows} not a multiple of tile rows {tm}")
    n_tiles = rows // tm
    grid_spec = pl.GridSpec(
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((tm, LANE), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((4, LANE), lambda i: (0, 0)),
    )
    acc = pl.pallas_call(
        _pilot_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((4, LANE), jnp.float32),
        interpret=interpret,
    )(values2d)
    return jnp.concatenate([acc[:3].sum(axis=-1), acc[3:].min(axis=-1)])
