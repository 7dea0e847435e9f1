"""Persistent (group, block) moment state — the online mode as a subsystem.

The paper's signature big-data claim (§VII-A) is that a block's entire
sampling state is its 8 streaming moments, so answers can be refined round
after round without ever recording sampled rows.  ``MomentStore`` is that
state lifted onto the relational (group, block) axis PR 1-2 built:

 * ``mom_s`` / ``mom_l`` — stacked (n_groups * n_blocks, 4) float64 region
   moment rows on the flattened ``engine.flat_segments`` axis;
 * ``totals`` — (n_groups * n_blocks, 3) plain (count, s1, s2) rows of ALL
   matching samples per cell (the extra accumulators VAR / COUNT / group
   weights compose from);
 * ``n_sampled`` — (n_blocks,) cumulative per-block draws (including
   masked-out rows — the denominator of selectivity-scaled cell weights);
 * ``rounds``, plus the anchor the moments were accumulated under:
   ``boundaries`` (region cuts are FROZEN for the store's lifetime — merged
   moments cannot be re-classified), the Phase 2 ``sketch0`` (re-anchorable,
   see ``reanchor``) and the footnote-1 ``shift``.

``ingest`` merges a fresh tagged pass through the engine's carry-prepend
bincount continuation, so k short rounds are **bit-identical** per cell to
one pass over the concatenated stream; ``continue_rounds`` is the
vectorized §VII-A loop (draw, merge, re-run batched Phase 2), and
``split_budget`` is the deadline-aware allocator the serving tier uses to
divide a tick's sample budget across warm stores by marginal-error
reduction.

The DEVICE-RESIDENT layer (PR 4) keeps that state where the compute is:
``DeviceMomentStore`` holds the same rows as jax arrays between ticks,
``DeviceStack`` concatenates the warm stores of a mode-group onto one
stacked cell axis, and a continuation round is ONE fused donated launch
(``distributed.fused_tick`` / ``fused_tick_dense``) — the host touches
only scalar answers and O(groups) statistics in steady state.  Stores
may carry PER-KEY refined anchors (``types.Anchor``): the stack groups
its cells by anchor (per-cell bounds table, inverse-anchor-scale
vector, per-key dense-pane affines) so hetero-anchor keys still share
the single launch.  ``iter_chunked_draws`` is the SHARED chunked draw
loop both serving draw paths ride (the RNG-order / quota-padding /
round-count contract).
"""
from __future__ import annotations

import collections
import dataclasses
import math
from typing import Optional, Sequence

import numpy as np

from . import sketch as _sketch
from .engine import (Sampler, block_quotas, flat_segments,
                     phase1_sampling_batch, phase2_iteration_batch,
                     sample_moments_batch)
from .modulation import ModulationBatchResult
from .summarize import summarize
from .types import Anchor, Boundaries, IslaParams


@dataclasses.dataclass
class DrawChunk:
    """One chunk of the shared chunked block-draw loop (see
    ``iter_chunked_draws``)."""

    start: int              # first block of the chunk (inclusive)
    end: int                # one past the last block of the chunk
    idx: "list[int]"        # blocks actually drawn (quota > 0), block order
    raws: list              # raw sampler outputs, aligned with ``idx``
    chunk_quotas: np.ndarray  # (n_blocks,) int64 — this chunk's quota rows
    first: bool             # True for the first non-empty chunk of the pass


def iter_chunked_draws(block_samplers: Sequence[Sampler],
                       quotas: np.ndarray, rng: np.random.Generator,
                       chunk_blocks: Optional[int] = None,
                       assemble=None, timings=None):
    """THE chunked draw loop: the RNG-order / quota-padding / round-count
    contract shared by ``multiquery._draw_and_ingest`` (row samplers
    fanning into several stores) and ``MomentStore.continue_rounds``
    (scalar samplers into one).  Both paths iterate this generator so they
    cannot silently diverge:

     * **RNG order** — samplers are invoked strictly in block order, one
       call per block with that block's full quota; zero-quota blocks are
       skipped WITHOUT consuming the RNG (deficit top-ups leave satisfied
       blocks' streams untouched).
     * **quota padding** — each chunk yields a full-width ``(n_blocks,)``
       quota row that is zero outside ``[start, end)``, so ingesting a
       chunk advances every store's cumulative ledger identically to the
       unchunked pass.
     * **round count** — exactly one yielded chunk carries ``first=True``
       (the first chunk that draws anything), so callers count one logical
       round per pass regardless of chunking; an all-zero pass yields
       nothing and counts no round.

    Each chunk's sampler calls run inside one ``draw.sample`` stage
    (``distributed.stage_trace``, booked into ``timings``), together with
    ``assemble(chunk)`` when given — the caller's column assembly, whose
    result is yielded in the chunk's place.
    """
    from . import distributed as D

    n_b = len(block_samplers)
    quotas = np.asarray(quotas, dtype=np.int64).reshape(-1)
    if quotas.shape != (n_b,):
        raise ValueError(f"quotas must be ({n_b},), got {quotas.shape}")
    step = n_b if chunk_blocks is None else int(chunk_blocks)
    if step < 1:
        raise ValueError(f"chunk_blocks must be >= 1, got {chunk_blocks}")
    first = True
    for start in range(0, n_b, step):
        end = min(start + step, n_b)
        idx = [j for j in range(start, end) if quotas[j] > 0]
        if not idx:
            continue
        with D.stage_trace("draw.sample", timings):
            raws = [block_samplers[j](int(quotas[j]), rng) for j in idx]
            chunk_quotas = np.zeros(n_b, dtype=np.int64)
            chunk_quotas[start:end] = quotas[start:end]
            chunk = DrawChunk(start=start, end=end, idx=idx, raws=raws,
                              chunk_quotas=chunk_quotas, first=first)
            out = chunk if assemble is None else assemble(chunk)
        yield out
        first = False


def block_deficit(n_sampled: np.ndarray, target_quotas: Sequence[int],
                  n_blocks: int) -> np.ndarray:
    """Per-block samples still owed against a target quota — THE deficit
    formula both store flavors plan with (host ``MomentStore`` and the
    device mirror share it so host- and device-route planning cannot
    desynchronize)."""
    target = np.asarray(target_quotas, dtype=np.int64).reshape(-1)
    if target.shape != (n_blocks,):
        raise ValueError(f"target quotas must be ({n_blocks},), got "
                         f"{target.shape}")
    return np.maximum(target - n_sampled, 0)


@dataclasses.dataclass
class MomentStore:
    """Everything the online mode persists between rounds — O(cells), not
    O(samples)."""

    n_blocks: int
    n_groups: int
    boundaries: Boundaries
    sketch0: float            # shifted-scale Phase 2 anchor (re-anchorable)
    shift: float
    mom_s: np.ndarray         # (n_groups * n_blocks, 4) S-region moments
    mom_l: np.ndarray         # (n_groups * n_blocks, 4) L-region moments
    totals: np.ndarray        # (n_groups * n_blocks, 3) all-sample moments
    n_sampled: np.ndarray     # (n_blocks,) cumulative draws, int64
    rounds: int = 0
    has_regions: bool = True  # False: totals-only store (COUNT-only keys)
    has_totals: bool = True   # False: regions-only (plain AVG/SUM passes
                              # — nothing reads weights/ex2/sample_sigma)
    anchor: Optional[Anchor] = None  # provenance of the frozen frame; its
                              # fingerprint keys warm-store reuse (a key
                              # whose anchor changed cannot merge moments)
    has_sketch: bool = False  # True: an HLL register plane rides every
                              # ingest (COUNT DISTINCT state)
    regs: Optional[np.ndarray] = None  # (n_cells, sketch.M) uint8 HLL
                              # registers; merge = elementwise max, so any
                              # tick partition folds bit-identically

    @staticmethod
    def fresh(n_blocks: int, boundaries: Boundaries, sketch0: float,
              shift: float = 0.0, n_groups: int = 1,
              has_regions: bool = True,
              has_totals: bool = True,
              anchor: Optional[Anchor] = None,
              has_sketch: bool = False) -> "MomentStore":
        if n_blocks < 1 or n_groups < 1:
            raise ValueError(f"need n_blocks, n_groups >= 1; got "
                             f"({n_blocks}, {n_groups})")
        if not (has_regions or has_totals):
            raise ValueError("a store must accumulate regions, totals, or "
                             "both")
        n_cells = n_groups * n_blocks
        return MomentStore(
            n_blocks=n_blocks, n_groups=n_groups, boundaries=boundaries,
            sketch0=float(sketch0), shift=float(shift),
            mom_s=np.zeros((n_cells, 4)), mom_l=np.zeros((n_cells, 4)),
            totals=np.zeros((n_cells, 3)),
            n_sampled=np.zeros(n_blocks, dtype=np.int64),
            has_regions=has_regions, has_totals=has_totals, anchor=anchor,
            has_sketch=has_sketch,
            regs=(np.zeros((n_cells, _sketch.M), dtype=np.uint8)
                  if has_sketch else None))

    @staticmethod
    def from_anchor(n_blocks: int, anchor: Anchor, n_groups: int = 1,
                    has_regions: bool = True,
                    has_totals: bool = True,
                    has_sketch: bool = False) -> "MomentStore":
        """``fresh`` with the frame taken wholesale from an ``Anchor`` —
        the per-key construction path of the incremental executor."""
        return MomentStore.fresh(
            n_blocks, anchor.boundaries, anchor.sketch0,
            shift=anchor.shift, n_groups=n_groups,
            has_regions=has_regions, has_totals=has_totals, anchor=anchor,
            has_sketch=has_sketch)

    @property
    def n_cells(self) -> int:
        return self.n_groups * self.n_blocks

    @property
    def total_sampled(self) -> int:
        return int(self.n_sampled.sum())

    # -- accumulation ------------------------------------------------------

    def ingest(self, values: np.ndarray, block_ids: np.ndarray,
               quotas: np.ndarray, *,
               group_ids: Optional[np.ndarray] = None,
               mask: Optional[np.ndarray] = None,
               chunk_size: Optional[int] = None,
               count_round: bool = True,
               raw_values: Optional[np.ndarray] = None) -> None:
        """Merge one tagged pass into the store.

        ``values`` are on the SHIFTED scale (the caller applies
        ``self.shift``); ``quotas`` is the per-block draw count this pass
        (a (n_blocks,) array — zero for blocks the pass skipped).  The
        merge routes the store's prior rows through the engine's carry, so
        the result is bit-identical per cell to a single accumulation over
        the concatenated stream.

        ``count_round=False`` marks this ingest as a continuation chunk of
        the current logical round (block-chunked draws), so ``rounds``
        counts refinement rounds, not chunks.

        ``raw_values`` (sketch stores) are the UN-shifted measure values —
        the HLL hash-input contract keys registers on raw float64 bits so
        every route and anchor builds the identical plane.  When omitted,
        the store reconstructs them as ``values - shift`` (bit-exact only
        for shift == 0; shifted stores should pass the raw stream).
        """
        quotas = np.asarray(quotas, dtype=np.int64).reshape(-1)
        if quotas.shape != (self.n_blocks,):
            raise ValueError(f"quotas must be ({self.n_blocks},), got "
                             f"{quotas.shape}")
        # Skip the carry only when the store holds nothing at all — NOT
        # merely when rounds == 0, so a store seeded with prior moments
        # (e.g. OnlineBlockState.as_store of a run_block result) merges
        # instead of silently overwriting.  The empty-carry path and a
        # zero-carry prepend are bit-identical; skipping is just cheaper.
        first = (self.rounds == 0 and not self.mom_s.any()
                 and not self.mom_l.any() and not self.totals.any())
        if self.has_regions:
            self.mom_s, self.mom_l = phase1_sampling_batch(
                values, block_ids, self.n_blocks, self.boundaries,
                group_ids=group_ids, n_groups=self.n_groups, mask=mask,
                chunk_size=chunk_size,
                carry=None if first else (self.mom_s, self.mom_l))
        if self.has_totals:
            self.totals = sample_moments_batch(
                values, block_ids, self.n_blocks, group_ids=group_ids,
                n_groups=self.n_groups, mask=mask,
                carry=None if first else self.totals)
        if self.has_sketch:
            raw = (np.asarray(raw_values, dtype=np.float64).reshape(-1)
                   if raw_values is not None
                   else np.asarray(values, dtype=np.float64).reshape(-1)
                   - self.shift)
            seg, _ = flat_segments(
                np.asarray(block_ids).reshape(-1).astype(np.intp),
                self.n_blocks, group_ids, self.n_groups)
            if mask is not None:
                keep = np.asarray(mask, dtype=bool).reshape(-1)
                raw, seg = raw[keep], seg[keep]
            j, rho = _sketch.encode(_sketch.hash_values(raw))
            _sketch.scatter_max(self.regs, seg, j, rho)
        self.n_sampled = self.n_sampled + quotas
        if count_round:
            self.rounds += 1

    # -- sketch plane ------------------------------------------------------

    def group_registers(self) -> np.ndarray:
        """The per-group folded register rows — max over the block axis
        (the mergeable-sketch group aggregate)."""
        if not self.has_sketch:
            raise ValueError("store was built without a sketch plane "
                             "(has_sketch=False)")
        return _sketch.fold_groups(self.regs, self.n_groups)

    def distinct_counts(self) -> np.ndarray:
        """(n_groups,) HLL COUNT DISTINCT estimates of the matching
        measure values seen so far."""
        return _sketch.estimate(self.group_registers())

    # -- solving -----------------------------------------------------------

    def solve(self, params: IslaParams, mode: str = "faithful",
              geometry=None) -> ModulationBatchResult:
        """Re-run the batched Phase 2 over the merged moments (host path;
        the device route feeds ``mom_s``/``mom_l`` to ``distributed.phase2``
        itself)."""
        if not self.has_regions:
            raise ValueError("totals-only store has no region moments to "
                             "solve (built with has_regions=False)")
        return phase2_iteration_batch(self.mom_s, self.mom_l, self.sketch0,
                                      params, mode=mode, geometry=geometry)

    def answer(self, avg: np.ndarray, block_sizes: Sequence[int]) -> float:
        """Summarize per-block partials to the un-shifted grand answer
        (n_groups == 1 stores; grouped stores compose via multiquery)."""
        if self.n_groups != 1:
            raise ValueError("grand answer is the ungrouped summarization; "
                             "grouped stores compose per group")
        return summarize(np.asarray(avg).reshape(-1), list(block_sizes)) \
            - self.shift

    def reanchor(self, avg: np.ndarray) -> float:
        """Re-anchor ``sketch0`` from the merged moments: the cell-count-
        weighted mean of the current partial answers (shifted scale).

        Later rounds then iterate against the refined picture instead of
        the initial rough sketch — the §VII-A continuation bugfix.  Cells
        with no samples carry no weight; an all-empty store keeps its
        anchor.
        """
        w = (self.totals[:, 0] if self.has_totals
             else self.mom_s[:, 0] + self.mom_l[:, 0])
        populated = w > 0
        if self.has_regions and np.any(populated):
            a = np.asarray(avg, dtype=np.float64).reshape(-1)
            self.sketch0 = float(np.sum(a[populated] * w[populated])
                                 / np.sum(w[populated]))
        return self.sketch0

    def continue_rounds(self, block_samplers: Sequence[Sampler],
                        block_sizes: Sequence[int], rate: float,
                        params: IslaParams, rng: np.random.Generator,
                        mode: str = "faithful", geometry=None,
                        max_samples: Optional[int] = None,
                        reanchor: bool = False,
                        chunk_blocks: Optional[int] = None,
                        chunk_size: Optional[int] = None
                        ) -> ModulationBatchResult:
        """One more online round, vectorized: draw a fresh tagged pass at
        ``rate`` (per block, block order — the engine's RNG stream), merge
        it into the store, and re-run the batched Phase 2.

        Parameters
        ----------
        block_samplers : sequence of callables
            ``sampler(n, rng) -> (n,) values`` per block, invoked in block
            order (the engine's RNG-stream contract).
        block_sizes : sequence of int
            Catalog block sizes (drive the per-block quotas).
        rate : float
            Sampling rate for this round (Eq. 1 scale; per-block quota is
            ``ceil(rate * block_size)``).
        params : IslaParams
            Phase 2 tunables.
        rng : numpy.random.Generator
            Host RNG the draw consumes.
        mode : str, optional
            Phase 2 solver ("faithful" maps onto its algebraic closed
            form — the batched path never runs a data-dependent loop).
        geometry : tuple, optional
            ``(kappa, b0)`` pilot geometry, required for
            ``mode="empirical"``.
        max_samples : int, optional
            Per-block quota cap (the §VII-F time-constraint extension).
        reanchor : bool, optional
            Refresh ``sketch0`` from the merged answer after solving, so
            the NEXT round iterates against the refined picture instead of
            the round-0 rough sketch.  The frozen part of the anchor
            (boundaries, shift) never moves.
        chunk_blocks : int, optional
            Draw and fold the round that many blocks at a time — the
            stream is never materialized whole, bit-identical via the
            carry contract.
        chunk_size : int, optional
            Phase 1 prefix-chunking within an ingest (same bit-identity).

        Returns
        -------
        ModulationBatchResult
            Per-block partial answers over the MERGED moments (shifted
            scale; ``answer`` composes the un-shifted grand mean).
        """
        if len(block_samplers) != self.n_blocks:
            raise ValueError(f"store holds {self.n_blocks} blocks, got "
                             f"{len(block_samplers)} samplers")
        if self.n_groups != 1:
            raise ValueError("continue_rounds draws ungrouped streams; "
                             "grouped stores are fed via multiquery")
        quotas = np.asarray(block_quotas(block_sizes, rate, max_samples),
                            dtype=np.int64)
        for chunk in iter_chunked_draws(block_samplers, quotas, rng,
                                        chunk_blocks):
            vals = np.concatenate([np.asarray(r, dtype=np.float64)
                                   for r in chunk.raws]) + self.shift
            ids = np.repeat(np.asarray(chunk.idx, dtype=np.intp),
                            quotas[chunk.idx])
            self.ingest(vals, ids, chunk.chunk_quotas,
                        chunk_size=chunk_size, count_round=chunk.first)
        res = self.solve(params, mode=mode, geometry=geometry)
        if reanchor:
            self.reanchor(res.avg)
        return res

    # -- planning helpers --------------------------------------------------

    def deficit(self, target_quotas: Sequence[int]) -> np.ndarray:
        """Per-block samples still owed against a target quota (what a new
        query's (e, beta) demands minus what the store already drew)."""
        return block_deficit(self.n_sampled, target_quotas, self.n_blocks)

    def matched_total(self) -> float:
        """Total matching samples accumulated (the budget splitter's n)."""
        return float(self.totals[:, 0].sum())

    def sample_sigma(self) -> float:
        """ddof-1 sigma of all matching samples seen so far (NaN until two
        samples exist) — the marginal-error signal ``split_budget`` reads."""
        n = float(self.totals[:, 0].sum())
        if n < 2:
            return float("nan")
        mean = float(self.totals[:, 1].sum()) / n
        var = max(float(self.totals[:, 2].sum()) / n - mean * mean, 0.0)
        return math.sqrt(var * n / (n - 1.0))


# ---------------------------------------------------------------------------
# Device-resident stores: the §VII-A state kept where the compute is.
# ---------------------------------------------------------------------------


def _bucket(m: int, floor: int = 256) -> int:
    """Round a tick's matched-sample count up to a power-of-two bucket so
    the fused launch does not retrace on every tick (padded slots land in
    the drop segment)."""
    b = floor
    while b < m:
        b <<= 1
    return b


def _dense_panes(values: np.ndarray, quotas: np.ndarray):
    """Pack a block-major tagged stream into (n_blocks, quota_bucket)
    panes for the mesh's dense fused tick (one upload each; the
    single-device tick packs all of them with ``_packed_panes``):
    row-major assignment through the ragged-quota mask preserves stream
    order, the pad mask zeroes the tail."""
    quotas = np.asarray(quotas, dtype=np.int64)
    qmax = _bucket(int(quotas.max()), floor=8)
    vmask = np.arange(qmax)[None, :] < quotas[:, None]
    v2d = np.zeros((quotas.shape[0], qmax), dtype=np.float64)
    v2d[vmask] = values
    pad = np.zeros_like(v2d)
    pad[vmask] = 1.0
    return v2d, pad, vmask


def _key_slots(key_gids, key_valids):
    """Each stacked key's GROUP BY codes and predicate mask, deduped by
    host-array identity: ``(distinct codes, gid slots, distinct masks,
    mask slots)``, slot -1 where a key has none.  One pane per distinct
    array, and the STATIC slot indices let the fused program batch keys
    that share a GROUP BY pane into one contraction (traced-operand
    identity is invisible inside jit)."""
    gids_out, valids_out = [], []
    gid_slots, valid_slots = [], []
    seen_g, seen_v = {}, {}
    for gids, valid in zip(key_gids, key_valids):
        for arr, seen, out, slots in ((gids, seen_g, gids_out, gid_slots),
                                      (valid, seen_v, valids_out,
                                       valid_slots)):
            if arr is None:
                slots.append(-1)
                continue
            if id(arr) not in seen:
                seen[id(arr)] = len(out)
                out.append(arr)
            slots.append(seen[id(arr)])
    return gids_out, tuple(gid_slots), valids_out, tuple(valid_slots)


def _key_panes(vmask: np.ndarray, key_gids, key_valids):
    """The dense tick's deduped GROUP BY code and predicate-mask panes
    (``_key_slots``): ``(gid panes, gid slots, mask panes, mask slots)``,
    int32 codes and float64 masks, zero in the ragged tail."""
    gids, gid_slots, valids, valid_slots = _key_slots(key_gids, key_valids)
    gid_panes, valid_panes = [], []
    for g in gids:
        g2d = np.zeros(vmask.shape, dtype=np.int32)
        g2d[vmask] = np.asarray(g).reshape(-1)
        gid_panes.append(g2d)
    for valid in valids:
        m2d = np.zeros(vmask.shape, dtype=np.float64)
        m2d[vmask] = np.asarray(valid, dtype=np.float64).reshape(-1)
        valid_panes.append(m2d)
    return gid_panes, gid_slots, valid_panes, valid_slots


def _packed_panes(pane_vals: np.ndarray, quotas: np.ndarray, key_gids,
                  key_valids, dtype, hash_values=None):
    """The single-device dense tick's one upload: every pane operand of
    the deduped keys (``_key_slots``) packed per ``distributed.PaneLayout``
    in the stack's ``dtype``.  No pad pane: the launch derives it from the
    quota column.  ``hash_values`` (a sketch stack's RAW stream) adds the
    (hi, lo) hash-limb panes.  Returns ``(buffer, layout, gid slots, mask
    slots)``."""
    from . import distributed as D

    quotas = np.asarray(quotas, dtype=np.int64)
    gids, gid_slots, valids, valid_slots = _key_slots(key_gids, key_valids)
    hashes = (() if hash_values is None
              else _sketch.value_limbs(hash_values))
    layout = D.PaneLayout(quotas.size, _bucket(int(quotas.max()), floor=8),
                          len(valids), len(gids), len(hashes))
    buf = layout.pack(dtype, quotas, pane_vals, valids, gids, hashes)
    return buf, layout, gid_slots, valid_slots


def _hash_panes(values: np.ndarray, vmask: np.ndarray):
    """The mesh dense tick's (hi, lo) register-hash panes of the RAW
    values."""
    hhi, hlo = _sketch.value_limbs(values)
    hi2d = np.zeros(vmask.shape, dtype=np.uint32)
    lo2d = np.zeros(vmask.shape, dtype=np.uint32)
    hi2d[vmask] = hhi
    lo2d[vmask] = hlo
    return hi2d, lo2d


def _tagged_panes(values: np.ndarray, seg: np.ndarray, drop: int,
                  hash_limbs=None):
    """The tagged tick's bucket-padded value and cell-id streams (pad
    slots carry the ``drop`` id) and, given ``hash_limbs``, its padded
    (hi, lo) hash streams (else None)."""
    m = values.size
    bucket = _bucket(m)
    v_pad = np.zeros(bucket, dtype=np.float64)
    v_pad[:m] = values
    s_pad = np.full(bucket, drop, dtype=np.int32)
    s_pad[:m] = seg
    h_pads = None
    if hash_limbs is not None:
        h_pads = (np.zeros(bucket, dtype=np.uint32),
                  np.zeros(bucket, dtype=np.uint32))
        h_pads[0][:m] = hash_limbs[0]
        h_pads[1][:m] = hash_limbs[1]
    return v_pad, s_pad, h_pads


class _LazyRows:
    """Deferred stat-row readout of ONE fused launch, shared by every
    store of its stack.

    The pipelined tick must not block the host on ``np.asarray(rows)``
    while later mode-groups still have samples to draw and stage, so
    ``_install_stats(..., defer=True)`` hands each store a slice view of
    this holder instead of a materialized numpy array: the device handle
    is kept (its d2h already started via ``distributed.d2h_async``), and
    the ONE blocking ``np.asarray`` happens the first time any consumer
    — the composer, a ledger read, next tick's budget split — actually
    needs the numbers.  ``timings`` (optional MutableMapping) books
    the blocking remainder as a ``readback`` stage."""

    __slots__ = ("_dev", "_np", "_timings", "_dtype")

    def __init__(self, dev, timings=None, dtype=np.float64) -> None:
        self._dev = dev
        self._np = None
        self._timings = timings
        self._dtype = dtype

    def resolve(self) -> np.ndarray:
        if self._np is None:
            from . import distributed as D

            with D.stage_trace("readback", self._timings):
                self._np = np.asarray(self._dev, dtype=self._dtype)  # d2h
            self._dev = None
        return self._np


class _RowsView:
    """One store's (n_groups, 9) slice of a ``_LazyRows`` holder.

    Quacks enough numpy to satisfy direct ``tick()`` callers (indexing,
    ``np.asarray``, ``shape``); the store's ``_rows`` property swaps the
    view for the materialized slice on first access, so steady-state
    consumers pay the laziness check only once per tick."""

    __slots__ = ("_holder", "_r0", "_r1")

    def __init__(self, holder: _LazyRows, r0: int, r1: int) -> None:
        self._holder = holder
        self._r0 = int(r0)
        self._r1 = int(r1)

    def materialize(self) -> np.ndarray:
        return self._holder.resolve()[self._r0:self._r1]

    def __array__(self, dtype=None, copy=None):
        out = self.materialize()
        return out.astype(dtype) if dtype is not None else out

    def __getitem__(self, idx):
        return self.materialize()[idx]

    @property
    def shape(self):
        return self.materialize().shape


class _PartialsSlice:
    """Lazy host-side slice of a stacked per-cell partials array.

    Slicing the device array eagerly (``partials[o0:o1]``) dispatches a
    device slice op whose scalar start indices are an IMPLICIT h2d
    upload — disallowed under ``jax.transfer_guard`` — and the
    group-stat compose path never reads per-cell partials anyway.  The
    d2h + slice run only if a host consumer materializes the view
    (``partials_host``)."""

    __slots__ = ("_partials", "_lo", "_hi")

    def __init__(self, partials, lo: int, hi: int) -> None:
        self._partials = partials
        self._lo, self._hi = int(lo), int(hi)

    def __array__(self, dtype=None, copy=None):
        out = np.asarray(self._partials)[self._lo:self._hi]
        return out.astype(dtype) if dtype is not None else out


class DeviceMomentStore:
    """Device-resident mirror of ``MomentStore``: the stacked (group,
    block) moment rows, totals and per-block draw ledger live as jax
    arrays BETWEEN ticks, so a continuation round is one fused launch
    (``distributed.fused_tick``) that consumes the resident buffers via
    donation and returns their successors — moments never cross the
    host boundary in steady state.

    Units: moments are stored on the SHIFTED scale (the same contract as
    the host store) additionally divided by ``scale`` — the fp32-safety
    lever (ISLA is exactly scale-equivariant).  When jax runs in x64 the
    store defaults to float64 with ``scale=1.0``, where the carry-prepend
    segment sums are **bit-identical** to the host bincount path.

    The per-block cumulative draw ledger is kept twice: an int64 host
    copy (``n_sampled`` — planning/deficit math stays host-side and
    never touches the device) and a device copy feeding the cell-weight
    computation inside the launch.
    """

    def __init__(self, n_blocks: int, n_groups: int, boundaries: Boundaries,
                 sketch0: float, shift: float, scale: float,
                 block_sizes: Sequence[int], dtype,
                 anchor: Optional[Anchor] = None,
                 has_sketch: bool = False, sizes=None) -> None:
        from . import distributed as D

        if len(block_sizes) != n_blocks:
            raise ValueError(f"need {n_blocks} block sizes, got "
                             f"{len(block_sizes)}")
        if sizes is not None and (sizes.shape != (n_blocks,)
                                  or sizes.dtype != dtype):
            raise ValueError(f"shared block sizes must be ({n_blocks},) "
                             f"{dtype}, got {sizes.shape} {sizes.dtype}")
        self.n_blocks = int(n_blocks)
        self.n_groups = int(n_groups)
        self.boundaries = boundaries
        self.sketch0 = float(sketch0)
        self.shift = float(shift)
        self.scale = float(scale)
        self.anchor = anchor
        self.block_sizes = [int(b) for b in block_sizes]
        self.dtype = dtype
        self.has_sketch = bool(has_sketch)
        n_cells = self.n_groups * self.n_blocks
        # Resident state: owned directly until a DeviceStack adopts the
        # store, after which the stacked tensors are authoritative and
        # these hold None (see the properties below).
        self._owner = None
        # The zero state and the anchor constants come from one launch
        # over one upload of six numbers (cold start — the steady-state
        # tick never re-ships them).  The host casts the float64
        # constants to the store's dtype, rounding as an on-device
        # convert would.  Sketch plane: resident uint8 HLL registers,
        # same ownership dance as the moments (the plane is NOT scaled —
        # registers are rank integers, identical across dtypes and
        # routes).
        consts = (np.asarray(boundaries.as_tuple() + (self.sketch0, 1.0),
                             dtype=np.float64) / self.scale).astype(dtype)
        state, (self._bounds, self._bound_row, self._sketch0_dev,
                self._sk_cells, self._inv_scale) = D.cold_store(
            D.h2d(consts, dtype), n_cells, self.n_blocks, self.has_sketch)
        (self._mom_s, self._mom_l, self._totals, self._ns_dev,
         self._regs) = state
        D.count("store_builds", 1)
        self._group_regs = None  # last launch's folded (n_groups, M) rows
        self.n_sampled = np.zeros(self.n_blocks, dtype=np.int64)
        self.rounds = 0
        # Block sizes: the caller's shared device copy (one per executor
        # and dtype, never donated) or an upload of this store's own.
        self._sizes = (sizes if sizes is not None else
                       D.h2d(np.asarray(self.block_sizes, dtype=np.float64),
                             dtype))
        # Per-tick stats cache (invalidated by any state change; keyed by
        # the solve configuration so a different mode re-solves).
        self._partials = None   # (n_cells,) device, scaled shifted units
        self._rows = None       # (n_groups, 9) numpy OR lazy _RowsView —
        #                         see the _rows property below
        self._stats_valid = False
        self._stats_cfg = None  # (params, mode, geometry) of the cache
        self._stack = None      # cached single-store DeviceStack

    # -- resident state (stack-aware) --------------------------------------

    def _detach(self) -> None:
        """Materialize this store's slices out of its owning stack (the
        whole stack releases — a store cannot leave alone)."""
        if self._owner is not None:
            self._owner.release()

    def _state_attr(self, name: str, idx: int):
        if self._owner is not None:
            return self._owner.state_slice(self, idx)
        return getattr(self, name)

    @property
    def mom_s(self):
        return self._state_attr("_mom_s", 0)

    @mom_s.setter
    def mom_s(self, v):
        self._detach()
        self._mom_s = v
        self._stats_valid = False

    @property
    def mom_l(self):
        return self._state_attr("_mom_l", 1)

    @mom_l.setter
    def mom_l(self, v):
        self._detach()
        self._mom_l = v
        self._stats_valid = False

    @property
    def totals(self):
        return self._state_attr("_totals", 2)

    @totals.setter
    def totals(self, v):
        self._detach()
        self._totals = v
        self._stats_valid = False

    @property
    def _n_sampled_dev(self):
        return self._state_attr("_ns_dev", 3)

    @_n_sampled_dev.setter
    def _n_sampled_dev(self, v):
        self._detach()
        self._ns_dev = v
        self._stats_valid = False

    @property
    def regs(self):
        if not self.has_sketch:
            return None
        return self._state_attr("_regs", 4)

    @regs.setter
    def regs(self, v):
        self._detach()
        self._regs = v
        self._stats_valid = False

    @property
    def _rows(self):
        """Cached (n_groups, 9) group-stat rows, float64 numpy.

        A pipelined tick installs a lazy ``_RowsView`` (the launch's rows
        still streaming d2h); the first read materializes it — the
        deferred sync the pipeline moved out of the launch stage — and
        caches the numpy slice so every later read is a plain attribute."""
        src = self._rows_src
        if isinstance(src, _RowsView):
            src = src.materialize()
            self._rows_src = src
        return src

    @_rows.setter
    def _rows(self, v):
        self._rows_src = v

    # -- construction ------------------------------------------------------

    @staticmethod
    def default_dtype():
        import jax
        import jax.numpy as jnp
        return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32

    @staticmethod
    def anchor_scale(boundaries: Boundaries, sketch0: float) -> float:
        """fp32-safety normalizer frozen with the anchor: the largest
        magnitude the S/L band can produce (outliers beyond the cuts feed
        only the plain totals, whose squares stay in fp32 range)."""
        return max(abs(boundaries.s_lo), abs(boundaries.l_hi),
                   abs(float(sketch0)), 1e-12)

    @staticmethod
    def canonical_dtype(dtype=None):
        """The dtype the backend will ACTUALLY allocate for ``dtype``
        (None: ``default_dtype``): a float64 request without
        jax_enable_x64 silently gives fp32, and the scale / bit-exactness
        / headroom contracts must follow the real dtype, not the
        requested one.  Allocates nothing on the device."""
        import jax
        if dtype is None:
            dtype = DeviceMomentStore.default_dtype()
        return jax.dtypes.canonicalize_dtype(dtype)

    @staticmethod
    def fresh_device(n_blocks: int, boundaries: Boundaries, sketch0: float,
                     block_sizes: Sequence[int], shift: float = 0.0,
                     n_groups: int = 1, scale: Optional[float] = None,
                     dtype=None,
                     anchor: Optional[Anchor] = None,
                     has_sketch: bool = False,
                     sizes=None) -> "DeviceMomentStore":
        """A cold store: zero state, this anchor's constants.  ``sizes``
        is an optional shared device copy of ``block_sizes`` in the
        store's dtype (``_sizes`` of an earlier store), which the store
        then uses instead of uploading its own."""
        import jax.numpy as jnp
        dtype = DeviceMomentStore.canonical_dtype(dtype)
        if scale is None:
            scale = (1.0 if dtype == jnp.float64
                     else DeviceMomentStore.anchor_scale(boundaries,
                                                         sketch0))
        return DeviceMomentStore(n_blocks, n_groups, boundaries,
                                 float(sketch0), float(shift), float(scale),
                                 block_sizes, dtype, anchor=anchor,
                                 has_sketch=has_sketch, sizes=sizes)

    @staticmethod
    def from_host(store: MomentStore, block_sizes: Sequence[int],
                  scale: Optional[float] = None, dtype=None
                  ) -> "DeviceMomentStore":
        """One-time cold-start upload of a host store's state (warm
        promotion); after this the device copy is authoritative."""
        import jax.numpy as jnp

        from . import distributed as D

        dst = DeviceMomentStore.fresh_device(
            store.n_blocks, store.boundaries, store.sketch0, block_sizes,
            shift=store.shift, n_groups=store.n_groups, scale=scale,
            dtype=dtype, anchor=store.anchor,
            has_sketch=store.has_sketch)
        p4 = dst.scale ** np.arange(4)
        dst.mom_s = D.h2d(store.mom_s / p4, dst.dtype)
        dst.mom_l = D.h2d(store.mom_l / p4, dst.dtype)
        dst.totals = D.h2d(store.totals / p4[:3], dst.dtype)
        dst.n_sampled = store.n_sampled.copy()
        dst._n_sampled_dev = D.h2d(store.n_sampled.astype(np.float64),
                                   dst.dtype)
        if store.has_sketch:
            dst.regs = D.h2d(store.regs, jnp.uint8)
        dst.rounds = store.rounds
        return dst

    def to_host(self) -> MomentStore:
        """Download into a host float64 ``MomentStore`` (diagnostics and
        parity tests — never on the serving tick path)."""
        p4 = self.scale ** np.arange(4)
        return MomentStore(
            n_blocks=self.n_blocks, n_groups=self.n_groups,
            boundaries=self.boundaries, sketch0=self.sketch0,
            shift=self.shift,
            mom_s=np.asarray(self.mom_s, dtype=np.float64) * p4,
            mom_l=np.asarray(self.mom_l, dtype=np.float64) * p4,
            totals=np.asarray(self.totals, dtype=np.float64) * p4[:3],
            n_sampled=self.n_sampled.copy(), rounds=self.rounds,
            anchor=self.anchor, has_sketch=self.has_sketch,
            regs=(np.asarray(self.regs, dtype=np.uint8)
                  if self.has_sketch else None))

    # -- sketch plane ------------------------------------------------------

    def group_registers(self) -> np.ndarray:
        """(n_groups, M) folded register rows.  Steady state serves the
        LAUNCH's folded rows (already streaming d2h with the stat rows —
        zero extra register-plane traffic); the cold/diagnostic fallback
        downloads the resident plane and folds on the host."""
        if not self.has_sketch:
            raise ValueError("store was built without a sketch plane "
                             "(has_sketch=False)")
        if self._stats_valid and self._group_regs is not None:
            return np.asarray(self._group_regs, dtype=np.uint8)
        return _sketch.fold_groups(np.asarray(self.regs), self.n_groups)

    def distinct_counts(self) -> np.ndarray:
        """(n_groups,) HLL COUNT DISTINCT estimates (host estimator over
        the folded rows — identical math on every route)."""
        return _sketch.estimate(self.group_registers())

    # -- properties / planning mirror --------------------------------------

    @property
    def n_cells(self) -> int:
        return self.n_groups * self.n_blocks

    @property
    def total_sampled(self) -> int:
        return int(self.n_sampled.sum())

    def deficit(self, target_quotas: Sequence[int]) -> np.ndarray:
        return block_deficit(self.n_sampled, target_quotas, self.n_blocks)

    def _grand_totals(self) -> "tuple[float, float, float]":
        """(n, s1, s2) over all cells, un-scaled — from the cached group
        rows when valid (zero device traffic), else three reduced scalars
        off the resident totals."""
        if self._stats_valid and self._rows is not None:
            t = self._rows[:, [0, 4, 5]].sum(axis=0)
        else:
            import jax.numpy as jnp
            t = np.asarray(jnp.sum(self.totals, axis=0), dtype=np.float64)
        return float(t[0]), float(t[1]) * self.scale, \
            float(t[2]) * self.scale ** 2

    def matched_total(self) -> float:
        """Total matching samples accumulated (the budget splitter's n)."""
        return self._grand_totals()[0]

    def sample_sigma(self) -> float:
        """ddof-1 sigma of all matching samples — the host ``MomentStore``
        contract served from device state."""
        n, s1, s2 = self._grand_totals()
        if n < 2:
            return float("nan")
        mean = s1 / n
        var = max(s2 / n - mean * mean, 0.0)
        return math.sqrt(var * n / (n - 1.0))

    # -- ticks -------------------------------------------------------------

    def _own_stack(self) -> "DeviceStack":
        if (self._owner is not None and not self._owner._released
                and len(self._owner.stores) == 1):
            return self._owner
        if self._stack is None or self._stack._released \
                or self._stack is not self._owner:
            self._stack = DeviceStack([self])
        return self._stack

    def build_seg(self, block_ids: np.ndarray,
                  group_ids: Optional[np.ndarray] = None,
                  mask: Optional[np.ndarray] = None,
                  offset: int = 0) -> np.ndarray:
        """Flatten (group, block) tags onto this store's cell axis (the
        engine's ``flat_segments`` contract), mask-filtered, offset for
        stacked launches.  Returns int32 segment ids aligned with the
        POST-mask value stream (callers apply the same mask to values)."""
        block_ids = np.asarray(block_ids).reshape(-1)
        seg, _ = flat_segments(block_ids.astype(np.intp), self.n_blocks,
                               group_ids, self.n_groups)
        if mask is not None:
            seg = seg[np.asarray(mask, dtype=bool).reshape(-1)]
        return (seg + offset).astype(np.int32)

    def ingest_tick(self, values: np.ndarray, block_ids: np.ndarray,
                    quotas: np.ndarray, params: IslaParams, *,
                    mode: str = "calibrated", geometry=None,
                    group_ids: Optional[np.ndarray] = None,
                    mask: Optional[np.ndarray] = None,
                    count_round: bool = True, layout: str = "auto"):
        """Single-store convenience tick: merge one tagged pass (values on
        the shifted scale, same contract as ``MomentStore.ingest``) and
        re-solve — one fused launch.  Returns ``(partials, rows)`` (device
        partials in scaled shifted units; see ``DeviceStack.tick``).

        ``layout="auto"`` picks the dense batched-contraction Phase 1
        when the stream is block-major canonical and the store runs fp32;
        float64 stores keep the tagged carry-prepend scatter (the
        bit-exact merge contract).  Force with "dense" / "tagged".
        """
        import jax.numpy as jnp

        values = np.asarray(values, dtype=np.float64).reshape(-1)
        quotas_arr = np.asarray(quotas, dtype=np.int64).reshape(-1)
        block_ids = np.asarray(block_ids).reshape(-1)
        if layout == "auto":
            canonical = np.array_equal(
                block_ids, np.repeat(np.arange(self.n_blocks),
                                     quotas_arr))
            layout = ("dense" if canonical and self.dtype != jnp.float64
                      else "tagged")
        stack = self._own_stack()
        if layout == "dense":
            # The stack's dense pane takes RAW measure values; this
            # single-store convenience API takes shifted ones (the
            # MomentStore contract), so un-shift before handing off —
            # a float64 round-trip well inside the fp32 tolerance the
            # dense layout runs at.
            out = stack.tick(
                params, mode=mode, geometry=geometry,
                values=values - self.shift,
                quotas=quotas_arr, dense=([group_ids], [mask]),
                count_round=count_round)
        else:
            # key_seg is the stack's cell-placement contract (plain
            # offset on a single device, shard placement on a mesh).
            seg = stack.key_seg(0, self, block_ids, group_ids, mask)
            if mask is not None:
                values = values[np.asarray(mask, dtype=bool).reshape(-1)]
            hash_limbs = None
            if self.has_sketch:
                # Hash-input contract: raw UN-shifted float64 bits.
                hash_limbs = _sketch.value_limbs(values - self.shift)
            out = stack.tick(
                params, mode=mode, geometry=geometry,
                values=values / self.scale,
                seg=seg, quotas=quotas_arr, count_round=count_round,
                hash_limbs=hash_limbs)
        return out[0]

    def solve_device(self, params: IslaParams, mode: str = "calibrated",
                     geometry=None):
        """Zero-draw re-solve of the resident moments (cached between
        state changes; at most one launch, zero h2d)."""
        return self._own_stack().tick(params, mode=mode,
                                      geometry=geometry)[0]

    def partials_host(self) -> np.ndarray:
        """Last solved per-cell partial answers, un-scaled back to the
        shifted float64 axis (these are answers, not moments)."""
        if not self._stats_valid or self._partials is None:
            raise ValueError("no solved partials cached; run a tick or "
                             "solve_device first")
        return np.asarray(self._partials, dtype=np.float64) * self.scale


class DeviceStack:
    """A stacked multi-store launch set: the warm stores of one mode-group
    concatenated onto one (total_cells, 4) moments axis so N predicates'
    continuation rounds are ONE fused kernel call.

    Member stores must share the block axis and dtype, but each store may
    carry its OWN anchor (boundaries / shift / scale) — the per-key
    boundary-refinement path, where a predicate's store classifies against
    cuts derived from its matching pilot rows.  The stack groups its
    stacked cells by anchor: the fused launch receives a per-cell bounds
    table, a per-cell inverse-scale vector (the fp32 pre-scaling and the
    Phase 2 stopping threshold ride it), and per-key value affines for the
    dense layout, so every cell classifies and solves in its own anchor's
    frame inside the single launch.  A stack whose stores all share one
    anchor collapses back to the scalar-broadcast constants (bit-identical
    to the pre-refinement launch).  ``sketch0`` may additionally differ
    per store (re-anchoring), so the stacked Phase 2 always takes a
    per-cell sketch vector.  Stack constants (cell->block map, group-row
    segments, catalog sizes, anchor tables) are uploaded once at stack
    build.
    """

    def __init__(self, stores: Sequence[DeviceMomentStore]) -> None:
        import jax.numpy as jnp

        from . import distributed as D

        if not stores:
            raise ValueError("a device stack needs at least one store")
        first = stores[0]
        for st in stores:
            if st.n_blocks != first.n_blocks or st.dtype != first.dtype:
                raise ValueError(
                    "stacked stores must share the block axis and dtype")
        self.stores = list(stores)
        self.n_blocks = first.n_blocks
        self.dtype = first.dtype
        cells = [st.n_cells for st in self.stores]
        groups = [st.n_groups for st in self.stores]
        self.offsets = np.concatenate([[0], np.cumsum(cells)])
        self.row_offsets = np.concatenate([[0], np.cumsum(groups)])
        self.n_cells = int(self.offsets[-1])
        self.n_rows = int(self.row_offsets[-1])
        self.n_groups_list = tuple(groups)
        self._sizes = (first._sizes if len(self.stores) == 1 else
                       jnp.concatenate([st._sizes for st in self.stores]))
        # -- anchor tables (built once; uniform stacks keep the scalar
        #    broadcast forms so the launch graph is unchanged) ------------
        self._uniform = all(
            st.boundaries == first.boundaries and st.shift == first.shift
            and st.scale == first.scale for st in self.stores)
        if self._uniform:
            # One (1, 4) bounds row — fused_tick broadcasts it.
            self._bounds = self._bound_rows = first._bound_row
            self._bound_slots = (0,) * len(self.stores)
        else:
            # Tagged layout: per-cell cuts (+1 inert pad row for the
            # bucket-padding drop segment — +inf matches no sample).
            self._bounds = jnp.concatenate(
                [jnp.broadcast_to(st._bounds, (st.n_cells, 4))
                 for st in self.stores]
                + [jnp.full((1, 4), jnp.inf, self.dtype)])
            # Dense layout: one row per DISTINCT anchor, static slots per
            # key (lets XLA CSE the shared-anchor weight panes).
            seen = {}
            rows, slots = [], []
            for st in self.stores:
                bkey = (st.boundaries, st.scale)
                if bkey not in seen:
                    seen[bkey] = len(rows)
                    rows.append(st._bounds)
                slots.append(seen[bkey])
            self._bound_rows = jnp.stack(rows)
            self._bound_slots = tuple(slots)
        # Per-cell inverse anchor scale: pre-scales the Phase 2 stopping
        # threshold (and the ISLA-E b0) into each cell's normalized frame.
        # A one-store stack adopts its store's per-cell constants as built.
        self._inv_scale = (first._inv_scale if len(self.stores) == 1 else
                           D.h2d(np.concatenate(
                               [np.full(st.n_cells, 1.0 / st.scale)
                                for st in self.stores]), self.dtype))
        # Dense value affines: pane holds raw/ref values; key k recovers
        # its own frame as v * ratio_k + off_k inside the launch.
        self._ref_scale = max(st.scale for st in self.stores)
        self._key_affine = tuple(
            (self._ref_scale / st.scale, st.shift / st.scale)
            for st in self.stores)
        # Per-cell sketch vector (device; built on first use when stacked).
        self._sk_cells = first._sk_cells if len(self.stores) == 1 else None
        # Zone-map pruning: when a pruned plan zeroes whole blocks'
        # quotas, the dense tick launches over a COMPACTED active-block
        # axis (gather before the fused Phase 1+2, scatter the delta
        # back) — pruned cells keep their resident rows untouched, so a
        # predicate change re-activates them warm.  Toggle for tests /
        # parity audits; the tagged (x64) path never compacts (it is
        # already O(matched samples) and owns the bit-parity contract).
        self.block_compaction = True
        self._active_cache = {}  # active-set bytes -> device index pair
        # Pipelined (deferred-stats) ticks ping-pong through at most TWO
        # in-flight launches: the host may stage chunk k+1's sample panes
        # while chunk k computes, but blocks on chunk k-1 first — bounding
        # live pane buffers to the classic double-buffer depth.
        self._inflight = collections.deque()
        # Adopt the stores: the stacked tensors become the authoritative
        # resident state (built once — steady ticks donate them in place,
        # no per-tick concat/split churn).  A store reads its slice
        # through ``state_slice``; ``release`` materializes the slices
        # back when the stack dissolves.
        for st in self.stores:
            st._detach()
        if len(self.stores) == 1:
            st = self.stores[0]
            self._state = (st._mom_s, st._mom_l, st._totals, st._ns_dev)
        else:
            self._state = (
                jnp.concatenate([st._mom_s for st in self.stores]),
                jnp.concatenate([st._mom_l for st in self.stores]),
                jnp.concatenate([st._totals for st in self.stores]),
                jnp.concatenate([st._ns_dev for st in self.stores]))
        # Sketch plane: any sketch member gives the whole stack a
        # register plane, optional state of every launch (non-sketch
        # members ride with inert all-zero register rows — max against
        # zero is a no-op); without one it is None and adds nothing.
        self.has_sketch = any(st.has_sketch for st in self.stores)
        if self.has_sketch:
            if len(self.stores) == 1:
                self._regs_state = self.stores[0]._regs
            else:
                self._regs_state = jnp.concatenate(
                    [st._regs if st.has_sketch
                     else jnp.zeros((st.n_cells, _sketch.M), jnp.uint8)
                     for st in self.stores])
        else:
            self._regs_state = None
        self._released = False
        for st in self.stores:
            st._mom_s = st._mom_l = st._totals = st._ns_dev = None
            st._regs = None
            st._owner = self

    # -- state plumbing ----------------------------------------------------

    def state_slice(self, store: DeviceMomentStore, idx: int):
        """One adopted store's view of the stacked state (idx: 0 mom_s,
        1 mom_l, 2 totals, 3 device draw ledger, 4 HLL registers) — an
        eager device slice, for diagnostics/downloads, never on the tick
        path."""
        k = next(i for i, st in enumerate(self.stores) if st is store)
        if idx < 3:
            return self._state[idx][int(self.offsets[k]):
                                    int(self.offsets[k + 1])]
        if idx == 4:
            return self._regs_state[int(self.offsets[k]):
                                    int(self.offsets[k + 1])]
        b = self.n_blocks
        return self._state[3][k * b:(k + 1) * b]

    def key_seg(self, k: int, store: DeviceMomentStore,
                block_ids: np.ndarray,
                group_ids: Optional[np.ndarray] = None,
                mask: Optional[np.ndarray] = None) -> np.ndarray:
        """Cell ids for store ``k``'s tagged draw in THIS stack's launch
        layout — the placement contract callers must use instead of
        assuming the offset arithmetic (the mesh stack overrides it with
        its block-run shard placement)."""
        return store.build_seg(block_ids, group_ids, mask,
                               offset=int(self.offsets[k]))

    def release(self) -> None:
        """Dissolve the stack: write every store's slices back so each
        owns its state again (e.g. before a store joins a new stack when
        the warm key set changes)."""
        if self._released:
            return
        mom_s, mom_l, totals, ns = self._state
        b = self.n_blocks
        for k, st in enumerate(self.stores):
            o0, o1 = int(self.offsets[k]), int(self.offsets[k + 1])
            st._mom_s, st._mom_l = mom_s[o0:o1], mom_l[o0:o1]
            st._totals = totals[o0:o1]
            st._ns_dev = ns[k * b:(k + 1) * b]
            if st.has_sketch:
                st._regs = self._regs_state[o0:o1]
            st._owner = None
        # Drop the stacked tensors: slicing copied, so keeping them (e.g.
        # through a stale executor cache entry) would pin a dead copy of
        # every store's moments in device memory.
        self._state = None
        self._regs_state = None
        self._sk_cells = None
        self._inflight.clear()
        self._released = True

    def _install_stats(self, partials, rows, cfg, defer=False,
                       timings=None, group_regs=None):
        """Hand each store its slice of the launch's stats.

        ``defer=False`` (the serial route): one blocking ``np.asarray``
        materializes the rows now — the pre-pipeline behavior, byte for
        byte.  ``defer=True`` (the pipelined route): the d2h is only
        STARTED (``distributed.d2h_async``) and each store gets a lazy
        ``_RowsView``; the host returns to drawing/staging the next
        mode-group and the sync moves to whoever first reads the rows.

        ``group_regs`` (sketch launches) is the launch's folded
        (n_rows, M) register rows; stores get lazy ``_RowsView`` slices of
        ONE shared holder — never an eager device slice, whose scalar
        start indices would be an implicit h2d under transfer_guard."""
        from . import distributed as D

        if defer:
            holder = _LazyRows(D.d2h_async(rows), timings)
            self._inflight.append(rows)
            while len(self._inflight) > 2:  # double-buffer depth
                self._inflight.popleft().block_until_ready()
        else:
            with D.stage_trace("readback", timings):
                rows_np = np.asarray(rows, dtype=np.float64)  # d2h: stats
        gr_holder = None
        if group_regs is not None:
            gr_holder = _LazyRows(D.d2h_async(group_regs), timings,
                                  dtype=np.uint8)
        out = []
        for k, st in enumerate(self.stores):
            r0, r1 = int(self.row_offsets[k]), int(self.row_offsets[k + 1])
            st._partials = self._partials_view(partials, k)
            st._rows = (_RowsView(holder, r0, r1) if defer
                        else rows_np[r0:r1] if len(self.stores) > 1
                        else rows_np)
            if gr_holder is not None and st.has_sketch:
                st._group_regs = _RowsView(gr_holder, r0, r1)
            st._stats_valid = True
            st._stats_cfg = cfg
            out.append((st._partials, st._rows_src))
        return out

    def _partials_view(self, partials, k: int):
        """Store ``k``'s lazy view of a launch's stacked per-cell
        partials (a one-store stack hands the array itself)."""
        if len(self.stores) == 1:
            return partials
        return _PartialsSlice(partials, int(self.offsets[k]),
                              int(self.offsets[k + 1]))

    # fp32 accumulators lose integer exactness at 2^24; warn with margin
    # so an eternal serving loop cannot silently stop accumulating.
    _FP32_COUNT_HEADROOM = 1 << 22

    def _check_fp32_headroom(self, quotas: np.ndarray) -> None:
        import jax.numpy as jnp
        if self.dtype == jnp.float64 or getattr(self, "_sat_warned",
                                                False):
            return
        # Per-block cells accumulate per-block draws; the group-stat rows
        # additionally sum matched counts across a whole store, bounded
        # by its TOTAL draws — both must stay inside fp32's exact-integer
        # range (2^24, checked with margin).
        worst_block = max(int(st.n_sampled.max()) for st in self.stores)
        worst_total = max(int(st.n_sampled.sum()) for st in self.stores)
        if (worst_block + int(quotas.max()) > self._FP32_COUNT_HEADROOM
                or worst_total + int(quotas.sum())
                > 4 * self._FP32_COUNT_HEADROOM):
            import warnings
            warnings.warn(
                "device store draw counts are approaching the float32 "
                "accumulator limit (2^24); further merges will degrade "
                "silently — run under jax_enable_x64 or reset_stores() "
                "to re-anchor", RuntimeWarning, stacklevel=3)
            self._sat_warned = True

    def _compact_plan(self, quotas: np.ndarray):
        """The dense tick's zone-pruned launch plan: ``(compact_quotas,
        active, (cell_idx, ns_idx))`` when compaction pays, else None.

        ``active`` is the ascending list of blocks with a non-zero quota
        — ascending block order IS the draw-stream order, so the compact
        pane fills from the stream unchanged.  The active count is
        rounded up to a power-of-two bucket (pad slots carry quota 0 and
        out-of-bounds scatter targets, so they drop) to bound retraces;
        a bucket reaching the full block axis falls back to the
        uncompacted launch — the identical pre-pruning graph.  The
        device-resident scatter index pair is cached per active set, so
        steady-state ticks under an unchanged plan upload only the usual
        packed sample panes.
        """
        if not self.block_compaction:
            return None
        active = np.flatnonzero(quotas > 0)
        a_pad = _bucket(max(int(active.size), 1), floor=8)
        if a_pad >= self.n_blocks:
            return None
        import jax.numpy as jnp

        from . import distributed as D

        q_c = np.zeros(a_pad, dtype=np.int64)
        q_c[:active.size] = quotas[active]
        ck = active.tobytes()
        pair = self._active_cache.get(ck)
        if pair is None:
            ext = np.full(a_pad, -1, dtype=np.int64)
            ext[:active.size] = active
            B = self.n_blocks
            K = len(self.stores)
            parts = []
            for k, st in enumerate(self.stores):
                idx = (int(self.offsets[k])
                       + np.arange(st.n_groups)[:, None] * B + ext[None, :])
                parts.append(np.where(ext[None, :] < 0, self.n_cells,
                                      idx).reshape(-1))
            cell_idx = np.concatenate(parts)
            ns_idx = np.arange(K)[:, None] * B + ext[None, :]
            ns_idx = np.where(ext[None, :] < 0, K * B, ns_idx).reshape(-1)
            if len(self._active_cache) >= 32:
                self._active_cache.clear()
            pair = (D.h2d(cell_idx.astype(np.int32), jnp.int32),
                    D.h2d(ns_idx.astype(np.int32), jnp.int32))
            self._active_cache[ck] = pair
        return q_c, active, pair

    def _sketch0_cells(self):
        # Broadcast from each store's resident device scalar — a plain
        # device op (cached across ticks), so warm ticks create no
        # scalar h2d transfers.
        import jax.numpy as jnp
        if self._sk_cells is None:
            self._sk_cells = jnp.concatenate([
                jnp.broadcast_to(st._sketch0_dev, (st.n_cells,))
                for st in self.stores])
        return self._sk_cells

    # -- the tick ----------------------------------------------------------

    def tick(self, params: IslaParams, mode: str = "calibrated",
             geometry=None, values: Optional[np.ndarray] = None,
             seg: Optional[np.ndarray] = None,
             quotas: Optional[np.ndarray] = None,
             dense=None, count_round: bool = True, timings=None,
             defer_stats: bool = False, hash_limbs=None):
        """One continuation round for every store in the stack.

        A sketch stack additionally scatters the tick's samples into the
        resident HLL register plane inside the SAME launch.  Tagged
        callers must pass ``hash_limbs=(hi, lo)`` — the
        ``sketch.value_limbs`` of the RAW unshifted measure values,
        aligned with ``values``/``seg`` (the hash-input contract; the
        scaled tagged values cannot recover the raw bits).  The dense
        pane already carries raw values, so dense callers pass nothing.

        Two sample payloads, one launch either way:

         * tagged — ``values`` (each store's OWN scaled shifted frame —
           ``(raw + store.shift) / store.scale`` per key slice, float64
           host, matched samples only) aligned with ``seg`` (stacked cell
           ids from ``key_seg``, in this stack's placement); the
           carry-prepend scatter, bit-identical to the host fold when the
           store runs float64 (scale 1.0).
         * dense — ``values`` is the FULL block-major chunk stream of RAW
           (unshifted) measure values and ``dense=(key_gids, key_valids)``
           carries per-store (m,) GROUP BY codes / predicate masks (None
           where absent); Phase 1 runs as one batched contraction
           (``fused_tick_dense``), each key recovering its own anchor
           frame from the shared pane via its static affine — the fast
           fp32 serving layout.

        ``quotas`` is the pass's per-block draw count.  With no draw the
        resident moments are re-solved (served from the stats cache when
        nothing changed — zero launches, zero transfers).

        Returns ``[(partials, rows), ...]`` per store — lazy partial
        answers (``_partials_view``) and the numpy group-stat rows, both
        in EACH STORE'S scaled shifted units
        (``DeviceMomentStore.partials_host`` / the executor's composer
        un-scale per store).

        ``timings`` (optional dict) books the ``h2d`` stage (its parts
        ``h2d.pane`` and ``h2d.put``), ``launch`` and ``readback``
        (``distributed.stage_trace``).  ``defer_stats=True``
        is the pipelined route: the launch is dispatched but the stat-row
        readback only STARTS (async d2h) — the returned rows are lazy
        views that block on first access, letting the host stage the next
        mode-group while this one computes.  At most two launches stay
        in flight (classic double-buffer depth).

        The launches are three hooks — ``_launch_solve``,
        ``_launch_dense`` and ``_launch_tagged`` — each building and
        uploading its panes and returning ``fused_tick``'s shape; a
        placement (``MeshDeviceStack``) overrides only those.
        """
        if geometry is not None:
            # kappa is dimensionless; b0 lives on the value axis — the
            # launch rescales it per cell via the inv_scale vector.
            geometry = (float(geometry[0]), float(geometry[1]))
        if self._released:
            raise ValueError(
                "stack was released (a store joined another stack); "
                f"build a fresh {type(self).__name__}")
        cfg = (params, mode, geometry)
        n_draw = 0 if quotas is None else int(np.sum(quotas))
        if values is None or n_draw == 0:
            if all(st._stats_valid and st._stats_cfg == cfg
                   for st in self.stores):
                # _rows_src keeps a pipelined tick's lazy views lazy —
                # going through the property here would force the sync.
                return [(st._partials, st._rows_src)
                        for st in self.stores]
            partials, rows, group_regs = self._launch_solve(cfg, timings)
            return self._install_stats(partials, rows, cfg,
                                       defer=defer_stats, timings=timings,
                                       group_regs=group_regs)

        values = np.asarray(values, dtype=np.float64).reshape(-1)
        quotas = np.asarray(quotas, dtype=np.int64).reshape(-1)
        if quotas.shape != (self.n_blocks,):
            raise ValueError(f"quotas must be ({self.n_blocks},), got "
                             f"{quotas.shape}")
        self._check_fp32_headroom(quotas)
        # All h2d crossings in the hooks are the tick's fresh samples and
        # their tags — moments never cross (the per-store tiling of the
        # quota row happens inside the launch).
        if dense is not None:
            if self._uniform:
                # One shared anchor: prepare the pane in its frame on the
                # host (float64 — the pre-refinement numerics) and let the
                # identity affine pass it through.
                st0 = self.stores[0]
                pane_vals = (values + st0.shift) / st0.scale
                key_affine = ((1.0, 0.0),) * len(self.stores)
            else:
                pane_vals = values / self._ref_scale
                key_affine = self._key_affine
            # Zone-pruned plans zero whole blocks' quotas; the draw
            # stream already skips those blocks, so the pane compacts to
            # the active rows and the delta scatters back through the
            # cached index pair.  The quotas cross in compact form too —
            # the launch never sees the pruned axis.
            cp = self._compact_plan(quotas)
            if cp is not None:
                pane_quotas, _, active_cells = cp
            else:
                pane_quotas, active_cells = quotas, None
            out = self._launch_dense(cfg, values, pane_vals, pane_quotas,
                                     active_cells, dense, key_affine,
                                     timings)
        else:
            seg = np.asarray(seg, dtype=np.int32).reshape(-1)
            if values.shape != seg.shape:
                raise ValueError("values and seg must align")
            if not self.has_sketch:
                hash_limbs = None
            elif hash_limbs is None:
                raise ValueError(
                    "sketch stack tagged tick needs hash_limbs "
                    "(sketch.value_limbs of the raw values)")
            out = self._launch_tagged(cfg, values, seg, quotas, hash_limbs,
                                      timings)
        (mom_s, mom_l, totals, ns, self._regs_state, partials, rows,
         group_regs) = out
        self._state = (mom_s, mom_l, totals, ns)
        for st in self.stores:
            st.n_sampled = st.n_sampled + quotas
            if count_round:
                st.rounds += 1
        return self._install_stats(partials, rows, cfg,
                                   defer=defer_stats, timings=timings,
                                   group_regs=group_regs)

    def _launch_solve(self, cfg, timings):
        """The zero-draw re-solve: ``(partials, rows, group_regs)``."""
        from . import distributed as D

        params, mode, geometry = cfg
        with D.stage_trace("launch", timings):
            return D.fused_solve(
                *self._state, self._sketch0_cells(), self._sizes,
                self._inv_scale, self._regs_state, params=params,
                mode=mode, geometry=geometry,
                n_groups_list=self.n_groups_list)

    def _launch_dense(self, cfg, values, pane_vals, quotas, active_cells,
                      dense, key_affine, timings):
        """The dense tick: every pane packed into one upload
        (``_packed_panes``), then ``fused_tick_dense``."""
        from . import distributed as D

        params, mode, geometry = cfg
        with D.stage_trace("h2d", timings):
            with D.stage_trace("h2d.pane", timings):
                # Hash panes key on the RAW dense stream (the value pane
                # is anchor-scaled; registers key on raw bits).
                panes, layout, gid_slots, valid_slots = _packed_panes(
                    pane_vals, quotas, *dense, self.dtype,
                    values if self.has_sketch else None)
                D.count("pane_slots", layout.pane_rows * layout.quota_max)
            with D.stage_trace("h2d.put", timings):
                panes_dev = D.h2d(panes, self.dtype)
        with D.stage_trace("launch", timings):
            return D.fused_tick_dense(
                *self._state, self._bound_rows, panes_dev,
                self._sketch0_cells(), self._sizes, self._inv_scale,
                active_cells, regs=self._regs_state, params=params,
                mode=mode, geometry=geometry,
                n_groups_list=self.n_groups_list, gid_slots=gid_slots,
                valid_slots=valid_slots, key_affine=key_affine,
                bound_slots=self._bound_slots, pane_layout=layout)

    def _launch_tagged(self, cfg, values, seg, quotas, hash_limbs, timings):
        """The tagged tick: bucket-padded sample streams, then
        ``fused_tick``."""
        import jax.numpy as jnp

        from . import distributed as D

        params, mode, geometry = cfg
        with D.stage_trace("h2d", timings):
            with D.stage_trace("h2d.pane", timings):
                v_pad, s_pad, h_pads = _tagged_panes(values, seg,
                                                     self.n_cells,
                                                     hash_limbs)
                D.count("pane_slots", v_pad.size)
            with D.stage_trace("h2d.put", timings):
                q_dev = D.h2d(quotas.astype(np.float64), self.dtype)
                v_dev = D.h2d(v_pad, self.dtype)
                s_dev = D.h2d(s_pad, jnp.int32)
                hashes = (None if h_pads is None else
                          tuple(D.h2d(h, jnp.uint32) for h in h_pads))
        with D.stage_trace("launch", timings):
            return D.fused_tick(
                *self._state, v_dev, s_dev, q_dev, self._bounds,
                self._sketch0_cells(), self._sizes, self._inv_scale,
                regs=self._regs_state, hashes=hashes, params=params,
                mode=mode, geometry=geometry,
                n_groups_list=self.n_groups_list)


class _MeshPartialsView:
    """Lazy store-layout view of mesh-layout per-cell partials.

    ``_install_stats`` on a mesh stack hands each store one of these
    instead of a device slice: the d2h download + inverse permutation
    happen only if a host consumer actually materializes it
    (``np.asarray`` via ``partials_host``) — the group-stat composer
    path never pays for per-cell partials it does not read.
    """

    def __init__(self, partials, cell_map: np.ndarray) -> None:
        self._partials = partials
        self._cell_map = cell_map

    def __array__(self, dtype=None, copy=None):
        out = np.asarray(self._partials)[self._cell_map]
        return out.astype(dtype) if dtype is not None else out


class MeshDeviceStack(DeviceStack):
    """``DeviceStack`` sharded over a 1-D jax mesh: the stacked (store,
    group, block) cell axis splits by BLOCK RUNS, so every shard owns a
    contiguous run of blocks for each (store, group) and keeps those
    moment / total / ledger rows resident on its own device.

    Layout: with S shards and B blocks, each shard owns
    ``B_local = ceil(B / S)`` blocks and ``L = sum_k G_k * B_local``
    cells; the mesh cell id of store k's (g, b) cell is ::

        s * L + off_k + g * B_local + (b - s * B_local),
        s = b // B_local,  off_k = sum_{j<k} G_j * B_local

    — i.e. each shard's local slice is the familiar store-major /
    group-major / block-minor stack over its OWN blocks, so the
    per-shard program is the single-device tick verbatim
    (``distributed._tick_core`` / ``_dense_core``).  ``_cell_maps`` /
    ``_ns_map`` hold the store-layout -> mesh-layout permutations;
    trailing pad blocks (B not divisible by S) carry zero sizes, zero
    quotas and +inf cuts, so they are inert in every reduction.  With
    S = 1 the layout degenerates to exactly the single-device stack.

    The launch contract generalizes the device tier's
    zero-moment-transfer discipline to zero-moment CROSS-DEVICE
    traffic: fresh samples upload replicated (each shard keeps the ones
    whose mesh id falls in its window and retags the rest onto its
    local drop row), resident state never moves, and the only
    collective is one psum of the O(groups) stat rows — audited via
    ``distributed.collective_footprint``.
    """

    def __init__(self, stores: Sequence[DeviceMomentStore], mesh) -> None:
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec

        from . import distributed as D

        # Adopt + stack on the default device first (anchor tables, cell
        # bookkeeping, state concat) — cold-start work; everything
        # device-resident is then re-laid-out onto the mesh below.
        super().__init__(stores)
        self.mesh = mesh
        ax = D.cell_axis(mesh)
        row = PartitionSpec(ax, None)
        vec = PartitionSpec(ax)
        rep2 = PartitionSpec(None, None)
        # The tick's upload placements: cell-sharded rows and vectors,
        # and the replicated sample stream.
        self._specs = (row, vec, PartitionSpec())
        S = 1
        for n in mesh.devices.shape:
            S *= int(n)
        self.n_shards = S
        B = self.n_blocks
        K = len(self.stores)
        self.blocks_local = bl = -(-B // S)
        self.cells_local = sum(g * bl for g in self.n_groups_list)
        L = self.cells_local
        self.n_cells_mesh = S * L
        # Store-layout -> mesh-layout permutations.
        b = np.arange(B)
        s_of_b, lb = b // bl, b % bl
        self._cell_maps = []
        off = 0
        for g in self.n_groups_list:
            cmap = (s_of_b[None, :] * L + off
                    + np.arange(g)[:, None] * bl + lb[None, :])
            self._cell_maps.append(cmap.reshape(-1).astype(np.int64))
            off += g * bl
        self._ns_map = (s_of_b[None, :] * (K * bl)
                        + np.arange(K)[:, None] * bl + lb[None, :]
                        ).reshape(-1).astype(np.int64)
        cmap_all = np.concatenate(self._cell_maps)

        # Re-lay the adopted state out onto the mesh (one cold-start
        # d2h/h2d round trip; float64 numpy preserves x64 bits exactly).
        def cells(a, width):
            out = np.zeros((self.n_cells_mesh, width), dtype=np.float64)
            out[cmap_all] = np.asarray(a, dtype=np.float64)
            return D.mesh_h2d(mesh, out, row, self.dtype)

        mom_s, mom_l, totals, ns = self._state
        ns_mesh = np.zeros(S * K * bl, dtype=np.float64)
        ns_mesh[self._ns_map] = np.asarray(ns, dtype=np.float64)
        self._state = (cells(mom_s, 4), cells(mom_l, 4),
                       cells(totals, 3),
                       D.mesh_h2d(mesh, ns_mesh, vec, self.dtype))
        if self.has_sketch:
            # Register plane in mesh placement (pad cells stay all-zero
            # — inert under max); uint8 end to end, no scaling.
            regs_mesh = np.zeros((self.n_cells_mesh, _sketch.M),
                                 dtype=np.uint8)
            regs_mesh[cmap_all] = np.asarray(self._regs_state)
            self._regs_state = D.mesh_h2d(mesh, regs_mesh, row, jnp.uint8)
        # Stack constants, re-uploaded in mesh placement (pad cells get
        # inert fills: zero sizes / sketch, unit inv_scale, +inf cuts).
        sizes = np.zeros(S * K * bl, dtype=np.float64)
        sizes[self._ns_map] = np.concatenate(
            [np.asarray(st.block_sizes, dtype=np.float64)
             for st in self.stores])
        self._sizes = D.mesh_h2d(mesh, sizes, vec, self.dtype)
        sk = np.zeros(self.n_cells_mesh, dtype=np.float64)
        sk[cmap_all] = np.concatenate(
            [np.full(st.n_cells, st.sketch0 / st.scale)
             for st in self.stores])
        self._sk_cells = D.mesh_h2d(mesh, sk, vec, self.dtype)
        inv = np.ones(self.n_cells_mesh, dtype=np.float64)
        inv[cmap_all] = np.concatenate(
            [np.full(st.n_cells, 1.0 / st.scale) for st in self.stores])
        self._inv_scale = D.mesh_h2d(mesh, inv, vec, self.dtype)
        if self._uniform:
            self._bounds = D.mesh_h2d(
                mesh, np.asarray(self.stores[0]._bounds,
                                 dtype=np.float64).reshape(1, 4),
                rep2, self.dtype)
        else:
            cuts = np.full((self.n_cells_mesh, 4), np.inf,
                           dtype=np.float64)
            cuts[cmap_all] = np.concatenate(
                [np.broadcast_to(
                    np.asarray(st._bounds, dtype=np.float64), (st.n_cells, 4))
                 for st in self.stores])
            self._bounds = D.mesh_h2d(mesh, cuts, row, self.dtype)
        self._bound_rows = D.mesh_h2d(
            mesh, np.asarray(self._bound_rows, dtype=np.float64),
            rep2, self.dtype)

    # -- state plumbing (mesh placement aware) -----------------------------

    def state_slice(self, store: DeviceMomentStore, idx: int):
        k = next(i for i, st in enumerate(self.stores) if st is store)
        if idx < 3:
            return self._state[idx][self._cell_maps[k]]
        if idx == 4:
            return self._regs_state[self._cell_maps[k]]
        b = self.n_blocks
        return self._state[3][self._ns_map[k * b:(k + 1) * b]]

    def key_seg(self, k: int, store: DeviceMomentStore,
                block_ids: np.ndarray,
                group_ids: Optional[np.ndarray] = None,
                mask: Optional[np.ndarray] = None) -> np.ndarray:
        seg = store.build_seg(block_ids, group_ids, mask)
        return self._cell_maps[k][seg].astype(np.int32)

    def release(self) -> None:
        """Dissolve the mesh stack: ONE d2h download of the four mesh
        arrays, inverse-permuted per store on the host, handed back as
        plain single-device arrays.  This is the shard-aware reset path:
        a per-key drift reset (``_reset_key`` -> ``_drop_key_state``)
        releases through here, so the key's rows come back from EVERY
        shard — never shard 0 alone."""
        if self._released:
            return
        import jax.numpy as jnp

        from . import distributed as D
        mom_s, mom_l, totals, ns = (np.asarray(a, dtype=np.float64)
                                    for a in self._state)
        regs = (np.asarray(self._regs_state) if self.has_sketch else None)
        b = self.n_blocks
        for k, st in enumerate(self.stores):
            cm = self._cell_maps[k]
            nm = self._ns_map[k * b:(k + 1) * b]
            st._mom_s = D.h2d(mom_s[cm], self.dtype)
            st._mom_l = D.h2d(mom_l[cm], self.dtype)
            st._totals = D.h2d(totals[cm], self.dtype)
            st._ns_dev = D.h2d(ns[nm], self.dtype)
            if st.has_sketch:
                st._regs = D.h2d(regs[cm], jnp.uint8)
            st._owner = None
        self._state = None
        self._regs_state = None
        self._sk_cells = None
        self._inflight.clear()
        self._released = True

    def _partials_view(self, partials, k: int):
        return _MeshPartialsView(partials, self._cell_maps[k])

    # -- the tick ----------------------------------------------------------

    def _compact_plan(self, quotas: np.ndarray):
        """Shard-aware zone-pruned launch plan.  Every shard's active
        blocks sit in its own contiguous run, so each shard compacts its
        run LOCALLY and all shards pad to one shared bucketed count
        ``amax`` — the compact pane stays shard-major, and ascending
        (shard, local block) order IS ascending global block order, so
        the draw stream fills it unchanged (no ``block_pad``).  The
        cached index pair carries each shard's LOCAL scatter targets
        (cell rows within its resident slice, ledger rows within its
        ``K * B_local`` window; pads out-of-bounds -> drop), uploaded
        sharded so the per-shard program never sees another shard's
        indices."""
        if not self.block_compaction:
            return None
        S, bl = self.n_shards, self.blocks_local
        active = np.flatnonzero(quotas > 0)
        s_of = active // bl
        counts = np.bincount(s_of, minlength=S)
        # Per-shard runs are short (B / S blocks), so the retrace-bounding
        # bucket floor drops to 2 — at most log2(B_local) pane variants.
        amax = _bucket(max(int(counts.max()), 1), floor=2)
        if amax >= bl:
            return None
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec

        from . import distributed as D

        vec = PartitionSpec(D.cell_axis(self.mesh))
        K = len(self.stores)
        ext = np.full((S, amax), -1, dtype=np.int64)
        q_c = np.zeros(S * amax, dtype=np.int64)
        for s in range(S):
            la = active[s_of == s]
            ext[s, :la.size] = la - s * bl
            q_c[s * amax:s * amax + la.size] = quotas[la]
        ck = active.tobytes()
        pair = self._active_cache.get(ck)
        if pair is None:
            base, off = [], 0
            for g in self.n_groups_list:
                base.append(off + np.arange(g) * bl)
                off += g * bl
            base = np.concatenate(base)  # per-(key, group) local row base
            lb = ext[:, None, :]
            cell_idx = np.where(lb < 0, self.cells_local,
                                base[None, :, None] + lb).reshape(-1)
            ns_idx = np.where(lb < 0, K * bl,
                              (np.arange(K) * bl)[None, :, None] + lb
                              ).reshape(-1)
            if len(self._active_cache) >= 32:
                self._active_cache.clear()
            pair = (D.mesh_h2d(self.mesh, cell_idx.astype(np.int32),
                               vec, jnp.int32),
                    D.mesh_h2d(self.mesh, ns_idx.astype(np.int32),
                               vec, jnp.int32))
            self._active_cache[ck] = pair
        return q_c, active, pair

    def _launch_solve(self, cfg, timings):
        from . import distributed as D

        with D.stage_trace("launch", timings):
            solve = D.mesh_solve_fn(self.mesh, *cfg, self.n_groups_list)
            return solve(*self._state, self._sketch0_cells(), self._sizes,
                         self._inv_scale, self._regs_state)

    def _launch_dense(self, cfg, values, pane_vals, quotas, active_cells,
                      dense, key_affine, timings):
        """One upload a pane, each sharded by block rows.  A compact pane
        is already shard-major (S * amax rows), so ``block_pad`` pads only
        the uncompacted pane out to S * B_local rows."""
        import jax.numpy as jnp

        from . import distributed as D

        row, vec, _ = self._specs
        with D.stage_trace("h2d", timings):
            with D.stage_trace("h2d.pane", timings):
                v2d, pad, vmask = _dense_panes(pane_vals, quotas)
                pane_rows = (self.n_shards * self.blocks_local
                             if active_cells is None else v2d.shape[0])
                q_pad = np.zeros(pane_rows, dtype=np.float64)
                q_pad[:quotas.size] = quotas

                def block_pad(a):
                    if a.shape[0] == pane_rows:
                        return a
                    out = np.zeros((pane_rows, a.shape[1]), dtype=a.dtype)
                    out[:a.shape[0]] = a
                    return out

                gid_host, gid_slots, valid_host, valid_slots = \
                    _key_panes(vmask, *dense)
                gid_host = [block_pad(g) for g in gid_host]
                valid_host = [block_pad(m) for m in valid_host]
                panes = [block_pad(v2d), block_pad(pad)]
                hash_host = ([block_pad(h) for h in _hash_panes(values,
                                                                vmask)]
                             if self.has_sketch else None)
                D.count("pane_slots", panes[0].size)
            with D.stage_trace("h2d.put", timings):
                q_dev = D.mesh_h2d(self.mesh, q_pad, vec, self.dtype)
                gid_panes = tuple(D.mesh_h2d(self.mesh, g, row, jnp.int32)
                                  for g in gid_host)
                valid_panes = tuple(
                    D.mesh_h2d(self.mesh, m, row, self.dtype)
                    for m in valid_host)
                v_dev, pad_dev = (D.mesh_h2d(self.mesh, p, row, self.dtype)
                                  for p in panes)
                hashes = (None if hash_host is None else tuple(
                    D.mesh_h2d(self.mesh, h, row, jnp.uint32)
                    for h in hash_host))
        with D.stage_trace("launch", timings):
            fn = D.mesh_tick_dense_fn(
                self.mesh, *cfg, self.n_groups_list, gid_slots,
                valid_slots, key_affine, self._bound_slots,
                len(gid_panes), len(valid_panes))
            return fn(*self._state, v_dev, pad_dev, q_dev, gid_panes,
                      valid_panes, self._bound_rows, self._sketch0_cells(),
                      self._sizes, self._inv_scale, active_cells,
                      self._regs_state, hashes)

    def _launch_tagged(self, cfg, values, seg, quotas, hash_limbs, timings):
        """The sample streams upload replicated; ``seg`` holds MESH cell
        ids (``key_seg``)."""
        import jax.numpy as jnp

        from . import distributed as D

        _, vec, rep = self._specs
        with D.stage_trace("h2d", timings):
            with D.stage_trace("h2d.pane", timings):
                # Pad/drop id: past every shard's window, so each shard
                # retags it onto its local drop row.
                v_pad, s_pad, h_pads = _tagged_panes(
                    values, seg, self.n_cells_mesh, hash_limbs)
                q_pad = np.zeros(self.n_shards * self.blocks_local,
                                 dtype=np.float64)
                q_pad[:self.n_blocks] = quotas
                D.count("pane_slots", v_pad.size)
            with D.stage_trace("h2d.put", timings):
                q_dev = D.mesh_h2d(self.mesh, q_pad, vec, self.dtype)
                v_dev = D.mesh_h2d(self.mesh, v_pad, rep, self.dtype)
                s_dev = D.mesh_h2d(self.mesh, s_pad, rep, jnp.int32)
                hashes = (None if h_pads is None else tuple(
                    D.mesh_h2d(self.mesh, h, rep, jnp.uint32)
                    for h in h_pads))
        with D.stage_trace("launch", timings):
            fn = D.mesh_tick_fn(self.mesh, *cfg, self.n_groups_list,
                                not self._uniform)
            return fn(*self._state, v_dev, s_dev, q_dev, self._bounds,
                      self._sketch0_cells(), self._sizes, self._inv_scale,
                      self._regs_state, hashes)


def proportional_allocate(amounts: np.ndarray, budget: int) -> np.ndarray:
    """Scale non-negative integer demands down to a total budget with
    largest-remainder rounding; never exceeds the budget or any demand."""
    amounts = np.asarray(amounts, dtype=np.int64)
    total = int(amounts.sum())
    if total <= budget:
        return amounts.copy()
    if budget <= 0:
        return np.zeros_like(amounts)
    exact = amounts * (budget / total)
    out = np.floor(exact).astype(np.int64)
    rem = budget - int(out.sum())
    if rem > 0:
        frac = exact - out
        frac[out >= amounts] = -1.0
        for i in np.argsort(-frac)[:rem]:
            if out[i] < amounts[i]:
                out[i] += 1
    return np.minimum(out, amounts)


def split_budget(n_now: Sequence[float], sigmas: Sequence[float],
                 deficits: Sequence[int], budget: int,
                 min_per_store: int = 0,
                 weights: Optional[Sequence[float]] = None) -> np.ndarray:
    """Split a tick's sample budget across stores by marginal-error
    reduction (deadline-aware QoS).

    A store holding n matching samples has half-width ~ z * sigma / sqrt(n);
    the marginal reduction per extra sample is ~ sigma / n^(3/2).  Water-
    filling equalizes that marginal across stores — allocate x_i so that
    sigma_i / (n_i + x_i)^(3/2) is level — subject to 0 <= x_i <= deficit_i.
    Solved by bisection on the level; stores with unknown sigma (no samples
    yet) are treated as maximally uncertain and filled first.

    Parameters
    ----------
    n_now : sequence of float
        Matching samples each store has already accumulated.
    sigmas : sequence of float
        Observed sample sigma per store (NaN = no evidence yet, treated as
        maximally uncertain).
    deficits : sequence of int
        Samples each store still owes against its target quota.
    budget : int
        Total new samples this tick may draw.
    min_per_store : int, optional
        Per-store budget FLOOR (admission-loop QoS): before the waterfill
        runs, every store with a positive deficit is guaranteed
        ``min(deficit_i, min_per_store)`` samples, so a flood of new
        cold predicates (unknown sigma — filled first by the waterfill)
        cannot starve a nearly-converged store's small top-up forever.
        When the budget cannot cover even the floors, the floors
        themselves are split proportionally.
    weights : sequence of float, optional
        Per-store priority weights, > 0 (default: all 1.0).  A store
        with weight ``w`` waterfills as if its sigma were ``w * sigma``,
        i.e. its marginal error reduction counts ``w``-fold — so at
        equal deficit and sigma a higher-priority store receives weakly
        more samples.  Floors (``min_per_store``) are weight-independent
        and honored first; cold stores (NaN sigma) stay
        filled-before-known within their weight class.

    Returns
    -------
    numpy.ndarray
        int64 allocation per store; never exceeds a store's deficit and
        sums to at most ``budget``.

    Examples
    --------
    A converged store's 10-sample top-up survives a cold flood:

    >>> cold = [float("nan")] * 3
    >>> split_budget([9000, 1, 1, 1], [0.5] + cold,
    ...              [10, 5000, 5000, 5000], 300).tolist()
    [0, 100, 100, 100]
    >>> split_budget([9000, 1, 1, 1], [0.5] + cold,
    ...              [10, 5000, 5000, 5000], 300,
    ...              min_per_store=10).tolist()
    [10, 97, 97, 96]
    """
    n_now = np.maximum(np.asarray(n_now, dtype=np.float64).reshape(-1), 1.0)
    sigmas = np.asarray(sigmas, dtype=np.float64).reshape(-1)
    deficits = np.maximum(
        np.asarray(deficits, dtype=np.int64).reshape(-1), 0)
    if not (n_now.shape == sigmas.shape == deficits.shape):
        raise ValueError("n_now, sigmas, deficits must align")
    if weights is None:
        w = np.ones_like(n_now)
    else:
        w = np.asarray(weights, dtype=np.float64).reshape(-1)
        if w.shape != n_now.shape:
            raise ValueError("weights must align with n_now")
        if not np.all(np.isfinite(w)) or np.any(w <= 0):
            raise ValueError("weights must be finite and > 0")
    budget = int(budget)
    total = int(deficits.sum())
    if budget >= total or total == 0:
        return deficits.copy()
    if min_per_store > 0:
        base = np.minimum(deficits, int(min_per_store))
        covered = int(base.sum())
        if covered >= budget:
            return proportional_allocate(base, budget)
        rest = split_budget(n_now + base, sigmas, deficits - base,
                            budget - covered, weights=weights)
        return base + rest
    # Unknown sigma (cold store, NaN) -> dominate every known marginal.
    # A KNOWN zero sigma stays zero: its error cannot shrink, so it is
    # served last, not first.
    known = sigmas[np.isfinite(sigmas) & (sigmas > 0)]
    fill = (float(known.max()) * 1e3) if known.size else 1.0
    # Priority weight scales the EFFECTIVE sigma: a weight-w store's
    # marginal w*sigma/n^1.5 levels against everyone else's, so it
    # drains first at equal observed error.  A known zero sigma stays
    # zero under any weight.
    sig = np.where(np.isfinite(sigmas), np.maximum(sigmas, 0.0), fill) * w
    if not np.any(sig > 0):
        # No marginal signal at all: plain proportional split.
        return proportional_allocate(deficits, budget)

    def allocated(level: float) -> np.ndarray:
        want = np.power(sig / level, 2.0 / 3.0) - n_now
        return np.clip(want, 0.0, deficits.astype(np.float64))

    # Marginal at zero extra samples bounds the level from above.
    hi = float(np.max(sig / np.power(n_now, 1.5))) * 2.0
    lo = hi * 1e-12
    for _ in range(80):
        mid = math.sqrt(hi * lo)
        if allocated(mid).sum() > budget:
            lo = mid  # level too low -> giving out too much
        else:
            hi = mid
    x = np.floor(allocated(hi)).astype(np.int64)
    # Hand out the rounding remainder greedily by current marginal gain.
    rem = budget - int(x.sum())
    if rem > 0:
        gain = sig / np.power(n_now + x, 1.5)
        gain[x >= deficits] = -np.inf
        for i in np.argsort(-gain)[:rem]:
            if gain[i] > -np.inf and x[i] < deficits[i]:
                x[i] += 1
    # Whatever the waterfill could not place (e.g. the deficit bulk sits
    # on zero-marginal stores) still belongs to this tick's budget: fill
    # remaining capacity proportionally instead of dropping it.
    rem = budget - int(x.sum())
    if rem > 0:
        x = x + proportional_allocate(deficits - x, rem)
    return x
