"""Operations and bytes the fused tick needs, from the work of a tick.

One tick folds ``n`` drawn samples into the resident moments of each key of
its stack and re-solves every cell.  The least work that does this:

* read each sample's value once (4 bytes, float32) and, per distinct
  predicate and GROUP BY column, one mask or code word (4 bytes);
* per key and sample: classify against four cuts and form the 11 moment
  columns (S and L region count, v, v^2, v^3 and the plain count, v, v^2):
  ``ELEMENT_FLOPS``; a grouped key then contracts those 11 columns against
  its group one-hot: ``2 * 11 * n_groups`` more;
* read and write each key's resident cells (11 float32 moments a cell) and
  read its per-block draw ledger.

Padding of the device panes, the re-solve of Phase 2 and the group rows are
not counted: they are overhead of the program's layout, not of the work.
A roofline share from these numbers can therefore only understate, never
overstate, what the kernel achieves.
"""
from __future__ import annotations

ELEMENT_FLOPS = 4 + 11
MOMENT_COLUMNS = 11
F32 = 4


def fused_tick(n_samples: int, keys: list, n_blocks: int) -> tuple:
    """``(flops, bytes)`` of one fused tick over ``n_samples`` drawn rows.

    ``keys`` lists each stacked key as ``(n_groups, where, group_by)``; the
    WHERE and GROUP BY entries only say which mask and code panes are
    distinct (keys sharing a predicate share its pane).
    """
    flops = 0.0
    cells = 0
    masks, codes = set(), set()
    for n_groups, where, group_by in keys:
        flops += n_samples * ELEMENT_FLOPS
        if n_groups > 1:
            flops += n_samples * 2.0 * MOMENT_COLUMNS * n_groups
        cells += n_groups * n_blocks
        if where is not None:
            masks.add(where)
        if group_by is not None:
            codes.add(group_by)
    sample_bytes = n_samples * F32 * (1 + len(masks) + len(codes))
    state_bytes = 2 * cells * MOMENT_COLUMNS * F32 + len(keys) * n_blocks * F32
    return flops, float(sample_bytes + state_bytes)
