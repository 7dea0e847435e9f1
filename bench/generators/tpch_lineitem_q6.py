"""Generator ``tpch_lineitem_q6``: the TPC-H ``lineitem`` columns that Q6
(clause 2.4.6) reads, generated from a seed.

``l_extendedprice``, ``l_quantity`` and ``l_shipdate`` are
``tpch_lineitem``'s own draws for the same seed and configuration (clause
4.2.3; its rules and its day numbers).  ``l_discount`` is added, uniform
over {0.00, 0.01, ..., 0.10} (clause 4.2.3), from a stream of its own, so
every other generator's table keeps its bits.  The four columns are
float64; ``rf_ls`` is not kept (Q6 has no GROUP BY).
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from isla_bench.deployment import load, seed_rng

STREAM_DISCOUNT = 6
COLUMNS = ("l_extendedprice", "l_quantity", "l_shipdate")
DISCOUNT_CODES = 11          # 0.00 .. 0.10 in hundredths


def generate(cfg: dict, seed: int):
    """The configuration's Q6 columns for ``seed`` (``cfg`` as
    ``tpch_lineitem.generate`` takes it; its ``columns`` are set here)."""
    lineitem = load(Path(__file__).resolve().parents[2], "generators",
                    "tpch_lineitem")
    table = lineitem.generate(dict(cfg, columns=list(COLUMNS)), seed)
    code = seed_rng(seed, STREAM_DISCOUNT).integers(
        0, DISCOUNT_CODES, size=table.rows, dtype=np.int8)
    table.columns["l_discount"] = code.astype(np.float64) / 100.0
    return table
