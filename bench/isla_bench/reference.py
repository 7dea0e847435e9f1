"""The plain reference the benchmark holds the served answers to.

Nothing here imports the program.  Two references, whatever the query
kind (the exact answers the ``(e, beta)`` guarantee is held against are the
kind's own, ``bench/queries/<kind>.py``):

* ``sample_stats`` -- what an answer computed from a given set of drawn
  rows has to say about those rows: how many matched, the matching
  population they estimate, their plain mean and spread.  The harness logs
  the rows the program drew for a sample of queries, and this recomputes,
  in float64, the fields the program reports from its resident moments.
* ``calibrated_stats`` -- the ISLA estimate (calibrated mode) that AVG and
  SUM answers report, from the same drawn rows and the anchor that the
  logged pilot rows fix (``Anchors``): per (group, block) cell, the S and
  L region moments, Theorem 3's ``c`` and the calibrated contraction
  toward the anchor's sketch, weighted by the cell's estimated population.

``precision="bfloat16"`` computes the same from values (and squares)
rounded to bfloat16, the cell sums rounded to float32: the control that
has to fail the check.
"""
from __future__ import annotations

import itertools
import math
from statistics import NormalDist

import numpy as np

# A drawn value this close to a region cut (relative) may fall on either
# side of it in float32 on the device; the reference takes both sides.
CUT_TOLERANCE = 1e-6
# Ambiguous rows of one cell enumerated both ways (2^n combinations);
# further ones keep their float64 side.
MAX_AMBIGUOUS = 8
# Theorem 3's slope k this small against the cell's mean c is nought to
# rounding: the cell may answer c (k nought) or move toward the sketch.
K_TOLERANCE = 1e-6


def z_score(beta: float) -> float:
    return float(NormalDist().inv_cdf((1.0 + beta) / 2.0))


def _to_bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even)."""
    u = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    u = u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))
    return (u & np.uint32(0xFFFF0000)).view(np.float32)


def sample_stats(block_ids: np.ndarray, values: np.ndarray,
                 match: np.ndarray, groups: np.ndarray | None,
                 n_groups: int, block_sizes: np.ndarray,
                 precision: str = "float64") -> dict:
    """Statistics of drawn rows under one key (WHERE mask, GROUP BY codes).

    ``block_ids``/``values``/``match``/``groups`` are aligned per drawn row;
    ``block_sizes`` are the catalog row counts.  Returns per group ``n``
    (matching rows), ``w`` (the matching population they estimate:
    ``sum_b size_b * matched_b / drawn_b``), ``mean`` and ``sigma`` (plain,
    ddof 1) and the same over all groups, plus ``drawn`` per block.
    """
    n_b = block_sizes.shape[0]
    drawn = np.bincount(block_ids, minlength=n_b).astype(np.float64)
    g = np.zeros(block_ids.shape, np.int64) if groups is None else groups
    g = g[match]
    b = block_ids[match]
    v = values[match]
    cell = g * n_b + b
    n_cells = n_groups * n_b
    if precision == "float64":
        v1 = v.astype(np.float64)
        v2 = v1 * v1
        acc = np.float64
    elif precision == "bfloat16":
        vf = _to_bf16(v.astype(np.float32))
        v1 = vf
        v2 = _to_bf16(vf * vf)
        acc = np.float32
    else:
        raise ValueError(f"unknown precision {precision!r}")
    cnt = np.bincount(cell, minlength=n_cells).astype(np.float64)
    s1 = np.bincount(cell, weights=v1, minlength=n_cells).astype(acc)
    s2 = np.bincount(cell, weights=v2, minlength=n_cells).astype(acc)
    cnt = cnt.reshape(n_groups, n_b)
    s1 = s1.reshape(n_groups, n_b).astype(np.float64)
    s2 = s2.reshape(n_groups, n_b).astype(np.float64)
    w = (block_sizes[None, :] * cnt / np.maximum(drawn, 1.0)[None, :])

    def moments(n, t1, t2):
        mean = t1 / n if n > 0 else float("nan")
        if n >= 2:
            var = max(t2 / n - mean * mean, 0.0)
            sigma = math.sqrt(var * n / (n - 1.0))
        else:
            sigma = float("nan")
        return mean, sigma

    per_group = []
    for k in range(n_groups):
        n = float(cnt[k].sum())
        mean, sigma = moments(n, float(s1[k].sum()), float(s2[k].sum()))
        per_group.append(dict(n=n, w=float(w[k].sum()), mean=mean,
                              sigma=sigma))
    n_all = float(cnt.sum())
    mean_all, sigma_all = moments(n_all, float(s1.sum()), float(s2.sum()))
    return dict(n=n_all, w=float(w.sum()), mean=mean_all, sigma=sigma_all,
                groups=per_group if groups is not None else None,
                drawn=drawn, total_drawn=float(drawn.sum()))


def answer_fields(agg: str, beta: float, st: dict,
                  est: dict | None = None) -> dict:
    """The fields of a served answer that ``st`` (``sample_stats``) and,
    for AVG and SUM, ``est`` (``calibrated_stats``) fix: exact counts,
    relative-tolerance population / moment fields (``rel``) and the ISLA
    estimate as a (low, high) range (``est``)."""
    z = z_score(beta)
    counts = {"matched": st["n"], "drawn": st["total_drawn"]}
    rel = {"population": st["w"]}
    ranges = {}
    if agg == "COUNT":
        rel["value"] = st["w"]
        rel["mean"] = st["mean"]
    elif st["n"] >= 2:
        hw = z * st["sigma"] / math.sqrt(st["n"])
        rel["half_width"] = hw if agg == "AVG" else st["w"] * hw
    if agg in ("AVG", "SUM") and est is not None:
        ranges["mean"] = est["mean"]
        ranges["value"] = _scaled(est["mean"], 1.0 if agg == "AVG"
                                  else st["w"])
    for k, gs in enumerate(st.get("groups") or []):
        counts[f"g{k}.matched"] = gs["n"]
        rel[f"g{k}.population"] = gs["w"]
        if agg == "COUNT":
            rel[f"g{k}.value"] = gs["w"]
            rel[f"g{k}.mean"] = gs["mean"]
        elif est is not None and agg in ("AVG", "SUM"):
            m = est["groups"][k]
            ranges[f"g{k}.mean"] = m
            ranges[f"g{k}.value"] = _scaled(m, 1.0 if agg == "AVG"
                                            else gs["w"])
    return dict(counts=counts, rel=rel, ranges=ranges)


def _scaled(r: tuple, f: float) -> tuple:
    a, b = f * r[0], f * r[1]
    return (min(a, b), max(a, b))


# ---------------------------------------------------------------------------
# The ISLA estimate (calibrated mode)
# ---------------------------------------------------------------------------

def split_first_tick(calls: list, n_blocks: int) -> tuple:
    """(bootstrap calls, pilot calls) of a cold executor's first tick.

    ``calls`` are the ``(block, rows)`` sampler calls of that tick in
    order: a bootstrap draw over some blocks, the pilot (every block once,
    in block order), then the tick's own pass (blocks in increasing
    order).  The pilot is the first sweep over every block after which
    only an increasing run of blocks follows."""
    blocks = [b for b, _ in calls]
    sweep = list(range(n_blocks))
    for p in range(len(calls) - n_blocks + 1):
        if blocks[p:p + n_blocks] != sweep:
            continue
        rest = blocks[p + n_blocks:]
        if all(a < b for a, b in zip(rest, rest[1:])):
            return calls[:p], calls[p:p + n_blocks]
    raise ValueError("no pilot sweep in the first tick's sampler calls")


def _columns(calls: list) -> dict:
    if not calls:
        return {}
    keys = calls[0][1].keys()
    return {k: np.concatenate([np.asarray(r[k], np.float64)
                               for _, r in calls]) for k in keys}


def lambda_star(p1: float, p2: float) -> float:
    """The calibrated step ratio: the S-and-L band mean's first-order
    response to a sketch error, for normal data with bands at (p1, p2)
    sigma (truncated-normal geometry)."""
    phi = lambda z: math.exp(-0.5 * z * z) / math.sqrt(2.0 * math.pi)  # noqa: E731
    cdf = lambda z: 0.5 * (1.0 + math.erf(z / math.sqrt(2.0)))  # noqa: E731
    return (p1 * phi(p1) - p2 * phi(p2)) / (cdf(p2) - cdf(p1))


class Anchors:
    """The frames the estimate is taken in, fixed by the pilot rows.

    The global anchor: the pilot's mean and sample spread (ddof 1); values
    are shifted to be positive only where the pilot saw a value <= 0.  A
    key with a WHERE takes its own anchor from the pilot and bootstrap rows
    (``rows``) that match it, where at least ``min_support`` match (and not
    all do).  Region cuts: S = (sketch - p2 sigma, sketch - p1 sigma), L =
    (sketch + p1 sigma, sketch + p2 sigma).  ``values`` maps a dict of
    columns to the values aggregated."""

    def __init__(self, boot_calls: list, pilot_calls: list, values,
                 est: dict, precision: str = "float64"):
        self.est = est
        self.precision = precision
        self.rows = _columns(boot_calls + pilot_calls)
        self._values = self._vals(values(self.rows))
        self.global_ = self._frame(self._vals(values(_columns(pilot_calls))))

    def _vals(self, v: np.ndarray) -> np.ndarray:
        if self.precision == "bfloat16":
            return _to_bf16(v.astype(np.float32)).astype(np.float64)
        return np.asarray(v, np.float64)

    def _frame(self, vals: np.ndarray, sigma: float | None = None) -> dict:
        sigma = float(np.std(vals, ddof=1)) if sigma is None else sigma
        mean, lo = float(np.mean(vals)), float(np.min(vals))
        shift = 0.0 if lo > 0.0 else -lo + sigma
        sk = mean + shift
        p1, p2 = float(self.est["p1"]), float(self.est["p2"])
        return dict(sketch0=sk, shift=shift, sigma=sigma,
                    cuts=(sk - p2 * sigma, sk - p1 * sigma,
                          sk + p1 * sigma, sk + p2 * sigma))

    def for_mask(self, m: np.ndarray) -> dict:
        """The anchor of a key whose WHERE picks the rows ``m`` of
        ``rows``."""
        if m.size == 0 or bool(m.all()):
            return self.global_
        if int(m.sum()) < max(int(self.est["anchor_min_support"]), 2):
            return self.global_
        vals = self._values[m]
        sigma = float(np.std(vals, ddof=1))
        if not math.isfinite(sigma) or sigma <= 0:
            return self.global_
        return self._frame(vals, sigma)


def _region_moments(x: np.ndarray, cell: np.ndarray, n_cells: int,
                    sel: np.ndarray) -> np.ndarray:
    """(n_cells, 4) rows (count, s1, s2, s3) of the rows ``sel`` picks."""
    xs, cs = x[sel], cell[sel]
    return np.stack([np.bincount(cs, minlength=n_cells).astype(np.float64),
                     np.bincount(cs, weights=xs, minlength=n_cells),
                     np.bincount(cs, weights=xs * xs, minlength=n_cells),
                     np.bincount(cs, weights=xs * xs * xs,
                                 minlength=n_cells)], axis=1)


def calibrated_partials(mom_s: np.ndarray, mom_l: np.ndarray,
                        sketch0: float, est: dict) -> tuple:
    """Per cell: (low, high) partial answer on the shifted scale, and
    whether it fell back to the sketch.  The calibrated estimate of a cell
    with rows in S and in L is ``c - lam (1 - eta^t) (c - sketch0) / (1 +
    lam)``: ``c`` the mean of its S and L rows, ``t`` the rounds until
    ``eta^t |c - sketch0|`` is within ``thr``, ``lam`` the calibrated step
    ratio.  With fewer than ``min_region_count`` rows in S or L it answers
    the sketch; where Theorem 3's slope ``k`` is nought (one row in each
    region) it answers ``c``.  Any other ``k`` within ``K_TOLERANCE`` of
    nought (relative to ``c``) is nought to rounding (as with constant
    regions of equal size), so either answer stands: the cell's range
    holds both."""
    u, sx, sx2, sx3 = (mom_s[:, i] for i in range(4))
    v, sy, sy2, sy3 = (mom_l[:, i] for i in range(4))
    mrc = float(est["min_region_count"])
    empty = (u < mrc) | (v < mrc)
    eta, thr = float(est["eta"]), float(est["thr"])
    lam = lambda_star(float(est["p1"]), float(est["p2"]))
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        dev = np.where(v > 0, u / v, np.inf)
        mild = (((est["mild_lo"] <= dev) & (dev < 0.97))
                | ((1.03 < dev) & (dev <= est["mild_hi"])))
        qp = np.where(mild, est["q_mild"], est["q_strong"])
        q = np.where(dev > 1.0, 1.0 / qp, qp)
        q = np.where((0.97 <= dev) & (dev <= 1.03), 1.0, q)
        t2 = sx2 + sy2
        term_s = (t2 * sx - sx3) / ((1.0 + v / (q * u)) * (u * t2 - sx2))
        term_l = v * sy3 / ((q * u + v) * sy2)
        c = (sx + sy) / (u + v)
        k = term_s + term_l - c
        d0 = c - sketch0
        ad = np.abs(d0)
        t = np.where(ad > thr, np.ceil(np.log(ad / thr)
                                       / math.log(1.0 / eta)), 0.0)
        avg = c - lam * (1.0 - eta ** t) * d0 / (1.0 + lam)
    # One row in each region: q = 1 and k = (x + y)/2 - c is nought.
    single = (u == 1) & (v == 1)
    flat = ~single & (np.abs(k) <= K_TOLERANCE * np.abs(c))
    avg = np.where(single, c, avg)
    lo = np.where(flat, np.minimum(c, avg), avg)
    hi = np.where(flat, np.maximum(c, avg), avg)
    return np.where(empty, sketch0, lo), np.where(empty, sketch0, hi), empty


def calibrated_stats(block_ids: np.ndarray, values: np.ndarray,
                     match: np.ndarray, groups: np.ndarray | None,
                     n_groups: int, block_sizes: np.ndarray, frame: dict,
                     est: dict, precision: str = "float64") -> dict:
    """The ISLA mean (raw scale) of drawn rows under one key, overall and
    per group, each as a (low, high) range: a row within
    ``CUT_TOLERANCE`` of a region cut is taken on both sides of it (the
    bfloat16 control takes its own side, so its range is a point).
    Cells are weighted by their estimated matching population, as
    ``sample_stats``'s ``w``."""
    n_b = block_sizes.shape[0]
    drawn = np.bincount(block_ids, minlength=n_b).astype(np.float64)
    g = np.zeros(block_ids.shape, np.int64) if groups is None else groups
    n_cells = n_groups * n_b
    cell = (g * n_b + block_ids)[match]
    raw = values[match]
    if precision == "bfloat16":
        x = (_to_bf16(raw.astype(np.float32)).astype(np.float64)
             + frame["shift"])
    else:
        x = raw.astype(np.float64) + frame["shift"]
    s_lo, s_hi, l_lo, l_hi = frame["cuts"]
    in_s = (x > s_lo) & (x < s_hi)
    in_l = (x > l_lo) & (x < l_hi)
    amb = np.zeros(x.shape, bool)
    if precision == "float64":
        for cut in frame["cuts"]:
            amb |= np.abs(x - cut) <= CUT_TOLERANCE * abs(cut)
    mom_s = _region_moments(x, cell, n_cells, in_s & ~amb)
    mom_l = _region_moments(x, cell, n_cells, in_l & ~amb)
    lo, hi, _ = calibrated_partials(mom_s, mom_l, frame["sketch0"], est)
    cut_s = x < frame["sketch0"]     # the S cuts lie below the sketch
    for c in np.unique(cell[amb]):
        rows = np.flatnonzero(amb & (cell == c))
        extra = rows[MAX_AMBIGUOUS:]
        rows = rows[:MAX_AMBIGUOUS]
        base_s, base_l = mom_s[c].copy(), mom_l[c].copy()
        for r in extra:
            if in_s[r]:
                base_s += _moment_row(x[r])
            if in_l[r]:
                base_l += _moment_row(x[r])
        combos = list(itertools.product((False, True), repeat=len(rows)))
        ms = np.tile(base_s, (len(combos), 1))
        ml = np.tile(base_l, (len(combos), 1))
        for i, pick in enumerate(combos):
            for r, inside in zip(rows, pick):
                if inside:
                    (ms if cut_s[r] else ml)[i] += _moment_row(x[r])
        p_lo, p_hi, _ = calibrated_partials(ms, ml, frame["sketch0"], est)
        lo[c], hi[c] = p_lo.min(), p_hi.max()
    cnt = np.bincount(cell, minlength=n_cells).astype(np.float64)
    w = (block_sizes[None, :] * cnt.reshape(n_groups, n_b)
         / np.maximum(drawn, 1.0)[None, :])
    lo, hi = lo.reshape(n_groups, n_b), hi.reshape(n_groups, n_b)
    shift = frame["shift"]

    def mean(ws, a, b):
        tot = float(ws.sum())
        if tot <= 0:
            return (math.nan, math.nan)
        return (float(np.where(ws > 0, a * ws, 0.0).sum() / tot) - shift,
                float(np.where(ws > 0, b * ws, 0.0).sum() / tot) - shift)

    per_group = [mean(w[k], lo[k], hi[k]) for k in range(n_groups)]
    return dict(mean=mean(w, lo, hi),
                groups=per_group if groups is not None else None)


def _moment_row(x: float) -> np.ndarray:
    return np.array([1.0, x, x * x, x * x * x])


def range_gap(got: float, want: tuple) -> float:
    """Relative distance of ``got`` outside the range ``want``; NaN against
    NaN is no gap, NaN against a number is."""
    lo, hi = want
    if math.isnan(lo) and math.isnan(got):
        return 0.0
    if math.isnan(lo) or math.isnan(got):
        return math.inf
    if lo <= got <= hi:
        return 0.0
    return min(abs(got - lo), abs(got - hi)) / max(abs(lo), abs(hi), 1e-30)


def required_samples(e: float, sigma: float, beta: float) -> float:
    """Eq. 1's m: the matching samples an (e, beta) bound needs at spread
    ``sigma`` (m = z^2 sigma^2 / e^2)."""
    z = z_score(beta)
    return z * z * sigma * sigma / (e * e)


def unbacked_bounds(agg: str, e: float, beta: float, st: dict,
                    answer: dict) -> int:
    """Rows of an AVG answer that claim the (e, beta) bound on fewer
    matching samples than Eq. 1 asks for at the spread of those samples.
    (A relative slack of 1e-4 on m keeps float32 rounding of the spread
    from deciding a row that sits exactly at m.)"""
    if agg != "AVG":
        return 0
    rows = [(st, answer)]
    rows += [(st["groups"][g["group"]], g) for g in answer["groups"] or []]
    bad = 0
    for ref, got in rows:
        if got["bound"] is None:
            continue
        if ref["n"] < 2 or math.isnan(ref["sigma"]):
            bad += 1
            continue
        m = required_samples(e, ref["sigma"], beta)
        bad += int(ref["n"] < m * (1.0 - 1e-4))
    return bad


def rel_gap(got: float, want: float) -> float:
    """Relative gap; NaN against NaN is no gap, NaN against a number is."""
    if math.isnan(want) and math.isnan(got):
        return 0.0
    if math.isnan(want) or math.isnan(got):
        return math.inf
    if got == want:
        return 0.0
    return abs(got - want) / max(abs(want), 1e-30)
