#!/usr/bin/env python3
"""Smoke run of the device-resident ISLA serving tick on a TPU.

    python chip_smoke.py            # phases A, B, C on one chip
    python chip_smoke.py --chips 4  # phase D alone, on four chips

A. Device: JAX must report a TPU; the script never runs on another backend.
B. Main path at the serving tier's deployment shape (1000 blocks x 16
   groups x 20 000 rows per block): the admission loop that
   ``python -m repro.launch.serve --workload isla --incremental`` builds,
   4 ticks x 6 random queries, run on ``route="device"`` serial, then
   pipelined, then on ``route="host"`` with the same seeds.  Device answers
   must match host answers within the fp32 tolerance contract
   (docs/ARCHITECTURE.md): values and per-group values 1e-5 relative, VAR
   1e-3, COUNT DISTINCT exactly; the resident moments likewise, and the
   register planes bit for bit.  Answers are also held against exact
   numpy answers over the generated tables.
C. Every ``*_pallas`` kernel compiled (``interpret=False``) at tm=512
   against its jnp reference.
D. (``--chips 4``) the phase-B mix on ``route="mesh"`` over four chips
   against ``route="device"`` on one, with every resident state array
   sharded over all four.

Wall times printed here are smoke times, not benchmarks.  Every failed
check or exception exits non-zero; the last line of stdout, printed only
when every phase passed, is one JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# The deployment shape of BENCH_device.json / BENCH_pipeline.json and the
# serve CLI: 1000 blocks x 16 groups, 20 000 rows per block.
DEPLOY = dict(n_blocks=1000, n_groups=16, rows=20000, ticks=4, qpt=6)
SEED = 0
PRECISION = 0.5          # serve's default --precision
TM = 512                 # the kernels' production tile rows

# Tolerance contract, docs/ARCHITECTURE.md "Bit-parity guarantees".
REL_TOL = 1e-5
VAR_REL_TOL = 1e-3

AGGS = ("AVG", "SUM", "COUNT", "VAR", "count_distinct")


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _rel(a: float, b: float) -> float:
    if math.isnan(a) and math.isnan(b):
        return 0.0
    if math.isnan(a) or math.isnan(b):
        return math.inf
    if a == b:
        return 0.0
    return abs(a - b) / max(abs(a), abs(b))


# ---------------------------------------------------------------------------
# Compile accounting
# ---------------------------------------------------------------------------

_COMPILE = {"seconds": 0.0, "count": 0}
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _watch_compiles() -> None:
    """Sum backend-compile durations (``jax.monitoring``) into _COMPILE."""
    import jax

    if _COMPILE.get("watching"):
        return
    _COMPILE["watching"] = True

    def on_duration(event, secs, **_):
        if event == BACKEND_COMPILE_EVENT:
            _COMPILE["seconds"] += secs
            _COMPILE["count"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def _peak_bytes(device) -> str:
    stats = device.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported by this backend"
    return str(stats["peak_bytes_in_use"])


# ---------------------------------------------------------------------------
# Phase B: the served path
# ---------------------------------------------------------------------------

def query_mix(n_blocks: int, ticks: int, qpt: int, seed: int, e: float):
    """The serve CLI's query stream: ``ticks`` batches of ``qpt``."""
    from repro.launch.serve import random_tick_queries

    qrng = np.random.default_rng(seed + 2)
    return [random_tick_queries(qrng, qpt, e, n_days=max(n_blocks // 2, 1))
            for _ in range(ticks)]


def check_mix_coverage(batches) -> None:
    qs = [q for b in batches for q in b]
    for agg in AGGS:
        check(any(q.agg == agg for q in qs), f"query mix lacks {agg}")
    for name, has in (("WHERE", lambda q: q.where is not None),
                      ("GROUP BY", lambda q: q.group_by is not None)):
        check(any(has(q) for q in qs), f"query mix lacks {name}")
        check(not all(has(q) for q in qs), f"every query has a {name}")


def run_mix(samplers, tables, n_groups: int, batches, seed: int, e: float,
            route: str, pipeline: bool = False):
    """One admission loop over the mix; returns (loop, answers in
    submission order, smoke seconds)."""
    from repro.launch.serve import build_isla_loop

    loop = build_isla_loop(samplers, tables, n_groups, e, seed,
                           route=route, incremental=True, pipeline=pipeline)
    t0 = time.perf_counter()
    done = []
    for batch in batches:
        for q in batch:
            loop.submit(q)
        done.extend(loop.tick())
    done.extend(loop.run_until_drained())
    dt = time.perf_counter() - t0
    done.sort(key=lambda t: t.tid)
    check(len(done) == sum(len(b) for b in batches),
          f"{route}: answered {len(done)} of "
          f"{sum(len(b) for b in batches)} queries")
    return loop, [t.answer for t in done], dt


def answer_diffs(ref, got, label: str) -> dict:
    """Largest relative difference per class; fails beyond the contract."""
    worst = {"value": 0.0, "var": 0.0, "distinct": 0.0}
    for i, (a, b) in enumerate(zip(ref, got)):
        check(a.query.agg == b.query.agg and a.query.where == b.query.where
              and a.query.group_by == b.query.group_by,
              f"{label}: query #{i} differs between the runs")
        pairs = [(a.value, b.value)]
        ga, gb = a.groups or [], b.groups or []
        check(len(ga) == len(gb), f"{label}: #{i} group rows differ")
        pairs += [(x.value, y.value) for x, y in zip(ga, gb)]
        d = max(_rel(x, y) for x, y in pairs)
        if a.query.agg == "count_distinct":
            check(d == 0.0, f"{label}: #{i} COUNT DISTINCT differs "
                            f"({pairs})")
            cls, tol = "distinct", 0.0
        elif a.query.agg == "VAR":
            cls, tol = "var", VAR_REL_TOL
        else:
            cls, tol = "value", REL_TOL
        check(d <= tol, f"{label}: #{i} {a.query.agg} differs by {d:.3g} "
                        f"relative (limit {tol:g})")
        worst[cls] = max(worst[cls], d)
    return worst


def _cols_rel(x: np.ndarray, y: np.ndarray) -> float:
    """Largest elementwise relative difference, each column scaled by its
    largest magnitude (so empty cells and near-zero sums stay fair)."""
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    if x.size == 0:
        return 0.0
    scale = np.maximum(np.abs(x).max(axis=0), np.abs(y).max(axis=0))
    scale = np.where(scale > 0, scale, 1.0)
    return float((np.abs(x - y) / scale).max())


def _host_view(store):
    """A store's state as a host float64 ``MomentStore``."""
    return store.to_host() if hasattr(store, "to_host") else store


def _stores(ex) -> dict:
    """StoreKey -> the store holding the key's authoritative state: the
    device mirrors on the device and mesh routes, else the host stores."""
    return ex._device_stores or ex._stores


def state_diffs(ref_ex, dev_ex, label: str) -> float:
    """Resident state: device stores (downloaded) vs the reference
    executor's stores, per StoreKey — moments within REL_TOL, register
    planes bit for bit."""
    ref_st, dev_st = _stores(ref_ex), _stores(dev_ex)
    check(set(ref_st) == set(dev_st),
          f"{label}: the runs built different store keys")
    worst = 0.0
    for key, store in ref_st.items():
        h = _host_view(store)
        d = _host_view(dev_st[key])
        check(np.array_equal(h.n_sampled, d.n_sampled),
              f"{label}: {key.describe()} draw ledgers differ")
        if h.has_regions:
            worst = max(worst, _cols_rel(h.mom_s, d.mom_s),
                        _cols_rel(h.mom_l, d.mom_l))
        if h.has_totals:
            worst = max(worst, _cols_rel(h.totals, d.totals))
        if h.has_sketch:
            check(np.array_equal(h.regs, d.regs),
                  f"{label}: {key.describe()} register planes differ")
    check(worst <= REL_TOL, f"{label}: resident moments differ by "
                            f"{worst:.3g} (limit {REL_TOL:g})")
    return worst


def resident_arrays(ex):
    """(name, array) of every stacked resident state array."""
    out = []
    for i, stack in enumerate(ex._device_stacks.values()):
        for name, arr in zip(("mom_s", "mom_l", "totals", "n_sampled"),
                             stack._state):
            out.append((f"stack{i}.{name}", arr))
        if stack._regs_state is not None:
            out.append((f"stack{i}.regs", stack._regs_state))
    return out


def exact_stats(tables, block_size: int):
    """Exact answers over the generated tables, per (where, group_by):
    each table row stands for ``block_size / rows`` catalog rows."""
    cols = {k: np.concatenate([t[k] for t in tables]) for k in tables[0]}
    w = np.concatenate([np.full(len(t["value"]), block_size / len(t["value"]))
                        for t in tables])
    cache = {}

    def stats(where, group_by, n_groups):
        key = (where, group_by)
        if key not in cache:
            m = np.ones(len(w), bool) if where is None else where.mask(cols)
            v, wm = cols["value"][m], w[m]
            if group_by is None:
                g, n = np.zeros(len(v), np.intp), 1
            else:
                g, n = cols[group_by][m].astype(np.intp), n_groups
            cnt = np.bincount(g, wm, n)
            s1 = np.bincount(g, wm * v, n)
            s2 = np.bincount(g, wm * v * v, n)
            with np.errstate(invalid="ignore", divide="ignore"):
                avg = s1 / cnt
                var = s2 / cnt - avg * avg
            tot = cnt.sum()
            all_avg = s1.sum() / tot
            cache[key] = dict(
                groups=dict(AVG=avg, SUM=s1, COUNT=cnt, VAR=var),
                all=dict(AVG=all_avg, SUM=s1.sum(), COUNT=tot,
                         VAR=s2.sum() / tot - all_avg * all_avg))
        return cache[key]

    return stats


def bound_report(answers, tables, n_groups: int, block_size: int) -> dict:
    """Earned bounds against the exact answers, and the error of every
    best-effort answer.  COUNT DISTINCT estimates the distinct values of
    the drawn stream, which the tables do not define, so it is left out."""
    stats = exact_stats(tables, block_size)
    inside = earned = 0
    best_effort = []
    for i, a in enumerate(answers):
        q = a.query
        if q.agg == "count_distinct":
            continue
        ex = stats(q.where, q.group_by, n_groups)
        rows = [("all", a.value, a.error_bound, ex["all"][q.agg])]
        for g in a.groups or []:
            rows.append((f"g{g.group}", g.value, g.error_bound,
                         ex["groups"][q.agg][g.group]))
        for name, val, bound, truth in rows:
            if bound is not None:
                earned += 1
                inside += int(abs(val - truth) <= bound + 1e-9 * abs(truth))
            else:
                best_effort.append((i, q, name, val, truth))
    return dict(earned=earned, inside=inside, best_effort=best_effort)


def _describe_query(q) -> str:
    sel = q.where.describe() if q.where is not None else "TRUE"
    return f"{q.agg} where[{sel}] group_by[{q.group_by or '-'}]"


def phase_main_path(n_blocks: int = DEPLOY["n_blocks"],
                    n_groups: int = DEPLOY["n_groups"],
                    rows: int = DEPLOY["rows"], ticks: int = DEPLOY["ticks"],
                    qpt: int = DEPLOY["qpt"], seed: int = SEED,
                    e: float = PRECISION,
                    expect_platform: str = "tpu") -> dict:
    """Phase B.  Runs the mix device-serial, device-pipelined, then host,
    checks parity and residency, prints the report; returns its numbers."""
    import jax

    from repro.launch.serve import BLOCK_SIZE, _synthetic_grouped_blocks

    _watch_compiles()
    t0 = time.perf_counter()
    samplers, tables = _synthetic_grouped_blocks(n_blocks, n_groups, rows,
                                                 seed, with_tables=True)
    print(f"[B] tables: {n_blocks} blocks x {n_groups} groups x {rows} "
          f"rows (smoke time {time.perf_counter() - t0:.1f}s)")
    batches = query_mix(n_blocks, ticks, qpt, seed, e)
    check_mix_coverage(batches)

    c0 = dict(_COMPILE)
    dev_loop, dev, t_dev = run_mix(samplers, tables, n_groups, batches, seed,
                                   e, "device")
    compile_s = _COMPILE["seconds"] - c0["seconds"]
    n_compiles = _COMPILE["count"] - c0["count"]
    dev_ex = dev_loop.executor
    arrays = resident_arrays(dev_ex)
    check(arrays, "device route kept no resident state")
    for name, arr in arrays:
        plats = {d.platform for d in arr.devices()}
        check(plats == {expect_platform},
              f"resident {name} lives on {sorted(plats)}, not "
              f"{expect_platform}")
    check(any(n.endswith(".regs") for n, _ in arrays),
          "no resident register plane (the mix has COUNT DISTINCT)")
    stacks = list(dev_ex._device_stacks.values())

    _, pipe, t_pipe = run_mix(samplers, tables, n_groups, batches, seed, e,
                              "device", pipeline=True)
    host_loop, host, t_host = run_mix(samplers, tables, n_groups, batches,
                                      seed, e, "host")

    d_serial = answer_diffs(host, dev, "device vs host")
    d_pipe = answer_diffs(host, pipe, "pipelined device vs host")
    d_sp = answer_diffs(dev, pipe, "pipelined vs serial device")
    d_state = state_diffs(host_loop.executor, dev_ex, "device vs host")
    report = bound_report(host, tables, n_groups, BLOCK_SIZE)
    peak = _peak_bytes(jax.devices()[0])

    print(f"[B] device route: {len(stacks)} stacks, "
          f"{sum(len(s.stores) for s in stacks)} keys "
          f"({len(_stores(dev_ex))} stores), "
          f"{sum(s.n_cells for s in stacks)} resident cells, "
          f"{len(arrays)} resident arrays on {expect_platform}")
    print(f"[B] peak_bytes_in_use {peak}")
    print(f"[B] compile {compile_s:.1f}s over {n_compiles} compiles "
          f"(device serial run)")
    print(f"[B] smoke times: device serial {t_dev:.1f}s, pipelined "
          f"{t_pipe:.1f}s, host {t_host:.1f}s (not a benchmark)")
    print(f"[B] max relative difference device vs host: values "
          f"{d_serial['value']:.3g}, VAR {d_serial['var']:.3g}, "
          f"COUNT DISTINCT {d_serial['distinct']:.3g}, resident moments "
          f"{d_state:.3g}; pipelined vs host: values {d_pipe['value']:.3g}, "
          f"VAR {d_pipe['var']:.3g}; pipelined vs serial: values "
          f"{d_sp['value']:.3g}")
    print(f"[B] earned bounds vs exact answers: {report['inside']} of "
          f"{report['earned']} inside")
    for i, q, name, val, truth in report["best_effort"]:
        print(f"[B] best-effort #{i} {_describe_query(q)} {name}: "
              f"{val:.6g} vs exact {truth:.6g} (error {val - truth:+.4g}, "
              f"{_rel(val, truth):.3g} relative)")
    return dict(max_rel_values=d_serial["value"], max_rel_var=d_serial["var"],
                max_rel_state=d_state, peak_bytes_in_use=peak,
                compile_seconds=compile_s, earned=report["earned"],
                inside=report["inside"],
                best_effort=len(report["best_effort"]))


# ---------------------------------------------------------------------------
# Phase C: compiled kernels
# ---------------------------------------------------------------------------

def _close(got, want, what: str, rtol: float = REL_TOL) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape,
          f"{what}: shape {got.shape} != {want.shape}")
    check(bool(np.all(np.isfinite(got))), f"{what}: non-finite output")
    d = float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-30)))
    check(d <= rtol, f"{what}: {d:.3g} relative vs reference "
                     f"(limit {rtol:g})")
    return d


def phase_kernels(tm: int = TM, interpret: bool = False,
                  seed: int = SEED) -> dict:
    """Phase C: every ``*_pallas`` kernel against its jnp reference."""
    import jax
    import jax.numpy as jnp

    from repro.core import distributed as D
    from repro.core import sketch as SK
    from repro.core.types import IslaParams
    from repro.kernels import isla_moments as K
    from repro.kernels import ref

    rng = np.random.default_rng(seed)
    bounds = (60.0, 90.0, 110.0, 140.0)
    b_arr = jnp.asarray(bounds, jnp.float32)
    n_cells, rows = 6, 2 * tm
    x = jnp.asarray(rng.normal(100, 20, (n_cells, rows, K.LANE)),
                    jnp.float32)
    cell_b = jnp.asarray(np.asarray(bounds)[None] + 3.0 * np.arange(
        n_cells)[:, None], jnp.float32)
    ref_cells = jax.jit(jax.vmap(ref.isla_moments_ref))
    params = IslaParams(e=0.5)
    worst = {}

    def record(name, d):
        worst[name] = d
        print(f"[C] {name}: max relative {d:.3g} vs reference")

    record("isla_moments_pallas", _close(
        K.isla_moments_pallas(x[0], b_arr, tm=tm, interpret=interpret),
        ref.isla_moments_ref(x[0], *bounds), "isla_moments_pallas"))
    sel = x[0].reshape(2, tm, K.LANE)[::2].reshape(-1, K.LANE)
    record("isla_moments_pallas stride=2", _close(
        K.isla_moments_pallas(x[0], b_arr, tm=tm, stride=2,
                              interpret=interpret),
        ref.isla_moments_ref(sel, *bounds), "isla_moments_pallas stride"))
    prior = jnp.asarray(rng.uniform(0, 10, (n_cells, 2, 4)), jnp.float32)
    want = ref_cells(x, *(jnp.full(n_cells, v, jnp.float32) for v in bounds))
    record("isla_moments_batched_pallas", _close(
        K.isla_moments_batched_pallas(x, b_arr, tm=tm, interpret=interpret,
                                      prior=prior),
        want + prior, "isla_moments_batched_pallas"))
    record("isla_moments_batched_pallas per-cell bounds", _close(
        K.isla_moments_batched_pallas(x, cell_b, tm=tm, interpret=interpret),
        ref_cells(x, *cell_b.T), "isla_moments_batched_pallas per-cell"))
    record("isla_moments_grouped_pallas", _close(
        K.isla_moments_grouped_pallas(x.reshape(2, 3, rows, K.LANE), b_arr,
                                      tm=tm, interpret=interpret),
        want.reshape(2, 3, 2, 4), "isla_moments_grouped_pallas"))
    mom, partials = K.isla_fused_pallas(
        x, b_arr, jnp.array(prior), jnp.float32(100.0), params, tm=tm,
        interpret=interpret)
    d = _close(mom, want + prior, "isla_fused_pallas moments")
    d = max(d, _close(partials, D.phase2(mom[:, 0], mom[:, 1],
                                         jnp.float32(100.0), params),
                      "isla_fused_pallas partials"))
    record("isla_fused_pallas", d)
    pilot = x[0].reshape(-1, K.LANE)
    record("pilot_stats_pallas", _close(
        K.pilot_stats_pallas(pilot, tm=tm, interpret=interpret),
        ref.pilot_stats_ref(pilot), "pilot_stats_pallas"))

    # Sketch plane: bit-exact against the jnp scatter-max twin.
    vals = np.round(rng.normal(0, 50, (n_cells, rows * K.LANE)))
    valid = rng.random((n_cells, rows * K.LANE)) < 0.9
    hi, lo = SK.value_limbs(vals.reshape(-1))
    shape3 = (n_cells, rows, K.LANE)
    hi3 = jnp.asarray(hi.reshape(shape3))
    lo3 = jnp.asarray(lo.reshape(shape3))
    v3 = jnp.asarray(valid.reshape(shape3).astype(np.uint32))
    prior_regs = jnp.asarray(rng.integers(0, 4, (n_cells, K.REG_ROWS,
                                                 K.LANE)), jnp.uint8)

    @jax.jit
    def regs_ref(hi3, lo3, v3, prior_regs):
        j, rho = D._sketch_encode(hi3.reshape(n_cells, -1),
                                  lo3.reshape(n_cells, -1))
        rho = jnp.where(v3.reshape(n_cells, -1) != 0, rho, jnp.uint8(0))
        cell = jnp.broadcast_to(jnp.arange(n_cells)[:, None], j.shape)
        regs = prior_regs.reshape(n_cells, SK.M).at[cell, j].max(rho)
        return regs.reshape(n_cells, K.REG_ROWS, K.LANE)

    want_regs = np.asarray(regs_ref(hi3, lo3, v3, prior_regs))
    got_regs = np.asarray(K.isla_sketch_pallas(hi3, lo3, v3, tm=tm,
                                               interpret=interpret,
                                               prior=prior_regs))
    check(got_regs.dtype == np.uint8 and np.array_equal(got_regs, want_regs),
          "isla_sketch_pallas: registers differ from the jnp twin")
    print("[C] isla_sketch_pallas: registers bit-identical to the jnp twin")
    mom2, regs2, partials2 = K.isla_fused_sketch_pallas(
        x, b_arr, jnp.array(prior), jnp.array(prior_regs), hi3, lo3, v3,
        jnp.float32(100.0), params, tm=tm, interpret=interpret)
    check(np.array_equal(np.asarray(regs2), want_regs),
          "isla_fused_sketch_pallas: registers differ from the jnp twin")
    check(np.array_equal(np.asarray(mom2), np.asarray(mom))
          and np.array_equal(np.asarray(partials2), np.asarray(partials)),
          "isla_fused_sketch_pallas: moments/partials differ from "
          "isla_fused_pallas")
    print("[C] isla_fused_sketch_pallas: registers bit-identical, moments "
          "and partials identical to isla_fused_pallas")
    return worst


# ---------------------------------------------------------------------------
# Phase D: the mesh route over every visible device
# ---------------------------------------------------------------------------

def phase_mesh(n_blocks: int = DEPLOY["n_blocks"],
               n_groups: int = DEPLOY["n_groups"],
               rows: int = DEPLOY["rows"], ticks: int = DEPLOY["ticks"],
               qpt: int = DEPLOY["qpt"], seed: int = SEED,
               e: float = PRECISION) -> dict:
    """Phase D: the phase-B mix on ``route="mesh"`` over every visible
    device against ``route="device"`` on the first, same seeds."""
    import jax

    from repro.launch.serve import _synthetic_grouped_blocks

    devices = jax.devices()
    samplers, tables = _synthetic_grouped_blocks(n_blocks, n_groups, rows,
                                                 seed, with_tables=True)
    batches = query_mix(n_blocks, ticks, qpt, seed, e)
    check_mix_coverage(batches)
    mesh_loop, mesh, t_mesh = run_mix(samplers, tables, n_groups, batches,
                                      seed, e, "mesh")
    mesh_ex = mesh_loop.executor
    check(set(mesh_ex._active_mesh().devices.flat) == set(devices),
          "the mesh does not span every device")
    arrays = resident_arrays(mesh_ex)
    check(arrays, "mesh route kept no resident state")
    for name, arr in arrays:
        shard_devs = {s.device for s in arr.addressable_shards}
        check(shard_devs == set(devices),
              f"resident {name} is on {len(shard_devs)} of "
              f"{len(devices)} devices")
        check(all(s.data.shape[0] * len(devices) == arr.shape[0]
                  for s in arr.addressable_shards),
              f"resident {name} is not split evenly over the cell axis")
    per_dev = {str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
               for d in devices}
    dev_loop, dev, t_dev = run_mix(samplers, tables, n_groups, batches,
                                   seed, e, "device")
    d = answer_diffs(dev, mesh, "mesh vs device")
    d_state = state_diffs(dev_loop.executor, mesh_ex, "mesh vs device")
    print(f"[D] mesh route over {len(devices)} devices: {len(arrays)} "
          f"resident arrays, each sharded over all {len(devices)}")
    print(f"[D] bytes_in_use per device: {per_dev}")
    print(f"[D] smoke times: mesh {t_mesh:.1f}s, device {t_dev:.1f}s "
          f"(not a benchmark)")
    print(f"[D] max relative difference mesh vs device: values "
          f"{d['value']:.3g}, VAR {d['var']:.3g}, COUNT DISTINCT "
          f"{d['distinct']:.3g}, resident moments {d_state:.3g}")
    return dict(max_rel_values=d["value"], max_rel_var=d["var"],
                max_rel_state=d_state, bytes_per_device=per_dev)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only phase D, the mesh route over four "
                         "chips against the device route on one")
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import configure_compile_cache
    except ImportError as exc:
        print(f"chip_smoke: the repository's src/ is not importable "
              f"({exc})", file=sys.stderr)
        return 2
    cache = configure_compile_cache()
    import jax

    devices = jax.devices()
    dev0 = devices[0]
    print(f"[A] jax {jax.__version__}, platform {dev0.platform}, "
          f"device_kind {dev0.device_kind!r}, {len(devices)} devices, "
          f"compile cache {cache}")
    try:
        check(dev0.platform == "tpu",
              f"JAX found no TPU (platform {dev0.platform!r})")
        if args.chips == 4:
            check(len(devices) == 4, f"--chips 4 needs exactly 4 devices, "
                                     f"JAX sees {len(devices)}")
            phase_mesh()
        else:
            phase_main_path()
            phase_kernels()
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
