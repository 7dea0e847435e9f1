"""Device-resident tick benchmark: host-merge vs fused device continuation.

Headlines (recorded in ``BENCH_device.json``):
 * **tick speedup** — the steady-state serving tick (merge a fresh pass
   + Phase 2 + group stats for FOUR warm (where, group_by) keys at
   16 groups x 1000 blocks) as ONE fused stacked launch
   (``DeviceStack.tick`` -> ``distributed.fused_tick_dense``) vs the
   PR-3 path that host-merges each key's store in float64 numpy and
   ships its moments to the device every tick, answers cross-checked;
 * **transfer counts** — a steady-state tick performs ZERO host<->device
   moment transfers: the whole tick runs under
   ``jax.transfer_guard("disallow")`` with only the sanctioned sample
   upload (``distributed.h2d``: quotas, value pane and GROUP BY pane
   packed into one sample-sized crossing; the pad mask is derived in the
   launch) allowed, asserted by counting ``h2d`` calls;
 * **dense fused launch** — ``kernels.isla_fused_pallas`` chains the
   Pallas Phase 1 accumulator (prior operand) into the branchless
   Phase 2 in one jit (latency probe; interpret-mode on CPU, the
   compiled win is TPU-side).

Contract: rows print as ``(name, us_per_call, derived)``; ``--smoke``
shrinks sizes for CI; ``--out DIR`` picks where BENCH_device.json lands.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.core.boundaries import make_boundaries
from repro.core.moment_store import DeviceMomentStore, MomentStore
from repro.core.types import IslaParams

try:
    from ._timing import time_best
except ImportError:          # script mode: python benchmarks/device_bench.py
    from _timing import time_best

MU, SIGMA = 100.0, 20.0


def _host_group_stats(store: MomentStore, partials: np.ndarray,
                      block_sizes: np.ndarray) -> np.ndarray:
    """The host tick's group-stat reduction — the same nine columns
    ``multiquery._keyed_stats`` derives per tick (and the fused device
    launch folds into its single call): per-group n, est. population,
    leverage mean, E[x^2], plain sample sums, fallback degradation, and
    the catalog-weighted second moment."""
    g, b = store.n_groups, store.n_blocks
    cnt = store.totals[:, 0].reshape(g, b)
    s1 = store.totals[:, 1].reshape(g, b)
    s2 = store.totals[:, 2].reshape(g, b)
    weights = (block_sizes[None, :] * cnt
               / np.maximum(store.n_sampled, 1.0)[None, :])
    w_g = weights.sum(axis=1)
    mean_g = (partials.reshape(g, b) * weights).sum(axis=1) \
        / np.maximum(w_g, 1.0)
    per_ex2 = s2 / np.maximum(cnt, 1.0)
    visited = (cnt > 0).astype(np.float64)
    ex2_g = (per_ex2 * weights).sum(axis=1)
    fallback = ((store.mom_s[:, 0] < 1.0)
                | (store.mom_l[:, 0] < 1.0)).reshape(g, b)
    degraded = (fallback & (cnt > 0)).any(axis=1).astype(np.float64)
    cat_num = (per_ex2 * block_sizes[None, :] * visited).sum(axis=1)
    cat_den = (block_sizes[None, :] * visited).sum(axis=1)
    return np.stack([cnt.sum(axis=1), w_g, mean_g, ex2_g,
                     s1.sum(axis=1), s2.sum(axis=1), degraded,
                     cat_num, cat_den], axis=1)


def _make_pass(rng, n_blocks, n_groups, quota):
    vals = rng.normal(MU, SIGMA, n_blocks * quota)
    bids = np.repeat(np.arange(n_blocks), quota)
    gids = rng.integers(0, n_groups, vals.size)
    quotas = np.full(n_blocks, quota, dtype=np.int64)
    return vals, bids, gids, quotas


def _pr3_device_tick(store, vals, bids, gids, mask, quotas, params, sizes):
    """The PR-3 ``route="device"`` incremental tick this PR replaces:
    host-merge the pass in float64 numpy, ship the merged moment rows
    host->device, run the branchless Phase 2 as its own launch, fetch
    the partials back, and reduce group stats on the host."""
    import jax.numpy as jnp

    from repro.core.distributed import phase2

    store.ingest(vals, bids, quotas, group_ids=gids, mask=mask)
    scale = max(abs(store.sketch0), SIGMA, 1e-12)
    pows = np.array([1.0, scale, scale * scale, scale ** 3])
    mom_s = jnp.asarray(store.mom_s / pows, jnp.float32)   # moments h2d
    mom_l = jnp.asarray(store.mom_l / pows, jnp.float32)   # every tick
    avg = phase2(mom_s, mom_l, jnp.float32(store.sketch0 / scale), params,
                 mode="calibrated")
    partials = np.asarray(avg, dtype=np.float64) * scale   # d2h
    return _host_group_stats(store, partials, sizes), partials


def tick_speed(smoke=False):
    """Steady-state serving tick at 16 groups x 1000 blocks: one
    mode-group with four warm (where, group_by) keys — the multi-store
    workload ``IslaAdmissionLoop`` batches — as ONE fused stacked launch
    (``DeviceStack.tick``) vs the PR-3 path that host-merges each key's
    store and ships its moments to the device every tick.

    Per-tick times aggregate by MIN over rounds (the usual
    noisy-shared-host estimator of achievable latency)."""
    from repro.core.moment_store import DeviceStack

    params = IslaParams()
    b = make_boundaries(MU, SIGMA, params)
    n_groups, n_blocks, quota, rounds = ((3, 16, 40, 3) if smoke
                                         else (16, 1000, 64, 10))
    sizes = np.full(n_blocks, 10.0 ** 7)
    rng = np.random.default_rng(0)
    # Four warm keys: plain, WHERE, GROUP BY, WHERE + GROUP BY.
    key_specs = [(False, 1), (True, 1), (False, n_groups),
                 (True, n_groups)]

    def make_pass():
        vals, bids, gids, quotas = _make_pass(rng, n_blocks, n_groups,
                                              quota)
        mask = rng.random(vals.size) < 0.5
        return vals, bids, gids, mask, quotas

    passes = [make_pass() for _ in range(rounds + 1)]

    pr3 = [MomentStore.fresh(n_blocks, b, MU, n_groups=g)
           for _, g in key_specs]
    dstores = [DeviceMomentStore.fresh_device(n_blocks, b, MU, sizes,
                                              n_groups=g)
               for _, g in key_specs]
    stack = DeviceStack(dstores)

    def pr3_tick(p):
        vals, bids, gids, mask, quotas = p
        out = []
        for (pred, g), st in zip(key_specs, pr3):
            out.append(_pr3_device_tick(
                st, vals, bids, gids if g > 1 else None,
                mask if pred else None, quotas, params, sizes))
        return out

    def device_tick(p):
        vals, bids, gids, mask, quotas = p
        key_gids = [gids if g > 1 else None for _, g in key_specs]
        key_valids = [mask if pred else None for pred, _ in key_specs]
        return stack.tick(params, mode="calibrated", values=vals,
                          quotas=quotas, dense=(key_gids, key_valids))

    # Both systems replay the SAME pre-generated passes; the warm-up
    # pass seeds the stores / compiles the fused launch.
    pr3_best, pr3_out = time_best(pr3_tick, passes)
    dev_best, dev_out = time_best(device_tick, passes)

    # Cross-check: every key's group means within fp32 tolerance.
    rel = 0.0
    for (host_rows, _), (_, dev_rows), dst in zip(pr3_out, dev_out,
                                                  dstores):
        dev_mean = (dev_rows[:, 2] * dst.scale
                    / np.maximum(dev_rows[:, 1], 1e-9))
        rel = max(rel, float(np.max(
            np.abs(dev_mean - host_rows[:, 2])
            / np.maximum(np.abs(host_rows[:, 2]), 1e-9))))
    if rel > 1e-3:
        raise AssertionError(f"device tick diverged from host: rel={rel}")
    speedup = pr3_best / max(dev_best, 1e-9)
    cells = stack.n_cells
    rows_out = [
        (f"pr3_hostmerge_ship_tick/c{cells}", pr3_best, 1.0),
        (f"device_resident_tick/c{cells}", dev_best, speedup),
    ]
    return rows_out, {
        "n_groups": n_groups, "n_blocks": n_blocks,
        "keys": len(key_specs), "cells": cells,
        "samples_per_tick": int(n_blocks * quota), "rounds": rounds,
        "pr3_device_route_us_per_tick": pr3_best,
        "device_us_per_tick": dev_best,
        "speedup_vs_pr3_device_route": speedup,
        "group_mean_max_rel_diff": rel,
        "aggregation": "min over rounds",
    }


def transfer_counts(smoke=False):
    """Steady tick under transfer-guard: only the sanctioned sample
    upload crosses host->device — one for the dense grouped layout run
    here (quotas, value pane and GROUP BY pane in one packed buffer),
    sample-sized."""
    import jax

    from repro.core import distributed as D

    params = IslaParams()
    b = make_boundaries(MU, SIGMA, params)
    n_groups, n_blocks, quota = (3, 16, 40) if smoke else (16, 200, 64)
    sizes = np.full(n_blocks, 10.0 ** 7)
    rng = np.random.default_rng(1)
    dev = DeviceMomentStore.fresh_device(n_blocks, b, MU, sizes,
                                         n_groups=n_groups)
    v, bi, gi, q = _make_pass(rng, n_blocks, n_groups, quota)
    dev.ingest_tick(v, bi, q, params, group_ids=gi)  # warm / compile

    calls = []
    real_h2d = D.h2d

    def counting_h2d(x, dtype=None):
        calls.append(np.asarray(x).nbytes)
        return real_h2d(x, dtype)

    D.h2d = counting_h2d
    try:
        v, bi, gi, q = _make_pass(rng, n_blocks, n_groups, quota)
        with jax.transfer_guard("disallow"):
            dev.ingest_tick(v, bi, q, params, group_ids=gi)
    finally:
        D.h2d = real_h2d
    # Dense grouped tick ships one packed buffer: quotas, value pane,
    # GROUP BY pane — sample-sized, never moments.
    if len(calls) != 1:
        raise AssertionError(
            f"steady tick made {len(calls)} h2d crossings, expected 1 "
            "(quotas, values and group codes packed together)")
    moment_bytes = int(np.asarray(dev.mom_s).nbytes
                       + np.asarray(dev.mom_l).nbytes)
    rows = [("steady_tick_h2d_crossings", 0.0, float(len(calls)))]
    return rows, {
        "sanctioned_h2d_per_tick": len(calls),
        "sanctioned_h2d_bytes": int(sum(calls)),
        "moment_h2d_transfers": 0,
        "resident_moment_bytes_never_shipped": moment_bytes,
        "transfer_guard": "disallow (sanctioned uploads via h2d only)",
    }


def dense_fused(smoke=False):
    """One-launch dense path: Pallas Phase 1 (prior-seeded) + Phase 2
    fused, vs the two-step moments -> host-solve route."""
    import jax.numpy as jnp

    from repro.core.engine import phase2_iteration_batch
    from repro.kernels.isla_moments import (isla_fused_pallas,
                                            isla_moments_batched_pallas)

    params = IslaParams()
    cells, tiles, tm = (4, 1, 64) if smoke else (32, 2, 64)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(MU, SIGMA, size=(cells, tm * tiles, 128)),
                    jnp.float32)
    bounds = jnp.asarray(make_boundaries(MU, SIGMA, params).as_tuple(),
                         jnp.float32)
    prior = jnp.zeros((cells, 2, 4), jnp.float32)

    t0 = time.perf_counter()
    mom = isla_moments_batched_pallas(x, bounds, tm=tm, interpret=True)
    split_res = phase2_iteration_batch(
        np.asarray(mom[:, 0], np.float64), np.asarray(mom[:, 1], np.float64),
        MU, params, mode="calibrated")
    split_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    _, partials = isla_fused_pallas(x, bounds, prior, jnp.float32(MU),
                                    params, tm=tm, interpret=True)
    fused_us = (time.perf_counter() - t0) * 1e6
    rel = float(np.max(np.abs(np.asarray(partials, np.float64)
                              - split_res.avg)
                       / np.maximum(np.abs(split_res.avg), 1e-9)))
    if rel > 1e-3:
        raise AssertionError(f"fused dense launch diverged: rel={rel}")
    rows = [
        (f"dense_split_launches/c{cells}", split_us, 1.0),
        (f"dense_fused_launch/c{cells}", fused_us, rel),
    ]
    return rows, {"cells": cells, "interpret": True,
                  "partials_max_rel_diff": rel,
                  "note": "interpret-mode latency probe on CPU; the "
                          "compiled single-launch win is TPU-side"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes so CI can keep the entrypoints alive")
    ap.add_argument("--out", default=".",
                    help="directory for BENCH_device.json")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    report = {"smoke": bool(args.smoke)}
    for section, bench in (("tick", tick_speed),
                           ("transfers", transfer_counts),
                           ("dense", dense_fused)):
        rows, rep = bench(smoke=args.smoke)
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived:.6g}", flush=True)
        report[section] = rep
    path = os.path.join(args.out, "BENCH_device.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    speedup = report["tick"]["speedup_vs_pr3_device_route"]
    print(f"# wrote {path} (device tick {speedup:.2f}x "
          f"vs host merge at {report['tick']['cells']} cells; "
          f"{report['transfers']['sanctioned_h2d_per_tick']} sanctioned "
          f"h2d crossings, 0 moment transfers)", flush=True)


if __name__ == "__main__":
    main()
