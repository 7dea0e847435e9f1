"""Zone-map block pruning benchmark: skip blocks the predicate provably
filters out, end to end through the device tick.

Headlines (recorded in ``BENCH_prune.json``):
 * **sample savings** — a block-clustered 1%-selectivity WHERE answered
   through the executor with a ``ZoneMap`` vs the masked path that
   samples every block and discards non-matching rows: the pruned plan
   rates provably-empty blocks at zero and re-weights Eq. 1 over the
   active mass, so it draws ~1/selectivity fewer rows at the SAME
   (e, beta) — both answers are checked against the ground truth;
 * **residual parity** — the compacted dense launch (gather the active
   block axis, scatter the delta back) against the full-axis launch on
   identical quotas, in float64: the resident moments must come back
   BIT-IDENTICAL on every cell (active cells see the same adds, pruned
   cells are never addressed);
 * **transfer audit** — a steady pruned tick under
   ``jax.transfer_guard("disallow")`` still makes exactly the one
   sanctioned sample-sized h2d crossing (compact quotas, value pane and
   GROUP BY pane packed together): the cached scatter-index pair adds
   ZERO steady-state uploads;
 * **tick speed** — the compacted vs full dense tick at 1% active
   blocks (the pane shrinks ~B/active-fold, so should the launch).

Contract: rows print as ``(name, us_per_call, derived)``; ``--smoke``
shrinks sizes for CI; ``--out DIR`` picks where BENCH_prune.json lands.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.core.boundaries import make_boundaries
from repro.core.engine import IslaQuery
from repro.core.moment_store import DeviceMomentStore
from repro.core.multiquery import MultiQueryExecutor, table_sampler
from repro.core.types import IslaParams, Predicate, ZoneMap

try:
    from ._timing import time_best
except ImportError:          # script mode: python benchmarks/prune_bench.py
    from _timing import time_best

MU, SIGMA = 100.0, 12.0


def _clustered_tables(n_blocks, rows, seed=0):
    """Block-clustered predicate column: block b holds day == b only, so
    ``day == <d>`` matches exactly one block (selectivity 1/n_blocks)."""
    rng = np.random.default_rng(seed)
    tables = []
    for b in range(n_blocks):
        tables.append({
            "value": rng.normal(MU, SIGMA, rows),
            "day": np.full(rows, float(b)),
        })
    return tables


def sample_savings(smoke=False):
    """Executor end-to-end: pruned vs masked at equal (e, beta)."""
    # Block rows sized so the matching population alone supports the
    # target half-width: n_req ~ (z * sigma / e)^2 ~ 2.2k rows.
    n_blocks, rows = (20, 2000) if smoke else (100, 4000)
    tables = _clustered_tables(n_blocks, rows)
    sizes = [rows] * n_blocks
    zm = ZoneMap.from_tables(tables, measure="value")
    q = IslaQuery(e=0.5, beta=0.95, where=Predicate("day", eq=3.0))
    truth = float(np.mean(tables[3]["value"]))

    def run(zone):
        ex = MultiQueryExecutor([table_sampler(t) for t in tables], sizes,
                                zone_map=zm if zone else None)
        t0 = time.perf_counter()
        ans = ex.run([q], np.random.default_rng(7))[0]
        return ans, (time.perf_counter() - t0) * 1e6

    pruned, pruned_us = run(True)
    masked, masked_us = run(False)
    for name, ans in (("pruned", pruned), ("masked", masked)):
        if abs(ans.value - truth) > q.e:
            raise AssertionError(f"{name} answer {ans.value} misses "
                                 f"truth {truth} at e={q.e}")
    savings = masked.new_samples / max(pruned.new_samples, 1)
    if savings <= 5.0:
        raise AssertionError(f"pruning saved only {savings:.2f}x samples "
                             "(need > 5x at 1% selectivity)")
    rows_out = [
        (f"masked_pass/b{n_blocks}", masked_us, float(masked.new_samples)),
        (f"pruned_pass/b{n_blocks}", pruned_us, float(pruned.new_samples)),
    ]
    return rows_out, {
        "n_blocks": n_blocks, "selectivity": 1.0 / n_blocks,
        "masked_samples": int(masked.new_samples),
        "pruned_samples": int(pruned.new_samples),
        "sample_savings_x": savings,
        "truth": truth, "pruned_answer": float(pruned.value),
        "masked_answer": float(masked.value), "e": q.e, "beta": q.beta,
    }


def _stack_pair(n_blocks, n_groups, sizes):
    from repro.core.moment_store import DeviceStack

    params = IslaParams()
    b = make_boundaries(MU, SIGMA, params)
    dstores = [DeviceMomentStore.fresh_device(n_blocks, b, MU, sizes,
                                              n_groups=g)
               for g in (1, n_groups)]
    return DeviceStack(dstores), params


def _pruned_pass(rng, n_blocks, n_groups, active, quota):
    """A zone-pruned pass: only ``active`` blocks draw (ascending block
    order — the ``iter_chunked_draws`` stream contract)."""
    quotas = np.zeros(n_blocks, dtype=np.int64)
    quotas[active] = quota
    vals = rng.normal(MU, SIGMA, active.size * quota)
    gids = rng.integers(0, n_groups, vals.size)
    return vals, gids, quotas


def residual_parity(smoke=False):
    """Compacted vs full dense launch, float64: bit-identical state."""
    import jax

    x64_was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        n_blocks, n_groups, quota = (16, 3, 32) if smoke else (128, 8, 64)
        sizes = np.full(n_blocks, 10.0 ** 6)
        active = np.asarray([3, n_blocks - 2])
        outs = []
        for compaction in (True, False):
            rng = np.random.default_rng(5)
            stack, params = _stack_pair(n_blocks, n_groups, sizes)
            stack.block_compaction = compaction
            for _ in range(3):
                vals, gids, quotas = _pruned_pass(rng, n_blocks, n_groups,
                                                  active, quota)
                stack.tick(params, values=vals, quotas=quotas,
                           dense=([None, gids], [None, None]))
            outs.append(tuple(np.asarray(a, dtype=np.float64)
                              for a in stack._state))
        exact = all(np.array_equal(a, b) for a, b in zip(*outs))
        if not exact:
            raise AssertionError("compacted launch is not bit-identical "
                                 "to the full-axis launch in float64")
    finally:
        jax.config.update("jax_enable_x64", x64_was)
    rows = [(f"residual_parity/b{n_blocks}", 0.0, 1.0)]
    return rows, {
        "n_blocks": n_blocks, "active_blocks": [int(a) for a in active],
        "rounds": 3, "dtype": "float64", "bit_identical": True,
    }


def transfer_audit(smoke=False):
    """Steady pruned tick under transfer-guard: 1 sanctioned crossing.

    A single grouped store (the same shape ``device_bench``'s audit
    uses — the multi-store stat-slice path is host-side either way), so
    the guard isolates exactly what pruning adds: nothing."""
    import jax

    from repro.core import distributed as D
    from repro.core.moment_store import DeviceStack

    n_blocks, n_groups, quota = (16, 3, 32) if smoke else (128, 8, 64)
    sizes = np.full(n_blocks, 10.0 ** 6)
    active = np.asarray([3, n_blocks - 2])
    rng = np.random.default_rng(6)
    params = IslaParams()
    b = make_boundaries(MU, SIGMA, params)
    stack = DeviceStack([DeviceMomentStore.fresh_device(
        n_blocks, b, MU, sizes, n_groups=n_groups)])

    def tick():
        vals, gids, quotas = _pruned_pass(rng, n_blocks, n_groups, active,
                                          quota)
        stack.tick(params, values=vals, quotas=quotas,
                   dense=([gids], [None]))

    tick()  # warm-up: compiles, caches the scatter-index pair
    calls = []
    real_h2d = D.h2d

    def counting_h2d(x, dtype=None):
        calls.append(np.asarray(x).nbytes)
        return real_h2d(x, dtype)

    D.h2d = counting_h2d
    try:
        with jax.transfer_guard("disallow"):
            tick()
    finally:
        D.h2d = real_h2d
    if len(calls) != 1:
        raise AssertionError(
            f"steady pruned tick made {len(calls)} h2d crossings, "
            "expected 1 (compact quotas, values and group codes packed)")
    rows = [("steady_pruned_tick_h2d_crossings", 0.0, float(len(calls)))]
    return rows, {
        "sanctioned_h2d_per_tick": len(calls),
        "sanctioned_h2d_bytes": int(sum(calls)),
        "index_pair_h2d_per_steady_tick": 0,
        "transfer_guard": "disallow (sanctioned uploads via h2d only)",
    }


def tick_speed(smoke=False):
    """Compacted vs full-axis dense tick wall time at ~1% active."""
    n_blocks, n_groups, quota, rounds = ((32, 3, 32, 3) if smoke
                                         else (256, 8, 64, 10))
    sizes = np.full(n_blocks, 10.0 ** 6)
    active = np.asarray([3, n_blocks - 2])
    best = {}
    for compaction in (True, False):
        rng = np.random.default_rng(8)
        stack, params = _stack_pair(n_blocks, n_groups, sizes)
        stack.block_compaction = compaction
        # rounds + 1 pre-generated passes: the first warms/compiles
        # (same RNG stream as the old draw-inside-the-loop shape).
        passes = [_pruned_pass(rng, n_blocks, n_groups, active, quota)
                  for _ in range(rounds + 1)]

        def tick_fn(p, stack=stack, params=params):
            vals, gids, quotas = p
            return stack.tick(params, values=vals, quotas=quotas,
                              dense=([None, gids], [None, None]))

        best[compaction], _ = time_best(tick_fn, passes)
    speedup = best[False] / max(best[True], 1e-9)
    rows = [
        (f"full_axis_pruned_tick/b{n_blocks}", best[False], 1.0),
        (f"compacted_pruned_tick/b{n_blocks}", best[True], speedup),
    ]
    return rows, {
        "n_blocks": n_blocks, "active_blocks": int(active.size),
        "full_us_per_tick": best[False],
        "compacted_us_per_tick": best[True],
        "speedup_compacted_vs_full": speedup,
        "aggregation": "min over rounds",
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes so CI can keep the entrypoints alive")
    ap.add_argument("--out", default=".",
                    help="directory for BENCH_prune.json")
    args = ap.parse_args()

    print("name,us_per_call,derived")
    report = {"smoke": bool(args.smoke)}
    for section, bench in (("savings", sample_savings),
                           ("parity", residual_parity),
                           ("transfers", transfer_audit),
                           ("tick", tick_speed)):
        rows, rep = bench(smoke=args.smoke)
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived:.6g}", flush=True)
        report[section] = rep
    path = os.path.join(args.out, "BENCH_prune.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path} ({report['savings']['sample_savings_x']:.1f}x "
          f"fewer samples at {report['savings']['selectivity']:.0%} "
          "selectivity; compacted launch bit-identical, "
          f"{report['transfers']['sanctioned_h2d_per_tick']} sanctioned "
          "h2d crossings)", flush=True)


if __name__ == "__main__":
    main()
