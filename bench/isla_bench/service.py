"""The system under test, as the benchmark drives it: the admission loop
(``repro.launch.serve.IslaAdmissionLoop``) around a ``MultiQueryExecutor``
over the generated blocks, on ``route="device"`` with persistent stores.

This is the one file that calls the program.  A configuration's
``"service"`` entry names the options it sets, and its ``"estimator"``
entry the estimator's constants (the reference reads the same entry);
every other option is the program's default.  The block samplers are the
program's own ``table_sampler``; each call is wrapped so the harness can
keep the rows the first tick's pilot and a sampled query drew (for the
reference) and, in a traced run, put a ``bench:draw`` span around it.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import numpy as np

from .deployment import seed_rng

STREAM_PROGRAM = 2
# The estimator constants a configuration states (``"estimator"``) and the
# service passes to the program.
ISLA_PARAMS = ("p1", "p2", "eta", "thr", "te", "mild_lo", "mild_hi",
               "q_mild", "q_strong", "min_region_count")
# The loop's cumulative counters the metric readers see, where the program
# keeps them.
COUNTERS = ("answered", "subsumed", "deduped", "samples_drawn", "pane_slots",
            "h2d_bytes", "store_builds")


class DrawLog:
    """Where sampler calls report the rows they returned: ``(block, rows)``
    into ``active`` while the harness records a tick, and into ``first``
    until the first tick has ended (None otherwise); the harness then
    keeps that tick's bootstrap and pilot calls in ``pilot``."""

    def __init__(self):
        self.active: Optional[list] = None
        self.first: Optional[list] = []
        self.pilot: Optional[tuple] = None   # (bootstrap, pilot) calls
        self.trace = False


def _logged(block: int, inner, log: DrawLog):
    import jax

    def sample(n, rng):
        span = (jax.profiler.TraceAnnotation("bench:draw") if log.trace
                else contextlib.nullcontext())
        with span:
            rows = inner(n, rng)
        if log.active is not None:
            log.active.append((block, rows))
        if log.first is not None:
            log.first.append((block, rows))
        return rows

    return sample


class Service:
    """The served path for one configuration, built from a table; ``where``
    maps a query spec to the fields of its ``Predicate`` (the query
    kind's)."""

    def __init__(self, cfg: dict, table, seed: int, log: DrawLog, where):
        import warnings

        from repro.core import IslaParams, ZoneMap
        from repro.core.multiquery import (MultiQueryExecutor,
                                           PlannedSelectivityFloorWarning,
                                           table_sampler)
        from repro.launch.serve import IslaAdmissionLoop

        # Short ranges sit under the planner's selectivity floor by design
        # of the mix; the answers say so (best-effort bounds).
        warnings.simplefilter("ignore", PlannedSelectivityFloorWarning)

        svc = cfg.get("service", {})
        est = cfg["estimator"]
        measure = cfg["measure"]
        blocks = table.blocks()
        self.block_sizes = np.asarray(table.block_sizes(), np.float64)
        self.where = where
        samplers = [_logged(b, table_sampler(t), log)
                    for b, t in enumerate(blocks)]
        zone_map = (ZoneMap.from_tables(blocks, measure=measure)
                    if svc.get("zone_map", False) else None)
        self.executor = MultiQueryExecutor(
            samplers, table.block_sizes(),
            params=IslaParams(e=float(cfg["e"]), beta=float(cfg["beta"]),
                              **{k: est[k] for k in ISLA_PARAMS}),
            measure=measure, group_domains=dict(cfg["group_domains"]),
            zone_map=zone_map,
            anchor_min_support=int(est["anchor_min_support"]),
            **svc.get("executor", {}))
        self.loop = IslaAdmissionLoop(
            self.executor, seed_rng(seed, STREAM_PROGRAM),
            mode=est["mode"], route=svc.get("route", "device"),
            incremental=svc.get("incremental", True),
            **svc.get("loop", {}))

    def submit(self, spec) -> int:
        from repro.core import IslaQuery, Predicate

        where = self.where(spec)
        if where is not None:
            where = Predicate(**where)
        return self.loop.submit(IslaQuery(e=spec.e, beta=spec.beta,
                                          agg=spec.agg, where=where,
                                          group_by=spec.group_by))

    def tick(self) -> list:
        """One admission round: ``[(ticket id, answer), ...]`` of the
        tickets it completed."""
        return [(t.tid, t.answer) for t in self.loop.tick()]

    def samples_drawn(self) -> int:
        """The loop's cumulative new samples (read around every tick)."""
        return self.loop.samples_drawn

    def counters(self) -> dict:
        """The loop's cumulative counters and stage seconds."""
        s = self.loop.stats
        out = {k: s[k] for k in COUNTERS if k in s}
        out["stage_seconds"] = dict(s["stage_seconds"])
        return out

    def close(self) -> None:
        """Drop the program's state, device arrays included."""
        self.loop = None
        self.executor = None


def answer_record(answer) -> dict:
    """The fields of a served answer the benchmark reads."""
    groups = None
    if answer.groups:
        groups = [dict(group=g.group, value=g.value, mean=g.mean,
                       bound=g.error_bound, n=g.n_samples, w=g.est_size)
                  for g in answer.groups]
    return dict(value=answer.value, mean=answer.mean,
                bound=answer.error_bound, n=answer.n_matched,
                w=answer.est_population, half_width=answer.half_width,
                drawn=answer.sample_size, served=answer.served,
                groups=groups)
