"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<config>.json``: data, precision, service options, check
limits) and a traffic mix (``bench/traffic/<mix>.json``).  The
configuration's generator and the mix's query kind are files of their own,
found by name (``deployment``).  A run:

1. checks that JAX sees a TPU and as many chips as the cell asks for;
2. generates the configuration's table from the seed, builds the query
   kind's exact reference and the served path (``service.Service``);
3. warms up on queries of the cell's own mix (another stream of the same
   seed) until no new program compiles, and counts all that as set-up;
4. drives the mix for ``--seconds`` seconds (a closed loop), then
   answers every query still in flight (the drain, whose latencies count);
5. with ``--trace 1``, traces that window and reports the per-layer
   metrics (``bench/metrics/<name>.py``); else the end-to-end metrics;
6. frees the program's state and checks the answers against the reference
   (``reference.py``), printing each number compared beside its limit.

The last line of standard output is one JSON object; lines before it, on
standard error, say how late the clients sent, how many earned bounds
held, the device's peak memory and each check.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import heapq
import itertools
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from . import deployment, reference, trace
from .service import DrawLog, Service, answer_record
from .traffic import Mix

STREAM_TRAFFIC = 3
STREAM_WARMUP = 4
STREAM_CHECK = 5

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
DRAIN_LIMIT_S = 60.0


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Loading the cell
# ---------------------------------------------------------------------------

def load_cell(root: Path, workload: str) -> tuple:
    """(benchmark, cell, configuration, mix spec) of ``workload``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    return bench, cell, cfg, mix


def metric_entries(bench: dict, kind: str, workload: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that apply to a cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(root: Path, name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return deployment.load(root, "metrics", name).read


# ---------------------------------------------------------------------------
# Device and compiler
# ---------------------------------------------------------------------------

class CompileWatch:
    """Counts backend compiles (``jax.monitoring``), process-wide."""

    count = 0
    seconds = 0.0
    _on = False

    @classmethod
    def install(cls) -> None:
        if cls._on:
            return
        import jax

        def on_duration(event, secs, **_):
            if event == BACKEND_COMPILE_EVENT:
                cls.count += 1
                cls.seconds += secs

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        cls._on = True


def setup_jax(compile_cache: bool = True) -> None:
    """The program's persistent compile cache (inside the checkout, or
    ``$JAX_COMPILATION_CACHE_DIR``), keeping every program however fast
    it compiled, so only the first run of a cell compiles."""
    if compile_cache:
        from repro.launch.compile_cache import configure_compile_cache

        configure_compile_cache()
        import jax

        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    CompileWatch.install()


def check_devices(chips: int, require_tpu: bool) -> list:
    import jax

    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoAccelerator(f"JAX found no TPU (platform "
                                f"{devices[0].platform!r})")
        if len(devices) < chips:
            raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                                f"{len(devices)}")
    return devices


def peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# ---------------------------------------------------------------------------
# Driving the traffic
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Sent:
    spec: object
    client: object
    due: float
    submitted: float
    flagged: bool = False
    answered: float = math.nan
    answer: dict = None
    rows: list = None          # drawn rows of its tick, when flagged
    reused: dict = None        # a served answer: the answer it reuses


@dataclasses.dataclass
class TickRecord:
    computed_keys: list        # keys of answers the executor computed
    samples: int               # new samples the tick drew


class Clients:
    """Sends a mix's queries into the service and records what comes back."""

    def __init__(self, svc: Service, mix: Mix, log: DrawLog,
                 check_rng=None, check_spec=None):
        self.svc = svc
        self.mix = mix
        self.log = log
        self.check_rng = check_rng
        self.check_share = float((check_spec or {}).get("share", 0.0))
        self.check_max = int((check_spec or {}).get("max", 0))
        self.n_flagged = 0
        self.seen_keys = set()
        self.sent: list = []
        self.n_answered = 0
        self.ticks: list = []
        self.computed: dict = {}   # (agg, key) -> latest computed answer
        self._inflight: dict = {}

    def _submit(self, spec, client, due: float) -> None:
        flagged = False
        if self.check_rng is not None and spec.key not in self.seen_keys:
            if (self.n_flagged < self.check_max
                    and self.check_rng.random() < self.check_share):
                flagged = True
                self.n_flagged += 1
        self.seen_keys.add(spec.key)
        tid = self.svc.submit(spec)
        s = Sent(spec=spec, client=client, due=due,
                 submitted=time.perf_counter(), flagged=flagged)
        self._inflight[tid] = s
        self.sent.append(s)

    def _tick(self, tracing: bool) -> list:
        import jax

        record = any(s.flagged and s.rows is None
                     for s in self._inflight.values())
        if record:
            self.log.active = []
        before = self.svc.samples_drawn()
        if tracing:
            with jax.profiler.TraceAnnotation("bench:tick"):
                done = self.svc.tick()
        else:
            done = self.svc.tick()
        t1 = time.perf_counter()
        drawn = self.svc.samples_drawn() - before
        rows, self.log.active = self.log.active, None
        if self.log.first is not None:
            try:
                self.log.pilot = reference.split_first_tick(
                    self.log.first, len(self.svc.block_sizes))
            except ValueError:
                self.log.pilot = None   # the check then finds no anchor
            else:
                if rows is not None:    # the tick's own pass follows
                    rows = rows[sum(map(len, self.log.pilot)):]
            self.log.first = None
        keys, out = [], []
        for tid, ans in done:
            s = self._inflight.pop(tid)
            s.answered = t1
            s.answer = answer_record(ans)
            if s.answer["served"] is None:
                keys.append(s.spec.key)
                self.computed[(s.spec.agg, s.spec.key)] = s.answer
            if s.flagged:
                s.rows = rows
            out.append(s)
        for s in out:
            if s.answer["served"] is not None:
                s.reused = self.computed.get((s.spec.agg, s.spec.key))
        self.n_answered += len(out)
        for s in self._inflight.values():
            if s.flagged and s.rows is None and record:
                # Its key drew in this tick but is answered later: the
                # logged rows would not be all it was composed from.
                s.flagged = False
        self.ticks.append(TickRecord(sorted(set(keys), key=str),
                                     int(drawn)))
        return out

    def run_closed(self, queries, seconds: float, tracing: bool = False,
                   stop=None) -> tuple:
        """Closed loop: each client sends its next query ``think_s`` after
        its last answer.  ``stop(clients)`` may end the loop early (warm-up).
        Returns (window start, window end, drain end)."""
        t0 = time.perf_counter()
        deadline = t0 + seconds
        ready = [(t0, c) for c in range(self.mix.clients)]
        heapq.heapify(ready)
        end = None
        while True:
            t = time.perf_counter()
            if end is None and (t >= deadline or (stop and stop(self))):
                end = t
            while end is None and ready and ready[0][0] <= t:
                due, c = heapq.heappop(ready)
                self._submit(next(queries), c, due)
            if self._inflight:
                if end is not None and t - end > DRAIN_LIMIT_S:
                    break
                for s in self._tick(tracing):
                    heapq.heappush(ready, (s.answered + self.mix.think_s,
                                           s.client))
                continue
            if end is not None or not ready:
                break
            self._wait(ready[0][0], tracing)
        return t0, end if end is not None else time.perf_counter(), \
            time.perf_counter()

    @staticmethod
    def _wait(until: float, tracing: bool) -> None:
        import jax

        dt = until - time.perf_counter()
        if dt <= 0:
            return
        if tracing:
            with jax.profiler.TraceAnnotation("bench:wait"):
                time.sleep(dt)
        else:
            time.sleep(dt)


def warm_up(svc: Service, mix: Mix, seed: int, log: DrawLog,
            spec: dict) -> dict:
    """Queries of the cell's own mix until the program stops compiling:
    first ``sweeps`` passes over every combination of its deck at each of
    ``e_factors`` (so the extremes of its sample counts are reached), then
    the mix itself, until at least ``min_queries`` are answered and the
    last ``quiet_queries`` compiled nothing, or ``max_queries`` /
    ``max_seconds`` is reached.  Returns the keys it sent and the answers
    it computed (a later answer may be served from them)."""
    cl = Clients(svc, mix, log)
    qmin = int(spec.get("min_queries", 16))
    quiet = int(spec.get("quiet_queries", 8))
    qmax = int(spec.get("max_queries", 100_000))
    state = {"last": CompileWatch.count, "since": 0, "seen": 0}

    def stop(d: Clients) -> bool:
        n = d.n_answered
        for _ in range(n - state["seen"]):
            if CompileWatch.count != state["last"]:
                state["last"], state["since"] = CompileWatch.count, 0
            else:
                state["since"] += 1
        state["seen"] = n
        return n >= qmax or (n >= qmin and state["since"] >= quiet)

    rng = deployment.seed_rng(seed, STREAM_WARMUP)
    queries = mix.queries(rng)
    if spec.get("e_factors"):
        sweeps = [q for _ in range(int(spec.get("sweeps", 1)))
                  for q in mix.sweep(rng, spec["e_factors"])]
        qmin = max(qmin, len(sweeps))
        queries = itertools.chain(sweeps, queries)
    cl.run_closed(queries, float(spec.get("max_seconds", 300)), stop=stop)
    return {"queries": len(cl.sent), "keys": cl.seen_keys,
            "computed": cl.computed}


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q))


def end_to_end(name: str, ctx) -> float:
    lat = ctx.latencies_ms
    if name == "setup_s":
        return ctx.setup_s
    if name == "latency_p50_ms":
        return _percentile(lat, 50) if lat else None
    if name == "latency_p95_ms":
        return _percentile(lat, 95) if lat else None
    if name == "answered_qps":
        return len(lat) / ctx.elapsed_s if lat else None
    if name == "earned_share":
        return 100.0 * ctx.earned_rows / ctx.rows if ctx.rows else None
    raise KeyError(f"no end-to-end metric {name!r} in the harness")


def metric_context(cl: Clients, cfg: dict, c0: dict, c1: dict,
                   window: tuple, compiles: int, summary, setup_s: float,
                   device_kind: str, n_blocks: int):
    t0, t_end, t_drain = window
    answered = [s for s in cl.sent if not math.isnan(s.answered)]
    rows = earned = 0
    for s in answered:
        a = s.answer
        for r in [a] + (a["groups"] or []):
            rows += 1
            earned += r["bound"] is not None
    executed = sum(1 for t in cl.ticks if t.computed_keys)

    def delta(key):
        """A counter's rise over the window; None where the program keeps
        no such counter."""
        if key not in c0 or key not in c1:
            return None
        return c1[key] - c0[key]

    def stage_ms(name):
        if not executed or name not in c1["stage_seconds"]:
            return None
        return 1e3 * (c1["stage_seconds"][name]
                      - c0["stage_seconds"].get(name, 0.0)) / executed

    return SimpleNamespace(
        latencies_ms=[1e3 * (s.answered - s.due) for s in answered],
        elapsed_s=max(t_drain, t_end) - t0,
        rows=rows, earned_rows=earned, setup_s=setup_s,
        ticks=cl.ticks, executed_ticks=executed, delta=delta,
        stage_ms=stage_ms, compiles_in_window=compiles, trace=summary,
        device_kind=device_kind, n_blocks=n_blocks,
        group_domains=dict(cfg["group_domains"]))


# ---------------------------------------------------------------------------
# Checks against the reference
# ---------------------------------------------------------------------------

def _drawn_columns(rows: list, names: list) -> tuple:
    """(block id per drawn row, column name -> values) of logged sampler
    calls."""
    bids = np.concatenate([np.zeros(0, np.int64)]
                          + [np.full(len(r[names[0]]), b, np.int64)
                             for b, r in rows])
    cols = {k: np.concatenate([np.zeros(0)]
                              + [np.asarray(r[k], np.float64)
                                 for _, r in rows]) for k in names}
    return bids, cols


def check_answers(cl: Clients, cfg: dict, kind, truth, names: list,
                  block_sizes, pilot, control: bool = False) -> dict:
    """Every number compared, for the program's answers and, with
    ``control``, for the bfloat16 control put in the program's place.

    The answers held to the sample reference are those the clients flagged
    (a seeded share of the queries whose key was new when sent) and saw
    answered in the tick that drew for them: their counts, populations and
    plain moments (``sample_stats``) and, for AVG and SUM, the ISLA
    estimate (``calibrated_stats``, in the anchor the logged pilot rows
    fix).  Every served answer is held to the answer it reuses.  The query
    kind gives each spec's WHERE mask and the values aggregated; ``names``
    are the table's columns."""
    est_cfg = cfg["estimator"]
    values = functools.partial(kind.values, cfg)
    anchors = anchors_c = None
    if pilot is not None:
        anchors = reference.Anchors(*pilot, values, est_cfg)
        if control:
            anchors_c = reference.Anchors(*pilot, values, est_cfg,
                                          precision="bfloat16")
    flagged = [s for s in cl.sent if s.flagged and s.rows is not None
               and s.answer is not None]
    gaps = {"program": dict(count_gap=0.0, moment_gap=0.0, estimate_gap=0.0)}
    if control:
        gaps["control"] = dict(count_gap=0.0, moment_gap=0.0,
                               estimate_gap=0.0)
    live_missed = unbacked = 0
    worst = None
    for s in flagged:
        sp = s.spec
        bids, cols = _drawn_columns(s.rows, names)
        vals = values(cols)
        match = kind.match(sp, cols)
        grouped = sp.group_by is not None
        gids = cols[sp.group_by].astype(np.int64) if grouped else None
        ng = int(cfg["group_domains"][sp.group_by]) if grouped else 1
        st = reference.sample_stats(bids, vals, match, gids, ng, block_sizes)
        est = None
        if sp.agg in ("AVG", "SUM"):
            if anchors is None:
                for g in gaps.values():
                    g["estimate_gap"] = math.inf
            else:
                est = reference.calibrated_stats(
                    bids, vals, match, gids, ng, block_sizes,
                    anchors.for_mask(kind.match(sp, anchors.rows)), est_cfg)
        want = reference.answer_fields(sp.agg, sp.beta, st, est)
        unbacked += reference.unbacked_bounds(sp.agg, sp.e, sp.beta, st,
                                              s.answer)
        got = {side: _answer_fields_of(s.answer) for side in gaps}
        if control and anchors_c is not None:
            frame_c = anchors_c.for_mask(kind.match(sp, anchors_c.rows))
            st_c = reference.sample_stats(bids, vals, match, gids, ng,
                                          block_sizes, precision="bfloat16")
            est_c = (reference.calibrated_stats(
                bids, vals, match, gids, ng, block_sizes, frame_c, est_cfg,
                precision="bfloat16") if est is not None else None)
            got["control"] = _reference_fields(
                reference.answer_fields(sp.agg, sp.beta, st_c, est_c))
        for side, g in gaps.items():
            f = got[side]
            for k, v in want["counts"].items():
                g["count_gap"] = max(g["count_gap"],
                                     abs(float(f[k]) - v)
                                     if f.get(k) is not None else math.inf)
            for k, v in want["rel"].items():
                gap = reference.rel_gap(_num(f.get(k)), v)
                if gap > g["moment_gap"]:
                    g["moment_gap"] = gap
                    if side == "program":
                        worst = [sp.agg, k, st["n"], sp.key]
            for k, r in want["ranges"].items():
                gap = reference.range_gap(_num(f.get(k)), r)
                if gap > g["estimate_gap"]:
                    g["estimate_gap"] = gap
                    if side == "program":
                        worst = [sp.agg, k, st["n"], sp.key]
        live = truth.live_blocks(sp)
        if live is not None:
            live_missed += int(np.sum(st["drawn"][live] == 0))
    misses, earned = bound_misses(cl, truth)
    served = [s for s in cl.sent
              if s.answer is not None and s.answer["served"] is not None]
    return {"checked_answers": len(flagged), "gaps": gaps,
            "pruned_live_blocks": live_missed, "unbacked_bounds": unbacked,
            "worst_field": worst, "bound_misses": misses,
            "earned_rows": earned, "served_answers": len(served),
            "served_gap": max([served_gap(s.answer, s.reused)
                               for s in served], default=0.0)}


def _num(x) -> float:
    return float(x) if x is not None else math.nan


def _answer_fields_of(a: dict) -> dict:
    got = {"matched": a["n"], "drawn": a["drawn"], "population": a["w"],
           "value": a["value"], "mean": a["mean"],
           "half_width": a["half_width"]}
    for g in a["groups"] or []:
        k = g["group"]
        got[f"g{k}.matched"] = g["n"]
        got[f"g{k}.population"] = g["w"]
        got[f"g{k}.value"] = g["value"]
        got[f"g{k}.mean"] = g["mean"]
    return got


def _reference_fields(fields: dict) -> dict:
    """The control's answer fields: its counts, moments and estimate (a
    point: the control takes its own side of every region cut)."""
    out = dict(fields["counts"])
    out.update(fields["rel"])
    out.update({k: r[0] for k, r in fields["ranges"].items()})
    return out


SERVED_FIELDS = ("value", "mean", "bound", "n", "w", "half_width", "drawn")


def served_gap(answer: dict, reused: dict | None) -> float:
    """Largest relative gap of a served answer's fields, overall and per
    group, from the latest answer computed on its aggregate and key (the
    answer a cache or a batch-mate serves); infinite when none exists."""
    if reused is None:
        return math.inf
    pairs = [(answer, reused)]
    ga, gr = answer["groups"] or [], reused["groups"] or []
    if len(ga) != len(gr):
        return math.inf
    pairs += list(zip(ga, gr))
    gap = 0.0
    for a, r in pairs:
        for k in SERVED_FIELDS:
            if k not in a:
                continue
            if (a[k] is None) != (r[k] is None):
                return math.inf
            if a[k] is not None:
                gap = max(gap, reference.rel_gap(float(a[k]), float(r[k])))
    return gap

def bound_misses(cl: Clients, truth) -> tuple:
    """(rows outside their earned bound, rows with an earned bound) over
    every answer of the window, against the query kind's exact answers."""
    misses = earned = 0
    for s in cl.sent:
        a = s.answer
        if a is None:
            continue
        rows = [(None, a)] + [(g["group"], g) for g in a["groups"] or []]
        for grp, r in rows:
            if r["bound"] is None:
                continue
            earned += 1
            exact = truth.answer(s.spec, grp)
            if not (abs(r["value"] - exact)
                    <= r["bound"] + 1e-9 * abs(exact)):
                misses += 1
    return misses, earned


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def run_cell(root: Path, workload: str, seed: int, seconds: float,
             tracing: bool, t_start: float, require_tpu: bool = True,
             control: bool = False, compile_cache: bool = True,
             check_share: float | None = None) -> dict:
    """One run of ``workload``: its result line and the notes printed
    before it.  ``control`` also holds the bfloat16 control to the
    reference and compares it in the program's place (the program's own
    numbers go to the notes); ``check_share`` overrides the mix's share
    of answers compared (a short window compares as many as a run)."""
    bench, cell, cfg, mix_spec = load_cell(root, workload)
    setup_jax(compile_cache)
    devices = check_devices(int(cell["chips"]), require_tpu)
    kind = devices[0].device_kind
    if require_tpu:
        from .peaks import peaks
        peaks(kind)
    phases = {"start": time.perf_counter() - t_start}
    generator = deployment.load(root, "generators", cfg["data"]["generator"])
    mix = Mix(mix_spec, cfg["e"], cfg["beta"], root)
    table = generator.generate(cfg["data"], seed)
    phases["data"] = time.perf_counter() - t_start
    truth = mix.kind.truth(cfg, mix_spec["queries"], table)
    log = DrawLog()
    svc = Service(cfg, table, seed, log, mix.kind.where)
    phases["service"] = time.perf_counter() - t_start
    block_sizes = svc.block_sizes
    warm = warm_up(svc, mix, seed, log, mix_spec.get("warmup", {}))
    phases["warmup"] = time.perf_counter() - t_start
    compiles_setup = CompileWatch.count
    check_spec = dict(mix_spec.get("check") or {})
    if check_share is not None:
        check_spec["share"] = check_share
    cl = Clients(svc, mix, log,
                 check_rng=deployment.seed_rng(seed, STREAM_CHECK),
                 check_spec=check_spec)
    cl.seen_keys = set(warm["keys"])
    cl.computed = dict(warm["computed"])
    queries = mix.queries(deployment.seed_rng(seed, STREAM_TRAFFIC))
    trace_dir = None
    if tracing:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        log.trace = True
        trace.start(trace_dir)
    setup_s = time.perf_counter() - t_start
    c0 = svc.counters()
    n0 = CompileWatch.count
    import jax
    ann = (jax.profiler.TraceAnnotation(trace.WINDOW) if tracing else None)
    if ann is not None:
        ann.__enter__()
    try:
        window = cl.run_closed(queries, seconds, tracing)
    finally:
        if ann is not None:
            ann.__exit__(None, None, None)
    compiles = CompileWatch.count - n0
    c1 = svc.counters()
    summary = None
    if tracing:
        trace.stop()
        log.trace = False
        summary = trace.reduce_planes(
            trace.read_planes(trace.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    mem_peak = peak_bytes(devices)
    ctx = metric_context(cl, cfg, c0, c1, window, compiles, summary,
                         setup_s, kind, len(block_sizes))
    metrics = {}
    if tracing:
        for m in metric_entries(bench, "per_layer", workload):
            v = load_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in metric_entries(bench, "end_to_end", workload):
            v = end_to_end(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    lateness = [1e3 * (s.submitted - s.due) for s in cl.sent]
    attempted = len(cl.sent)
    answered = sum(1 for s in cl.sent if s.answer is not None)
    svc.close()
    del svc
    gc.collect()
    checks = check_answers(cl, cfg, mix.kind, truth, list(table.columns),
                           block_sizes, log.pilot, control=control)
    limits = cfg["checks"]
    earned, misses = checks["earned_rows"], checks["bound_misses"]
    beta = float(cfg["beta"])
    # The (e, beta) guarantee: at most 1 - beta of the earned rows miss,
    # give or take the binomial spread of the window's earned rows.
    miss_limit = (1.0 - beta) + float(limits["bound_miss_z"]) * math.sqrt(
        beta * (1.0 - beta) / max(earned, 1))

    def compared_of(gaps):
        return {
            "unanswered": (float(attempted - answered), 0.0),
            "unchecked": (float(max(limits["min_checked_answers"]
                                    - checks["checked_answers"], 0)), 0.0),
            "count_gap": (gaps["count_gap"], limits["count_gap"]),
            "moment_gap": (gaps["moment_gap"], limits["moment_gap"]),
            "estimate_gap": (gaps["estimate_gap"], limits["estimate_gap"]),
            "served_gap": (checks["served_gap"], limits["served_gap"]),
            "pruned_live_blocks": (float(checks["pruned_live_blocks"]),
                                   limits["pruned_live_blocks"]),
            "unbacked_bounds": (float(checks["unbacked_bounds"]),
                                limits["unbacked_bounds"]),
            "bound_miss_share": (misses / earned if earned else 0.0,
                                 miss_limit),
        }

    compared = compared_of(checks["gaps"]["control" if control
                                         else "program"])
    correct = all(v <= lim for v, lim in compared.values())
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    notes = {
        "lateness_ms": ([_percentile(lateness, 50),
                         _percentile(lateness, 95), max(lateness)]
                        if lateness else []),
        "bounds_held": [checks["earned_rows"] - checks["bound_misses"],
                        checks["earned_rows"]],
        "peak_bytes_in_use": mem_peak,
        "compiles": {"setup": compiles_setup, "window": compiles},
        "warmup_queries": warm["queries"],
        "setup_phases_s": phases,
        "worst_field": checks["worst_field"],
        "served_answers": checks["served_answers"],
        "ticks": len(cl.ticks), "executed_ticks": ctx.executed_ticks,
        "samples_per_tick": (ctx.delta("samples_drawn") / ctx.executed_ticks
                             if ctx.executed_ticks else 0.0),
        "stage_ms": {k: ctx.stage_ms(k) for k in c1["stage_seconds"]},
        "checked_answers": checks["checked_answers"],
    }
    if control:
        notes["program_checks"] = {
            k: v for k, (v, _) in compared_of(
                checks["gaps"]["program"]).items()}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": attempted - answered, "metrics": metrics,
              "device": device}
    if summary is not None:
        result["breakdown"] = trace.breakdown(summary)
    # A missing field reads as an infinite gap; JSON holds none.
    result["checks"] = {
        k: {"value": v if math.isfinite(v) else sys.float_info.max,
            "limit": lim}
        for k, (v, lim) in compared.items()}
    return {"result": result, "notes": notes}


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def emit(out: dict) -> None:
    """Notes and checks on standard error (checks last), then the result
    line as the last line of standard output."""
    for k, v in out["notes"].items():
        print(f"note {k}: {json.dumps(v, default=str)}", file=sys.stderr)
    for k, c in out["result"]["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out["result"]), flush=True)


def main(argv=None, root: Path | None = None, t_start: float | None = None,
         require_tpu: bool = True) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    root = Path(root) if root is not None else deployment.ROOT
    try:
        out = run_cell(root, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start, require_tpu=require_tpu)
    except NoAccelerator as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    emit(out)
    return 0
