"""Stage spans and stage clocks of the served tick
(``distributed.stage_trace``).

Every stage of a tick is an ``isla:<name>`` profiler span and books the
same interval into the run's stage clock: a top-level stage its self time
(top-level stages nested in it on the same thread taken out), a dotted
part its inclusive time.  The loop's tick is the ``isla:tick`` span,
booked as ``admit``.  On the serial route the top-level stages therefore
add up to the tick's wall time; the pipelined route's worker runs its
stages on another thread, so nothing of them is subtracted.
"""
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import IslaParams, IslaQuery, Predicate
from repro.core import distributed as D
from repro.core.multiquery import _STAGES, MultiQueryExecutor
from repro.launch.serve import IslaAdmissionLoop, _synthetic_grouped_blocks

# The span of each stage and the span it opens inside on the serial
# device route (readback: the blocking stat-row read of the launch).
PARENT = {
    "isla:tick": None,
    "isla:plan": "isla:tick",
    "isla:draw": "isla:tick",
    "isla:draw.stack": "isla:draw",
    "isla:draw.sample": "isla:draw",
    "isla:draw.mask": "isla:draw",
    "isla:h2d": "isla:draw",
    "isla:h2d.pane": "isla:h2d",
    "isla:h2d.put": "isla:h2d",
    "isla:launch": "isla:draw",
    "isla:readback": "isla:draw",
    "isla:compose": "isla:tick",
}
TOP = [k for k in _STAGES if "." not in k]


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        return self.t


class _Spans:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each span
    with the span open around it on the same thread."""

    def __init__(self):
        self.opened = []          # (name, parent)
        self._open = threading.local()

    def __call__(self, name):
        spans = self

        class _Span:
            def __enter__(self):
                stack = spans._stack()
                spans.opened.append((name, stack[-1] if stack else None))
                stack.append(name)

            def __exit__(self, *exc):
                spans._stack().pop()

        return _Span()

    def _stack(self):
        if not hasattr(self._open, "names"):
            self._open.names = []
        return self._open.names


def _loop(route="device", **kw):
    samplers = _synthetic_grouped_blocks(n_blocks=8, n_groups=3, rows=4000,
                                         seed=0)
    ex = MultiQueryExecutor(samplers, [10 ** 6] * 8,
                            params=IslaParams(e=0.5),
                            group_domains={"region": 3})
    return IslaAdmissionLoop(ex, np.random.default_rng(1), route=route,
                             incremental=True, **kw)


def _fresh_queries(i):
    """Tick ``i``'s batch: keys not seen before, so every tick draws."""
    where = Predicate(column="flag", eq=float(i % 2))
    return [IslaQuery(e=0.5 / (i + 1), agg="AVG", group_by="region",
                      where=where),
            IslaQuery(e=0.5 / (i + 1), agg="COUNT", where=where)]


def _tick(loop, i):
    for q in _fresh_queries(i):
        loop.submit(q)
    before = loop.stats
    t0 = time.perf_counter()
    done = loop.tick()
    wall = time.perf_counter() - t0
    after = loop.stats
    spent = {k: v - before["stage_seconds"].get(k, 0.0)
             for k, v in after["stage_seconds"].items()}
    return done, wall, spent, before, after


def test_stage_books_self_time_and_parts(monkeypatch):
    clock = _FakeClock()
    monkeypatch.setattr(D, "time", clock)
    times = D.StageClock(("draw", "draw.sample", "h2d", "h2d.put"),
                         ("h2d_bytes",))
    with D.stage_trace("draw", times):
        clock.t += 1.0
        with D.stage_trace("draw.sample", times):
            clock.t += 2.0
        with D.stage_trace("h2d", times):
            clock.t += 4.0
            with D.stage_trace("h2d.put", times):
                clock.t += 8.0
                D.count("h2d_bytes", 16)
        clock.t += 32.0
    # draw: 47 s open, less the 12 s h2d nested in it; parts inclusive.
    assert times == {"draw": 35.0, "draw.sample": 2.0, "h2d": 12.0,
                     "h2d.put": 8.0}
    assert times.counts == {"h2d_bytes": 16}
    D.count("h2d_bytes", 5)       # no stage open: dropped
    assert times.counts == {"h2d_bytes": 16}


def test_stage_key_override_and_unbooked_nesting(monkeypatch):
    clock = _FakeClock()
    monkeypatch.setattr(D, "time", clock)
    outer = {}
    with D.stage_trace("tick", outer, key="admit"):
        clock.t += 1.0
        with D.stage_trace("plan"):   # books nothing, still nested
            clock.t += 2.0
            D.count("h2d_bytes", 3)   # a plain dict keeps no counts
    assert outer == {"admit": 1.0}


def test_stage_on_another_thread_is_not_subtracted():
    times = {}

    def worker():
        with D.stage_trace("launch", times):
            time.sleep(0.05)

    with D.stage_trace("draw", times):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert times["launch"] >= 0.05
    assert times["draw"] >= times["launch"]


class _ThreadClock:
    """Each thread reads its own clock, one second on per reading: every
    stage a thread opens and closes with nothing nested spans 1 s."""

    def __init__(self):
        self._local = threading.local()

    def perf_counter(self):
        self._local.t = getattr(self._local, "t", 0.0) + 1.0
        return self._local.t


def test_concurrent_bookings_are_not_lost(monkeypatch):
    monkeypatch.setattr(D, "time", _ThreadClock())
    times = D.StageClock(("h2d",), ("h2d_bytes",))
    n_threads, n_stages = 16, 300

    def worker():
        for _ in range(n_stages):
            with D.stage_trace("h2d", times):
                D.count("h2d_bytes", 1)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert times == {"h2d": float(n_threads * n_stages)}
    assert times.counts == {"h2d_bytes": n_threads * n_stages}


def test_stage_spans_are_named_and_emitted_without_a_clock(monkeypatch):
    spans = _Spans()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", spans)
    with D.stage_trace("draw"):
        with D.stage_trace("draw.sample"):
            pass
    assert spans.opened == [("isla:draw", None),
                            ("isla:draw.sample", "isla:draw")]


@pytest.mark.parametrize("route", ["device", "mesh"])
def test_device_tick_span_nesting(monkeypatch, route):
    """Every span of the table opens inside its parent, once per tick
    (one chunk, one mode-group): never per block or per row."""
    loop = _loop(route)
    _tick(loop, 0)                # pilot, compiles
    spans = _Spans()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", spans)
    done, *_ = _tick(loop, 1)
    assert len(done) == 2
    assert set(spans.opened) == set(PARENT.items())
    names = [n for n, _ in spans.opened]
    assert len(names) <= 20
    for n in ("isla:tick", "isla:plan", "isla:draw", "isla:draw.sample",
              "isla:h2d", "isla:launch", "isla:compose"):
        assert names.count(n) == 1, (n, names)


def test_serial_stages_add_up_to_the_tick():
    """Serial device route: admit + plan + draw + h2d + launch + readback
    + compose is the loop tick's wall time (readback is no longer also
    inside draw), and every part is at most its parent."""
    loop = _loop()
    for i in range(4):
        done, wall, spent, before, after = _tick(loop, i)
        assert done and after["samples_drawn"] > before["samples_drawn"]
        assert set(spent) == set(_STAGES)
        assert sum(spent[k] for k in TOP) == pytest.approx(wall, rel=0.02)
        assert spent["readback"] > 0.0
        for part in ("draw.stack", "draw.sample", "draw.mask"):
            assert 0.0 < spent[part] <= spent["draw"]
        for part in ("h2d.pane", "h2d.put"):
            assert 0.0 < spent[part] <= spent["h2d"]


def test_pipelined_stages_book_every_key():
    loop = _loop(pipeline=True)
    for i in range(2):
        done, _, spent, _, _ = _tick(loop, i)
        assert len(done) == 2
    assert set(spent) == set(_STAGES)
    assert set(loop.executor.last_stage_times) == set(_STAGES)
    assert spent["draw"] > 0.0 and spent["admit"] > 0.0


@pytest.mark.parametrize("route", ["device", "mesh"])
def test_upload_counters(route):
    """A drawing tick uploads a value pane at least as large as its
    samples, and its uploads and their bytes are counted: on one device
    a tick that builds no store (tick 2 repeats tick 0's WHERE) ships
    its samples in one upload."""
    loop = _loop(route)
    warm_ticks = 0
    for i in range(3):
        _, _, _, before, after = _tick(loop, i)
        drawn = after["samples_drawn"] - before["samples_drawn"]
        slots = after["pane_slots"] - before["pane_slots"]
        calls = after["h2d_calls"] - before["h2d_calls"]
        builds = after["store_builds"] - before["store_builds"]
        assert drawn > 0
        assert slots >= drawn
        assert after["h2d_bytes"] - before["h2d_bytes"] >= 4 * slots
        assert calls >= 1 + builds
        if route == "device" and builds == 0:
            warm_ticks += 1
            assert calls == 1
    if route == "device":
        assert warm_ticks == 1
    counts = loop.executor.last_stage_times.counts
    assert set(counts) == {"pane_slots", "h2d_calls", "h2d_bytes",
                           "store_builds"}


def test_host_route_uploads_nothing():
    loop = _loop("host")
    _, _, spent, before, after = _tick(loop, 0)
    assert set(spent) == set(_STAGES)
    assert spent["draw.sample"] > 0.0 and spent["launch"] == 0.0
    assert after["pane_slots"] == 0 and after["h2d_bytes"] == 0
