"""The readers of the program's stage spans and counters on a hand-built
context: each reads its stage's milliseconds (or its counter's rate) per
executed tick, and nothing from a program that books no such stage or
keeps no such counter."""
from types import SimpleNamespace

import pytest

import bench_tiny
from isla_bench import harness

STAGE_OF = {
    "stage.admit_ms": "admit",
    "stage.stack_ms": "draw.stack",
    "stage.sample_ms": "draw.sample",
    "stage.mask_ms": "draw.mask",
    "stage.pane_ms": "h2d.pane",
    "stage.put_ms": "h2d.put",
}


def _ctx(stage_seconds0: dict, stage_seconds1: dict, executed: int,
         counters0: dict | None = None, counters1: dict | None = None):
    counters = dict(answered=0, subsumed=0, deduped=0, samples_drawn=0)
    ticks = ([harness.TickRecord([("AVG",)], 100)] * executed
             + [harness.TickRecord([], 0)])
    clients = SimpleNamespace(sent=[], ticks=ticks)
    return harness.metric_context(
        clients, {"group_domains": {"rf_ls": 4}},
        dict(counters, stage_seconds=stage_seconds0, **(counters0 or {})),
        dict(counters, stage_seconds=stage_seconds1, **(counters1 or {})),
        (0.0, 1.0, 1.0), 0, None, 0.0, "cpu", 8)


@pytest.mark.parametrize("case", ["value", "absent"])
@pytest.mark.parametrize("name", sorted(STAGE_OF))
def test_stage_reader(name, case):
    read = harness.load_reader(bench_tiny.BENCH.parent, name)
    stage = STAGE_OF[name]
    other = {"draw": 9.0, "plan": 3.0}
    if case == "value":
        ctx = _ctx(dict(other, **{stage: 1.0}),
                   dict(other, **{stage: 1.5}), executed=4)
        assert read(ctx) == pytest.approx(125.0)
    else:
        # A program that books no such stage (the parent of the change
        # that added it) reports nothing and does not raise.
        assert read(_ctx(other, other, executed=4)) is None


COUNTER_READS = {
    # name: (counters at the window's start, at its end, reading)
    "pane.fill_share": ({"samples_drawn": 100, "pane_slots": 1000},
                        {"samples_drawn": 400, "pane_slots": 1500}, 60.0),
    "h2d.mb_per_tick": ({"h2d_bytes": 1_000_000}, {"h2d_bytes": 9_000_000},
                        2.0),
    "draw.store_builds_per_tick": ({"store_builds": 5}, {"store_builds": 8},
                                   0.75),
}


@pytest.mark.parametrize("case", ["value", "absent"])
@pytest.mark.parametrize("name", sorted(COUNTER_READS))
def test_counter_reader(name, case):
    read = harness.load_reader(bench_tiny.BENCH.parent, name)
    c0, c1, want = COUNTER_READS[name]
    if case == "value":
        assert read(_ctx({}, {}, 4, c0, c1)) == pytest.approx(want)
    else:
        # A program that keeps no such counter reports nothing.
        assert read(_ctx({}, {}, 4)) is None
