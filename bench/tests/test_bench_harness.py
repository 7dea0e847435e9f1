"""The harness end to end on the CPU at a tiny size: the result line, the
data-driven layout (a configuration, a mix and a per-layer metric added as
new files only) and the exit without a chip."""
import json
import os
import subprocess
import sys

import pytest

import bench_tiny
from isla_bench import harness

TOY_METRIC = '''"""Answers per executed tick (a toy reader for the tests)."""


def read(ctx):
    if not ctx.executed_ticks:
        return None
    return ctx.delta("answered") / ctx.executed_ticks
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    toy_cfg = bench_tiny.tiny_config(block_rows=3_000)
    toy_mix = bench_tiny.tiny_mix()
    toy_mix.update(clients=3, think_ms=5)
    toy_mix["queries"]["lengths"] = [30, 91]
    cells = [{"name": "tiny.adhoc", "config": "tiny", "traffic": "adhoc",
              "chips": 1, "why": "test"},
             {"name": "toy.toy_three", "config": "toy",
              "traffic": "toy_three", "chips": 1, "why": "test"}]
    return bench_tiny.make_root(
        tmp_path_factory.mktemp("bench"),
        configs={"tiny": bench_tiny.tiny_config(), "toy": toy_cfg},
        mixes={"adhoc": bench_tiny.tiny_mix(), "toy_three": toy_mix},
        metrics={"toy.answers_per_tick": TOY_METRIC}, cells=cells,
        per_layer=[{"name": "toy.answers_per_tick", "unit": "answers",
                    "better": "higher", "source": "program_counter",
                    "layer": "Admission (IslaAdmissionLoop)",
                    "moves": "latency_p50_ms",
                    "workloads": ["toy.toy_three"]}])


def test_end_to_end_result(root):
    # A window long enough that a loaded test machine still answers a
    # COUNT query (whose rows always earn their bound) inside it.
    out = bench_tiny.run(root, "tiny.adhoc", seed=2 ** 31 + 3, seconds=8.0)
    res = out["result"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    bench = json.loads((root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["end_to_end"]}
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert res["device"]["platform"] == "cpu"
    assert res["checks"]["count_gap"]["value"] == 0
    assert out["notes"]["compiles"]["window"] >= 0
    assert out["notes"]["bounds_held"][1] > 0


def test_added_files_make_a_new_cell(root):
    """A configuration (another block size), a mix (three clients with a
    think time, other lengths) and a per-layer metric, each a new file,
    run with no code change."""
    out = bench_tiny.run(root, "toy.toy_three", seed=11, seconds=1.5,
                         trace=True)
    res = out["result"]
    assert res["correct"] is True, res["checks"]
    assert "toy.answers_per_tick" in res["metrics"]
    assert res["metrics"]["toy.answers_per_tick"]["value"] >= 1.0
    assert "stage.draw_ms" in res["metrics"]
    # No device plane on the CPU: device-trace readers stay silent.
    assert "fused_tick_roofline" not in res["metrics"]
    assert "breakdown" in res and "window_s" in res["device"]
    assert len(out["notes"]["lateness_ms"]) == 3


def test_counter_readers_on_a_traced_run(root):
    """The loop's pane, upload and store-build counters reach their
    readers through ``Service.counters()``."""
    out = bench_tiny.run(root, "tiny.adhoc", seed=2 ** 31 + 5, seconds=2.0,
                         trace=True)
    m = out["result"]["metrics"]
    assert 0.0 < m["pane.fill_share"]["value"] <= 100.0
    assert m["pane.fill_share"]["unit"] == "%"
    assert m["h2d.mb_per_tick"]["value"] > 0.0
    # Nearly every key of the mix is new: about one store a tick.
    assert 0.5 < m["draw.store_builds_per_tick"]["value"] <= 1.0


def test_metric_entries_respect_workloads():
    bench = {"per_layer": [{"name": "a"}, {"name": "b", "workloads": ["x"]}]}
    assert [m["name"] for m in harness.metric_entries(
        bench, "per_layer", "y")] == ["a"]
    assert len(harness.metric_entries(bench, "per_layer", "x")) == 2


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run(
        [sys.executable, str(bench_tiny.BENCH / "run.py"), "--workload",
         "tpch_sf10.adhoc", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert run.returncode != 0
    assert run.stdout.strip() == ""
    assert "no TPU" in run.stderr
