"""A tiny benchmark checkout for the CPU tests: the real configuration,
mix, generator, query-kind and metric files, cut to a table a test can
hold, under a temporary root laid out like the repository
(``BENCHMARK.json`` + ``bench/...``)."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from isla_bench import deployment, harness  # noqa: E402

TINY_DATA = {"rows": 240_000, "block_rows": 2_000, "scale_factor": 0.1}
# The directories of files a deployment brings, copied into a tiny root.
FILE_DIRS = ("metrics", "generators", "queries")


def load(directory: str, name: str):
    """The repository's ``bench/<directory>/<name>.py`` module."""
    return deployment.load(BENCH.parent, directory, name)


def tiny_config(name: str = "tpch_sf10", **data) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg["data"].update(TINY_DATA, **data)
    cfg["checks"]["min_checked_answers"] = 2
    return cfg


def tiny_mix(name: str = "adhoc") -> dict:
    mix = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    mix["warmup"] = {"min_queries": 4, "quiet_queries": 2,
                     "max_queries": 12}
    # Every new key is compared, so a slow (loaded) test machine still
    # checks enough answers in a short window.
    mix["check"] = {"share": 1.0, "max": 24}
    return mix


def make_root(tmp: Path, configs: dict | None = None,
              mixes: dict | None = None, metrics: dict | None = None,
              cells: list | None = None, per_layer: list | None = None,
              generators: dict | None = None, queries: dict | None = None
              ) -> Path:
    """Write a benchmark root under ``tmp``: the real metric readers,
    generators and query kinds, plus the given configurations, mixes and
    extra metric, generator and query-kind files (name -> source)."""
    root = Path(tmp)
    for d in FILE_DIRS:
        shutil.copytree(BENCH / d, root / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    configs = configs or {"tiny": tiny_config()}
    mixes = mixes or {"adhoc": tiny_mix()}
    for name, cfg in configs.items():
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
    for name, mix in mixes.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    for d, files in zip(FILE_DIRS, (metrics, generators, queries)):
        for name, src in (files or {}).items():
            (root / "bench" / d / f"{name}.py").write_text(src)
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cells = cells or [{"name": "tiny.adhoc", "config": "tiny",
                       "traffic": "adhoc", "chips": 1, "why": "test"}]
    names = [c["name"] for c in cells]
    layer = [dict(m, workloads=names) for m in real["per_layer"]]
    bench = dict(real, configs=[
        {"name": n, "source": "test", "file": f"bench/configs/{n}.json",
         "reduced": [], "why": "test"} for n in configs],
        workloads=cells, per_layer=layer + (per_layer or []))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run(root: Path, workload: str = "tiny.adhoc", seed: int = 7,
        seconds: float = 2.0, trace: bool = False,
        control: bool = False) -> dict:
    """One CPU run of a cell: the harness without its look for a chip and
    without the persistent compile cache."""
    return harness.run_cell(root, workload, seed, seconds, trace,
                            time.perf_counter(), require_tpu=False,
                            control=control, compile_cache=False)
