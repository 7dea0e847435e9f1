"""What a deployment brings to the benchmark as files, and how the harness
finds them by name.

A configuration (``bench/configs/<config>.json``) names its table's
generator in ``data.generator``; a traffic mix (``bench/traffic/<mix>.json``)
names its query shape in ``queries.kind``.  Each is a Python file of its
own, loaded from the benchmark root by that name, so a new deployment adds
files and edits none:

* ``bench/generators/<generator>.py`` provides ``generate(data_cfg, seed)
  -> Table``: the configuration's ``data`` entry and the run's seed give
  the table, the same bits for the same seed.
* ``bench/queries/<kind>.py`` provides everything that depends on the shape
  of a query:

  - ``queries(q, e, beta, rng)``: the endless stream of query specs the mix
    (its ``queries`` entry ``q``) sends, drawn from ``rng``;
  - ``sweep(q, e, beta, rng, e_factors)``: the warm-up's list of specs that
    reach the mix's extremes at each precision factor;
  - ``where(spec)``: the WHERE as the fields of the program's ``Predicate``,
    or None;
  - ``match(spec, columns)``: the WHERE's mask over a dict of columns (the
    drawn rows, and the pilot rows that pick a refined anchor);
  - ``values(cfg, columns)``: the values the aggregate runs over;
  - ``truth(cfg, q, table)``: the exact reference, with ``answer(spec,
    group)`` (the exact COUNT, SUM or AVG, overall when ``group`` is None)
    and ``live_blocks(spec)`` (the blocks holding a matching row, or None
    where pruning cannot be judged).

  A spec is hashable and has ``agg``, ``group_by``, ``e``, ``beta`` and
  ``key`` (what the served path shares state on).  The kind's files import
  nothing of the program.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one named stream of a run's seed (any integer)."""
    return np.random.default_rng([int(seed) % (1 << 64), int(stream)])


@dataclasses.dataclass
class Table:
    """A generated table cut into blocks of ``block_rows`` rows."""

    columns: dict              # name -> (rows,) float64
    block_rows: int

    @property
    def rows(self) -> int:
        return int(next(iter(self.columns.values())).shape[0])

    @property
    def n_blocks(self) -> int:
        return -(-self.rows // self.block_rows)

    def block_slice(self, b: int) -> slice:
        return slice(b * self.block_rows,
                     min((b + 1) * self.block_rows, self.rows))

    def blocks(self) -> list:
        """Per-block column dicts (views, no copy)."""
        return [{k: v[self.block_slice(b)] for k, v in self.columns.items()}
                for b in range(self.n_blocks)]

    def block_sizes(self) -> list:
        return [self.block_slice(b).stop - self.block_slice(b).start
                for b in range(self.n_blocks)]


def load(root: Path, directory: str, name: str):
    """The module ``bench/<directory>/<name>.py`` under a benchmark root."""
    path = Path(root) / "bench" / directory / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no {directory} file {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory}_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # what dataclasses look up
    spec.loader.exec_module(mod)
    return mod
