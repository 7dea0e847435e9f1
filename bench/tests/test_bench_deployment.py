"""A deployment joins the benchmark as files: a generator and a query kind
found by the names its configuration and mix carry.  A new query shape
(an equality on a code column, through ``Predicate(eq=...)``) runs through
the harness with no code edited and comes out correct, a fault planted in
its match comes out not correct, and the ``tpch_sf10`` draws are pinned to
the values they had before the generator and the range kind moved into
files of their own."""
import copy
import hashlib
import itertools
import json

import pytest

import bench_tiny
from isla_bench import harness
from isla_bench.deployment import seed_rng
from isla_bench.traffic import Mix

GENERATOR = '''"""Generator ``coded_amounts``: an ``amount`` uniform over [50, 150),
a ``code`` uniform over ``codes`` values and a ``region`` of three."""
import numpy as np

from isla_bench.deployment import Table, seed_rng


def generate(cfg, seed):
    rows = int(cfg["rows"])
    rng = seed_rng(seed, 1)
    code = rng.integers(0, int(cfg["codes"]), size=rows)
    region = rng.integers(0, 3, size=rows)
    amount = rng.uniform(50.0, 150.0, size=rows)
    return Table(columns={"amount": amount,
                          "code": code.astype(np.float64),
                          "region": region.astype(np.float64)},
                 block_rows=int(cfg["block_rows"]))
'''

KIND = '''"""Query kind ``code_eq``: an aggregate of the measure where one code
column equals a value, grouped or not; every (value, grouping) once in a
seeded order before any repeats, so nearly every key is new (as in the
range mix).  Exact answers by a scan."""
import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    agg: str
    column: str
    value: float
    group_by: Optional[str]
    e: float
    beta: float

    @property
    def key(self):
        return (self.column, self.value, self.group_by)


def _query(rng, q, e, beta, combo, factor=1.0):
    agg = q["aggs"][int(rng.integers(len(q["aggs"])))]
    value, grouped = divmod(int(combo), 2)
    return QuerySpec(agg, q["column"], float(value),
                     q["group_by"] if grouped else None, e * factor, beta)


def queries(q, e, beta, rng):
    while True:
        for combo in rng.permutation(2 * int(q["codes"])):
            yield _query(rng, q, e, beta, combo)


def sweep(q, e, beta, rng, e_factors):
    return [_query(rng, q, e, beta, c, f) for f in e_factors
            for c in rng.permutation(2 * int(q["codes"]))[:4]]


def where(spec):
    return {"column": spec.column, "eq": spec.value}


def match(spec, columns):
    return columns[spec.column] == spec.value


def values(cfg, columns):
    return columns[cfg["measure"]]


def truth(cfg, q, table):
    return Scan(cfg, table)


class Scan:
    def __init__(self, cfg, table):
        self.table = table
        self.measure = cfg["measure"]

    def _rows(self, spec, group):
        cols = self.table.columns
        m = match(spec, cols)
        if group is not None:
            m &= cols[spec.group_by] == group
        return m

    def answer(self, spec, group=None):
        v = self.table.columns[self.measure][self._rows(spec, group)]
        if spec.agg == "COUNT":
            return float(v.size)
        if spec.agg == "SUM":
            return float(v.sum())
        return float(v.mean()) if v.size else float("nan")

    def live_blocks(self, spec):
        m = self._rows(spec, None)
        return np.flatnonzero([m[self.table.block_slice(b)].any()
                               for b in range(self.table.n_blocks)])
'''

CONFIG = {
    "data": {"generator": "coded_amounts", "rows": 240_000,
             "block_rows": 2_000, "codes": 512},
    "measure": "amount",
    "group_domains": {"region": 3},
    "e": 5.0,
    "beta": 0.95,
    "service": {"route": "device", "incremental": True, "zone_map": True},
}

MIX = {
    "loop": "closed", "clients": 1, "think_ms": 0,
    "queries": {"kind": "code_eq", "aggs": ["SUM", "AVG", "COUNT"],
                "column": "code", "codes": 512, "group_by": "region"},
    "warmup": {"e_factors": [0.71, 1.0, 1.41], "min_queries": 4,
               "quiet_queries": 2, "max_queries": 16},
    "check": {"share": 1.0, "max": 24},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tiny = bench_tiny.tiny_config()
    cfg = dict(CONFIG, checks=tiny["checks"], estimator=tiny["estimator"])
    # The same kind with its match shifted by one code: a fault.
    shifted = KIND.replace("return columns[spec.column] == spec.value",
                           "return columns[spec.column] == spec.value + 1")
    assert shifted != KIND
    mix_shifted = copy.deepcopy(MIX)
    mix_shifted["queries"]["kind"] = "code_eq_shifted"
    cells = [{"name": "coded.eq", "config": "coded", "traffic": "eq",
              "chips": 1, "why": "test"},
             {"name": "coded.eq_shifted", "config": "coded",
              "traffic": "eq_shifted", "chips": 1, "why": "test"}]
    return bench_tiny.make_root(
        tmp_path_factory.mktemp("bench"), configs={"coded": cfg},
        mixes={"eq": MIX, "eq_shifted": mix_shifted}, cells=cells,
        generators={"coded_amounts": GENERATOR},
        queries={"code_eq": KIND, "code_eq_shifted": shifted})


def test_a_new_shape_as_files_only(root):
    out = bench_tiny.run(root, "coded.eq", seed=2 ** 31 + 21, seconds=4.0)
    res = out["result"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert out["notes"]["checked_answers"] >= 2
    assert res["checks"]["count_gap"]["value"] == 0
    assert out["notes"]["bounds_held"][1] > 0


def test_a_shifted_match_is_caught(root):
    out = bench_tiny.run(root, "coded.eq_shifted", seed=2 ** 31 + 22,
                         seconds=2.0)
    assert out["result"]["correct"] is False
    assert out["result"]["checks"]["count_gap"]["value"] > 0


# Taken from the tree before the generator and the range kind became files
# of their own (``tpch.generate`` and ``traffic.Mix``), for this seed on
# ``bench_tiny.TINY_DATA``.
SEED = 2 ** 31 + 17
TABLE_SHA256 = ("b9823afcfa261f959741964cc2fc1bebea4bd635ce267f1acde9a84930b4af7d")
SPECS_SHA256 = {
    "traffic": "aae7fd95ca7ef92751fac84b6569aa10633f8022268fa0f7ca7ed68506d3e022",
    "warmup": "ad17a0b989177496e4bc56b1a555b8d9011b64fd722c51c5b53dd9f03501ad34",
}
FIRST_SPECS = {
    "traffic": [["AVG", "l_shipdate", 890.0, 1255.0, None, 1911.0, 0.95],
                ["SUM", "l_shipdate", 1940.0, 1947.0, "rf_ls", 1911.0, 0.95],
                ["COUNT", "l_shipdate", 1649.0, 2014.0, "rf_ls", 1911.0,
                 0.95]],
    "warmup": [["COUNT", "l_shipdate", 2038.0, 2129.0, "rf_ls", 955.5, 0.95],
               ["COUNT", "l_shipdate", 624.0, 715.0, "rf_ls", 1356.81, 0.95],
               ["COUNT", "l_shipdate", 1090.0, 1181.0, "rf_ls", 1911.0,
                0.95]],
}


def test_tpch_sf10_table_is_pinned():
    cfg = bench_tiny.tiny_config()
    gen = bench_tiny.load("generators", cfg["data"]["generator"])
    table = gen.generate(cfg["data"], SEED)
    h = hashlib.sha256()
    for k in sorted(table.columns):
        h.update(k.encode())
        h.update(table.columns[k].tobytes())
    assert (table.rows, table.n_blocks) == (240_000, 120)
    assert h.hexdigest() == TABLE_SHA256


@pytest.mark.parametrize("stream", ["traffic", "warmup"])
def test_adhoc_specs_are_pinned(stream):
    """The first 48 specs the window sends, and the first 48 of the
    warm-up's sweep."""
    cfg = bench_tiny.tiny_config()
    spec = json.loads((bench_tiny.BENCH / "traffic" / "adhoc.json")
                      .read_text())
    mix = Mix(spec, cfg["e"], cfg["beta"])
    if stream == "traffic":
        qs = itertools.islice(
            mix.queries(seed_rng(SEED, harness.STREAM_TRAFFIC)), 48)
    else:
        rng = seed_rng(SEED, harness.STREAM_WARMUP)
        qs = mix.sweep(rng, spec["warmup"]["e_factors"])[:48]
    rows = [[q.agg, q.column, q.lo, q.hi, q.group_by, q.e, q.beta]
            for q in qs]
    assert rows[:3] == FIRST_SPECS[stream]
    assert hashlib.sha256(json.dumps(rows).encode()).hexdigest() == \
        SPECS_SHA256[stream]
