"""The mergeable HLL sketch plane: partition-invariance, route parity,
compaction survival, transfer hygiene, and cross-process determinism.

The plane's correctness story is ONE property — tick-merged registers
are bit-identical to the one-pass registers, because the merge
(elementwise max) is associative, commutative and idempotent — so these
tests drive exactly that, generalized by hypothesis from fixed splits to
ARBITRARY partitions, across the host ingest, the device fused tick
(tagged and dense), the mesh-sharded tick, and zone-pruned compacted
launches whose pruned cells must keep their resident registers warm.

The hash-input contract (raw float64 bits through splitmix64, no Python
``hash``) makes the plane reproducible across interpreters — audited
here with two fresh subprocesses.
"""
import hashlib
import subprocess
import sys

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax

from repro.core import sketch as SK
from repro.core.moment_store import (DeviceMomentStore, DeviceStack,
                                     MeshDeviceStack, MomentStore)
from repro.core.types import Boundaries, IslaParams
from repro.launch.mesh import make_cell_mesh

PARAMS = IslaParams()
BOUNDS = Boundaries(60.0, 90.0, 110.0, 140.0)
B, G = 5, 3
SIZES = [10 ** 6] * B
N_DEV = jax.device_count()

multi_shard = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count>=2 "
           "set before jax import")


def _stream(rng, n, distinct=200):
    """A measure stream with bounded cardinality plus random tags."""
    vals = np.round(rng.normal(100.0, 20.0, n) * 4.0) / 4.0
    vals = vals if distinct is None else np.floor(vals) % distinct + 60.0
    bids = rng.integers(0, B, n)
    gids = rng.integers(0, G, n)
    return vals, bids, gids


def _host_one_pass(vals, bids, gids):
    st_ = MomentStore.fresh(B, BOUNDS, 100.0, n_groups=G,
                            has_sketch=True)
    st_.ingest(vals, bids, np.full(B, len(vals), np.int64),
               group_ids=gids)
    return st_


def _partition(idx_n, cut_list):
    """Split ``range(idx_n)`` at the (possibly empty/duplicate) cuts."""
    cuts = sorted(set(c % (idx_n + 1) for c in cut_list))
    return np.split(np.arange(idx_n), cuts)


# ------------------------------------------------------- hash twin parity

@given(st.lists(st.floats(allow_nan=False, width=64), min_size=1,
                max_size=64))
@settings(max_examples=50, deadline=None)
def test_limb_hash_twin_matches_uint64_twin(values):
    """The in-graph uint32-limb splitmix64 agrees bit for bit with the
    numpy uint64 twin on arbitrary float64 bit patterns."""
    import jax.numpy as jnp

    v = np.asarray(values, dtype=np.float64)
    want_j, want_rho = SK.encode(SK.hash_values(v))
    hi, lo = SK.value_limbs(v)
    got_j, got_rho = SK.encode_graph(*SK.splitmix64_graph(
        jnp.asarray(hi), jnp.asarray(lo)))
    assert np.array_equal(np.asarray(got_j, np.int64), want_j)
    assert np.array_equal(np.asarray(got_rho, np.uint8), want_rho)


def test_estimator_accuracy_within_standard_error():
    """n distinct values estimate to within ~5x the 1.04/sqrt(m)
    standard error in both regimes (linear counting + raw HLL)."""
    rng = np.random.default_rng(0)
    for true in (150, 3000, 40000):
        regs = np.zeros((1, SK.M), np.uint8)
        v = rng.permutation(10 ** 6)[:true].astype(np.float64)
        j, rho = SK.encode(SK.hash_values(v))
        SK.scatter_max(regs, np.zeros(true, np.int64), j, rho)
        est = float(SK.estimate(regs)[0])
        assert abs(est - true) / true < 5 * SK.REL_ERROR


# ------------------------------------------- partition invariance (host)

@given(st.integers(0, 2 ** 32 - 1),
       st.lists(st.integers(0, 4000), min_size=0, max_size=6))
@settings(max_examples=15, deadline=None)
def test_host_random_partition_merges_bit_identically(seed, cut_list):
    """ANY partition of a stream into ticks folds registers — and the
    moment plane in lockstep — bit-identically to one pass."""
    rng = np.random.default_rng(seed)
    vals, bids, gids = _stream(rng, 1500)
    one = _host_one_pass(vals, bids, gids)
    quotas = np.full(B, len(vals), np.int64)

    ticks = MomentStore.fresh(B, BOUNDS, 100.0, n_groups=G,
                              has_sketch=True)
    for seg in _partition(len(vals), cut_list):
        if seg.size:
            ticks.ingest(vals[seg], bids[seg], quotas,
                         group_ids=gids[seg])
    assert np.array_equal(one.regs, ticks.regs)
    assert np.array_equal(one.totals, ticks.totals)
    assert np.array_equal(one.mom_s, ticks.mom_s)
    assert np.array_equal(one.group_registers(),
                          ticks.group_registers())
    assert np.array_equal(one.distinct_counts(),
                          ticks.distinct_counts())


# --------------------------------------------- device route (fused tick)

@given(st.integers(0, 2 ** 32 - 1),
       st.lists(st.integers(0, 4000), min_size=1, max_size=4))
@settings(max_examples=8, deadline=None)
def test_device_tagged_partition_matches_host_plane(seed, cut_list):
    """The tagged fused tick's resident register plane is bit-identical
    to the host plane under any tick partition (registers key on raw
    float64 bits via the limb twin — fp32 moment math never touches
    them)."""
    rng = np.random.default_rng(seed)
    vals, bids, gids = _stream(rng, 1200)
    one = _host_one_pass(vals, bids, gids)
    quotas = np.full(B, len(vals), np.int64)

    dev = DeviceMomentStore.fresh_device(B, BOUNDS, 100.0, SIZES,
                                         n_groups=G, has_sketch=True)
    for seg in _partition(len(vals), cut_list):
        if seg.size:
            dev.ingest_tick(vals[seg], bids[seg], quotas, PARAMS,
                            group_ids=gids[seg])
    assert np.array_equal(np.asarray(dev.regs), one.regs)
    assert np.array_equal(dev.group_registers(), one.group_registers())
    assert np.array_equal(dev.distinct_counts(), one.distinct_counts())
    # The round trip keeps the plane: host export carries the registers.
    back = dev.to_host()
    assert back.has_sketch and np.array_equal(back.regs, one.regs)


def test_dense_stack_tick_matches_host_plane(rng):
    """The dense block-major fused tick (the fp32 serving layout)
    produces the bit-identical register plane."""
    quota = 200
    passes = []
    for _ in range(3):
        vals = np.round(rng.normal(100.0, 20.0, B * quota))
        gids = rng.integers(0, G, vals.size)
        passes.append((vals, gids))
    bids = np.repeat(np.arange(B), quota)
    quotas = np.full(B, quota, np.int64)

    host = MomentStore.fresh(B, BOUNDS, 100.0, n_groups=G,
                             has_sketch=True)
    dev = DeviceMomentStore.fresh_device(B, BOUNDS, 100.0, SIZES,
                                         n_groups=G, has_sketch=True)
    stack = DeviceStack([dev])
    for vals, gids in passes:
        host.ingest(vals, bids, quotas, group_ids=gids)
        stack.tick(PARAMS, values=vals, quotas=quotas,
                   dense=([gids], [None]))
    assert np.array_equal(np.asarray(dev.regs), host.regs)
    assert np.array_equal(dev.group_registers(), host.group_registers())


def test_pruned_cells_keep_registers_and_reactivate_warm(rng):
    """Zone-pruned compacted ticks never address pruned cells' register
    rows: their state survives the pruned rounds untouched and merges
    seamlessly when the blocks reactivate — bit-identical to the host
    fold of the same per-block sample history."""
    quota = 150
    host = MomentStore.fresh(B, BOUNDS, 100.0, n_groups=G,
                             has_sketch=True)
    dev = DeviceMomentStore.fresh_device(B, BOUNDS, 100.0, SIZES,
                                         n_groups=G, has_sketch=True)
    stack = DeviceStack([dev])
    for r in range(4):
        # Alternate ticks prune blocks {0, 3} (zero quota, no rows).
        active = (np.arange(B) % 3 != 0) if r % 2 else np.ones(B, bool)
        quotas = np.where(active, quota, 0).astype(np.int64)
        vals = np.round(rng.normal(100.0, 20.0, int(quotas.sum())))
        bids = np.repeat(np.arange(B), quotas)
        gids = rng.integers(0, G, vals.size)
        host.ingest(vals, bids, quotas, group_ids=gids)
        stack.tick(PARAMS, values=vals, quotas=quotas,
                   dense=([gids], [None]))
    assert np.array_equal(np.asarray(dev.regs), host.regs)
    assert np.array_equal(dev.distinct_counts(), host.distinct_counts())


# ----------------------------------------------------------- mesh route

def _mesh_pair():
    mk = lambda: DeviceMomentStore.fresh_device(  # noqa: E731
        B, BOUNDS, 100.0, SIZES, n_groups=G, has_sketch=True)
    a, b = mk(), mk()
    return MeshDeviceStack([a, b], make_cell_mesh()), (a, b)


def test_mesh_tick_folds_shard_local_registers(rng):
    """The mesh route's resident per-shard registers and its O(groups)
    folded rows are bit-identical to the host plane — on 1 shard or
    many (the collective is a pmax of folded rows, never per-cell
    state)."""
    quota = 150
    hosts = [MomentStore.fresh(B, BOUNDS, 100.0, n_groups=G,
                               has_sketch=True) for _ in range(2)]
    stack, (da, db) = _mesh_pair()
    bids = np.repeat(np.arange(B), quota)
    quotas = np.full(B, quota, np.int64)
    for _ in range(3):
        vals = np.round(rng.normal(100.0, 20.0, B * quota))
        gids = rng.integers(0, G, vals.size)
        for h in hosts:
            h.ingest(vals, bids, quotas, group_ids=gids)
        stack.tick(PARAMS, values=vals, quotas=quotas,
                   dense=([gids, gids], [None, None]))
    for h, d in zip(hosts, (da, db)):
        assert np.array_equal(d.group_registers(), h.group_registers())
        assert np.array_equal(d.distinct_counts(), h.distinct_counts())
    # Release gathers every shard's rows back to per-store planes.
    stack.release()
    for h, d in zip(hosts, (da, db)):
        assert np.array_equal(np.asarray(d.regs), h.regs)


@multi_shard
def test_mesh_executor_route_matches_device_route(rng):
    """``route="mesh"`` serves the byte-identical count_distinct answers
    as ``route="device"`` (same registers, same host estimator)."""
    from repro.core.multiquery import MultiQueryExecutor, table_sampler
    from repro.core import IslaQuery

    tables = []
    for b in range(8):
        g = rng.integers(0, 3, size=1500)
        tables.append({
            "value": np.round(rng.normal(100.0 + 4.0 * g, 10.0, 1500)),
            "region": g.astype(np.float64),
        })
    def answers(route, mesh):
        kw = {"params": IslaParams(e=0.5), "group_domains": {"region": 3}}
        if mesh is not None:
            kw["mesh"] = mesh
        ex = MultiQueryExecutor([table_sampler(t) for t in tables],
                                [10 ** 6] * 8, **kw)
        q = np.random.default_rng(5)
        batch = [IslaQuery(e=0.5, agg="count_distinct",
                           group_by="region"),
                 IslaQuery(e=0.5, agg="count_distinct")]
        for _ in range(2):
            out = ex.run(batch, q, route=route, incremental=True)
        return out

    dev = answers("device", None)
    mesh = answers("mesh", make_cell_mesh())
    assert [g.value for g in dev[0].groups] == \
        [g.value for g in mesh[0].groups]
    assert dev[1].value == mesh[1].value


# ------------------------------------------------------ transfer hygiene

def _counting_h2d(calls):
    from repro.core import distributed as D
    real = D.h2d

    def h2d(x, dtype=None):
        calls.append(np.asarray(x).nbytes)
        return real(x, dtype)
    return h2d


@pytest.mark.transfer_guard
def test_warm_distinct_tick_moves_zero_register_bytes(rng, monkeypatch):
    """The steady sketch tick under ``transfer_guard("disallow")``: the
    resident (n_cells, 4096) register plane never crosses — only the
    sample-sized uploads (values, tags, hash limb panes) go h2d, and
    the d2h readback is the O(groups) stat + folded-register rows."""
    from repro.core import distributed as D

    n_blocks, n_groups, quota = 40, 8, 50
    sizes = [10 ** 6] * n_blocks
    dev = DeviceMomentStore.fresh_device(n_blocks, BOUNDS, 100.0, sizes,
                                         n_groups=n_groups,
                                         has_sketch=True)

    def tick():
        vals = np.round(rng.normal(100.0, 20.0, n_blocks * quota))
        bids = rng.integers(0, n_blocks, vals.size)
        gids = rng.integers(0, n_groups, vals.size)
        quotas = np.full(n_blocks, quota, np.int64)
        dev.ingest_tick(vals, bids, quotas, PARAMS, group_ids=gids)
        return vals.size

    tick()                                      # warm / compile
    calls = []
    monkeypatch.setattr(D, "h2d", _counting_h2d(calls))
    with jax.transfer_guard("disallow"):
        n = tick()
        # Reading the folded per-group rows is the sanctioned O(groups)
        # d2h — still no register-plane crossing either way.
        folded = dev.group_registers()
    assert folded.shape == (n_groups, SK.M)
    regs_bytes = n_blocks * n_groups * SK.M     # the resident plane
    assert calls, "expected sanctioned sample uploads"
    # Every crossing is sample-sized (float64 pane <= 2x bucket pad),
    # far below the register plane none of which may ship.
    assert max(calls) <= 8 * 2 * n
    assert max(calls) < regs_bytes
    # Warm zero-draw repeat: answered from the stats cache — no h2d.
    calls.clear()
    with jax.transfer_guard("disallow"):
        dev.solve_device(PARAMS)
    assert calls == []


@pytest.mark.transfer_guard
def test_warm_dense_distinct_tick_is_one_upload(rng, monkeypatch):
    """The steady DENSE sketch tick (a block-major stream, the fp32
    serving layout) ships its quotas, values, GROUP BY codes and both
    hash limb panes in ONE upload under ``transfer_guard("disallow")``,
    booked as one ``h2d_calls`` — and the register plane never crosses."""
    from repro.core import distributed as D

    n_blocks, n_groups, quota = 40, 8, 50
    sizes = [10 ** 6] * n_blocks
    dev = DeviceMomentStore.fresh_device(n_blocks, BOUNDS, 100.0, sizes,
                                         n_groups=n_groups,
                                         has_sketch=True)
    stack = DeviceStack([dev])
    quotas = np.full(n_blocks, quota, np.int64)

    def tick(timings=None):
        vals = np.round(rng.normal(100.0, 20.0, n_blocks * quota))
        gids = rng.integers(0, n_groups, vals.size)
        stack.tick(PARAMS, values=vals, quotas=quotas,
                   dense=([gids], [None]), timings=timings)
        return vals.size

    tick()                                      # warm / compile
    calls = []
    monkeypatch.setattr(D, "h2d", _counting_h2d(calls))
    clock = D.StageClock(("h2d", "h2d.pane", "h2d.put", "launch",
                          "readback"), ("h2d_calls", "h2d_bytes"))
    with jax.transfer_guard("disallow"):
        n = tick(clock)
        folded = dev.group_registers()
    assert folded.shape == (n_groups, SK.M)
    assert len(calls) == 1
    assert clock.counts["h2d_calls"] == 1
    assert clock.counts["h2d_bytes"] == calls[0]
    # Four panes of 4-byte words (values, codes, two hash limbs) at
    # <= 2x bucket pad, plus the quota word and the row's lane-tile pad
    # (< 128 words a block): sample-sized, far below the register plane.
    assert calls[0] <= 4 * 4 * 2 * n + 4 * 128 * n_blocks
    assert calls[0] < n_blocks * n_groups * SK.M


# ----------------------------------------- cross-process determinism

_SUBPROC = r"""
import hashlib
import numpy as np
from repro.core.moment_store import MomentStore
from repro.core.types import Boundaries

rng = np.random.default_rng(123)
vals = np.round(rng.normal(100.0, 20.0, 4000) * 8.0) / 8.0
bids = rng.integers(0, 4, vals.size)
gids = rng.integers(0, 3, vals.size)
st = MomentStore.fresh(4, Boundaries(60.0, 90.0, 110.0, 140.0), 100.0,
                       n_groups=3, has_sketch=True)
st.ingest(vals, bids, np.full(4, vals.size, np.int64), group_ids=gids)
print(hashlib.sha256(st.regs.tobytes()).hexdigest())
"""


def test_register_plane_is_deterministic_across_interpreters():
    """Two FRESH interpreters hash the same stream to byte-identical
    register planes — no Python ``hash``, no per-process salt anywhere
    in the plane (PYTHONHASHSEED deliberately differs between runs)."""
    digests = []
    for seed in ("1", "2"):
        out = subprocess.run(
            [sys.executable, "-c", _SUBPROC], capture_output=True,
            text=True, check=True,
            env={"PYTHONPATH": "src", "PYTHONHASHSEED": seed},
        )
        digests.append(out.stdout.strip())
    assert digests[0] == digests[1]
    # And the in-process plane agrees (same digest, third interpreter
    # would be redundant).
    rng = np.random.default_rng(123)
    vals = np.round(rng.normal(100.0, 20.0, 4000) * 8.0) / 8.0
    bids = rng.integers(0, 4, vals.size)
    gids = rng.integers(0, 3, vals.size)
    st_ = MomentStore.fresh(4, Boundaries(60.0, 90.0, 110.0, 140.0),
                            100.0, n_groups=3, has_sketch=True)
    st_.ingest(vals, bids, np.full(4, vals.size, np.int64),
               group_ids=gids)
    assert hashlib.sha256(st_.regs.tobytes()).hexdigest() == digests[0]


# ------------------------------------------------- executor composition

def test_count_distinct_accuracy_through_executor(rng):
    """End to end: COUNT DISTINCT answers land within the sketch's
    (slack-scaled) standard error of the truth when the draw covers the
    stream, per group and globally, with the bound reported."""
    from repro.core.multiquery import MultiQueryExecutor, table_sampler
    from repro.core import IslaQuery

    tables = []
    for b in range(4):
        g = rng.integers(0, 3, size=3000)
        # Low per-group cardinality (~200-400): every value rides many
        # rows, so a full-rate with-replacement draw all but surely
        # samples each one and the only error left is the sketch's own.
        tables.append({
            "value": (rng.integers(0, 600, 3000)
                      % (200 * (g + 1))).astype(np.float64),
            "region": g.astype(np.float64),
        })
    truth = [len(set(float(v) for t in tables
                     for v in t["value"][t["region"] == g]))
             for g in range(3)]
    ex = MultiQueryExecutor(
        [table_sampler(t) for t in tables], [3000] * 4,
        params=IslaParams(e=0.5), group_domains={"region": 3})
    q = IslaQuery(e=0.5, agg="count_distinct", group_by="region")
    ans = ex.run([q], np.random.default_rng(1), rate_override=1.0)[0]
    assert ans.error_bound is not None and ans.error_bound > 0
    for g, row in enumerate(ans.groups):
        assert abs(row.value - truth[g]) / truth[g] < 5 * SK.REL_ERROR
        assert row.error_bound is not None


def test_count_distinct_subsumes_and_survives_late_arrival(rng):
    """A warm count_distinct answer serves dominated asks from the
    cache; a distinct ask landing on a warm key WITHOUT a sketch drops
    that key cold (history cannot be re-hashed) and serves correctly
    from the rebuilt plane."""
    from repro.core.multiquery import MultiQueryExecutor, table_sampler
    from repro.core import IslaQuery

    tables = []
    for b in range(4):
        g = rng.integers(0, 3, size=2000)
        tables.append({
            "value": np.round(rng.normal(100.0 + 4.0 * g, 10.0, 2000)),
            "region": g.astype(np.float64),
        })
    ex = MultiQueryExecutor(
        [table_sampler(t) for t in tables], [10 ** 6] * 4,
        params=IslaParams(e=0.5), group_domains={"region": 3})
    q_rng = np.random.default_rng(2)
    # Warm the key with a moments-only aggregate first.
    ex.run([IslaQuery(e=0.5, agg="AVG", group_by="region")], q_rng,
           incremental=True)
    # Late-arriving distinct on the SAME key: must not serve a partial
    # plane that missed the first tick's samples.
    q = IslaQuery(e=0.5, agg="count_distinct", group_by="region")
    ans = ex.run([q], q_rng, incremental=True)[0]
    assert ans.error_bound is not None
    ledger = [st for st in ex._stores.values() if st.has_sketch] + \
        [d for d in ex._device_stores.values() if d.has_sketch]
    assert ledger, "distinct key should now carry a sketch plane"
    # Weaker ask: served from the subsumption cache, zero new samples.
    weak = IslaQuery(e=0.9, beta=0.9, agg="count_distinct",
                     group_by="region")
    hit = ex.lookup_answer(weak)
    assert hit is not None and hit.served == "subsumed"
    assert hit.new_samples == 0 and hit.value == ans.value
