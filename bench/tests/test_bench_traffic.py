"""Each traffic mix is reproducible from its seed and sends the shares it
states."""
import collections
import itertools
import json

import numpy as np
import pytest

import bench_tiny
from isla_bench.deployment import seed_rng
from isla_bench.traffic import Mix

TRAFFIC = bench_tiny.BENCH / "traffic"


def load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def take(mix, seed, n):
    return list(itertools.islice(mix.queries(seed_rng(seed, 3)), n))


@pytest.mark.parametrize("name", sorted(p.stem for p in TRAFFIC.glob("*.json")))
def test_every_mix_is_reproducible(name):
    mix = Mix(load(name), e=764.0, beta=0.95)
    assert take(mix, 2 ** 31 + 5, 300) == take(mix, 2 ** 31 + 5, 300)
    assert take(mix, 1, 300) != take(mix, 2, 300)


def test_adhoc_deck_shares():
    spec = load("adhoc")
    mix = Mix(spec, e=764.0, beta=0.95)
    deck = mix.kind.deck(mix.queries_spec)
    n_s = spec["queries"]["start_strata"]
    qs = take(mix, 77, len(deck) * n_s * 3)
    # Every combination once per deck.
    for d in range(len(qs) // len(deck)):
        chunk = qs[d * len(deck):(d + 1) * len(deck)]
        combos = collections.Counter(
            (q.agg, q.hi - q.lo, q.group_by is not None) for q in chunk)
        assert set(combos) == set(deck) and set(combos.values()) == {1}
    assert np.mean([q.group_by is not None for q in qs]) == 0.5
    assert {q.agg for q in qs} == set(spec["queries"]["aggs"])
    # Over start_strata decks each combination starts once per stratum.
    lo, hi = spec["queries"]["start"]
    width = (hi - lo + 1) / n_s
    block = qs[:len(deck) * n_s]
    seen = collections.defaultdict(set)
    for q in block:
        seen[(q.agg, q.hi - q.lo, q.group_by is not None)].add(
            int((q.lo - lo) // width))
    assert all(s == set(range(n_s)) for s in seen.values())
    assert all(q.e == 764.0 and q.beta == 0.95 for q in qs)


def test_sweep_covers_deck_at_each_factor():
    mix = Mix(load("adhoc"), e=100.0, beta=0.95)
    qs = mix.sweep(np.random.default_rng(0), [0.5, 1.0])
    n = len(mix.kind.deck(mix.queries_spec))
    assert len(qs) == 2 * n
    assert collections.Counter(q.e for q in qs) == {50.0: n, 100.0: n}


def test_unbalanced_mix_keeps_its_shares():
    spec = load("adhoc")
    spec["queries"]["balanced"] = False
    qs = take(Mix(spec, e=764.0, beta=0.95), 11, 6000)
    assert np.mean([q.group_by is not None for q in qs]) == pytest.approx(
        0.5, abs=0.03)
    aggs = collections.Counter(q.agg for q in qs)
    assert all(n / len(qs) == pytest.approx(1 / 3, abs=0.03)
               for n in aggs.values())
    lo, hi = spec["queries"]["start"]
    assert all(lo <= q.lo <= hi for q in qs)


@pytest.mark.parametrize("change", [{"loop": "open"},
                                    {"queries": {"kind": "tiles"}}],
                         ids=["open_loop", "tiles"])
def test_unknown_mix_is_refused(change):
    spec = dict(load("adhoc"), **change)
    with pytest.raises(ValueError):
        Mix(spec, e=764.0, beta=0.95)
