"""The traffic generator: one seed gives one pool, its rows all differ, and
non-iid nodes draw from their own permutation of the vocabulary."""
from __future__ import annotations

import numpy as np
import pytest

import harness
from traffic import Traffic, markov_rows

MODEL = dict(vocab_size=512, d_model=64)


def spec(**kw):
    base = harness.load_json(harness.ROOT / "bench/traffic/ring4.s2k.tau4.json")
    return {**base, "seq_len": 64, "pool_rounds": 3, **kw}


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 2**40 + 3])
def test_same_seed_same_pool_and_rows_differ(seed):
    a = Traffic.make(spec(), MODEL, 4, seed)
    b = Traffic.make(spec(), MODEL, 4, seed)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    rows = a.tokens.reshape(-1, a.tokens.shape[-1])
    assert len(np.unique(rows, axis=0)) == len(rows)
    assert a.tokens.min() >= 0 and a.tokens.max() < MODEL["vocab_size"]
    batch = a.round(4)                                   # the pool repeats
    np.testing.assert_array_equal(batch["tokens"], a.round(1)["tokens"])
    assert batch["tokens"].shape == (4, 4, 1, 64)
    np.testing.assert_array_equal(batch["targets"][..., :-1], batch["tokens"][..., 1:])


def _count_correlations(t):
    counts = [np.bincount(t.tokens[:, :, n].ravel(), minlength=512) for n in range(4)]
    return [np.corrcoef(counts[0], counts[n])[0, 1] for n in range(1, 4)]


def test_non_iid_nodes_favour_different_tokens():
    t = Traffic.make(spec(pool_rounds=20), MODEL, 4, 11)
    assert max(_count_correlations(t)) < 0.5
    iid = Traffic.make(spec(pool_rounds=20, tokens=dict(spec()["tokens"], non_iid=False)),
                       MODEL, 4, 11)
    assert min(_count_correlations(iid)) > 0.9


def test_markov_rows_follow_their_context_table():
    rng = np.random.default_rng(0)
    rows = markov_rows(rng, 50, 40, 100, 1.3, 2, 8, 64)
    pairs = {}
    for r in rows:
        for t in range(2, len(r)):
            pairs.setdefault((r[t - 2], r[t - 1]), set()).add(r[t])
    assert max(len(v) for v in pairs.values()) <= 8

