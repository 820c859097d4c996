"""The traffic generator: deterministic per seed, the same work for every
seed in another order, and the distributions its mixes state."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import spec, traffic  # noqa: E402

BIG_SEED = 2**31 + 12345


def test_quantiles_match_the_stated_distributions():
    q = traffic.quantiles({"dist": "lognormal", "median": 100, "sigma": 0.5,
                           "min": 1, "max": 10**6}, 1001)
    assert q[500] == 100
    assert abs(np.log(q[841]) - (np.log(100) + 0.5)) < 0.01  # one sigma up
    u = traffic.quantiles({"dist": "uniform", "min": 8, "max": 32}, 25)
    assert list(u) == list(range(8, 33))
    with pytest.raises(ValueError):
        traffic.quantiles({"dist": "exponential", "mean": 2.0}, 10)


def test_corpus_packs_documents():
    m = dict(spec.traffic("train-2k"), corpus_steps=2, batch=2, seq_len=512)
    a = traffic.corpus(m, BIG_SEED, 50304, 50279)
    assert len(a) == 2 * 2 * 512 + 1 and a.dtype == np.int32
    assert np.array_equal(a, traffic.corpus(m, BIG_SEED, 50304, 50279))
    assert 0 <= a.min() and a.max() < 50304
    assert (a == 50279).sum() >= 1


@pytest.mark.parametrize("seeds", [(1, 7), (BIG_SEED, 2**40 + 3)])
def test_every_seed_gets_the_same_documents_in_another_order(seeds):
    m = spec.traffic("train-2k")
    a, b = (traffic.corpus(m, s, 50304, 50279) for s in seeds)
    assert len(a) == len(b) and not np.array_equal(a[:4096], b[:4096])
    lens = lambda t: np.diff(np.flatnonzero(t == 50279))  # noqa: E731
    la, lb = lens(a), lens(b)
    # The documents are drawn from the same stratified lengths.
    assert len(la) == pytest.approx(len(lb), rel=0.05)
    assert np.median(la) == pytest.approx(np.median(lb), rel=0.05)


def test_train_corpus_is_unchanged():
    """The corpus of the training cell, byte for byte as it was before the
    serving traffic shared its token draw."""
    import hashlib

    m = dict(spec.traffic("train-2k"), corpus_steps=2, batch=2, seq_len=512)
    a = traffic.corpus(m, BIG_SEED, 50304, 50279)
    assert hashlib.sha256(a.tobytes()).hexdigest() == (
        "15bd197d78265f3cbf841182b83387199c8026200daf08b5c0d87c124c19d1fa")


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED, 2**40 + 3])
def test_every_block_of_requests_holds_every_quantile(seed):
    m = spec.traffic("serve-prefill-backlog")
    reqs = traffic.requests(m, seed, 64000, 2)
    assert len(reqs) == m["backlog"]
    b = m["block"]
    for key, dist in ((0, m["prompt"]), (1, m["output"])):
        want = sorted(traffic.quantiles(dist, b))
        sizes = [len(r[0]) if key == 0 else r[1] for r in reqs]
        for i in range(0, len(sizes) - b + 1, b):
            assert sorted(sizes[i:i + b]) == want
    toks = np.concatenate([p for p, _ in reqs])
    assert toks.dtype == np.int32 and 0 <= toks.min() and toks.max() < 64000
    assert not (toks == 2).any()


def test_requests_are_the_same_for_a_seed_and_differ_between_seeds():
    m = spec.traffic("serve-prefill-backlog")
    a, b = (traffic.requests(m, BIG_SEED, 64000, 2) for _ in range(2))
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1] for x, y in zip(a, b))
    c = traffic.requests(m, BIG_SEED + 1, 64000, 2)
    assert [len(x[0]) for x in a] != [len(x[0]) for x in c]
    with pytest.raises(ValueError):
        traffic.requests(dict(m, arrivals="poisson"), 1, 64000, 2)
