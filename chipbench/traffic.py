"""The generator of traffic.  A mix is a data file, ``traffic/<name>.json``;
its ``kind`` names the module whose ``Cell`` runs it (``train``: a corpus of
packed documents for the training step, ``corpus`` below; ``serve``: a
backlog of requests, ``requests`` below).

Every draw is stratified: the n sizes of a run are the n quantiles
``(i + 0.5) / n`` of the stated distribution, and the seed only permutes
them and draws the token ids.  So every seed gives the same work in another
order, and runs with different seeds differ no more than the order makes
them.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


def rng_for(seed: int, salt: str) -> np.random.Generator:
    """A generator keyed by the seed (any size) and the use it serves."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % 2**63, *salt.encode()]))


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles of ``dist``, in increasing order.

    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
    or ``{"dist": "uniform", "min": a, "max": b}`` (integers, both ends in)."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(x) for x in u])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
        return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)
    if kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        return (lo + np.floor(u * (hi - lo + 1))).astype(np.int64)
    raise ValueError(f"unknown distribution {kind!r}")


def draw(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    return rng.permutation(quantiles(dist, n))


def blocks(dist: dict, n: int, block: int, rng: np.random.Generator) -> np.ndarray:
    """n sizes in blocks of ``block``: each whole block holds the ``block``
    quantiles of ``dist`` in an order of its own, so any run of a few blocks
    sees nearly the same mix of sizes whatever the seed."""
    q = quantiles(dist, block)
    return np.concatenate([rng.permutation(q) for _ in range(-(-n // block))])[:n]


def token_ids(rng: np.random.Generator, n: int, zipf_a: float, vocab: int,
              eos_id: int) -> np.ndarray:
    """n token ids from a Zipf law (exponent ``zipf_a``) over the
    vocabulary, with ``eos_id`` replaced by 0."""
    toks = np.minimum(rng.zipf(zipf_a, size=n) - 1, vocab - 1).astype(np.int32)
    toks[toks == eos_id] = 0
    return toks


def requests(traffic: dict, seed: int, vocab: int, eos_id: int) -> list[tuple[np.ndarray, int]]:
    """The ``backlog`` requests of a serving mix, as (prompt, output
    length): prompt and output lengths drawn from the mix's ``prompt`` and
    ``output`` distributions in blocks of ``block`` (``blocks``), token ids
    from ``token_ids``."""
    if traffic["arrivals"] != "backlog":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    rng = rng_for(seed, "requests")
    n, block = traffic["backlog"], traffic["block"]
    plens = blocks(traffic["prompt"], n, block, rng)
    outs = blocks(traffic["output"], n, block, rng)
    toks = token_ids(rng, int(plens.sum()), traffic["zipf_a"], vocab, eos_id)
    starts = np.concatenate([[0], np.cumsum(plens)[:-1]])
    return [(toks[s:s + p], int(o)) for s, p, o in zip(starts, plens, outs)]


def corpus(traffic: dict, seed: int, vocab: int, eos_id: int) -> np.ndarray:
    """Packed documents for ``corpus_steps`` steps of ``batch`` rows of
    ``seq_len + 1`` tokens: document lengths drawn from the mix's
    ``document`` distribution, each document ended by ``eos_id``, token ids
    drawn from a Zipf law (exponent ``zipf_a``) over the vocabulary."""
    rng = rng_for(seed, "corpus")
    total = traffic["corpus_steps"] * traffic["batch"] * traffic["seq_len"] + 1
    lengths = draw(traffic["document"], max(1, 4 * total // traffic["document"]["median"]), rng)
    ends = np.cumsum(lengths)
    ends = ends[ends < total]
    toks = token_ids(rng, total, traffic["zipf_a"], vocab, eos_id)
    toks[ends] = eos_id
    return toks
