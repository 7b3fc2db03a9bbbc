"""The one general traffic generator. A traffic mix is a data file of
parameters (``benchmarks/traffic/<name>.json``); this module turns it and
``--seed`` into inputs. Adding a mix adds a file, never code.

Two kinds:

``token_batches``   training: ``batch`` x ``seq`` token ids a step, uniform
                    over the vocabulary, labels a second independent draw,
                    so every row differs and no step repeats.
``requests``        serving: a FIXED sequence of requests. The sizes are
                    ``set_size`` (prompt length, output length) pairs, the
                    quantiles of the two distributions paired by one fixed
                    permutation, and they come in one fixed order, lap after
                    lap. ``--seed`` draws the token ids (and the weights),
                    never a size, the order or an arrival time: on the chip
                    the order alone moved a 51 s window's tokens per second
                    by 1.7% from seed to seed (PERF.md, PR 25), so a seed
                    that ordered the set changed the work. Every request is
                    greedy: ``correct`` compares greedy tokens, and a mix
                    that samples brings the field with its cell. ``arrival``
                    is ``closed`` (``clients`` callers, each sending its
                    next request when the last one ends) or ``poisson``
                    (``rate_per_s``, open loop, each request timed from when
                    it was due).
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def token_batches(params: dict, seed: int, vocab: int):
    """Endless ``(ids, labels)`` int32 batches of the mix."""
    rng = _rng(seed, 1)
    shape = (int(params["batch"]), int(params["seq"]))
    while True:
        yield (rng.integers(0, vocab, shape, dtype=np.int32),
               rng.integers(0, vocab, shape, dtype=np.int32))


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of ``dist``, clipped to its range."""
    if dist["dist"] == "lognormal":
        nd = NormalDist(math.log(dist["median"]), dist["sigma"])
        x = np.exp([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    elif dist["dist"] == "uniform":
        x = np.linspace(dist["min"], dist["max"], n)
    elif dist["dist"] == "fixed":
        x = np.full(n, dist["value"], float)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)


def size_set(params: dict) -> np.ndarray:
    """The mix's fixed ``[set_size, 2]`` array of (prompt, output) lengths."""
    n = int(params["set_size"])
    prompts = _quantiles(params["prompt"], n)
    outputs = _quantiles(params["output"], n)
    pair = np.random.default_rng(0)  # the pairing is part of the generator
    return np.stack([prompts, outputs[pair.permutation(n)]], axis=1)


class RequestSource:
    """Hands out the mix's requests in the mix's own order, cycling through
    the set as often as the run needs, with token ids from the seed.
    ``next()`` is called under the caller's lock; request ``k`` is the same
    whatever thread asks for it."""

    def __init__(self, params: dict, seed: int, vocab: int) -> None:
        self.params = params
        self.sizes = size_set(params)
        self.seed = int(seed)
        self.vocab = int(vocab)
        self._order = np.empty((0,), int)
        self._k = 0

    def next(self) -> dict:
        k = self._k
        self._k += 1
        n = len(self.sizes)
        while k >= len(self._order):
            lap = len(self._order) // n
            self._order = np.concatenate(
                [self._order, _rng(0, 100 + lap).permutation(n)])
        plen, olen = (int(x) for x in self.sizes[self._order[k]])
        ids = _rng(self.seed, 10_000 + k).integers(0, self.vocab, plen)
        return {"k": k, "prompt": ids.tolist(), "max_tokens": olen}


def poisson_due_times(params: dict, horizon_s: float) -> np.ndarray:
    """Due times (seconds from the schedule's start) of an open loop: one
    fixed draw, the same for every seed."""
    rate = float(params["rate_per_s"])
    n = int(rate * horizon_s * 1.5) + 16
    return np.cumsum(_rng(0, 2).exponential(1.0 / rate, n))
