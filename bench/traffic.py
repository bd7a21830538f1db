"""The one traffic generator: reads a mix's parameters, makes its requests.

The sequence of request sizes is fixed by the mix file alone: ``pool``
prompt lengths and ``pool`` output lengths at evenly spaced quantiles of
their clipped lognormal distributions, paired by a permutation drawn from
the file's ``pairing_seed`` and sent in orders drawn from its
``order_seed`` (a new order each time the pool is used up).  The run's
``--seed`` draws the prompt tokens (and the weights), never a size, so
every seed offers the same work: a closed loop whose window covers about
one pass of the pool would otherwise see a different share of it on each
seed.  (The heavy-tailed lengths follow the generator of
``benchmarks/serving_engine.py``.)
"""
from __future__ import annotations

import statistics

import numpy as np


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 1/2)/n of a lognormal with the
    given median and sigma, rounded and clipped to [min, max]."""
    if dist.get("dist", "lognormal") != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                  for i in range(n)])
    x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


class ClosedLoopTraffic:
    """Requests of one serving mix: sizes in the mix's order, tokens from
    the seed."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        if mix.get("loop") != "closed":
            raise ValueError(f"unsupported loop {mix.get('loop')!r}")
        if mix.get("tokens", "uniform") != "uniform":
            raise ValueError(f"unknown token law {mix['tokens']!r}")
        n = int(mix["pool"])
        prompts = quantile_lengths(mix["prompt"], n)
        outputs = quantile_lengths(mix["output"], n)
        pairing = np.random.default_rng(int(mix["pairing_seed"])).permutation(n)
        self.pool = list(zip(prompts.tolist(), outputs[pairing].tolist()))
        if max(p + o for p, o in self.pool) > mix["max_len"]:
            raise ValueError("a pooled request exceeds max_len")
        if min(o for _, o in self.pool) < 2:
            raise ValueError("outputs must be at least 2 tokens")
        self.mix = mix
        self.vocab = int(vocab)
        self._rng = np.random.default_rng([int(seed), 0x7AFF1C])
        self._order_rng = np.random.default_rng(int(mix["order_seed"]))
        self._order: list[int] = []

    def next(self) -> tuple[np.ndarray, int]:
        """(prompt tokens int32 [p], output tokens) of the next request."""
        if not self._order:
            self._order = self._order_rng.permutation(len(self.pool)).tolist()
        p, o = self.pool[self._order.pop()]
        tokens = self._rng.integers(0, self.vocab, p, dtype=np.int32)
        return tokens, o

    def buckets(self) -> list[int]:
        """Prefill lengths the engine pads this mix's prompts to: the
        next power of two, clamped to ``max_len``."""
        return sorted({min(self.mix["max_len"], next_pow2(p))
                       for p, _ in self.pool})
