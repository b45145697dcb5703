"""The one traffic generator. A traffic mix is a data file of parameters;
everything drawn from it is a function of (file, seed).

Distributions (`{"dist": ...}`):
  uniform     {"lo": a, "hi": b}            integers in [a, b]
  loguniform  {"lo": a, "hi": b}            integers, log-uniform in [a, b]
A length distribution may carry `"strata": k`: consecutive draws then come
in shuffled blocks of k, one from each k-quantile, so that any run of a few
dozen requests sees nearly the same mix of lengths whatever the seed
(steadier medians; the marginal distribution is unchanged).

Token distributions (`{"dist": ...}` over `[first, vocab)`):
  uniform     every id equally likely
  zipf        {"s": 1.0}: p(rank r) ~ 1 / r**s, ranks permuted by the seed,
              so a model can learn the unigram frequencies
"""
from __future__ import annotations

import numpy as np


def _quantile(spec: dict, u: np.ndarray) -> np.ndarray:
    dist = spec["dist"]
    lo, hi = float(spec["lo"]), float(spec["hi"])
    if dist == "uniform":
        x = lo + u * (hi + 1 - lo)
    elif dist == "loguniform":
        x = np.exp(np.log(lo) + u * (np.log(hi + 1) - np.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


class Lengths:
    """An endless seeded stream of lengths from one distribution."""

    def __init__(self, spec: dict, rng: np.random.Generator):
        self.spec, self.rng = spec, rng
        self.strata = int(spec.get("strata", 1))
        self._block: list = []

    def next(self) -> int:
        if not self._block:
            k = self.strata
            u = (self.rng.permutation(k) + self.rng.random(k)) / k
            self._block = list(_quantile(self.spec, u))
        return int(self._block.pop())


class Tokens:
    """Seeded token ids over `[first, vocab)`."""

    def __init__(self, spec: dict, vocab: int, rng: np.random.Generator,
                 first: int = 0):
        self.rng, self.first, self.n = rng, first, vocab - first
        self.cdf = None
        if spec["dist"] == "zipf":
            p = 1.0 / np.arange(1, self.n + 1) ** float(spec.get("s", 1.0))
            self.cdf = np.cumsum(p / p.sum())
            # which id holds which rank is the seed's choice
            self.ids = rng.permutation(self.n)
        elif spec["dist"] != "uniform":
            raise ValueError(f"unknown token distribution {spec['dist']!r}")

    def draw(self, shape) -> np.ndarray:
        if self.cdf is None:
            out = self.rng.integers(0, self.n, shape)
        else:
            ranks = np.searchsorted(self.cdf, self.rng.random(shape))
            out = self.ids[np.minimum(ranks, self.n - 1)]
        return (out + self.first).astype(np.int32)


def request_stream(traffic: dict, vocab: int, seed: int):
    """Endless (prompt ids, output length) pairs: the requests of a mix in
    the order they are sent, whichever client sends them, so that the
    lengths' strata hold over the whole load and not client by client.
    Prompts are distinct random ids, so nothing is shared between
    requests unless the mix says so."""
    rng = np.random.default_rng([seed, 1000])
    prompts = Lengths(traffic["prompt_tokens"], rng)
    outputs = Lengths(traffic["output_tokens"], rng)
    toks = Tokens(traffic.get("prompt_ids", {"dist": "uniform"}), vocab,
                  rng, first=1)
    while True:
        yield toks.draw((prompts.next(),)), outputs.next()


def check_requests(traffic: dict, vocab: int, seed: int, n: int) -> list:
    """The `n` requests a serve job compares with the reference before its
    window: lengths at the middle of each n-quantile of the mix (the same in
    every run, so that set-up is as long whatever the seed), ids from the
    seed. Outputs are cut to `check_output_tokens` where the mix says so."""
    rng = np.random.default_rng([seed, 999])
    u = (np.arange(n) + 0.5) / n
    prompts = _quantile(traffic["prompt_tokens"], u)
    outputs = _quantile(traffic["output_tokens"], u[::-1])
    cap = int(traffic.get("check_output_tokens", outputs.max()))
    toks = Tokens(traffic.get("prompt_ids", {"dist": "uniform"}), vocab,
                  rng, first=1)
    return [(toks.draw((int(p),)), int(min(o, cap)))
            for p, o in zip(prompts, outputs)]


def training_samples(traffic: dict, vocab: int, seed: int):
    """Endless (input ids [S], labels [S]) samples: S + 1 seeded tokens,
    the labels being the inputs shifted by one."""
    rng = np.random.default_rng([seed, 7])
    toks = Tokens(traffic["token_ids"], vocab, rng)
    seq = int(traffic["sequence_length"])
    while True:
        row = toks.draw((seq + 1,))
        yield row[:-1], row[1:]
