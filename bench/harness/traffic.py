"""One general generator of open-loop traffic from a traffic file.

A traffic file (``bench/traffic/<name>.json``) gives the mix: the arrival
process, the prompt-length distribution and the answer length.  The rate
is the cell's (``bench/cells/<cell>.json``), since one mix runs at a
different rate on each deployment.

Every seed of a cell gets the same work: the same set of prompt lengths
and the same set of inter-arrival gaps, each drawn as evenly spaced
quantiles of its distribution, in an order shuffled by the seed; the
tokens themselves are drawn from the seed.  So the seed changes the order
and the content of the work, not its amount.  The order is balanced: the
sorted set is cut into ``STRATA`` strata, and every round of ``STRATA``
consecutive requests takes one from each, so any stretch of the run (the
requests a window above the knee gets to, say) holds about the same mix.

The length and arrival laws follow ``repro.launch.serve_async``'s
``sample_lengths``/``poisson_arrivals``, and the tokens its
``repro.data.bigram_lm``; they are re-implemented here so that a change to
the program cannot change the yardstick.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Request:
    arrival: float          # seconds after the first arrival
    prompt: np.ndarray      # int32 [length]


STRATA = 8


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def balanced_order(ascending: np.ndarray, rng: np.random.Generator,
                   strata: int = STRATA) -> np.ndarray:
    """``ascending`` in a seeded order in which each round of ``strata``
    consecutive items holds one item of each stratum (each run of
    ``len / strata`` neighbours in sorted order), in shuffled order
    within the round."""
    n = len(ascending)
    k = max(min(strata, n), 1)
    groups = [list(rng.permutation(ascending[(i * n) // k:((i + 1) * n) // k]))
              for i in range(k)]
    out = []
    while any(groups):
        for g in rng.permutation(k):
            if groups[g]:
                out.append(groups[g].pop())
    return np.asarray(out, dtype=np.asarray(ascending).dtype)


def prompt_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` prompt lengths: lognormal quantiles with the given median and
    sigma, clipped to ``[min, max]``, in balanced order."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown prompt length law {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(float(q)) for q in _quantiles(n)])
    lens = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    lens = np.clip(np.rint(lens), spec["min"], spec["max"]).astype(np.int64)
    return balanced_order(lens, rng)


def arrival_times(spec: dict, rate: float, n: int,
                  rng: np.random.Generator) -> np.ndarray:
    """``n`` arrival times starting at 0: a Poisson process's gaps
    (exponential quantiles at ``rate``) in balanced order."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    gaps = balanced_order(-np.log1p(-_quantiles(n - 1)) / rate, rng)
    return np.concatenate([[0.0], np.cumsum(gaps)])


def bigram_tokens(lengths: np.ndarray, vocab: int, seed: int,
                  branching: int = 4, trigram_frac: float = 0.3
                  ) -> List[np.ndarray]:
    """Token sequences from a sparse bigram table with hashed trigram
    exceptions (each token has ``branching`` uniform successors; with
    probability ``trigram_frac`` the successor is a seeded hash of the
    two previous tokens instead)."""
    rng = np.random.default_rng(seed)
    bigram = rng.integers(0, vocab, size=(vocab, branching))
    a, b, c = (int(x) for x in rng.integers(1, vocab, size=3))
    n, width = len(lengths), int(max(lengths))
    out = np.empty((n, width), np.int64)
    tok = rng.integers(0, vocab, size=n)
    prev = rng.integers(0, vocab, size=n)
    for t in range(width):
        out[:, t] = tok
        tri = rng.random(n) < trigram_frac
        nxt = np.where(tri, (prev * a + tok * b + c) % vocab,
                       bigram[tok, rng.integers(0, branching, size=n)])
        prev, tok = tok, nxt
    return [out[i, :int(k)].astype(np.int32) for i, k in enumerate(lengths)]


def generate(traffic: dict, rate: float, horizon_s: float, vocab: int,
             seed: int) -> List[Request]:
    """Every request of one run: arrivals from 0 up to ``horizon_s`` at
    ``rate`` per second, in arrival order."""
    n = max(int(math.ceil(rate * horizon_s)), 1)
    rng = np.random.default_rng([seed, 1])
    lengths = prompt_lengths(traffic["prompt"], n, rng)
    arrivals = arrival_times(traffic["arrival"], rate, n, rng)
    prompts = bigram_tokens(lengths, vocab, int(rng.integers(2**63 - 1)))
    return [Request(float(t), p) for t, p in zip(arrivals, prompts)]
