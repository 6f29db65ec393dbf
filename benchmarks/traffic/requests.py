"""Serving traffic: an open loop of requests, fixed before the run starts.

Parameters (a traffic file with `"generator": "requests"`):

- `rate_per_s`: offered load. `n = round(rate * seconds)` requests are all
  due inside the window.
- `prompt_len`, `output_len`: `{"median", "sigma", "min", "max"}` of a
  lognormal, clipped.
- `shuffle_block` (optional): how far the seed moves a gap or a length.
- `greedy`: every request decodes greedily (the engine has no sampler).

The trace is fixed and stratified, not drawn: every seed gets the same
multiset of gaps (an exponential distribution's quantiles at (i + 0.5) / n,
so Poisson-spaced at exactly `rate_per_s`) and of lengths (the lognormals'
quantiles), and its own tokens. The multiset is laid out once in a canonical
order (drawn from a constant); the seed then shuffles it within consecutive
blocks of `shuffle_block` requests (default: all of it). So the work of a run
does not change with the seed, and with a small block neither does the load
over time: the seed changes the tokens and which neighbours swap places.
PERF.md gives the spread under a full shuffle beside the committed block's."""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Request:
    """What the engine's `run` reads of a request (tpudml.serve.load.Request
    has the same fields; kept here so the yardstick owns its traffic)."""

    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    arrival_time: float


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, dist: dict) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    lengths = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.round(lengths), dist["min"], dist["max"]).astype(np.int64)


def arrival_gaps(n: int, rate: float) -> np.ndarray:
    gaps = -np.log1p(-_quantiles(n))  # an exponential distribution's quantiles
    return gaps * (n / rate) / gaps.sum()  # mean gap exactly 1 / rate


CANONICAL = 0xBE11C4  # the constant the canonical order is drawn from


def _order(values: np.ndarray, canonical, rng, block: int) -> np.ndarray:
    """``values`` in the canonical order, then shuffled by ``rng`` within
    consecutive blocks of ``block``."""
    values = canonical.permutation(values)
    for lo in range(0, len(values), block):
        values[lo:lo + block] = rng.permutation(values[lo:lo + block])
    return values


def make(params: dict, config: dict, seed: int, seconds: float) -> list[Request]:
    n = max(1, round(params["rate_per_s"] * seconds))
    rng = np.random.default_rng(seed)
    canonical = np.random.default_rng(CANONICAL)
    block = params.get("shuffle_block") or n
    gaps = _order(arrival_gaps(n, params["rate_per_s"]), canonical, rng, block)
    # The first request is due half a gap in, the last half a gap before the end.
    arrivals = np.cumsum(gaps) - gaps[0] / 2
    arrivals *= min(1.0, seconds * (1 - 0.5 / n) / arrivals[-1])
    prompts = _order(lognormal_lengths(n, params["prompt_len"]), canonical, rng, block)
    outputs = _order(lognormal_lengths(n, params["output_len"]), canonical, rng, block)
    vocab = config["vocab_size"]
    return [Request(rid=i, prompt=rng.integers(0, vocab, int(prompts[i])).astype(np.int32),
                    max_new_tokens=int(outputs[i]), arrival_time=float(arrivals[i]))
            for i in range(n)]
