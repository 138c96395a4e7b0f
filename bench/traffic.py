"""One generator for every traffic mix: reads ``bench/traffic/<mix>.json``.

A mix states its loop (``closed``: one client per slot, each sends its
next request when the last one finished; ``open``: arrivals on a schedule,
whatever the server does), its length distributions and, for an open
loop, its rate. Every seed gets the same multiset of sizes and of
inter-arrival gaps — the distribution's quantiles at ``(i + 1/2) / pool``
— in an order of its own, so seeds change the order of the work and not
its amount. Token ids are uniform over the vocabulary, from the seed.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np

__all__ = ["Spec", "arrival_gaps", "due_times", "quantile_sizes", "specs"]


@dataclasses.dataclass
class Spec:
    index: int
    prompt: np.ndarray
    max_new: int


def quantile_sizes(dist: dict, n: int) -> np.ndarray:
    """``n`` sizes at the quantiles ``(i + 1/2) / n`` of a clipped
    lognormal ``{median, sigma, min, max}``."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    sizes = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(sizes), dist["min"], dist["max"]).astype(np.int64)


def arrival_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at their quantiles (seconds)."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


def specs(mix: dict, seed: int, vocab: int) -> Iterator[Spec]:
    """Requests in the order this seed sends them, without end: each pass
    over the pool is a fresh permutation of the same sizes."""
    n = mix["pool"]
    prompts = quantile_sizes(mix["prompt_len"], n)
    outputs = quantile_sizes(mix["output_len"], n)
    order_rng, tok_rng = _rng(seed, 1), _rng(seed, 2)
    i = 0
    while True:
        pi, oi = order_rng.permutation(n), order_rng.permutation(n)
        for a, b in zip(pi, oi):
            yield Spec(i, tok_rng.integers(0, vocab, prompts[a]).astype(
                np.int32), int(outputs[b]))
            i += 1


def due_times(mix: dict, seed: int, count: int,
              rate: Optional[float] = None) -> np.ndarray:
    """Due times (seconds from the loop's start) of the first ``count``
    open-loop arrivals: the pool's gaps, permuted per pass by the seed."""
    rate = rate if rate is not None else mix["rate_per_s"]
    if not rate or rate <= 0:
        raise ValueError(f"traffic {mix['name']!r} states no arrival rate")
    gaps = arrival_gaps(rate, mix["pool"])
    rng = _rng(seed, 3)
    out: List[float] = []
    while len(out) < count:
        out.extend(gaps[rng.permutation(len(gaps))])
    return np.cumsum(out[:count])
