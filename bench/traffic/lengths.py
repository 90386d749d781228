"""Sizes and gaps shared by the arrival generators.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps (evenly spaced quantiles of the stated distribution);
the seed only shuffles their order and draws the token ids. So two seeds
do the same amount of work, and a metric's spread over seeds is the
system's, not the sampler's.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile_points(n: int) -> list:
    """The ``n`` evenly spaced probabilities (i + 0.5) / n."""
    return [(i + 0.5) / n for i in range(n)]


def lognormal_sizes(n: int, spec: dict) -> list:
    """``n`` lengths at evenly spaced quantiles of a lognormal with the
    given ``median`` and ``sigma``, clipped to [``min``, ``max``]."""
    nd = NormalDist()
    out = []
    for u in quantile_points(n):
        x = spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(u))
        out.append(int(min(spec["max"], max(spec["min"], round(x)))))
    return out


def exponential_gaps(n: int, rate: float) -> list:
    """``n`` gaps at evenly spaced quantiles of an exponential of mean
    1 / ``rate``, rescaled so that they sum to exactly n / rate."""
    raw = [-math.log(1.0 - u) for u in quantile_points(n)]
    k = n / rate / sum(raw)
    return [g * k for g in raw]


def rng_for(seed: int, *tags: int) -> np.random.Generator:
    """One independent stream per (seed, tags): any whole-number seed."""
    return np.random.default_rng([seed, *tags])


def request(rng: np.random.Generator, vocab: int, plen: int, olen: int,
            greedy: bool, sampling: dict) -> dict:
    """One request's body fields: random token ids and its sampling."""
    prompt = rng.integers(0, vocab, plen).astype(int).tolist()
    temp = 0.0 if greedy else float(sampling.get("temperature", 0.0))
    return {"prompt": prompt, "max_tokens": int(olen),
            "temperature": temp,
            "top_p": 1.0 if greedy else float(sampling.get("top_p", 1.0)),
            "seed": int(rng.integers(0, 2**31 - 1)), "greedy": temp == 0.0}


def greedy_flags(n: int, share: float, rng: np.random.Generator) -> list:
    """Exactly round(share * n) greedy requests, at shuffled places."""
    k = int(round(share * n))
    flags = np.zeros(n, bool)
    flags[:k] = True
    return rng.permutation(flags).tolist()


def max_seq_len(mix: dict) -> int:
    """The longest prompt plus the longest output the mix can send."""
    return int(mix["prompt"]["max"] + mix["output"]["max"])
