"""Open-loop arrivals at a fixed rate (a Poisson process in its gaps).

Two phases, each with its own fixed multiset of sizes and gaps: a warm-up
of ``warmup_s`` seconds, then the measured window of ``seconds``. Every
request is due at its scheduled time whatever the server is doing, and
is timed from then.
"""

from __future__ import annotations

import numpy as np

import lengths


def schedule(mix: dict, vocab: int, seed: int, seconds: float) -> dict:
    """``{"kind": "open", "warmup_s": W, "jobs": [...]}``; each job has a
    ``due`` offset in seconds from the start of the warm-up and a
    ``phase`` ("warmup" or "window")."""
    rate = float(mix["rate_per_s"])
    warm = float(mix["warmup_s"])
    jobs = []
    for tag, (phase, start, span) in enumerate(
            (("warmup", 0.0, warm), ("window", warm, float(seconds)))):
        n = int(np.floor(rate * span))
        if n == 0:
            continue
        rng = lengths.rng_for(seed, tag)
        plens = rng.permutation(lengths.lognormal_sizes(n, mix["prompt"]))
        olens = rng.permutation(lengths.lognormal_sizes(n, mix["output"]))
        gaps = rng.permutation(lengths.exponential_gaps(n, rate))
        greedy = lengths.greedy_flags(n, mix.get("greedy_share", 1.0), rng)
        due = start + np.cumsum(gaps)
        for i in range(n):
            job = lengths.request(rng, vocab, int(plens[i]), int(olens[i]),
                                  greedy[i], mix.get("sampling", {}))
            job.update(phase=phase, due=float(due[i]))
            jobs.append(job)
    return {"kind": "open", "warmup_s": warm, "jobs": jobs}
