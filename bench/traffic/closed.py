"""Closed-loop clients: each sends its next request when the last one
completes. The window opens once every client has its first token.

Which requests finish inside the window, and so how many prompts are
prefilled there, depends on each client's sequence of sizes, not only on
the multiset of sizes. So every seed runs the same client scripts: the
mix's sizes are paired and dealt into ``clients`` scripts once, by a
fixed layout, and the seed decides which client runs which script (the
order in which the first requests reach the server) and draws every
token id. Two seeds then do the same work in the window.
"""

from __future__ import annotations

import lengths

LAYOUT_SEED = 0  # pairs the sizes and deals them into scripts, for every seed


def scripts(mix: dict) -> list:
    """The ``clients`` scripts: each a list of (prompt length, output
    length, greedy) in the order its client sends them."""
    clients = int(mix["clients"])
    n = clients * int(mix["requests_per_client"])
    layout = lengths.rng_for(LAYOUT_SEED, 0)
    plens = layout.permutation(lengths.lognormal_sizes(n, mix["prompt"]))
    olens = layout.permutation(lengths.lognormal_sizes(n, mix["output"]))
    greedy = lengths.greedy_flags(n, mix.get("greedy_share", 1.0), layout)
    return [[(int(plens[i]), int(olens[i]), bool(greedy[i]))
             for i in range(c, n, clients)] for c in range(clients)]


def schedule(mix: dict, vocab: int, seed: int, seconds: float) -> dict:
    """``{"kind": "closed", "clients": [[job, ...], ...]}``: each client's
    requests in the order it sends them."""
    plan = scripts(mix)
    rng = lengths.rng_for(seed, 0)
    out = []
    for c, s in enumerate(rng.permutation(len(plan))):
        jobs = []
        for plen, olen, greedy in plan[s]:
            job = lengths.request(rng, vocab, plen, olen, greedy,
                                  mix.get("sampling", {}))
            job.update(phase="window", client=c)
            jobs.append(job)
        out.append(jobs)
    return {"kind": "closed", "clients": out}
