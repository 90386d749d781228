"""The comparison that decides ``correct``: served tokens against the plain
reference, on a sample of what the measured window served.

For each sampled request the reference runs once over its prompt and the
tokens it was served, and reads the logits at every served position.
Two numbers are compared with the configuration's limits:

* ``max_logit_gap`` — over greedy requests, the widest gap by which a
  served token's reference logit lies below the reference's best logit
  at that position (0 where the served token is the reference argmax);
* ``max_logprob_err`` — over every sampled request, the largest
  difference between the log-probability the server streamed with a
  token and the reference's log-softmax of that token (the server's
  logprobs are of the raw, untempered distribution).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def served(rec) -> bool:
    return rec.status == 200 and bool(rec.tokens)


def sample(records, seed: int, max_requests: int = 6,
           target_tokens: int = 300) -> list:
    """The greedy request with the most served tokens, then others drawn
    from the seed until ``target_tokens`` served tokens or
    ``max_requests`` requests. Requests still in flight at the close
    count with the tokens they were served by then."""
    pool = [r for r in records if served(r)]
    greedy = [r for r in pool if r.job["greedy"]]
    if not greedy:
        return []
    first = max(greedy, key=lambda r: len(r.tokens))
    rest = [r for r in pool if r is not first]
    order = np.random.default_rng([seed, 99]).permutation(len(rest))
    out, n = [first], len(first.tokens)
    for i in order:
        if len(out) >= max_requests or n >= target_tokens:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def _worst(x) -> float:
    """The largest value, or infinity where any value is not finite (a
    NaN must fail the comparison, not drop out of a max)."""
    x = np.asarray(x, np.float64)
    return float(np.max(x)) if np.all(np.isfinite(x)) else float("inf")


@jax.jit
def _score(ref, toks, ctl):
    """Per served position: the reference's gap below its best logit, its
    log-softmax of the served token, the same gap for the control's own
    first choice, and the control's log-softmax of the served token."""
    best = jnp.max(ref, axis=-1)
    at = lambda x, t: jnp.take_along_axis(x, t[:, None], axis=-1)[:, 0]
    lp_ref = at(jax.nn.log_softmax(ref, axis=-1), toks)
    ctl_top = jnp.argmax(ctl, axis=-1)
    lp_ctl = at(jax.nn.log_softmax(ctl, axis=-1), toks)
    return best - at(ref, toks), lp_ref, best - at(ref, ctl_top), lp_ctl


def compare(ref_mod, weights, cfg: dict, recs, max_len: int, max_rows: int,
            control: bool = False) -> dict:
    """Run the reference (and with ``control`` the fp8 control) over each
    sampled request. Returns the compared numbers, and with ``control``
    the control's readings of the same two numbers."""
    gap, lp_err, ctl_gap, ctl_lp_err, tokens = 0.0, 0.0, 0.0, 0.0, 0
    for rec in recs:
        prompt, toks = rec.job["prompt"], rec.tokens
        n = len(toks)
        seq = np.zeros(max_len, np.int32)
        full = prompt + toks[:-1]
        seq[:len(full)] = full
        rows = np.zeros(max_rows, np.int32)
        rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        seq, rows_d = jnp.asarray(seq), jnp.asarray(rows)
        ref = ref_mod.logits_at(weights, cfg, seq, rows_d)
        ctl = ref_mod.logits_at(weights, cfg, seq, rows_d, fp8=True) \
            if control else ref
        tk = np.zeros(max_rows, np.int32)
        tk[:n] = toks
        g, lpr, cg, lpc = (np.asarray(a)[:n] for a in
                           _score(ref, jnp.asarray(tk), ctl))
        del ref, ctl
        lps = np.asarray(rec.logprobs, np.float64)
        tokens += n
        lp_err = max(lp_err, _worst(np.abs(lps - lpr)))
        if control:
            ctl_lp_err = max(ctl_lp_err, _worst(np.abs(lpc - lpr)))
        if rec.job["greedy"]:
            gap = max(gap, _worst(g))
            if control:
                ctl_gap = max(ctl_gap, _worst(cg))
    out = {"max_logit_gap": gap, "max_logprob_err": lp_err,
           "requests": len(recs), "tokens": tokens}
    if control:
        out["control"] = {"max_logit_gap": ctl_gap,
                          "max_logprob_err": ctl_lp_err}
    return out
