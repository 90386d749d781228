"""Differential fuzzing of the continuous-batching scheduler: seeded random
admission / abort / preempt / swap schedules, driven through every tick mode
with speculation off and on, must emit greedy streams BIT-IDENTICAL to the
per-request ``Engine.generate`` oracle — whatever the schedule interleaving.

Each (tick_mode, speculate_k) config reuses ONE scheduler instance across
schedules so the jitted tick functions compile once; the pool must drain to
zero pages between schedules (leak check rides along for free). A failing
schedule is SHRUNK — jobs dropped one at a time while the failure
reproduces — so the assertion message carries a minimal repro, not a
20-request haystack.

Every config — packed included — is held to the per-request Engine oracle.
The packed tick historically could NOT be (PR6): the varlen flat-batch
kernel attended a decode token's OWN key as fresh f32 where the
Engine/chunked/verify paths read it int8-quantized from the cache, and a
near-tie in the top-2 logits flipped the argmax. The scheduler now marks
the packed buffer's decode rows in a ``quant_fresh`` mask and the packed
step routes those rows' fresh k/v through the int8 round trip
(``codes.astype(f32) * scale`` — the exact dequantized values a
sequential decode step reads back from the pool), which restores the
bit-identity and retired the solo-run invariance oracle this file used
to carry for packed configs.

Tier-1 runs a small schedule count; ``-m slow`` scales the same walk past
200 schedules (the CI slow job).
"""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.transformer import RuntimeOpts, init_params
from repro.serving.engine import Engine
from repro.serving.scheduler import Scheduler

OPTS_Q = RuntimeOpts(q_chunk=16, kv_chunk=16, remat=False, quantized_kv=True,
                     moe_capacity_factor=0.0)
CONFIGS = [(mode, k) for mode in ("packed", "chunked", "wave")
           for k in (0, 2)]
MAX_TICKS = 400


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama2-7b").tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def oracle(tiny_model):
    """Per-request greedy Engine reference, memoized across schedules."""
    cfg, params = tiny_model
    eng = Engine(cfg, params, OPTS_Q, cache_len=64)
    cache = {}

    def get(prompt, max_new):
        key = (prompt.tobytes(), len(prompt), max_new)
        if key not in cache:
            cache[key] = eng.generate(prompt[None], max_new).tokens[0]
        return cache[key]

    return get


def _random_schedule(rng, vocab):
    """One schedule: jobs with staggered submit ticks, occasional aborts,
    and a mix of repetitive prompts (prompt-lookup drafting has signal →
    acceptance > 0) and random prompts (drafts mostly rejected →
    rollback exercised)."""
    jobs = []
    for _ in range(int(rng.integers(2, 6))):
        if rng.random() < 0.5:
            base = rng.integers(0, vocab, (int(rng.integers(2, 4)),))
            prompt = np.tile(base, 5)[: int(rng.integers(3, 11))]
        else:
            prompt = rng.integers(0, vocab, (int(rng.integers(2, 13)),))
        jobs.append({
            "prompt": prompt.astype(np.int32),
            "max_new": int(rng.integers(1, 9)),
            "submit_at": int(rng.integers(0, 4)),
            "abort_at": int(rng.integers(1, 12))
            if rng.random() < 0.25 else None,
        })
    return jobs


def _drive(sched, jobs):
    """Play one schedule: submit jobs at their ticks, abort on cue, step to
    drain. Returns {job_index: rid}."""
    rids = {}
    tick = 0
    while True:
        for j, job in enumerate(jobs):
            if j not in rids and job["submit_at"] <= tick:
                rids[j] = sched.submit(job["prompt"], job["max_new"])
            if (job["abort_at"] == tick and j in rids):
                sched.abort(rids[j])
        if sched.pending:
            sched.step()
        elif len(rids) == len(jobs):
            break
        tick += 1
        assert tick < MAX_TICKS, "schedule failed to drain"
    return rids


def _check_schedule(sched, oracle, jobs):
    """Drive one schedule and return a list of mismatch descriptions
    (empty = the schedule round-trips bit-exactly)."""
    rids = _drive(sched, jobs)
    events = sched.drain_events()
    problems = []
    seen = {}
    for rid, idx, tok, lp, _ in events:
        if idx != seen.get(rid, -1) + 1:
            problems.append(f"rid {rid}: event index {idx} after "
                            f"{seen.get(rid, -1)}")
        seen[rid] = idx
        assert np.isfinite(lp)
    for j, job in enumerate(jobs):
        rid = rids[j]
        got = sched.results[rid]
        reason = sched.finish_reasons[rid]
        want = oracle(job["prompt"], job["max_new"])
        if reason == "abort":
            if not np.array_equal(got, want[: len(got)]):
                problems.append(f"job {j} (abort): partial stream is not "
                                f"a prefix of the oracle stream")
        elif not np.array_equal(got, want):
            d = next((i for i in range(min(len(got), len(want)))
                      if got[i] != want[i]), min(len(got), len(want)))
            problems.append(
                f"job {j}: diverged from the oracle at token {d} "
                f"(prompt_len={len(job['prompt'])}, "
                f"max_new={job['max_new']}): {got[d:d + 3]} vs "
                f"{want[d:d + 3]}")
    if sched.pool.pages_in_use != 0:
        problems.append(f"pool leaked {sched.pool.pages_in_use} pages")
    return problems


def _shrink(make_sched, oracle, jobs):
    """Greedy delta-debugging: drop jobs one at a time while the failure
    still reproduces on a FRESH scheduler."""
    cur = list(jobs)
    changed = True
    while changed and len(cur) > 1:
        changed = False
        for i in range(len(cur)):
            trial = cur[:i] + cur[i + 1:]
            if _check_schedule(make_sched(), oracle, trial):
                cur = trial
                changed = True
                break
    return cur


def _fuzz(tiny_model, oracle, mode, k, n_schedules, seed=0):
    cfg, params = tiny_model

    def make_sched():
        # lazy growth + a tight pool: concurrent load forces the
        # preempt → swap → resume path to fire inside the schedules
        return Scheduler(cfg, params, OPTS_Q, num_pages=24, page_size=4,
                         max_slots=3, tick_mode=mode, speculate_k=k,
                         lazy_growth=True)

    sched = make_sched()
    rng = np.random.default_rng(seed)
    for n in range(n_schedules):
        jobs = _random_schedule(rng, cfg.vocab_size)
        problems = _check_schedule(sched, oracle, jobs)
        if problems:
            minimal = _shrink(make_sched, oracle, jobs)
            spec = [(list(map(int, j["prompt"])), j["max_new"],
                     j["submit_at"], j["abort_at"]) for j in minimal]
            pytest.fail(
                f"{mode} speculate_k={k} schedule {n}: {problems}\n"
                f"minimal repro (prompt, max_new, submit_at, abort_at): "
                f"{spec}")
    assert sched.stats.aborted + sched.stats.preemptions > 0 or \
        sched.stats.evicted > 0
    if k:
        assert sched.stats.spec_rounds > 0


@pytest.mark.parametrize("mode,k", CONFIGS,
                         ids=[f"{m}-k{k}" for m, k in CONFIGS])
def test_fuzz_schedules_match_engine(tiny_model, oracle, mode, k):
    """Tier-1: a handful of randomized schedules per config — every
    non-aborted request's greedy stream equals the per-request Engine
    oracle's, aborted ones are exact prefixes, events arrive in index
    order, the pool drains clean."""
    _fuzz(tiny_model, oracle, mode, k, n_schedules=3)


@pytest.mark.slow
@pytest.mark.parametrize("mode,k", CONFIGS,
                         ids=[f"{m}-k{k}" for m, k in CONFIGS])
def test_fuzz_schedules_match_engine_deep(tiny_model, oracle, mode, k):
    """The CI slow job: the same walk, 35 schedules per config — 210
    schedules across the grid, all bit-exact."""
    _fuzz(tiny_model, oracle, mode, k, n_schedules=35, seed=1000)
