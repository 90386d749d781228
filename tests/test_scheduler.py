"""Continuous-batching scheduler: greedy parity with per-request
``Engine.generate`` while ragged requests are admitted and evicted
mid-stream from ONE shared pool; queueing/backpressure; EOS eviction; full
pool reclamation after drain."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.sampling import SamplingParams
from repro.models.transformer import RuntimeOpts, init_params
from repro.serving.engine import Engine
from repro.serving.scheduler import Scheduler

OPTS_Q = RuntimeOpts(q_chunk=16, kv_chunk=16, remat=False, quantized_kv=True,
                     moe_capacity_factor=0.0)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama2-7b").tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_scheduler_matches_engine_with_midstream_admission(tiny_model):
    """Acceptance: 5 ragged requests through 3 slots — mid-stream admission
    and eviction, a single shared pool — must produce IDENTICAL greedy
    tokens to the per-request Engine over the same quantized-cache setup."""
    cfg, params = tiny_model
    rng = np.random.default_rng(0)
    jobs = [(5, 6), (8, 3), (3, 9), (6, 4), (2, 7)]  # (prompt_len, max_new)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)) for n, _ in jobs]

    sched = Scheduler(cfg, params, OPTS_Q, num_pages=24, page_size=4,
                      max_slots=3)
    rids = [sched.submit(p, mn) for p, (_, mn) in zip(prompts, jobs)]
    results = sched.run()

    assert sched.stats.admitted == 5 and sched.stats.evicted == 5
    assert sched.stats.prefills >= 2  # queue drained in waves, not one batch
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    for rid, p, (_, mn) in zip(rids, prompts, jobs):
        want = eng.generate(p[None], mn).tokens[0]
        np.testing.assert_array_equal(results[rid], want)


def test_scheduler_pool_fully_reclaimed(tiny_model):
    cfg, params = tiny_model
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=16, page_size=4,
                      max_slots=2)
    rng = np.random.default_rng(1)
    for n, mn in [(4, 3), (7, 2), (2, 5)]:
        sched.submit(rng.integers(0, cfg.vocab_size, (n,)), mn)
    sched.run()
    assert sched.pool.pages_in_use == 0
    assert not sched.pool.active.any()
    assert sched.pool.occupancy() == 0.0
    assert sched.stats.peak_occupancy > 0.0
    assert sched.stats.peak_eq2_bytes > 0


def test_scheduler_backpressure_queues_oversized_wave(tiny_model):
    """A pool that only fits one request at a time still serves all of them
    — later submissions wait in the queue instead of failing."""
    cfg, params = tiny_model
    # 6 usable pages of 4 slots; each request needs 2-3 pages incl. headroom
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=7, page_size=4,
                      max_slots=2)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, (8,)) for _ in range(3)]
    rids = [sched.submit(p, 3) for p in prompts]
    results = sched.run()
    assert len(results) == 3
    assert sched.stats.prefills >= 2  # memory forced at least two waves
    assert sched.stats.peak_occupancy == 1.0  # the pool really saturated
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(results[rid],
                                      eng.generate(p[None], 3).tokens[0])


def test_scheduler_eos_evicts_early(tiny_model):
    """An EOS-terminated request frees its slot for the queue: pick the
    token the model actually emits first as the EOS id, and require the
    result to be truncated at it."""
    cfg, params = tiny_model
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, (5,))
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    free_run = eng.generate(prompt[None], 6).tokens[0]
    eos = int(free_run[5 + 2])  # the 3rd generated token

    sched = Scheduler(cfg, params, OPTS_Q, num_pages=16, page_size=4,
                      max_slots=2)
    rid = sched.submit(prompt, 6, eos_id=eos)
    results = sched.run()
    got = results[rid]
    assert got[-1] == eos and got.size == 5 + 3  # truncated at EOS
    np.testing.assert_array_equal(got, free_run[: 5 + 3])


def test_scheduler_impossible_request_fails_loudly(tiny_model):
    """A request whose worst case exceeds the whole pool raises instead of
    spinning the run loop forever."""
    from repro.serving.kv_pool import PoolExhaustedError

    cfg, params = tiny_model
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=4, page_size=4,
                      max_slots=2)  # 3 usable pages = 12 tokens
    rng = np.random.default_rng(5)
    sched.submit(rng.integers(0, cfg.vocab_size, (10,)), 8)  # needs 18
    with pytest.raises(PoolExhaustedError, match="never be admitted"):
        sched.run()


def test_scheduler_single_token_requests(tiny_model):
    """max_new_tokens=1 finishes on its prefill logits — no decode step."""
    cfg, params = tiny_model
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=16, page_size=4,
                      max_slots=2)
    rng = np.random.default_rng(4)
    p = rng.integers(0, cfg.vocab_size, (6,))
    rid = sched.submit(p, 1)
    results = sched.run()
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    np.testing.assert_array_equal(results[rid], eng.generate(p[None], 1).tokens[0])
    assert sched.stats.steps == 0  # finished at prefill, never decoded


def test_prefix_sharing_matches_engine_and_saves_pool_bytes(tiny_model):
    """Acceptance: requests attached to a shared 10-token prefix (page 4 →
    partial boundary page, so the CoW path runs) produce greedy tokens
    IDENTICAL to the per-request Engine, while the pool's physical peak is
    LOWER than the same workload served without sharing."""
    cfg, params = tiny_model
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, cfg.vocab_size, (10,))
    jobs = [(3, 3), (2, 4), (4, 2), (3, 3)]  # (suffix_len, max_new)
    prompts = [np.concatenate([prefix,
                               rng.integers(0, cfg.vocab_size, (n,))])
               for n, _ in jobs]

    def serve(shared: bool):
        sched = Scheduler(cfg, params, OPTS_Q, num_pages=32, page_size=4,
                          max_slots=2)
        # only the key's FIRST submit declares prefix_len; later submits
        # (ragged prompt lengths) inherit the registered length
        rids = [sched.submit(p, mn,
                             prefix_key="sys" if shared else None,
                             prefix_len=10 if i == 0 else None)
                for i, (p, (_, mn)) in enumerate(zip(prompts, jobs))]
        return sched, rids, sched.run()

    sched, rids, results = serve(shared=True)
    base, _, base_results = serve(shared=False)
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    for rid, p, (_, mn) in zip(rids, prompts, jobs):
        want = eng.generate(p[None], mn).tokens[0]
        np.testing.assert_array_equal(results[rid], want)
        np.testing.assert_array_equal(base_results[rid], want)
    assert sched.stats.prefix_forks >= 2  # later requests really attached
    assert sched.stats.peak_shared_pages > 0
    assert sched.stats.peak_pool_bytes < base.stats.peak_pool_bytes
    # drained: pinned prefix released, every page home again
    assert sched.pool.pages_in_use == 0 and not sched.pool.active.any()


@pytest.mark.parametrize("resume", ["swap", "refill"])
def test_preemption_lazy_growth_matches_engine(tiny_model, resume):
    """Acceptance: lazy admission over a pool too small for every request's
    worst case — growth exhausts the pool mid-decode, the lowest-priority
    request is evicted to the queue and later RESUMED (bit-identical page
    restore by default; re-prefill also matches on this workload) — and
    every result is identical to the isolated Engine run."""
    cfg, params = tiny_model
    rng = np.random.default_rng(11)
    jobs = [(6, 8, 1), (5, 9, 0), (4, 8, 0)]  # (prompt, max_new, priority)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)) for n, _, _ in jobs]
    # 8 usable pages: prompts alone need 2+2+1, worst cases need 4+4+3
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=9, page_size=4,
                      max_slots=3, lazy_growth=True, resume=resume)
    rids = [sched.submit(p, mn, priority=pr)
            for p, (_, mn, pr) in zip(prompts, jobs)]
    results = sched.run()
    assert sched.stats.preemptions >= 1  # the pool really forced eviction
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    for rid, p, (_, mn, _) in zip(rids, prompts, jobs):
        np.testing.assert_array_equal(results[rid],
                                      eng.generate(p[None], mn).tokens[0])
    assert sched.pool.pages_in_use == 0  # preempt/resume leaked nothing


def test_preemption_victim_is_lowest_priority(tiny_model):
    """Victim selection: the priority-0 request is evicted (and resumed),
    the priority-1 request admitted at the same time never is."""
    cfg, params = tiny_model
    rng = np.random.default_rng(13)
    hi = rng.integers(0, cfg.vocab_size, (5,))
    lo = rng.integers(0, cfg.vocab_size, (5,))
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=6, page_size=4,
                      max_slots=2, lazy_growth=True)
    rid_hi = sched.submit(hi, 8, priority=1)
    rid_lo = sched.submit(lo, 8, priority=0)
    results = sched.run()
    assert sched.stats.preemptions >= 1
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    np.testing.assert_array_equal(results[rid_hi],
                                  eng.generate(hi[None], 8).tokens[0])
    np.testing.assert_array_equal(results[rid_lo],
                                  eng.generate(lo[None], 8).tokens[0])


def test_shared_prefix_with_preemption_roundtrip(tiny_model):
    """The full tentpole combination: forked requests under lazy growth get
    preempted, re-fork on resume (their prefix stays pinned), and still
    match the Engine bit-for-bit."""
    cfg, params = tiny_model
    rng = np.random.default_rng(17)
    prefix = rng.integers(0, cfg.vocab_size, (8,))  # 2 full pages
    jobs = [(2, 6), (3, 6), (2, 6)]
    prompts = [np.concatenate([prefix,
                               rng.integers(0, cfg.vocab_size, (n,))])
               for n, _ in jobs]
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=8, page_size=4,
                      max_slots=3, lazy_growth=True)
    rids = [sched.submit(p, mn, prefix_key="sys", prefix_len=8)
            for p, (_, mn) in zip(prompts, jobs)]
    results = sched.run()
    assert sched.stats.prefix_forks >= 2
    assert sched.stats.preemptions >= 1
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    for rid, p, (_, mn) in zip(rids, prompts, jobs):
        np.testing.assert_array_equal(results[rid],
                                      eng.generate(p[None], mn).tokens[0])
    assert sched.pool.pages_in_use == 0


def test_prefix_mismatch_rejected(tiny_model):
    cfg, params = tiny_model
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=16, page_size=4,
                      max_slots=2)
    rng = np.random.default_rng(19)
    a = rng.integers(0, cfg.vocab_size, (8,))
    b = a.copy()
    b[2] = (b[2] + 1) % cfg.vocab_size
    sched.submit(a, 2, prefix_key="k", prefix_len=6)
    with pytest.raises(ValueError, match="does not match"):
        sched.submit(b, 2, prefix_key="k", prefix_len=6)


def test_swap_snapshot_excludes_speculative_append(tiny_model):
    """Regression: slot 0 runs its speculative append for the tick, then
    slot 1's append exhausts the pool and preempts slot 0 — the snapshot
    must cover only WRITTEN positions (the pending token's position holds
    no KV yet), or the restore carries a permanent pos=-1 hole and the
    resumed decode diverges. Both results must match the Engine exactly."""
    cfg, params = tiny_model
    rng = np.random.default_rng(0)
    a = rng.integers(0, cfg.vocab_size, (5,))  # slot 0, preemption victim
    b = rng.integers(0, cfg.vocab_size, (5,))
    # 5 usable pages: both admit at 2 pages (prompt 5 + 1 headroom), slot 0
    # grabs the 5th page at length 9, slot 1's matching append exhausts →
    # victim is slot 0 (priority), AFTER its own append already landed
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=6, page_size=4,
                      max_slots=2, lazy_growth=True, resume="swap")
    ra = sched.submit(a, 8, priority=0)
    rb = sched.submit(b, 8, priority=1)
    results = sched.run()
    assert sched.stats.preemptions >= 1
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    np.testing.assert_array_equal(results[ra],
                                  eng.generate(a[None], 8).tokens[0])
    np.testing.assert_array_equal(results[rb],
                                  eng.generate(b[None], 8).tokens[0])


# ----------------------------------------------- split-boundary speculation


def _repetitive_prompts(cfg, n=4, seed=7):
    """Prompts with a repeating 3-gram: prompt-lookup drafting has signal,
    so accepted bursts actually occur (the random-init model still rejects
    plenty — both accept and rollback paths run)."""
    rng = np.random.default_rng(seed)
    return [np.tile(rng.integers(0, cfg.vocab_size, (3,)), 4)[:9]
            .astype(np.int32) for _ in range(n)]


def _serve_spec(cfg, params, mode, prompts, max_new, k, **kw):
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=32, page_size=4,
                      max_slots=3, tick_mode=mode, speculate_k=k, **kw)
    rids = [sched.submit(p, max_new) for p in prompts]
    res = sched.run()
    return [res[r] for r in rids], sched


@pytest.mark.parametrize("mode", ["packed", "chunked", "wave"])
def test_speculative_scheduler_matches_engine(tiny_model, mode):
    """Tentpole acceptance: ``speculate_k`` NEVER changes the greedy
    stream — bit-identical to the per-request Engine in every tick mode —
    while the verify rounds fold multiple tokens into single decode
    ticks (fewer steps than the k=0 run of the same workload)."""
    cfg, params = tiny_model
    prompts = _repetitive_prompts(cfg)
    max_new = 6
    _, s0 = _serve_spec(cfg, params, mode, prompts, max_new, 0)
    outs, s = _serve_spec(cfg, params, mode, prompts, max_new, 3)
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    for p, got in zip(prompts, outs):
        np.testing.assert_array_equal(
            got, eng.generate(p[None], max_new).tokens[0])
    st, st0 = s.stats, s0.stats
    assert st.steps < st0.steps, "speculation must reduce decode ticks"
    assert st.spec_rounds > 0 and st.spec_drafted >= st.spec_accepted > 0
    assert 0.0 < st.acceptance_rate <= 1.0
    # multi-token emission: indices strictly ordered with finite logprobs
    seen = {}
    for rid, idx, tok, lp, _ in s.drain_events():
        assert idx == seen.get(rid, -1) + 1 and np.isfinite(lp)
        seen[rid] = idx


def test_speculative_per_request_cap(tiny_model):
    """``SamplingParams(speculate_k=1)`` lowers a request's draft burst
    below the scheduler-level k: no verify round may carry more than one
    draft token, and the stream still equals the Engine's."""
    cfg, params = tiny_model
    p = _repetitive_prompts(cfg, n=1)[0]
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=32, page_size=4,
                      max_slots=3, tick_mode="chunked", speculate_k=3)
    rid = sched.submit(p, sampling=SamplingParams(max_tokens=6, speculate_k=1))
    res = sched.run()
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    np.testing.assert_array_equal(res[rid], eng.generate(p[None], 6).tokens[0])
    st = sched.stats
    assert st.spec_rounds > 0
    assert st.spec_drafted <= st.spec_rounds  # capped at 1 draft per round


def test_speculative_rejection_rolls_back_exactly(tiny_model):
    """Prompt-lookup drafts continue the prompt's repetition, but the
    random-init model mostly doesn't — rejected tails are truncated out of
    the pool every round, and the stream must still be bit-identical to
    the Engine with the pool draining clean."""
    cfg, params = tiny_model
    prompts = _repetitive_prompts(cfg, n=3, seed=11)
    outs, s = _serve_spec(cfg, params, "chunked", prompts, 7, 3)
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    for p, got in zip(prompts, outs):
        np.testing.assert_array_equal(got, eng.generate(p[None], 7).tokens[0])
    assert s.stats.spec_accepted < s.stats.spec_drafted  # rollbacks happened
    assert s.pool.pages_in_use == 0
