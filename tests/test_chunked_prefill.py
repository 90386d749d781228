"""Chunked prefill: the Pallas ragged prefill page-walk kernel against its
oracle and the dense-gather/chunked_attention path (ragged lengths, GQA
ratios, shared-prefix forks, non-aligned trailing pages), the kernel on the
default model route (no dense pool gather), and the chunked-prefill
scheduler's greedy parity with per-request ``Engine.generate`` — including
mid-chunk admission, mid-prefill preemption, and the anti-thrash admission
cooldown."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ref
from repro.kernels.ops import paged_prefill_attention
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


def _prefill_fixture(rng, hist_lens, suf_lens, kh=2, g=2, page=4, hd=32,
                     p=16):
    """A hand-built pool + in-call batch: request r holds ``hist_lens[r]``
    HISTORY tokens in its pages (its earlier chunks / shared prefix) and
    prefills ``suf_lens[r]`` fresh tokens right-aligned from position
    ``hist_lens[r]``. The call's fresh tokens are ALSO scattered into the
    pool (post-update convention) so the kernel's ``pos < start`` history
    mask is really exercised against double counting."""
    kc = np.asarray(rng.integers(-127, 128, (p, kh, page, hd)), np.int8)
    vc = np.asarray(rng.integers(-127, 128, (p, kh, page, hd)), np.int8)
    ks = np.asarray(rng.uniform(0.005, 0.02, (p, kh, 1, page)), np.float32)
    vs = np.asarray(rng.uniform(0.005, 0.02, (p, kh, 1, page)), np.float32)
    r = len(hist_lens)
    totals = [h + s for h, s in zip(hist_lens, suf_lens)]
    maxb = max(-(-t // page) for t in totals)
    bt = np.zeros((r, maxb), np.int32)
    pool_pos = np.full((p, 1, page), -1, np.int32)
    nxt = 1
    for i, t in enumerate(totals):
        for b in range(-(-t // page)):
            bt[i, b] = nxt
            nxt += 1
        for tok in range(t):  # history AND this call's tokens stored
            pool_pos[bt[i, tok // page], 0, tok % page] = tok
    assert nxt <= p
    s = max(suf_lens)
    q_pos = np.full((r, s), -1, np.int32)
    for i, (h, ns) in enumerate(zip(hist_lens, suf_lens)):
        q_pos[i, s - ns:] = np.arange(h, h + ns)
    q = rng.normal(size=(r, kh, s, g, hd)).astype(np.float32)
    kf = rng.normal(size=(r, kh, s, hd)).astype(np.float32)
    vf = rng.normal(size=(r, kh, s, hd)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in
                 (q, kc, ks, vc, vs, pool_pos, bt, q_pos, kf, vf))


@pytest.mark.parametrize("g,kh", [(2, 2), (4, 1), (1, 2)])
@pytest.mark.parametrize("hist,suf", [
    ((9, 5, 0), (4, 6, 3)),    # ragged, non-aligned trailing pages
    ((8, 8, 8), (4, 4, 4)),    # page-aligned shared-prefix forks
    ((13, 0, 1), (2, 7, 5)),   # long fork / plain / 1-token history
])
def test_prefill_kernel_matches_oracle(g, kh, hist, suf):
    rng = np.random.default_rng(g * 100 + sum(hist) + sum(suf))
    q, kc, ks, vc, vs, pp, bt, qp, kf, vf = _prefill_fixture(
        rng, hist, suf, kh=kh, g=g)
    start = jnp.min(jnp.where(qp >= 0, qp, jnp.int32(2 ** 30)), axis=1)
    got = paged_prefill_attention(q, kc, ks, vc, vs, pp, bt, qp, kf, vf)
    want = ref.paged_prefill_attention_ref(q, kc, ks, vc, vs, pp, bt, qp,
                                           start, kf, vf)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # pad query columns emit exact zeros (fixed-shape scheduler ticks rely
    # on finite outputs for inactive rows)
    s = qp.shape[1]
    for i, ns in enumerate(suf):
        np.testing.assert_array_equal(np.asarray(got[i, :, : s - ns]), 0.0)


def test_prefill_kernel_multiple_q_blocks():
    """q_block smaller than S: the online state must survive across query
    blocks AND the (nb + fresh) minor axis."""
    rng = np.random.default_rng(3)
    q, kc, ks, vc, vs, pp, bt, qp, kf, vf = _prefill_fixture(
        rng, (9, 5, 0), (7, 6, 3))
    start = jnp.min(jnp.where(qp >= 0, qp, jnp.int32(2 ** 30)), axis=1)
    want = ref.paged_prefill_attention_ref(q, kc, ks, vc, vs, pp, bt, qp,
                                           start, kf, vf)
    got = paged_prefill_attention(q, kc, ks, vc, vs, pp, bt, qp, kf, vf,
                                  q_block=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_model_route_matches_dense_gather_and_skips_it(tiny_model,
                                                       monkeypatch):
    """Acceptance: the model-level ``paged_prefill_attention`` kernel route
    agrees with the dense-gather/chunked_attention fallback on a forked
    shared-prefix prefill, and the default (non-softcap) path never calls
    ``_gather_dense_kv``."""
    from repro.models import layers as L
    from repro.models.transformer import paged_prefill, paged_prefill_shared
    from repro.serving.kv_pool import PagedKVPool

    cfg, params = tiny_model
    rng = np.random.default_rng(11)
    prefix_len, suf = 6, 4
    prompt = rng.integers(0, cfg.vocab_size, (prefix_len + suf,))

    def run(opts):
        pool = PagedKVPool(cfg, num_pages=16, page_size=4, max_requests=2)
        s0 = pool.admit(prefix_len + suf)
        # creator prefills the full prompt (plain path)
        tokens = prompt[None].astype(np.int32)
        logits, caches = paged_prefill(
            params, cfg, jnp.asarray(tokens), pool.device_caches(rows=[s0]),
            jnp.asarray(np.arange(prefix_len + suf)[None].astype(np.int32)),
            opts)
        pool.update_from(caches)
        pool.commit_prefill(s0, prefix_len + suf)
        handle = pool.share_prefix(s0, prefix_len)
        s1 = pool.admit(prefix_len + suf, prefix=handle)
        # fork prefills only its suffix THROUGH the pool
        stoks = np.zeros((1, suf), np.int32)
        stoks[0] = prompt[prefix_len:]
        spos = np.arange(prefix_len, prefix_len + suf)[None].astype(np.int32)
        logits2, caches2 = paged_prefill_shared(
            params, cfg, jnp.asarray(stoks), pool.device_caches(rows=[s1]),
            jnp.asarray(spos), opts)
        return np.asarray(logits2[0])

    calls = []
    orig = L._gather_dense_kv
    monkeypatch.setattr(L, "_gather_dense_kv",
                        lambda c: calls.append(1) or orig(c))
    kernel_logits = run(OPTS_Q)
    assert not calls, "default path must not gather the pool dense"
    dense_logits = run(
        __import__("dataclasses").replace(OPTS_Q, paged_prefill_kernel=False))
    assert calls, "fallback path exercises the dense gather"
    np.testing.assert_allclose(kernel_logits, dense_logits,
                               rtol=2e-4, atol=2e-4)
    assert int(np.argmax(kernel_logits)) == int(np.argmax(dense_logits))


# ------------------------------------------------- scheduler equivalence


def test_chunked_scheduler_matches_engine_multi_chunk(tiny_model):
    """Acceptance: prompts LONGER than the chunk (here 3-5 chunks each) are
    admitted piecewise — later chunks attend earlier ones through the
    page-walk kernel — while other requests keep decoding, and every
    greedy output is IDENTICAL to the per-request Engine. Mid-chunk
    admission is forced by queueing more requests than slots."""
    cfg, params = tiny_model
    rng = np.random.default_rng(21)
    jobs = [(18, 5), (9, 4), (4, 6), (14, 3)]  # (prompt_len, max_new)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)) for n, _ in jobs]
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=32, page_size=4,
                      max_slots=2, prefill_chunk=4)
    rids = [sched.submit(p, mn) for p, (_, mn) in zip(prompts, jobs)]
    results = sched.run()
    # 18 tokens / chunk 4 → ≥ 5 chunks for request 0 alone
    assert sched.stats.prefill_chunks >= 5 + 3 + 1 + 4
    assert sched.stats.ttft_ticks[rids[0]] >= 5  # ticks, one chunk each
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    for rid, p, (_, mn) in zip(rids, prompts, jobs):
        np.testing.assert_array_equal(results[rid],
                                      eng.generate(p[None], mn).tokens[0])
    # ONE compiled shape per step kind, whatever the admission pattern
    assert sched.stats.compiled_shapes <= 3


def test_chunked_scheduler_decodes_while_long_prompt_admits(tiny_model):
    """The Sarathi property: a decoding request keeps emitting one token
    per tick WHILE a long prompt is being admitted chunk by chunk (wave
    mode would stall it for the whole prompt)."""
    cfg, params = tiny_model
    rng = np.random.default_rng(23)
    short = rng.integers(0, cfg.vocab_size, (3,))
    long = rng.integers(0, cfg.vocab_size, (16,))
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=32, page_size=4,
                      max_slots=2, prefill_chunk=4)
    r_short = sched.submit(short, 10)
    r_long = sched.submit(long, 2)
    ticks_with_progress = 0
    last = 0
    while sched.step():
        st = next((s for s in sched.slots
                   if s is not None and s.req.rid == r_short), None)
        if st is not None and len(st.generated) > last:
            last = len(st.generated)
            ticks_with_progress += 1
    results = sched.results
    # the long prompt needed 4 chunk ticks; the short request decoded
    # through every one of them
    assert ticks_with_progress >= 4
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    np.testing.assert_array_equal(results[r_short],
                                  eng.generate(short[None], 10).tokens[0])
    np.testing.assert_array_equal(results[r_long],
                                  eng.generate(long[None], 2).tokens[0])


@pytest.mark.parametrize("resume", ["swap", "refill"])
def test_chunked_prefill_preemption_roundtrip(tiny_model, resume):
    """A mid-prefill slot evicted by a decoding neighbour's growth resumes
    CHUNKING where it left off (swap) or re-prefills (refill) — and both
    requests still match the Engine exactly."""
    cfg, params = tiny_model
    rng = np.random.default_rng(29)
    a = rng.integers(0, cfg.vocab_size, (5,))   # decodes and grows
    b = rng.integers(0, cfg.vocab_size, (24,))  # chunked mid-prefill victim
    # 9 usable pages: a admits at 2 (5+1 tokens), b's lazy target takes the
    # other 7; a's growth to a 3rd page exhausts the pool on tick 5 while b
    # (6 chunks of 4) has only written 16 of 24 prompt tokens — b is
    # evicted MID-PREFILL with just its chunks and must resume them
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=10, page_size=4,
                      max_slots=2, prefill_chunk=4, lazy_growth=True,
                      resume=resume, preempt_cooldown=1)
    ra = sched.submit(a, 10, priority=1)
    rb = sched.submit(b, 3, priority=0)
    results = sched.run()
    assert sched.stats.preemptions >= 1
    # an uninterrupted 24-token prompt takes exactly 6 chunk ticks; the
    # preempted one must have waited out its eviction
    assert sched.stats.ttft_ticks[rb] > 6
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    np.testing.assert_array_equal(results[ra],
                                  eng.generate(a[None], 10).tokens[0])
    np.testing.assert_array_equal(results[rb],
                                  eng.generate(b[None], 3).tokens[0])
    assert sched.pool.pages_in_use == 0


def test_chunked_prefix_sharing_matches_engine(tiny_model):
    """Prefix forks under chunked prefill: the creator's prefix is pinned
    as soon as its chunks cover it, forks chunk only their suffix, and
    every output matches the Engine."""
    cfg, params = tiny_model
    rng = np.random.default_rng(31)
    prefix = rng.integers(0, cfg.vocab_size, (10,))
    jobs = [(6, 3), (2, 4), (5, 3)]
    prompts = [np.concatenate([prefix,
                               rng.integers(0, cfg.vocab_size, (n,))])
               for n, _ in jobs]
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=32, page_size=4,
                      max_slots=2, prefill_chunk=4)
    rids = [sched.submit(p, mn, prefix_key="sys",
                         prefix_len=10 if i == 0 else None)
            for i, (p, (_, mn)) in enumerate(zip(prompts, jobs))]
    results = sched.run()
    assert sched.stats.prefix_forks >= 2
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    for rid, p, (_, mn) in zip(rids, prompts, jobs):
        np.testing.assert_array_equal(results[rid],
                                      eng.generate(p[None], mn).tokens[0])
    assert sched.pool.pages_in_use == 0


# --------------------------------------------------- anti-thrash cooldown


def _swap_storm(cfg, params, cooldown):
    """One high-priority long-runner crossing a page boundary every other
    tick, a low-priority victim, and a stream of short requests whose
    evictions keep opening just enough slack for the victim to re-admit —
    the evict → re-admit → evict oscillation the cooldown exists to damp."""
    rng = np.random.default_rng(37)
    eng = Engine(cfg, params, OPTS_Q, cache_len=64)
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=12, page_size=2,
                      max_slots=3, lazy_growth=True,
                      preempt_cooldown=cooldown)
    jobs = [(rng.integers(0, cfg.vocab_size, (4,)), 14, 2),  # grower
            (rng.integers(0, cfg.vocab_size, (4,)), 14, 0)]  # victim
    jobs += [(rng.integers(0, cfg.vocab_size, (3,)), 2, 1) for _ in range(6)]
    rids = [sched.submit(p, mn, priority=pr) for p, mn, pr in jobs]
    results = sched.run()
    for rid, (p, mn, _) in zip(rids, jobs):
        np.testing.assert_array_equal(results[rid],
                                      eng.generate(p[None], mn).tokens[0])
    return sched.stats.preemptions


def test_anti_thrash_cooldown_damps_swap_storm(tiny_model):
    """Regression for the ROADMAP follow-on: without a cooldown the victim
    is re-admitted as soon as slack reopens — right after its preemptor
    grew — and re-evicted at the preemptor's next page boundary, a swap
    storm that re-plays the same pages over and over. A cooldown spanning
    a few growth boundaries lets the preemptor drain first and must cut
    the preemption count (with identical outputs, which both runs
    assert)."""
    cfg, params = tiny_model
    storm = _swap_storm(cfg, params, cooldown=0)
    calm = _swap_storm(cfg, params, cooldown=4)
    assert storm >= 2, "workload must provoke repeated preemption today"
    assert calm < storm


def test_wave_mode_still_available_and_compiles_per_bucket(tiny_model):
    """``prefill_mode="wave"`` keeps the old behavior: same outputs, but a
    distinct prefill shape per (R_adm, S_pad) bucket — the compile-count
    counter shows exactly what chunked mode eliminates."""
    cfg, params = tiny_model
    rng = np.random.default_rng(41)
    jobs = [(3, 3), (9, 3), (17, 3)]  # three different buckets
    prompts = [rng.integers(0, cfg.vocab_size, (n,)) for n, _ in jobs]

    def serve(mode):
        sched = Scheduler(cfg, params, OPTS_Q, num_pages=32, page_size=4,
                          max_slots=1, prefill_mode=mode, prefill_chunk=8)
        rids = [sched.submit(p, mn) for p, (_, mn) in zip(prompts, jobs)]
        return sched, rids, sched.run()

    wave, wrids, wres = serve("wave")
    chunk, crids, cres = serve("chunked")
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    for (wr, cr, p, (_, mn)) in zip(wrids, crids, prompts, jobs):
        want = eng.generate(p[None], mn).tokens[0]
        np.testing.assert_array_equal(wres[wr], want)
        np.testing.assert_array_equal(cres[cr], want)
    # wave: one prefill shape per bucket (4, 16, 32) + decode ≥ 4 shapes;
    # chunked: first-chunk + continuation + decode ≤ 3, bucket-independent
    assert wave.stats.compiled_shapes >= 4
    assert chunk.stats.compiled_shapes <= 3
    assert chunk.stats.prefill_chunks == 1 + 2 + 3  # ceil(n / 8) each
