"""Token-packed varlen ticks: the flat-batch Pallas varlen attention
kernel against its dense oracle (pure-decode / pure-prefill / mixed
segment packs, GQA ratios, non-page-aligned boundaries, all-pad tails,
and a cross-check against the decode oracle), the packed scheduler's
greedy parity with per-request ``Engine.generate`` and with the chunked
tick under admission pressure and preemption, the one-compiled-shape
guarantee, the cached sampling-operand upload, and per-token logprobs
threaded through the sampler, the scheduler events, and the serving
API backends."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.sampling import SamplingParams, token_logprobs
from repro.kernels import ops, ref
from repro.kernels.varlen_attention import segment_start
from repro.models.transformer import RuntimeOpts, init_params
from repro.serving import Engine, LLMServer, Scheduler

OPTS_Q = RuntimeOpts(q_chunk=16, kv_chunk=16, remat=False, quantized_kv=True,
                     moe_capacity_factor=0.0)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama2-7b").tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _varlen_fixture(rng, segs, kh=2, g=2, page=4, hd=32, p=16, pad=0):
    """A hand-built pool + flat token batch: slot ``i`` holds
    ``segs[i][0]`` HISTORY tokens in its pages and contributes
    ``segs[i][1]`` fresh in-call tokens from that position — a decode
    token is the ``n = 1`` case. The call's own tokens are ALSO stored in
    the pool (post-update convention) so the ``pos < start`` history mask
    is really exercised against double counting; ``pad`` inactive rows
    (slot -1, position -1) close the fixed-budget buffer's tail."""
    kc = np.asarray(rng.integers(-127, 128, (p, kh, page, hd)), np.int8)
    vc = np.asarray(rng.integers(-127, 128, (p, kh, page, hd)), np.int8)
    ks = np.asarray(rng.uniform(0.005, 0.02, (p, kh, 1, page)), np.float32)
    vs = np.asarray(rng.uniform(0.005, 0.02, (p, kh, 1, page)), np.float32)
    r = len(segs)
    totals = [h + n for h, n in segs]
    maxb = max(-(-t // page) for t in totals)
    bt = np.zeros((r, maxb), np.int32)
    pool_pos = np.full((p, 1, page), -1, np.int32)
    nxt = 1  # page 0 is the trash page
    for i, t in enumerate(totals):
        for b in range(-(-t // page)):
            bt[i, b] = nxt
            nxt += 1
        for tok in range(t):  # history AND this call's tokens stored
            pool_pos[bt[i, tok // page], 0, tok % page] = tok
    assert nxt <= p
    t_flat = sum(n for _, n in segs) + pad
    q_pos = np.full((t_flat,), -1, np.int32)
    tok_slot = np.full((t_flat,), -1, np.int32)
    cur = 0
    for i, (h, n) in enumerate(segs):
        q_pos[cur:cur + n] = np.arange(h, h + n)
        tok_slot[cur:cur + n] = i
        cur += n
    q = rng.normal(size=(kh, t_flat, g, hd)).astype(np.float32)
    kf = rng.normal(size=(kh, t_flat, hd)).astype(np.float32)
    vf = rng.normal(size=(kh, t_flat, hd)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in
                 (q, kc, ks, vc, vs, pool_pos, bt, q_pos, tok_slot, kf, vf))


MIXES = {
    # decode-only pack: three length-1 segments + pad tail
    "pure_decode": dict(segs=[(5, 1), (9, 1), (3, 1)], pad=3),
    # prefill-only pack, non-page-aligned segment totals (4, 9, 5 on
    # page 4) including a fresh request and a mid-prompt continuation
    "pure_prefill": dict(segs=[(0, 4), (6, 3), (0, 5)], pad=0),
    # the packed tick's real shape: decode tokens and ragged prefill
    # chunks interleaved in one buffer
    "mixed": dict(segs=[(9, 1), (5, 4), (0, 6), (7, 1)], pad=2),
}


@pytest.mark.parametrize("g,kh", [(2, 2), (4, 1), (1, 2)])
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_varlen_kernel_matches_oracle(g, kh, mix):
    spec = MIXES[mix]
    rng = np.random.default_rng(abs(hash((g, kh, mix))) % 2 ** 31)
    q, kc, ks, vc, vs, pp, bt, qp, sl, kf, vf = _varlen_fixture(
        rng, spec["segs"], kh=kh, g=g, pad=spec["pad"])
    got = ops.varlen_attention(q, kc, ks, vc, vs, pp, bt, qp, sl, kf, vf)
    start = segment_start(qp, sl, bt.shape[0])
    want = ref.varlen_attention_ref(q, kc, ks, vc, vs, pp, bt, qp, sl,
                                    start, kf, vf)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # pad tail rows emit exact zeros — the fixed-budget scheduler tick
    # relies on inactive rows being finite and inert
    if spec["pad"]:
        np.testing.assert_array_equal(
            np.asarray(got[:, -spec["pad"]:]), 0.0)


def test_varlen_all_pad_rows_emit_exact_zeros():
    """A buffer with NO active tokens (every row slot -1 / position -1)
    must come back all-zero — never NaN from an empty softmax."""
    rng = np.random.default_rng(17)
    q, kc, ks, vc, vs, pp, bt, qp, sl, kf, vf = _varlen_fixture(
        rng, [(4, 2), (7, 1)], pad=1)
    qp = jnp.full_like(qp, -1)
    sl = jnp.full_like(sl, -1)
    got = ops.varlen_attention(q, kc, ks, vc, vs, pp, bt, qp, sl, kf, vf)
    np.testing.assert_array_equal(np.asarray(got), 0.0)


def test_varlen_pure_decode_matches_decode_oracle():
    """A pure-decode pack whose fresh k/v equal the pool's dequantized
    self entries is EXACTLY the decode kernel's problem — row r of the
    flat batch must reproduce ``paged_decode_attention_ref`` for request
    r (same pages, same causal bound)."""
    kh, g, page, hd = 2, 2, 4, 32
    segs = [(5, 1), (9, 1), (3, 1)]
    rng = np.random.default_rng(23)
    q, kc, ks, vc, vs, pp, bt, qp, sl, kf, vf = _varlen_fixture(
        rng, segs, kh=kh, g=g, page=page, hd=hd)
    # overwrite the fresh keys with the pool's own (dequantized) entry at
    # each token's position, so both conventions see identical self keys
    kf, vf = np.asarray(kf).copy(), np.asarray(vf).copy()
    for t, (h, _) in enumerate(segs):
        pg, off = bt[t, h // page], h % page
        kf[:, t] = np.asarray(kc)[pg, :, off] * np.asarray(ks)[pg, :, 0, off,
                                                              None]
        vf[:, t] = np.asarray(vc)[pg, :, off] * np.asarray(vs)[pg, :, 0, off,
                                                               None]
    got = ops.varlen_attention(q, kc, ks, vc, vs, pp, bt, qp, sl,
                               jnp.asarray(kf), jnp.asarray(vf))
    want = ref.paged_decode_attention_ref(
        jnp.swapaxes(q, 0, 1), kc, ks, vc, vs, pp, bt,
        jnp.asarray([h for h, _ in segs], jnp.int32))
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(got, 0, 1)),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


# ------------------------------------------------- scheduler equivalence


def test_packed_scheduler_matches_engine_and_chunked(tiny_model):
    """Acceptance: ``tick_mode="packed"`` serves the multi-chunk workload
    (prompts 3-5 chunks long, more requests than slots, mid-tick
    admission) through ONE compiled shape, greedy outputs IDENTICAL to
    the per-request Engine — and therefore to the chunked tick."""
    cfg, params = tiny_model
    rng = np.random.default_rng(21)
    jobs = [(18, 5), (9, 4), (4, 6), (14, 3)]  # (prompt_len, max_new)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)) for n, _ in jobs]

    def serve(**kw):
        sched = Scheduler(cfg, params, OPTS_Q, num_pages=32, page_size=4,
                          max_slots=2, prefill_chunk=4, **kw)
        rids = [sched.submit(p, mn) for p, (_, mn) in zip(prompts, jobs)]
        return sched, rids, sched.run()

    packed, prids, pres = serve(tick_mode="packed")
    chunked, crids, cres = serve(tick_mode="chunked")
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    for pr, cr, p, (_, mn) in zip(prids, crids, prompts, jobs):
        want = eng.generate(p[None], mn).tokens[0]
        np.testing.assert_array_equal(pres[pr], want)
        np.testing.assert_array_equal(cres[cr], want)
    # the whole run is ONE jitted shape: (1, token_budget) packed steps —
    # vs the chunked tick's first-chunk + continuation + decode trio
    assert packed.stats.compiled_shapes == 1
    assert packed.stats.compiled_shapes <= chunked.stats.compiled_shapes
    assert packed.stats.packed_ticks > 0
    # exact token accounting: every prompt token is processed once, plus
    # one decode row per generated token except the first (it rides the
    # final prefill row) and the last (sampled, never fed back)
    assert packed.stats.packed_tokens == (sum(n for n, _ in jobs)
                                          + sum(m - 1 for _, m in jobs))
    assert packed.pool.pages_in_use == 0


def test_packed_decodes_while_long_prompt_admits(tiny_model):
    """The Sarathi property survives packing: a decoding request keeps
    emitting one token per PACKED tick while a long prompt's chunks share
    the same buffer."""
    cfg, params = tiny_model
    rng = np.random.default_rng(23)
    short = rng.integers(0, cfg.vocab_size, (3,))
    long = rng.integers(0, cfg.vocab_size, (16,))
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=32, page_size=4,
                      max_slots=2, prefill_chunk=4, tick_mode="packed")
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
    assert ticks_with_progress >= 4
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    np.testing.assert_array_equal(sched.results[r_short],
                                  eng.generate(short[None], 10).tokens[0])
    np.testing.assert_array_equal(sched.results[r_long],
                                  eng.generate(long[None], 2).tokens[0])


@pytest.mark.parametrize("resume", ["swap", "refill"])
def test_packed_preemption_roundtrip(tiny_model, resume):
    """A mid-prefill slot evicted by a decoding neighbour's growth
    resumes its packed pieces where it left off (swap) or re-prefills
    (refill) — and both requests still match the Engine exactly."""
    cfg, params = tiny_model
    rng = np.random.default_rng(29)
    a = rng.integers(0, cfg.vocab_size, (5,))   # decodes and grows
    b = rng.integers(0, cfg.vocab_size, (24,))  # mid-prefill victim
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=10, page_size=4,
                      max_slots=2, prefill_chunk=4, lazy_growth=True,
                      resume=resume, preempt_cooldown=1,
                      tick_mode="packed")
    ra = sched.submit(a, 10, priority=1)
    rb = sched.submit(b, 3, priority=0)
    results = sched.run()
    assert sched.stats.preemptions >= 1
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    np.testing.assert_array_equal(results[ra],
                                  eng.generate(a[None], 10).tokens[0])
    np.testing.assert_array_equal(results[rb],
                                  eng.generate(b[None], 3).tokens[0])
    assert sched.pool.pages_in_use == 0


# ------------------------------------------- cached sampling operands


def test_device_ops_upload_cached_across_ticks(tiny_model):
    """Satellite regression: steady-state ticks must ship the SAME device
    operand arrays — greedy admissions into greedy-reset rows and
    membership-stable decode ticks never trigger a re-upload."""
    cfg, params = tiny_model
    rng = np.random.default_rng(31)
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=32, page_size=4,
                      max_slots=2, tick_mode="packed")
    for _ in range(3):  # all greedy (seed 0) — the reset-row no-op case
        sched.submit(rng.integers(0, cfg.vocab_size, (5,)), 4)
    assert sched.step()
    first = sched._device_ops()
    while sched.step():
        assert sched._device_ops() is first  # never rebuilt, never re-sent
    # a NON-default row must invalidate the cache exactly once
    sched.submit(rng.integers(0, cfg.vocab_size, (4,)),
                 sampling=SamplingParams(max_tokens=3, temperature=0.7,
                                         seed=5))
    sched.step()
    second = sched._device_ops()
    assert second is not first
    while sched.step():
        assert sched._device_ops() is not first


def test_seeded_draws_unchanged_by_operand_cache(tiny_model):
    """Same seeds ⇒ same draws through the cached-operand path: seeded
    non-greedy requests through the packed scheduler equal the fused
    per-request engine row for row."""
    cfg, params = tiny_model
    rng = np.random.default_rng(37)
    prompts = [rng.integers(0, cfg.vocab_size, (6,)) for _ in range(3)]
    sps = [SamplingParams(max_tokens=5, temperature=0.8, top_k=7, seed=s)
           for s in (3, 11, 3)]
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=32, page_size=4,
                      max_slots=2, tick_mode="packed")
    rids = [sched.submit(p, sampling=sp) for p, sp in zip(prompts, sps)]
    results = sched.run()
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    for rid, p, sp in zip(rids, prompts, sps):
        want = eng.generate_requests(p[None], [sp]).tokens[0]
        np.testing.assert_array_equal(results[rid], want)


# ------------------------------------------------------------- logprobs


def test_token_logprobs_matches_numpy():
    """The sampler helper is log-softmax of the RAW logits at the emitted
    token — checked against numpy, including the (B, K, V) codebook
    shape."""
    rng = np.random.default_rng(41)
    logits = rng.normal(size=(3, 11)).astype(np.float32) * 3
    toks = rng.integers(0, 11, (3,))
    got = np.asarray(token_logprobs(jnp.asarray(logits), jnp.asarray(toks)))
    z = logits - logits.max(-1, keepdims=True)
    want = (z - np.log(np.exp(z).sum(-1, keepdims=True)))[
        np.arange(3), toks]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    lk = rng.normal(size=(2, 4, 9)).astype(np.float32)
    tk = rng.integers(0, 9, (2, 4))
    got = np.asarray(token_logprobs(jnp.asarray(lk), jnp.asarray(tk)))
    assert got.shape == (2, 4)
    np.testing.assert_allclose(
        got[1, 2],
        jax.nn.log_softmax(lk[1, 2])[tk[1, 2]], rtol=1e-5)


def test_logprob_events_across_backends(tiny_model):
    """Every streamed token carries its raw-distribution logprob on both
    the fused (replayed) and paged (true-streaming) backends — same
    greedy tokens, logprobs agreeing to kernel-numerics tolerance, finish
    markers logprob-free."""
    cfg, params = tiny_model
    rng = np.random.default_rng(43)
    p = rng.integers(0, cfg.vocab_size, (6,))
    sp = SamplingParams(max_tokens=5)

    def collect(srv):
        rid = srv.submit(p, sp)
        toks, lps = [], []
        for ev in srv.stream():
            if ev.finished:
                assert ev.logprob is None
            else:
                toks.append(ev.token)
                lps.append(ev.logprob)
        return rid, np.asarray(toks), np.asarray(lps)

    srv_f = LLMServer(cfg, params, OPTS_Q, backend="fused", cache_len=32)
    _, toks_f, lps_f = collect(srv_f)
    srv_p = LLMServer(cfg, params, OPTS_Q, backend="paged", num_pages=24,
                      page_size=4, max_slots=2, tick_mode="packed")
    _, toks_p, lps_p = collect(srv_p)
    np.testing.assert_array_equal(toks_f, toks_p)
    assert np.all(np.isfinite(lps_f)) and np.all(lps_f <= 0.0)
    # fused reads fp logits, paged reads the packed int8-pool path — the
    # distributions agree to quantization/kernel tolerance
    np.testing.assert_allclose(lps_f, lps_p, atol=5e-2, rtol=5e-2)


def test_engine_generate_returns_logprobs(tiny_model):
    """``Engine.generate`` logprobs: one per generated token, finite,
    <= 0, and for greedy equal to the max of the step's log-softmax (the
    argmax token's own probability)."""
    cfg, params = tiny_model
    rng = np.random.default_rng(47)
    prompts = rng.integers(0, cfg.vocab_size, (2, 6))
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    res = eng.generate(prompts, 4)
    assert res.logprobs.shape == (2, 4)
    assert np.all(np.isfinite(res.logprobs)) and np.all(res.logprobs <= 0)
    # deterministic across calls (pure function of the logits)
    np.testing.assert_array_equal(res.logprobs,
                                  eng.generate(prompts, 4).logprobs)
