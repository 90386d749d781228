"""Paged decode path: the block-table Pallas kernel against the paged and
dense oracles (ragged causal bounds, trash-page masking, free-slot rows),
and the model-level ragged paged prefill/decode against the dense
quantized-cache path per request."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ref
from repro.kernels.ops import decode_attention, paged_decode_attention
from repro.kernels.paged_decode_attention import live_pages, pages_per_step
from repro.models.transformer import (RuntimeOpts, decode_step, init_params,
                                      paged_decode_step, paged_prefill,
                                      prefill)
from repro.serving.kv_pool import PagedKVPool

OPTS_Q = RuntimeOpts(q_chunk=16, kv_chunk=16, remat=False, quantized_kv=True,
                     moe_capacity_factor=0.0)


def _pool_fixture(rng, p=10, kh=2, page=16, hd=32, lens=(40, 20, 10),
                  maxb=None):
    """A hand-built pool: request r holds ``lens[r]`` tokens in pages
    [1 + sum(prior pages)...]; page 0 is trash (pos = -1). The block
    table is ``maxb`` entries wide (default: the longest request's)."""
    kc = jnp.asarray(rng.integers(-127, 128, (p, kh, page, hd)), jnp.int8)
    vc = jnp.asarray(rng.integers(-127, 128, (p, kh, page, hd)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (p, kh, 1, page)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (p, kh, 1, page)), jnp.float32)
    maxb = maxb or max(-(-n // page) for n in lens)
    bt = np.zeros((len(lens), maxb), np.int32)
    pool_pos = np.full((p, 1, page), -1, np.int32)
    nxt = 1
    for r, n in enumerate(lens):
        for b in range(-(-n // page)):
            bt[r, b] = nxt
            nxt += 1
        for t in range(n):
            pool_pos[bt[r, t // page], 0, t % page] = t
    assert nxt <= p
    return kc, ks, vc, vs, jnp.asarray(pool_pos), jnp.asarray(bt)


@pytest.mark.parametrize("g,kh", [(2, 2), (4, 1), (1, 2)])
@pytest.mark.parametrize("lens", [(40, 20, 10), (16, 16, 16), (31, 1, 7)])
def test_paged_kernel_matches_paged_oracle(g, kh, lens):
    rng = np.random.default_rng(g * 10 + sum(lens))
    kc, ks, vc, vs, pool_pos, bt = _pool_fixture(rng, kh=kh, lens=lens)
    q = jnp.asarray(rng.normal(size=(len(lens), kh, g, 32)), jnp.float32)
    q_pos = jnp.asarray([n - 1 for n in lens], jnp.int32)
    got = paged_decode_attention(q, kc, ks, vc, vs, pool_pos, bt, q_pos)
    want = ref.paged_decode_attention_ref(q, kc, ks, vc, vs, pool_pos, bt,
                                          q_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_paged_kernel_matches_dense_kernel():
    """Gathering a request's pages dense and running the PR 1 dense kernel
    must agree with the paged kernel reading the pool in place."""
    rng = np.random.default_rng(3)
    lens = (40, 20, 10)
    kc, ks, vc, vs, pool_pos, bt = _pool_fixture(rng, lens=lens)
    q = jnp.asarray(rng.normal(size=(3, 2, 2, 32)), jnp.float32)
    q_pos = jnp.asarray([n - 1 for n in lens], jnp.int32)
    got = paged_decode_attention(q, kc, ks, vc, vs, pool_pos, bt, q_pos)
    for r, n in enumerate(lens):
        dense = decode_attention(
            q[r:r + 1],
            ref.gather_pages_ref(kc, bt[r:r + 1]),
            ref.gather_pages_ref(ks[:, :, 0], bt[r:r + 1]),
            ref.gather_pages_ref(vc, bt[r:r + 1]),
            ref.gather_pages_ref(vs[:, :, 0], bt[r:r + 1]),
            ref.gather_pages_ref(pool_pos[:, 0], bt[r:r + 1]),
            jnp.int32(n - 1), block_s=16)
        np.testing.assert_allclose(np.asarray(got[r]), np.asarray(dense[0]),
                                   rtol=1e-4, atol=1e-4)


def test_paged_kernel_per_request_causal_bounds():
    """Ragged q_pos: lowering one request's bound must change only that
    request's output (per-request causal masking, not a shared scalar)."""
    rng = np.random.default_rng(5)
    kc, ks, vc, vs, pool_pos, bt = _pool_fixture(rng)
    q = jnp.asarray(rng.normal(size=(3, 2, 2, 32)), jnp.float32)
    a = paged_decode_attention(q, kc, ks, vc, vs, pool_pos, bt,
                               jnp.asarray([39, 19, 9], jnp.int32))
    b = paged_decode_attention(q, kc, ks, vc, vs, pool_pos, bt,
                               jnp.asarray([5, 19, 9], jnp.int32))
    assert float(jnp.max(jnp.abs(a[0] - b[0]))) > 1e-6
    np.testing.assert_allclose(np.asarray(a[1:]), np.asarray(b[1:]), rtol=1e-6)


def test_paged_kernel_inactive_row_is_finite_zero():
    """A free decode slot (block table all trash, q_pos = -1) must produce a
    finite all-zero row, never NaN — the scheduler decodes a fixed-shape
    batch with such rows every step."""
    rng = np.random.default_rng(7)
    kc, ks, vc, vs, pool_pos, bt_full = _pool_fixture(rng)
    bt = jnp.asarray(np.vstack([np.asarray(bt_full[:1]),
                                np.zeros((1, bt_full.shape[1]), np.int32)]))
    q = jnp.asarray(rng.normal(size=(2, 2, 2, 32)), jnp.float32)
    out = paged_decode_attention(q, kc, ks, vc, vs, pool_pos, bt,
                                 jnp.asarray([39, -1], jnp.int32))
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_array_equal(np.asarray(out[1]), 0.0)


# The walk's bounds. Every case poisons the block-table entries past each
# row's last live page (q_pos // page + 1 entries live) with another row's
# first page, whose positions 0..15 include keys <= q_pos: a walk that
# went past the live entries would attend them. The kernel must equal the
# oracle on the clean table, where those entries hold the trash page.
WALK_CASES = {
    # q_pos on both sides of page boundaries (page = 16)
    "page_boundaries": dict(lens=(16, 17, 32, 33), maxb=4),
    # a table not a multiple of the 16 pages a step takes: two steps, the
    # second 5 entries long
    "odd_max_blocks": dict(lens=(40, 20, 300, 10), maxb=21),
    # the agent cell's widths (8 kv heads of 128, 4 queries each), rows
    # of unequal length and one free row
    "agent_shapes": dict(lens=(300, 37, 129, 0, 64, 1), maxb=21, kh=8, g=4,
                         hd=128),
}


@pytest.mark.parametrize("case", list(WALK_CASES))
def test_paged_kernel_walk_stops_at_last_live_page(case):
    c = dict(kh=2, g=2, hd=32) | WALK_CASES[case]
    lens, page = c["lens"], 16
    rng = np.random.default_rng(len(case))
    kc, ks, vc, vs, pool_pos, bt = _pool_fixture(
        rng, p=2 + sum(-(-n // page) for n in lens), kh=c["kh"], hd=c["hd"],
        lens=lens, maxb=c["maxb"])
    q_pos = np.asarray([n - 1 for n in lens], np.int32)
    live = live_pages(q_pos, page, c["maxb"])
    np.testing.assert_array_equal(live, [-(-n // page) for n in lens])
    clean = np.asarray(bt)
    poisoned = clean.copy()
    for r in range(len(lens)):
        poisoned[r, live[r]:] = clean[(r + 1) % len(lens), 0] or clean[0, 0]
    q = jnp.asarray(rng.normal(size=(len(lens), c["kh"], c["g"], c["hd"])),
                    jnp.float32)
    args = (q, kc, ks, vc, vs, pool_pos)
    qp = jnp.asarray(q_pos)
    got = np.asarray(paged_decode_attention(*args, jnp.asarray(poisoned), qp))
    want = np.asarray(ref.paged_decode_attention_ref(
        *args, jnp.asarray(clean), qp))
    seen = q_pos >= 0  # the oracle does not model a free row
    np.testing.assert_allclose(got[seen], want[seen], rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(got[~seen], 0.0)
    # the poison is live: read past the last live page, it changes rows
    wrong = np.asarray(ref.paged_decode_attention_ref(
        *args, jnp.asarray(poisoned), qp))
    for r in np.flatnonzero(seen & (live < c["maxb"])):
        assert np.max(np.abs(wrong[r] - want[r])) > 1e-3, (case, r)


def test_paged_kernel_all_rows_free_read_nothing():
    """Every row free (q_pos = -1) over a table still pointing at live
    pages: the walk covers no entry and every row is exact zeros."""
    rng = np.random.default_rng(9)
    kc, ks, vc, vs, pool_pos, bt = _pool_fixture(rng)
    q = jnp.asarray(rng.normal(size=(3, 2, 2, 32)), jnp.float32)
    q_pos = np.full((3,), -1, np.int32)
    assert live_pages(q_pos, 16, bt.shape[1]).sum() == 0
    out = paged_decode_attention(q, kc, ks, vc, vs, pool_pos, bt,
                                 jnp.asarray(q_pos))
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_pages_per_step_and_live_pages():
    assert pages_per_step(16, 192) == 16
    assert pages_per_step(16, 3) == 3
    assert pages_per_step(64, 192) == 4
    assert pages_per_step(512, 192) == 1
    np.testing.assert_array_equal(
        live_pages(np.asarray([-1, 0, 15, 16, 31, 32, 10_000]), 16, 192),
        [0, 1, 1, 2, 2, 3, 192])


# -------------------------------------------------- model-level parity


def test_ragged_paged_prefill_and_decode_match_dense_per_request():
    """Acceptance: a ragged batch of 3 requests with unequal prompt lengths
    through paged_prefill + paged_decode_step matches the dense quantized
    per-request path — prefill logits BIT-exactly (same math, the pool only
    re-addresses the writes), decode within fp-reassociation tolerance of
    the page walk, and greedy argmax identically."""
    cfg = get_config("llama2-7b").tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    lens = [5, 8, 3]
    r, s_pad = len(lens), max(lens)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)) for n in lens]
    tokens = np.zeros((r, s_pad), np.int32)
    posn = np.full((r, s_pad), -1, np.int32)
    for i, p in enumerate(prompts):
        tokens[i, s_pad - p.size:] = p
        posn[i, s_pad - p.size:] = np.arange(p.size)

    pool = PagedKVPool(cfg, num_pages=16, page_size=4, max_requests=r)
    slots = [pool.admit(n) for n in lens]
    logits, caches = paged_prefill(params, cfg, jnp.asarray(tokens),
                                   pool.device_caches(rows=slots),
                                   jnp.asarray(posn), OPTS_Q)
    pool.update_from(caches)
    for slot, n in zip(slots, lens):
        pool.commit_prefill(slot, n)

    nxt = np.asarray(jnp.argmax(logits, axis=-1))[:, None].astype(np.int32)
    pos = np.asarray(lens, np.int32)
    for slot in slots:
        pool.append(slot, 1)
    logits2, caches2 = paged_decode_step(params, cfg, jnp.asarray(nxt),
                                         pool.device_caches(),
                                         jnp.asarray(pos), OPTS_Q)

    for i, p in enumerate(prompts):
        want, dense_caches = prefill(params, cfg, jnp.asarray(p[None]), None,
                                     16, OPTS_Q)
        np.testing.assert_array_equal(np.asarray(logits[i]),
                                      np.asarray(want[0]))  # bit-exact
        want2, _ = decode_step(params, cfg, jnp.asarray(nxt[i][None]),
                               dense_caches, jnp.int32(lens[i]), OPTS_Q)
        np.testing.assert_allclose(np.asarray(logits2[i]), np.asarray(want2[0]),
                                   rtol=2e-4, atol=2e-4)
        assert int(jnp.argmax(logits2[i])) == int(jnp.argmax(want2[0]))
