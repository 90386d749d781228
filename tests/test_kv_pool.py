"""Paged KV pool: block allocator semantics (alloc/free/LIFO reuse,
exhaustion, page-boundary appends), uniform-page validation, occupancy
accounting against Eq. 2, the property that block-table gather of pool
pages reconstructs the dense quantized cache bit-exactly, and the
refcounted copy-on-write ownership model (share_prefix/fork, CoW on append
into a shared page, double-free protection, randomized invariant walk)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.decode_attention import padded_cache_len
from repro.models import layers as L
from repro.serving.kv_pool import PagedKVPool, PoolExhaustedError

CFG = get_config("llama2-7b").tiny()


def make_pool(num_pages=16, page_size=4, max_requests=3, **kw):
    return PagedKVPool(CFG, num_pages=num_pages, page_size=page_size,
                       max_requests=max_requests, **kw)


# ------------------------------------------------------------- allocator


def test_alloc_free_reuse_ordering():
    pool = make_pool()
    a = pool.admit(6)  # 2 pages
    b = pool.admit(4)  # 1 page
    pages_a = [p for p in pool.block_tables[a] if p != 0]
    pages_b = [p for p in pool.block_tables[b] if p != 0]
    assert len(pages_a) == 2 and len(pages_b) == 1
    assert not set(pages_a) & set(pages_b)  # disjoint
    assert 0 not in pages_a + pages_b  # trash page never handed out
    used = pool.pages_in_use
    pool.free(a)
    assert pool.pages_in_use == used - 2
    # LIFO reuse: the next admit gets a's just-freed pages back, most
    # recently freed first
    c = pool.admit(8)  # 2 pages
    pages_c = [p for p in pool.block_tables[c] if p != 0]
    assert set(pages_c) == set(pages_a)


def test_pool_exhaustion_raises():
    pool = make_pool(num_pages=4, page_size=4, max_requests=4)  # 3 usable
    pool.admit(12)  # takes all 3 pages
    with pytest.raises(PoolExhaustedError, match="exhausted"):
        pool.admit(4)
    assert not pool.can_admit(4)


def test_slot_exhaustion_raises():
    pool = make_pool(num_pages=16, page_size=4, max_requests=1)
    pool.admit(4)
    with pytest.raises(PoolExhaustedError, match="slots"):
        pool.admit(4)


def test_append_across_page_boundary():
    pool = make_pool(page_size=4)
    slot = pool.admit(4)  # exactly one page
    pool.commit_prefill(slot, 4)
    before = pool.pages_in_use
    pool.append(slot, 1)  # crosses into a second page
    assert pool.pages_in_use == before + 1
    assert int(pool.lengths[slot]) == 5
    pool.append(slot, 1)  # stays inside the second page
    assert pool.pages_in_use == before + 1
    # growing past max_blocks is a clean error, not silent corruption
    small = make_pool(num_pages=16, page_size=4, max_seq_len=8)
    s = small.admit(8)
    small.commit_prefill(s, 8)
    with pytest.raises(PoolExhaustedError, match="max_blocks"):
        small.append(s, 1)


def test_free_scrubs_positions_on_device():
    pool = make_pool(page_size=4)
    slot = pool.admit(4)
    page = int(pool.block_tables[slot][0])
    # simulate a written page: stored positions >= 0
    pool._caches = tuple(
        type(c)(c.k, c.v, c.k_scale, c.v_scale,
                c.pos.at[:, page].set(jnp.arange(4, dtype=jnp.int32)),
                c.block_table)
        for c in pool._caches)
    pool.free(slot)
    for c in pool._caches:
        assert int(jnp.max(c.pos[:, page])) == -1  # stale tokens unreachable


# ---------------------------------------------------- uniform-page contract


def test_padded_cache_len_uniform_flag():
    # dense contract: short lengths stay unpadded (single clamped block)
    assert padded_cache_len(40, 512) == 40
    assert padded_cache_len(600, 512) == 1024
    # pool contract: every length rounds to whole uniform pages
    assert padded_cache_len(40, 512, uniform=True) == 512
    assert padded_cache_len(512, 512, uniform=True) == 512
    assert padded_cache_len(600, 512, uniform=True) == 1024


def test_pool_rejects_bad_page_sizes():
    with pytest.raises(ValueError, match="positive"):
        make_pool(page_size=0)
    with pytest.raises(ValueError, match="reserved"):
        make_pool(num_pages=1)
    pool = make_pool(page_size=4)
    bad = tuple(
        type(c)(c.k[..., :3, :], c.v[..., :3, :], c.k_scale[..., :3],
                c.v_scale[..., :3], c.pos[..., :3], c.block_table)
        for c in pool._caches)
    with pytest.raises(ValueError, match="non-uniform page"):
        pool.update_from(bad)
    # a page dim that IS a multiple of page_size but not equal is still wrong
    doubled = tuple(
        type(c)(jnp.concatenate([c.k, c.k], axis=-2),
                jnp.concatenate([c.v, c.v], axis=-2),
                jnp.concatenate([c.k_scale, c.k_scale], axis=-1),
                jnp.concatenate([c.v_scale, c.v_scale], axis=-1),
                jnp.concatenate([c.pos, c.pos], axis=-1), c.block_table)
        for c in pool._caches)
    with pytest.raises(ValueError, match="non-uniform page size"):
        pool.update_from(doubled)


def test_pool_rejects_sliding_window_patterns():
    gemma = get_config("gemma2-2b").tiny()  # local/global alternation
    with pytest.raises(NotImplementedError, match="sliding-window"):
        PagedKVPool(gemma, num_pages=8, page_size=4, max_requests=1)


# ------------------------------------------------------------- accounting


def test_occupancy_and_eq2_accounting():
    pool = make_pool(num_pages=9, page_size=4)  # 8 usable pages
    assert pool.occupancy() == 0.0 and pool.eq2_bytes() == 0
    slot = pool.admit(6)  # 2 pages
    pool.commit_prefill(slot, 6)
    assert pool.occupancy() == pytest.approx(2 / 8)
    eq2 = pool.eq2_bytes()
    paged = pool.page_bytes_in_use()
    assert eq2 > 0 and paged > 0
    # page granularity over-allocates vs the analytical Eq. 2 bytes
    # (internal fragmentation: 8 slots held for 6 tokens)
    assert paged > eq2 * 0.5  # same order of magnitude
    pool.free(slot)
    assert pool.occupancy() == 0.0 and pool.eq2_bytes() == 0


def test_paged_update_routes_out_of_table_positions_to_trash():
    """A position past the block table's reach, or one whose table entry is
    still unallocated (caller skipped the host-side append), must behave
    like a pad — never overwrite a live page slot, and never store a real
    position on the shared trash page (cross-request leak)."""
    pool = make_pool(num_pages=8, page_size=4, max_requests=1,
                     max_seq_len=8)  # max_blocks = 2
    slot = pool.admit(4)  # one page allocated; table entry 1 stays 0
    cache = jax.tree_util.tree_map(lambda a: a[0],
                                   pool.device_caches(rows=[slot])[0])
    kv = jnp.ones((1, 4, CFG.pattern[0].mixer.num_kv_heads,
                   CFG.pattern[0].mixer.head_dim), jnp.float32)
    cache = L.paged_cache_update(cache, kv, kv,
                                 jnp.asarray([[0, 1, 2, 3]], jnp.int32))
    live = np.asarray(cache.pos[int(pool.block_tables[slot][0])]).copy()
    one = jnp.ones((1, 1) + kv.shape[2:], jnp.float32)
    # position 9 exceeds max_blocks * page = 8; position 5 is in reach but
    # its table entry is unallocated (0) — both must leave live pages and
    # the trash page's -1 positions untouched
    for bad_pos in (9, 5):
        cache = L.paged_cache_update(cache, one, one,
                                     jnp.asarray([[bad_pos]], jnp.int32))
        np.testing.assert_array_equal(
            np.asarray(cache.pos[int(pool.block_tables[slot][0])]), live)
        assert int(jnp.max(cache.pos[0])) == -1  # trash page stays masked


# ---------------------------------------- gather reconstructs dense cache


@pytest.mark.parametrize("lens", [(5, 8, 3), (4, 4, 4), (1, 9, 2)])
def test_gather_reconstructs_dense_cache_bit_exact(lens):
    """Property: writing a ragged batch through paged_cache_update and
    gathering each request's pages by its block table must reproduce the
    dense quantized cache of the same tokens BIT-exactly (same per-token
    quantization, different addressing only)."""
    spec = CFG.pattern[0].mixer
    kh, hd = spec.num_kv_heads, spec.head_dim
    page = 4
    pool = make_pool(num_pages=16, page_size=page)
    rng = np.random.default_rng(sum(lens))
    r, s_pad = len(lens), max(lens)
    kv = rng.normal(size=(r, s_pad, kh, hd)).astype(np.float32)

    slots = [pool.admit(n) for n in lens]
    posn = np.full((r, s_pad), -1, np.int32)
    for i, n in enumerate(lens):  # right-aligned ragged positions
        posn[i, s_pad - n:] = np.arange(n)
    caches = pool.device_caches(rows=slots)
    updated = tuple(
        L.paged_cache_update(
            jax.tree_util.tree_map(lambda a: a[0], c),
            jnp.asarray(kv), jnp.asarray(kv), jnp.asarray(posn))
        for c in caches)
    # write back with the nb axis restored (nb=2 identical layer slices)
    pool.update_from(tuple(
        jax.tree_util.tree_map(lambda a: jnp.stack([a] * pool.nb), u)
        for u in updated))
    for i, (slot, n) in enumerate(zip(slots, lens)):
        pool.commit_prefill(slot, n)

    for i, (slot, n) in enumerate(zip(slots, lens)):
        # dense reference: same tokens through the dense quantized cache
        dense = L.init_cache(1, n, kh, hd, quantized=True)
        valid = kv[i, s_pad - n:][None]  # (1, n, K, hd)
        dense = L.cache_update(dense, jnp.asarray(valid), jnp.asarray(valid),
                               jnp.int32(0))
        got = pool.gather_dense(slot)[0]  # pattern position 0
        gk, gv, gks, gvs, gpos = (np.asarray(x[0]) for x in got)
        order = np.argsort(np.asarray(gpos))  # gather is block-table order
        keep = np.asarray(gpos) >= 0
        assert keep.sum() == n
        sl = order[-n:]  # the n valid slots, position-sorted
        np.testing.assert_array_equal(gk[:, sl], np.asarray(dense.k[0]))
        np.testing.assert_array_equal(gv[:, sl], np.asarray(dense.v[0]))
        np.testing.assert_array_equal(gks[:, sl], np.asarray(dense.k_scale[0]))
        np.testing.assert_array_equal(gvs[:, sl], np.asarray(dense.v_scale[0]))
        np.testing.assert_array_equal(np.asarray(gpos)[sl], np.arange(n))


# --------------------------------------------- refcounts / CoW / prefixes


def test_share_prefix_fork_refcounts_and_cow():
    """Fork onto a 6-token prefix (page 4 → partial boundary page): shared
    full page aliased, boundary page CoW-copied, refcounts track every
    owner, and pages only return to the free list at refcount zero."""
    pool = make_pool(num_pages=16, page_size=4)
    a = pool.admit(6)
    pool.commit_prefill(a, 6)
    h = pool.share_prefix(a, 6)
    p0, p1 = h.pages
    assert pool.refcount[p0] == 2 and pool.refcount[p1] == 2  # slot + handle
    assert pool.pages_shared == 2

    b = pool.admit(8, prefix=h)
    assert int(pool.lengths[b]) == 6  # prefix tokens already resident
    tb = pool.block_tables[b]
    assert tb[0] == p0  # full prefix page aliased, not copied
    cow = int(tb[1])
    assert cow not in (p1, 0)  # boundary page copy-on-write
    assert pool.refcount[p0] == 3  # a + handle + b
    assert pool.refcount[p1] == 2  # a + handle (b dropped it for the copy)
    assert pool.refcount[cow] == 1
    assert pool.pages_in_use == 3  # p0, p1, cow — shared counted once

    pool.free(a)
    assert pool.refcount[p0] == 2 and pool.refcount[p1] == 1  # handle holds
    pool.free(b)
    assert pool.refcount[p0] == 1 and pool.refcount[cow] == 0
    pool.release_prefix(h)
    assert pool.pages_in_use == 0
    assert int(pool.refcount.sum()) == 0
    pool.release_prefix(h)  # idempotent


def test_cow_copy_scrubs_foreign_positions():
    """The CoW copy keeps only positions < the forker's length: the
    creator's tokens past the shared prefix are scrubbed to -1 in the copy
    so they can never leak into the fork's attention."""
    pool = make_pool(num_pages=16, page_size=4)
    a = pool.admit(8)
    pool.commit_prefill(a, 8)  # creator wrote positions 0..7 (2 pages)
    p1 = int(pool.block_tables[a][1])
    # simulate device contents of the boundary page: positions 4..7
    pool._caches = tuple(
        type(c)(c.k, c.v, c.k_scale, c.v_scale,
                c.pos.at[:, p1].set(jnp.arange(4, 8, dtype=jnp.int32)),
                c.block_table)
        for c in pool._caches)
    h = pool.share_prefix(a, 6)  # prefix covers positions 0..5 only
    b = pool.admit(7, prefix=h)
    cow = int(pool.block_tables[b][1])
    for c in pool._caches:
        got = np.asarray(c.pos[:, cow, 0])
        np.testing.assert_array_equal(got, np.tile([4, 5, -1, -1],
                                                   (pool.nb, 1)))
        # the original page is untouched
        np.testing.assert_array_equal(np.asarray(c.pos[:, p1, 0]),
                                      np.tile([4, 5, 6, 7], (pool.nb, 1)))


def test_cow_on_append_into_shared_page():
    """The CREATOR side of CoW: once its boundary page is pinned by a
    shared prefix, the creator's own append must copy before writing."""
    pool = make_pool(num_pages=16, page_size=4)
    a = pool.admit(6)
    pool.commit_prefill(a, 6)
    h = pool.share_prefix(a, 6)
    p1 = int(pool.block_tables[a][1])
    pool.append(a, 1)  # next write lands in the shared boundary page
    new = int(pool.block_tables[a][1])
    assert new != p1
    assert pool.refcount[p1] == 1  # handle only
    assert pool.refcount[new] == 1
    assert int(pool.lengths[a]) == 7


def test_aligned_prefix_forks_without_cow():
    """A page-aligned prefix needs no boundary copy: the fork's first write
    lands in a fresh page."""
    pool = make_pool(num_pages=16, page_size=4)
    a = pool.admit(8)
    pool.commit_prefill(a, 8)
    h = pool.share_prefix(a, 8)  # exactly 2 full pages
    before = pool.pages_in_use
    b = pool.admit(9, prefix=h)
    assert pool.pages_in_use == before + 1  # one suffix page, zero copies
    assert tuple(pool.block_tables[b][:2]) == h.pages


def test_fork_admission_is_atomic_on_exhaustion():
    """A fork that cannot afford its CoW + suffix pages raises BEFORE any
    state changes — refcounts, tables and the free list stay intact."""
    pool = make_pool(num_pages=4, page_size=4, max_requests=3)  # 3 usable
    a = pool.admit(6)  # 2 pages
    pool.commit_prefill(a, 6)
    h = pool.share_prefix(a, 6)
    rc = pool.refcount.copy()
    free = list(pool._free)
    with pytest.raises(PoolExhaustedError, match="fork needs"):
        pool.admit(10, prefix=h)  # wants CoW + 1 suffix page, only 1 free
    np.testing.assert_array_equal(rc, pool.refcount)
    assert pool._free == free
    assert not pool.active[1:].any()


def test_double_free_is_an_assert_never_silent_reuse():
    pool = make_pool()
    a = pool.admit(4)
    pool.free(a)
    with pytest.raises(AssertionError, match="not active"):
        pool.free(a)
    with pytest.raises(AssertionError, match="double free"):
        pool._decref([int(pool._free[-1])])


def test_page_bytes_written_counts_shared_pages_once():
    pool = make_pool(num_pages=16, page_size=4)
    a = pool.admit(8)
    pool.commit_prefill(a, 8)
    solo = pool.page_bytes_written()
    assert solo == 2 * pool.page_bytes()
    h = pool.share_prefix(a, 8)
    b = pool.admit(9, prefix=h)
    pool.commit_prefill(b, 9)
    # a holds pages {p0,p1}; b holds {p0,p1,s} — shipment moves 3 pages,
    # not 5 (the shared prefix crosses the uplink once)
    assert pool.page_bytes_written() == 3 * pool.page_bytes()
    # the logical per-request Eq. 2 total keeps double-counting (8 + 9
    # tokens): the gap vs page bytes IS the sharing win
    assert pool.eq2_bytes() > pool.page_bytes_written() * 0  # sanity
    pool.release_prefix(h)


# ------------------------------------------------- speculative KV rollback


def test_truncate_rolls_back_rejected_tail():
    """The speculative-rollback primitive: truncate scrubs stored positions
    >= new_len on device (a rejected draft token can never be attended or
    swapped out), keeps the pages allocated (they sit inside the slot's
    reservation; the next append rewrites the same page slots), and leaves
    everything below the cut untouched."""
    pool = make_pool(num_pages=16, page_size=4)
    s = pool.admit(6)            # pages p0 (pos 0..3), p1 (pos 4..5)
    pool.commit_prefill(s, 6)
    pool.append(s, 3)            # draft burst: pos 6..8 — p1 fills, p2 opens
    p0, p1, p2 = (int(p) for p in pool.block_tables[s][:3])
    pool._caches = tuple(
        type(c)(c.k, c.v, c.k_scale, c.v_scale,
                c.pos.at[:, p0].set(jnp.arange(4, dtype=jnp.int32))
                     .at[:, p1].set(jnp.arange(4, 8, dtype=jnp.int32))
                     .at[:, p2].set(jnp.asarray([8, -1, -1, -1], jnp.int32)),
                c.block_table)
        for c in pool._caches)
    used = pool.pages_in_use
    pool.truncate(s, 6)          # verify rejected the whole 3-token draft
    assert int(pool.lengths[s]) == 6
    assert pool.pages_in_use == used  # rollback never frees pages
    for c in pool._caches:
        np.testing.assert_array_equal(
            np.asarray(c.pos[:, p0, 0]), np.tile(np.arange(4), (pool.nb, 1)))
        np.testing.assert_array_equal(
            np.asarray(c.pos[:, p1, 0]), np.tile([4, 5, -1, -1], (pool.nb, 1)))
        assert int(jnp.max(c.pos[:, p2])) == -1
    # bounds: rolling back to zero or past the length is a caller bug
    with pytest.raises(ValueError, match="outside"):
        pool.truncate(s, 0)
    with pytest.raises(ValueError, match="outside"):
        pool.truncate(s, 7)


def test_truncate_refuses_to_scrub_shared_pages():
    """A rollback that would reach into a refcount > 1 page is a caller
    bug — shared prefix tokens are immutable. The refusal must leave pool
    state AND the shared page's device positions untouched; once the other
    reference drops, the same rollback proceeds."""
    pool = make_pool(num_pages=16, page_size=4)
    a = pool.admit(8)
    pool.commit_prefill(a, 8)
    p1 = int(pool.block_tables[a][1])
    pool._caches = tuple(
        type(c)(c.k, c.v, c.k_scale, c.v_scale,
                c.pos.at[:, p1].set(jnp.arange(4, 8, dtype=jnp.int32)),
                c.block_table)
        for c in pool._caches)
    h = pool.share_prefix(a, 8)  # p1 now refcount 2
    before_ref = pool.refcount.copy()
    before_len = pool.lengths.copy()
    with pytest.raises(ValueError, match="shared page"):
        pool.truncate(a, 6)      # the cut lands inside the shared page
    np.testing.assert_array_equal(pool.refcount, before_ref)
    np.testing.assert_array_equal(pool.lengths, before_len)
    for c in pool._caches:
        np.testing.assert_array_equal(np.asarray(c.pos[:, p1, 0]),
                                      np.tile([4, 5, 6, 7], (pool.nb, 1)))
    pool.release_prefix(h)
    pool.truncate(a, 6)          # exclusive again → rollback proceeds
    for c in pool._caches:
        np.testing.assert_array_equal(np.asarray(c.pos[:, p1, 0]),
                                      np.tile([4, 5, -1, -1], (pool.nb, 1)))


# --------------------------------------------------- randomized invariants


def _check_pool_invariants(pool, handles):
    """The ownership-model invariants the docstring promises: refcounts
    equal the live references (block-table entries of active slots + unreleased
    handles), the free list is duplicate-free and disjoint from live pages,
    every page is accounted for, and physical residency never exceeds the
    pool."""
    refs = np.zeros((pool.num_pages,), np.int64)
    for slot in np.flatnonzero(pool.active):
        for p in pool.block_tables[slot]:
            if p != 0:
                refs[p] += 1
    for h in handles:
        if not h.released:
            for p in h.pages:
                refs[p] += 1
    np.testing.assert_array_equal(refs, pool.refcount)
    free = pool._free
    assert len(set(free)) == len(free), "free list holds duplicates"
    assert all(pool.refcount[p] == 0 for p in free), "free list holds live pages"
    live = {p for p in range(1, pool.num_pages) if pool.refcount[p] > 0}
    assert live | set(free) == set(range(1, pool.num_pages)), "page leaked"
    assert pool.pages_in_use <= pool.num_pages - 1
    assert pool.page_bytes_in_use() <= (pool.num_pages - 1) * pool.page_bytes()
    for slot in np.flatnonzero(pool.active):
        npages = int(np.count_nonzero(pool.block_tables[slot]))
        assert npages >= pool.pages_for(max(1, int(pool.lengths[slot])))
    # the per-tick telemetry gauges are views of the same counters — they
    # must agree with the allocator state at every step of the walk
    g = pool.gauges()
    assert g["pages_in_use"] == pool.pages_in_use
    assert g["pages_shared"] == int(np.count_nonzero(pool.refcount > 1))
    assert g["pages_free"] == pool.free_pages
    assert g["pages_in_use"] + g["pages_free"] == pool.num_pages - 1
    assert g["swap_bytes"] >= 0
    assert g["page_bytes_in_use"] == pool.page_bytes_in_use()
    assert 0.0 <= g["occupancy"] <= 1.0


def test_property_random_admit_fork_append_preempt_free_never_corrupts():
    """Random walk over the full allocator API — admit / share / fork /
    append / preempt-style free / release / speculative truncate-rollback —
    holding every refcount invariant at each step. This is the double-free /
    leak / over-capacity property test for the CoW ownership model; the
    truncate op additionally pins that a rollback never mutates a
    refcount > 1 page (the refusal is atomic)."""
    rng = np.random.default_rng(12345)
    pool = make_pool(num_pages=20, page_size=4, max_requests=5)
    handles: list = []
    for step in range(250):
        op = rng.integers(0, 6)
        active = list(np.flatnonzero(pool.active))
        try:
            if op == 0:  # admit, sometimes onto a random live prefix
                live_handles = [h for h in handles if not h.released]
                if live_handles and rng.random() < 0.5:
                    h = live_handles[rng.integers(len(live_handles))]
                    n = h.n_tokens + int(rng.integers(1, 9))
                    s = pool.admit(n, prefix=h)
                else:
                    n = int(rng.integers(1, 17))
                    s = pool.admit(n)
                pool.commit_prefill(s, n)
            elif op == 1 and active:  # share a prefix of a live request
                s = active[rng.integers(len(active))]
                length = int(pool.lengths[s])
                if length >= 2:
                    n = int(rng.integers(1, length))
                    handles.append(pool.share_prefix(s, n))
            elif op == 2 and active:  # decode growth
                s = active[rng.integers(len(active))]
                pool.append(s, int(rng.integers(1, 4)))
            elif op == 3 and active:  # finish / preempt: both are free()
                s = active[rng.integers(len(active))]
                pool.free(s)
            elif op == 4 and handles:  # registry drops a prefix
                h = handles[rng.integers(len(handles))]
                pool.release_prefix(h)
            elif op == 5 and active:  # speculative rollback: truncate a tail
                s = active[rng.integers(len(active))]
                length = int(pool.lengths[s])
                new_len = int(rng.integers(1, length + 1))
                before = (pool.refcount.copy(), pool.lengths.copy(),
                          np.asarray(pool.block_tables).copy())
                try:
                    pool.truncate(s, new_len)
                    assert int(pool.lengths[s]) == new_len
                except ValueError:
                    # the cut reached a CoW-shared page: refused atomically —
                    # refcounts, lengths and block tables must be untouched
                    np.testing.assert_array_equal(pool.refcount, before[0])
                    np.testing.assert_array_equal(pool.lengths, before[1])
                    np.testing.assert_array_equal(
                        np.asarray(pool.block_tables), before[2])
        except PoolExhaustedError:
            pass  # backpressure is a legal outcome; state must be unchanged
        _check_pool_invariants(pool, handles)
    # drain: everything returns, nothing double-frees, nothing leaks
    for s in list(np.flatnonzero(pool.active)):
        pool.free(s)
    for h in handles:
        pool.release_prefix(h)
    _check_pool_invariants(pool, handles)
    assert pool.pages_in_use == 0 and pool.free_pages == pool.num_pages - 1
