"""Paged KV-cache pool: one shared block pool, per-request block tables,
refcounted copy-on-write pages for multi-tenant prefix sharing.

The paper's serving constraint is Eq. (2) — the KV cache is the term that
grows with every generated token — and the dense per-request cache the seed
engine allocates wastes exactly the memory the optimizer is trying to
budget: every request holds ``cache_len`` slots regardless of its actual
length, and a batch must be bucketed to equal prompt lengths to share the
allocation. This module replaces that with the vLLM-style design: a single
fixed-size pool of ``page_size``-token pages (int8 codes + f32 scales per
page, ``kv_pos = -1`` marking empty slots), an allocator with free-list
reuse, and per-request block tables ``(R, max_blocks) int32`` that the
paged decode-attention kernel walks via its scalar-prefetch index map.

Layout per pattern position (leading ``num_blocks`` axis consumed by the
transformer's block scan, exactly like the dense caches):

  k / v          (nb, P, K, page, hd) int8
  k/v_scale      (nb, P, K, 1, page)  f32
  pos            (nb, P, 1, page)     int32   (-1 = empty)
  block_table    (nb, R, max_blocks)  int32   (host-owned, installed per call)

(The unit axis before ``page`` keeps the kernels' per-page blocks whole in
their last two dimensions, which the TPU compiler requires.)

Page 0 is RESERVED as the trash page: block-table entries of inactive rows
and pad-token writes point at it, its positions stay -1, and the kernel's
validity mask keeps it out of every softmax. The allocator therefore hands
out pages [1, P).

Ownership model (the refcount state machine):

  Every non-trash page carries a host-side refcount. A reference is held by
  (a) each active slot whose block table names the page, and (b) each live
  :class:`SharedPrefix` handle that pins it. Pages move through exactly
  three states::

      free ──admit/append──▶ owned (refcount 1)
      owned ──share_prefix / admit(prefix=…)──▶ shared (refcount ≥ 2)
      shared ──decref──▶ owned ──decref──▶ free (positions scrubbed)

  Writes are only legal into pages the writer owns EXCLUSIVELY (refcount 1
  through its own table entry). ``reserve_write`` enforces this with
  copy-on-write: when the next token would land in a shared page, the page
  is copied on device to a fresh page (stored positions ≥ the writer's
  length scrubbed to -1, so another tenant's tokens can never leak into
  the copy), the writer's table entry is repointed, and the shared page is
  decref'd. Freeing is always a decref; only a page reaching refcount 0 is
  scrubbed and returned to the free list — so a double free is an assert,
  never silent reuse.

Lifecycle (driven by ``serving.scheduler``):
  admit        — reserve a slot row + pages for the prompt (and optionally a
                 worst-case ``reserve_tokens``); with ``prefix=`` the slot
                 attaches to a shared prefix's pages instead of allocating
  share_prefix — pin a slot's leading pages as a :class:`SharedPrefix` that
                 outlives the slot (system prompts, beams)
  append       — extend a live request's page list when its length crosses a
                 page boundary, CoW-copying a shared boundary page first
                 (raises ``PoolExhaustedError`` when the pool is full — the
                 scheduler's backpressure/preemption signal)
  free         — decref a finished request's pages; pages reaching zero are
                 scrubbed (-1 positions) on device and returned LIFO

Occupancy is accounted two ways: ``page_bytes_in_use`` (page-granular, what
the device actually holds — internal fragmentation included, shared pages
counted ONCE) and ``eq2_bytes`` (the paper's analytical B_kv via
``core.opsc.kv_cache_bytes`` summed PER REQUEST at the pool's int8
activation width). The gap between them is the paging overhead minus the
sharing win: with prefix sharing, ``eq2_bytes`` double-counts the shared
tokens that the pool physically holds once (``core.opsc.
kv_cache_bytes_shared`` is the sharing-aware analytical model).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, AttnSpec
from repro.models.layers import PagedKVCache

TRASH_PAGE = 0
DEFAULT_PAGE_SIZE = 16


class PoolExhaustedError(RuntimeError):
    """Raised when an admit/append needs more pages than the pool has free."""


def uniform_page_count(seq_len: int, page_size: int) -> int:
    """Pages needed to hold ``seq_len`` tokens in UNIFORM ``page_size`` pages
    (``kernels.decode_attention.padded_cache_len(s, page_size, uniform=True)``
    is the same rounding in token units)."""
    return max(1, -(-seq_len // page_size))


def page_bytes(cfg: ArchConfig, page_size: int = DEFAULT_PAGE_SIZE,
               num_blocks: int | None = None) -> int:
    """Device BYTES of ONE page across every attention layer of ``cfg``
    (int8 k/v codes, f32 per-token scales, int32 positions) — what sizes a
    pool to the memory the weights leave."""
    m = cfg.pattern[0].mixer
    kh, hd, ps = m.num_kv_heads, m.head_dim, page_size
    nb = cfg.num_blocks if num_blocks is None else num_blocks
    per_layer = 2 * kh * ps * hd * 1 + 2 * kh * ps * 4 + ps * 4
    return per_layer * nb * len(cfg.pattern)


@dataclasses.dataclass
class SharedPrefix:
    """Handle to a pinned run of pool pages holding a shared prompt prefix.

    ``pages`` are physical page ids in position order covering the first
    ``n_tokens`` TOKENS of some prefilled request; the handle OWNS one
    refcount reference per page, so the prefix outlives the request that
    wrote it. Slots attach with ``PagedKVPool.admit(..., prefix=handle)``
    (each attachment adds one more reference per page) and the registry that
    created the handle releases it with ``PagedKVPool.release_prefix`` —
    until then the pages can never be scrubbed or reused.

    The creator must guarantee the covered tokens are (or will be, before
    any fork attends them) written: ``share_prefix`` checks page coverage,
    not device contents."""

    pages: tuple
    n_tokens: int
    released: bool = False

    @property
    def num_pages(self) -> int:
        return len(self.pages)


class PagedKVPool:
    """Fixed-size paged KV pool + host-side refcounting block allocator
    (see module doc for the ownership model).

    ``cfg`` must be an attention-only pattern without sliding windows (ring
    writes inside fixed pages are a follow-on); ``num_blocks`` overrides
    ``cfg.num_blocks`` so a split engine can pool just its cloud segment.

    ``mesh=`` (a ``("kv", "model")`` mesh from ``repro.launch.mesh.
    make_serving_mesh``) turns on sharded mode: every pool leaf's PAGE axis
    is laid out over the mesh's ``kv`` axis via ``NamedSharding`` (the page
    count is rounded up to divide evenly), block tables stay replicated,
    and the host-side allocator / refcount / CoW / truncate logic is
    byte-for-byte the single-device logic — sharding only changes WHERE
    pages live, never which request owns them.

    Units note (applies to every method): ``*_tokens``/``*_len`` arguments
    count TOKENS, ``pages_*``/``*_pages`` count fixed-size PAGES, and
    ``*_bytes`` are device bytes across every covered layer."""

    def __init__(self, cfg: ArchConfig, *, num_pages: int,
                 page_size: int = DEFAULT_PAGE_SIZE, max_requests: int,
                 max_seq_len: int | None = None, num_blocks: int | None = None,
                 mesh=None):
        if page_size <= 0:
            raise ValueError(f"page_size must be positive, got {page_size}")
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is reserved)")
        if mesh is not None:
            # sharded mode: the PAGE axis (axis 1 of every leaf) is split
            # over the mesh's "kv" axis; round the page count up so it
            # divides evenly (extra pages just enlarge the free list)
            kv_size = mesh.shape["kv"]
            num_pages = -(-num_pages // kv_size) * kv_size
        self.specs = []
        for ls in cfg.pattern:
            m = ls.mixer
            if not isinstance(m, AttnSpec):
                raise NotImplementedError(
                    "PagedKVPool covers attention-only patterns; SSM/hybrid "
                    f"states are fixed-size (no paging needed), got {m.kind}")
            if m.sliding_window is not None:
                raise NotImplementedError(
                    "sliding-window layers ring-write inside their window; "
                    "paged ring-append is not supported yet")
            self.specs.append(m)
        if len({(m.num_kv_heads, m.head_dim) for m in self.specs}) != 1:
            raise NotImplementedError(
                "pattern positions must share (num_kv_heads, head_dim)")

        self.cfg = cfg
        self.nb = cfg.num_blocks if num_blocks is None else num_blocks
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_requests = max_requests
        max_seq_len = (num_pages - 1) * page_size if max_seq_len is None \
            else max_seq_len
        self.max_blocks = uniform_page_count(max_seq_len, page_size)
        self.num_layers = self.nb * len(cfg.pattern)

        kh, hd = self.specs[0].num_kv_heads, self.specs[0].head_dim
        self.kv_heads, self.head_dim = kh, hd
        nb, p, ps = self.nb, num_pages, page_size
        self._caches = tuple(
            PagedKVCache(
                k=jnp.zeros((nb, p, kh, ps, hd), jnp.int8),
                v=jnp.zeros((nb, p, kh, ps, hd), jnp.int8),
                k_scale=jnp.zeros((nb, p, kh, 1, ps), jnp.float32),
                v_scale=jnp.zeros((nb, p, kh, 1, ps), jnp.float32),
                pos=jnp.full((nb, p, 1, ps), -1, jnp.int32),
                block_table=jnp.zeros((nb, max_requests, self.max_blocks),
                                      jnp.int32),
            )
            for _ in cfg.pattern)

        # sharded mode: pin each leaf's placement — pages split over the
        # "kv" mesh axis, block tables replicated. The allocator / refcount
        # / CoW / truncate logic below is untouched: host-driven `.at`
        # updates may produce unplaced results, so :meth:`device_caches`
        # re-applies these shardings before every jitted step (a no-op when
        # the array is already placed correctly).
        self.mesh = mesh
        self._page_sharding = self._repl_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._page_sharding = NamedSharding(mesh, P(None, "kv"))
            self._repl_sharding = NamedSharding(mesh, P())
            self._caches = tuple(self._place(c) for c in self._caches)

        # host allocator state: LIFO free list (most-recently-freed page is
        # reused first — keeps the hot pages hot), trash page 0 excluded,
        # and per-page refcounts (0 = free, 1 = exclusively owned,
        # >= 2 = shared / copy-on-write)
        self._free = list(range(num_pages - 1, 0, -1))
        self.refcount = np.zeros((num_pages,), np.int32)
        self.block_tables = np.zeros((max_requests, self.max_blocks), np.int32)
        self.lengths = np.zeros((max_requests,), np.int64)
        self.active = np.zeros((max_requests,), bool)
        # host BYTES held by live export_slot snapshots (the scheduler's
        # swap-resume preemption): export adds, restore/discard subtracts
        self.swap_bytes = 0

    # ------------------------------------------------------------ allocator

    @property
    def free_pages(self) -> int:
        """Count of PAGES currently on the free list."""
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        """Count of allocated PAGES — each shared page counts ONCE (physical
        residency, not the sum of logical references)."""
        return (self.num_pages - 1) - len(self._free)

    @property
    def pages_shared(self) -> int:
        """Count of PAGES currently referenced by more than one owner."""
        return int(np.sum(self.refcount > 1))

    def pages_for(self, n_tokens: int) -> int:
        """PAGES needed to hold ``n_tokens`` TOKENS (≥ 1)."""
        return uniform_page_count(n_tokens, self.page_size)

    def _alloc(self) -> int:
        """Pop one page off the free list with refcount 1 (caller has
        already checked capacity)."""
        page = self._free.pop()
        assert self.refcount[page] == 0, f"free list held live page {page}"
        self.refcount[page] = 1
        return page

    def _decref(self, pages) -> None:
        """Drop one reference per page; pages reaching zero are scrubbed on
        device (stored positions → -1, so a reusing request can never attend
        stale tokens) and returned to the free list LIFO."""
        dead = []
        for p in pages:
            p = int(p)
            assert self.refcount[p] > 0, f"double free of page {p}"
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                dead.append(p)
        if dead:
            idx = jnp.asarray(dead, jnp.int32)
            self._caches = tuple(
                dataclasses.replace(c, pos=c.pos.at[:, idx].set(-1))
                for c in self._caches)
            self._free.extend(reversed(dead))

    def _copy_page(self, src: int, dst: int, keep_below: int) -> None:
        """Copy-on-write device copy of page ``src`` → ``dst`` across every
        layer, keeping only stored positions < ``keep_below`` TOKENS (the
        forker's own history; another tenant's tokens past the shared prefix
        are scrubbed to -1 in the copy so they can never leak into the
        forker's attention)."""
        def cp(c):
            pos_src = c.pos[:, src]
            return dataclasses.replace(
                c,
                k=c.k.at[:, dst].set(c.k[:, src]),
                v=c.v.at[:, dst].set(c.v[:, src]),
                k_scale=c.k_scale.at[:, dst].set(c.k_scale[:, src]),
                v_scale=c.v_scale.at[:, dst].set(c.v_scale[:, src]),
                pos=c.pos.at[:, dst].set(
                    jnp.where(pos_src < keep_below, pos_src, -1)))

        self._caches = tuple(cp(c) for c in self._caches)

    def _write_need(self, length: int, have: int, boundary_shared: bool,
                    n_tokens: int):
        """THE growth formula, shared by :meth:`reserve_write` (actual
        writes) and :meth:`_fork_cost` (pre-attach admission check) so the
        two can never drift apart — admit's leak-free guarantee rests on
        the pre-check and the later reserve computing identical needs.
        Returns (cow_pages, new_pages, want_pages) for writing ``n_tokens``
        TOKENS past ``length`` given ``have`` allocated pages whose
        boundary page is (``boundary_shared``) refcount-shared."""
        if n_tokens <= 0:
            return 0, 0, have
        want = self.pages_for(length + n_tokens)
        boundary = length // self.page_size
        cow = 1 if (boundary < have and boundary_shared) else 0
        return cow, max(0, want - have), want

    def _fork_cost(self, prefix: SharedPrefix, target_tokens: int):
        """(pages needed from the free list NOW, eventual table pages) for
        admitting a request of ``target_tokens`` TOKENS onto ``prefix`` —
        includes the CoW copy of a partially-filled boundary page (the
        boundary is always shared at fork time: the handle plus the new
        slot both reference it)."""
        cow, new, want = self._write_need(
            prefix.n_tokens, prefix.num_pages, True,
            target_tokens - prefix.n_tokens)
        return cow + new, max(want, prefix.num_pages)

    def can_admit(self, n_tokens: int, prefix: SharedPrefix | None = None
                  ) -> bool:
        """Whether :meth:`admit` for ``n_tokens`` TOKENS (the admission
        target: prompt, or prompt + worst-case generation) would succeed."""
        if self.active.all():
            return False
        if prefix is not None:
            if prefix.released or n_tokens < prefix.n_tokens:
                return False
            need, want = self._fork_cost(prefix, n_tokens)
        else:
            need = want = self.pages_for(n_tokens)
        return need <= len(self._free) and want <= self.max_blocks

    def admit(self, prompt_len: int, reserve_tokens: int | None = None,
              prefix: SharedPrefix | None = None) -> int:
        """Reserve a slot row + pages; returns the slot index. Capacity is
        checked BEFORE any state changes, so a failed admit leaks nothing.

        ``prompt_len`` / ``reserve_tokens`` count TOKENS. ``reserve_tokens``
        reserves pages for MORE than the prompt up front (typically
        prompt + max_new_tokens — worst-case admission control): a request
        admitted this way can never hit an exhausted pool mid-decode. A
        lazily-grown request (no reserve) relies on the caller to handle
        ``PoolExhaustedError`` from :meth:`append` — e.g. the scheduler's
        preemption path.

        ``prefix`` attaches the slot to a :class:`SharedPrefix`: the slot's
        leading block-table entries alias the prefix's pages (one refcount
        reference each), its length starts at ``prefix.n_tokens``, and only
        the suffix pages (plus, for a non-page-aligned prefix, one CoW copy
        of the boundary page) are newly allocated — the physical-memory win
        of prefix sharing. The slot owns its references until :meth:`free`.
        """
        if prompt_len < 1:
            raise ValueError("cannot admit an empty prompt")
        free_slots = np.flatnonzero(~self.active)
        if free_slots.size == 0:
            raise PoolExhaustedError(
                f"no free request slots (all {self.max_requests} active)")
        target = max(prompt_len, reserve_tokens or 0)
        if prefix is not None:
            if prefix.released:
                raise ValueError("cannot admit onto a released SharedPrefix")
            if prompt_len < prefix.n_tokens:
                raise ValueError(
                    f"prompt ({prompt_len} tokens) shorter than its shared "
                    f"prefix ({prefix.n_tokens} tokens)")
            need, want = self._fork_cost(prefix, target)
            if want > self.max_blocks:
                raise PoolExhaustedError(
                    f"request needs {want} pages > max_blocks "
                    f"{self.max_blocks}")
            if need > len(self._free):
                raise PoolExhaustedError(
                    f"KV pool exhausted: fork needs {need} page(s) beyond "
                    f"the {prefix.num_pages} shared, {len(self._free)} free "
                    f"of {self.num_pages - 1}")
            slot = int(free_slots[0])
            self.active[slot] = True
            for b, p in enumerate(prefix.pages):
                self.block_tables[slot, b] = p
                self.refcount[p] += 1
            self.lengths[slot] = prefix.n_tokens
            # CoW the boundary page + allocate the suffix pages (cannot
            # raise: need was checked against the same formula above)
            self.reserve_write(slot, target - prefix.n_tokens)
            return slot
        need = self.pages_for(target)
        if need > self.max_blocks:
            raise PoolExhaustedError(
                f"prompt needs {need} pages > max_blocks {self.max_blocks}")
        if need > len(self._free):
            raise PoolExhaustedError(
                f"KV pool exhausted: prompt needs {need} page(s), "
                f"{len(self._free)} free of {self.num_pages - 1}")
        slot = int(free_slots[0])
        self.active[slot] = True
        self.lengths[slot] = 0
        self.reserve_write(slot, target)
        return slot

    def share_prefix(self, slot: int, n_tokens: int) -> SharedPrefix:
        """Pin ``slot``'s pages covering its first ``n_tokens`` TOKENS as a
        :class:`SharedPrefix` (one new refcount reference per page, owned by
        the returned handle). The pages survive ``free(slot)`` until
        :meth:`release_prefix` drops the handle's references.

        The caller guarantees those tokens are written (scheduler: share
        after ``commit_prefill``) or will be written before any fork attends
        them (split engine: rows prefill in the same device call)."""
        assert self.active[slot], f"slot {slot} is not active"
        if n_tokens < 1:
            raise ValueError("a shared prefix must cover at least one token")
        npages = self.pages_for(n_tokens)
        pages = [int(p) for p in self.block_tables[slot][:npages]]
        if TRASH_PAGE in pages:
            raise ValueError(
                f"slot {slot} has only "
                f"{int(np.count_nonzero(self.block_tables[slot]))} pages "
                f"allocated; cannot share a {n_tokens}-token prefix")
        for p in pages:
            self.refcount[p] += 1
        return SharedPrefix(tuple(pages), int(n_tokens))

    def release_prefix(self, prefix: SharedPrefix) -> None:
        """Drop the handle's page references; pages reaching refcount 0 are
        scrubbed and returned to the free list. Idempotent."""
        if prefix.released:
            return
        prefix.released = True
        self._decref(prefix.pages)

    def reserve_write(self, slot: int, n_tokens: int) -> None:
        """Make the next ``n_tokens`` TOKEN positions of ``slot`` writable
        WITHOUT changing its length: CoW-copy a shared boundary page, then
        allocate pages out to ``pages_for(length + n_tokens)``. All capacity
        checks happen before any state changes (a failed reserve leaks
        nothing — the scheduler's preempt-and-retry path depends on this).

        Callers never invoke this directly in the normal lifecycle —
        :meth:`admit` and :meth:`append` drive it — but the split engine's
        pool and tests may use it to stage capacity explicitly."""
        assert self.active[slot], f"slot {slot} is not active"
        if n_tokens <= 0:
            return
        length = int(self.lengths[slot])
        have = int(np.count_nonzero(self.block_tables[slot]))
        boundary = length // self.page_size
        boundary_shared = (
            boundary < have
            and self.refcount[self.block_tables[slot, boundary]] > 1)
        cow, new_pages, want = self._write_need(length, have,
                                                boundary_shared, n_tokens)
        if want > self.max_blocks:
            raise PoolExhaustedError(
                f"request needs {want} pages > max_blocks "
                f"{self.max_blocks} (max_seq_len too small)")
        if cow + new_pages > len(self._free):
            raise PoolExhaustedError(
                f"KV pool exhausted: slot {slot} needs {cow + new_pages} "
                f"more page(s), {len(self._free)} free of "
                f"{self.num_pages - 1}")
        if cow:
            old = int(self.block_tables[slot, boundary])
            new = self._alloc()
            self._copy_page(old, new, keep_below=length)
            self.block_tables[slot, boundary] = new
            self._decref([old])
        for b in range(have, want):
            self.block_tables[slot, b] = self._alloc()

    def commit_prefill(self, slot: int, n_tokens: int) -> None:
        """Record that the request's first ``n_tokens`` TOKENS were written
        by a prefill — pages were already reserved by ``admit`` (including
        any shared-prefix pages, which count toward ``n_tokens``), this only
        sets the length (callers must not poke ``lengths`` directly; the
        decode path's ``append`` arithmetic builds on it)."""
        assert self.active[slot], f"slot {slot} is not active"
        length = int(self.lengths[slot])
        assert length <= n_tokens, \
            f"slot {slot} already holds {length} > {n_tokens} tokens"
        if self.pages_for(n_tokens) > int(
                np.count_nonzero(self.block_tables[slot])):
            # legacy under-admitted growth: the device writes past the
            # reserved pages were routed to the trash page (lost), but the
            # accounting stays consistent
            self.reserve_write(slot, n_tokens - length)
        self.lengths[slot] = n_tokens

    def append(self, slot: int, n_tokens: int = 1) -> None:
        """Account ``n_tokens`` TOKENS about to be written to ``slot``:
        CoW-copies a shared boundary page and allocates a new page when the
        length crosses a page boundary. Raises ``PoolExhaustedError`` (with
        no state change) when the pool is full — the backpressure signal
        the scheduler's preemption path consumes."""
        assert self.active[slot], f"slot {slot} is not active"
        self.reserve_write(slot, n_tokens)
        self.lengths[slot] = int(self.lengths[slot]) + n_tokens

    def truncate(self, slot: int, new_len: int) -> None:
        """Roll ``slot`` back to ``new_len`` TOKENS — the speculative-decode
        rejection primitive: a verify round appends the draft burst
        optimistically, then truncates away the rejected tail.

        Stored positions >= ``new_len`` are scrubbed to -1 on device in the
        pages covering them, so the next step can never attend a rejected
        token (the varlen/paged history masks drop pos -1, and the swap
        exporter would otherwise snapshot the stale entries). The pages
        themselves STAY allocated: they sit inside the slot's reservation
        and the very next append rewrites the same page slots, so freeing
        and re-allocating them would only churn the free list and re-raise
        mid-tick ``PoolExhaustedError`` risk.

        CoW safety: a page is only scrubbed if this slot owns it
        EXCLUSIVELY. Shared pages hold immutable prefix tokens — drafts are
        only ever written past the prefix into exclusively-owned (possibly
        CoW-copied) pages — so a rollback reaching into a refcount > 1 page
        is a caller bug and raises ``ValueError`` with no state change
        (pinned by the property walk in ``tests/test_kv_pool.py``)."""
        assert self.active[slot], f"slot {slot} is not active"
        length = int(self.lengths[slot])
        if not 0 < new_len <= length:
            raise ValueError(f"truncate to {new_len} outside (0, {length}]")
        if new_len == length:
            return
        first = new_len // self.page_size  # boundary page: may keep a head
        pages = [int(p) for p in self.block_tables[slot][first:self.pages_for(length)]
                 if p != TRASH_PAGE]
        shared = [p for p in pages if self.refcount[p] > 1]
        if shared:
            raise ValueError(
                f"truncate({slot}, {new_len}) would scrub shared page(s) "
                f"{shared} (refcount > 1): CoW-shared prefixes are immutable")
        if pages:
            idx = jnp.asarray(pages, jnp.int32)
            self._caches = tuple(
                dataclasses.replace(c, pos=c.pos.at[:, idx].set(
                    jnp.where(c.pos[:, idx] >= new_len, -1, c.pos[:, idx])))
                for c in self._caches)
        self.lengths[slot] = new_len

    def free(self, slot: int) -> None:
        """Return a finished request's page REFERENCES. Pages the slot owned
        exclusively are scrubbed on device (stored positions → -1) and
        returned to the free list LIFO; pages still shared (a live
        :class:`SharedPrefix` or another slot) survive untouched."""
        assert self.active[slot], f"slot {slot} is not active"
        pages = [int(p) for p in self.block_tables[slot] if p != TRASH_PAGE]
        self._decref(pages)
        self.block_tables[slot] = TRASH_PAGE
        self.lengths[slot] = 0
        self.active[slot] = False

    # ---------------------------------------------------- preemption swap

    def export_slot(self, slot: int, n_tokens: int | None = None) -> dict:
        """Host snapshot of ``slot``'s WRITTEN pages (the first
        ``pages_for(n_tokens)`` table entries) for evict-to-queue
        preemption: ``{"length": tokens, "data": per-pattern-position
        (k, v, k_scale, v_scale, pos) numpy arrays with a leading
        page-run axis}``. Read-only — the slot stays live until the caller
        frees it. :meth:`restore_slot` puts the snapshot back
        bit-identically (the restored request decodes exactly as if never
        preempted).

        ``n_tokens`` (TOKENS, default the slot's accounted length) lets a
        caller exclude positions it has APPENDED but not yet written — the
        scheduler's speculative same-tick append: snapshotting the
        accounted length there would bake a never-written hole into the
        restore."""
        assert self.active[slot], f"slot {slot} is not active"
        n = int(self.lengths[slot]) if n_tokens is None else int(n_tokens)
        assert 1 <= n <= int(self.lengths[slot]), \
            f"cannot export {n} of slot {slot}'s {int(self.lengths[slot])}"
        pages = [int(p)
                 for p in self.block_tables[slot][:self.pages_for(n)]]
        assert TRASH_PAGE not in pages, f"slot {slot} under-allocated"
        idx = jnp.asarray(pages, jnp.int32)
        data = tuple(
            tuple(np.asarray(leaf[:, idx])
                  for leaf in (c.k, c.v, c.k_scale, c.v_scale, c.pos))
            for c in self._caches)
        snapshot = {"length": n, "data": data}
        self.swap_bytes += self.snapshot_bytes(snapshot)
        return snapshot

    @staticmethod
    def snapshot_bytes(snapshot: dict) -> int:
        """Host BYTES one :meth:`export_slot` snapshot holds."""
        return sum(a.nbytes for leaves in snapshot["data"] for a in leaves)

    def discard_snapshot(self, snapshot: dict) -> None:
        """Drop an :meth:`export_slot` snapshot that will never be
        restored (the preempted request was aborted) — releases its
        ``swap_bytes`` accounting."""
        self.swap_bytes -= self.snapshot_bytes(snapshot)
        assert self.swap_bytes >= 0, "snapshot discarded twice"

    def adopt_snapshot(self, snapshot: dict) -> None:
        """Take accounting ownership of a snapshot EXPORTED FROM ANOTHER
        pool (the disaggregated prefill→decode page stream,
        ``serving.page_transport.PageStreamTransport``): charges this
        pool's ``swap_bytes`` so the eventual :meth:`restore_slot`
        decrement balances. The exporting pool must release its own side
        with :meth:`discard_snapshot` — exactly one pool owns a snapshot's
        bytes at any time."""
        self.swap_bytes += self.snapshot_bytes(snapshot)

    def restore_slot(self, snapshot: dict,
                     reserve_tokens: int | None = None) -> int:
        """Re-admit a preempted request from an :meth:`export_slot`
        snapshot: allocates fresh pages (plus any ``reserve_tokens``
        headroom, in TOKENS) and writes the saved page contents back, so
        the stored int8 codes/scales/positions — and therefore every
        subsequent decoded token — are bit-identical to the un-preempted
        run. Returns the new slot index; raises ``PoolExhaustedError``
        (leaking nothing) when the pool cannot hold it yet."""
        n = int(snapshot["length"])
        slot = self.admit(n, reserve_tokens=reserve_tokens)
        pages = [int(p)
                 for p in self.block_tables[slot][:self.pages_for(n)]]
        idx = jnp.asarray(pages, jnp.int32)
        new = []
        for c, (k, v, ks, vs, pos) in zip(self._caches, snapshot["data"]):
            new.append(dataclasses.replace(
                c,
                k=c.k.at[:, idx].set(jnp.asarray(k)),
                v=c.v.at[:, idx].set(jnp.asarray(v)),
                k_scale=c.k_scale.at[:, idx].set(jnp.asarray(ks)),
                v_scale=c.v_scale.at[:, idx].set(jnp.asarray(vs)),
                pos=c.pos.at[:, idx].set(jnp.asarray(pos))))
        self._caches = tuple(new)
        self.lengths[slot] = n
        # the snapshot is consumed: its host bytes are no longer held
        # (the admit above already succeeded — nothing leaks on failure)
        self.swap_bytes -= self.snapshot_bytes(snapshot)
        assert self.swap_bytes >= 0, "snapshot restored twice"
        return slot

    # ------------------------------------------------------- device plumbing

    def _place(self, c: PagedKVCache) -> PagedKVCache:
        """Re-apply the mesh shardings to one pattern position's leaves
        (sharded mode only): page-axis leaves onto ``P(None, "kv")``, the
        block table replicated. ``jax.device_put`` is a no-op when the
        array already sits where it should, so calling this after every
        host-driven ``.at`` mutation costs nothing in steady state."""
        import jax

        return dataclasses.replace(
            c,
            k=jax.device_put(c.k, self._page_sharding),
            v=jax.device_put(c.v, self._page_sharding),
            k_scale=jax.device_put(c.k_scale, self._page_sharding),
            v_scale=jax.device_put(c.v_scale, self._page_sharding),
            pos=jax.device_put(c.pos, self._page_sharding),
            block_table=jax.device_put(c.block_table, self._repl_sharding))

    def device_caches(self, rows=None) -> tuple:
        """The pool pytree with the CURRENT block tables installed —
        ``rows`` selects a sub-batch (e.g. the freshly admitted requests for
        a ragged prefill); default is every slot row. In sharded mode every
        leaf is (re)placed onto the mesh first, so the jitted step always
        sees page-sharded pool leaves + replicated tables."""
        bt = self.block_tables if rows is None else self.block_tables[rows]
        bt = jnp.broadcast_to(jnp.asarray(bt, jnp.int32)[None],
                              (self.nb,) + bt.shape)
        caches = tuple(dataclasses.replace(c, block_table=bt)
                       for c in self._caches)
        if self.mesh is not None:
            caches = tuple(self._place(c) for c in caches)
        return caches

    def update_from(self, new_caches: tuple) -> None:
        """Adopt the pool arrays a jitted prefill/decode step returned (the
        block tables riding in the pytree are per-call views; the host copy
        stays authoritative)."""
        for c in new_caches:
            if c.k.shape[-2] != self.page_size:
                raise ValueError(
                    f"non-uniform page size: pool pages are {self.page_size} "
                    f"tokens, got {c.k.shape[-2]}; pages must be uniform — "
                    f"round lengths with padded_cache_len(s, "
                    f"{self.page_size}, uniform=True) before paging")
        self._caches = tuple(
            dataclasses.replace(c, block_table=old.block_table)
            for c, old in zip(new_caches, self._caches))

    def gather_dense(self, slot: int) -> tuple:
        """Reassemble ``slot``'s cache densely from its pages (tests/debug):
        returns (k_codes, k_scale, v_codes, v_scale, pos) with leading nb."""
        from repro.kernels.ref import gather_pages_ref

        bt = jnp.asarray(self.block_tables[slot][None], jnp.int32)  # (1, mb)
        outs = []
        for c in self._caches:
            leaves = []
            for leaf in (c.k, c.v, c.k_scale[:, :, :, 0], c.v_scale[:, :, :, 0],
                         c.pos[:, :, 0]):
                g = jnp.stack([gather_pages_ref(leaf[i], bt)[0]
                               for i in range(self.nb)])
                leaves.append(g)
            outs.append(tuple(leaves))
        return tuple(outs)

    # ----------------------------------------------------------- accounting

    def page_bytes(self) -> int:
        """Device BYTES of ONE page across every covered layer."""
        return page_bytes(self.cfg, self.page_size, self.nb)

    def page_bytes_in_use(self) -> int:
        """Page-granular occupancy in BYTES: what the allocated pages
        actually hold (internal fragmentation AND worst-case reservation
        included; shared pages counted ONCE — physical residency)."""
        return self.pages_in_use * self.page_bytes()

    def page_bytes_written(self) -> int:
        """Page-granular BYTES of DISTINCT pages that hold at least one
        token — what a page-level KV shipment actually has to move
        (reserved-but-empty pages excluded, and pages shared between
        requests shipped ONCE, unlike a per-request dense transfer)."""
        written: set = set()
        for slot in np.flatnonzero(self.active):
            n = int(self.lengths[slot])
            if n > 0:
                written.update(
                    int(p) for p in self.block_tables[slot][:self.pages_for(n)]
                    if p != TRASH_PAGE)
        return self.page_bytes() * len(written)

    def eq2_bytes(self, qa_bits: int = 8) -> int:
        """The paper's analytical B_kv in BYTES (Eq. 2 via ``core.opsc.
        kv_cache_bytes``) summed over resident requests at the pool's int8
        activation width — the quantity the OPSC optimizer constrains.
        This is the LOGICAL (per-request) total: shared prefix tokens are
        counted once per sharing request, so under prefix sharing
        ``eq2_bytes() > page_bytes_in_use()`` measures the sharing win
        (``core.opsc.kv_cache_bytes_shared`` is the dedup-aware model)."""
        from repro.core.opsc import kv_cache_bytes

        total = 0
        for slot in np.flatnonzero(self.active):
            w = int(self.lengths[slot])
            if w > 0:
                total += kv_cache_bytes(w, self.num_layers, self.num_layers,
                                        self.kv_heads * self.head_dim,
                                        qa_bits, qa_bits)
        return total

    def occupancy(self) -> float:
        """Fraction of allocatable pages currently in use (shared pages
        counted once)."""
        return self.pages_in_use / max(1, self.num_pages - 1)

    def gauges(self) -> dict:
        """One consistent occupancy sample — what the telemetry tracer
        records per scheduler tick: pages in use / shared / free (page
        counts), host swap bytes, occupancy fraction, and the physical
        page bytes resident on device."""
        return {"pages_in_use": self.pages_in_use,
                "pages_shared": self.pages_shared,
                "pages_free": self.free_pages,
                "swap_bytes": self.swap_bytes,
                "occupancy": self.occupancy(),
                "page_bytes_in_use": self.page_bytes_in_use()}
