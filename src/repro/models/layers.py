"""Model building blocks: norms, rotary embeddings (RoPE / M-RoPE /
sinusoidal), GQA attention with flash-style double-chunked online softmax
(pure JAX for training/prefill — attention stays XLA-fusable and
differentiable), SwiGLU/GELU MLPs, and KV caches.

Caches come in three layouts:
  * fp (bf16/f32): token-major (B, S, K, hd) — read by chunked_attention;
  * int8-quantized: kv-head-major (B, K, S, hd) codes + per-(token, head)
    scales (B, K, S) — the exact layout streamed by the Pallas
    ``kernels.decode_attention`` kernel, which decode-time attention routes
    to (see :func:`quantized_decode_attention`);
  * paged (``PagedKVCache``): the int8 layout cut into fixed pages of a
    shared pool addressed by per-request block tables — ragged batches from
    ``serving.kv_pool``, streamed by ``kernels.paged_decode_attention``
    at decode (see :func:`paged_decode_attention_layer`) and by
    ``kernels.paged_prefill_attention`` for shared-prefix / chunked
    prefill (see :func:`paged_prefill_attention`).

Shapes: activations (B, S, D); q/k/v (B, S, H|K, hd).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w).astype(dt)


# ---------------------------------------------------------------------------
# Position encodings
# ---------------------------------------------------------------------------


def rope_table(positions: jax.Array, dim: int, theta: float = 10000.0):
    """positions (..., S) → (cos, sin) of shape (..., S, dim//2)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (B, S, H, hd); cos/sin (B, S, hd//2) or (S, hd//2)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    if cos.ndim == 2:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).astype(dt)


def mrope_tables(positions_thw: jax.Array, dim: int, sections: tuple,
                 theta: float = 10000.0):
    """Qwen2-VL M-RoPE: ``positions_thw`` (3, B, S) temporal/height/width ids;
    ``sections`` splits dim//2 into per-axis bands (e.g. (16, 24, 24))."""
    assert sum(sections) == dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    cos_parts, sin_parts = [], []
    start = 0
    for axis, sec in enumerate(sections):
        f = freqs[start : start + sec]
        ang = positions_thw[axis].astype(jnp.float32)[..., None] * f  # (B,S,sec)
        cos_parts.append(jnp.cos(ang))
        sin_parts.append(jnp.sin(ang))
        start += sec
    return jnp.concatenate(cos_parts, -1), jnp.concatenate(sin_parts, -1)


def sinusoidal_embedding(positions: jax.Array, dim: int) -> jax.Array:
    """Classic transformer sinusoidal absolute embedding (MusicGen)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# KV caches
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class KVCache:
    """Per-layer-stack KV cache (a pytree). ``k``/``v`` are either fp tensors
    in token-major (B, S, K, hd) layout, or int8 code tensors in kv-head-major
    (B, K, S, hd) layout with per-(token, head) scales (B, K, S) — realizing
    the paper's Q^a activation-bit control on the cache (Eq. 2) in the exact
    layout the Pallas decode-attention kernel streams.

    ``pos`` holds the absolute position stored in each slot (ring buffers for
    sliding-window layers overwrite slots; attention masks by position, so
    slot order is irrelevant)."""

    k: jax.Array
    v: jax.Array
    k_scale: jax.Array | None  # (B, K, S) when quantized
    v_scale: jax.Array | None
    pos: jax.Array  # (B, S) int32; -1 = empty

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


jax.tree_util.register_pytree_node(
    KVCache,
    lambda c: ((c.k, c.v, c.k_scale, c.v_scale, c.pos), None),
    lambda _, ch: KVCache(*ch),
)


@dataclasses.dataclass
class PagedKVCache:
    """Per-layer view of the shared paged KV pool (a pytree) — int8 codes +
    f32 scales in PAGE-major layout, addressed through per-request block
    tables instead of a dense per-request sequence axis. Allocation lives in
    ``serving.kv_pool``; this type is what flows through the block scan and
    what ``attention_layer`` routes on.

      k / v        (P, K, page, hd) int8    k/v_scale (P, K, 1, page) f32
      pos          (P, 1, page) int32       (-1 = empty slot)
      block_table  (R, max_blocks) int32    (page ids; 0 = reserved trash
                                             page for pads/inactive rows)

    Unlike the dense ``KVCache`` there is no batch axis on the pool leaves:
    requests of ragged lengths share the pool, and a request's cache is the
    gather of its block-table pages."""

    k: jax.Array
    v: jax.Array
    k_scale: jax.Array
    v_scale: jax.Array
    pos: jax.Array
    block_table: jax.Array

    @property
    def quantized(self) -> bool:
        return True

    @property
    def page_size(self) -> int:
        return self.k.shape[-2]


jax.tree_util.register_pytree_node(
    PagedKVCache,
    lambda c: ((c.k, c.v, c.k_scale, c.v_scale, c.pos, c.block_table), None),
    lambda _, ch: PagedKVCache(*ch),
)


def init_cache(batch: int, size: int, kv_heads: int, head_dim: int,
               dtype=jnp.bfloat16, quantized: bool = False) -> KVCache:
    if quantized:  # kv-head-major kernel layout
        shape = (batch, kv_heads, size, head_dim)
        return KVCache(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros((batch, kv_heads, size), jnp.float32),
            v_scale=jnp.zeros((batch, kv_heads, size), jnp.float32),
            pos=jnp.full((batch, size), -1, jnp.int32),
        )
    shape = (batch, size, kv_heads, head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), None, None,
                   jnp.full((batch, size), -1, jnp.int32))


def _quantize_kv(x: jax.Array):
    """Symmetric int8 per-(token, head): the Eq. 2 Q_a control realized."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return codes, scale


def cache_update(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                 pos: jax.Array, window: int | None = None) -> KVCache:
    """Write ``k_new``/``v_new`` (B, S_new, K, hd) at absolute position ``pos``
    (scalar int32). Ring-buffered when ``window`` is set. Quantized caches are
    written in the kernel's kv-head-major layout, the slot axis being 2
    instead of 1."""
    seq_axis = 2 if cache.quantized else 1
    size = cache.k.shape[seq_axis]
    if window is not None:
        # quantized caches may be block-padded past the window; the ring must
        # wrap within it so stale positions can't outlive the window (pad
        # slots are never written and keep pos = -1 → masked)
        size = min(window, size)
    s_new = k_new.shape[1]
    if window is not None and s_new >= size:
        # writing ≥ a full ring: only the last ``size`` tokens survive; slice
        # them out so scatter indices stay unique (a permutation of the ring)
        keep = slice(s_new - size, None)
        k_new, v_new = k_new[:, keep], v_new[:, keep]
        pos = pos + (s_new - size)
        s_new = size
    if window is not None:
        slots = (pos + jnp.arange(s_new)) % size  # ring buffer

        def write(buf, val, axis=1):
            idx = (slice(None),) * axis + (slots,)
            return buf.at[idx].set(val.astype(buf.dtype))

        def write_pos(buf):
            return buf.at[:, slots].set(pos + jnp.arange(s_new))

    else:

        def write(buf, val, axis=1):  # contiguous → dynamic_update_slice
            idx = (0,) * axis + (pos,) + (0,) * (buf.ndim - axis - 1)
            return jax.lax.dynamic_update_slice(buf, val.astype(buf.dtype), idx)

        def write_pos(buf):
            upd = (pos + jnp.arange(s_new)[None, :]) * jnp.ones(
                (buf.shape[0], 1), jnp.int32)
            return jax.lax.dynamic_update_slice(buf, upd, (0, pos))

    if cache.quantized:
        kc, ks = _quantize_kv(k_new)  # (B, S_new, K, hd), (B, S_new, K, 1)
        vc, vs = _quantize_kv(v_new)
        to_hm = lambda c: jnp.swapaxes(c, 1, 2)  # token- → kv-head-major
        return KVCache(write(cache.k, to_hm(kc), seq_axis),
                       write(cache.v, to_hm(vc), seq_axis),
                       write(cache.k_scale, to_hm(ks[..., 0]), seq_axis),
                       write(cache.v_scale, to_hm(vs[..., 0]), seq_axis),
                       write_pos(cache.pos))
    return KVCache(write(cache.k, k_new), write(cache.v, v_new), None, None,
                   write_pos(cache.pos))


def paged_cache_update(cache: PagedKVCache, k_new: jax.Array, v_new: jax.Array,
                       positions: jax.Array,
                       slots: jax.Array | None = None) -> PagedKVCache:
    """Scatter ``k_new``/``v_new`` (R, S_new, K, hd) into the shared pool.

    ``positions`` (R, S_new) carries each token's ABSOLUTE position; negative
    entries are ragged-prefill pads (or inactive decode slots) and are routed
    to the reserved trash page 0 with ``pos = -1``, so they can never be
    attended — as is a position past the block table's reach or one whose
    table entry is still unallocated (a caller that skipped the host-side
    ``PagedKVPool.append`` would otherwise corrupt a live page or leak a
    real position onto the shared trash page). Valid tokens land at
    page ``block_table[r, p // page]``, slot ``p % page`` — distinct
    positions of a request hit distinct (page, slot) pairs, so every valid
    scatter index is unique. Quantization is the same per-(token, head) int8
    transform as the dense cache (bit-identical codes — the dense↔paged
    parity tests rely on this).

    ``slots`` (R, S_new) switches to SEGMENT-AWARE scatter for the
    token-packed varlen path: each token's block-table row is its own slot
    id rather than its batch row (the packed call's batch dim is 1 while
    its tokens span many requests). Tokens with slot -1 are pads."""
    page = cache.page_size
    r, s_new = positions.shape
    nbt = cache.block_table.shape[1]
    valid = (positions >= 0) & (positions < nbt * page)
    page_idx = jnp.where(valid, positions // page, 0)
    if slots is None:
        pages = jnp.take_along_axis(cache.block_table, page_idx, axis=1)
    else:
        valid = valid & (slots >= 0)
        pages = cache.block_table[jnp.maximum(slots, 0), page_idx]
    pages = jnp.where(valid, pages, 0)
    # a position whose block-table entry is still 0 (page not yet allocated)
    # must not store a real pos on the shared trash page — every request's
    # unused table entries point there, so it would leak across requests
    valid = valid & (pages != 0)
    slots = jnp.where(valid, positions % page, 0)
    pr, sl = pages.reshape(-1), slots.reshape(-1)

    kc, ks = _quantize_kv(k_new)  # (R, S_new, K, hd), (R, S_new, K, 1)
    vc, vs = _quantize_kv(v_new)

    def put(buf, val):  # buf (P, K, page, hd); val (R, S_new, K, hd)
        flat = val.reshape((r * s_new,) + val.shape[2:])
        return buf.at[pr, :, sl].set(flat.astype(buf.dtype))

    def put_scale(buf, val):  # buf (P, K, 1, page); val (R, S_new, K, 1)
        return buf.at[pr, :, 0, sl].set(val.reshape(r * s_new, -1))

    new_pos = cache.pos.at[pr, 0, sl].set(
        jnp.where(valid, positions, -1).reshape(-1))
    return PagedKVCache(put(cache.k, kc), put(cache.v, vc),
                        put_scale(cache.k_scale, ks), put_scale(cache.v_scale, vs),
                        new_pos, cache.block_table)


# ---------------------------------------------------------------------------
# Flash-style attention (pure JAX, double-chunked online softmax)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _soft_cap(scores: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


@partial(jax.jit, static_argnames=("causal", "window", "softcap", "q_chunk", "kv_chunk"))
def chunked_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Skv, K, hd)
    v: jax.Array,  # (B, Skv, K, hd)
    q_pos: jax.Array,  # (B, Sq) absolute positions of queries
    kv_pos: jax.Array,  # (B, Skv) absolute positions of keys (-1 = invalid)
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Memory-bounded attention: outer scan over query chunks, inner scan over
    KV chunks with online softmax. Never materializes an (Sq, Skv) score
    tensor — required for the 32k/500k shapes. Supports GQA (grouped heads),
    sliding windows and logit soft-capping. (Quantized-cache decode routes to
    :func:`quantized_decode_attention` instead.)"""
    b, sq, h, hd = q.shape
    _, skv, kh, _ = k.shape
    g = h // kh
    qc = min(q_chunk, sq)
    kc = min(kv_chunk, skv)
    nq = -(-sq // qc)
    nk = -(-skv // kc)
    pad_q = nq * qc - sq
    pad_k = nk * kc - skv

    scale = 1.0 / math.sqrt(hd)
    qf = (q * scale).astype(jnp.float32)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)), constant_values=-(10 ** 9))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad_k)), constant_values=-1)

    if nq == 1 and nk == 1:
        # single-block fast path (decode): no scan, no reshape/dynamic-slice
        # — keeps a seq- or head-sharded KV cache shardable under GSPMD
        # (the scan path's dynamic-slice forces involuntary remat/all-gather)
        q1 = qf.reshape(b, qc, kh, g, hd)
        kf = k.astype(jnp.float32)
        vf = v.astype(jnp.float32)
        s = jnp.einsum("bqkgd,bckd->bkgqc", q1, kf,
                       preferred_element_type=jnp.float32)
        s = _soft_cap(s, softcap)
        mask = kv_pos[:, None, None, None, :] >= 0
        if causal:
            mask &= kv_pos[:, None, None, None, :] <= q_pos[:, None, None, :, None]
        if window is not None:
            mask &= kv_pos[:, None, None, None, :] > (
                q_pos[:, None, None, :, None] - window)
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgqc,bckd->bkgqd", p, vf,
                         preferred_element_type=jnp.float32)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, qc, h, hd)[:, :sq]
        return out.astype(q.dtype)

    # (B, nq, qc, K, G, hd) view of queries
    qf = qf.reshape(b, nq, qc, kh, g, hd)
    qp = q_pos.reshape(b, nq, qc)
    kr = k.reshape(b, nk, kc, kh, hd)
    vr = v.reshape(b, nk, kc, kh, hd)
    kp = kv_pos.reshape(b, nk, kc)

    def q_step(_, qi):
        q_blk = qf[:, qi]  # (B, qc, K, G, hd)
        qp_blk = qp[:, qi]  # (B, qc)

        def kv_step(carry, ki):
            m, l, acc = carry
            k_blk = kr[:, ki]
            v_blk = vr[:, ki]
            kp_blk = kp[:, ki]  # (B, kc)
            s = jnp.einsum("bqkgd,bckd->bkgqc", q_blk,
                           k_blk.astype(jnp.float32),
                           preferred_element_type=jnp.float32)
            s = _soft_cap(s, softcap)
            mask = kp_blk[:, None, None, None, :] >= 0
            if causal:
                mask &= kp_blk[:, None, None, None, :] <= qp_blk[:, None, None, :, None]
            if window is not None:
                mask &= kp_blk[:, None, None, None, :] > (qp_blk[:, None, None, :, None] - window)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqc,bckd->bkgqd", p, v_blk.astype(jnp.float32),
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kh, g, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, qc), jnp.float32)
        a0 = jnp.zeros((b, kh, g, qc, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]  # (B, K, G, qc, hd)
        return None, out.transpose(0, 3, 1, 2, 4)  # (B, qc, K, G, hd)

    if nq == 1:
        _, out = q_step(None, 0)
        out = out[:, None]
    else:
        _, out = jax.lax.scan(q_step, None, jnp.arange(nq))
        out = jnp.moveaxis(out, 0, 1)  # (B, nq, qc, K, G, hd)
    out = out.reshape(b, nq * qc, h, hd)[:, :sq]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Quantized-cache decode attention (Pallas fast path + fallback)
# ---------------------------------------------------------------------------


def quantized_decode_attention(q, cache: KVCache, spec, q_positions, pos, *,
                               q_chunk=1024, kv_chunk=1024):
    """Decode-time attention over the kv-head-major int8 cache.

    Kernel-eligible layers — single-token query, no logit softcap — stream
    the int8 codes straight through the Pallas ``decode_attention`` kernel
    (``interpret=True`` off-TPU gives bit-identical CPU parity), never
    materializing a dequantized fp copy of the cache. Sliding-window layers
    are eligible too: their ring buffer only ever holds in-window positions,
    so the kernel's position mask is sufficient. Softcapped layers (gemma2)
    dequantize to the token-major layout and take chunked_attention.
    """
    b, s, h, hd = q.shape
    kh = cache.k.shape[1]
    if s == 1 and spec.attn_softcap is None:
        from repro.kernels.ops import decode_attention

        qh = q[:, 0].reshape(b, kh, h // kh, hd)
        out = decode_attention(qh, cache.k, cache.k_scale, cache.v,
                               cache.v_scale, cache.pos,
                               jnp.asarray(pos, jnp.int32))
        return out.reshape(b, 1, h, hd).astype(q.dtype)
    k = jnp.swapaxes(cache.k.astype(jnp.float32) * cache.k_scale[..., None], 1, 2)
    v = jnp.swapaxes(cache.v.astype(jnp.float32) * cache.v_scale[..., None], 1, 2)
    return chunked_attention(q, k, v, q_positions, cache.pos, causal=True,
                             window=spec.sliding_window,
                             softcap=spec.attn_softcap,
                             q_chunk=q_chunk, kv_chunk=kv_chunk)


def _head_shard(head_axis, head_shards: int, kh: int):
    """This shard's kv-head slice ``(offset, kh_local)`` under the
    ``RuntimeOpts.head_axis`` split, or None to run the full head set.
    Only meaningful inside a ``shard_map`` that binds ``head_axis``; the
    split must divide the kv-head count evenly (``sharded_step_fns``
    guarantees it)."""
    if head_axis is None or head_shards <= 1 or kh % head_shards:
        return None
    kh_loc = kh // head_shards
    return jax.lax.axis_index(head_axis) * kh_loc, kh_loc


def _slice_cache_heads(cache: PagedKVCache, off, kh_loc: int) -> PagedKVCache:
    """Slice the pool leaves' kv-head axis (axis 1 of the per-block
    (P, K, page, hd) and (P, K, 1, page) leaves) down to one shard's head
    group."""
    sl = lambda a: jax.lax.dynamic_slice_in_dim(a, off, kh_loc, axis=1)
    return PagedKVCache(sl(cache.k), sl(cache.v), sl(cache.k_scale),
                        sl(cache.v_scale), cache.pos, cache.block_table)


def _gather_dense_kv(cache: PagedKVCache):
    """Gather a paged cache dense via its block table and dequantize:
    (k, v) (R, S_pool, K, hd) f32 token-major + kv_pos (R, S_pool)."""
    from repro.kernels.ref import gather_pages_ref

    kd = gather_pages_ref(cache.k, cache.block_table)  # (R, K, Sp, hd)
    vd = gather_pages_ref(cache.v, cache.block_table)
    ks = gather_pages_ref(cache.k_scale[:, :, 0], cache.block_table)
    vs = gather_pages_ref(cache.v_scale[:, :, 0], cache.block_table)
    kv_pos = gather_pages_ref(cache.pos[:, 0], cache.block_table)  # (R, Sp)
    k = jnp.swapaxes(kd.astype(jnp.float32) * ks[..., None], 1, 2)
    v = jnp.swapaxes(vd.astype(jnp.float32) * vs[..., None], 1, 2)
    return k, v, kv_pos


def paged_prefill_attention(q, cache: PagedKVCache, k_fresh, v_fresh, spec,
                            q_positions, *, q_chunk=1024, kv_chunk=1024,
                            use_kernel: bool = True, head_axis=None,
                            head_shards: int = 1):
    """Prefill attention THROUGH the paged pool — the shared-prefix /
    chunked-prefill entry.

    A plain prefill attends only the call's fresh k/v; a request forked from
    a shared prefix — or a chunked prefill's continuation chunk — additionally
    owns block-table pages holding tokens written BEFORE this call (the
    prefix / the earlier chunks). Each row attends the union of

      * its pool history, masked to stored positions < the row's FIRST
        in-call position (so tokens this very call scatters into the pool
        are not double-counted, and a row prefilling from position 0 sees
        no history at all), dequantized from int8 — exactly what its decode
        steps will read; and
      * the call's fresh keys/values at full precision, masked causally by
        ``q_positions`` like the plain ragged prefill.

    ``cache`` must be the post-update pool (this call's tokens already
    scattered), so rows created in the SAME call can serve as each other's
    prefix — the split engine prefills the prefix owner and its forks in
    one batched call.

    The default path walks the history pages in place with the Pallas
    ``kernels.paged_prefill_attention`` flash kernel (int8 dequantized
    in-register through the block-table index map — no dense f32 copy of
    the pool in HBM). Softcapped / windowed layers, and callers passing
    ``use_kernel=False`` (``RuntimeOpts.paged_prefill_kernel``), fall back
    to gathering the pool dense into ``chunked_attention`` — correct, not
    fast."""
    if use_kernel and spec.attn_softcap is None and spec.sliding_window is None:
        from repro.kernels.ops import paged_prefill_attention as _kernel

        b, s, h, hd = q.shape
        kh = cache.k.shape[1]
        qk = q.reshape(b, s, kh, h // kh, hd).transpose(0, 2, 1, 3, 4)
        kf, vf = jnp.swapaxes(k_fresh, 1, 2), jnp.swapaxes(v_fresh, 1, 2)
        shard = _head_shard(head_axis, head_shards, kh)
        if shard is not None:  # this shard walks the pages with its heads
            off, kh_loc = shard
            dyn = lambda a: jax.lax.dynamic_slice_in_dim(a, off, kh_loc, 1)
            qk, kf, vf = dyn(qk), dyn(kf), dyn(vf)
            cache = _slice_cache_heads(cache, off, kh_loc)
        out = _kernel(qk, cache.k, cache.k_scale, cache.v, cache.v_scale,
                      cache.pos, cache.block_table,
                      jnp.asarray(q_positions, jnp.int32), kf, vf)
        if shard is not None:  # exact tiled reassembly — no reduction
            out = jax.lax.all_gather(out, head_axis, axis=1, tiled=True)
        return out.transpose(0, 2, 1, 3, 4).reshape(b, s, h, hd).astype(q.dtype)
    from repro.kernels.paged_prefill_attention import first_call_position

    k_hist, v_hist, hist_pos = _gather_dense_kv(cache)
    start = first_call_position(q_positions)  # (R,) per-row history bound
    hist_pos = jnp.where(hist_pos < start[:, None], hist_pos, -1)
    k = jnp.concatenate([k_hist, k_fresh.astype(jnp.float32)], axis=1)
    v = jnp.concatenate([v_hist, v_fresh.astype(jnp.float32)], axis=1)
    kv_pos = jnp.concatenate([hist_pos, q_positions], axis=1)
    return chunked_attention(q, k, v, q_positions, kv_pos, causal=True,
                             window=spec.sliding_window,
                             softcap=spec.attn_softcap,
                             q_chunk=q_chunk, kv_chunk=kv_chunk)


def paged_decode_attention_layer(q, cache: PagedKVCache, spec, q_positions, *,
                                 q_chunk=1024, kv_chunk=1024, head_axis=None,
                                 head_shards: int = 1):
    """Decode-time attention through the PAGED pool.

    Kernel-eligible layers — single-token query, no logit softcap — walk
    their block-table pages with the Pallas ``paged_decode_attention`` kernel
    (scalar-prefetch gather, per-request causal bounds for ragged batches).
    Softcapped layers gather their pages dense via the block table and
    dequantize into ``chunked_attention`` — correct, not fast.

    ``q_positions`` (R, S): the per-request absolute query positions; the
    last column is each row's causal bound (-1 marks an inactive decode
    slot, which masks every key and yields a finite all-zero output)."""
    b, s, h, hd = q.shape
    kh = cache.k.shape[1]
    q_pos = q_positions[:, -1].astype(jnp.int32)
    if s == 1 and spec.attn_softcap is None:
        from repro.kernels.ops import paged_decode_attention

        qh = q[:, 0].reshape(b, kh, h // kh, hd)
        shard = _head_shard(head_axis, head_shards, kh)
        if shard is not None:  # this shard walks the pages with its heads
            off, kh_loc = shard
            qh = jax.lax.dynamic_slice_in_dim(qh, off, kh_loc, 1)
            cache = _slice_cache_heads(cache, off, kh_loc)
        out = paged_decode_attention(qh, cache.k, cache.k_scale, cache.v,
                                     cache.v_scale, cache.pos,
                                     cache.block_table, q_pos)
        if shard is not None:  # exact tiled reassembly — no reduction
            out = jax.lax.all_gather(out, head_axis, axis=1, tiled=True)
        return out.reshape(b, 1, h, hd).astype(q.dtype)
    k, v, kv_pos = _gather_dense_kv(cache)
    return chunked_attention(q, k, v, q_positions, kv_pos, causal=True,
                             window=spec.sliding_window,
                             softcap=spec.attn_softcap,
                             q_chunk=q_chunk, kv_chunk=kv_chunk)


def varlen_attention_layer(q, cache: PagedKVCache, k_fresh, v_fresh, spec,
                           q_positions, token_slots, *,
                           use_kernel: bool = True, head_axis=None,
                           head_shards: int = 1):
    """Token-packed VARLEN attention through the pool — the packed tick's
    entry. ONE flat batch (batch dim 1) whose tokens span many requests:
    q (1, T, H, hd), per-token ``q_positions``/``token_slots`` (1, T), the
    call's fresh k/v (1, T, K, hd). Each token attends its own slot's pool
    history (stored positions below the slot's first in-call position) plus
    the causally-ordered fresh keys of its own segment; pad rows (slot -1)
    emit exact zeros. ``cache`` must be the post-update pool, exactly like
    :func:`paged_prefill_attention`.

    The default path is the Pallas ``kernels.varlen_attention`` page walk;
    softcapped / windowed layers have no varlen route (the packed scheduler
    refuses such models up front), and ``use_kernel=False`` falls back to
    the dense ``kernels.ref`` oracle — correct, not fast."""
    if spec.attn_softcap is not None or spec.sliding_window is not None:
        raise NotImplementedError(
            "the token-packed varlen path requires kernel-eligible "
            "attention (no softcap, no sliding window)")
    b, t, h, hd = q.shape
    kh = cache.k.shape[1]
    qk = q.reshape(t, kh, h // kh, hd).transpose(1, 0, 2, 3)  # (K, T, G, hd)
    kf = jnp.swapaxes(k_fresh.reshape(t, kh, hd), 0, 1)  # (K, T, hd)
    vf = jnp.swapaxes(v_fresh.reshape(t, kh, hd), 0, 1)
    qp = jnp.asarray(q_positions, jnp.int32).reshape(-1)
    sl = jnp.asarray(token_slots, jnp.int32).reshape(-1)
    shard = _head_shard(head_axis, head_shards, kh)
    if shard is not None:  # this shard walks the pages with its heads
        off, kh_loc = shard
        dyn = lambda a: jax.lax.dynamic_slice_in_dim(a, off, kh_loc, 0)
        qk, kf, vf = dyn(qk), dyn(kf), dyn(vf)
        cache = _slice_cache_heads(cache, off, kh_loc)
    if use_kernel:
        from repro.kernels.ops import varlen_attention as _kernel

        out = _kernel(qk, cache.k, cache.k_scale, cache.v, cache.v_scale,
                      cache.pos, cache.block_table, qp, sl, kf, vf)
    else:
        from repro.kernels.ref import varlen_attention_ref
        from repro.kernels.varlen_attention import segment_start

        start = segment_start(qp, sl, cache.block_table.shape[0])
        out = varlen_attention_ref(qk, cache.k, cache.k_scale, cache.v,
                                   cache.v_scale, cache.pos,
                                   cache.block_table, qp, sl, start, kf, vf)
    if shard is not None:  # exact tiled reassembly — no reduction
        out = jax.lax.all_gather(out, head_axis, axis=0, tiled=True)
    return out.transpose(1, 0, 2, 3).reshape(b, t, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention layer (projections + rope + cache plumbing)
# ---------------------------------------------------------------------------


def init_attention_params(key, d_model: int, num_heads: int, num_kv_heads: int,
                          head_dim: int, dtype=jnp.float32, qk_norm: bool = False):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(num_heads * head_dim)
    p = {
        "wq": (jax.random.normal(k1, (d_model, num_heads * head_dim)) * s_in).astype(dtype),
        "wk": (jax.random.normal(k2, (d_model, num_kv_heads * head_dim)) * s_in).astype(dtype),
        "wv": (jax.random.normal(k3, (d_model, num_kv_heads * head_dim)) * s_in).astype(dtype),
        "wo": (jax.random.normal(k4, (num_heads * head_dim, d_model)) * s_out).astype(dtype),
    }
    if qk_norm:
        p["q_norm"] = jnp.ones((head_dim,), dtype)
        p["k_norm"] = jnp.ones((head_dim,), dtype)
    return p


def attention_layer(params, x: jax.Array, spec, *, rope_cs, cache: KVCache | None,
                    pos, q_positions, q_chunk=1024, kv_chunk=1024,
                    decode: bool = False, attend_cache: bool = False,
                    prefill_kernel: bool = True, token_slots=None,
                    quant_fresh=None, head_axis=None, head_shards: int = 1):
    """One attention layer.

    ``rope_cs``: (cos, sin) tables for the query positions, or None.
    ``cache``/``pos``: cache plumbing (None for pure training). During
    prefill the cache is *written* but attention runs over the fresh k/v
    (a window-sized ring cache cannot serve early queries their own window;
    chunked multi-segment prefill is not used by this framework). Only
    ``decode=True`` attends through the cache — except ``attend_cache=True``
    on a paged cache, which prefills THROUGH the pool (shared-prefix
    suffix prefill: history pages + fresh k/v, see
    :func:`paged_prefill_attention`), and ``token_slots`` on a paged cache,
    which routes the token-packed VARLEN path (per-token block-table rows
    for a flat mixed prefill/decode batch, see
    :func:`varlen_attention_layer`). Returns (output, new_cache).

    ``quant_fresh`` (B, S) bool (varlen route only): rows whose fresh k/v
    are attended through the int8 quantize→dequantize round trip — the
    exact values ``paged_cache_update`` stores, so a packed decode token
    attends its OWN key identically to a sequential decode step reading it
    back from the pool. The cache write always uses the original f32 k/v
    (re-quantizing a dequantized tensor is not code-stable).
    ``head_axis``/``head_shards``: see ``RuntimeOpts`` — split the paged
    kernels' kv-head axis across a shard_map mesh axis."""
    b, s, d = x.shape
    h, kh, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
    q = (x @ params["wq"]).reshape(b, s, h, hd)
    k = (x @ params["wk"]).reshape(b, s, kh, hd)
    v = (x @ params["wv"]).reshape(b, s, kh, hd)
    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if rope_cs is not None:
        cos, sin = rope_cs
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    new_cache = None
    if cache is not None:
        if isinstance(cache, PagedKVCache):
            # paged pool: positions are per-token (ragged prefill pads < 0)
            with jax.named_scope("kv_write"):
                new_cache = paged_cache_update(cache, k, v, q_positions,
                                               slots=token_slots)
        else:
            new_cache = cache_update(cache, k, v, pos, spec.sliding_window)
    if token_slots is not None and isinstance(new_cache, PagedKVCache):
        k_att, v_att = k, v
        if quant_fresh is not None:
            # int8 round trip for the masked rows: bit-identical to what
            # paged_cache_update just stored for them, so attending these
            # "fresh" keys equals reading them back from the pool. Kept in
            # f32 like the pool's dequantized reads: a bf16 model's k/v
            # dtype would round the dequantized values again
            kc, ks = _quantize_kv(k)
            vc, vs = _quantize_kv(v)
            m = quant_fresh[..., None, None]  # (B, S, 1, 1)
            k_att = jnp.where(m, kc.astype(jnp.float32) * ks, k)
            v_att = jnp.where(m, vc.astype(jnp.float32) * vs, v)
        out = varlen_attention_layer(q, new_cache, k_att, v_att, spec,
                                     q_positions, token_slots,
                                     use_kernel=prefill_kernel,
                                     head_axis=head_axis,
                                     head_shards=head_shards)
    elif cache is not None and decode:
        if isinstance(new_cache, PagedKVCache):
            out = paged_decode_attention_layer(
                q, new_cache, spec, q_positions,
                q_chunk=q_chunk, kv_chunk=kv_chunk,
                head_axis=head_axis, head_shards=head_shards)
        elif new_cache.quantized:
            out = quantized_decode_attention(
                q, new_cache, spec, q_positions, pos,
                q_chunk=q_chunk, kv_chunk=kv_chunk)
        else:
            out = chunked_attention(
                q, new_cache.k, new_cache.v, q_positions, new_cache.pos,
                causal=True, window=spec.sliding_window,
                softcap=spec.attn_softcap, q_chunk=q_chunk, kv_chunk=kv_chunk)
    elif attend_cache and isinstance(new_cache, PagedKVCache):
        out = paged_prefill_attention(q, new_cache, k, v, spec, q_positions,
                                      q_chunk=q_chunk, kv_chunk=kv_chunk,
                                      use_kernel=prefill_kernel,
                                      head_axis=head_axis,
                                      head_shards=head_shards)
    else:
        out = chunked_attention(
            q, k, v, q_positions, q_positions,
            causal=True, window=spec.sliding_window, softcap=spec.attn_softcap,
            q_chunk=q_chunk, kv_chunk=kv_chunk)
    out = out.reshape(b, s, h * hd) @ params["wo"]
    return out, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp_params(key, d_model: int, d_ff: int, gated: bool = True, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    p = {"w_up": (jax.random.normal(ks[0], (d_model, d_ff)) * s_in).astype(dtype),
         "w_down": (jax.random.normal(ks[1], (d_ff, d_model)) * s_out).astype(dtype)}
    if gated:
        p["w_gate"] = (jax.random.normal(ks[2], (d_model, d_ff)) * s_in).astype(dtype)
    return p


def mlp_layer(params, x: jax.Array, activation: str = "silu") -> jax.Array:
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[activation]
    up = x @ params["w_up"]
    if "w_gate" in params:
        up = act(x @ params["w_gate"]) * up
    else:
        up = act(up)
    return up @ params["w_down"]
