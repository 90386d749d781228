"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def tabq_quantize_ref(x: jax.Array, bits: int):
    """Per-token asymmetric magnitude quantization (TAB-Q inner op, Eq. 5-6).

    x (T, D) → (codes (T, D) int8 rebased to [0, 2^(bits-1)-1], scale (T,1),
    zero (T,1), sign (T, D) int8). Matches repro.core.quant.aiq on |x| with
    per-token reduction, then shifts codes/zero by the per-token code floor
    so dequant (codes - zero)·scale·sign is unchanged."""
    assert bits <= 8, "int8 code carrier requires bits <= 8"  # match kernel
    sign = jnp.sign(x).astype(jnp.int8)
    mag = jnp.abs(x.astype(jnp.float32))
    qmax = float(2 ** (bits - 1) - 1)
    t_min = jnp.min(mag, axis=-1, keepdims=True)
    t_max = jnp.max(mag, axis=-1, keepdims=True)
    s = jnp.maximum((t_max - t_min) / max(qmax, 1.0), 1e-8)
    z = jnp.ceil(t_min / s)
    codes = jnp.round(mag / s + z)
    c_lo = jnp.round(t_min / s + z)
    codes = jnp.clip(codes, c_lo, c_lo + qmax)
    return (codes - c_lo).astype(jnp.int8), s, z - c_lo, sign


def tabq_dequantize_ref(codes, s, z, sign):
    return (codes.astype(jnp.float32) - z) * s * sign


def dequant_matmul_ref(x: jax.Array, w_codes: jax.Array, w_scale: jax.Array):
    """x (M, K) × int8 codes (K, N) with per-output-channel scale (N,) →
    f32 (M, N): out = (x @ codes) · scale."""
    acc = jnp.dot(x.astype(jnp.float32), w_codes.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    return acc * w_scale[None, :]


def ts_mask_ref(x: jax.Array, tau: float):
    """Threshold split (Eq. 4): (below, mask uint8, count int32)."""
    mask = (jnp.abs(x) >= tau)
    below = jnp.where(mask, 0.0, x.astype(jnp.float32))
    return below, mask.astype(jnp.uint8), jnp.sum(mask, dtype=jnp.int32)


def decode_attention_ref(q, k_codes, k_scale, v_codes, v_scale, kv_pos, q_pos):
    """Dense oracle for the int8-KV decode-attention kernel.

    q (B,K,G,hd); codes (B,K,S,hd) int8 with scales (B,K,S); kv_pos (B,S);
    q_pos scalar or (B,) per-request → (B,K,G,hd) f32."""
    hd = q.shape[-1]
    k = k_codes.astype(jnp.float32) * k_scale[..., None]
    v = v_codes.astype(jnp.float32) * v_scale[..., None]
    s = jnp.einsum("bkgd,bksd->bkgs", q.astype(jnp.float32), k) / (hd ** 0.5)
    q_pos = jnp.broadcast_to(jnp.asarray(q_pos), (q.shape[0],))
    valid = (kv_pos >= 0) & (kv_pos <= q_pos[:, None])
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgs,bksd->bkgd", p, v)


def gather_pages_ref(pool_leaf, block_table):
    """Gather a request's pages from a shared pool into dense per-request
    layout — the paged↔dense bridge both oracles and tests rely on.

    pool_leaf (P, K, page, ...) or (P, page); block_table (R, nb) →
    dense (R, K, nb·page, ...) or (R, nb·page) in block-table order. The
    pool's scale (P, K, 1, page) and position (P, 1, page) leaves are passed
    with their unit axis dropped (``k_scale[:, :, 0]``, ``pool_pos[:, 0]``)."""
    g = pool_leaf[block_table]  # (R, nb, K, page, ...) or (R, nb, page)
    if pool_leaf.ndim == 2:
        return g.reshape(g.shape[0], -1)
    g = jnp.moveaxis(g, 2, 1)  # (R, K, nb, page, ...)
    return g.reshape(g.shape[0], g.shape[1], -1, *g.shape[4:])


def paged_decode_attention_ref(q, k_codes, k_scale, v_codes, v_scale,
                               pool_pos, block_table, q_pos):
    """Paged oracle: gather each request's pages dense (via the block table),
    then run :func:`decode_attention_ref` with per-request causal bounds.

    q (R,K,G,hd); pool codes (P,K,page,hd) int8 with scales (P,K,1,page);
    pool_pos (P,1,page); block_table (R,nb) int32; q_pos (R,) → (R,K,G,hd)."""
    kc = gather_pages_ref(k_codes, block_table)
    ks = gather_pages_ref(k_scale[:, :, 0], block_table)
    vc = gather_pages_ref(v_codes, block_table)
    vs = gather_pages_ref(v_scale[:, :, 0], block_table)
    kv_pos = gather_pages_ref(pool_pos[:, 0], block_table)
    return decode_attention_ref(q, kc, ks, vc, vs, kv_pos, q_pos)


def paged_prefill_attention_ref(q, k_codes, k_scale, v_codes, v_scale,
                                pool_pos, block_table, q_pos, start,
                                k_fresh, v_fresh):
    """Dense oracle for the paged PREFILL page-walk kernel.

    q (R,K,S,G,hd); pool codes (P,K,page,hd) int8 with scales (P,K,1,page);
    pool_pos (P,1,page); block_table (R,nb); q_pos (R,S) per-token positions
    (-1 pads); start (R,) each row's first in-call position; fresh k/v
    (R,K,S,hd) full precision → (R,K,S,G,hd) f32.

    Each query row attends the union of (a) its gathered pool pages,
    dequantized, masked to stored positions < start (the shared-prefix /
    earlier-chunk history — this call's own pool writes are excluded), and
    (b) the call's fresh keys, causally masked by q_pos. Rows with no valid
    key emit exact zeros."""
    hd = q.shape[-1]
    kd = gather_pages_ref(k_codes, block_table)  # (R, K, Sp, hd)
    vd = gather_pages_ref(v_codes, block_table)
    ks = gather_pages_ref(k_scale[:, :, 0], block_table)
    vs = gather_pages_ref(v_scale[:, :, 0], block_table)
    hist_pos = gather_pages_ref(pool_pos[:, 0], block_table)  # (R, Sp)
    k_hist = kd.astype(jnp.float32) * ks[..., None]
    v_hist = vd.astype(jnp.float32) * vs[..., None]
    k_all = jnp.concatenate([k_hist, k_fresh.astype(jnp.float32)], axis=2)
    v_all = jnp.concatenate([v_hist, v_fresh.astype(jnp.float32)], axis=2)
    ok_hist = (hist_pos >= 0) & (hist_pos < start[:, None])
    kv_pos = jnp.concatenate(
        [jnp.where(ok_hist, hist_pos, -1), q_pos], axis=1)  # (R, Sp+S)
    s = jnp.einsum("rksgd,rked->rksge", q.astype(jnp.float32) / (hd ** 0.5),
                   k_all, preferred_element_type=jnp.float32)
    valid = ((kv_pos[:, None, :] >= 0)
             & (kv_pos[:, None, :] <= q_pos[:, :, None]))  # (R, S, Skv)
    s = jnp.where(valid[:, None, :, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("rksge,rked->rksgd", p, v_all)
    any_valid = jnp.any(valid, axis=-1)  # (R, S)
    return jnp.where(any_valid[:, None, :, None, None], out, 0.0)


def varlen_attention_ref(q, k_codes, k_scale, v_codes, v_scale, pool_pos,
                         block_table, q_pos, tok_slot, start,
                         k_fresh, v_fresh):
    """Dense oracle for the token-packed VARLEN kernel.

    q (K,T,G,hd) — one flat token batch; q_pos/tok_slot (T,) per-token
    positions and slot ids (-1 pads); start (R,) per-slot first in-call
    position; pool + block_table as the pool holds them; fresh k/v (K,T,hd)
    → (K,T,G,hd) f32.

    Each token row gathers ITS slot's pages dense, masked to stored
    positions < start[slot] (this call's own pool writes excluded), and
    attends the call's fresh keys under a block-diagonal causal mask (same
    slot, q_pos[col] <= q_pos[row]). Pad rows emit exact zeros."""
    kh, t, g, hd = q.shape
    slu = jnp.maximum(tok_slot, 0)
    kd = gather_pages_ref(k_codes, block_table)  # (R, K, Sp, hd)
    vd = gather_pages_ref(v_codes, block_table)
    ks = gather_pages_ref(k_scale[:, :, 0], block_table)
    vs = gather_pages_ref(v_scale[:, :, 0], block_table)
    k_hist = (kd.astype(jnp.float32) * ks[..., None])[slu]  # (T, K, Sp, hd)
    v_hist = (vd.astype(jnp.float32) * vs[..., None])[slu]
    hist_pos = gather_pages_ref(pool_pos[:, 0], block_table)[slu]  # (T, Sp)
    ok_hist = ((tok_slot[:, None] >= 0) & (hist_pos >= 0)
               & (hist_pos < start[slu][:, None]))  # (T, Sp)
    ok_fresh = ((tok_slot[None, :] == tok_slot[:, None])
                & (tok_slot[None, :] >= 0) & (q_pos[None, :] >= 0)
                & (q_pos[None, :] <= q_pos[:, None]))  # (T, T)
    k_fr = jnp.broadcast_to(jnp.swapaxes(k_fresh, 0, 1)[None],
                            (t, t, kh, hd)).swapaxes(1, 2)  # (T, K, T, hd)
    v_fr = jnp.broadcast_to(jnp.swapaxes(v_fresh, 0, 1)[None],
                            (t, t, kh, hd)).swapaxes(1, 2)
    k_all = jnp.concatenate([k_hist, k_fr.astype(jnp.float32)], axis=2)
    v_all = jnp.concatenate([v_hist, v_fr.astype(jnp.float32)], axis=2)
    valid = jnp.concatenate([ok_hist, ok_fresh], axis=1)  # (T, Sp+T)
    s = jnp.einsum("ktgd,tked->ktge", q.astype(jnp.float32) / (hd ** 0.5),
                   k_all, preferred_element_type=jnp.float32)
    s = jnp.where(valid[None, :, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("ktge,tked->ktgd", p, v_all)
    any_valid = jnp.any(valid, axis=-1)  # (T,)
    return jnp.where(any_valid[None, :, None, None], out, 0.0)
