"""Pallas TPU kernel: ragged PREFILL attention over a paged int8 KV pool.

The decode-side page walk (``kernels.paged_decode_attention``) serves one
query token per request; prefill is the TTFT-critical phase and needs the
same walk for a whole BLOCK of query tokens per request — a forked request
prefilling its suffix against a shared prefix, or a chunked prefill whose
later chunks attend the chunks already scattered into the pool. Before this
kernel, that path gathered the ENTIRE pool dense and dequantized it to f32
per layer (``models.layers._gather_dense_kv`` — "correct, not fast"); here
the pool is walked page by page through the same scalar-prefetched
block-table index map as decode, int8 codes + per-(token, head) scales
consumed in-register, and the dense f32 copy never materializes in HBM.

Two key groups fold into ONE online softmax, so the attended set (and its
precision) is exactly the dense-gather path's:

  * POOL HISTORY — the request's block-table pages, masked per query row to
    stored positions BELOW the row's first in-call position (``start``):
    tokens this very call scatters into the pool are excluded (they are
    attended as fresh keys instead, not double-counted), and a row starting
    at position 0 sees no history at all;
  * FRESH KEYS — the call's own k/v at full precision, causally masked by
    the per-token positions (left pads carry position -1 → masked), walked
    as the minor axis' final step.

Operand layout (pool exactly as ``serving.kv_pool`` holds it):

  q            (R, K, S, G, hd)      queries, kv-head-major
  k/v_codes    (P, K, page, hd) int8  k/v_scale (P, K, 1, page) f32
  pool_pos     (P, 1, page) int32     (-1 = empty slot)
  block_table  (R, nb) int32          (unused entries → trash page 0)
  q_pos        (R, S) int32           per-token absolute positions (-1 pad)
  start        (R,) int32             first in-call position (2^30 if none)
  k/v_fresh    (R, K, S, hd)          this call's keys/values, full precision
  out          (R, K, S, G, hd) f32

The unit axes keep each block's last two dimensions whole, as the TPU
compiler requires; for the same reason ``q_pos`` enters the kernel as
``(R, 1, S)`` twice — the whole row (the fresh keys' positions) and the
q block's slice (the query rows' positions).

Grid: one program per (request, kv_head, query block); the minor axis walks
``nb`` block-table pages then the single fresh block. A fully-masked page
contributes garbage that the next valid step's correction factor
``exp(m_prev - m_new) = exp(-inf) = 0`` scrubs exactly; a query row whose
every key is masked (a pad column, or an inactive row in a fixed-shape
scheduler tick) is caught by the epilogue's ``seen`` guard and emits exact
zeros, never NaN.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
TRASH_PAGE = 0  # page id reserved by the pool for masked/pad gathers


def first_call_position(q_pos):
    """``start`` (R,) from per-token positions ``q_pos`` (R, S): each row's
    FIRST in-call position (2^30 for fully-padded rows, which every mask
    neutralizes). The single source both the kernel route and the
    dense-gather fallback derive their history bound from — they can never
    disagree on it."""
    q_pos = jnp.asarray(q_pos, jnp.int32)
    return jnp.min(jnp.where(q_pos >= 0, q_pos, jnp.int32(2 ** 30)), axis=1)


def _fold(q2, k, v, valid, g, m_ref, l_ref, acc_ref):
    """One online-softmax step: fold keys ``k``/values ``v`` (L, hd) with
    per-(q-row, key) mask ``valid`` (QB, L) into the (QB·G, ·) scratch."""
    s = jnp.dot(q2, k.T, preferred_element_type=jnp.float32)  # (QB*G, L)
    qb = valid.shape[0]
    vm = jnp.broadcast_to(valid[:, None, :], (qb, g, valid.shape[1]))
    s = jnp.where(vm.reshape(s.shape), s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _kernel(nb: int, qb: int, scale: float, bt_ref, start_ref, q_ref, qp_ref,
            qpb_ref, kc_ref, ks_ref, vc_ref, vs_ref, pos_ref, fk_ref, fv_ref,
            o_ref, m_ref, l_ref, acc_ref):
    si = pl.program_id(3)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    r = pl.program_id(0)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (QB, G, hd)
    g, hd = q.shape[1], q.shape[2]
    q2 = q.reshape(qb * g, hd)
    # per-row query positions of THIS q block as a column (the full row in
    # qp_ref lets the fresh step below mask every in-call key against them)
    qp = qpb_ref[0].T  # (QB, 1)
    start = start_ref[r]

    @pl.when(si < nb)
    def _pool_page():
        k = kc_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0].T  # (page, hd)
        v = vc_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0].T
        kv_pos = pos_ref[0]  # (1, page)
        # history only: stored positions below the row's first in-call
        # position (this call's own tokens live in the pool too — post-
        # update — and are attended as fresh keys instead)
        valid = (kv_pos >= 0) & (kv_pos < start) & (kv_pos <= qp)
        _fold(q2, k, v, valid, g, m_ref, l_ref, acc_ref)

    @pl.when(si == nb)
    def _fresh_and_finish():
        k = fk_ref[0, 0].astype(jnp.float32)  # (S, hd) full precision
        v = fv_ref[0, 0].astype(jnp.float32)
        kv_pos = qp_ref[0]  # (1, S) — fresh keys sit at the call's positions
        valid = (kv_pos >= 0) & (kv_pos <= qp)  # causal in-call
        _fold(q2, k, v, valid, g, m_ref, l_ref, acc_ref)
        # a row whose every key was masked (pad column / inactive row)
        # never raises m above its init — emit exact zeros, not the
        # exp(0)-uniform average of garbage values
        seen = m_ref[...] > NEG_INF * 0.5
        out = jnp.where(seen, acc_ref[...] / jnp.maximum(l_ref[...], 1e-30),
                        0.0)
        o_ref[0, 0] = out.reshape(qb, g, hd)


def paged_prefill_attention(q, k_codes, k_scale, v_codes, v_scale, pool_pos,
                            block_table, q_pos, start, k_fresh, v_fresh,
                            q_block: int = 128, interpret: bool = False):
    """See module docstring. Returns (R, K, S, G, hd) f32.

    ``S`` need not divide ``q_block``: the query axis is padded on call and
    pad columns (position -1) emit zeros. ``q_block`` is clamped to S."""
    r, kh, s, g, hd = q.shape
    p, _, page, _ = k_codes.shape
    nb = block_table.shape[1]
    assert block_table.shape[0] == r and q_pos.shape == (r, s)
    assert start.shape == (r,) and pool_pos.shape == (p, 1, page)
    assert k_fresh.shape == (r, kh, s, hd) and v_fresh.shape == k_fresh.shape
    qb = min(q_block, s)
    pad = (-s) % qb
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)), constant_values=-1)
        k_fresh = jnp.pad(k_fresh, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_fresh = jnp.pad(v_fresh, ((0, 0), (0, 0), (0, pad), (0, 0)))
    sp = s + pad
    nq = sp // qb
    scale = 1.0 / (hd ** 0.5)
    kern = functools.partial(_kernel, nb, qb, scale)
    # the minor axis walks nb pool pages then one fresh step; pool specs pin
    # their index during the fresh step (same block as the last page — the
    # unchanged index elides the DMA) and the fresh specs pin theirs during
    # the pool walk
    last = nb - 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_table, start
        grid=(r, kh, nq, nb + 1),
        in_specs=[
            pl.BlockSpec((1, 1, qb, g, hd),
                         lambda i, j, qi, si, bt, st: (i, j, qi, 0, 0)),
            pl.BlockSpec((1, 1, sp), lambda i, j, qi, si, bt, st: (i, 0, 0)),
            pl.BlockSpec((1, 1, qb), lambda i, j, qi, si, bt, st: (i, 0, qi)),
            pl.BlockSpec((1, 1, page, hd),
                         lambda i, j, qi, si, bt, st:
                         (bt[i, jnp.minimum(si, last)], j, 0, 0)),
            pl.BlockSpec((1, 1, 1, page),
                         lambda i, j, qi, si, bt, st:
                         (bt[i, jnp.minimum(si, last)], j, 0, 0)),
            pl.BlockSpec((1, 1, page, hd),
                         lambda i, j, qi, si, bt, st:
                         (bt[i, jnp.minimum(si, last)], j, 0, 0)),
            pl.BlockSpec((1, 1, 1, page),
                         lambda i, j, qi, si, bt, st:
                         (bt[i, jnp.minimum(si, last)], j, 0, 0)),
            pl.BlockSpec((1, 1, page),
                         lambda i, j, qi, si, bt, st:
                         (bt[i, jnp.minimum(si, last)], 0, 0)),
            pl.BlockSpec((1, 1, sp, hd),
                         lambda i, j, qi, si, bt, st: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, sp, hd),
                         lambda i, j, qi, si, bt, st: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, g, hd),
                               lambda i, j, qi, si, bt, st: (i, j, qi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((qb * g, 1), jnp.float32),
            pltpu.VMEM((qb * g, 1), jnp.float32),
            pltpu.VMEM((qb * g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, kh, sp, g, hd), jnp.float32),
        interpret=interpret,
    )(block_table, start, q, q_pos[:, None], q_pos[:, None], k_codes, k_scale,
      v_codes, v_scale, pool_pos, k_fresh, v_fresh)
    return out[:, :, :s]
