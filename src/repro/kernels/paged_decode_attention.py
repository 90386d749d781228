"""Pallas TPU kernel: decode attention over a PAGED int8 KV-cache pool.

The dense ``kernels.decode_attention`` walk is already page-shaped: each grid
step loads one sequence block, masks by stored position, and folds it into
online-softmax state. This kernel keeps that walk unchanged and only swaps
the addressing — the minor grid axis no longer strides a per-request dense
cache but *gathers* the request's pages from a shared pool through a
block-table index map (``pltpu.PrefetchScalarGridSpec``: the block table and
per-request query positions are scalar-prefetched so the DMA addresses are
known before the body runs).

Pool layout (one pool per layer; `serving.kv_pool` owns allocation):

  k_codes  (P, K, page, hd) int8     k_scale (P, K, 1, page) f32
  v_codes  (P, K, page, hd) int8     v_scale (P, K, 1, page) f32
  pool_pos (P, 1, page)     int32    (-1 = empty/pad slot)

The unit axis before ``page`` on the scale and position leaves keeps every
block's last two dimensions whole: the TPU compiler only accepts blocks
whose last two dimensions are (8, 128)-divisible or span the array, and a
``(1, page)`` slice of a ``(K, page)`` or ``(P, page)`` plane is neither.

Per-request operands:

  q            (R, K, G, hd)        one query token per active slot
  block_table  (R, max_blocks) int32  page ids; unused entries point at the
                                      reserved trash page 0 (all pos = -1,
                                      masked like any empty slot)
  q_pos        (R,) int32           per-request absolute position (ragged
                                      batches decode at unequal positions)

Grid: one program per (request, kv_head); the minor axis walks the request's
``max_blocks`` block-table entries. A fully-invalid page (trash or padding)
contributes garbage that the first valid page's correction factor
``exp(m_prev - m_new) = exp(-inf)`` scrubs to zero — and a row whose table
is ALL trash (a free decode slot) is caught by the epilogue's ``seen`` guard
and emits exact zeros, never NaN (the oracle in ``ref.py`` does not model
this free-slot case; parity holds on rows with ≥ 1 valid key).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
TRASH_PAGE = 0  # page id reserved by the pool for masked/pad gathers


def _kernel(nb: int, scale: float, bt_ref, qp_ref, q_ref, kc_ref, ks_ref,
            vc_ref, vs_ref, pos_ref, o_ref, m_ref, l_ref, acc_ref):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    r = pl.program_id(0)
    q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, hd)
    # scales arrive as (1, page) rows; .T makes the per-token column
    k = kc_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0].T  # (page, hd)
    v = vc_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0].T
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (G, page)

    kv_pos = pos_ref[0]  # (1, page)
    valid = (kv_pos >= 0) & (kv_pos <= qp_ref[r])
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(si == nb - 1)
    def _finish():
        # a row whose every page was masked (free decode slot: all-trash
        # block table, q_pos = -1) never raises m above its init — emit
        # exact zeros instead of the exp(0)-uniform average of trash values
        seen = m_ref[...] > NEG_INF * 0.5
        o_ref[0, 0] = jnp.where(
            seen, acc_ref[...] / jnp.maximum(l_ref[...], 1e-30), 0.0)


def paged_decode_attention(q, k_codes, k_scale, v_codes, v_scale, pool_pos,
                           block_table, q_pos, interpret: bool = False):
    """See module docstring. Returns (R, K, G, hd) f32."""
    r, kh, g, hd = q.shape
    p, _, page, _ = k_codes.shape
    nb = block_table.shape[1]
    assert block_table.shape[0] == r and q_pos.shape == (r,)
    assert k_scale.shape == (p, kh, 1, page) and pool_pos.shape == (p, 1, page)
    scale = 1.0 / (hd ** 0.5)
    kern = functools.partial(_kernel, nb, scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_table, q_pos
        grid=(r, kh, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda i, j, si, bt, qp: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, page, hd),
                         lambda i, j, si, bt, qp: (bt[i, si], j, 0, 0)),
            pl.BlockSpec((1, 1, 1, page),
                         lambda i, j, si, bt, qp: (bt[i, si], j, 0, 0)),
            pl.BlockSpec((1, 1, page, hd),
                         lambda i, j, si, bt, qp: (bt[i, si], j, 0, 0)),
            pl.BlockSpec((1, 1, 1, page),
                         lambda i, j, si, bt, qp: (bt[i, si], j, 0, 0)),
            pl.BlockSpec((1, 1, page),
                         lambda i, j, si, bt, qp: (bt[i, si], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda i, j, si, bt, qp: (i, j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, kh, g, hd), jnp.float32),
        interpret=interpret,
    )(block_table, q_pos, q, k_codes, k_scale, v_codes, v_scale, pool_pos)
