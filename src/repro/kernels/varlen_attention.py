"""Pallas TPU kernel: token-packed VARLEN attention over a paged int8 KV pool.

The chunked-prefill tick pads every prefill wave to a ``(max_slots, chunk)``
rectangle and runs decode as a SECOND compiled step — at high occupancy most
of that rectangle is pad and every decoding request pays two dispatches per
tick. This kernel serves ONE flat token batch instead: ragged prefill chunks
and single decode tokens from different requests coexist in the same call
(a decode token is just a length-1 segment), so the scheduler's whole tick
is one fixed-shape dispatch whose pad is only the flat buffer's tail.

Queries arrive as ``(K, T, G, hd)`` — a flat token axis of ``T =
token_budget`` rows, each carrying its request's slot id (= block-table
row) and absolute position. Two key groups fold into one online softmax per
row, exactly like the rectangular prefill kernel
(``kernels.paged_prefill_attention``), but masked per TOKEN rather than per
grid row:

  * POOL HISTORY — the minor grid axis walks EVERY slot's block-table pages
    (step ``si`` serves slot ``si // nb``, page ``block_table[slot,
    si % nb]``); a page's keys are valid only for query rows whose slot id
    matches the step's slot AND whose stored positions lie below that
    slot's first in-call position ``start[slot]`` (tokens this very call
    scatters into the pool are excluded — they are attended as fresh keys
    instead). ``start[slot]`` is each row's causal history bound: every
    row of the slot sits at a position ``>= start``, so the per-row causal
    check is implied by the per-slot one.
  * FRESH KEYS — the call's own k/v ``(K, T, hd)`` at full precision,
    walked as the final minor step with a block-diagonal causal mask:
    key column ``c`` is valid for query row ``r`` iff both carry the same
    slot id and ``q_pos[c] <= q_pos[r]``.

Operand layout (pool exactly as ``serving.kv_pool`` holds it):

  q            (K, T, G, hd)         flat token batch, kv-head-major
  k/v_codes    (P, K, page, hd) int8  k/v_scale (P, K, 1, page) f32
  pool_pos     (P, 1, page) int32     (-1 = empty slot)
  block_table  (R, nb) int32          (unused entries → trash page 0)
  q_pos        (T,) int32             per-token absolute positions (-1 pad)
  tok_slot     (T,) int32             per-token slot ids (-1 pad)
  start        (R,) int32             per-slot first in-call position
                                      (2^30 for slots absent from the call)
  k/v_fresh    (K, T, hd)             this call's keys/values, full precision
  out          (K, T, G, hd) f32

The pool leaves' unit axis before ``page`` keeps each per-page block's
last two dimensions whole, as the TPU compiler requires.

Grid: one program per (kv_head, minor step); total page visits are
``R * nb`` — identical to the decode kernel's ``(R, K, nb)`` grid, so the
packed tick never walks more pages than the two-step tick it replaces. A
fully-masked step contributes garbage that the next valid step's correction
factor ``exp(m_prev - m_new) = exp(-inf) = 0`` scrubs exactly; a pad row
(slot id -1, position -1) matches no key anywhere and the epilogue's
``seen`` guard emits exact zeros for it, never NaN.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_prefill_attention import _fold

NEG_INF = -1e30
TRASH_PAGE = 0  # page id reserved by the pool for masked/pad gathers


def segment_start(q_pos, tok_slot, num_slots: int):
    """``start`` (R,) from flat per-token operands: each slot's FIRST
    in-call position (2^30 for slots with no tokens in the call, which
    every mask neutralizes). The single source the kernel route, the
    dense fallback, and the oracle all derive the history bound from —
    they can never disagree on it."""
    q_pos = jnp.asarray(q_pos, jnp.int32).reshape(-1)
    sl = jnp.asarray(tok_slot, jnp.int32).reshape(-1)
    big = jnp.int32(2 ** 30)
    vals = jnp.where((sl >= 0) & (q_pos >= 0), q_pos, big)
    return jnp.full((num_slots,), big, jnp.int32).at[
        jnp.maximum(sl, 0)].min(vals)


def _kernel(nsteps: int, nb: int, t: int, scale: float, bt_ref, start_ref,
            q_ref, qp_ref, sl_ref, kc_ref, ks_ref, vc_ref, vs_ref, pos_ref,
            fk_ref, fv_ref, o_ref, m_ref, l_ref, acc_ref):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # (T, G, hd)
    g, hd = q.shape[1], q.shape[2]
    q2 = q.reshape(t * g, hd)
    qp = qp_ref[0]  # (T,) per-token positions
    sl = sl_ref[0]  # (T,) per-token slot ids

    @pl.when(si < nsteps)
    def _pool_page():
        slot = si // nb  # the slot this walk step serves
        start = start_ref[slot]
        k = kc_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0].T  # (page, hd)
        v = vc_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0].T
        kv_pos = pos_ref[0]  # (1, page)
        # history only, and only for this step's slot: positions below the
        # slot's first in-call position (this call's own tokens live in the
        # pool too — post-update — and are attended as fresh keys instead;
        # every row of the slot sits at >= start, so per-row causality is
        # implied). Pad rows carry slot -1 and match nothing.
        valid = ((sl[:, None] == slot) & (kv_pos >= 0) & (kv_pos < start))
        _fold(q2, k, v, valid, g, m_ref, l_ref, acc_ref)

    @pl.when(si == nsteps)
    def _fresh_and_finish():
        k = fk_ref[0].astype(jnp.float32)  # (T, hd) full precision
        v = fv_ref[0].astype(jnp.float32)
        # block-diagonal causal mask over the flat batch: same slot,
        # causally ordered; pad columns (position -1) match no row and pad
        # rows (position -1) accept no column
        valid = ((sl[None, :] == sl[:, None]) & (sl[None, :] >= 0)
                 & (qp[None, :] >= 0) & (qp[None, :] <= qp[:, None]))
        _fold(q2, k, v, valid, g, m_ref, l_ref, acc_ref)
        # a row whose every key was masked (a pad row in the fixed-budget
        # buffer) never raises m above its init — emit exact zeros, not
        # the exp(0)-uniform average of garbage values
        seen = m_ref[...] > NEG_INF * 0.5
        out = jnp.where(seen, acc_ref[...] / jnp.maximum(l_ref[...], 1e-30),
                        0.0)
        o_ref[0] = out.reshape(t, g, hd)


def varlen_attention(q, k_codes, k_scale, v_codes, v_scale, pool_pos,
                     block_table, q_pos, tok_slot, start, k_fresh, v_fresh,
                     interpret: bool = False):
    """See module docstring. Returns (K, T, G, hd) f32."""
    kh, t, g, hd = q.shape
    p, _, page, _ = k_codes.shape
    r, nb = block_table.shape
    assert q_pos.shape == (t,) and tok_slot.shape == (t,)
    assert start.shape == (r,) and pool_pos.shape == (p, 1, page)
    assert k_fresh.shape == (kh, t, hd) and v_fresh.shape == k_fresh.shape
    nsteps = r * nb
    scale = 1.0 / (hd ** 0.5)
    kern = functools.partial(_kernel, nsteps, nb, t, scale)
    # the minor axis walks every slot's nb pages then one fresh step; pool
    # specs pin their index during the fresh step (same block as the last
    # page — the unchanged index elides the DMA) and the fresh specs pin
    # theirs during the pool walk
    last = nsteps - 1

    def page_of(si):
        su = jnp.minimum(si, last)
        return (su // nb, su % nb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_table, start
        grid=(kh, nsteps + 1),
        in_specs=[
            pl.BlockSpec((1, t, g, hd), lambda j, si, bt, st: (j, 0, 0, 0)),
            pl.BlockSpec((1, t), lambda j, si, bt, st: (0, 0)),
            pl.BlockSpec((1, t), lambda j, si, bt, st: (0, 0)),
            pl.BlockSpec((1, 1, page, hd),
                         lambda j, si, bt, st: (bt[page_of(si)], j, 0, 0)),
            pl.BlockSpec((1, 1, 1, page),
                         lambda j, si, bt, st: (bt[page_of(si)], j, 0, 0)),
            pl.BlockSpec((1, 1, page, hd),
                         lambda j, si, bt, st: (bt[page_of(si)], j, 0, 0)),
            pl.BlockSpec((1, 1, 1, page),
                         lambda j, si, bt, st: (bt[page_of(si)], j, 0, 0)),
            pl.BlockSpec((1, 1, page),
                         lambda j, si, bt, st: (bt[page_of(si)], 0, 0)),
            pl.BlockSpec((1, t, hd), lambda j, si, bt, st: (j, 0, 0)),
            pl.BlockSpec((1, t, hd), lambda j, si, bt, st: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, g, hd),
                               lambda j, si, bt, st: (j, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((t * g, 1), jnp.float32),
            pltpu.VMEM((t * g, 1), jnp.float32),
            pltpu.VMEM((t * g, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kh, t, g, hd), jnp.float32),
        interpret=interpret,
    )(block_table, start, q, q_pos[None], tok_slot[None], k_codes, k_scale,
      v_codes, v_scale, pool_pos, k_fresh, v_fresh)
