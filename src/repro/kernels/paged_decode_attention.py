"""Pallas TPU kernel: decode attention over a PAGED int8 KV-cache pool.

The dense ``kernels.decode_attention`` walk is already page-shaped: each grid
step loads one sequence block, masks by stored position, and folds it into
online-softmax state. This kernel keeps that online softmax and swaps the
addressing: a request's keys are *gathered* page by page from a shared pool
through its block table. The block table and the per-request query
positions are scalar-prefetched (``pltpu.PrefetchScalarGridSpec``), so the
page addresses are known before the body runs.

Pool layout (one pool per layer; `serving.kv_pool` owns allocation):

  k_codes  (P, K, page, hd) int8     k_scale (P, K, 1, page) f32
  v_codes  (P, K, page, hd) int8     v_scale (P, K, 1, page) f32
  pool_pos (P, 1, page)     int32    (-1 = empty/pad slot)

The unit axis before ``page`` on the scale and position leaves keeps every
copied block's last two dimensions whole: the TPU compiler only accepts
blocks whose last two dimensions are (8, 128)-divisible or span the array,
and a ``(1, page)`` slice of a ``(K, page)`` or ``(P, page)`` plane is
neither.

Per-request operands:

  q            (R, K, G, hd)        one query token per active slot
  block_table  (R, max_blocks) int32  page ids; unused entries point at the
                                      reserved trash page 0 (all pos = -1,
                                      masked like any empty slot)
  q_pos        (R,) int32           per-request absolute position (ragged
                                      batches decode at unequal positions)

Grid: ``(R, walk)``, one program per request row and step of its walk. A
step takes ``pages_per_step`` consecutive block-table entries (enough pages
for 256 tokens), each page with all K kv heads, which the pool stores as one
contiguous ``(K, page, hd)`` block per leaf. Each leaf is passed once per
page of a step, with its own block spec, so Pallas gathers the step's pages
and double-buffers them: the next step's copies are in flight while this
one computes. (A manual copy of one page's ``(K, 1, page)`` scale block is
refused by the TPU compiler, whose slices of a tiled ref must be whole
128-lane tiles.) The walk of a row stops at its last live entry,
``live_pages = q_pos // page + 1`` (0 for a free slot, ``q_pos = -1``):
the pool keeps a request's pages in position order (position ``t`` lives
in entry ``t // page``; freed and rolled-back positions are scrubbed to -1),
so no key the row may attend lies past that entry. Entries past it inside
the last live step map to the trash page; steps past it map to that step's
pages again, so Pallas copies nothing, and the body is skipped. A
fully-invalid page contributes garbage that the first valid key's
correction factor ``exp(m_prev - m_new) = exp(-inf)`` scrubs to zero — and
a row that saw no valid key (a free decode slot) is caught by the
epilogue's ``seen`` guard and emits exact zeros, never NaN (the oracle in
``ref.py`` does not model this free-slot case; parity holds on rows with
≥ 1 valid key).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
TRASH_PAGE = 0  # page id reserved by the pool for masked/pad gathers
STEP_TOKENS = 256  # tokens a walk step gathers


def pages_per_step(page: int, max_blocks: int) -> int:
    """Block-table entries one walk step gathers: enough pages for
    ``STEP_TOKENS`` tokens, never more than the table holds."""
    return max(1, min(max_blocks, -(-STEP_TOKENS // page)))


def live_pages(q_pos, page: int, max_blocks: int):
    """Block-table entries each row's walk covers: ``q_pos // page + 1``,
    0 for a free slot (``q_pos < 0``), at most ``max_blocks``. For NumPy
    and JAX integer arrays alike."""
    return (q_pos // page + 1).clip(0, max_blocks)


def _kernel(pps: int, scale: float, walk_ref, steps_ref, qp_ref, q_ref,
            *refs):
    # refs: pps blocks each of k_codes, k_scale, v_codes, v_scale, pool_pos
    # (one per page of the step), then the output and the scratch
    kc, ks, vc, vs, pos = (refs[n * pps:(n + 1) * pps] for n in range(5))
    o_ref, m_ref, l_ref, acc_ref = refs[5 * pps:]
    i, si = pl.program_id(0), pl.program_id(1)
    kh = q_ref.shape[1]

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(si < steps_ref[i])
    def _walk():
        qp = qp_ref[i]
        valid = [(p[0] >= 0) & (p[0] <= qp) for p in pos]  # (1, page) each

        def head(h, carry):
            q = q_ref[0, h].astype(jnp.float32) * scale  # (G, hd)
            scores = []
            for j in range(pps):
                # scales arrive as (1, page) rows; .T makes the column
                k = kc[j][0, h].astype(jnp.float32) * ks[j][0, h].T
                s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
                scores.append(jnp.where(valid[j], s, NEG_INF))  # (G, page)
            m_prev = m_ref[h]
            m_new = functools.reduce(
                jnp.maximum, [jnp.max(s, axis=-1, keepdims=True)
                              for s in scores], m_prev)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_ref[h] * corr
            acc = acc_ref[h] * corr
            for j, s in enumerate(scores):
                p = jnp.exp(s - m_new)
                v = vc[j][0, h].astype(jnp.float32) * vs[j][0, h].T
                l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
                acc = acc + jnp.dot(p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
            l_ref[h] = l_new
            acc_ref[h] = acc
            return carry

        jax.lax.fori_loop(0, kh, head, 0)

    @pl.when(si == pl.num_programs(1) - 1)
    def _finish():
        # a row that saw no valid key (free decode slot: q_pos = -1, no
        # live page) never raises m above its init — emit exact zeros
        # instead of the exp(0)-uniform average of trash values
        seen = m_ref[...] > NEG_INF * 0.5
        o_ref[0] = jnp.where(
            seen, acc_ref[...] / jnp.maximum(l_ref[...], 1e-30), 0.0)


def _page_map(n_steps: int, pps: int, j: int, trailing: int):
    """Index map of the ``j``-th page of a walk step: its entry of the
    row's walk table. A step past the row's last live one maps like that
    step, so its blocks repeat and Pallas copies nothing for it."""
    def index(i, si, walk, steps, qp):
        last = jnp.maximum(steps[i] - 1, 0)
        return (walk[(i * n_steps + jnp.minimum(si, last)) * pps + j],) + \
            (0,) * trailing
    return index


def paged_decode_attention(q, k_codes, k_scale, v_codes, v_scale, pool_pos,
                           block_table, q_pos, interpret: bool = False):
    """See module docstring. Returns (R, K, G, hd) f32."""
    r, kh, g, hd = q.shape
    p, _, page, _ = k_codes.shape
    nb = block_table.shape[1]
    assert block_table.shape[0] == r and q_pos.shape == (r,)
    assert k_scale.shape == (p, kh, 1, page) and pool_pos.shape == (p, 1, page)
    pps = pages_per_step(page, nb)
    n_steps = pl.cdiv(nb, pps)
    # each row's walk: its live entries, then the trash page up to a whole
    # number of steps; and the steps that hold a live entry
    n_live = live_pages(q_pos, page, nb)
    entry = jnp.arange(n_steps * pps)
    walk = jnp.where(entry < n_live[:, None],
                     jnp.pad(block_table, ((0, 0), (0, n_steps * pps - nb))),
                     TRASH_PAGE)
    steps = (n_live + pps - 1) // pps
    kern = functools.partial(_kernel, pps, 1.0 / (hd ** 0.5))
    leaves = (k_codes, k_scale, v_codes, v_scale, pool_pos)
    pages = [pl.BlockSpec((1, *leaf.shape[1:]),
                          _page_map(n_steps, pps, j, leaf.ndim - 1))
             for leaf in leaves for j in range(pps)]
    row = lambda i, si, *_: (i, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # walk table (flat), steps, q_pos
        grid=(r, n_steps),
        in_specs=[pl.BlockSpec((1, kh, g, hd), row), *pages],
        out_specs=pl.BlockSpec((1, kh, g, hd), row),
        scratch_shapes=[
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, kh, g, hd), jnp.float32),
        interpret=interpret,
    )(walk.reshape(-1), steps, q_pos, q,
      *(leaf for leaf in leaves for _ in range(pps)))
