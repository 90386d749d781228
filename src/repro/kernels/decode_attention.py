"""Pallas TPU kernel: decode attention over an int8-quantized KV cache.

The §Roofline analysis shows decode is memory-bound with the cache read as
the irreducible term — so the kernel's job is to stream the int8 cache
through VMEM ONCE, dequantizing in-register (the pure-XLA path on CPU
materializes an f32 copy of the cache; on TPU the fusion is also not
guaranteed across the scale-multiply + masked-softmax chain).

Layout: one program per (batch, kv-head); the grid's minor axis walks the
sequence in BS-sized blocks carrying online-softmax state (m, l, acc) in
VMEM scratch. GQA handled by processing all G = H/K query heads of the
kv-head together — the (G, BS) score tile feeds the MXU with hd as the
contraction dim.

  q        (B, K, G, hd)   bf16/f32
  k_codes  (B, K, S, hd)   int8      k_scale (B, K, S)   f32
  v_codes  (B, K, S, hd)   int8      v_scale (B, K, S)   f32
  kv_pos   (B, S)          int32     (-1 = empty slot)
  q_pos    scalar int32    (current absolute position, causal bound)
  out      (B, K, G, hd)   f32

The scales and positions enter the kernel as (B, K, 1, S) and (B, 1, S):
the TPU compiler only accepts blocks whose last two dimensions are
(8, 128)-divisible or span the array, which a (1, BS) slice of a (K, S) or
(B, S) plane is not.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_S = 512  # default sequence-block size of the grid's minor axis


def padded_cache_len(s: int, block_s: int = BLOCK_S, uniform: bool = False) -> int:
    """Round a cache length up to a whole number of kernel blocks.

    With ``uniform=False`` (default — the dense-cache contract), a length
    ``s <= block_s`` is returned UNPADDED: the dense kernel clamps its block
    size to ``min(block_s, s)``, so a single short block needs no padding.
    Callers that allocate caches at this size (pad slots carry
    ``kv_pos = -1``) keep the per-step path copy-free; other lengths still
    work via the pad-on-call fallback below.

    With ``uniform=True``, every length — including ``s <= block_s`` — is
    rounded up to whole ``block_s``-sized blocks. This is the PAGED-POOL
    contract: ``serving.kv_pool`` pages must all be exactly one block long
    (the block-table index map addresses the pool in fixed page strides), so
    the short-block exemption above would produce a non-uniform final page.
    The pool rejects non-multiple lengths with a clear error and points here.
    """
    if not uniform and s <= block_s:
        return s  # a single (possibly short) block — no padding needed
    return -(-s // block_s) * block_s


def _kernel(ns: int, scale: float, q_ref, kc_ref, ks_ref, vc_ref, vs_ref,
            pos_ref, qpos_ref, o_ref, m_ref, l_ref, acc_ref):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, hd)
    # scales arrive as (1, BS) rows; .T makes the per-token column
    k = kc_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0].T  # (BS, hd)
    v = vc_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0].T
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (G, BS)

    kv_pos = pos_ref[0]  # (1, BS)
    valid = (kv_pos >= 0) & (kv_pos <= qpos_ref[0, 0])
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(si == ns - 1)
    def _finish():
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def decode_attention(q, k_codes, k_scale, v_codes, v_scale, kv_pos, q_pos,
                     block_s: int = BLOCK_S, interpret: bool = False):
    """See module docstring. Returns (B, K, G, hd) f32.

    ``cache_len`` need not divide ``block_s``: the trailing block is padded
    and the pad slots carry ``kv_pos = -1``, which the in-kernel validity
    mask already treats as empty."""
    b, kh, g, hd = q.shape
    s = k_codes.shape[2]
    bs = min(block_s, s)
    pad = (-s) % bs
    if pad:
        k_codes = jnp.pad(k_codes, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_codes = jnp.pad(v_codes, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_scale = jnp.pad(k_scale, ((0, 0), (0, 0), (0, pad)))
        v_scale = jnp.pad(v_scale, ((0, 0), (0, 0), (0, pad)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)
        s += pad
    ns = s // bs
    scale = 1.0 / (hd ** 0.5)
    kern = functools.partial(_kernel, ns, scale)
    qpos_arr = jnp.full((1, 1), q_pos, jnp.int32)
    return pl.pallas_call(
        kern,
        grid=(b, kh, ns),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda i, j, si: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda i, j, si: (i, j, si, 0)),
            pl.BlockSpec((1, 1, 1, bs), lambda i, j, si: (i, j, 0, si)),
            pl.BlockSpec((1, 1, bs, hd), lambda i, j, si: (i, j, si, 0)),
            pl.BlockSpec((1, 1, 1, bs), lambda i, j, si: (i, j, 0, si)),
            pl.BlockSpec((1, 1, bs), lambda i, j, si: (i, 0, si)),
            pl.BlockSpec((1, 1), lambda i, j, si: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda i, j, si: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, hd), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k_codes, k_scale[:, :, None], v_codes, v_scale[:, :, None],
      kv_pos[:, None], qpos_arr)
