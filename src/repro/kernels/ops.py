"""Jit'd public wrappers over the Pallas kernels.

``interpret=None`` (the default) follows the default backend: compiled
Mosaic kernels on a TPU, the Pallas interpreter anywhere else (CPU test
runs, where the kernel bodies execute as plain JAX against the oracles in
``kernels.ref``). Pass ``interpret=False`` to lower for a TPU from a
machine without one, e.g. to compile for a described topology.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.dequant_matmul import dequant_matmul as _dequant_matmul
from repro.kernels.tabq_kernel import tabq_quantize as _tabq_quantize
from repro.kernels.ts_mask import ts_mask as _ts_mask


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("bits", "block_t", "interpret"))
def tabq_quantize(x, bits: int = 8, block_t: int = 8, interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _tabq_quantize(x, bits, block_t, interpret)


def tabq_dequantize(codes, scale, zero, sign):
    return ref.tabq_dequantize_ref(codes, scale, zero, sign)


@partial(jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret"))
def dequant_matmul(x, w_codes, w_scale, block_m: int = 128, block_n: int = 128,
                   block_k: int = 512, interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _dequant_matmul(x, w_codes, w_scale, block_m, block_n, block_k,
                           interpret)


@partial(jax.jit, static_argnames=("tau", "block_t", "interpret"))
def ts_mask(x, tau: float, block_t: int = 8, interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _ts_mask(x, tau, block_t, interpret)


@partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(q, k_codes, k_scale, v_codes, v_scale, kv_pos, q_pos,
                     block_s: int | None = None, interpret: bool | None = None):
    from repro.kernels.decode_attention import BLOCK_S
    from repro.kernels.decode_attention import decode_attention as _da

    block_s = BLOCK_S if block_s is None else block_s

    interpret = _default_interpret() if interpret is None else interpret
    return _da(q, k_codes, k_scale, v_codes, v_scale, kv_pos, q_pos,
               block_s, interpret)


@partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_codes, k_scale, v_codes, v_scale, pool_pos,
                           block_table, q_pos, interpret: bool | None = None):
    from repro.kernels.paged_decode_attention import \
        paged_decode_attention as _pda

    interpret = _default_interpret() if interpret is None else interpret
    return _pda(q, k_codes, k_scale, v_codes, v_scale, pool_pos, block_table,
                q_pos, interpret)


@partial(jax.jit, static_argnames=("q_block", "interpret"))
def paged_prefill_attention(q, k_codes, k_scale, v_codes, v_scale, pool_pos,
                            block_table, q_pos, k_fresh, v_fresh,
                            q_block: int = 128, interpret: bool | None = None):
    """Ragged prefill page walk: q (R,K,S,G,hd) against the pool's history
    pages (masked below each row's first in-call position) plus the call's
    fresh k/v (R,K,S,hd) at full precision. ``start`` is derived from
    ``q_pos`` here so kernel and callers can never disagree on it."""
    from repro.kernels.paged_prefill_attention import (
        first_call_position, paged_prefill_attention as _ppa)

    interpret = _default_interpret() if interpret is None else interpret
    q_pos = jnp.asarray(q_pos, jnp.int32)
    start = first_call_position(q_pos)
    return _ppa(q, k_codes, k_scale, v_codes, v_scale, pool_pos, block_table,
                q_pos, start, k_fresh, v_fresh, q_block, interpret)


@partial(jax.jit, static_argnames=("interpret",))
def varlen_attention(q, k_codes, k_scale, v_codes, v_scale, pool_pos,
                     block_table, q_pos, tok_slot, k_fresh, v_fresh,
                     interpret: bool | None = None):
    """Token-packed varlen page walk: ONE flat batch q (K,T,G,hd) whose rows
    carry their own slot id and position, so ragged prefill chunks and
    single decode tokens coexist in one call. Attends each row's pool
    history (stored positions below its slot's first in-call position) plus
    the call's fresh k/v (K,T,hd) under a block-diagonal causal mask.
    ``start`` is derived from (q_pos, tok_slot) here so kernel and callers
    can never disagree on it."""
    from repro.kernels.varlen_attention import (
        segment_start, varlen_attention as _va)

    interpret = _default_interpret() if interpret is None else interpret
    q_pos = jnp.asarray(q_pos, jnp.int32)
    tok_slot = jnp.asarray(tok_slot, jnp.int32)
    start = segment_start(q_pos, tok_slot, block_table.shape[0])
    return _va(q, k_codes, k_scale, v_codes, v_scale, pool_pos, block_table,
               q_pos, tok_slot, start, k_fresh, v_fresh, interpret)
