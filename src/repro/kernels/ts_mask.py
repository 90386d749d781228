"""Pallas TPU kernel: threshold split (TS, Eq. 4) — mask + below-tensor +
per-tile outlier counts in one VMEM pass.

TPU adaptation of the paper's CSR extraction (see DESIGN.md §2): the kernel
emits (below, mask, per-tile counts); the host/XLA side turns counts into
offsets and compacts the few outliers (≈0.0005 % above τ=100) — the dense
O(N) scan is what belongs on the TPU, the O(nnz) tail doesn't."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ts_kernel(tau_ref, x_ref, below_ref, mask_ref, count_ref):
    x = x_ref[...].astype(jnp.float32)
    tau = tau_ref[0, 0]
    mask = jnp.abs(x) >= tau
    below_ref[...] = jnp.where(mask, 0.0, x)
    mask_ref[...] = mask.astype(jnp.uint8)
    count_ref[...] = jnp.full(count_ref.shape, jnp.sum(mask.astype(jnp.int32)))


def ts_mask(x: jax.Array, tau: float, block_t: int = 8,
            interpret: bool = False):
    """x (T, D) → (below f32 (T, D), mask u8 (T, D), counts i32 (T//bt, 1))."""
    t, d = x.shape
    assert t % block_t == 0
    grid = (t // block_t,)
    tau_arr = jnp.full((1, 1), tau, jnp.float32)
    # counts carry a unit axis so each (1, 1) count block spans its array's
    # last two dimensions, as the TPU compiler requires
    below, mask, counts = pl.pallas_call(
        _ts_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
            pl.BlockSpec((block_t, d), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, d), jnp.float32),
            jax.ShapeDtypeStruct((t, d), jnp.uint8),
            jax.ShapeDtypeStruct((t // block_t, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(tau_arr, x)
    return below, mask, counts[:, 0]
