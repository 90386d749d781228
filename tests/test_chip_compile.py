"""Compile rehearsal: every Pallas kernel in ``repro.kernels``, compiled for a
described (not attached) TPU v5e chip at Llama-2-7B widths — 32 kv heads of
128, 16-token pages, a 256-page pool, 8 slots, a 260-token packed budget —
with ``interpret=False``; the split payload kernels at 256 tokens of
d_model 4096. The TPU compiler refuses block shapes, layouts
and VMEM use that interpret mode accepts, so these catch at no chip time
what would otherwise fail on the first tick.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

P, K, PAGE, HD, G = 256, 32, 16, 128, 1  # pool pages, kv heads, page, head dim
R, NB, T, S, D = 8, 64, 260, 256, 4096   # slots, pages/slot, budget, chunk
M = 256  # tokens per split-boundary payload (ts_mask, tabq, dequant_matmul)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described chip, with the persistent compilation
    cache off: such compiles cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _cases(dev):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=dev)
    i32, bf16, f32 = jnp.int32, jnp.bfloat16, jnp.float32
    pool = (s((P, K, PAGE, HD), jnp.int8), s((P, K, 1, PAGE), f32),
            s((P, K, PAGE, HD), jnp.int8), s((P, K, 1, PAGE), f32),
            s((P, 1, PAGE), i32))
    return {
        "paged_decode_attention": (ops.paged_decode_attention, (
            s((R, K, G, HD), bf16), *pool, s((R, NB), i32), s((R,), i32)), {}),
        "paged_prefill_attention": (ops.paged_prefill_attention, (
            s((4, K, S, G, HD), bf16), *pool, s((4, NB), i32),
            s((4, S), i32), s((4, K, S, HD), bf16), s((4, K, S, HD), bf16)),
            {}),
        "varlen_attention": (ops.varlen_attention, (
            s((K, T, G, HD), bf16), *pool, s((4, NB), i32), s((T,), i32),
            s((T,), i32), s((K, T, HD), f32), s((K, T, HD), f32)), {}),
        "decode_attention": (ops.decode_attention, (
            s((R, K, G, HD), bf16), s((R, K, 1024, HD), jnp.int8),
            s((R, K, 1024), f32), s((R, K, 1024, HD), jnp.int8),
            s((R, K, 1024), f32), s((R, 1024), i32), s((), i32)), {}),
        "ts_mask": (ops.ts_mask, (s((M, D), bf16),), dict(tau=0.5)),
        "tabq_quantize": (ops.tabq_quantize, (s((M, D), bf16),), {}),
        "dequant_matmul": (ops.dequant_matmul, (
            s((M, D), bf16), s((D, 11008), jnp.int8), s((11008,), f32)), {}),
    }


@pytest.mark.parametrize("name", [
    "paged_decode_attention", "paged_prefill_attention", "varlen_attention",
    "decode_attention", "ts_mask", "tabq_quantize", "dequant_matmul"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args, kw = _cases(one_chip)[name]
    lowered = fn.lower(*args, interpret=False, **kw)
    assert "tpu_custom_call" in lowered.as_text()  # the Mosaic kernel itself
    lowered.compile()  # raises what the chip's compiler would raise
