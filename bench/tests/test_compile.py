"""Compile rehearsal for the benchmark's cells on a described (not
attached) TPU v5e chip: the paged attention kernels at each cell's tick
shapes, and each configuration's whole decode and continuation-chunk
step programs with their weights and pool, so that a shape the TPU
compiler refuses, or a program that does not fit the chip, shows here
before any chip time is spent.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import lengths
import run

HBM = 16 * 2**30  # one v5e chip


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


def _cells():
    return [w["name"] for w in
            run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))["workloads"]]


def _shapes(name):
    spec = run.resolve(name)
    cfg, mix = spec["config"], spec["traffic"]
    ref = run.load_module(os.path.join(run.BENCH, "configs",
                                       cfg["reference"] + ".py"))
    srv = cfg["serving"]
    k = ref.dims(cfg)
    page = srv["page_size"]
    weights = ref.param_count(cfg) * 2
    pages = (HBM - weights - run.RESERVE_BYTES) // (
        run.POOL_COPIES * ref.page_bytes(cfg, page))
    nb = -(-lengths.max_seq_len(mix) // page)
    return cfg, k, int(pages), nb, srv


@pytest.mark.parametrize("name", _cells())
def test_kernels_compile_at_cell_shapes(name, one_chip):
    from repro.kernels import ops

    cfg, k, pages, nb, srv = _shapes(name)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    r, kh, hd, g, page = srv["max_slots"], k["kh"], k["hd"], k["h"] // k["kh"], \
        srv["page_size"]
    c = srv["prefill_chunk"]
    i32, bf16, f32 = jnp.int32, jnp.bfloat16, jnp.float32
    pool = (s((pages, kh, page, hd), jnp.int8), s((pages, kh, 1, page), f32),
            s((pages, kh, page, hd), jnp.int8), s((pages, kh, 1, page), f32),
            s((pages, 1, page), i32))
    dec = jax.jit(lambda *a: ops.paged_decode_attention(*a, interpret=False))
    dec.lower(s((r, kh, g, hd), bf16), *pool, s((r, nb), i32),
              s((r,), i32)).compile()
    pre = jax.jit(lambda *a: ops.paged_prefill_attention(*a, interpret=False))
    pre.lower(s((r, kh, c, g, hd), bf16), *pool, s((r, nb), i32),
              s((r, c), i32), s((r, kh, c, hd), bf16),
              s((r, kh, c, hd), bf16)).compile()


@pytest.mark.parametrize("name", _cells())
def test_tick_programs_fit_one_chip(name, one_chip, monkeypatch):
    """The decode step and the continuation-chunk step of the program, at
    the cell's shapes, compile for the chip and fit its memory beside the
    weights and the pool."""
    from repro.kernels import ops as kops
    from repro.models import transformer as T
    from repro.models.layers import PagedKVCache

    monkeypatch.setattr(kops, "_default_interpret", lambda: False)
    cfg, k, pages, nb, srv = _shapes(name)
    prog = run.program_module(cfg)
    acfg, opts = prog.arch_config(cfg), prog.runtime_opts(cfg)
    put = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(put, T.abstract_params(acfg))
    n, kh, hd, page, r = k["n"], k["kh"], k["hd"], srv["page_size"], \
        srv["max_slots"]
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    caches = (PagedKVCache(
        s((n, pages, kh, page, hd), jnp.int8),
        s((n, pages, kh, page, hd), jnp.int8),
        s((n, pages, kh, 1, page), jnp.float32),
        s((n, pages, kh, 1, page), jnp.float32),
        s((n, pages, 1, page), jnp.int32), s((n, r, nb), jnp.int32)),)
    c = srv["prefill_chunk"]
    dec = jax.jit(lambda p, t, ca, pos: T.paged_decode_step(p, acfg, t, ca,
                                                            pos, opts))
    pre = jax.jit(lambda p, t, ca, pos: T.paged_prefill_shared(p, acfg, t,
                                                               ca, pos, opts))
    for prog, toks in ((dec, s((r, 1), jnp.int32)),
                       (pre, s((r, c), jnp.int32))):
        pos = s((r,), jnp.int32) if prog is dec else s((r, c), jnp.int32)
        compiled = prog.lower(params, toks, caches, pos).compile()
        mem = compiled.memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        assert total < HBM, f"{name}: {total} bytes > one chip"
