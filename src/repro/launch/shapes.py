"""Assigned input shapes and per-(arch × shape) lowering targets.

  train_4k        seq 4,096    global_batch 256  → train_step (grad-accum scan)
  prefill_32k     seq 32,768   global_batch 32   → chunked prefill
  decode_32k      seq 32,768   global_batch 128  → serve_step (1 token, full KV)
  long_500k       seq 524,288  global_batch 1    → serve_step, context-parallel
  paged_decode_32k seq 32,768  global_batch 128  → paged_decode_step (ragged
                                                   pool, block-table kernel)

``input_specs(cfg, shape, mesh)`` returns (fn, args) where args are
ShapeDtypeStructs with NamedShardings attached — weak-type-correct,
shardable, zero allocation.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.launch import sharding as shd
from repro.launch.mesh import data_axes, data_size
from repro.models.transformer import RuntimeOpts, init_caches, prefill
from repro.serving.engine import serve_step_fn
from repro.training.optimizer import AdamWConfig, adamw_init
from repro.training.train_loop import TrainConfig, make_train_step


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
    # ragged continuous-batching decode: one paged_decode_step over a shared
    # kv_pool at decode_32k scale — kernel pages of BLOCK_S, pool page axis
    # sharded over the data axes, block tables replicated
    "paged_decode_32k": ShapeSpec("paged_decode_32k", 32768, 128,
                                  "paged_decode"),
}

MICRO_GLOBAL = 32  # tokensets per grad-accum microbatch (train_4k)


def supports(cfg: ArchConfig, shape: ShapeSpec) -> bool:
    """long_500k only for sub-quadratic stacks (DESIGN.md §Arch-applicability);
    the paged pool covers attention-only patterns without sliding windows
    (see serving.kv_pool)."""
    if shape.name == "long_500k":
        return cfg.supports_long_context
    if shape.kind == "paged_decode":
        from repro.configs.base import AttnSpec

        return all(isinstance(ls.mixer, AttnSpec)
                   and ls.mixer.sliding_window is None for ls in cfg.pattern)
    return True


def _token_struct(cfg: ArchConfig, b: int, s: int, mesh, b_axes, lead=()):
    shape = lead + ((b, s, cfg.num_codebooks) if cfg.embed == "musicgen" else (b, s))
    spec = [None] * len(shape)
    spec[len(lead)] = b_axes
    return jax.ShapeDtypeStruct(shape, jnp.int32,
                                sharding=NamedSharding(mesh, P(*spec)))


def _patch_struct(cfg: ArchConfig, b: int, mesh, b_axes, lead=()):
    if cfg.embed != "vlm":
        return None
    shape = lead + (b, cfg.num_patches, cfg.d_vision)
    spec = [None] * len(shape)
    spec[len(lead)] = b_axes
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                sharding=NamedSharding(mesh, P(*spec)))


def default_opts(cfg: ArchConfig, shape: ShapeSpec, **overrides) -> RuntimeOpts:
    base = dict(q_chunk=1024, kv_chunk=1024, remat=True,
                # paper's Q^a on the cache: kv-head-major int8 codes +
                # per-(token, head) f32 scales (the Pallas decode-attention
                # layout — init_caches/cache_specs carry the dtypes/shapes;
                # the paged pool is int8 by construction)
                quantized_kv=shape.kind in ("decode", "paged_decode"),
                moe_capacity_factor=1.25)
    if shape.kind in ("decode", "paged_decode"):
        # single KV block: no scan over a sharded cache dim (DESIGN.md §5);
        # bf16 SSD-state storage (f32 compute) — jamba fit fix
        base.update(kv_chunk=shape.seq_len, q_chunk=1, remat=False,
                    ssm_state_dtype="bfloat16")
    base.update(overrides)
    return RuntimeOpts(**base)


# ------------------------------------------------------------------ train


def train_target(cfg: ArchConfig, shape: ShapeSpec, mesh, opts: RuntimeOpts,
                 param_dtype=jnp.bfloat16):
    import dataclasses

    from repro.models.transformer import abstract_params

    dax = data_axes(mesh)
    if opts.act_sharding is None:
        # pin the residual stream to (batch=data, seq=None, d=None) across
        # the block scan (§Perf hillclimb 2)
        opts = dataclasses.replace(opts, act_sharding=(dax, None, None))
    if opts.moe_groups == 1:
        # shard-local expert dispatch (§Perf hillclimb 2): kills the global
        # dispatch scatter's full-buffer all-reduce
        opts = dataclasses.replace(opts, moe_groups=data_size(mesh))
    accum = max(1, shape.global_batch // MICRO_GLOBAL)
    micro = shape.global_batch // accum
    tc = TrainConfig(optimizer=AdamWConfig(), accum_steps=accum,
                     batch_pre_split=True)

    params = abstract_params(cfg, param_dtype)
    pspecs = shd.param_specs(cfg, mesh, fsdp=True)
    params = shd.to_shaped(params, pspecs, mesh)
    opt = jax.eval_shape(adamw_init, params)
    ospecs = shd.opt_state_specs(pspecs)
    opt = shd.to_shaped(opt, ospecs, mesh)

    lead = (accum,) if accum > 1 else ()
    b = micro if accum > 1 else shape.global_batch
    batch = {
        "tokens": _token_struct(cfg, b, shape.seq_len, mesh, dax, lead),
        "labels": _token_struct(cfg, b, shape.seq_len, mesh, dax, lead),
        "loss_mask": jax.ShapeDtypeStruct(
            lead + (b, shape.seq_len), jnp.float32,
            sharding=NamedSharding(mesh, P(*([None] * len(lead)), dax, None))),
    }
    if cfg.embed == "vlm":
        batch["patches"] = _patch_struct(cfg, b, mesh, dax, lead)
    if cfg.embed == "musicgen":
        # labels carry the codebook axis too
        batch["labels"] = _token_struct(cfg, b, shape.seq_len, mesh, dax, lead)

    fn = make_train_step(cfg, tc, opts)
    return fn, (params, opt, batch)


# ---------------------------------------------------------------- prefill


def make_prefill_chunked(cfg: ArchConfig, opts: RuntimeOpts, n_chunks: int):
    def fn(params, tokens, patches=None):
        if n_chunks == 1:
            return prefill(params, cfg, tokens, patches, None, opts)
        b = tokens.shape[0]
        bs = b // n_chunks
        toks = tokens.reshape(n_chunks, bs, *tokens.shape[1:])
        pat = (patches.reshape(n_chunks, bs, *patches.shape[1:])
               if patches is not None else None)

        def body(_, xs):
            tk = xs[0]
            pt = xs[1] if len(xs) > 1 else None
            logits, caches = prefill(params, cfg, tk, pt, None, opts)
            return None, (logits, caches)

        xs = (toks,) if pat is None else (toks, pat)
        _, (logits, caches) = jax.lax.scan(body, None, xs)

        def merge(a):  # (chunks, nb, bs, ...) → (nb, chunks·bs, ...)
            a = jnp.moveaxis(a, 0, 1)
            return a.reshape(a.shape[0], n_chunks * a.shape[2], *a.shape[3:])

        caches = jax.tree_util.tree_map(merge, caches)
        logits = logits.reshape(b, *logits.shape[2:])
        return logits, caches

    return fn


def prefill_target(cfg: ArchConfig, shape: ShapeSpec, mesh, opts: RuntimeOpts,
                   param_dtype=jnp.bfloat16):
    from repro.models.transformer import abstract_params

    dax = data_axes(mesh)
    dsz = data_size(mesh)
    fsdp = cfg.total_params() * 2 / mesh.shape["model"] > 8e9
    params = shd.to_shaped(abstract_params(cfg, param_dtype),
                           shd.param_specs(cfg, mesh, fsdp=fsdp), mesh)
    # largest chunk count keeping per-chunk batch divisible by the data axes
    n_chunks = 1
    for c in (8, 4, 2):
        if shape.global_batch % c == 0 and (shape.global_batch // c) % dsz == 0:
            n_chunks = c
            break
    tokens = _token_struct(cfg, shape.global_batch, shape.seq_len, mesh, dax)
    patches = _patch_struct(cfg, shape.global_batch, mesh, dax)
    inner = make_prefill_chunked(cfg, opts, n_chunks)
    # constrain the returned caches to the decode cache layout (seq over
    # 'model'): GSPMD otherwise leaves them model-replicated (~13 GB/dev on
    # internlm2) — §Perf fleet note
    cspecs = shd.cache_specs(cfg, mesh, shape.global_batch, shape.seq_len,
                             opts.quantized_kv)

    def fn(params, tokens, patches=None):
        logits, caches = (inner(params, tokens, patches)
                          if patches is not None else inner(params, tokens))
        from jax.sharding import NamedSharding

        caches = jax.tree_util.tree_map(
            lambda c, sp: jax.lax.with_sharding_constraint(
                c, NamedSharding(mesh, sp)),
            caches, cspecs)
        return logits, caches

    args = (params, tokens) + ((patches,) if patches is not None else ())
    return fn, args


# ----------------------------------------------------------------- decode


def decode_target(cfg: ArchConfig, shape: ShapeSpec, mesh, opts: RuntimeOpts,
                  param_dtype=jnp.bfloat16):
    from repro.models.transformer import abstract_params

    dax = data_axes(mesh)
    fsdp = cfg.total_params() * 2 / mesh.shape["model"] > 8e9
    params = shd.to_shaped(abstract_params(cfg, param_dtype),
                           shd.param_specs(cfg, mesh, fsdp=fsdp), mesh)
    b = shape.global_batch
    b_axes = dax if b % data_size(mesh) == 0 else None
    tokens = _token_struct(cfg, b, 1, mesh, b_axes)
    caches = jax.eval_shape(
        partial(init_caches, cfg, b, shape.seq_len, opts))
    cspecs = shd.cache_specs(cfg, mesh, b, shape.seq_len, opts.quantized_kv)
    caches = shd.to_shaped(caches, cspecs, mesh)
    pos = jax.ShapeDtypeStruct((), jnp.int32,
                               sharding=NamedSharding(mesh, P()))
    inner = serve_step_fn(cfg, opts)

    def fn(params, tokens, caches, pos):
        toks, new_caches = inner(params, tokens, caches, pos)
        # pin output caches to the input layout → donation can alias them
        new_caches = jax.tree_util.tree_map(
            lambda c, sp: jax.lax.with_sharding_constraint(
                c, NamedSharding(mesh, sp)),
            new_caches, cspecs)
        return toks, new_caches

    return fn, (params, tokens, caches, pos)


def paged_decode_target(cfg: ArchConfig, shape: ShapeSpec, mesh,
                        opts: RuntimeOpts, param_dtype=jnp.bfloat16):
    """One ragged ``paged_decode_step`` over a worst-case-sized kv_pool:
    pool page axis sharded over the data axes (pages are independent; the
    block-table gather crosses shards only at page granularity), block
    tables and per-request positions replicated."""
    from repro.kernels.decode_attention import BLOCK_S
    from repro.models import layers as L
    from repro.models.transformer import abstract_params, paged_decode_step

    dax = data_axes(mesh)
    fsdp = cfg.total_params() * 2 / mesh.shape["model"] > 8e9
    params = shd.to_shaped(abstract_params(cfg, param_dtype),
                           shd.param_specs(cfg, mesh, fsdp=fsdp), mesh)
    b = shape.global_batch
    page = min(BLOCK_S, shape.seq_len)
    maxb = -(-shape.seq_len // page)
    # worst-case reservation + trash page, rounded so the sharded page axis
    # divides the data-axis size
    dsz = data_size(mesh)
    num_pages = -(-(b * maxb + 1) // dsz) * dsz
    nb = cfg.num_blocks
    m = cfg.pattern[0].mixer
    kh, hd = m.num_kv_heads, m.head_dim

    def leaf(shp, dtype, spec):
        return jax.ShapeDtypeStruct(shp, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    caches = tuple(
        L.PagedKVCache(
            k=leaf((nb, num_pages, kh, page, hd), jnp.int8,
                   (None, dax, None, None, None)),
            v=leaf((nb, num_pages, kh, page, hd), jnp.int8,
                   (None, dax, None, None, None)),
            k_scale=leaf((nb, num_pages, kh, 1, page), jnp.float32,
                         (None, dax, None, None, None)),
            v_scale=leaf((nb, num_pages, kh, 1, page), jnp.float32,
                         (None, dax, None, None, None)),
            pos=leaf((nb, num_pages, 1, page), jnp.int32,
                     (None, dax, None, None)),
            block_table=leaf((nb, b, maxb), jnp.int32, (None, None, None)),
        )
        for _ in cfg.pattern)
    b_axes = dax if b % data_size(mesh) == 0 else None
    tokens = _token_struct(cfg, b, 1, mesh, b_axes)
    pos = leaf((b,), jnp.int32, (None,))

    def fn(params, tokens, caches, pos):
        logits, new_caches = paged_decode_step(params, cfg, tokens, caches,
                                               pos, opts)
        return jnp.argmax(logits, axis=-1), new_caches

    return fn, (params, tokens, caches, pos)


def get_target(cfg: ArchConfig, shape_name: str, mesh, **opt_overrides):
    shape = SHAPES[shape_name]
    opts = default_opts(cfg, shape, **opt_overrides)
    if shape.kind == "train":
        return train_target(cfg, shape, mesh, opts)
    if shape.kind == "prefill":
        return prefill_target(cfg, shape, mesh, opts)
    if shape.kind == "paged_decode":
        return paged_decode_target(cfg, shape, mesh, opts)
    return decode_target(cfg, shape, mesh, opts)
