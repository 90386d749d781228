"""Model assembly: pattern-stacked decoder with ``lax.scan`` over blocks.

Parameters for each pattern position are stacked across ``num_blocks`` (the
leading axis), so compile time and HLO size stay flat in depth — essential
for the 88/94-layer assigned configs. Three entry points:

  forward_train(params, cfg, tokens, ...)          → (logits, aux_loss)
  prefill(params, cfg, tokens, ...)                → (last_logits, caches)
  decode_step(params, cfg, token, caches, pos, ...)→ (logits, new_caches)

Caches are a tuple over pattern positions: ``KVCache`` for attention layers,
``(conv_state, ssm_state)`` for Mamba layers — each leaf carrying a leading
``num_blocks`` axis consumed by the scan.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, AttnSpec, LayerSpec, MLPSpec, MoESpec, SSMSpec
from repro.models import layers as L
from repro.models.moe import init_moe_params, moe_layer
from repro.models.ssm import init_ssm_params, ssm_layer


@dataclasses.dataclass(frozen=True)
class RuntimeOpts:
    """Per-call knobs (all static under jit)."""

    q_chunk: int = 1024
    kv_chunk: int = 1024
    remat: bool = True
    quantized_kv: bool = False
    cache_dtype: str = "bfloat16"
    moe_capacity_factor: float = 1.25  # ≤ 0 → dropless routing
    # uniform per-layer activation fake-quant (baseline quantizers in
    # benchmarks apply Q_a at EVERY layer; the paper's method only at the
    # split — None disables)
    act_bits: int | None = None
    # pin the residual-stream layout between blocks, e.g. (('pod','data'),
    # None, None) — stops GSPMD sharding oscillation across the block scan
    # under remat (§Perf hillclimb 2); None disables
    act_sharding: tuple | None = None
    # grouped MoE dispatch: set to the data-shard count so the dispatch
    # scatter partitions shard-locally (§Perf hillclimb 2); 1 = global
    moe_groups: int = 1
    # SSD recurrent-state STORAGE dtype (compute stays f32): bf16 halves the
    # hybrid/SSM decode cache footprint (jamba fit fix, EXPERIMENTS §Dry-run)
    ssm_state_dtype: str = "float32"
    # route shared-prefix / chunked prefill attention through the Pallas
    # page-walk kernel (kernels.paged_prefill_attention); False falls back
    # to gathering the pool dense per layer — the pre-kernel baseline the
    # chunked_prefill benchmark measures against
    paged_prefill_kernel: bool = True
    # split the paged kernels' kv-head axis over a named mesh axis: each
    # shard walks the pages with its own head group and an exact tiled
    # all_gather reassembles the outputs (no psum — reduction order, and
    # therefore greedy argmaxes, stay bit-identical to single-device).
    # Only meaningful inside shard_map; set by sharded_step_fns, never by
    # callers directly. head_shards must divide num_kv_heads.
    head_axis: str | None = None
    head_shards: int = 1


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_layer(key, cfg: ArchConfig, ls: LayerSpec, dtype):
    km, kf = jax.random.split(key)
    p = {"ln1": jnp.ones((cfg.d_model,), dtype)}
    if isinstance(ls.mixer, AttnSpec):
        p["mixer"] = L.init_attention_params(
            km, cfg.d_model, ls.mixer.num_heads, ls.mixer.num_kv_heads,
            ls.mixer.head_dim, dtype, ls.mixer.qk_norm)
    else:
        p["mixer"] = init_ssm_params(km, cfg.d_model, ls.mixer, dtype)
    if ls.ffn is not None:
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
        if isinstance(ls.ffn, MoESpec):
            p["ffn"] = init_moe_params(kf, cfg.d_model, ls.ffn, dtype)
        else:
            p["ffn"] = init_mlp(kf, cfg, ls.ffn, dtype)
    return p


def init_mlp(key, cfg, spec: MLPSpec, dtype):
    return L.init_mlp_params(key, cfg.d_model, spec.d_ff, spec.gated, dtype)


def init_params(cfg: ArchConfig, key, dtype=jnp.float32):
    keys = jax.random.split(key, 4 + len(cfg.pattern))
    d, v = cfg.d_model, cfg.vocab_size
    params: dict = {}
    if cfg.embed == "musicgen":
        params["embed"] = (jax.random.normal(keys[0], (cfg.num_codebooks, v, d))
                           * 0.02).astype(dtype)
    else:
        params["embed"] = (jax.random.normal(keys[0], (v, d)) * 0.02).astype(dtype)
    if cfg.embed == "vlm":
        params["w_proj"] = (jax.random.normal(keys[1], (cfg.d_vision, d))
                            * (1.0 / math.sqrt(cfg.d_vision))).astype(dtype)
    params["final_norm"] = jnp.ones((d,), dtype)
    if not (cfg.tie_embeddings and cfg.embed == "token"):
        params["lm_head"] = (jax.random.normal(keys[2], (d, v * cfg.num_codebooks))
                             * 0.02).astype(dtype)

    # stacked per pattern position
    blocks = {}
    for i, ls in enumerate(cfg.pattern):
        bkeys = jax.random.split(keys[4 + i], cfg.num_blocks)
        blocks[f"p{i}"] = jax.vmap(lambda k: _init_layer(k, cfg, ls, dtype))(bkeys)
    params["blocks"] = blocks
    return params


def init_params_jit(cfg: ArchConfig, key, dtype=jnp.bfloat16,
                    out_shardings=None):
    """:func:`init_params` as one compiled program. Eager init draws each
    stacked tensor in float32 before casting it; compiled, the draw and the
    cast fuse, so no full-size float32 tensor exists on the device (a 7B
    model's float32 MLP draws alone would not fit a 16 GB chip).
    ``out_shardings`` places the weights, e.g. replicated over a mesh, so
    they are built once where they will live."""
    return jax.jit(partial(init_params, cfg, dtype=dtype),
                   out_shardings=out_shardings)(key)


def abstract_params(cfg: ArchConfig, dtype=jnp.bfloat16):
    """ShapeDtypeStruct pytree of the parameters — no allocation (dry-run)."""
    return jax.eval_shape(lambda k: init_params(cfg, k, dtype),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


# ---------------------------------------------------------------------------
# Positions & rope
# ---------------------------------------------------------------------------


def make_positions(cfg: ArchConfig, b: int, s: int, offset=0):
    """Sequence-order positions (B, S) for causal masking and caches."""
    return jnp.broadcast_to(offset + jnp.arange(s, dtype=jnp.int32)[None], (b, s))


def make_mrope_positions(cfg: ArchConfig, positions: jax.Array):
    """Qwen2-VL M-RoPE ids (3, B, S) from sequence positions.

    Patches (first ``num_patches`` slots, a √P×√P grid): t = 0, (h, w) grid.
    Text: all three axes = seq_pos - P + √P (continuing past the grid).
    The mapping depends only on the *absolute* position, so prefill and
    decode agree by construction."""
    p = cfg.num_patches
    grid = max(int(math.isqrt(max(p, 1))), 1)
    is_patch = positions < p
    text = positions - p + grid
    pos_t = jnp.where(is_patch, 0, text)
    pos_h = jnp.where(is_patch, (positions // grid) % grid, text)
    pos_w = jnp.where(is_patch, positions % grid, text)
    return jnp.stack([pos_t, pos_h, pos_w])


def rope_tables(cfg: ArchConfig, positions: jax.Array):
    """(cos, sin) for the pattern's attention head_dim, or None."""
    attn_specs = [ls.mixer for ls in cfg.pattern if isinstance(ls.mixer, AttnSpec)]
    if not attn_specs or cfg.rope in ("none", "sinusoidal"):
        return None
    hd = attn_specs[0].head_dim
    if cfg.rope == "mrope":
        thw = make_mrope_positions(cfg, positions)
        return L.mrope_tables(thw, hd, cfg.mrope_sections, cfg.rope_theta)
    return L.rope_table(positions, hd, cfg.rope_theta)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_inputs(cfg: ArchConfig, params, tokens, patches=None, positions=None):
    if cfg.embed == "musicgen":
        # tokens (B, S, K): sum the per-codebook embeddings
        x = sum(jnp.take(params["embed"][k], tokens[..., k], axis=0)
                for k in range(cfg.num_codebooks))
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed == "vlm" and patches is not None:
        proj = (patches.astype(x.dtype) @ params["w_proj"])  # (B, P, D)
        x = jnp.concatenate([proj, x[:, cfg.num_patches:]], axis=1)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    if cfg.rope == "sinusoidal" and positions is not None:
        x = x + L.sinusoidal_embedding(positions, cfg.d_model).astype(x.dtype)
    return x


def apply_head(cfg: ArchConfig, params, x):
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if "lm_head" in params:
        w = params["lm_head"]
    else:
        w = params["embed"].T  # tied
    logits = (x @ w).astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    if cfg.num_codebooks > 1:
        logits = logits.reshape(*logits.shape[:-1], cfg.num_codebooks, cfg.vocab_size)
    return logits


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------


def _apply_layer(cfg, ls: LayerSpec, p, x, *, rope_cs, q_positions, cache, pos,
                 opts: RuntimeOpts, decode: bool, attend_cache: bool = False,
                 token_slots=None, quant_fresh=None):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if isinstance(ls.mixer, AttnSpec):
        with jax.named_scope("attention"):
            out, new_cache = L.attention_layer(
                p["mixer"], h, ls.mixer, rope_cs=rope_cs, cache=cache,
                pos=pos, q_positions=q_positions, q_chunk=opts.q_chunk,
                kv_chunk=opts.kv_chunk, decode=decode,
                attend_cache=attend_cache,
                prefill_kernel=opts.paged_prefill_kernel,
                token_slots=token_slots, quant_fresh=quant_fresh,
                head_axis=opts.head_axis, head_shards=opts.head_shards)
    else:
        conv_state, ssm_state = cache if cache is not None else (None, None)
        out, new_cache = ssm_layer(p["mixer"], h, ls.mixer,
                                   conv_state=conv_state, ssm_state=ssm_state,
                                   decode=decode)
        if cache is not None:  # preserve the configured storage dtypes
            new_cache = jax.tree_util.tree_map(
                lambda new, old: new.astype(old.dtype), new_cache, cache)
    x = x + out
    if ls.ffn is not None:
        with jax.named_scope("mlp"):
            h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
            if isinstance(ls.ffn, MoESpec):
                out, aux = moe_layer(p["ffn"], h, ls.ffn,
                                     opts.moe_capacity_factor,
                                     opts.moe_groups)
            else:
                out = L.mlp_layer(p["ffn"], h, ls.ffn.activation)
            x = x + out
    if opts.act_bits is not None:  # uniform activation quantization baseline
        from repro.core.quant import aiq, aiq_dequant

        b_, s_, d_ = x.shape
        codes, sc, z = aiq(x.reshape(b_ * s_, d_).astype(jnp.float32),
                           opts.act_bits, axis=-1)
        x = aiq_dequant(codes, sc, z).reshape(b_, s_, d_).astype(x.dtype)
    return x, new_cache, aux


def _apply_blocks_train(cfg, blocks, x, *, rope_cs, q_positions, opts: RuntimeOpts):
    def constrain(x):
        if opts.act_sharding is None:
            return x
        from jax.sharding import PartitionSpec

        return jax.lax.with_sharding_constraint(
            x, PartitionSpec(*opts.act_sharding))

    def body(carry, p_slice):
        x, aux = carry
        for i, ls in enumerate(cfg.pattern):
            x, _, a = _apply_layer(cfg, ls, p_slice[f"p{i}"], x, rope_cs=rope_cs,
                                   q_positions=q_positions, cache=None, pos=None,
                                   opts=opts, decode=False)
            x = constrain(x)
            aux = aux + a
        return (x, aux), None

    if opts.remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), blocks)
    return x, aux


def _apply_blocks_cached(cfg, blocks, x, caches, *, rope_cs, q_positions, pos,
                         opts: RuntimeOpts, decode: bool,
                         attend_cache: bool = False, token_slots=None,
                         quant_fresh=None):
    """Caches ride in the scan CARRY (sliced per block by index, written back
    with dynamic_update_slice) rather than as xs→ys: carries can be buffer-
    aliased/donated, so a serve step updates the multi-GB cache in place —
    xs/ys would keep two full copies live (observed +16 GB temp on jamba)."""

    def body(carry, xs):
        x, caches = carry
        p_slice, i = xs
        new_caches = []
        for pi, ls in enumerate(cfg.pattern):
            cache_i = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                caches[pi])
            x, nc, _ = _apply_layer(cfg, ls, p_slice[f"p{pi}"], x,
                                    rope_cs=rope_cs, q_positions=q_positions,
                                    cache=cache_i, pos=pos, opts=opts,
                                    decode=decode, attend_cache=attend_cache,
                                    token_slots=token_slots,
                                    quant_fresh=quant_fresh)
            with jax.named_scope("kv_write"):
                new_caches.append(jax.tree_util.tree_map(
                    lambda full, sl: jax.lax.dynamic_update_slice_in_dim(
                        full, sl[None].astype(full.dtype), i, axis=0),
                    caches[pi], nc))
        return (x, tuple(new_caches)), None

    nb = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    (x, new_caches), _ = jax.lax.scan(
        body, (x, caches), (blocks, jnp.arange(nb)))
    return x, new_caches


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def init_caches(cfg: ArchConfig, batch: int, cache_len: int, opts: RuntimeOpts):
    """Tuple over pattern positions, each leaf stacked (num_blocks, ...)."""
    nb = cfg.num_blocks
    dtype = jnp.dtype(opts.cache_dtype)
    caches = []
    for ls in cfg.pattern:
        m = ls.mixer
        if isinstance(m, AttnSpec):
            size = min(cache_len, m.sliding_window) if m.sliding_window else cache_len
            if opts.quantized_kv:
                # kv-head-major kernel layout: int8 codes + per-(token, head)
                # scales, streamed as-is by kernels.decode_attention; the
                # slot axis is block-aligned so the kernel never re-pads the
                # cache per step (pad slots keep pos = -1 → masked; ring
                # writes stay modulo the logical window, see cache_update)
                from repro.kernels.decode_attention import padded_cache_len

                psize = padded_cache_len(size)
                qshape = (nb, batch, m.num_kv_heads, psize, m.head_dim)
                c = L.KVCache(jnp.zeros(qshape, jnp.int8),
                              jnp.zeros(qshape, jnp.int8),
                              jnp.zeros(qshape[:-1], jnp.float32),
                              jnp.zeros(qshape[:-1], jnp.float32),
                              jnp.full((nb, batch, psize), -1, jnp.int32))
            else:
                shape = (nb, batch, size, m.num_kv_heads, m.head_dim)
                c = L.KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                              None, None, jnp.full((nb, batch, size), -1, jnp.int32))
        else:
            conv_ch = m.d_inner + 2 * m.d_state
            c = (jnp.zeros((nb, batch, m.conv_width - 1, conv_ch), dtype),
                 jnp.zeros((nb, batch, m.n_heads, m.d_inner // m.n_heads, m.d_state),
                           jnp.dtype(opts.ssm_state_dtype)))
        caches.append(c)
    return tuple(caches)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def forward_train(params, cfg: ArchConfig, tokens, patches=None,
                  opts: RuntimeOpts = RuntimeOpts()):
    b, s = tokens.shape[:2]
    positions = make_positions(cfg, b, s)
    x = embed_inputs(cfg, params, tokens, patches, positions)
    rope_cs = rope_tables(cfg, positions)
    x, aux = _apply_blocks_train(cfg, params["blocks"], x, rope_cs=rope_cs,
                                 q_positions=positions, opts=opts)
    return apply_head(cfg, params, x), aux


def prefill(params, cfg: ArchConfig, tokens, patches=None, cache_len=None,
            opts: RuntimeOpts = RuntimeOpts()):
    """Process the prompt, returning last-position logits + filled caches."""
    b, s = tokens.shape[:2]
    cache_len = cache_len or s
    positions = make_positions(cfg, b, s)
    x = embed_inputs(cfg, params, tokens, patches, positions)
    rope_cs = rope_tables(cfg, positions)
    caches = init_caches(cfg, b, cache_len, opts)
    x, caches = _apply_blocks_cached(cfg, params["blocks"], x, caches,
                                     rope_cs=rope_cs, q_positions=positions,
                                     pos=jnp.int32(0), opts=opts, decode=False)
    logits = apply_head(cfg, params, x[:, -1:])
    return logits[:, 0], caches


def decode_step(params, cfg: ArchConfig, tokens, caches, pos,
                opts: RuntimeOpts = RuntimeOpts()):
    """One autoregressive step. ``tokens`` (B, 1) (or (B, 1, K) musicgen);
    ``pos`` scalar int32 — the absolute position being generated."""
    b = tokens.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32)[None, None], (b, 1))
    x = embed_inputs(cfg, params, tokens, None, positions)
    rope_cs = rope_tables(cfg, positions)
    x, caches = _apply_blocks_cached(cfg, params["blocks"], x, caches,
                                     rope_cs=rope_cs, q_positions=positions,
                                     pos=jnp.asarray(pos, jnp.int32), opts=opts,
                                     decode=True)
    logits = apply_head(cfg, params, x)
    return logits[:, 0], caches


# --------------------------------------------------------- paged (ragged)


def paged_prefill(params, cfg: ArchConfig, tokens, caches, positions,
                  opts: RuntimeOpts = RuntimeOpts()):
    """Ragged prefill over the paged KV pool.

    ``tokens`` (R, S) RIGHT-ALIGNED: each row's prompt occupies the trailing
    slots, left pads carry ``positions = -1``. ``positions`` (R, S) are the
    per-row absolute positions (0..len-1 in the tail). Right alignment means
    the LAST column is every row's final prompt token, so one slice yields
    the next-token logits for the whole ragged batch; pad queries/keys are
    masked by the negative positions, and pad cache writes land on the
    pool's trash page. ``caches`` is the pool pytree from
    ``serving.kv_pool.PagedKVPool.device_caches`` (block tables installed
    for exactly these R rows). Returns (last_logits (R, V), caches)."""
    positions = jnp.asarray(positions, jnp.int32)
    with jax.named_scope("embed"):
        x = embed_inputs(cfg, params, tokens, None, jnp.maximum(positions, 0))
        rope_cs = rope_tables(cfg, positions)
    x, caches = _apply_blocks_cached(cfg, params["blocks"], x, caches,
                                     rope_cs=rope_cs, q_positions=positions,
                                     pos=jnp.int32(0), opts=opts, decode=False)
    with jax.named_scope("head"):
        logits = apply_head(cfg, params, x[:, -1:])
    return logits[:, 0], caches


def paged_prefill_shared(params, cfg: ArchConfig, tokens, caches, positions,
                         opts: RuntimeOpts = RuntimeOpts()):
    """Ragged prefill THROUGH the paged pool — the shared-prefix entry point.

    Same calling convention as :func:`paged_prefill` (right-aligned
    ``tokens``/``positions`` (R, S), ``-1`` pads, last column = each row's
    final prompt token), but rows may start at a position > 0: a row forked
    from a shared prefix passes only its SUFFIX tokens with absolute
    positions ``[prefix_len, prompt_len)``, and its attention additionally
    reads the prefix tokens already stored in its block-table pages
    (``models.layers.paged_prefill_attention`` — history masked to stored
    positions below the row's first in-call position, so the suffix
    attends exactly prefix + itself). Rows starting at position 0 behave
    like the plain ragged prefill. Returns (last_logits (R, V), caches)."""
    positions = jnp.asarray(positions, jnp.int32)
    with jax.named_scope("embed"):
        x = embed_inputs(cfg, params, tokens, None, jnp.maximum(positions, 0))
        rope_cs = rope_tables(cfg, positions)
    x, caches = _apply_blocks_cached(cfg, params["blocks"], x, caches,
                                     rope_cs=rope_cs, q_positions=positions,
                                     pos=jnp.int32(0), opts=opts, decode=False,
                                     attend_cache=True)
    with jax.named_scope("head"):
        logits = apply_head(cfg, params, x[:, -1:])
    return logits[:, 0], caches


def paged_decode_step(params, cfg: ArchConfig, tokens, caches, pos,
                      opts: RuntimeOpts = RuntimeOpts()):
    """One RAGGED autoregressive step over the paged pool: ``pos`` is (R,)
    int32 — each request decodes at its own absolute position (-1 marks an
    inactive slot, whose write is routed to the trash page and whose
    attention masks every key). This is the step the continuous-batching
    scheduler jits once for the full slot count and reuses as requests come
    and go."""
    positions = jnp.asarray(pos, jnp.int32)[:, None]  # (R, 1)
    with jax.named_scope("embed"):
        x = embed_inputs(cfg, params, tokens, None, jnp.maximum(positions, 0))
        rope_cs = rope_tables(cfg, positions)
    x, caches = _apply_blocks_cached(cfg, params["blocks"], x, caches,
                                     rope_cs=rope_cs, q_positions=positions,
                                     pos=jnp.int32(0), opts=opts, decode=True)
    with jax.named_scope("head"):
        logits = apply_head(cfg, params, x)
    return logits[:, 0], caches


def packed_step(params, cfg: ArchConfig, tokens, caches, positions, slots,
                logit_rows, opts: RuntimeOpts = RuntimeOpts(),
                quant_fresh=None):
    """ONE token-packed step over the paged pool: the whole tick — every
    decoding slot's next token AND up-to-budget prefill-chunk tokens — as a
    single flat batch.

    ``tokens``/``positions``/``slots`` are (1, T): a fixed ``token_budget``
    buffer laid out slot-major (each active slot owns one contiguous run —
    a length-1 run for a decode token, a longer one for a prefill chunk),
    tail-padded with ``positions = slots = -1`` rows whose cache writes
    land on the trash page and whose attention emits exact zeros.
    ``logit_rows`` (R,) names the buffer row holding each slot's LAST token
    (any row for absent slots — their logits are garbage the scheduler
    never samples), so logits keep the ``(R, V)`` shape the per-slot
    sampling operand lanes expect.

    ``quant_fresh`` (1, T) bool marks rows whose FRESH self-keys must be
    attended through the int8 quantize→dequantize round trip instead of at
    full precision — the scheduler sets it on its decode rows, whose one
    fresh key IS their own token: a sequential decode step would read that
    key back from the pool's codes, so attending it at f32 here is the one
    value-level divergence packed mode had from ``paged_decode_step`` (and
    from ``Engine.generate``). With the mask on, packed greedy streams are
    bit-identical to the per-request oracle; prefill rows keep full-
    precision fresh keys exactly like the chunked prefill path. Returns
    (logits (R, V), caches)."""
    positions = jnp.asarray(positions, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)
    with jax.named_scope("embed"):
        x = embed_inputs(cfg, params, tokens, None, jnp.maximum(positions, 0))
        rope_cs = rope_tables(cfg, positions)
    x, caches = _apply_blocks_cached(cfg, params["blocks"], x, caches,
                                     rope_cs=rope_cs, q_positions=positions,
                                     pos=jnp.int32(0), opts=opts, decode=False,
                                     token_slots=slots,
                                     quant_fresh=quant_fresh)
    with jax.named_scope("head"):
        xl = jnp.take(x[0], jnp.asarray(logit_rows, jnp.int32), axis=0)
        logits = apply_head(cfg, params, xl[None])  # xl: (R, D)
    return logits[0], caches


def paged_verify_step(params, cfg: ArchConfig, tokens, caches, positions,
                      opts: RuntimeOpts = RuntimeOpts()):
    """Multi-token verify THROUGH the paged pool — the (R, S) generalization
    of :func:`paged_decode_step`: each row carries its last committed token
    plus its draft burst and gets logits at EVERY in-call position back.

    ``tokens``/``positions`` (R, S) RIGHT-ALIGNED (-1 pads route to the
    trash page), with ``S = 1 + speculate_k``. The in-call tokens are
    WRITTEN to the pool first and attention then reads every key —
    history and the burst itself — back through the pool's quantized
    codes, exactly like ``S`` single-token decode steps would
    (quantization is per-token, so batching the writes leaves the codes
    bit-identical; prefill-style fresh-f32 in-call keys would diverge
    from the sequential path at quantization scale and flip argmaxes).
    Returns (logits (R, S, V), caches): column j of row r is the target
    distribution after consuming the row's in-call tokens <= j (left-pad
    columns are garbage)."""
    positions = jnp.asarray(positions, jnp.int32)
    with jax.named_scope("embed"):
        x = embed_inputs(cfg, params, tokens, None, jnp.maximum(positions, 0))
        rope_cs = rope_tables(cfg, positions)
    x, caches = _apply_blocks_cached(cfg, params["blocks"], x, caches,
                                     rope_cs=rope_cs, q_positions=positions,
                                     pos=jnp.int32(0), opts=opts, decode=True)
    with jax.named_scope("head"):
        logits = apply_head(cfg, params, x)
    return logits, caches


# --------------------------------------------------------------- sharded


def sharded_step_fns(cfg: ArchConfig, opts: RuntimeOpts, mesh) -> dict:
    """``shard_map``-lowered drop-in versions of the five paged step
    functions over a ``("kv", "model")`` mesh (``repro.launch.mesh.
    make_serving_mesh``). Returns ``{"prefill", "prefill_shared", "decode",
    "packed", "verify"}`` — same signatures as the base entry points with
    ``cfg``/``opts`` closed over, so the scheduler's jitted tick lambdas
    swap them in unchanged (one jitted tick per mode is preserved).

    Execution model, chosen for exactness (the repo's bit-identity bar):

      * pool PAGE leaves arrive sharded ``P(None, "kv")`` (each device
        STORES 1/kv of the pool — the memory-constrained axis); the body
        starts with a tiled ``all_gather`` over "kv" so every device walks
        the full page set with the block tables, then slices its own page
        shard back out of the updated pool. Gather/slice are exact — page
        values are moved, never reduced.
      * attention kv-heads are split over "model" via
        ``RuntimeOpts.head_axis``/``head_shards`` (the layers slice their
        head group, walk the pages with it, and reassemble with an exact
        tiled ``all_gather`` — no psum, so no reduction-order drift).
      * everything dense (embeddings, MLPs, lm head) runs replicated;
        logits come out ``P()`` and per-slot sampling stays OUTSIDE the
        shard_map, inside the scheduler's same jit.

    Greedy token streams are therefore bit-identical to the single-device
    step functions (asserted by ``tests/test_sharded_serving.py`` on
    forced CPU device counts with the Pallas kernels in interpret mode)."""
    from jax.sharding import PartitionSpec

    page = PartitionSpec(None, "kv")
    repl = PartitionSpec()
    cache_spec = tuple(L.PagedKVCache(page, page, page, page, page, repl)
                       for _ in cfg.pattern)
    ksize, msize = mesh.shape["kv"], mesh.shape["model"]
    kh = cfg.pattern[0].mixer.num_kv_heads
    inner = opts
    if msize > 1:
        if kh % msize != 0:
            raise ValueError(
                f"mesh 'model' axis {msize} must divide num_kv_heads {kh} "
                f"(make_serving_mesh only builds such meshes)")
        inner = dataclasses.replace(opts, head_axis="model",
                                    head_shards=msize)

    def _gather(caches):
        g = lambda a: jax.lax.all_gather(a, "kv", axis=1, tiled=True)
        return tuple(L.PagedKVCache(g(c.k), g(c.v), g(c.k_scale),
                                    g(c.v_scale), g(c.pos), c.block_table)
                     for c in caches)

    def _scatter(caches):
        i = jax.lax.axis_index("kv")

        def s(a):
            local = a.shape[1] // ksize
            return jax.lax.dynamic_slice_in_dim(a, i * local, local, axis=1)

        return tuple(L.PagedKVCache(s(c.k), s(c.v), s(c.k_scale),
                                    s(c.v_scale), s(c.pos), c.block_table)
                     for c in caches)

    def _wrap(step, n_repl: int):
        """shard_map a step whose args are (params, *n_repl replicated
        operands, caches-last-moved-to-front)…"""

        def body(params, caches, *args):
            logits, out = step(params, _gather(caches), *args)
            return logits, _scatter(out)

        sm = jax.shard_map(body, mesh=mesh,
                           in_specs=(repl, cache_spec) + (repl,) * n_repl,
                           out_specs=(repl, cache_spec), check_vma=False)

        def fn(params, caches, *args):
            return sm(params, caches, *args)

        return fn

    prefill = _wrap(
        lambda p, c, tokens, positions: paged_prefill(
            p, cfg, tokens, c, positions, inner), 2)
    prefill_shared = _wrap(
        lambda p, c, tokens, positions: paged_prefill_shared(
            p, cfg, tokens, c, positions, inner), 2)
    decode = _wrap(
        lambda p, c, tokens, pos: paged_decode_step(
            p, cfg, tokens, c, pos, inner), 2)
    packed = _wrap(
        lambda p, c, tokens, positions, slots, logit_rows, quant_fresh:
        packed_step(p, cfg, tokens, c, positions, slots, logit_rows, inner,
                    quant_fresh), 5)
    verify = _wrap(
        lambda p, c, tokens, positions: paged_verify_step(
            p, cfg, tokens, c, positions, inner), 2)

    return {
        "prefill": lambda params, tokens, caches, positions:
            prefill(params, caches, tokens, positions),
        "prefill_shared": lambda params, tokens, caches, positions:
            prefill_shared(params, caches, tokens, positions),
        "decode": lambda params, tokens, caches, pos:
            decode(params, caches, tokens, pos),
        "packed": lambda params, tokens, caches, positions, slots,
            logit_rows, quant_fresh:
            packed(params, caches, tokens, positions, slots,
                   jnp.asarray(logit_rows, jnp.int32),
                   (jnp.zeros(jnp.asarray(tokens).shape, bool)
                    if quant_fresh is None else quant_fresh)),
        "verify": lambda params, tokens, caches, positions:
            verify(params, caches, tokens, positions),
    }
