"""Split-computing over the pod axis — the paper's edge/cloud split mapped
onto a 2-pod TPU system (DESIGN.md §2).

``make_pipeline_decode_step`` builds a 2-stage pipelined decode step under
``jax.shard_map`` manual over the 'pod' axis only ('data'/'model' stay under
GSPMD): pod 0 ("edge") owns the front half of the stacked blocks, pod 1
("cloud") the back half. The decode batch is split into ``n_micro``
microbatches that flow through the two stages GPipe-style (n_micro + 1
iterations, one bubble). The stage-boundary activation is compressed before
the inter-pod ``ppermute``:

  payload_bits = 16 → bf16 (baseline)
  payload_bits = 8  → per-token int8 (fixed-bit TAB-Q: codes + f32 scale)
  payload_bits = 4  → per-token int4, two codes packed per byte

Adaptive per-token bit-widths (Algorithm 1 proper) would make message sizes
data-dependent — unsupported on ICI — so the TPU-native adaptation is
fixed-bit TAB-Q with per-token scales; the *choice* of bit-width moves to
the launcher (the paper's Eq. 8/12 decision layer). Inter-pod bytes drop
~2×/4×, measured directly in the dry-run's collective-permute traffic
(EXPERIMENTS.md §Perf).

Cache note (§Perf pair-3 iter 4): caches are **microbatch-major** —
(num_blocks, n_micro+1, bs, seq, ...) — so per-iteration slicing is a
dynamic-index on an UNSHARDED dim; slicing row ranges of a flat batch dim
instead forces GSPMD to rematerialize the (seq-sharded) cache every
iteration (~258 GB/dev of resharding permutes measured). The last micro
slot is trash for the bubble iterations (memory overhead 1/n_micro).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.transformer import (RuntimeOpts, _apply_blocks_cached,
                                      apply_head, embed_inputs, init_caches,
                                      make_positions, rope_tables)


def init_pipeline_caches(cfg: ArchConfig, bs: int, n_micro: int,
                         cache_len: int, opts: RuntimeOpts):
    """Microbatch-major caches: (num_blocks, n_micro+1, bs, ...) — slot
    n_micro is the bubble trash slot."""
    base = init_caches(cfg, bs, cache_len, opts)
    return jax.tree_util.tree_map(
        lambda a: jnp.repeat(a[:, None], n_micro + 1, axis=1), base)


def _quant_payload(h: jax.Array, bits: int):
    """h (bs, 1, D) → (codes, scale). Fixed-bit TAB-Q (per-token scale)."""
    if bits >= 16:
        return h.astype(jnp.bfloat16), jnp.zeros((*h.shape[:-1], 1), jnp.float32)
    hf = h.astype(jnp.float32)
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(hf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    codes = jnp.clip(jnp.round(hf / scale), -qmax, qmax)
    if bits == 8:
        return codes.astype(jnp.int8), scale
    # int4: pack two codes per uint8 byte
    c = codes.astype(jnp.int32) & 0xF
    lo, hi = c[..., 0::2], c[..., 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8), scale


def _dequant_payload(codes: jax.Array, scale: jax.Array, bits: int, d: int,
                     dtype) -> jax.Array:
    if bits >= 16:
        return codes.astype(dtype)
    if bits == 8:
        return (codes.astype(jnp.float32) * scale).astype(dtype)
    p = codes.astype(jnp.int32)
    lo, hi = p & 0xF, (p >> 4) & 0xF
    vals = jnp.stack([lo, hi], axis=-1).reshape(*codes.shape[:-1], d)
    vals = jnp.where(vals >= 8, vals - 16, vals)
    return (vals.astype(jnp.float32) * scale).astype(dtype)


def make_pipeline_decode_step(cfg: ArchConfig, opts: RuntimeOpts, n_micro: int,
                              payload_bits: int = 16, prefill: bool = False):
    """Returns fn(blocks, other_params, tokens, caches, pos) → (tokens_out,
    caches). Call under ``jax.shard_map(..., axis_names={'pod'})`` via
    :func:`pipeline_decode_sharded`. Caches must carry B + B/n_micro batch
    rows (trash slot); blocks/caches leading dim = num_blocks (sharded over
    'pod' by the wrapper). ``prefill=True`` processes full prompts (tokens
    (B, S)), where the stage boundary is B/n_micro × S × D per microbatch —
    the regime where payload compression moves real inter-pod bytes."""
    assert cfg.num_blocks % 2 == 0, "pipeline needs an even block count"

    def fn(blocks, other_params, tokens, caches, pos):
        stage = jax.lax.axis_index("pod")
        b = tokens.shape[0]
        seq = tokens.shape[1] if prefill else 1
        bs = b // n_micro
        d = cfg.d_model
        payload_d = d // 2 if payload_bits == 4 else d
        payload_dtype = (jnp.bfloat16 if payload_bits >= 16
                         else jnp.int8 if payload_bits == 8 else jnp.uint8)

        if prefill:
            positions = jnp.broadcast_to(
                jnp.arange(seq, dtype=jnp.int32)[None], (bs, seq))
        else:
            positions = jnp.broadcast_to(
                jnp.asarray(pos, jnp.int32)[None, None], (bs, 1))
        rope_cs = rope_tables(cfg, positions)
        n_vocab_out = cfg.vocab_size * cfg.num_codebooks

        def iter_body(carry, i):
            codes_in, scale_in, caches, out = carry
            valid0 = i < n_micro
            valid1 = i >= 1
            tok_off = jnp.where(valid0, i * bs, 0)
            tok = jax.lax.dynamic_slice_in_dim(tokens, tok_off, bs, 0)
            dec = not prefill
            # micro slot this stage touches (slot n_micro = bubble trash)
            slot = jnp.where(stage == 0,
                             jnp.where(valid0, i, n_micro),
                             jnp.where(valid1, i - 1, n_micro))

            x_edge = embed_inputs(cfg, other_params, tok, None, positions)
            x_cloud = _dequant_payload(codes_in, scale_in, payload_bits, d,
                                       x_edge.dtype)
            x = jnp.where(stage == 0, x_edge, x_cloud)

            cache_slice = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, slot, axis=1,
                                                       keepdims=False),
                caches)
            x, new_slice = _apply_blocks_cached(
                cfg, blocks, x, cache_slice, rope_cs=rope_cs,
                q_positions=positions, pos=jnp.asarray(pos, jnp.int32),
                opts=opts, decode=dec)
            caches = jax.tree_util.tree_map(
                lambda full, sl: jax.lax.dynamic_update_slice_in_dim(
                    full, sl[:, None].astype(full.dtype), slot, axis=1),
                caches, new_slice)

            # compress + ship the boundary activation across the pod link
            codes, scale = _quant_payload(x, payload_bits)
            codes = jax.lax.ppermute(codes, "pod", [(0, 1), (1, 0)])
            scale = jax.lax.ppermute(scale, "pod", [(0, 1), (1, 0)])

            # cloud head for microbatch i-1 (stage 0's write lands in trash)
            logits = apply_head(cfg, other_params, x[:, -1:])[:, 0]
            logits = logits.reshape(bs, n_vocab_out)
            out_slot = jnp.where(stage == 1,
                                 jnp.where(valid1, i - 1, n_micro), n_micro)
            out = jax.lax.dynamic_update_slice(
                out, logits[None].astype(out.dtype), (out_slot, 0, 0))
            return (codes, scale, caches, out), None

        codes0 = jnp.zeros((bs, seq, payload_d), payload_dtype)
        scale0 = jnp.zeros((bs, seq, 1), jnp.float32)
        out0 = jnp.zeros((n_micro + 1, bs, n_vocab_out), jnp.float32)
        (_, _, caches, out), _ = jax.lax.scan(
            iter_body, (codes0, scale0, caches, out0),
            jnp.arange(n_micro + 1))
        logits = out[:n_micro].reshape(b, n_vocab_out)
        # only the cloud stage holds real logits → replicate via masked psum
        logits = jax.lax.psum(jnp.where(stage == 1, logits, 0.0), "pod")
        if cfg.num_codebooks > 1:
            logits = logits.reshape(b, cfg.num_codebooks, cfg.vocab_size)
        return jnp.argmax(logits, axis=-1)[:, None], caches

    return fn


def pipeline_decode_sharded(cfg: ArchConfig, opts: RuntimeOpts, mesh,
                            n_micro: int, payload_bits: int = 16,
                            prefill: bool = False):
    """shard_map wrapper: blocks/caches sharded over 'pod' (stage dim 0);
    everything else replicated across pods ('data'/'model' stay auto)."""
    fn = make_pipeline_decode_step(cfg, opts, n_micro, payload_bits, prefill)

    def blocks_spec(tree):
        return jax.tree_util.tree_map(lambda _: P("pod"), tree)

    def wrapped(blocks, other_params, tokens, caches, pos):
        return jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(blocks_spec(blocks), jax.tree_util.tree_map(
                lambda _: P(), other_params), P(), blocks_spec(caches), P()),
            out_specs=(P(), blocks_spec(caches)),
            # manual over 'pod' only; any other mesh axes stay under GSPMD
            axis_names={"pod"},
            check_vma=False,
        )(blocks, other_params, tokens, caches, pos)

    return wrapped
