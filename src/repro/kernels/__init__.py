"""Pallas TPU kernels for the paper's quantization hot spots — WIRED into
the serving path, not a side gallery:

  decode_attention — int8-KV decode attention. The §Roofline irreducible
      term: streams the kv-head-major quantized cache (codes (B, K, S, hd)
      int8 + per-(token, head) scales) through VMEM once, dequantizing
      in-register with online softmax. ``models.layers`` routes every
      decode-time attention over a quantized cache here (see
      ``quantized_decode_attention``); ``RuntimeOpts.quantized_kv=True``
      makes both serving engines take this path inside their fused loops.
  paged_decode_attention — the same online-softmax block walk re-addressed
      through per-request BLOCK TABLES (``pltpu.PrefetchScalarGridSpec``):
      each request's walk gathers its pages, all kv heads and 128 tokens a
      step, from the shared ``serving.kv_pool`` pool, up to the page of its
      query position and no further, with per-request causal bounds for
      ragged continuous batching. ``models.layers.paged_decode_attention_layer``
      routes every decode over a ``PagedKVCache`` here.
  paged_prefill_attention — the PREFILL page walk (the TTFT path): a
      flash-style (request, kv-head, q-block) grid folds the request's
      block-table pages (int8 history dequantized in-register, masked per
      query row below its first in-call position) and the call's fresh
      full-precision keys into one online softmax — the dense f32 gather
      of the pool never materializes. ``models.layers.
      paged_prefill_attention`` routes shared-prefix and chunked-prefill
      attention here (dense-gather fallback for softcapped layers or
      ``RuntimeOpts.paged_prefill_kernel=False``).
  tabq_kernel — per-token TAB-Q magnitude quantization (Eq. 5-6), int8
      code carrier (codes rebased per token to [0, Q_max]).
  dequant_matmul — int8-weight × fp-activation matmul with per-channel
      dequant fused into the epilogue (OPSC front segments).
  ts_mask — threshold splitting (Eq. 4) for the stage-boundary payload.

``ops.py`` exposes jit'd wrappers that default to ``interpret=True`` off-TPU
(CPU correctness / parity testing); ``ref.py`` holds the pure-jnp oracles
the tests allclose against; ``tests/test_chip_compile.py`` compiles every
kernel for a described TPU v5e at Llama-2-7B widths, and ``chip_smoke.py``
checks the attention kernels against their oracles on the chip.
"""
