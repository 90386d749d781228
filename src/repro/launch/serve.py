"""Serving launcher: batched requests through the engine, optionally in
split-computing mode (the paper's deployment).

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-2b --tiny \
      --batch 4 --new 16 [--split --split-layer 1 --qw-front 8]
"""

import os

if os.environ.get("REPRO_FORCE_DEVICES"):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count="
                               f"{os.environ['REPRO_FORCE_DEVICES']}").strip()

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.channel import ChannelConfig
from repro.core.opsc import OPSCConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import RuntimeOpts, init_params
from repro.serving.engine import Engine
from repro.serving.split_engine import SplitEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new", type=int, default=16)
    ap.add_argument("--quantized-kv", action="store_true")
    ap.add_argument("--split", action="store_true")
    ap.add_argument("--split-layer", type=int, default=1)
    ap.add_argument("--qw-front", type=int, default=8)
    ap.add_argument("--deadline-ms", type=float, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.tiny:
        cfg = cfg.tiny()
    opts = RuntimeOpts(q_chunk=64, kv_chunk=64, remat=False,
                       quantized_kv=args.quantized_kv,
                       moe_capacity_factor=0.0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    if cfg.embed == "musicgen":
        prompts = rng.integers(0, cfg.vocab_size,
                               (args.batch, args.prompt_len, cfg.num_codebooks))
    else:
        prompts = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len))
    prompts = prompts.astype(np.int32)
    cache_len = args.prompt_len + args.new

    if args.split:
        # snap the split to a pattern boundary (OPSC splits between blocks)
        plen = len(cfg.pattern)
        ell = max(plen, args.split_layer - args.split_layer % plen)
        if ell != args.split_layer:
            print(f"[serve/split] split_layer {args.split_layer} → {ell} "
                  f"(pattern boundary)")
        opsc = OPSCConfig(split_layer=ell, qw_front=args.qw_front)
        eng = SplitEngine(cfg, params, opsc, channel=ChannelConfig(),
                          deadline_s=(args.deadline_ms or 0) / 1e3 or None,
                          opts=opts, cache_len=cache_len)
        t0 = time.time()
        tokens, stats = eng.generate(prompts, args.new)
        dt = time.time() - t0
        print(f"[serve/split] {tokens.shape[0]}×{args.new} tokens in {dt:.2f}s; "
              f"uplink {stats.uplink_bits_measured / 8e3:.1f} KB measured "
              f"({stats.uplink_bits_eq3 / 8e3:.1f} KB Eq.3), "
              f"early_exits={stats.early_exits}")
    else:
        eng = Engine(cfg, params, opts, cache_len=cache_len)
        t0 = time.time()
        res = eng.generate(prompts, args.new)
        dt = time.time() - t0
        tps = args.batch * args.new / dt
        print(f"[serve] {res.tokens.shape} in {dt:.2f}s = {tps:.1f} tok/s "
              f"(kv={'int8' if args.quantized_kv else 'bf16'})")


if __name__ == "__main__":
    main()
