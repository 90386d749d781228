"""Distributed training launcher.

On real hardware this wires the same ``make_train_step`` through pjit with
the FSDP×TP shardings from repro.launch.sharding; in this CPU container use
``REPRO_FORCE_DEVICES=N`` to simulate an N-device host mesh (must be set
before jax initializes, hence the env hook at module top).

  REPRO_FORCE_DEVICES=8 PYTHONPATH=src python -m repro.launch.train \
      --arch gemma2-2b --tiny --steps 20 --mesh 2x4
"""

import os

if os.environ.get("REPRO_FORCE_DEVICES"):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count="
                               f"{os.environ['REPRO_FORCE_DEVICES']}").strip()

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.data.pipeline import ZipfMarkov, lm_loader
from repro.launch import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import RuntimeOpts
from repro.training.checkpoint import save_checkpoint
from repro.training.optimizer import AdamWConfig, adamw_init
from repro.training.train_loop import TrainConfig, init_train_state, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", default=None, help="e.g. 2x4 (data x model)")
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.tiny:
        cfg = cfg.tiny()
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
        mesh = jax.make_mesh(dims, axes)
    else:
        mesh = jax.make_mesh((1, 1), ("data", "model"))
    print(f"[train] arch={cfg.name} params={cfg.total_params():,} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    opts = RuntimeOpts(q_chunk=min(1024, args.seq), kv_chunk=min(1024, args.seq),
                       remat=True)
    tc = TrainConfig(AdamWConfig(lr=args.lr, warmup_steps=10,
                                 total_steps=args.steps),
                     accum_steps=args.accum)
    params, opt_state = init_train_state(cfg, jax.random.PRNGKey(0))

    pspecs = shd.param_specs(cfg, mesh, fsdp=True)
    with mesh:
        params = jax.device_put(params, shd.shardings_of(pspecs, mesh))
        opt_state = jax.device_put(
            opt_state, shd.shardings_of(shd.opt_state_specs(pspecs), mesh))
        step_fn = jax.jit(make_train_step(cfg, tc, opts),
                          donate_argnums=(0, 1))
        corpus = ZipfMarkov(cfg.vocab_size, branching=8, seed=0)
        loader = lm_loader(corpus, args.batch, args.seq, args.steps)
        dax = shd.data_axes(mesh) if args.batch % shd.len_prod(
            mesh, shd.data_axes(mesh)) == 0 else None
        bshard = NamedSharding(mesh, P(dax))
        t0 = time.time()
        for i, batch in enumerate(loader):
            batch = {k: jax.device_put(jnp.asarray(v), bshard)
                     for k, v in batch.items()}
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if i % 10 == 0:
                print(f"[train] step {i:4d} loss {float(metrics['loss']):.4f} "
                      f"({(time.time() - t0) / (i + 1):.2f}s/step)")
    if args.checkpoint:
        save_checkpoint(args.checkpoint, params, step=args.steps)
        print(f"[train] saved checkpoint → {args.checkpoint}")


if __name__ == "__main__":
    main()
