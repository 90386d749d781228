#!/usr/bin/env python3
"""Bring-up smoke run: the paged serving path on a TPU at published widths.

    python chip_smoke.py             # one chip: kernels, then HTTP serving
    python chip_smoke.py --chips 4   # four chips: deployment="sharded" only

Phases, in one process:

  a. device check — a TPU must be present, else exit non-zero;
  b. the main path's attention kernels (paged decode, paged prefill, varlen)
     against their ``kernels/ref.py`` oracles at Llama-2-7B widths;
  c. eight seeded greedy requests over a socket through ServingHTTPServer →
     AsyncLLMServer → LLMServer(backend="paged"), once in the chunked tick
     and once in the packed tick, on bf16 ``llama2-7b`` weights drawn from
     ``--seed``; each first-token logprob is compared with ``log_softmax``
     of ``forward_train``, the dense path with no kernels and no cache;
  d. a report, then one JSON line ``{"ok": true, "device": {...}}``.

Any failed check raises, so the process exits non-zero and prints no
result line. This is a bring-up check, not a speed measurement.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import sys
import time
from importlib import metadata

REPO = os.path.dirname(os.path.abspath(__file__))

CONFIG = "llama2-7b"
PAGE = 16           # tokens per pool page
MAX_SLOTS = 4       # fewer slots than requests, so some requests queue
N_REQUESTS = 8
NEW_TOKENS = 32
PROMPT_LENS = (32, 1024)  # inclusive range; one prompt is always 1024 long
# bytes kept free beside the weights and the two pool copies a tick holds
# (the scheduler's jits do not donate the pool): step activations, logits
# and the reference forward's (1, 1024, vocab) float32 logits
RESERVE_BYTES = 1 << 30
# Kernel-vs-oracle tolerance (absolute, on outputs whose values are bounded
# by the dequantized |v| <= 2.54): the chip may run the kernels' float32
# dots as single bf16 MXU passes, which rounds each dequantized key and
# value to 8 mantissa bits (relative 2^-9). Scores of size ~1.5 then move
# by ~3e-3, softmax weights by ~0.3%, and outputs by < 1e-2.
KERNEL_ATOL = 2e-2
# Served first-token logprob vs forward_train (nats). The served path reads
# history keys and values from the int8 pool (per-token, per-head
# symmetric int8: relative error <= 1/254 of each row's max) while
# forward_train attends bf16 k/v; both run bf16 weights and activations
# (relative 2^-9 per rounding) through 32 layers. Logits have a std of
# ~1.3 at these random weights, so each of those errors moves a logprob by
# ~1e-2; 0.15 leaves room for them to add up over the stack.
LOGPROB_TOL = 0.15


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check_device(chips: int):
    """Phase a: the devices JAX sees, or SystemExit when no TPU is there."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found: jax.devices()[0] is "
                         f"{d.platform} ({d.device_kind}), {len(devs)} "
                         f"device(s)")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, found {len(devs)}")
    return devs[:chips]


class CompileEvents:
    """XLA compile seconds, persistent-cache hits and new cache entries, from
    JAX's monitoring events. JAX reports a miss only when it writes the
    entry, and it writes only programs that took at least
    ``jax_persistent_cache_min_compile_time_secs`` (1 s) to compile, so
    faster compiles count as neither."""

    def __init__(self):
        import jax

        self.seconds, self.hits, self.written = 0.0, 0, 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.written += 1

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def snapshot(self) -> tuple:
        return self.seconds, self.hits, self.written


# ------------------------------------------------------------ phase b


def _pool(key, p: int, kh: int, page: int, hd: int):
    """Random pool leaves in the serving layout (codes, scales, and every
    position stored), built on the device."""
    import jax
    import jax.numpy as jnp

    k1, k2, k3, k4 = jax.random.split(key, 4)
    codes = lambda k: jax.random.randint(k, (p, kh, page, hd), -127, 128,
                                         jnp.int32).astype(jnp.int8)
    scale = lambda k: jax.random.uniform(k, (p, kh, 1, page), jnp.float32,
                                         0.005, 0.02)
    return codes(k1), scale(k2), codes(k3), scale(k4)


def _layout(lens, page: int, seed: int):
    """Block tables for requests of ``lens`` tokens over shuffled pages
    (page 0 is the trash page) and the matching stored positions."""
    import numpy as np

    rng = np.random.default_rng(seed)
    nb = max(-(-n // page) for n in lens)
    need = sum(-(-n // page) for n in lens)
    pages = rng.permutation(np.arange(1, need + 1))
    bt = np.zeros((len(lens), nb), np.int32)
    pos = np.full((need + 1, 1, page), -1, np.int32)
    nxt = 0
    for r, n in enumerate(lens):
        for b in range(-(-n // page)):
            bt[r, b] = pages[nxt]
            nxt += 1
        for t in range(n):
            pos[bt[r, t // page], 0, t % page] = t
    return bt, pos


def kernel_phase(kh: int = 32, hd: int = 128, page: int = PAGE,
                 seed: int = 0) -> dict:
    """Phase b: each main-path attention kernel against its oracle, at
    ``kh`` kv heads of ``hd`` (one query head per kv head, as in
    Llama-2-7B) and ``page``-token pages. Returns the max |error| per
    kernel; raises when one exceeds ``KERNEL_ATOL``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.kernels.paged_prefill_attention import first_call_position
    from repro.kernels.varlen_attention import segment_start

    key = jax.random.PRNGKey(seed)
    errs = {}

    def check(name, got, want):
        err = float(jnp.max(jnp.abs(got - want)))
        finite = bool(jnp.all(jnp.isfinite(got)))
        errs[name] = err
        log(f"kernel {name}: max |kernel - oracle| = {err:.3e} "
            f"(tolerance {KERNEL_ATOL})")
        if not finite or err > KERNEL_ATOL:
            raise AssertionError(f"{name} kernel disagrees with its oracle: "
                                 f"max error {err}, finite={finite}")

    # paged decode: 8 requests of ragged lengths up to 1024 tokens
    lens = (1024, 777, 512, 301, 64, 17, 1000, 5)
    bt, pos = _layout(lens, page, seed)
    kc, ks, vc, vs = _pool(key, pos.shape[0], kh, page, hd)
    bt, pos = jnp.asarray(bt), jnp.asarray(pos)
    q = jax.random.normal(jax.random.fold_in(key, 1),
                          (len(lens), kh, 1, hd), jnp.bfloat16)
    q_pos = jnp.asarray([n - 1 for n in lens], jnp.int32)
    got = ops.paged_decode_attention(q, kc, ks, vc, vs, pos, bt, q_pos)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_decode_attention_ref(q, kc, ks, vc, vs, pos, bt,
                                              q_pos)
    check("paged_decode", got, want)

    # paged prefill: 4 rows each continuing a prompt by a 256-token chunk
    # over its pool history (the chunked tick's continuation chunks)
    hist, s = (768, 256, 0, 500), 256
    bt, pos = _layout([h + s for h in hist], page, seed + 1)
    kc, ks, vc, vs = _pool(jax.random.fold_in(key, 2), pos.shape[0], kh,
                           page, hd)
    bt, pos = jnp.asarray(bt), jnp.asarray(pos)
    qp = jnp.asarray(np.stack([np.arange(h, h + s) for h in hist]), jnp.int32)
    kq, kk, kv = jax.random.split(jax.random.fold_in(key, 3), 3)
    q = jax.random.normal(kq, (len(hist), kh, s, 1, hd), jnp.bfloat16)
    kf = jax.random.normal(kk, (len(hist), kh, s, hd), jnp.bfloat16)
    vf = jax.random.normal(kv, (len(hist), kh, s, hd), jnp.bfloat16)
    got = ops.paged_prefill_attention(q, kc, ks, vc, vs, pos, bt, qp, kf, vf)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_prefill_attention_ref(
            q, kc, ks, vc, vs, pos, bt, qp, first_call_position(qp), kf, vf)
    check("paged_prefill", got, want)

    # varlen: one packed buffer of three decode tokens, two prefill pieces
    # and pad rows — a 96-token budget over five slots
    segs = ((300, 1), (40, 1), (0, 48), (128, 32), (17, 1))
    budget = 96
    bt, pos = _layout([h + n for h, n in segs], page, seed + 2)
    kc, ks, vc, vs = _pool(jax.random.fold_in(key, 4), pos.shape[0], kh,
                           page, hd)
    bt, pos = jnp.asarray(bt), jnp.asarray(pos)
    qp = np.full((budget,), -1, np.int32)
    sl = np.full((budget,), -1, np.int32)
    cur = 0
    for i, (h, n) in enumerate(segs):
        qp[cur:cur + n], sl[cur:cur + n] = np.arange(h, h + n), i
        cur += n
    qp, sl = jnp.asarray(qp), jnp.asarray(sl)
    kq, kk, kv = jax.random.split(jax.random.fold_in(key, 5), 3)
    q = jax.random.normal(kq, (kh, budget, 1, hd), jnp.bfloat16)
    kf = jax.random.normal(kk, (kh, budget, hd), jnp.float32)
    vf = jax.random.normal(kv, (kh, budget, hd), jnp.float32)
    got = ops.varlen_attention(q, kc, ks, vc, vs, pos, bt, qp, sl, kf, vf)
    with jax.default_matmul_precision("highest"):
        want = ref.varlen_attention_ref(
            q, kc, ks, vc, vs, pos, bt, qp, sl,
            segment_start(qp, sl, bt.shape[0]), kf, vf)
    check("varlen", got, want)
    return errs


# ------------------------------------------------------------ phase c


def make_prompts(vocab: int, seed: int, n: int = N_REQUESTS,
                 lens=PROMPT_LENS) -> list:
    """``n`` seeded prompts with lengths in ``lens``; the first is always
    ``lens[1]`` long so that prefill spans several chunks."""
    import numpy as np

    rng = np.random.default_rng(seed)
    sizes = [lens[1]] + [int(x) for x in rng.integers(lens[0], lens[1] + 1,
                                                      n - 1)]
    return [rng.integers(0, vocab, size).astype(np.int32).tolist()
            for size in sizes]


def reference_logprobs(cfg, params, prompts) -> list:
    """``log_softmax`` of ``forward_train`` at each prompt's last position
    (a (vocab,) float32 row per prompt). Prompts are right-padded to one
    length, which causal attention keeps from touching earlier positions,
    so a single program serves all of them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.transformer import RuntimeOpts, forward_train

    length = max(len(p) for p in prompts)
    opts = RuntimeOpts(remat=False, moe_capacity_factor=0.0)

    @jax.jit
    def last_logprobs(params, tokens, last):
        logits, _ = forward_train(params, cfg, tokens, opts=opts)
        return jax.nn.log_softmax(logits[0, last].astype(jnp.float32))

    out = []
    for p in prompts:
        toks = np.zeros((1, length), np.int32)
        toks[0, :len(p)] = p
        out.append(np.asarray(last_logprobs(params, jnp.asarray(toks),
                                            len(p) - 1)))
    return out


async def _complete(host: str, port: int, prompt: list, max_tokens: int,
                    timeout_s: float) -> dict:
    """One non-streaming greedy completion over a raw socket."""
    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "temperature": 0.0, "stream": False}).encode()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(b"POST /v1/completions HTTP/1.1\r\nHost: smoke\r\n"
                     b"Content-Type: application/json\r\n"
                     + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(), timeout_s)
    finally:
        writer.close()
        await writer.wait_closed()
    head, _, payload = raw.partition(b"\r\n\r\n")
    status = head.split(b"\r\n", 1)[0]
    if b" 200 " not in status + b" ":
        raise AssertionError(f"HTTP request failed: {status!r} {payload!r}")
    return json.loads(payload)


async def _serve_requests(server, prompts, max_tokens: int,
                          timeout_s: float) -> list:
    from repro.serving.async_engine import AsyncLLMServer
    from repro.serving.http import ServingHTTPServer

    engine = AsyncLLMServer(server)
    http = ServingHTTPServer(engine, "127.0.0.1", 0)
    await http.start()
    try:
        return await asyncio.gather(*(
            _complete(http.host, http.port, p, max_tokens, timeout_s)
            for p in prompts))
    finally:
        await http.stop()
        if engine.error is not None:
            raise AssertionError(f"tick thread failed: {engine.error!r}")


def serve_phase(cfg, params, opts, prompts, ref_lps, *, tick_mode: str,
                num_pages: int, page_size: int = PAGE,
                max_slots: int = MAX_SLOTS, max_tokens: int = NEW_TOKENS,
                prefill_chunk: int = 256, deployment: str = "fused", mesh=None,
                timeout_s: float = 900.0) -> dict:
    """Phase c for one tick mode: boot the HTTP service in-process on an
    ephemeral port, run ``prompts`` over sockets, and check every result.
    Returns the streams and the worst first-token logprob gap. On a TPU
    each lowered tick must contain the Pallas kernels' ``tpu_custom_call``
    (elsewhere the kernels lower to the interpreter)."""
    import jax
    import numpy as np

    from repro.serving.api import LLMServer

    server = LLMServer(cfg, params, opts, backend="paged",
                       deployment=deployment, mesh=mesh, num_pages=num_pages,
                       page_size=page_size, max_slots=max_slots,
                       max_seq_len=max(map(len, prompts)) + max_tokens,
                       prefill_chunk=prefill_chunk, tick_mode=tick_mode)
    sched = server.backend.scheduler
    t0 = time.perf_counter()
    outs = asyncio.run(_serve_requests(server, prompts, max_tokens,
                                       timeout_s))
    wall = time.perf_counter() - t0
    gaps = []
    for p, out, ref in zip(prompts, outs, ref_lps):
        toks, lps = out["tokens"], out["logprobs"]
        if len(toks) != max_tokens or out["finish_reason"] != "length":
            raise AssertionError(
                f"{tick_mode}: request of {len(p)} tokens finished with "
                f"{len(toks)} tokens ({out['finish_reason']})")
        if len(lps) != max_tokens or not np.all(np.isfinite(lps)):
            raise AssertionError(f"{tick_mode}: non-finite logprobs {lps}")
        gaps.append(abs(lps[0] - float(ref[toks[0]])))
    in_use = sched.pool.pages_in_use
    if in_use != 0:
        raise AssertionError(f"{tick_mode}: {in_use} pages in use after drain")
    worst = max(gaps)
    if worst > LOGPROB_TOL:
        raise AssertionError(f"{tick_mode}: first-token logprob gap {worst} "
                             f"exceeds {LOGPROB_TOL}: {gaps}")
    lowered = {name: low.as_text() for name, low in
               sched.lower_ticks().items()}
    marker = "tpu_custom_call"
    missing = [name for name, txt in lowered.items() if marker not in txt]
    if missing and jax.default_backend() == "tpu":
        raise AssertionError(f"{tick_mode}: {missing} lowered without "
                             f"{marker!r}")
    log(f"{tick_mode}: {len(outs)} requests x {max_tokens} tokens finished "
        f"in {wall:.1f} s wall (compiles included), pages in use after "
        f"drain = {in_use}, worst first-token logprob gap = {worst:.4e} "
        f"(tolerance {LOGPROB_TOL}), {marker} in "
        f"{sorted(set(lowered) - set(missing))}")
    streams = [out["tokens"] for out in outs]
    del server, sched
    gc.collect()
    return {"streams": streams, "worst_gap": worst, "gaps": gaps,
            "wall_s": wall}


def pool_pages(cfg, devices, copies: int) -> int:
    """Pool pages that fit the memory the weights leave on the fullest
    device, keeping ``RESERVE_BYTES`` free and room for ``copies`` pools."""
    from repro.serving.kv_pool import page_bytes

    free = min(d.memory_stats()["bytes_limit"]
               - d.memory_stats()["bytes_in_use"] for d in devices)
    return int((free - RESERVE_BYTES) // (copies * page_bytes(cfg, PAGE)))


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = check_device(args.chips)
    sys.path.insert(0, os.path.join(REPO, "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    events = CompileEvents()
    d = devices[0]
    log(f"device {d.device_kind} x {len(devices)} ({d.platform}); jax "
        f"{jax.__version__}, libtpu {metadata.version('libtpu')}; compile "
        f"cache {cache_dir}")

    phases = {}

    def phase(name, fn, *a, **kw):
        (c0, h0, m0), t0 = events.snapshot(), time.perf_counter()
        out = fn(*a, **kw)
        c1, h1, m1 = events.snapshot()
        phases[name] = (time.perf_counter() - t0, c1 - c0, h1 - h0, m1 - m0)
        log(f"phase {name}: {phases[name][0]:.1f} s wall, "
            f"{phases[name][1]:.1f} s compiling, persistent cache "
            f"{phases[name][2]} hits / {phases[name][3]} entries written")
        return out

    if args.chips == 1:
        phase("kernels", kernel_phase, seed=args.seed)

    from jax.sharding import NamedSharding, PartitionSpec

    from repro.configs import get_config
    from repro.launch.mesh import make_serving_mesh
    from repro.serving.http import build_model
    from repro.serving.kv_pool import page_bytes

    mesh = repl = None
    if args.chips > 1:
        mesh = make_serving_mesh(
            get_config(CONFIG).pattern[0].mixer.num_kv_heads, devices)
        repl = NamedSharding(mesh, PartitionSpec())
    cfg, params, opts = phase("init", build_model, CONFIG, args.seed, repl)
    jax.block_until_ready(params)
    weight_bytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(params))
    # one chip: the tick holds the pool it reads and the pool it returns;
    # sharded: each device also holds the step's all-gathered full pool
    pages = pool_pages(cfg, devices, 2 if mesh is None else 3)
    log(f"weights {weight_bytes} bytes ({cfg.total_params()} params, "
        f"bf16); pool {pages} pages x {PAGE} tokens = "
        f"{pages * page_bytes(cfg, PAGE)} bytes; mesh "
        f"{None if mesh is None else dict(mesh.shape)}")

    prompts = make_prompts(cfg.vocab_size, args.seed)
    log(f"prompt lengths {[len(p) for p in prompts]}")
    ref = phase("reference", reference_logprobs, cfg, params, prompts)
    deployment = "fused" if mesh is None else "sharded"
    runs = {}
    for mode in ("chunked", "packed"):
        runs[mode] = phase(f"serve_{mode}", serve_phase, cfg, params, opts,
                           prompts, ref, tick_mode=mode, num_pages=pages,
                           deployment=deployment, mesh=mesh)
    a, b = runs["chunked"]["streams"], runs["packed"]["streams"]
    same = sum(x == y for s, t in zip(a, b) for x, y in zip(s, t))
    total = sum(len(s) for s in a)
    log(f"packed vs chunked greedy streams: {same}/{total} tokens agree, "
        f"{sum(s == t for s, t in zip(a, b))}/{len(a)} streams identical "
        f"(reported, not asserted: greedy picks on random weights flip on "
        f"rounding)")
    peak = max(dev.memory_stats()["peak_bytes_in_use"] for dev in devices)
    limit = min(dev.memory_stats()["bytes_limit"] for dev in devices)
    worst = max(r["worst_gap"] for r in runs.values())
    log(f"peak_bytes_in_use {peak} of bytes_limit {limit}; worst "
        f"first-token logprob gap {worst:.4e}; compile seconds per phase "
        f"{ {k: round(v[1], 1) for k, v in phases.items()} }")
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
