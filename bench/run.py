"""Served-path benchmark: one cell of BENCHMARK.json on the chip it runs on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one configuration (``bench/configs/<config>.json``) under one
traffic mix (``bench/traffic/<traffic>.json``, read by the generator its
``arrival`` names). The run:

1. finds a TPU with as many chips as the cell asks for, or exits non-zero
   and prints no result;
2. sets up: draws the weights on the device from ``--seed`` in one
   compiled call, sizes the KV pool to the memory they leave, warms up
   the cell's compiled shapes (fresh chunk, continuation chunk, decode,
   first-token sampler) and runs the traffic's own warm-up;
3. measures for ``--seconds``: clients speak HTTP/SSE to the in-process
   server (``ServingHTTPServer`` -> ``AsyncLLMServer`` ->
   ``LLMServer(backend="paged")``) and record every streamed token;
4. with ``--trace 1``, attaches the serving tracer for the whole run and
   profiles a few seconds in the middle of the window;
5. frees the server, then checks a sample of the served tokens against
   the configuration's plain reference (``check.py``);
6. prints the compared numbers beside their limits as the last lines of
   standard error, and one JSON result as the last line of standard
   output: end-to-end metrics with ``--trace 0``, per-layer metrics
   (each read by ``bench/metrics/<name>.py``) with ``--trace 1``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (BENCH, os.path.join(BENCH, "traffic"),
           os.path.join(BENCH, "metrics")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

PROFILE_S = 20.0  # profiled stretch in the middle of a traced window
OUT_DIR = os.path.join(ROOT, ".bench_out")
# the pool's sizing rule and the tick, facts of the program's serving path
# rather than of a deployment: memory kept free for compiled programs'
# temporaries, and the two pool copies a chunked tick holds (it reads one
# pool and returns the next)
RESERVE_BYTES = 1 << 30
POOL_COPIES = 2
TICK_MODE = "chunked"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_").replace(
        "-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str) -> dict:
    """The cell, its configuration and traffic, and its metric entries,
    all found by name from BENCHMARK.json."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, entry["file"]))
    mix = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return {"cell": cell, "config": cfg, "traffic": mix,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def check_device(chips: int) -> list:
    """The chips this cell runs on; SystemExit when there is no TPU or
    too few of them. Never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU: jax.devices()[0] is "
                         f"{devs[0].platform} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} TPU chips, found "
                         f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, caching every program however fast it compiled. The path is
    part of the cache's key, so it never moves."""
    import jax

    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileEvents:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (any thread)."""

    def __init__(self):
        import jax

        self.compiles, self.seconds, self.hits = 0, 0.0, 0
        self.times: list = []  # perf_counter time each compile ended
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration
            self.times.append(time.perf_counter())


def program_module(cfg: dict):
    """The module that builds the program's ArchConfig and RuntimeOpts
    for this configuration (its file's ``"program"`` key)."""
    return load_module(os.path.join(BENCH, "configs", cfg["program"] + ".py"))


def pool_pages(ref, cfg: dict, devices) -> int:
    """Pages that fit the memory the weights leave on the fullest chip,
    keeping the reserve free and room for the pool copies a tick holds
    (the tick reads one pool and returns another)."""
    free = min(d.memory_stats()["bytes_limit"]
               - d.memory_stats()["bytes_in_use"] for d in devices)
    return int((free - RESERVE_BYTES) // (
        POOL_COPIES * ref.page_bytes(cfg, cfg["serving"]["page_size"])))


def build_server(cfg: dict, weights, pages: int, max_len: int, tracer):
    from repro.serving.api import LLMServer

    srv, prog = cfg["serving"], program_module(cfg)
    return LLMServer(prog.arch_config(cfg), weights, prog.runtime_opts(cfg),
                     backend="paged", telemetry=tracer, num_pages=pages,
                     page_size=srv["page_size"], max_slots=srv["max_slots"],
                     max_seq_len=max_len, prefill_chunk=srv["prefill_chunk"],
                     tick_mode=TICK_MODE)


def jobs_of(sched: dict) -> list:
    """Every request of a schedule, open or closed loop."""
    if sched["kind"] == "open":
        return sched["jobs"]
    return [j for jobs in sched["clients"] for j in jobs]


def warm_shapes(server, cfg: dict, mix: dict, jobs: list) -> dict:
    """Compile (or load from the cache) every program this cell's traffic
    dispatches: one request through a fresh chunk, a continuation chunk
    when the mix has prompts longer than a chunk, the first-token sampler
    and one decode step. Returns {rid: prompt length}."""
    import numpy as np

    from repro.core.sampling import SamplingParams

    chunk = cfg["serving"]["prefill_chunk"]
    plen = chunk + 1 if mix["prompt"]["max"] > chunk else mix["prompt"]["min"]
    prompt = np.arange(plen, dtype=np.int32) % cfg["vocab_size"]
    rid = server.submit(prompt, SamplingParams(max_tokens=2))
    server.run()
    server.release(rid)
    # freeing a request scrubs its reserved pages on the device with an
    # eager op whose shape is its page count: run each count the schedule
    # reserves once (every seed's schedule has the same sizes)
    pool = server.backend.scheduler.pool
    for n in sorted({pool.pages_for(len(j["prompt"]) + j["max_tokens"])
                     for j in jobs}):
        pool.free(pool.admit(n * pool.page_size))
    return {rid: plen}


class Profiler:
    """Profiles [start, start + length] of the window into the run's own
    directory, with host marks that put the trace on perf_counter time."""

    def __init__(self, log_dir: str, length: float):
        self.log_dir = log_dir
        self.length = length
        self.marks: dict = {}
        self.error: BaseException | None = None

    def _mark(self, name: str) -> None:
        import jax

        with jax.profiler.TraceAnnotation(name):
            self.marks[name] = time.perf_counter()

    def run(self, start: float) -> None:
        import jax

        try:
            time.sleep(max(0.0, start - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self._mark("bench.open")
            time.sleep(self.length)
            self._mark("bench.close")
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — reported with the result
            self.error = e


async def drive(server, sched: dict, seconds: float, profiler) -> dict:
    """Serve ``sched`` over HTTP and measure ``seconds`` of it."""
    import clients
    from repro.serving.async_engine import AsyncLLMServer
    from repro.serving.http import ServingHTTPServer

    engine = AsyncLLMServer(server, max_queue_depth=1 << 16)
    http = ServingHTTPServer(engine, "127.0.0.1", 0)
    await http.start()
    prof_thread = None

    def on_open(t_open):
        nonlocal prof_thread
        if profiler is not None:
            start = t_open + (seconds - profiler.length) / 2
            prof_thread = threading.Thread(target=profiler.run,
                                           args=(start,), daemon=True)
            prof_thread.start()

    try:
        run = clients.run_open if sched["kind"] == "open" else \
            clients.run_closed
        recs, t_open, t_close, late = await run(http.host, http.port, sched,
                                                seconds, on_open=on_open)
    finally:
        await http.stop(shutdown_engine=True, drain=False)
        if prof_thread is not None:
            prof_thread.join()
    if engine.error is not None:
        raise RuntimeError(f"serving tick thread failed: {engine.error!r}")
    return {"records": recs, "t_open": t_open, "t_close": t_close,
            "lateness": late}


class RunData:
    """Everything a metric reader may read; see bench/metrics/."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def read_metrics(entries, run: RunData) -> dict:
    """Each entry's reader on ``run``. A reader that finds nothing returns
    None, and an entry that lists this cell must find something: its
    silence is an error, not a metric left out of the line."""
    out = {}
    for m in entries:
        mod = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"))
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
        elif run.cell["name"] in m.get("workloads", ()):
            raise RuntimeError(f"metric {m['name']} is listed for "
                               f"{run.cell['name']} but read nothing")
    return out


def reduce_profile(profiler, run: RunData) -> tuple:
    """(busy_s, window_s, breakdown) from the profile; sets ``run.trace``."""
    import work
    import xplane

    tr = xplane.load(xplane.find_xplane(profiler.log_dir))
    lo, hi = tr["marks"]["bench.open"], tr["marks"]["bench.close"]
    # trace time = perf_counter time + offset
    run.trace_offset = lo - profiler.marks["bench.open"]
    run.trace = tr
    run.trace_window = (lo, hi)
    busy, named = [], []
    for dev in tr["devices"].values():
        ops = xplane.clip(dev["ops"], lo, hi)
        busy.append(xplane.union_seconds(ops))
        mods = xplane.module_of(ops, dev["modules"])
        # an op's name in the trace is its whole HLO instruction; the
        # breakdown keeps the part before " = " (e.g. "%copy.188")
        named += [(f"{mod}/{short}" if mod else short, s, d)
                  for (name, s, d), mod in zip(ops, mods)
                  for short in [name.split(" = ", 1)[0]]]
    if not busy:
        raise RuntimeError("the profile holds no TPU device plane")
    top = sorted(xplane.seconds_by_name(named).items(),
                 key=lambda kv: -kv[1])[:10]
    gaps = []
    for start, length in xplane.gaps(named, lo, hi)[:10]:
        rec = work.tick_of(run.ticks, start - run.trace_offset)
        label = ("between ticks" if rec is None else
                 f"tick {rec.tick} ({rec.mode}, {rec.active_slots} active, "
                 f"{rec.prefilling_slots} prefilling)")
        gaps.append([label, length])
    return (sum(busy) / len(busy), hi - lo,
            {"device_ops": [[k, v] for k, v in top], "idle_gaps": gaps})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = resolve(args.workload)
    cell, cfg, mix = spec["cell"], spec["config"], spec["traffic"]
    devices = check_device(cell["chips"])
    sys.path.insert(0, os.path.join(ROOT, "src"))
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      devices)
    print(json.dumps(result), flush=True)
    return 0


def peaks_for(kind: str) -> dict:
    """The chip's peaks from bench/peaks.json; an unknown kind is an
    error, never a default."""
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             devices, control: bool = False, on_data=None) -> dict:
    """Set up, measure, check and read one run of a cell; returns the
    result line's object. With ``control`` the fp8 control, read on the
    same sample, takes the program's place in the comparison that decides
    ``correct``, and the program's own numbers go under ``"program"``
    (bench/control.py; the benchmark's own runs never do this);
    ``on_data`` is handed the run's RunData (bench/sweep.py)."""
    import jax

    cell, cfg, mix = spec["cell"], spec["config"], spec["traffic"]
    cache = enable_compile_cache()
    events = CompileEvents()
    import lengths

    ref = load_module(os.path.join(BENCH, "configs",
                                   cfg["reference"] + ".py"))
    gen = load_module(os.path.join(BENCH, "traffic", mix["arrival"] + ".py"))
    weights = ref.make_weights(cfg, seed)
    jax.block_until_ready(weights)
    max_len = lengths.max_seq_len(mix)
    pages = pool_pages(ref, cfg, devices)
    log(f"{cell['name']}: {devices[0].device_kind} x {len(devices)}, "
        f"{ref.param_count(cfg)} parameters, pool {pages} pages of "
        f"{cfg['serving']['page_size']} tokens, max_seq_len {max_len}, "
        f"compile cache {cache}")

    tracer = None
    if trace:
        from repro.serving.telemetry import Tracer

        tracer = Tracer()
    server = build_server(cfg, weights, pages, max_len, tracer)
    log(f"weights and server ready at {time.perf_counter() - T_START:.1f} s")
    sched = gen.schedule(mix, cfg["vocab_size"], seed, seconds)
    prompt_len = warm_shapes(server, cfg, mix, jobs_of(sched))
    log(f"warm shapes done at {time.perf_counter() - T_START:.1f} s: "
        f"{events.compiles} compiles ({events.seconds:.1f} s), "
        f"{events.hits} cache hits")

    profiler = None
    if trace:
        log_dir = os.path.join(OUT_DIR, f"{cell['name']}-{seed}")
        shutil.rmtree(log_dir, ignore_errors=True)
        profiler = Profiler(log_dir, min(PROFILE_S, seconds))
    loop_out = asyncio.run(drive(server, sched, seconds, profiler))
    recs, t_open, t_close = (loop_out["records"], loop_out["t_open"],
                             loop_out["t_close"])
    setup_s = t_open - T_START
    in_window = sum(t_open <= t <= t_close for t in events.times)
    peak = max(d.memory_stats()["peak_bytes_in_use"] for d in devices)
    late = sorted(loop_out["lateness"])
    for r in recs:
        if r.rid is not None:
            prompt_len[r.rid] = len(r.job["prompt"])
    due = [r for r in recs if r.due is not None and t_open <= r.due < t_close]
    unfinished = sum(1 for r in due if r.first is None or r.first > t_close)
    failed = [r for r in recs if r.error is not None and r.sent is not None
              and r.sent < t_close]
    attempted = [r for r in recs if r.sent is not None and r.sent < t_close
                 and (r.done is None or r.done >= t_open)]
    log(f"window {seconds:.0f} s: {len(attempted)} requests attempted, "
        f"{len(failed)} failed, {len(due)} due in the window of which "
        f"{unfinished} had no first token at the close; "
        f"{in_window} compiles inside the window; generator lateness "
        f"max {late[-1] if late else 0:.4f} s, median "
        f"{late[len(late) // 2] if late else 0:.4f} s")
    ticks = list(tracer.ticks) if tracer is not None else []
    spans = list(tracer.spans) if tracer is not None else []
    max_slots = cfg["serving"]["max_slots"]
    del server
    gc.collect()
    log(f"server freed: {max(d.memory_stats()['bytes_in_use'] for d in devices)}"
        f" bytes in use before the reference")

    kind = devices[0].device_kind
    peaks = peaks_for(kind)
    t_ref = time.perf_counter()
    import check

    sample = check.sample(recs, seed)
    res = check.compare(ref, weights, cfg, sample, max_len,
                        mix["output"]["max"], control=control)
    limits = cfg["correct"]
    ok_sample = bool(sample) and any(r.job["greedy"] for r in sample)
    # the control stands in the program's place and is judged the same way
    judged = res["control"] if control else res
    checks = {k: {"value": judged[k], "limit": limits[k]} for k in limits}
    correct = ok_sample and all(v["value"] <= v["limit"]
                                for v in checks.values())
    log(f"reference over {res['requests']} requests, {res['tokens']} served "
        f"tokens in {time.perf_counter() - t_ref:.1f} s")

    run = RunData(cell=cell, config=cfg, traffic=mix, seconds=seconds,
                  t_open=t_open, t_close=t_close, records=recs,
                  setup_s=setup_s, ticks=ticks, spans=spans,
                  prompt_len=prompt_len, max_slots=max_slots,
                  peaks=peaks, dims=ref.dims(cfg),
                  matmul_params=ref.matmul_params_per_token(cfg),
                  trace=None, trace_window=None, trace_offset=None)
    if on_data is not None:
        on_data(run)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": len(attempted),
           "failed": len(failed)}
    if trace:
        if profiler.error is not None:
            raise RuntimeError(f"profiling failed: {profiler.error!r}")
        busy, window, breakdown = reduce_profile(profiler, run)
        device.update(busy_s=busy, window_s=window)
        out["metrics"] = read_metrics(spec["per_layer"], run)
        out["breakdown"] = breakdown
    else:
        out["metrics"] = read_metrics(spec["end_to_end"], run)
    out["device"] = device
    for name, v in checks.items():
        log(f"check {name} = {v['value']!r} (limit {v['limit']!r})")
    log(f"check sampled_requests = {res['requests']} with "
        f"{res['tokens']} tokens (at least one greedy request needed)")
    if control:
        out["program"] = {k: res[k] for k in limits}
    out["checks"] = checks
    return out


if __name__ == "__main__":
    sys.exit(main())
