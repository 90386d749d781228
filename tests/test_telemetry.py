"""Unified serving telemetry (``serving.telemetry``): streaming-histogram
percentile correctness, full request-lifecycle span coverage on a
preemption workload, host phases that tile each tick, the per-tick work
counters, the HTTP/SSE front end's spans, bounded retention, the
disabled-path no-op guarantee, Chrome-trace schema validation (incl.
``tools/trace_report.py``), greedy bit-identity with tracing on vs. off
and no device sync of the tracer's own, ``LLMServer.metrics()``, and
split-engine wire accounting."""

import asyncio
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.opsc import OPSCConfig
from repro.core.sampling import SamplingParams
from repro.models.transformer import RuntimeOpts, init_params
from repro.serving import (Engine, Histogram, LLMServer, Scheduler,
                           SplitEngine, Tracer)
from repro.serving.async_engine import AsyncLLMServer
from repro.serving.http import ServingHTTPServer, SSEParser

OPTS_Q = RuntimeOpts(q_chunk=16, kv_chunk=16, remat=False, quantized_kv=True,
                     moe_capacity_factor=0.0)
OPTS = RuntimeOpts(q_chunk=16, kv_chunk=16, remat=False,
                   moe_capacity_factor=0.0)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama2-7b").tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ------------------------------------------------------------- histogram


def test_histogram_percentiles_on_known_distribution():
    """1..10000 recorded once each: every quantile is within the sketch's
    relative error of the true value, count/sum/min/max are exact."""
    h = Histogram(rel_err=0.01)
    for v in range(1, 10001):
        h.record(float(v))
    assert h.count == 10000
    assert h.sum == pytest.approx(10000 * 10001 / 2)
    assert h.min == 1.0 and h.max == 10000.0
    for q in (0.10, 0.50, 0.95, 0.99):
        true = q * (h.count - 1) + 1
        assert h.percentile(q) == pytest.approx(true, rel=0.021)
    assert h.percentile(0.0) == 1.0
    assert h.percentile(1.0) == 10000.0
    s = h.summary()
    assert set(s) == {"count", "sum", "mean", "min", "max",
                      "p50", "p95", "p99"}


def test_histogram_zero_and_edge_cases():
    h = Histogram()
    assert h.percentile(0.5) is None and h.mean is None
    assert h.summary() == {"count": 0}
    h.record(0.0)
    h.record(0.0)
    h.record(5.0)
    assert h.percentile(0.0) == 0.0  # the exact zero bucket
    assert h.percentile(1.0) == 5.0
    with pytest.raises(ValueError):
        h.percentile(1.5)
    with pytest.raises(ValueError):
        Histogram(rel_err=0.0)


def test_metrics_registry_flat():
    from repro.serving import MetricsRegistry
    m = MetricsRegistry()
    m.count("a")
    m.count("a", 4)
    m.gauge("g", 7.5)
    m.observe("h", 2.0)
    m.observe("h", 4.0)
    flat = m.flat()
    assert flat["a"] == 5 and flat["g"] == 7.5
    assert flat["h.count"] == 2 and flat["h.min"] == 2.0
    assert flat["h.mean"] == pytest.approx(3.0)


# ------------------------------------------- lifecycle spans (scheduler)


def _preemption_run(cfg, params, tracer, resume="swap", abort_one=False):
    """The PR 3 preemption workload: lazy growth over a pool too small for
    every worst case forces at least one eviction + resume."""
    rng = np.random.default_rng(11)
    jobs = [(6, 8, 1), (5, 9, 0), (4, 8, 0)]  # (prompt, max_new, priority)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)) for n, _, _ in jobs]
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=9, page_size=4,
                      max_slots=3, lazy_growth=True, resume=resume,
                      telemetry=tracer)
    rids = [sched.submit(p, mn, priority=pr)
            for p, (_, mn, pr) in zip(prompts, jobs)]
    if abort_one:
        extra = sched.submit(rng.integers(0, cfg.vocab_size, (4,)), 6)
        sched.abort(extra)
    results = sched.run()
    assert sched.stats.preemptions >= 1
    return sched, rids, prompts, jobs, results


def test_span_lifecycle_covers_every_phase(tiny_model):
    """Acceptance: a mixed prefill/decode/preemption run lands >= 1 span
    or instant per lifecycle phase — queued, prefill, first_token, decode,
    preempt, swap_out/swap_resume, finish — with consistent timestamps."""
    cfg, params = tiny_model
    tracer = Tracer()
    sched, rids, _, _, _ = _preemption_run(cfg, params, tracer,
                                           abort_one=True)
    by_name = {}
    for sp in sched.telemetry.spans:
        by_name.setdefault(sp.name, []).append(sp)
    ev_names = {e[0] for e in tracer.events}
    for phase in ("queued", "prefill", "decode", "swap_out", "swap_resume"):
        assert phase in by_name, f"no {phase} span recorded"
    assert {"first_token", "finish", "preempt"} <= ev_names
    # every span closed (run drained), every duration non-negative
    for sp in tracer.spans:
        assert sp.end is not None, f"{sp.name} left open"
        assert sp.duration >= 0.0
    # preempted request: its queued span count exceeds one (requeued)
    requeued = [sp for sp in by_name["queued"]
                if sp.attrs.get("requeued")]
    assert requeued and requeued[0].attrs["reason"] == "preempt"
    # ttft bookkeeping: every finished request got a ttft_ticks entry,
    # and spans carry the tick ids they started under
    assert set(rids) <= set(tracer.ttft_ticks)
    assert all(t >= 1 for t in tracer.ttft_ticks.values())
    assert any("tick" in sp.attrs for sp in by_name["prefill"])
    m = tracer.metrics_dict()
    assert m["scheduler.preemptions"] >= 1
    assert m["requests.finish_reason.abort"] == 1
    assert m["ttft_s.count"] == len(rids)
    assert m["tick.count"] == len(tracer.ticks) > 0


def test_tick_timeline_records(tiny_model):
    """Per-tick records: every tick carries mode/token/pool/queue fields,
    compile counts sum to the scheduler's compiled-shape stat, and the
    final tick leaves the pool empty."""
    cfg, params = tiny_model
    tracer = Tracer()
    sched, _, _, jobs, _ = _preemption_run(cfg, params, tracer)
    ticks = tracer.ticks
    assert [r.tick for r in ticks] == sorted(r.tick for r in ticks)
    assert all(r.wall_s >= 0 and r.mode == sched.tick_mode for r in ticks)
    assert sum(r.new_compiles for r in ticks) == sched.stats.compiled_shapes
    assert sum(r.new_compiles + r.shape_hits for r in ticks) \
        == tracer.metrics.counters["compile.dispatches"]
    # generated tokens all appear in the timeline (prefill + decode)
    total = sum(r.tokens for r in ticks)
    assert total >= sum(mn for _, mn, _ in jobs)
    assert ticks[-1].pages_in_use == 0 and ticks[-1].queue_depth == 0
    assert max(r.pages_in_use for r in ticks) > 0
    assert max(r.swap_bytes for r in ticks) > 0  # swap really happened


# --------------------------------------------------- disabled path no-op


def test_disabled_path_never_touches_tracer(tiny_model, monkeypatch):
    """Overhead guard: with ``telemetry=None`` (the default) NO Tracer
    method may run — every public method is patched to raise, and a full
    preemption run plus fused + split generations must still succeed."""
    cfg, params = tiny_model

    def boom(self, *a, **k):  # pragma: no cover - must never fire
        raise AssertionError("Tracer touched on the disabled path")

    for name in dir(Tracer):
        if not name.startswith("_"):
            monkeypatch.setattr(Tracer, name, boom)
    sched, _, _, _, _ = _preemption_run(cfg, params, None)
    assert sched.telemetry is None
    eng = Engine(cfg, params, OPTS_Q, cache_len=32)
    assert eng.telemetry is None
    eng.generate(np.arange(4, dtype=np.int32)[None] % cfg.vocab_size, 3)
    opsc = OPSCConfig(split_layer=1, qw_front=16, i_kv=1)
    se = SplitEngine(cfg, params, opsc, opts=OPTS, cache_len=32)
    assert se.telemetry is None
    se.generate(np.arange(5, dtype=np.int32)[None] % cfg.vocab_size, 3,
                compress=False)
    # the served path: HTTP/SSE front end -> async engine -> paged backend
    srv = LLMServer(cfg, params, OPTS_Q, backend="paged", num_pages=24,
                    page_size=4, max_slots=2)
    assert srv.tracer is None
    outs = _serve_http(srv, [[1, 2, 3, 4, 5], [6, 7, 8]], max_tokens=3)
    assert all(len(_frames(msgs)) == 4 for msgs in outs)  # 3 + finish


# ------------------------------------------------------- greedy identity


def test_greedy_bit_identical_telemetry_on_vs_off(tiny_model, monkeypatch):
    """Acceptance: tracing must observe, never perturb — the preemption
    workload's greedy tokens are IDENTICAL with telemetry on and off, and
    the traced run makes no device sync of its own (no
    ``block_until_ready``): it schedules as the untraced one does."""
    cfg, params = tiny_model
    _, rids_off, _, _, res_off = _preemption_run(cfg, params, None)
    syncs = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: syncs.append(1) or real(x))
    _, rids_on, _, _, res_on = _preemption_run(cfg, params, Tracer())
    assert syncs == []
    for ra, rb in zip(rids_off, rids_on):
        np.testing.assert_array_equal(res_off[ra], res_on[rb])


# ----------------------------------------------------- chrome trace export


def test_chrome_trace_schema_and_report(tiny_model, tmp_path):
    """The exported trace is valid Chrome trace-event JSON: every event
    has ph/pid/tid/ts, spans have non-negative dur, tracks map to stable
    tids (ticks=0, queue=1, slot<i>=2+i), metadata names every track, and
    ``tools/trace_report.py`` validates it with all 7 phases required."""
    cfg, params = tiny_model
    tracer = Tracer()
    _preemption_run(cfg, params, tracer, abort_one=True)
    path = tmp_path / "trace.json"
    trace = tracer.export_chrome_trace(str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk["displayTimeUnit"] == "ms"
    assert on_disk["repro_metrics"] == pytest.approx(trace["repro_metrics"])
    evs = trace["traceEvents"]
    assert all({"name", "ph", "pid"} <= set(e) for e in evs)
    for e in evs:
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
    tids = {e["args"]["name"]: e["tid"] for e in evs
            if e["ph"] == "M" and e["name"] == "thread_name"}
    assert tids["ticks"] == 0 and tids["queue"] == 1
    assert tids["slot0"] == 2
    queued = [e for e in evs if e.get("cat") == "span"
              and e["name"] == "queued"]
    assert queued and all(e["tid"] == 1 for e in queued)
    tick_evs = [e for e in evs if e.get("cat") == "tick"]
    assert tick_evs and all(e["tid"] == 0 for e in tick_evs)

    from tools.trace_report import report, validate
    problems = validate(
        trace, require_phases=("queued", "prefill", "first_token", "decode",
                               "preempt", "swap_resume", "finish"),
        min_spans=5, min_ticks=5)
    assert problems == []
    import io
    buf = io.StringIO()
    report(trace, out=buf)
    text = buf.getvalue()
    assert "prefill" in text and "SLO table" in text
    from tools.trace_report import main as report_main
    assert report_main([str(path), "--require-spans", "5",
                        "--require-ticks", "5",
                        "--require-phases", "queued,preempt,finish"]) == 0
    assert report_main([str(path), "--require-phases", "warpdrive"]) == 1


def test_open_spans_export_closed_at_export_instant():
    t = [0.0]
    tracer = Tracer(clock=lambda: t[0])
    tracer.request_submitted(1)
    t[0] = 2.0
    trace = tracer.export_chrome_trace()
    sp = [e for e in trace["traceEvents"] if e.get("cat") == "span"]
    assert len(sp) == 1 and sp[0]["args"]["open"] is True
    assert sp[0]["dur"] == pytest.approx(2e6)


# ------------------------------------------------------ server integration


def test_llmserver_metrics_and_ttft_ticks_paged(tiny_model):
    cfg, params = tiny_model
    rng = np.random.default_rng(3)
    srv = LLMServer(cfg, params, OPTS_Q, backend="paged", num_pages=24,
                    page_size=4, max_slots=3, telemetry=True)
    assert srv.tracer is not None
    rids = [srv.submit(rng.integers(0, cfg.vocab_size, (n,)),
                       SamplingParams(max_tokens=4)) for n in (5, 7)]
    outs = srv.run()
    m = srv.metrics()
    assert m["requests.submitted"] == 2 and m["requests.finished"] == 2
    assert m["ttft_s.count"] == 2 and m["tick.count"] >= 1
    assert m["requests.retained"] == 2
    assert m["requests.reason.length"] == 2
    for rid in rids:
        assert outs[rid].metrics.ttft_ticks == srv.tracer.ttft_ticks[rid]


def test_llmserver_metrics_without_telemetry(tiny_model):
    """server.metrics() still reports request-level aggregates with the
    tracer off — from the retained RequestOutputs."""
    cfg, params = tiny_model
    rng = np.random.default_rng(4)
    srv = LLMServer(cfg, params, OPTS_Q, backend="paged", num_pages=24,
                    page_size=4, max_slots=2)
    assert srv.tracer is None
    srv.submit(rng.integers(0, cfg.vocab_size, (5,)),
               SamplingParams(max_tokens=3))
    srv.run()
    m = srv.metrics()
    assert m["requests.retained"] == 1
    assert m["requests.reason.length"] == 1
    assert m["requests.ttft_s.count"] == 1
    assert "requests.ttft_ticks.p50" in m


def test_fused_backend_ttft_ticks_and_span(tiny_model):
    """Satellite: the fused backend now populates RequestMetrics.ttft_ticks
    (one fused call = tick 1) and lands a fused_generate span."""
    cfg, params = tiny_model
    rng = np.random.default_rng(5)
    srv = LLMServer(cfg, params, OPTS_Q, backend="fused", cache_len=32,
                    telemetry=True)
    rid = srv.submit(rng.integers(0, cfg.vocab_size, (5,)),
                     SamplingParams(max_tokens=4))
    out = srv.run()[rid]
    assert out.metrics.ttft_ticks == 1
    names = {sp.name for sp in srv.tracer.spans}
    assert "fused_generate" in names
    m = srv.metrics()
    assert m["fused.calls"] >= 1 and m["fused.batch_s.count"] >= 1


def test_split_backend_telemetry_wire_accounting(tiny_model):
    """Split backend: edge/cloud segment spans, per-step uplink events
    whose bits sum to SplitStats.uplink_bits_measured, and the TAB-Q
    bit-width histogram with one entry per uplinked token."""
    cfg, params = tiny_model
    rng = np.random.default_rng(6)
    opsc = OPSCConfig(split_layer=1, qw_front=16, i_kv=1)
    srv = LLMServer(cfg, params, OPTS, backend="split", opsc=opsc,
                    cache_len=32, telemetry=True)
    rid = srv.submit(rng.integers(0, cfg.vocab_size, (6,)),
                     SamplingParams(max_tokens=4))
    out = srv.run()[rid]
    assert out.metrics.ttft_ticks == 1
    tr = srv.tracer
    tracks = {sp.track for sp in tr.spans}
    assert "split:edge" in tracks and "split:cloud" in tracks
    stages = {sp.attrs.get("stage") for sp in tr.spans
              if sp.track == "split:edge"}
    assert {"prefill", "decode"} <= stages
    uplinks = [e for e in tr.events if e[0] == "uplink"]
    assert sum(e[4]["bits"] for e in uplinks) \
        == out.split_stats.uplink_bits_measured
    m = tr.metrics_dict()
    assert m["split.uplink_bits_measured"] \
        == out.split_stats.uplink_bits_measured
    assert m["split.tabq_bits.count"] > 0
    assert 1 <= m["split.tabq_bits.min"] <= m["split.tabq_bits.max"] <= 16
    assert m["split.edge_s.count"] >= 1 and m["split.cloud_s.count"] >= 1


# ------------------------------------------ host phases and work counters

HOST_PHASES = {"admit", "prefill.build", "prefill.dispatch", "prefill.wait",
               "decode.build", "decode.dispatch", "decode.wait", "emit",
               "evict"}


def _chunked_run(cfg, params, tracer, tick_mode="chunked", wrap=None):
    """Ragged prompts longer than the 8-token chunk, so ticks mix fresh
    chunks, continuation chunks and decode. ``wrap(sched)`` may swap the
    scheduler's jitted calls for spies before anything is submitted.
    Returns the drained scheduler and {rid: prompt length}."""
    rng = np.random.default_rng(21)
    sched = Scheduler(cfg, params, OPTS_Q, num_pages=48, page_size=4,
                      max_slots=3, prefill_chunk=8, tick_mode=tick_mode,
                      telemetry=tracer)
    if wrap is not None:
        wrap(sched)
    prompts = {}
    for n, new in ((5, 6), (19, 4), (11, 7), (3, 5), (14, 3)):
        p = rng.integers(0, cfg.vocab_size, (n,))
        prompts[sched.submit(p, new)] = n
    sched.run()
    return sched, prompts


def test_host_phases_tile_chunked_ticks(tiny_model):
    """The host phases of a chunked run tile each tick: the first starts
    at the tick's start, each next one where the last ended, and together
    they cover >= 95% of its wall time. They carry their tick, and the
    tick's wait_s is the sum of its ``*.wait`` phases."""
    cfg, params = tiny_model
    tracer = Tracer()
    _chunked_run(cfg, params, tracer)
    phases = [sp for sp in tracer.spans if sp.track == "host"]
    assert {sp.name for sp in phases} == HOST_PHASES
    by_tick: dict = {}
    for sp in phases:
        by_tick.setdefault(sp.attrs["tick"], []).append(sp)
    assert len(tracer.ticks) > 5
    for rec in tracer.ticks:
        mine = sorted(by_tick[rec.tick], key=lambda sp: sp.start)
        assert all(a.end == b.start for a, b in zip(mine, mine[1:]))
        assert mine[0].start == rec.start
        assert mine[-1].end <= rec.start + rec.wall_s
        covered = sum(sp.duration for sp in mine)
        assert covered >= 0.95 * rec.wall_s, (rec.tick, covered, rec.wall_s)
        assert rec.wait_s == pytest.approx(sum(
            sp.duration for sp in mine if sp.name.endswith(".wait")))


@pytest.mark.parametrize("tick_mode", ["chunked", "packed"])
def test_kv_tokens_read_matches_pool_lengths_and_work_rebuild(tiny_model,
                                                              tick_mode):
    """kv_tokens_read is counted where the calls are dispatched: on every
    chunked decode tick it equals the decoding slots' pool lengths (read
    here off the decode call's own position operand), and on every tick
    it equals what the benchmark's ``bench/work.py`` rebuilds from the
    spans — decode contexts plus each continuation chunk's pool history;
    a packed decode row attends its own new key from the buffer."""
    from bench import work

    cfg, params = tiny_model
    tracer = Tracer()
    decode_kv = {}  # tick -> pool tokens the decode call's rows attend

    def observe(sched):
        real = sched._decode

        def spy(*a, pos, **k):
            p = np.asarray(pos)
            decode_kv[sched._tick] = int((p[p >= 0] + 1).sum())
            return real(*a, pos=pos, **k)

        sched._decode = spy

    _, prompts = _chunked_run(cfg, params, tracer, tick_mode=tick_mode,
                              wrap=observe)
    chunks = work.prefill_chunks(tracer.spans)
    ranges = work.decode_ranges(tracer.spans, prompts)
    assert any(hist for c in chunks.values() for _, hist, _, _ in c)
    own = 1 if tick_mode == "packed" else 0
    for rec in tracer.ticks:
        if tick_mode == "chunked" and rec.tick not in chunks:
            assert rec.kv_tokens_read == decode_kv[rec.tick], rec.tick
        ctx = work.contexts_at(ranges, rec.tick)
        want = sum(ctx) - own * len(ctx) + sum(
            hist for _, hist, _, _ in chunks.get(rec.tick, ()))
        assert rec.kv_tokens_read == want, rec.tick


@pytest.mark.parametrize("tick_mode", ["chunked", "packed", "wave"])
def test_step_calls_count_the_dispatches(tiny_model, tick_mode):
    """TickRecord.step_calls counts every jitted model call a tick
    dispatches: each prefill chunk or wave, decode, packed call."""
    cfg, params = tiny_model
    tracer, calls = Tracer(), []

    def count(sched):
        for name in ("_prefill", "_prefill_shared", "_decode", "_packed"):
            fn = getattr(sched, name)
            setattr(sched, name, lambda *a, _fn=fn, **k:
                    calls.append(1) or _fn(*a, **k))

    _chunked_run(cfg, params, tracer, tick_mode=tick_mode, wrap=count)
    assert sum(r.step_calls for r in tracer.ticks) == len(calls) > 0
    if tick_mode != "packed":  # a chunk and a decode share some tick
        assert max(r.step_calls for r in tracer.ticks) >= 2


# ------------------------------------------------ HTTP/SSE front end spans


def _serve_http(server, prompts, max_tokens):
    """Stream each prompt through ServingHTTPServer -> AsyncLLMServer ->
    ``server`` concurrently; returns each stream's parsed SSE payloads."""

    async def one(host, port, prompt):
        reader, writer = await asyncio.open_connection(host, port)
        body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                           "stream": True}).encode()
        writer.write(f"POST /v1/completions HTTP/1.1\r\nContent-Length: "
                     f"{len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        raw = await reader.read()  # Connection: close — EOF-terminated
        writer.close()
        return SSEParser().feed(raw.split(b"\r\n\r\n", 1)[1])

    async def go():
        http = ServingHTTPServer(AsyncLLMServer(server))
        await http.start()
        try:
            return await asyncio.gather(
                *[one(http.host, http.port, p) for p in prompts])
        finally:
            await http.stop()

    return asyncio.run(asyncio.wait_for(go(), 120.0))


def _frames(msgs):
    return [m for m in msgs if m != "[DONE]"]


def test_http_records_one_span_per_request_and_frame(tiny_model):
    """A traced server records one ``http.request`` span per completion
    request and one ``sse.write`` span per streamed frame, on the
    request's own track; a token frame's span starts when its token
    reached the host — the end of the wait phase that pulled it."""
    cfg, params = tiny_model
    srv = LLMServer(cfg, params, OPTS_Q, backend="paged", num_pages=24,
                    page_size=4, max_slots=2, telemetry=True)
    outs = _serve_http(srv, [[1, 2, 3, 4, 5], [6, 7, 8], [9, 10]],
                       max_tokens=4)
    spans = list(srv.tracer.spans)
    reqs = [sp for sp in spans if sp.name == "http.request"]
    writes = [sp for sp in spans if sp.name == "sse.write"]
    assert len(reqs) == 3
    rids = {m["rid"] for msgs in outs for m in _frames(msgs)}
    assert {sp.rid for sp in reqs} == rids
    for sp in reqs:
        assert sp.track == f"http:{sp.rid}" and "tick" not in sp.attrs
        assert sp.end >= sp.start
    for msgs in outs:
        rid = _frames(msgs)[0]["rid"]
        mine = [sp for sp in writes if sp.rid == rid]
        assert len(mine) == len(_frames(msgs)) == 5  # 4 tokens + finish
        assert {sp.track for sp in mine} == {f"http:{rid}"}
        assert all(sp.end >= sp.start for sp in mine)
    wait_ends = {sp.end for sp in spans if sp.name.endswith(".wait")}
    tokens = [sp for sp in writes if sp.attrs["index"] < 4]
    assert all(sp.start in wait_ends for sp in tokens)
    # the engine thread's work between ticks is phased too
    names = {sp.name for sp in spans if sp.track == "host"}
    assert {"engine.collect", "engine.fanout", "engine.commands"} <= names


# ------------------------------------------------------ bounded retention


def test_tracer_keeps_the_newest_records_and_counts_drops(monkeypatch):
    """spans, events and ticks each keep the newest ``MAX_RECORDS``; what
    falls off is counted under ``tracer.dropped``."""
    from repro.serving import telemetry

    monkeypatch.setattr(telemetry, "MAX_RECORDS", 4)
    tracer = Tracer()
    for i in range(7):
        tracer.add_span("s", float(i), float(i) + 0.5, track="t", n=i)
        tracer.event("e", n=i)
        tracer.tick_begin(i, "chunked")
        tracer.tick_end()
    assert [sp.attrs["n"] for sp in tracer.spans] == [3, 4, 5, 6]
    assert [e[4]["n"] for e in tracer.events] == [3, 4, 5, 6]
    assert [r.tick for r in tracer.ticks] == [3, 4, 5, 6]
    assert tracer.metrics_dict()["tracer.dropped"] == 9


def test_export_while_another_thread_appends():
    """A live server exports while its tick and event-loop threads keep
    recording: the export reads a snapshot and never sees a list change
    under it."""
    import threading

    tracer = Tracer()
    for i in range(20_000):
        tracer.add_span("s", float(i), float(i) + 0.5, track="t")
    stop = threading.Event()

    def append():
        i = 0
        while not stop.is_set():
            tracer.add_span("sse.write", 0.0, 1.0, track="req0", tick=None)
            tracer.event("e", n=i)
            tracer.tick_begin(i, "chunked")
            tracer.tick_end()
            i += 1

    writer = threading.Thread(target=append)
    writer.start()
    try:
        for _ in range(5):
            trace = tracer.export_chrome_trace()
            assert len(trace["traceEvents"]) > 20_000
    finally:
        stop.set()
        writer.join()


# ------------------------------------------------------- kv pool gauges


def test_pool_swap_bytes_accounting(tiny_model):
    """pool.swap_bytes tracks bytes parked on the host: export raises it,
    restore and discard both return it to zero."""
    cfg, params = tiny_model
    from repro.serving.kv_pool import PagedKVPool
    pool = PagedKVPool(cfg, num_pages=8, page_size=4, max_requests=2)
    assert pool.gauges()["swap_bytes"] == 0
    slot = pool.admit(6)
    pool.commit_prefill(slot, 6)
    snap = pool.export_slot(slot)
    nbytes = PagedKVPool.snapshot_bytes(snap)
    assert nbytes > 0 and pool.gauges()["swap_bytes"] == nbytes
    pool.free(slot)
    slot2 = pool.restore_slot(snap)
    assert pool.gauges()["swap_bytes"] == 0
    snap2 = pool.export_slot(slot2)
    assert pool.gauges()["swap_bytes"] == PagedKVPool.snapshot_bytes(snap2)
    pool.discard_snapshot(snap2)
    assert pool.gauges()["swap_bytes"] == 0
