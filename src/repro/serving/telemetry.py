"""Unified serving telemetry: request-lifecycle spans, per-tick timeline
records, and a counter/gauge/histogram registry with streaming percentiles.

The paper's unified optimizer (§2) picks split points, quantization
settings, and sequence lengths against *measured* memory and latency
constraints — this module is the measurement substrate. One
:class:`Tracer` instance is threaded (``telemetry=``, default ``None``)
through all three serving front ends:

  * ``serving.scheduler.Scheduler`` — every tick (any ``tick_mode``)
    emits a :class:`TickRecord` (wall time, mode, live/pad token counts,
    compiled-shape cache hits vs. new compiles, pool page occupancy,
    queue depth) and each request's lifecycle lands as spans:
    ``queued → prefill chunk(s) → first_token → decode →
    preempt/swap_out/swap_resume → finish``, each carrying its tick id
    and reason. Inside each tick the host's stretches are
    :meth:`Tracer.phase` spans on a ``host`` track (``admit``,
    ``prefill.build`` / ``.dispatch`` / ``.wait``, ``decode.build`` /
    ``.dispatch`` / ``.wait``, ``emit``, ``evict``), which tile the tick;
    the async engine adds ``engine.collect`` / ``engine.fanout`` /
    ``engine.commands`` between ticks;
  * ``serving.http.ServingHTTPServer`` — one ``http.request`` span per
    completion request and one ``sse.write`` span per streamed frame,
    from the token reaching the host to the socket drain;
  * ``serving.engine.Engine`` — one ``fused_generate`` span per jitted
    prefill+scan call with batch/token counters;
  * ``serving.split_engine.SplitEngine`` — per-segment ``edge`` /
    ``cloud`` spans (prefill and every decode step), per-step uplink-bit
    events, and TAB-Q bit-width histograms, unifying the existing
    ``SplitStats`` uplink accounting.

Everything is zero-dependency (stdlib only) and strictly pay-for-what-
you-use: with ``telemetry=None`` no Tracer method is ever called (the
disabled path is guarded at every instrumentation site — enforced by
``tests/test_telemetry.py``'s no-op test), and an enabled Tracer never
touches device values, so greedy outputs are bit-identical with
telemetry on or off.

Exporters:

  * :meth:`Tracer.export_chrome_trace` — Chrome trace-event JSON
    (load in Perfetto / ``chrome://tracing``): one track per scheduler
    slot plus a ``ticks`` track, a ``queue`` track, and per-engine
    tracks, with the flat metrics dict embedded under ``repro_metrics``;
  * :meth:`Tracer.metrics_dict` — the flat ``{name: value}`` metrics
    dict consumed by ``LLMServer.metrics()`` and benchmark artifacts
    (histograms expand to ``name.p50`` / ``name.p95`` / ``name.p99`` /
    ``name.mean`` / ... keys);
  * ``tools/trace_report.py`` — text summary (per-phase time breakdown,
    preemption/swap counts, compile events, SLO table) of an exported
    trace, used by CI to validate smoke traces.

Clock: ``time.perf_counter`` (monotonic) by default — the same clock
``serving.api`` stamps ``RequestMetrics`` with — injectable for tests.
While a ``jax.profiler`` trace is active, every host phase is also a
``jax.profiler.TraceAnnotation``, so it sits beside the device's ops in
the profile on the profiler's own clock (jax is looked up only if the
process already imported it; this module stays stdlib-only).

Retention is bounded: ``spans``, ``events`` and ``ticks`` keep the newest
``MAX_RECORDS`` entries each, and the ``tracer.dropped`` counter says how
many older ones a long-lived server let go.

Percentiles are streaming via a DDSketch-style log-bucketed histogram
(:class:`Histogram`): bounded relative error (default 1%), O(log range)
memory, no sample retention — fit for a long-lived server.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import math
import sys
import threading
import time


# --------------------------------------------------------------- histogram


class Histogram:
    """Streaming histogram with bounded RELATIVE quantile error.

    DDSketch-style log-spaced buckets: a value ``v > 0`` lands in bucket
    ``ceil(log_gamma(v))`` with ``gamma = (1 + rel_err) / (1 - rel_err)``,
    so any reported quantile is within ``rel_err`` (relatively) of the
    true one. Non-positive values collapse into one exact zero bucket.
    Count/sum/min/max are exact.
    """

    def __init__(self, rel_err: float = 0.01):
        if not 0.0 < rel_err < 1.0:
            raise ValueError(f"rel_err must be in (0, 1), got {rel_err}")
        self.rel_err = rel_err
        self._gamma = (1.0 + rel_err) / (1.0 - rel_err)
        self._lg = math.log(self._gamma)
        self.count = 0
        self.sum = 0.0
        self.min: float | None = None
        self.max: float | None = None
        self._zero = 0  # values <= 0 (exact bucket)
        self._buckets: dict = {}  # key -> count, value ~ gamma**key

    def record(self, value: float) -> None:
        v = float(value)
        self.count += 1
        self.sum += v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)
        if v <= 0.0:
            self._zero += 1
            return
        key = math.ceil(math.log(v) / self._lg)
        self._buckets[key] = self._buckets.get(key, 0) + 1

    @property
    def mean(self) -> float | None:
        return self.sum / self.count if self.count else None

    def percentile(self, q: float) -> float | None:
        """The q-quantile (``q`` in [0, 1]) within the sketch's relative
        error, clamped to the exact observed [min, max]."""
        if self.count == 0:
            return None
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"q must be in [0, 1], got {q}")
        if q == 0.0:
            return self.min  # exact extremes, not bucket midpoints
        if q == 1.0:
            return self.max
        rank = q * (self.count - 1)
        if rank < self._zero:
            # all values in the zero bucket are <= 0; min is exact
            return min(self.min, 0.0)
        cum = self._zero
        for key in sorted(self._buckets):
            cum += self._buckets[key]
            if cum > rank:
                # bucket midpoint: 2 * gamma^key / (gamma + 1) is the
                # value whose relative distance to both bucket edges
                # is exactly rel_err
                v = 2.0 * self._gamma ** key / (self._gamma + 1.0)
                return max(self.min, min(self.max, v))
        return self.max

    def summary(self) -> dict:
        """{count, sum, mean, min, max, p50, p95, p99} (empty → count 0)."""
        if self.count == 0:
            return {"count": 0}
        return {"count": self.count, "sum": self.sum, "mean": self.mean,
                "min": self.min, "max": self.max,
                "p50": self.percentile(0.50), "p95": self.percentile(0.95),
                "p99": self.percentile(0.99)}


class MetricsRegistry:
    """Named counters (monotonic), gauges (last value), and histograms
    (streaming percentiles). ``flat()`` renders everything as one
    ``{name: number}`` dict — histograms expand to dotted sub-keys."""

    def __init__(self):
        self.counters: dict = {}
        self.gauges: dict = {}
        self.histograms: dict = {}

    def count(self, name: str, n: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name: str, value: float) -> None:
        self.gauges[name] = value

    def observe(self, name: str, value: float) -> None:
        h = self.histograms.get(name)
        if h is None:
            h = self.histograms[name] = Histogram()
        h.record(value)

    def flat(self) -> dict:
        out: dict = {}
        out.update(self.counters)
        out.update(self.gauges)
        for name, h in self.histograms.items():
            for k, v in h.summary().items():
                out[f"{name}.{k}"] = v
        return out


# ------------------------------------------------------------------- spans


@dataclasses.dataclass
class Span:
    """One duration on one track. ``end`` is None while the span is open;
    ``attrs`` carries reasons / tick ids / token counts."""

    name: str
    track: str
    start: float
    end: float | None = None
    rid: int | None = None
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def duration(self) -> float | None:
        return None if self.end is None else self.end - self.start


@dataclasses.dataclass
class TickRecord:
    """One scheduler tick's timeline entry."""

    tick: int
    start: float
    wall_s: float
    mode: str  # "packed" | "chunked" | "wave"
    tokens: int  # live tokens the tick's jitted calls carried
    pad_tokens: int | None  # buffer pad rows (packed mode; None otherwise)
    new_compiles: int  # jitted call shapes first seen this tick
    shape_hits: int  # dispatches that reused an already-seen shape
    pages_in_use: int
    pages_shared: int
    swap_bytes: int
    queue_depth: int
    active_slots: int
    prefilling_slots: int
    wait_s: float = 0.0  # host seconds blocked on device results (*.wait)
    kv_tokens_read: int = 0  # live pool tokens the calls' attention read

    @property
    def step_calls(self) -> int:
        """Jitted model calls the tick dispatched (decode, each prefill
        chunk or wave, verify, packed): every one registers its shape."""
        return self.new_compiles + self.shape_hits


def _profiler_note(name: str, tick: int | None):
    """A ``jax.profiler.TraceAnnotation`` for ``name`` while a profiler
    trace is active, else None. Never imports jax: a process that has not
    imported it cannot be tracing."""
    jax = sys.modules.get("jax")
    if jax is None or not jax.profiler.TraceAnnotation.is_enabled():
        return None
    if tick is None:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.TraceAnnotation(name, tick=tick)


class _Phase:
    """One host phase in progress; see :meth:`Tracer.phase`."""

    __slots__ = ("tracer", "name", "start", "end", "_note")

    def __init__(self, tracer: "Tracer", name: str):
        self.tracer, self.name = tracer, name
        self.start = self.end = None
        self._note = None

    def __enter__(self) -> "_Phase":
        tel = self.tracer
        self._note = _profiler_note(self.name, tel.current_tick)
        if self._note is not None:
            self._note.__enter__()
        # inside a tick a phase starts where the tick's last phase (or
        # the tick) ended, so the phases tile it
        mark = tel._phase_mark
        self.start = tel.now() if mark is None else mark
        return self

    def __exit__(self, *exc) -> None:
        tel = self.tracer
        self.end = tel.now()
        if tel._phase_mark is not None:
            tel._phase_mark = self.end
        if self._note is not None:
            self._note.__exit__(*exc)
            self._note = None
        tel.add_span(self.name, self.start, self.end, track="host")
        if self.name.endswith(".wait"):
            tel._tick_wait += self.end - self.start


def maybe_phase(tel, name: str):
    """``tel.phase(name)``, or a context that does nothing when ``tel`` is
    None — the guard every phase site uses, so ``telemetry=None`` calls
    no Tracer method."""
    return _NO_PHASE if tel is None else tel.phase(name)


_NO_PHASE = contextlib.nullcontext()


# ------------------------------------------------------------------ tracer

_CURRENT = object()  # add_span's default: the tick in progress
# records kept per list: a traced benchmark run (about 100 spans per
# second over its set-up and window) stays far below it
MAX_RECORDS = 200_000


class Tracer:
    """Collects spans, instant events, tick records, and metrics from the
    serving layer. One instance per server/scheduler; share one across
    backends to get a single merged trace.

    Request-lifecycle helpers (``request_submitted`` ... ``request_
    finished``) encapsulate the span bookkeeping so the scheduler's
    instrumentation stays one guarded line per site; the generic
    ``span_begin`` / ``span_end`` / ``add_span`` / ``event`` API is
    available for everything else.

    ``MAX_RECORDS`` bounds each of ``spans``, ``events`` and ``ticks``:
    the oldest entry goes when a new one would pass it, counted under the
    ``tracer.dropped`` counter.
    """

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.t0 = clock()
        # closed AND open spans, begin order; appended from the tick thread
        # and (add_span only) the HTTP front end's event-loop thread
        self.spans = collections.deque(maxlen=MAX_RECORDS)
        # (name, t, track, rid, attrs) instants
        self.events = collections.deque(maxlen=MAX_RECORDS)
        self.ticks = collections.deque(maxlen=MAX_RECORDS)
        self._keep_lock = threading.Lock()
        self.metrics = MetricsRegistry()
        self.ttft_ticks: dict = {}  # rid -> ticks submit → first token
        self._open: dict = {}  # key -> Span
        self._submit_t: dict = {}  # rid -> submit time
        self._first_t: dict = {}  # rid -> first-token time
        self._tick_open: tuple | None = None  # (tick, t_start, mode)
        self._tick_compiles = 0
        self._tick_hits = 0
        self._tick_wait = 0.0
        self._tick_kv = 0
        self._phase_mark: float | None = None  # last phase boundary in a tick
        self.current_tick: int | None = None

    def now(self) -> float:
        return self.clock()

    def _keep(self, records: collections.deque, item) -> None:
        """Append to a bounded record list, counting what falls off."""
        with self._keep_lock:
            if len(records) == records.maxlen:
                self.metrics.count("tracer.dropped")
            records.append(item)

    # -------------------------------------------------------- generic API

    def span_begin(self, key, name: str, track: str, rid: int | None = None,
                   **attrs) -> Span:
        """Open a span under ``key`` (any hashable); re-opening a live key
        closes the old span first (never silently drops one)."""
        if key in self._open:
            self.span_end(key)
        if self.current_tick is not None:
            attrs.setdefault("tick", self.current_tick)
        sp = Span(name, track, self.now(), rid=rid, attrs=attrs)
        self._open[key] = sp
        self._keep(self.spans, sp)
        return sp

    def span_end(self, key, **attrs) -> Span | None:
        """Close the span opened under ``key`` (no-op for unknown keys —
        lifecycle paths may legitimately close a span twice, e.g. abort
        racing evict)."""
        sp = self._open.pop(key, None)
        if sp is None:
            return None
        sp.end = self.now()
        if self.current_tick is not None:
            attrs.setdefault("end_tick", self.current_tick)
        sp.attrs.update(attrs)
        return sp

    def add_span(self, name: str, start: float, end: float, track: str,
                 rid: int | None = None, tick=_CURRENT, **attrs) -> Span:
        """Record an already-timed duration (caller holds t0/t1). The span
        carries the tick in progress unless ``tick`` is given; a caller on
        another thread than the tick thread passes it (None: no tick)."""
        if tick is _CURRENT:
            tick = self.current_tick
        if tick is not None:
            attrs.setdefault("tick", tick)
        sp = Span(name, track, start, end, rid=rid, attrs=attrs)
        self._keep(self.spans, sp)
        return sp

    def phase(self, name: str) -> _Phase:
        """Context manager timing one host phase: a span named ``name`` on
        the ``host`` track carrying the current tick, mirrored as a
        ``jax.profiler.TraceAnnotation`` while a profiler trace is active.
        Inside a tick the phases tile it: each starts where the previous
        one (or the tick) ended, so host time between two phase sites,
        the OS running another thread included, counts to the phase that
        follows; only the stretch after the tick's last phase is left.
        Phases named ``*.wait`` (the host blocked on device results) sum
        into the tick's ``TickRecord.wait_s``; the phase's ``end`` is the
        time those results reached the host."""
        return _Phase(self, name)

    def event(self, name: str, track: str = "ticks", rid: int | None = None,
              t: float | None = None, **attrs) -> None:
        """Record an instant event (Chrome ``ph: "i"``)."""
        if self.current_tick is not None:
            attrs.setdefault("tick", self.current_tick)
        self._keep(self.events, (name, self.now() if t is None else t,
                                 track, rid, attrs))

    # -------------------------------------------------- request lifecycle

    def request_submitted(self, rid: int) -> None:
        self._submit_t[rid] = self.now()
        self.metrics.count("requests.submitted")
        self.span_begin(("queued", rid), "queued", "queue", rid=rid)

    def request_admitted(self, rid: int, slot: int,
                         resumed: bool = False) -> None:
        self.span_end(("queued", rid), slot=slot, resumed=resumed)
        self.metrics.count("requests.admitted")
        if resumed:
            self.metrics.count("requests.resumed")

    def request_requeued(self, rid: int, reason: str) -> None:
        """Back to the queue (preemption): a fresh ``queued`` span opens
        with the reason attached."""
        self.span_begin(("queued", rid), "queued", "queue", rid=rid,
                        requeued=True, reason=reason)

    def first_token(self, rid: int, track: str,
                    ttft_ticks: int | None = None) -> None:
        t = self.now()
        self._first_t.setdefault(rid, t)
        if ttft_ticks is not None:
            self.ttft_ticks.setdefault(rid, int(ttft_ticks))
        self.event("first_token", track=track, rid=rid, t=t)
        sub = self._submit_t.get(rid)
        if sub is not None:
            self.metrics.observe("ttft_s", t - sub)

    def decode_begin(self, rid: int, track: str) -> None:
        """Open the request's decode-residency span — idempotent, so the
        per-tick decode paths can call it unconditionally."""
        if ("decode", rid) not in self._open:
            self.span_begin(("decode", rid), "decode", track, rid=rid)

    def request_finished(self, rid: int, track: str, reason: str,
                         n_tokens: int) -> None:
        t = self.now()
        self.span_end(("queued", rid), outcome=reason)  # aborted-in-queue
        self.span_end(("decode", rid), outcome=reason)
        self.event("finish", track=track, rid=rid, t=t, reason=reason,
                   tokens=n_tokens)
        self.metrics.count("requests.finished")
        self.metrics.count(f"requests.finish_reason.{reason}")
        sub = self._submit_t.pop(rid, None)
        first = self._first_t.pop(rid, None)
        if sub is not None:
            self.metrics.observe("e2e_s", t - sub)
        if first is not None and n_tokens > 1:
            self.metrics.observe("tpot_s", (t - first) / (n_tokens - 1))

    # ---------------------------------------------------------- tick API

    def tick_begin(self, tick: int, mode: str) -> None:
        self._tick_open = (int(tick), self.now(), mode)
        self._phase_mark = self._tick_open[1]
        self.current_tick = int(tick)
        self._tick_compiles = 0
        self._tick_hits = 0
        self._tick_wait = 0.0
        self._tick_kv = 0

    def shape_dispatch(self, new: bool, kv_tokens: int = 0) -> None:
        """One jitted model call this tick; ``new`` = first time this call
        shape was seen (an XLA compile); ``kv_tokens`` = live pool tokens
        its attention reads, summed over its rows."""
        self._tick_kv += int(kv_tokens)
        if new:
            self._tick_compiles += 1
            self.metrics.count("compile.shapes")
            if self._tick_open is not None:
                self.event("compile", track="ticks",
                           tick=self._tick_open[0])
        else:
            self._tick_hits += 1
        self.metrics.count("compile.dispatches")

    def tick_end(self, *, tokens: int = 0, pad_tokens: int | None = None,
                 pages_in_use: int = 0, pages_shared: int = 0,
                 swap_bytes: int = 0, queue_depth: int = 0,
                 active_slots: int = 0, prefilling_slots: int = 0) -> None:
        if self._tick_open is None:
            return
        tick, t_start, mode = self._tick_open
        self._tick_open = None
        self._phase_mark = None
        self.current_tick = None
        wall = self.now() - t_start
        rec = TickRecord(tick, t_start, wall, mode, int(tokens),
                         None if pad_tokens is None else int(pad_tokens),
                         self._tick_compiles, self._tick_hits,
                         int(pages_in_use), int(pages_shared),
                         int(swap_bytes), int(queue_depth),
                         int(active_slots), int(prefilling_slots),
                         self._tick_wait, self._tick_kv)
        self._keep(self.ticks, rec)
        m = self.metrics
        m.observe("tick.wall_s", wall)
        m.count("tick.count")
        m.count("tick.tokens", rec.tokens)
        if rec.pad_tokens is not None:
            m.count("tick.pad_tokens", rec.pad_tokens)
        m.gauge("pool.pages_in_use", rec.pages_in_use)
        m.gauge("pool.pages_shared", rec.pages_shared)
        m.gauge("pool.swap_bytes", rec.swap_bytes)
        m.gauge("queue.depth", rec.queue_depth)

    # ----------------------------------------------------------- exporters

    def metrics_dict(self) -> dict:
        """The flat metrics dict (counters + gauges + histogram
        summaries) — ``LLMServer.metrics()`` and benchmark artifacts."""
        return self.metrics.flat()

    def _us(self, t: float) -> float:
        return (t - self.t0) * 1e6

    def export_chrome_trace(self, path: str | None = None) -> dict:
        """Chrome trace-event JSON (Perfetto-loadable). Tracks become
        threads of one process: tid 0 is the ``ticks`` track, tid 1 the
        ``queue`` track, ``slot<i>`` tracks follow in slot order, then
        any remaining tracks in first-seen order. Spans still open at
        export time are emitted closed at the export instant with
        ``"open": true``. The flat metrics dict rides along under the
        top-level ``repro_metrics`` key. Returns the trace dict;
        ``path`` additionally writes it as JSON."""
        order = {"ticks": 0, "queue": 1}

        def tid(track: str) -> int:
            if track not in order:
                if track.startswith("slot"):
                    try:  # keep slot tracks contiguous from tid 2
                        order[track] = 2 + int(track[4:])
                    except ValueError:
                        order[track] = 1000 + len(order)
                else:
                    order[track] = 1000 + len(order)
            return order[track]

        # copy under the append lock: the tick thread and the front end's
        # event-loop thread keep appending while a live server exports
        with self._keep_lock:
            spans, instants, ticks = (list(self.spans), list(self.events),
                                      list(self.ticks))
        now = self.now()
        events: list = []
        for sp in spans:
            end = now if sp.end is None else sp.end
            args = dict(sp.attrs)
            if sp.rid is not None:
                args["rid"] = sp.rid
            if sp.end is None:
                args["open"] = True
            events.append({
                "name": sp.name, "ph": "X", "cat": "span", "pid": 0,
                "tid": tid(sp.track), "ts": self._us(sp.start),
                "dur": max(0.0, self._us(end) - self._us(sp.start)),
                "args": args})
        for name, t, track, rid, attrs in instants:
            args = dict(attrs)
            if rid is not None:
                args["rid"] = rid
            events.append({"name": name, "ph": "i", "cat": "instant",
                           "pid": 0, "tid": tid(track),
                           "ts": self._us(t), "s": "t", "args": args})
        for rec in ticks:
            args = dataclasses.asdict(rec)
            del args["start"], args["wall_s"]
            args["step_calls"] = rec.step_calls
            events.append({
                "name": f"tick[{rec.mode}]", "ph": "X", "cat": "tick",
                "pid": 0, "tid": tid("ticks"), "ts": self._us(rec.start),
                "dur": rec.wall_s * 1e6, "args": args})
        meta = [{"name": "process_name", "ph": "M", "pid": 0,
                 "args": {"name": "repro.serving"}}]
        meta += [{"name": "thread_name", "ph": "M", "pid": 0, "tid": t,
                  "args": {"name": track}} for track, t in order.items()]
        meta += [{"name": "thread_sort_index", "ph": "M", "pid": 0,
                  "tid": t, "args": {"sort_index": t}}
                 for track, t in order.items()]
        trace = {"traceEvents": meta + events, "displayTimeUnit": "ms",
                 "repro_metrics": self.metrics_dict()}
        if path is not None:
            with open(path, "w") as f:
                json.dump(trace, f)
        return trace
