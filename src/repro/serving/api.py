"""One request-level serving API over pluggable backends.

The repo's three serving front ends — the fused static-batch ``Engine``,
the paged continuous-batching ``Scheduler``, and the split-computing
``SplitEngine`` — historically had three divergent call shapes. This
module gives them ONE request-level surface:

  * :class:`~repro.core.sampling.SamplingParams` — every per-request knob
    (max_tokens, temperature / top-k / top-p / seed, stop tokens,
    priority, prefix sharing, latency hint) in one frozen dataclass;
  * :class:`GenerationRequest` / :class:`RequestOutput` — a prompt going
    in; tokens, finish reason, and latency metrics coming out, with
    per-token :class:`TokenEvent` streaming in between;
  * :class:`ServingBackend` — the small protocol (``submit`` / ``step`` /
    ``abort`` / ``pending`` / ``outputs``) each front end adapts to:
    ``fused`` (wraps ``Engine``'s jitted scan), ``paged`` (wraps
    ``Scheduler`` — true per-tick streaming, on-device per-slot
    sampling), ``split`` (wraps ``SplitEngine`` — each
    :class:`RequestOutput` carries the call's ``SplitStats`` uplink /
    residency accounting);
  * :class:`LLMServer` — the facade: ``submit()`` requests, ``stream()``
    token events, ``run()`` to drain, ``abort()`` to cancel.

Every backend samples through the same ``core.sampling.sample_tokens``
(per-request PRNG lanes folded per generation index), so default
``SamplingParams()`` is greedy on all three bit-for-bit with the legacy
entry points, and a seeded non-greedy request draws the same tokens on
the fused and paged backends. Event streams observe one invariant
everywhere: per request, token indices arrive strictly in position order
(interleaving across requests is backend-dependent — the paged backend
interleaves per tick; fused and split replay after the batch computes).
Finish events carry ``token = -1``, ``index = len(generated)`` and the
finish reason (``"stop"`` | ``"length"`` | ``"abort"`` | ``"deadline"``).

Quickstart::

    from repro.serving import LLMServer, SamplingParams

    server = LLMServer(cfg, params, opts, backend="paged",
                       num_pages=64, max_slots=4)
    rid = server.submit(prompt, SamplingParams(max_tokens=32,
                                               temperature=0.8, seed=1))
    for ev in server.stream():          # or: outputs = server.run()
        print(ev.rid, ev.index, ev.token)
    out = server.outputs()[rid]         # RequestOutput
"""

from __future__ import annotations

import dataclasses
import time
from typing import Protocol, runtime_checkable

import numpy as np

from repro.core.sampling import SamplingParams, truncate_at_stop
from repro.models.transformer import RuntimeOpts
from repro.serving.engine import Engine
from repro.serving.page_transport import DisaggregatedScheduler
from repro.serving.scheduler import Scheduler
from repro.serving.split_engine import SplitEngine
from repro.serving.telemetry import maybe_phase

FINISH_REASONS = ("stop", "length", "abort", "deadline")


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One streamed token (or the finish marker, ``token = -1``)."""

    rid: int
    index: int  # 0-based generation index; strictly increasing per rid
    token: int  # -1 on the finish marker
    finished: bool = False
    finish_reason: str | None = None  # set only on the finish marker
    # the emitted token's log-probability under the RAW model distribution
    # (before temperature / top-k / top-p — core.sampling.token_logprobs);
    # None on finish markers and abort events
    logprob: float | None = None
    # tracer-clock time the event reached the host (the end of the wait
    # that pulled the token; for a finish marker, when it was collected);
    # set only by the paged backend, and only when it is traced
    host_t: float | None = None


@dataclasses.dataclass
class GenerationRequest:
    """A prompt plus its :class:`SamplingParams`; ``rid`` is assigned by
    the backend at submit."""

    prompt: np.ndarray
    sampling: SamplingParams = SamplingParams()
    rid: int = -1


@dataclasses.dataclass
class RequestMetrics:
    """Wall-clock latency accounting per request (CPU wall times are
    call-path numbers off-TPU; ``ttft_ticks`` is exact on any backend).
    All times are ``time.perf_counter()`` stamps — monotonic, so they
    never jump under wall-clock adjustments; ``submit_s`` is only
    meaningful relative to other stamps from the same process."""

    submit_s: float = 0.0  # perf_counter stamp at submit
    ttft_s: float | None = None  # submit → first streamed token
    latency_s: float | None = None  # submit → finish
    # submit → finish (same stamp pair as latency_s, kept as its own field
    # so SLO surfaces — LLMServer.metrics(), /v1/metrics — read one
    # canonical end-to-end name). Populated on the thread that drives the
    # backend (the async front end's tick thread), so it is correct with
    # telemetry=None
    e2e_s: float | None = None
    # scheduling quanta from submit to first token: scheduler ticks on the
    # paged backend, server steps on the fused/split replay backends
    ttft_ticks: int | None = None


@dataclasses.dataclass
class RequestOutput:
    """The per-request result: generated tokens (stop token included,
    truncated at it), finish reason, metrics, and — on the split backend —
    the ``SplitStats`` uplink/residency accounting of the serving call."""

    rid: int
    prompt: np.ndarray
    tokens: np.ndarray  # generated tokens only
    finished: bool = False
    finish_reason: str | None = None
    metrics: RequestMetrics = dataclasses.field(default_factory=RequestMetrics)
    split_stats: object | None = None  # serving.split_engine.SplitStats

    @property
    def full_tokens(self) -> np.ndarray:
        """Prompt + generation — the legacy engines' return shape."""
        return np.concatenate([np.asarray(self.prompt, np.int32),
                               np.asarray(self.tokens, np.int32)])


@runtime_checkable
class ServingBackend(Protocol):
    """What :class:`LLMServer` drives. ``step()`` advances the backend by
    one scheduling quantum and returns the token events it produced;
    ``pending`` is True while any submitted request has undelivered
    events; ``outputs()`` maps rid → :class:`RequestOutput` for every
    finished (or aborted) request."""

    def submit(self, req: GenerationRequest) -> int: ...

    def step(self) -> list: ...

    def abort(self, rid: int) -> bool: ...

    def release(self, rid: int) -> bool: ...

    @property
    def pending(self) -> bool: ...

    @property
    def queue_depth(self) -> int: ...

    def outputs(self) -> dict: ...


def _apply_stop(gen: np.ndarray, sp: SamplingParams) -> tuple:
    """The shared stop-set truncation (``core.sampling.truncate_at_stop``)
    with an ndarray result — the replay backends' output shaping."""
    toks, reason = truncate_at_stop(gen, sp)
    return np.asarray(toks, np.int32), reason


class _RequestBook:
    """Per-request bookkeeping every backend shares: tracked requests,
    wall-clock metrics, finished outputs, deferred finish events, and the
    ``release`` memory valve."""

    def __init__(self):
        self._reqs: dict = {}
        self._metrics: dict = {}
        self._outputs: dict = {}
        self._pending_events: list = []  # finish markers for the next step

    def _track(self, req: GenerationRequest, rid: int) -> int:
        req.rid = rid
        self._reqs[rid] = req
        # perf_counter: monotonic — ttft_s/latency_s can never go negative
        # or jump when the wall clock is adjusted mid-serve
        self._metrics[rid] = RequestMetrics(submit_s=time.perf_counter())
        return rid

    def outputs(self) -> dict:
        return dict(self._outputs)

    def _release_dicts(self) -> tuple:
        """Extra per-rid dicts a backend also retains (popped by release)."""
        return ()

    def release(self, rid: int) -> bool:
        """Drop a FINISHED request's retained state (output, metrics,
        prompt). A long-lived server that never releases grows linearly
        with total requests served. Returns False for unknown/unfinished
        rids (live requests must finish or be aborted first)."""
        if rid not in self._outputs:
            return False
        for d in (self._outputs, self._metrics,
                  self._reqs) + self._release_dicts():
            d.pop(rid, None)
        return True


class _ReplayBackend(_RequestBook):
    """Shared machinery for backends that compute whole requests and then
    REPLAY them as streams (fused, split): queueing, abort, and the
    round-robin one-token-per-request-per-step event emitter (which keeps
    the per-request position-order invariant and interleaves across
    requests)."""

    def __init__(self, telemetry=None):
        super().__init__()
        self.telemetry = telemetry
        self._next_rid = 0
        self._queued: list = []
        # rid → [tokens np, cursor, finish_reason, logprobs np | None] for
        # computed-but-not-fully-streamed requests
        self._streams: dict = {}
        self._split_stats: dict = {}
        # replay-backend "ticks" are server steps: rid → step at submit,
        # so ttft_ticks is populated on fused/split too (paged parity)
        self._steps = 0
        self._submit_step: dict = {}

    def submit(self, req: GenerationRequest) -> int:
        rid = self._track(req, self._next_rid)
        self._next_rid += 1
        self._queued.append(req)
        self._submit_step[rid] = self._steps
        if self.telemetry is not None:
            self.telemetry.request_submitted(rid)
        return rid

    @property
    def pending(self) -> bool:
        return bool(self._queued or self._streams or self._pending_events)

    @property
    def queue_depth(self) -> int:
        """Requests accepted but not yet computing — the replay backends'
        admission-backpressure signal (``AsyncLLMServer`` bounds it)."""
        return len(self._queued)

    def _release_dicts(self) -> tuple:
        return (self._split_stats, self._submit_step)

    def abort(self, rid: int) -> bool:
        """Cancel: a queued request never computes; a streaming one is cut
        at its cursor (tokens already streamed are kept). The finish
        marker (reason "abort") arrives on the next ``step()``."""
        for req in self._queued:
            if req.rid == rid:
                self._queued.remove(req)
                self._finalize(rid, np.zeros((0,), np.int32), "abort")
                self._pending_events.append(TokenEvent(
                    rid, 0, -1, finished=True, finish_reason="abort"))
                return True
        if rid in self._streams:
            toks, cur, _, _ = self._streams.pop(rid)
            self._finalize(rid, toks[:cur], "abort")
            self._pending_events.append(TokenEvent(
                rid, cur, -1, finished=True, finish_reason="abort"))
            return True
        return False

    def _finalize(self, rid: int, gen, reason: str) -> None:
        m = self._metrics[rid]
        m.latency_s = m.e2e_s = time.perf_counter() - m.submit_s
        self._outputs[rid] = RequestOutput(
            rid, self._reqs[rid].prompt, np.asarray(gen, np.int32),
            finished=True, finish_reason=reason, metrics=m,
            split_stats=self._split_stats.get(rid))
        if self.telemetry is not None:
            self.telemetry.request_finished(rid, "requests", reason,
                                            len(self._outputs[rid].tokens))

    def _emit_round(self) -> list:
        events, self._pending_events = self._pending_events, []
        self._steps += 1
        now = time.perf_counter()
        for rid in list(self._streams):
            toks, cur, reason, lps = self._streams[rid]
            if cur < len(toks):
                m = self._metrics[rid]
                if m.ttft_s is None:
                    m.ttft_s = now - m.submit_s
                    m.ttft_ticks = self._steps - self._submit_step[rid]
                    if self.telemetry is not None:
                        self.telemetry.first_token(
                            rid, "requests", ttft_ticks=m.ttft_ticks)
                lp = None if lps is None else float(lps[cur])
                events.append(TokenEvent(rid, cur, int(toks[cur]),
                                         logprob=lp))
                cur += 1
                self._streams[rid][1] = cur
            if cur >= len(toks):
                del self._streams[rid]
                self._finalize(rid, toks, reason)
                events.append(TokenEvent(rid, cur, -1, finished=True,
                                         finish_reason=reason))
        return events


class FusedBackend(_ReplayBackend):
    """``Engine``'s jitted prefill + ``lax.scan`` loop behind the request
    API. Submitted requests accumulate until the next ``step()``, which
    computes ALL of them — grouped by prompt length (the fused scan wants
    rectangular batches), each group one ``Engine.generate_requests``
    call with per-row sampling operands, scanned to the group's largest
    ``max_tokens`` — then replays the tokens as interleaved events.
    Per-request ``max_tokens`` and stop sets truncate the replay."""

    def __init__(self, cfg, params, opts: RuntimeOpts = RuntimeOpts(),
                 *, cache_len: int = 4096, telemetry=None):
        super().__init__(telemetry=telemetry)
        self.engine = Engine(cfg, params, opts, cache_len=cache_len,
                             telemetry=telemetry)

    def step(self) -> list:
        if self._queued:
            self._compute()
        return self._emit_round()

    def _compute(self) -> None:
        groups: dict = {}
        for req in self._queued:
            groups.setdefault(req.prompt.shape, []).append(req)
        self._queued = []
        for group in groups.values():
            prompts = np.stack([r.prompt for r in group])
            res = self.engine.generate_requests(
                prompts, [r.sampling for r in group])
            for i, (row, req) in enumerate(zip(res.tokens, group)):
                plen = req.prompt.shape[0]
                gen = row[plen: plen + req.sampling.max_tokens]
                gen, reason = _apply_stop(gen, req.sampling)
                lps = (None if res.logprobs is None
                       else res.logprobs[i, : gen.shape[0]])
                self._streams[req.rid] = [gen, 0, reason, lps]


class SplitBackend(_ReplayBackend):
    """The paper's split system behind the request API: each request runs
    ``SplitEngine.generate`` (edge front → TS+TAB-Q uplink → cloud back,
    Algorithm 2 deadline ladder) with its own sampling params, one request
    per ``step()``. The resulting :class:`RequestOutput` carries the
    call's ``SplitStats`` (measured/Eq. 3 uplink bits, paged-cloud
    residency, early exits). A generation the deadline ladder truncated
    finishes with reason ``"deadline"``.

    ``SamplingParams(speculate_k=)`` turns the request's serving call
    speculative: the edge drafts that many tokens per round and the cloud
    verifies them in ONE uplink round trip
    (``SplitEngine.generate(speculate_k=)``) — the carried ``SplitStats``
    then report ``uplink_round_trips`` < tokens generated and the round's
    ``acceptance_rate``."""

    def __init__(self, cfg, params, opts: RuntimeOpts = RuntimeOpts(),
                 *, opsc=None, compress: bool = True, telemetry=None,
                 **split_kwargs):
        if opsc is None:
            raise ValueError("the split backend needs opsc=OPSCConfig(...)")
        super().__init__(telemetry=telemetry)
        self.compress = compress
        self.engine = SplitEngine(cfg, params, opsc, opts=opts,
                                  telemetry=telemetry, **split_kwargs)

    def step(self) -> list:
        if self._queued and not self._streams:
            req = self._queued.pop(0)
            sp = req.sampling
            toks, stats, lps = self.engine.generate(
                req.prompt[None], sp.max_tokens, compress=self.compress,
                sampling=sp, with_logprobs=True,
                speculate_k=sp.speculate_k)
            gen = toks[0, req.prompt.shape[0]:]
            gen, reason = _apply_stop(gen, sp)
            if reason == "length" and gen.shape[0] < sp.max_tokens:
                reason = "deadline"  # Algorithm 2 cut the generation short
            self._split_stats[req.rid] = stats
            self._streams[req.rid] = [gen, 0, reason,
                                      lps[0, : gen.shape[0]]]
        return self._emit_round()


class PagedBackend(_RequestBook):
    """The continuous-batching ``Scheduler`` behind the request API — the
    one backend with TRUE streaming: each ``step()`` is one scheduler tick
    (admit → chunked prefill → one-shape ragged decode with on-device
    per-slot sampling → evict), and the tick's sampled tokens come back as
    events immediately. ``abort()`` cancels in place (pages reclaimed this
    call); the drained scheduler releases its pinned prefixes exactly like
    ``Scheduler.run``; ``release()`` also drops the scheduler's retained
    results/finish_reasons.

    Construct with ``speculate_k=`` (a ``Scheduler`` keyword) to make
    decode ticks speculative — each tick then verifies a prompt-lookup
    draft burst per slot in one call, and a request's own
    ``SamplingParams(speculate_k=)`` may lower its burst below the
    scheduler-wide width. The fused backend has no incremental tick to
    amortize, so it ignores ``speculate_k`` (documented on
    ``SamplingParams``).

    ``deployment`` picks the serving topology — greedy token streams are
    bit-identical across all three (the sharded/disaggregated acceptance
    bar, pinned by ``tests/test_sharded_serving.py``):

    * ``"fused"`` (default) — one scheduler, single-device step fns.
    * ``"sharded"`` — one scheduler whose ticks are ``shard_map``-lowered
      over a device mesh (pool pages sharded over the ``"kv"`` axis,
      attention heads over ``"model"``). Pass ``mesh=`` to pin a
      ``jax.sharding.Mesh``; omitted, ``launch.mesh.make_serving_mesh``
      builds one over every visible device.
    * ``"disaggregated"`` — a prefill replica and a decode replica with
      separate pools, joined by the page-stream transport
      (:class:`~repro.serving.page_transport.DisaggregatedScheduler`);
      ``prefill_kwargs=``/``decode_kwargs=`` tune the sides."""

    def __init__(self, cfg, params, opts: RuntimeOpts = RuntimeOpts(),
                 *, telemetry=None, deployment: str = "fused",
                 **scheduler_kwargs):
        super().__init__()
        self.telemetry = telemetry
        self.deployment = deployment
        if deployment == "fused":
            if "mesh" in scheduler_kwargs and \
                    scheduler_kwargs["mesh"] is not None:
                raise ValueError(
                    "mesh= requires deployment='sharded' (a fused "
                    "deployment never lowers through shard_map)")
            scheduler_kwargs.pop("mesh", None)
            self.scheduler = Scheduler(cfg, params, opts,
                                       telemetry=telemetry,
                                       **scheduler_kwargs)
        elif deployment == "sharded":
            mesh = scheduler_kwargs.pop("mesh", None)
            if mesh is None:
                from repro.launch.mesh import make_serving_mesh
                mesh = make_serving_mesh(cfg.pattern[0].mixer.num_kv_heads)
            self.scheduler = Scheduler(cfg, params, opts,
                                       telemetry=telemetry, mesh=mesh,
                                       **scheduler_kwargs)
        elif deployment == "disaggregated":
            self.scheduler = DisaggregatedScheduler(
                cfg, params, opts, telemetry=telemetry, **scheduler_kwargs)
        else:
            raise ValueError(
                f"unknown deployment {deployment!r}: expected 'fused', "
                f"'sharded' or 'disaggregated'")

    def submit(self, req: GenerationRequest) -> int:
        return self._track(req, self.scheduler.submit(
            req.prompt, sampling=req.sampling))

    @property
    def pending(self) -> bool:
        return self.scheduler.pending or bool(self._pending_events)

    @property
    def queue_depth(self) -> int:
        """Requests waiting UNADMITTED in the scheduler queue (slots and
        pages all busy) — the paged admission-backpressure signal. The
        disaggregated facade sums both replicas' queues."""
        sched = self.scheduler
        if hasattr(sched, "queue"):
            return len(sched.queue)
        return len(sched.prefill.queue) + len(sched.decode.queue)

    def _release_dicts(self) -> tuple:
        rd = getattr(self.scheduler, "_release_dicts", None)
        if rd is not None:  # disaggregated facade: merged-copy properties
            return rd()
        return (self.scheduler.results, self.scheduler.finish_reasons)

    def step(self) -> list:
        events, sched = self._pending_events, self.scheduler
        self._pending_events = []
        if sched.pending:
            sched.step()
        with maybe_phase(self.telemetry, "engine.collect"):
            events += self._collect(time.perf_counter())
            if not sched.pending:  # drained — same reclamation as run()
                sched.release_prefixes()
        return events

    def abort(self, rid: int) -> bool:
        ok = self.scheduler.abort(rid)
        if ok:  # surface the partial result now, its events next step
            self._pending_events += self._collect(time.perf_counter())
        return ok

    def _collect(self, now: float) -> list:
        sched, events = self.scheduler, []
        tel = self.telemetry
        for rid, idx, tok, lp, host_t in sched.drain_events():
            m = self._metrics[rid]
            if m.ttft_s is None:
                m.ttft_s = now - m.submit_s
            events.append(TokenEvent(rid, idx, tok, logprob=lp,
                                     host_t=host_t))
        for rid in sched.drain_finished():
            req = self._reqs[rid]
            reason = sched.finish_reasons.get(rid, "length")
            gen = np.asarray(sched.results[rid][req.prompt.shape[0]:],
                             np.int32)
            m = self._metrics[rid]
            m.latency_s = m.e2e_s = now - m.submit_s
            # tracer-sourced when tracing (the first-token span records the
            # tick), scheduler stats otherwise — identical values, but the
            # tracer copy survives a stats reset
            if sched.telemetry is not None:
                m.ttft_ticks = sched.telemetry.ttft_ticks.get(
                    rid, sched.stats.ttft_ticks.get(rid))
            else:
                m.ttft_ticks = sched.stats.ttft_ticks.get(rid)
            self._outputs[rid] = RequestOutput(
                rid, req.prompt, gen, finished=True, finish_reason=reason,
                metrics=m)
            events.append(TokenEvent(
                rid, gen.shape[0], -1, finished=True, finish_reason=reason,
                host_t=None if tel is None else tel.now()))
        return events


_BACKENDS = {"fused": FusedBackend, "paged": PagedBackend,
             "split": SplitBackend}


class LLMServer:
    """The facade: one request-level API over the fused / paged / split
    backends. ``backend`` is a name from ``{"fused", "paged", "split"}``
    (extra keyword arguments reach that backend's constructor — e.g.
    ``num_pages=``/``max_slots=``/``lazy_growth=`` for paged, ``opsc=``
    and channel/deadline knobs for split, ``cache_len=`` for fused) or an
    already-built :class:`ServingBackend`. The paged backend additionally
    takes ``deployment="fused"|"sharded"|"disaggregated"`` — same API,
    same greedy streams, different topology (see :class:`PagedBackend`).

    ``telemetry`` threads one :class:`~repro.serving.telemetry.Tracer`
    through the chosen backend (``True`` builds a fresh one, exposed as
    ``server.tracer``): request-lifecycle spans, per-tick timelines, and
    the :meth:`metrics` SLO summaries all record into it; export a
    Perfetto-loadable trace with ``server.tracer.export_chrome_trace``.
    The default ``None`` keeps every instrumented path a strict no-op."""

    def __init__(self, cfg=None, params=None,
                 opts: RuntimeOpts = RuntimeOpts(), *,
                 backend="paged", telemetry=None, **backend_kwargs):
        if telemetry is True:
            from repro.serving.telemetry import Tracer

            telemetry = Tracer()
        self.tracer = telemetry
        if isinstance(backend, str):
            if backend not in _BACKENDS:
                raise ValueError(f"backend must be one of "
                                 f"{sorted(_BACKENDS)}, got {backend!r}")
            backend = _BACKENDS[backend](cfg, params, opts,
                                         telemetry=telemetry,
                                         **backend_kwargs)
        elif telemetry is not None and getattr(
                backend, "telemetry", None) is None:
            raise ValueError(
                "pass telemetry= to the backend's constructor when handing "
                "LLMServer an already-built backend")
        self.backend: ServingBackend = backend
        if self.tracer is None:  # adopt a prebuilt backend's tracer
            self.tracer = getattr(backend, "telemetry", None)

    def submit(self, prompt,
               sampling: SamplingParams = SamplingParams()) -> int:
        """Enqueue ONE request — ``prompt`` is a 1-D token sequence;
        returns its rid. A batch is a sequence of submits (silently
        flattening a (B, S) matrix into one long prompt is exactly the
        migration accident this guards against)."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim == 0:
            prompt = prompt.reshape(1)
        if prompt.ndim != 1:
            raise ValueError(
                f"submit takes ONE 1-D prompt, got shape {prompt.shape} — "
                f"submit a batch as one request per row")
        return self.backend.submit(GenerationRequest(prompt, sampling))

    @property
    def pending(self) -> bool:
        return self.backend.pending

    @property
    def queue_depth(self) -> int:
        """Requests accepted but not yet scheduled — what the async front
        end's bounded admission (429 backpressure) is measured against."""
        return getattr(self.backend, "queue_depth", 0)

    def stream(self):
        """Drive the backend, yielding :class:`TokenEvent`s as they are
        produced, until every submitted request has finished. Requests
        submitted (or aborted) mid-iteration join the stream."""
        while self.backend.pending:
            yield from self.backend.step()

    def run(self) -> dict:
        """Drain everything; returns {rid: :class:`RequestOutput`}."""
        for _ in self.stream():
            pass
        return self.backend.outputs()

    def outputs(self) -> dict:
        """{rid: RequestOutput} for every finished/aborted request so far."""
        return self.backend.outputs()

    def abort(self, rid: int) -> bool:
        """Cancel a request; its partial output (finish reason
        ``"abort"``) appears in :meth:`outputs`."""
        return self.backend.abort(rid)

    def release(self, rid: int) -> bool:
        """Drop a finished request's retained output/metrics — call after
        consuming a :class:`RequestOutput` so a long-lived server's memory
        tracks LIVE requests, not total requests ever served."""
        return self.backend.release(rid)

    def metrics(self) -> dict:
        """One flat ``{name: number}`` metrics dict — the serving layer's
        SLO surface, superseding ad-hoc :class:`RequestMetrics` plumbing.

        Always present (telemetry on or off): ``requests.*`` aggregates
        built from the finished outputs still retained — finished count,
        per-reason counts, and streaming-percentile summaries of
        ``requests.ttft_s`` / ``requests.latency_s`` (``.p50``/``.p95``/
        ``.p99``/``.mean``/...). With a tracer attached, the tracer's
        full registry (tick latencies, pool gauges, TTFT/TPOT/e2e
        histograms, compile counters, split uplink accounting) is merged
        in under its own names."""
        from repro.serving.telemetry import Histogram

        out: dict = {}
        if self.tracer is not None:
            out.update(self.tracer.metrics_dict())
        finished = self.backend.outputs()
        out["requests.retained"] = len(finished)
        ttft, lat = Histogram(), Histogram()
        ticks, e2e, tpot = Histogram(), Histogram(), Histogram()
        for o in finished.values():
            out[f"requests.reason.{o.finish_reason}"] = out.get(
                f"requests.reason.{o.finish_reason}", 0) + 1
            m = o.metrics
            if m.ttft_s is not None:
                ttft.record(m.ttft_s)
            if m.latency_s is not None:
                lat.record(m.latency_s)
            if m.ttft_ticks is not None:
                ticks.record(m.ttft_ticks)
            # e2e_s falls back to latency_s so outputs stamped by older
            # drivers still aggregate; TPOT is the post-first-token decode
            # cadence — (e2e - ttft) / (n - 1), requests with one token
            # have no decode phase to measure
            e2e_v = m.e2e_s if m.e2e_s is not None else m.latency_s
            if e2e_v is not None:
                e2e.record(e2e_v)
                if m.ttft_s is not None and len(o.tokens) > 1:
                    tpot.record((e2e_v - m.ttft_s) / (len(o.tokens) - 1))
        for name, h in (("requests.ttft_s", ttft),
                        ("requests.latency_s", lat),
                        ("requests.ttft_ticks", ticks),
                        ("requests.e2e_s", e2e),
                        ("requests.tpot_s", tpot)):
            for k, v in h.summary().items():
                out[f"{name}.{k}"] = v
        return out
