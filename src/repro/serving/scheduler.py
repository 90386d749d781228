"""Continuous-batching scheduler over the paged KV pool: prefix sharing,
preemption, lazy page growth.

Replaces ``Engine``'s equal-length bucketing: requests of RAGGED prompt and
generation lengths share one decode batch and one KV pool, and the batch
composition changes mid-stream — a finished request's slot and pages are
reclaimed and handed to the next queued request without draining the batch.

Lifecycle per request (see ``serving/README.md``):

  admit   — the queue head is admitted when a slot row AND its admission
            pages are free. Two admission policies:
              * reserve (default) — pages for the WORST case
                (prompt + max_new_tokens) are reserved up front, so a
                mid-decode append can never hit an exhausted pool; the
                queue, not an exception, is the backpressure
              * lazy (``lazy_growth=True``) — only the PROMPT's pages are
                reserved; decode grows page by page and pool exhaustion is
                resolved by PREEMPTION (below). Strictly higher admitted
                concurrency from the same pool, at the cost of preemption
                work under pressure
            A request submitted with ``prefix_key=`` attaches to the shared
            prefix instead of allocating its own copy: the first such
            request (the CREATOR) prefills the full prompt and its prefix
            pages are pinned as a ``kv_pool.SharedPrefix``; later requests
            FORK — their block tables alias the pinned pages (refcount +1
            each) and only suffix pages (plus one CoW boundary copy when
            the prefix is not page-aligned) are newly allocated
  prefill — three tick shapes (``tick_mode=``, defaulting to
            ``prefill_mode`` for the two legacy values):
              * "packed" — ONE token-packed call serves the whole tick:
                every decoding slot's next token AND up-to-budget
                prefill-chunk tokens ride in a single flat
                ``(1, token_budget)`` buffer (each slot one contiguous
                segment — a decode token is a length-1 segment), attended
                in one pass by the Pallas ``kernels.varlen_attention``
                page walk and sampled through the same per-slot operand
                lanes (``models.transformer.packed_step``). One compiled
                shape, one dispatch per tick, pad limited to the buffer's
                tail — see serving/README.md for the segment layout
            and two legacy prefill policies (``prefill_mode=``):
              * "chunked" (default, Sarathi-style) — every prompt is split
                into fixed ``prefill_chunk``-token pieces and each tick
                advances every mid-prefill slot by ONE chunk through a
                FIXED-shape ``(max_slots, prefill_chunk)`` call: one
                compile serves every admission, per-tick latency is
                bounded by the chunk (a long prompt can no longer stall
                the decoding batch for its full length), and continuation
                chunks attend their earlier chunks THROUGH the pool via
                the Pallas ``kernels.paged_prefill_attention`` page walk
                — exactly what their decode steps will read. The chunk's
                last column yields the first sampled token when it
                completes the prompt
              * "wave" — the pre-chunking behavior: the admitted group
                prefills RAGGEDLY in one right-aligned call of bucketed
                ``(R_adm, S_pad)`` shape (a distinct compile per bucket,
                and decode waits for the full prompt)
            Either way, forked rows prefill ONLY their suffix, attending
            the shared prefix through their block tables
            (``models.transformer.paged_prefill_shared``)
  decode  — ALL active slots step together through ONE jitted
            ``paged_decode_step`` (fixed slot-count shape → a single
            compile, whatever the batch mix); each row decodes at its own
            absolute position, inactive rows ride along masked
  preempt — (lazy mode) when an append exhausts the pool, idle pinned
            prefixes are released first; then the lowest-priority (ties:
            most recently admitted) running request is EVICTED back to the
            queue head carrying its generated-so-far tokens, its pages
            freed. Two resume mechanisms (``resume=``):
              * "swap" (default) — the victim's written pages are
                snapshotted to HOST memory at eviction and restored
                bit-identically on re-admission (``kv_pool.export_slot`` /
                ``restore_slot``): the resumed decode is exactly the
                un-preempted one, token for token
              * "refill" — nothing is saved; the resumed request RE-PREFILLS
                prompt + generated tokens (re-attaching to its shared
                prefix if it has one). Cheaper in host memory, but the
                re-prefilled K/V travel a different numeric path than the
                decode-written originals, so the continuation is only
                approximately (not bit-) identical
            Already-emitted tokens are never re-sampled either way. Lazy
            admission always reserves one token of decode headroom past the
            (re-)prefill, so every admitted request makes ≥ 1 token of
            progress before it can be preempted — no livelock
  evict   — on max-tokens or EOS the slot's page references return to the
            pool (exclusively-owned pages scrubbed device-side; shared
            prefix pages survive for the next fork) and the next admit
            reuses them

Sampling is ON DEVICE and PER REQUEST: the decode tick jits
``paged_decode_step`` + ``core.sampling.sample_tokens`` as one function —
per-slot temperature / top-k / top-p operands, a per-request PRNG lane
folded with the row's own generation index, inactive rows masked — so a
batch mixing greedy and non-greedy requests runs through ONE compiled
shape (no per-request recompiles, no per-step host argmax; only the
sampled token ids cross to the host for bookkeeping). Greedy rows
(``temperature <= 0`` or ``top_k == 1``) take the exact argmax lane.
Per-request STOP-TOKEN SETS (``SamplingParams.stop_set``) finish a
request mid-stream, and ``abort(rid)`` cancels one wherever it is —
queued, mid-prefill, or decoding. Per-token events — each carrying the
token's log-probability under the raw model distribution — stream out
through ``drain_events()`` (consumed by ``serving.api.LLMServer``).

The tick loop itself stays host-orchestrated: what this scheduler buys is
MEMORY — shared prefixes are resident once however many requests attach,
residency is bounded by what the CURRENTLY resident requests actually use
(lazy mode), reclaimed the tick each finishes — and admission latency,
not per-step dispatch. The fused single-batch scan in ``serving.engine``
remains the static-batch fast path.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.sampling import (SamplingParams, bias_rows,
                                 sample_tokens_with_logprobs,
                                 speculative_verify, truncate_at_stop)
from repro.kernels.paged_decode_attention import live_pages
from repro.models.transformer import (RuntimeOpts, packed_step,
                                      paged_decode_step, paged_prefill,
                                      paged_prefill_shared, paged_verify_step,
                                      sharded_step_fns)
from repro.serving.kv_pool import (DEFAULT_PAGE_SIZE, PagedKVPool,
                                   PoolExhaustedError, SharedPrefix)
from repro.serving.page_transport import HostSwapTransport
from repro.serving.telemetry import maybe_phase

# the adaptive-prefill ladder ``prefill_chunk="auto"`` expands to: three
# compiled chunk shapes, picked per tick by batch composition (see
# Scheduler._pick_chunk)
AUTO_CHUNK_LADDER = (64, 128, 256)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32 — the ORIGINAL prompt tokens
    max_new_tokens: int
    eos_id: int | None = None
    prefix_key: object = None  # hashable; same key ⇒ shared prompt prefix
    priority: int = 0  # higher = preempted later
    # per-request sampling knobs (temperature/top-k/top-p/seed/stop set) —
    # turned into per-slot device operands at admission
    sampling: SamplingParams = SamplingParams(max_tokens=1)
    # resume state: tokens generated before a preemption — re-seeded into
    # the slot on re-admission, never re-sampled — and (swap resume) the
    # host snapshot of the request's written pages
    generated: list = dataclasses.field(default_factory=list)
    snapshot: dict | None = dataclasses.field(default=None, repr=False)
    submit_tick: int = 0  # scheduler tick at submission (TTFT accounting)
    # anti-thrash backoff: a preempted request is not re-admitted before
    # this tick while its preemptor still runs (see _admit_wave)
    cooldown_until: int = 0

    def __post_init__(self):
        # the stop set lives in sampling; fold a directly-passed eos_id in
        # so a hand-built Request(…, eos_id=…) stops like a submitted one
        if self.eos_id is not None \
                and self.eos_id not in self.sampling.stop_set:
            self.sampling = dataclasses.replace(
                self.sampling, stop_token_ids=self.sampling.stop_token_ids
                + (int(self.eos_id),))

    @property
    def prefill_tokens(self) -> np.ndarray:
        """TOKENS a (re-)prefill must write: the prompt plus every generated
        token already FED to the model (all but the last generated token,
        which is the next decode input)."""
        if not self.generated:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.generated[:-1], np.int32)])


@dataclasses.dataclass
class _PrefixEntry:
    """Registry row for one shared prompt prefix."""

    key: object
    tokens: np.ndarray  # (prefix_len,) int32 — validated on every submit
    handle: SharedPrefix | None = None  # pinned pages once materialized
    creator_rid: int | None = None  # request whose prefill materializes it
    forks: int = 0


@dataclasses.dataclass
class _SlotState:
    req: Request
    generated: list
    seq: int  # admission sequence number (preemption tie-break)
    prefilled: int = 0  # prompt/resume TOKENS already written to the pool

    @property
    def prefilling(self) -> bool:
        """Still mid-prefill: more chunks to write before the slot decodes."""
        return self.prefilled < len(self.req.prefill_tokens)

    @property
    def done(self) -> bool:
        if len(self.generated) >= self.req.max_new_tokens:
            return True
        return bool(self.generated
                    and self.generated[-1] in self.req.sampling.stop_set)


@dataclasses.dataclass
class SchedulerStats:
    steps: int = 0  # ragged decode steps executed
    prefills: int = 0  # prefill CALLS: admission waves in "wave" mode, per-
    #                    tick fixed-shape chunk calls in "chunked" mode
    prefill_chunks: int = 0  # per-slot chunks written (chunked mode; a
    #                          single-chunk prompt counts 1)
    admitted: int = 0  # admissions incl. resumptions
    evicted: int = 0  # completed requests
    aborted: int = 0  # abort() cancellations
    preemptions: int = 0  # evict-to-queue events (lazy mode)
    prefix_forks: int = 0  # admissions that attached to a shared prefix
    slot_ticks: int = 0  # Σ active slots over decode steps (mean concurrency
    #                       = slot_ticks / steps)
    peak_occupancy: float = 0.0
    peak_pool_bytes: int = 0  # physical page bytes (shared pages once)
    peak_eq2_bytes: int = 0  # logical per-request Eq. 2 bytes
    peak_shared_pages: int = 0  # pages with refcount > 1
    peak_swap_bytes: int = 0  # host bytes held by swapped-out snapshots
    compiled_shapes: int = 0  # distinct jitted step shapes seen (packed
    #                           mode is exactly 1; chunked stays O(1); wave
    #                           grows per bucket)
    spec_rounds: int = 0  # verify rounds that carried >= 1 draft token
    spec_drafted: int = 0  # draft tokens proposed across those rounds
    spec_accepted: int = 0  # draft tokens EMITTED (accepted and not cut by
    #                         a stop token) — acceptance rate is
    #                         spec_accepted / spec_drafted
    auto_prefix_hits: int = 0  # submits auto-attached to a detected shared
    #                            prefix (Scheduler(auto_prefix=True))
    packed_ticks: int = 0  # token-packed calls dispatched (packed mode)
    packed_tokens: int = 0  # live tokens those calls carried
    packed_pad_tokens: int = 0  # tail-pad rows they carried (pad fraction
    #                             = packed_pad_tokens / (packed_ticks * T))
    prefill_tokens: int = 0  # prompt/resume TOKENS written by prefill calls
    #                          (all tick modes — the prefill side of the
    #                          tick timeline's token accounting)
    # rid → ticks from submit to the first sampled token (TTFT in ticks)
    ttft_ticks: dict = dataclasses.field(default_factory=dict)
    # chunk size → ticks it was picked (adaptive prefill_chunk="auto")
    auto_chunks: dict = dataclasses.field(default_factory=dict)

    @property
    def acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens that were emitted."""
        return (self.spec_accepted / self.spec_drafted
                if self.spec_drafted else 0.0)


def _bucket(n: int) -> int:
    """Next power of two — bounds the distinct (R_adm, S_pad) prefill
    compiles the same way Engine buckets its scan length."""
    return 1 << max(0, (n - 1).bit_length())


def _prompt_lookup_draft(context: np.ndarray, k: int,
                         max_ngram: int = 3) -> np.ndarray:
    """Model-free draft proposal by PROMPT LOOKUP: find the most recent
    earlier occurrence of the context's trailing n-gram (longest of
    ``max_ngram`` .. 1 that matches) and propose the up-to-``k`` tokens
    that followed it.

    This is the scheduler's draft source — no second model, no extra
    weights, pure host-side token matching — and it is SAFE BY
    CONSTRUCTION: the verify step accepts a draft position only when the
    target model (greedy: argmax match; sampled: rejection test) agrees,
    so a bad guess costs acceptance length, never correctness. Repetitive
    continuations (code, structured text, tiny-vocab test models) accept
    long runs; incompressible ones degenerate to one verified token per
    round, exactly the non-speculative tick. Returns (<= k,) int32,
    possibly empty."""
    context = np.asarray(context, np.int32).reshape(-1)
    length = context.size
    if k <= 0 or length < 2:
        return np.zeros((0,), np.int32)
    for n in range(min(max_ngram, length - 1), 0, -1):
        pat = context[length - n:]
        # windows over context[:-1]: every start whose match leaves >= 1
        # follower token; the trailing n-gram itself can never match
        windows = np.lib.stride_tricks.sliding_window_view(
            context[:length - 1], n)
        hits = np.flatnonzero((windows == pat).all(axis=1))
        if hits.size:
            start = int(hits[-1])  # most recent occurrence wins
            return context[start + n:start + n + k].copy()
    return np.zeros((0,), np.int32)


class Scheduler:
    """Continuous-batching front end over one shared ``PagedKVPool``.

    ``submit`` enqueues; ``run`` drains queue + batch; ``step`` advances one
    admit→prefill→decode→evict tick for incremental/streaming use.
    ``lazy_growth=True`` switches admission control from worst-case page
    reservation to current-need reservation with preemption on exhaustion
    (see module doc).

    ``prefill_mode="chunked"`` (default) admits prompts in fixed
    ``prefill_chunk``-token pieces through one compiled step shape (see
    module doc); ``"wave"`` restores the per-bucket ragged wave prefill.
    ``prefill_chunk`` also takes ``"auto"`` (the ``AUTO_CHUNK_LADDER``
    sizes) or an explicit tuple of sizes: the chunk is then picked PER
    TICK from the ladder — small when decode slots dominate (a decoding
    request pays the chunk's latency every tick, so tail latency wins) or
    when any decoding request carries
    ``SamplingParams(latency_hint="interactive")``, large when the batch
    is prefill-heavy (throughput wins) — bounding the compile count by
    the ladder length instead of 1 (``stats.auto_chunks`` records the
    choices; ``benchmarks/chunked_prefill.py`` measures the tail-tick
    effect). ``preempt_cooldown`` (ticks) is the anti-thrash backoff: a
    preempted request is held in the queue that many extra ticks before
    re-admission while other work runs, so an evict→re-admit→evict swap
    storm can't oscillate tick over tick (0 restores the immediate
    re-admit).

    ``tick_mode="packed"`` replaces the per-tick prefill call(s) + decode
    call pair with ONE token-packed ``packed_step`` over a flat
    ``(1, token_budget)`` buffer (see module doc); ``"chunked"`` and
    ``"wave"`` keep the legacy two-phase tick. The default (None) follows
    ``prefill_mode`` so existing callers are untouched. ``token_budget``
    (packed mode) is the buffer's fixed token count — it must cover every
    decoding slot plus at least one prefill token, so it is clamped to
    ``>= max_slots + 1``; the default ``prefill_chunk + max_slots`` gives
    prefill the same per-tick bandwidth as one chunked-mode chunk even at
    full decode occupancy.

    ``speculate_k=k`` (k > 0) turns every decode tick SPECULATIVE: each
    decoding slot proposes up to k draft tokens by prompt lookup
    (:func:`_prompt_lookup_draft` — model-free n-gram matching over its
    own prompt + generation), the pool optimistically appends the burst,
    ONE fixed ``(max_slots, 1 + k)`` ``paged_verify_step`` call scores
    every position through the pool's quantized codes (in packed mode
    the packed buffer then carries prefill only — in-segment fresh-f32
    draft keys would drift from the sequential path at quantization
    scale), and
    ``core.sampling.speculative_verify`` accepts per slot — rejected
    positions roll back via ``kv_pool.truncate``. Greedy requests emit a
    stream BIT-IDENTICAL to ``speculate_k=0`` (acceptance is argmax
    match, emission is the argmax itself); sampled requests emit the
    exact target distribution (rejection sampling). ``k`` is the
    compiled verify width and the per-request cap —
    ``SamplingParams(speculate_k=)`` may lower it per request, and 0
    (the default) disables speculation entirely, leaving every code path
    byte-identical to the non-speculative scheduler.

    ``auto_prefix=True`` turns on AUTOMATIC prefix detection: a submit
    with no explicit ``prefix_key`` is longest-common-prefix matched
    against the last ``auto_prefix_window`` prompts and against already
    auto-registered prefixes; a match of >= ``auto_prefix_min`` tokens
    attaches the request to a shared prefix through the exact same CoW
    fork machinery as an explicit key (:meth:`_detect_auto_prefix`) —
    repeated system prompts share pages with zero caller cooperation.
    Greedy streams are unchanged (prefix sharing is bit-exact);
    ``stats.auto_prefix_hits`` counts the attachments.

    ``mesh=`` (a ``("kv", "model")`` mesh from
    ``launch.mesh.make_serving_mesh``) turns every tick MULTI-DEVICE: the
    pool's page axis is sharded over the mesh's "kv" axis
    (``kv_pool.PagedKVPool(mesh=)`` — each device stores 1/kv of the
    pages) and the five step functions are swapped for their
    ``models.transformer.sharded_step_fns`` shard_map lowerings
    (kv-heads split over "model", exact all_gathers at the attention
    boundary — no psum). The host-side scheduling logic, the per-slot
    sampling lanes and the compiled-shape accounting are UNTOUCHED, and
    greedy token streams stay bit-identical to the single-device
    scheduler (``tests/test_sharded_serving.py``)."""

    def __init__(self, cfg: ArchConfig, params,
                 opts: RuntimeOpts = RuntimeOpts(),
                 *, num_pages: int = 128, page_size: int = DEFAULT_PAGE_SIZE,
                 max_slots: int = 4, max_seq_len: int | None = None,
                 lazy_growth: bool = False, resume: str = "swap",
                 prefill_mode: str = "chunked",
                 prefill_chunk: int | str | tuple = 256,
                 preempt_cooldown: int = 1, tick_mode: str | None = None,
                 token_budget: int | None = None, speculate_k: int = 0,
                 auto_prefix: bool = False, auto_prefix_min: int = 8,
                 auto_prefix_window: int = 16, telemetry=None, mesh=None):
        if resume not in ("swap", "refill"):
            raise ValueError(f"resume must be 'swap' or 'refill', got {resume}")
        if prefill_mode not in ("chunked", "wave"):
            raise ValueError(
                f"prefill_mode must be 'chunked' or 'wave', got {prefill_mode}")
        if tick_mode is None:
            tick_mode = prefill_mode
        if tick_mode not in ("packed", "chunked", "wave"):
            raise ValueError(f"tick_mode must be 'packed', 'chunked' or "
                             f"'wave', got {tick_mode}")
        if tick_mode != "packed":
            prefill_mode = tick_mode
        if prefill_chunk == "auto":
            ladder = AUTO_CHUNK_LADDER
        elif isinstance(prefill_chunk, (tuple, list)):
            ladder = tuple(sorted({int(c) for c in prefill_chunk}))
        else:
            ladder = (int(prefill_chunk),)
        if not ladder or min(ladder) < 1:
            raise ValueError(
                f"prefill_chunk sizes must be >= 1, got {prefill_chunk!r}")
        if speculate_k < 0:
            raise ValueError(f"speculate_k must be >= 0, got {speculate_k}")
        self.cfg, self.params, self.opts = cfg, params, opts
        self.mesh = mesh
        self.pool = PagedKVPool(cfg, num_pages=num_pages, page_size=page_size,
                                max_requests=max_slots, max_seq_len=max_seq_len,
                                mesh=mesh)
        self.max_slots = max_slots
        self.lazy_growth = lazy_growth
        self.resume = resume
        self.prefill_mode = prefill_mode
        self.tick_mode = tick_mode
        # no prompt can exceed the block table's reach, so neither need a chunk
        reach = self.pool.max_blocks * page_size
        self._chunk_ladder = tuple(sorted({min(c, reach) for c in ladder}))
        self.prefill_chunk = self._chunk_ladder[-1]
        self.speculate_k = int(speculate_k)
        if token_budget is None:
            token_budget = self.prefill_chunk + max_slots
        # every decoding slot needs a row, plus >= 1 for prefill progress
        self.token_budget = max(int(token_budget), max_slots + 1)
        self.preempt_cooldown = preempt_cooldown
        # telemetry.Tracer | None — every instrumentation site below is
        # guarded on it, so the disabled path never calls the tracer (and
        # never forces a device sync): telemetry=None is a strict no-op
        self.telemetry = telemetry
        # the preempt/resume page mover — all swap spans + byte accounting
        # flow through the unified transport layer (page_transport)
        self._swap = HostSwapTransport(telemetry=telemetry)
        self._tick = 0
        self._shapes: set = set()  # distinct jitted call shapes dispatched
        self.queue: deque = deque()
        self.slots: list = [None] * max_slots
        self.results: dict = {}
        self.finish_reasons: dict = {}  # rid → "stop" | "length" | "abort"
        self.stats = SchedulerStats()
        self._prefixes: dict = {}
        self._next_rid = 0
        self._admit_seq = 0
        # automatic prefix detection (auto_prefix=True): submits with no
        # explicit prefix_key are longest-common-prefix matched against the
        # last `auto_prefix_window` prompts and against already-registered
        # auto prefixes; a match of >= auto_prefix_min tokens mints/joins a
        # shared prefix through the ordinary CoW fork machinery
        self.auto_prefix = bool(auto_prefix)
        self.auto_prefix_min = max(1, int(auto_prefix_min))
        self._recent_reqs: deque = deque(maxlen=max(1, int(auto_prefix_window)))
        self._auto_keys: set = set()
        self._auto_seq = 0
        # per-token streaming events (rid, token_index, token) in emission
        # order, and rids finished since the last drain — both consumed by
        # serving.api.LLMServer; a long-lived driver reads the finished
        # QUEUE instead of rescanning the whole results dict per tick.
        # THREAD MODEL: the scheduler is single-driver — submit/abort/step
        # mutate pool and slot state and must all run on ONE thread (the
        # async front end's tick thread marshals everything there; the
        # step() re-entry guard below turns a violation into a loud
        # RuntimeError instead of corrupted block tables). The two drain
        # surfaces are the exception: _emit_lock makes event/finished
        # APPENDS atomic with the drain swap, so drain_events() /
        # drain_finished() may be called from any thread, each by a single
        # consumer (a drained event exists exactly once — two competing
        # consumers would each see a disjoint, useless half of the stream)
        self._events: list = []
        self._finished: list = []
        # traced only: the end of the wait that pulled the tick's tokens to
        # the host, stamped on each event (None untraced)
        self._ready_t: float | None = None
        self._emit_lock = threading.Lock()
        self._step_guard = threading.Lock()
        # per-slot sampling operands, updated at admit/evict so every tick
        # ships the SAME (max_slots,)-shaped arrays — per-request sampling
        # without per-request compiles. Freed rows reset to greedy.
        self._op_keys = np.zeros((max_slots, 2), np.uint32)
        self._op_temp = np.zeros((max_slots,), np.float32)
        self._op_topk = np.zeros((max_slots,), np.int32)
        self._op_topp = np.ones((max_slots,), np.float32)
        # dense per-slot logit-bias rows (SamplingParams.logit_bias) — an
        # all-zero row is the bitwise identity, so bias-free slots ride the
        # same compiled shape untouched
        self._op_bias = np.zeros((max_slots, cfg.vocab_size), np.float32)
        # device-resident copy, rebuilt lazily after _set_ops/_reset_ops —
        # the hot decode tick must not re-upload unchanged operands
        self._dev_ops: tuple | None = None
        if mesh is not None:
            # shard_map lowerings of the five step fns — same signatures,
            # so the jitted tick wrappers below are shared verbatim
            sf = sharded_step_fns(cfg, opts, mesh)
            prefill_fn, prefill_shared_fn = sf["prefill"], sf["prefill_shared"]
            decode_fn, packed_fn, verify_fn = (sf["decode"], sf["packed"],
                                               sf["verify"])
        else:
            prefill_fn = lambda params, tokens, caches, positions: \
                paged_prefill(params, cfg, tokens, caches, positions, opts)
            prefill_shared_fn = lambda params, tokens, caches, positions: \
                paged_prefill_shared(params, cfg, tokens, caches, positions,
                                     opts)
            decode_fn = lambda params, tokens, caches, pos: \
                paged_decode_step(params, cfg, tokens, caches, pos, opts)
            packed_fn = lambda params, tokens, caches, positions, slots, \
                logit_rows, quant_fresh: \
                packed_step(params, cfg, tokens, caches, positions, slots,
                            logit_rows, opts, quant_fresh)
            verify_fn = lambda params, tokens, caches, positions: \
                paged_verify_step(params, cfg, tokens, caches, positions,
                                  opts)
        self._prefill = jax.jit(prefill_fn)
        self._prefill_shared = jax.jit(prefill_shared_fn)

        def decode_sample(params, tokens, caches, pos, keys, t, temp, tk, tp,
                          bias):
            # decode + sampling as ONE jitted function: logits never leave
            # the device — only the sampled token ids (and their logprobs)
            # cross to the host
            logits, new_caches = decode_fn(params, tokens, caches, pos)
            with jax.named_scope("sample"):
                toks, lps = sample_tokens_with_logprobs(logits, keys, t,
                                                        temp, tk, tp, bias)
            return toks, lps, new_caches

        self._decode = jax.jit(decode_sample)

        def packed_sample(params, tokens, caches, positions, slots,
                          logit_rows, quant_fresh, keys, t, temp, tk, tp,
                          bias):
            # the whole packed tick as ONE jitted function: embed → varlen
            # attention over the int8 pages → per-slot sampling lanes.
            # quant_fresh marks the buffer's DECODE rows: their fresh
            # self-keys round-trip through the int8 quantizer so they
            # attend the same values a sequential decode step reads back
            # from the pool (bit-identity with the chunked/wave ticks)
            logits, new_caches = packed_fn(params, tokens, caches, positions,
                                           slots, logit_rows, quant_fresh)
            with jax.named_scope("sample"):
                toks, lps = sample_tokens_with_logprobs(logits, keys, t,
                                                        temp, tk, tp, bias)
            return toks, lps, new_caches

        self._packed = jax.jit(packed_sample)
        self._sample = jax.jit(sample_tokens_with_logprobs)

        def sample_rows(logits, rows, keys, t, temp, tk, tp, bias):
            # wave-mode prefill samples a SUBSET of slot rows: gather the
            # rows' lanes from the cached full-slot operands on device
            # instead of rebuilding (R_adm,)-shaped host arrays per call
            return sample_tokens_with_logprobs(
                logits, keys[rows], t, temp[rows], tk[rows], tp[rows],
                bias[rows])

        self._sample_rows = jax.jit(sample_rows)

        def verify_sample(params, tokens, caches, positions, gather, draft,
                          draft_len, keys, t0, temp, tk, tp, bias):
            # speculative tick (every tick mode): one multi-token verify
            # through the pool, logits realigned from the right-aligned call layout
            # to generation-index order, then draft acceptance — all ONE
            # jitted function; only accepted tokens cross to the host
            logits, new_caches = verify_fn(params, tokens, caches, positions)
            with jax.named_scope("sample"):
                logits = jnp.take_along_axis(logits, gather[:, :, None],
                                             axis=1)
                out, n, lps = speculative_verify(draft, draft_len, logits,
                                                 keys, t0, temp, tk, tp, bias)
            return out, n, lps, new_caches

        self._verify = jax.jit(verify_sample)

    # -------------------------------------------------------------- intake

    def submit(self, prompt, max_new_tokens: int | None = None,
               eos_id: int | None = None,
               *, prefix_key=None, prefix_len: int | None = None,
               priority: int | None = None,
               sampling: SamplingParams | None = None) -> int:
        """Enqueue a request; returns its rid.

        ``sampling`` carries every per-request knob of the serving API
        (``core.sampling.SamplingParams``): max_tokens, temperature /
        top-k / top-p / seed (the on-device per-slot sampler operands),
        the stop-token set, priority, prefix declaration and latency
        hint. When given, it is the single source of truth and the legacy
        positional arguments must be omitted. The legacy form
        ``submit(prompt, max_new_tokens, eos_id, prefix_key=, ...)``
        keeps working — it builds greedy ``SamplingParams`` internally.

        ``prefix_key`` (any hashable) declares that this prompt's first
        ``prefix_len`` TOKENS are shared verbatim with every other request
        carrying the same key (a system prompt, a beam stem): the prefix is
        prefilled once and later requests attach to its pages. The key's
        FIRST submit fixes the shared length (pass ``prefix_len``
        explicitly there — it defaults to that whole prompt minus one
        token); later submits inherit the registered length, so they may
        omit ``prefix_len``. The shared length is capped at
        ``len(prompt) - 1`` (at least one suffix token must prefill to
        produce the request's first logits) and must match token-for-token
        across the key's requests. ``priority`` orders preemption victims
        in lazy mode (lower evicts first)."""
        if sampling is None:
            if max_new_tokens is None:
                raise ValueError("submit needs max_new_tokens or sampling=")
            sampling = SamplingParams(
                max_tokens=int(max_new_tokens), eos_id=eos_id,
                priority=priority or 0, prefix_key=prefix_key,
                prefix_len=prefix_len)
            priority = sampling.priority
        elif any(a is not None for a in (max_new_tokens, eos_id, prefix_key,
                                         prefix_len, priority)):
            raise ValueError(
                "pass either sampling= or the legacy arguments, not both — "
                "sampling is the single source of truth when given")
        else:
            prefix_key = sampling.prefix_key
            prefix_len = sampling.prefix_len
            priority = sampling.priority
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        assert prompt.size >= 1 and sampling.max_tokens >= 1
        if prefix_key is None and self.auto_prefix:
            prefix_key, prefix_len = self._detect_auto_prefix(prompt)
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, prompt, sampling.max_tokens, sampling.eos_id,
                      priority=priority, sampling=sampling,
                      submit_tick=self._tick)
        if prefix_key is not None:
            entry = self._prefixes.get(prefix_key)
            if prefix_len is not None:
                plen = int(prefix_len)
            elif entry is not None:
                plen = int(entry.tokens.size)  # inherit the key's length
            else:
                plen = prompt.size - 1
            plen = min(plen, prompt.size - 1)
            if plen >= 1:
                if entry is None:
                    entry = _PrefixEntry(prefix_key, prompt[:plen].copy())
                    self._prefixes[prefix_key] = entry
                else:
                    if entry.tokens.size != plen or not np.array_equal(
                            entry.tokens, prompt[:plen]):
                        raise ValueError(
                            f"prefix_key {prefix_key!r}: request {rid}'s "
                            f"declared {plen}-token prefix does not match "
                            f"the registered {entry.tokens.size}-token one")
                req.prefix_key = prefix_key
        if self.auto_prefix:
            self._recent_reqs.append(req)
        self.queue.append(req)
        if self.telemetry is not None:
            self.telemetry.request_submitted(rid)
        return rid

    @staticmethod
    def _lcp(a: np.ndarray, b: np.ndarray) -> int:
        """Length of the longest common prefix of two token sequences."""
        n = min(a.size, b.size)
        neq = np.nonzero(a[:n] != b[:n])[0]
        return int(neq[0]) if neq.size else n

    def _detect_auto_prefix(self, prompt: np.ndarray) -> tuple:
        """Automatic prefix detection (``auto_prefix=True``): find the
        longest shared prefix of >= ``auto_prefix_min`` tokens between
        this prompt and (a) any already-registered auto prefix or (b) any
        of the last ``auto_prefix_window`` submitted prompts, and return
        the ``(prefix_key, prefix_len)`` to attach with — HTTP clients
        get CoW prefix-page sharing without ever naming a ``prefix_key``.

        Matching an existing auto prefix simply joins it (the ordinary
        fork path). A longer match against a RECENT raw prompt mints a
        new auto key covering the common prefix; when that earlier
        request is still QUEUED and keyless it is retroactively attached,
        so the FIFO-first of the pair materializes the prefix and the
        later one forks — first-pair sharing, not just third-request-on.
        Both lengths are capped at each prompt's size - 1 (at least one
        suffix token must prefill to produce first logits). Returns
        ``(None, None)`` when nothing clears the threshold."""
        best_key, best_len = None, 0
        for key in self._auto_keys:
            entry = self._prefixes.get(key)
            if entry is None:
                continue
            plen = int(entry.tokens.size)
            if (plen > best_len and plen <= prompt.size - 1
                    and np.array_equal(entry.tokens, prompt[:plen])):
                best_key, best_len = key, plen
        best_req, best_req_len = None, best_len
        for other in self._recent_reqs:
            lcp = min(self._lcp(prompt, other.prompt),
                      prompt.size - 1, other.prompt.size - 1)
            if lcp > best_req_len:
                best_req, best_req_len = other, lcp
        if best_req is not None and best_req_len >= self.auto_prefix_min:
            self._auto_seq += 1
            key = ("auto_prefix", self._auto_seq)
            self._auto_keys.add(key)
            self._prefixes[key] = _PrefixEntry(
                key, prompt[:best_req_len].copy())
            if best_req.prefix_key is None and best_req in self.queue:
                best_req.prefix_key = key  # FIFO-first becomes the creator
            self.stats.auto_prefix_hits += 1
            return key, best_req_len
        if best_key is not None and best_len >= self.auto_prefix_min:
            self.stats.auto_prefix_hits += 1
            return best_key, best_len
        return None, None

    def release_prefixes(self) -> None:
        """Release every pinned shared prefix (their pages return to the
        free list once the last attached request finishes) and prune
        registry entries no queued or running request still names — a
        long-running scheduler must not accumulate one entry per
        prefix_key ever submitted. ``run`` calls this after draining;
        streaming users call it when a prefix's tenancy ends."""
        for entry in self._prefixes.values():
            if entry.handle is not None:
                self.pool.release_prefix(entry.handle)
                entry.handle = None
                entry.creator_rid = None
        live = {r.prefix_key for r in self.queue} | {
            st.req.prefix_key for st in self.slots if st is not None}
        self._prefixes = {k: e for k, e in self._prefixes.items()
                          if k in live}
        self._auto_keys &= set(self._prefixes)

    def abort(self, rid: int) -> bool:
        """Cancel a request wherever it currently is — queued (including
        preempted-and-swapped), mid-prefill, or decoding. The partial
        result (prompt + tokens emitted so far) is recorded with finish
        reason ``"abort"``; a live slot's pages return to the pool this
        call. Returns False when the rid is unknown or already finished
        (finished results are never retracted)."""
        for req in self.queue:
            if req.rid != rid:
                continue
            if req.snapshot is not None:
                self.pool.discard_snapshot(req.snapshot)
                req.snapshot = None
            self.queue.remove(req)
            self._finish_abort(req, req.generated)
            return True
        for i, st in enumerate(self.slots):
            if st is None or st.req.rid != rid:
                continue
            self.pool.free(i)
            self.slots[i] = None
            self._reset_ops(i)
            self._finish_abort(st.req, st.generated, track=f"slot{i}")
            return True
        return False

    def _finish_abort(self, req: Request, generated: list,
                      track: str = "queue") -> None:
        # an aborted prefix CREATOR must not strand waiting forks: clear
        # the claim so the next same-key admission materializes the prefix
        entry = self._prefixes.get(req.prefix_key) \
            if req.prefix_key is not None else None
        if entry is not None and entry.creator_rid == req.rid:
            entry.creator_rid = None
        self.results[req.rid] = np.concatenate(
            [req.prompt, np.asarray(generated, np.int32)])
        self.finish_reasons[req.rid] = "abort"
        self._mark_finished(req.rid)
        self.stats.aborted += 1
        if self.telemetry is not None:
            self.telemetry.request_finished(req.rid, track, "abort",
                                            len(generated))

    def extract(self, rid: int) -> Request | None:
        """Detach a RUNNING request from its slot and return it carrying a
        host snapshot of every page position it has WRITTEN — the
        prefill→decode handoff of the disaggregated deployment
        (``serving.page_transport``). The snapshot machinery is exactly
        the swap-preemption export (``kv_pool.export_slot``), so a request
        re-injected into ANOTHER scheduler's queue (:meth:`inject`)
        resumes its decode bit-identically — same guarantee as a
        preempt-and-resume on one scheduler. The slot and its pages free
        immediately; already-emitted tokens ride along in
        ``req.generated`` and are never re-sampled. Returns None when the
        rid is not currently in a slot (queued/finished requests are not
        extractable)."""
        for i, st in enumerate(self.slots):
            if st is None or st.req.rid != rid:
                continue
            st.req.generated = list(st.generated)
            # snapshot only WRITTEN positions: the last generated token is
            # the next decode input, not yet in the pool (same accounting
            # as the swap-preemption export)
            if st.generated:
                written = len(st.req.prompt) + len(st.generated) - 1
            else:
                written = st.prefilled
            st.req.snapshot = self.pool.export_slot(i, n_tokens=written)
            self.pool.free(i)
            self.slots[i] = None
            self._reset_ops(i)
            if self.telemetry is not None:
                self.telemetry.event("extract", track=f"slot{i}", rid=rid,
                                     tokens=written)
            return st.req
        return None

    def inject(self, req: Request) -> None:
        """Enqueue a :class:`Request` EXTRACTED from another scheduler
        (snapshot and generated tokens intact) — the decode-replica side
        of the disaggregated handoff. The next admission wave restores the
        snapshot through the ordinary swap-resume path. The caller owns
        rid uniqueness: injected rids come from the extracting scheduler,
        so a scheduler that both ``submit``s and ``inject``s must keep the
        two rid spaces disjoint (``serving.page_transport`` does)."""
        self.queue.append(req)
        if self.telemetry is not None:
            self.telemetry.request_submitted(req.rid)

    def _emit_event(self, rid: int, idx: int, tok: int, lp: float) -> None:
        """Append one streamed-token event atomically w.r.t. the drain
        swap — the tick thread may be mid-step while another thread calls
        ``drain_events``; without the lock an append racing the swap can
        land in the already-drained list and vanish."""
        with self._emit_lock:
            self._events.append((rid, idx, tok, lp, self._ready_t))

    def _mark_finished(self, rid: int) -> None:
        with self._emit_lock:
            self._finished.append(rid)

    def drain_events(self) -> list:
        """Return and clear the per-token events emitted since the last
        call: ``(rid, token_index, token, logprob, host_t)`` tuples in
        emission order — position order per request, interleaved across
        requests. ``logprob`` is the token's log-probability under the
        row's raw model distribution (``core.sampling.token_logprobs``);
        ``host_t`` is the tracer-clock time the token reached the host
        (None when the scheduler is untraced).

        SINGLE-CONSUMER: safe to call from a thread other than the one
        driving ``step()`` (the swap is atomic with event appends), but
        only ONE consumer may drain — each event is returned exactly once,
        so two competing drainers would each see a useless interleaved
        half of every request's stream."""
        with self._emit_lock:
            ev, self._events = self._events, []
        return ev

    def drain_finished(self) -> list:
        """Return and clear the rids that finished (evicted or aborted)
        since the last call — O(newly finished), however many results a
        long-running scheduler retains. Same single-consumer contract as
        :meth:`drain_events`."""
        with self._emit_lock:
            f, self._finished = self._finished, []
        return f

    # ------------------------------------------------------------ lifecycle

    def _set_ops(self, slot: int, req: Request) -> None:
        """Install the request's sampling operands in its slot row. A
        WRITE happens only when the row's values actually change (slot
        membership or per-request params): re-admitting the same request
        after a swap, or a greedy request landing in a greedy-reset row,
        keeps the uploaded device copy valid — steady-state ticks ship the
        SAME device arrays with zero host work."""
        sp = req.sampling
        key = np.asarray(jax.random.PRNGKey(sp.seed), np.uint32)
        row = (key, np.float32(sp.temperature), np.int32(sp.top_k),
               np.float32(sp.top_p))
        brow = bias_rows([sp], self._op_bias.shape[1])[0] \
            if sp.logit_bias else None
        if (np.array_equal(self._op_keys[slot], key)
                and self._op_temp[slot] == row[1]
                and self._op_topk[slot] == row[2]
                and self._op_topp[slot] == row[3]
                and (not self._op_bias[slot].any() if brow is None
                     else np.array_equal(self._op_bias[slot], brow))):
            return
        (self._op_keys[slot], self._op_temp[slot], self._op_topk[slot],
         self._op_topp[slot]) = row
        self._op_bias[slot] = 0.0 if brow is None else brow
        self._dev_ops = None

    def _reset_ops(self, slot: int) -> None:
        if (self._op_temp[slot] == 0.0 and self._op_topk[slot] == 0
                and self._op_topp[slot] == 1.0
                and not self._op_keys[slot].any()
                and not self._op_bias[slot].any()):
            return  # already the greedy reset row — keep the device copy
        self._op_keys[slot] = 0
        self._op_temp[slot] = 0.0
        self._op_topk[slot] = 0
        self._op_topp[slot] = 1.0
        self._op_bias[slot] = 0.0
        self._dev_ops = None

    def _device_ops(self) -> tuple:
        """(keys, temperature, top_k, top_p, bias) for ALL slot rows,
        uploaded once per operand change rather than once per tick."""
        if self._dev_ops is None:
            self._dev_ops = (jnp.asarray(self._op_keys),
                             jnp.asarray(self._op_temp),
                             jnp.asarray(self._op_topk),
                             jnp.asarray(self._op_topp),
                             jnp.asarray(self._op_bias))
        return self._dev_ops

    def lower_ticks(self) -> dict:
        """The jitted tick programs this tick mode dispatches through the
        paged attention kernels, lowered (not compiled) at their serving
        shapes: ``{name: jax.stages.Lowered}``. ``as_text()`` shows what a
        tick runs — e.g. whether the Pallas kernels are in it
        (``tpu_custom_call`` on a TPU)."""
        r = self.max_slots
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        keys, temp, tk, tp, bias = self._device_ops()
        ops = dict(keys=keys, temp=temp, tk=tk, tp=tp, bias=bias)
        caches = self.pool.device_caches()
        if self.tick_mode == "packed":
            t = self.token_budget
            return {"packed": self._packed.lower(
                self.params, i32(1, t), caches=caches, positions=i32(1, t),
                slots=i32(1, t), logit_rows=i32(r),
                quant_fresh=jax.ShapeDtypeStruct((1, t), jnp.bool_),
                t=i32(r), **ops)}
        c = self.prefill_chunk
        return {"decode": self._decode.lower(
                    self.params, i32(r, 1), caches=caches, pos=i32(r),
                    t=i32(r), **ops),
                "prefill_chunk": self._prefill_shared.lower(
                    self.params, i32(r, c), caches=caches,
                    positions=i32(r, c))}

    def _register_shape(self, *shape, rows=(), fresh: int = 0,
                        walk: bool = False) -> None:
        """Track every distinct jitted call shape the scheduler dispatches —
        ``stats.compiled_shapes`` is the compile-count the chunked mode
        exists to bound. Called once per jitted model call. When traced,
        the call's live pool tokens read go to the tick's counter: the
        pool lengths of the ``rows`` it attends, less ``fresh`` of those
        tokens that it attends from its own inputs instead. A ``walk``
        call (single-token decode, the paged decode kernel's walk) also
        counts the block-table entries its rows walk: each row's
        ``live_pages`` of its query position, the last pool token."""
        new = shape not in self._shapes
        self._shapes.add(shape)
        self.stats.compiled_shapes = len(self._shapes)
        if self.telemetry is not None:
            kv = sum(int(self.pool.lengths[i]) for i in rows) - fresh
            walked = 0
            if walk and rows:
                walked = int(live_pages(
                    self.pool.lengths[list(rows)] - 1, self.pool.page_size,
                    self.pool.max_blocks).sum())
            self.telemetry.shape_dispatch(new, kv, walked)

    def _pull(self, phase: str, *arrays) -> tuple:
        """Copy device results to the host: where a tick blocks on the
        device, timed as the ``phase`` wait phase when traced. Its end is
        when the tokens emitted from these results reached the host."""
        tel = self.telemetry
        if tel is None:
            return tuple(np.asarray(a) for a in arrays)
        with tel.phase(phase) as ph:
            out = tuple(np.asarray(a) for a in arrays)
        self._ready_t = ph.end
        return out

    def _admission_target(self, req: Request) -> int:
        """TOKENS the admission must cover. Reserve mode: the request's
        worst-case final length. Lazy mode: the (re-)prefill/restore length
        plus ONE decode token of headroom (capped at the final written
        length), so an admitted request always decodes at least one token
        before it can be preempted — the liveness guarantee. A swap
        snapshot never holds MORE than the (re-)prefill length (a
        mid-prefill victim holds less — its remaining chunks must still
        fit), so the prefill length covers both admission paths."""
        final = len(req.prompt) + req.max_new_tokens
        if not self.lazy_growth:
            return final
        held = len(req.prefill_tokens)
        # final - 1: the last sampled token is emitted, never written back
        return min(held + 1, final - 1)

    def _admit_wave(self) -> tuple:
        """Admit queue heads while a slot row and their admission pages fit.
        FIFO: a too-big head blocks the queue (no starvation-prone
        skipping), and a head whose shared prefix is still being prefilled
        by its creator waits one wave, then forks. A freshly PREEMPTED head
        additionally waits out its anti-thrash cooldown while its preemptor
        (or any other slot) runs — re-admitting it on the very next tick
        would only re-provoke the same exhaustion and evict it again, a
        swap storm that makes no progress; with every slot idle the
        cooldown is moot and is ignored. Returns (slots needing a prefill,
        slots restored from a swap snapshot)."""
        admitted, restored = [], []
        while self.queue:
            req = self.queue[0]
            if (req.cooldown_until > self._tick
                    and any(st is not None for st in self.slots)):
                break
            handle, entry = None, None
            if req.snapshot is None and req.prefix_key is not None:
                entry = self._prefixes.get(req.prefix_key)
                if entry is not None:
                    if entry.handle is not None:
                        handle = entry.handle
                    elif entry.creator_rid is not None:
                        break  # creator's prefill lands next wave; wait
            target = self._admission_target(req)
            if not self.pool.can_admit(target, prefix=handle):
                break
            tel = self.telemetry
            # swap resume carries a snapshot; refill resume carries only
            # its already-generated tokens — both re-admissions
            resumed = req.snapshot is not None or bool(req.generated)
            if req.snapshot is not None:
                slot = self._swap.swap_in(self.pool, req.snapshot,
                                          reserve_tokens=target, rid=req.rid)
                req.snapshot = None
                restored.append(slot)
            else:
                slot = self.pool.admit(len(req.prefill_tokens),
                                       reserve_tokens=target, prefix=handle)
                if handle is not None:
                    entry.forks += 1
                    self.stats.prefix_forks += 1
                elif entry is not None:
                    entry.creator_rid = req.rid
                admitted.append(slot)
            self.queue.popleft()
            # the pool length at admission = tokens already resident (0,
            # a shared prefix, or a restored snapshot — which for a victim
            # evicted mid-prefill is less than its prompt: it resumes
            # CHUNKING right where it left off)
            self.slots[slot] = _SlotState(req, list(req.generated),
                                          self._admit_seq,
                                          prefilled=int(self.pool.lengths[slot]))
            self._set_ops(slot, req)
            self._admit_seq += 1
            if tel is not None:
                tel.request_admitted(req.rid, slot, resumed=resumed)
        return admitted, restored

    def _record_first_token(self, st: _SlotState, slot: int, token: int,
                            logprob: float) -> None:
        """Seed the slot's first sampled token (resumed requests keep their
        already-emitted tokens — the last one is the next decode input, not
        a fresh sample) and record its TTFT."""
        if not st.generated:
            st.generated.append(token)
            self._emit_event(st.req.rid, 0, token, logprob)
            self.stats.ttft_ticks.setdefault(
                st.req.rid, self._tick - st.req.submit_tick)
            if self.telemetry is not None:
                self.telemetry.first_token(
                    st.req.rid, f"slot{slot}",
                    ttft_ticks=self._tick - st.req.submit_tick)

    def _maybe_pin_prefix(self, st: _SlotState, slot: int) -> None:
        """Pin the shared prefix once its creator has WRITTEN the covered
        tokens — under chunked prefill that can be mid-prompt, so waiting
        forks admit as soon as the prefix pages exist, not only after the
        creator's whole (possibly much longer) prompt lands."""
        entry = self._prefixes.get(st.req.prefix_key) \
            if st.req.prefix_key is not None else None
        if entry is not None and entry.handle is None \
                and entry.creator_rid == st.req.rid \
                and st.prefilled >= entry.tokens.size:
            entry.handle = self.pool.share_prefix(slot, entry.tokens.size)
            entry.creator_rid = None

    def _prefill_wave(self, admitted: list) -> None:
        """One ragged right-aligned prefill over the admitted rows; the last
        column is every row's final prompt token → first sampled token.
        Forked rows carry only their SUFFIX (positions from prefix_len) and
        attend the shared pages through ``paged_prefill_shared``."""
        tel = self.telemetry
        with maybe_phase(tel, "prefill.build"):
            toks = [self.slots[s].req.prefill_tokens for s in admitted]
            starts = [int(self.pool.lengths[s]) for s in admitted]  # 0/prefix
            lens = [t.size - st for t, st in zip(toks, starts)]  # suffixes
            s_pad = _bucket(max(lens))
            r = len(admitted)
            tokens = np.zeros((r, s_pad), np.int32)
            posn = np.full((r, s_pad), -1, np.int32)
            for i, slot in enumerate(admitted):
                suffix = toks[i][starts[i]:]
                tokens[i, s_pad - suffix.size:] = suffix
                posn[i, s_pad - suffix.size:] = np.arange(starts[i],
                                                          toks[i].size)
            shared = any(st > 0 for st in starts)
            fn = self._prefill_shared if shared else self._prefill
        with maybe_phase(tel, "prefill.dispatch"):
            self._register_shape("prefill_shared" if shared else "prefill",
                                 r, s_pad, rows=admitted)
            t0 = tel.now() if tel is not None else 0.0
            logits, new_caches = fn(
                self.params, jnp.asarray(tokens),
                caches=self.pool.device_caches(rows=admitted),
                positions=jnp.asarray(posn))
            t1 = tel.now() if tel is not None else 0.0
            self.pool.update_from(new_caches)
            first, first_lp = self._sample_first(logits, admitted)
        first, first_lp = self._pull("prefill.wait", first, first_lp)
        with maybe_phase(tel, "emit"):
            for i, slot in enumerate(admitted):
                st = self.slots[slot]
                self.pool.commit_prefill(slot, int(toks[i].size))
                st.prefilled = int(toks[i].size)
                if tel is not None:
                    tel.add_span("prefill", t0, t1, track=f"slot{slot}",
                                 rid=st.req.rid, tokens=lens[i],
                                 stage="wave")
                self._record_first_token(st, slot, int(first[i]),
                                         float(first_lp[i]))
                self._maybe_pin_prefix(st, slot)
            self.stats.prefills += 1
            self.stats.prefill_tokens += sum(lens)
            self.stats.admitted += r

    def _sample_first(self, logits, rows: list | None) -> tuple:
        """Sample each row's FIRST token (generation index 0) from prefill
        logits with its own sampling operands — same device sampler, same
        per-request PRNG lane as the decode tick, so a request's stream is
        seamless across the prefill→decode boundary. ``rows`` are the slot
        indices matching ``logits``'s rows (``None`` = all slots; a subset
        gathers its rows' lanes from the same cached device operands —
        the per-slot arrays are never rebuilt host-side per call); rows
        that didn't finish their prompt this call simply discard the
        sample. Returns (tokens, logprobs) as device arrays (the caller
        pulls them with :meth:`_pull`)."""
        keys, temp, tk, tp, bias = self._device_ops()
        if rows is None:
            toks, lps = self._sample(logits, keys,
                                     jnp.zeros((self.max_slots,), jnp.int32),
                                     temp, tk, tp, bias)
        else:
            toks, lps = self._sample_rows(
                logits, jnp.asarray(np.asarray(rows, np.int32)), keys,
                jnp.zeros((len(rows),), jnp.int32), temp, tk, tp, bias)
        return toks, lps

    def _pick_chunk(self) -> int:
        """The tick's prefill chunk size. Fixed ladder of one → that size.
        Adaptive (``prefill_chunk="auto"`` or an explicit ladder): shrink
        when decode slots dominate the batch — every decoding request pays
        the chunk call's latency this tick — or when any decoding request
        hints ``latency_hint="interactive"``; grow when the batch is
        prefill-heavy and nobody decoding objects (throughput); middle
        rung when balanced. Each rung is one compiled shape, so the
        compile count stays bounded by the ladder length."""
        ladder = self._chunk_ladder
        if len(ladder) == 1:
            return ladder[0]
        decoding = [st for st in self.slots
                    if st is not None and not st.prefilling and not st.done]
        n_pre = sum(1 for st in self.slots
                    if st is not None and st.prefilling)
        if decoding and any(st.req.sampling.latency_hint == "interactive"
                            for st in decoding):
            c = ladder[0]
        elif len(decoding) > n_pre:
            c = ladder[0]
        elif n_pre > len(decoding):
            c = ladder[-1]
        else:
            c = ladder[len(ladder) // 2]
        self.stats.auto_chunks[c] = self.stats.auto_chunks.get(c, 0) + 1
        return c

    def _prefill_chunk_tick(self) -> bool:
        """Advance every mid-prefill slot by ONE ``prefill_chunk``-token
        chunk through a FIXED-shape ``(max_slots, chunk)`` call — rows with
        nothing pending ride along fully padded (their writes trash-route,
        their attention masks out), so one compiled shape serves every
        admission state and the tick's latency is bounded by the chunk.

        First chunks (nothing of the request in the pool yet) keep the
        plain fresh-only attention path — the same math as ``Engine``'s
        prefill. Continuation chunks and prefix forks attend their pool
        history through the Pallas page-walk kernel
        (``models.layers.paged_prefill_attention``) — int8 in place,
        exactly what their decode steps will read. A chunk whose last
        token completes the prompt yields the row's first sampled token
        from the call's last column."""
        rows = [i for i, st in enumerate(self.slots)
                if st is not None and st.prefilling]
        if not rows:
            return False
        tel = self.telemetry
        with maybe_phase(tel, "prefill.build"):
            c = self._pick_chunk()
            fresh = [i for i in rows if int(self.pool.lengths[i]) == 0]
            cont = [i for i in rows if int(self.pool.lengths[i]) > 0]
        for group, fn, kind in ((fresh, self._prefill, "chunk"),
                                (cont, self._prefill_shared, "chunk_shared")):
            if not group:
                continue
            with maybe_phase(tel, "prefill.build"):
                tokens = np.zeros((self.max_slots, c), np.int32)
                posn = np.full((self.max_slots, c), -1, np.int32)
                ends = {}
                for i in group:
                    st = self.slots[i]
                    toks = st.req.prefill_tokens
                    lo = st.prefilled
                    hi = min(lo + c, toks.size)
                    chunk = toks[lo:hi]
                    tokens[i, c - chunk.size:] = chunk
                    posn[i, c - chunk.size:] = np.arange(lo, hi)
                    ends[i] = (hi, toks.size)
                # only sample on ticks where some row actually completes
                # its prompt — mid-prompt chunks discard the sample
                completes = any(hi == total for hi, total in ends.values())
            with maybe_phase(tel, "prefill.dispatch"):
                # a continuation chunk attends its pool history
                self._register_shape(kind, self.max_slots, c, rows=group)
                t0 = tel.now() if tel is not None else 0.0
                logits, new_caches = fn(
                    self.params, jnp.asarray(tokens),
                    caches=self.pool.device_caches(),
                    positions=jnp.asarray(posn))
                t1 = tel.now() if tel is not None else 0.0
                self.pool.update_from(new_caches)
                if completes:
                    first, first_lp = self._sample_first(logits, None)
            if completes:
                first, first_lp = self._pull("prefill.wait", first, first_lp)
            with maybe_phase(tel, "emit"):
                for i in group:
                    st = self.slots[i]
                    hi, total = ends[i]
                    self.pool.commit_prefill(i, hi)
                    chunk_tokens = hi - st.prefilled
                    st.prefilled = hi
                    self.stats.prefill_chunks += 1
                    self.stats.prefill_tokens += chunk_tokens
                    if tel is not None:
                        tel.add_span("prefill", t0, t1, track=f"slot{i}",
                                     rid=st.req.rid, tokens=chunk_tokens,
                                     stage=kind, done=hi == total)
                    self._maybe_pin_prefix(st, i)
                    if hi == total:  # prompt complete → first token
                        self._record_first_token(st, i, int(first[i]),
                                                 float(first_lp[i]))
                self.stats.prefills += 1
        return True

    def _release_idle_prefix(self) -> bool:
        """Unpin one materialized prefix whose pages nobody but the handle
        references (refcount 1 everywhere — e.g. every attached request
        finished, or was preempted and will resume from its own swap
        snapshot) — the cheapest way to make room before preempting live
        work. A later same-key request simply re-creates the prefix."""
        for entry in self._prefixes.values():
            if entry.handle is None:
                continue
            if any(self.pool.refcount[p] > 1 for p in entry.handle.pages):
                continue  # a live slot still reads these pages
            self.pool.release_prefix(entry.handle)
            entry.handle = None
            entry.creator_rid = None
            return True
        return False

    def _preempt_one(self, requester: int) -> bool:
        """Evict the lowest-priority (ties: most recently admitted) running
        request back to the queue head with its generated tokens; its pages
        are freed for ``requester``'s growth. Refuses (returns False) when
        the only candidate is the requester itself AND no idle prefix can
        be released — then the pool is simply too small for the request and
        the caller must fail loudly rather than thrash."""
        if self._release_idle_prefix():
            return True
        cands = [(st.req.priority, -st.seq, i)
                 for i, st in enumerate(self.slots) if st is not None]
        if not cands:
            return False
        victim = min(cands)[2]
        if victim == requester and len(cands) == 1:
            return False
        st = self.slots[victim]
        st.req.generated = list(st.generated)
        # anti-thrash: the victim re-queues but is not re-admitted before
        # its cooldown elapses while other slots run (see _admit_wave)
        st.req.cooldown_until = self._tick + 1 + self.preempt_cooldown
        tel = self.telemetry
        if tel is not None:
            tel.span_end(("decode", st.req.rid), outcome="preempt")
            tel.event("preempt", track=f"slot{victim}", rid=st.req.rid,
                      reason="pool_exhausted", resume=self.resume)
            tel.metrics.count("scheduler.preemptions")
        if self.resume == "swap":
            # snapshot only positions actually WRITTEN: the victim may have
            # run its speculative append this very tick (its pending token
            # was never decoded, so its position holds no KV yet) — the
            # accounted length would bake a permanent hole into the restore.
            # A victim still mid-prefill has written exactly its chunks so
            # far; its restore resumes chunking from there
            if st.generated:
                written = len(st.req.prompt) + len(st.generated) - 1
            else:
                written = st.prefilled
            st.req.snapshot = self._swap.swap_out(self.pool, victim,
                                                  n_tokens=written,
                                                  rid=st.req.rid)
            self.stats.peak_swap_bytes = max(self.stats.peak_swap_bytes,
                                             self.pool.swap_bytes)
        self.pool.free(victim)
        self.slots[victim] = None
        self._reset_ops(victim)
        self.queue.appendleft(st.req)
        self.stats.preemptions += 1
        if tel is not None:
            tel.request_requeued(st.req.rid, reason="preempt")
        return True

    def _draft_plan(self) -> dict:
        """Propose this tick's draft burst per decoding slot: ``{slot:
        drafts (kd,) int32}`` with ``kd <= speculate_k``, empty when
        speculation is off. Each slot's cap is the scheduler-wide
        ``speculate_k`` (the compiled verify width), optionally lowered by
        the request's own ``SamplingParams.speculate_k``, and always
        bounded by the tokens it may still emit (``kd + 1`` emit at most —
        the bound that keeps the reserve-mode admission reservation
        unbreachable). Drafts come from :func:`_prompt_lookup_draft` over
        prompt + generated."""
        k = self.speculate_k
        if k == 0:
            return {}
        plan = {}
        for i, st in enumerate(self.slots):
            if st is None or st.prefilling:
                continue
            sp = st.req.sampling
            cap = min(k, sp.speculate_k) if sp.speculate_k > 0 else k
            kd = min(cap, st.req.max_new_tokens - len(st.generated) - 1)
            plan[i] = _prompt_lookup_draft(
                np.concatenate([st.req.prompt,
                                np.asarray(st.generated, np.int32)]), kd)
        return plan

    def _grow_decode_slots(self, plan: dict | None = None) -> None:
        """Reserve pool tokens for every slot about to decode this tick —
        one per slot, plus its planned draft burst when speculating.
        In lazy mode, page-boundary growth that exhausts the pool sheds
        the slot's OWN drafts first (a draft burst is optional work; a
        request is not), then preempts before the step runs (the victim's
        un-decoded tick is simply not taken — its resume re-prefills from
        exactly the tokens it had emitted)."""
        for i in range(self.max_slots):
            if self.slots[i] is None or self.slots[i].prefilling:
                continue
            want = 1 + (plan[i].size if plan and i in plan else 0)
            while True:
                try:
                    self.pool.append(i, want)
                    break
                except PoolExhaustedError:
                    if want > 1:
                        plan[i] = plan[i][:0]
                        want = 1
                        continue
                    if not self._preempt_one(requester=i):
                        raise PoolExhaustedError(
                            f"request {self.slots[i].req.rid} cannot grow: "
                            f"the pool's {self.pool.num_pages - 1} page(s) "
                            f"cannot hold its worst case even alone")
                    if self.slots[i] is None:
                        break  # we were the victim; skip our own step

    def _emit_burst(self, slot: int, toks, n: int, lps, kd: int) -> None:
        """Land one verify round's accepted tokens on slot ``slot``:
        ``toks[:n]`` emit IN INDEX ORDER, each event carrying the token's
        logprob under the true verify distribution (never the drafter's).
        The burst is cut at its first stop token — the sequential decode
        would have finished there, so later accepted tokens must not leak
        out — and the slot's pool length rolls back to its fed-token count
        whenever part of the appended burst went unemitted
        (``kv_pool.truncate``: rejected/cut positions are scrubbed so no
        later step, export or history walk can see them)."""
        st = self.slots[slot]
        stop = st.req.sampling.stop_set
        emit = 0
        for j in range(n):
            tok = int(toks[j])
            st.generated.append(tok)
            self._emit_event(st.req.rid, len(st.generated) - 1, tok,
                             float(lps[j]))
            emit += 1
            if tok in stop:
                break
        if kd:
            self.stats.spec_rounds += 1
            self.stats.spec_drafted += kd
            self.stats.spec_accepted += emit - 1
            if self.telemetry is not None:
                self.telemetry.metrics.observe(
                    "scheduler.accepted_tokens", float(emit))
        if emit < 1 + kd:
            self.pool.truncate(slot, int(self.pool.lengths[slot])
                               - (1 + kd) + emit)

    def _verify_tick(self, active: list, plan: dict) -> None:
        """The speculative decode tick (every tick mode): each decoding
        slot's last token plus its draft burst ride one fixed
        ``(max_slots, 1 + speculate_k)`` right-aligned call through the
        pool (``models.transformer.paged_verify_step`` — all keys read
        back quantized, bit-identical attention inputs to the sequential
        decode steps), and the fused
        ``core.sampling.speculative_verify`` accepts per slot — k drafts
        verified for one dispatch instead of k ticks. Greedy slots emit
        the exact argmax stream (bit-identical to the non-speculative
        tick); sampled slots emit the exact target distribution by
        rejection sampling. Inactive rows ride fully padded as ever."""
        k = self.speculate_k
        s = 1 + k
        tel = self.telemetry
        with maybe_phase(tel, "decode.build"):
            tokens = np.zeros((self.max_slots, s), np.int32)
            posn = np.full((self.max_slots, s), -1, np.int32)
            gather = np.zeros((self.max_slots, s), np.int32)
            draft = np.zeros((self.max_slots, k), np.int32)
            dlen = np.zeros((self.max_slots,), np.int32)
            t0 = np.zeros((self.max_slots,), np.int32)
            for i in active:
                st = self.slots[i]
                d = plan[i]
                kd = d.size
                # first position being written: the grow appended 1 + kd
                base = int(self.pool.lengths[i]) - 1 - kd
                tokens[i, s - 1 - kd:] = np.concatenate(
                    [[st.generated[-1]], d]).astype(np.int32)
                posn[i, s - 1 - kd:] = np.arange(base, base + 1 + kd)
                # verify column j (generation index t0 + j) lives at call
                # column s - 1 - kd + j; clamp past the draft count (those
                # gathers are garbage the sampler masks by draft_len)
                gather[i] = s - 1 - kd + np.minimum(np.arange(s), kd)
                draft[i, :kd] = d
                dlen[i] = kd
                t0[i] = len(st.generated)
        with maybe_phase(tel, "decode.dispatch"):
            # every key, the burst's too, is read back through the pool
            self._register_shape("verify", self.max_slots, s, rows=active)
            keys, temp, tk, tp, bias = self._device_ops()
            if tel is not None:
                for i in active:
                    tel.decode_begin(self.slots[i].req.rid, f"slot{i}")
            out, n_acc, lps, new_caches = self._verify(
                self.params, jnp.asarray(tokens),
                caches=self.pool.device_caches(),
                positions=jnp.asarray(posn), gather=jnp.asarray(gather),
                draft=jnp.asarray(draft), draft_len=jnp.asarray(dlen),
                keys=keys, t0=jnp.asarray(t0), temp=temp, tk=tk, tp=tp,
                bias=bias)
            self.pool.update_from(new_caches)
        out, n_acc, lps = self._pull("decode.wait", out, n_acc, lps)
        with maybe_phase(tel, "emit"):
            for i in active:
                self._emit_burst(i, out[i], int(n_acc[i]), lps[i],
                                 plan[i].size)
            self.stats.steps += 1
            self.stats.slot_ticks += len(active)

    def _decode_tick(self) -> None:
        """One ragged decode step over EVERY slot (single compiled shape);
        inactive rows — free slots AND slots still mid-prefill — carry
        position -1 and are masked end-to-end, so prefill chunks and decode
        share the tick without sharing a shape. With ``speculate_k`` set
        the tick dispatches as one multi-token verify instead
        (:meth:`_verify_tick`)."""
        tel = self.telemetry
        with maybe_phase(tel, "decode.build"):
            plan = self._draft_plan()
            self._grow_decode_slots(plan)
            active = [i for i, st in enumerate(self.slots)
                      if st is not None and not st.prefilling]
            plain = bool(active) and not self.speculate_k
            if plain:
                tokens = np.zeros((self.max_slots, 1), np.int32)
                pos = np.full((self.max_slots,), -1, np.int32)
                # each row samples at its OWN generation index (folded
                # into its PRNG lane) — the stream a request draws is
                # independent of which slot it sits in and who else
                # shares the batch
                t = np.zeros((self.max_slots,), np.int32)
                for i in active:
                    tokens[i, 0] = self.slots[i].generated[-1]
                    # the position being written
                    pos[i] = int(self.pool.lengths[i]) - 1
                    t[i] = len(self.slots[i].generated)
        if not active:
            return
        if self.speculate_k:
            self._verify_tick(active, plan)
            return
        with maybe_phase(tel, "decode.dispatch"):
            # each row attends its whole pool length, the new key included
            self._register_shape("decode", self.max_slots, 1, rows=active,
                                 walk=True)
            keys, temp, tk, tp, bias = self._device_ops()
            if tel is not None:
                for i in active:
                    tel.decode_begin(self.slots[i].req.rid, f"slot{i}")
            nxt, lps, new_caches = self._decode(
                self.params, jnp.asarray(tokens),
                caches=self.pool.device_caches(), pos=jnp.asarray(pos),
                keys=keys, t=jnp.asarray(t), temp=temp, tk=tk, tp=tp,
                bias=bias)
            self.pool.update_from(new_caches)
        nxt, lps = self._pull("decode.wait", nxt, lps)
        with maybe_phase(tel, "emit"):
            for i in active:
                st = self.slots[i]
                st.generated.append(int(nxt[i]))
                self._emit_event(st.req.rid, len(st.generated) - 1,
                                 int(nxt[i]), float(lps[i]))
            self.stats.steps += 1
            self.stats.slot_ticks += len(active)

    def _packed_tick(self) -> bool:
        """ONE token-packed call for the whole tick: every decoding slot
        contributes its next-token row and every mid-prefill slot up to the
        remaining budget contributes its next chunk, laid out slot-major as
        contiguous segments in a fixed ``(1, token_budget)`` buffer (tail
        rows carry position/slot -1: their writes trash-route, their
        attention emits exact zeros). The call embeds, runs the varlen
        page-walk attention, gathers each slot's LAST row into ``(R, V)``
        logits and samples through the per-slot operand lanes — prefill
        chunks and decode tokens share one dispatch AND one compiled shape.
        With ``speculate_k`` set, decoding slots are EXCLUDED from the
        buffer: a draft burst must be verified through the pool's
        quantized codes (:meth:`_verify_tick`, dispatched right after by
        the packed step), not as fresh in-segment f32 keys, or the verify
        logits drift from the sequential decode path at quantization
        scale. Returns whether any work was dispatched."""
        k = self.speculate_k
        tel = self.telemetry
        with maybe_phase(tel, "decode.build"):
            if not k:
                self._grow_decode_slots()
            decode_rows = [] if k else [
                i for i, st in enumerate(self.slots)
                if st is not None and not st.prefilling]
            t_budget = self.token_budget
            tokens = np.zeros((1, t_budget), np.int32)
            posn = np.full((1, t_budget), -1, np.int32)
            slot_ids = np.full((1, t_budget), -1, np.int32)
            # decode rows' fresh self-keys round-trip the int8 quantizer
            # inside the packed step, so they attend exactly what a
            # sequential decode step reads back from the pool; prefill rows
            # keep f32 fresh keys (the same math as Engine's prompt prefill)
            quant_fresh = np.zeros((1, t_budget), bool)
            logit_rows = np.zeros((self.max_slots,), np.int32)
            t_idx = np.zeros((self.max_slots,), np.int32)
            # decode rows are never cut
            budget = t_budget - len(decode_rows)
            cap = self._pick_chunk() if any(
                st is not None and st.prefilling for st in self.slots) else 0
            cur = 0
            pieces = {}  # slot → (lo, hi, total) prefill piece this tick
            for i in range(self.max_slots):
                st = self.slots[i]
                if st is None:
                    continue
                if not st.prefilling:
                    if k:
                        continue  # speculating: decodes ride _verify_tick
                    tokens[0, cur] = st.generated[-1]
                    posn[0, cur] = int(self.pool.lengths[i]) - 1
                    slot_ids[0, cur] = i
                    quant_fresh[0, cur] = True
                    logit_rows[i] = cur
                    t_idx[i] = len(st.generated)
                    cur += 1
                elif budget > 0:
                    toks = st.req.prefill_tokens
                    lo = st.prefilled
                    hi = min(lo + min(cap, budget), toks.size)
                    n = hi - lo
                    tokens[0, cur:cur + n] = toks[lo:hi]
                    posn[0, cur:cur + n] = np.arange(lo, hi)
                    slot_ids[0, cur:cur + n] = i
                    logit_rows[i] = cur + n - 1
                    pieces[i] = (lo, hi, toks.size)
                    budget -= n
                    cur += n
        if cur == 0:
            return False
        with maybe_phase(tel, "decode.dispatch"):
            # a segment attends the pool below its first in-call position:
            # a decode row's own new key comes from the buffer
            self._register_shape("packed", self.max_slots, t_budget,
                                 rows=decode_rows + list(pieces),
                                 fresh=len(decode_rows))
            keys, temp, tk, tp, bias = self._device_ops()
            if tel is not None:
                for i in decode_rows:
                    tel.decode_begin(self.slots[i].req.rid, f"slot{i}")
            t0 = tel.now() if tel is not None else 0.0
            nxt, lps, new_caches = self._packed(
                self.params, jnp.asarray(tokens),
                caches=self.pool.device_caches(),
                positions=jnp.asarray(posn), slots=jnp.asarray(slot_ids),
                logit_rows=jnp.asarray(logit_rows),
                quant_fresh=jnp.asarray(quant_fresh), keys=keys,
                t=jnp.asarray(t_idx), temp=temp, tk=tk, tp=tp, bias=bias)
            t1 = tel.now() if tel is not None else 0.0
            self.pool.update_from(new_caches)
        nxt, lps = self._pull("decode.wait", nxt, lps)
        with maybe_phase(tel, "emit"):
            for i, (lo, hi, total) in pieces.items():
                st = self.slots[i]
                self.pool.commit_prefill(i, hi)
                st.prefilled = hi
                self.stats.prefill_chunks += 1
                self.stats.prefill_tokens += hi - lo
                if tel is not None:
                    tel.add_span("prefill", t0, t1, track=f"slot{i}",
                                 rid=st.req.rid, tokens=hi - lo,
                                 stage="packed", done=hi == total)
                self._maybe_pin_prefix(st, i)
                if hi == total:  # prompt complete → first token
                    self._record_first_token(st, i, int(nxt[i]),
                                             float(lps[i]))
            for i in decode_rows:
                st = self.slots[i]
                st.generated.append(int(nxt[i]))
                self._emit_event(st.req.rid, len(st.generated) - 1,
                                 int(nxt[i]), float(lps[i]))
            self.stats.packed_ticks += 1
            self.stats.packed_tokens += cur
            self.stats.packed_pad_tokens += t_budget - cur
            if pieces:
                self.stats.prefills += 1
            if decode_rows:
                self.stats.steps += 1
                self.stats.slot_ticks += len(decode_rows)
        return True

    def _evict_finished(self) -> None:
        for i, st in enumerate(self.slots):
            if st is None or not st.done:
                continue
            toks, reason = truncate_at_stop(
                st.generated[: st.req.max_new_tokens], st.req.sampling)
            self.results[st.req.rid] = np.concatenate(
                [st.req.prompt, np.asarray(toks, np.int32)])
            self.finish_reasons[st.req.rid] = reason
            self._mark_finished(st.req.rid)
            self.pool.free(i)
            self.slots[i] = None
            self._reset_ops(i)
            self.stats.evicted += 1
            if self.telemetry is not None:
                self.telemetry.request_finished(st.req.rid, f"slot{i}",
                                                reason, len(toks))

    def _track_occupancy(self) -> None:
        self.stats.peak_occupancy = max(self.stats.peak_occupancy,
                                        self.pool.occupancy())
        self.stats.peak_pool_bytes = max(self.stats.peak_pool_bytes,
                                         self.pool.page_bytes_in_use())
        self.stats.peak_eq2_bytes = max(self.stats.peak_eq2_bytes,
                                        self.pool.eq2_bytes())
        self.stats.peak_shared_pages = max(self.stats.peak_shared_pages,
                                           self.pool.pages_shared)

    # ------------------------------------------------------------- driving

    @property
    def pending(self) -> bool:
        return bool(self.queue) or any(s is not None for s in self.slots)

    def _fail_stuck_queue(self) -> bool:
        """The batch is idle yet the queue head still doesn't fit: release
        an idle pinned prefix and retry (returns True); if nothing is
        releasable it never will fit — fail loudly instead of spinning
        forever."""
        if self._release_idle_prefix():
            return True
        req = self.queue[0]
        need = self.pool.pages_for(self._admission_target(req))
        kind = "for admission" if self.lazy_growth else "worst-case"
        raise PoolExhaustedError(
            f"request {req.rid} needs {need} pages {kind} but the "
            f"whole pool has {self.pool.num_pages - 1} (max_blocks "
            f"{self.pool.max_blocks}); it can never be admitted")

    def step(self) -> bool:
        """One scheduler tick. Packed mode: admit, then ONE token-packed
        call carrying every decode token and up-to-budget prefill tokens,
        then evict. Chunked/wave modes: admit, advance prefill (one
        fixed-size chunk per mid-prefill slot, or the full wave), evict
        anything that finished on its prefill token, decode the ragged
        batch, evict. Returns whether work remains.

        With ``telemetry=`` set, each tick additionally lands one
        :class:`~repro.serving.telemetry.TickRecord` (wall time, token/pad
        counts, compile events, pool occupancy, queue depth, seconds
        blocked on the device, model calls and pool tokens read) and its
        host phases as ``host``-track spans that tile it (``admit``,
        ``prefill.build``/``.dispatch``/``.wait``, ``decode.build``/
        ``.dispatch``/``.wait``, ``emit``, ``evict``); the timeline is
        assembled from stat deltas and host clocks, with no device sync
        of its own, so the instrumented tick runs the exact same
        scheduling decisions as the bare one.

        SINGLE-DRIVER: ticks mutate pool pages, block tables and slot
        state with no internal locking — one thread must own them
        (``serving.async_engine.AsyncLLMServer`` marshals every call onto
        its tick thread). A second thread entering mid-tick raises
        RuntimeError instead of silently corrupting the pool."""
        if not self._step_guard.acquire(blocking=False):
            raise RuntimeError(
                "Scheduler.step() re-entered from another thread mid-tick: "
                "the scheduler is single-driver — submit/abort/step must "
                "all run on ONE thread (drain_events/drain_finished are "
                "the only cross-thread-safe surfaces)")
        try:
            return self._step_guarded()
        finally:
            self._step_guard.release()

    def _step_guarded(self) -> bool:
        tel = self.telemetry
        if tel is None:
            return self._step_inner()
        s = self.stats
        pre = (s.packed_tokens, s.packed_pad_tokens, s.prefill_tokens,
               s.slot_ticks)
        tel.tick_begin(self._tick + 1, self.tick_mode)
        try:
            pending = self._step_inner()
        finally:
            if self.tick_mode == "packed":
                tokens = s.packed_tokens - pre[0]
                pad = s.packed_pad_tokens - pre[1]
                if self.speculate_k:
                    # the multi-token verify dispatch rides OUTSIDE the
                    # packed buffer (decode slots are excluded from it);
                    # count its stepped slots like the two-phase ticks do
                    tokens += s.slot_ticks - pre[3]
            else:
                # legacy two-phase tick: prefill tokens + one decode token
                # per stepped slot (no fixed buffer → no pad accounting)
                tokens = (s.prefill_tokens - pre[2]) + (s.slot_ticks - pre[3])
                pad = None
            g = self.pool.gauges()
            tel.tick_end(
                tokens=tokens, pad_tokens=pad,
                pages_in_use=g["pages_in_use"],
                pages_shared=g["pages_shared"],
                swap_bytes=g["swap_bytes"], queue_depth=len(self.queue),
                active_slots=sum(st is not None for st in self.slots),
                prefilling_slots=sum(st is not None and st.prefilling
                                     for st in self.slots))
        return pending

    def _evict(self) -> None:
        """The tick's evict phase: occupancy peaks, then finished slots."""
        with maybe_phase(self.telemetry, "evict"):
            self._track_occupancy()
            self._evict_finished()

    def _step_inner(self) -> bool:
        self._tick += 1
        with maybe_phase(self.telemetry, "admit"):
            admitted, restored = self._admit_wave()
        if restored:
            self.stats.admitted += len(restored)
        if self.tick_mode == "packed":
            self.stats.admitted += len(admitted)
            did = self._packed_tick()
            if did or restored:
                self._evict()
            if self.speculate_k:
                # speculating: the packed call carried prefill only; the
                # decode slots now ride the pool-only multi-token verify
                # (max_new == 1 slots already finished on their prefill
                # token and were evicted above)
                if any(st is not None and not st.prefilling
                       for st in self.slots):
                    self._decode_tick()
                    self._evict()
                    return self.pending
            if not did and (not admitted and not restored and self.queue
                            and all(st is None for st in self.slots)):
                self._fail_stuck_queue()
            return self.pending
        did_prefill = False
        if self.prefill_mode == "wave":
            if admitted:
                # prefill fresh rows and forked rows separately: the shared
                # path's full-pool history walk is paid only by rows that
                # actually attend history
                fresh = [s for s in admitted
                         if int(self.pool.lengths[s]) == 0]
                forked = [s for s in admitted
                          if int(self.pool.lengths[s]) > 0]
                for group in (fresh, forked):
                    if group:
                        self._prefill_wave(group)
                did_prefill = True
        else:
            self.stats.admitted += len(admitted)
            did_prefill = self._prefill_chunk_tick()
        if did_prefill or restored:
            self._evict()  # max_new_tokens == 1 finishes here
        if any(st is not None and not st.prefilling for st in self.slots):
            self._decode_tick()
            self._evict()
        elif (not admitted and not restored and self.queue
              and all(st is None for st in self.slots)):
            self._fail_stuck_queue()
        return self.pending

    def run(self) -> dict:
        """Drain queue and batch; returns {rid: np.ndarray tokens} (prompt +
        generation, EOS-truncated). Pinned prefixes are released after the
        drain so the pool ends fully reclaimed."""
        while self.step():
            pass
        self.release_prefixes()
        return self.results
