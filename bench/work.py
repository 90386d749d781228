"""What each scheduler tick had to do, rebuilt from the serving tracer's
records: the context each decoding slot attended, the continuation
chunks and the history each attended, and the tokens the matmuls
processed. The roofline and MFU readers divide this needed work by the
time the device or the window took.

The tracer's ``TickRecord`` carries no per-slot lengths, so they are
rebuilt from its spans: a request's ``decode`` span opens on the tick of
its first decode step (``attrs["tick"]``) and it decodes on every tick
after that until its eviction, attending prompt + 1 + (tick - first
tick) keys; each ``prefill`` span carries its chunk's token count and the
tick it ran on, so a continuation chunk's history is the sum of that
request's earlier chunks.
"""

from __future__ import annotations


def ticks_within(ticks, lo: float, hi: float) -> list:
    """Tick records that started and ended inside [lo, hi]."""
    return [t for t in ticks if t.start >= lo and t.start + t.wall_s <= hi]


def decode_ranges(spans, prompt_len: dict) -> list:
    """(first decode tick, last decode tick, prompt length) per request;
    a request still decoding has no last tick (taken as unbounded)."""
    out = []
    for sp in spans:
        if sp.name != "decode" or sp.rid not in prompt_len:
            continue
        t0, t1 = sp.attrs.get("tick"), sp.attrs.get("end_tick")
        if t0 is not None:
            out.append((t0, float("inf") if t1 is None else t1,
                        prompt_len[sp.rid]))
    return out


def contexts_at(ranges: list, tick: int) -> list:
    """Keys attended by each slot that decoded on ``tick``."""
    return [plen + 1 + (tick - t0) for t0, last, plen in ranges
            if t0 <= tick <= last]


def prefill_chunks(spans) -> dict:
    """{tick: [(stage, history, tokens, done)]} for every prefill chunk."""
    seen: dict = {}
    out: dict = {}
    for sp in sorted((s for s in spans if s.name == "prefill"),
                     key=lambda s: (s.attrs.get("tick", -1), s.start)):
        n = int(sp.attrs.get("tokens", 0))
        hist = seen.get(sp.rid, 0)
        seen[sp.rid] = hist + n
        out.setdefault(sp.attrs.get("tick"), []).append(
            (sp.attrs.get("stage"), hist, n, bool(sp.attrs.get("done"))))
    return out


def tick_of(ticks, t: float):
    """The tick record whose interval holds host time ``t``, or None."""
    for rec in ticks:
        if rec.start <= t <= rec.start + rec.wall_s:
            return rec
    return None


def attention_cost(dims: dict, contexts, queries: int = 1) -> tuple:
    """(flops, bytes) one paged attention call needs over every layer:
    ``contexts`` are the pool keys each row attends; ``queries`` query
    tokens per row also attend ``queries`` fresh keys causally. Bytes
    count the int8 key and value codes, their float32 scales and int32
    positions of the attended pages' live tokens, the bf16 queries and
    fresh keys and values, and the float32 output."""
    n, h, kh, hd = dims["n"], dims["h"], dims["kh"], dims["hd"]
    flops = byt = 0
    for ctx in contexts:
        fresh = 0 if queries == 1 else (queries + 1) / 2
        flops += 4 * h * hd * queries * (ctx + fresh)
        byt += kh * ctx * (2 * hd + 2 * 4 + 4)
        byt += queries * (h * hd * (2 + 4)
                          + (0 if queries == 1 else 2 * kh * hd * 2))
    return n * flops, n * byt


def kernel_seconds_by_tick(run, module_re, op_re) -> dict:
    """{tick: device seconds} of the profiled ops whose module name
    matches ``module_re`` and whose own name matches ``op_re``, each op
    placed in the tick whose host interval holds its start. Only ticks
    wholly inside the profiled stretch are kept."""
    import xplane

    lo, hi = run.trace_window
    off = run.trace_offset
    inside = ticks_within(run.ticks, lo - off, hi - off)
    out = {t.tick: 0.0 for t in inside}
    for dev in run.trace["devices"].values():
        ops = xplane.clip(dev["ops"], lo, hi)
        mods = xplane.module_of(ops, dev["modules"])
        for (name, s, d), mod in zip(ops, mods):
            if mod is None or not module_re.search(mod) or \
                    not op_re.search(name):
                continue
            rec = tick_of(inside, s - off)
            if rec is not None:
                out[rec.tick] += d / len(run.trace["devices"])
    return out


def roofline(run, needed: dict, seconds: dict) -> float | None:
    """Percent of the roofline: the least time the chip could take for
    the needed (flops, bytes) of each tick, summed, over the kernel's
    summed device time in those ticks. None where no kernel time was
    found (nothing to read)."""
    t_dev = sum(seconds.get(k, 0.0) for k in needed)
    if t_dev <= 0.0:
        return None
    pk = run.peaks
    t_min = sum(max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
                for f, b in needed.values())
    return 100.0 * t_min / t_dev
