"""Clients that speak raw HTTP/SSE to ``POST /v1/completions`` and record,
for every request, when it was due, when it was sent, and when each
streamed token arrived (``time.perf_counter``, the clock the serving
tracer uses too).

Open loop: each request waits for its due time and is timed from then,
so a stall shows in every request that was due during it. Closed loop:
each client sends its next request when the last one completes.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time


@dataclasses.dataclass
class Record:
    job: dict
    due: float | None = None  # perf_counter time the request was due
    sent: float | None = None
    status: int | None = None
    rid: int | None = None
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    logprobs: list = dataclasses.field(default_factory=list)
    finish: str | None = None
    done: float | None = None
    error: str | None = None

    @property
    def first(self) -> float | None:
        return self.times[0] if self.times else None


def parse_sse(buf: bytes) -> tuple:
    """Split complete ``data:`` frames off ``buf``: (payloads, rest). A
    payload is a dict, or the string "[DONE]"."""
    out = []
    while b"\n\n" in buf:
        frame, buf = buf.split(b"\n\n", 1)
        for line in frame.splitlines():
            if line.startswith(b"data:"):
                data = line[5:].strip()
                out.append("[DONE]" if data == b"[DONE]" else json.loads(data))
    return out, buf


async def complete(host: str, port: int, rec: Record) -> None:
    """Send ``rec.job`` as one streaming completion and fill ``rec`` as
    its frames arrive. Cancellation closes the socket, which the server
    reads as a disconnect and aborts the request."""
    job = rec.job
    body = json.dumps({"prompt": job["prompt"],
                       "max_tokens": job["max_tokens"],
                       "temperature": job["temperature"],
                       "top_p": job["top_p"], "seed": job["seed"],
                       "stream": True}).encode()
    rec.sent = time.perf_counter()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(b"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Type: application/json\r\n"
                     + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        status = await reader.readline()
        rec.status = int(status.split()[1])
        while await reader.readline() not in (b"\r\n", b"\n", b""):
            pass
        if rec.status != 200:
            rec.error = (await reader.read()).decode(errors="replace")[:200]
            rec.done = time.perf_counter()
            return
        buf = b""
        while True:
            chunk = await reader.read(65536)
            now = time.perf_counter()
            if not chunk:
                rec.error = "stream closed before [DONE]"
                rec.done = now
                return
            frames, buf = parse_sse(buf + chunk)
            for msg in frames:
                if msg == "[DONE]":
                    rec.done = now
                    return
                rec.rid = msg["rid"]
                if msg.get("finished"):
                    rec.finish = msg["finish_reason"]
                    continue
                rec.times.append(now)
                rec.tokens.append(int(msg["token"]))
                rec.logprobs.append(float(msg.get("logprob", float("nan"))))
    finally:
        writer.close()


async def _guarded(host, port, rec) -> None:
    try:
        await complete(host, port, rec)
    except (ConnectionError, OSError, ValueError) as e:
        rec.error = f"{type(e).__name__}: {e}"
        rec.done = time.perf_counter()


async def run_open(host: str, port: int, sched: dict, seconds: float,
                   on_open=None) -> tuple:
    """Drive an open-loop schedule. Returns (records, t_open, t_close,
    lateness): lateness is each request's send time minus its due time."""
    t0 = time.perf_counter() + 0.05
    t_open = t0 + sched["warmup_s"]
    t_close = t_open + seconds
    recs = []
    for job in sched["jobs"]:
        recs.append(Record(job, due=t0 + job["due"]))

    async def one(rec):
        await asyncio.sleep(max(0.0, rec.due - time.perf_counter()))
        await _guarded(host, port, rec)

    tasks = [asyncio.ensure_future(one(r)) for r in recs]
    await asyncio.sleep(max(0.0, t_open - time.perf_counter()))
    if on_open is not None:
        on_open(t_open)
    await asyncio.sleep(max(0.0, t_close - time.perf_counter()))
    await _cancel(tasks)
    late = [r.sent - r.due for r in recs if r.sent is not None]
    return recs, t_open, t_close, late


async def run_closed(host: str, port: int, sched: dict, seconds: float,
                     on_open=None, open_timeout_s: float = 300.0) -> tuple:
    """Drive closed-loop clients; the window opens once every client has
    its first token (or fails with TimeoutError after ``open_timeout_s``).
    Returns (records, t_open, t_close, lateness) like :func:`run_open`;
    a closed loop is never late."""
    recs, inner, firsts = [], [], asyncio.Event()
    pending = {i for i in range(len(sched["clients"]))}

    async def client(i, jobs):
        for job in jobs:
            rec = Record(job, due=time.perf_counter())
            recs.append(rec)
            task = asyncio.ensure_future(_guarded(host, port, rec))
            inner.append(task)
            while not task.done() and not rec.times:
                await asyncio.sleep(0.01)
            if i in pending and rec.times:
                pending.discard(i)
                if not pending:
                    firsts.set()
            await task

    tasks = [asyncio.ensure_future(client(i, jobs))
             for i, jobs in enumerate(sched["clients"])]
    try:
        await asyncio.wait_for(firsts.wait(), open_timeout_s)
    except asyncio.TimeoutError:
        await _cancel(tasks + inner)
        raise
    t_open = time.perf_counter()
    if on_open is not None:
        on_open(t_open)
    t_close = t_open + seconds
    await asyncio.sleep(seconds)
    await _cancel(tasks + inner)
    return recs, t_open, t_close, [0.0]


async def _cancel(tasks) -> None:
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
