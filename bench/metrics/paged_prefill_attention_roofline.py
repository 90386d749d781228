"""Attention kernels: the Pallas paged prefill attention kernel's share
of its roofline, in percent, over the continuation chunks (a prompt's
first chunk attends only itself and takes no kernel). Needed work counts
each chunk's real query tokens against its real history (rebuilt from
the tracer's prefill spans, see work.py), not the padded rows and
unused block-table entries the kernel walks. Moves ttft_p90_s."""

import re

import work

# the continuation-chunk program is the only non-decode program whose
# ops include a Pallas kernel
MODULE = re.compile(r"^(?!.*decode_sample)")
OP = re.compile(r"custom-call|_kernel|paged_prefill")


def read(run):
    if run.trace is None:
        return None
    secs = work.kernel_seconds_by_tick(run, MODULE, OP)
    chunks = work.prefill_chunks(run.spans)
    needed = {}
    for tick in secs:
        flops = byt = 0
        for stage, hist, n, _done in chunks.get(tick, []):
            if stage == "chunk_shared":
                f, b = work.attention_cost(run.dims, [hist], queries=n)
                flops, byt = flops + f, byt + b
        if flops:
            needed[tick] = (flops, byt)
    return work.roofline(run, needed, secs)
