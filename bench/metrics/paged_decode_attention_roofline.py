"""Attention kernels: the Pallas paged decode attention kernel's share of
its roofline, in percent. Needed work counts only the keys each live
decoding slot attends (rebuilt from the tracer's decode spans, see
work.py), not the pages the kernel walks for free slots and unused
block-table entries; the device time is the kernel's summed duration in
the profile. Moves tpot_p90_s."""

import re

import work

# the decode tick's program and the kernel's op inside it, as they are
# named in the device trace
MODULE = re.compile(r"decode_sample")
OP = re.compile(r"custom-call|_kernel|paged_decode")


def read(run):
    if run.trace is None:
        return None
    secs = work.kernel_seconds_by_tick(run, MODULE, OP)
    ranges = work.decode_ranges(run.spans, run.prompt_len)
    needed = {}
    for tick in secs:
        ctx = work.contexts_at(ranges, tick)
        if ctx:
            needed[tick] = work.attention_cost(run.dims, ctx)
    return work.roofline(run, needed, secs)
