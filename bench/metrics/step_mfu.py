"""Whole model step: model FLOPs of the tokens the window's ticks
processed, over (the summed wall time of those ticks x the chip's bf16
peak), in percent. Time between ticks, when the server has nothing to
do, is left out, so an open-loop cell below capacity reads how well its
steps use the chip and not how much load it was offered. A token costs
2 x the decoder's matmul parameters; the head (2 x hidden x vocab) is
counted only for rows whose logits are used: every decode token and
each prompt's last chunk. Attention's own FLOPs are left out, so this is
a lower bound of the model's share of the peak. Moves tpot_p90_s."""

from _window import ticks_in_window

import work


def read(run):
    ticks = ticks_in_window(run)
    if not ticks:
        return None
    chunks = work.prefill_chunks(run.spans)
    d, v = run.dims["d"], run.dims["v"]
    flops = 0
    for t in ticks:
        pre = chunks.get(t.tick, [])
        n_pre = sum(c[2] for c in pre)
        n_dec = t.tokens - n_pre
        heads = n_dec + sum(1 for c in pre if c[3])
        flops += 2 * run.matmul_params * t.tokens + 2 * d * v * heads
    busy = sum(t.wall_s for t in ticks)
    return 100.0 * flops / (busy * run.peaks["bf16_flops_per_s"])
