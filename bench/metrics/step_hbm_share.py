"""Step functions: the window's ticks' share of the chip's HBM bandwidth,
in percent: the bytes their model calls had to move over (their summed
wall time x the chip's bytes per second). Each call streams the
decoder's matmul weights and the head once, in bf16
(TickRecord.step_calls); its attention reads the live pool tokens the
scheduler counted when it dispatched the call (TickRecord.kv_tokens_read),
at the per-token bytes of every layer that work.attention_cost reckons.
Norms, embedding rows, activations and the pages a kernel walks past
unused are left out, so this is a lower bound. Moves tpot_p90_s."""

from _window import ticks_in_window

import work

WEIGHT_BYTES = 2  # bf16, the dtype the program serves every weight in


def read(run):
    ticks = ticks_in_window(run)
    if not ticks or not hasattr(ticks[0], "kv_tokens_read"):
        return None
    d, v = run.dims["d"], run.dims["v"]
    weights = WEIGHT_BYTES * (run.matmul_params + d * v)
    # what one more attended token adds to a call's bytes
    per_token = (work.attention_cost(run.dims, [1])[1]
                 - work.attention_cost(run.dims, [0])[1])
    moved = sum(t.step_calls * weights + t.kv_tokens_read * per_token
                for t in ticks)
    wall = sum(t.wall_s for t in ticks)
    return 100.0 * moved / (wall * run.peaks["hbm_bytes_per_s"])
