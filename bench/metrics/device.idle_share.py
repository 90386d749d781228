"""Device: share of the profiled stretch in which no operation ran on
the chip, in percent: 100 * (1 - busy / window). Moves
tpot_p90_s."""

import xplane


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    busy = [xplane.union_seconds(xplane.clip(d["ops"], lo, hi))
            for d in run.trace["devices"].values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
