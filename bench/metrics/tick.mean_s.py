"""Step functions and the host tick: mean wall time of the scheduler
ticks inside the window (TickRecord.wall_s). Moves tpot_p90_s."""

from _window import ticks_in_window


def read(run):
    ticks = ticks_in_window(run)
    if not ticks:
        return None
    return sum(t.wall_s for t in ticks) / len(ticks)
