"""Scheduler layer: mean over the window's ticks of active slots over
max_slots, in percent. Moves output_tokens_per_s."""

from _window import ticks_in_window


def read(run):
    ticks = ticks_in_window(run)
    if not ticks:
        return None
    return 100.0 * sum(t.active_slots for t in ticks) / (
        len(ticks) * run.max_slots)
