"""Scheduler: host seconds per tick period in which no device result was
awaited. The mean over consecutive window ticks of (next tick's start -
this tick's start - this tick's TickRecord.wait_s, the seconds its
``*.wait`` host phases blocked on the device). The engine's work between
ticks (collecting and fanning out events, commands) lies inside the
period, so it is counted. Divided by the mean period it should come
close to device.idle_share. Moves tpot_p90_s."""

from _window import ticks_in_window


def read(run):
    ticks = ticks_in_window(run)
    if not ticks or not hasattr(ticks[0], "wait_s"):
        return None
    host = [b.start - a.start - a.wait_s for a, b in zip(ticks, ticks[1:])
            if b.tick == a.tick + 1]
    return sum(host) / len(host) if host else None
