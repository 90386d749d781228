"""Helpers shared by the metric readers: percentiles and what the
measured window saw of each request."""

from __future__ import annotations

import statistics


def p90(values) -> float | None:
    """The 90th percentile (statistics.quantiles, inclusive method)."""
    values = list(values)
    if not values:
        return None
    if len(values) == 1:
        return float(values[0])
    return float(statistics.quantiles(values, n=10, method="inclusive")[8])


def in_window(run, times) -> list:
    return [t for t in times if run.t_open <= t <= run.t_close]


def ticks_in_window(run) -> list:
    return [t for t in run.ticks
            if run.t_open <= t.start and t.start + t.wall_s <= run.t_close]
