"""90th percentile, over every request with at least two tokens inside
the window, of (last - first token time) / (tokens - 1), counting the
tokens it received inside the window."""

from _window import in_window, p90


def read(run):
    vals = []
    for r in run.records:
        ts = in_window(run, r.times)
        if len(ts) >= 2:
            vals.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return p90(vals)
