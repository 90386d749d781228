"""90th percentile, over every request due in the window, of the time
from its scheduled send time to its first token. A request with no first
token at the close, or one that failed or was refused, enters at its age
then (close minus due)."""

from _window import p90


def read(run):
    vals = []
    for r in run.records:
        if r.due is None or not run.t_open <= r.due < run.t_close:
            continue
        ok = r.first is not None and r.first <= run.t_close and r.error is None
        vals.append((r.first if ok else run.t_close) - r.due)
    return p90(vals)
