"""Scheduler layer: 90th percentile of the time requests waited in the
scheduler's queue (the tracer's ``queued`` spans) for every request
admitted inside the window; one still queued at the close enters at its
wait so far. Moves ttft_p90_s."""

from _window import p90


def read(run):
    vals = []
    for sp in run.spans:
        if sp.name != "queued":
            continue
        end = sp.end if sp.end is not None else run.t_close
        if run.t_open <= min(end, run.t_close) <= run.t_close and \
                sp.start <= run.t_close:
            vals.append(min(end, run.t_close) - sp.start)
    return p90(vals)
