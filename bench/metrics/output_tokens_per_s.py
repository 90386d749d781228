"""Every token clients received inside the window, over its length."""

from _window import in_window


def read(run):
    n = sum(len(in_window(run, r.times)) for r in run.records)
    return n / (run.t_close - run.t_open)
