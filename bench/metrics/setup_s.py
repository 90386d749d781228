"""Set-up time: from process start to the window opening (loading,
weights, pool, compiling or loading compiled programs, warm-up)."""


def read(run):
    return run.setup_s
