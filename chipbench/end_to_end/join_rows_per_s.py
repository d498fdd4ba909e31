"""Query rows whose counts reached the host, over the window's wall time."""


def read(run):
    return sum(c.n for c in run.calls if c.counts is not None) / run.window_s
