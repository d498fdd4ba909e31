"""1 - (union of the intervals in which an op ran on the device) over the
traced window, averaged over the chips used."""


def read(run):
    t = run.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
