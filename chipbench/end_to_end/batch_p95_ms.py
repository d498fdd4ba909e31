"""95th percentile, over every call (or stream batch) of the window, of
the time from its submission to its counts on the host."""
import numpy as np


def read(run):
    lat = [c.t1 - c.t0 for c in run.calls if c.counts is not None]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
