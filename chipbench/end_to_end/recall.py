"""Pairs found over true pairs, over every answer of the window:
sum of min(count, reference count) over sum of reference counts."""
import numpy as np


def read(run):
    found = true = 0
    for c in run.calls:
        exp = run.expected_for(c)
        true += int(exp.sum())
        if c.counts is not None and np.shape(c.counts) == exp.shape:
            found += int(np.minimum(c.counts, exp).sum())
    return found / true if true else None
