"""Seconds of the Xling fit's estimator training (the estimator's `fit`),
from the benchmark's span around it."""


def read(run):
    return run.spans.get("fit_train")
