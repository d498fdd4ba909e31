"""Seconds of the Xling fit's ground-truth sweep (`xling.cardinality_table`,
which blocks and returns numpy), from the benchmark's span around it."""


def read(run):
    return run.spans.get("fit_ground_truth")
