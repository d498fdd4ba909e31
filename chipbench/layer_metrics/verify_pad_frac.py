"""Share of the verify rows spent on bucket padding: 1 - sum of `n_pos`
over sum of `capacity`, over the traced window's `join.verify` spans
(`program_spans.py`)."""
from program_spans import count_sums


def read(run):
    c = count_sums(run.trace, "join.verify")
    if not c or not c.get("capacity"):
        return None
    return 1.0 - c["n_pos"] / c["capacity"]
