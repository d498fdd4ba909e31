"""Share of the traced window in which the device idled while the host
was in the program's `join.sync.*` spans: the declared device-to-host
waits (the positive count, the result readback; `program_spans.py`)."""
from program_spans import idle_frac


def read(run):
    return idle_frac(run.trace, "sync")
