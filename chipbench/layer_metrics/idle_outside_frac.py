"""Share of the traced window in which the device idled while the host
was in no `join.` span: the caller's own code between calls
(`program_spans.py`)."""
from program_spans import idle_frac


def read(run):
    return idle_frac(run.trace, "outside")
