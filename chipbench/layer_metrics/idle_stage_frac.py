"""Share of the traced window in which the device idled while the host
was in the program's `join.stage` spans (padding the query rows,
uploading them, dispatching the filter; `program_spans.py`)."""
from program_spans import idle_frac


def read(run):
    return idle_frac(run.trace, "stage")
