"""Share of the traced window in which the device idled while the host
was in any other `join.` span: Python and jit dispatch in `run`,
`submit`, `probe` and `verify` (`program_spans.py`)."""
from program_spans import idle_frac


def read(run):
    return idle_frac(run.trace, "dispatch")
