"""Bytes of query rows uploaded per query row asked: sum of `h2d_bytes`
(the padded query buffer) over sum of `rows`, over the traced window's
`join.stage` spans (`program_spans.py`)."""
from program_spans import count_sums


def read(run):
    c = count_sums(run.trace, "join.stage")
    if not c or not c.get("rows"):
        return None
    return c["h2d_bytes"] / c["rows"]
