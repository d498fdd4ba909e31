"""Seconds from the start of the plan's build until R is resident on
every device of its placement, from the route's `place_r` span (None
where the route records none)."""


def read(run):
    return run.spans.get("place_r")
