"""Device milliseconds of the verify program per call on one chip, over
the calls of the traced window, where R is sharded over several chips.

Every chip runs the verify module (`jit_prog`: gather the compacted
block to every R shard, `ops.blocked_hist` against the shard, sum the
counts across chips) once a call, and the trace's `module_s` sums it
over the device planes; this divides by the planes the trace holds
(`devices`), so it reads one chip's milliseconds."""

MODULES = ("jit_prog",)


def read(run):
    calls = run.traced_calls()
    if run.trace is None or not calls:
        return None
    s = sum(run.trace["module_s"].get(m, 0.0) for m in MODULES)
    return s / run.trace["devices"] / len(calls) * 1e3 if s > 0 else None
