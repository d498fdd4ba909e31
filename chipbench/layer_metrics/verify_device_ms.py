"""Device milliseconds of the verify program per call, over the calls of
the traced window.  Verify is `Topology._compact_scaffold`'s jitted
`prog` (gather the positives, `ops.blocked_hist` against R, scatter);
the trace names its module by that function."""

MODULES = ("jit_prog",)


def read(run):
    calls = run.traced_calls()
    if run.trace is None or not calls:
        return None
    s = sum(run.trace["module_s"].get(m, 0.0) for m in MODULES)
    return s / len(calls) * 1e3 if s > 0 else None
