"""Device milliseconds of the filter program per call, over the calls of
the traced window.  The filter is `JoinEngine._filter_program`'s jitted
`program`; the trace names its module by that function."""

MODULES = ("jit_program",)


def read(run):
    calls = run.traced_calls()
    if run.trace is None or not calls:
        return None
    s = sum(run.trace["module_s"].get(m, 0.0) for m in MODULES)
    return s / len(calls) * 1e3 if s > 0 else None
