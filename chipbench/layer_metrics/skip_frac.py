"""Share of the window's query rows the filter skipped: 1 - sum of the
program's `n_searched` over sum of `n_queries` (JoinResult counters)."""


def read(run):
    n = sum(c.n for c in run.calls)
    return 1.0 - sum(c.n_searched for c in run.calls) / n if n else None
