"""Closed loop of `JoinPlan.run`, one pool set per call, the sets cycled.

Traffic keys: `rows` (rows per set and call)."""
import time

from harness import Call


def warm_up(plan, pool, traffic: dict, eps: float) -> None:
    """Every pool set once, so every capacity bucket of the window is
    compiled."""
    for start in range(0, pool.n, pool.rows):
        plan.run(pool.rows_at(start, pool.rows), eps)


def window(plan, pool, traffic: dict, eps: float, seconds: float,
           tracer) -> list:
    """The calls of the measured window; the last starts before
    `seconds` have passed."""
    import jax
    calls = []
    t_start = time.perf_counter()
    i = 0
    while True:
        c = Call(start=i * pool.rows, n=pool.rows, t0=time.perf_counter(),
                 traced=tracer.active)
        with jax.profiler.TraceAnnotation("bench.run"):
            res = plan.run(pool.rows_at(c.start, c.n), eps)
        c.t1 = time.perf_counter()
        c.counts, c.n_searched = res.counts, res.n_searched
        calls.append(c)
        i += 1
        tracer.maybe_stop(c.t1)
        if c.t1 - t_start >= seconds:
            return calls
