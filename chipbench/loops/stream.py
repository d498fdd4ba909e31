"""Closed loop of a `JoinPlan.session`: batches of `batch` rows submitted
back to back at `depth`, cycled through the pool, flushed at the end.

Traffic keys: `batch`, `depth`, `warm_batches` (batches run through a
stream in warm-up)."""
import time
from collections import deque

from harness import Call


def warm_up(plan, pool, traffic: dict, eps: float) -> None:
    """A stream of the window's batch shape, deep enough to fill the
    pipeline."""
    b = int(traffic["batch"])
    batches = [pool.rows_at(j * b, b)
               for j in range(int(traffic["warm_batches"]))]
    list(plan.stream(batches, eps, depth=int(traffic["depth"])))


def window(plan, pool, traffic: dict, eps: float, seconds: float,
           tracer) -> list:
    """The batches of the measured window, each timed from its submission
    to the result that answers it; the last is submitted before
    `seconds` have passed."""
    import jax
    ann = jax.profiler.TraceAnnotation
    b = int(traffic["batch"])
    sess = plan.session(eps, depth=int(traffic["depth"]))
    calls, pending = [], deque()

    def take(results, traced_now: bool) -> None:
        now = time.perf_counter()
        for r in results:
            c = pending.popleft()
            c.t1, c.counts, c.n_searched = now, r.counts, r.n_searched
            c.traced = c.traced and traced_now

    t_start = time.perf_counter()
    j = 0
    while True:
        c = Call(start=j * b, n=b, t0=time.perf_counter(),
                 traced=tracer.active)
        pending.append(c)
        calls.append(c)
        with ann("bench.submit"):
            out = sess.submit(pool.rows_at(j * b, b))
        take(out, tracer.active)
        tracer.maybe_stop(time.perf_counter())
        j += 1
        if time.perf_counter() - t_start >= seconds:
            break
    with ann("bench.flush"):
        out = sess.flush()
    take(out, tracer.active)
    return calls
