"""The exact route on the placement the configuration states:
`filter("none")`, every query verified, with R placed as the
configuration's `placement` says (`JoinPlan.on(**placement)`, e.g.
`topology="ring", r_shards=4`).

The configuration's guarantee on this route is the exact route's: every
count equals the number of rows of R within eps; so its `wrong_mask`
and `searched_mask` are `routes/exact.py`'s."""
import time

import numpy as np

from harness import route_module

_exact = route_module("exact")
wrong_mask, searched_mask = _exact.wrong_mask, _exact.searched_mask


def build(config: dict, R: np.ndarray, seed: int, spans: dict):
    """The built `JoinPlan` of the route (nothing to fit).  `spans`
    gets `place_r`: seconds from the start of the build until R is
    resident on every device of the placement."""
    import jax
    from repro.core import JoinPlan
    t0 = time.perf_counter()
    plan = (JoinPlan(R, config["metric"]).search("naive")
            .on(backend="auto", **config["placement"]).filter("none")
            .build())
    jax.block_until_ready(plan.engine.r_device)
    spans["place_r"] = time.perf_counter() - t0
    return plan
