"""The exact route: `filter("none")`, every query verified.

The configuration's guarantee on this route: every count equals the
number of rows of R within eps."""
import numpy as np


def build(config: dict, R: np.ndarray, seed: int, spans: dict):
    """The built `JoinPlan` of the route (nothing to fit)."""
    from repro.core import JoinPlan
    return (JoinPlan(R, config["metric"]).search("naive").on(backend="auto")
            .filter("none").build())


def wrong_mask(got: np.ndarray, exp: np.ndarray) -> np.ndarray:
    """bool [n]: answers that differ from the reference's counts."""
    return got != exp


def searched_mask(plan, Q: np.ndarray, eps: float) -> np.ndarray:
    """bool [len(Q)]: the queries the route sends to verify (all)."""
    return np.ones((len(Q),), bool)
