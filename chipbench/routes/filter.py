"""The filter route: the configuration's Xling filter in front of verify.

The configuration's guarantee on this route: a query is skipped (count
0) or verified (its exact count).  How many true pairs the skips cost is
the check's `missed_pair_frac`."""
import numpy as np

from harness import derived_seed, timed_calls


def build(config: dict, R: np.ndarray, seed: int, spans: dict):
    """The built `JoinPlan`, its filter fitted with the seed's training
    seed; the fit's two sub-steps are timed into `spans`
    (`fit_ground_truth`, `fit_train`)."""
    from repro.core import JoinPlan
    from repro.core import xling
    from repro.models import ESTIMATORS

    f = config["filter"]
    plan = (JoinPlan(R, config["metric"]).search("naive").on(backend="auto")
            .filter(f["kind"], tau=f["tau"], xdt=f["xdt"],
                    fpr_tolerance=f["fpr_tolerance"], estimator=f["estimator"],
                    m=f["m"], s=f["s"], epochs=f["epochs"],
                    seed=derived_seed(seed, 1),
                    estimator_kwargs={"stage_sizes": tuple(f["stage_sizes"]),
                                      "widths": tuple(f["widths"])}))
    with timed_calls(spans, {"fit_ground_truth": (xling, "cardinality_table"),
                             "fit_train": (ESTIMATORS[f["estimator"]], "fit")}):
        plan.build()
    return plan


def wrong_mask(got: np.ndarray, exp: np.ndarray) -> np.ndarray:
    """bool [n]: answers that are neither 0 (skipped) nor the reference's
    count (verified)."""
    return (got != exp) & (got != 0)


def searched_mask(plan, Q: np.ndarray, eps: float) -> np.ndarray:
    """bool [len(Q)]: the queries the plan's own filter program sends to
    verify, read from the engine's filter stage as `run` dispatches it."""
    predict, threshold = plan._filter_state(eps)
    st = plan.build()._built.engine._stage_filter(
        Q, eps, predict=predict, threshold=threshold)
    return np.asarray(st.pos_dev)[:len(Q)].astype(bool)
