"""Recursive Model Index estimator (Kraska et al.) — the paper's main model.

§VI-A configuration: three stages of 1 / 2 / 4 fully-connected networks,
each sub-model the 512/512/256/128 MLP. Training is the classic greedy
stage-by-stage procedure: stage k's prediction routes each tuple to a stage
k+1 child; children train on their routed subset (implemented as masked
losses so batches stay static for XLA).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.mlp import PAPER_WIDTHS, apply_mlp, init_mlp
from repro.models.train import fit_regressor
from repro.utils import memoize_device_fn

#: rows per device call when the fit predicts over its training tuples:
#: op-by-op over all of them, the [n, 512] activations of a paper-scale
#: fit (720,000 tuples) took the device's peak to 12.9 GB on a v5e
_FIT_PREDICT_ROWS = 1 << 16

_apply_jit = jax.jit(apply_mlp)


def _fit_predict(params, X: np.ndarray) -> np.ndarray:
    """`apply_mlp` over the training tuples, one compiled call per row
    slice, gathered on the host."""
    return np.concatenate([
        np.asarray(_apply_jit(params, jnp.asarray(X[i:i + _FIT_PREDICT_ROWS])))
        for i in range(0, len(X), _FIT_PREDICT_ROWS)])


class RMIEstimator:
    name = "rmi"

    def __init__(self, din: int, stage_sizes=(1, 2, 4), widths=PAPER_WIDTHS, *,
                 lr=1e-3, epochs=30, batch_size=512, seed=0, log_target=True):
        self.din = din
        self.stage_sizes = tuple(stage_sizes)
        self.widths = tuple(widths)
        self.lr, self.epochs, self.batch_size = lr, epochs, batch_size
        self.seed, self.log_target = seed, log_target
        key = jax.random.key(seed)
        self.stages = []
        for si, n_models in enumerate(self.stage_sizes):
            key, sub = jax.random.split(key)
            ks = jax.random.split(sub, n_models)
            self.stages.append([init_mlp(k, din, widths) for k in ks])
        self._ylo, self._yhi = 0.0, 1.0
        self._jit_route = jax.jit(self._routed_predict)

    # -- routing ------------------------------------------------------------
    def _route_ids(self, preds: jax.Array, n_children: int) -> jax.Array:
        """Map a (transformed) prediction to a child index by target range."""
        z = (preds - self._ylo) / max(self._yhi - self._ylo, 1e-9)
        return jnp.clip((z * n_children).astype(jnp.int32), 0, n_children - 1)

    def _routed_predict(self, stages_params, X):
        pred = apply_mlp(stages_params[0][0], X)
        for si in range(1, len(self.stage_sizes)):
            kids = stages_params[si]
            route = self._route_ids(pred, len(kids))
            all_preds = jnp.stack([apply_mlp(p, X) for p in kids], axis=1)
            pred = jnp.take_along_axis(all_preds, route[:, None], axis=1)[:, 0]
        return pred

    # -- fit/predict ----------------------------------------------------------
    def _transform(self, y):
        return np.log1p(y.astype(np.float32)) if self.log_target else y.astype(np.float32)

    def fit(self, X: np.ndarray, y: np.ndarray, weights=None):
        yt = self._transform(y)
        self._ylo, self._yhi = float(yt.min()), float(yt.max())
        base_w = np.ones((len(X),), np.float32) if weights is None else weights

        # stage 0: single root model on everything
        self.stages[0][0], loss = fit_regressor(
            self.stages[0][0], apply_mlp, X, yt, weights=base_w, lr=self.lr,
            epochs=self.epochs, batch_size=self.batch_size, seed=self.seed)

        pred = _fit_predict(self.stages[0][0], X)
        for si in range(1, len(self.stage_sizes)):
            kids = self.stages[si]
            route = np.asarray(self._route_ids(jnp.asarray(pred), len(kids)))
            new_pred = np.zeros_like(pred)
            for ci, child in enumerate(kids):
                mask = (route == ci).astype(np.float32) * base_w
                if mask.sum() < 2:  # child got (almost) nothing routed
                    continue
                kids[ci], _ = fit_regressor(
                    child, apply_mlp, X, yt, weights=mask, lr=self.lr,
                    epochs=self.epochs, batch_size=self.batch_size,
                    seed=self.seed + 17 * si + ci)
                cp = _fit_predict(kids[ci], X)
                new_pred = np.where(route == ci, cp, new_pred)
            pred = new_pred
        return loss

    def predict(self, X, *, backend: str = "auto") -> np.ndarray:
        stages_params = [list(s) for s in self.stages]
        raw = self._jit_route(stages_params, jnp.asarray(X))
        out = jnp.expm1(raw) if self.log_target else raw
        return np.asarray(out, np.float32)

    def device_predict_fn(self):
        """(params, fn) for the engine's fused filter program; the routing
        bounds (_ylo/_yhi) are baked in at trace time (post-fit), so fn is
        memoized per (log_target, ylo, yhi) — a refit invalidates it."""
        def build():
            log = self.log_target

            def fn(params, X):
                raw = self._routed_predict(params, X)
                return jnp.expm1(raw) if log else raw
            return fn
        key = (self.log_target, self._ylo, self._yhi)
        return [list(s) for s in self.stages], memoize_device_fn(self, key, build)

    # -- persistence ----------------------------------------------------------
    def state_dict(self) -> dict:
        out = {"kind": np.asarray("rmi"), "din": np.asarray(self.din),
               "stage_sizes": np.asarray(self.stage_sizes),
               "ylo": np.asarray(self._ylo), "yhi": np.asarray(self._yhi),
               "log_target": np.asarray(self.log_target)}
        for si, stage in enumerate(self.stages):
            for ci, params in enumerate(stage):
                for li, (w, b) in enumerate(params):
                    out[f"s{si}c{ci}w{li}"] = np.asarray(w)
                    out[f"s{si}c{ci}b{li}"] = np.asarray(b)
        return out

    def load_state_dict(self, d: dict):
        self._ylo, self._yhi = float(d["ylo"]), float(d["yhi"])
        self.log_target = bool(d["log_target"])
        n_layers = len(self.widths) + 1
        for si, stage in enumerate(self.stages):
            for ci in range(len(stage)):
                stage[ci] = tuple(
                    (jnp.asarray(d[f"s{si}c{ci}w{li}"]), jnp.asarray(d[f"s{si}c{ci}b{li}"]))
                    for li in range(n_layers))
