"""Minibatch trainer shared by all JAX regressors (no optax dependency)."""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import adam


def fit_regressor(params, apply_fn: Callable, X: np.ndarray, y: np.ndarray,
                  *, weights: Optional[np.ndarray] = None, lr: float = 1e-3,
                  epochs: int = 30, batch_size: int = 512, seed: int = 0,
                  log_every: int = 0) -> tuple:
    """MSE fit of apply_fn(params, X) -> y. Returns (params, last_loss).

    `weights` (0/1 or soft) implements the masked-subset training the RMI
    stages need without ragged batches.
    """
    n = X.shape[0]
    batch_size = min(batch_size, n)
    if weights is None:
        weights = np.ones((n,), np.float32)
    opt = adam(lr=lr)
    state = opt.init(params)

    @jax.jit
    def step(params, state, xb, yb, wb):
        def loss_fn(p):
            pred = apply_fn(p, xb)
            return jnp.sum(wb * (pred - yb) ** 2) / jnp.maximum(jnp.sum(wb), 1.0)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, state = opt.apply(params, state, grads)
        return params, state, loss

    rng = np.random.default_rng(seed)
    nb = max(1, n // batch_size)

    @jax.jit
    def epoch(params, state, Xj, yj, wj, perm):
        # one dispatch per epoch: the minibatch loop is a scan over the
        # same per-step update (a per-step host loop is bound by dispatch
        # latency on an accelerator)
        def body(carry, b):
            params, state, _ = carry
            idx = jax.lax.dynamic_slice_in_dim(perm, b * batch_size,
                                               batch_size)
            return step(params, state, Xj[idx], yj[idx], wj[idx]), None

        carry = (params, state, jnp.float32(jnp.inf))
        return jax.lax.scan(body, carry, jnp.arange(nb))[0]

    loss = np.inf
    Xj, yj, wj = jnp.asarray(X), jnp.asarray(y), jnp.asarray(weights)
    for ep in range(epochs):
        perm = jnp.asarray(rng.permutation(n))
        params, state, loss = epoch(params, state, Xj, yj, wj, perm)
        if log_every and (ep + 1) % log_every == 0:
            print(f"  epoch {ep+1}/{epochs} loss={float(loss):.5f}")
    return params, float(loss)
