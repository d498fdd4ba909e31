"""Pure-jnp oracles for every Pallas kernel in this package.

These are the correctness references the kernel tests sweep against
(shapes x dtypes, assert_allclose). They are deliberately unblocked and
simple — clarity over speed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pair_distances(q: jax.Array, r: jax.Array, metric: str) -> jax.Array:
    """Distances between unit-normalized rows of q [nq,d] and r [nr,d].

    The dot runs at HIGHEST precision: on TPU the default rounds f32
    operands to bf16, which moves distances across an eps boundary."""
    dots = jnp.einsum("qd,rd->qr", q.astype(jnp.float32), r.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    if metric == "cosine":
        return 1.0 - dots
    if metric == "l2":
        return jnp.sqrt(jnp.maximum(2.0 - 2.0 * dots, 0.0))
    raise ValueError(f"unknown metric {metric!r}")


def range_count_hist(q: jax.Array, r: jax.Array, eps_grid: jax.Array,
                     metric: str = "cosine") -> jax.Array:
    """counts[i, j] = #{rows r_k of r : d(q_i, r_k) <= eps_grid[j]}.  int32 [nq, m].

    eps_grid must be sorted ascending. Oracle for kernels/range_count.py.
    """
    d = pair_distances(q, r, metric)                       # [nq, nr]
    cmp = d[:, :, None] <= eps_grid[None, None, :].astype(jnp.float32)
    return jnp.sum(cmp, axis=1, dtype=jnp.int32)           # [nq, m]


def range_count(q: jax.Array, r: jax.Array, eps: float, metric: str = "cosine") -> jax.Array:
    """counts[i] = #-neighbors of q_i within eps. int32 [nq]."""
    return range_count_hist(q, r, jnp.asarray([eps]), metric)[:, 0]


def mlp_forward(params, x: jax.Array) -> jax.Array:
    """ReLU MLP regressor forward. params: list of (w [din,dout], b [1,dout]).

    Returns f32 [n] (last layer must have dout == 1). Oracle for
    kernels/fused_mlp.py.
    """
    h = x.astype(jnp.float32)
    for i, (w, b) in enumerate(params):
        h = h @ w.astype(jnp.float32) + b.astype(jnp.float32)
        if i < len(params) - 1:
            h = jnp.maximum(h, 0.0)
    return h[:, 0]
