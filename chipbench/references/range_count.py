"""Plain reference of the range-count join: for every query q, the number
of rows r of R with d(q, r) <= eps.

Straightforward `jax.numpy`, imported from nothing of the program.  Rows
are unit-norm (the configurations state it), so cosine distance is
1 - q.r and l2 distance is sqrt(2 - 2 q.r).  The dot runs at the
precision asked for:

- "highest": float32 at `Precision.HIGHEST`, the precision the
  configurations state and the one every check compares against;
- "high": the control, one step below: `Precision.HIGH`, three
  bfloat16 passes (hi*hi + hi*lo + lo*hi, each operand split into a
  bfloat16 head and a bfloat16 tail), written out with
  `lax.reduce_precision` so that it means the same on every platform and
  no compiler can fold the split away;
- "bf16": one bfloat16 pass, the step below that.

Queries go in blocks of `q_block` rows, each swept over R in blocks of
`r_block` rows inside one compiled loop, so the distance block is
[q_block, r_block] whatever the sizes of Q and R.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high", "bf16")


def _dot(q, r, precision: str):
    """q [n, d] . r [m, d]^T in float32, at `precision`."""
    if precision == "highest":
        return jnp.matmul(q, r.T, precision=jax.lax.Precision.HIGHEST)

    def head(x):            # x rounded to bfloat16, kept in float32
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def bf16_dot(a, b):     # operands exact in bfloat16, float32 sums
        return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)

    if precision == "bf16":
        return bf16_dot(head(q), head(r))
    if precision == "high":
        q_hi, r_hi = head(q), head(r)
        q_lo, r_lo = head(q - q_hi), head(r - r_hi)
        return (bf16_dot(q_hi, r_hi) + bf16_dot(q_hi, r_lo)
                + bf16_dot(q_lo, r_hi))
    raise ValueError(f"precision {precision!r}: expected one of {PRECISIONS}")


@functools.partial(jax.jit, static_argnames=("metric", "precision",
                                             "r_block"))
def _block_counts(q, r, n_r, eps, *, metric: str, precision: str,
                  r_block: int):
    """Counts of the query block `q` against `r`, whose first `n_r` rows
    are R and the rest padding, taken `r_block` rows at a time."""
    def step(i, acc):
        rb = jax.lax.dynamic_slice_in_dim(r, i * r_block, r_block)
        dots = _dot(q, rb, precision)
        if metric == "cosine":
            dist = 1.0 - dots
        elif metric == "l2":
            dist = jnp.sqrt(jnp.maximum(2.0 - 2.0 * dots, 0.0))
        else:
            raise ValueError(f"metric {metric!r}: expected 'cosine' or 'l2'")
        real = i * r_block + jnp.arange(r_block) < n_r
        return acc + jnp.sum((dist <= eps) & real, axis=1, dtype=jnp.int32)
    return jax.lax.fori_loop(0, r.shape[0] // r_block, step,
                             jnp.zeros((q.shape[0],), jnp.int32))


def counts(Q: np.ndarray, R: np.ndarray, eps: float, metric: str, *,
           precision: str = "highest", q_block: int = 4096,
           r_block: int = 16384) -> np.ndarray:
    """int32 [len(Q)]: neighbours of each query within `eps`."""
    n_r, dim = R.shape
    r_block = min(r_block, -(-n_r // 8) * 8)
    pad = -n_r % r_block
    r = jnp.asarray(np.concatenate([R, np.zeros((pad, dim), R.dtype)])
                    if pad else R, jnp.float32)
    e = jnp.float32(eps)
    out = np.zeros((len(Q),), np.int32)
    for q0 in range(0, len(Q), q_block):
        blk = np.asarray(Q[q0:q0 + q_block], np.float32)
        n = len(blk)
        if n < q_block:
            blk = np.concatenate(
                [blk, np.zeros((q_block - n, blk.shape[1]), np.float32)])
        out[q0:q0 + n] = np.asarray(
            _block_counts(jnp.asarray(blk), r, n_r, e, metric=metric,
                          precision=precision, r_block=r_block))[:n]
    return out
