"""Public jit'd wrappers around the Pallas kernels.

Backend selection:
  * "pallas"  — the TPU kernel (interpret=True automatically on CPU, which
    executes the kernel body in Python for correctness validation).
  * "jnp"     — a blocked pure-jnp path (fast on this CPU container; same
    math, compiled by XLA:CPU). Used as the default off-TPU so benchmarks
    are not bottlenecked by interpret-mode overhead.
  * "auto"    — pallas on TPU, jnp elsewhere.

All padding/unpadding (row blocks, eps-chunk multiples, feature-dim
alignment) is handled here so kernels only ever see aligned shapes.
Kernel `interpret=` mode is derived from the runtime platform at these
call sites (`range_count.default_interpret`: compiled on TPU, interpret
elsewhere) — a TPU run can never silently interpret.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.adc_rank import (adc_rank_chain, adc_rank_jnp,
                                    adc_rank_pallas)
from repro.kernels.fused_mlp import mlp_forward_pallas
from repro.kernels.lsh_gather import (lsh_bucket_gather_jnp,
                                      lsh_bucket_gather_pallas)
from repro.kernels.range_count import range_count_hist_pallas


def _resolve(backend: str) -> str:
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return backend


def _pad_rows(x: jax.Array, mult: int) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)


def blocked_hist(q, r, eps_grid, *, metric: str, block_r: int, nr_valid: int):
    """Traceable lax.scan over R blocks: O(block) memory, XLA-fused
    compare+reduce. r.shape[0] must be a block_r multiple. This is the
    per-shard compute of the engine's sharded sweep (core/engine.py) —
    keep it jit-free so it composes under shard_map / outer jits. The
    dot runs at HIGHEST precision, like the kernel and the oracle, so
    eps-boundary verdicts agree across backends on TPU."""
    nr = r.shape[0]
    nblk = nr // block_r
    eps = eps_grid.astype(jnp.float32)
    qf = q.astype(jnp.float32)

    def body(carry, base):
        # a row slice, not a [nblk, block_r, d] reshape of R: R is read in
        # place whatever its layout (reshaping a column-major R copies it)
        blk = jax.lax.dynamic_slice_in_dim(r, base, block_r, axis=0)
        dots = jnp.matmul(qf, blk.astype(jnp.float32).T,
                          precision=jax.lax.Precision.HIGHEST)
        if metric == "cosine":
            d = 1.0 - dots
        else:
            d = jnp.sqrt(jnp.maximum(2.0 - 2.0 * dots, 0.0))
        valid = (base + jnp.arange(block_r)) < nr_valid
        d = jnp.where(valid[None, :], d, jnp.inf)
        cnt = jnp.sum(d[:, :, None] <= eps[None, None, :], axis=1, dtype=jnp.int32)
        return carry + cnt, None

    init = jnp.zeros((q.shape[0], eps.shape[0]), jnp.int32)
    bases = jnp.arange(nblk) * block_r
    out, _ = jax.lax.scan(body, init, bases)
    return out


_jnp_blocked_hist = functools.partial(
    jax.jit, static_argnames=("metric", "block_r", "nr_valid"))(blocked_hist)


def range_count_hist(q, r, eps_grid, *, metric: str = "cosine",
                     backend: str = "auto", block_q: int = 256,
                     block_r: int = 512, eps_chunk: int = 8) -> jax.Array:
    """counts[i, j] = #-neighbors of q[i] in r within eps_grid[j]. int32 [nq, m].

    Handles arbitrary nq/nr/m by padding; eps_grid must be sorted ascending.
    """
    q = jnp.asarray(q)
    r = jnp.asarray(r)
    eps_grid = jnp.asarray(eps_grid, jnp.float32)
    nq, m = q.shape[0], eps_grid.shape[0]
    nr = r.shape[0]
    be = _resolve(backend)

    if be == "ref":
        return ref.range_count_hist(q, r, eps_grid, metric)

    if be == "jnp":
        rp = _pad_rows(r, block_r)
        out = _jnp_blocked_hist(q, rp, eps_grid, metric=metric,
                                block_r=block_r, nr_valid=nr)
        return out

    if be == "pallas":
        qp = _pad_rows(q, block_q)
        rp = _pad_rows(r, block_r)
        mp = (-m) % eps_chunk
        # pad eps grid with +inf-like large values, slice the extra cols off
        egp = jnp.concatenate([eps_grid, jnp.full((mp,), jnp.inf, jnp.float32)])
        out = range_count_hist_pallas(qp, rp, egp, metric=metric, nr_valid=nr,
                                      block_q=block_q, block_r=block_r,
                                      eps_chunk=eps_chunk, interpret=None)
        return out[:nq, :m]

    raise ValueError(f"unknown backend {be!r}")


def range_count(q, r, eps: float, *, metric: str = "cosine",
                backend: str = "auto", **kw) -> jax.Array:
    """Neighbor count within a single eps. int32 [nq]."""
    return range_count_hist(q, r, jnp.asarray([eps], jnp.float32),
                            metric=metric, backend=backend, **kw)[:, 0]


def mlp_forward(params, x, *, backend: str = "auto", block_n: int = 256) -> jax.Array:
    """Fused estimator inference. params: tuple of (w, b [1,dout]) pairs."""
    x = jnp.asarray(x)
    be = _resolve(backend)
    if be in ("jnp", "ref"):
        return ref.mlp_forward(params, x)
    n = x.shape[0]
    xp = _pad_rows(x, block_n)
    out = mlp_forward_pallas(tuple((jnp.asarray(w), jnp.asarray(b)) for w, b in params),
                             xp, block_n=block_n, interpret=None)
    return out[:n]


def lsh_bucket_gather(tables, pb, *, backend: str = "auto",
                      block_q: int = 128) -> jax.Array:
    """LSH member-table gather + multiprobe dedup (kernels/lsh_gather.py).

    tables int32 [l, cap, B] (`lsh_gather.bucket_minor`), pb int32
    [q, l, n_probes].  Returns int32
    [q, l*n_probes*cap] candidate ids; duplicate probe blocks blanked to
    -1.  All backends are bit-identical by construction (integer-only);
    "ref"/"jnp" take the direct-gather formulation, "pallas" the fused
    one-hot kernel (interpret mode derived from the platform).  Safe to
    call inside jitted programs — padding here is traceable."""
    be = _resolve(backend)
    if be in ("jnp", "ref"):
        return lsh_bucket_gather_jnp(tables, pb)
    if be == "pallas":
        nq = pb.shape[0]
        pbp = _pad_rows(pb, block_q)
        return lsh_bucket_gather_pallas(tables, pbp, block_q=block_q)[:nq]
    raise ValueError(f"unknown backend {be!r}")


def adc_rank(q, codebooks, cand, codes, *, n_cand: int,
             backend: str = "auto", block_b: int = 8) -> jax.Array:
    """IVF-PQ ADC candidate ranking (kernels/adc_rank.py).

    q f32 [b, dim], codebooks f32 [m, 256, seg], cand int32 [b, C]
    (-1 padded), codes uint8 [n, m].  Returns the n_cand best candidate
    ids int32 [b, n_cand].  "jnp" (flat per-segment LUT accumulate) and
    "pallas" (fused kernel) are bit-identical by construction; "ref" is
    the pre-kernel transpose+take_along_axis+top_k chain (value-
    identical, tie order unspecified) kept as baseline/oracle.  Safe to
    call inside jitted programs."""
    be = _resolve(backend)
    if be == "ref":
        return adc_rank_chain(q, codebooks, cand, codes, n_cand=n_cand)
    if be == "jnp":
        return adc_rank_jnp(q, codebooks, cand, codes, n_cand=n_cand)
    if be == "pallas":
        b = q.shape[0]
        qp = _pad_rows(q, block_b)
        cp = jnp.concatenate(
            [cand, jnp.full((qp.shape[0] - b,) + cand.shape[1:], -1,
                            cand.dtype)], axis=0)
        return adc_rank_pallas(qp, codebooks, cp, codes, n_cand=n_cand,
                               block_b=block_b)[:b]
    raise ValueError(f"unknown backend {be!r}")
