"""Shared machinery for index-based joins: k-means, candidate verification.

The joins follow a host/device split that mirrors a production FAISS-on-TPU
style serving stack: index *probing* (data-dependent, pointer-heavy) runs on
host; candidate *verification* (dense distance math) runs on device in
static-shape blocks.

`verify_candidates` is also the engine's approximate-verification backend
(DESIGN.md §5): `JoinEngine` hands it a *device-resident* R (its padded
replica — candidate ids only ever index valid rows, so padding is inert)
and uses the non-blocking `dispatch_verify_candidates` form so candidate
verification overlaps the next batch's dispatch. The `backend` arg mirrors
the kernel matrix (DESIGN.md §2): "ref" verifies each chunk unpadded with
the oracle semantics; "jnp"/"auto"/"pallas" use the bucketed blocked path
(counts are identical — integer comparisons on the same f32 distances).
"""
from __future__ import annotations

import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import register_program_cache


def searcher_candidates(searcher, Q: np.ndarray, eps: float) -> np.ndarray:
    """Probe a Searcher for candidate ids, passing `eps` only when the
    probe is eps-aware (the protocol's `candidates(Q[, eps])` form,
    DESIGN.md §9). Grid needs the radius to size its cells; LSH / IVF-PQ /
    k-means-tree probes are radius-independent."""
    try:
        eps_aware = "eps" in inspect.signature(searcher.candidates).parameters
    except (TypeError, ValueError):         # builtins / C callables
        eps_aware = False
    if eps_aware:
        return searcher.candidates(Q, eps=float(eps))
    return searcher.candidates(Q)


def kmeans(X: np.ndarray, k: int, *, iters: int = 10, seed: int = 0,
           sample: int | None = 8192) -> np.ndarray:
    """Lloyd's k-means (jit'd distance steps). Returns centroids [k, d]."""
    rng = np.random.default_rng(seed)
    data = X[rng.choice(len(X), min(sample or len(X), len(X)), replace=False)]
    cent = data[rng.choice(len(data), k, replace=False)].copy()

    @jax.jit
    def assign(c, x):
        d = (jnp.sum(x * x, 1)[:, None] - 2 * x @ c.T + jnp.sum(c * c, 1)[None, :])
        return jnp.argmin(d, axis=1)

    for _ in range(iters):
        a = np.asarray(assign(jnp.asarray(cent), jnp.asarray(data)))
        for ci in range(k):
            mask = a == ci
            if mask.any():
                cent[ci] = data[mask].mean(axis=0)
            else:  # empty cluster: reseed on a random point
                cent[ci] = data[rng.integers(len(data))]
    return cent.astype(np.float32)


def assign_nearest(X: np.ndarray, centroids: np.ndarray, block: int = 4096) -> np.ndarray:
    @jax.jit
    def go(c, x):
        d = jnp.sum(x * x, 1)[:, None] - 2 * x @ c.T + jnp.sum(c * c, 1)[None, :]
        return jnp.argmin(d, axis=1)
    out = [np.asarray(go(jnp.asarray(centroids), jnp.asarray(X[i:i + block])))
           for i in range(0, len(X), block)]
    return np.concatenate(out)


def build_capacity_table(assignments: np.ndarray, n_buckets: int,
                         cap: int | None = None) -> np.ndarray:
    """Dense [n_buckets, cap] member table (-1 padded) from bucket ids:
    each bucket keeps its first `cap` members in row order."""
    order = np.argsort(assignments, kind="stable")
    sorted_b = assignments[order]
    counts = np.bincount(assignments, minlength=n_buckets)
    if cap is None:
        cap = max(int(counts.max()), 1)
    table = np.full((n_buckets, cap), -1, np.int32)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(len(order)) - starts[sorted_b]     # rank in its bucket
    keep = slot < cap
    table[sorted_b[keep], slot[keep]] = order[keep]
    return table


def _verify_block_impl(R, q, cand, eps, *, metric, tomb=None):
    """counts of unique candidates within eps. q [bq,d], cand [bq,C] (-1 pad).
    Traceable — composes under the blocked scan below. `tomb` is the
    optional int32 tombstone mask over R's rows (DESIGN.md §13): a
    candidate whose row is tombstoned never counts, on every backend."""
    cand_sorted = jnp.sort(cand, axis=1)
    dup = jnp.concatenate([jnp.zeros((cand.shape[0], 1), bool),
                           cand_sorted[:, 1:] == cand_sorted[:, :-1]], axis=1)
    valid = (cand_sorted >= 0) & ~dup
    if tomb is not None:
        valid &= tomb[jnp.maximum(cand_sorted, 0)] == 0
    x = R[jnp.maximum(cand_sorted, 0)]                   # [bq, C, d]
    dots = jnp.einsum("qcd,qd->qc", x.astype(jnp.float32), q.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    if metric == "cosine":
        d = 1.0 - dots
    else:
        d = jnp.sqrt(jnp.maximum(2.0 - 2.0 * dots, 0.0))
    return jnp.sum(valid & (d <= eps), axis=1, dtype=jnp.int32)


#: candidate-axis tile of the live-chunked verify below: the lcm of the
#: probe capacity quantum (engine.py `_stage_probe`) and the q block, so
#: typical LSH capacities (l * n_probes * cap) pad by < one tile
_LIVE_CHUNK = 64


def _verify_block_live(R, q, cand, eps, *, metric, tomb=None,
                       chunk=_LIVE_CHUNK):
    """`_verify_block_impl` with cost scaled to LIVE candidates, not probe
    capacity (DESIGN.md §15): multiprobe candidate lists are mostly -1
    padding (empty buckets, dedup blanks), yet the R-row gather — the
    verify's dominant cost — runs over the full width in the oracle form.
    Sorting each row DESCENDING packs live ids to the front, so a
    fori_loop with a traced trip count of ceil(max_live / chunk) gathers
    only chunks that contain a live id.  Counts stay bit-identical to the
    oracle: skipped chunks are all-pad (exactly zero contribution), each
    surviving (q, id) pair's distance is the same f32 dot reduced over the
    same axis, and the int32 partial sums add associatively."""
    bq, C = cand.shape
    cs = jnp.sort(cand, axis=1)[:, ::-1]
    dup = jnp.concatenate([jnp.zeros((bq, 1), bool),
                           cs[:, 1:] == cs[:, :-1]], axis=1)
    valid = (cs >= 0) & ~dup
    if tomb is not None:
        valid &= tomb[jnp.maximum(cs, 0)] == 0
    pad = (-C) % chunk
    if pad:
        cs = jnp.pad(cs, ((0, 0), (0, pad)), constant_values=-1)
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    n_live = jnp.max(jnp.sum(cs >= 0, axis=1))      # traced scalar bound

    def body(i, acc):
        c_sl = jax.lax.dynamic_slice_in_dim(cs, i * chunk, chunk, 1)
        v_sl = jax.lax.dynamic_slice_in_dim(valid, i * chunk, chunk, 1)
        x = R[jnp.maximum(c_sl, 0)]                  # [bq, chunk, d]
        dots = jnp.einsum("qcd,qd->qc", x.astype(jnp.float32),
                          q.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
        if metric == "cosine":
            d = 1.0 - dots
        else:
            d = jnp.sqrt(jnp.maximum(2.0 - 2.0 * dots, 0.0))
        return acc + jnp.sum(v_sl & (d <= eps), axis=1, dtype=jnp.int32)

    return jax.lax.fori_loop(0, (n_live + chunk - 1) // chunk, body,
                             jnp.zeros((bq,), jnp.int32))


@functools.partial(jax.jit, static_argnames=("metric", "block"))
def _verify_blocks(R, q, cand, eps, tomb=None, *, metric, block):
    """lax.map over q blocks — ONE device program for the whole candidate
    set (q rows % block == 0), peak memory still O(block * C * d); each
    block runs the live-chunked form above (its max-live bound is per q
    block, so dense rows never widen a sparse block's gather)."""
    nb = q.shape[0] // block
    qb = q.reshape(nb, block, q.shape[1])
    cb = cand.reshape(nb, block, cand.shape[1])
    out = jax.lax.map(
        lambda xc: _verify_block_live(R, xc[0], xc[1], eps, metric=metric,
                                      tomb=tomb),
        (qb, cb))
    return out.reshape(-1)


@functools.partial(jax.jit, static_argnames=("metric",))
def _verify_ref(R, q, cand, eps, tomb=None, *, metric):
    """Unblocked oracle form — no padding, one program per chunk shape
    (mirrors the "ref" row of the DESIGN.md §2 matrix)."""
    return _verify_block_impl(R, q, cand, eps, metric=metric, tomb=tomb)


def localized_shard_verify(r_axis, shard_rows, metric, block, backend):
    """Per-shard candidate verification against an R row-sharded over
    `r_axis`: `shard_fn(rs, qb, cb, e, tb=None)` localizes the global
    candidate ids to this device's row range ([me*shard_rows,
    (me+1)*shard_rows) -> masked to -1 outside), verifies them against
    the resident shard, and `psum`s the counts over `r_axis`. A candidate
    id maps to exactly one shard, so the per-shard sort/dedup of
    `_verify_block_impl` stays correct and R's padding rows (never
    referenced by valid ids) stay inert. `tb` is the local slice of the
    tombstone mask (sharded exactly like R, so the localized ids index
    it directly — DESIGN.md §13). The SINGLE implementation behind
    `_sharded_verify_program` (host probing) and `probe.py`'s ring
    verify programs (device probing, DESIGN.md §11) — the two routes
    cannot diverge."""
    def shard_fn(rs, qb, cb, e, tb=None):
        lo = jax.lax.axis_index(r_axis) * shard_rows
        local = cb - lo
        keep = (cb >= 0) & (local >= 0) & (local < shard_rows)
        cl = jnp.where(keep, local, -1).astype(jnp.int32)
        if backend == "ref" or qb.shape[0] % block != 0:
            cnt = _verify_block_impl(rs, qb, cl, e, metric=metric, tomb=tb)
        else:
            cnt = _verify_blocks(rs, qb, cl, e, tb, metric=metric,
                                 block=block)
        return jax.lax.psum(cnt, r_axis)

    return shard_fn


@register_program_cache
@functools.lru_cache(maxsize=64)
def _sharded_verify_program(mesh, r_axis, data_axis, shard_rows, metric,
                            block, backend, has_tomb=False):
    """Candidate verification against an R row-sharded over `r_axis`
    (the ring topology, DESIGN.md §10): `localized_shard_verify` mapped
    over the mesh. The query/candidate chunk additionally shards over
    `data_axis` whenever its (block-bucketed) row count divides evenly —
    the data columns split the work instead of repeating it. `has_tomb`
    keys the program on whether a tombstone mask rides along (shard_map
    in_specs are fixed-arity — DESIGN.md §13). Cached per (mesh,
    geometry); evicted by `engine.clear_program_cache`."""
    from repro.core.topology import _data_size, _shard_mapped
    from jax.sharding import PartitionSpec as P

    ndata = _data_size(mesh, data_axis)
    shard_fn = localized_shard_verify(r_axis, shard_rows, metric, block,
                                      backend)

    def run(rs, qb, cb, e, tb=None):
        # rows are static at trace time, so the placement choice is too;
        # jit caches one executable per chunk-shape bucket either way
        qspec = P(data_axis) if (ndata > 1 and qb.shape[0] % ndata == 0
                                 and (backend == "ref"
                                      or (qb.shape[0] // ndata) % block == 0)
                                 ) else P()
        in_specs = (P(r_axis), qspec, qspec, P())
        if has_tomb:
            in_specs += (P(r_axis),)        # tomb shards exactly like R
            mapped = _shard_mapped(shard_fn, mesh, in_specs=in_specs,
                                   out_specs=qspec)
            return mapped(rs, qb, cb, e, tb)
        mapped = _shard_mapped(shard_fn, mesh, in_specs=in_specs,
                               out_specs=qspec)
        return mapped(rs, qb, cb, e)

    return jax.jit(run)


class PendingCounts:
    """In-flight candidate verification: per-chunk device arrays with their
    host copies already started. `result()` is the only blocking point."""

    def __init__(self, parts: list, n: int):
        self._parts = parts                 # [(device_counts, lo, hi)]
        self._n = n

    def result(self) -> np.ndarray:
        """Materialize the int32 [q] counts (blocking if still computing)."""
        out = np.zeros((self._n,), np.int32)
        for cnt, lo, hi in self._parts:
            out[lo:hi] = np.asarray(cnt)[: hi - lo]
        return out


def dispatch_verify_candidates(R, Q: np.ndarray, cand_ids: np.ndarray,
                               eps: float, metric: str, *, block: int = 32,
                               chunk: int = 8192, backend: str = "auto",
                               mesh=None, r_axis: str | None = None,
                               data_axis: str = "data",
                               shard_rows: int = 0, tomb=None) -> PendingCounts:
    """Non-blocking form of `verify_candidates`: dispatches every chunk's
    device program, kicks off async device→host copies, and returns a
    `PendingCounts` handle. `R` may be a host array or an already
    device-resident replica (e.g. `JoinEngine`'s padded R — candidate ids
    never reference padding rows, so the extra rows are inert). `tomb`
    optionally masks tombstoned R rows out of the counts (DESIGN.md §13;
    sharded like R on ring placements).

    When `R` is row-sharded over a mesh axis (the ring topology), pass
    `mesh`, `r_axis`, and `shard_rows` (rows per shard): each device then
    verifies only the ids landing in its own shard and the counts are
    `psum`'d over `r_axis` — R is never gathered."""
    from repro.core.engine import _bucket_size, _start_host_copy
    from repro.kernels import ops
    backend = ops._resolve(backend)
    n = len(Q)
    Rj = R if isinstance(R, jax.Array) else jnp.asarray(R)
    sharded = mesh is not None and r_axis is not None
    if sharded:
        prog = _sharded_verify_program(mesh, r_axis, data_axis,
                                       int(shard_rows), metric, block,
                                       backend, tomb is not None)
    parts = []
    for i in range(0, n, chunk):
        j = min(i + chunk, n)
        if backend == "ref":
            qb = jnp.asarray(Q[i:j], jnp.float32)
            cb = jnp.asarray(cand_ids[i:j], jnp.int32)
        else:
            n_pad = _bucket_size(j - i, block)
            qh = np.zeros((n_pad,) + Q.shape[1:], np.float32)
            qh[:j - i] = Q[i:j]
            ch = np.full((n_pad,) + cand_ids.shape[1:], -1, np.int32)
            ch[:j - i] = cand_ids[i:j]
            qb, cb = jnp.asarray(qh), jnp.asarray(ch)
        if sharded:
            cnt = prog(Rj, qb, cb, jnp.float32(eps), tomb)
        elif backend == "ref":
            cnt = _verify_ref(Rj, qb, cb, jnp.float32(eps), tomb,
                              metric=metric)
        else:
            cnt = _verify_blocks(Rj, qb, cb, jnp.float32(eps), tomb,
                                 metric=metric, block=block)
        _start_host_copy(cnt)
        parts.append((cnt, i, j))
    return PendingCounts(parts, n)


def verify_candidates(R, Q: np.ndarray, cand_ids: np.ndarray,
                      eps: float, metric: str, *, block: int = 32,
                      chunk: int = 8192, backend: str = "auto",
                      mesh=None, r_axis: str | None = None,
                      data_axis: str = "data",
                      shard_rows: int = 0, tomb=None) -> np.ndarray:
    """Exact verification of candidate lists. cand_ids [q, C] int32 (-1 pad).
    Returns int32 [q] counts of unique true neighbors among candidates.
    Queries are padded to a bucketed multiple of `block` (bounded
    recompiles) and verified in one device call per `chunk` — the chunk
    bounds device residency of the [q, C] candidate matrix; typical query
    sets fit in a single call. `backend` selects the §2 compute path
    ("ref" = unpadded oracle); counts are backend-invariant. Pass
    `mesh`/`r_axis`/`shard_rows` to verify against a row-sharded R
    (see `dispatch_verify_candidates`).
    """
    return dispatch_verify_candidates(R, Q, cand_ids, eps, metric,
                                      block=block, chunk=chunk,
                                      backend=backend, mesh=mesh,
                                      r_axis=r_axis, data_axis=data_axis,
                                      shard_rows=shard_rows,
                                      tomb=tomb).result()
