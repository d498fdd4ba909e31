"""Device-resident probing layer (DESIGN.md §11).

The engine's approximate verification (DESIGN.md §5) used to split every
batch across the PCIe boundary: candidate *verification* ran on device,
but the index *probe* that produces the candidates ran in NumPy on the
host — a device→host→device round trip inside every streamed batch,
exactly the sync the async pipeline was built to avoid.  This module
moves probing onto the mesh: FALCONN-style LSH multiprobe (hyperplane +
p-stable) and the FAISS-style IVF-PQ coarse quantizer + ADC ranking are
dense einsum + gather workloads, so they compile into the same
bucketed-static-shape device programs as the range-count sweep.

Three layers:

  * **Shared probing math** — `lsh_hash_codes` / `lsh_bucket_ids` /
    `lsh_probe_buckets` / `ivfpq_candidates` are jitted jnp functions
    used by BOTH the host path (`LSHJoin.candidates`,
    `IVFPQJoin.candidates` call them and pull the result back) and the
    device probe programs.  One source of truth means device-probe
    candidates are bit-identical to host-probe candidates — the parity
    the subprocess tests enforce.
  * **Probe specs + the adapter registry** — a Searcher advertises the
    capability with `device_probe(eps)` (the `DeviceSearcher` half of
    the DESIGN.md §9 protocol, analogous to `Filter.device_filter`),
    returning a spec (`LSHProbe` / `IVFPQProbe`) or None.  Third-party
    searchers that cannot grow the method register a builder in
    `PROBE_BUILDERS`; `as_device_probe` resolves either form, and
    host-only searchers (grid, kmeans-tree, plug-ins) simply keep the
    host path.
  * **Placed probes** — `spec.place(engine)` uploads the probe tables
    once, pinned like R, with placement chosen per topology
    (`core/topology.py::Topology.probe_shards`): replicated by default;
    under `"ring"` the LSH member tables are row-partitioned over the
    `r` axis (`_shard_lsh_tables` — each shard's table holds exactly
    the global table's ids that land in its R shard, so candidate ids
    stay local and R is never gathered), while the IVF-PQ tables stay
    replicated because ADC ranking is a global top-k.  The returned
    `PlacedProbe` exposes `probe(qpos)` (candidate generation) and
    `verify(...)` (candidate verification + scatter) as separately
    dispatchable device programs, which is what lets the engine stage
    batch k+1's probing while batch k verifies (DESIGN.md §11 staging).

Compiled programs live in module-level `lru_cache`s keyed ONLY on static
geometry (mesh, metric, probe shape) — table arrays are runtime
arguments — so engines sharing a geometry share executables.  Every one
is registered in `engine._PROGRAM_CACHES` via `register_program_cache`
(enforced by xlint's cache-registry rule, DESIGN.md §12), so
`engine.clear_program_cache()` can never silently miss one.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.engine import register_program_cache
from repro.core.joins.common import (_verify_block_impl, _verify_blocks,
                                     localized_shard_verify)
from repro.core.topology import _data_size, _shard_mapped
from repro.kernels import ops
from repro.kernels.lsh_gather import bucket_minor

# ====================================================== shared LSH math
# Bucket combination runs in int32 with two's-complement wraparound on
# host AND device (the sum of salted codes is reduced mod 2**32 before
# the mod-n_buckets): identical residues everywhere, no x64 dependency.


def _lsh_codes(X, proj, bias, *, metric: str, W: float):
    """int32 [n, l, k] hash codes: hyperplane sign bits (cosine) or
    p-stable quantized projections (l2)."""
    h = jnp.einsum("nd,lkd->nlk", X.astype(jnp.float32),
                   proj.astype(jnp.float32))
    if metric == "cosine":
        return (h > 0).astype(jnp.int32)
    return jnp.floor((h + bias[None]) / jnp.float32(W)).astype(jnp.int32)


def _lsh_combine(codes, salt32, n_buckets: int):
    """int32 [n, l] bucket ids from salted-code sums (int32 wraparound;
    `jnp.mod` keeps the result non-negative)."""
    mixed = jnp.sum(codes * salt32[None], axis=2, dtype=jnp.int32)
    return jnp.mod(mixed, jnp.int32(n_buckets))


def _lsh_multiprobe(codes, salt32, *, metric: str, n_probes: int,
                    n_buckets: int):
    """int32 [n, l, n_probes] probe bucket ids: the identity probe plus
    single-coordinate perturbations (bit-flip / ±1), FALCONN-style
    structured multiprobe. The schedule is a trace-time Python loop so
    host and device paths share it exactly."""
    probes = [_lsh_combine(codes, salt32, n_buckets)]
    for j in range(codes.shape[2]):
        if len(probes) >= n_probes:
            break
        if metric == "cosine":
            pert = codes.at[:, :, j].set(1 - codes[:, :, j])
        else:
            pert = codes.at[:, :, j].add(1 if j % 2 == 0 else -1)
        probes.append(_lsh_combine(pert, salt32, n_buckets))
    while len(probes) < n_probes:
        probes.append(probes[0])
    return jnp.stack(probes[:n_probes], axis=2)


@functools.partial(jax.jit, static_argnames=("metric", "W"))
def _lsh_codes_fn(X, proj, bias, *, metric, W):
    return _lsh_codes(X, proj, bias, metric=metric, W=W)


@functools.partial(jax.jit, static_argnames=("n_buckets",))
def _lsh_combine_fn(codes, salt32, *, n_buckets):
    return _lsh_combine(codes, salt32, n_buckets)


@functools.partial(jax.jit,
                   static_argnames=("metric", "W", "n_probes", "n_buckets"))
def _lsh_probe_fn(X, proj, bias, salt32, *, metric, W, n_probes, n_buckets):
    codes = _lsh_codes(X, proj, bias, metric=metric, W=W)
    return _lsh_multiprobe(codes, salt32, metric=metric, n_probes=n_probes,
                           n_buckets=n_buckets)


#: rows per device call of the host hashing entries: an index of millions
#: of rows is hashed in slices, so its [n, l, k] codes never sit on the
#: device at once
_HASH_CHUNK = 1 << 18


def _by_rows(fn, X) -> np.ndarray:
    """fn over row slices of X (at most `_HASH_CHUNK` rows per device
    call), concatenated on host; fn is row-wise."""
    n = len(X)
    if n <= _HASH_CHUNK:
        return np.asarray(fn(X))
    return np.concatenate([np.asarray(fn(X[i:i + _HASH_CHUNK]))
                           for i in range(0, n, _HASH_CHUNK)])


def lsh_hash_codes(X, proj, bias, *, metric: str, W: float) -> np.ndarray:
    """Host entry: int32 [n, l, k] codes via the shared device math —
    the single implementation behind table build, host probing, and the
    device probe programs (bit parity by construction)."""
    proj, bias = jnp.asarray(proj, jnp.float32), jnp.asarray(bias, jnp.float32)
    return _by_rows(lambda x: _lsh_codes_fn(
        jnp.asarray(x, jnp.float32), proj, bias, metric=metric, W=float(W)),
        X)


def lsh_bucket_ids(codes, salt, n_buckets: int) -> np.ndarray:
    """Host entry: int32 [n, l] bucket ids for table build (same int32
    wraparound combine as probing — build and probe can never skew)."""
    salt32 = jnp.asarray(np.asarray(salt, np.int64).astype(np.int32))
    return _by_rows(lambda c: _lsh_combine_fn(
        jnp.asarray(c, jnp.int32), salt32, n_buckets=int(n_buckets)), codes)


def _bucket_rows(X: np.ndarray) -> np.ndarray:
    """Zero-pad query rows to the engine's 64-row bucket quantum so the
    jitted host wrappers compile once per bucket, not once per distinct
    batch size (probing is row-independent; padding rows are sliced off
    by the caller)."""
    from repro.core.engine import _bucket_size, _pad_rows_np
    X = np.asarray(X, np.float32)
    return _pad_rows_np(X, _bucket_size(max(len(X), 1), 64))


def lsh_probe_buckets(X, proj, bias, salt, *, metric: str, W: float,
                      n_probes: int, n_buckets: int) -> np.ndarray:
    """Host entry: int32 [q, l, n_probes] multiprobe bucket ids."""
    n = len(X)
    return np.asarray(_lsh_probe_fn(
        jnp.asarray(_bucket_rows(X)), jnp.asarray(proj, jnp.float32),
        jnp.asarray(bias, jnp.float32),
        jnp.asarray(np.asarray(salt, np.int64).astype(np.int32)),
        metric=metric, W=float(W), n_probes=int(n_probes),
        n_buckets=int(n_buckets)))[:n]


# ==================================== bucket histograms + re-bucketing
# Skew-aware re-bucketing (DESIGN.md §16): when a few LSH buckets are
# far above the mean occupancy (clustered data), the auto capacity —
# sized at the occupancy p99.9 — is gated by that hot tail: every bucket
# slot pays the hot bucket's width, and under the ring topology the one
# shard holding the cluster's rows gates the whole SPMD sweep.  The
# transform splits each hot bucket's ROWS on extra hyperplanes
# (median-thresholded so the split is balanced) into `fanout` child
# buckets appended after the original id space, and records an
# `expand[l, B, fanout]` map; probing keeps the ORIGINAL multiprobe
# schedule and simply expands every probed bucket to all of its children
# (non-hot buckets expand to themselves + an always-empty filler
# bucket).  Because a query probes every child of each probed bucket,
# the candidate id SET per query is exactly the pre-split set whenever
# no bucket overflows its capacity — bit-identical verified counts (the
# parity the tests enforce) — while the per-bucket capacity drops to the
# post-split occupancy and capacity overflow can only shrink (hot
# buckets now own `fanout` slots).


def bucket_occupancy(tables: np.ndarray) -> np.ndarray:
    """Retained-entry occupancy histogram int64 [l, B] of a member table
    [l, B, cap] (-1 padded) — the planner's skew measurement input."""
    return (np.asarray(tables) >= 0).sum(axis=2)


def bucket_skew_stats(occ: np.ndarray) -> dict:
    """Skew summary of an occupancy histogram [l, B] (flattened): Gini
    coefficient, top-16 mass fraction, and the max/mean-nonzero ratio
    (`hot_factor` — the planner's re-bucketing trigger scale)."""
    flat = np.sort(np.asarray(occ, np.float64).reshape(-1))
    total = float(flat.sum())
    n = len(flat)
    if total <= 0 or n == 0:
        return {"gini": 0.0, "top16_mass": 0.0, "hot_factor": 0.0,
                "mean_nonzero": 0.0, "max": 0}
    cum = np.cumsum(flat)
    gini = float(1.0 - 2.0 * np.sum(cum) / (total * n) + 1.0 / n)
    nz = flat[flat > 0]
    return {
        "gini": gini,
        "top16_mass": float(flat[-16:].sum() / total),
        "hot_factor": float(flat[-1] / nz.mean()),
        "mean_nonzero": float(nz.mean()),
        "max": int(flat[-1]),
    }


def split_hot_buckets(buckets: np.ndarray, X: np.ndarray, *,
                      n_buckets: int, hot_factor: float,
                      max_fanout: int = 8, seed: int = 0):
    """Split hot buckets of a raw assignment [n, l] on extra hyperplanes.

    A bucket is HOT when its occupancy exceeds ``max(hot_factor *
    mean-nonzero-occupancy, 4)``.  Each hot bucket's rows are
    partitioned by the sign pattern of ``log2(fanout)`` fresh random
    projections, thresholded at the per-(table, bucket, plane) MEDIAN so
    children come out balanced for any metric.  Children are appended
    after the original ``n_buckets`` ids plus one trailing always-empty
    filler bucket (the expansion slot for non-hot buckets).

    Returns ``None`` when nothing is hot, else ``(buckets2 [n, l],
    expand [l, n_buckets, fanout] int32, n_total_buckets, info)`` where
    ``info`` is the machine-readable summary `JoinPlan.explain()`
    surfaces.  The transform only relabels rows — the union of any
    original bucket's children is exactly that bucket's row set, the
    candidate-set-preservation invariant."""
    buckets = np.asarray(buckets)
    n, l = buckets.shape
    occ = np.stack([np.bincount(buckets[:, t], minlength=n_buckets)
                    for t in range(l)])
    nz = occ[occ > 0]
    mean_nz = float(nz.mean()) if len(nz) else 0.0
    threshold = max(hot_factor * mean_nz, 4.0)
    hot = occ > threshold
    if not hot.any():
        return None
    max_occ = int(occ.max())
    fanout = 2
    while fanout < max_fanout and max_occ / fanout > threshold:
        fanout *= 2
    s = int(math.log2(fanout))
    rng = np.random.default_rng(seed)
    proj2 = rng.normal(size=(l, s, X.shape[1])).astype(np.float32)
    H = np.einsum("nd,lsd->nls", np.asarray(X, np.float32), proj2)
    n_hot_max = int(hot.sum(axis=1).max())
    filler = n_buckets + n_hot_max * fanout
    n_total = filler + 1
    expand = np.full((l, n_buckets, fanout), filler, np.int32)
    expand[:, :, 0] = np.arange(n_buckets, dtype=np.int32)[None, :]
    buckets2 = buckets.copy()
    for t in range(l):
        for i, b in enumerate(np.nonzero(hot[t])[0]):
            base = n_buckets + i * fanout
            expand[t, b] = base + np.arange(fanout, dtype=np.int32)
            rows = np.nonzero(buckets[:, t] == b)[0]
            bits = np.zeros(len(rows), np.int32)
            for j in range(s):
                h = H[rows, t, j]
                bits |= (h > np.median(h)).astype(np.int32) << j
            buckets2[rows, t] = base + bits
    occ2 = np.stack([np.bincount(buckets2[:, t], minlength=n_total)
                     for t in range(l)])
    info = {
        "n_hot": int(hot.sum()),
        "fanout": fanout,
        "threshold": float(threshold),
        "max_occ_before": max_occ,
        "max_occ_after": int(occ2.max()),
        "n_total_buckets": n_total,
    }
    return buckets2, expand, n_total, info


# =================================================== shared IVF-PQ math
_IVFPQ_BLOCK = 64      # query tile of the blocked ADC scan


def _sq_dists(a, b):
    return (jnp.sum(a * a, 1)[:, None] - 2.0 * a @ b.T
            + jnp.sum(b * b, 1)[None, :])


def _ivfpq_block(qb, centroids, lists, codes, codebooks, *, n_probe: int,
                 n_cand: int, backend: str = "jnp"):
    """One query tile: coarse-quantize, gather the probed lists, ADC-rank
    the pool, keep the best n_cand ids. int32 [b, n_cand] (-1 padded).

    The ADC ranking dispatches through `ops.adc_rank`
    (kernels/adc_rank.py): the fused flash-style kernel under
    backend="pallas", the bit-identical flat-LUT jnp formulation for
    every other backend — host probing and "ref"-backend engines take
    the jnp path too, so host/device candidate parity holds across the
    whole backend matrix."""
    b = qb.shape[0]
    dc = _sq_dists(qb, centroids)
    _, probed = jax.lax.top_k(-dc, n_probe)                # [b, P]
    cand = lists[probed].reshape(b, -1)                    # [b, P*cap]
    be = "pallas" if backend == "pallas" else "jnp"
    return ops.adc_rank(qb, codebooks, cand, codes, n_cand=n_cand,
                        backend=be)


@functools.partial(jax.jit, static_argnames=("n_probe", "n_cand", "backend"))
def _ivfpq_probe_fn(q, centroids, lists, codes, codebooks, *, n_probe,
                    n_cand, backend="jnp"):
    # tile size divides the (static) row count exactly: the full ADC tile
    # when rows are a 64-multiple (the host wrapper and the engine's
    # default capacity buckets), its gcd otherwise (small block_q engines
    # whose padded batch is shorter than one tile)
    blk = math.gcd(q.shape[0], _IVFPQ_BLOCK)
    nb = q.shape[0] // blk
    qb = q.reshape(nb, blk, q.shape[1])
    out = jax.lax.map(
        lambda x: _ivfpq_block(x, centroids, lists, codes, codebooks,
                               n_probe=n_probe, n_cand=n_cand,
                               backend=backend), qb)
    return out.reshape(nb * blk, -1)


def ivfpq_candidates(Q, centroids, lists, codes, codebooks, *, n_probe: int,
                     n_cand: int) -> np.ndarray:
    """Host entry: ADC-ranked candidate ids int32 [q, n_cand] (-1 padded)
    via the shared blocked device math (`IVFPQJoin.candidates` delegates
    here; the device probe program runs the identical tiles)."""
    Q = np.asarray(Q, np.float32)
    n = len(Q)
    if n == 0:
        return np.empty((0, n_cand), np.int32)
    qp = _bucket_rows(Q)                   # 64-row buckets: one compile
    out = _ivfpq_probe_fn(jnp.asarray(qp), jnp.asarray(centroids),
                          jnp.asarray(lists), jnp.asarray(codes),
                          jnp.asarray(codebooks), n_probe=int(n_probe),
                          n_cand=int(n_cand))
    return np.asarray(out)[:n]


# ============================================= compiled device programs
def _whole_on_each_device(fn, mesh, n_args: int):
    """`fn` run whole on every device of `mesh`, inputs and output
    replicated.  A program holding a Pallas kernel must be mapped this
    way on a multi-device mesh: the compiler cannot partition a Mosaic
    kernel, even over replicated operands."""
    if mesh is None or mesh.size == 1:
        return fn
    return _shard_mapped(fn, mesh, in_specs=(P(),) * n_args, out_specs=P())


@register_program_cache
@functools.lru_cache(maxsize=128)
def _gather_program(mesh, data_axis):
    """Compiled positive-compaction gather `(q, pos, *, capacity) ->
    (qpos [capacity, d], idx [capacity])`, output replicated so the
    probe programs see the whole compacted block. Padding lanes point at
    row 0; the verify scatter masks their contribution to 0."""
    def run(q, pos, *, capacity: int):
        idx = jnp.nonzero(pos, size=capacity, fill_value=0)[0] \
                 .astype(jnp.int32)
        qpos = jnp.take(q, idx, axis=0)
        if mesh is not None:
            rep = NamedSharding(mesh, P())
            qpos = jax.lax.with_sharding_constraint(qpos, rep)
            idx = jax.lax.with_sharding_constraint(idx, rep)
        return qpos, idx

    return jax.jit(run, static_argnames=("capacity",))


@register_program_cache
@functools.lru_cache(maxsize=128)
def _lsh_probe_program(mesh, metric, W, n_probes, n_buckets, backend="jnp"):
    """Compiled replicated LSH probe `(qpos, proj, bias, salt, tables) ->
    cand [q, l*p*cap]` — tables are runtime args, so every engine with
    this geometry shares one executable.  The member-table gather +
    multiprobe dedup runs through `ops.lsh_bucket_gather`
    (kernels/lsh_gather.py): the fused Pallas kernel under
    backend="pallas", the bit-identical direct-gather formulation
    otherwise; every device of `mesh` probes the whole batch."""
    def run(qpos, proj, bias, salt, tables):
        codes = _lsh_codes(qpos, proj, bias, metric=metric, W=W)
        pb = _lsh_multiprobe(codes, salt, metric=metric, n_probes=n_probes,
                             n_buckets=n_buckets)
        return ops.lsh_bucket_gather(tables, pb, backend=backend)

    return jax.jit(_whole_on_each_device(run, mesh, 5))


@register_program_cache
@functools.lru_cache(maxsize=128)
def _lsh_ring_probe_program(mesh, r_axis, metric, W, n_probes, n_buckets,
                            backend="jnp"):
    """Compiled ring LSH probe: each device probes its OWN per-shard
    member table (`_shard_lsh_tables` row-partition), producing the
    candidate axis sharded over `r` — ids stay local to the R shard that
    will verify them, and neither tables nor candidates are gathered.
    The per-shard gather dispatches through `ops.lsh_bucket_gather` like
    the replicated program (same kernel, per-shard tables)."""
    def shard_fn(qpos, proj, bias, salt, tables):
        codes = _lsh_codes(qpos, proj, bias, metric=metric, W=W)
        pb = _lsh_multiprobe(codes, salt, metric=metric, n_probes=n_probes,
                             n_buckets=n_buckets)
        # tables[0]: this device's shard table
        return ops.lsh_bucket_gather(tables[0], pb, backend=backend)

    mapped = _shard_mapped(shard_fn, mesh,
                           in_specs=(P(), P(), P(), P(), P(r_axis)),
                           out_specs=P(None, r_axis))
    return jax.jit(mapped)


def _expand_pb(pb, expand):
    """[q, l, p] probed bucket ids -> [q, l, p*fanout] via the re-bucket
    expansion map [l, B, fanout] (trace-safe; shared by both expanded
    programs so replicated and ring candidates agree bit-for-bit)."""
    q, l, p = pb.shape
    pb2 = expand[jnp.arange(l)[None, :, None], pb]     # [q, l, p, F]
    return pb2.reshape(q, l, p * expand.shape[2])


@register_program_cache
@functools.lru_cache(maxsize=128)
def _lsh_expand_probe_program(mesh, metric, W, n_probes, n_buckets,
                              backend="jnp"):
    """`_lsh_probe_program` with skew-aware re-bucketing (DESIGN.md
    §16): the multiprobe schedule is unchanged (bucket domain [0, B)),
    then every probed bucket expands to its child buckets through the
    runtime `expand` map before the member-table gather.  The gather's
    dedup blanks the repeated filler slots exactly like repeated identity
    probes, so counts stay bit-identical to the un-rebucketed path."""
    def run(qpos, proj, bias, salt, expand, tables):
        codes = _lsh_codes(qpos, proj, bias, metric=metric, W=W)
        pb = _lsh_multiprobe(codes, salt, metric=metric, n_probes=n_probes,
                             n_buckets=n_buckets)
        return ops.lsh_bucket_gather(tables, _expand_pb(pb, expand),
                                     backend=backend)

    return jax.jit(_whole_on_each_device(run, mesh, 6))


@register_program_cache
@functools.lru_cache(maxsize=128)
def _lsh_ring_expand_probe_program(mesh, r_axis, metric, W, n_probes,
                                   n_buckets, backend="jnp"):
    """Ring variant of the expanded probe: the expansion map is
    replicated (it indexes the GLOBAL bucket space, identical on every
    shard) while the member tables stay row-partitioned over `r` —
    candidate ids remain local to the R shard that verifies them."""
    def shard_fn(qpos, proj, bias, salt, expand, tables):
        codes = _lsh_codes(qpos, proj, bias, metric=metric, W=W)
        pb = _lsh_multiprobe(codes, salt, metric=metric, n_probes=n_probes,
                             n_buckets=n_buckets)
        return ops.lsh_bucket_gather(tables[0], _expand_pb(pb, expand),
                                     backend=backend)

    mapped = _shard_mapped(shard_fn, mesh,
                           in_specs=(P(), P(), P(), P(), P(), P(r_axis)),
                           out_specs=P(None, r_axis))
    return jax.jit(mapped)


@register_program_cache
@functools.lru_cache(maxsize=128)
def _probe_verify_program(mesh, data_axis, metric, block, backend):
    """Compiled candidate-verify + scatter program for replicated R:
    `(R, qpos, cand, idx, n_pos, eps, tomb, *, out_rows) -> int32
    [out_rows]`. The work shards over `data` when the capacity divides
    evenly. `tomb` (None when R is unmutated) masks tombstoned rows out
    of the counts (DESIGN.md §13)."""
    ndata = _data_size(mesh, data_axis)

    def run(R, qpos, cand, idx, n_pos, eps, tomb=None, *, out_rows: int):
        cap = qpos.shape[0]
        qp, cb = qpos, cand
        if (mesh is not None and ndata > 1 and cap % ndata == 0
                and (backend == "ref" or (cap // ndata) % block == 0)):
            s = NamedSharding(mesh, P(data_axis))
            qp = jax.lax.with_sharding_constraint(qp, s)
            cb = jax.lax.with_sharding_constraint(cb, s)
        if backend == "ref" or cap % block != 0:
            # unblocked fallback also covers small-block_q engines whose
            # capacity is below one verify tile
            cnt = _verify_block_impl(R, qp, cb, eps, metric=metric,
                                     tomb=tomb)
        else:
            cnt = _verify_blocks(R, qp, cb, eps, tomb, metric=metric,
                                 block=block)
        contrib = jnp.where(jnp.arange(cap) < n_pos, cnt, 0) \
                     .astype(jnp.int32)
        return jnp.zeros((out_rows,), jnp.int32).at[idx].add(contrib)

    return jax.jit(run, static_argnames=("out_rows",))


@register_program_cache
@functools.lru_cache(maxsize=128)
def _ring_probe_verify_program(mesh, r_axis, data_axis, shard_rows, metric,
                               block, backend, cand_sharded,
                               has_tomb=False):
    """Compiled candidate-verify + scatter for ring-sharded R: each
    device verifies the candidate ids that land in its own shard's row
    range against its resident R shard and the counts are `psum`'d over
    `r` (`joins.common.localized_shard_verify` — the same shard compute
    as the host-probe route). With `cand_sharded` (per-shard probe
    tables) each device sees only its own candidate slice; otherwise the
    replicated candidate list is localized per shard (ids outside the
    range mask to -1). `has_tomb` keys on whether the tombstone mask
    (sharded like R) rides along — shard_map in_specs are fixed-arity
    (DESIGN.md §13)."""
    cspec = P(None, r_axis) if cand_sharded else P()
    shard_fn = localized_shard_verify(r_axis, shard_rows, metric, block,
                                      backend)
    in_specs = (P(r_axis), P(), cspec, P())
    if has_tomb:
        in_specs += (P(r_axis),)
    mapped = _shard_mapped(shard_fn, mesh, in_specs=in_specs,
                           out_specs=P())

    def run(R, qpos, cand, idx, n_pos, eps, tomb=None, *, out_rows: int):
        if has_tomb:
            cnt = mapped(R, qpos, cand, eps, tomb)
        else:
            cnt = mapped(R, qpos, cand, eps)
        contrib = jnp.where(jnp.arange(qpos.shape[0]) < n_pos, cnt, 0) \
                     .astype(jnp.int32)
        return jnp.zeros((out_rows,), jnp.int32).at[idx].add(contrib)

    return jax.jit(run, static_argnames=("out_rows",))


def clear_probe_program_cache() -> None:
    """Evict this module's compiled probe-program caches only (the caches
    key on the mesh and would otherwise pin executables for meshes a
    long-lived serve process has discarded).  Kept as a targeted hook;
    `engine.clear_program_cache()` now evicts these through the
    `_PROGRAM_CACHES` registry instead of calling here. Programs rebuild
    transparently."""
    for cache in (_gather_program, _lsh_probe_program,
                  _lsh_ring_probe_program, _lsh_expand_probe_program,
                  _lsh_ring_expand_probe_program, _probe_verify_program,
                  _ring_probe_verify_program):
        cache.cache_clear()


# ============================================== table sharding (ring)
def _shard_lsh_tables(tables: np.ndarray, shards: int,
                      rows: int) -> np.ndarray:
    """Partition a global [l, B, cap] LSH member table into per-shard
    tables [shards, l, B, cap_s] (-1 padded), shard s holding EXACTLY
    the global table's ids in row range [s*rows, (s+1)*rows).

    Because the partition is of the *retained* global entries (not a
    rebuild from scratch), the union over shards equals the global
    table bit-for-bit — per-shard probing stays candidate-identical to
    the replicated probe, and per-device table bytes drop by roughly
    the shard count (cap_s ≈ cap / shards on balanced data).  Works in
    int32 one shard at a time: besides the input and the output, only
    one shard's masked copy of the table exists at once."""
    tables = np.asarray(tables, np.int32)

    def in_shard(s):
        return (tables >= s * rows) & (tables < (s + 1) * rows)

    cap_s = max(max(int(in_shard(s).sum(axis=-1).max())
                    for s in range(shards)), 1)
    empty = np.iinfo(np.int32).max          # sorts after every row id
    out = np.empty((shards,) + tables.shape[:-1] + (cap_s,), np.int32)
    for s in range(shards):
        part = np.where(in_shard(s), tables, empty)
        part.sort(axis=-1)
        part = part[..., :cap_s]
        out[s] = np.where(part == empty, -1, part)
    return out


# ================================================ specs + placed probes
class PlacedProbe:
    """A probe spec bound to one engine: tables uploaded per the
    engine's topology, probe/verify programs resolved. `probe(qpos)`
    and `verify(...)` are separately dispatchable device programs —
    the split that lets `StreamSession` stage batch k+1's probing while
    batch k's verification executes (DESIGN.md §11)."""

    def __init__(self, engine, *, name: str, probe_fn: Callable,
                 state: tuple, cand_sharded: bool, table_bytes: int,
                 cand_width: int):
        self.engine = engine
        self.name = name
        self._probe_fn = probe_fn
        self._state = state
        self.cand_sharded = cand_sharded
        #: probe-table bytes resident on EACH device (reported by
        #: `JoinPlan.describe()["exec"]["probe"]`)
        self.table_bytes_per_device = int(table_bytes)
        #: candidate ids produced per query (global, across shards)
        self.cand_width = int(cand_width)

    def probe(self, qpos) -> jax.Array:
        """Dispatch the probe program: compacted queries [capacity, d]
        -> candidate ids [capacity, cand_width] (-1 padded), all on
        device — no host hop."""
        return self._probe_fn(qpos, *self._state)

    def verify(self, qpos, cand, idx, n_pos, eps, *, out_rows: int,
               block: int = 32, Rdev=None, tomb=None) -> jax.Array:
        """Dispatch candidate verification + scatter against the
        engine's resident R; returns the per-query counts [out_rows]
        (device array — the caller starts the async host copy). `Rdev` /
        `tomb` override the engine's live buffers with a staged batch's
        snapshot of R and its tombstone mask (DESIGN.md §13) so streamed
        batches verify against their submit-time logical set."""
        eng = self.engine
        R = eng._Rdev if Rdev is None else Rdev
        if eng.r_shards > 1:
            prog = _ring_probe_verify_program(
                eng.mesh, eng.topology.r_axis, eng.data_axis,
                eng.nr_padded // eng.r_shards, eng.metric, block,
                eng.backend, self.cand_sharded, tomb is not None)
        else:
            prog = _probe_verify_program(eng.mesh, eng.data_axis,
                                         eng.metric, block, eng.backend)
        return prog(R, qpos, cand, idx, n_pos, eps, tomb,
                    out_rows=out_rows)


def _device_put(arr, mesh, spec=P()):
    if mesh is not None:
        return jax.device_put(arr, NamedSharding(mesh, spec))
    return jnp.asarray(arr)


class LSHProbe:
    """Device-probe spec for `LSHJoin` (DESIGN.md §11): projection /
    bias / salt / member tables uploaded once (the tables bucket-minor,
    `lsh_gather.bucket_minor`); under the ring topology
    the member tables are row-partitioned over `r`
    (`_shard_lsh_tables`) so probing AND verification stay local to
    each R shard."""

    name = "lsh"

    def __init__(self, join):
        self.join = join

    def place(self, engine) -> PlacedProbe:
        """Upload the probe tables onto the engine's mesh (placement per
        its topology) and resolve the compiled probe program."""
        j = self.join
        mesh = engine.mesh
        salt32 = np.asarray(j.salt, np.int64).astype(np.int32)
        shards = engine.topology.probe_shards(mesh)
        small = (_device_put(j.proj, mesh), _device_put(j.bias, mesh),
                 _device_put(salt32, mesh))
        expand = getattr(j, "expand", None)
        fanout = 1 if expand is None else int(expand.shape[2])
        if expand is not None:
            # re-bucketed index (DESIGN.md §16): the expansion map rides
            # along replicated; probe width grows by the fanout while the
            # table capacity shrinks to the post-split occupancy
            small = small + (_device_put(np.asarray(expand, np.int32),
                                         mesh),)
        if shards > 1:
            tabs = bucket_minor(_shard_lsh_tables(
                j.tables, shards, engine.nr_padded // shards))
            tables = _device_put(tabs, mesh, engine.topology.probe_spec())
            if expand is None:
                prog = _lsh_ring_probe_program(
                    mesh, engine.topology.r_axis, j.metric, float(j.W),
                    int(j.n_probes), int(j.n_buckets), engine.backend)
            else:
                prog = _lsh_ring_expand_probe_program(
                    mesh, engine.topology.r_axis, j.metric, float(j.W),
                    int(j.n_probes), int(j.n_buckets), engine.backend)
            table_bytes = (tabs.nbytes // shards + j.proj.nbytes
                           + j.bias.nbytes + salt32.nbytes)
            cand_width = (shards * tabs.shape[1] * j.n_probes * fanout
                          * tabs.shape[2])
            cand_sharded = True
        else:
            tab = bucket_minor(j.tables)
            tables = _device_put(tab, mesh)
            if expand is None:
                prog = _lsh_probe_program(mesh, j.metric, float(j.W),
                                          int(j.n_probes),
                                          int(j.n_buckets), engine.backend)
            else:
                prog = _lsh_expand_probe_program(
                    mesh, j.metric, float(j.W), int(j.n_probes),
                    int(j.n_buckets), engine.backend)
            table_bytes = (tab.nbytes + j.proj.nbytes + j.bias.nbytes
                           + salt32.nbytes)
            cand_width = j.l * j.n_probes * fanout * j.tables.shape[2]
            cand_sharded = False
        if expand is not None:
            table_bytes += expand.nbytes
        return PlacedProbe(engine, name=self.name, probe_fn=prog,
                           state=small + (tables,),
                           cand_sharded=cand_sharded,
                           table_bytes=table_bytes, cand_width=cand_width)


class IVFPQProbe:
    """Device-probe spec for `IVFPQJoin`: centroids / inverted lists /
    PQ codes / codebooks uploaded once, replicated on every device
    under EITHER topology — ADC ranking is a global top-k, so the
    candidate list must see the whole pool; under the ring topology the
    replicated candidates are localized per R shard by the verify
    program instead."""

    name = "ivfpq"

    def __init__(self, join):
        self.join = join

    def place(self, engine) -> PlacedProbe:
        """Upload the quantizer state replicated and resolve the blocked
        coarse-probe + ADC-rank program."""
        j = self.join
        mesh = engine.mesh
        n_cand = int(min(j.n_candidates, j.n_probe * j.lists.shape[1]))
        state = (_device_put(j.centroids, mesh),
                 _device_put(np.asarray(j.lists, np.int32), mesh),
                 _device_put(j.codes, mesh),
                 _device_put(j.codebooks, mesh))

        def run(qpos, centroids, lists, codes, codebooks):
            return _ivfpq_probe_fn(qpos, centroids, lists, codes, codebooks,
                                   n_probe=int(j.n_probe), n_cand=n_cand,
                                   backend=engine.backend)

        prog = jax.jit(_whole_on_each_device(run, mesh, 5))

        table_bytes = (j.centroids.nbytes + j.lists.nbytes + j.codes.nbytes
                       + j.codebooks.nbytes)
        return PlacedProbe(engine, name=self.name, probe_fn=prog,
                           state=state, cand_sharded=False,
                           table_bytes=table_bytes, cand_width=n_cand)


# ============================================== the adapter registry
#: Searcher type -> `builder(searcher, eps) -> spec | None` for searcher
#: classes that cannot grow a `device_probe` method themselves (the
#: DESIGN.md §9 adapter-registry pattern, mirroring FILTER_ADAPTERS).
#: Searchers matching neither route simply keep the host probe path.
PROBE_BUILDERS: dict[type, Callable[[Any, Optional[float]], Any]] = {}


def register_probe(searcher_type: type, builder: Callable) -> None:
    """Register a device-probe builder for a searcher class (the
    extension point for searchers whose source cannot be edited)."""
    PROBE_BUILDERS[searcher_type] = builder


def as_device_probe(searcher, eps: float | None = None):
    """Resolve a searcher's device-probe spec, or None for host-only
    searchers. Resolution order: the searcher's own `device_probe(eps)`
    (the DeviceSearcher protocol), then the `PROBE_BUILDERS` registry
    walked over the class MRO. Returning None is not an error — it
    selects the host probe path. `eps` may be None (plan-build
    validation); the engine caches placement per returned spec, so
    radius-free probes should memoize one spec per index and eps-aware
    probes one spec per distinct eps."""
    fn = getattr(searcher, "device_probe", None)
    if fn is not None:
        return fn(eps)
    for cls in type(searcher).__mro__:
        builder = PROBE_BUILDERS.get(cls)
        if builder is not None:
            return builder(searcher, eps)
    return None
