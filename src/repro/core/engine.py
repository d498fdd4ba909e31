"""Sharded, device-resident batch-join execution engine (DESIGN.md §4).

The paper reduces similarity join to filter-then-verify, and both halves
bottom out in dense range counting — work that should saturate accelerators.
This module is the execution layer that makes that true:

  * `JoinEngine` pins the index set R on device once and runs every sweep
    against it with bucketed static shapes.  WHERE R lives is a
    first-class choice (DESIGN.md §10): `topology="replicated"` (the
    default — R on every device, queries sharded over the mesh's data
    axis) or `topology="ring"` (R row-sharded over a second `r` mesh
    axis; the sweep runs as a `jax.lax.ppermute` ring with per-shard
    partial counts `psum`'d over `r`, so |R| scales past one device's
    memory).  The placement logic itself lives in `core/topology.py`;
    this module stays the scheduling/caching layer.
  * The range-count sweep shards the QUERY axis over the mesh
    with `shard_map` (each device sweeps its query slice against its
    topology-resident view of R), so ground-truth `cardinality_table`
    construction and naive-join verification scale across devices.
  * `filtered_join` is the fused XJoin hot path: estimator inference + XDT
    thresholding run as one device program; the single host sync reads the
    positive count to pick a power-of-two capacity bucket; compaction +
    verification then run as a second device program (gather the
    positives, count, scatter back) — skipped queries cost nothing.
  * Verification is pluggable (DESIGN.md §5): `verify="exact"` is the
    brute-force sweep above; `verify="lsh"` / `"ivfpq"` replace the sweep
    with an approximate index probe over the same device-resident R —
    candidates are verified on device through
    `joins.common.verify_candidates`, so counts stay exact *per candidate*
    and recall is measured against the exact path. WHERE the probe runs
    is a placement choice (DESIGN.md §11, `probe="auto"|"device"|"host"`):
    with a device-capable searcher the probe tables live on the mesh
    (`core/probe.py`) and compact → probe → verify is all device
    programs — the positive-count read is the only per-batch host sync.
  * `stream` / `StreamSession` wrap that path for serving as an
    asynchronous pipelined stream (DESIGN.md §5, §11): batches flow
    filter-staged -> probe-staged -> committed, so batch *k+1*'s device
    programs (and, with device probing, batch *k*'s probe) are dispatched
    while batch *k−1*'s verification is still in flight and its results
    transfer back via non-blocking host copies; a bounded in-flight queue
    caps memory and `flush()` is the shutdown barrier. Compiled programs
    are reused across batches because every shape is bucketed.
  * Dynamic R (DESIGN.md §13): `insert` / `delete` mutate the logical
    index set with NO index rebuild — inserts accumulate in a small
    replicated device-resident delta shard (power-of-two bucketed,
    probed exactly and added into every count); deletes zero the
    tombstoned rows inside the pinned R (their closed-form zero-row
    contribution is subtracted, the same mechanism as ring pad-row
    masking) and mask them in candidate verification via an int32
    tombstone mask. `compact()` folds the delta into the pinned R,
    rebuilds the approximate indices, and evicts the compiled programs
    through `clear_program_cache()` — counts stay bit-identical to a
    fresh `ref` oracle over the logical (R ∪ delta − tombstones) set at
    every point in a mutation sequence.

Backend matrix (DESIGN.md §2): per-shard compute is the Pallas kernel on
TPU ("pallas"), the blocked-jnp path elsewhere ("jnp"/"auto"), or the
unblocked oracle ("ref" — no padding, used as the bit-for-bit reference).
The backend also selects the probe-side kernels (DESIGN.md §15):
`engine.backend` threads into the placed probe programs, which dispatch
the LSH bucket gather and IVF-PQ ADC ranking through
`kernels/lsh_gather.py` / `kernels/adc_rank.py` under "pallas" and
their bit-identical jnp formulations otherwise.  Every compiled probe
program is a module-level `lru_cache` registered here via
`register_program_cache`, so `clear_program_cache()` evicts the whole
backend-keyed matrix at once.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import time
import weakref
from dataclasses import dataclass
from typing import Callable, Iterable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.core.topology import (Topology, _data_size, _zero_row_distance,
                                 resolve_topology)
from repro.kernels import ops


def _bucket_size(n: int, block: int) -> int:
    """Round n up to a bucketed multiple of block (recompile bounding).

    Power-of-two growth, refined with quarter steps once those are still
    block multiples — shape count stays logarithmic but padding overshoot
    is capped at 25% (a pure power-of-two bucket wastes up to ~50% of the
    work on padding rows at large n)."""
    if n <= block:
        return block
    b = block
    while b < n:
        b *= 2
    if b >= 8 * block:
        for eighths in (5, 6, 7):
            c = (b // 8) * eighths
            if c >= n:
                return c
    return b


def _put_row_major(x: np.ndarray, sharding) -> jax.Array:
    """`device_put` with the row-major layout pinned.  On TPU a 2-D f32
    array whose minor dim is not a multiple of 128 defaults to a
    column-major layout, and every program that tiles or gathers rows of
    it (the range_count kernel, candidate verification) would first copy
    it whole into row-major; a jitted program compiles for the layout of
    a committed argument, so pinning it at upload removes those copies."""
    from jax.experimental.layout import Format, Layout
    return jax.device_put(x, Format(Layout(tuple(range(x.ndim))), sharding))


def _pad_rows_np(x: np.ndarray, n: int) -> np.ndarray:
    if x.shape[0] >= n:
        return x
    pad = np.zeros((n - x.shape[0],) + x.shape[1:], x.dtype)
    return np.concatenate([x, pad])


#: Registry of every module-level compiled-program `lru_cache` in
#: `core/` (DESIGN.md §12).  The caches key on the mesh (among others)
#: and thereby pin XLA executables — and through them device buffers —
#: alive for meshes a long-lived process has already discarded, so each
#: one MUST be evictable by `clear_program_cache()`.  Registration is by
#: the `register_program_cache` decorator; xlint's cache-registry rule
#: rejects any `functools.lru_cache` program builder in `core/` that is
#: not registered, so a new cache can never silently escape eviction.
_PROGRAM_CACHES: list = []


def register_program_cache(cache):
    """Register a module-level `functools.lru_cache` program builder in
    `_PROGRAM_CACHES` so `clear_program_cache()` evicts it.

    Stack it ABOVE `@functools.lru_cache` (it returns its argument, so
    the bound name keeps `cache_clear`/`cache_info`).  Mandatory for
    every program cache in `core/` — enforced statically by xlint's
    cache-registry rule (DESIGN.md §12)."""
    _PROGRAM_CACHES.append(cache)
    return cache


@register_program_cache
@functools.lru_cache(maxsize=128)
def _hist_program(mesh, data_axis, backend, metric, block_q, block_r,
                  eps_chunk, nr_valid, topology):
    """Compiled topology-parametrized sweep `(q, r, eps, nrv) -> [n, m]`.
    Module-level cache so engines over the same (mesh, topology, |R|)
    share one XLA executable; evict with `clear_program_cache`."""
    return topology.hist_program(mesh, data_axis, backend, metric, block_q,
                                 block_r, eps_chunk, nr_valid)


@register_program_cache
@functools.lru_cache(maxsize=128)
def _compact_program(mesh, data_axis, backend, metric, block_q, block_r,
                     nr_valid, topology):
    """Compiled topology-parametrized compact -> verify -> scatter program
    `(q, pos, n_pos, r, eps, nrv, *, capacity) -> [n]`. `capacity` is the
    bucketed static shape; `n_pos` rides along as a device scalar so the
    same executable serves every occupancy of a bucket. Cached like
    `_hist_program`; evict with `clear_program_cache`."""
    return topology.compact_program(mesh, data_axis, backend, metric,
                                    block_q, block_r, nr_valid)


@register_program_cache
@functools.lru_cache(maxsize=32)
def _delete_program(mesh, r_spec):
    """Compiled tombstone apply `(R, tomb, rows) -> (R', tomb')`: zero the
    deleted rows in the pinned R and set their tombstone flags, keeping
    the topology's R sharding.  Deliberately NOT donating: staged stream
    batches snapshot the pre-delete buffers (`_WorldView`), so the update
    must be purely functional — old snapshots stay valid until their
    batch commits.  `rows` is bucketed (repeat-padded with rows[0], an
    idempotent re-delete) so one executable serves every delete size."""
    def run(R, tomb, rows):
        R2 = R.at[rows].set(0.0)
        t2 = tomb.at[rows].set(1)
        if mesh is not None:
            s = NamedSharding(mesh, r_spec)
            R2 = jax.lax.with_sharding_constraint(R2, s)
            t2 = jax.lax.with_sharding_constraint(t2, s)
        return R2, t2
    return jax.jit(run)


@register_program_cache
@functools.lru_cache(maxsize=64)
def _delta_count_program(mesh, metric):
    """Compiled per-batch mutation adjustment for single-eps counts
    (DESIGN.md §13): sweep the queries against the replicated delta shard
    with the oracle's own distance math (`ref.pair_distances`, so delta
    verdicts are bit-identical to a fresh oracle over the live rows) and,
    on exact-sweep routes, subtract the tombstoned rows' closed-form
    zero-row contribution (`n_tomb` traced; None on candidate routes,
    where the tombstone mask already removed them in verification).
    `counts=None` returns the bare adjustment (host-probe routes add it
    after their own scatter).  None-ness of counts/n_tomb keys retraces,
    not recompiles per batch — both are fixed per route."""
    from repro.kernels import ref

    def run(counts, q, pos, delta, dvalid, eps, n_tomb):
        d = ref.pair_distances(q, delta, metric)
        dcnt = jnp.sum((d <= eps) & (dvalid[None, :] == 1),
                       axis=1, dtype=jnp.int32)
        if n_tomb is not None:
            hit = (_zero_row_distance(metric) <= eps).astype(jnp.int32)
            dcnt = dcnt - n_tomb * hit
        adj = jnp.where(pos, dcnt, 0).astype(jnp.int32)
        return adj if counts is None else counts + adj
    return jax.jit(run)


@register_program_cache
@functools.lru_cache(maxsize=64)
def _delta_hist_program(mesh, metric):
    """Compiled mutation adjustment for the eps-grid histogram sweep:
    adds the live delta rows' counts and subtracts the tombstoned rows'
    closed-form zero-row contribution per eps bin — the histogram twin of
    `_delta_count_program`, applied by `device_range_count_hist` so the
    ground-truth tables also see the logical (R ∪ delta − tombstones)
    set."""
    from repro.kernels import ref

    def run(counts, q, delta, dvalid, eps_grid, n_tomb):
        d = ref.pair_distances(q, delta, metric)
        dcnt = jnp.sum((d[:, :, None] <= eps_grid[None, None, :])
                       & (dvalid[None, :, None] == 1),
                       axis=1, dtype=jnp.int32)
        zhit = (_zero_row_distance(metric) <= eps_grid).astype(jnp.int32)
        return counts + dcnt - n_tomb * zhit[None, :]
    return jax.jit(run)


def clear_program_cache() -> None:
    """Evict every registered module-level compiled-program cache.

    Iterates the `_PROGRAM_CACHES` registry, so it can never silently
    miss a cache: every `functools.lru_cache` program builder in `core/`
    registers itself via `register_program_cache` at import time (the
    xlint cache-registry rule enforces this, DESIGN.md §12).  Call this
    after tearing down a mesh (tests do) to release the executables it
    pins; programs rebuild transparently on the next engine call."""
    for cache in list(_PROGRAM_CACHES):
        cache.cache_clear()


@dataclass
class EngineJoinResult:
    """Result of one filtered-join batch through the engine."""
    counts: np.ndarray      # int32 [n] neighbor counts (0 for skipped)
    n_searched: int         # queries that reached verification
    t_filter: float
    t_search: float
    verify: str = "exact"   # label of the backend that produced `counts`
    probe: Optional[str] = None   # "device" | "host" | None (exact sweep)


#: Verification backends accepted *by name* in `filtered_join(verify=...)` /
#: `stream(verify=...)`. "exact" is the engine's fused brute-force sweep;
#: the others probe an approximate index and verify candidates on device
#: (DESIGN.md §5). Beyond these names, `verify=` also accepts any Searcher
#: object (DESIGN.md §9): one exposing `candidates(Q)` routes its
#: candidates through the on-device verification path; one exposing only
#: `query_counts(Q, eps)` verifies the compacted positives on host.
VERIFY_BACKENDS = ("exact", "lsh", "ivfpq")

#: A verify spec: "exact", a VERIFY_BACKENDS name, or a Searcher object
#: (candidates() for device verification, query_counts() for the host
#: fallback) — validated by `_check_verify`.
VerifySpec = "str | object"

#: Probe placement modes (DESIGN.md §11): "auto" runs the probe on
#: device whenever the verify route's searcher advertises a device probe
#: (DeviceSearcher / probe.PROBE_BUILDERS), "device" requires it (fails
#: at construction when unavailable), "host" forces the legacy host
#: probe even when a device probe exists.
PROBE_MODES = ("auto", "device", "host")


#: active `host_sync_guard` scopes — a stack of frozensets of allowed
#: sync kinds consulted by `_note_host_sync`
_SYNC_GUARDS: list = []


class HostSyncError(RuntimeError):
    """An UNDECLARED per-batch host sync fired inside a
    `host_sync_guard` scope (DESIGN.md §12)."""


def _note_host_sync(kind: str) -> None:
    """Instrumentation hook invoked at every per-batch host
    synchronization point: "n_pos" (the positive-count read), "verdicts"
    (device->host verdict readback for host probing), "probe" (the host
    index probe itself), "result" (final counts materialization). A
    no-op in production; tests monkeypatch it to assert the device-probe
    route performs no per-batch host transfers beyond the count read and
    the result readback (the ISSUE 5 acceptance invariant). Under an
    active `host_sync_guard`, a kind outside the allowed set raises
    `HostSyncError` — the hook doubles as the runtime guard's tripwire
    on backends whose zero-copy array transfers are invisible to
    `jax.transfer_guard` (the CPU backend)."""
    if _SYNC_GUARDS and kind not in _SYNC_GUARDS[-1]:
        raise HostSyncError(
            f"disallowed host sync {kind!r} inside host_sync_guard scope "
            f"(allowed kinds: {sorted(_SYNC_GUARDS[-1])}) — DESIGN.md §12")


@contextlib.contextmanager
def host_sync_guard(*allowed: str):
    """Runtime guard scope (DESIGN.md §12): every per-batch host sync in
    the scope must be one of `allowed` or `HostSyncError` is raised.

    Two enforcement layers compose here.  The hook layer
    (`_note_host_sync`) catches any instrumented sync with an undeclared
    kind — it works on every backend, including CPU, where JAX's
    zero-copy transfers never reach the XLA transfer guard.  The XLA
    layer (`jax.transfer_guard_device_to_host("disallow")`, entered for
    the whole scope) additionally catches UNinstrumented device→host
    transfers on accelerator backends; the declared sync points open
    their own `"allow"` windows via `_allowed_transfer`, which is why
    `allowed` should normally be exactly `("n_pos", "result")` — the two
    syncs the exact and device-probe streamed routes are specified to
    perform (§11).  tests/test_guards.py runs the parity lanes inside
    this scope."""
    _SYNC_GUARDS.append(frozenset(allowed))
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            yield
    finally:
        _SYNC_GUARDS.pop()


@contextlib.contextmanager
def _allowed_transfer(kind: str):
    """Scope of one DECLARED per-batch device→host sync (DESIGN.md §12).

    The exact and device-probe routes declare exactly two such points —
    the positive-count read ("n_pos") and the final result readback
    ("result").  Entering the scope notes the sync for the test
    instrumentation (`_note_host_sync`) and opens a
    `jax.transfer_guard_device_to_host("allow")` window, so the
    transfer-guard test lane (tests/test_guards.py) can run the whole
    stream under `"disallow"` and any UNdeclared transfer raises — the
    §11 "only two host transfers per batch" claim as an enforced runtime
    property, not just instrumentation.  Host-probe syncs ("verdicts" /
    "probe") deliberately do NOT open an allow window: under the guard
    the host route fails, which is what proves the guard is live."""
    _note_host_sync(kind)
    with jax.transfer_guard_device_to_host("allow"):
        yield


def _check_verify(verify) -> str:
    """Validate a `verify=` spec and return its display label.

    Accepted: "exact", a name from `VERIFY_BACKENDS`, or a plug-in
    searcher object exposing `candidates(Q)` (device candidate
    verification) or `query_counts(Q, eps)` (host verification of the
    compacted positives). Raises ValueError otherwise — at construction
    time, not data-dependently inside the pipeline."""
    if isinstance(verify, str):
        if verify not in VERIFY_BACKENDS:
            raise ValueError(f"verify={verify!r}: expected one of "
                             f"{sorted(VERIFY_BACKENDS)} or a searcher "
                             "object exposing candidates()/query_counts()")
        return verify
    if hasattr(verify, "candidates") or hasattr(verify, "query_counts"):
        return getattr(verify, "name", type(verify).__name__)
    raise ValueError(
        f"verify={type(verify).__name__!r} object: plug-in verification "
        "searchers must expose candidates(Q) -> int32 [q, C] (-1 padded) "
        "or query_counts(Q, eps) -> int32 [q]")


def _start_host_copy(arr) -> None:
    """Kick off a non-blocking device→host transfer so the later
    `np.asarray` materialization finds the bytes already resident.

    This is the asynchronous START of the declared "result" readback
    (the blocking half lives in `PendingJoin.result` under
    `_allowed_transfer("result")`), so it runs inside an explicit
    device→host allow window of its own — one readback, two phases."""
    with jax.transfer_guard_device_to_host("allow"):
        arr.copy_to_host_async()


class _WorldView:
    """Immutable snapshot of the engine's logical index state, pinned on
    a `_StagedBatch` at stage time (DESIGN.md §13).

    Mutations (`insert` / `delete`) are purely functional — they swap the
    engine's references to fresh device buffers, never write into the old
    ones — so a batch staged BEFORE a mutation keeps sweeping the exact
    logical set that existed at its submit time, even though its
    verification commits later.  That is the streamed snapshot-consistency
    contract: batch k's counts always equal a fresh oracle over the
    logical set as of batch k's submission."""
    __slots__ = ("Rdev", "nrv", "delta", "dvalid", "tomb", "n_tomb",
                 "n_tomb_dev", "mutated")


class _StagedBatch:
    """Stage-1/2 handle: queries resident, filter program dispatched,
    nothing synced. `n_pos` is None until `JoinEngine._stage_probe` (or
    `_commit_verify` as a fallback) reads it; on a device-probe route
    `_stage_probe` additionally fills `qpos_dev` / `idx_dev` / `cand_dev`
    and sets `probe` to the placed probe that produced them. `world` is
    the submit-time `_WorldView` snapshot (DESIGN.md §13)."""
    __slots__ = ("Q", "n", "eps", "qdev", "eps_dev", "pos_dev", "n_pos_dev",
                 "n_pos", "t_stage", "probe", "qpos_dev", "idx_dev",
                 "cand_dev", "capacity", "world")


class PendingJoin:
    """Stage-2 handle for one in-flight batch.

    Verification is dispatched and the device→host copy is running;
    `result()` is the only blocking point and is idempotent. Async-path
    timing convention: `t_search` = dispatch-side cost + whatever wait
    `result()` actually observed (≈0 when the pipeline hid the readback).
    """

    def __init__(self, finalize: Callable[[], np.ndarray], *, verify: str,
                 n_searched: int, t_filter: float, t_dispatch: float,
                 probe: Optional[str] = None):
        self._finalize = finalize
        self._verify = verify
        self._probe = probe
        self._n_searched = n_searched
        self._t_filter = t_filter
        self._t_dispatch = t_dispatch
        self._res: Optional[EngineJoinResult] = None

    def result(self) -> EngineJoinResult:
        """Materialize (blocking if the device is still busy)."""
        if self._res is None:
            t0 = time.perf_counter()
            with _allowed_transfer("result"):
                counts = self._finalize()
            self._res = EngineJoinResult(
                counts, self._n_searched, self._t_filter,
                self._t_dispatch + (time.perf_counter() - t0), self._verify,
                self._probe)
        return self._res


class StreamSession:
    """Asynchronous pipelined serving session (DESIGN.md §5, §11).

    Push interface under `JoinEngine.stream`. Batches flow through THREE
    stages — filter-staged -> probe-staged -> committed (verifying) —
    so with a device-probe route batch k+1's probing executes on device
    while batch k's verification is still in flight. `submit(Q)` stages
    the new batch's filter programs, commits the probe-staged batch's
    verification, advances the filter-staged batch into the probe stage
    (its positive-count read is the per-batch host sync), and returns
    any results forced out by the `depth` bound; `flush()` is the
    shutdown barrier — it drains all three stages and returns the
    remaining results.

    Invariants:
      * results come back in submission order (FIFO), bit-identical to
        per-batch `filtered_join` calls;
      * at most `depth` committed batches plus one probe-staged and one
        filter-staged batch are in flight, bounding device memory at
        (depth + 3) padded batches;
      * on the exact and device-probe verify routes, the only per-batch
        host syncs are the probe-staged batch's positive-count read —
        issued AFTER the next batch's filter programs and the previous
        batch's verification are enqueued — and the final result
        readback (host-probe routes additionally read back the verdicts
        and probe on host inside commit — their candidate *verification*
        still overlaps, but probing is synchronous);
      * after `flush()` returns, no engine program of this session is
        outstanding.
    """

    def __init__(self, engine: "JoinEngine", eps: float, *, predict=None,
                 threshold=None, verify: VerifySpec = "exact", depth: int = 2,
                 block: int | None = None, probe: str = "auto"):
        _check_verify(verify)
        # resolve the probe route up front: probe="device" without a
        # device-capable searcher fails here, never mid-stream
        self._placed = engine.device_probe_for(verify, probe, eps=eps)
        self._probe_mode = probe
        self.engine = engine
        self.eps = float(eps)
        self.predict, self.threshold = predict, threshold
        self.verify, self.depth, self.block = verify, max(int(depth), 0), block
        self._staged: Optional[_StagedBatch] = None
        self._probed: Optional[_StagedBatch] = None
        self._inflight: collections.deque[PendingJoin] = collections.deque()
        # results forced out by a mid-stream compact() drain (§13): they
        # are re-emitted FIRST by the next submit/flush, preserving FIFO
        self._ready: list[EngineJoinResult] = []
        engine._sessions.add(self)

    def _commit_probed(self) -> None:
        if self._probed is not None:
            self._inflight.append(self.engine._commit_verify(
                self._probed, verify=self.verify, block=self.block))
            self._probed = None

    def _advance_staged(self) -> None:
        if self._staged is not None:
            self._probed = self.engine._stage_probe(
                self._staged, placed=self._placed, block=self.block)
            self._staged = None

    def submit(self, Q, *, verdicts=None) -> list[EngineJoinResult]:
        """Feed one query batch; returns the (possibly empty) list of OLDER
        batches' results whose readback completed under the depth bound.
        `verdicts` optionally carries precomputed host filter verdicts for
        this batch (plug-in filters without a device predict fn)."""
        st = self.engine._stage_filter(
            Q, self.eps, predict=self.predict, threshold=self.threshold,
            verdicts=verdicts)
        self._commit_probed()               # batch k-1 enters verify
        self._advance_staged()              # batch k probes (count read)
        self._staged = st
        out, self._ready = self._ready, []  # compact-drained results first
        while len(self._inflight) > self.depth:
            out.append(self._inflight.popleft().result())
        return out

    def set_depth(self, depth: int) -> None:
        """Retarget the in-flight bound mid-stream (the serve gateway's
        adaptive-depth hook, DESIGN.md §14). A smaller depth takes effect
        on the NEXT submit — already-committed batches drain under the new
        bound; nothing is cancelled, so results stay FIFO and
        bit-identical."""
        self.depth = max(int(depth), 0)

    def flush(self) -> list[EngineJoinResult]:
        """Barrier: drain the pipeline, returning all remaining results in
        submission order. Safe to call repeatedly; the session can keep
        submitting afterwards (the pipeline just restarts cold)."""
        self._commit_probed()
        self._advance_staged()
        self._commit_probed()
        out, self._ready = self._ready, []  # compact-drained results first
        while self._inflight:
            out.append(self._inflight.popleft().result())
        return out

    # -------------------------------------- dynamic-R compaction hooks
    def _drain_for_compact(self) -> None:
        """Flush every in-flight batch into the session's ready buffer so
        `JoinEngine.compact()` can swap geometry with nothing staged
        (DESIGN.md §13).  The results are re-emitted in FIFO order by the
        next `submit`/`flush`, so callers observe the same sequence as an
        uninterrupted stream."""
        drained = self.flush()      # flush() rebinds _ready — extend AFTER
        self._ready.extend(drained)

    def _rebind_after_compact(self) -> None:
        """Re-resolve the placed probe: compaction rebuilt the verify
        indices over the merged R, so the pre-compact probe tables are
        stale."""
        self._placed = self.engine.device_probe_for(
            self.verify, self._probe_mode, eps=self.eps)


class JoinEngine:
    """Device-resident exact join over a fixed index set R.

    mesh: optional `jax.sharding.Mesh` (use `launch.mesh.make_data_mesh()`
    or, for the ring topology, `launch.mesh.make_join_mesh(data=, r=)`).
    topology: "replicated" (default — queries shard over `data_axis`, R
    replicates) or "ring" (R row-sharded over the mesh's `r` axis; the
    sweep runs as a ppermute ring, DESIGN.md §10), or a `Topology`
    instance. Without a mesh everything runs single-device through the
    same programs.
    """

    def __init__(self, R, metric: str = "cosine", *, mesh=None,
                 backend: str = "auto", block_q: int = 256, block_r: int = 512,
                 block: int = 512, eps_chunk: int = 8, data_axis: str = "data",
                 topology: "str | Topology" = "replicated"):
        self.metric = metric
        self.backend = ops._resolve(backend)
        self.mesh, self.data_axis = mesh, data_axis
        self.block_q, self.block_r, self.block = block_q, block_r, block
        self.eps_chunk = eps_chunk
        self.topology = resolve_topology(topology)
        self.topology.validate(mesh, data_axis)
        R = np.asarray(R, np.float32)
        self.dim = R.shape[1]
        self._verifiers: dict = {}
        self._probes: dict = {}     # searcher -> PlacedProbe | None (§11)
        self.ndata = _data_size(mesh, data_axis)
        self.r_shards = self.topology.r_shards(mesh)
        self._q_sharding = None if mesh is None else NamedSharding(
            mesh, self.topology.q_spec(data_axis))
        self._upload_R(R)
        self._filter_progs: dict = {}
        #: per-batch staging constants (DESIGN.md §5): streamed batches
        #: re-stage the same radius scalar and — on unfiltered plans —
        #: the same all-positive mask every submit; both depend only on
        #: (value, shape bucket), so one upload serves the whole stream.
        #: Bounded: distinct radii / shape buckets per engine are few.
        self._eps_scalar_cache: dict = {}
        self._allpos_cache: dict = {}
        # ---- dynamic-R state (DESIGN.md §13) ----------------------------
        #: compact automatically once delta_frac reaches this fraction of
        #: |R| (None = manual compaction only; JoinPlan.mutable sets it)
        self.auto_compact_at: float | None = None
        self.n_compactions = 0
        #: monotone logical-set version: bumped by every insert/delete/
        #: compact, never reset. Cache layers (the serve gateway's
        #: eps-aware result cache, DESIGN.md §14) key entries on it so a
        #: result computed against one world can never answer a query
        #: against another.
        self.world_version = 0
        self._next_id = self.nr             # monotone logical row ids
        self._main_ids = np.arange(self.nr, dtype=np.int64)
        self._delta_rows = np.empty((0, self.dim), np.float32)
        self._delta_ids = np.empty((0,), np.int64)
        self._delta_live = np.empty((0,), bool)
        self._tomb_rows: set[int] = set()   # physical rows tombstoned in R
        self._id_index: dict | None = None  # lazy id -> location map
        self._delta_dev = None              # padded delta rows on device
        self._delta_valid_dev = None        # int32 live mask over the pad
        self._tomb_dev = None               # int32 [nr_padded] tombstones
        self._n_tomb_dev = None             # int32 scalar tombstone count
        self._sessions: weakref.WeakSet = weakref.WeakSet()
        self._verifier_params: dict = {}    # name -> params for rebuilds

    def _upload_R(self, R: np.ndarray) -> None:
        """Pad R to the topology's row quantum and pin it on the mesh —
        shared by `__init__` and `compact()` (which re-uploads the merged
        logical set after evicting the compiled programs)."""
        self.nr = len(R)
        # host-side R backs lazy approximate-verifier construction (§5);
        # np.asarray is a no-copy view for float32 input
        self._R_host = R
        # "ref" on the replicated topology sweeps the raw R (the oracle
        # handles any shape); everything else sees an R padded to the
        # topology's row quantum (equal block-aligned shards) and masks —
        # statically via nr_valid, or via the traced pad-row correction
        # on sharded placements
        if self.backend == "ref" and self.r_shards == 1:
            Rp = R
        else:
            quantum = self.topology.r_row_quantum(self.block_r, self.mesh)
            Rp = _pad_rows_np(R, -(-self.nr // quantum) * quantum)
        self.nr_padded = len(Rp)
        nrv = self.topology.nr_valid_shards(self.nr, self.nr_padded,
                                            self.mesh)
        if self.mesh is not None:
            r_sharding = NamedSharding(self.mesh, self.topology.r_spec())
            self._Rdev = _put_row_major(Rp, r_sharding)
            self._nrv_dev = None if nrv is None else jax.device_put(
                nrv, r_sharding)
        else:
            self._Rdev = _put_row_major(
                Rp, SingleDeviceSharding(jax.devices()[0]))
            self._nrv_dev = None if nrv is None else jnp.asarray(nrv)

    @property
    def per_device_r_bytes(self) -> int:
        """Bytes of (padded) R resident on EACH device — the number the
        topology choice moves; reported by `JoinPlan.describe()`."""
        return self.topology.per_device_r_bytes(self.nr_padded, self.dim,
                                                self.mesh)

    # ------------------------------------------- dynamic R (DESIGN.md §13)
    @property
    def n_delta(self) -> int:
        """Live (non-deleted) rows currently in the delta shard."""
        return int(self._delta_live.sum())

    @property
    def n_tombstones(self) -> int:
        """Main-R rows deleted but not yet compacted away."""
        return len(self._tomb_rows)

    @property
    def delta_capacity(self) -> int:
        """Bucketed device rows the delta shard currently occupies."""
        return 0 if self._delta_dev is None else int(self._delta_dev.shape[0])

    @property
    def delta_frac(self) -> float:
        """Pending mutations as a fraction of |R| — the auto-compaction
        trigger metric (`describe()` reports it)."""
        return (len(self._delta_rows) + len(self._tomb_rows)) / max(self.nr, 1)

    def _world(self) -> _WorldView:
        """Snapshot the logical index state for one staged batch."""
        w = _WorldView()
        w.Rdev, w.nrv = self._Rdev, self._nrv_dev
        w.delta, w.dvalid = self._delta_dev, self._delta_valid_dev
        w.tomb = self._tomb_dev
        w.n_tomb, w.n_tomb_dev = len(self._tomb_rows), self._n_tomb_dev
        w.mutated = self._delta_dev is not None
        return w

    def _stable_index(self) -> dict:
        """id -> ("main", physical row) | ("delta", slot); rebuilt lazily
        after compaction invalidates the physical positions."""
        if self._id_index is None:
            self._id_index = {int(i): ("main", r)
                              for r, i in enumerate(self._main_ids)}
            self._id_index.update(
                {int(i): ("delta", s)
                 for s, i in enumerate(self._delta_ids)})
        return self._id_index

    def _put_replicated(self, x: np.ndarray) -> jax.Array:
        if self.mesh is not None:
            return jax.device_put(
                x, NamedSharding(self.mesh, self.topology.delta_spec()))
        return jnp.asarray(x)

    def _upload_delta(self) -> None:
        """Re-pin the delta shard: rows padded to a 64-row power-of-two
        bucket (matching the probe capacity quantum) with an int32 live
        mask, replicated per `topology.delta_spec()` so the ring sweep
        schedule is untouched.  A fresh buffer every time — staged
        batches keep their snapshot of the old one."""
        cap = _bucket_size(max(len(self._delta_rows), 1), 64)
        self._delta_dev = self._put_replicated(
            _pad_rows_np(self._delta_rows, cap))
        valid = np.zeros((cap,), np.int32)
        valid[: len(self._delta_live)] = self._delta_live
        self._delta_valid_dev = self._put_replicated(valid)
        if self._n_tomb_dev is None:
            self._n_tomb_dev = jnp.asarray(0, jnp.int32)

    def _ensure_tomb(self) -> jax.Array:
        """The int32 [nr_padded] tombstone mask, materialized on first
        delete (sharded like R so candidate verification indexes it
        locally on every placement)."""
        if self._tomb_dev is None:
            tomb = np.zeros((self.nr_padded,), np.int32)
            if self.mesh is not None:
                self._tomb_dev = jax.device_put(
                    tomb, NamedSharding(self.mesh, self.topology.r_spec()))
            else:
                self._tomb_dev = jnp.asarray(tomb)
        return self._tomb_dev

    def insert(self, rows) -> np.ndarray:
        """Insert rows into the logical index set; returns their int64 ids.

        The rows land in the device-resident delta shard — probed exactly
        and merged into every subsequent count (`_delta_count_program`) —
        with NO rebuild of R, the learned filter, or the approximate
        verify indices.  `compact()` (or the `auto_compact_at` policy)
        later folds them into the pinned R."""
        rows = np.atleast_2d(np.asarray(rows, np.float32))
        if rows.ndim != 2 or rows.shape[1] != self.dim:
            raise ValueError(
                f"insert: rows have shape {rows.shape}; expected (k, "
                f"{self.dim}) matching the engine's R")
        ids = np.arange(self._next_id, self._next_id + len(rows),
                        dtype=np.int64)
        self._next_id += len(rows)
        base = len(self._delta_rows)
        self._delta_rows = np.concatenate([self._delta_rows, rows])
        self._delta_ids = np.concatenate([self._delta_ids, ids])
        self._delta_live = np.concatenate(
            [self._delta_live, np.ones((len(rows),), bool)])
        if self._id_index is not None:
            for s, i in enumerate(ids):
                self._id_index[int(i)] = ("delta", base + s)
        self._upload_delta()
        self.world_version += 1
        self._maybe_auto_compact()
        return ids

    def delete(self, ids) -> None:
        """Delete rows by id. Main-R rows become tombstones — zeroed in
        the pinned R (their closed-form zero-row contribution is
        subtracted from exact sweeps, the ring pad-row mechanism) and
        masked out of candidate verification; delta rows just drop their
        live flag.  Unknown or already-deleted ids raise KeyError BEFORE
        any state changes, so a failed delete mutates nothing."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        index = self._stable_index()
        seen: set[int] = set()
        resolved = []
        for i in ids:
            i = int(i)
            loc = index.get(i)
            dead = (loc is None or i in seen
                    or (loc[0] == "main" and loc[1] in self._tomb_rows)
                    or (loc[0] == "delta" and not self._delta_live[loc[1]]))
            if dead:
                raise KeyError(
                    f"delete: id {i} is unknown or already deleted")
            seen.add(i)
            resolved.append(loc)
        main = [r for kind, r in resolved if kind == "main"]
        slots = [s for kind, s in resolved if kind == "delta"]
        if slots:
            self._delta_live[slots] = False
            self._upload_delta()
        if main:
            self._tomb_rows.update(main)
            rows = np.asarray(main, np.int32)
            # bucket the row list (repeat rows[0]: an idempotent pad) so
            # one compiled delete program serves every delete size
            rp = np.full((_bucket_size(len(rows), 64),), rows[0], np.int32)
            rp[: len(rows)] = rows
            prog = _delete_program(self.mesh, self.topology.r_spec())
            self._Rdev, self._tomb_dev = prog(
                self._Rdev, self._ensure_tomb(), jnp.asarray(rp))
            self._n_tomb_dev = jnp.asarray(len(self._tomb_rows), jnp.int32)
            if self._delta_dev is None:     # mutated: adjust must run even
                self._upload_delta()        # with an empty delta
        self.world_version += 1
        self._maybe_auto_compact()

    def compact(self) -> dict:
        """Fold the delta into the pinned R and drop the tombstones.

        Drains every live stream session (their in-flight results are
        re-emitted FIFO), evicts all compiled programs through
        `clear_program_cache()` (geometry changes: nr/nr_padded key the
        caches), re-uploads the merged (R ∪ delta − tombstones) set, and
        rebuilds the cached approximate verifiers with their recorded
        params so post-compact counts are what a fresh engine over the
        merged set would produce.  Returns a stats dict; a no-op (nothing
        pending) returns `{"compacted": False, ...}` without touching the
        program caches."""
        merged = len(self._tomb_rows) + len(self._delta_rows)
        if merged == 0:
            return {"compacted": False, "n_r": self.nr, "n_merged": 0,
                    "n_dropped": 0}
        for sess in list(self._sessions):
            sess._drain_for_compact()
        keep = np.ones((self.nr,), bool)
        keep[list(self._tomb_rows)] = False
        live = self._delta_live
        newR = np.concatenate([self._R_host[keep],
                               self._delta_rows[live]])
        if len(newR) == 0:
            raise ValueError(
                "compact: the logical index set is empty (every row "
                "deleted) — insert rows before compacting")
        n_merged = int(live.sum())
        n_dropped = len(self._tomb_rows) + int((~live).sum())
        clear_program_cache()
        self._upload_R(newR)
        self._main_ids = np.concatenate(
            [self._main_ids[keep], self._delta_ids[live]])
        self._id_index = None
        self._tomb_rows = set()
        self._delta_rows = np.empty((0, self.dim), np.float32)
        self._delta_ids = np.empty((0,), np.int64)
        self._delta_live = np.empty((0,), bool)
        self._delta_dev = self._delta_valid_dev = None
        self._tomb_dev = self._n_tomb_dev = None
        # rebuild approximate verify indices over the merged set with the
        # params their last build recorded (drop instances + placed probes)
        self._verifiers.clear()
        self._probes.clear()
        for name, params in self._verifier_params.items():
            self.verifier(name, **params)
        self.n_compactions += 1
        self.world_version += 1
        for sess in list(self._sessions):
            sess._rebind_after_compact()
        return {"compacted": True, "n_r": self.nr, "n_merged": n_merged,
                "n_dropped": n_dropped}

    def _maybe_auto_compact(self) -> None:
        if (self.auto_compact_at is not None
                and self.delta_frac >= self.auto_compact_at):
            self.compact()

    # ------------------------------------------------------------- plumbing
    def padded_rows(self, n: int) -> int:
        """Query rows a batch of `n` actually occupies after `_pad_q`'s
        power-of-two bucketing — the batch-composition hook the serve
        gateway's coalescer uses to pack requests up to a bucket boundary
        instead of paying the same padded sweep for half-empty batches
        (DESIGN.md §14)."""
        quantum = self.topology.q_row_quantum(self.block_q, self.mesh,
                                              self.data_axis)
        return _bucket_size(max(int(n), 1), quantum)

    def _pad_q(self, Q) -> np.ndarray:
        """Bucket the query count to a power-of-two multiple of one full
        mesh sweep (block_q rows per device, over every axis the topology
        shards queries on) — bounds recompiles AND keeps per-shard shapes
        block-aligned."""
        Q = np.asarray(Q, np.float32)
        quantum = self.topology.q_row_quantum(self.block_q, self.mesh,
                                              self.data_axis)
        return _pad_rows_np(Q, _bucket_size(len(Q), quantum))

    def _put_q(self, qp: np.ndarray) -> jax.Array:
        if self._q_sharding is not None:
            return jax.device_put(qp, self._q_sharding)
        return jnp.asarray(qp)

    def _pad_eps(self, eps_grid) -> np.ndarray:
        e = np.asarray(eps_grid, np.float32).reshape(-1)
        if self.backend == "pallas":
            pad = (-len(e)) % self.eps_chunk
            if pad:
                e = np.concatenate([e, np.full((pad,), np.inf, np.float32)])
        return e

    # ------------------------------------------------------- range counting
    def device_range_count_hist(self, Q, eps_grid) -> jax.Array:
        """Sharded sweep; returns the DEVICE array [n_padded, m_padded]
        (query axis distributed over the data axis). Callers that want the
        exact [n, m] table use `range_count_hist`."""
        qp = self._pad_q(Q)
        ep = self._pad_eps(eps_grid)
        prog = _hist_program(self.mesh, self.data_axis, self.backend,
                             self.metric, self.block_q, self.block_r,
                             self.eps_chunk, self.nr, self.topology)
        qdev, ep_dev = self._put_q(qp), jnp.asarray(ep)
        out = prog(qdev, self._Rdev, ep_dev, self._nrv_dev)
        w = self._world()
        if w.mutated:
            # logical-set adjustment (§13): add the live delta rows,
            # subtract the tombstones' closed-form contribution (padded
            # query rows / inf eps pad columns are sliced off by callers)
            out = _delta_hist_program(self.mesh, self.metric)(
                out, qdev, w.delta, w.dvalid, ep_dev, w.n_tomb_dev)
        return out

    def range_count_hist(self, Q, eps_grid) -> np.ndarray:
        """counts[i, j] = #-neighbors of Q[i] in R within eps_grid[j]."""
        m = np.asarray(eps_grid).reshape(-1).shape[0]
        out = self.device_range_count_hist(Q, eps_grid)
        return np.asarray(out)[: len(Q), :m]

    def range_count(self, Q, eps: float) -> np.ndarray:
        """counts[i] = #-neighbors of Q[i] in R within a single eps."""
        return self.range_count_hist(Q, [float(eps)])[:, 0]

    def cardinality_table(self, points, eps_grid, *,
                          exclude_self: bool = False) -> np.ndarray:
        """Ground-truth target table over the eps grid (optionally with
        each point's self-match removed, for R-vs-R training tables)."""
        t = self.range_count_hist(points, eps_grid)
        if exclude_self:
            t = np.maximum(t - 1, 0)
        return t

    # ------------------------------------------------- fused filtered join
    def _filter_program(self, predict):
        # keyed by the fn object itself (estimators memoize it): survives
        # refits without id-reuse aliasing, and the key pins the fn alive
        _, fn = predict
        prog = self._filter_progs.get(fn)
        if prog is None:
            def program(params, q, eps, thr, n_valid):
                X = jnp.concatenate(
                    [q, jnp.full((q.shape[0], 1), eps, jnp.float32)], axis=1)
                preds = fn(params, X)
                pos = (preds > thr) & (jnp.arange(q.shape[0]) < n_valid)
                return preds, pos, jnp.sum(pos, dtype=jnp.int32)
            prog = jax.jit(program)
            self._filter_progs[fn] = prog
        return prog

    # --------------------------------------------- stage 1: filter dispatch
    def _stage_filter(self, Q, eps: float, *, predict=None, threshold=None,
                      verdicts=None) -> "_StagedBatch":
        """Dispatch the filter program for one batch WITHOUT any host sync.

        Pads + `device_put`s the queries (async H2D), enqueues the fused
        estimator/XDT program (or uploads precomputed host verdicts), and
        returns a `_StagedBatch` handle. Nothing here waits on the device,
        so batch k+1 can be staged while batch k's verification is still
        executing — the double-buffering half of DESIGN.md §5."""
        st = _StagedBatch()
        st.Q = np.asarray(Q, np.float32)
        st.n = len(st.Q)
        st.eps = float(eps)
        t0 = time.perf_counter()
        qp = self._pad_q(st.Q)
        st.qdev = self._put_q(qp)
        st.eps_dev = self._eps_scalar_cache.get(st.eps)
        if st.eps_dev is None:
            if len(self._eps_scalar_cache) > 64:
                self._eps_scalar_cache.clear()
            st.eps_dev = jnp.asarray(st.eps, jnp.float32)
            self._eps_scalar_cache[st.eps] = st.eps_dev
        if predict is None and verdicts is None:
            # no filter: verify everything — the all-positive mask and its
            # count depend only on (padded rows, batch rows), so the
            # stream reuses one device-resident pair per shape bucket
            cached = self._allpos_cache.get((len(qp), st.n))
            if cached is None:
                if len(self._allpos_cache) > 64:
                    self._allpos_cache.clear()
                pos_host = np.zeros((len(qp),), bool)
                pos_host[:st.n] = True
                cached = ((jax.device_put(pos_host, self._q_sharding)
                           if self._q_sharding is not None
                           else jnp.asarray(pos_host)),
                          jnp.asarray(st.n, jnp.int32))
                self._allpos_cache[(len(qp), st.n)] = cached
            st.pos_dev, st.n_pos_dev = cached
            st.n_pos = st.n
        elif verdicts is not None:
            pos_host = np.zeros((len(qp),), bool)
            pos_host[:st.n] = np.asarray(verdicts, bool)
            st.n_pos = int(pos_host.sum())
            st.pos_dev = (jax.device_put(pos_host, self._q_sharding)
                          if self._q_sharding is not None
                          else jnp.asarray(pos_host))
            st.n_pos_dev = jnp.asarray(st.n_pos, jnp.int32)
        else:
            params, _ = predict
            prog = self._filter_program(predict)
            _, st.pos_dev, st.n_pos_dev = prog(
                params, st.qdev, st.eps_dev,
                jnp.asarray(threshold, jnp.float32),
                jnp.asarray(st.n, jnp.int32))
            st.n_pos = None                 # read at commit time
        st.probe = None                     # set by _stage_probe (§11)
        st.world = self._world()            # submit-time snapshot (§13)
        st.t_stage = time.perf_counter() - t0
        return st

    # ------------------------------------------- stage 2: probe dispatch
    def device_probe_for(self, verify: VerifySpec, mode: str = "auto", *,
                         eps: float | None = None):
        """Resolve the device-probe route for a verify spec (§11).

        mode="host" returns None (legacy host probing); "auto" returns a
        placed probe when the route's searcher advertises one
        (`device_probe(eps)` / `probe.PROBE_BUILDERS`) and None
        otherwise; "device" REQUIRES one and raises ValueError when the
        route has no probe stage (the exact sweep, query_counts-only
        plug-ins) or the searcher is host-only — at construction time,
        not mid-stream. `eps` is forwarded to the searcher's
        `device_probe` (None at plan-build/validation time); placement
        (table upload + program build) is cached per returned SPEC, so
        radius-free probes — which memoize one spec per index — pay the
        upload once, while an eps-aware searcher gets one placement per
        distinct spec it returns."""
        if mode not in PROBE_MODES:
            raise ValueError(f"probe={mode!r}: expected one of "
                             f"{list(PROBE_MODES)}")
        if mode == "host":
            return None
        label = _check_verify(verify)
        searcher = None
        if isinstance(verify, str):
            if verify != "exact":
                searcher = self.verifier(verify)
        elif hasattr(verify, "candidates"):
            searcher = verify
        if searcher is None:
            if mode == "device":
                raise ValueError(
                    f"probe='device': verify={label!r} has no probe stage "
                    "(the exact sweep and query_counts-only plug-ins "
                    "produce no candidates); use probe='auto'|'host' or an "
                    "approximate searcher")
            return None
        from repro.core.probe import as_device_probe
        spec = as_device_probe(searcher, eps)
        if spec is None:
            if mode == "device":
                raise ValueError(
                    f"probe='device': searcher {label!r} exposes no device "
                    "probe — implement device_probe(eps) (DESIGN.md §11) "
                    "or register a builder in probe.PROBE_BUILDERS; "
                    "probe='auto' falls back to host probing")
            return None
        placed = self._probes.get(spec)
        if placed is None:
            placed = spec.place(self)
            self._probes[spec] = placed
        return placed

    def _stage_probe(self, st: "_StagedBatch", *, placed=None,
                     block: int | None = None) -> "_StagedBatch":
        """Stage 2 of the pipeline (§11): read the staged batch's positive
        count (the pipeline's per-batch host sync — it waits on this
        batch's cheap filter program only) and, on a device-probe route,
        dispatch the compact-gather and probe programs, producing the
        candidate ids on device while the PREVIOUS batch's verification
        is still executing. Host-probe routes only perform the count
        read here; the probing itself stays in `_commit_verify`."""
        t0 = time.perf_counter()
        if st.n_pos is None:
            with _allowed_transfer("n_pos"):
                # xlint: allow-host-sync(n_pos: per-batch count read)
                st.n_pos = int(st.n_pos_dev)
        if placed is not None:
            st.probe = placed               # the route, even if this batch
            if st.n_pos > 0:                # stages nothing (all-negative)
                from repro.core.probe import _gather_program
                # probe cost is per-row (unlike the exact sweep, whose
                # program cost is dominated by |R|), so the capacity bucket
                # uses a fine 64-row quantum — the lcm of the IVF-PQ ADC
                # tile and the verify block — instead of the coarse
                # compaction block: small batches probe ~n_pos rows, not a
                # whole padded batch
                st.capacity = min(_bucket_size(st.n_pos, 64),
                                  st.qdev.shape[0])
                gather = _gather_program(self.mesh, self.data_axis)
                st.qpos_dev, st.idx_dev = gather(st.qdev, st.pos_dev,
                                                 capacity=st.capacity)
                st.cand_dev = placed.probe(st.qpos_dev)
        st.t_stage += time.perf_counter() - t0
        return st

    # ------------------------------------- stage 3: verify dispatch (commit)
    def _commit_verify(self, st: "_StagedBatch", *, verify: VerifySpec = "exact",
                       block: int | None = None) -> "PendingJoin":
        """Read the staged batch's positive count and dispatch verification.

        The `int(n_pos_dev)` here is the pipeline's only per-batch host
        sync; it waits on this batch's *filter* program only — earlier
        batches' (much deeper) verification programs keep running behind
        it. Returns a `PendingJoin`; device→host copies are started
        non-blocking so `result()` is usually a no-wait.

        `verify` is "exact", a `VERIFY_BACKENDS` name, or a plug-in
        searcher object (see `_check_verify`): any join method's
        `candidates()` can route the compacted positives through the
        device candidate-verification path — the Searcher half of the
        DESIGN.md §9 protocol contract."""
        label = _check_verify(verify)       # fail fast, not data-dependently
        t0 = time.perf_counter()
        if st.n_pos is None:                # direct callers skipped stage 2
            with _allowed_transfer("n_pos"):
                # xlint: allow-host-sync(n_pos: per-batch count read)
                st.n_pos = int(st.n_pos_dev)
        t_filter = st.t_stage + (time.perf_counter() - t0)
        n, n_pos = st.n, st.n_pos
        w = st.world                        # submit-time logical set (§13)
        probe_label = None if verify == "exact" else \
            ("device" if st.probe is not None else "host")

        if n_pos == 0:
            return PendingJoin(lambda: np.zeros((n,), np.int32), verify=label,
                               n_searched=0, t_filter=t_filter,
                               t_dispatch=0.0, probe=probe_label)

        t1 = time.perf_counter()
        if verify == "exact":
            capacity = min(_bucket_size(n_pos, block or self.block),
                           st.qdev.shape[0])
            cprog = _compact_program(self.mesh, self.data_axis, self.backend,
                                     self.metric, self.block_q, self.block_r,
                                     self.nr, self.topology)
            counts_dev = cprog(st.qdev, st.pos_dev, st.n_pos_dev, w.Rdev,
                               st.eps_dev, w.nrv, capacity=capacity)
            if w.mutated:
                # exact sweep counted tombstones (zeroed rows): subtract
                # their closed-form contribution and add the delta rows
                counts_dev = _delta_count_program(self.mesh, self.metric)(
                    counts_dev, st.qdev, st.pos_dev, w.delta, w.dvalid,
                    st.eps_dev, w.n_tomb_dev)
            _start_host_copy(counts_dev)
            # xlint: allow-host-sync(result: readback in PendingJoin.result)
            finalize = lambda: np.asarray(counts_dev)[:n]   # noqa: E731
        elif st.probe is not None:
            # device-probe route (§11): candidates were produced on device
            # by _stage_probe — verification + scatter dispatch here, with
            # no host transfer of verdicts or candidates at all
            counts_dev = st.probe.verify(
                st.qpos_dev, st.cand_dev, st.idx_dev, st.n_pos_dev,
                st.eps_dev, out_rows=st.qdev.shape[0], Rdev=w.Rdev,
                tomb=w.tomb)
            if w.mutated:
                # tombstones were masked in verification (a deleted row
                # may not even be a candidate), so only the delta is added
                counts_dev = _delta_count_program(self.mesh, self.metric)(
                    counts_dev, st.qdev, st.pos_dev, w.delta, w.dvalid,
                    st.eps_dev, None)
            _start_host_copy(counts_dev)
            # xlint: allow-host-sync(result: readback in PendingJoin.result)
            finalize = lambda: np.asarray(counts_dev)[:n]   # noqa: E731
        else:
            from repro.core.joins.common import (dispatch_verify_candidates,
                                                 searcher_candidates)
            searcher = self.verifier(verify) if isinstance(verify, str) \
                else verify
            # host probing needs the verdicts; the filter program is already
            # complete (n_pos was just read), so this transfer is cheap.
            # NOT an _allowed_transfer: host-probe routes are expected to
            # trip the transfer-guard lane (DESIGN.md §12)
            _note_host_sync("verdicts")
            # xlint: allow-host-sync(verdicts: host probe needs the verdicts)
            pos_host = np.asarray(st.pos_dev)[:n]
            idx = np.nonzero(pos_host)[0]
            qpos = st.Q[idx]
            # under mutations the delta adjustment runs through the SAME
            # device program as the device routes (not host numpy), so
            # host-vs-device probe count parity is preserved bit-for-bit
            adj_dev = None
            if w.mutated:
                adj_dev = _delta_count_program(self.mesh, self.metric)(
                    None, st.qdev, st.pos_dev, w.delta, w.dvalid,
                    st.eps_dev, None)
                _start_host_copy(adj_dev)
            if hasattr(searcher, "candidates"):
                _note_host_sync("probe")
                cand = searcher_candidates(searcher, qpos, st.eps)
                # on sharded placements each device verifies the candidate
                # ids that land in its own R shard (common.py psums them)
                shard = {} if self.r_shards == 1 else dict(
                    mesh=self.mesh, r_axis=self.topology.r_axis,
                    data_axis=self.data_axis,
                    shard_rows=self.nr_padded // self.r_shards)
                pend = dispatch_verify_candidates(
                    w.Rdev, qpos, cand, st.eps, self.metric,
                    backend=self.backend, tomb=w.tomb, **shard)

                def finalize():
                    counts = np.zeros((n,), np.int32)
                    counts[idx] = pend.result()
                    if adj_dev is not None:
                        # xlint: allow-host-sync(result: readback in PendingJoin.result)
                        counts = counts + np.asarray(adj_dev)[:n]
                    return counts
            else:
                # candidate-less plug-in: the searcher verifies the
                # compacted positives itself (synchronous host hop — the
                # generic "any loop-based method" fallback). It sweeps its
                # own copy of R, which cannot honor tombstones — refuse
                # rather than return silently wrong counts
                if w.n_tomb > 0:
                    raise RuntimeError(
                        f"verify={label!r}: query_counts-only plug-in "
                        "searchers cannot honor tombstoned deletes — "
                        "compact() first, or use a candidates() searcher "
                        "(DESIGN.md §13)")
                _note_host_sync("probe")
                found = np.asarray(searcher.query_counts(qpos, st.eps),
                                   np.int32)

                def finalize():
                    counts = np.zeros((n,), np.int32)
                    counts[idx] = found
                    if adj_dev is not None:
                        # xlint: allow-host-sync(result: readback in PendingJoin.result)
                        counts = counts + np.asarray(adj_dev)[:n]
                    return counts
        t_dispatch = time.perf_counter() - t1
        return PendingJoin(finalize, verify=label, n_searched=n_pos,
                           t_filter=t_filter, t_dispatch=t_dispatch,
                           probe=probe_label)

    # ------------------------------------------------ verification backends
    def verifier(self, name: str, **params):
        """The approximate searcher backing `verify=name` (DESIGN.md §5).

        Built lazily over the engine's host-side R and cached per name, so
        a serving session pays index construction once. Calling with
        `params` always (re)builds the index with those params and replaces
        the cached instance (e.g. `engine.verifier("lsh", l=16,
        n_probes=8)` before streaming is the tuning hook — a silent
        cache hit here would drop the override); calling without params
        returns the cached index, building with defaults on first use.
        The searcher must expose `candidates(Q) -> int32 [q, C]` (-1 pad).
        """
        if name not in VERIFY_BACKENDS or name == "exact":
            raise ValueError(
                f"verifier={name!r}: expected an approximate backend "
                f"({sorted(set(VERIFY_BACKENDS) - {'exact'})}; "
                "'exact' is the fused sweep — it has no index to build)")
        v = None if params else self._verifiers.get(name)
        if v is None:
            from repro.core.joins import make_join   # circular at import time
            stale = self._verifiers.get(name)
            if stale is not None:
                # a retune replaces the index: drop the old searcher's
                # placed probe too, or its device-resident tables would
                # stay pinned in self._probes for the engine's lifetime
                self._probes.pop(getattr(stale, "_probe_spec", None), None)
            v = make_join(name, self._R_host, self.metric, **params)
            if not hasattr(v, "candidates"):
                raise TypeError(f"join {name!r} exposes no candidates()")
            self._verifiers[name] = v
            # compact() rebuilds the index over the merged R with the
            # exact params of its last build (DESIGN.md §13)
            self._verifier_params[name] = dict(params)
        return v

    # --------------------------------------------------- one-shot join call
    def filtered_join(self, Q, eps: float, *, predict=None, threshold=None,
                      verdicts=None, block: int | None = None,
                      verify: VerifySpec = "exact",
                      probe: str = "auto") -> EngineJoinResult:
        """One synchronous filter -> threshold -> probe -> verify pass.

        Either pass `predict` = (params, fn) from an estimator's
        `device_predict_fn()` plus the XDT `threshold` (fully fused path),
        or a precomputed host bool `verdicts` array (plug-in filters).
        `block` overrides the compaction bucket quantum (default
        self.block); `verify` picks the verification backend ("exact" |
        "lsh" | "ivfpq", DESIGN.md §5 — or any Searcher object whose
        `candidates()` feeds the device verification path, DESIGN.md §9);
        `probe` ("auto" | "device" | "host", DESIGN.md §11) selects where
        the approximate route's index probe runs. This is the synchronous
        reference path — `stream` pipelines the same three stages."""
        placed = self.device_probe_for(verify, probe, eps=eps)
        st = self._stage_filter(Q, eps, predict=predict, threshold=threshold,
                                verdicts=verdicts)
        self._stage_probe(st, placed=placed, block=block)
        return self._commit_verify(st, verify=verify, block=block).result()

    # ------------------------------------------------------------ streaming
    def stream_session(self, eps: float, *, predict=None, threshold=None,
                       verify: VerifySpec = "exact", depth: int = 2,
                       block: int | None = None,
                       probe: str = "auto") -> "StreamSession":
        """Open an asynchronous `StreamSession` (push interface) over this
        engine; `stream` is the pull/iterator form of the same pipeline."""
        return StreamSession(self, eps, predict=predict, threshold=threshold,
                             verify=verify, depth=depth, block=block,
                             probe=probe)

    def stream(self, batches: Iterable, eps: float, *, predict=None,
               threshold=None, verify: VerifySpec = "exact", depth: int = 2,
               block: int | None = None,
               probe: str = "auto") -> Iterator[EngineJoinResult]:
        """Serving loop: pipeline query batches through the engine.

        Asynchronous double-buffered (DESIGN.md §5): each incoming batch is
        staged (filter dispatched) before the previous batch's verification
        is committed, and results are materialized only when more than
        `depth` batches are in flight — dispatch of batch k+1 overlaps the
        readback of batch k. Results are yielded in submission order and
        are bit-identical to per-batch `filtered_join` calls. R, the
        estimator, and all compiled programs stay device-resident across
        the whole stream (bucketed shapes). `depth=0` degenerates to
        commit-then-materialize per batch (still one staged batch of
        lookahead)."""
        sess = self.stream_session(eps, predict=predict, threshold=threshold,
                                   verify=verify, depth=depth, block=block,
                                   probe=probe)
        for Q in batches:
            yield from sess.submit(Q)
        yield from sess.flush()


def sharded_range_count_hist(Q, R, eps_grid, *, metric: str = "cosine",
                             mesh=None, backend: str = "auto",
                             block_q: int = 256, block_r: int = 512,
                             data_axis: str = "data",
                             topology: "str | Topology" = "replicated",
                             engine: "JoinEngine | None" = None) -> np.ndarray:
    """One-shot functional form of `JoinEngine.range_count_hist` (used by
    `data.groundtruth.cardinality_table`).

    Pass a pre-built `engine=` over the same (R, metric) to reuse its
    device-resident padded R — without it every call re-pads and
    re-uploads R (and that is exactly what repeated ground-truth sweeps
    used to do). The engine is validated against (R, metric): a mismatch
    raises instead of silently sweeping the wrong index set."""
    if engine is not None:
        if (engine.metric != metric or engine.nr != len(R)
                or not (engine._R_host is R
                        or np.array_equal(engine._R_host,
                                          np.asarray(R, np.float32)))):
            raise ValueError(
                "sharded_range_count_hist(engine=...): engine is built over "
                f"a different (R, metric) — engine has |R|={engine.nr}/"
                f"{engine.metric!r}, call has |R|={len(R)}/{metric!r}")
        if mesh is not None and engine.mesh is not mesh:
            raise ValueError(
                "sharded_range_count_hist(engine=..., mesh=...): the engine "
                "carries its own placement; drop mesh= (the engine's mesh "
                "wins) or drop engine= (a fresh engine is built on that "
                "mesh) — silently ignoring the mesh request would change "
                "where the sweep runs")
        return engine.range_count_hist(Q, eps_grid)
    eng = JoinEngine(R, metric, mesh=mesh, backend=backend, block_q=block_q,
                     block_r=block_r, data_axis=data_axis, topology=topology)
    return eng.range_count_hist(Q, eps_grid)
