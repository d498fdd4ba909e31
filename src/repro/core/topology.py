"""Topology layer: where R lives on the mesh (DESIGN.md §10).

The engine (core/engine.py) used to bake one placement decision into every
device program: queries shard over the mesh's ``data`` axis, the index set
R replicates onto every device.  That caps |R| at a single device's HBM —
the opposite of the multi-host north star.  This module makes placement a
first-class, swappable layer.  A `Topology` answers four questions:

  1. how are the padded query rows sharded (`q_spec` / `q_row_quantum`),
  2. how are the padded R rows sharded (`r_spec` / `r_row_quantum`),
  3. how does the range-count sweep run over that placement
     (`hist_program`), and
  4. how does the fused compact -> verify -> scatter program run
     (`compact_program`).

Two implementations:

  * `Replicated` — the original placement.  Q shards over ``data``; every
    device sweeps its query slice against the full replicated R.  Zero
    communication per sweep; per-device R memory is all of R.
  * `RingSharded` — R row-shards over a second mesh axis (``r`` by
    default, built by `launch.mesh.make_join_mesh(data=, r=)`), so peak
    per-device R bytes drop by the r-axis size.  Q shards over BOTH axes.
    The sweep runs as a `jax.lax.ppermute` ring: at each of the
    ``r_shards`` steps every device histograms its resident R shard
    against the query block currently rotating through it and records the
    partial counts under that block's home position; after the rotation
    the partial counts are `psum`'d over ``r`` and each device keeps its
    own block's total.  The compact/verify path gathers only the
    predicted-positive candidates across R shards (replicating the small
    compacted block, or sharding it over ``data`` when it divides evenly)
    and `psum`s the per-shard counts.

Padding convention: R rows are padded to a multiple of
``r_row_quantum(block_r)`` so every shard is block-aligned with the SAME
static shape.  Padding rows are all-zero vectors, which sit at a known
distance from any unit query (cosine: exactly 1.0; l2: exactly sqrt(2)),
so instead of threading a static per-shard valid count into the kernels
(impossible — shards differ, programs are shared), the ring programs
count padded rows too and subtract the closed-form zero-row contribution
using the traced per-shard valid count (`nr_valid_shards`).  Counts stay
bit-identical to the unpadded oracle.

Topologies are tiny frozen dataclasses: hashable, so they key the
engine's module-level `lru_cache` of compiled programs (every one
registered in `engine._PROGRAM_CACHES` — xlint's jit-cache-key rule
rejects unhashable program-builder params, DESIGN.md §12), and
stateless, so one instance can serve any number of engines.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.kernels import ops, ref
from repro.kernels.range_count import range_count_hist_pallas


def _shard_mapped(f, mesh, in_specs, out_specs):
    """`jax.shard_map` with the varying-manual-axes check off: the ring
    programs carry per-shard values (rotating query blocks, partial
    counts) through Python-unrolled loops and scans whose carries start
    replicated, which the check would reject as a type mismatch."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def _data_size(mesh, data_axis: str) -> int:
    return int(mesh.shape.get(data_axis, 1)) if mesh is not None else 1


def _q_blocked_hist(q, r, eps, *, metric, block_q, block_r, nr_valid):
    """[n, m] histogram, scanning q in block_q tiles so the fused
    compare tensor stays O(block_q * block_r * m). q rows % block_q == 0."""
    nblk = q.shape[0] // block_q
    qb = q.reshape(nblk, block_q, q.shape[1])
    out = jax.lax.map(
        lambda x: ops.blocked_hist(x, r, eps, metric=metric,
                                   block_r=block_r, nr_valid=nr_valid), qb)
    return out.reshape(nblk * block_q, eps.shape[0])


def _per_shard_hist(backend, metric, block_q, block_r, eps_chunk, nr_valid):
    """(q, r, eps) -> int32 [q, m] per-shard sweep for one backend.

    `nr_valid` masks R rows past that global index; None means "count
    every row" (the ring topology masks via the zero-row correction
    instead, because its per-shard valid counts are traced values)."""
    if backend == "pallas":
        def shard_fn(q, r, eps):
            return range_count_hist_pallas(
                q, r, eps, metric=metric, nr_valid=nr_valid, block_q=block_q,
                block_r=block_r, eps_chunk=eps_chunk)
    elif backend == "ref":
        def shard_fn(q, r, eps):
            return ref.range_count_hist(q, r, eps, metric)
    else:
        def shard_fn(q, r, eps):
            return _q_blocked_hist(
                q, r, eps, metric=metric, block_q=block_q, block_r=block_r,
                nr_valid=r.shape[0] if nr_valid is None else nr_valid)
    return shard_fn


def _zero_row_distance(metric: str) -> jax.Array:
    """Distance of an all-zero padding row from any unit query, computed
    with the same f32 ops as the sweep kernels (bit-exact correction)."""
    if metric == "cosine":
        return jnp.asarray(1.0, jnp.float32)          # 1 - q.0
    return jnp.sqrt(jnp.asarray(2.0, jnp.float32))    # sqrt(2 - 2 q.0)


def _subtract_pad_rows(counts, eps, n_pad, metric):
    """Remove the padding rows' contribution from a per-shard histogram.

    All padding rows are identical zero vectors at `_zero_row_distance`,
    so each contributes 1 to every eps bin at or above that distance;
    `n_pad` is traced (per-shard), making this the masking mechanism that
    works under shared static-shape programs."""
    hit = (_zero_row_distance(metric)
           <= eps.astype(jnp.float32)).astype(jnp.int32)
    return counts - n_pad.astype(jnp.int32) * hit[None, :]


# ============================================================== the contract
@dataclass(frozen=True)
class Topology:
    """Placement contract for the join engine (DESIGN.md §10).

    Subclasses are stateless frozen dataclasses (hashable — they key the
    engine's module-level compiled-program caches) answering: how Q and R
    shard over the mesh, what row quanta their paddings must honor, and
    how the sweep / compact programs execute over that placement."""

    name = "abstract"

    def r_shards(self, mesh) -> int:
        """Number of R row-shards on this mesh (1 = fully replicated)."""
        return 1

    def validate(self, mesh, data_axis: str) -> None:
        """Raise ValueError when `mesh` cannot host this placement."""

    def q_spec(self, data_axis: str) -> P:
        """PartitionSpec of the padded query row axis."""
        raise NotImplementedError

    def r_spec(self) -> P:
        """PartitionSpec of the device-resident padded R rows."""
        raise NotImplementedError

    def r_row_quantum(self, block_r: int, mesh) -> int:
        """R rows are padded to a multiple of this before upload."""
        return block_r

    def q_row_quantum(self, block_q: int, mesh, data_axis: str) -> int:
        """Query rows are bucketed to a multiple of this (one full mesh
        sweep: block-aligned per-device shapes on every device)."""
        raise NotImplementedError

    def nr_valid_shards(self, nr: int, nr_padded: int, mesh):
        """int32 [r_shards] valid-row count per R shard, or None when the
        placement needs no per-shard masking (replicated)."""
        return None

    def probe_shards(self, mesh) -> int:
        """Number of ways device-probe tables shard on this mesh
        (DESIGN.md §11): 1 = replicated on every device."""
        return 1

    def probe_spec(self) -> P:
        """PartitionSpec of a probe table's leading shard axis (only
        meaningful when `probe_shards` > 1)."""
        return P()

    def delta_spec(self) -> P:
        """PartitionSpec of the dynamic-R delta shard (DESIGN.md §13):
        replicated under EVERY placement.  The delta is small by policy
        (auto-compaction bounds it at a fraction of |R|), so replicating
        it keeps the ring sweep schedule untouched — no extra ppermute
        steps; the delta adjustment is a purely local dense op on each
        device, psum-free under both topologies."""
        return P()

    def per_device_r_bytes(self, nr_padded: int, dim: int, mesh) -> int:
        """Bytes of R resident on EACH device under this placement."""
        raise NotImplementedError

    def sweep_collectives(self, r_shards: int) -> int:
        """Cross-device collectives issued by ONE sweep/probe dispatch
        under this placement at `r_shards` R shards (the planner's
        communication cost hook, DESIGN.md §16): 0 for a replicated R,
        the ring-schedule hop count for sharded placements.  Takes the
        shard count, not a mesh — the planner prices candidate
        configurations before any mesh exists."""
        return 0

    def verify_collectives(self, r_shards: int) -> int:
        """Collectives per candidate-verify dispatch at `r_shards` R
        shards: 0 when counts are device-local, 1 for the sharded
        placements' combining `psum`."""
        return 0

    def hist_program(self, mesh, data_axis, backend, metric, block_q,
                     block_r, eps_chunk, nr_valid):
        """Compiled sweep `(q, r, eps, nrv) -> int32 [n, m]` over this
        placement (cached by the engine per argument tuple)."""
        raise NotImplementedError

    def compact_program(self, mesh, data_axis, backend, metric, block_q,
                        block_r, nr_valid):
        """Compiled fused compact -> verify -> scatter program
        `(q, pos, n_pos, r, eps, nrv, *, capacity) -> int32 [n]`."""
        raise NotImplementedError

    def _compact_scaffold(self, sweep):
        """Shared compact -> verify -> scatter shell around a placement's
        `sweep(qpos, r, eps1, nrv, capacity) -> int32 [capacity]` hook:
        gather the positives into the bucketed static shape, sweep them,
        and scatter the counts back (padding lanes all add 0 onto row 0).
        One place owns the compaction conventions so the
        topologies cannot diverge."""

        def prog(q, pos, n_pos, r, eps, nrv, *, capacity: int):
            idx = jnp.nonzero(pos, size=capacity, fill_value=0)[0]
            valid = jnp.arange(capacity) < n_pos
            qpos = jnp.take(q, idx, axis=0)
            eps1 = jnp.reshape(eps, (1,)).astype(jnp.float32)
            found = sweep(qpos, r, eps1, nrv, capacity)
            contrib = jnp.where(valid, found, 0).astype(jnp.int32)
            return jnp.zeros((q.shape[0],), jnp.int32).at[idx].add(contrib)

        return jax.jit(prog, static_argnames=("capacity",))


# ================================================================ replicated
@dataclass(frozen=True)
class Replicated(Topology):
    """R replicated on every device; Q sharded over the ``data`` axis.

    The original engine placement: zero communication per sweep, every
    device holds all of (padded) R.  Right whenever R fits in one
    device's memory — it is the fastest placement at that scale."""

    name = "replicated"

    def q_spec(self, data_axis: str) -> P:
        """Queries shard over the data axis only."""
        return P(data_axis)

    def r_spec(self) -> P:
        """R is fully replicated."""
        return P()

    def q_row_quantum(self, block_q: int, mesh, data_axis: str) -> int:
        """block_q rows per data-axis device."""
        return block_q * _data_size(mesh, data_axis)

    def per_device_r_bytes(self, nr_padded: int, dim: int, mesh) -> int:
        """Every device holds the full padded R."""
        return int(nr_padded) * int(dim) * 4

    def hist_program(self, mesh, data_axis, backend, metric, block_q,
                     block_r, eps_chunk, nr_valid):
        """Per-device sweep of the local query slice vs all of R,
        shard_map'ped over ``data`` when the mesh has >1 data device."""
        shard_fn = _per_shard_hist(backend, metric, block_q, block_r,
                                   eps_chunk, nr_valid)
        if _data_size(mesh, data_axis) > 1:
            shard_fn = _shard_mapped(shard_fn, mesh,
                                     in_specs=(P(data_axis), P(), P()),
                                     out_specs=P(data_axis))
        return jax.jit(lambda q, r, eps, nrv=None: shard_fn(q, r, eps))

    def compact_program(self, mesh, data_axis, backend, metric, block_q,
                        block_r, nr_valid):
        """Gather positives -> single-eps sweep vs replicated R -> scatter.
        `capacity` is the bucketed static shape; `n_pos` rides along as a
        device scalar so one executable serves every bucket occupancy."""
        from jax.sharding import NamedSharding

        def sweep(qpos, r, eps1, nrv, capacity):
            if _data_size(mesh, data_axis) > 1:
                qpos = jax.lax.with_sharding_constraint(
                    qpos, NamedSharding(mesh, P(data_axis)))
            if backend == "ref":
                return ref.range_count_hist(qpos, r, eps1, metric)[:, 0]
            # one eps: the compare temporary is [capacity, block_r], so the
            # positives need no query tiling
            return ops.blocked_hist(qpos, r, eps1, metric=metric,
                                    block_r=block_r, nr_valid=nr_valid)[:, 0]

        return self._compact_scaffold(sweep)


# =============================================================== ring-sharded
@dataclass(frozen=True)
class RingSharded(Topology):
    """R row-sharded over the mesh's ``r`` axis; ppermute ring sweep.

    Per-device R memory drops by the r-axis size, so |R| scales past one
    device's HBM.  Q shards over BOTH mesh axes; each sweep runs
    ``r_shards`` ring steps (rotate the query block over ``r`` with
    `jax.lax.ppermute`, histogram it against the resident R shard) and a
    final `psum` over ``r`` combines the per-shard partial counts.  Use
    `launch.mesh.make_join_mesh(data=, r=)` to build the 2-D mesh."""

    name = "ring"
    r_axis: str = "r"
    #: overlapped sweep schedule (DESIGN.md §15): the next query block's
    #: `ppermute` is issued BEFORE the current histogram step so the hop
    #: hides behind compute, and the partial counts combine via a ring
    #: reduce-scatter (r_size - 1 hops of one [q_local, m] int32 row)
    #: instead of a [r_size, q_local, m] buffer + full psum + take.
    #: int32 addition is associative, so counts stay bit-identical to
    #: the serial formulation (`overlap=False`, kept for benchmarking).
    overlap: bool = True

    def r_shards(self, mesh) -> int:
        """Size of the mesh's ``r`` axis."""
        return int(mesh.shape[self.r_axis]) if mesh is not None else 1

    def validate(self, mesh, data_axis: str) -> None:
        """Ring placement needs a mesh carrying both the ``r`` axis and
        the data axis (`launch.mesh.make_join_mesh`)."""
        if mesh is None:
            raise ValueError(
                f"topology='ring' needs a mesh with an {self.r_axis!r} "
                "axis — build one with launch.mesh.make_join_mesh(data=, "
                "r=) or let JoinPlan.on(topology='ring', r_shards=...) "
                "build it")
        missing = {self.r_axis, data_axis} - set(mesh.axis_names)
        if missing:
            raise ValueError(
                f"topology='ring': mesh axes {mesh.axis_names} lack "
                f"{sorted(missing)} (expected a make_join_mesh(data=, r=) "
                "mesh)")

    def q_spec(self, data_axis: str) -> P:
        """Queries shard over (r, data) jointly — every device owns a
        block, so Q memory also drops by the r-axis size."""
        return P((self.r_axis, data_axis))

    def r_spec(self) -> P:
        """R rows shard over the ``r`` axis (replicated over ``data``)."""
        return P(self.r_axis)

    def r_row_quantum(self, block_r: int, mesh) -> int:
        """Shards must be equal-sized AND block_r-aligned."""
        return block_r * self.r_shards(mesh)

    def q_row_quantum(self, block_q: int, mesh, data_axis: str) -> int:
        """block_q rows per device over both axes."""
        return block_q * _data_size(mesh, data_axis) * self.r_shards(mesh)

    def nr_valid_shards(self, nr: int, nr_padded: int, mesh) -> np.ndarray:
        """Valid (non-padding) rows in each equal-sized R shard."""
        r = self.r_shards(mesh)
        rows = nr_padded // r
        return np.clip(nr - np.arange(r) * rows, 0, rows).astype(np.int32)

    def probe_shards(self, mesh) -> int:
        """Probe tables shard `r_shards` ways: each device probes only
        the member table of its own R shard (DESIGN.md §11), so
        candidate ids stay local and per-device table bytes drop by the
        r-axis size alongside R itself."""
        return self.r_shards(mesh)

    def probe_spec(self) -> P:
        """Probe tables shard their leading axis over the ``r`` axis."""
        return P(self.r_axis)

    def per_device_r_bytes(self, nr_padded: int, dim: int, mesh) -> int:
        """Each device holds one R shard: padded rows / r_shards."""
        return int(nr_padded) // self.r_shards(mesh) * int(dim) * 4

    def sweep_collectives(self, r_shards: int) -> int:
        """PR 9 ring schedule (DESIGN.md §15): the overlapped sweep
        issues ``r - 1`` query-rotation ppermutes plus ``r - 1``
        reduce-scatter hops = ``2 (r - 1)``; the serial sweep issues
        ``r - 1`` rotations plus one combining psum = ``r``."""
        r = int(r_shards)
        return 2 * (r - 1) if self.overlap else r

    def verify_collectives(self, r_shards: int) -> int:
        """Sharded candidate verification combines per-shard counts with
        one `psum` over ``r``."""
        return 1

    def hist_program(self, mesh, data_axis, backend, metric, block_q,
                     block_r, eps_chunk, nr_valid):
        """The ring sweep (DESIGN.md §10).

        shard_map'd over the full mesh: at step k each device histograms
        its resident R shard against the query block that has rotated k
        hops along the ``r`` ring, storing the partial counts under the
        block's home position; the per-position partials are then
        `psum`'d over ``r`` and each device keeps its own block's total.
        Padding rows are counted and subtracted in closed form
        (`_subtract_pad_rows`) using the traced per-shard valid count, so
        one static-shape program serves every shard.

        Two schedules (DESIGN.md §15):

        * `overlap=True` (default) — the next block's `ppermute` is
          issued BEFORE the current `inner(...)` histogram and consumed
          after it, so the hop transfers while the MXU sweeps (XLA's
          latency-hiding scheduler overlaps an async collective with
          independent compute; `launch.xla_flags` enables the same on
          GPU).  Partial counts combine via a ring reduce-scatter:
          each block's running sum rides the ring absorbing one
          device's contribution per hop, r_size - 1 hops of a single
          [q_local, m] int32 row — no [r_size, q_local, m] buffer, no
          full-buffer `psum`, no final `take`, and 2(r_size - 1) total
          collectives vs the serial schedule's r_size.
        * `overlap=False` — the original serial formulation (histogram,
          park the partial in a per-position buffer, rotate, `psum` at
          the end), kept as the benchmark baseline.

        Both accumulate the same int32 partials (addition over ints is
        associative + commutative), so counts are bit-identical."""
        self.validate(mesh, data_axis)
        r_size = self.r_shards(mesh)
        inner = _per_shard_hist(backend, metric, block_q, block_r,
                                eps_chunk, None)
        perm = [(i, (i + 1) % r_size) for i in range(r_size)]

        def sweep_overlap(q, r_shard, eps, nrv):
            n_pad = r_shard.shape[0] - nrv[0]
            qc = q
            parts = []
            for k in range(r_size):
                qn = (jax.lax.ppermute(qc, self.r_axis, perm)
                      if k < r_size - 1 else None)     # start the hop...
                # the block in hand is k hops from home: parts[k] is this
                # shard's contribution to block (me - k)
                parts.append(_subtract_pad_rows(inner(qc, r_shard, eps),
                                                eps, n_pad, metric))
                if qn is not None:
                    qc = qn                            # ...consume it here
            # ring reduce-scatter: block b's running sum starts one hop
            # past home (device b+1, = this device's parts[1]) and rides
            # the ring absorbing each host device's contribution; after
            # r_size - 1 hops of one [q_local, m] row each, the carry on
            # every device is its own block's total.  r_size == 1
            # compiles to zero collectives.
            carry = parts[1 % r_size]
            for j in range(1, r_size):
                carry = jax.lax.ppermute(carry, self.r_axis, perm)
                carry = carry + parts[(j + 1) % r_size]
            return carry

        def sweep_serial(q, r_shard, eps, nrv):
            n_pad = r_shard.shape[0] - nrv[0]
            me = jax.lax.axis_index(self.r_axis)
            buf = jnp.zeros((r_size, q.shape[0], eps.shape[0]), jnp.int32)
            qc = q
            for k in range(r_size):
                part = _subtract_pad_rows(inner(qc, r_shard, eps), eps,
                                          n_pad, metric)
                # the block in hand is k hops from home along the ring
                buf = buf.at[jnp.mod(me - k, r_size)].set(part)
                if k < r_size - 1:
                    qc = jax.lax.ppermute(qc, self.r_axis, perm)
            buf = jax.lax.psum(buf, self.r_axis)
            return jnp.take(buf, me, axis=0)

        sweep = sweep_overlap if self.overlap else sweep_serial
        mapped = _shard_mapped(
            sweep, mesh,
            in_specs=(P((self.r_axis, data_axis)), P(self.r_axis), P(),
                      P(self.r_axis)),
            out_specs=P((self.r_axis, data_axis)))
        return jax.jit(mapped)

    def compact_program(self, mesh, data_axis, backend, metric, block_q,
                        block_r, nr_valid):
        """Compact -> sharded verify -> scatter for ring placement.

        Only the predicted-positive candidates travel: the compacted
        block (bucketed `capacity` rows) is gathered across R shards —
        sharded over ``data`` when capacity divides evenly, replicated
        otherwise — each device sweeps it against its resident R shard,
        and the per-shard counts are `psum`'d over ``r``."""
        self.validate(mesh, data_axis)
        ndata = _data_size(mesh, data_axis)

        def sweep(qpos, r, eps1, nrv, capacity):
            shard_data = ndata > 1 and capacity % ndata == 0
            qspec = P(data_axis) if shard_data else P()

            def shard_fn(qp, rs, e, nv):
                if backend == "ref":
                    found = ref.range_count_hist(qp, rs, e, metric)
                else:
                    found = ops.blocked_hist(qp, rs, e, metric=metric,
                                             block_r=block_r,
                                             nr_valid=rs.shape[0])
                found = _subtract_pad_rows(found, e, rs.shape[0] - nv[0],
                                           metric)
                return jax.lax.psum(found, self.r_axis)

            mapped = _shard_mapped(
                shard_fn, mesh,
                in_specs=(qspec, P(self.r_axis), P(), P(self.r_axis)),
                out_specs=qspec)
            return mapped(qpos, r, eps1, nrv)[:, 0]

        return self._compact_scaffold(sweep)


#: Registered topology names -> classes (the `JoinPlan.on(topology=...)`
#: and `JoinEngine(topology=...)` vocabulary).
TOPOLOGIES = {"replicated": Replicated, "ring": RingSharded}


def resolve_topology(spec, *, r_axis: str = "r") -> Topology:
    """Coerce a topology spec onto the Topology contract.

    Accepts a `Topology` instance (returned as-is), None / "replicated"
    (the default placement), or "ring" (R sharded over `r_axis`).  Raises
    ValueError for anything else — at construction time, not
    data-dependently inside a device program."""
    if isinstance(spec, Topology):
        return spec
    if spec is None or spec == "replicated":
        return Replicated()
    if spec == "ring":
        return RingSharded(r_axis=r_axis)
    raise ValueError(f"topology={spec!r}: expected one of "
                     f"{sorted(TOPOLOGIES)} or a Topology instance")
