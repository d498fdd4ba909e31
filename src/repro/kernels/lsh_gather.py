"""Pallas TPU kernel: fused LSH bucket-gather + multiprobe dedup.

Device-side LSH probing (core/probe.py, DESIGN.md §11) turns probe
bucket ids into candidate ids by indexing the member tables:
``cand[q, t, j] = tables[t, pb[q, t, j]]``.  As a plain XLA gather this
materializes the full ``[q, l, n_probes]`` index tensor in HBM and
re-reads the (multi-MB) member table per probe.  This kernel streams
each table through VMEM in bucket tiles once per query block, serving
every probe of the block from the tile in hand, and emits the candidate
block directly.

Fused multiprobe **dedup**: `_lsh_multiprobe` pads its probe schedule by
repeating the identity probe, so duplicate bucket ids within one
(query, table) pair are common — every duplicate block is pure wasted
verify bandwidth.  Probe j whose bucket id equals an earlier probe
j' < j of the same pair emits an all ``-1`` block instead (``-1`` is the
existing empty-slot sentinel), which preserves the candidate *set* and
the verified counts exactly (verification already sort-dedups ids and
masks ``-1``) while letting the verify stage skip the repeats.

TPU formulation (no gather primitive inside Pallas kernels):
  * the tables are stored bucket-minor, ``[l, cap, B]``, so the bucket
    axis fills the 128-wide lanes densely (bucket-major, a small ``cap``
    would be padded to 128 lanes in HBM and VMEM).  `bucket_minor`
    lays a host table out this way once, when it is uploaded; every
    gather path reads that layout, so no call transposes a table.
  * grid ``(q_blocks, l, bucket_blocks)`` — per step the probe ids of one
    query block and one table, and ONE ``[cap, block_b]`` tile of that
    table's buckets, are VMEM-resident; the bucket axis is innermost and
    the output block accumulates across it, so a table of any bucket
    count streams through VMEM in fixed-size tiles.
  * the row gather is a one-hot MXU matmul: ``tile[cap, block_b] @
    onehot[Bq, block_b]^T``.  Ids are shifted by one (``-1`` -> 0) and
    split into four bytes, each gathered as a bf16 matmul with f32
    accumulation: a byte value and the one-hot's 0/1 are exact in bf16,
    and a row of the one-hot has at most one 1, so every gathered byte is
    exact whatever precision the matmul runs at.  The bytes recombine in
    int32; the output block starts at ``-1`` and each tile adds its
    (zero or shifted-id) contribution, so the result is bit-identical to
    a direct gather for every int32 id.
  * dedup is applied before the kernel: a duplicate probe's bucket id is
    replaced by ``-1``, which matches no one-hot column, so its block
    stays all ``-1``.
  * BlockSpecs keep the table axis and the probe axis outside the last
    two block dimensions, which are ``(block_q, 1)`` for the probe ids,
    ``(cap, block_b)`` for the table tiles and ``(cap, block_q)`` for the
    output — each a multiple of the (8, 128) tiling or the full array
    dimension, as Mosaic requires (so ``block_q`` is a multiple of 128).

VMEM budget at the defaults (``block_q=128``, ``block_b=2048``, cap <=
64): table tile 64 x 2048 int32 = 512 KB and probe ids 4 x 128 x 128
int32 (lane-padded) = 256 KB, each double-buffered; the ``[128, 2048]``
bucket iota, one-hot and the tile's byte planes (about 2.5 MB) — about
4 MB, inside the 16 MB scoped VMEM default for any bucket count.

Cost: every query block streams every bucket of every table, so the
work is ``O(q/block_q * l * B * cap * n_probes)`` — it grows with the
index's bucket count (about its row count), where a direct gather's
grows with ``q * l * n_probes * cap`` only.  No trace yet says which
of the two is faster on the chip at a given size (PERF.md, open
questions).

The jnp path (`lsh_bucket_gather_jnp`) is the reference formulation:
direct advanced-indexing gather + the same dedup mask.  Both paths
consume and produce only integers, so they are bit-identical by
construction — the device-probe parity tests compare them exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.range_count import default_interpret


def lsh_probe_dup_mask(pb: jax.Array) -> jax.Array:
    """bool [..., n_probes]: True where the probe's bucket id equals an
    EARLIER probe of the same (query, table) pair — the blocks the fused
    gather replaces with ``-1``.  Shared by the jnp path, the kernel
    (loop form of the same compares), and the tests."""
    n_probes = pb.shape[-1]
    eq = pb[..., :, None] == pb[..., None, :]
    earlier = jnp.tril(jnp.ones((n_probes, n_probes), bool), k=-1)
    return jnp.any(eq & earlier, axis=-1)


#: buckets per table tile streamed through VMEM (a table of fewer
#: buckets is one tile)
_BLOCK_B = 2048


def padded_buckets(n_buckets: int) -> int:
    """Bucket count of a `bucket_minor` table: a multiple of 128, and of
    `_BLOCK_B` once it spans more than one tile."""
    nb = -(-n_buckets // 128) * 128
    return nb if nb <= _BLOCK_B else -(-nb // _BLOCK_B) * _BLOCK_B


def bucket_minor(tables: np.ndarray) -> np.ndarray:
    """Host: member tables ``[..., B, cap]`` -> ``[..., cap, B_pad]``
    int32, the layout every gather path reads.  The padding buckets hold
    -1 (empty) and no probe id points at them."""
    t = np.swapaxes(np.asarray(tables, np.int32), -1, -2)
    nb = t.shape[-1]
    pad = [(0, 0)] * (t.ndim - 1) + [(0, padded_buckets(nb) - nb)]
    return np.ascontiguousarray(np.pad(t, pad, constant_values=-1))


def lsh_bucket_gather_jnp(tables: jax.Array, pb: jax.Array) -> jax.Array:
    """Reference formulation: XLA gather + dedup mask.

    tables int32 [l, cap, B] (`bucket_minor`; -1 marks empty slots), pb
    int32 [q, l, n_probes] probe bucket ids.  Returns int32
    [q, l*n_probes*cap] candidate ids, duplicate probes blanked to -1.
    """
    q = pb.shape[0]
    cand = tables[jnp.arange(tables.shape[0])[None, :, None], :, pb]
    dup = lsh_probe_dup_mask(pb)
    cand = jnp.where(dup[..., None], jnp.int32(-1), cand)
    return cand.reshape(q, -1)


def _kernel(pb_ref, tab_ref, out_ref, *, n_probes: int, block_b: int):
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, -1, jnp.int32)

    bq = pb_ref.shape[1]
    shifted = tab_ref[...] + 1                     # [cap, block_b], >= 0
    planes = [((shifted >> (8 * k)) & 0xFF).astype(jnp.float32)
              .astype(jnp.bfloat16) for k in range(4)]
    bucket = b * block_b + jax.lax.broadcasted_iota(jnp.int32, (bq, block_b),
                                                    1)
    for j in range(n_probes):
        onehot = (bucket == pb_ref[j]).astype(jnp.bfloat16)   # [Bq, block_b]
        val = jnp.zeros(out_ref.shape[1:], jnp.int32)          # [cap, Bq]
        for k, plane in enumerate(planes):
            g = jax.lax.dot_general(plane, onehot, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            val = val | (g.astype(jnp.int32) << (8 * k))
        out_ref[j] += val


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def lsh_bucket_gather_pallas(tables: jax.Array, pb: jax.Array, *,
                             block_q: int = 128,
                             interpret: bool | None = None) -> jax.Array:
    """Padded-shape kernel entry: pb rows must be a block_q multiple and
    block_q a multiple of 128 (padding handled by ops.lsh_bucket_gather);
    the tables' bucket count is `padded_buckets`-aligned, as
    `bucket_minor` leaves it.  Same contract as `lsh_bucket_gather_jnp`,
    bit-identical output.
    `interpret=None` derives the mode from the runtime platform
    (compiled on TPU, interpret elsewhere)."""
    if interpret is None:
        interpret = default_interpret()
    q, l, n_probes = pb.shape
    _, cap, nb = tables.shape
    assert q % block_q == 0 and block_q % 128 == 0
    assert nb == padded_buckets(nb)
    block_b = min(_BLOCK_B, nb)
    # duplicate probes -> bucket -1 (matches nothing: the block stays -1)
    pbd = jnp.where(lsh_probe_dup_mask(pb), jnp.int32(-1), pb)
    pbt = jnp.transpose(pbd, (1, 2, 0))[..., None]            # [l, p, q, 1]

    kernel = functools.partial(_kernel, n_probes=n_probes, block_b=block_b)
    out = pl.pallas_call(
        kernel,
        grid=(q // block_q, l, nb // block_b),
        in_specs=[
            pl.BlockSpec((None, n_probes, block_q, 1),
                         lambda i, t, b: (t, 0, i, 0)),
            pl.BlockSpec((None, cap, block_b), lambda i, t, b: (t, 0, b)),
        ],
        out_specs=pl.BlockSpec((None, n_probes, cap, block_q),
                               lambda i, t, b: (t, 0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((l, n_probes, cap, q), jnp.int32),
        interpret=interpret,
    )(pbt, tables)
    return jnp.transpose(out, (3, 0, 1, 2)).reshape(q, -1)
