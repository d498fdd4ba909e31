"""Pallas TPU kernel: IVF-PQ ADC scoring for the candidate ranking.

The IVF-PQ device probe (core/probe.py `_ivfpq_block`) used to rank the
candidate pool through a generic XLA chain: build the full ``[b, m,
256]`` LUT tensor, ``transpose`` it, gather per-candidate codes with
``take_along_axis``, reduce over segments, then ``top_k`` — four HBM
round-trips over intermediates larger than the inputs.  The kernel path
splits the work where each side is strongest:

  1. **LUT build** (XLA) — per PQ segment ``mi`` the ``[b, 256]`` table
     ``|q_mi|^2 - 2 q_mi . c + |c|^2`` is one matmul against the codebook
     slice (`lut_segment`, shared verbatim with the jnp path, so both
     paths look up the same bits).
  2. **code gather** (XLA) — the candidates' PQ code rows are gathered
     from the ``[n, m]`` code table in HBM by an XLA gather; the table
     itself never enters VMEM, so its size is bounded by HBM, not by the
     kernel's scoped VMEM.
  3. **lookup + accumulate** (kernel) — grid ``(query blocks, candidate
     blocks)``; per query row and segment the LUT lookup is a
     select-and-sum over the 256 codewords on the VPU (exact: one
     selected value plus zeros), accumulated in ascending segment order
     into the ``[b, C]`` ADC distances.  Tiling the candidate axis keeps
     VMEM bounded for any pool width (IVF-PQ pools are ``n_probe x
     list capacity`` wide — tens of thousands at paper scale).
  4. **rank** (XLA) — `_rank_tail`: ``-1`` lanes to inf, `jax.lax.top_k`,
     id gather — the same function the jnp path ends with.

Bit-identity contract: the jnp path (`adc_rank_jnp`) accumulates the
same per-segment lookups of the same LUT values in the same ascending
order and ranks with the same `_rank_tail`, so pallas and jnp candidate
ids are bit-identical by construction — including inf ties from ``-1``
padding lanes and duplicate ids from overlapping inverted lists.  The
pre-existing transpose+take_along_axis+top_k chain survives as
`adc_rank_chain` (the ops-level "ref" backend and the benchmark
baseline); its segment reduction order is whatever XLA picks for
``.sum()``, so it is value-identical but not guaranteed bit-identical on
ties.

VMEM budget at the defaults (``block_b=8``, ``block_c=512``, m <= 32):
LUT block ``[m, 256, 8]`` f32 lane-padded to 128 = 4 MB and codes
``[m, 8, 512]`` int32 = 512 KB (each double-buffered), plus the ``[256,
512]`` select temporaries (1 MB) — about 10 MB, independent of the
corpus size and of the pool width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.range_count import default_interpret


def lut_segment(q_mi: jax.Array, cb_mi: jax.Array) -> jax.Array:
    """f32 [b, 256] ADC table for ONE PQ segment: ``|q|^2 - 2 q.c +
    |c|^2`` with q_mi [b, seg], cb_mi [256, seg].  The single source of
    truth for both the jnp path and the kernel path — identical
    primitive sequence means identical bits.  HIGHEST keeps the dot in
    f32 on TPU (the default rounds both operands to bf16)."""
    dots = jax.lax.dot_general(q_mi, cb_mi, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    return (jnp.sum(q_mi * q_mi, -1)[:, None] - 2.0 * dots
            + jnp.sum(cb_mi * cb_mi, -1)[None, :])


def adc_rank_chain(q: jax.Array, codebooks: jax.Array, cand: jax.Array,
                   codes: jax.Array, *, n_cand: int) -> jax.Array:
    """The pre-kernel XLA chain (benchmark baseline / ops-level "ref"):
    full-LUT einsum, transpose, take_along_axis, sum, top_k.

    q f32 [b, dim], codebooks f32 [m, 256, seg], cand int32 [b, C]
    (-1 padded), codes uint8 [n, m].  Returns int32 [b, n_cand].
    """
    b = q.shape[0]
    m, _, seg = codebooks.shape
    qseg = q.reshape(b, m, seg)
    tables = (jnp.sum(qseg * qseg, -1)[:, :, None]
              - 2.0 * jnp.einsum("bms,mcs->bmc", qseg, codebooks,
                                 precision=jax.lax.Precision.HIGHEST)
              + jnp.sum(codebooks * codebooks, -1)[None])
    code_blk = codes[jnp.maximum(cand, 0)].astype(jnp.int32)
    adc = jnp.take_along_axis(jnp.transpose(tables, (0, 2, 1)),
                              code_blk, axis=1).sum(axis=2)
    adc = jnp.where(cand < 0, jnp.inf, adc)
    _, top = jax.lax.top_k(-adc, n_cand)
    return jnp.take_along_axis(cand, top, axis=1)


def _rank_tail(adc: jax.Array, cand: jax.Array, n_cand: int) -> jax.Array:
    """The n_cand lowest-ADC candidate ids: ``-1`` lanes rank last,
    `jax.lax.top_k` tie order (lower lane first).  Shared by the jnp and
    pallas paths."""
    adc = jnp.where(cand < 0, jnp.inf, adc)
    _, top = jax.lax.top_k(-adc, n_cand)
    return jnp.take_along_axis(cand, top, axis=1)


def adc_rank_jnp(q: jax.Array, codebooks: jax.Array, cand: jax.Array,
                 codes: jax.Array, *, n_cand: int) -> jax.Array:
    """Flat-LUT formulation: per-segment ``[b, 256]`` tables looked up
    and accumulated in ascending segment order — no ``[b, m, 256]``
    tensor, no transpose, no ``[b, C, m]`` gather intermediate.  Same
    contract as `adc_rank_chain`; bit-identical to the pallas kernel by
    construction (see module docstring)."""
    b = q.shape[0]
    m, _, seg = codebooks.shape
    qseg = q.reshape(b, m, seg)
    code_blk = codes[jnp.maximum(cand, 0)].astype(jnp.int32)   # [b, C, m]
    adc = jnp.zeros(cand.shape, jnp.float32)
    for mi in range(m):
        lut = lut_segment(qseg[:, mi], codebooks[mi])           # [b, 256]
        adc = adc + jnp.take_along_axis(lut, code_blk[:, :, mi], axis=1)
    return _rank_tail(adc, cand, n_cand)


#: candidates per pool tile (clipped to the pool width, rounded up to 128)
_BLOCK_C = 512


def _kernel(lut_ref, codes_ref, out_ref):
    bb, c = out_ref.shape
    code_iota = jax.lax.broadcasted_iota(jnp.int32, (256, c), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (bb, c), 0)

    def segment(mi, adc):
        # one PQ segment for every row of the block: select each
        # candidate's codeword entry of the row's LUT and sum over the
        # 256 codewords (one non-zero addend — exact)
        lut = lut_ref[mi]                         # [256, Bb] f32
        codes = codes_ref[mi]                     # [Bb, Ct] int32
        look = jnp.zeros((bb, c), jnp.float32)
        for b in range(bb):
            hit = code_iota == codes[b:b + 1, :]                  # [256, Ct]
            val = jnp.sum(jnp.where(hit, lut[:, b:b + 1], 0.0), axis=0,
                          keepdims=True)                          # [1, Ct]
            look = jnp.where(row == b, val, look)
        return adc + look

    out_ref[...] = jax.lax.fori_loop(0, codes_ref.shape[0], segment,
                                     jnp.zeros((bb, c), jnp.float32))


@functools.partial(jax.jit, static_argnames=("n_cand", "block_b", "interpret"))
def adc_rank_pallas(q: jax.Array, codebooks: jax.Array, cand: jax.Array,
                    codes: jax.Array, *, n_cand: int, block_b: int = 8,
                    interpret: bool | None = None) -> jax.Array:
    """Padded-shape kernel entry: q rows must be a block_b multiple
    (padding handled by ops.adc_rank; block_b a multiple of 8).  Same
    contract as `adc_rank_jnp`, bit-identical output.  `interpret=None`
    derives the mode from the runtime platform (compiled on TPU,
    interpret elsewhere)."""
    if interpret is None:
        interpret = default_interpret()
    b, dim = q.shape
    m, _, seg = codebooks.shape
    c = cand.shape[1]
    assert b % block_b == 0 and m * seg == dim and n_cand <= c
    nblk = b // block_b
    block_c = min(_BLOCK_C, -(-c // 128) * 128)
    c_pad = -(-c // block_c) * block_c
    qseg = q.reshape(b, m, seg)
    luts = jnp.stack([lut_segment(qseg[:, mi], codebooks[mi])
                      for mi in range(m)])                     # [m, b, 256]
    # per query tile, the LUT is [m, 256, Bb]: codewords on sublanes, so
    # a query's table is one lane column inside the kernel
    luts = luts.reshape(m, nblk, block_b, 256).transpose(1, 0, 3, 2)
    code_blk = codes[jnp.maximum(cand, 0)].astype(jnp.int32)   # [b, C, m]
    code_blk = jnp.transpose(code_blk, (2, 0, 1))               # [m, b, C]
    code_blk = jnp.pad(code_blk, ((0, 0), (0, 0), (0, c_pad - c)))

    adc = pl.pallas_call(
        _kernel,
        grid=(nblk, c_pad // block_c),
        in_specs=[
            pl.BlockSpec((None, m, 256, block_b), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((m, block_b, block_c), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, c_pad), jnp.float32),
        interpret=interpret,
    )(luts, code_blk)
    return _rank_tail(adc[:, :c], cand, n_cand)
