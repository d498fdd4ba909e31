"""Compile rehearsals for a TPU v5e: every Pallas kernel of the main path,
at the widths the chip runs it, compiled ahead of time against a
described (not attached) ``v5e:2x2`` topology.

Each test lowers the kernel with `interpret=False`, compiles it with the
TPU compiler and asserts that the program holds the Mosaic kernel
(`tpu_custom_call`).  A compile that passes is not a chip run: it proves
the kernel lowers (BlockSpecs legal for the (8, 128) tiling, no
primitive Mosaic cannot lower) and that its blocks fit the scoped VMEM
limit, which the compiler enforces.  Nothing runs, so no result or time
comes from here.

The topology is described inside a module fixture and every sharding is
built from it there: the TPU library may be loaded by one process at a
time, so nothing touches it while test modules are imported.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.adc_rank import adc_rank_pallas
from repro.kernels.fused_mlp import mlp_forward_pallas
from repro.kernels.lsh_gather import lsh_bucket_gather_pallas
from repro.kernels.range_count import range_count_hist_pallas
from repro.models.mlp import PAPER_WIDTHS


@pytest.fixture(scope="module")
def one_chip():
    """A sharding on chip 0 of a described v5e:2x2 host (skips when the
    TPU compiler cannot describe one).  The persistent compilation cache
    is off meanwhile: a compile for a described chip cannot be read
    back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("d,block_r", [(200, 512), (960, 1024)])
def test_range_count_compiles_for_v5e(one_chip, d, block_r):
    """The sweep kernel at the glove (d=200, default tile) and gist
    (d=960, widened tile) widths, over the 104-column Xling eps grid."""
    nq, nr, m = 1024, 4 * block_r, 104
    fn = jax.jit(lambda q, r, e: range_count_hist_pallas(
        q, r, e, metric="cosine", nr_valid=nr - 7, block_q=256,
        block_r=block_r, interpret=False))
    _assert_kernel(fn.lower(_spec((nq, d), jnp.float32, one_chip),
                            _spec((nr, d), jnp.float32, one_chip),
                            _spec((m,), jnp.float32, one_chip)).compile())


def test_fused_mlp_compiles_for_v5e(one_chip):
    """The estimator at the paper's 512/512/256/128 widths over d=200
    inputs plus the eps feature."""
    dims = (201,) + PAPER_WIDTHS + (1,)
    params = tuple((_spec((a, b), jnp.float32, one_chip),
                    _spec((1, b), jnp.float32, one_chip))
                   for a, b in zip(dims[:-1], dims[1:]))
    fn = jax.jit(lambda p, x: mlp_forward_pallas(p, x, interpret=False))
    _assert_kernel(fn.lower(params,
                            _spec((1024, 201), jnp.float32, one_chip))
                   .compile())


def test_lsh_gather_compiles_for_v5e(one_chip):
    """The bucket gather over the member tables of a 120k-row index
    (l=10 tables of 2**17 buckets, stored bucket-minor, 4 probes)."""
    tables = _spec((10, 37, 1 << 17), jnp.int32, one_chip)
    pb = _spec((512, 10, 4), jnp.int32, one_chip)
    fn = jax.jit(lambda t, p: lsh_bucket_gather_pallas(t, p,
                                                       interpret=False))
    _assert_kernel(fn.lower(tables, pb).compile())


@pytest.mark.parametrize("pool", [512, 100_000])
def test_adc_rank_compiles_for_v5e(one_chip, pool):
    """ADC ranking at d=200 (m=25 segments of 8) over a 120k-row code
    table, for a small pool and a paper-scale one (n_probe=50 lists of
    2,000)."""
    m, seg, n = 25, 8, 120_000
    fn = jax.jit(lambda q, cb, c, codes: adc_rank_pallas(
        q, cb, c, codes, n_cand=min(1000, pool), interpret=False))
    _assert_kernel(fn.lower(
        _spec((64, m * seg), jnp.float32, one_chip),
        _spec((m, 256, seg), jnp.float32, one_chip),
        _spec((64, pool), jnp.int32, one_chip),
        _spec((n, m), jnp.uint8, one_chip)).compile())
