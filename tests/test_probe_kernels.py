"""The fused probe kernels + overlapped ring sweep (DESIGN.md §15).

Covers: interpret-mode Pallas parity with the jnp path for the LSH
bucket-gather and ADC-rank kernels — EXACT array equality (the
bit-identity-by-construction claim), candidate-set equality vs the
pre-dedup gather, and count parity through the engine on both metrics;
non-divisible shapes; empty-bucket / all-tombstoned(-1) candidate edge
cases; `clear_program_cache()` evicting the backend-keyed probe
programs; the platform-derived `interpret=` default; and — in forced
multi-device subprocesses (r=2 and r=3, the latter exercising the
reduce-scatter carry's ring wraparound) — the overlapped ring sweep's
bit-identity with the serial schedule plus a guard lane proving overlap
adds no host syncs beyond the two declared per-batch points.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.engine import JoinEngine
from repro.core import probe as probe_mod
from repro.kernels import ops
from repro.kernels.adc_rank import adc_rank_chain, adc_rank_jnp
from repro.kernels.lsh_gather import (bucket_minor, lsh_bucket_gather_jnp,
                                      lsh_probe_dup_mask)
from repro.kernels.range_count import default_interpret

EPS = 0.4
LSH_PARAMS = dict(k=10, l=8, n_probes=4, W=2.5)
IVFPQ_PARAMS = dict(C=24, m=8, n_probe=8, n_candidates=200)


def _unit(rng, n, d):
    x = rng.normal(size=(n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


@pytest.fixture(scope="module")
def clustered():
    rng = np.random.default_rng(5)
    d, nc, spread = 32, 6, 0.03
    c = rng.normal(size=(nc, d))
    c /= np.linalg.norm(c, axis=1, keepdims=True)

    def draw(per):
        pts = (np.repeat(c, per, axis=0)
               + rng.normal(size=(nc * per, d)) * spread)
        pts /= np.linalg.norm(pts, axis=1, keepdims=True)
        return pts.astype(np.float32)

    return draw(150), draw(25)


# ------------------------------------------------ lsh_gather: ops-level
@pytest.mark.parametrize("shape", [
    (64, 4, 64, 8, 3),        # aligned rows
    (37, 5, 48, 7, 4),        # nothing divides the 128-row kernel tile
    (1, 1, 8, 1, 1),          # degenerate single-everything
])
def test_lsh_gather_pallas_matches_jnp_exactly(shape):
    """Pallas (interpret) and jnp outputs are bit-identical — including
    the dedup blanks — and the candidate set matches the raw pre-dedup
    gather."""
    q, l, B, cap, n_probes = shape
    rng = np.random.default_rng(0)
    tables = rng.integers(-1, 900, size=(l, B, cap)).astype(np.int32)
    pb = rng.integers(0, B, size=(q, l, n_probes)).astype(np.int32)
    pb[..., -1] = pb[..., 0]          # the pad schedule repeats probe 0
    stored = jnp.asarray(bucket_minor(tables))
    a = np.asarray(ops.lsh_bucket_gather(stored, jnp.asarray(pb),
                                         backend="jnp"))
    b = np.asarray(ops.lsh_bucket_gather(stored, jnp.asarray(pb),
                                         backend="pallas"))
    np.testing.assert_array_equal(a, b)
    raw = tables[np.arange(l)[None, :, None], pb].reshape(q, -1)
    for i in range(q):
        assert (set(a[i][a[i] >= 0].tolist())
                == set(raw[i][raw[i] >= 0].tolist()))


def test_lsh_gather_large_ids_exact():
    """The 16-bit-split one-hot gather is exact for ids far past the f32
    24-bit integer window (the failure a naive f32 gather would hit)."""
    ids = np.array([2**30 - 1, 2**24 + 1, 16_777_217, -1],
                   np.int32).reshape(1, 1, 4)
    tables = np.broadcast_to(ids, (2, 8, 4)).copy()
    rng = np.random.default_rng(9)
    pb = rng.integers(0, 8, size=(5, 2, 3)).astype(np.int32)
    stored = jnp.asarray(bucket_minor(tables))
    a = np.asarray(ops.lsh_bucket_gather(stored, jnp.asarray(pb),
                                         backend="jnp"))
    b = np.asarray(ops.lsh_bucket_gather(stored, jnp.asarray(pb),
                                         backend="pallas"))
    np.testing.assert_array_equal(a, b)
    for v in (2**30 - 1, 2**24 + 1, 16_777_217):
        assert v in set(b.ravel().tolist())


def test_lsh_gather_empty_buckets_and_full_dup():
    """All-empty tables emit all -1; a fully duplicated probe schedule
    keeps exactly the first probe's block."""
    l, B, cap, q, n_probes = 3, 16, 5, 9, 4
    empty = np.full((l, B, cap), -1, np.int32)
    rng = np.random.default_rng(1)
    pb = rng.integers(0, B, size=(q, l, n_probes)).astype(np.int32)
    for be in ("jnp", "pallas"):
        out = np.asarray(ops.lsh_bucket_gather(
            jnp.asarray(bucket_minor(empty)), jnp.asarray(pb), backend=be))
        assert (out == -1).all(), be
    # every probe identical -> dup mask true for all but probe 0
    pb_dup = np.repeat(pb[:, :, :1], n_probes, axis=2)
    dup = np.asarray(lsh_probe_dup_mask(jnp.asarray(pb_dup)))
    assert not dup[..., 0].any() and dup[..., 1:].all()
    tables = rng.integers(-1, 100, size=(l, B, cap)).astype(np.int32)
    out = np.asarray(ops.lsh_bucket_gather(
        jnp.asarray(bucket_minor(tables)), jnp.asarray(pb_dup),
        backend="pallas")).reshape(q, l, n_probes, cap)
    assert (out[:, :, 1:] == -1).all()
    np.testing.assert_array_equal(
        out[:, :, 0], tables[np.arange(l)[None, :], pb_dup[:, :, 0]])


# -------------------------------------------------- adc_rank: ops-level
@pytest.mark.parametrize("b,C,n_cand", [(16, 64, 32), (21, 48, 20),
                                        (3, 10, 10)])
def test_adc_rank_pallas_matches_jnp_exactly(b, C, n_cand):
    """Pallas (interpret) and jnp ADC ranking are bit-identical — same
    ids in the same order, ties included — and value-identical to the
    pre-kernel chain (same id multiset per row)."""
    rng = np.random.default_rng(2)
    m, seg, n = 4, 8, 300
    q = rng.normal(size=(b, m * seg)).astype(np.float32)
    cbs = rng.normal(size=(m, 256, seg)).astype(np.float32)
    codes = rng.integers(0, 256, size=(n, m)).astype(np.uint8)
    cand = rng.integers(-1, n, size=(b, C)).astype(np.int32)
    if C > 2:
        cand[:, 2] = cand[:, 1]       # duplicate ids (overlapping lists)
    args = (jnp.asarray(q), jnp.asarray(cbs), jnp.asarray(cand),
            jnp.asarray(codes))
    a = np.asarray(ops.adc_rank(*args, n_cand=n_cand, backend="jnp"))
    p = np.asarray(ops.adc_rank(*args, n_cand=n_cand, backend="pallas"))
    np.testing.assert_array_equal(a, p)
    c = np.asarray(ops.adc_rank(*args, n_cand=n_cand, backend="ref"))
    for i in range(b):
        assert sorted(a[i].tolist()) == sorted(c[i].tolist())


def test_adc_rank_all_tombstoned_candidates():
    """A fully -1 candidate row (empty probed lists / everything
    tombstoned) ranks to all -1 on every backend, bit-identically."""
    rng = np.random.default_rng(3)
    b, C, m, seg, n = 8, 24, 4, 8, 50
    q = rng.normal(size=(b, m * seg)).astype(np.float32)
    cbs = rng.normal(size=(m, 256, seg)).astype(np.float32)
    codes = rng.integers(0, 256, size=(n, m)).astype(np.uint8)
    cand = np.full((b, C), -1, np.int32)
    cand[0, :3] = [4, 4, 7]           # one row keeps a few live ids
    args = (jnp.asarray(q), jnp.asarray(cbs), jnp.asarray(cand),
            jnp.asarray(codes))
    outs = [np.asarray(ops.adc_rank(*args, n_cand=12, backend=be))
            for be in ("jnp", "pallas")]
    np.testing.assert_array_equal(outs[0], outs[1])
    assert (outs[0][1:] == -1).all()
    assert set(outs[0][0][outs[0][0] >= 0].tolist()) == {4, 7}


def test_adc_rank_formulations_share_values():
    """The flat-LUT path computes the same ADC sums as the chain (the
    per-segment accumulation is a reordering of the same addends) —
    checked through the id sets of unambiguous (untied) rankings."""
    rng = np.random.default_rng(4)
    b, C, m, seg, n = 6, 32, 8, 4, 200
    q = rng.normal(size=(b, m * seg)).astype(np.float32)
    cbs = rng.normal(size=(m, 256, seg)).astype(np.float32)
    codes = rng.integers(0, 256, size=(n, m)).astype(np.uint8)
    cand = rng.permutation(n)[:C].astype(np.int32)[None].repeat(b, 0)
    a = np.asarray(adc_rank_jnp(jnp.asarray(q), jnp.asarray(cbs),
                                jnp.asarray(cand), jnp.asarray(codes),
                                n_cand=C))
    c = np.asarray(adc_rank_chain(jnp.asarray(q), jnp.asarray(cbs),
                                  jnp.asarray(cand), jnp.asarray(codes),
                                  n_cand=C))
    np.testing.assert_array_equal(np.sort(a, 1), np.sort(c, 1))


# ------------------------------------- engine-level parity, both metrics
@pytest.mark.parametrize("metric", ["cosine", "l2"])
@pytest.mark.parametrize("verify,params", [
    ("lsh", LSH_PARAMS), ("ivfpq", IVFPQ_PARAMS)])
def test_device_probe_pallas_backend_parity(clustered, metric, verify,
                                            params):
    """Through the engine, the pallas-backed probe programs produce
    candidates bit-identical to the jnp-backed ones (same placed-probe
    geometry) and counts bit-identical to the host probe."""
    R, Q = clustered
    eng_j = JoinEngine(R, metric, backend="jnp")
    eng_p = JoinEngine(R, metric, backend="pallas")
    cands = {}
    for eng in (eng_j, eng_p):
        eng.verifier(verify, **params)
        placed = eng.device_probe_for(verify, "device")
        qp = np.zeros((256, Q.shape[1]), np.float32)
        qp[:len(Q)] = Q
        cands[eng.backend] = np.asarray(placed.probe(jnp.asarray(qp)))
    np.testing.assert_array_equal(cands["jnp"], cands["pallas"])
    host = eng_p.filtered_join(Q, EPS, verify=verify, probe="host")
    dev = eng_p.filtered_join(Q, EPS, verify=verify, probe="device")
    np.testing.assert_array_equal(dev.counts, host.counts)


def test_lsh_dedup_preserves_candidate_sets(clustered):
    """Device candidates (dedup'd) cover exactly the host candidate id
    sets — dedup drops repeats, never members."""
    R, Q = clustered
    eng = JoinEngine(R, "l2", backend="pallas")
    searcher = eng.verifier("lsh", **LSH_PARAMS)
    placed = eng.device_probe_for("lsh", "device")
    qp = np.zeros((256, Q.shape[1]), np.float32)
    qp[:len(Q)] = Q
    dev = np.asarray(placed.probe(jnp.asarray(qp)))[:len(Q)]
    host = searcher.candidates(Q)
    for h, d in zip(host, dev):
        assert (set(d[d >= 0].tolist())
                == set(h[h >= 0].tolist()))


# ------------------------------------------------------------ interpret
def test_interpret_default_derives_from_platform(monkeypatch):
    """`interpret=None` resolves via default_interpret(): interpret off
    TPU, compiled on TPU — a TPU run can never silently interpret."""
    assert default_interpret() is (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert default_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert default_interpret() is True
    # the kernel entries default to the derived policy, not a hard True
    import inspect
    from repro.kernels import adc_rank, fused_mlp, lsh_gather, range_count
    for fn in (range_count.range_count_hist_pallas,
               fused_mlp.mlp_forward_pallas,
               lsh_gather.lsh_bucket_gather_pallas,
               adc_rank.adc_rank_pallas):
        assert inspect.signature(fn).parameters["interpret"].default is None


# ------------------------------------------------------- cache eviction
def test_clear_program_cache_evicts_backend_keyed_probe_programs(clustered):
    """The backend-keyed probe programs (pallas + jnp entries coexist in
    one cache) are evicted by engine.clear_program_cache() and rebuild
    bit-identically."""
    from repro.core import engine as engine_mod
    R, Q = clustered
    want = {}
    for backend in ("jnp", "pallas"):
        eng = JoinEngine(R, "l2", backend=backend)
        eng.verifier("lsh", **LSH_PARAMS)
        want[backend] = eng.filtered_join(Q, EPS, verify="lsh",
                                          probe="device").counts
    assert probe_mod._lsh_probe_program.cache_info().currsize >= 2
    engine_mod.clear_program_cache()
    assert probe_mod._lsh_probe_program.cache_info().currsize == 0
    for backend in ("jnp", "pallas"):
        eng = JoinEngine(R, "l2", backend=backend)
        eng.verifier("lsh", **LSH_PARAMS)
        np.testing.assert_array_equal(
            eng.filtered_join(Q, EPS, verify="lsh", probe="device").counts,
            want[backend])
    np.testing.assert_array_equal(want["jnp"], want["pallas"])


# --------------------------------------- overlapped ring (subprocesses)
def _run_forced_devices(code: str, n: int = 2) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    prelude = (
        "import os\n"
        "from repro.launch.xla_flags import apply_xla_flags, "
        "host_device_count_flag\n"
        f"apply_xla_flags(host_device_count_flag({n}))\n")
    out = subprocess.run(
        [sys.executable, "-c", prelude + code], env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("r_shards", [2, 3])
def test_overlapped_ring_bit_identical_to_serial(r_shards):
    """Forced r-device subprocess: RingSharded(overlap=True) counts are
    bit-identical to overlap=False and to the replicated ref oracle, on
    the jnp AND pallas backends.  r=3 exercises the reduce-scatter
    carry's ring wraparound, which r=2 cannot distinguish from a plain
    exchange (a carry-index bug is invisible at two shards)."""
    code = (
        "import numpy as np\n"
        "from repro.core.engine import JoinEngine\n"
        "from repro.core.topology import RingSharded\n"
        "from repro.launch.mesh import make_join_mesh\n"
        "rng = np.random.default_rng(7)\n"
        "def unit(n, d=24):\n"
        "    x = rng.normal(size=(n, d)).astype(np.float32)\n"
        "    return x / np.linalg.norm(x, axis=1, keepdims=True)\n"
        "R, Q = unit(700), unit(130)\n"
        "base = np.asarray(JoinEngine(R, 'cosine', backend='ref')"
        ".range_count(Q, 0.7))\n"
        f"mesh = make_join_mesh(data=1, r={r_shards})\n"
        "for overlap in (True, False):\n"
        "    for backend in ('jnp', 'pallas'):\n"
        "        eng = JoinEngine(R, 'cosine', backend=backend, mesh=mesh,\n"
        "                         topology=RingSharded(overlap=overlap))\n"
        "        np.testing.assert_array_equal(\n"
        "            np.asarray(eng.range_count(Q, 0.7)), base)\n"
        "print('RING_OVERLAP_PARITY_OK')\n")
    assert "RING_OVERLAP_PARITY_OK" in _run_forced_devices(code, n=r_shards)


@pytest.mark.slow
@pytest.mark.guard
def test_overlapped_ring_adds_no_host_syncs_2dev():
    """Forced 2-device subprocess, guard lane: a streamed device-probe
    run over the OVERLAPPED ring topology completes under
    host_sync_guard('n_pos', 'result') — the extra ppermutes introduce
    no new host syncs — and stays bit-identical to the unguarded run."""
    code = (
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "from repro.core.engine import JoinEngine, host_sync_guard\n"
        "from repro.core.topology import RingSharded\n"
        "from repro.launch.mesh import make_join_mesh\n"
        "rng = np.random.default_rng(5)\n"
        "c = rng.normal(size=(6, 32))\n"
        "c /= np.linalg.norm(c, axis=1, keepdims=True)\n"
        "def draw(per):\n"
        "    p = (np.repeat(c, per, axis=0)\n"
        "         + rng.normal(size=(6 * per, 32)) * 0.03)\n"
        "    return (p / np.linalg.norm(p, axis=1, keepdims=True))"
        ".astype(np.float32)\n"
        "R, Q = draw(150), draw(25)\n"
        "params = dict(k=10, l=8, n_probes=4, W=2.5)\n"
        "def trivial():\n"
        "    p = jnp.zeros((1,), jnp.float32)\n"
        "    return p, (lambda p, X: jnp.ones((X.shape[0],), jnp.float32))\n"
        "mesh = make_join_mesh(data=1, r=2)\n"
        "eng = JoinEngine(R, 'l2', backend='jnp', mesh=mesh,\n"
        "                 topology=RingSharded(overlap=True))\n"
        "eng.verifier('lsh', **params)\n"
        "kw = dict(verify='lsh', probe='device', predict=trivial(),\n"
        "          threshold=0.5)\n"
        "batches = [Q[:10], Q[10:]]\n"
        "ref = [np.asarray(r.counts)\n"
        "       for r in eng.stream(batches, 0.4, depth=2, **kw)]\n"
        "import repro.core.engine as em\n"
        "events, orig = [], em._note_host_sync\n"
        "em._note_host_sync = events.append\n"
        "list(eng.stream(batches, 0.4, depth=2, **kw))\n"
        "em._note_host_sync = orig\n"
        "assert set(events) <= {'n_pos', 'result'}, events\n"
        "with host_sync_guard('n_pos', 'result'):\n"
        "    got = [np.asarray(r.counts)\n"
        "           for r in eng.stream(batches, 0.4, depth=2, **kw)]\n"
        "for a, b in zip(ref, got):\n"
        "    np.testing.assert_array_equal(a, b)\n"
        "print('RING_GUARD_OK')\n")
    assert "RING_GUARD_OK" in _run_forced_devices(code)


def test_bucket_minor_layout_round_trips():
    """`bucket_minor` transposes to [.., cap, B_pad] with -1 padding
    buckets, tile-aligned once the table spans more than one tile."""
    from repro.kernels.lsh_gather import padded_buckets
    rng = np.random.default_rng(3)
    tables = rng.integers(-1, 500, size=(2, 3, 2500, 5)).astype(np.int32)
    out = bucket_minor(tables)
    assert out.shape == (2, 3, 5, 4096) and out.dtype == np.int32
    assert padded_buckets(2500) == 4096 and padded_buckets(300) == 384
    np.testing.assert_array_equal(np.swapaxes(out[..., :2500], -1, -2),
                                  tables)
    assert (out[..., 2500:] == -1).all()


def test_shard_lsh_tables_partitions_exactly():
    """Each shard's table holds exactly the global ids of its row range,
    bucket by bucket, at the smallest capacity that fits every shard."""
    rng = np.random.default_rng(4)
    l, B, cap, shards, rows = 3, 40, 9, 4, 25
    tables = rng.integers(-1, shards * rows, size=(l, B, cap)).astype(
        np.int32)
    out = probe_mod._shard_lsh_tables(tables, shards, rows)
    need = max(int(((tables >= s * rows) & (tables < (s + 1) * rows))
                   .sum(axis=-1).max()) for s in range(shards))
    assert out.shape == (shards, l, B, need) and out.dtype == np.int32
    for s in range(shards):
        for t in range(l):
            for b in range(B):
                got = out[s, t, b]
                want = tables[t, b]
                want = want[(want >= s * rows) & (want < (s + 1) * rows)]
                np.testing.assert_array_equal(np.sort(got[got >= 0]),
                                              np.sort(want))
