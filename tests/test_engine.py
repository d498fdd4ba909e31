"""The sharded join engine (core/engine.py) + topology + mesh-compat.

Covers: single-device engine vs the ref oracle, FilteredJoin compaction
parity for every verdict pattern, the streaming API (including the async
double-buffered pipeline vs the synchronous path, and the StreamSession
submit/flush invariants), the pluggable verification backends (lsh/ivfpq
recall floors vs the exact oracle, verify_candidates backend parity), the
topology layer (DESIGN.md §10: ring == ref on a degenerate 1-device ring,
build-time validation, program-cache eviction, ground-truth engine
reuse), the exact-mode target clamp regression, and — in forced-8-device
subprocesses, mirroring test_system — bit-for-bit equality of the sharded
sweep with the ref backend while the query axis is genuinely distributed,
for BOTH the replicated and the ring (r x data ppermute ring) topologies.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import XlingConfig, XlingFilter, make_join
from repro.core.engine import (_STACK_BELOW, JoinEngine, _bucket_size,
                               _stack_by_shape, sharded_range_count_hist)
from repro.core.xjoin import FilteredJoin
from repro.kernels import ops, ref


def _unit(rng, n, d):
    x = rng.normal(size=(n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


@pytest.fixture(scope="module")
def world():
    rng = np.random.default_rng(0)
    R = _unit(rng, 900, 24)
    Q = _unit(rng, 157, 24)
    eps = np.linspace(0.2, 1.8, 23).astype(np.float32)
    return R, Q, eps


# -------------------------------------------------------------- single device
def test_engine_hist_matches_ref(world):
    R, Q, eps = world
    import jax.numpy as jnp
    want = np.asarray(ref.range_count_hist(jnp.asarray(Q), jnp.asarray(R),
                                           jnp.asarray(eps), "l2"))
    for backend in ("jnp", "ref"):
        eng = JoinEngine(R, "l2", backend=backend)
        np.testing.assert_array_equal(eng.range_count_hist(Q, eps), want)
    np.testing.assert_array_equal(
        sharded_range_count_hist(Q, R, eps, metric="l2", backend="jnp"), want)


def test_naive_join_routes_through_engine(world):
    R, Q, _ = world
    j = make_join("naive", R, "l2", backend="jnp")
    assert isinstance(j.engine, JoinEngine)
    want = np.asarray(ops.range_count(Q, R, 0.8, metric="l2", backend="jnp"))
    np.testing.assert_array_equal(j.query_counts(Q, 0.8), want)


@pytest.mark.parametrize("pattern", ["all_positive", "all_negative", "mixed"])
def test_filtered_join_compaction_patterns(world, pattern):
    """Engine compaction must return counts identical to the host-compaction
    path for every verdict shape."""
    R, Q, _ = world
    rng = np.random.default_rng(3)
    verdicts = {"all_positive": np.ones(len(Q), bool),
                "all_negative": np.zeros(len(Q), bool),
                "mixed": rng.random(len(Q)) > 0.5}[pattern]
    base = make_join("naive", R, "l2", backend="jnp")
    filt = lambda Q_, eps_: verdicts  # noqa: E731
    host = FilteredJoin(base, filter=filt).run(Q, 0.8)
    eng = FilteredJoin(base, filter=filt, engine=base.engine).run(Q, 0.8)
    assert eng.meta.get("engine") is True
    assert eng.n_searched == host.n_searched == int(verdicts.sum())
    np.testing.assert_array_equal(eng.counts, host.counts)
    true = np.asarray(ops.range_count(Q, R, 0.8, metric="l2", backend="jnp"))
    np.testing.assert_array_equal(eng.counts, np.where(verdicts, true, 0))


def test_engine_fused_estimator_path_matches_host(world):
    R, Q, _ = world
    cfg = XlingConfig(estimator="nn", metric="l2", epochs=3, backend="jnp", m=12)
    filt = XlingFilter(cfg).fit(R)
    base = make_join("naive", R, "l2", backend="jnp")
    eng = FilteredJoin(base, filter=filt, tau=0, xdt_mode="fpr",
                       engine=base.engine)
    host = FilteredJoin(base, filter=filt, tau=0, xdt_mode="fpr")
    r_eng, r_host = eng.run(Q, 0.8), host.run(Q, 0.8)
    assert r_eng.meta.get("engine") is True
    # same estimator math on both paths -> same verdicts -> same counts
    np.testing.assert_array_equal(r_eng.counts, r_host.counts)
    assert r_eng.n_searched == r_host.n_searched


def test_engine_streaming_matches_oneshot(world):
    R, Q, _ = world
    cfg = XlingConfig(estimator="nn", metric="l2", epochs=3, backend="jnp", m=12)
    filt = XlingFilter(cfg).fit(R)
    base = make_join("naive", R, "l2", backend="jnp")
    fj = FilteredJoin(base, filter=filt, tau=0, xdt_mode="fpr",
                      engine=base.engine)
    one = fj.run(Q, 0.8)
    batches = [Q[:64], Q[64:128], Q[128:]]
    results = list(fj.run_stream(batches, 0.8))
    assert len(results) == 3
    np.testing.assert_array_equal(
        np.concatenate([r.counts for r in results]), one.counts)
    assert sum(r.n_searched for r in results) == one.n_searched
    # the engine-level stream (predict + threshold) agrees with the join-level
    predict = filt.estimator.device_predict_fn()
    thr = filt.xdt(0.8, 0, mode="fpr", predict=predict)
    eng_results = list(base.engine.stream(batches, 0.8, predict=predict,
                                          threshold=thr))
    np.testing.assert_array_equal(
        np.concatenate([r.counts for r in eng_results]), one.counts)


def test_async_stream_bit_identical_to_sync(world):
    """The async double-buffered pipeline must return results bit-identical
    to per-batch synchronous `filtered_join` calls (ordering-insensitive:
    compared as the concatenated multiset AND per-batch)."""
    R, Q, _ = world
    cfg = XlingConfig(estimator="nn", metric="l2", epochs=3, backend="jnp", m=12)
    filt = XlingFilter(cfg).fit(R)
    base = make_join("naive", R, "l2", backend="jnp")
    fj = FilteredJoin(base, filter=filt, tau=0, xdt_mode="fpr",
                      engine=base.engine)
    # deliberately ragged batch sizes to exercise distinct shape buckets
    batches = [Q[:50], Q[50:51], Q[51:120], Q[120:]]
    sync = [fj.run(b, 0.8) for b in batches]
    for depth in (0, 1, 3, 10):
        stream = list(fj.run_stream(batches, 0.8, depth=depth))
        assert len(stream) == len(batches)
        for s, a in zip(sync, stream):
            np.testing.assert_array_equal(a.counts, s.counts)
            assert a.n_searched == s.n_searched
        np.testing.assert_array_equal(
            np.sort(np.concatenate([r.counts for r in stream])),
            np.sort(np.concatenate([r.counts for r in sync])))


def test_stream_session_submit_flush_invariants(world):
    """StreamSession: the in-flight queue stays bounded by `depth`, results
    come back FIFO, flush() drains everything and is idempotent."""
    R, Q, _ = world
    eng = JoinEngine(R, "l2", backend="jnp")
    rng = np.random.default_rng(9)
    verdicts = [rng.random(40) > 0.5 for _ in range(6)]
    sess = eng.stream_session(0.8, depth=2)
    got = []
    for i in range(6):
        out = sess.submit(Q[i * 20:i * 20 + 40], verdicts=verdicts[i])
        got.extend(out)
        # bounded: at most depth committed + 1 staged in flight
        assert len(sess._inflight) <= 2
    rest = sess.flush()
    assert len(sess._inflight) == 0 and sess._staged is None
    assert sess.flush() == []            # idempotent barrier
    got.extend(rest)
    assert len(got) == 6                 # every submitted batch came back
    for i, res in enumerate(got):        # FIFO + correct per-batch counts
        want = eng.filtered_join(Q[i * 20:i * 20 + 40], 0.8,
                                 verdicts=verdicts[i])
        np.testing.assert_array_equal(res.counts, want.counts)


# ------------------------------------------------- verification backends
@pytest.fixture(scope="module")
def clustered_world():
    """Clustered corpus/queries sharing centers — enough true pairs that
    approximate-verifier recall is a meaningful, stable number."""
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


@pytest.mark.parametrize("backend,floor,params", [
    ("lsh", 0.90, dict(k=10, l=8, n_probes=4, W=2.5)),
    ("ivfpq", 0.95, dict(C=24, m=8, n_probe=8, n_candidates=600)),
])
def test_verify_backend_recall_floor(clustered_world, backend, floor, params):
    """Approximate verification: counts never exceed the exact sweep (the
    verification itself is exact over candidates, so precision is 1) and
    recall vs the exact oracle stays above the configured floor."""
    R, Q = clustered_world
    eng = JoinEngine(R, "l2", backend="jnp")
    eng.verifier(backend, **params)      # pre-build with tuned params
    true = eng.range_count(Q, 0.4)
    assert true.sum() > 1000             # the workload is meaningful
    res = eng.filtered_join(Q, 0.4, verdicts=np.ones(len(Q), bool),
                            verify=backend)
    assert res.verify == backend
    assert res.n_searched == len(Q)
    assert (res.counts <= true).all()    # no false pairs
    recall = float(np.minimum(res.counts, true).sum() / true.sum())
    assert recall >= floor, f"{backend} recall {recall:.3f} < {floor}"
    # the streamed form of the same verify backend is bit-identical
    streamed = list(eng.stream([Q[:70], Q[70:]], 0.4, verify=backend,
                               depth=2))
    np.testing.assert_array_equal(
        np.concatenate([r.counts for r in streamed]), res.counts)


def test_verifier_registry(world):
    R, Q, _ = world
    eng = JoinEngine(R, "l2", backend="jnp")
    with pytest.raises(ValueError):
        eng.filtered_join(Q, 0.8, verdicts=np.ones(len(Q), bool),
                          verify="annoy")
    v1 = eng.verifier("lsh", k=6, l=4)
    assert eng.verifier("lsh") is v1     # cached per name


def test_verify_candidates_backend_parity(world):
    """verify_candidates counts are backend-invariant (§2): the blocked
    path and the unpadded ref oracle agree, with host or device R."""
    import jax.numpy as jnp
    from repro.core.joins.common import verify_candidates
    R, Q, _ = world
    rng = np.random.default_rng(4)
    cand = rng.integers(-1, len(R), size=(len(Q), 37)).astype(np.int32)
    want = verify_candidates(R, Q, cand, 0.8, "l2", backend="jnp")
    got_ref = verify_candidates(R, Q, cand, 0.8, "l2", backend="ref")
    got_dev = verify_candidates(jnp.asarray(R), Q, cand, 0.8, "l2")
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_array_equal(got_dev, want)


@pytest.mark.parametrize("metric", ["cosine", "l2"])
def test_live_chunked_verify_oracle_parity(world, metric):
    """The live-chunked verify (_verify_block_live, DESIGN.md §15) is
    bit-identical to the oracle form on the shapes that stress its
    schedule: all-pad rows (zero trip count), dup-heavy rows, full-width
    rows (every chunk live), a candidate width that does not divide the
    chunk, and a tombstone mask riding along."""
    import jax.numpy as jnp
    from repro.core.joins.common import (_LIVE_CHUNK, _verify_block_impl,
                                         _verify_block_live)
    R, Q, _ = world
    Rj = jnp.asarray(R)
    rng = np.random.default_rng(11)
    tomb = jnp.asarray((rng.random(len(R)) < 0.15).astype(np.int32))
    cases = []
    for C in (_LIVE_CHUNK * 3, _LIVE_CHUNK - 9, 1):
        sparse = rng.integers(-1, len(R), size=(32, C)).astype(np.int32)
        sparse[rng.random(size=sparse.shape) > 0.15] = -1
        sparse[0] = -1                          # an all-pad row
        dense = rng.integers(0, len(R), size=(32, C)).astype(np.int32)
        dense[:, : C // 2] = dense[:, C // 2:][:, : C // 2] \
            if C > 1 else dense[:, :1]          # heavy duplication
        cases += [sparse, dense, np.full((32, C), -1, np.int32)]
    q = jnp.asarray(Q[:32])
    for cand in cases:
        for tb in (None, tomb):
            want = np.asarray(_verify_block_impl(
                Rj, q, jnp.asarray(cand), np.float32(0.9), metric=metric,
                tomb=tb))
            got = np.asarray(_verify_block_live(
                Rj, q, jnp.asarray(cand), np.float32(0.9), metric=metric,
                tomb=tb))
            np.testing.assert_array_equal(got, want)


def test_stream_staging_constant_caches(world):
    """Unfiltered streams re-stage the same radius scalar and all-positive
    mask every batch; the engine uploads each once per (value, shape
    bucket) and reuses the device arrays (DESIGN.md §5) — and the cached
    route stays bit-identical to the one-shot join."""
    R, Q, _ = world
    j = make_join("naive", R, "l2", backend="jnp")
    eng = j.engine
    want = j.query_counts(Q, 0.8)
    batches = [Q[:64], Q[64:128], Q[128:]]
    got = np.concatenate([r.counts for r in eng.stream(batches, 0.8)])
    np.testing.assert_array_equal(got, want)
    assert len(eng._scalar_cache) == 1          # one radius staged once
    keys = set(eng._allpos_cache)
    assert len(keys) == 2                       # 64-row + 29-row buckets
    st = eng._stage_filter(Q[:64], 0.8)
    assert st.eps_dev is eng._scalar_cache[("float32", 0.8)]
    assert st.pos_dev is eng._allpos_cache[(st.qdev.shape[0], 64)][0]
    assert set(eng._allpos_cache) == keys       # no new upload


def test_engine_filter_program_cache_stable(world):
    """device_predict_fn must hand back a memoized fn so the engine's
    program cache hits across run() calls — one compiled filter program per
    estimator, not one per batch (the serving steady-state guarantee)."""
    R, Q, _ = world
    cfg = XlingConfig(estimator="nn", metric="l2", epochs=2, backend="jnp", m=12)
    filt = XlingFilter(cfg).fit(R)
    base = make_join("naive", R, "l2", backend="jnp")
    fj = FilteredJoin(base, filter=filt, tau=0, xdt_mode="fpr",
                      engine=base.engine)
    for _ in range(3):
        fj.run(Q, 0.8)
    assert len(base.engine._filter_progs) == 1


def _fitted_estimator(name, R, seed=0):
    """A registry estimator fitted for one epoch on `R`'s rows with a
    radius column, at widths small enough for the CPU, with weight
    matrices on both sides of `_STACK_BELOW`."""
    from repro.models import make_estimator
    est = make_estimator(name, R.shape[1] + 1, widths=(16, 256, 16, 4),
                         epochs=1, seed=seed)
    _refit(est, R, seed)
    return est


def _refit(est, R, seed):
    rng = np.random.default_rng(seed)
    X = np.concatenate([R, rng.uniform(0.2, 1.8, (len(R), 1))],
                       axis=1).astype(np.float32)
    est.fit(X, rng.integers(0, 40, len(R)).astype(np.float32))


def _filter_on_leaves(predict, st, thr):
    """The filter program's body over the original parameter leaves,
    jitted: what the engine ran before it stacked them."""
    import jax
    import jax.numpy as jnp
    params, fn = predict

    @jax.jit
    def program(params, q, eps, thr, n_valid):
        X = jnp.concatenate(
            [q, jnp.full((q.shape[0], 1), eps, jnp.float32)], axis=1)
        preds = fn(params, X)
        pos = (preds > thr) & (jnp.arange(q.shape[0]) < n_valid)
        return preds, pos, jnp.sum(pos, dtype=jnp.int32)
    return program(params, st.qdev, st.eps_dev, jnp.float32(thr),
                   jnp.int32(st.n))


@pytest.mark.parametrize("name", ["rmi", "nn", "selnet", "linear"])
def test_stacked_filter_program_matches_leaves(world, name):
    """The filter program takes the estimator's small parameter leaves
    stacked by (shape, dtype), its large ones whole, and rebuilds the
    pytree inside the trace: `preds`, `pos` and `n_pos` are
    bit-identical to the same program over the original leaves, and the
    program gets one buffer per large leaf and per group of small
    ones."""
    import jax
    import jax.numpy as jnp
    R, Q, _ = world
    predict = _fitted_estimator(name, R).device_predict_fn()
    X = np.concatenate([Q, np.full((len(Q), 1), 0.8, np.float32)], axis=1)
    thr = float(np.median(np.asarray(jax.jit(predict[1])(predict[0], X))))
    eng = JoinEngine(R, "l2", backend="jnp")
    st = eng._stage_filter(Q, 0.8, predict=predict, threshold=thr)
    prog, stacked = eng._filter_program(predict)
    leaves = jax.tree_util.tree_leaves(predict[0])
    big = [x for x in leaves if np.size(x) >= _STACK_BELOW]
    small = {(np.shape(x), x.dtype) for x in leaves
             if np.size(x) < _STACK_BELOW}
    assert len(stacked) == len(big) + len(small)
    for b in big:
        assert any(s is b for s in stacked)     # passed as it is
    got = prog(stacked, st.qdev, st.eps_dev, jnp.float32(thr),
               jnp.int32(st.n))
    want = _filter_on_leaves(predict, st, thr)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(st.pos_dev), np.asarray(want[1]))
    assert int(st.n_pos_dev) == int(want[2])
    assert 0 < int(want[2]) < st.n          # the threshold splits the batch


def test_paper_rmi_filter_buffers():
    """The paper's RMI (stages 1/2/4 of the 512/512/256/128 MLP, d = 200):
    70 leaves reach the filter program as 33 buffers, its 28 weight
    matrices whole and the rest in 5 stacked groups."""
    from repro.models.rmi import RMIEstimator
    est = RMIEstimator(201, stage_sizes=(1, 2, 4))
    buffers, (_, slots) = _stack_by_shape([list(s) for s in est.stages])
    assert (len(slots), len(buffers)) == (70, 33)
    assert sum(j is None for _, j in slots) == 28


@pytest.mark.parametrize("name", ["rmi", "nn", "selnet", "linear"])
def test_stacked_params_cache_refit_misses(world, name):
    """The stacked parameters are built once per set of leaves: the same
    leaves hit the cache; a refit's new leaves miss it, are stacked anew
    and filter as the refit estimator does; a refit that keeps the fn
    and shapes reuses the compiled program."""
    R, Q, _ = world
    est = _fitted_estimator(name, R)
    predict = est.device_predict_fn()
    eng = JoinEngine(R, "l2", backend="jnp")
    prog, stacked = eng._filter_program(predict)
    again = eng._filter_program(predict)
    assert again[0] is prog and again[1] is stacked
    _refit(est, R, seed=1)
    refit = est.device_predict_fn()
    prog2, stacked2 = eng._filter_program(refit)
    assert stacked2 is not stacked
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(stacked, stacked2))
    if refit[1] is predict[1]:
        assert prog2 is prog and len(eng._filter_progs) == 1
    st = eng._stage_filter(Q, 0.8, predict=refit, threshold=3.0)
    want = _filter_on_leaves(refit, st, 3.0)
    np.testing.assert_array_equal(np.asarray(st.pos_dev), np.asarray(want[1]))
    assert eng._filter_program(refit)[1] is stacked2


def test_bucket_size_reexport():
    # _bucket_size moved to engine; xjoin re-exports it (test_property uses it)
    from repro.core.xjoin import _bucket_size as xb
    assert xb is _bucket_size
    assert _bucket_size(513, 512) == 1024


# ----------------------------------------------------- topology layer (§10)
def test_ring_topology_single_device_parity(world):
    """The ring topology on a degenerate 1x1 (r, data) mesh must stay
    bit-identical to the ref oracle — this exercises the full ring code
    path (ppermute ring, psum, zero-pad-row correction: R=900 pads to
    1024 rows, and l2 eps up to 1.8 > sqrt(2) means uncorrected padding
    rows WOULD count) without needing forced devices."""
    from repro.launch.mesh import make_join_mesh
    R, Q, eps = world
    mesh = make_join_mesh(data=1, r=1)
    assert mesh.axis_names == ("r", "data")
    eng = JoinEngine(R, "l2", mesh=mesh, backend="jnp", topology="ring")
    ref_eng = JoinEngine(R, "l2", backend="ref")
    np.testing.assert_array_equal(eng.range_count_hist(Q, eps),
                                  ref_eng.range_count_hist(Q, eps))
    want = np.asarray(ref_eng.range_count(Q, 0.8))
    v = np.random.default_rng(11).random(len(Q)) > 0.5
    res = eng.filtered_join(Q, 0.8, verdicts=v)
    np.testing.assert_array_equal(res.counts, np.where(v, want, 0))
    # StreamSession parity + invariants under topology="ring"
    sess = eng.stream_session(0.8, depth=1)
    got = []
    verdicts = [np.random.default_rng(s).random(50) > 0.5 for s in range(4)]
    for i in range(4):
        got.extend(sess.submit(Q[i * 25:i * 25 + 50], verdicts=verdicts[i]))
        assert len(sess._inflight) <= 1
    got.extend(sess.flush())
    assert len(got) == 4
    for i, r in enumerate(got):
        w = eng.filtered_join(Q[i * 25:i * 25 + 50], 0.8,
                              verdicts=verdicts[i])
        np.testing.assert_array_equal(r.counts, w.counts)


def test_topology_validation():
    """Placement misconfiguration must fail at build/construction time
    with actionable messages, never data-dependently mid-stream."""
    from repro.core import JoinPlan, resolve_topology
    from repro.core.topology import RingSharded
    R = np.eye(8, dtype=np.float32)
    with pytest.raises(ValueError, match="topology"):
        resolve_topology("bogus")
    with pytest.raises(ValueError, match="ring"):
        JoinEngine(R, "l2", topology="ring")        # no mesh
    with pytest.raises(ValueError, match="r_shards"):
        JoinPlan(R, "l2").on(r_shards=2).build()    # replicated + r_shards
    with pytest.raises(ValueError, match="r_shards"):
        JoinPlan(R, "l2").on(topology="ring").build()
    with pytest.raises(ValueError):                 # more shards than devices
        JoinPlan(R, "l2").on(topology="ring", r_shards=64).build()
    eng = JoinEngine(R, "l2", backend="jnp")        # replicated engine
    with pytest.raises(ValueError, match="placement"):
        JoinPlan(R, "l2").on(engine=eng, topology="ring",
                             r_shards=1).build()
    assert isinstance(resolve_topology("ring"), RingSharded)
    assert resolve_topology(None).name == "replicated"


def test_clear_program_cache(world):
    """clear_program_cache() must evict the module-level compiled-program
    caches (long-lived serve processes / test suites would otherwise pin
    executables for discarded meshes) and the engine must transparently
    rebuild afterwards."""
    from repro.core import engine as engine_mod
    R, Q, _ = world
    eng = JoinEngine(R, "l2", backend="jnp")
    want = eng.range_count(Q, 0.8)
    assert engine_mod._hist_program.cache_info().currsize > 0
    engine_mod.clear_program_cache()
    assert engine_mod._hist_program.cache_info().currsize == 0
    assert engine_mod._compact_program.cache_info().currsize == 0
    np.testing.assert_array_equal(eng.range_count(Q, 0.8), want)


def test_groundtruth_engine_reuse(world):
    """cardinality_table(engine=...) must reuse the prebuilt engine's
    device-resident R (identical counts) and reject an engine built over
    a different index set instead of silently sweeping the wrong R."""
    from repro.data.groundtruth import cardinality_table
    R, Q, eps = world
    eng = JoinEngine(R, "l2", backend="jnp")
    want = cardinality_table(Q, R, eps, "l2", backend="jnp")
    np.testing.assert_array_equal(
        cardinality_table(Q, R, eps, "l2", engine=eng), want)
    with pytest.raises(ValueError, match="different"):
        cardinality_table(Q, R[:100], eps, "l2", engine=eng)
    with pytest.raises(ValueError, match="different"):
        cardinality_table(Q, R, eps, "cosine", engine=eng)


# ------------------------------------------------- exact-target clamp (bugfix)
def test_exact_targets_clamped_on_outliers():
    """An isolated point has range-count 1 (itself); after the self-match
    subtraction its exact-mode target must clamp to 0, matching the interp
    targets built from cardinality_table — not go to -1 and bias XDT."""
    rng = np.random.default_rng(7)
    # tight cluster around e1 ...
    core = _unit(rng, 120, 8) * 0.05
    core[:, 0] += 1.0
    core /= np.linalg.norm(core, axis=1, keepdims=True)
    # ... plus 6 mutually-orthogonal isolated points. At norm 0.5 they do
    # not even self-match on the cosine grid (d_self = 1 - 0.25 = 0.75 >
    # 0.4), so their raw exact count is 0 and the unclamped target is -1.
    outliers = 0.5 * np.eye(8, dtype=np.float32)[2:]
    R = np.concatenate([core, outliers]).astype(np.float32)
    cfg = XlingConfig(estimator="linear", metric="cosine", m=10,
                      backend="jnp", target_mode="exact")
    filt = XlingFilter(cfg).fit(R)
    eps = float(filt.eps_grid[0])
    exact = filt._targets_at(eps)
    assert (exact >= 0).all(), exact.min()
    interp = np.asarray(
        __import__("repro.core.xdt", fromlist=["interp_targets"]).interp_targets(
            filt.eps_grid, filt.target_table, eps))
    # both conventions agree on the isolated points: target exactly 0
    iso = exact[len(core):]
    np.testing.assert_array_equal(iso, np.zeros_like(iso))
    np.testing.assert_allclose(exact, interp, atol=1e-6)


# ------------------------------------------------------- multi-device (mesh)
@pytest.mark.slow
def test_sharded_engine_subprocess_8dev():
    """Forced 8-host-device subprocess (mirrors test_system): the sharded
    sweep must distribute the query axis over all devices and stay
    bit-for-bit equal to the ref backend, for the raw engine AND for
    cardinality_table; the compact/verify program must agree too."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'\n"
        "import numpy as np, jax\n"
        "from repro.launch.mesh import make_data_mesh\n"
        "from repro.core.engine import JoinEngine\n"
        "from repro.data.groundtruth import cardinality_table\n"
        "assert len(jax.devices()) == 8\n"
        "rng = np.random.default_rng(1)\n"
        "def unit(n, d):\n"
        "    x = rng.normal(size=(n, d)).astype(np.float32)\n"
        "    return x / np.linalg.norm(x, axis=1, keepdims=True)\n"
        "R, Q = unit(700, 16), unit(357, 16)\n"
        "eps = np.linspace(0.2, 1.8, 19).astype(np.float32)\n"
        "mesh = make_data_mesh()\n"
        "eng = JoinEngine(R, 'l2', mesh=mesh, backend='jnp')\n"
        "out = eng.device_range_count_hist(Q, eps)\n"
        "assert len({s.device for s in out.addressable_shards}) == 8\n"
        "ref_eng = JoinEngine(R, 'l2', backend='ref')\n"
        "want = ref_eng.range_count_hist(Q, eps)\n"
        "np.testing.assert_array_equal(eng.range_count_hist(Q, eps), want)\n"
        "t_mesh = cardinality_table(Q, R, eps, 'l2', backend='jnp', mesh=mesh)\n"
        "t_ref = cardinality_table(Q, R, eps, 'l2', backend='ref')\n"
        "np.testing.assert_array_equal(t_mesh, t_ref)\n"
        "v = rng.random(len(Q)) > 0.4\n"
        "res = eng.filtered_join(Q, float(eps[9]), verdicts=v)\n"
        "np.testing.assert_array_equal(res.counts, np.where(v, want[:, 9], 0))\n"
        "print('ENGINE_SHARDED_OK')\n"
    )
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         capture_output=True, text=True, timeout=300)
    assert "ENGINE_SHARDED_OK" in out.stdout, out.stderr[-2000:]


@pytest.mark.slow
def test_ring_topology_subprocess_8dev():
    """Forced 8-host-device subprocess: the ring topology (R row-sharded
    over the r axis, ppermute ring sweep) must stay bit-for-bit equal to
    the ref oracle on a 2x4 (r, data) mesh — raw sweep, compaction,
    sharded candidate verification, and the async stream — and on a 4x2
    mesh `JoinPlan.describe()` must report per-device R bytes reduced 4x
    vs the replicated placement."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'\n"
        "import numpy as np, jax\n"
        "from repro.launch.mesh import make_join_mesh\n"
        "from repro.core.engine import JoinEngine\n"
        "from repro.core.api import JoinPlan\n"
        "from repro.core.joins.common import verify_candidates\n"
        "assert len(jax.devices()) == 8\n"
        "rng = np.random.default_rng(2)\n"
        "def unit(n, d):\n"
        "    x = rng.normal(size=(n, d)).astype(np.float32)\n"
        "    return x / np.linalg.norm(x, axis=1, keepdims=True)\n"
        "R, Q = unit(700, 16), unit(357, 16)\n"
        "eps = np.linspace(0.2, 1.8, 19).astype(np.float32)\n"
        "ref_eng = JoinEngine(R, 'l2', backend='ref')\n"
        "want = ref_eng.range_count_hist(Q, eps)\n"
        "mesh = make_join_mesh(data=4, r=2)\n"
        "assert dict(zip(mesh.axis_names, mesh.devices.shape)) == "
        "{'r': 2, 'data': 4}\n"
        "eng = JoinEngine(R, 'l2', mesh=mesh, backend='jnp', "
        "topology='ring')\n"
        "out = eng.device_range_count_hist(Q, eps)\n"
        "assert len({s.device for s in out.addressable_shards}) == 8\n"
        "assert len({s.device for s in eng._Rdev.addressable_shards}) == 8\n"
        "np.testing.assert_array_equal(eng.range_count_hist(Q, eps), want)\n"
        "for seed in (0, 1):\n"
        "    v = np.random.default_rng(seed).random(len(Q)) > 0.4\n"
        "    res = eng.filtered_join(Q, float(eps[9]), verdicts=v)\n"
        "    np.testing.assert_array_equal(res.counts, "
        "np.where(v, want[:, 9], 0))\n"
        "cand = rng.integers(-1, len(R), size=(len(Q), 33)).astype(np.int32)\n"
        "want_vc = verify_candidates(R, Q, cand, 0.8, 'l2', backend='jnp')\n"
        "got_vc = verify_candidates(eng._Rdev, Q, cand, 0.8, 'l2', "
        "backend='jnp', mesh=mesh, r_axis='r', "
        "shard_rows=eng.nr_padded // eng.r_shards)\n"
        "np.testing.assert_array_equal(got_vc, want_vc)\n"
        "batches = [Q[:50], Q[50:51], Q[51:200], Q[200:]]\n"
        "sync = [eng.filtered_join(b, 0.8, verdicts=np.ones(len(b), bool)) "
        "for b in batches]\n"
        "stream = list(eng.stream(batches, 0.8, depth=2))\n"
        "for s, a in zip(sync, stream):\n"
        "    np.testing.assert_array_equal(a.counts, s.counts)\n"
        # JoinPlan on a 4x2 mesh: counts identical to replicated/ref AND
        # per-device R bytes down 4x (|R|=4096 divides 4*block_r evenly)
        "R2, Q2 = unit(4096, 16), unit(193, 16)\n"
        "mesh4 = make_join_mesh(data=2, r=4)\n"
        "ring_plan = JoinPlan(R2, 'l2').filter('none').on(mesh=mesh4, "
        "backend='jnp', topology='ring')\n"
        "rep_plan = JoinPlan(R2, 'l2').filter('none').on(backend='jnp')\n"
        "want2 = JoinEngine(R2, 'l2', backend='ref').range_count(Q2, 0.8)\n"
        "a, b = ring_plan.run(Q2, 0.8), rep_plan.run(Q2, 0.8)\n"
        "np.testing.assert_array_equal(a.counts, want2)\n"
        "np.testing.assert_array_equal(b.counts, want2)\n"
        "sc = np.concatenate([r.counts for r in "
        "ring_plan.stream([Q2[:100], Q2[100:]], 0.8)])\n"
        "np.testing.assert_array_equal(sc, want2)\n"
        "tr = ring_plan.describe()['exec']['topology']\n"
        "tp = rep_plan.describe()['exec']['topology']\n"
        "assert tr['name'] == 'ring' and tr['r_shards'] == 4, tr\n"
        "assert tp['per_device_r_bytes'] == 4 * tr['per_device_r_bytes'], "
        "(tp, tr)\n"
        "print('RING_TOPOLOGY_OK')\n"
    )
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         capture_output=True, text=True, timeout=300)
    assert "RING_TOPOLOGY_OK" in out.stdout, out.stderr[-2000:]


# ------------------------------------------------------------- mesh compat
def test_make_mesh_no_axistype_dependency():
    """The one mesh builder must build meshes over the default device
    order and over explicit devices, with every axis in Auto mode."""
    import jax
    from repro.launch.mesh import (make_cpu_mesh, make_data_mesh,
                                   make_join_mesh, make_mesh)
    m = make_mesh((1, 1), ("data", "model"))
    assert m.axis_names == ("data", "model")
    m2 = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    assert m2.devices.shape == (1,)
    assert [t.name for t in m2.axis_types] == ["Auto"]
    assert make_cpu_mesh().axis_names == ("data", "model")
    assert make_data_mesh().axis_names == ("data",)
    assert make_join_mesh(data=1, r=1).axis_names == ("r", "data")
    with pytest.raises(ValueError):
        make_join_mesh(r=0)
    with pytest.raises(ValueError):
        make_join_mesh(r=len(jax.devices()) + 1)
