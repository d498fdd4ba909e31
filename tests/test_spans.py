"""The join pipeline's host spans (`engine._span`, DESIGN.md §12).

Covers: the span tree `JoinPlan.run` opens on the filter route, the
exact route and a batch with no positives; the counts each span carries
(`rows`, `h2d_bytes`, `n_pos`, `capacity`) against the call's own
numbers and the query buffer actually uploaded; the filter dispatch's
`args` and `uploads`, none after a stream's first batch; one `batch` id tying a streamed batch's stage,
verify and result spans together across the `submit` calls of a depth-2
session; the declared syncs (`_note_host_sync`) as `join.sync.<kind>`
spans; a real profiler session on the CPU returning the spans with
their counts as event stats; the module names the trace gives the
filter and verify programs; and R's landing (`join.place_r`) with the
shards verify sweeps (`r_shards`), on one device and, in a child
process on four virtual devices, under `topology="ring"`.
"""
import contextlib
import dataclasses
import glob
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import JoinPlan
from repro.core import api, engine
from repro.core.engine import _compact_program

EPS = 0.45


def _first_coord(params, X):
    return X[:, 0] - params


class FirstCoordFilter:
    """A Filter-protocol object with a device form: keeps the queries
    whose first coordinate exceeds `cut`."""
    tau = 0

    def __init__(self, cut: float):
        self.cut = cut

    def verdicts(self, Q, eps):
        return np.asarray(Q)[:, 0] > self.cut

    def device_filter(self, eps):
        return (jnp.float32(self.cut), _first_coord), 0.0


@dataclasses.dataclass
class Span:
    name: str
    counts: dict
    parent: "Span | None"


class Recorder:
    """Stands in for `_span` and `_note_host_sync`: every span opened,
    with its counts and the span it opened in, and every sync noted."""

    def __init__(self):
        self.spans: list[Span] = []
        self.syncs: list[str] = []
        self._open: list[Span] = []

    @contextlib.contextmanager
    def span(self, name, **counts):
        s = Span(name, counts, self._open[-1] if self._open else None)
        self.spans.append(s)
        self._open.append(s)
        try:
            yield
        finally:
            self._open.pop()

    def named(self, name):
        return [s for s in self.spans if s.name == name]

    def names(self):
        return [s.name for s in self.spans]

    def children(self, parent):
        return [s.name for s in self.spans if s.parent is parent]


@pytest.fixture
def rec(monkeypatch):
    r = Recorder()
    monkeypatch.setattr(engine, "_span", r.span)
    monkeypatch.setattr(api, "_span", r.span)
    monkeypatch.setattr(engine, "_note_host_sync", r.syncs.append)
    return r


@pytest.fixture
def staged(monkeypatch):
    """Every `_StagedBatch` the engine stages, in order."""
    out = []
    stage = engine.JoinEngine._stage_filter

    def recording(self, *a, **k):
        out.append(stage(self, *a, **k))
        return out[-1]
    monkeypatch.setattr(engine.JoinEngine, "_stage_filter", recording)
    return out


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(3)

    def unit(n, d=16):
        x = rng.normal(size=(n, d)).astype(np.float32)
        return x / np.linalg.norm(x, axis=1, keepdims=True)
    return unit(700), unit(300)


def _plan(R, filt):
    return (JoinPlan(R, "cosine").search("naive").on(backend="jnp")
            .filter(filt).build())


def test_filter_route_span_tree(data, rec, staged):
    R, Q = data
    plan = _plan(R, FirstCoordFilter(0.0))
    res = plan.run(Q, EPS)
    assert 0 < res.n_searched < len(Q)
    (run,) = rec.named("join.run")
    assert run.parent is None and run.counts == {}
    assert rec.children(run) == ["join.stage", "join.sync.n_pos",
                                 "join.verify", "join.sync.result"]
    (stage,) = rec.named("join.stage")
    assert rec.children(stage) == ["join.stage.pad", "join.stage.upload",
                                   "join.stage.filter"]
    (st,) = staged
    assert stage.counts == {"batch": st.batch, "rows": len(Q),
                            "h2d_bytes": st.qdev.nbytes}
    assert st.qdev.shape[0] > len(Q)            # the padding is counted
    # one stacked parameter buffer + queries, eps, threshold, row count;
    # a new engine uploads the three scalars
    (filt,) = rec.named("join.stage.filter")
    assert filt.counts == {"args": 5, "uploads": 3}
    (verify,) = rec.named("join.verify")
    assert verify.counts["n_pos"] == res.n_searched
    assert verify.counts["capacity"] >= res.n_searched
    batch = stage.counts["batch"]
    assert verify.counts["batch"] == batch
    for kind in ("n_pos", "result"):
        assert rec.named(f"join.sync.{kind}")[0].counts == {"batch": batch}
    assert rec.syncs == ["n_pos", "result"]


def test_exact_route_reads_no_count(data, rec):
    """Without a filter the count is known when the batch is staged: no
    `join.sync.n_pos`, and no filter program to dispatch."""
    R, Q = data
    res = _plan(R, "none").run(Q, EPS)
    assert res.n_searched == len(Q)
    assert "join.sync.n_pos" not in rec.names()
    assert "join.stage.filter" not in rec.names()
    (verify,) = rec.named("join.verify")
    assert verify.counts["n_pos"] == len(Q)
    assert verify.counts["capacity"] >= len(Q)
    assert rec.syncs == ["result"]


def test_no_positives_opens_no_verify(data, rec):
    R, Q = data
    res = _plan(R, FirstCoordFilter(2.0)).run(Q, EPS)   # unit rows: none
    assert res.n_searched == 0 and not res.counts.any()
    assert "join.verify" not in rec.names()
    assert rec.names()[-1] == "join.sync.result"


def test_session_batches_share_one_id(data, rec):
    """A depth-2 session stages, verifies and reads each batch in
    different `submit` calls; the `batch` id ties its spans together."""
    R, Q = data
    plan = _plan(R, FirstCoordFilter(0.0))
    sess = plan.session(EPS, depth=2)
    batches = [Q[i:i + 60] for i in range(0, 300, 60)]
    out = []
    for b in batches:
        out += sess.submit(b)
    out += sess.flush()
    assert len(out) == len(batches)
    stages = rec.named("join.stage")
    ids = [s.counts["batch"] for s in stages]
    assert len(set(ids)) == len(batches)
    assert all(s.parent.name == "join.submit" for s in stages)
    verifies = {s.counts["batch"]: s for s in rec.named("join.verify")}
    results = [s.counts["batch"] for s in rec.named("join.sync.result")]
    assert results == ids                   # FIFO, one read per batch
    for i, r in zip(ids, out):
        if r.n_searched:
            assert verifies[i].counts["n_pos"] == r.n_searched
        else:
            assert i not in verifies
    # a batch's verify opens in a later submit than its stage
    first = stages[0]
    assert verifies[ids[0]].parent is not first.parent
    assert rec.named("join.flush")[0].parent is None


def test_stream_uploads_no_scalars_after_first_batch(data, rec):
    """A stream's radius, threshold and row count are the same on every
    batch: the first batch uploads them, later ones reuse them."""
    R, Q = data
    plan = _plan(R, FirstCoordFilter(0.0))
    list(plan.stream([Q[:100], Q[100:200], Q[200:300]], EPS))
    filt = rec.named("join.stage.filter")
    assert [s.counts["uploads"] for s in filt] == [3, 0, 0]
    assert {s.counts["args"] for s in filt} == {5}


def test_profiler_session_returns_the_spans(data, tmp_path):
    """Under a real profiler session the spans land in the trace with
    their counts as event stats."""
    R, Q = data
    plan = _plan(R, FirstCoordFilter(0.0))
    plan.run(Q, EPS)                                    # compile first
    with jax.profiler.trace(str(tmp_path)):
        res = plan.run(Q, EPS)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("join."):
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    assert {"join.run", "join.stage", "join.stage.pad", "join.stage.upload",
            "join.stage.filter", "join.sync.n_pos", "join.verify",
            "join.sync.result"} <= set(found)
    (stage,) = found["join.stage"]
    padded = plan.engine.padded_rows(len(Q))
    assert stage["rows"] == len(Q)
    assert stage["h2d_bytes"] == padded * Q.shape[1] * 4
    assert found["join.verify"][0]["n_pos"] == res.n_searched
    assert found["join.stage.filter"] == [{"args": 5, "uploads": 0}]


def test_program_module_names(data):
    """The trace names a program's module by its jitted function; the
    benchmark's device metrics find the filter and verify modules under
    these names."""
    R, Q = data
    eng = engine.JoinEngine(R, "cosine", backend="jnp")
    predict = (jnp.float32(0.0), _first_coord)
    st = eng._stage_filter(Q, EPS, predict=predict, threshold=0.0)
    prog, stacked = eng._filter_program(predict)
    lowered = prog.lower(stacked, st.qdev, st.eps_dev, jnp.float32(0.0),
                         jnp.int32(st.n))
    assert "module @jit_program" in lowered.as_text()
    cprog = _compact_program(eng.mesh, eng.data_axis, eng.backend,
                             eng.metric, eng.block_q, eng.block_r, eng.nr,
                             eng.topology)
    w = st.world
    lowered = cprog.lower(st.qdev, st.pos_dev, st.n_pos_dev, w.Rdev,
                          st.eps_dev, w.nrv, capacity=st.qdev.shape[0])
    assert "module @jit_prog " in lowered.as_text()


def test_place_r_span_on_one_device(data, rec):
    """The engine's construction lands R under `join.place_r`; one
    device holds all of padded R, and verify sweeps one shard."""
    R, Q = data
    plan = _plan(R, "none")
    eng = plan.engine
    (place,) = rec.named("join.place_r")
    assert place.parent is None
    assert place.counts == {"rows": len(R), "shards": 1,
                            "bytes_per_chip": eng.per_device_r_bytes}
    assert eng.per_device_r_bytes == eng.nr_padded * R.shape[1] * 4
    assert eng.r_device.shape == (eng.nr_padded, R.shape[1])
    plan.run(Q, EPS)
    (verify,) = rec.named("join.verify")
    assert verify.counts["r_shards"] == 1


RING_CHILD = """
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core import JoinPlan, engine
spans = []
class Span:
    def __init__(self, name, **counts):
        spans.append([name, counts])
    def __enter__(self):
        return self
    def __exit__(self, *exc):
        return False
engine._span = Span
rng = np.random.default_rng(5)
def unit(n, d=16):
    x = rng.normal(size=(n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)
R, Q = unit(2500), unit(300)
plan = (JoinPlan(R, "cosine").search("naive")
        .on(backend="jnp", topology="ring", r_shards=4).filter("none")
        .build())
eng = plan.engine
res = plan.run(Q, 0.45)
want = JoinPlan(R, "cosine").on(backend="ref").filter("none").run(Q, 0.45)
print(json.dumps({
    "spans": spans, "nr_padded": eng.nr_padded,
    "per_device_r_bytes": eng.per_device_r_bytes,
    "shard_devices": len({s.device for s in eng.r_device.addressable_shards}),
    "equal": bool((res.counts == want.counts).all())}))
"""


def test_ring_spans_on_four_devices():
    """Under `topology="ring", r_shards=4` on four devices, R lands in
    four shards of a quarter of its padded bytes each, and verify sweeps
    every query against four shards (a forced four-device child)."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", RING_CHILD], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    named = {}
    for name, counts in got["spans"]:
        named.setdefault(name, []).append(counts)
    # 2,500 rows padded to 4,096 (512 x 4): 1,024 rows of 16 floats a chip
    assert got["nr_padded"] == 4096
    assert got["per_device_r_bytes"] == 1024 * 16 * 4
    assert got["shard_devices"] == 4 and got["equal"]
    ring_place = [c for c in named["join.place_r"] if c["shards"] == 4]
    assert ring_place == [{"rows": 2500, "shards": 4,
                           "bytes_per_chip": 1024 * 16 * 4}]
    assert [c["r_shards"] for c in named["join.verify"]] == [4, 1]
