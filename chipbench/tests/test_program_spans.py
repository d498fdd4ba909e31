"""The program's spans in a trace (`program_spans.py`) and the metrics
that read them: the innermost-span split of the device's idle time on
hand-made windows, the spans of a real profiler session on the CPU, and
a small trace recorded on a TPU v5e with the program's spans, committed
in flattened form."""
import json
import os

import numpy as np
import pytest

import harness
import program_spans
import trace_program
import tracing
from program_spans import OUTSIDE, PROGRAM_LINE

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV = "/device:TPU:0"
IDLE_METRICS = ("idle_stage_frac", "idle_sync_frac", "idle_dispatch_frac",
                "idle_outside_frac")
PROGRAM_METRICS = IDLE_METRICS + ("verify_pad_frac", "h2d_bytes_per_row")


def ev(line, name, start, dur, **args):
    e = {"plane": DEV if line in (tracing.OPS_LINE, tracing.MODULES_LINE)
         else "/host:CPU", "line": line, "name": name, "start_ns": start,
         "dur_ns": dur}
    if line == PROGRAM_LINE:
        e["args"] = args
    return e


def read(metric, trace):
    """`metric`'s reader on a run record holding `trace`."""
    mod = harness.load_module(
        os.path.join(harness.BENCH_DIR, "layer_metrics", metric + ".py"),
        "t_" + metric)
    run = harness.RunRecord(cell=None, calls=[], window_s=0.0, setup_s=0.0,
                            compile_s=0.0, spans={}, peak_bytes=None,
                            device_kind="", trace=trace)
    return mod.read(run)


def program_of(events):
    """`reduce_program` on the window and idle gaps of `events`."""
    return program_spans.reduce_program(events,
                                        *trace_program.window_gaps(events))


def with_program(events):
    r = tracing.reduce_events(events)
    r["program"] = program_of(events)
    return r


HAND_MADE = [
    ev("host", "bench.window", 0, 1000),
    ev("host", "bench.run", 0, 900),
    ev(PROGRAM_LINE, "join.run", 50, 800),
    ev(PROGRAM_LINE, "join.stage", 60, 200, batch=0, rows=10,
       h2d_bytes=640),
    ev(PROGRAM_LINE, "join.stage.pad", 70, 50),
    ev(PROGRAM_LINE, "join.stage.upload", 120, 100),
    ev(PROGRAM_LINE, "join.sync.n_pos", 300, 100, batch=0),
    ev(PROGRAM_LINE, "join.verify", 420, 80, batch=0, n_pos=6, capacity=8),
    ev(PROGRAM_LINE, "join.sync.result", 600, 250, batch=0),
    ev(tracing.MODULES_LINE, "jit_program(1)", 250, 100),
    ev(tracing.OPS_LINE, "fusion.1", 250, 100),
    ev(tracing.MODULES_LINE, "jit_prog(2)", 480, 300),
    ev(tracing.OPS_LINE, "fusion.2", 480, 300),
]


def test_idle_split_by_innermost_span():
    """Idle [0,250) and [350,480) and [780,1000) on the device; each part
    goes to the innermost program span open over it."""
    p = program_of(HAND_MADE)
    # outside: [0,50) and [850,1000); join.run: [50,60) and [400,420);
    # join.stage: [60,70) and [220,250); join.sync.n_pos: [350,400);
    # join.verify: [420,480); join.sync.result: [780,850)
    expect = {OUTSIDE: 200e-9, "join.run": 30e-9, "join.stage": 40e-9,
              "join.stage.pad": 50e-9, "join.stage.upload": 100e-9,
              "join.sync.n_pos": 50e-9, "join.verify": 60e-9,
              "join.sync.result": 70e-9}
    assert p["idle_s"] == pytest.approx(expect)
    r = tracing.reduce_events(HAND_MADE)
    assert sum(p["idle_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert p["spans"]["join.stage"] == [1, pytest.approx(50e-9)]
    assert p["spans"]["join.run"][1] == pytest.approx(
        (800 - 200 - 100 - 80 - 250) * 1e-9)
    assert p["counts"]["join.verify"] == {"n_pos": 6, "capacity": 8}
    assert "join.stage.pad" not in p["counts"]
    assert "join.sync.n_pos" not in p["counts"]     # a batch id only


def test_four_idle_metrics_sum_to_device_idle():
    trace = with_program(HAND_MADE)
    parts = {m: read(m, trace) for m in IDLE_METRICS}
    assert parts["idle_stage_frac"] == pytest.approx((40 + 150) / 1000)
    assert parts["idle_sync_frac"] == pytest.approx((50 + 70) / 1000)
    assert parts["idle_dispatch_frac"] == pytest.approx((30 + 60) / 1000)
    assert parts["idle_outside_frac"] == pytest.approx(200 / 1000)
    assert sum(parts.values()) == pytest.approx(
        read("device_idle_frac", trace), abs=1e-12)
    assert read("verify_pad_frac", trace) == pytest.approx(0.25)
    assert read("h2d_bytes_per_row", trace) == pytest.approx(64.0)


def test_spans_after_the_window_are_not_counted():
    late = HAND_MADE + [ev(PROGRAM_LINE, "join.stage", 1200, 10, batch=1,
                           rows=10, h2d_bytes=640)]
    p = program_of(late)
    assert p["spans"]["join.stage"][0] == 1
    assert p["counts"]["join.stage"]["rows"] == 10


def test_metrics_read_nothing_without_program_spans():
    """A trace of a program that opens no `join.` span (or a reduction
    without the program's part) gives no value, and raises nothing."""
    bare = [e for e in HAND_MADE if e["line"] != PROGRAM_LINE]
    for trace in (with_program(bare), tracing.reduce_events(bare), None):
        for m in PROGRAM_METRICS:
            assert read(m, trace) is None


def test_reduce_program_needs_a_window_and_a_device():
    """No window span or no device op: no window to split, as
    `tracing.reduce_events` finds none either."""
    for events in ([ev(PROGRAM_LINE, "join.run", 0, 5)],
                   [ev("host", "bench.window", 0, 5)]):
        assert trace_program.window_gaps(events) is None
        assert tracing.reduce_events(events) is None


def test_nested_spans_on_one_start():
    """Spans opened at the same nanosecond: the shorter is the inner."""
    events = [ev("host", "bench.window", 0, 100),
              ev(PROGRAM_LINE, "join.run", 0, 100),
              ev(PROGRAM_LINE, "join.stage", 0, 40),
              ev(tracing.OPS_LINE, "fusion.1", 90, 10)]
    p = program_of(events)
    assert p["idle_s"] == pytest.approx({"join.stage": 40e-9,
                                         "join.run": 50e-9})


def test_cpu_profiler_session_spans(tmp_path):
    """The program's spans of a real profiler session, read back with
    their counts."""
    import glob

    import jax
    from repro.core import JoinPlan
    rng = np.random.default_rng(0)
    R = rng.normal(size=(300, 8)).astype(np.float32)
    Q = rng.normal(size=(50, 8)).astype(np.float32)
    plan = JoinPlan(R, "l2").search("naive").on(backend="jnp") \
        .filter("none").build()
    plan.run(Q, 1.0)
    with jax.profiler.trace(str(tmp_path)):
        plan.run(Q, 1.0)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = program_spans.flatten_program(path)
    names = [e["name"] for e in events]
    assert {"join.run", "join.stage", "join.verify",
            "join.sync.result"} <= set(names)
    assert all(e["line"] == PROGRAM_LINE for e in events)
    stage = events[names.index("join.stage")]
    assert stage["args"]["rows"] == 50
    assert stage["args"]["h2d_bytes"] == plan.engine.padded_rows(50) * 8 * 4


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_program_small.json")) as f:
        return json.load(f)["events"]


def test_recorded_trace_keeps_every_existing_key(recorded):
    """The program's events change nothing `tracing.reduce_events`
    computes: every key reads the same with and without them."""
    bare = [e for e in recorded if e["line"] != PROGRAM_LINE]
    assert len(bare) < len(recorded)
    assert tracing.reduce_events(recorded) == tracing.reduce_events(bare)
    with open(os.path.join(DATA, "trace_small.json")) as f:
        old = json.load(f)["events"]
    assert all(e["line"] != PROGRAM_LINE for e in old)
    assert program_of(old)["spans"] == {}


@pytest.mark.parametrize("name", ["trace_small.json",
                                  "trace_program_small.json"])
def test_window_gaps_are_reduce_events_idle_time(name):
    """The window and idle gaps the program's split runs on are those of
    `tracing.reduce_events`: same window, and the gaps add up to its
    idle time."""
    with open(os.path.join(DATA, name)) as f:
        events = json.load(f)["events"]
    lo, hi, gaps = trace_program.window_gaps(events)
    r = tracing.reduce_events(events)
    assert (hi - lo) * 1e-9 == pytest.approx(r["window_s"], abs=1e-12)
    assert sum(b - a for a, b in gaps) * 1e-9 == pytest.approx(
        sum(s for _, s in tracing.reduce_events(events, top=10**6)
            ["idle_gaps"]), abs=1e-9)


def test_recorded_trace_program_split(recorded):
    """Six `run` calls of a tiny glove200 filter cell on a TPU v5e: the
    program's spans cover the calls' idle time, the four idle metrics
    sum to `device_idle_frac`, and the counts give the shapes' bytes."""
    trace = with_program(recorded)
    p = trace["program"]
    assert p["spans"]["join.run"][0] == 6
    assert p["spans"]["join.stage"][0] == 6
    assert p["spans"]["join.sync.n_pos"][0] == 6
    parts = [read(m, trace) for m in IDLE_METRICS]
    assert all(v is not None and v >= 0 for v in parts)
    assert sum(parts) == pytest.approx(read("device_idle_frac", trace),
                                       abs=1e-6)
    stage = p["counts"]["join.stage"]
    assert stage["rows"] == 6 * 1000
    # 1,000 rows upload as 1,024 (the engine's bucket) x 200 f32
    assert read("h2d_bytes_per_row", trace) == pytest.approx(
        1024 * 200 * 4 / 1000)
    assert 0 < read("verify_pad_frac", trace) < 1
