"""The trace reduction, on hand-made events and on a small trace
recorded on a TPU v5e and committed in flattened form."""
import json
import os

import pytest

import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV = "/device:TPU:0"


def ev(line, name, start, dur, plane=DEV):
    return {"plane": plane if line != "host" else "/host:CPU", "line": line,
            "name": name, "start_ns": start, "dur_ns": dur}


def test_union_merges_overlaps():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_reduce_hand_made_window():
    events = [
        ev("host", "bench.window", 0, 1000),
        ev("host", "bench.run", 0, 600),
        ev("host", "bench.flush", 700, 300),
        ev(tracing.MODULES_LINE, "jit_prog(7)", 100, 300),
        ev(tracing.OPS_LINE, "fusion.1", 100, 200),
        ev(tracing.OPS_LINE, "fusion.2", 250, 150),    # overlaps fusion.1
        ev(tracing.MODULES_LINE, "jit_program(3)", 800, 100),
        ev(tracing.OPS_LINE, "dot.3", 800, 100),
        ev(tracing.OPS_LINE, "dot.4", 1200, 100),      # after the window
    ]
    r = tracing.reduce_events(events)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"] == pytest.approx(400e-9)        # [100,400) + [800,900)
    assert r["module_s"] == {"jit_prog": pytest.approx(300e-9),
                             "jit_program": pytest.approx(100e-9)}
    assert r["module_calls"] == {"jit_prog": 1, "jit_program": 1}
    gaps = dict(r["idle_gaps"])
    # a gap goes whole to the span that overlaps it most: [0,100) and
    # [400,800) to bench.run, [900,1000) to bench.flush
    assert gaps == {"bench.run": pytest.approx(500e-9),
                    "bench.flush": pytest.approx(100e-9)}
    assert dict(r["device_ops"])["jit_prog/fusion.1"] == pytest.approx(200e-9)


def test_reduce_needs_a_window_and_a_device():
    assert tracing.reduce_events([ev("host", "bench.run", 0, 5)]) is None
    assert tracing.reduce_events([ev("host", "bench.window", 0, 5)]) is None


def test_reduce_recorded_tpu_trace():
    """Six `run` calls of a tiny glove200 cell, traced on a TPU v5e: each
    call runs the filter and the verify module once, the metric files
    find their modules by the names this trace shows, and the device's
    idle time falls inside the benchmark's `bench.run` spans."""
    with open(os.path.join(DATA, "trace_small.json")) as f:
        events = json.load(f)["events"]
    r = tracing.reduce_events(events)
    assert r["devices"] == 1
    assert r["module_calls"]["jit_program"] == 6
    assert r["module_calls"]["jit_prog"] == 6
    assert 0 < r["busy_s"] < r["window_s"]
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert r["idle_gaps"][0][0] == "bench.run"
    assert r["device_ops"][0][0].startswith("jit_prog/")
    import harness
    for metric in ("filter_device_ms", "verify_device_ms", "verify_roofline"):
        mod = harness.load_module(
            os.path.join(harness.BENCH_DIR, "layer_metrics", metric + ".py"),
            "t_" + metric)
        assert all(m in r["module_s"] for m in mod.MODULES)
