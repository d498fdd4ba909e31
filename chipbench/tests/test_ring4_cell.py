"""The four-chip exact cell, `glove200-ring4-exact`, off the chip, and the
readers of its per-layer metrics.

The cell's runs go through one child process on four virtual CPU devices
(`--xla_force_host_platform_device_count=4`, which must be set before
JAX starts, so not in the test process): the cell at `conftest.tiny`'s
size through `harness.run_cell`, sound and under each fault of
`test_faults.FAULTS`, and the check's control at `test_control.small`'s
size.  The plan is built as a run builds it, through
`JoinPlan.on(topology="ring", r_shards=4)`, so R lies in four shards on
four devices and verify sums the shards' counts across them.

The readers are checked on a hand-made trace of four device planes and
on the one-chip trace `data/trace_small.json`.

Run the child alone with `python chipbench/tests/test_ring4_cell.py`
(it sets the flag itself); its last line of output is the JSON the
tests read."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH_DIR, ROOT, tiny  # puts the benchmark on the path
import harness  # noqa: E402
import tracing  # noqa: E402
from test_faults import FAULTS, SEED  # noqa: E402

WORKLOAD = "glove200-ring4-exact"
DEVICES = 4
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


# ============================================== the cell on four devices
class Recording:
    """A built `JoinPlan` whose `run` keeps every query set and answer."""

    def __init__(self, plan, calls: list):
        self._plan, self._calls = plan, calls

    def run(self, Q, eps):
        res = self._plan.run(Q, eps)
        self._calls.append((np.array(Q), np.array(res.counts)))
        return res


def child_readings() -> dict:
    """What the tests read, computed on four devices (the child's body)."""
    import jax
    import control
    import test_control
    from corpus import draw
    from test_faults import Faulty

    assert len(jax.devices()) == DEVICES
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        base = harness.resolve_cell(json.load(f), WORKLOAD)
    cell = tiny(base)
    out, calls = {}, []

    def record(plan):
        eng = plan.engine
        out["shard_devices"] = len({s.device
                                    for s in eng.r_device.addressable_shards})
        out["mesh"] = dict(eng.mesh.shape)
        out["r_rows"] = [int(s.data.shape[0])
                         for s in eng.r_device.addressable_shards]
        return Recording(plan, calls)

    out["sound"] = harness.run_cell(cell, SEED, 0.3, False, plan_hook=record)
    R, _ = draw(cell.config, SEED, int(cell.traffic["pool"]),
                int(cell.traffic["rows"]))
    ref = harness.load_module(
        os.path.join(BENCH_DIR, "references", "range_count.py"), "ref_ring4")
    exp = ref.counts(np.concatenate([q for q, _ in calls]), R,
                     float(cell.config["eps"]), cell.config["metric"])
    got = np.concatenate([c for _, c in calls])
    out["answers"] = len(got)
    out["answers_off_reference"] = int((got != exp).sum())
    out["faults"] = {
        name: harness.run_cell(cell, SEED, 0.3, False,
                               plan_hook=lambda p, f=fault: Faulty(p, f))
        for name, fault in FAULTS.items() if name != "sound"}
    out["control"] = {r["answers"]: r for r in control.control_readings(
        test_control.small(base), 1, ["highest", "bf16"])}
    return out


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={DEVICES}")
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_r_shards_lie_on_four_devices(four):
    assert four["mesh"] == {"r": DEVICES, "data": 1}
    assert four["shard_devices"] == DEVICES
    # 2,000 rows padded to 2,048 (512 x 4): four equal shards
    assert four["r_rows"] == [512] * DEVICES


def test_sound_run_is_correct(four):
    out = four["sound"]
    assert out["attempted"] > 0
    assert out["correct"] and out["failed"] == 0
    assert out["checks"]["wrong_queries"]["value"] == 0


def test_counts_equal_the_reference(four):
    """Every answer of the run, warm-up and window, equals the plain
    reference's count exactly."""
    assert four["answers"] >= 3 * 500
    assert four["answers_off_reference"] == 0


@pytest.mark.parametrize("fault", sorted(set(FAULTS) - {"sound"}))
def test_fault_fails_the_check(four, fault):
    out = four["faults"][fault]
    assert out["attempted"] > 0
    assert not out["correct"] and out["failed"] > 0
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_control_is_flagged_by_the_check(four):
    readings = four["control"]
    program = readings["program"]
    assert program["searched"] == program["queries"]
    assert program["wrong_queries"] == 0 and program["program_off"] == 0
    assert readings["highest"]["wrong_queries"] == 0
    assert readings["bf16"]["wrong_queries"] > 0
    assert readings["answer_altered"]["wrong_queries"] > 0
    assert readings["half_zeroed"]["wrong_queries"] > 0


# ============================================================ the readers
def reader(name: str):
    return harness.metric_reader("layer_metrics", name)


def layer_module(name: str):
    return harness.load_module(
        os.path.join(BENCH_DIR, "layer_metrics", f"{name}.py"), f"t_{name}")


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


def four_plane_events() -> list:
    """A traced window of two verify calls on four chips: per chip and
    call, a `jit_prog` module of 600 ns holding a 50 ns all-reduce (the
    gather of the compacted block), a 400 ns sweep and a 100 ns all-reduce
    of the counts, named by its primitive as the TPU compiler names it;
    and one `jit_program` module holding a collective-permute, which is no
    verify op."""
    events = [ev("/host:CPU", "host", "bench.window", 0, 3000),
              ev("/host:CPU", "host", "bench.run", 0, 1000),
              ev("/host:CPU", "host", "bench.run", 1000, 1000)]
    for d in range(DEVICES):
        plane = f"{tracing.DEVICE_PLANE_PREFIX}{d}"
        for t in (100, 1100):
            events += [
                ev(plane, tracing.MODULES_LINE, "jit_prog(5)", t, 600),
                ev(plane, tracing.OPS_LINE, "all-reduce.3", t, 50),
                ev(plane, tracing.OPS_LINE, "while.1", t + 50, 400),
                ev(plane, tracing.OPS_LINE, "psum.7", t + 450, 100)]
        events += [ev(plane, tracing.MODULES_LINE, "jit_program(3)", 2200, 200),
                   ev(plane, tracing.OPS_LINE, "collective-permute.4", 2200,
                      200)]
    return events


@pytest.fixture
def record(bench):
    cell = harness.resolve_cell(bench, WORKLOAD)
    calls = [harness.Call(start=0, n=30000, t0=0.0, t1=1.0, n_searched=30000,
                          traced=True) for _ in range(2)]
    return harness.RunRecord(
        cell=cell, calls=calls, window_s=3e-6, setup_s=0.0, compile_s=0.0,
        spans={}, peak_bytes=None, device_kind="TPU v5 lite",
        trace=tracing.reduce_events(four_plane_events()))


def test_verify_device_ms_reads_one_chip(record):
    assert record.trace["devices"] == DEVICES
    assert record.trace["module_s"]["jit_prog"] == pytest.approx(
        DEVICES * 2 * 600e-9)
    one_chip = reader("verify_device_ms.ring4")(record)
    assert one_chip == pytest.approx(600e-6)          # 600 ns a call
    assert reader("verify_device_ms")(record) == pytest.approx(
        DEVICES * one_chip)


def test_verify_device_ms_on_one_plane_is_the_plain_reading():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        trace = tracing.reduce_events(json.load(f)["events"])
    rec = harness.RunRecord(
        cell=None, calls=[harness.Call(start=0, n=1, t0=0.0, traced=True)],
        window_s=1.0, setup_s=0.0, compile_s=0.0, spans={}, peak_bytes=None,
        device_kind="TPU v5 lite", trace=trace)
    assert trace["devices"] == 1
    assert reader("verify_device_ms.ring4")(rec) == pytest.approx(
        reader("verify_device_ms")(rec))


def test_verify_roofline_divides_the_work_by_the_chips(record):
    ring, plain = layer_module("verify_roofline.ring4"), \
        layer_module("verify_roofline")
    n, n_r, d = 30000, 1183514, 200
    ops4, bytes4 = ring.work(n, n_r, d, DEVICES)
    assert ops4 * DEVICES == ring.work(n, n_r, d, 1)[0] == plain.work(n, n_r, d)[0]
    assert ring.work(n, n_r, d, 1)[1] == plain.work(n, n_r, d)[1]
    assert bytes4 == (n_r / DEVICES + n) * d * 4
    # the hand-made calls are compute-bound: 2 calls' operations over the
    # bf16 peak, against one chip's 2 x 600 ns of verify
    want = 100 * 2 * ops4 / 197e12 / (2 * 600e-9)
    assert ring.read(record) == pytest.approx(want)


def test_verify_collective_frac_counts_only_verify_collectives(record):
    # (50 + 100) of every 600 ns of jit_prog; the sweep and the other
    # module's collective-permute are not counted
    assert reader("verify_collective_frac")(record) == pytest.approx(0.25)
    record.trace = None
    assert reader("verify_collective_frac")(record) is None


def test_r_place_s_reads_the_span(record):
    assert reader("r_place_s")(record) is None
    record.spans["place_r"] = 1.75
    assert reader("r_place_s")(record) == 1.75


if __name__ == "__main__":
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={DEVICES}")
    print(json.dumps(child_readings(), default=float), flush=True)
