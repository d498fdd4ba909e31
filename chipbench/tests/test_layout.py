"""`BENCHMARK.json` against the benchmark's contract, every name it gives
resolving to its files, the peak table, and the refusal to run off the
chip."""
import json
import os
import re
import subprocess
import sys

import pytest

import harness
from peaks import PEAKS, peaks_for

from conftest import BENCH_DIR, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
METRIC_KEYS = {"end_to_end": {"name", "unit", "better", "bound", "source"},
               "per_layer": {"name", "unit", "better", "source", "layer",
                             "moves"}}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert bench["command"][1].startswith("chipbench/")
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_text(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and TEXT.match(c["source"])
        assert TEXT.match(c["why"]) and all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and TEXT.match(w["why"])
    for kind, keys in METRIC_KEYS.items():
        for m in bench[kind]:
            assert set(m) - {"workloads"} == keys
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    names = [m["name"] for k in METRIC_KEYS for m in bench[k]]
    assert len(names) == len(set(names))


def test_bounds(bench):
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    assert bounds["setup_s"] <= 0.25
    assert all(0.01 <= b <= 0.25 for b in bounds.values())
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")


def test_per_layer_metrics_move_what_their_cells_report(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            assert w in moved.get("workloads", cells)


@pytest.mark.parametrize("workload", [
    w["name"] for w in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    ["workloads"]])
def test_workload_resolves_to_its_files(bench, workload):
    cell = harness.resolve_cell(bench, workload)
    route = harness.route_module(cell.traffic["route"])
    assert all(callable(getattr(route, f))
               for f in ("build", "wrong_mask", "searched_mask"))
    loop = harness.loop_module(cell.traffic["loop"])
    assert callable(loop.warm_up) and callable(loop.window)
    assert os.path.exists(os.path.join(BENCH_DIR, "references",
                                       cell.config["reference"] + ".py"))
    assert cell.checks and all("limit" in v for v in cell.checks.values())
    assert cell.end_to_end and cell.per_layer
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    for kind, entries in (("end_to_end", cell.end_to_end),
                          ("layer_metrics", cell.per_layer)):
        for m in entries:
            assert callable(harness.metric_reader(kind, m["name"]))


def test_config_files_are_distinct_and_state_their_cuts(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert c["file"].startswith("chipbench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["assumed"] and cfg["precision"] and cfg["guarantees"]


def test_peak_table_refuses_an_unknown_device():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert all(p["source"] for p in PEAKS.values())
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "glove200-exact-s30k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert "metrics" not in out.stdout


def test_run_fails_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files
    holds no system to measure: the run exits non-zero and prints no
    result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "glove200-exact-s30k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=120)
    assert out.returncode != 0 and "metrics" not in out.stdout
