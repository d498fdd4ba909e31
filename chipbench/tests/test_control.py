"""The check's control at a size a test run holds: the reference one
precision step down, put where the program's verify stands, is flagged
by the cell's own check on the exact route, while the reference itself
reads nothing; and the faults `control.py` plants in the program's own
answers are flagged on every route.

On the chip the control runs at the cell's own size
(`python3 chipbench/control.py --workload <cell> --seeds ...`); its
readings and the limits set from them are in PERF.md.  Here R has
20,000 rows and the pool two sets of 5,000 queries: enough pairs lie
near eps=0.45 for one bfloat16 pass to move some counts across it.  A
filter cell's fit is cut as in `conftest.tiny`."""
import copy

import pytest

import control
import harness


def small(cell):
    cell = copy.deepcopy(cell)
    cell.config.update(n_sample=25000, n_r=20000)
    if "filter" in cell.config:
        cell.config["filter"].update(epochs=1, widths=[32, 32],
                                     stage_sizes=[1, 2])
    cell.traffic.update(rows=5000, pool=2)
    return cell


@pytest.mark.parametrize("workload,seed", [("glove200-exact-s30k", 1),
                                           ("sift128-xjoin-s30k", 1),
                                           ("sift128-exact-s30k", 1)])
def test_control_is_flagged_by_the_check(bench, workload, seed):
    cell = small(harness.resolve_cell(bench, workload))
    readings = {r["answers"]: r for r in control.control_readings(
        cell, seed, ["highest", "bf16"])}
    program = readings["program"]
    assert program["wrong_queries"] == 0 and program["program_off"] == 0
    assert readings["highest"]["wrong_queries"] == 0
    assert readings["answer_altered"]["wrong_queries"] > 0
    zeroed = readings["half_zeroed"]
    assert (zeroed["missed_pair_frac"]
            > program["missed_pair_frac"] + (1 - program["missed_pair_frac"]) / 4)
    if cell.traffic["route"] == "exact":
        assert program["searched"] == program["queries"]
        assert readings["bf16"]["wrong_queries"] > 0
        assert zeroed["wrong_queries"] > 0
