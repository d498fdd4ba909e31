"""The benchmark's CPU tests: its modules and the program's `src` on the
path, and a tiny cell for runs off the chip."""
import copy
import json
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def tiny(cell):
    """`cell` cut to a size the CPU runs in seconds: R of 2,000 rows, a
    pool of three 500-row sets, a one-epoch fit of a small RMI."""
    cell = copy.deepcopy(cell)
    cell.config.update(n_sample=2500, n_r=2000)
    if "filter" in cell.config:
        cell.config["filter"].update(epochs=1, widths=[32, 32],
                                     stage_sizes=[1, 2])
    cell.traffic.update(rows=500, pool=3)
    return cell
