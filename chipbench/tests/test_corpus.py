"""The cell's draw: each configuration's R and query pool are the split
of the program's own generator at the cell's seed, byte for byte."""
import json
import os

import numpy as np
import pytest

import corpus
from conftest import BENCH_DIR

CONFIGS = sorted(f[:-len(".json")]
                 for f in os.listdir(os.path.join(BENCH_DIR, "configs")))


@pytest.mark.parametrize("name", CONFIGS)
def test_draw_splits_the_generators_bytes(name):
    from repro.data import synthetic
    with open(os.path.join(BENCH_DIR, "configs", f"{name}.json")) as f:
        config = json.load(f)
    config.update(n_sample=2500, n_r=2000)
    for seed in (0, 2 ** 31 + 5):
        R, Q = corpus.draw(config, seed, pool=3, rows=500)
        x = synthetic.generate(config["corpus"], 3500, seed)
        assert R.dtype == Q.dtype == np.float32
        np.testing.assert_array_equal(R, x[:2000])
        np.testing.assert_array_equal(Q, x[2000:])
