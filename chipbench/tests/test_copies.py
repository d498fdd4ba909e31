"""The benchmark's copies of the corpus generator and of the plain
reference agree with the program's originals at a tiny size."""
import jax
import numpy as np
import pytest

import corpus
from references import range_count


@pytest.mark.parametrize("name", sorted(corpus.DATASETS))
def test_generator_copy_matches_program(name):
    from repro.data import synthetic
    assert tuple(corpus.DATASETS[name].__dict__.values()) == tuple(
        synthetic.DATASETS[name].__dict__.values())
    for seed, sample in ((0, 1), (2 ** 31 + 5, 1), (7, 2)):
        np.testing.assert_array_equal(
            corpus.generate(name, 300, seed, sample),
            synthetic.generate(name, 300, seed, sample))


def test_draw_splits_one_corpus():
    cfg = {"corpus": "glove", "n_sample": 500, "n_r": 400}
    R, Q = corpus.draw(cfg, 3, pool=3, rows=100)
    x = corpus.generate("glove", 700, 3)
    np.testing.assert_array_equal(R, x[:400])
    np.testing.assert_array_equal(Q, x[400:])
    with pytest.raises(ValueError):
        corpus.draw(cfg, 3, pool=3, rows=50)


@pytest.mark.parametrize("metric,name", [("cosine", "glove"), ("l2", "sift")])
def test_reference_copy_matches_program_oracle(metric, name):
    from repro.kernels import ref
    x = corpus.generate(name, 1300, 11)
    Q, R = x[:300], x[300:]
    for eps in (0.3, 0.45, 0.6):
        want = np.asarray(ref.range_count(jax.numpy.asarray(Q),
                                          jax.numpy.asarray(R), eps, metric))
        got = range_count.counts(Q, R, eps, metric, q_block=128)
        np.testing.assert_array_equal(got, want)
        assert got.sum() > 0


@pytest.mark.parametrize("precision", ["high", "bf16"])
def test_control_precisions_stay_close(precision):
    """The control precisions count almost every pair as the reference."""
    x = corpus.generate("glove", 1300, 11)
    Q, R = x[:300], x[300:]
    hi = range_count.counts(Q, R, 0.45, "cosine")
    lo = range_count.counts(Q, R, 0.45, "cosine", precision=precision)
    assert np.abs(lo - hi).sum() <= max(1, hi.sum() // 100)
