"""The benchmark's own copy of the corpus stand-ins, and the draw of one
cell's data from its seed.

`generate` is a copy of `repro.data.synthetic.generate` (the seeded
stand-ins of the Xling paper's evaluation corpora, made without a
download), kept here so that no change to the program can change the
yardstick's data.  `tests/test_copies.py` pins the two together.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class DatasetSpec:
    name: str
    dim: int
    n_clusters: int
    spread: float          # within-cluster noise scale (always-positive pop.)
    pair_frac: float       # "threshold pairs": NN distance inside the eps band
    pair_band: tuple       # (lo, hi) distance band for pair separation
    outlier_frac: float    # isotropic background points (always negative)
    metric: str            # paper: cosine for text, l2 for image
    kind: str              # "text" | "image"


DATASETS: dict[str, DatasetSpec] = {
    "fasttext": DatasetSpec("fasttext", 300, 24, 0.40, 0.13, (0.33, 0.52), 0.008, "cosine", "text"),
    "glove":    DatasetSpec("glove",    200, 160, 0.45, 0.24, (0.36, 0.53), 0.63, "cosine", "text"),
    "word2vec": DatasetSpec("word2vec", 300, 64, 0.42, 0.25, (0.34, 0.53), 0.06, "cosine", "text"),
    "gist":     DatasetSpec("gist",     960, 96, 0.25, 0.80, (0.38, 0.52), 0.08, "l2", "image"),
    "sift":     DatasetSpec("sift",     128, 128, 0.25, 0.46, (0.36, 0.53), 0.13, "l2", "image"),
    "nuswide":  DatasetSpec("nuswide",  500, 400, 0.28, 0.03, (0.40, 0.52), 0.945, "l2", "image"),
}


def _pair_points(rng, n_pairs: int, dim: int, band: tuple, metric: str) -> np.ndarray:
    """2*n_pairs unit vectors in isolated pairs at controlled distance."""
    u = rng.normal(size=(n_pairs, dim))
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    w = rng.normal(size=(n_pairs, dim))
    w -= np.sum(w * u, axis=1, keepdims=True) * u
    w /= np.linalg.norm(w, axis=1, keepdims=True)
    dist = np.exp(rng.uniform(np.log(band[0]), np.log(band[1]), size=(n_pairs, 1)))
    if metric == "cosine":
        cos = 1.0 - dist
    else:  # l2 on the unit sphere: d^2 = 2 - 2 cos
        cos = 1.0 - dist ** 2 / 2.0
    cos = np.clip(cos, -1.0, 1.0)
    v = cos * u + np.sqrt(1.0 - cos ** 2) * w
    return np.concatenate([u, v], axis=0)


def _generate(spec: DatasetSpec, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    n_out = int(spec.outlier_frac * n)
    n_pair = int(spec.pair_frac * n) // 2 * 2
    n_clu = n - n_out - n_pair

    centers = rng.normal(size=(spec.n_clusters, spec.dim))
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    w = 1.0 / np.arange(1, spec.n_clusters + 1) ** 0.8
    w /= w.sum()
    assign = rng.choice(spec.n_clusters, size=n_clu, p=w)
    noise = rng.normal(size=(n_clu, spec.dim)) * (spec.spread / np.sqrt(spec.dim))
    x_clu = centers[assign] + noise

    x_pair = _pair_points(rng, n_pair // 2, spec.dim, spec.pair_band, spec.metric)
    x_out = rng.normal(size=(n_out, spec.dim))
    x = np.concatenate([x_clu, x_pair, x_out], axis=0)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    rng.shuffle(x)
    return x.astype(np.float32)


def generate(name: str, n: int, seed: int = 0, sample: int = 1) -> np.ndarray:
    """n float32 unit rows of corpus `name`, drawn from `seed`."""
    return _generate(DATASETS[name], n, seed + 104729 * (sample - 1))


def draw(config: dict, seed: int, pool: int, rows: int):
    """(R, Q) for one cell, from one draw of `config["corpus"]` at `seed`,
    so every query comes from the distribution R comes from (the same
    cluster centres).  The first `n_sample` rows are the paper's
    evaluation sample: R is its first `n_r` rows and the rest, `rows`
    rows, the first query set (the 8:2 split); Q is that set followed by
    `pool - 1` further sets of `rows` rows of the same draw."""
    n_sample, n_r = int(config["n_sample"]), int(config["n_r"])
    if n_sample - n_r != rows:
        raise ValueError(f"the first query set is the sample's last "
                         f"{n_sample - n_r} rows, not {rows}")
    x = generate(config["corpus"], n_sample + (pool - 1) * rows, seed)
    return x[:n_r], x[n_r:]
