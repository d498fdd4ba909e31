"""Shared small utilities: PRNG plumbing, tree math, timing, caching."""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import time
from dataclasses import asdict, is_dataclass
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np

_REPO_ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", ".."))
CACHE_DIR = os.environ.get("REPRO_CACHE", os.path.join(_REPO_ROOT, ".cache"))
#: JAX persistent compilation cache used when JAX_COMPILATION_CACHE_DIR is
#: unset: one fixed directory inside the checkout (git ignores `.cache/`),
#: so a later process of the same checkout finds what an earlier one
#: compiled (the path is part of the cache key — a moving path never hits)
COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".cache", "jax_compile")


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With `JAX_COMPILATION_CACHE_DIR` set, that directory is the cache and
    no other path is configured; otherwise the cache goes to
    `COMPILE_CACHE_DIR`.  Called by the entry points that compile real
    programs (the serve CLI, `chip_smoke.py`), never at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or COMPILE_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cache_path(*key: Any, ext: str = "npz") -> str:
    os.makedirs(CACHE_DIR, exist_ok=True)
    blob = json.dumps([repr(k) for k in key], sort_keys=True).encode()
    h = hashlib.sha1(blob).hexdigest()[:16]
    return os.path.join(CACHE_DIR, f"{h}.{ext}")


def tree_size(tree: Any) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_bytes(tree: Any) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_cast(tree: Any, dtype) -> Any:
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def tree_zeros_like(tree: Any, dtype=None) -> Any:
    return jax.tree.map(lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree)


def split_like(key: jax.Array, tree: Any) -> Any:
    """One PRNG key per leaf of `tree`'s structure."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, list(keys))


@contextlib.contextmanager
def timed() -> Iterator[dict]:
    """with timed() as t: ...; t['s'] holds elapsed seconds."""
    box = {}
    t0 = time.perf_counter()
    try:
        yield box
    finally:
        box["s"] = time.perf_counter() - t0


def block_until_ready(x: Any) -> Any:
    jax.tree.map(lambda a: a.block_until_ready() if hasattr(a, "block_until_ready") else a, x)
    return x


def config_dict(cfg: Any) -> dict:
    if is_dataclass(cfg):
        return asdict(cfg)
    return dict(cfg)


def memoize_device_fn(obj, key, build):
    """Per-object memo for traceable device predict fns (estimator
    protocol): the engine's program cache is keyed by fn identity, so the
    SAME fn object must come back across calls until `key` changes."""
    if getattr(obj, "_device_fn", None) is None or obj._device_fn_key != key:
        obj._device_fn, obj._device_fn_key = build(), key
    return obj._device_fn


def cost_analysis_dict(compiled) -> dict:
    """`Compiled.cost_analysis()` as a plain dict (empty when the backend
    reports none)."""
    return dict(compiled.cost_analysis() or {})


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pad_to(arr: np.ndarray, n: int, axis: int = 0, value=0) -> np.ndarray:
    pad = n - arr.shape[axis]
    if pad <= 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return np.pad(arr, widths, constant_values=value)
