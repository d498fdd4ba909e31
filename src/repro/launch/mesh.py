"""Mesh construction — the single mesh entry point.

Every mesh in the codebase (production, CPU smoke, the engine's data
mesh, the ring topology's join mesh, tests) is built through `make_mesh`
here, which pins every axis to `jax.sharding.AxisType.Auto` so sharding
propagation behaves the same whatever default newer JAX releases pick.
The policy is enforced mechanically: xlint's mesh-policy rule
(DESIGN.md §7, §12, `make lint`) rejects raw `jax.sharding.Mesh(...)` /
`jax.make_mesh` calls, `AxisType` access, and `axis_types=` kwargs
everywhere but here.

Functions, not module constants — importing this module never touches jax
device state.
"""
from __future__ import annotations

from typing import Sequence

import jax
import numpy as np


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None) -> jax.sharding.Mesh:
    """Build a mesh with every axis in `AxisType.Auto` mode.

    shape/axes as for `jax.make_mesh`.  Pass `devices` (flat sequence, length
    prod(shape)) to pin an explicit device order (elastic rebuilds); otherwise
    jax picks a performant order over all local devices.
    """
    axis_types = (jax.sharding.AxisType.Auto,) * len(axes)
    if devices is not None:
        devs = np.asarray(devices).reshape(tuple(shape))
        return jax.sharding.Mesh(devs, tuple(axes), axis_types=axis_types)
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=axis_types)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_cpu_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever host devices exist (tests / driver)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return make_mesh((data, model), ("data", "model"))


def make_data_mesh(n: int | None = None) -> jax.sharding.Mesh:
    """1-D `("data",)` mesh over the first n local devices (join engine,
    replicated topology)."""
    devs = jax.devices()
    n = len(devs) if n is None else min(n, len(devs))
    return make_mesh((n,), ("data",), devices=devs[:n])


def make_join_mesh(data: int | None = None, r: int = 1) -> jax.sharding.Mesh:
    """2-D `("r", "data")` join-engine mesh (DESIGN.md §10).

    `r` is the index-sharding axis (R row-shards over it under
    `topology="ring"`; per-device R memory drops by this factor), `data`
    the query-sharding axis; `data=None` spreads the remaining devices
    (len(devices) // r). Built through the mandatory `make_mesh`. Raises
    ValueError when the local device count cannot host the requested
    shape — at build time, not inside a sweep."""
    devs = jax.devices()
    if r < 1:
        raise ValueError(f"make_join_mesh(r={r}): r must be >= 1")
    if len(devs) < r:
        raise ValueError(
            f"make_join_mesh(r={r}): only {len(devs)} local device(s); the "
            "r axis cannot exceed the device count")
    if data is None:
        data = len(devs) // r
    n = r * data
    if data < 1 or n > len(devs):
        raise ValueError(
            f"make_join_mesh(data={data}, r={r}): needs {n} devices, have "
            f"{len(devs)}")
    return make_mesh((r, data), ("r", "data"), devices=devs[:n])
