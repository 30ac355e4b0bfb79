"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; everything else (tests, benches) sees the real single CPU device.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The target v5e topology: one pod = 16x16 (data, model); two pods add
    a leading "pod" axis used as an outer data-parallel dimension."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model: int = 1) -> Mesh:
    """Whatever this host has (tests/examples): (data, model) with model=|model|."""
    devs = np.array(jax.devices())
    n = devs.size
    assert n % model == 0, (n, model)
    return Mesh(devs.reshape(n // model, model), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def make_worker_mesh(num_workers: int | None = None) -> Mesh:
    """1-D mesh for the ASYMP graph engine (the `workers` axis) over the
    first ``num_workers`` devices (all of them by default)."""
    devs = np.array(jax.devices())
    n = num_workers or devs.size
    if n > devs.size:
        raise ValueError(f"a mesh of {n} workers, but JAX found "
                         f"{devs.size} device(s)")
    return Mesh(devs[:n], ("workers",))
