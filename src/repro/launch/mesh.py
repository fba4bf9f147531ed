"""Production mesh construction.

A FUNCTION (not a module constant) so importing this module never
touches jax device state.  Single pod: (16, 16) = 256 chips,
("data", "model").  Multi-pod: (2, 16, 16) = 512 chips,
("pod", "data", "model") — the pod axis is pure data-parallel (gradient
all-reduce crosses pods once per step) and can host pipeline stages via
distributed/pipeline.py.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def mesh_from_devices(devices, axis_names: Sequence[str]) -> Mesh:
    """``Mesh`` over an explicit (nested) device array; every axis is
    ``AxisType.Auto`` (GSPMD propagates the logical-axis sharding
    rules of ``repro.distributed.sharding``)."""
    return Mesh(np.asarray(devices), tuple(axis_names),
                axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        # jax.make_mesh defaults to Explicit axes; this repo's sharding
        # rules need Auto (GSPMD) axes
        return jax.make_mesh(shape, axes,
                             axis_types=(AxisType.Auto,) * len(axes))
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} (see launch/dryrun.py)")
    # more devices than needed (e.g. 512 placeholders, single-pod mesh):
    # take a prefix so both meshes work in one process.
    return mesh_from_devices(np.asarray(devices[:n]).reshape(shape), axes)


def make_host_mesh(model: int = 1) -> Mesh:
    """Small mesh over however many (CPU) devices exist — tests."""
    n = len(jax.devices())
    data = n // model
    dev = np.asarray(jax.devices()[:data * model]).reshape(data, model)
    return mesh_from_devices(dev, ("data", "model"))
