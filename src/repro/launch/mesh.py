"""Production mesh factory.

Defined as a FUNCTION (never a module-level constant) so importing this
module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and smoke tests / benches must keep seeing the single real device.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_from_shape(shape, axes)


def make_mesh_from_shape(shape: Tuple[int, ...],
                         axes: Tuple[str, ...]) -> Mesh:
    """A mesh over the first ``prod(shape)`` devices (e.g. (1, 1) on a
    CPU host, or one pod under the 512-device dry-run flag).  Every axis
    is ``Auto``: the sharding rules here are GSPMD layouts, not the
    sharding-in-types that ``jax.make_mesh`` defaults to."""
    auto = (AxisType.Auto,) * len(axes)
    try:
        return jax.make_mesh(shape, axes, axis_types=auto)
    except ValueError:
        # jax.make_mesh requires len(devices) == prod(shape)
        n = int(np.prod(shape))
        devs = np.asarray(jax.devices()[:n]).reshape(shape)
        return Mesh(devs, axes, axis_types=auto)


def single_device_mesh(axes: Tuple[str, ...] = ("data", "model")) -> Mesh:
    return make_mesh_from_shape((1,) * len(axes), axes)


def serving_mesh(spec: str) -> Mesh:
    """Parse a ``DxM`` serving-mesh flag ("1x4", "2x2") into a
    (data, model) mesh for :class:`repro.serving.engine.ServingEngine`.
    Needs D*M visible devices — on CPU hosts that means
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` exported
    BEFORE the first jax import (the CI multidevice lane does this)."""
    try:
        d, m = (int(x) for x in spec.lower().split("x"))
    except ValueError:
        raise ValueError(
            f"mesh spec must be DxM (e.g. 1x4, 2x2), got {spec!r}"
        ) from None
    n = d * m
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"mesh {spec} needs {n} devices but jax sees "
            f"{len(jax.devices())}; export XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before any jax "
            "import")
    return make_mesh_from_shape((d, m), ("data", "model"))
