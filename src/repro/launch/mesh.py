"""Device meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state; launch/dryrun.py sets XLA_FLAGS *before* any jax import.

Every mesh has ``AxisType.Auto`` axes: the model code shards by
``with_sharding_constraint`` and GSPMD propagation, which Explicit axes
(``jax.make_mesh``'s default) would turn into sharding-type errors.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod (dry-run)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_device_mesh(devices: Sequence | None = None):
    """(data, model) mesh over the devices present: 1x1 on one chip, 2x2 on
    four (model parallelism of 2 wherever the count is a multiple of 4)."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    model = 2 if n % 4 == 0 else 1
    return _auto_mesh((n // model, model), ("data", "model"), devices)


def make_data_mesh(devices: Sequence | None = None):
    """1-d ``data`` mesh: one curve segment of the cuboid array per device."""
    devices = list(jax.devices() if devices is None else devices)
    return _auto_mesh((len(devices),), ("data",), devices)
