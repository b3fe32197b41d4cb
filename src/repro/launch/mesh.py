"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because only
``dryrun.py`` runs under ``--xla_force_host_platform_device_count=512``;
smoke tests and benches see the host's single real device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ("data","model"). Multi-pod: 2 pods =
    512 chips ("pod","data","model"); the pod axis is the DCI domain."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh, every axis Auto (tests use small fake-device meshes
    like (2,2,2))."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
