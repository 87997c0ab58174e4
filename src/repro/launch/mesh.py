"""Mesh construction for single-pod (16x16 = 256 chips) and multi-pod
(2 pods x 256 = 512 chips) deployments.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — crucial because ``dryrun.py`` must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # the models place shardings through with_sharding_constraint (GSPMD),
    # so every axis is Auto; jax.make_mesh defaults to Explicit axes
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Mesh over the first ``data * model`` local devices."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _mesh((data, model), ("data", "model"))
