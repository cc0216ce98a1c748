"""Mesh construction: every mesh in the repository is built here.

All axes are ``AxisType.Auto``: the model and comms code place data with
``PartitionSpec``s and leave propagation to GSPMD (``jax.make_mesh``
would otherwise default to Explicit axes).  Meshes are made by
functions, never held in module-level constants, so importing this
module touches no jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before jax init.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """A mesh of ``shape`` over ``axes`` with Auto axis types, over
    ``devices`` (default: all of ``jax.devices()``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production mesh: 16x16 chips per pod; 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over however many (virtual) devices exist — tests and
    CPU examples."""
    n = len(jax.devices())
    need = max(1, data) * max(1, model) * max(1, pod or 1)
    assert need <= n, f"need {need} devices, have {n}"
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def mesh_for_devices(n: int, prefer_model: int = 0):
    """Factor ``n`` devices into a (data, model) mesh."""
    model = prefer_model or int(np.gcd(n, 16))
    while n % model:
        model //= 2
    return make_mesh((n // model, model), ("data", "model"))
