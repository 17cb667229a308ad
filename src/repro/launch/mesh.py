"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (one TPU v5e pod slice).
Multi-pod: (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is a
pure data-parallel axis whose gradient reduction crosses the inter-pod
link (where int8-EF gradient compression applies, repro.train.compress).

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(*, model: int | None = None):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    model = model or 1
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def dp_axes(mesh) -> tuple[str, ...]:
    """The pure data-parallel axes of a mesh (everything but 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def dp_size(mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in dp_axes(mesh)]))


def model_size(mesh) -> int:
    return int(mesh.shape.get("model", 1))
