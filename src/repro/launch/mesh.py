"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Production target: TPU v5e, 256 chips/pod, 16x16
(data, model); multi-pod adds a leading "pod" axis for cross-pod DP.

``make_mesh_for(n)`` supports *elastic* restarts: given however many
devices survive, it picks the largest (data, model) grid with model <= 16,
and checkpoint restore reshards into it (see repro.checkpointing).

``make_mesh`` is ``jax.make_mesh`` with ``Auto`` axis types: the sharding
rules in ``repro.distributed`` annotate inputs and outputs and leave the
rest to XLA's propagation, while ``jax.make_mesh`` defaults to
``Explicit`` axes.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, **kwargs):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes), **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for(n_devices: int | None = None, *, max_model: int = 16):
    """Largest (data, model) mesh for an arbitrary device count (elastic)."""
    n = n_devices or len(jax.devices())
    model = math.gcd(n, max_model)
    while model > 1 and n % model:
        model //= 2
    return make_mesh((n // model, model), ("data", "model"))


def describe(mesh) -> str:
    return " x ".join(f"{a}={mesh.shape[a]}" for a in mesh.axis_names)
