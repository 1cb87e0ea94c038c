"""Production mesh builders. Functions (not module constants) so importing
never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules here
    (``with_sharding_constraint``, ``NamedSharding`` in/out shardings) are
    written for Auto axes, and ``jax.make_mesh`` defaults to Explicit."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: one pod = 16x16 = 256 chips; multi-pod = 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, *, pod: int = 0):
    """Small mesh for subprocess integration tests (host platform devices)."""
    if pod:
        return auto_mesh((pod, data, model), ("pod", "data", "model"))
    return auto_mesh((data, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh ('pod' folds into data parallelism)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_size(mesh) -> int:
    out = 1
    for a in dp_axes(mesh):
        out *= mesh.shape[a]
    return out
