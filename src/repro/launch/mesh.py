"""Production meshes.

``make_production_mesh`` is a FUNCTION (module import never touches jax
device state).  Single pod = 16x16 = 256 chips over ("data", "model");
multi-pod = 2x16x16 = 512 chips with a leading pure-DP "pod" axis whose
gradient all-reduce is the only traffic crossing the pod boundary.
Every mesh here is fully ``Auto``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh() -> Mesh:
    """1x1 mesh on the local device (CPU smoke tests / examples)."""
    return _make_mesh((1, 1), ("data", "model"))


def make_mesh_for(n_devices: int) -> Mesh:
    """Largest (data, model) mesh that fits n_devices (elastic re-slice)."""
    model = 1
    for m in (16, 8, 4, 2, 1):
        if n_devices % m == 0:
            model = m
            break
    return _make_mesh((n_devices // model, model), ("data", "model"))
