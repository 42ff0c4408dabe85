"""Production meshes (assignment spec) + local test meshes.

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state (device count is locked at first jax init; dryrun.py sets
XLA_FLAGS before any import).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (GSPMD propagates shardings
    from the logical constraints in ``sharding/rules.py``)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = ("data", "model") — 256 chips.
    Multi-pod: (2, 16, 16) = ("pod", "data", "model") — 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many local devices exist (tests/examples)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return make_mesh((data, model), ("data", "model"))


def mesh_info(mesh) -> dict:
    return {
        "axis_names": tuple(mesh.axis_names),
        "shape": tuple(mesh.devices.shape),
        "num_devices": int(mesh.devices.size),
    }
