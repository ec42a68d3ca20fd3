"""Production meshes. TPU v5e: 16x16 = 256 chips per pod; 2 pods = 512 chips.

A FUNCTION (not a module constant) so importing this module never touches jax
device state — the dry-run sets XLA_FLAGS before first jax init; tests and
benches see the real single CPU device. Axes are Auto (GSPMD propagation):
the model pins layouts with ``with_sharding_constraint`` on bare
PartitionSpecs, which Explicit axes (``make_mesh``'s default) reject.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Tiny mesh over however many devices exist (CPU tests)."""
    n = len(jax.devices())
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def data_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.devices.shape[mesh.axis_names.index(name)]
