"""Production mesh builders. FUNCTIONS, not module constants — importing this
module never touches jax device state (jax locks the device count on first
backend init, and only launch/dryrun.py may force 512 host devices)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """`jax.make_mesh` with Auto axes: its default (Explicit) axes reject
    the `with_sharding_constraint` calls `dist.constrain` makes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (data, model); multi_pod prepends a 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Whatever this host has — used by tests/examples (usually 1 device)."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))


def mesh_chip_count(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
