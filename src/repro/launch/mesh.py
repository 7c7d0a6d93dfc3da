"""Production meshes.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16) — the ``pod``
axis is pure data parallelism whose gradient all-reduce crosses DCN once per
step; everything else stays inside a pod's ICI.

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).

Every mesh axis is ``Auto``: the model places activations with
``with_sharding_constraint`` hints and lets the partitioner propagate the
rest. (``jax.make_mesh`` defaults to ``Explicit`` axes, under which such a
constraint is an assertion instead of a hint.)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple, *, devices=None):
    """A mesh with ``Auto`` axes over ``devices`` (default: this host's)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def pod_size(mesh) -> int | None:
    """Devices per pod (for DCN/ICI classification); None if single pod."""
    if "pod" in mesh.axis_names:
        i = mesh.axis_names.index("pod")
        per_pod = mesh.devices.size // mesh.devices.shape[i]
        return per_pod
    return None
