"""Parameter templates with logical sharding axes.

Every parameter is declared once as a ``ParamSpec`` (shape, dtype, logical
axes, init). The same template drives three consumers:

* ``init_params``      — real initialization (smoke tests, training)
* ``template_shapes``  — ``ShapeDtypeStruct`` stand-ins (multi-pod dry-run)
* ``parallel.sharding.template_pspecs`` — logical axes -> ``PartitionSpec``
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple            # logical axis name (str) or None per dim
    dtype: str = "bfloat16"
    init: str = "normal"   # normal | zeros | ones | small_normal
    scale: float | None = None   # stddev; default fan-in

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def template_shapes(tpl):
    """Template -> pytree of ShapeDtypeStruct (no allocation; dry-run path)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)),
        tpl, is_leaf=is_spec)


def _init_one(spec: ParamSpec, key) -> jax.Array:
    dtype = jnp.dtype(spec.dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "neg_ones":
        return jnp.full(spec.shape, -1, dtype)
    # the scan dim that stack_cycle prepends is not an input of the layer
    dims = [n for n, a in zip(spec.shape, spec.axes) if a != "layers"]
    fan_in = dims[0] if dims else 1
    std = spec.scale if spec.scale is not None else 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)


def init_params(tpl, key):
    """Template -> pytree of initialized arrays (deterministic per-leaf keys)."""
    leaves, treedef = jax.tree.flatten(tpl, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef,
                              [_init_one(s, k) for s, k in zip(leaves, keys)])


def count_params(tpl) -> int:
    return sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(tpl, is_leaf=is_spec))


def stack_cycle(tpl, n_cycles: int):
    """Add a leading scan ('layers') dim to every param in a cycle template."""
    return jax.tree.map(
        lambda s: ParamSpec((n_cycles,) + s.shape, ("layers",) + s.axes,
                            s.dtype, s.init, s.scale),
        tpl, is_leaf=is_spec)


@dataclass
class ParamTree:
    """Convenience bundle: template + metadata."""
    template: dict
    n_params: int = field(init=False)

    def __post_init__(self):
        self.n_params = count_params(self.template)
