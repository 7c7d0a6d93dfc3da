"""Random weights from the seed, made on the device in one jitted call.

The tree's structure, shapes and dtypes are the program's (its parameter
template); the values are the benchmark's: each leaf drawn from its own
key split from the seed, matrices N(0, 1/fan_in) over the first dim that
is not the scan's layer dim, or N(0, scale) where the template states a
scale, and constants where it states zeros or ones. The reference makes
the same tree from the same seed, so it shares no array with the program.
"""

from __future__ import annotations

import math


def prng_key(seed: int):
    """A key for any whole number, 64-bit seeds included."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _leaf(spec, key):
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(spec.dtype)
    if spec.init in ("zeros", "ones", "neg_ones"):
        fill = {"zeros": 0, "ones": 1, "neg_ones": -1}[spec.init]
        return jnp.full(spec.shape, fill, dtype)
    dims = [n for n, a in zip(spec.shape, spec.axes) if a != "layers"]
    std = spec.scale if spec.scale is not None else \
        1.0 / math.sqrt(max(dims[0] if dims else 1, 1))
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)


def maker(template):
    """A jitted ``seed_key -> params`` for the program's parameter template."""
    import jax
    from repro.models.param import is_spec
    specs, treedef = jax.tree.flatten(template, is_leaf=is_spec)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(specs))
        return jax.tree.unflatten(treedef,
                                  [_leaf(s, k) for s, k in zip(specs, keys)])
    return make


def make_params(template, seed: int):
    return maker(template)(prng_key(seed))
