"""Initial weights made from the seed by the benchmark, not the program.

The program's layout (leaf names, shapes and dtypes) comes from
``jax.eval_shape`` of its init; the values come from here, in one jitted
call on the device, in the dtype each leaf is served in:

* RMSNorm / LayerNorm ``scale``: ones;
* biases (``b``, ``bq``, ``bk``, ``bv``): zeros;
* the token embedding: N(0, 0.02);
* every other matrix or kernel: a normal truncated at two standard
  deviations, scaled by fan-in ** -0.5 (fan-in: all but the last axis of
  a leaf, after any stacked-layer axis).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_BIASES = ("b", "bq", "bk", "bv")


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _fan_in(path, shape) -> int:
    stacked = any(str(getattr(k, "key", "")) == "layers" for k in path)
    dims = shape[1:-1] if stacked else shape[:-1]
    fan = 1
    for d in dims:
        fan *= d
    return max(fan, 1)


def make(abstract, seed_key):
    """Weights of ``abstract``'s layout (a pytree of ShapeDtypeStruct)
    drawn from ``seed_key``, as one jitted call."""
    paths, tree = jax.tree_util.tree_flatten_with_path(abstract)

    def init(key):
        ks = jax.random.split(key, len(paths))
        out = []
        for k, (path, leaf) in zip(ks, paths):
            name = _leaf_name(path)
            if name == "scale":
                v = jnp.ones(leaf.shape, jnp.float32)
            elif name in _BIASES:
                v = jnp.zeros(leaf.shape, jnp.float32)
            elif name == "embed":
                v = 0.02 * jax.random.normal(k, leaf.shape, jnp.float32)
            else:
                v = (jax.random.truncated_normal(k, -2.0, 2.0, leaf.shape,
                                                 jnp.float32)
                     * _fan_in(path, leaf.shape) ** -0.5)
            out.append(v.astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(init)(seed_key)
