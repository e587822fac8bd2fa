"""Weights from the seed, made on the device in one jitted call.

The benchmark makes its own weights, in the program's param layout (taken
from ``jax.eval_shape`` of the program's init: shapes and dtypes only), so
the reference can make the same values again from the seed without taking
anything the program made.  Every leaf has its own key, folded from the
seed and the leaf's index, so one leaf can be made again alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any seed below 2**64 (the low and high 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_paths(tree) -> list:
    """'/'-joined key paths of the leaves, in ``tree_leaves`` order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def leaf_value(key: jax.Array, index: int, path: str, shape, dtype):
    """The value of one leaf: norm scales 1, biases 0, the embedding
    N(0, 0.01^2), every other matrix N(0, 1/fan_in) with fan_in its
    second-to-last dimension (the program's own init scales)."""
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name in ("bias", "gate"):
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, index)
    if name == "embedding":
        return (jax.random.normal(k, shape, jnp.float32) * 0.01).astype(dtype)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = 1.0 / np.sqrt(max(int(fan_in), 1))
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


def make_params(abstract, seed: int, sharding=None):
    """All leaves of ``abstract`` (a tree of ShapeDtypeStruct) in one
    jitted call."""
    paths = leaf_paths(abstract)
    leaves, treedef = jax.tree_util.tree_flatten(abstract)
    specs = tuple((tuple(l.shape), jnp.dtype(l.dtype)) for l in leaves)
    fn = _maker(tuple(paths), specs, sharding)
    return jax.tree_util.tree_unflatten(treedef, fn(seed_key(seed)))


@functools.lru_cache(maxsize=None)
def _maker(paths, specs, sharding):
    def make(key):
        return [leaf_value(key, i, p, s, d)
                for i, (p, (s, d)) in enumerate(zip(paths, specs))]
    return jax.jit(make, out_shardings=sharding)


def leaf_index(abstract) -> dict:
    """path -> (index, shape, dtype) of every leaf."""
    leaves = jax.tree_util.tree_leaves(abstract)
    return {p: (i, tuple(l.shape), jnp.dtype(l.dtype))
            for i, (p, l) in enumerate(zip(leaf_paths(abstract), leaves))}
