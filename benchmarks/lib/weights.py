"""Weights on the device, from the seed, without a random-number op.

The families hand in the tree of shapes (``jax.eval_shape(model.init, ..)``
-- no ``model.init`` runs: its per-shape helper programs keep adding
compile-cache entries run after run and would make float32 for a model
served in bfloat16).  Leaves are told apart by their path: ``scale`` is
ones, ``bias`` zeros, an ``embed`` table normal(0.02), every other leaf
normal with deviation 1/sqrt(fan_in).

A leaf's values are a hash of (salt, index): two rounds of a 32-bit
integer mixer give four 16-bit uniforms whose sum is the "normal".  One
small jitted program a distinct leaf shape, called once a leaf with the
leaf's salt: on the v5e one jitted call for a whole tree (390 leaves of
BERT-Large) compiled for 75 s with jax's ``rbg`` generator and 23 s with
threefry, and the program never came back from the persistent cache
(PERF.md section 6); a leaf's program compiles in well under a second.
The same seed gives the same bits on every device and runtime.
"""

import functools
import math

import jax
import jax.numpy as jnp


def leaf_salt(seed: int, index: int) -> int:
    """32 bits for (seed, leaf index), any whole-number seed."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    x = (seed * 0x9E3779B97F4A7C15 + (index + 1) * 0xBF58476D1CE4E5B9) \
        & 0xFFFFFFFFFFFFFFFF
    x ^= x >> 31
    x = (x * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    x ^= x >> 29
    return int(x & 0xFFFFFFFF)


def _mix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def hash_normal(salt, shape):
    """Traceable: float32 ``shape`` of mean 0, deviation 1, from the
    uint32 scalar ``salt``."""
    n = math.prod(shape)
    if n >= 2 ** 32:
        raise ValueError(f"leaf of {n} elements needs a wider index")
    idx = jax.lax.iota(jnp.uint32, n).reshape(shape)
    h1 = _mix(idx ^ salt)
    h2 = _mix(idx ^ (salt * jnp.uint32(0x85EBCA6B) + jnp.uint32(1)))
    total = ((h1 & 0xFFFF) + (h1 >> 16) + (h2 & 0xFFFF) + (h2 >> 16))
    return ((total.astype(jnp.float32) - 2.0 * 65535.0)
            * (math.sqrt(3.0) / 65536.0))


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def leaf_kind(path: str) -> str:
    """What decides a leaf's values: the last part of its path."""
    name = path.rsplit("/", 1)[-1]
    if name in ("scale", "bias"):
        return name
    return "embed" if "embed" in name else "kernel"


def leaf_values(salt, kind: str, shape, dtype):
    """Traceable: one leaf's seeded values, ``kind`` from ``leaf_kind``."""
    if kind == "scale":
        return jnp.ones(shape, dtype)
    if kind == "bias":
        return jnp.zeros(shape, dtype)
    std = 0.02 if kind == "embed" else 1.0 / math.sqrt(shape[0])
    return (hash_normal(salt, shape) * std).astype(dtype)


@functools.lru_cache(maxsize=None)
def _generator(kind: str, shape, dtype, sharding):
    return jax.jit(lambda salt: leaf_values(salt, kind, shape, dtype),
                   out_shardings=sharding)


def make_weights(seed: int, shapes, dtype, sharding=None):
    """The tree on the device(s): one small program a distinct shape."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    dtype = jnp.dtype(dtype)
    leaves = []
    for i, (path, s) in enumerate(flat):
        leaves.append(_generator(leaf_kind(path_name(path)), tuple(s.shape),
                                 dtype, sharding)(
            jnp.uint32(leaf_salt(seed, i))))
    return jax.tree_util.tree_unflatten(treedef, leaves)
