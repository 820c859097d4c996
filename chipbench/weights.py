"""Seeded random weights, made by the benchmark on the device in one jitted
call, in the program's parameter layout and dtype.  The program and the
reference both read these; neither makes its own."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_ONES = ("scale", "q_norm", "k_norm")
_ZEROS = ("bias", "bq", "bk", "bv")
_EMBED = ("embed", "lm_head")


def key_for(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    a, b = np.random.SeedSequence([int(seed) % 2**63, 0]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a)), int(b))


def _leaf(path, shape: jax.ShapeDtypeStruct, key):
    name = str(getattr(path[-1], "key", path[-1]))
    if name in _ONES:
        return jnp.ones(shape.shape, shape.dtype)
    if name in _ZEROS:
        return jnp.zeros(shape.shape, shape.dtype)
    scale = 0.02 if name in _EMBED else 1.0 / np.sqrt(shape.shape[-2])
    return (jax.random.normal(key, shape.shape, jnp.float32) * scale).astype(shape.dtype)


def make(shapes, seed: int, device=None):
    """A pytree like ``shapes`` (ShapeDtypeStructs): norm scales 1, biases 0,
    embedding and head N(0, 0.02), every other matrix N(0, 1/fan_in), where
    fan_in is the next-to-last dimension."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    device = device or jax.devices()[0]

    def build(key):
        leaves = [_leaf(p, s, jax.random.fold_in(key, i)) for i, (p, s) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    sharding = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(build, out_shardings=sharding)(key_for(seed))
