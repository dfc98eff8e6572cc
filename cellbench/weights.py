"""Seeded weights by plain name, from the table that the configuration names.

The benchmark makes the weights, not the program and not the reference:
both are handed the same arrays, made on the device from `--seed`, one
jitted call to a leaf (one compile to a shape), a leaf at a time so that
making them never needs more memory than holding them.

A table is `cellbench/weight_tables/<config["weights"]>.py`: `shapes(config)`
gives name -> (shape, initialiser) in a fixed order (an entry's place is
its index in the key: appending keeps every earlier leaf's draws), and
`SINGLE` names the leaves that are one array. Every other shape leads with a
count and is a list of that many leaves, one a layer of its kind. An
initialiser is a standard deviation (normal x std), None (a norm's scale:
1 + 0.1 x normal, so that a scale left out shows), a name with its arguments,
`("log_uniform", low, high)` or `("constant", value)` (`initialiser`), or a
function `(key, shape) -> float32 array` of the table's own.
"""

from __future__ import annotations

import importlib
import math


def table(config: dict) -> dict:
    """name -> (shape, initialiser) of the configuration's weight table."""
    return _module(config).shapes(config)


def _module(config: dict):
    return importlib.import_module("cellbench.weight_tables." + config["weights"])


def seed_key(seed: int):
    """A key from any whole number: the driver's seeds pass 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def initialiser(entry):
    """`(key, shape) -> float32 array` of a table entry's second element."""
    import jax
    import jax.numpy as jnp

    def normal(key, shape, std):
        return jax.random.normal(key, shape, jnp.float32) * std

    def norm_scale(key, shape):
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)

    def log_uniform(key, shape, low, high):
        # positive, uniform in the logarithm: decay rates, time steps
        return jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(low), math.log(high)))

    def constant(key, shape, value):
        return jnp.full(shape, value, jnp.float32)

    if entry is None:
        return norm_scale
    if callable(entry):
        return entry
    name, *args = entry if isinstance(entry, (tuple, list)) else ("normal", entry)
    named = {"normal": normal, "norm_scale": norm_scale,
             "log_uniform": log_uniform, "constant": constant}[name]
    return lambda key, shape: named(key, shape, *args)


def make(config: dict, seed: int, dtypes: dict | None = None) -> dict:
    """name -> array, or list of one array a layer, on the default device.
    `dtypes` (name -> dtype) gives the type a leaf is kept in; float32 where
    it says nothing."""
    import jax
    import jax.numpy as jnp

    dtypes = dtypes or {}
    root = seed_key(seed)
    single = _module(config).SINGLE
    out = {}
    for index, (name, (shape, entry)) in enumerate(table(config).items()):
        dtype = jnp.dtype(dtypes.get(name, jnp.float32))
        one = shape if name in single else shape[1:]

        @jax.jit
        def leaf(key, shape=one, init=initialiser(entry), dtype=dtype):
            return init(key, shape).astype(dtype)

        key = jax.random.fold_in(root, index)
        out[name] = leaf(key) if name in single else [
            leaf(jax.random.fold_in(key, layer)) for layer in range(shape[0])]
    return out
