"""Seeded weights of a Llama-shaped decoder, by plain name.

The benchmark makes the weights, not the program and not the reference:
both are handed the same arrays, made on the device from `--seed`, one
jitted call to a leaf (one compile to a shape), a layer at a time so that
making them never needs more memory than holding them.

Names: `embedding [V, D]`, `lm_head [D, V]`, `final_norm [D]`, and a list over
layers of `attn_norm`, `mlp_norm [D]`, `wq [D, H*hd]`, `wk`, `wv [D, KV*hd]`,
`wo [H*hd, D]`, `w_gate`, `w_up [D, F]`, `w_down [F, D]`. Matrices multiply
from the right (`x @ w`).
"""

from __future__ import annotations

import math


def shapes(sizes: dict) -> dict:
    """name -> (shape, standard deviation; None for a norm's scale). A shape
    that leads with the number of layers is a list of that many leaves."""
    d, f, v = sizes["hidden_size"], sizes["intermediate_size"], sizes["vocab_size"]
    layers = sizes["num_hidden_layers"]
    q = sizes["num_attention_heads"] * sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]

    def fan(n):
        return 1.0 / math.sqrt(n)

    return {
        "embedding": ((v, d), 0.02),
        "lm_head": ((d, v), 0.02),
        "final_norm": ((d,), None),
        "attn_norm": ((layers, d), None),
        "mlp_norm": ((layers, d), None),
        "wq": ((layers, d, q), fan(d)),
        "wk": ((layers, d, kv), fan(d)),
        "wv": ((layers, d, kv), fan(d)),
        "wo": ((layers, q, d), fan(q)),
        "w_gate": ((layers, d, f), fan(d)),
        "w_up": ((layers, d, f), fan(d)),
        "w_down": ((layers, f, d), fan(f)),
    }


def seed_key(seed: int):
    """A key from any whole number: the driver's seeds pass 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def make(sizes: dict, seed: int, dtypes: dict | None = None) -> dict:
    """name -> array, or list of one array a layer, on the default device.
    `dtypes` (name -> dtype) gives the type a leaf is kept in; float32 where
    it says nothing. Norm scales are 1 + 0.1 * normal, so that a scale left
    out shows."""
    import jax
    import jax.numpy as jnp

    dtypes = dtypes or {}
    root = seed_key(seed)
    out = {}
    single = ("final_norm", "embedding", "lm_head")
    for index, (name, (shape, std)) in enumerate(shapes(sizes).items()):
        dtype = jnp.dtype(dtypes.get(name, jnp.float32))
        one = shape if name in single else shape[1:]

        @jax.jit
        def leaf(key, shape=one, std=std, dtype=dtype):
            x = jax.random.normal(key, shape, jnp.float32)
            x = 1.0 + 0.1 * x if std is None else x * std
            return x.astype(dtype)

        key = jax.random.fold_in(root, index)
        out[name] = leaf(key) if name in single else [
            leaf(jax.random.fold_in(key, layer)) for layer in range(shape[0])]
    return out
