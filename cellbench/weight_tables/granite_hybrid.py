"""Weight table of a `granitemoehybrid` decoder (reference/granite_hybrid.py
has the equations), for one chip's share: `num_local_experts_here` of the
experts and the file's `vocab_size` rows, over the first `num_hidden_layers`
of the published `layer_types`.

Names: `embedding [V, D]` (the head is its transpose), `final_norm [D]`; a
list over all layers of `mixer_norm`, `moe_norm [D]`, `router [D, E]`,
`w_in [held, D, 2F]`, `w_out [held, F, D]`, `shared_in [D, 2Fs]`,
`shared_out [Fs, D]`; a list over the mamba layers of `in_proj [D, 2I + 2N +
H]` (`z | x B C | dt`), `conv_w [K, I + 2N]` (tap K-1 on the current
position), `conv_b`, `A_log`, `D`, `dt_bias [H]`, `gate_norm [I]`,
`out_proj [I, D]`; a list over the attention layers of `wq`, `wk`, `wv`,
`wo`. Matrices multiply from the right (`x @ w`).

The embedding is narrow on purpose. The head is tied to it and the input is
12 x the token's row, so a token's own logit leads the others' by `12 |E|^2`
over the hidden state's norm: with random rows any wider than this every
token predicts itself, whatever the layers add, and no precision could fail
the comparison. At `sqrt(0.1 layers / hidden) / 6` (0.0026 at the published
width and ten layers) that lead is two standard deviations of the other
logits, and what the layers compute decides the served token.

Every matrix is drawn and then rounded to the nearest bfloat16, so that the
program, which stores matrices in bfloat16, and the float32 reference hold
the same numbers. Vectors stay float32 in both.
"""

from __future__ import annotations

import math

SINGLE = ("embedding", "final_norm")


def rounded(std):
    """normal x std, rounded to a value bfloat16 holds."""

    def draw(key, shape):
        import jax
        import jax.numpy as jnp

        value = jax.random.normal(key, shape, jnp.float32) * std
        return value.astype(jnp.bfloat16).astype(jnp.float32)

    return draw


def log_decay_rate(low, high):
    """`A_log = log(a)`, a log-uniform in [low, high]: the state decays by
    `exp(-dt a)` a position (Mamba-2's own initialisation)."""

    def draw(key, shape):
        import jax
        import jax.numpy as jnp

        return jax.random.uniform(key, shape, jnp.float32,
                                  math.log(low), math.log(high))

    return draw


def time_step_bias(low, high):
    """`dt_bias` such that `softplus(dt_bias)` is log-uniform in [low, high]
    (Mamba-2's own initialisation): time steps short enough that the state
    remembers hundreds of positions, so that a state lost or stale between
    two calls shows in the comparison."""

    def draw(key, shape):
        import jax
        import jax.numpy as jnp

        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                          math.log(low), math.log(high)))
        return step + jnp.log(-jnp.expm1(-step))  # softplus's inverse

    return draw


def shapes(sizes: dict) -> dict:
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    kinds = sizes["layer_types"][:sizes["num_hidden_layers"]]
    layers, mamba, attn = len(kinds), kinds.count("mamba"), kinds.count("attention")
    heads, d_state = sizes["mamba_n_heads"], sizes["mamba_d_state"]
    inner = heads * sizes["mamba_d_head"]
    width, taps = inner + 2 * sizes["mamba_n_groups"] * d_state, sizes["mamba_d_conv"]
    experts, held = sizes["num_local_experts"], sizes["num_local_experts_here"]
    f, shared = sizes["intermediate_size"], sizes["shared_intermediate_size"]
    q = sizes["num_attention_heads"] * (d // sizes["num_attention_heads"])
    kv = sizes["num_key_value_heads"] * (d // sizes["num_attention_heads"])

    def fan(n):
        return rounded(1.0 / math.sqrt(n))

    return {
        "embedding": ((v, d), rounded(math.sqrt(0.1 * layers / d) / 6)),
        "final_norm": ((d,), None),
        "mixer_norm": ((layers, d), None),
        "moe_norm": ((layers, d), None),
        "router": ((layers, d, experts), fan(d)),
        "w_in": ((layers, held, d, 2 * f), fan(d)),
        "w_out": ((layers, held, f, d), fan(f)),
        "shared_in": ((layers, d, 2 * shared), fan(d)),
        "shared_out": ((layers, shared, d), fan(shared)),
        "in_proj": ((mamba, d, inner + width + heads), fan(d)),
        "conv_w": ((mamba, taps, width), 1.0 / math.sqrt(taps)),
        "conv_b": ((mamba, width), 0.1),
        "A_log": ((mamba, heads), log_decay_rate(1.0, 16.0)),
        "D": ((mamba, heads), ("constant", 1.0)),
        "dt_bias": ((mamba, heads), time_step_bias(0.001, 0.1)),
        "gate_norm": ((mamba, inner), None),
        "out_proj": ((mamba, inner, d), fan(inner)),
        "wq": ((attn, d, q), fan(d)),
        "wk": ((attn, d, kv), fan(d)),
        "wv": ((attn, d, kv), fan(d)),
        "wo": ((attn, q, d), fan(q)),
    }
