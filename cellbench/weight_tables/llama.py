"""Weight table of a Llama-shaped decoder.

Names: `embedding [V, D]`, `lm_head [D, V]`, `final_norm [D]`, and a list over
layers of `attn_norm`, `mlp_norm [D]`, `wq [D, H*hd]`, `wk`, `wv [D, KV*hd]`,
`wo [H*hd, D]`, `w_gate`, `w_up [D, F]`, `w_down [F, D]`. Matrices multiply
from the right (`x @ w`).
"""

from __future__ import annotations

import math

SINGLE = ("final_norm", "embedding", "lm_head")


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
