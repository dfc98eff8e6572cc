"""Weight table of a `LongCat-Flash` decoder's language model
(reference/longcat_flash.py has the equations), for one chip's share:
`n_routed_experts_here` of the routed experts and the file's `vocab_size`
rows, over `num_layers` layers.

Names: `embedding [V, D]`, `head [D, V]` (untied), `final_norm [D]`; lists
two a layer, the first sublayer's then the second's (`2 l`, `2 l + 1`), of
`attn_norm`, `ffn_norm [D]`, the attention's `q_a [D, r_q]`, `q_norm [r_q]`,
`q_b [r_q, H (d_n + d_r)]`, `kv_a [D, r_kv + d_r]` (`c_raw | k_raw`),
`kv_norm [r_kv]`, `kv_b [r_kv, H (d_n + d_v)]` (per head `k_n | v`),
`o [H d_v, D]`, and the dense feed-forward's `dense_gate`, `dense_up [D, F]`,
`dense_down [F, D]`; lists one a layer of `router [D, E + Z]` (the routed
experts' outputs, then the zero-compute experts'), `router_bias [E + Z]`,
`w_in [held, D, 2 Fe]` (`a | b`), `w_out [held, Fe, D]`. Matrices multiply
from the right (`x @ w`).

Every matrix is drawn at 1 / sqrt(fan_in) and rounded to a value bfloat16
holds (`weight_tables/dots3_note.py`'s `rounded`: `lax.reduce_precision`,
which the chip's compiler keeps), so that the program, which stores
matrices in bfloat16, and the float32 reference hold the same numbers.
Vectors stay float32 in both: norm scales 1 + 0.1 normal. The embedding is
drawn at 0.05 and `q_b` at a sixth of 1 / sqrt(fan_in), for that table's
reasons: with both latents rescaled (c_q at 2, c at sqrt(12)) matrices at
1 / sqrt(fan_in) give attention scores a standard deviation of 5.7, and a
softmax of random weights then picks one key of thousands; at a sixth
hundreds of keys share a head, as in a trained model's broad heads.

The router is drawn at 1.5 / sqrt(fan_in). Its softmax runs over all 768
outputs, and at 1 / sqrt(fan_in) the twelve chosen hold 0.12 of it between
them: six times that, the whole branch, would be a fifth of what one dense
feed-forward adds to the stream, and a lost expert or a lost identity term
would hide under the check's limits. At 1.5 the chosen gates times 6 sum to
1.5 (the largest 0.34, the twelfth 0.07): the identity term is 0.5 u on
average and the branch is as large in the stream as a dense feed-forward's
output (0.6 an element). The correction bias is drawn at 0.0004, a tenth of
the spread of `p` (0.0036), so that it decides near-ties only, as a trained
balance term does.
"""

from __future__ import annotations

import math

from cellbench.weight_tables.dots3_note import (
    EMBEDDING_STD,
    QUERY_NARROWING,
    fan,
    rounded,
)

SINGLE = ("embedding", "head", "final_norm")
ROUTER_WIDENING = 1.5
ROUTER_BIAS_STD = 0.0004


def shapes(sizes: dict) -> dict:
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    layers = sizes["num_layers"]
    heads = sizes["num_attention_heads"]
    r_q, r_kv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    d_n, d_r = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    d_v = sizes["v_head_dim"]
    f, fe = sizes["ffn_hidden_size"], sizes["expert_ffn_hidden_size"]
    outputs = sizes["n_routed_experts"] + sizes["zero_expert_num"]
    held = sizes["n_routed_experts_here"]
    two = 2 * layers
    return {
        "embedding": ((v, d), rounded(EMBEDDING_STD)),
        "head": ((d, v), fan(d)),
        "final_norm": ((d,), None),
        "attn_norm": ((two, d), None),
        "ffn_norm": ((two, d), None),
        "q_a": ((two, d, r_q), fan(d)),
        "q_norm": ((two, r_q), None),
        "q_b": ((two, r_q, heads * (d_n + d_r)),
                rounded(QUERY_NARROWING / math.sqrt(r_q))),
        "kv_a": ((two, d, r_kv + d_r), fan(d)),
        "kv_norm": ((two, r_kv), None),
        "kv_b": ((two, r_kv, heads * (d_n + d_v)), fan(r_kv)),
        "o": ((two, heads * d_v, d), fan(heads * d_v)),
        "dense_gate": ((two, d, f), fan(d)),
        "dense_up": ((two, d, f), fan(d)),
        "dense_down": ((two, f, d), fan(f)),
        "router": ((layers, d, outputs),
                   rounded(ROUTER_WIDENING / math.sqrt(d))),
        "router_bias": ((layers, outputs), ROUTER_BIAS_STD),
        "w_in": ((layers, held, d, 2 * fe), fan(d)),
        "w_out": ((layers, held, fe, d), fan(fe)),
    }
