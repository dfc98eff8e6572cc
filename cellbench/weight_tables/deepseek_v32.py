"""Weight table of a `deepseek_v32` decoder (reference/deepseek_v32.py has the
equations), for one chip's share: `n_routed_experts_here` of the routed
experts and the file's `vocab_size` rows, over `num_hidden_layers` layers of
which the first `first_k_dense_replace` are dense.

Names: `embedding [V, D]`, `head [D, V]` (untied), `final_norm [D]`; a list
over all layers of `attn_norm`, `ffn_norm [D]`, `q_a [D, r_q]`, `q_norm
[r_q]`, `q_b [r_q, H (d_n + d_r)]`, `kv_a [D, r_kv + d_r]` (`c_raw | k_raw`),
`kv_norm [r_kv]`, `kv_b [r_kv, H (d_n + d_v)]` (per head `k_n | v`), `o [H
d_v, D]`, and the indexer's `index_q [r_q, Hi Di]`, `index_k [D, Di]`,
`index_k_scale`, `index_k_bias [Di]` (its LayerNorm), `index_w [D, Hi]`; a
list over the leading dense layers of `dense_gate`, `dense_up [D, F]`,
`dense_down [F, D]`; a list over the later layers of `router [D, E]`,
`router_bias [E]`, `w_in [held, D, 2 Fe]` (`a | b`), `w_out [held, Fe, D]`,
`shared_in [D, 2 Fs]`, `shared_out [Fs, D]`. Matrices multiply from the right
(`x @ w`).

Every matrix is drawn at 1 / sqrt(fan_in) and then rounded to the nearest
bfloat16 (`lax.reduce_precision`, which the chip's compiler keeps; a round
trip through bfloat16 it takes for the identity), so that the program, which
stores matrices in bfloat16, and the float32 reference hold the same numbers.
Vectors stay float32 in both: norm scales 1 + 0.1 normal, the LayerNorm's
bias 0.1 normal, the router's correction bias 0.02 normal (small beside the
sigmoid scores' spread of 0.2, so that it decides near-ties only, as a
trained balance term does). The embedding is drawn at 0.05, as the other
latent tables draw it: beside rows that narrow, what the first layer's
attention adds is as large as the token's own row, so a cache row lost or
stale shows in the logits.

`q_b` is drawn at half of 1 / sqrt(fan_in). Matrices at 1 / sqrt(fan_in) give
`q_n.k_n + q_r.k_r` a standard deviation of sqrt(192) at the published
widths, and YaRN's `mscale`^2 = 1.87 stays on the softmax scale: scores then
spread by 1.9, a head puts a twentieth of its weight on one key of 2048, and
with five selecting layers in a row a key that the rounding of an index
score moves across the top-2048's edge moves the logits. At half the scores
spread by 0.94 and hundreds of keys share a head, as in a trained model's
broad heads, which are the ones a selection of 2048 keys is for.
"""

from __future__ import annotations

import math

from cellbench.weight_tables.dots3_note import EMBEDDING_STD, fan, rounded

SINGLE = ("embedding", "head", "final_norm")
QUERY_NARROWING = 0.5


def layers_of(sizes: dict):
    """(layers, leading dense layers, expert layers)."""
    layers = sizes["num_hidden_layers"]
    dense = min(sizes["first_k_dense_replace"], layers)
    return layers, dense, layers - dense


def shapes(sizes: dict) -> dict:
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    layers, dense, moe = layers_of(sizes)
    heads = sizes["num_attention_heads"]
    r_q, r_kv = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    d_n, d_r = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    d_v = sizes["v_head_dim"]
    hi, di = sizes["index_n_heads"], sizes["index_head_dim"]
    experts, held = sizes["n_routed_experts"], sizes["n_routed_experts_here"]
    f, fe = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    shared = fe * sizes["n_shared_experts"]
    return {
        "embedding": ((v, d), rounded(EMBEDDING_STD)),
        "head": ((d, v), fan(d)),
        "final_norm": ((d,), None),
        "attn_norm": ((layers, d), None),
        "ffn_norm": ((layers, d), None),
        "q_a": ((layers, d, r_q), fan(d)),
        "q_norm": ((layers, r_q), None),
        "q_b": ((layers, r_q, heads * (d_n + d_r)),
                rounded(QUERY_NARROWING / math.sqrt(r_q))),
        "kv_a": ((layers, d, r_kv + d_r), fan(d)),
        "kv_norm": ((layers, r_kv), None),
        "kv_b": ((layers, r_kv, heads * (d_n + d_v)), fan(r_kv)),
        "o": ((layers, heads * d_v, d), fan(heads * d_v)),
        "index_q": ((layers, r_q, hi * di), fan(r_q)),
        "index_k": ((layers, d, di), fan(d)),
        "index_k_scale": ((layers, di), None),
        "index_k_bias": ((layers, di), 0.1),
        "index_w": ((layers, d, hi), fan(d)),
        "dense_gate": ((dense, d, f), fan(d)),
        "dense_up": ((dense, d, f), fan(d)),
        "dense_down": ((dense, f, d), fan(f)),
        "router": ((moe, d, experts), fan(d)),
        "router_bias": ((moe, experts), 0.02),
        "w_in": ((moe, held, d, 2 * fe), fan(d)),
        "w_out": ((moe, held, fe, d), fan(fe)),
        "shared_in": ((moe, d, 2 * shared), fan(d)),
        "shared_out": ((moe, shared, d), fan(shared)),
    }
