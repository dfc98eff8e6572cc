"""Weight table of a `dots3_note` decoder's language model
(reference/dots3_note.py has the equations), for one chip's share:
`n_routed_experts_here` of the routed experts and the file's `vocab_size`
rows, over the first `num_hidden_layers` of the published `layer_types`.

Names: `embedding [V, D]`, `head [D, V]` (untied), `final_norm [D]`; a list
over all layers of `attn_norm`, `ffn_norm [D]`; a list over the
`full_attention` layers of `q_a [D, r_q]`, `q_norm [r_q]`, `q_b [r_q, H (d_n +
d_r)]`, `kv_a [D, r_kv + d_r]` (`c_raw | k_raw`), `kv_norm [r_kv]`, `kv_b
[r_kv, H (d_n + d_v)]` (per head `k_n | v`), `gate [D, H]`, `o [H d_v, D]`,
and the indexer's `index_q [r_q, Hi Di]`, `index_k [D, Di]`, `index_k_scale`,
`index_k_bias [Di]` (its LayerNorm), `index_w [D, Hi]`; a list over the
`sliding_attention` layers of the same eight under `swa_`, at that kind's
sizes; a list over the layers below `first_k_dense_replace` of `dense_gate`,
`dense_up [D, F]`, `dense_down [F, D]`; a list over the later layers of
`router [D, E]`, `router_bias [E]`, `w_in [held, D, 2 Fe]` (`a | b`),
`w_out [held, Fe, D]`, `shared_in [D, 2 Fe]`, `shared_out [Fe, D]`. Matrices
multiply from the right (`x @ w`).

Every matrix is drawn at 1 / sqrt(fan_in) and then rounded to the nearest
bfloat16, so that the program, which stores matrices in bfloat16, and the
float32 reference hold the same numbers. Vectors stay float32 in both: norm
scales 1 + 0.1 normal, the LayerNorm's bias 0.1 normal, the router's
correction bias 0.02 normal (small beside the sigmoid scores' spread of 0.2,
so that it decides near-ties only, as a trained balance term does). The
embedding is drawn at 0.05: beside rows that narrow what the first layer's
attention adds is as large as the token's own row, so a cache row lost or
stale shows in the logits.

`q_b` is drawn at a sixth of 1 / sqrt(fan_in). With both latents rescaled
(`apply_mla_qkv_lora_rescale`: c_q at sqrt(5), c at sqrt(10)), matrices at
1 / sqrt(fan_in) give attention scores a standard deviation of 5.8 (full) and
4.3 (sliding) at the published widths: the softmax of random weights then
picks one key of thousands, every rounding flips whole heads from one key to
another, and no precision can be told from another (on the chip: the program
in bfloat16 0.29 mean gap, the reference in int8 1.15, 45 % first choices).
At a sixth the scores spread by 1.0 and 0.7 and hundreds of keys share a
head, as in a trained model's broad heads.
"""

from __future__ import annotations

import math

SINGLE = ("embedding", "head", "final_norm")
EMBEDDING_STD = 0.05
QUERY_NARROWING = 1.0 / 6


def rounded(std):
    """normal x std, rounded to a value bfloat16 holds."""

    def draw(key, shape):
        import jax
        import jax.numpy as jnp

        value = jax.random.normal(key, shape, jnp.float32) * std
        return value.astype(jnp.bfloat16).astype(jnp.float32)

    return draw


def fan(n):
    return rounded(1.0 / math.sqrt(n))


def attention_shapes(sizes: dict, pre: str, count: int) -> dict:
    d = sizes["hidden_size"]
    heads = sizes[pre + "num_attention_heads"]
    r_q, r_kv = sizes[pre + "q_lora_rank"], sizes[pre + "kv_lora_rank"]
    d_n, d_r = sizes[pre + "qk_nope_head_dim"], sizes[pre + "qk_rope_head_dim"]
    d_v = sizes[pre + "v_head_dim"]
    return {
        pre + "q_a": ((count, d, r_q), fan(d)),
        pre + "q_norm": ((count, r_q), None),
        pre + "q_b": ((count, r_q, heads * (d_n + d_r)),
                      rounded(QUERY_NARROWING / math.sqrt(r_q))),
        pre + "kv_a": ((count, d, r_kv + d_r), fan(d)),
        pre + "kv_norm": ((count, r_kv), None),
        pre + "kv_b": ((count, r_kv, heads * (d_n + d_v)), fan(r_kv)),
        pre + "gate": ((count, d, heads), fan(d)),
        pre + "o": ((count, heads * d_v, d), fan(heads * d_v)),
    }


def shapes(sizes: dict) -> dict:
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    kinds = sizes["layer_types"][:sizes["num_hidden_layers"]]
    layers = len(kinds)
    full, sliding = kinds.count("full_attention"), kinds.count("sliding_attention")
    dense = min(sizes["first_k_dense_replace"], layers)
    moe = layers - dense
    experts, held = sizes["n_routed_experts"], sizes["n_routed_experts_here"]
    f, fe = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    shared = fe * sizes["n_shared_experts"]
    hi, di = sizes["index_n_heads"], sizes["index_head_dim"]
    r_q = sizes["q_lora_rank"]
    table = {
        "embedding": ((v, d), rounded(EMBEDDING_STD)),
        "head": ((d, v), fan(d)),
        "final_norm": ((d,), None),
        "attn_norm": ((layers, d), None),
        "ffn_norm": ((layers, d), None),
    }
    table.update(attention_shapes(sizes, "", full))
    table.update({
        "index_q": ((full, r_q, hi * di), fan(r_q)),
        "index_k": ((full, d, di), fan(d)),
        "index_k_scale": ((full, di), None),
        "index_k_bias": ((full, di), 0.1),
        "index_w": ((full, d, hi), fan(d)),
    })
    table.update(attention_shapes(sizes, "swa_", sliding))
    table.update({
        "dense_gate": ((dense, d, f), fan(d)),
        "dense_up": ((dense, d, f), fan(d)),
        "dense_down": ((dense, f, d), fan(f)),
        "router": ((moe, d, experts), fan(d)),
        "router_bias": ((moe, experts), 0.02),
        "w_in": ((moe, held, d, 2 * fe), fan(d)),
        "w_out": ((moe, held, fe, d), fan(fe)),
        "shared_in": ((moe, d, 2 * shared), fan(d)),
        "shared_out": ((moe, shared, d), fan(shared)),
    })
    return table
