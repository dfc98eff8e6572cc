"""Weight table of a `laguna` decoder's language model
(reference/laguna.py has the equations): the first `num_hidden_layers` layers
of the published lists (`layer_types`, `mlp_layer_types`,
`num_attention_heads_per_layer`), every expert of a layer and the whole
vocabulary.

Names: `embedding [V, D]`, `head [D, V]` (untied), `final_norm [D]`; a list
over all layers of `attn_norm`, `ffn_norm [D]`; a list over the
`full_attention` layers of `q [D, H 128]`, `k`, `v [D, 8 x 128]`,
`gate [D, H]`, `o [H 128, D]` at that kind's count of heads; a list over the
`sliding_attention` layers of the same five under `swa_`, at that kind's; a
list over the `dense` layers of `dense_gate`, `dense_up [D, F]`,
`dense_down [F, D]`; a list over the `sparse` layers of `router [D, E]`,
`w_in [E, D, 2 Fe]` (`a | b`), `w_out [E, Fe, D]`, `shared_in [D, 2 Fs]`,
`shared_out [Fs, D]`. Matrices multiply from the right (`x @ w`).

Every matrix is drawn at 1 / sqrt(fan_in) and rounded to a value bfloat16
holds (`weight_tables/dots3_note.py`'s `rounded`: `lax.reduce_precision`,
which the chip's compiler keeps), so that the program, which stores matrices
in bfloat16, and the float32 reference hold the same numbers. Norm scales
stay float32 in both, 1 + 0.1 normal. The embedding is drawn at 0.05, for
that table's reason: beside rows that narrow, what the first layer's
attention adds is as large as the token's own row, so a cache row lost or
stale shows in the logits.

What this model's mechanisms need of the draw. *The gate.* `u W_g` at
1 / sqrt(fan_in) has a standard deviation of 1 (u is a normed row), so
`sigmoid` spreads over 0.27..0.73 and beyond: a gate stuck at 1/2 could be
left out unseen, this one cannot. *The scores.* q and k have unit elements,
so `q.k / sqrt(128)` spreads by 1.0 on a sliding layer and by
sqrt((64 x 1.4159^4 + 64) / 128) = 1.58 on a full one, whose rotated half
carries YaRN's attention factor on both sides: hundreds of keys share a
head, and a factor left out (scores at 1.0) shows.

*The router is drawn at 3 / sqrt(fan_in)* (`ROUTER_WIDENING`). Every expert
of a layer is held here, so every change of the chosen eight moves the
stream, where a share of 16 of 256 feels one change in sixteen. At
1 / sqrt(fan_in) the logits spread by 1, the eighth and ninth largest of 256
lie 0.055 apart and the chosen gates are nearly equal (2.5 x 0.06..0.25): the
program in bfloat16 and the float32 reference then choose another eighth
expert for about half the tokens in some layer, each such choice moves the
stream by 0.17 of an expert's output, and no precision can be told from
another (on the chip, PR 42, seed 4200042001: gap_mean 0.066, gap_p99 1.06,
78 % first choices, where the same program without its routed branch reads
0.0007 at a middle size on the CPU; dots3's table met the same in its
scores). At 3 the logits spread by 3 as a trained router's do, the eighth
and ninth lie 0.17 apart, the eighth gate is 2.5 x 0.02, and a middle size
on the CPU read bfloat16 at gap_mean 0.017 against the int8 control's 0.217
(1 / sqrt(fan_in): 0.071 against 0.293; 6 / sqrt(fan_in): 0.041: past 3 the
gates themselves, near one-hot, move with every rounding of a logit). The
gates still sum to 2.5 and the routed branch is 2.5 x sqrt(sum g^2) = about
1.5 of one expert's output: as large in the stream as the shared expert's,
which has the same width. A lost expert, a lost scale (2.5 -> 1) or a lost
shared expert then moves the stream by what one feed-forward adds.
"""

from __future__ import annotations

import math

from cellbench.weight_tables.dots3_note import EMBEDDING_STD, fan, rounded

SINGLE = ("embedding", "head", "final_norm")
ROUTER_WIDENING = 3.0
FULL, SLIDING = "full_attention", "sliding_attention"


def kinds(sizes: dict) -> dict:
    """The first `num_hidden_layers` entries of the published lists: how many
    layers of each attention kind and of each feed-forward kind, and each
    attention kind's count of heads."""
    depth = sizes["num_hidden_layers"]
    types = sizes["layer_types"][:depth]
    heads = sizes["num_attention_heads_per_layer"][:depth]
    ffn = sizes["mlp_layer_types"][:depth]
    by_kind = {}
    for kind, count in zip(types, heads):
        if by_kind.setdefault(kind, count) != count:
            raise ValueError(f"{kind} layers with {by_kind[kind]} and {count} heads")
    return {"layers": depth, "heads": by_kind,
            "count": {k: types.count(k) for k in (FULL, SLIDING)},
            "dense": ffn.count("dense"), "sparse": ffn.count("sparse")}


def attention_shapes(sizes: dict, pre: str, count: int, heads: int) -> dict:
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    kv = sizes["num_key_value_heads"]
    return {
        pre + "q": ((count, d, heads * hd), fan(d)),
        pre + "k": ((count, d, kv * hd), fan(d)),
        pre + "v": ((count, d, kv * hd), fan(d)),
        pre + "gate": ((count, d, heads), fan(d)),
        pre + "o": ((count, heads * hd, d), fan(heads * hd)),
    }


def shapes(sizes: dict) -> dict:
    d, v = sizes["hidden_size"], sizes["vocab_size"]
    about = kinds(sizes)
    layers, dense, sparse = about["layers"], about["dense"], about["sparse"]
    experts = sizes["num_experts"]
    f, fe = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    fs = sizes["shared_expert_intermediate_size"]
    table = {
        "embedding": ((v, d), rounded(EMBEDDING_STD)),
        "head": ((d, v), fan(d)),
        "final_norm": ((d,), None),
        "attn_norm": ((layers, d), None),
        "ffn_norm": ((layers, d), None),
    }
    for kind, pre in ((FULL, ""), (SLIDING, "swa_")):
        table.update(attention_shapes(
            sizes, pre, about["count"][kind], about["heads"].get(kind, 0)))
    table.update({
        "dense_gate": ((dense, d, f), fan(d)),
        "dense_up": ((dense, d, f), fan(d)),
        "dense_down": ((dense, f, d), fan(f)),
        "router": ((sparse, d, experts),
                   rounded(ROUTER_WIDENING / math.sqrt(d))),
        "w_in": ((sparse, experts, d, 2 * fe), fan(d)),
        "w_out": ((sparse, experts, fe, d), fan(fe)),
        "shared_in": ((sparse, d, 2 * fs), fan(d)),
        "shared_out": ((sparse, fs, d), fan(fs)),
    })
    return table
