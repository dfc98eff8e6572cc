"""What one decode step of a `LongCat-Flash` share needs, from this run's
live arrays and the program's own counters (the step returns them with its
tokens: what the expert layers and the attention sublayers counted).

Bytes: every matrix outside the routed experts once, in the type the live
array of that shape has now (both sublayers' latent projections and dense
feed-forwards, the router, the head), and the float32 vectors; each held
expert's two matrices once for every layer-step in which a token reached it
(`moe_experts_touched` over `moe_layer_steps`); for each active slot and
attention sublayer its live latent rows (`latent_live_token_steps`: this
step's row included), at the width a row needs (`kv_lora_rank +
qk_rope_head_dim`: the padding to whole lanes is the program's, and is not
counted); this step's rows written; the embedding rows of the active slots.
Operations: 2 per matrix element outside the experts per active slot (the
absorbed products are the `kv_b` matrix's), 2 per element of an expert's
matrices per assignment that reached a held expert, `4 r_kv + 2 d_r` per
live row and head (scores against `c` and `k_r`, values out of `c`). A free
slot, the padding up to the context length, a row read twice, an expert no
token reached and a zero-compute expert, which returns its input, need
nothing: a program that spends on them is the slower for it, and its share
says so.
"""

from cellbench.opcount.decode_step import _elements, _itemsize
from cellbench.opcount.dots3_step import _cache_item, _grown
from cellbench.weights import _module, table

EXPERT = ("w_in", "w_out")
COUNTERS = ("moe_experts_touched", "moe_layer_steps", "moe_assignments_here",
            "slot_steps", "latent_live_token_steps")


def steps_and(run, *keys):
    """(steps in the window, growth a step of each `/stats` counter in
    `keys`), or None where the program lacks a counter or no step ran."""
    wanted = ("moe_layer_steps",) + keys
    if not all(key in stats for key in wanted
               for stats in (run["stats_open"], run["stats_close"])):
        return None
    layer_steps = _grown(run, "moe_layer_steps")
    if not layer_steps:
        return None
    steps = layer_steps / run["config"]["num_layers"]
    return steps, [_grown(run, key) / steps for key in keys]


def count(run):
    sizes, live = run["config"], run["device"]["live_arrays"]
    counted = steps_and(run, *COUNTERS[:1], *COUNTERS[2:])
    if counted is None:
        return None
    _, (touched, reached, slots, rows_live) = counted
    shapes, single = table(sizes), _module(sizes).SINGLE
    fixed_bytes = fixed_elements = expert_bytes = expert_elements = 0
    for name, (shape, _) in shapes.items():
        if name == "embedding":
            continue
        if name in EXPERT:
            item = _itemsize(live, shape[1:])          # [held, ...] a layer
            if item is None:
                return None
            one = _elements(shape[2:])                 # one expert, one layer
            expert_elements += one
            expert_bytes += one * item
            continue
        item = _itemsize(live, shape)
        if item is None:
            return None
        fixed_bytes += _elements(shape) * item
        if len(shape) - (name not in single) >= 2:     # a matrix
            fixed_elements += _elements(shape)
    rank, rope = sizes["kv_lora_rank"], sizes["qk_rope_head_dim"]
    width = rank + rope
    row_item = _cache_item(live, width)
    if row_item is None:
        return None
    embed_item = _itemsize(live, shapes["embedding"][0]) or 4
    written = slots * 2 * sizes["num_layers"] * width * row_item
    cache_bytes = rows_live * width * row_item + written
    return {
        "bytes": fixed_bytes + touched * expert_bytes + cache_bytes
        + slots * sizes["hidden_size"] * embed_item,
        "flops": 2 * fixed_elements * slots + 2 * expert_elements * reached
        + rows_live * sizes["num_attention_heads"] * (4 * rank + 2 * rope),
        "active_slots": slots,
        "weight_bytes": fixed_bytes + touched * expert_bytes,
        "cache_bytes": cache_bytes, "experts_touched_a_step": touched,
        "latent_rows_a_step": rows_live,
    }
