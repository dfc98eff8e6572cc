"""What one decode step of a `deepseek_v32` share needs, from this run's live
arrays and the program's own counters (the step returns them with its
tokens: what the expert layers and the attention layers counted).

Bytes: every matrix outside the routed experts once, in the type the live
array of that shape has now (the latent projections, the indexer, the
router, the shared expert, the dense ffn, the head), and the float32 vectors;
each held expert's two matrices once for every layer-step in which a token
reached it (`moe_experts_touched` over `moe_layer_steps`); for each active
slot and layer its live index keys (`index_live_token_steps`) and the latent
rows it selected, `min(L, index_topk)` (`index_selected_token_steps`), at the
width a row needs (`kv_lora_rank + qk_rope_head_dim`: the padding to whole
lanes is the program's, and is not counted); this step's rows written; the
embedding rows of the active slots. Operations: 2 per matrix element outside
the experts per active slot (the absorbed products are the `kv_b` matrix's),
2 per element of an expert's matrices per assignment that reached a held
expert, `2 Di + 2` per live index key and index head, `4 r_kv + 2 d_r` per
attended row and head (scores against `c` and `k_r`, values out of `c`). A
free slot, the padding up to the context length, a key scored or sorted past
a slot's length, a row outside the selection and an expert no token reached
need nothing: a program that spends on them is the slower for it, and its
share says so. The same count whatever implements the step.
"""

from cellbench.opcount.decode_step import _elements, _itemsize
from cellbench.opcount.dots3_step import _cache_item, _grown
from cellbench.weight_tables.deepseek_v32 import layers_of
from cellbench.weights import _module, table

EXPERT = ("w_in", "w_out")
COUNTERS = ("moe_experts_touched", "moe_assignments_here", "slot_steps",
            "index_live_token_steps", "index_selected_token_steps")


def steps_and(run, *keys):
    """(steps in the window, growth a step of each `/stats` counter in
    `keys`), or None where the program lacks a counter or no step ran."""
    wanted = ("moe_layer_steps",) + keys
    if not all(key in stats for key in wanted
               for stats in (run["stats_open"], run["stats_close"])):
        return None
    layer_steps = _grown(run, "moe_layer_steps")
    moe_layers = layers_of(run["config"])[2]
    if not layer_steps or not moe_layers:
        return None
    steps = layer_steps / moe_layers
    return steps, [_grown(run, key) / steps for key in keys]


def cache_items(run):
    """(item size of a cached latent row, of an index key), or None."""
    sizes, live = run["config"], run["device"]["live_arrays"]
    items = (_cache_item(live, sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]),
             _cache_item(live, sizes["index_head_dim"]))
    return None if None in items else items


def count(run):
    sizes, live = run["config"], run["device"]["live_arrays"]
    counted, items = steps_and(run, *COUNTERS), cache_items(run)
    if counted is None or items is None:
        return None
    _, (touched, reached, slots, index_live, selected) = counted
    row_item, index_item = items
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
    index_heads, index_dim = sizes["index_n_heads"], sizes["index_head_dim"]
    embed_item = _itemsize(live, shapes["embedding"][0]) or 4
    written = slots * sizes["num_hidden_layers"] * (
        (rank + rope) * row_item + index_dim * index_item)
    cache_bytes = index_live * index_dim * index_item \
        + selected * (rank + rope) * row_item + written
    return {
        "bytes": fixed_bytes + touched * expert_bytes + cache_bytes
        + slots * sizes["hidden_size"] * embed_item,
        "flops": 2 * fixed_elements * slots + 2 * expert_elements * reached
        + index_live * index_heads * (2 * index_dim + 2)
        + selected * sizes["num_attention_heads"] * (4 * rank + 2 * rope),
        "active_slots": slots,
        "weight_bytes": fixed_bytes + touched * expert_bytes,
        "cache_bytes": cache_bytes, "experts_touched_a_step": touched,
        "selected_rows_a_step": selected, "index_keys_a_step": index_live,
    }
