"""What one decode step of a `dots3_note` share needs, from this run's live
arrays and the program's own counters (the step returns them with its
tokens: what the expert layers and the attention layers counted).

Bytes: every matrix outside the routed experts once, in the type the live
array of that shape has now (the latent projections, the indexer, the gate,
the router, the shared expert, the dense ffn, the head), and the float32
vectors; each held expert's two matrices once for every layer-step in which
a token reached it (`moe_experts_touched` over `moe_layer_steps`); for each
active slot and full layer its live index keys (`index_live_token_steps`)
and the latent rows it selected, `min(L, index_topk)`
(`index_selected_token_steps`); for each sliding layer the rows inside the
window, `min(L, window)` (`window_live_token_steps`); this step's rows
written; the embedding rows of the active slots. Operations: 2 per matrix
element outside the experts per active slot (the absorbed products are the
`kv_b` matrix's), 2 per element of an expert's matrices per assignment that
reached a held expert, `2 Di + 2` per live index key and index head,
`4 r_kv + 2 d_r` per attended row and head (scores against `c` and `k_r`,
values out of `c`). A free slot, the padding up to the context length, a row
outside the window or the selection, and an expert no token reached need
nothing: a program that reads them is the slower for it, and its share says
so.
"""

from cellbench.opcount.decode_step import ITEMSIZE, _elements, _itemsize
from cellbench.weights import _module, table

EXPERT = ("w_in", "w_out")
COUNTERS = ("moe_experts_touched", "moe_layer_steps", "moe_assignments_here",
            "slot_steps", "index_live_token_steps",
            "index_selected_token_steps", "window_live_token_steps")


def _grown(run, key):
    return run["stats_close"][key] - run["stats_open"][key]


LANES = 128


def _cache_item(live, width):
    """Item size of the largest live array of four or more axes whose rows
    are `width` wide, or that padded to whole lanes as the program stores
    them (576 -> 640): the pool's or the rings' leaf of that width. The
    padding is not needed, and is not counted."""
    stored = (width, -(-width // LANES) * LANES)
    found = [a for a in live if a["shape"] and a["shape"][-1] in stored
             and a["dtype"] in ITEMSIZE and len(a["shape"]) >= 4]
    if not found:
        return None
    return ITEMSIZE[max(found, key=lambda a: _elements(a["shape"]))["dtype"]]


def count(run):
    sizes, live = run["config"], run["device"]["live_arrays"]
    if not all(key in stats for key in COUNTERS
               for stats in (run["stats_open"], run["stats_close"])):
        return None
    kinds = sizes["layer_types"][:sizes["num_hidden_layers"]]
    moe_layers = len(kinds) - min(sizes["first_k_dense_replace"], len(kinds))
    layer_steps = _grown(run, "moe_layer_steps")
    if not layer_steps or not moe_layers:
        return None
    steps = layer_steps / moe_layers
    slots = _grown(run, "slot_steps") / steps
    touched = _grown(run, "moe_experts_touched") / steps    # a step, all layers
    reached = _grown(run, "moe_assignments_here") / steps
    index_live = _grown(run, "index_live_token_steps") / steps
    selected = _grown(run, "index_selected_token_steps") / steps
    window_live = _grown(run, "window_live_token_steps") / steps
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
    full = {"heads": sizes["num_attention_heads"], "rank": sizes["kv_lora_rank"],
            "rope": sizes["qk_rope_head_dim"]}
    swa = {"heads": sizes["swa_num_attention_heads"],
           "rank": sizes["swa_kv_lora_rank"], "rope": sizes["swa_qk_rope_head_dim"]}
    index_heads, index_dim = sizes["index_n_heads"], sizes["index_head_dim"]
    widths = (full["rank"] + full["rope"], index_dim, swa["rank"] + swa["rope"])
    items = [_cache_item(live, width) for width in widths]
    if None in items:
        return None
    row_item, index_item, window_item = items
    embed_item = _itemsize(live, shapes["embedding"][0]) or 4
    written = slots * (
        kinds.count("full_attention") * (widths[0] * row_item
                                         + widths[1] * index_item)
        + kinds.count("sliding_attention") * widths[2] * window_item)
    cache_bytes = index_live * widths[1] * index_item \
        + selected * widths[0] * row_item \
        + window_live * widths[2] * window_item + written
    return {
        "bytes": fixed_bytes + touched * expert_bytes + cache_bytes
        + slots * sizes["hidden_size"] * embed_item,
        "flops": 2 * fixed_elements * slots + 2 * expert_elements * reached
        + index_live * index_heads * (2 * index_dim + 2)
        + selected * full["heads"] * (4 * full["rank"] + 2 * full["rope"])
        + window_live * swa["heads"] * (4 * swa["rank"] + 2 * swa["rope"]),
        "active_slots": slots, "weight_bytes": fixed_bytes + touched * expert_bytes,
        "cache_bytes": cache_bytes, "experts_touched_a_step": touched,
        "selected_rows_a_step": selected, "index_keys_a_step": index_live,
        "window_rows_a_step": window_live,
    }
