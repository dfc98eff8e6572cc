"""What the two-stage read of one decode step of a `deepseek_v32` share needs
(the operations under the scope `indexer`: `indexer/q`, `indexer/k`,
`indexer/scores`, `indexer/topk`, `indexer/gather`, once a layer): the
indexer's three matrices and its LayerNorm once a layer, in the type of the
live arrays; each active slot's live index keys of each layer
(`index_live_token_steps`, this step's key included) once, and the latent
rows it selected (`index_selected_token_steps`: `min(L, index_topk)`) once,
at the width a row needs, in the type of the live pool. Operations: 2 per
element of the indexer's matrices per active slot, `2 Di + 2` per live index
key and index head (the score product and its weighted sum). A selection
needs no more than that: what the program spends on scoring, sorting and
writing as wide as a slot's whole table, or on a copy of the rows it
gathered, lowers its share.
"""

from cellbench.opcount.decode_step import _elements, _itemsize
from cellbench.opcount.dsv32_step import cache_items, steps_and
from cellbench.weights import table

INDEXER = ("index_q", "index_k", "index_k_scale", "index_k_bias", "index_w")


def count(run):
    sizes, live = run["config"], run["device"]["live_arrays"]
    counted = steps_and(run, "slot_steps", "index_live_token_steps",
                        "index_selected_token_steps")
    items = cache_items(run)
    if counted is None or items is None:
        return None
    _, (slots, index_live, selected) = counted
    row_item, index_item = items
    shapes = table(sizes)
    matrix_bytes = matrix_elements = 0
    for name in INDEXER:
        shape = shapes[name][0]
        item = _itemsize(live, shape)
        if item is None:
            return None
        matrix_bytes += _elements(shape) * item
        if len(shape) >= 3:                            # [layers, in, out]
            matrix_elements += _elements(shape)
    width = sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]
    index_heads, index_dim = sizes["index_n_heads"], sizes["index_head_dim"]
    return {"bytes": matrix_bytes + index_live * index_dim * index_item
            + selected * width * row_item,
            "flops": 2 * matrix_elements * slots
            + index_live * index_heads * (2 * index_dim + 2),
            "index_keys_a_step": index_live, "selected_rows_a_step": selected}
