"""What one decode step of a `laguna` stage needs, from this run's live
arrays and the program's own counters (the step returns them with its
tokens: what the expert layers and the attention layers counted).

Bytes: every matrix outside the routed experts once, in the type the live
array of that shape has now (both kinds' attention projections and gates,
the dense feed-forward, the routers, the shared experts, the head), and the
float32 vectors; each expert's two matrices once for every layer-step in
which a token reached it (`moe_experts_touched` over `moe_layer_steps`: of
256 a layer, about 221 at 64 slots); for each active slot and full layer its
live keys and values (`pool_live_token_steps`: this step's row included);
for each sliding layer the rows inside the window, `min(L, window)`
(`window_live_token_steps`); this step's rows written; the embedding rows of
the active slots. Operations: 2 per matrix element outside the experts per
active slot, 2 per element of an expert's matrices per assignment, `4
head_dim` per live row and query head of its layer's kind (the score and the
value). A free slot, the padding up to the context length, a row outside the
window, a row read twice and an expert no token reached need nothing: a
program that spends on them is the slower for it, and its share says so.
"""

from cellbench.opcount.decode_step import ITEMSIZE, _elements, _itemsize
from cellbench.opcount.dots3_step import _grown
from cellbench.weight_tables.laguna import FULL, SLIDING, kinds
from cellbench.weights import _module, table

EXPERT = ("w_in", "w_out")
COUNTERS = ("moe_experts_touched", "moe_layer_steps", "moe_assignments_here",
            "slot_steps", "pool_live_token_steps", "window_live_token_steps")


def steps_and(run, *keys):
    """(steps in the window, growth a step of each `/stats` counter in
    `keys`), or None where the program lacks a counter or no step ran."""
    wanted = ("moe_layer_steps",) + keys
    if not all(key in stats for key in wanted
               for stats in (run["stats_open"], run["stats_close"])):
        return None
    sparse = kinds(run["config"])["sparse"]
    layer_steps = _grown(run, "moe_layer_steps")
    if not layer_steps or not sparse:
        return None
    steps = layer_steps / sparse
    return steps, [_grown(run, key) / steps for key in keys]


def cache_item(run):
    """Item size of the largest live array whose rows are [KV heads, head
    size] wide: the pool's leaf (the rings hold the same type)."""
    sizes = run["config"]
    row = (sizes["num_key_value_heads"], sizes["head_dim"])
    found = [a for a in run["device"]["live_arrays"]
             if tuple(a["shape"][-2:]) == row and a["dtype"] in ITEMSIZE
             and len(a["shape"]) >= 4]
    if not found:
        return None
    return ITEMSIZE[max(found, key=lambda a: _elements(a["shape"]))["dtype"]]


def count(run):
    sizes, live = run["config"], run["device"]["live_arrays"]
    counted = steps_and(run, *COUNTERS[:1], *COUNTERS[2:])
    if counted is None:
        return None
    _, (touched, reached, slots, pool_live, window_live) = counted
    shapes, single = table(sizes), _module(sizes).SINGLE
    fixed_bytes = fixed_elements = expert_bytes = expert_elements = 0
    for name, (shape, _) in shapes.items():
        if name == "embedding" or not shape[0]:
            continue
        if name in EXPERT:
            item = _itemsize(live, shape[1:])          # [experts, ...] a layer
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
    item = cache_item(run)
    if item is None:
        return None
    about = kinds(sizes)
    head_dim = sizes["head_dim"]
    row = 2 * sizes["num_key_value_heads"] * head_dim * item  # a key, a value
    embed_item = _itemsize(live, shapes["embedding"][0]) or 4
    written = slots * about["layers"] * row
    cache_bytes = (pool_live + window_live) * row + written
    attend = 4 * head_dim * (
        pool_live * about["heads"].get(FULL, 0)
        + window_live * about["heads"].get(SLIDING, 0))
    return {
        "bytes": fixed_bytes + touched * expert_bytes + cache_bytes
        + slots * sizes["hidden_size"] * embed_item,
        "flops": 2 * fixed_elements * slots + 2 * expert_elements * reached
        + attend,
        "active_slots": slots,
        "weight_bytes": fixed_bytes + touched * expert_bytes,
        "cache_bytes": cache_bytes, "experts_touched_a_step": touched,
        "pool_rows_a_step": pool_live, "window_rows_a_step": window_live,
    }
