"""What one decode step of a `granitemoehybrid` share needs, from this run's
live arrays, live token counts and the program's own expert counters.

Bytes: every matrix outside the experts once, in the type the live array of
that shape has now (router, shared expert, the mixers' projections, and the
tied embedding once as the head), and the float32 vectors; each held
expert's two matrices once for every layer-step in which a token reached it
(`moe_experts_touched` over `moe_layer_steps`, as the step itself counted
them); the recurrent state and the convolution's tail of every mamba layer,
read and written, for each active slot; the keys and values of every token
the active slots hold in the attention layers, and this step's rows
written; the embedding rows of the active slots. Operations: 2 per matrix
element outside the experts per active slot, 2 per element of an expert's
matrices per assignment that reached a held expert, 4 per cached token,
head and head dimension, and 6 per element of the recurrent state per active
slot (decay, the outer product added, the contraction with C). A free slot,
the padding up to the context length and an expert no token reached need
nothing: a program that reads them is the slower for it, and its share says
so.
"""

from cellbench.opcount.decode_step import ITEMSIZE, _elements, _itemsize
from cellbench.readers._spans import live_tokens_per_step
from cellbench.weights import table

EXPERT = ("w_in", "w_out")
COUNTERS = ("moe_experts_touched", "moe_layer_steps", "moe_assignments_here")


def _grown(run, key):
    return run["stats_close"][key] - run["stats_open"][key]


def _live_item(live, tail):
    """Item size of the largest live array whose shape ends in `tail`."""
    found = [a for a in live if tuple(a["shape"][-len(tail):]) == tuple(tail)
             and a["dtype"] in ITEMSIZE and len(a["shape"]) > len(tail)]
    if not found:
        return None
    return ITEMSIZE[max(found, key=lambda a: _elements(a["shape"]))["dtype"]]


def count(run):
    sizes, live = run["config"], run["device"]["live_arrays"]
    loaded = live_tokens_per_step(run, run["trace_window"])
    if loaded is None or not all(
            key in stats for key in COUNTERS
            for stats in (run["stats_open"], run["stats_close"])):
        return None
    tokens, slots, _ = loaded
    layer_steps = _grown(run, "moe_layer_steps")
    if not layer_steps:
        return None
    kinds = sizes["layer_types"][:sizes["num_hidden_layers"]]
    layers, mamba, attn = len(kinds), kinds.count("mamba"), kinds.count("attention")
    steps = layer_steps / layers
    touched = _grown(run, "moe_experts_touched") / steps    # a step, all layers
    reached = _grown(run, "moe_assignments_here") / steps
    shapes = table(sizes)
    fixed_bytes = fixed_elements = expert_bytes = expert_elements = 0
    for name, (shape, _) in shapes.items():
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
        if len(shape) - (name != "embedding") >= 2:    # a matrix
            fixed_elements += _elements(shape)
    heads, hd = sizes["num_attention_heads"], \
        sizes["hidden_size"] // sizes["num_attention_heads"]
    kv_heads = sizes["num_key_value_heads"]
    state = (sizes["mamba_n_heads"], sizes["mamba_d_head"], sizes["mamba_d_state"])
    tail = (sizes["mamba_d_conv"] - 1, shapes["conv_w"][0][-1])
    kv_item, state_item, tail_item = (
        _live_item(live, (kv_heads, hd)), _live_item(live, state),
        _live_item(live, tail))
    if None in (kv_item, state_item, tail_item):
        return None
    embed_item = _itemsize(live, shapes["embedding"][0]) or 4
    state_bytes = mamba * 2 * slots * (
        _elements(state) * state_item + _elements(tail) * tail_item)
    kv_row = attn * 2 * kv_heads * hd * kv_item
    return {
        "bytes": fixed_bytes + touched * expert_bytes + state_bytes
        + (tokens + slots) * kv_row
        + slots * sizes["hidden_size"] * embed_item,
        "flops": 2 * fixed_elements * slots + 2 * expert_elements * reached
        + 4 * tokens * attn * heads * hd
        + 6 * mamba * slots * _elements(state),
        "live_tokens": tokens, "active_slots": slots,
        "weight_bytes": fixed_bytes + touched * expert_bytes,
        "state_bytes": state_bytes, "experts_touched_a_step": touched,
    }
