"""What one decode step of a Llama-shaped model needs, from this run's live
arrays and live token counts.

Bytes: every matrix of every layer and the head once, in the type the live
array of that shape has now (so a change of storage changes the count);
the embedding rows of the active slots; the keys and values of every token
the active slots hold, in the type of the live KV pool, and this step's
rows written. Operations: 2 per matrix element per active slot, and 4 per
cached token, head and head dimension. A free slot and the padding up to
the context length need nothing: a program that reads them is the slower
for it, and its share says so.
"""

from cellbench.readers._spans import live_tokens_per_step
from cellbench.weights import table

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
            "float8_e4m3fn": 1, "float8_e5m2": 1, "int4": 0.5}


def _itemsize(live, shape):
    """Smallest item among live arrays of `shape`, or of one layer of it."""
    found = [ITEMSIZE[a["dtype"]] for a in live
             if a["dtype"] in ITEMSIZE
             and (tuple(a["shape"]) == tuple(shape)
                  or tuple(a["shape"]) == tuple(shape[1:]))]
    return min(found) if found else None


def count(run):
    sizes, live = run["config"], run["device"]["live_arrays"]
    loaded = live_tokens_per_step(run, run["trace_window"])
    if loaded is None:
        return None
    tokens, slots, _ = loaded
    layers, hd = sizes["num_hidden_layers"], sizes["head_dim"]
    kv_heads, heads = sizes["num_key_value_heads"], sizes["num_attention_heads"]
    weight_bytes = weight_elements = 0
    for name, (shape, std) in table(sizes).items():
        if std is None or name == "embedding":
            continue
        item = _itemsize(live, shape)
        if item is None:
            return None
        n = 1
        for dim in shape:
            n *= dim
        weight_elements += n
        weight_bytes += n * item
    kv = [a for a in live if tuple(a["shape"][-2:]) == (kv_heads, hd)
          and a["dtype"] in ITEMSIZE and len(a["shape"]) >= 4]
    if not kv:
        return None
    kv_item = ITEMSIZE[max(kv, key=lambda a: _elements(a["shape"]))["dtype"]]
    row = layers * 2 * kv_heads * hd * kv_item  # one token's keys and values
    embed_item = _itemsize(live, table(sizes)["embedding"][0]) or 4
    return {
        "bytes": weight_bytes + (tokens + slots) * row
        + slots * sizes["hidden_size"] * embed_item,
        "flops": 2 * weight_elements * slots
        + 4 * tokens * layers * heads * hd,
        "live_tokens": tokens, "active_slots": slots,
        "weight_bytes": weight_bytes,
    }


def _elements(shape):
    n = 1
    for dim in shape:
        n *= dim
    return n
