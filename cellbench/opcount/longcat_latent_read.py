"""What the latent read of one decode step of a `LongCat-Flash` share needs
(the kernel under `attention/paged_kernel`, eight calls a step at four
layers): each active slot's live rows of each attention sublayer
(`latent_live_token_steps`, this step's row included) once, at the width
they are stored in (`kv_lora_rank + qk_rope_head_dim` padded to whole lanes
of 128: a page is read whole), in the type of the live pool; `4 W` operations
per live row and head (one product against the row for the score, one for
the value, both over the stored width, as the kernel makes them). The query
and the result, `[heads, W]` a slot, are small beside a thousand rows and are
left out. A kernel that loads a row twice, as keys and again as values,
reads its share of this the lower for it.
"""

from cellbench.opcount.dots3_step import LANES, _cache_item
from cellbench.opcount.longcat_step import steps_and


def count(run):
    sizes = run["config"]
    counted = steps_and(run, "latent_live_token_steps")
    if counted is None:
        return None
    _, (rows_live,) = counted
    width = sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]
    item = _cache_item(run["device"]["live_arrays"], width)
    if item is None:
        return None
    stored = -(-width // LANES) * LANES
    return {"bytes": rows_live * stored * item,
            "flops": rows_live * sizes["num_attention_heads"] * 4 * stored,
            "latent_rows_a_step": rows_live}
