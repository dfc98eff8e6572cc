"""What the full layers' read of the pool needs in one decode step of a
`laguna` stage (the kernel under `attention/paged_kernel`, one call a full
layer): each active slot's live keys and values of each full layer
(`pool_live_token_steps`, this step's row included) once, as stored
(`num_key_value_heads x head_dim` numbers each, in the type of the live
pool); `4 head_dim` operations per live row and query head (one product for
the score, one for the value: 6 query heads share a row's key and value).
The query and the result, `[heads, head_dim]` a slot, are small beside a
thousand rows and are left out. What the kernel spends beside that — every
query head's product against every KV head's rows, a read rounded up to
its loop trip — lowers its share.
"""

from cellbench.opcount.laguna_step import cache_item, steps_and
from cellbench.weight_tables.laguna import FULL, kinds


def count(run):
    sizes = run["config"]
    counted = steps_and(run, "pool_live_token_steps")
    item = cache_item(run)
    if counted is None or item is None:
        return None
    _, (rows_live,) = counted
    head_dim = sizes["head_dim"]
    return {"bytes": rows_live * 2 * sizes["num_key_value_heads"] * head_dim
            * item,
            "flops": rows_live * kinds(sizes)["heads"].get(FULL, 0) * 4
            * head_dim,
            "pool_rows_a_step": rows_live}
