"""Pipeline parallelism over the `pp` mesh axis (GPipe schedule).

Stage weights live on their pp shard; activations hop stage-to-stage with
`ppermute` (neighbor ICI transfers); microbatches fill the pipe so the
bubble shrinks as num_microbatches grows. The classic shard_map pipelining
pattern: every tick, every stage computes (early/late ticks process
garbage that is masked out of the final gather), then activations rotate
one hop. No reference analog (SURVEY.md §2.5: pipeline parallelism — NO).

Usage (per-shard values under shard_map; `pipeline_apply` wraps it):

    out = pipeline_apply(stage_fn, stage_params, x, mesh,
                         num_microbatches=8)

* `stage_params`: pytree whose leaves have a leading axis of size
  n_stages, sharded over pp (one stage's slice per device).
* `stage_fn(params_slice, activation) -> activation`.
* `x`: [global_batch, ...] input to stage 0; output comes from the last
  stage with identical shape/meaning.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tf_yarn_tpu.parallel.mesh import AXIS_PP


def _pipeline_shard(stage_fn: Callable, params, x, *, axis: str, n_micro: int):
    """Body under shard_map: params [1, ...] (this stage's slice),
    x [micro, mb, ...] (replicated along pp)."""
    n_stages = jax.lax.psum(1, axis)
    stage = jax.lax.axis_index(axis)
    params = jax.tree_util.tree_map(lambda p: p[0], params)

    micro, mb = x.shape[0], x.shape[1]
    assert micro == n_micro
    total_ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        held, outputs = carry
        # Stage 0 ingests microbatch t (garbage once t >= n_micro).
        mb_idx = jnp.minimum(t, n_micro - 1)
        incoming = jnp.where(stage == 0, x[mb_idx], held)
        computed = stage_fn(params, incoming)
        # Last stage emits microbatch t - (n_stages - 1) when valid.
        out_idx = t - (n_stages - 1)
        valid = (out_idx >= 0) & (stage == n_stages - 1)
        outputs = jax.lax.cond(
            valid,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, computed, jnp.maximum(out_idx, 0), 0
            ),
            lambda o: o,
            outputs,
        )
        held = jax.lax.ppermute(computed, axis, perm)
        return (held, outputs), None

    held0 = jnp.zeros_like(x[0])
    outputs0 = jnp.zeros_like(x)
    (_, outputs), _ = jax.lax.scan(
        tick, (held0, outputs0), jnp.arange(total_ticks)
    )
    # Only the last stage holds real outputs; broadcast them to every pp
    # shard so the result is replicated along pp (psum of one-hot copies).
    outputs = jax.lax.psum(
        jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)), axis
    )
    return outputs


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x: jax.Array,
    mesh,
    num_microbatches: int = 4,
    batch_axes=("dp", "fsdp"),
):
    """Run x through the staged computation on `mesh`'s pp axis.

    stage_params leaves: [n_stages, ...] sharded P(pp, ...); x:
    [batch, ...] (batch additionally sharded over `batch_axes` if those
    axes exist in the mesh). Batch must divide num_microbatches x the
    batch sharding.
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_stages = axis_sizes.get(AXIS_PP, 1)
    if n_stages == 1:
        def sequential(x):
            n = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
            for i in range(n):
                x = stage_fn(
                    jax.tree_util.tree_map(lambda p: p[i], stage_params), x
                )
            return x

        return sequential(x)

    leading = {
        leaf.shape[0] for leaf in jax.tree_util.tree_leaves(stage_params)
    }
    if leading != {n_stages}:
        raise ValueError(
            f"stage_params leading dims {sorted(leading)} must all equal the "
            f"pp mesh axis size {n_stages} (one stage slice per pp shard)"
        )

    batch = x.shape[0]
    if batch % num_microbatches:
        raise ValueError(
            f"batch {batch} not divisible by num_microbatches {num_microbatches}"
        )
    mb = batch // num_microbatches
    data_shards = 1
    for axis in batch_axes:
        data_shards *= axis_sizes.get(axis, 1)
    if mb % data_shards:
        raise ValueError(
            f"microbatch size {mb} (= batch {batch} / {num_microbatches} "
            f"microbatches) must be a multiple of the data sharding "
            f"{data_shards} (product of mesh axes {batch_axes}) — use fewer "
            "microbatches or a larger batch"
        )
    x_micro = x.reshape(num_microbatches, mb, *x.shape[1:])

    present_batch_axes = tuple(
        a for a in batch_axes if axis_sizes.get(a, 1) > 1
    ) or None

    params_spec = jax.tree_util.tree_map(
        lambda p: P(AXIS_PP, *([None] * (p.ndim - 1))), stage_params
    )
    x_spec = P(None, present_batch_axes, *([None] * (x.ndim - 1)))

    fn = functools.partial(
        _pipeline_shard, stage_fn, axis=AXIS_PP, n_micro=num_microbatches
    )
    out = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(params_spec, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(stage_params, x_micro)
    return out.reshape(batch, *out.shape[2:])
