"""Ulysses-style all-to-all sequence parallelism over the `sp` mesh axis.

The second context-parallel scheme (ring attention in
parallel/ring_attention.py is the first; the reference has neither —
SURVEY.md §5 "Long-context / sequence parallelism: Absent"). Instead of
rotating k/v around a ring, one `all_to_all` re-shards q/k/v from
sequence-sharded ``[B, S/sp, H, D]`` to head-sharded ``[B, S, H/sp, D]``;
each device then runs ordinary *full-sequence* attention over its head
subset, and a second all_to_all restores sequence sharding.

Trade-off vs the ring: two all-to-alls of the whole activation instead of
sp neighbor hops of k/v — fewer, larger transfers (better for
short-hop-rich ICI tori and when sp is large), and the inner attention is
a plain single-device call, so the pallas flash kernel applies unchanged
per shard. The constraint is head divisibility: n_heads % sp == 0 (GQA
k/v heads expand to lcm(H_kv, sp) first when they don't divide sp — the
minimal widening that keeps chunk boundaries on group boundaries; the
remaining GQA expansion happens inside the shard, off the wire).

Surfaces mirror ring_attention: :func:`ulysses_attention` inside
`shard_map`, :func:`ulysses_attention_sharded` for the ops.attention
dispatch seam.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from tf_yarn_tpu.parallel.mesh import (
    AXIS_SP,
    AXIS_TP,
    BATCH_AXES,
    current_mesh,
)


def ulysses_attention(
    query: jax.Array,
    key: jax.Array,
    value: jax.Array,
    *,
    axis_name: str = AXIS_SP,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    inner: str = "xla",
) -> jax.Array:
    """Per-shard Ulysses attention (call inside shard_map).

    Shapes per shard: q [B, S_local, H, D], k/v [B, S_local, Hkv, D];
    returns [B, S_local, H, D]. `inner` picks the single-device attention
    run on the gathered sequence ("xla" | "flash").
    """
    from tf_yarn_tpu.ops.attention import _repeat_kv, xla_attention

    sp = jax.lax.psum(1, axis_name)
    n_heads = query.shape[2]
    if n_heads % sp:
        raise ValueError(
            f"ulysses needs n_heads ({n_heads}) divisible by sp ({sp})"
        )
    if key.shape[2] % sp:
        # GQA kv heads must split evenly over sp. Expand to the *minimal*
        # sp-divisible multiple — lcm(hkv, sp) heads — not all the way to
        # n_heads: lcm | n_heads holds (both hkv and sp divide n_heads),
        # and the contiguous q-group -> kv-head mapping stays aligned
        # per all_to_all chunk since (hkv' % sp == 0) is exactly the
        # chunk-boundary condition. The inner attention GQA-expands the
        # rest locally, off the wire.
        hkv = key.shape[2]
        target = hkv * sp // math.gcd(hkv, sp)
        key, value = _repeat_kv(key, value, target // hkv)

    # Devices along sp hold consecutive sequence shards, so the tiled
    # all_to_all's concat along the seq axis reassembles global order:
    # [B, S/sp, H, D] -> [B, S, H/sp, D].
    seq_to_heads = functools.partial(
        jax.lax.all_to_all, axis_name=axis_name, split_axis=2, concat_axis=1,
        tiled=True,
    )
    q = seq_to_heads(query)
    k = seq_to_heads(key)
    v = seq_to_heads(value)

    if inner == "flash":
        from tf_yarn_tpu.ops.flash_attention import flash_attention

        # Already per-shard here (inside ulysses' own shard_map), which
        # the kernels' wrapper sees for itself.
        out = flash_attention(q, k, v, causal=causal,
                              softmax_scale=softmax_scale)
    else:
        out = xla_attention(q, k, v, causal=causal,
                            softmax_scale=softmax_scale)
    # [B, S, H/sp, D] -> [B, S/sp, H, D]
    return jax.lax.all_to_all(
        out, axis_name=axis_name, split_axis=1, concat_axis=2, tiled=True
    ).astype(query.dtype)


def ulysses_attention_sharded(
    query: jax.Array,
    key: jax.Array,
    value: jax.Array,
    *,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
    inner: str = "xla",
) -> jax.Array:
    """shard_map wrapper over the run's registered mesh; plain XLA
    attention when no mesh is registered or sp == 1 (identical
    semantics, nothing to re-shard)."""
    mesh = current_mesh()
    sp_size = 1
    if mesh is not None:
        sp_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get(AXIS_SP, 1)
    if mesh is None or sp_size == 1:
        from tf_yarn_tpu.ops.attention import xla_attention

        return xla_attention(
            query, key, value, causal=causal, softmax_scale=softmax_scale
        )

    qkv_spec = P(BATCH_AXES, AXIS_SP, AXIS_TP, None)
    fn = functools.partial(
        ulysses_attention, causal=causal, softmax_scale=softmax_scale,
        inner=inner,
    )
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )(query, key, value)
