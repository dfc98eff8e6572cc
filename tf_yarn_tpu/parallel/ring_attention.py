"""Ring attention: sequence/context parallelism over the `sp` mesh axis.

First-class long-context support the reference entirely lacks (SURVEY.md
§5 "Long-context / sequence parallelism: Absent"). Sequences are sharded
over the `sp` axis; each device holds its local q/k/v block, computes
blockwise attention against the kv block it currently holds, and rotates
k/v one hop around the ring with `ppermute` — after sp steps every q saw
every kv, with only O(S/sp) sequence resident per chip. Online-softmax
(running max / sum-exp) merging keeps the math exact, and the hop is a
neighbor-to-neighbor ICI transfer, the cheapest collective the torus has.

Two surfaces:
* :func:`ring_attention` — per-shard function, call inside `shard_map`.
* :func:`ring_attention_sharded` — drop-in for ops.attention dispatch:
  wraps itself in shard_map over the run's mesh (registered by the train
  loop via `parallel.mesh.set_current_mesh`).
"""

from __future__ import annotations

import functools
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

NEG_INF = -1e30


def _block_attend(q, k, v, q_offset, k_offset, causal, scale):
    """Unnormalized blockwise attention: returns (m, l, acc) for merging.

    q [B,Sq,H,D]; k/v [B,Sk,Hkv,D] — GQA heads are expanded here, per
    block, AFTER the ring hop, so the ppermute only moves Hkv heads
    (H/Hkv x less ICI traffic than rotating expanded KV). Positions are
    global: q_offset/k_offset locate the shards in the full sequence so
    the causal mask stays exact across the ring.
    """
    from tf_yarn_tpu.ops.attention import _repeat_kv

    k, v = _repeat_kv(k, v, q.shape[2] // k.shape[2])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])[:, None]
        k_pos = k_offset + jnp.arange(k.shape[1])[None, :]
        logits = jnp.where((q_pos >= k_pos)[None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)  # [B,H,Sq,1]
    # Fully-masked rows: exp(NEG_INF - NEG_INF) would be 1; clamp m so the
    # probabilities stay 0 and the merge is a no-op for those rows.
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(logits - m_safe)
    p = jnp.where(m > NEG_INF / 2, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)  # [B,H,Sq,1]
    acc = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v).astype(jnp.float32)
    return m_safe, l, acc


def ring_attention(
    query: jax.Array,
    key: jax.Array,
    value: jax.Array,
    *,
    axis_name: str = AXIS_SP,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """Per-shard ring attention (call inside shard_map).

    Shapes per shard: q [B, S_local, H, D], k/v [B, S_local, Hkv, D].
    """
    b, s_local, n_heads, head_dim = query.shape
    scale = softmax_scale if softmax_scale is not None else head_dim**-0.5

    sp = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    q_offset = my_idx * s_local

    m0 = jnp.full((b, n_heads, s_local, 1), NEG_INF / 2, jnp.float32)
    l0 = jnp.zeros((b, n_heads, s_local, 1), jnp.float32)
    acc0 = jnp.zeros((b, n_heads, s_local, head_dim), jnp.float32)

    # Static python loop: sp is a trace-time constant; each iteration's
    # ppermute is its own ICI hop XLA can overlap with the block compute.
    k_cur, v_cur = key, value
    m, l, acc = m0, l0, acc0
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    for step in range(sp):
        # kv currently held came from shard (my_idx - step) mod sp.
        src = (my_idx - step) % sp
        k_offset = src * s_local

        def compute(operands):
            q, k, v, k_off = operands
            return _block_attend(q, k, v, q_offset, k_off, causal, scale)

        def skip(operands):
            # Fully-masked block: identity under the online-softmax merge.
            return m0, l0, acc0

        if causal:
            # Shards strictly after mine are entirely in the future: skip
            # the whole block matmul (halves causal FLOPs on average; the
            # per-device branch is data-dependent on axis_index, which
            # lax.cond handles under shard_map).
            m_blk, l_blk, acc_blk = jax.lax.cond(
                src <= my_idx, compute, skip, (query, k_cur, v_cur, k_offset)
            )
        else:
            m_blk, l_blk, acc_blk = compute((query, k_cur, v_cur, k_offset))
        m_new = jnp.maximum(m, m_blk)
        c_old = jnp.exp(m - m_new)
        c_blk = jnp.exp(m_blk - m_new)
        l = l * c_old + l_blk * c_blk
        acc = acc * c_old + acc_blk * c_blk
        m = m_new
        if step != sp - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)

    out = acc / jnp.maximum(l, 1e-30)  # [B,H,S,1] broadcast over D
    return out.transpose(0, 2, 1, 3).astype(query.dtype)  # [B,S,H,D]


def ring_attention_sharded(
    query: jax.Array,
    key: jax.Array,
    value: jax.Array,
    *,
    causal: bool = True,
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """shard_map wrapper over the run's registered mesh.

    Reduces to plain XLA attention when no mesh is registered or sp == 1 —
    the semantics are identical, there is just nothing to ring over.
    """
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
        ring_attention, causal=causal, softmax_scale=softmax_scale
    )
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec),
        out_specs=qkv_spec,
        check_vma=False,
    )(query, key, value)
