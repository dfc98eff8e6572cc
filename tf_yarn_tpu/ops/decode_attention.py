"""Single-token decode attention over an INT8 KV cache (pallas).

The decode bottleneck at long context is streaming the KV cache from HBM
every generated token. `models/transformer.py` can *store* the cache as
int8 + per-row scales (kv_cache_dtype="int8"), but dequantizing outside
the attention op materializes the full bf16 cache each step — traffic
goes UP, not down. This kernel closes that loop: it reads the int8
values and f32 scales directly, dequantizes tile-by-tile in VMEM, and
runs the online-softmax reduction across kv blocks — so HBM streams half
the bytes of a bf16 cache.

Layout choices (the part that makes it fast on TPU):
* K/V enter as ``[B, S, Hkv*D]`` — a FREE reshape of the cache's
  ``[B, S, Hkv, D]`` storage (no transpose copy of the thing we're
  trying not to copy). Blocks of shape (1, block_k, Hkv*D) are
  lane-native (Hkv*D is a multiple of 128 for every config in the zoo).
* The per-kv-head dots are unrolled in-kernel over the static Hkv range;
  each head's GQA query group rides the same tile.
* Valid cache length arrives via scalar prefetch (SMEM), masking dead
  positions with -inf before the online-softmax update.

Kernel semantics match ``xla_attention(q[:, None], k, v, causal=True,
segment_offset=length-1)`` for a single query token at position
``length - 1`` (tested in tests/test_ops.py).

``paged_int8_decode_attention`` is the same reduction over the PAGED KV
layout (models/decode_engine.py `make_paged_pool`): the cache arrives as
a global pool of fixed-size blocks plus a per-slot block table, and the
kernel walks each slot's table with the table in SMEM (scalar prefetch)
— physical block ids become pallas index-map coordinates, so the pool
streams block-by-block with NO gather materializing a dense per-slot
cache first. Scales may be per-row ([NB, bs, Hkv, 1]) or per-BLOCK
([NB, 1, Hkv, 1], from `quantize_int8_grouped(group_rows=block_size)`)
— the per-block layout cuts scale storage/stream by the block size.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = float("-inf")


def _decode_kernel(length_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, n_kv: int, group: int,
                   head_dim: int, block_k: int, softmax_scale: float):
    """Grid (B, S // block_k); kv-block axis innermost/sequential.

    Refs: q (1, H, D); k/v (1, block_k, Hkv*D) int8; scales (1, block_k,
    Hkv) f32; out (1, H, D). Scratch: m/l (H, 128) f32, acc (H, D) f32.
    """
    ki = pl.program_id(1)
    num_k = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = length_ref[0]
    # Positions of this kv block; everything at/after `length` is dead
    # (cache slots not yet written).
    pos = ki * block_k + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    live_row = pos < length  # (1, block_k): masks the logits' lanes
    # The same mask down the sublanes, for v's rows. Built from its own
    # iota: Mosaic cannot reshape a lane-major bool vector into a column.
    live_col = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_k, 1), 0
    ) < length  # (block_k, 1)

    @pl.when(ki * block_k < length)
    def _step():
        for h in range(n_kv):
            k_blk = k_ref[0, :, h * head_dim:(h + 1) * head_dim]
            v_blk = v_ref[0, :, h * head_dim:(h + 1) * head_dim]
            scale_k = ks_ref[0, :, h:h + 1]  # (block_k, 1) f32
            scale_v = vs_ref[0, :, h:h + 1]
            # Dequant in VMEM: int8 -> f32 rows * per-row scale. Dead rows
            # (past `length` or in the padded trailing block) must be
            # zeroed in v, not just masked in the logits: p is 0 there but
            # pad garbage in the f32 scales can be NaN, and 0 * NaN = NaN
            # in the p @ v accumulation.
            k_f = k_blk.astype(jnp.float32) * scale_k
            v_f = jnp.where(
                live_col, v_blk.astype(jnp.float32) * scale_v, 0.0
            )
            q_h = q_ref[0, h * group:(h + 1) * group, :].astype(jnp.float32)
            logits = lax.dot_general(
                q_h * softmax_scale, k_f, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (group, block_k)
            logits = jnp.where(live_row, logits, NEG_INF)

            rows = slice(h * group, (h + 1) * group)
            m_prev = m_scr[rows]                      # (group, 128)
            m_blk = jnp.max(logits, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_blk, m_prev.shape))
            p = jnp.exp(logits - m_new[:, :1])
            corr = jnp.exp(m_prev - m_new)
            m_scr[rows] = m_new
            l_scr[rows] = l_scr[rows] * corr + jnp.broadcast_to(
                jnp.sum(p, axis=-1, keepdims=True), m_prev.shape
            )
            acc_scr[rows] = acc_scr[rows] * corr[:, :1] + lax.dot_general(
                p, v_f, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(ki == num_k - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)


def int8_decode_attention(
    query: jax.Array,
    key_q: jax.Array,
    key_scale: jax.Array,
    value_q: jax.Array,
    value_scale: jax.Array,
    length: jax.Array,
    *,
    softmax_scale: Optional[float] = None,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """query [B, H, D] (one token/batch row), int8 cache [B, S, Hkv, D]
    + scales [B, S, Hkv, 1], length scalar int32 (valid positions) ->
    [B, H, D] attention output in `query`'s dtype."""
    from jax.experimental.pallas import tpu as pltpu

    b, n_heads, head_dim = query.shape
    _, s, n_kv, _ = key_q.shape
    if query.size == 0 or s == 0:  # empty batch or cache
        return jnp.zeros(query.shape, query.dtype)
    group = n_heads // n_kv
    # Any S the cache can hold must decode at full tile width: the grid
    # rounds up and pallas pads the trailing partial block (dead positions
    # are masked in-kernel), so an odd S never collapses block_k.
    block_k = min(block_k, s)
    num_kb = -(-s // block_k)
    if softmax_scale is None:
        softmax_scale = head_dim**-0.5
    if interpret is None:
        from tf_yarn_tpu.ops._rowwise import default_interpret

        interpret = default_interpret()

    kf = key_q.reshape(b, s, n_kv * head_dim)
    vf = value_q.reshape(b, s, n_kv * head_dim)
    ks = key_scale.reshape(b, s, n_kv)
    vs = value_scale.reshape(b, s, n_kv)
    length = jnp.asarray(length, jnp.int32).reshape((1,))

    kernel = functools.partial(
        _decode_kernel, n_kv=n_kv, group=group, head_dim=head_dim,
        block_k=block_k, softmax_scale=softmax_scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, num_kb),
        in_specs=[
            pl.BlockSpec((1, n_heads, head_dim), lambda bi, ki, length: (bi, 0, 0)),
            pl.BlockSpec((1, block_k, n_kv * head_dim),
                         lambda bi, ki, length: (bi, ki, 0)),
            pl.BlockSpec((1, block_k, n_kv), lambda bi, ki, length: (bi, ki, 0)),
            pl.BlockSpec((1, block_k, n_kv * head_dim),
                         lambda bi, ki, length: (bi, ki, 0)),
            pl.BlockSpec((1, block_k, n_kv), lambda bi, ki, length: (bi, ki, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, n_heads, head_dim), lambda bi, ki, length: (bi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((n_heads, 128), jnp.float32),
            pltpu.VMEM((n_heads, 128), jnp.float32),
            pltpu.VMEM((n_heads, head_dim), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_heads, head_dim), query.dtype),
        interpret=interpret,
        compiler_params=(
            None
            if interpret
            else pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            )
        ),
    )(length, query, kf, ks, vf, vs)
    return out


def _paged_decode_kernel(tables_ref, lengths_ref, q_ref, k_ref, ks_ref,
                         v_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr, *,
                         n_kv: int, group: int, head_dim: int,
                         block_size: int, softmax_scale: float):
    """Grid (slots, blocks-per-slot); logical block axis innermost and
    sequential. The index maps already routed this invocation's refs to
    the PHYSICAL block `tables[s, ki]`; in here only the LOGICAL
    position ``ki * block_size + row`` matters for masking.

    Refs: q (1, H, D); k/v (1, block_size, Hkv*D) int8; scales
    (1, sb, Hkv) f32 with sb == block_size (per-row) or 1 (per-block —
    broadcast over the rows). Scratch: m/l (H, 128) f32, acc (H, D) f32.
    """
    si = pl.program_id(0)
    ki = pl.program_id(1)
    num_k = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[si]
    pos = ki * block_size + lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1
    )
    live_row = pos < length  # (1, block_size)
    live_col = ki * block_size + lax.broadcasted_iota(
        jnp.int32, (block_size, 1), 0
    ) < length  # (block_size, 1), in its own layout (see _decode_kernel)

    @pl.when(ki * block_size < length)
    def _step():
        for h in range(n_kv):
            k_blk = k_ref[0, :, h * head_dim:(h + 1) * head_dim]
            v_blk = v_ref[0, :, h * head_dim:(h + 1) * head_dim]
            scale_k = ks_ref[0, :, h:h + 1]  # (sb, 1): broadcasts sb=1
            scale_v = vs_ref[0, :, h:h + 1]
            k_f = k_blk.astype(jnp.float32) * scale_k
            v_f = jnp.where(
                live_col, v_blk.astype(jnp.float32) * scale_v, 0.0
            )
            q_h = q_ref[0, h * group:(h + 1) * group, :].astype(jnp.float32)
            logits = lax.dot_general(
                q_h * softmax_scale, k_f, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (group, block_size)
            logits = jnp.where(live_row, logits, NEG_INF)

            rows = slice(h * group, (h + 1) * group)
            m_prev = m_scr[rows]
            m_blk = jnp.max(logits, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_blk, m_prev.shape))
            p = jnp.exp(logits - m_new[:, :1])
            corr = jnp.exp(m_prev - m_new)
            m_scr[rows] = m_new
            l_scr[rows] = l_scr[rows] * corr + jnp.broadcast_to(
                jnp.sum(p, axis=-1, keepdims=True), m_prev.shape
            )
            acc_scr[rows] = acc_scr[rows] * corr[:, :1] + lax.dot_general(
                p, v_f, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(ki == num_k - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)


def paged_int8_decode_attention(
    query: jax.Array,
    key_pool: jax.Array,
    key_scale: jax.Array,
    value_pool: jax.Array,
    value_scale: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    softmax_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-token decode attention straight off the paged int8 pool.

    query [S, H, D] (one token per slot), pools [NB, bs, Hkv, D] int8 +
    scales [NB, sb, Hkv, 1] f32 (sb = bs for per-row scales, 1 for
    per-block), block_tables [S, MB] int32 (physical block id per
    logical block; rows beyond a slot's length may point anywhere —
    those positions are masked), lengths [S] int32 -> [S, H, D] in
    `query`'s dtype. Per slot s this equals
    ``int8_decode_attention(q[s:s+1], gathered-dense cache, length[s])``
    without ever materializing the gathered cache: the block table rides
    in SMEM (scalar prefetch) and each grid step streams one physical
    block."""
    from jax.experimental.pallas import tpu as pltpu

    slots, n_heads, head_dim = query.shape
    nb, block_size, n_kv, _ = key_pool.shape
    _, max_blocks = block_tables.shape
    if query.size == 0 or max_blocks == 0:
        return jnp.zeros(query.shape, query.dtype)
    sb = key_scale.shape[1]
    if sb not in (block_size, 1) or value_scale.shape[1] != sb:
        raise ValueError(
            f"scale pools must carry per-row ({block_size}) or per-block "
            f"(1) scales; got key {key_scale.shape}, value "
            f"{value_scale.shape}"
        )
    group = n_heads // n_kv
    if softmax_scale is None:
        softmax_scale = head_dim**-0.5
    if interpret is None:
        from tf_yarn_tpu.ops._rowwise import default_interpret

        interpret = default_interpret()

    kf = key_pool.reshape(nb, block_size, n_kv * head_dim)
    vf = value_pool.reshape(nb, block_size, n_kv * head_dim)
    ks = key_scale.reshape(nb, sb, n_kv)
    vs = value_scale.reshape(nb, sb, n_kv)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32).reshape((slots,))

    kernel = functools.partial(
        _paged_decode_kernel, n_kv=n_kv, group=group, head_dim=head_dim,
        block_size=block_size, softmax_scale=softmax_scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, lengths -> SMEM
        grid=(slots, max_blocks),
        in_specs=[
            pl.BlockSpec((1, n_heads, head_dim),
                         lambda si, ki, tables, lengths: (si, 0, 0)),
            pl.BlockSpec((1, block_size, n_kv * head_dim),
                         lambda si, ki, tables, lengths:
                         (tables[si, ki], 0, 0)),
            pl.BlockSpec((1, sb, n_kv),
                         lambda si, ki, tables, lengths:
                         (tables[si, ki], 0, 0)),
            pl.BlockSpec((1, block_size, n_kv * head_dim),
                         lambda si, ki, tables, lengths:
                         (tables[si, ki], 0, 0)),
            pl.BlockSpec((1, sb, n_kv),
                         lambda si, ki, tables, lengths:
                         (tables[si, ki], 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, n_heads, head_dim),
            lambda si, ki, tables, lengths: (si, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((n_heads, 128), jnp.float32),
            pltpu.VMEM((n_heads, 128), jnp.float32),
            pltpu.VMEM((n_heads, head_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, n_heads, head_dim),
                                       query.dtype),
        interpret=interpret,
        compiler_params=(
            None
            if interpret
            else pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            )
        ),
    )(block_tables, lengths, query, kf, ks, vf, vs)


def paged_int8_window_attention(
    query: jax.Array,
    key_pool: jax.Array,
    key_scale: jax.Array,
    value_pool: jax.Array,
    value_scale: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    softmax_scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """W-token-window decode attention straight off the paged int8 pool
    — the speculative-verify companion of `paged_int8_decode_attention`.

    query [S, W, H, D] (W window positions per slot), pools/scales/
    block_tables as above, lengths [S] = each slot's valid length
    BEFORE the window. Precondition: the window's own K/V rows are
    already scattered into the pool at logical positions
    ``lengths[s] + w`` — window position ``w`` then attends causally
    over ``lengths[s] + w + 1`` pool positions (prefix + the window
    prefix up to and including itself), exactly the mask the sequential
    one-token path applies.

    Implementation: each (slot, window) pair becomes a *virtual slot*
    of the single-token kernel — query row ``s*W + w`` walks slot `s`'s
    block table with effective length ``lengths[s] + w + 1``. The pool
    streams block-by-block per virtual slot with the table in SMEM; no
    dense per-slot cache view is ever materialized. (The W queries of
    one slot re-stream that slot's blocks independently — acceptable
    for the small W speculative decoding uses; a multi-query kernel
    row-tiling the window is the follow-on if W grows.)"""
    slots, width, n_heads, head_dim = query.shape
    virtual_q = query.reshape(slots * width, n_heads, head_dim)
    virtual_tables = jnp.repeat(block_tables, width, axis=0)
    virtual_lengths = (
        lengths[:, None]
        + 1
        + jnp.arange(width, dtype=jnp.int32)[None, :]
    ).reshape(-1)
    out = paged_int8_decode_attention(
        virtual_q, key_pool, key_scale, value_pool, value_scale,
        virtual_tables, virtual_lengths,
        softmax_scale=softmax_scale, interpret=interpret,
    )
    return out.reshape(slots, width, n_heads, head_dim)
