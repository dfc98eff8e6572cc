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

``paged_decode_attention`` is the one-token step's read of the pool as
the serving engine holds it: one op, two ways to execute it. The plain
one gathers every slot's whole table into a dense view and runs the dense
cache's attention on it; the kernel, for the bf16 pool (no scales), walks
each slot's table a chunk of pages at a time with double-buffered DMAs
and stops at the slot's length, so its work grows with the live blocks
and no view is ever built (an int8 pool goes to the kernel above).
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


# --------------------------------------------------------------------------
# The one-token paged step's attention over the bf16 pool
# --------------------------------------------------------------------------

# Pages a chunk: one loop trip DMAs this many blocks of K and of V and
# contracts them in one pair of matrix products. 8 pages of 16 tokens are
# 128 keys a trip; a slot reads its length rounded up to that.
PAGES_PER_CHUNK = 8


def paged_chunk_tokens(block_size: int, max_blocks: int) -> int:
    """Tokens the kernel reads a loop trip: a slot's read is its length
    rounded up to a multiple of this."""
    return min(PAGES_PER_CHUNK, max_blocks) * block_size


def paged_kernel_serves(key_pool) -> bool:
    """Whether a kernel reads this pool leaf [NB, bs, Hkv, D] here: on a
    TPU (`_rowwise.default_interpret`'s rule), and for a bf16 pool where
    one page's [bs * Hkv, D] rows tile the chip's vector registers whole
    (lanes of 128, bf16 rows in pairs of 8). Elsewhere the plain
    implementation serves. A pool sharded over devices is the caller's to
    refuse: a Pallas call cannot be partitioned."""
    from tf_yarn_tpu.ops._rowwise import default_interpret

    _, block_size, n_kv, head_dim = key_pool.shape
    if default_interpret():
        return False
    if key_pool.dtype == jnp.int8:
        return True
    return (
        key_pool.dtype == jnp.bfloat16
        and head_dim % 128 == 0
        and (block_size * n_kv) % 16 == 0
    )


def _paged_plain(query, key_pool, value_pool, tables, lengths, softmax_scale,
                 key_scale, value_scale):
    """Every slot's whole table gathered into a dense [S, MB * bs, Hkv, *]
    view, then the dense cache's attention a slot at its length
    (`xla_attention` with the causal mask there; `int8_decode_attention`
    where the pool is int8): the arithmetic the paged step had before the
    kernels, bit for bit."""
    from tf_yarn_tpu.ops.attention import xla_attention

    slots = query.shape[0]
    seq = tables.shape[1] * key_pool.shape[1]

    def view(pool):
        return jnp.take(pool, tables, axis=0).reshape(
            (slots, seq) + pool.shape[2:])

    with jax.named_scope("attention/kv_gather"):
        keys, values = view(key_pool), view(value_pool)
        if key_scale is not None:
            key_scale, value_scale = view(key_scale), view(value_scale)

    if key_scale is None:
        def one_slot(q, k, v, length):
            return xla_attention(
                q[None, None], k[None], v[None], causal=True,
                segment_offset=length - 1, softmax_scale=softmax_scale,
            )[0, 0]

        out = jax.vmap(one_slot)(query, keys, values, lengths)
        # A slot with nothing to attend to: the softmax over an all-masked
        # row is uniform over garbage. Zeros, as the kernels give.
        return jnp.where((lengths > 0)[:, None, None], out,
                         jnp.zeros((), out.dtype))

    def one_slot_int8(q, k, ks, v, vs, length):
        return int8_decode_attention(
            q[None], k[None], ks[None], v[None], vs[None], length,
            softmax_scale=softmax_scale,
        )[0]

    return jax.vmap(one_slot_int8)(
        query, keys, key_scale, values, value_scale, lengths)


def _paged_kernel(tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, *, pages: int, max_blocks: int,
                  block_size: int, n_kv: int, group: int,
                  softmax_scale: float):
    """Grid (slots,). One program is one slot: a loop over its live chunks
    of `pages` pages, the next chunk's DMAs in flight while this one is
    contracted.

    Refs: tables [S * MB] and lengths [S] in SMEM; q, o (1, H, D) in VMEM;
    k_hbm, v_hbm the pools [NB, bs * Hkv, D] where they live (one page =
    rows `token * Hkv + head`, contiguous); k_buf, v_buf
    (2, pages * bs * Hkv, D); sems (2, 2) = (K | V, buffer).

    All heads ride one matrix product: q [H, D] against a chunk's rows
    [pages * bs * Hkv, D] gives a logit for every (query head, token, KV
    head); the columns of a foreign KV head are masked like dead
    positions, so the second product sums over a head's own keys only.
    Seven eighths of the first product are thrown away and it is still
    a few microseconds: slicing one head's rows out of the page would
    be a sublane gather a token.
    """
    from jax.experimental.pallas import tpu as pltpu

    slot = pl.program_id(0)
    length = lengths_ref[slot]
    rows = block_size * n_kv                  # rows a page
    chunk_tokens = pages * block_size
    n_chunks = (length + chunk_tokens - 1) // chunk_tokens

    def copies(chunk, buf):
        first = slot * max_blocks + chunk * pages
        out = []
        for i in range(pages):
            page = tables_ref[first + i]
            dst = pl.ds(i * rows, rows)
            out.append(pltpu.make_async_copy(
                k_hbm.at[page], k_buf.at[buf, dst], sems.at[0, buf]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[page], v_buf.at[buf, dst], sems.at[1, buf]))
        return out

    @pl.when(n_chunks > 0)
    def _first():
        for copy in copies(0, 0):
            copy.start()

    n_heads, head_dim = q_ref.shape[1], q_ref.shape[2]
    q = q_ref[0]
    width = pages * rows
    # What a column of the logits is: (token of the chunk, KV head).
    col = lax.broadcasted_iota(jnp.int32, (n_heads, width), 1)
    own_head = (col % n_kv) == (
        lax.broadcasted_iota(jnp.int32, (n_heads, width), 0) // group)
    col_token = col // n_kv
    row_token = lax.broadcasted_iota(jnp.int32, (width, 1), 0) // n_kv

    def body(chunk, carry):
        m_prev, l_prev, acc = carry
        buf = chunk % 2

        @pl.when(chunk + 1 < n_chunks)
        def _next():
            for copy in copies(chunk + 1, 1 - buf):
                copy.start()

        for copy in copies(chunk, buf):
            copy.wait()
        left = length - chunk * chunk_tokens  # live tokens from here on
        logits = lax.dot_general(
            q, k_buf[buf], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * softmax_scale                         # [H, width]
        logits = jnp.where(own_head & (col_token < left), logits, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        # Dead rows of V are zeroed, not only weighted by p's exact 0:
        # what a dead block holds may be NaN, and 0 * NaN is NaN.
        v = v_buf[buf]
        v = jnp.where(row_token < left, v, jnp.zeros((), v.dtype))
        acc = acc * corr + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc

    # Every chunk the loop visits holds a live token of every head, so
    # m is finite from the first trip on and exp(m_prev - m_new) never
    # sees inf - inf.
    _, l, acc = lax.fori_loop(0, n_chunks, body, (
        jnp.full((n_heads, 1), NEG_INF, jnp.float32),
        jnp.zeros((n_heads, 1), jnp.float32),
        jnp.zeros((n_heads, head_dim), jnp.float32),
    ))
    # A slot of length 0 never entered the loop: zeros.
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(
    query: jax.Array,
    key_pool: jax.Array,
    value_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    softmax_scale: Optional[float] = None,
    *,
    key_scale: Optional[jax.Array] = None,
    value_scale: Optional[jax.Array] = None,
    kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """One token a slot against the paged pool, through the block table.

    query [S, H, D], pools [NB, bs, Hkv, D] (K and V: bf16 as the engine
    holds them, or int8 with `key_scale` / `value_scale` [NB, bs, Hkv, 1]),
    block_tables [S, MB] int32 (physical block of each logical block;
    entries past a slot's length may point anywhere valid), lengths [S]
    int32 (positions each slot attends over, this token's own row
    included: the caller wrote it first) -> [S, H, D] in `query`'s dtype.
    Softmax in f32; query head h reads KV head h // (H // Hkv); positions
    >= length get exactly zero weight whatever the block holds; a slot of
    length 0 returns zeros.

    `kernel` picks the implementation: None lets `paged_kernel_serves`
    decide from the backend and the pool, False is the plain gather + the
    dense cache's attention (what a sharded pool needs), True the Pallas
    kernel that walks the table (`_paged_kernel`; for an int8 pool
    `paged_int8_decode_attention`), in interpret mode off the TPU unless
    `interpret` says otherwise."""
    slots, n_heads, head_dim = query.shape
    nb, block_size, n_kv, _ = key_pool.shape
    max_blocks = block_tables.shape[1]
    if query.size == 0 or max_blocks == 0:
        return jnp.zeros(query.shape, query.dtype)
    if softmax_scale is None:
        softmax_scale = head_dim**-0.5
    block_tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32).reshape((slots,))
    if kernel is None:
        kernel = paged_kernel_serves(key_pool)
    if not kernel:
        return _paged_plain(query, key_pool, value_pool, block_tables,
                            lengths, softmax_scale, key_scale, value_scale)
    if key_scale is not None:
        return paged_int8_decode_attention(
            query, key_pool, key_scale, value_pool, value_scale,
            block_tables, lengths, softmax_scale=softmax_scale,
            interpret=interpret,
        )

    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        from tf_yarn_tpu.ops._rowwise import default_interpret

        interpret = default_interpret()
    pages = min(PAGES_PER_CHUNK, max_blocks)
    if max_blocks % pages:
        # Whole chunks only: the padding points at block 0, past every
        # slot's length.
        pad = pages - max_blocks % pages
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
        max_blocks += pad
    rows = block_size * n_kv
    body = functools.partial(
        _paged_kernel, pages=pages, max_blocks=max_blocks,
        block_size=block_size, n_kv=n_kv, group=n_heads // n_kv,
        softmax_scale=softmax_scale,
    )
    q_spec = pl.BlockSpec((1, n_heads, head_dim),
                          lambda si, tables, lengths: (si, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # tables (flat: SMEM pads 2-D rows), lengths
        grid=(slots,),
        in_specs=[
            q_spec,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, pages * rows, head_dim), key_pool.dtype),
            pltpu.VMEM((2, pages * rows, head_dim), value_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    with jax.named_scope("attention/paged_kernel"):
        # [NB, bs, Hkv, D] -> [NB, bs * Hkv, D]: the leaf's bytes as they
        # lie (rows of D in tiles of 8), not a copy.
        return pl.pallas_call(
            body,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(query.shape, query.dtype),
            interpret=interpret,
            name="paged_decode_attention",
            compiler_params=(
                None if interpret
                else pltpu.CompilerParams(dimension_semantics=("arbitrary",))
            ),
        )(block_tables.reshape(-1), lengths, query,
          key_pool.reshape(nb, rows, head_dim),
          value_pool.reshape(nb, rows, head_dim))
