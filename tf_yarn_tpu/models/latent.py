"""Latent-attention decoder with a learned key selection, window layers and
a sigmoid-routed expert layer — the `dots3_note` shape (dots3-note-prev).

    h'     = h + attn_kind(RMSNorm(h))
    h''    = h' + ffn(RMSNorm(h'))
    logits = RMSNorm(h_L) @ W_head                          (untied head)

*latent attention* (both kinds, each with its own sizes `AttentionSizes`),
on x = RMSNorm(h): `c_q = a_q RMSNorm(x W_qa)`; `[q_n | q_r] = c_q W_qb` per
head; `[c_raw | k_raw] = x W_kva`; `c = a_kv RMSNorm(c_raw)`; `q_r, k_r =
rope(q_r), rope(k_raw)` (one `k_r` for all heads; a rotary recipe a kind,
`AttentionSizes.recipe`: plain RoPE at the kind's theta, or YaRN's blended
frequencies, `transformer.RotaryRecipe`); `[k_n | v] = c W_kvb` per head;
`s = m^2 (q_n.k_n + q_r.k_r) / sqrt(d_n + d_r)` over the allowed keys (`m`
the kind's `mscale`: YaRN's, 1 without); `o = softmax(s) v`; on the kinds
the configuration lists as `gated`, `g = sigmoid(x W_g)`, one number a
head, and `attn = concat_h(g_h o_h) W_o`; on the others `attn =
concat_h(o_h) W_o`. `a_q`, `a_kv` are the variance rescales
(`rescale_latents`; 1 without). **What is cached a token and layer is the
row `[c | k_r]`**: `kv_rank + d_rope` numbers, no head axis, stored padded
with zeros to whole lanes (`row_multiple`).

Two paths that agree (`tests/test_latent.py`): the *expanded* one above for
any number of tokens from an empty cache (prefill, the plain forward), a
block of queries at a time, keys cut at the call's own tokens, so that no
`[heads, tokens, max_seq_len]` array exists; and the *absorbed* one for one
token against cached rows, `q~_h = W_kvb,K,h q_n,h`, `s = (q~_h.c_j +
q_r,h.k_r,j) / sqrt(.)`, `o_h = W_kvb,V,h^T sum_j p_j c_j`, which never
expands a cached row.

*allowed keys.* `sliding_attention`: `t - j < window` (the token itself
counts). `full_attention`: the `index_topk` largest of `I_tj = sum_i w_ti
relu(q^I_ti . k^I_j) / sqrt(index_heads index_dim)` over `j <= t` (all of
them while there are no more); `q^I = c_q W_iq`, `k^I = LayerNorm(x W_ik)`
(cached a token: `index_key`), `w = x W_iw`, the kind's rope on the first
`index_rope_dim` numbers of `q^I`, `k^I`. The selection is exact
(`jax.lax.top_k`), and its scores, like the router's and every norm, are
float32 at full precision: which keys and which experts are discrete
choices.

*ffn.* Layers below `first_dense`: `transformer.SwiGLU` of `d_ff_dense`.
Later layers: `moe.DroplessMoE` with sigmoid scores, selection under the
correction bias (over all experts, or inside the `topk_group` best of
`n_group` groups), the chosen scores normalised and scaled, one shared
expert.

The serving engine is told what each cache leaf is (`serving_contract`): a
full layer's `latent` and `index_key` rows are paged by token; a sliding
layer's `window_latent` is a `ring` of `ring_len` rows held once a slot
(row `p % ring_len` holds position p), so its bytes do not grow with the
context; a prefill writes it from the rows that end where the prompt does
(`prompt_len`), whatever pad follows. The one-token step (`paged_ctx`)
reads, on a full layer, the slot's live index keys a chunk of blocks at a
time (`indexer/scores`), picks (`indexer/topk`) and gathers only the chosen
rows (`indexer/gather`); on a sliding layer the ring (`window/read`). It
sows what it read into `cache_stats` for the slots `count_mask` marks.

A call of more than one token with `decode=True` is a prefill: it makes the
cache. Over a cache that is already there (the windowed step's gathered
view) it is refused by name.

A third kind, `plain_attention` (models/longcat.py), is the full kind's
sizes with nothing between a query and its keys: every `j <= t`, no
indexer, never a gate, one paged leaf (`latent`). Its one-token step hands the
absorbed query `[q~ | q_r]` to `ops.decode_attention.paged_decode_attention`
as 64 heads over one KV head whose keys and values are the same cached row
(`latent/read`): on a TPU the kernel that walks the slot's block table no
further than its length, elsewhere the plain gather.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tf_yarn_tpu.models.moe import DroplessMoE, ExpertRow
from tf_yarn_tpu.models.transformer import (
    EMBED,
    HEADS,
    RMSNorm,
    RotaryRecipe,
    SwiGLU,
    TransformerConfig,
    _partitioned,
    key_span,
    map_query_blocks,
    ring_after_prefill,
    ring_rows,
    ring_valid,
    span_width,
)
from tf_yarn_tpu.models.trunk import DecoderLM, LayerCall, ServingContract

HIGHEST = jax.lax.Precision.HIGHEST
FULL, SLIDING = "full_attention", "sliding_attention"
PLAIN = "plain_attention"
# What an attention layer sows into `cache_stats` a step, over the counted
# slots: rows live and rows read of each leaf, the keys selected, and how
# wide the selection's sort was (the slot's whole table, not its live keys).
READS = ("index_live", "index_read", "index_selected", "latent_read",
         "window_live", "window_read", "index_sorted")
# What a plain layer sows: it reads every live row.
PLAIN_READS = ("latent_live", "latent_read")


@dataclasses.dataclass(frozen=True)
class AttentionSizes:
    n_heads: int
    q_rank: int
    kv_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    rope_theta: float
    # YaRN over `rope_theta` (its `theta`; `rotary_dim` is set where it is
    # used, `recipe`); None = plain RoPE.
    rotary: Optional[RotaryRecipe] = None
    # The softmax scale carries its square: YaRN's `0.1 mscale_all_dim
    # ln(factor) + 1`.
    mscale: float = 1.0

    @property
    def row_width(self) -> int:
        """What a token caches: `[c | k_r]`."""
        return self.kv_rank + self.d_rope

    @property
    def softmax_scale(self) -> float:
        return (self.d_nope + self.d_rope) ** -0.5 * self.mscale ** 2

    def recipe(self, n: int) -> RotaryRecipe:
        """The kind's positional function over `n` numbers: `q_r` and `k_r`
        (`d_rope`), and the front of the indexer's queries and keys."""
        if self.rotary is None:
            return RotaryRecipe(self.rope_theta, n)
        return dataclasses.replace(self.rotary, rotary_dim=n)


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    vocab_size: int = 152064
    d_model: int = 5120
    layer_types: Tuple[str, ...] = (FULL, FULL, SLIDING, SLIDING, SLIDING)
    max_seq_len: int = 6144
    norm_eps: float = 1e-5
    full: AttentionSizes = AttentionSizes(128, 1024, 512, 128, 64, 128, 8e7)
    sliding: AttentionSizes = AttentionSizes(64, 1024, 1024, 192, 64, 128, 5e4)
    rescale_latents: bool = True
    # The kinds whose heads pass a sigmoid gate (`plain_attention` never).
    gated: Tuple[str, ...] = (FULL, SLIDING)
    window: int = 513
    index_heads: int = 64
    index_dim: int = 128
    index_rope_dim: int = 64
    index_topk: int = 2048
    # ffn
    first_dense: int = 1
    d_ff_dense: int = 13824
    num_experts: int = 256
    num_experts_here: int = 256
    expert_offset: int = 0
    experts_per_token: int = 8
    d_expert: int = 1536
    d_shared: int = 1536
    norm_topk: bool = True
    routed_scale: float = 1.0
    # The router's groups, and how many of them a token may choose inside.
    n_group: int = 1
    topk_group: int = 1
    # Matrices are stored in `param_dtype`; norm scales, the LayerNorm of the
    # index keys and the router's bias stay float32.
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # Only "bf16": a latent row has no head axis for the int8 kernels' scales.
    kv_cache_dtype: str = "bf16"
    # Queries a block of the expanded path, and tokens of index keys a
    # chunk of the one-token step's scoring loop.
    query_block: int = 256
    index_chunk: int = 512
    # A cached row is stored padded to whole lanes of the chip's vector
    # registers: 576 -> 640, 1088 -> 1152. At 576 XLA relaid the whole pool
    # leaf on the way into and out of every step (5 of 25 ms on a v5e).
    row_multiple: int = 128

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_heads(self) -> int:
        """What `DecodeEngine(mesh=...)` checks against `tp`."""
        return math.gcd(self.full.n_heads, self.sliding.n_heads)

    @property
    def ring_len(self) -> int:
        """Rows of a sliding layer's ring: the window, rounded up to whole
        tiles of the cache's type."""
        return ring_rows(self.window)

    def sizes(self, kind: str) -> AttentionSizes:
        return self.sliding if kind == SLIDING else self.full

    def stored_width(self, kind: str) -> int:
        """A cached row `[c | k_r]` as it is stored: whole lanes."""
        return -(-self.sizes(kind).row_width // self.row_multiple) \
            * self.row_multiple

    def __post_init__(self):
        unknown = set(self.layer_types) - {FULL, SLIDING, PLAIN}
        if unknown or not self.layer_types or set(self.gated) - {FULL, SLIDING}:
            raise ValueError(
                f"layer_types: {self.layer_types!r}, gated: {self.gated!r}")
        if self.kv_cache_dtype != "bf16":
            raise ValueError(
                f"kv_cache_dtype={self.kv_cache_dtype!r}: a latent cache row "
                "[c | k_r] has no head axis for the int8 cache's per-head "
                "scales, and no int8 read of it exists; it is refused until "
                "one does (docs/Serving.md \"Latent, index and window "
                "leaves\")"
            )
        if self.index_rope_dim > self.index_dim or self.window < 1:
            raise ValueError("index_rope_dim > index_dim, or window < 1")

    def norm_config(self) -> TransformerConfig:
        """`transformer.RMSNorm` with its scale in float32."""
        return TransformerConfig(
            vocab_size=self.vocab_size, d_model=self.d_model,
            n_layers=self.n_layers, d_ff=self.d_ff_dense,
            max_seq_len=self.max_seq_len, norm_eps=self.norm_eps,
            dtype=self.dtype, param_dtype=jnp.float32,
        )

    def dense_config(self) -> TransformerConfig:
        """What `transformer.SwiGLU` reads."""
        return dataclasses.replace(
            self.norm_config(), param_dtype=self.param_dtype)

    @classmethod
    def tiny(cls, **overrides) -> "LatentConfig":
        defaults = dict(
            vocab_size=256, d_model=64, max_seq_len=64,
            layer_types=(FULL, FULL, SLIDING, SLIDING, SLIDING),
            full=AttentionSizes(4, 32, 16, 16, 8, 16, 8e7),
            sliding=AttentionSizes(2, 32, 32, 24, 8, 16, 5e4),
            window=5, index_heads=4, index_dim=16, index_rope_dim=8,
            index_topk=8, d_ff_dense=96, num_experts=16, num_experts_here=16,
            experts_per_token=3, d_expert=32, d_shared=32, query_block=8,
            index_chunk=16, row_multiple=8,
        )
        defaults.update(overrides)
        return cls(**defaults)


def rope(x, positions, recipe: RotaryRecipe):
    """Rotary embedding over the last dim of x [B, S, ..., n] at `positions`
    [B, S], by `recipe` (of `n` numbers): the pairs (2i, 2i + 1) turned;
    float32."""
    n = x.shape[-1]
    if recipe.rotary_dim != n:
        raise ValueError(f"a recipe of {recipe.rotary_dim} over {n} numbers")
    if recipe.factor:
        freqs = recipe.inv_freq()
    else:
        # Plain RoPE as it always was here, in float32 on the device: the
        # recipe's float64 table differs from it in the last bit.
        freqs = 1.0 / recipe.theta ** (
            jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    angles = positions.astype(jnp.float32).reshape(
        positions.shape + (1,) * (x.ndim - 2)) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if recipe.attention_factor != 1.0:
        cos, sin = (cos * recipe.attention_factor,
                    sin * recipe.attention_factor)
    x = x.astype(jnp.float32)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _rope_front(x, positions, recipe: RotaryRecipe):
    n = recipe.rotary_dim
    return jnp.concatenate(
        [rope(x[..., :n], positions, recipe), x[..., n:].astype(jnp.float32)],
        axis=-1)


def index_scores(q_index, weight, keys):
    """`I = sum_i w_i relu(q^I_i . k^I_j) / sqrt(heads dim)`, float32 at full
    precision. q_index [..., T, Hi, Di], weight [..., T, Hi], keys
    [..., J, Di] -> [..., T, J]."""
    heads, dim = q_index.shape[-2:]
    dots = jnp.einsum("...thd,...jd->...thj", q_index.astype(jnp.float32),
                      keys.astype(jnp.float32), precision=HIGHEST)
    return jnp.einsum("...th,...thj->...tj", weight.astype(jnp.float32),
                      nn.relu(dots), precision=HIGHEST) * (heads * dim) ** -0.5


def top_k_mask(score, k: int):
    """[..., J] bool: the `k` entries of each row that `jax.lax.top_k` picks
    (of equal scores the earlier), as a mask and without a scatter: every
    score above the k-th largest, and of those equal to it the first few."""
    kth = jax.lax.top_k(score, k)[0][..., -1:]
    above, ties = score > kth, score == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, axis=-1) <= room))


def expanded_attention(q_n, q_r, rows, w_kvb, sizes: AttentionSizes, *,
                       window: int = 0, select=None, query_block: int = 256,
                       dtype=jnp.bfloat16, prompt_len=None):
    """The expanded path over a call's own tokens, from position 0: q_n
    [B, S, H, d_n], q_r [B, S, H, d_r], rows [B, S, kv_rank + d_r] (as they
    are cached), w_kvb [kv_rank, H, d_n + d_v] -> [B, S, H, d_v] float32.
    `window` > 0 keeps `t - j < window`; `select` (q_index, weight, keys,
    top_k) keeps the indexer's `top_k` largest `j <= t`. A block of queries
    at a time, over the keys the block can see (`transformer.key_span`):
    the largest array is [B, H, query_block, W] float32, W = `span_width`
    (`query_block + window - 1` in whole lanes) under a window alone, S
    without one or with `select` (the top-k needs every causal key's
    score; the half above the diagonal is formed and masked).
    `prompt_len`: `map_query_blocks`' (rows past it come out zero)."""
    batch, s, heads, _ = q_n.shape
    c = rows[..., :sizes.kv_rank]
    k_r = rows[..., sizes.kv_rank:sizes.row_width]
    with jax.named_scope("latent/kv"):
        expanded = jnp.einsum("bjr,rhf->bjhf", c, w_kvb)
        k_n, v = expanded[..., :sizes.d_nope], expanded[..., sizes.d_nope:]
    block = min(query_block, s)
    pad = -s % block
    nb = (s + pad) // block

    def blocks(value):
        value = jnp.pad(value, [(0, 0), (0, pad)] + [(0, 0)] * (value.ndim - 2))
        return jnp.moveaxis(
            value.reshape((batch, nb, block) + value.shape[2:]), 1, 0)

    scale = sizes.softmax_scale
    width = s if select is not None else span_width(s, block, window)
    keys = jnp.arange(width)[None, :]

    def some_rows(args):
        start, qn_block, qr_block, *index_block = args
        at = (start + jnp.arange(block))[:, None]
        kn_seen, kr_seen, v_seen, seen = k_n, k_r, v, keys
        if width < s:
            offset, _ = key_span(start, s, block, window)
            kn_seen, kr_seen, v_seen = (
                jax.lax.dynamic_slice_in_dim(value, offset, width, axis=1)
                for value in (k_n, k_r, v))
            seen = offset + keys
        mask = jnp.broadcast_to(at >= seen, (batch, block, width))
        if window:
            mask &= at - seen < window
        if select is not None:
            with jax.named_scope("indexer/scores"):
                score = jnp.where(
                    mask, index_scores(*index_block, select[2]), -jnp.inf)
            with jax.named_scope("indexer/topk"):
                mask &= top_k_mask(score, min(select[3], s))
        with jax.named_scope("latent/scores"):
            scores = (jnp.einsum("bthd,bjhd->bhtj", qn_block, kn_seen,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bthd,bjd->bhtj", qr_block, kr_seen,
                                   preferred_element_type=jnp.float32)) * scale
            scores = jnp.where(mask[:, None], scores, -jnp.inf)
            weights = jax.nn.softmax(scores, axis=-1)
        with jax.named_scope("latent/values"):
            return jnp.einsum("bhtj,bjhd->bthd", weights.astype(dtype),
                              v_seen, preferred_element_type=jnp.float32)

    inputs = [jnp.arange(nb) * block, blocks(q_n.astype(dtype)),
              blocks(q_r.astype(dtype))]
    if select is not None:
        inputs += [blocks(select[0]), blocks(select[1])]
    out = map_query_blocks(some_rows, tuple(inputs), block, prompt_len)
    return jnp.moveaxis(out, 0, 1).reshape(
        batch, nb * block, heads, sizes.d_v)[:, :s]


def absorb_query(q_n, q_r, w_kvb, sizes: AttentionSizes, width: int,
                 dtype=jnp.bfloat16):
    """`[q~ | q_r]` padded with zeros to a cached row's `width`: q_n
    [B, H, d_n], q_r [B, H, d_r], w_kvb [kv_rank, H, d_n + d_v] ->
    [B, H, width]. A cached row is [c | k_r], so one product against it
    gives both terms of the score and the row is never sliced."""
    with jax.named_scope("latent/absorb"):
        q_abs = jnp.einsum("bhn,rhn->bhr", q_n.astype(dtype),
                           w_kvb[..., :sizes.d_nope],
                           preferred_element_type=jnp.float32)
        query = jnp.concatenate([q_abs, q_r], axis=-1).astype(dtype)
        return jnp.pad(query, [(0, 0), (0, 0), (0, width - query.shape[-1])])


def expand_values(mixed, w_kvb, sizes: AttentionSizes, dtype=jnp.bfloat16):
    """Values out of the attended latents: mixed [B, H, >= kv_rank] (a
    head's softmax-weighted sum of cached rows) -> [B, H, d_v] float32."""
    with jax.named_scope("latent/values"):
        return jnp.einsum("bhr,rhv->bhv",
                          mixed[..., :sizes.kv_rank].astype(dtype),
                          w_kvb[..., sizes.d_nope:],
                          preferred_element_type=jnp.float32)


def absorbed_attention(q_n, q_r, rows, valid, w_kvb, sizes: AttentionSizes,
                       dtype=jnp.bfloat16):
    """One token a row against cached rows, which are never expanded: q_n
    [B, H, d_n], q_r [B, H, d_r], rows [B, K, kv_rank + d_r], valid [B, K],
    w_kvb [kv_rank, H, d_n + d_v] -> [B, H, d_v] float32."""
    query = absorb_query(q_n, q_r, w_kvb, sizes, rows.shape[-1], dtype)
    with jax.named_scope("latent/scores"):
        scores = jnp.einsum(
            "bhw,bkw->bhk", query, rows, preferred_element_type=jnp.float32
        ) * sizes.softmax_scale
        scores = jnp.where(valid[:, None, :], scores, -jnp.inf)
        weights = jax.nn.softmax(scores, axis=-1)
    with jax.named_scope("latent/values"):
        mixed = jnp.einsum("bhk,bkw->bhw", weights.astype(dtype), rows,
                           preferred_element_type=jnp.float32)
    return expand_values(mixed, w_kvb, sizes, dtype)


def select_rows(q_index, weight, index_pool, latent_pool, tables, lengths,
                top_k: int, chunk_tokens: int):
    """The two-stage read of a full layer's one-token step. Score each
    slot's live index keys, a chunk of blocks at a time and no further than
    the longest slot reaches; keep the `top_k` largest `j < length` (exact:
    a stable sort, of equal scores the earlier key, as `jax.lax.top_k`
    orders them), each score carrying its row of the pool so that no table
    is looked up afterwards; gather those latent rows and no others.

    q_index [S, Hi, Di], weight [S, Hi] float32; index_pool [NB, bs, Di],
    latent_pool [NB, bs, W]; tables [S, MB]; lengths [S] (this step's row
    included: the keys are `j < length`).
    -> rows [S, K, W], valid [S, K], the chosen rows of the pool [S, K],
    index rows read a slot, keys sorted a slot (the table's width)."""
    slots, max_blocks = tables.shape
    block_size = index_pool.shape[1]
    per_chunk = max(1, min(chunk_tokens // block_size, max_blocks))
    n_chunks = -(-max_blocks // per_chunk)
    chunk = per_chunk * block_size
    tables = jnp.pad(tables, [(0, 0), (0, n_chunks * per_chunk - max_blocks)])
    total = n_chunks * chunk
    trips = (jnp.max(lengths) + chunk - 1) // chunk

    def score_chunk(state):
        at, scores, places = state
        with jax.named_scope("indexer/scores"):
            ids = jax.lax.dynamic_slice_in_dim(tables, at * per_chunk,
                                               per_chunk, axis=1)
            keys = index_pool[ids].reshape(slots, chunk, -1)
            part = index_scores(q_index[:, None], weight[:, None], keys)[:, 0]
            place = (ids[:, :, None] * block_size
                     + jnp.arange(block_size)).reshape(slots, chunk)
            return (at + 1,
                    jax.lax.dynamic_update_slice_in_dim(
                        scores, part, at * chunk, axis=1),
                    jax.lax.dynamic_update_slice_in_dim(
                        places, place, at * chunk, axis=1))

    _, scores, places = jax.lax.while_loop(
        lambda state: state[0] < trips, score_chunk,
        (jnp.zeros((), jnp.int32),
         jnp.full((slots, total), -jnp.inf, jnp.float32),
         jnp.zeros((slots, total), jnp.int32)))
    with jax.named_scope("indexer/topk"):
        k = min(top_k, total)
        dead = jnp.arange(total)[None, :] >= lengths[:, None]
        falling, places = jax.lax.sort(
            (jnp.where(dead, jnp.inf, -scores), places), num_keys=1,
            is_stable=True)
        valid, chosen = falling[:, :k] < jnp.inf, places[:, :k]
    with jax.named_scope("indexer/gather"):
        rows = latent_pool.reshape(-1, latent_pool.shape[-1])[chosen]
    return rows, valid, chosen, trips * chunk, total


class LatentAttention(nn.Module):
    config: LatentConfig
    kind: str
    decode: bool = False

    @nn.compact
    def __call__(self, x, paged_ctx=None, count_mask=None, prompt_len=None):
        cfg, sizes = self.config, self.config.sizes(self.kind)
        full, plain = self.kind == FULL, self.kind == PLAIN
        batch, s, d = x.shape
        heads = sizes.n_heads
        f32, dtype = jnp.float32, cfg.dtype
        normal = nn.initializers.lecun_normal()
        # The latents' norms hand float32 on: one rounding, after the rescale.
        norm_cfg = dataclasses.replace(cfg.norm_config(), dtype=f32)
        one_token = self.decode and s == 1
        paged = one_token and paged_ctx is not None
        if self.decode and s != 1 and (
                paged_ctx is not None
                or self.has_variable("cache", "cache_index")):
            # Over the pool, or over a cache that is already there (the
            # gathered view of the windowed step): not a prefill.
            raise NotImplementedError(
                f"the paged step of {type(self).__name__} reads one token a "
                f"slot; a window of {s} (speculation, chunked prefill) does "
                "not carry the latent, index and ring leaves"
            )

        def matrix(name, shape, names):
            return self.param(name, _partitioned(names)(normal), shape,
                              cfg.param_dtype).astype(dtype)

        def rescale(rank):
            return math.sqrt(d / rank) if cfg.rescale_latents else 1.0

        # Where this call's tokens stand: a slot's length in the paged step,
        # `cache_index` in a one-token call on a dense cache, 0 otherwise.
        index_var = None
        if paged:
            lengths = paged_ctx.lengths.astype(jnp.int32)
        elif self.decode:
            index_var = self.variable("cache", "cache_index",
                                      lambda: jnp.zeros((), jnp.int32))
            lengths = jnp.broadcast_to(
                index_var.value if one_token else 0, (batch,)).astype(jnp.int32)
        else:
            lengths = jnp.zeros((batch,), jnp.int32)
        positions = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        turn = sizes.recipe(sizes.d_rope)
        index_turn = sizes.recipe(cfg.index_rope_dim)

        with jax.named_scope("latent/q"):
            c_q = jnp.einsum("bsd,dr->bsr", x, matrix(
                "q_a", (d, sizes.q_rank), (EMBED, None)),
                preferred_element_type=f32)
            c_q = (rescale(sizes.q_rank) * RMSNorm(norm_cfg, name="q_norm")(
                c_q)).astype(dtype)
            q = jnp.einsum("bsr,rf->bsf", c_q, matrix(
                "q_b", (sizes.q_rank, heads * (sizes.d_nope + sizes.d_rope)),
                (None, HEADS)), preferred_element_type=f32).reshape(
                batch, s, heads, sizes.d_nope + sizes.d_rope)
            q_n = q[..., :sizes.d_nope]
            q_r = rope(q[..., sizes.d_nope:], positions, turn)
        with jax.named_scope("latent/kv"):
            kv = jnp.einsum("bsd,df->bsf", x, matrix(
                "kv_a", (d, sizes.row_width), (EMBED, None)),
                preferred_element_type=f32)
            c = rescale(sizes.kv_rank) * RMSNorm(norm_cfg, name="kv_norm")(
                kv[..., :sizes.kv_rank])
            k_r = rope(kv[..., sizes.kv_rank:], positions, turn)
            rows = jnp.concatenate([c, k_r], axis=-1).astype(dtype)
            rows = jnp.pad(rows, [(0, 0), (0, 0), (
                0, cfg.stored_width(self.kind) - sizes.row_width)])
            w_kvb = matrix(
                "kv_b", (sizes.kv_rank, heads * (sizes.d_nope + sizes.d_v)),
                (None, HEADS)).reshape(sizes.kv_rank, heads, -1)
        if full:
            with jax.named_scope("indexer/q"):
                q_index = jnp.einsum("bsr,rf->bsf", c_q, matrix(
                    "index_q", (sizes.q_rank, cfg.index_heads * cfg.index_dim),
                    (None, None)), preferred_element_type=f32).reshape(
                    batch, s, cfg.index_heads, cfg.index_dim)
                q_index = _rope_front(q_index, positions, index_turn)
                index_weight = jnp.einsum("bsd,dh->bsh", x, matrix(
                    "index_w", (d, cfg.index_heads), (EMBED, None)),
                    preferred_element_type=f32)
            with jax.named_scope("indexer/k"):
                k_index = jnp.einsum("bsd,df->bsf", x, matrix(
                    "index_k", (d, cfg.index_dim), (EMBED, None)),
                    preferred_element_type=f32)
                k_index = nn.LayerNorm(
                    epsilon=cfg.norm_eps, dtype=f32, param_dtype=f32,
                    name="index_k_norm")(k_index)
                k_index = _rope_front(
                    k_index, positions, index_turn).astype(dtype)

        names = PLAIN_READS if plain else READS
        reads = dict.fromkeys(names, 0)
        if one_token:
            counted = jnp.ones((batch,), bool) if count_mask is None \
                else count_mask
            if plain:
                out, read = self._step_plain(
                    q_n[:, 0], q_r[:, 0], rows[:, 0], w_kvb, lengths,
                    paged_ctx, counted)
                reads.update(read)
            elif full:
                out, read = self._step_full(
                    q_n[:, 0], q_r[:, 0], q_index[:, 0], index_weight[:, 0],
                    rows[:, 0], k_index[:, 0], w_kvb, lengths, paged_ctx,
                    counted)
                reads.update(read, index_live=jnp.sum(
                    jnp.where(counted, lengths + 1, 0)))
            else:
                out = self._step_window(q_n[:, 0], q_r[:, 0], rows[:, 0],
                                        w_kvb, lengths)
                reads.update(
                    window_live=jnp.sum(jnp.where(
                        counted, jnp.minimum(lengths + 1, cfg.window), 0)),
                    window_read=jnp.sum(counted) * cfg.ring_len)
            out = out[:, None]
            if index_var is not None:
                index_var.value = index_var.value + 1
        else:
            if self.decode:
                self._write_prefill(
                    {"latent": rows, "index_key": k_index} if full
                    else {"latent": rows}, prompt_len)
                index_var.value = jnp.asarray(s, jnp.int32)
            select = None
            if full and s > cfg.index_topk:
                select = (q_index, index_weight, k_index, cfg.index_topk)
            out = expanded_attention(
                q_n, q_r, rows, w_kvb, sizes, select=select,
                window=cfg.window if self.kind == SLIDING else 0,
                query_block=cfg.query_block, dtype=dtype,
                prompt_len=prompt_len)
        if count_mask is not None and one_token:
            self.sow("cache_stats", "reads", jnp.stack(
                [jnp.asarray(reads[name], jnp.int32) for name in names]))

        if self.kind not in cfg.gated:
            out = out.astype(dtype)
        else:
            with jax.named_scope("latent/gate"):
                gate = nn.sigmoid(jnp.einsum("bsd,dh->bsh", x, matrix(
                    "gate", (d, heads), (EMBED, HEADS)),
                    preferred_element_type=f32))
                out = (out * gate[..., None]).astype(dtype)
        with jax.named_scope("latent/out"):
            return jnp.einsum(
                "bsf,fd->bsd", out.reshape(batch, s, heads * sizes.d_v),
                matrix("o", (heads * sizes.d_v, d), (HEADS, EMBED)),
                preferred_element_type=f32).astype(dtype)

    @nn.nowrap
    def _write_prefill(self, fresh, prompt_len=None):
        """A prefill's rows (`fresh`: leaf name -> [B, s, width]) into a
        fresh dense cache: a full or plain layer's at [0, s) of each leaf;
        of a sliding layer's the last `ring_len` that are the prompt's
        (it ends at `prompt_len`, a traced scalar, where the later rows
        are pad; None = at s) into the ring, position p at row
        `p % ring_len`."""
        cfg = self.config

        def put(name, value):
            self.variable("cache", name, lambda: value).value = value

        with jax.named_scope("latent/cache_write"):
            if self.kind == SLIDING:
                put("window_latent", ring_after_prefill(
                    fresh["latent"], cfg.ring_len, prompt_len))
                return
            for name, value in fresh.items():
                put(name, jnp.pad(value, [
                    (0, 0), (0, cfg.max_seq_len - value.shape[1]), (0, 0)]))

    @nn.nowrap
    def _step_full(self, q_n, q_r, q_index, index_weight, row, k_index,
                   w_kvb, lengths, paged_ctx, counted):
        """One token a slot on a full layer: write the token's rows, score
        the live index keys, gather the chosen latent rows, attend."""
        cfg, sizes = self.config, self.config.full
        unwrap, tables = self._write_token(
            {"latent": row, "index_key": k_index}, lengths, paged_ctx)
        rows, valid, chosen, index_read, index_sorted = select_rows(
            q_index, index_weight, unwrap["index_key"], unwrap["latent"],
            tables, lengths + 1, cfg.index_topk, cfg.index_chunk)
        # for the tests, which ask for `intermediates`: rows of the pool
        self.sow("intermediates", "selected", jnp.where(valid, chosen, -1))
        out = absorbed_attention(q_n, q_r, rows, valid, w_kvb, sizes,
                                 cfg.dtype)
        return out, {
            "index_read": jnp.sum(counted) * index_read,
            "index_sorted": jnp.sum(counted) * index_sorted,
            "index_selected": jnp.sum(valid & counted[:, None]),
            "latent_read": jnp.sum(counted) * rows.shape[1]}

    @nn.nowrap
    def _write_token(self, fresh, lengths, paged_ctx):
        """This token's rows (`fresh`: leaf name -> [slots, width]) into the
        slots' blocks at their lengths. -> (name -> the written leaf
        [NB, bs, width], tables [slots, MB]). The leaves are the `kv_pool`
        collection's in the paged step; elsewhere the dense cache, taken as
        a pool of one block a row."""
        cfg = self.config
        batch = lengths.shape[0]
        if paged_ctx is not None:
            def _missing():
                raise ValueError(
                    "the paged step needs the kv_pool collection (the "
                    "engine's paged_state_step provides it)")

            pools = {name: self.variable("kv_pool", name, _missing)
                     for name in fresh}
            tables = paged_ctx.tables
            unwrap = {name: var.value[0] for name, var in pools.items()}
        else:
            # The dense cache as a pool of one block a row.
            pools = {name: self.variable(
                "cache", name, lambda w=value.shape[-1]: jnp.zeros(
                    (batch, cfg.max_seq_len, w), cfg.dtype))
                for name, value in fresh.items()}
            tables = jnp.arange(batch, dtype=jnp.int32)[:, None]
            unwrap = {name: var.value for name, var in pools.items()}
        block_size = unwrap["latent"].shape[1]
        max_blocks = tables.shape[1]
        logical = lengths // block_size
        # A row past the slot's blocks goes to the trash block 0.
        blocks = jnp.where(
            logical < max_blocks, jnp.take_along_axis(
                tables, jnp.clip(logical, 0, max_blocks - 1)[:, None],
                axis=1)[:, 0], 0)
        with jax.named_scope("latent/cache_write"):
            for name in unwrap:
                unwrap[name] = unwrap[name].at[
                    blocks, lengths % block_size].set(fresh[name])
                pools[name].value = unwrap[name][None] \
                    if paged_ctx is not None else unwrap[name]
        return unwrap, tables

    @nn.nowrap
    def _step_plain(self, q_n, q_r, row, w_kvb, lengths, paged_ctx, counted):
        """One token a slot on a plain layer: write the token's row, then
        every live row of the slot off the pool through its table, as 64
        heads over one KV head whose keys and values are the same row."""
        from tf_yarn_tpu.ops.decode_attention import (
            paged_chunk_tokens,
            paged_decode_attention,
            paged_kernel_serves,
        )

        cfg, sizes = self.config, self.config.sizes(PLAIN)
        unwrap, tables = self._write_token({"latent": row}, lengths, paged_ctx)
        pool = unwrap["latent"][:, :, None, :]        # [NB, bs, 1, width]
        block_size, max_blocks = pool.shape[1], tables.shape[1]
        kernel = False if paged_ctx is None else paged_ctx.kernel
        if kernel is None:
            kernel = paged_kernel_serves(pool)
        query = absorb_query(q_n, q_r, w_kvb, sizes, row.shape[-1], cfg.dtype)
        with jax.named_scope("latent/read"):
            mixed = paged_decode_attention(
                query, pool, pool, tables, lengths + 1,
                sizes.softmax_scale, kernel=kernel)
        # The kernel reads a slot's length rounded up to its chunk; the
        # plain gather the whole table.
        chunk = paged_chunk_tokens(block_size, max_blocks) if kernel \
            else block_size * max_blocks
        live = jnp.where(counted, lengths + 1, 0)
        return expand_values(mixed, w_kvb, sizes, cfg.dtype), {
            "latent_live": jnp.sum(live),
            "latent_read": jnp.sum(-(-live // chunk) * chunk)}

    @nn.nowrap
    def _step_window(self, q_n, q_r, row, w_kvb, lengths):
        """One token a slot on a sliding layer: the token's row into the
        slot's ring, then the ring's rows that lie inside the window."""
        cfg, sizes = self.config, self.config.sliding
        batch, ring = row.shape[0], cfg.ring_len
        # One row a batch element, or (the paged step) a leading slot axis
        # over batch-1 rows: flattened here, restored on the way out.
        width = row.shape[-1]
        var = self.variable(
            "cache", "window_latent",
            lambda: jnp.zeros((batch, ring, width), cfg.dtype))
        with jax.named_scope("latent/cache_write"):
            held = var.value.reshape(batch, ring, width).at[
                jnp.arange(batch), lengths % ring].set(row)
            var.value = held.reshape(var.value.shape)
        with jax.named_scope("window/read"):
            valid = ring_valid(lengths, ring, cfg.window)
        return absorbed_attention(q_n, q_r, held, valid, w_kvb, sizes,
                                  cfg.dtype)


class LatentBlock(nn.Module):
    config: LatentConfig
    index: int
    decode: bool = False

    @nn.compact
    def __call__(self, x, call=LayerCall()):
        cfg = self.config
        norm_cfg = cfg.norm_config()
        batch, t, d = x.shape
        x = x + LatentAttention(
            cfg, cfg.layer_types[self.index], self.decode, name="attn")(
            RMSNorm(norm_cfg, name="attn_norm")(x), call.paged_ctx,
            call.count_mask, call.prompt_len)
        normed = RMSNorm(norm_cfg, name="ffn_norm")(x)
        if self.index < cfg.first_dense:
            with jax.named_scope("mlp"):
                return x + SwiGLU(cfg.dense_config(), name="dense")(normed)
        moe = DroplessMoE(
            num_experts=cfg.num_experts, num_experts_here=cfg.num_experts_here,
            expert_offset=cfg.expert_offset, top_k=cfg.experts_per_token,
            d_expert=cfg.d_expert, d_shared=cfg.d_shared, scoring="sigmoid",
            norm_topk=cfg.norm_topk, routed_scale=cfg.routed_scale,
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="moe",
        )(normed.reshape(batch * t, d), call.count_mask)
        return x + moe.reshape(batch, t, d)


class LatentLM(DecoderLM):
    """`trunk.DecoderLM` over `LatentBlock`s, which keep their own
    positions; a prefill writes the rings where `prompt_len` ends."""

    config: LatentConfig
    block_positions = False

    @nn.nowrap
    def layer(self, index, **module):
        return LatentBlock(self.config, index, **module)

    def serving_contract(self):
        cfg = self.config
        # Row t of a prefill's cache depends on tokens <= t alone (causal
        # and window masks, the indexer's top-k over `j <= t`, per-token
        # dropless experts); a ring is written where `prompt_len` ends.
        return ServingContract(
            leaf_kinds={"latent": ("paged", -2), "index_key": ("paged", -2),
                        "window_latent": ("ring", None),
                        "cache_index": ("index", None)},
            prefill_layers=tuple(
                (cfg.window if kind == SLIDING else 0, cfg.query_block)
                for kind in cfg.layer_types),
            rows_causal=True, takes_prompt_len=True, counts=True,
            reads=READS, experts=ExpertRow.of(cfg))
