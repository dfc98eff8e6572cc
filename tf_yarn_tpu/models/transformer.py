"""Decoder-only transformer family (llama-style) — the flagship model.

Covers BASELINE.json config 5 (Llama-3-8B LoRA fine-tune) and serves as
the `__graft_entry__` flagship. Nothing like it exists in the reference —
tf-yarn carries user models opaquely — so this is where the TPU-first
design pays: megatron tensor-parallel sharding annotations, sequence
(ring) attention seam, bf16 compute / f32 params, `lax.scan` over stacked
layers + per-layer remat for compile time and HBM, and LoRA adapters with
a frozen-base optimizer mask.

Architecture: RMSNorm pre-norm, RoPE positions, GQA, SwiGLU MLP — the
llama recipe.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tf_yarn_tpu.ops.attention import attention, xla_attention

# Logical axis names (mapped to mesh axes by parallel.sharding.LOGICAL_RULES).
EMBED = "embed"
HEADS = "heads"
KV = "kv"
MLP = "mlp"
VOCAB = "vocab"


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "xla"  # xla | flash | ring | ulysses | ulysses_flash
    scan_layers: bool = True
    remat: bool = True
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # Mixture-of-Experts (0 = dense SwiGLU). Experts shard over the `ep`
    # mesh axis (models/moe.py).
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # Fused pallas RMSNorm (ops/rmsnorm.py). Under the train loop's mesh
    # the kernel runs on each device's own rows (ops/_rowwise.per_shard),
    # feature dim whole. Opt-in.
    fused_norms: bool = False
    # KV-cache storage for autoregressive decode: "bf16" (exact) or
    # "int8" (per-row symmetric quantization via ops/quantize.py — halves
    # the cache's resident HBM, i.e. 2x context length per chip; stream
    # traffic is unchanged until a decode kernel reads int8 directly).
    kv_cache_dtype: str = "bf16"
    # GPipe schedule for the layer stack over the pp mesh axis: >0 sets the
    # microbatch count and routes the blocks through
    # parallel.pipeline.pipeline_apply (overlapped stages) instead of the
    # naive layer-sharded scan. Requires scan_layers=True, n_layers % pp
    # == 0, batch % microbatches == 0; train-path only (no decode/MoE).
    gpipe_microbatches: int = 0
    # Attention without positional encoding (`use_rope=False`) and with a
    # softmax scale other than head_dim**-0.5 (`attention_scale`): what a
    # hybrid model's attention layers ask for (models/hybrid.py). The
    # defaults are the llama recipe.
    use_rope: bool = True
    attention_scale: Optional[float] = None

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def llama3_8b(cls, **overrides) -> "TransformerConfig":
        return cls(
            vocab_size=128256,
            d_model=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=8,
            d_ff=14336,
            max_seq_len=8192,
            rope_theta=500000.0,
            **overrides,
        )

    @classmethod
    def tiny(cls, **overrides) -> "TransformerConfig":
        defaults = dict(
            vocab_size=256,
            d_model=64,
            n_layers=2,
            n_heads=4,
            n_kv_heads=2,
            d_ff=128,
            max_seq_len=128,
        )
        defaults.update(overrides)
        return cls(**defaults)


# The decode cache `Attention` keeps, by leaf name: (kind, sequence axis
# from the end of the shape). Shared by every model built on `Attention`.
# A model declares its own leaves the same way (its contract's `leaf_kinds`);
# the kinds are the engine's (`decode_engine.py`): `paged` by token (a head
# axis may follow the sequence axis, or none: a latent row), `slot` (state
# held once a slot), `ring` (a window layer's last rows, once a slot), `index`.
CACHE_LEAF_KINDS = {
    "cached_key": ("paged", -3),
    "cached_value": ("paged", -3),
    "cached_key_scale": ("paged", -3),
    "cached_value_scale": ("paged", -3),
    "cache_index": ("index", None),
}


# What `Attention` sows into `cache_stats` a one-token step, over the counted
# slots: rows live and rows read of the pool (a full layer), of a ring (a
# window layer). A model built on it names them as its contract's `reads`.
ATTENTION_READS = ("pool_live", "pool_read", "window_live", "window_read")


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["tables", "lengths"], meta_fields=["kernel"],
)
@dataclasses.dataclass(frozen=True)
class PagedContext:
    """What a paged decode call knows of the slots beside the pool:
    block `tables` [S, MB] and `lengths` [S] before this call's rows
    (traced), and which implementation reads a bf16 pool (`kernel`,
    static: None = `ops.decode_attention.paged_kernel_serves` decides
    from the backend and the shapes, False = the plain gather a sharded
    pool needs)."""

    tables: Any
    lengths: Any
    kernel: Optional[bool] = None


def _partitioned(names):
    return lambda init: nn.with_partitioning(init, names)


# Scopes on the model's device operations (docs/Observability.md "Device
# operations by scope"): `jax.named_scope` only adds to the operations'
# metadata, so a profile can say "attention/scores" where the operation's
# own name is its shape. The names are read by cellbench's step_*_share
# metrics; Flax adds the module path (layer_3/block/attn/...) around them.


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over the last dim of [B, S, H, D]."""
    with jax.named_scope("attention/rope"):
        d = x.shape[-1]
        freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angles = positions[:, :, None, None].astype(jnp.float32) * freqs  # [B,S,1,D/2]
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        x1, x2 = x[..., 0::2], x[..., 1::2]
        rx1 = x1 * cos - x2 * sin
        rx2 = x2 * cos + x1 * sin
        out = jnp.stack([rx1, rx2], axis=-1).reshape(x.shape)
        return out.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class RotaryRecipe:
    """One kind of layer's positional function: `rotary_dim` leading numbers
    of a head turn at `theta`, the rest pass. `factor` > 0 is YaRN
    (arXiv:2309.00071, as `transformers` computes it): the frequencies
    between the correction dims of `beta_fast` and `beta_slow` turns over
    `original_max` positions blend from their own to a `factor`-th of it,
    and cos and sin carry `attention_factor`. Shared by the grouped-query
    layers of models/laguna.py and the latent layers of models/latent.py."""

    theta: float
    rotary_dim: int
    factor: float = 0.0
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def correction_range(self) -> Tuple[int, int]:
        """(low, high): the pairs below `low` keep their frequency, those
        from `high` on are interpolated."""
        n = self.rotary_dim

        def dim_of(turns):
            return n * math.log(self.original_max / (turns * 2 * math.pi)) \
                / (2 * math.log(self.theta))

        return (max(math.floor(dim_of(self.beta_fast)), 0),
                min(math.ceil(dim_of(self.beta_slow)), n - 1))

    def inv_freq(self) -> np.ndarray:
        n = self.rotary_dim
        own = 1.0 / self.theta ** (np.arange(0, n, 2, dtype=np.float64) / n)
        if not self.factor:
            return own.astype(np.float32)
        low, high = self.correction_range()
        ramp = np.clip((np.arange(n // 2, dtype=np.float64) - low)
                       / max(high - low, 0.001), 0.0, 1.0)
        return (ramp * own / self.factor + (1.0 - ramp) * own).astype(
            np.float32)


RING_MULTIPLE = 16


def ring_rows(window: int) -> int:
    """Rows of a window layer's ring: the window, rounded up to whole tiles
    of the cache's type (row `p % rows` holds position p)."""
    return -(-window // RING_MULTIPLE) * RING_MULTIPLE


def ring_positions(newest, rows: int):
    """[..., rows]: the position each row of a ring holds once position
    `newest` (a scalar, or [..., 1]) is written: row i the largest
    p <= newest with p % rows == i, negative where there is none yet."""
    return newest - (newest - jnp.arange(rows)) % rows


def ring_valid(lengths, rows: int, window: int):
    """[B, rows] bool: which rows of a ring hold a position inside the window
    of the token at `lengths` [B] (its own row, just written, included)."""
    at = ring_positions(lengths[:, None], rows)
    return (at >= 0) & (lengths[:, None] - at < window)


def ring_after_prefill(fresh, rows: int, prompt_len=None):
    """The ring a prefill leaves: `fresh` [B, S, ...] are the rows of
    positions 0 .. S - 1, of which the first `prompt_len` are the prompt's
    and the rest pad (a traced scalar or an int; None = all S).
    -> [B, rows, ...]: `ring_positions` at the prompt's last token, zeros
    where a row has none: what a prefill of exactly `prompt_len` tokens
    leaves, and nothing of the pad."""
    last = (fresh.shape[1] if prompt_len is None else prompt_len) - 1
    at = ring_positions(last, rows)
    held = jnp.take(fresh, jnp.maximum(at, 0), axis=1)
    some = (at >= 0).reshape((1, rows) + (1,) * (fresh.ndim - 2))
    return jnp.where(some, held, jnp.zeros((), fresh.dtype))


def map_query_blocks(fn, blocks, block: int, prompt_len=None):
    """`jax.lax.map(fn, blocks)` over the blocks of `block` queries of a
    prefill (each entry of `blocks` leads with the block axis). Told where
    the prompt ends (`prompt_len`, a traced scalar), only the blocks that
    hold a prompt row are computed and the rest, all pad, stay zero: a
    padded prompt does not pay for its pad's attention."""
    if prompt_len is None:
        return jax.lax.map(fn, blocks)
    nb = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    at = lambda i: jax.tree_util.tree_map(lambda x: x[i], blocks)  # noqa: E731
    one = jax.eval_shape(fn, at(0))
    return jax.lax.fori_loop(
        0, jnp.minimum(-(-prompt_len // block), nb),
        lambda i, out: out.at[i].set(fn(at(i))),
        jnp.zeros((nb,) + one.shape, one.dtype))


SPAN_MULTIPLE = 128
# Queries a block of a prefill's attention where a layer names no other.
PREFILL_QUERY_BLOCK = 256


def span_width(s: int, block: int, window: int = 0) -> int:
    """How many keys a prefill's scores are formed over for one block of
    `block` queries of a call of `s` tokens: the `block + window - 1` keys
    its queries can see between them, rounded up to whole lanes; all `s`
    where there is no window or the call is no longer than that."""
    if not window:
        return s
    return min(s, -(-(block + window - 1) // SPAN_MULTIPLE) * SPAN_MULTIPLE)


def key_span(start, s: int, block: int, window: int = 0):
    """(offset, width): the keys `offset <= j < offset + width` hold every
    key inside the causal mask and the window of the queries `start <= t <
    start + block` (`start` may be traced; `width` is static, `span_width`):
    the span ends where the block ends and is kept inside the call."""
    width = span_width(s, block, window)
    return jnp.clip(start + block - width, 0, s - width), width


def prefill_key_pairs(s: int, kept: int, layers, told: bool):
    """(formed, visible) query-key pairs, a head, of one prefill of `s`
    tokens of which the first `kept` are the prompt's. `layers`: each
    attention layer's `(window, query_block)` (a model's
    `ServingContract.prefill_layers`). Formed: the computed blocks of queries
    times their `span_width` (`told`: the prefill is given `prompt_len`, so
    blocks past the prompt are not computed). Visible: the pairs inside the
    causal mask and the window of the prompt's rows. Host arithmetic on
    static facts; the model calls the same `span_width`."""
    formed = visible = 0
    for window, query_block in layers:
        block = min(query_block or s, s)
        blocks = -(-(kept if told else s) // block)
        formed += blocks * block * span_width(s, block, window)
        # row t sees min(t + 1, window) keys: a triangle, then full rows
        full = min(kept, window) if window else kept
        visible += full * (full + 1) // 2 + (kept - full) * full
    return formed, visible


def own_token_attention(q, k, v, *, window: int = 0,
                        softmax_scale: Optional[float] = None,
                        query_block: int = 256, prompt_len=None):
    """Causal attention of a call's own tokens, from position 0, a block of
    queries at a time: q [B, S, H, D], k, v [B, S, Hkv, D] -> [B, S, H, D]
    float32. `window` > 0 keeps the keys `t - j < window` (the query itself
    counts). Query head h reads KV head h // (H // Hkv). Scores are formed
    over the keys a block can see and no others (`key_span`): the largest
    array is [B, H, query_block, W] float32, W = `span_width`: S without a
    window (the causal half above the diagonal is formed and masked),
    `query_block + window - 1` in whole lanes with one.
    `prompt_len`: `map_query_blocks`' (rows past it come out zero)."""
    batch, s, heads, dim = q.shape
    n_kv = k.shape[2]
    scale = dim ** -0.5 if softmax_scale is None else softmax_scale
    block = min(query_block, s)
    pad = -s % block
    nb = (s + pad) // block
    grouped = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)]).reshape(
        batch, nb, block, n_kv, heads // n_kv, dim)
    width = span_width(s, block, window)
    keys = jnp.arange(width)[None, :]

    def some_rows(args):
        start, q_block = args
        at = (start + jnp.arange(block))[:, None]
        k_seen, v_seen, seen = k, v, keys
        if width < s:
            offset, _ = key_span(start, s, block, window)
            k_seen = jax.lax.dynamic_slice_in_dim(k, offset, width, axis=1)
            v_seen = jax.lax.dynamic_slice_in_dim(v, offset, width, axis=1)
            seen = offset + keys
        mask = at >= seen
        if window:
            mask &= at - seen < window
        with jax.named_scope("attention/scores"):
            scores = jnp.einsum("bqgrd,bkgd->bgrqk", q_block, k_seen,
                                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(mask, scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        with jax.named_scope("attention/values"):
            return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v_seen,
                              preferred_element_type=jnp.float32)

    out = map_query_blocks(
        some_rows, (jnp.arange(nb) * block, jnp.moveaxis(grouped, 1, 0)),
        block, prompt_len)
    return jnp.moveaxis(out, 0, 1).reshape(
        batch, nb * block, heads, dim)[:, :s]


class RMSNorm(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        scale = self.param(
            "scale", _partitioned((None,))(nn.initializers.ones), (x.shape[-1],),
            cfg.param_dtype,
        )
        with jax.named_scope("norm"):
            if cfg.fused_norms:
                from tf_yarn_tpu.ops.rmsnorm import rmsnorm

                return rmsnorm(x, scale, eps=cfg.norm_eps).astype(cfg.dtype)
            x32 = x.astype(jnp.float32)
            norm = x32 * jax.lax.rsqrt(
                jnp.mean(x32 * x32, axis=-1, keepdims=True) + cfg.norm_eps
            )
            return (norm * scale.astype(jnp.float32)).astype(cfg.dtype)


class LoraDense(nn.Module):
    """Dense with optional LoRA adapter: y = x @ W + scale * (x @ A) @ B.

    The base kernel carries logical names for TP; LoRA factors stay
    replicated (they're tiny). `lora_` prefix lets the optimizer mask
    freeze everything else (see `lora_label_tree`).
    """

    features: int
    kernel_names: tuple
    config: TransformerConfig
    use_bias: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        kernel = self.param(
            "kernel",
            _partitioned(self.kernel_names)(nn.initializers.lecun_normal()),
            (x.shape[-1], self.features),
            cfg.param_dtype,
        )
        y = jnp.einsum("...d,df->...f", x, kernel.astype(cfg.dtype))
        if cfg.lora_rank > 0:
            lora_a = self.param(
                "lora_a",
                nn.initializers.normal(stddev=0.02),
                (x.shape[-1], cfg.lora_rank),
                cfg.param_dtype,
            )
            lora_b = self.param(
                "lora_b",
                nn.initializers.zeros_init(),
                (cfg.lora_rank, self.features),
                cfg.param_dtype,
            )
            scale = cfg.lora_alpha / cfg.lora_rank
            y = y + scale * jnp.einsum(
                "...d,dr,rf->...f", x, lora_a.astype(cfg.dtype), lora_b.astype(cfg.dtype)
            )
        if self.use_bias:
            bias = self.param(
                "bias", nn.initializers.zeros_init(), (self.features,), cfg.param_dtype
            )
            y = y + bias.astype(cfg.dtype)
        return y


class Attention(nn.Module):
    """Grouped-query attention of the llama recipe. What a model whose
    attention differs by layer says of one layer (models/laguna.py) are
    properties of the module, and their defaults are the config's and the
    recipe's, so that a model that says nothing runs the program it ran:

    `n_heads`, `head_dim`: query heads and the width of a head, where they
    are not `config.n_heads` and `d_model // n_heads`. `rotary`: the
    positional function `(x [B, S, H, D], positions [B, S]) -> x` in place
    of `rope` at `config.rope_theta`. `gate`: `sigmoid(x W_g)`, one number
    a head (`wg` [d_model, H]) from the attention's own input, on each
    head's output before `wo`. `window` > 0: a query sees the keys
    `t - j < window`, and a decode call keeps the last rows in a ring
    (`window_key`, `window_value` [B, ring_rows(window), Hkv, D], position
    p at row `p % rows`: `ring` leaves to the serving engine) in place of
    `cached_key` / `cached_value`. A prefill from an empty cache (more than
    one token, not the int8 cache) always attends over its own tokens
    (`own_token_attention`: scores over the keys a block of queries can
    see, never over the `max_seq_len` rows of the cache it has just made);
    `query_block` is how many queries at a time, `PREFILL_QUERY_BLOCK` where
    a layer says 0. Attention over the cache is for a call onto a cache
    that is already there (a continuation, the speculative window) and for
    the int8 cache. Outside decode, `query_block` or `window` > 0 asks for
    `own_token_attention` in place of `config.attention_impl`.

    `count_mask` [B] marks the rows of a one-token decode call whose cache
    reads are sown into `cache_stats` (`ATTENTION_READS`: rows live and
    rows read, of the pool and of a ring)."""

    config: TransformerConfig
    decode: bool = False  # static: KV-cache path (see _ScanBody note)
    n_heads: Optional[int] = None
    head_dim: Optional[int] = None
    rotary: Optional[Callable] = None
    gate: bool = False
    window: int = 0
    query_block: int = 0

    @nn.compact
    def __call__(self, x, positions, paged_ctx=None, count_mask=None,
                 prompt_len=None):
        cfg = self.config
        decode = self.decode
        b, s, _ = x.shape
        n_heads = self.n_heads or cfg.n_heads
        head_dim = self.head_dim or cfg.head_dim
        with jax.named_scope("attention/qkv"):
            q = LoraDense(n_heads * head_dim, (EMBED, HEADS), cfg, name="wq")(x)
            k = LoraDense(cfg.n_kv_heads * head_dim, (EMBED, KV), cfg, name="wk")(x)
            v = LoraDense(cfg.n_kv_heads * head_dim, (EMBED, KV), cfg, name="wv")(x)
            q = q.reshape(b, s, n_heads, head_dim)
            k = k.reshape(b, s, cfg.n_kv_heads, head_dim)
            v = v.reshape(b, s, cfg.n_kv_heads, head_dim)
        own_tokens = self.window or self.query_block
        if decode and self.window:
            # A window layer keeps a ring and no row past it: prefill,
            # the one-token call on a dense cache and the paged step alike.
            out = self._ring_decode(q, k, v, paged_ctx, count_mask,
                                    prompt_len)
        elif decode and paged_ctx is not None:
            # Paged decode: the serving engine passed the KV block pool
            # (kv_pool collection) + per-slot tables and lengths. Rows
            # are the batch's slots, the s axis the one token (or the
            # int8 speculative window); no dense cache variables exist
            # on this path at all. A Pallas kernel reads the whole pool
            # and cannot be partitioned: tensor-parallel serving asks
            # for the plain read (`paged_ctx.kernel=False`) or refuses
            # (DecodeEngine.paged_spec_step).
            out = self._paged_decode(q, k, v, paged_ctx)
            if count_mask is not None and s == 1:
                self._sow_pool_reads(paged_ctx, count_mask)
        elif decode:
            # KV cache for autoregressive decoding: append this call's
            # keys/values at cache_index, attend against the whole cache
            # (future slots masked by the offset causal mask). Under
            # tensor-parallel serving the engine shards these cache
            # variables' kv-heads axis over `tp` (decode_engine.
            # kv_partition_spec) while wq/wo place by their HEADS
            # annotations — this body needs no sharding awareness: XLA
            # derives the per-device attention and inserts the wo/
            # w_down all-reduces from the placements alone.
            if cfg.kv_cache_dtype not in ("bf16", "int8"):
                raise ValueError(
                    f"kv_cache_dtype={cfg.kv_cache_dtype!r}: expected "
                    "'bf16' or 'int8'"
                )
            int8_cache = cfg.kv_cache_dtype == "int8"
            if int8_cache and cfg.attention_scale is not None:
                raise NotImplementedError(
                    "the int8 decode-attention kernel scales by "
                    "head_dim**-0.5; attention_scale needs kv_cache_dtype="
                    "'bf16'"
                )
            cache_shape = (b, cfg.max_seq_len, cfg.n_kv_heads, head_dim)
            store_dtype = jnp.int8 if int8_cache else cfg.dtype
            fresh = not self.has_variable("cache", "cache_index")
            cached_k = self.variable(
                "cache", "cached_key", lambda: jnp.zeros(cache_shape, store_dtype)
            )
            cached_v = self.variable(
                "cache", "cached_value",
                lambda: jnp.zeros(cache_shape, store_dtype),
            )
            if int8_cache:
                scale_shape = cache_shape[:-1] + (1,)
                k_scale = self.variable(
                    "cache", "cached_key_scale",
                    lambda: jnp.zeros(scale_shape, jnp.float32),
                )
                v_scale = self.variable(
                    "cache", "cached_value_scale",
                    lambda: jnp.zeros(scale_shape, jnp.float32),
                )
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
            idx = cache_index.value
            positions = idx + jnp.arange(s, dtype=jnp.int32)[None, :]
            positions = jnp.broadcast_to(positions, (b, s))
            q, k = self._rotate(q, positions), self._rotate(k, positions)

            def _append(var, rows):
                with jax.named_scope("attention/kv_write"):
                    var.value = jax.lax.dynamic_update_slice(
                        var.value, rows, (0, idx, 0, 0)
                    )

            if int8_cache:
                # Per-(position, head) rows over head_dim (ops/quantize.py
                # pallas kernel): half the resident cache HBM, 2x context
                # per chip.
                from tf_yarn_tpu.ops.quantize import (
                    dequantize_int8,
                    quantize_int8,
                )

                k_q, k_s = quantize_int8(k.astype(jnp.float32))
                v_q, v_s = quantize_int8(v.astype(jnp.float32))
                _append(cached_k, k_q)
                _append(cached_v, v_q)
                _append(k_scale, k_s)
                _append(v_scale, v_s)
            else:
                _append(cached_k, k.astype(cfg.dtype))
                _append(cached_v, v.astype(cfg.dtype))
            cache_index.value = idx + s
            if s > 1 and fresh and not int8_cache:
                # A prefill from an empty cache: its own tokens are all the
                # keys there are.
                out = own_token_attention(
                    q, k.astype(cfg.dtype), v.astype(cfg.dtype),
                    softmax_scale=cfg.attention_scale,
                    query_block=self.query_block or PREFILL_QUERY_BLOCK,
                    prompt_len=prompt_len)
            elif int8_cache and s == 1:
                # Steady-state decode: the pallas kernel streams the int8
                # cache directly, dequantizing tile-by-tile in VMEM
                # instead of materializing a full bf16 copy per token
                # (ops/decode_attention.py; measured at parity with the
                # dequant+xla path at B=1 — single-token decode is
                # latency-bound — while never paying the 2x materialized
                # cache).
                from tf_yarn_tpu.ops.decode_attention import (
                    int8_decode_attention,
                )

                out = int8_decode_attention(
                    q[:, 0], cached_k.value, k_scale.value,
                    cached_v.value, v_scale.value, idx + 1,
                )[:, None]
            else:
                if int8_cache:
                    # Prefill (s > 1): one-shot dequant, amortized over
                    # the whole prompt.
                    key_all = dequantize_int8(
                        cached_k.value, k_scale.value, cfg.dtype
                    )
                    value_all = dequantize_int8(
                        cached_v.value, v_scale.value, cfg.dtype
                    )
                else:
                    key_all, value_all = cached_k.value, cached_v.value
                out = xla_attention(
                    q, key_all, value_all, causal=True, segment_offset=idx,
                    softmax_scale=cfg.attention_scale,
                )
        else:
            q, k = self._rotate(q, positions), self._rotate(k, positions)
            if own_tokens:
                out = own_token_attention(
                    q, k, v, window=self.window,
                    softmax_scale=cfg.attention_scale,
                    query_block=self.query_block or s)
            else:
                out = attention(q, k, v, impl=cfg.attention_impl, causal=True,
                                softmax_scale=cfg.attention_scale)
        if self.gate:
            with jax.named_scope("attention/gate"):
                w_gate = self.param(
                    "wg", _partitioned((EMBED, HEADS))(
                        nn.initializers.lecun_normal()),
                    (x.shape[-1], n_heads), cfg.param_dtype)
                gate = nn.sigmoid(jnp.einsum(
                    "bsd,dh->bsh", x, w_gate.astype(cfg.dtype),
                    preferred_element_type=jnp.float32))
                out = out * gate[..., None]
        with jax.named_scope("attention/out"):
            out = out.astype(cfg.dtype).reshape(b, s, n_heads * head_dim)
            return LoraDense(cfg.d_model, (HEADS, EMBED), cfg, name="wo")(out)

    @nn.nowrap
    def _rotate(self, value, positions):
        """The layer's positional function on q or k [B, S, H, D]."""
        if self.rotary is not None:
            return self.rotary(value, positions)
        if self.config.use_rope:
            return rope(value, positions, self.config.rope_theta)
        return value

    @nn.nowrap
    def _sow_pool_reads(self, paged_ctx, count_mask):
        """What the one-token paged step read of the pool, over the counted
        slots: their live rows (this step's included), and those rounded up
        to the kernel's loop trip, or the whole table on the plain read."""
        from tf_yarn_tpu.ops.decode_attention import (
            paged_chunk_tokens,
            paged_kernel_serves,
        )

        pool = self.get_variable("kv_pool", "cached_key")[0]
        block_size, max_blocks = pool.shape[1], paged_ctx.tables.shape[1]
        kernel = paged_ctx.kernel
        if kernel is None:
            kernel = paged_kernel_serves(pool)
        chunk = paged_chunk_tokens(block_size, max_blocks) if kernel \
            else block_size * max_blocks
        live = jnp.where(count_mask, paged_ctx.lengths.astype(jnp.int32) + 1, 0)
        zero = jnp.zeros((), jnp.int32)
        self.sow("cache_stats", "reads", jnp.stack([
            jnp.sum(live), jnp.sum(-(-live // chunk) * chunk), zero, zero]))

    @nn.nowrap
    def _ring_decode(self, q, k, v, paged_ctx, count_mask, prompt_len=None):
        """A window layer's decode call. More than one token is a prefill
        from an empty cache: attend over the call's own tokens inside the
        window and leave the prompt's last rows in the ring (the prompt
        ends at `prompt_len`, a traced scalar, where the call's later
        tokens are pad; None = at the call's end). One token (a row of a
        dense cache at `cache_index`, or a slot of the paged step at its
        length: the rings then lead with a slot axis over batch-1 rows):
        the token's row into the ring at `p % rows`, then the ring's rows
        that lie inside the window."""
        cfg = self.config
        b, s, n_kv, head_dim = k.shape
        rows = ring_rows(self.window)
        if s != 1 and (paged_ctx is not None
                       or self.has_variable("cache", "cache_index")):
            raise NotImplementedError(
                f"a window layer of {type(self).__name__} reads one token a "
                f"slot from its ring; a window of {s} tokens over a cache "
                "that is already there (speculation, chunked prefill) does "
                "not carry the ring"
            )
        index_var = None
        if paged_ctx is not None:
            lengths = paged_ctx.lengths.astype(jnp.int32)
        else:
            index_var = self.variable("cache", "cache_index",
                                      lambda: jnp.zeros((), jnp.int32))
            lengths = jnp.broadcast_to(
                index_var.value if s == 1 else 0, (b,)).astype(jnp.int32)
        positions = lengths[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        q, k = self._rotate(q, positions), self._rotate(k, positions)
        k, v = k.astype(cfg.dtype), v.astype(cfg.dtype)
        if s != 1:
            with jax.named_scope("attention/ring_write"):
                for name, value in (("window_key", k), ("window_value", v)):
                    ring = ring_after_prefill(value, rows, prompt_len)
                    self.variable("cache", name, lambda r=ring: r).value = ring
            index_var.value = jnp.asarray(s, jnp.int32)
            return own_token_attention(
                q, k, v, window=self.window,
                softmax_scale=cfg.attention_scale,
                query_block=self.query_block or s, prompt_len=prompt_len)
        held = {}
        with jax.named_scope("attention/ring_write"):
            for name, value in (("window_key", k), ("window_value", v)):
                var = self.variable(
                    "cache", name,
                    lambda: jnp.zeros((b, rows, n_kv, head_dim), cfg.dtype))
                held[name] = var.value.reshape(b, rows, n_kv, head_dim).at[
                    jnp.arange(b), lengths % rows].set(value[:, 0])
                var.value = held[name].reshape(var.value.shape)
        if index_var is not None:
            index_var.value = index_var.value + 1
        with jax.named_scope("attention/window_read"):
            valid = ring_valid(lengths, rows, self.window)
            scale = head_dim ** -0.5 if cfg.attention_scale is None \
                else cfg.attention_scale
            grouped = q[:, 0].reshape(b, n_kv, -1, head_dim)
            scores = jnp.einsum("bgrd,bkgd->bgrk", grouped, held["window_key"],
                                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
            out = jnp.einsum("bgrk,bkgd->bgrd", probs, held["window_value"],
                             preferred_element_type=jnp.float32)
        if count_mask is not None:
            zero = jnp.zeros((), jnp.int32)
            self.sow("cache_stats", "reads", jnp.stack([
                zero, zero,
                jnp.sum(jnp.where(
                    count_mask, jnp.minimum(lengths + 1, self.window), 0)),
                jnp.sum(count_mask, dtype=jnp.int32) * rows]))
        return out.reshape(b, 1, -1, head_dim)

    @nn.nowrap  # a helper of __call__: no scope of its own on the operations
    def _paged_decode(self, q, k, v, paged_ctx):
        """Decode attention straight off the paged KV pool: rope at
        per-slot positions, scatter this call's K/V rows into their
        blocks, then attend through the block table — no dense per-slot
        cache view is built here, and no dense cache variables are
        created. The pool travels as the mutable ``kv_pool`` collection
        (per layer; elided index leaves stay host-side as the engine's
        ``lengths``); tables/lengths ride as the ``paged_ctx`` call
        argument, broadcast across layers.

        One token a slot is read by `paged_decode_attention` (a kernel
        that reads each slot's live blocks, or the plain gather, as
        `paged_ctx.kernel` and the backend say); the int8 speculative
        window by `paged_int8_window_attention`."""
        cfg = self.config
        if cfg.kv_cache_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"kv_cache_dtype={cfg.kv_cache_dtype!r}: expected "
                "'bf16' or 'int8'"
            )
        int8_pool = cfg.kv_cache_dtype == "int8"
        if int8_pool and (not cfg.use_rope or cfg.attention_scale is not None):
            raise NotImplementedError(
                "the int8 paged decode kernel applies rope and scales by "
                "head_dim**-0.5; use_rope=False / attention_scale need "
                "kv_cache_dtype='bf16'"
            )
        tables, lengths = paged_ctx.tables, paged_ctx.lengths
        slots, width = q.shape[0], q.shape[1]
        if not int8_pool and width != 1:
            raise NotImplementedError(
                "a bf16 pool is read one token a slot "
                f"(paged_decode_attention); a window of {width} needs "
                "decode_attention='gather'"
            )
        positions = (
            lengths[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
        )
        q, k = self._rotate(q, positions), self._rotate(k, positions)

        def _missing():
            raise ValueError(
                "paged decode needs the kv_pool collection (the engine's "
                "paged step provides it)"
            )

        names = ("cached_key", "cached_value") + (
            ("cached_key_scale", "cached_value_scale") if int8_pool else ())
        pool_vars = {
            name: self.variable("kv_pool", name, _missing) for name in names
        }
        block_size = pool_vars["cached_key"].value.shape[2]
        max_blocks = tables.shape[1]
        logical = positions // block_size
        # A row past the slot's reserved blocks (a rejected-draft
        # position) routes to the reserved trash block 0.
        blocks = jnp.take_along_axis(
            tables, jnp.clip(logical, 0, max_blocks - 1), axis=1
        )
        blocks = jnp.where(logical < max_blocks, blocks, 0).reshape(-1)
        offsets = (positions % block_size).reshape(-1)

        def scatter(name, rows):
            # Pool leaves keep the slot-row cache's vestigial batch-1
            # axis: [1, NB, bs, Hkv, *]. The leaf is donated: the rows
            # are written in place.
            var = pool_vars[name]
            with jax.named_scope("attention/kv_write"):
                pool = var.value[0]
                rows = rows.reshape((slots * width,) + rows.shape[2:])
                pool = pool.at[blocks, offsets].set(rows.astype(pool.dtype))
                var.value = pool[None]
                return pool

        from tf_yarn_tpu.ops import decode_attention

        if not int8_pool:
            key_pool = scatter("cached_key", k)
            value_pool = scatter("cached_value", v)
            scales = {}
        else:
            from tf_yarn_tpu.ops.quantize import quantize_int8

            k_q, k_s = quantize_int8(k.astype(jnp.float32))
            v_q, v_s = quantize_int8(v.astype(jnp.float32))
            key_pool = scatter("cached_key", k_q)
            value_pool = scatter("cached_value", v_q)
            scales = dict(key_scale=scatter("cached_key_scale", k_s),
                          value_scale=scatter("cached_value_scale", v_s))
        if width == 1:
            return decode_attention.paged_decode_attention(
                q[:, 0], key_pool, value_pool, tables, lengths + 1,
                cfg.attention_scale, kernel=paged_ctx.kernel, **scales,
            )[:, None]
        return decode_attention.paged_int8_window_attention(
            q, key_pool, scales["key_scale"], value_pool,
            scales["value_scale"], tables, lengths,
        )


class SwiGLU(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        gate = LoraDense(cfg.d_ff, (EMBED, MLP), cfg, name="w_gate")(x)
        up = LoraDense(cfg.d_ff, (EMBED, MLP), cfg, name="w_up")(x)
        return LoraDense(cfg.d_model, (MLP, EMBED), cfg, name="w_down")(
            nn.silu(gate) * up
        )


class Block(nn.Module):
    config: TransformerConfig
    decode: bool = False  # static: KV-cache path (see _ScanBody note)

    @nn.compact
    def __call__(self, x, positions, paged_ctx=None):
        cfg = self.config
        x = x + Attention(cfg, self.decode, name="attn")(
            RMSNorm(cfg, name="attn_norm")(x), positions, paged_ctx
        )
        normed = RMSNorm(cfg, name="mlp_norm")(x)
        with jax.named_scope("mlp"):
            if cfg.moe_experts > 0:
                from tf_yarn_tpu.models.moe import MoEMlp

                return x + MoEMlp(cfg, name="moe")(normed)
            return x + SwiGLU(cfg, name="mlp")(normed)


class _ScanBody(nn.Module):
    """Scan adapter: gives Block the (carry, out) protocol nn.scan wants,
    with remat applied per layer (activation memory ~ O(sqrt) instead of
    O(n_layers) — the HBM/FLOPs trade SURVEY's TPU notes call for)."""

    config: TransformerConfig
    # Static module field, not a call arg: scan lifting would trace (or
    # drop) an argument, and `decode` must stay a python bool.
    decode: bool = False

    @nn.compact
    def __call__(self, x, positions, paged_ctx=None):
        block_cls = (
            nn.remat(
                Block,
                policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            )
            if self.config.remat and not self.decode
            else Block
        )
        return (
            block_cls(self.config, self.decode, name="block")(
                x, positions, paged_ctx
            ),
            None,
        )


def _make_scanned(cfg: TransformerConfig):
    """The lifted layer-stack constructor, shared by the scan path and the
    GPipe path's init so both produce byte-identical param structure and
    sharding metadata (checkpoint interchangeability between schedules).

    intermediates rides along stacked so sown values (MoE aux loss)
    survive the scan lift; cache likewise stacks each layer's KV cache
    for decoding. The "layers" partition name maps the stacked axis onto
    the pp mesh axis (parallel.sharding.LOGICAL_RULES).
    """
    return nn.scan(
        _ScanBody,
        # kv_pool: the paged decode path's per-layer KV block pool slice
        # (absent everywhere else — an empty collection is free).
        variable_axes={"params": 0, "intermediates": 0, "cache": 0,
                       "kv_pool": 0},
        split_rngs={"params": True, "dropout": True},
        in_axes=nn.broadcast,
        length=cfg.n_layers,
        metadata_params={nn.PARTITION_NAME: "layers"},
    )


class Transformer(nn.Module):
    """tokens [B, S] int32 -> logits [B, S, vocab].

    `return_hidden=True` yields the pre-head hidden states [B, S, d]
    instead — the seam the chunked-vocab loss uses to avoid materializing
    the full [B, S, vocab] logits (models/common.lm_loss_chunked).
    """

    config: TransformerConfig

    def serving_contract(self):
        """What the serving engine reads of this model
        (`trunk.ServingContract`). Keys, values and their int8 scales are
        paged by token, the sequence axis third from the end of [..., seq,
        kv_heads, head_dim | 1]. Rows are causal under the dense per-token
        feed-forward; `MoEMlp` counts its capacity over the tokens of the
        call, pads included, so a pad could push a prompt token out of its
        expert. Nothing is counted and no ring is written."""
        from tf_yarn_tpu.models.trunk import ServingContract

        cfg = self.config
        return ServingContract(
            leaf_kinds=CACHE_LEAF_KINDS,
            prefill_layers=((0, PREFILL_QUERY_BLOCK),) * cfg.n_layers,
            rows_causal=cfg.moe_experts == 0, takes_prompt_len=False,
            counts=False)

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True,
                 return_hidden: bool = False, decode: bool = False,
                 paged_ctx=None):
        # deterministic accepted for loss-contract uniformity (this
        # decoder family carries no dropout). `paged_ctx` (a
        # `PagedContext`: block tables [S, MB], lengths [S]) switches
        # decode attention onto the paged path (Attention._paged_decode):
        # rows are serving slots, the kv_pool collection holds the
        # block pool.
        cfg = self.config
        embedding = self.param(
            "embedding",
            _partitioned((VOCAB, EMBED))(nn.initializers.normal(stddev=0.02)),
            (cfg.vocab_size, cfg.d_model),
            cfg.param_dtype,
        )
        with jax.named_scope("embed"):
            x = embedding.astype(cfg.dtype)[tokens]
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
        )

        if cfg.gpipe_microbatches > 0 and not decode:
            x = self._gpipe_layers(x, positions)
        elif cfg.scan_layers:
            x, _ = _make_scanned(cfg)(cfg, decode, name="layers")(
                x, positions, paged_ctx
            )
        else:
            for i in range(cfg.n_layers):
                x = _ScanBody(cfg, decode, name=f"layer_{i}")(
                    x, positions, paged_ctx
                )[0]

        x = RMSNorm(cfg, name="final_norm")(x)
        head = self.param(
            "lm_head",
            _partitioned((EMBED, VOCAB))(nn.initializers.normal(stddev=0.02)),
            (cfg.d_model, cfg.vocab_size),
            cfg.param_dtype,
        )
        if return_hidden:
            return x
        with jax.named_scope("lm_head"):
            return jnp.einsum(
                "bsd,dv->bsv", x, head.astype(cfg.dtype)
            ).astype(jnp.float32)

    def _gpipe_layers(self, x, positions):
        """Layer stack under the overlapped GPipe schedule
        (parallel.pipeline.pipeline_apply over the pp mesh axis).

        Parameters are created by (and stored identically to) the scan
        path — init runs the scanned blocks once — so checkpoints are
        interchangeable between schedules.
        """
        cfg = self.config
        if not cfg.scan_layers:
            raise ValueError("gpipe_microbatches requires scan_layers=True")
        if cfg.moe_experts or cfg.attention_impl != "xla":
            raise ValueError(
                "gpipe_microbatches supports dense blocks with xla attention"
            )
        scanned = _make_scanned(cfg)
        if self.is_initializing():
            # Creates the stacked "layers" params; init output is unused
            # beyond shapes, so the schedule difference is irrelevant.
            x, _ = scanned(cfg, False, name="layers")(x, positions)
            return x

        from tf_yarn_tpu.parallel.mesh import AXIS_PP, current_mesh
        from tf_yarn_tpu.parallel.pipeline import pipeline_apply

        mesh = current_mesh()
        if mesh is None:
            x, _ = scanned(cfg, False, name="layers")(x, positions)
            return x
        pp = dict(zip(mesh.axis_names, mesh.devices.shape)).get(AXIS_PP, 1)
        if cfg.n_layers % pp:
            raise ValueError(
                f"n_layers={cfg.n_layers} must divide over pp={pp} stages"
            )
        layer_params = self.get_variable("params", "layers")
        layers_per_stage = cfg.n_layers // pp
        stage_params = jax.tree_util.tree_map(
            lambda p: p.reshape(pp, layers_per_stage, *p.shape[1:]),
            layer_params,
        )

        # One row of positions broadcasts over any microbatch size (the
        # full [B, S] array would smuggle the global batch dim into the
        # microbatch-local stage compute).
        positions_row = positions[:1]

        # Constructed HERE, at the parent apply's trace level: a Module
        # built inside the shard_map/scan body trips flax's trace-level
        # check (the active parent scope was opened outside the
        # transform). `parent=None` keeps it detached — it is driven
        # through its own .apply with explicit params, never bound.
        block = Block(cfg, parent=None)

        def stage_fn(params_slice, h):
            def layer_body(carry, layer_p):
                out = block.apply(
                    {"params": layer_p["block"]}, carry, positions_row
                )
                return out, None

            if cfg.remat:
                # Same activation-memory trade as the scan path: recompute
                # each layer in backward instead of keeping every in-flight
                # microbatch's full activations.
                layer_body = jax.checkpoint(
                    layer_body,
                    policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
                )
            h, _ = jax.lax.scan(layer_body, h, params_slice)
            return h

        return pipeline_apply(
            stage_fn, stage_params, x, mesh,
            num_microbatches=cfg.gpipe_microbatches,
        )


def lora_label_tree(params) -> Any:
    """Label pytree for optax.multi_transform: "lora" for adapter params,
    "frozen" for the base model — the LoRA fine-tune recipe."""
    import jax.tree_util as jtu

    flat, treedef = jtu.tree_flatten_with_path(params)

    def label(path) -> str:
        names = (str(getattr(k, "key", getattr(k, "name", ""))) for k in path)
        return "lora" if any(n.startswith("lora_") for n in names) else "frozen"

    return jtu.tree_unflatten(treedef, [label(path) for path, _ in flat])


def merge_lora(params, config) -> Any:
    """Fold trained LoRA adapters into the base kernels for deployment:
    every LoraDense's W becomes W + (alpha/rank)·A@B and the adapter
    factors are dropped, so the result loads into the SAME architecture
    with `lora_rank=0` — no adapter math at serving time, and the plain
    checkpoint works with inference/generation unchanged. Accepts either
    the full `{"params": ...}` variables dict or the inner params tree;
    flax partitioning boxes on kernels are preserved."""
    if getattr(config, "lora_rank", 0) <= 0:
        return params
    from collections.abc import Mapping

    scale = config.lora_alpha / config.lora_rank

    def _unbox(leaf):
        return leaf.value if hasattr(leaf, "value") else leaf

    def _walk(node):
        # Mapping, not dict: a FrozenDict tree must merge too, not come
        # back untouched with the adapters silently dropped at serving.
        if not isinstance(node, Mapping):
            return node
        out = {key: _walk(child) for key, child in node.items()}
        if "kernel" in out and "lora_a" in out and "lora_b" in out:
            kernel = out["kernel"]
            delta = scale * (_unbox(out.pop("lora_a"))
                             @ _unbox(out.pop("lora_b")))
            merged = _unbox(kernel) + delta.astype(_unbox(kernel).dtype)
            out["kernel"] = (kernel.replace_boxed(merged)
                             if hasattr(kernel, "replace_boxed") else merged)
        return out

    return _walk(dict(params))


def make_lora_optimizer(learning_rate: float = 1e-4, inner=None):
    """`inner` (default adamw) on LoRA params, frozen base (reference has
    no analog — LoRA is a BASELINE.json config 5 requirement)."""
    import optax

    return optax.multi_transform(
        {
            "lora": inner if inner is not None else optax.adamw(learning_rate),
            "frozen": optax.set_to_zero(),
        },
        lora_label_tree,
    )


def make_experiment(
    config: Optional[TransformerConfig] = None,
    model_dir: Optional[str] = None,
    train_steps: int = 100,
    batch_size: int = 8,
    seq_len: Optional[int] = None,
    learning_rate: float = 3e-4,
    mesh_spec=None,
    input_fn=None,
    loss_chunk_size: Optional[int] = None,
    optimizer: "Optional[str | object]" = None,
    **train_param_overrides,
):
    """Causal-LM experiment (synthetic tokens unless input_fn given); LoRA
    configs (config.lora_rank > 0) get the frozen-base optimizer.

    `loss_chunk_size` switches to the chunked-vocab cross-entropy
    (common.lm_loss_chunked) — set for large-vocab configs (>= ~64k) where
    full [B, S, vocab] f32 logits dominate HBM; defaults on automatically
    for vocab >= 65536. MoE aux losses are collected on both paths."""
    import functools

    import optax

    from tf_yarn_tpu.experiment import JaxExperiment, TrainParams
    from tf_yarn_tpu.models import common

    config = config or TransformerConfig.tiny()
    seq_len = seq_len or config.max_seq_len
    if loss_chunk_size is None and config.vocab_size >= 65536:
        loss_chunk_size = 16384
    loss_fn = (
        functools.partial(common.lm_loss_chunked, chunk_size=loss_chunk_size)
        if loss_chunk_size
        else common.lm_loss
    )
    if optimizer == "adafactor":
        # Factored second moments: optimizer state shrinks from 2x params
        # to ~params + O(rows+cols) — the HBM saver for full fine-tunes of
        # multi-B-param models on small slices.
        optimizer = optax.adafactor(learning_rate)
    elif optimizer == "adamw":
        optimizer = common.adamw_with_decay_mask(learning_rate)
    elif isinstance(optimizer, str):
        raise ValueError(
            f"unknown optimizer {optimizer!r}; use 'adamw', 'adafactor', or "
            "pass an optax GradientTransformation"
        )
    if config.lora_rank > 0:
        # LoRA always keeps the base frozen, whatever inner optimizer was
        # chosen: adapters get it, everything else is zeroed.
        optimizer = make_lora_optimizer(
            learning_rate,
            inner=optimizer
            if optimizer is not None
            else common.adamw_with_decay_mask(learning_rate),
        )
    elif optimizer is None:
        optimizer = common.adamw_with_decay_mask(learning_rate)
    defaults = dict(train_steps=train_steps, log_every_steps=max(1, train_steps // 10))
    defaults.update(train_param_overrides)
    return JaxExperiment(
        model=Transformer(config),
        optimizer=optimizer,
        loss_fn=loss_fn,
        train_input_fn=input_fn
        or (lambda: common.synthetic_token_iter(batch_size, seq_len, config.vocab_size)),
        train_params=TrainParams(**defaults),
        model_dir=model_dir,
        init_fn=lambda rng, batch: Transformer(config).init(rng, batch["tokens"]),
        mesh_spec=mesh_spec,
    )
