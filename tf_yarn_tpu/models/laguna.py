"""Grouped-query decoder whose attention differs by layer, with a per-head
output gate and an expert layer — the `laguna` shape (Laguna-XS.2).

    u      = RMSNorm(h)
    h'     = h + concat_h(sigmoid(u W_g)_h * attn_h(u)) W_o
    h''    = h' + ffn(RMSNorm(h'))
    logits = RMSNorm(h_L) @ W_head                          (untied head)

*attention* is `transformer.Attention` with what a layer says of itself
(`layer_types`, `heads`): a `full_attention` layer has its own count of query
heads over the model's KV heads, sees every `j <= t`, turns the first
`rotary_dim` numbers of a head by YaRN's frequencies and leaves the rest
(`RotaryRecipe`, `rotary`); a `sliding_attention` layer has another count of
heads, sees `t - j < window` (the query itself counts) and turns the whole
head by plain RoPE at its own theta. Both gate each head's output by a
sigmoid of the attention's own input.

*ffn.* `mlp_types[l] == "dense"`: `transformer.SwiGLU` of `d_ff_dense`.
`"sparse"`: `moe.DroplessMoE`, the `top_k` largest router logits, a softmax
over those alone times `routed_scale`, one shared expert.

The serving engine is told what each cache leaf is (`serving_contract`): a
full layer's `cached_key` / `cached_value` are paged by token and read by
`ops.decode_attention.paged_decode_attention`; a sliding layer's
`window_key` / `window_value` are `ring`s of `ring_rows(window)` rows with a
head axis, held once a slot, so their bytes do not grow with the context;
a prefill writes them from the rows that end where the prompt does
(`prompt_len`), whatever pad follows.
The one-token step sows what it read into `cache_stats` for the slots
`count_mask` marks (`ATTENTION_READS`: pool rows and ring rows apart).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tf_yarn_tpu.models.moe import DroplessMoE, ExpertRow
from tf_yarn_tpu.models.transformer import (
    ATTENTION_READS,
    CACHE_LEAF_KINDS,
    PREFILL_QUERY_BLOCK,
    Attention,
    RMSNorm,
    RotaryRecipe,
    SwiGLU,
    TransformerConfig,
)
from tf_yarn_tpu.models.trunk import DecoderLM, LayerCall, ServingContract

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


def rotary(recipe: RotaryRecipe):
    """`(x [B, S, H, D], positions [B, S]) -> x`: the pairs (2i, 2i + 1) of
    the first `rotary_dim` numbers turned, in float32."""
    inv_freq = recipe.inv_freq()
    n = recipe.rotary_dim

    def turn(x, positions):
        with jax.named_scope("attention/rope"):
            angles = positions[:, :, None, None].astype(jnp.float32) * inv_freq
            cos = jnp.cos(angles) * recipe.attention_factor
            sin = jnp.sin(angles) * recipe.attention_factor
            front = x[..., :n].astype(jnp.float32)
            x1, x2 = front[..., 0::2], front[..., 1::2]
            turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).reshape(front.shape)
            return jnp.concatenate(
                [turned.astype(x.dtype), x[..., n:]], axis=-1)

    return turn


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    d_model: int = 2048
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING, FULL)
    heads: Tuple[int, ...] = (48, 64, 64, 64, 48)
    n_kv_heads: int = 8
    head_dim: int = 128
    window: int = 512
    max_seq_len: int = 6144
    norm_eps: float = 1e-6
    full_rotary: RotaryRecipe = RotaryRecipe(
        5e5, 64, factor=64.0, original_max=4096, beta_fast=64.0,
        beta_slow=1.0, attention_factor=1.4158883083359672)
    sliding_rotary: RotaryRecipe = RotaryRecipe(1e4, 128)
    # ffn
    mlp_types: Tuple[str, ...] = (DENSE, SPARSE, SPARSE, SPARSE, SPARSE)
    d_ff_dense: int = 8192
    num_experts: int = 256
    num_experts_here: int = 256
    expert_offset: int = 0
    experts_per_token: int = 8
    d_expert: int = 512
    d_shared: int = 512
    routed_scale: float = 2.5
    # Matrices are stored in `param_dtype`; norm scales stay float32.
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # Only "bf16": a ring has no int8 read.
    kv_cache_dtype: str = "bf16"
    # Queries a block of a prefill's attention over its own tokens.
    query_block: int = 256

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_heads(self) -> int:
        """What `DecodeEngine(mesh=...)` checks against `tp`."""
        return math.gcd(*self.heads)

    def __post_init__(self):
        if not self.layer_types or set(self.layer_types) - {FULL, SLIDING} \
                or set(self.mlp_types) - {DENSE, SPARSE}:
            raise ValueError(
                f"layer_types / mlp_types: {self.layer_types!r}, "
                f"{self.mlp_types!r}")
        if not len(self.layer_types) == len(self.heads) == len(self.mlp_types):
            raise ValueError(
                "layer_types, heads and mlp_types list one entry a layer: "
                f"{len(self.layer_types)}, {len(self.heads)}, "
                f"{len(self.mlp_types)}")
        if any(h % self.n_kv_heads for h in self.heads) or self.window < 1:
            raise ValueError(
                f"heads {self.heads!r} over {self.n_kv_heads} KV heads, "
                f"window {self.window}")
        if self.kv_cache_dtype != "bf16":
            raise ValueError(
                f"kv_cache_dtype={self.kv_cache_dtype!r}: a window layer's "
                "ring has no int8 read; it is refused until one exists "
                "(docs/Serving.md \"State held once a slot\")")

    def attention_config(self) -> TransformerConfig:
        """What `transformer.Attention` reads beside what a layer says of
        itself (heads, head size, rotary function, gate, window)."""
        return TransformerConfig(
            vocab_size=self.vocab_size, d_model=self.d_model,
            n_layers=self.n_layers, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, d_ff=self.d_ff_dense,
            max_seq_len=self.max_seq_len, norm_eps=self.norm_eps,
            dtype=self.dtype, param_dtype=self.param_dtype,
            kv_cache_dtype=self.kv_cache_dtype,
        )

    def norm_config(self) -> TransformerConfig:
        """`transformer.RMSNorm` with its scale in float32."""
        return dataclasses.replace(
            self.attention_config(), param_dtype=jnp.float32)

    @classmethod
    def tiny(cls, **overrides) -> "LagunaConfig":
        defaults = dict(
            vocab_size=256, d_model=64, max_seq_len=64,
            heads=(4, 8, 8, 8, 4), n_kv_heads=2, head_dim=16, window=8,
            full_rotary=RotaryRecipe(
                5e5, 8, factor=64.0, original_max=16, beta_fast=64.0,
                beta_slow=1.0, attention_factor=1.4158883083359672),
            sliding_rotary=RotaryRecipe(1e4, 16),
            d_ff_dense=96, num_experts=16, num_experts_here=16,
            experts_per_token=2, d_expert=32, d_shared=32, query_block=8,
        )
        defaults.update(overrides)
        return cls(**defaults)


class LagunaBlock(nn.Module):
    config: LagunaConfig
    index: int
    decode: bool = False

    @nn.compact
    def __call__(self, x, call=LayerCall()):
        cfg = self.config
        norm_cfg = cfg.norm_config()
        batch, t, d = x.shape
        sliding = cfg.layer_types[self.index] == SLIDING
        x = x + Attention(
            cfg.attention_config(), self.decode, name="attn",
            n_heads=cfg.heads[self.index], head_dim=cfg.head_dim,
            rotary=rotary(cfg.sliding_rotary if sliding else cfg.full_rotary),
            gate=True, window=cfg.window if sliding else 0,
            query_block=cfg.query_block,
        )(RMSNorm(norm_cfg, name="attn_norm")(x), call.positions,
          call.paged_ctx, call.count_mask, call.prompt_len)
        normed = RMSNorm(norm_cfg, name="ffn_norm")(x)
        if cfg.mlp_types[self.index] == DENSE:
            with jax.named_scope("mlp"):
                return x + SwiGLU(cfg.attention_config(), name="dense")(normed)
        moe = DroplessMoE(
            num_experts=cfg.num_experts, num_experts_here=cfg.num_experts_here,
            expert_offset=cfg.expert_offset, top_k=cfg.experts_per_token,
            d_expert=cfg.d_expert, d_shared=cfg.d_shared, scoring="softmax",
            routed_scale=cfg.routed_scale, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="moe",
        )(normed.reshape(batch * t, d), call.count_mask)
        return x + moe.reshape(batch, t, d)


class LagunaLM(DecoderLM):
    """`trunk.DecoderLM` over `LagunaBlock`s; a prefill returns its last
    position's logits alone and writes the rings where `prompt_len` ends."""

    config: LagunaConfig
    head_on_prefill_last_row = True

    @nn.nowrap
    def layer(self, index, **module):
        return LagunaBlock(self.config, index, **module)

    def serving_contract(self):
        cfg = self.config
        # Row t of a prefill's cache depends on tokens <= t alone (causal
        # and window masks, per-token dropless experts), and a ring is
        # written where `prompt_len` says the prompt ends.
        return ServingContract(
            leaf_kinds={**CACHE_LEAF_KINDS, "window_key": ("ring", None),
                        "window_value": ("ring", None)},
            prefill_layers=tuple(
                (cfg.window, cfg.query_block) if kind == SLIDING
                else (0, cfg.query_block or PREFILL_QUERY_BLOCK)
                for kind in cfg.layer_types),
            rows_causal=True, takes_prompt_len=True, counts=True,
            reads=ATTENTION_READS, experts=ExpertRow.of(cfg))
