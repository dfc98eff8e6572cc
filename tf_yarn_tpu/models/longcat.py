"""Shortcut-connected decoder — the `LongCat-Flash` shape (the language model
of LongCat-Flash-Omni): two latent-attention sublayers, two dense
feed-forwards and one expert branch on one residual stream, the branch
leaving the stream at the first feed-forward's input and joining it a
sublayer later.

    x1 = RMSNorm_a0(h)      h1 = h  + MLA_0(x1)
    u  = RMSNorm_f0(h1)     m  = MoE(u)                   (leaves here)
                            h2 = h1 + SwiGLU_0(u)
    x2 = RMSNorm_a1(h2)     h3 = h2 + MLA_1(x2)
    v  = RMSNorm_f1(h3)     h' = h3 + SwiGLU_1(v) + m     (joins here)
    logits = RMSNorm(h_L) @ W_head                        (untied head)

*MLA_i* is `latent.LatentAttention` of the `plain_attention` kind, each with
its own weights and its own cache leaf: the latents rescaled, RoPE on
`q_r`, `k_raw`, every `j <= t` attended, no window, no selection, no gate.
What is cached a token and sublayer is the row `[c | k_r]` (576 numbers,
stored 640), paged by token: nothing of this model is held once a slot.

*MoE* is `moe.DroplessMoE` with `scoring="softmax_all"`: a float32 router
`num_experts + num_zero_experts` wide, a softmax over all its outputs, the
`experts_per_token` largest under the correction bias, gates `routed_scale
p_i` (not renormalised); a chosen output past `num_experts` returns its
input, so the layer adds `(sum of those gates) u`; no shared expert.

The serving engine pages every leaf (`serving_contract`), steps the model
with the counting step (`moe_stats`, `cache_stats`: `PLAIN_READS`) and
reads the pool through `paged_decode_attention` as one KV head a row
(`pool_rows_are_one_kv_head`). A call of more than one token with
`decode=True` is a prefill from an empty cache; the windowed paths are
`LatentAttention`'s to refuse.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax

from tf_yarn_tpu.models.latent import (
    PLAIN,
    PLAIN_READS,
    AttentionSizes,
    LatentAttention,
    LatentConfig,
)
from tf_yarn_tpu.models.moe import DroplessMoE, ExpertRow
from tf_yarn_tpu.models.transformer import RMSNorm, SwiGLU
from tf_yarn_tpu.models.trunk import DecoderLM, LayerCall, ServingContract


@dataclasses.dataclass(frozen=True)
class LongcatConfig(LatentConfig):
    """`LatentConfig` at this family's sizes: `full` holds the one kind's
    attention sizes, every layer is `plain_attention`, and the router is
    `num_experts + num_zero_experts` wide."""

    vocab_size: int = 131072
    d_model: int = 6144
    layer_types: Tuple[str, ...] = (PLAIN,) * 28
    max_seq_len: int = 4096
    full: AttentionSizes = AttentionSizes(64, 1536, 512, 128, 64, 128, 1e7)
    first_dense: int = 0
    d_ff_dense: int = 12288
    num_experts: int = 512
    num_experts_here: int = 512
    num_zero_experts: int = 256
    experts_per_token: int = 12
    d_expert: int = 2048
    d_shared: int = 0
    norm_topk: bool = False
    routed_scale: float = 6.0

    def __post_init__(self):
        super().__post_init__()
        if set(self.layer_types) != {PLAIN}:
            raise ValueError(
                f"layer_types: {self.layer_types!r}; every layer of this "
                f"model is {PLAIN!r}")

    @classmethod
    def tiny(cls, **overrides) -> "LongcatConfig":
        defaults = dict(
            vocab_size=256, d_model=64, max_seq_len=64,
            layer_types=(PLAIN,) * 2,
            full=AttentionSizes(4, 32, 16, 16, 8, 16, 1e7),
            d_ff_dense=96, num_experts=16, num_experts_here=16,
            num_zero_experts=8, experts_per_token=4, d_expert=32,
            query_block=8, row_multiple=8,
        )
        defaults.update(overrides)
        return cls(**defaults)


class ShortcutBlock(nn.Module):
    """One layer, by the module's equations."""

    config: LongcatConfig
    decode: bool = False

    @nn.compact
    def __call__(self, h, call=LayerCall()):
        cfg = self.config
        norm_cfg = cfg.norm_config()
        batch, t, d = h.shape

        def attention(index, stream):
            return LatentAttention(cfg, PLAIN, self.decode,
                                   name=f"attn_{index}")(
                RMSNorm(norm_cfg, name=f"attn_norm_{index}")(stream),
                call.paged_ctx, call.count_mask)

        def dense(index, normed):
            with jax.named_scope("mlp"):
                return SwiGLU(cfg.dense_config(), name=f"dense_{index}")(normed)

        h = h + attention(0, h)
        normed = RMSNorm(norm_cfg, name="ffn_norm_0")(h)
        # The branch leaves the stream here and joins it a sublayer later.
        branch = DroplessMoE(
            num_experts=cfg.num_experts, num_experts_here=cfg.num_experts_here,
            expert_offset=cfg.expert_offset, top_k=cfg.experts_per_token,
            d_expert=cfg.d_expert, scoring="softmax_all",
            norm_topk=cfg.norm_topk, routed_scale=cfg.routed_scale,
            num_zero_experts=cfg.num_zero_experts,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="moe",
        )(normed.reshape(batch * t, d), call.count_mask).reshape(batch, t, d)
        h = h + dense(0, normed)
        h = h + attention(1, h)
        return h + dense(1, RMSNorm(norm_cfg, name="ffn_norm_1")(h)) + branch


class LongcatLM(DecoderLM):
    """`trunk.DecoderLM` over `ShortcutBlock`s, which keep their own
    positions; every sublayer's rows are paged."""

    config: LongcatConfig
    block_positions = False

    @nn.nowrap
    def layer(self, index, **module):
        return ShortcutBlock(self.config, **module)

    def serving_contract(self):
        cfg = self.config
        # Row t of a prefill's cache depends on tokens <= t alone (causal
        # attention, per-token dropless experts, every leaf paged). A cached
        # row has no head axis and is read as one KV head of its width.
        return ServingContract(
            leaf_kinds={"latent": ("paged", -2),
                        "cache_index": ("index", None)},
            # two attention sublayers a layer
            prefill_layers=((0, cfg.query_block),) * (2 * cfg.n_layers),
            rows_causal=True, takes_prompt_len=False, counts=True,
            reads=PLAIN_READS, pool_rows_are_one_kv_head=True,
            experts=ExpertRow.of(cfg, cfg.num_zero_experts))
