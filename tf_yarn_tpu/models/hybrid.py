"""Hybrid decoder: a Mamba-2 or an attention mixer per layer, chosen from
`layer_types`, and after every mixer a dropless top-k expert layer with a
shared expert (`models/moe.DroplessMoE`) — the `granitemoehybrid` shape.

    h0     = embedding_multiplier * E[tok]
    u      = h + residual_multiplier * mixer(RMSNorm(h))
    h'     = u + residual_multiplier * (moe(RMSNorm(u)) + shared(RMSNorm(u)))
    logits = RMSNorm(h_L) @ E^T / logits_scaling          (tied embedding)

*mamba mixer.* `[z | xBC | dt] = x W_in`; `xBC = silu(causal depthwise
conv4(xBC) + b)`, split `x [H, P] | B [N] | C [N]` (one group);
`dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`; per head
`S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`, `y_t = S_t C_t + D x_t`;
`out = RMSNorm_w(y * silu(z)) W_out`. Two paths that agree: a chunked scan
for any number of tokens from any state (`ssm/scan`), and the one-token
update (`ssm/state_update`). Both, the state and `dt`, are float32 whatever
`dtype` is; a call with `decode=True` carries `ssm_state [B, H, P, N]` and
the last `d_conv - 1` rows of the conv input (`conv_state`) in the cache.

*attention mixer.* `transformer.Attention`, rope off, softmax scale
`attention_multiplier`.

The serving engine is told what each cache leaf is (`serving_contract`):
keys and values are paged by token, `ssm_state` and `conv_state` are held
once a slot, `cache_index` is the slot's position. A call with `paged_ctx`
(`transformer.PagedContext`) is the engine's paged step: tokens [slots, 1],
the state leaves lead with a slot axis over batch-1 rows, attention reads
and writes the block pool (`kv_pool`) through the slots' tables, and the
whole model, the experts above all, sees all slots' tokens together.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tf_yarn_tpu.models.moe import DroplessMoE, ExpertRow
from tf_yarn_tpu.models.transformer import (
    CACHE_LEAF_KINDS,
    EMBED,
    HEADS,
    PREFILL_QUERY_BLOCK,
    Attention,
    RMSNorm,
    TransformerConfig,
    _partitioned,
)
from tf_yarn_tpu.models.trunk import DecoderLM, LayerCall, ServingContract

HIGHEST = jax.lax.Precision.HIGHEST
MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 100352
    d_model: int = 4096
    layer_types: Tuple[str, ...] = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    max_seq_len: int = 4096
    norm_eps: float = 1e-5
    # attention mixer (no positional encoding)
    n_heads: int = 32
    n_kv_heads: int = 8
    attention_multiplier: float = 1.0 / 128
    # mamba-2 mixer
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk: int = 256
    # experts
    num_experts: int = 72
    num_experts_here: int = 72
    expert_offset: int = 0
    experts_per_token: int = 10
    d_expert: int = 768
    d_shared: int = 1536
    # multipliers
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    # Matrices are stored in `param_dtype`; vectors (A_log, D, dt_bias, the
    # conv bias and every norm's scale) stay float32.
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        return self.d_inner + 2 * self.mamba_d_state

    def __post_init__(self):
        unknown = set(self.layer_types) - {MAMBA, ATTENTION}
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types: {self.layer_types!r}")

    def attention_config(self) -> TransformerConfig:
        """What `transformer.Attention` reads, at this model's sizes."""
        return TransformerConfig(
            vocab_size=self.vocab_size, d_model=self.d_model,
            n_layers=self.n_layers, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, max_seq_len=self.max_seq_len,
            norm_eps=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, use_rope=False,
            attention_scale=self.attention_multiplier,
        )

    def norm_config(self) -> TransformerConfig:
        """`transformer.RMSNorm` with its scale in float32."""
        return dataclasses.replace(
            self.attention_config(), param_dtype=jnp.float32)

    @classmethod
    def tiny(cls, **overrides) -> "HybridConfig":
        defaults = dict(
            vocab_size=256, d_model=64, max_seq_len=64,
            layer_types=(MAMBA, ATTENTION, MAMBA),
            n_heads=4, n_kv_heads=2, attention_multiplier=1.0 / 16,
            mamba_heads=4, mamba_head_dim=8, mamba_d_state=16, mamba_chunk=8,
            num_experts=8, num_experts_here=8, experts_per_token=3,
            d_expert=32, d_shared=48,
        )
        defaults.update(overrides)
        return cls(**defaults)


def ssd_scan(x, dt, a, b, c, state, chunk: int):
    """The recurrence `S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) b_t`,
    `y_t = S_t c_t` over T tokens from `state`, a chunk at a time: inside a
    chunk the tokens meet through one masked [Q, Q] matrix, and the state
    steps from chunk to chunk. float32 throughout.

    x [B, T, H, P], dt [B, T, H], a [H], b, c [B, T, N], state [B, H, P, N]
    -> y [B, T, H, P], final state.
    """
    batch, t, heads, p = x.shape
    n = b.shape[-1]
    q = min(chunk, t)
    pad = -t % q
    if pad:
        # dt = 0 on the padding: the state passes through it unchanged.
        x, dt, b, c = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (t + pad) // q
    x = x.reshape(batch, nc, q, heads, p)
    dt = dt.reshape(batch, nc, q, heads)
    b = b.reshape(batch, nc, q, n)
    c = c.reshape(batch, nc, q, n)
    log_decay = jnp.cumsum(dt * a, axis=2)                    # [B, C, Q, H]
    xdt = x * dt[..., None]
    # inside a chunk
    span = log_decay[:, :, :, None, :] - log_decay[:, :, None, :, :]
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    mix = jnp.where(causal, jnp.exp(jnp.where(causal, span, 0.0)), 0.0) \
        * jnp.einsum("bctn,bcsn->bcts", c, b, precision=HIGHEST)[..., None]
    y = jnp.einsum("bctsh,bcshp->bcthp", mix, xdt, precision=HIGHEST)
    # what each chunk adds to the state, and the state before each chunk
    to_end = jnp.exp(log_decay[:, :, -1:, :] - log_decay)     # [B, C, Q, H]
    added = jnp.einsum("bcsh,bcshp,bcsn->bchpn", to_end, xdt, b,
                       precision=HIGHEST)
    chunk_decay = jnp.exp(log_decay[:, :, -1, :])             # [B, C, H]

    def step(before, inputs):
        add, decay = inputs
        return decay[:, :, None, None] * before + add, before

    state, before = jax.lax.scan(
        step, state, (jnp.moveaxis(added, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    y = y + jnp.einsum("bctn,bchpn,bcth->bcthp", c, jnp.moveaxis(before, 0, 1),
                       jnp.exp(log_decay), precision=HIGHEST)
    return y.reshape(batch, nc * q, heads, p)[:, :t], state


def ssm_update(x, dt, a, b, c, state):
    """One token of the same recurrence. x [B, H, P], dt [B, H], b, c [B, N],
    state [B, H, P, N] -> y [B, H, P], state."""
    decay = jnp.exp(dt * a)[:, :, None, None]
    state = decay * state + (dt[:, :, None] * x)[..., None] * b[:, None, None, :]
    return jnp.sum(state * c[:, None, None, :], axis=-1), state


class Mamba2Mixer(nn.Module):
    config: HybridConfig
    decode: bool = False

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        batch, t, d = x.shape
        heads, p, n = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_d_state
        inner, width, taps = cfg.d_inner, cfg.conv_width, cfg.mamba_d_conv
        f32 = jnp.float32
        normal = nn.initializers.lecun_normal()

        with jax.named_scope("ssm/in_proj"):
            w_in = self.param(
                "in_proj", _partitioned((EMBED, HEADS))(normal),
                (d, inner + width + heads), cfg.param_dtype,
            )
            # float32 out of the matmul: dt and the conv's input keep what
            # the accumulator had.
            proj = jnp.einsum("btd,df->btf", x, w_in.astype(cfg.dtype),
                              preferred_element_type=f32)
            z, xbc, dt = jnp.split(proj, [inner, inner + width], axis=-1)

        conv_w = self.param("conv_w", normal, (taps, width), f32)
        conv_b = self.param("conv_b", nn.initializers.zeros_init(), (width,), f32)
        a_log = self.param("A_log", nn.initializers.zeros_init(), (heads,), f32)
        skip = self.param("D", nn.initializers.ones, (heads,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros_init(), (heads,), f32)

        if self.decode:
            # One row a batch element, or (the paged step) a leading slot
            # axis over batch-1 rows: flattened here, restored on the way
            # out.
            state_var = self.variable(
                "cache", "ssm_state",
                lambda: jnp.zeros((batch, heads, p, n), f32))
            conv_var = self.variable(
                "cache", "conv_state",
                lambda: jnp.zeros((batch, taps - 1, width), f32))
            state = state_var.value.reshape(batch, heads, p, n)
            tail = conv_var.value.reshape(batch, taps - 1, width)
        else:
            state = jnp.zeros((batch, heads, p, n), f32)
            tail = jnp.zeros((batch, taps - 1, width), f32)

        with jax.named_scope("ssm/conv"):
            rows = jnp.concatenate([tail, xbc], axis=1)       # [B, T+3, W]
            xbc = conv_b + sum(
                conv_w[k] * rows[:, k:k + t] for k in range(taps))
            xbc = nn.silu(xbc)
            tail = rows[:, t:]
        with jax.named_scope("ssm/state_update" if t == 1 else "ssm/scan"):
            xs, b, c = jnp.split(xbc, [inner, inner + n], axis=-1)
            xs = xs.reshape(batch, t, heads, p)
            dt = jax.nn.softplus(dt + dt_bias)
            a = -jnp.exp(a_log)
            if t == 1:
                y, state = ssm_update(xs[:, 0], dt[:, 0], a, b[:, 0], c[:, 0],
                                      state)
                y = y[:, None]
            else:
                y, state = ssd_scan(xs, dt, a, b, c, state, cfg.mamba_chunk)
            y = y + skip[:, None] * xs
        if self.decode:
            state_var.value = state.reshape(state_var.value.shape)
            conv_var.value = tail.reshape(conv_var.value.shape)

        with jax.named_scope("ssm/gate_norm"):
            scale = self.param("norm", nn.initializers.ones, (inner,), f32)
            y = y.reshape(batch, t, inner) * nn.silu(z)
            y = y * jax.lax.rsqrt(
                jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps) * scale
        with jax.named_scope("ssm/out_proj"):
            w_out = self.param(
                "out_proj", _partitioned((HEADS, EMBED))(normal),
                (inner, d), cfg.param_dtype,
            )
            return jnp.einsum(
                "btf,fd->btd", y.astype(cfg.dtype), w_out.astype(cfg.dtype),
                preferred_element_type=f32,
            ).astype(cfg.dtype)


class HybridBlock(nn.Module):
    config: HybridConfig
    kind: str
    decode: bool = False

    @nn.compact
    def __call__(self, x, call=LayerCall()):
        cfg = self.config
        norm_cfg = cfg.norm_config()
        batch, t, d = x.shape
        normed = RMSNorm(norm_cfg, name="mixer_norm")(x)
        if self.kind == MAMBA:
            mixed = Mamba2Mixer(cfg, self.decode, name="mamba")(normed)
        else:
            mixed = Attention(cfg.attention_config(), self.decode, name="attn")(
                normed, call.positions, call.paged_ctx)
        x = x + (cfg.residual_multiplier * mixed).astype(x.dtype)
        normed = RMSNorm(norm_cfg, name="moe_norm")(x)
        moe = DroplessMoE(
            num_experts=cfg.num_experts, num_experts_here=cfg.num_experts_here,
            expert_offset=cfg.expert_offset, top_k=cfg.experts_per_token,
            d_expert=cfg.d_expert, d_shared=cfg.d_shared, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name="moe",
        )(normed.reshape(batch * t, d), call.count_mask)
        return x + (cfg.residual_multiplier * moe.reshape(batch, t, d)
                    ).astype(x.dtype)


class HybridLM(DecoderLM):
    """`trunk.DecoderLM` over mamba and attention layers, with granite's
    tied head and multipliers."""

    config: HybridConfig
    scaled_embedding = True
    tied_head = True

    @nn.nowrap
    def layer(self, index, **module):
        return HybridBlock(
            self.config, self.config.layer_types[index], **module)

    def serving_contract(self):
        cfg = self.config
        # Row t depends on tokens <= t alone (causal scan, convolution and
        # attention, per-token dropless experts); the floor rule holds all
        # the same: the state and the tail are what the prefill left at the
        # end of its bucket (`DecodeEngine.ceiling_prefill`).
        return ServingContract(
            leaf_kinds={**CACHE_LEAF_KINDS, "ssm_state": ("slot", None),
                        "conv_state": ("slot", None)},
            prefill_layers=tuple(
                (0, PREFILL_QUERY_BLOCK)
                for kind in cfg.layer_types if kind == ATTENTION),
            rows_causal=True, takes_prompt_len=False, counts=True,
            experts=ExpertRow.of(cfg))
