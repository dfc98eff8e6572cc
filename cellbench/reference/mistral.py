"""Plain reference of the Mistral-7B decoder (Llama-shaped: RMSNorm, grouped
query attention with rotary embedding on interleaved pairs as in
mistral-inference, SwiGLU), in float32 `jax.numpy` at the highest matmul
precision: no cache, no kernels, no batching. It imports nothing of the
program under test. The sliding window of the published config is null in
v0.3, so attention is full and causal.

`lower="int8"` is the control of "How correct is decided": the same
forward with every linear layer's two operands rounded to 8-bit integers
(weights per output channel, activations per token), the nearest precision
below the bfloat16 that the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _linear(x, w, lower):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if lower == "int8":
        x, w = _int8(x, -1), _int8(w, 0)
    elif lower is not None:
        raise ValueError(f"unknown lower precision {lower!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    """x [S, H, hd]; pairs (2i, 2i+1) rotate by position * theta**(-2i/hd)."""
    s, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "hd", "theta",
                                             "eps", "lower"))
def layer(x, w, *, heads, kv_heads, hd, theta, eps, lower=None):
    """One block on x [S, D]; `w` holds this layer's leaves."""
    s = x.shape[0]
    h = _rmsnorm(x, w["attn_norm"], eps)
    q = _rope(_linear(h, w["wq"], lower).reshape(s, heads, hd), theta)
    k = _rope(_linear(h, w["wk"], lower).reshape(s, kv_heads, hd), theta)
    v = _linear(h, w["wv"], lower).reshape(s, kv_heads, hd)
    group = heads // kv_heads
    q = q.reshape(s, kv_heads, group, hd)
    scores = jnp.einsum("skgd,tkd->kgst", q, k, precision=HIGHEST) / hd ** 0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(scores, -1), v,
                     precision=HIGHEST)
    x = x + _linear(out.reshape(s, heads * hd), w["wo"], lower)
    h = _rmsnorm(x, w["mlp_norm"], eps)
    gate = _linear(h, w["w_gate"], lower)
    up = _linear(h, w["w_up"], lower)
    return x + _linear(jax.nn.silu(gate) * up, w["w_down"], lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def head(x, final_norm, lm_head, *, eps, lower=None):
    return _linear(_rmsnorm(x, final_norm, eps), lm_head, lower)


LAYER_LEAVES = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo",
                "w_gate", "w_up", "w_down")


def logits(weights, tokens, sizes, rows, lower=None):
    """Logits [len(rows), V] of the full forward over `tokens` [S], at the
    positions `rows`. Layer by layer, so that it fits beside the weights."""
    x = weights["embedding"].astype(jnp.float32)[tokens]
    for index in range(sizes["num_hidden_layers"]):
        x = layer(
            x, {name: weights[name][index] for name in LAYER_LEAVES},
            heads=sizes["num_attention_heads"],
            kv_heads=sizes["num_key_value_heads"], hd=sizes["head_dim"],
            theta=float(sizes["rope_theta"]), eps=float(sizes["rms_norm_eps"]),
            lower=lower,
        )
    return head(x[rows], weights["final_norm"], weights["lm_head"],
                eps=float(sizes["rms_norm_eps"]), lower=lower)
