"""Plain reference of the `LongCat-Flash` decoder (the language model of
LongCat-Flash-Omni; HF `LongcatFlashDecoderLayer`, `LongcatFlashTopkRouter`),
in float32 `jax.numpy` at the highest matmul precision: no cache, no absorbed
products, no kernels, no batching. It imports nothing of the program under
test. A leaf may be held in a narrower type that holds its values exactly
(check.py): it is taken up to float32 where it is used, a matrix, an expert
or a row at a time, never a stack of experts whole. `d` = hidden_size, eps =
rms_norm_eps.

One layer holds two attention sublayers, two dense feed-forwards and one
expert branch on one residual stream `h`:

    x1 = rmsnorm_a0(h)      h1 = h  + mla_0(x1)
    u  = rmsnorm_f0(h1)     m  = moe(u)                   (leaves the stream)
                            h2 = h1 + swiglu_0(u)         (ffn_hidden_size)
    x2 = rmsnorm_a1(h2)     h3 = h2 + mla_1(x2)
    v  = rmsnorm_f1(h3)     h' = h3 + swiglu_1(v) + m     (joins it here)
    logits = rmsnorm(h_L) @ W_head                        (untied head)

*mla_i* (each its own weights): `c_q = a_q rmsnorm(x W_qa)`; `[q_n | q_r] =
c_q W_qb` per head; `[c_raw | k_raw] = x W_kva`; `c = a_kv rmsnorm(c_raw)`;
`q_r, k_r = rope(q_r), rope(k_raw)` (one `k_r` for all heads); `[k_n | v] = c
W_kvb` per head; `s = (q_n.k_n + q_r.k_r) / sqrt(d_n + d_r)` over every
`j <= t`; `attn = concat_h(softmax(s) v) W_o`. `a_q = sqrt(d / q_lora_rank)`
where `mla_scale_q_lora`, `a_kv = sqrt(d / kv_lora_rank)` where
`mla_scale_kv_lora` (else 1).
*moe(u)*: `z = u W_r`, `n_routed_experts + zero_expert_num` wide; `p =
softmax(z)` over all of them; the `moe_topk` largest of `p + b` (`b` the
correction bias); `g_i = routed_scaling_factor p_i`, not renormalised;
`m = sum_{chosen i < n_routed_experts} g_i E_i(u) + (sum_{chosen i >=
n_routed_experts} g_i) u`: `E_i` a SwiGLU of `expert_ffn_hidden_size`, and a
zero-compute expert returns its input (`zero_expert_type: identity`). No
shared expert. The sum over real experts runs over those this share holds:
`n_routed_experts_here` of them from `routed_expert_offset` (0 where the
file has none), in a loop over the held experts with a mask. The identity
term needs the router alone, which every chip of a deployment holds whole:
it is in every share's result (`identity=False` leaves it out, for the test
that adds the shares up and counts it once). The vocabulary is the file's
`vocab_size`, the share's slice, and `num_layers` layers are run.

Departures from the published code: *rope* turns the pairs (2i, 2i + 1) by
`position / theta^(2i / n)`, this repo's convention, where the published
code turns the halves (i, i + n/2) after a permutation of `W_qb`'s and
`W_kva`'s columns (the same function of other weights); the audio and vision
encoders and the codec decoder are not run; what the file lists under
`assumed`.

Queries go a block at a time, so that the scores of 4096 tokens x 64 heads
fit beside the weights.

`lower="int8"` is the control: the same forward with both operands of every
linear layer (the router too) rounded to 8-bit integers, weights per output
channel and activations per token, the nearest precision below the bfloat16
that the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 128
LENGTH_STEP = 2048


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
    """x [S, ..., n] at positions 0..S-1: the pairs (2i, 2i+1) turned."""
    s, n = x.shape[0], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    angles = jnp.arange(s, dtype=jnp.float32).reshape(
        (s,) + (1,) * (x.ndim - 1)) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(x, w_in, w_out, lower):
    a, b = jnp.split(_linear(x, w_in, lower), 2, axis=-1)
    return _linear(jax.nn.silu(a) * b, w_out, lower)


def attention_sizes(sizes: dict) -> dict:
    d = sizes["hidden_size"]
    return {
        "heads": sizes["num_attention_heads"],
        "d_nope": sizes["qk_nope_head_dim"],
        "d_rope": sizes["qk_rope_head_dim"],
        "d_v": sizes["v_head_dim"],
        "theta": float(sizes["rope_theta"]),
        "alpha_q": math.sqrt(d / sizes["q_lora_rank"])
        if sizes["mla_scale_q_lora"] else 1.0,
        "alpha_kv": math.sqrt(d / sizes["kv_lora_rank"])
        if sizes["mla_scale_kv_lora"] else 1.0,
    }


@functools.partial(jax.jit, static_argnames=(
    "heads", "d_nope", "d_rope", "d_v", "theta", "alpha_q", "alpha_kv",
    "eps", "lower"))
def latent_attention(x, w, *, heads, d_nope, d_rope, d_v, theta, alpha_q,
                     alpha_kv, eps, lower=None):
    """One attention sublayer on rmsnorm'ed x [S, D] (S a multiple of the
    query block or below it): every `j <= t`, the keys and values expanded
    out of the latents."""
    s = x.shape[0]
    c_q = alpha_q * _rmsnorm(_linear(x, w["q_a"], lower), w["q_norm"], eps)
    q = _linear(c_q, w["q_b"], lower).reshape(s, heads, d_nope + d_rope)
    q_n, q_r = q[..., :d_nope], _rope(q[..., d_nope:], theta)
    kv = _linear(x, w["kv_a"], lower)
    c = alpha_kv * _rmsnorm(kv[:, :-d_rope], w["kv_norm"], eps)
    k_r = _rope(kv[:, -d_rope:], theta)
    expanded = _linear(c, w["kv_b"], lower).reshape(s, heads, d_nope + d_v)
    k_n, v = expanded[..., :d_nope], expanded[..., d_nope:]
    scale = (d_nope + d_rope) ** -0.5
    block = min(QUERY_BLOCK, s)
    starts = jnp.arange(s // block) * block
    keys = jnp.arange(s)[None, :]

    def some_rows(args):
        qn_block, qr_block, start = args
        rows = (start + jnp.arange(block))[:, None]
        scores = (jnp.einsum("thd,jhd->htj", qn_block, k_n, precision=HIGHEST)
                  + jnp.einsum("thd,jd->htj", qr_block, k_r,
                               precision=HIGHEST)) * scale
        scores = jnp.where((rows >= keys)[None], scores, -jnp.inf)
        return jnp.einsum("htj,jhd->thd", jax.nn.softmax(scores, -1), v,
                          precision=HIGHEST)

    out = jax.lax.map(some_rows, (
        q_n.reshape(s // block, block, heads, d_nope),
        q_r.reshape(s // block, block, heads, d_rope), starts))
    return _linear(out.reshape(s, heads * d_v), w["o"], lower)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "experts", "offset", "scale", "identity", "lower"))
def moe(u, w, *, top_k, experts, offset, scale, identity=True, lower=None):
    """The expert branch on rmsnorm'ed u [S, D]: the held experts, one after
    the other, each over every token and masked by its gate; then the
    zero-compute experts' term, a multiple of u."""
    p = jax.nn.softmax(_linear(u, w["router"], lower), -1)
    _, top_index = jax.lax.top_k(
        p + w["router_bias"].astype(jnp.float32), top_k)
    gates = scale * jnp.take_along_axis(p, top_index, -1)
    held = w["w_in"].shape[0]

    def one(total, inputs):
        w_in, w_out, expert = inputs
        gate = jnp.sum(jnp.where(top_index == expert, gates, 0.0), -1)
        return total + gate[:, None] * _swiglu(u, w_in, w_out, lower), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (w["w_in"], w["w_out"], offset + jnp.arange(held)))
    if not identity:
        return routed
    handed_back = jnp.sum(jnp.where(top_index >= experts, gates, 0.0), -1)
    return routed + handed_back[:, None] * u


@functools.partial(jax.jit, static_argnames=("lower",))
def dense(x, w_gate, w_up, w_down, *, lower=None):
    return _linear(jax.nn.silu(_linear(x, w_gate, lower))
                   * _linear(x, w_up, lower), w_down, lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def head(x, final_norm, w_head, *, eps, lower=None):
    return _linear(_rmsnorm(x, final_norm, eps), w_head, lower)


ATTENTION_LEAVES = ("q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "o")
EXPERT_LEAVES = ("router", "router_bias", "w_in", "w_out")


def layer(weights, h, index, sizes, lower=None, identity=True):
    """One layer on the stream h [S, D]. The attention sublayers' and the
    dense feed-forwards' leaves are listed two a layer (`2 index`,
    `2 index + 1`), the expert branch's one a layer."""
    eps = float(sizes["rms_norm_eps"])
    about = attention_sizes(sizes)

    def attend(stream, nth):
        return latent_attention(
            _rmsnorm(stream, weights["attn_norm"][nth], eps),
            {n: weights[n][nth] for n in ATTENTION_LEAVES},
            eps=eps, lower=lower, **about)

    def feed(normed, nth):
        return dense(normed, weights["dense_gate"][nth],
                     weights["dense_up"][nth], weights["dense_down"][nth],
                     lower=lower)

    h = h + attend(h, 2 * index)
    u = _rmsnorm(h, weights["ffn_norm"][2 * index], eps)
    branch = moe(
        u, {n: weights[n][index] for n in EXPERT_LEAVES},
        top_k=sizes["moe_topk"], experts=sizes["n_routed_experts"],
        offset=int(sizes.get("routed_expert_offset", 0)),
        scale=float(sizes["routed_scaling_factor"]), identity=identity,
        lower=lower)
    h = h + feed(u, 2 * index)
    h = h + attend(h, 2 * index + 1)
    v = _rmsnorm(h, weights["ffn_norm"][2 * index + 1], eps)
    return h + feed(v, 2 * index + 1) + branch


def hidden(weights, tokens, sizes, lower=None):
    """The last layer's output [S, D] of the full forward over `tokens`."""
    x = weights["embedding"][tokens].astype(jnp.float32)
    for index in range(sizes["num_layers"]):
        x = layer(weights, x, index, sizes, lower)
    return x


def padded_length(n: int) -> int:
    """Few lengths to compile for: a power of two times the query block up
    to `LENGTH_STEP`, whole steps above it."""
    if n > LENGTH_STEP:
        return -(-n // LENGTH_STEP) * LENGTH_STEP
    length = QUERY_BLOCK
    while length < n:
        length *= 2
    return length


def logits(weights, tokens, sizes, rows, lower=None):
    """Logits [len(rows), V] of the full forward over `tokens` [S], at the
    positions `rows`. Layer by layer, so that it fits beside the weights.
    The tokens are padded at the end to one of a few lengths (the forward is
    causal: no row sees the padding), so that a run compiles few shapes."""
    tokens = jnp.pad(tokens, (0, padded_length(tokens.shape[0]) - tokens.shape[0]))
    with jax.default_matmul_precision("highest"):
        x = hidden(weights, tokens, sizes, lower)
        return head(x[rows], weights["final_norm"], weights["head"],
                    eps=float(sizes["rms_norm_eps"]), lower=lower)
