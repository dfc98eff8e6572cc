"""Plain reference of the `granitemoehybrid` decoder (Granite 4.0-H), in
float32 `jax.numpy` at the highest matmul precision: no cache, no kernels,
no chunking of the recurrence, no batching. It imports nothing of the
program under test.

    h0 = embedding_multiplier * E[tok]
    u  = h + residual_multiplier * mixer(rmsnorm(h))
    h' = u + residual_multiplier * (moe(rmsnorm(u)) + shared(rmsnorm(u)))
    logits = rmsnorm(h_L) @ E^T / logits_scaling

*mamba* (`layer_types[i] == "mamba"`): `[z | xBC | dt] = x W_in`;
`xBC = silu(conv(xBC) + b)`, a causal depthwise convolution over the last
`mamba_d_conv` positions; `xBC = x [heads, d_head] | B [d_state] | C
[d_state]` (one group); `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`;
per head, one position after the other, `S = exp(dt A) S + dt x (x) B`,
`y = S C + D x`; `out = rmsnorm_w(y * silu(z)) W_out`.
*attention*: grouped queries, no positional encoding, softmax of
`q k^T * attention_multiplier`, causal (a block of query rows at a time, so
that the scores fit beside the weights).
*moe*: router logits over `num_local_experts`; the `num_experts_per_tok`
largest; a softmax over those alone; `sum_i g_i W_out,i (silu(a_i) * b_i)`,
`[a_i | b_i] = x W_in,i`, every token, no capacity. The sum runs over the
chosen experts that this share holds: `num_local_experts_here` of them from
`local_expert_offset` (0 where the file has none), in a loop over the held
experts with a mask. *shared*: the same SwiGLU at `shared_intermediate_size`.
The vocabulary is the file's `vocab_size`, the share's slice, and the
layers are the first `num_hidden_layers` of `layer_types`.

`lower="int8"` is the control: the same forward with both operands of every
linear layer (the router and the experts too) rounded to 8-bit integers,
weights per output channel and activations per token, the nearest precision
below the bfloat16 that the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


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


def _swiglu(x, w_in, w_out, lower):
    a, b = jnp.split(_linear(x, w_in, lower), 2, axis=-1)
    return _linear(jax.nn.silu(a) * b, w_out, lower)


@functools.partial(jax.jit, static_argnames=(
    "heads", "d_head", "d_state", "eps", "lower"))
def mamba(x, w, *, heads, d_head, d_state, eps, lower=None):
    """The mixer on rmsnorm'ed x [S, D]; one position after the other."""
    s = x.shape[0]
    inner = heads * d_head
    proj = _linear(x, w["in_proj"], lower)
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * d_state], axis=-1)
    taps = w["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(w["conv_b"] + sum(
        w["conv_w"][k] * padded[k:k + s] for k in range(taps)))
    xs, b, c = jnp.split(xbc, [inner, inner + d_state], axis=-1)
    xs = xs.reshape(s, heads, d_head)
    dt = jax.nn.softplus(dt + w["dt_bias"])                   # [S, H]
    a = -jnp.exp(w["A_log"])                                  # [H]

    def step(state, inputs):                                  # [H, P, N]
        x_t, dt_t, b_t, c_t = inputs
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.sum(state * c_t[None, None, :], -1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, d_head, d_state)),
                        (xs, dt, b, c))
    y = (y + w["D"][:, None] * xs).reshape(s, inner) * jax.nn.silu(z)
    return _linear(_rmsnorm(y, w["gate_norm"], eps), w["out_proj"], lower)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "scale", "lower"))
def attention(x, w, *, heads, kv_heads, scale, lower=None):
    """The mixer on rmsnorm'ed x [S, D]: no positional encoding."""
    s = x.shape[0]
    hd = w["wq"].shape[1] // heads
    group = heads // kv_heads
    k = _linear(x, w["wk"], lower).reshape(s, kv_heads, hd)
    v = _linear(x, w["wv"], lower).reshape(s, kv_heads, hd)
    block = min(QUERY_BLOCK, s)
    rows = -(-s // block) * block
    q = jnp.pad(_linear(x, w["wq"], lower), [(0, rows - s), (0, 0)])
    q = q.reshape(rows // block, block, kv_heads, group, hd)
    starts = jnp.arange(rows // block) * block

    def some_rows(args):
        q_block, start = args
        scores = jnp.einsum("skgd,tkd->kgst", q_block, k,
                            precision=HIGHEST) * scale
        causal = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(scores, -1), v,
                          precision=HIGHEST)

    out = jax.lax.map(some_rows, (q, starts)).reshape(rows, heads * hd)[:s]
    return _linear(out, w["wo"], lower)


@functools.partial(jax.jit, static_argnames=("top_k", "offset", "lower"))
def experts(x, w, *, top_k, offset, lower=None):
    """moe(x) + shared(x) on rmsnorm'ed x [S, D]: the held experts, one
    after the other, each over every token and masked by its gate."""
    logits = _linear(x, w["router"], lower)
    top_logits, top_index = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(top_logits, -1)
    held = w["w_in"].shape[0]

    def one(total, inputs):
        w_in, w_out, expert = inputs
        gate = jnp.sum(jnp.where(top_index == expert, gates, 0.0), -1)
        return total + gate[:, None] * _swiglu(x, w_in, w_out, lower), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (w["w_in"], w["w_out"], offset + jnp.arange(held)))
    return routed + _swiglu(x, w["shared_in"], w["shared_out"], lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def head(x, final_norm, embedding, *, eps, lower=None):
    return _linear(_rmsnorm(x, final_norm, eps), embedding.T, lower)


MAMBA_LEAVES = ("in_proj", "conv_w", "conv_b", "A_log", "D", "dt_bias",
                "gate_norm", "out_proj")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo")
EXPERT_LEAVES = ("router", "w_in", "w_out", "shared_in", "shared_out")


def hidden(weights, tokens, sizes, lower=None):
    """The last layer's output [S, D] of the full forward over `tokens`."""
    eps = float(sizes["rms_norm_eps"])
    residual = float(sizes["residual_multiplier"])
    x = weights["embedding"].astype(jnp.float32)[tokens] \
        * float(sizes["embedding_multiplier"])
    of_kind = {"mamba": 0, "attention": 0}
    kinds = sizes["layer_types"][:sizes["num_hidden_layers"]]
    for index, kind in enumerate(kinds):
        normed = _rmsnorm(x, weights["mixer_norm"][index], eps)
        nth = of_kind[kind]
        of_kind[kind] += 1
        if kind == "mamba":
            mixed = mamba(
                normed, {n: weights[n][nth] for n in MAMBA_LEAVES},
                heads=sizes["mamba_n_heads"], d_head=sizes["mamba_d_head"],
                d_state=sizes["mamba_d_state"], eps=eps, lower=lower)
        elif kind == "attention":
            mixed = attention(
                normed, {n: weights[n][nth] for n in ATTENTION_LEAVES},
                heads=sizes["num_attention_heads"],
                kv_heads=sizes["num_key_value_heads"],
                scale=float(sizes["attention_multiplier"]), lower=lower)
        else:
            raise ValueError(f"layer_types[{index}] = {kind!r}")
        x = x + residual * mixed
        normed = _rmsnorm(x, weights["moe_norm"][index], eps)
        x = x + residual * experts(
            normed, {n: weights[n][index] for n in EXPERT_LEAVES},
            top_k=sizes["num_experts_per_tok"],
            offset=int(sizes.get("local_expert_offset", 0)), lower=lower)
    return x


def logits(weights, tokens, sizes, rows, lower=None):
    """Logits [len(rows), V] of the full forward over `tokens` [S], at the
    positions `rows`. Layer by layer, so that it fits beside the weights."""
    with jax.default_matmul_precision("highest"):
        x = hidden(weights, tokens, sizes, lower)
        return head(x[rows], weights["final_norm"], weights["embedding"],
                    eps=float(sizes["rms_norm_eps"]), lower=lower) \
            / float(sizes["logits_scaling"])
