"""Plain reference of the `dots3_note` decoder's language model
(dots3-note-prev), in float32 `jax.numpy` at the highest matmul precision: no
cache, no absorbed products, no kernels, no batching. It imports nothing of
the program under test. `d` = hidden_size, eps = rms_norm_eps.

    h' = h + attn_kind(rmsnorm(h));  h'' = h' + ffn(rmsnorm(h'))
    logits = rmsnorm(h_L) @ W_head                       (untied head)

*latent attention*, on x = rmsnorm(h), with the sizes of the layer's kind
(`full_attention`: the plain keys; `sliding_attention`: the `swa_` keys):
`c_q = a_q rmsnorm(x W_qa)`; `[q_n | q_r] = c_q W_qb` per head;
`[c_raw | k_raw] = x W_kva`; `c = a_kv rmsnorm(c_raw)`; `q_r, k_r =
rope(q_r), rope(k_raw)` (one `k_r` for all heads); `[k_n | v] = c W_kvb` per
head; `s = (q_n.k_n + q_r.k_r) / sqrt(d_n + d_r)` over the allowed `j <= t`;
`o = softmax(s) v`; `g = sigmoid(x W_g)` (one number a head);
`attn = concat_h(g_h o_h) W_o`. `a_q = sqrt(d / q_lora_rank)`, `a_kv =
sqrt(d / kv_lora_rank)` where `apply_mla_qkv_lora_rescale` (else 1).
*allowed keys.* sliding: `t - j < sliding_window_size` (the token itself
counts). full: the `index_topk` largest of `I_tj = sum_i w_ti relu(q^I_ti .
k^I_j) / sqrt(index_n_heads index_head_dim)` over `j <= t`, chosen by an
explicit `top_k` and made a mask (every `j <= t` while there are no more than
`index_topk`); `q^I = c_q W_iq` per index head, `k^I = layernorm(x W_ik)`,
`w = x W_iw`, rope on the first `qk_rope_head_dim` numbers of `q^I`, `k^I`.
*rope* turns the pairs (2i, 2i + 1) by `position / theta^(2i / n)`.
*ffn.* layers below `first_k_dense_replace`: one SwiGLU of
`intermediate_size`. Later layers: `s = sigmoid(u W_r)`; the
`num_experts_per_tok` largest of `s + b`; `g_i = s_i / sum_chosen s`
(`norm_topk_prob`) times `routed_scaling_factor`; `sum_i g_i E_i(u) +
E_shared(u)`, each a SwiGLU of `moe_intermediate_size`. The sum runs over
the chosen experts that this share holds: `n_routed_experts_here` of them
from `routed_expert_offset` (0 where the file has none), in a loop over the
held experts with a mask. The vocabulary is the file's `vocab_size`, the
share's slice, and the layers are the first `num_hidden_layers` of
`layer_types`.

Departures from the published model: the vision and audio towers and the
MTP module are not run; the indexer's Hadamard rotation and fp8 are an
implementation's and left out; what the file lists under `assumed`.

Queries go a block at a time, so that the scores of 6144 tokens x 128 heads
fit beside the weights.

`lower="int8"` is the control: the same forward with both operands of every
linear layer (router and indexer too) rounded to 8-bit integers, weights per
output channel and activations per token, the nearest precision below the
bfloat16 that the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 128
LENGTH_STEP = 2048
FULL, SLIDING = "full_attention", "sliding_attention"


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


def _layernorm(x, scale, bias, eps):
    centred = x - jnp.mean(x, -1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(centred * centred, -1, keepdims=True) + eps) * scale + bias


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


def kind_sizes(sizes: dict, kind: str) -> dict:
    """(H, r_q, r_kv, d_n, d_r, d_v, theta) of a layer's kind."""
    pre = "swa_" if kind == SLIDING else ""
    return {
        "heads": sizes[pre + "num_attention_heads"],
        "d_nope": sizes[pre + "qk_nope_head_dim"],
        "d_rope": sizes[pre + "qk_rope_head_dim"],
        "d_v": sizes[pre + "v_head_dim"],
        "theta": float(sizes[pre + "rope_theta"]),
        "alpha_q": math.sqrt(sizes["hidden_size"] / sizes[pre + "q_lora_rank"])
        if sizes["apply_mla_qkv_lora_rescale"] else 1.0,
        "alpha_kv": math.sqrt(sizes["hidden_size"] / sizes[pre + "kv_lora_rank"])
        if sizes["apply_mla_qkv_lora_rescale"] else 1.0,
    }


@functools.partial(jax.jit, static_argnames=(
    "index_heads", "rope_dim", "theta", "top_k", "eps", "lower"))
def selected(x, c_q, w, *, index_heads, rope_dim, theta, top_k, eps,
             lower=None):
    """[S, S] bool: the keys `j <= t` that the indexer keeps for query t."""
    s = x.shape[0]
    q = _linear(c_q, w["index_q"], lower).reshape(s, index_heads, -1)
    k = _layernorm(_linear(x, w["index_k"], lower), w["index_k_scale"],
                   w["index_k_bias"], eps)
    weight = _linear(x, w["index_w"], lower)                  # [S, heads]
    q = jnp.concatenate([_rope(q[..., :rope_dim], theta), q[..., rope_dim:]], -1)
    k = jnp.concatenate([_rope(k[..., :rope_dim], theta), k[..., rope_dim:]], -1)
    scale = (index_heads * q.shape[-1]) ** -0.5
    block = min(QUERY_BLOCK, s)
    starts = jnp.arange(s // block) * block

    def some_rows(args):
        q_block, w_block, start = args
        score = jnp.einsum(
            "th,thj->tj", w_block,
            jax.nn.relu(jnp.einsum("thd,jd->thj", q_block, k,
                                   precision=HIGHEST)),
            precision=HIGHEST) * scale
        causal = (start + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        score = jnp.where(causal, score, -jnp.inf)
        # an explicit top_k, made a mask: every score above the k-th
        # largest and, of those equal to it, the earliest (top_k's order)
        kth = jax.lax.top_k(score, min(top_k, s))[0][:, -1:]
        above, equal = score > kth, score == kth
        room = min(top_k, s) - jnp.sum(above, -1, keepdims=True)
        keep = above | (equal & (jnp.cumsum(equal, -1) <= room))
        return keep & causal

    return jax.lax.map(some_rows, (
        q.reshape(s // block, block, index_heads, -1),
        weight.reshape(s // block, block, index_heads), starts)).reshape(s, s)


@functools.partial(jax.jit, static_argnames=(
    "heads", "d_nope", "d_rope", "d_v", "theta", "alpha_q", "alpha_kv",
    "window", "eps", "lower"))
def latent_attention(x, w, allowed, *, heads, d_nope, d_rope, d_v, theta,
                     alpha_q, alpha_kv, window, eps, lower=None):
    """The mixer on rmsnorm'ed x [S, D] (S a multiple of the query block or
    below it). `allowed` [S, S] bool or None (every `j <= t`); `window` the
    sliding kind's, or 0."""
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
        mask = rows >= keys
        if window:
            mask &= rows - keys < window
        if allowed is not None:
            mask &= jax.lax.dynamic_slice_in_dim(allowed, start, block, 0)
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("htj,jhd->thd", jax.nn.softmax(scores, -1), v,
                          precision=HIGHEST)

    out = jax.lax.map(some_rows, (
        q_n.reshape(s // block, block, heads, d_nope),
        q_r.reshape(s // block, block, heads, d_rope), starts))
    gate = jax.nn.sigmoid(_linear(x, w["gate"], lower))       # [S, heads]
    out = out.reshape(s, heads, d_v) * gate[:, :, None]
    return _linear(out.reshape(s, heads * d_v), w["o"], lower)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "offset", "normalise", "scale", "lower"))
def experts(x, w, *, top_k, offset, normalise, scale, lower=None):
    """moe(x) + shared(x) on rmsnorm'ed x [S, D]: the held experts, one
    after the other, each over every token and masked by its gate."""
    score = jax.nn.sigmoid(_linear(x, w["router"], lower))
    _, top_index = jax.lax.top_k(score + w["router_bias"], top_k)
    gates = jnp.take_along_axis(score, top_index, -1)
    if normalise:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * scale
    held = w["w_in"].shape[0]

    def one(total, inputs):
        w_in, w_out, expert = inputs
        gate = jnp.sum(jnp.where(top_index == expert, gates, 0.0), -1)
        return total + gate[:, None] * _swiglu(x, w_in, w_out, lower), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (w["w_in"], w["w_out"], offset + jnp.arange(held)))
    return routed + _swiglu(x, w["shared_in"], w["shared_out"], lower)


@functools.partial(jax.jit, static_argnames=("lower",))
def dense(x, w, *, lower=None):
    return _linear(jax.nn.silu(_linear(x, w["dense_gate"], lower))
                   * _linear(x, w["dense_up"], lower), w["dense_down"], lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def head(x, final_norm, w_head, *, eps, lower=None):
    return _linear(_rmsnorm(x, final_norm, eps), w_head, lower)


ATTENTION_LEAVES = ("q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "gate", "o")
INDEX_LEAVES = ("index_q", "index_k", "index_k_scale", "index_k_bias", "index_w")
DENSE_LEAVES = ("dense_gate", "dense_up", "dense_down")
EXPERT_LEAVES = ("router", "router_bias", "w_in", "w_out", "shared_in",
                 "shared_out")


def hidden(weights, tokens, sizes, lower=None):
    """The last layer's output [S, D] of the full forward over `tokens`."""
    eps = float(sizes["rms_norm_eps"])
    kinds = sizes["layer_types"][:sizes["num_hidden_layers"]]
    first_dense = sizes["first_k_dense_replace"]
    x = weights["embedding"].astype(jnp.float32)[tokens]
    of_kind = {FULL: 0, SLIDING: 0}
    for index, kind in enumerate(kinds):
        if kind not in of_kind:
            raise ValueError(f"layer_types[{index}] = {kind!r}")
        nth = of_kind[kind]
        of_kind[kind] += 1
        pre = "swa_" if kind == SLIDING else ""
        w = {n: weights[pre + n][nth] for n in ATTENTION_LEAVES}
        about = kind_sizes(sizes, kind)
        normed = _rmsnorm(x, weights["attn_norm"][index], eps)
        allowed = None
        if kind == FULL and x.shape[0] > sizes["index_topk"]:
            # the attention's own c_q, which the indexer reads
            c_q = about["alpha_q"] * _rmsnorm(
                _linear(normed, w["q_a"], lower), w["q_norm"], eps)
            allowed = selected(
                normed, c_q, {n: weights[n][nth] for n in INDEX_LEAVES},
                index_heads=sizes["index_n_heads"],
                rope_dim=sizes["qk_rope_head_dim"], theta=about["theta"],
                top_k=sizes["index_topk"], eps=eps, lower=lower)
        mixed = latent_attention(
            normed, w, allowed, eps=eps, lower=lower,
            window=sizes["sliding_window_size"] if kind == SLIDING else 0,
            **about)
        x = x + mixed
        normed = _rmsnorm(x, weights["ffn_norm"][index], eps)
        if index < first_dense:
            x = x + dense(normed, {n: weights[n][index] for n in DENSE_LEAVES},
                          lower=lower)
        else:
            x = x + experts(
                normed, {n: weights[n][index - first_dense]
                         for n in EXPERT_LEAVES},
                top_k=sizes["num_experts_per_tok"],
                offset=int(sizes.get("routed_expert_offset", 0)),
                normalise=bool(sizes["norm_topk_prob"]),
                scale=float(sizes["routed_scaling_factor"]), lower=lower)
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
