"""Plain reference of the `deepseek_v32` decoder (DeepSeek-V3.2; HF
`DeepseekV3Attention` / `DeepseekV3TopkRouter` with the V3.2 inference code's
`Indexer`), in float32 `jax.numpy` at the highest matmul precision: no cache,
no absorbed products, no kernels, no batching. It imports nothing of the
program under test. A leaf may be held in a narrower type that holds its
values exactly (check.py): it is taken up to float32 where it is used, a
matrix, an expert or a row at a time, never a stack of experts whole.
`d` = hidden_size, eps = rms_norm_eps.

    h' = h + attn(rmsnorm(h));  h'' = h' + ffn(rmsnorm(h'))
    logits = rmsnorm(h_L) @ W_head                       (untied head)

*attention* (every layer), on x = rmsnorm(h): `c_q = rmsnorm(x W_qa)`;
`[q_n | q_r] = c_q W_qb` per head; `[c_raw | k_raw] = x W_kva`; `c =
rmsnorm(c_raw)`; `q_r, k_r = rope(q_r), rope(k_raw)` (one `k_r` for all
heads); `[k_n | v] = c W_kvb` per head; `s = m^2 (q_n.k_n + q_r.k_r) /
sqrt(d_n + d_r)` over the allowed `j <= t`, `m = 0.1 mscale_all_dim
ln(factor) + 1`; `attn = concat_h(softmax(s) v) W_o`. No gate, no rescale.
*allowed keys*: the `index_topk` largest of `I_tj = sum_i w_ti relu(q^I_ti .
k^I_j) / sqrt(index_n_heads index_head_dim)` over `j <= t`, chosen by an
explicit `top_k` and made a mask (every `j <= t` while there are no more than
`index_topk`); `q^I = c_q W_iq` per index head, `k^I = layernorm(x W_ik)`,
`w = x W_iw`, rope on the first `qk_rope_head_dim` numbers of `q^I`, `k^I`.
*rope* is YaRN as `transformers` computes it for this `rope_scaling`: the
pair i turns by `position x f_i`, `f_i` blended between `theta^(-2i / n)`
and a `factor`-th of it over the correction range of `beta_fast` and
`beta_slow` turns in `original_max_position_embeddings` positions; cos and
sin carry `mscale(mscale) / mscale(mscale_all_dim)`, which is 1 as published.
*ffn.* layers below `first_k_dense_replace`: one SwiGLU of
`intermediate_size`. Later layers (`noaux_tc`): `s = sigmoid(u W_r)`; the
experts lie in `n_group` groups; a group's score is the sum of its two
largest `s + b`; the `topk_group` best groups are kept; the
`num_experts_per_tok` largest `s + b` inside them are chosen; `g_i = s_i /
sum_chosen s` (`norm_topk_prob`) times `routed_scaling_factor`; `sum_i g_i
E_i(u) + E_shared(u)`, each a SwiGLU (`moe_intermediate_size`; the shared one
`n_shared_experts` times as wide). The sum runs over the chosen experts that
this share holds: `n_routed_experts_here` of them from `routed_expert_offset`
(0 where the file has none), in a loop over the held experts with a mask;
`shared=False` leaves the shared expert out, for the test that adds the
shares up and counts it once. The vocabulary is the file's `vocab_size`, the
share's slice, and `num_hidden_layers` layers are run.

Departures from the published code, each at its line below: *rope* turns the
pairs (2i, 2i + 1), this repo's convention, for the attention and the indexer
alike (where a published implementation turns the halves (i, i + n/2), as
its indexer does, that is the same function of other columns of `W_iq`,
`W_ik`); the indexer's Hadamard rotation of `q^I`, `k^I` and their fp8
quantisation are an implementation's and left out (the rotation is
orthogonal: it leaves `q^I . k^I` what it was); entries outside the kept
groups are put at -inf (the published inference code; `transformers` puts
them at 0, which differs only where a kept `s + b` is negative); the
multi-token-prediction layer is not run; what the file lists under
`assumed`.

Queries go a block at a time, so that the scores of 12,288 tokens x 128 heads
never form `[128, S, S]`.

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
import numpy as np

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


def _layernorm(x, scale, bias, eps):
    centred = x - jnp.mean(x, -1, keepdims=True)
    return centred * jax.lax.rsqrt(
        jnp.mean(centred * centred, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32) + bias.astype(jnp.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(n: int, theta: float, scaling: dict) -> np.ndarray:
    """`transformers`' `_compute_yarn_parameters` over `n` numbers: [n / 2]
    float32, computed in float64."""
    factor = float(scaling["factor"])
    original = scaling["original_max_position_embeddings"]

    def correction_dim(turns):
        return n * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), n - 1)
    if low == high:
        high += 0.001
    own = theta ** -(np.arange(0, n, 2, dtype=np.float64) / n)
    ramp = np.clip((np.arange(n // 2, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    return (own / factor * ramp + own * (1.0 - ramp)).astype(np.float32)


def _rope(x, frequencies, factor):
    """x [S, ..., n] at positions 0..S-1. Departure: the pairs (2i, 2i + 1)
    are turned, here and in the indexer."""
    s = x.shape[0]
    angles = jnp.arange(s, dtype=jnp.float32).reshape(
        (s,) + (1,) * (x.ndim - 1)) * frequencies
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(x, w_in, w_out, lower):
    a, b = jnp.split(_linear(x, w_in, lower), 2, axis=-1)
    return _linear(jax.nn.silu(a) * b, w_out, lower)


def rotary_of(sizes: dict):
    """(frequencies as a tuple, what cos and sin carry, the softmax's m)."""
    scaling = sizes["rope_scaling"]
    if scaling["type"] != "yarn":
        raise ValueError(f"rope_scaling.type {scaling['type']!r}")
    factor = float(scaling["factor"])
    frequencies = yarn_frequencies(
        sizes["qk_rope_head_dim"], float(sizes["rope_theta"]), scaling)
    return (tuple(float(f) for f in frequencies),
            yarn_mscale(factor, scaling["mscale"])
            / yarn_mscale(factor, scaling["mscale_all_dim"]),
            yarn_mscale(factor, scaling["mscale_all_dim"]))


@functools.partial(jax.jit, static_argnames=(
    "index_heads", "frequencies", "rope_factor", "top_k", "eps", "lower"))
def selected(x, c_q, w, *, index_heads, frequencies, rope_factor, top_k, eps,
             lower=None):
    """[S, S] bool: the keys `j <= t` that the indexer keeps for query t.
    Departure: no Hadamard rotation, no fp8."""
    s = x.shape[0]
    freqs = jnp.asarray(frequencies, jnp.float32)
    rope_dim = 2 * len(frequencies)
    q = _linear(c_q, w["index_q"], lower).reshape(s, index_heads, -1)
    k = _layernorm(_linear(x, w["index_k"], lower), w["index_k_scale"],
                   w["index_k_bias"], eps)
    weight = _linear(x, w["index_w"], lower)                  # [S, heads]
    q = jnp.concatenate([_rope(q[..., :rope_dim], freqs, rope_factor),
                         q[..., rope_dim:]], -1)
    k = jnp.concatenate([_rope(k[..., :rope_dim], freqs, rope_factor),
                         k[..., rope_dim:]], -1)
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
    "heads", "d_nope", "d_rope", "d_v", "frequencies", "rope_factor",
    "mscale", "eps", "lower"))
def latent_attention(x, w, allowed, *, heads, d_nope, d_rope, d_v,
                     frequencies, rope_factor, mscale, eps, lower=None):
    """The mixer on rmsnorm'ed x [S, D] (S a multiple of the query block or
    below it). `allowed` [S, S] bool or None (every `j <= t`)."""
    s = x.shape[0]
    freqs = jnp.asarray(frequencies, jnp.float32)
    c_q = _rmsnorm(_linear(x, w["q_a"], lower), w["q_norm"], eps)
    q = _linear(c_q, w["q_b"], lower).reshape(s, heads, d_nope + d_rope)
    q_n, q_r = q[..., :d_nope], _rope(q[..., d_nope:], freqs, rope_factor)
    kv = _linear(x, w["kv_a"], lower)
    c = _rmsnorm(kv[:, :-d_rope], w["kv_norm"], eps)
    k_r = _rope(kv[:, -d_rope:], freqs, rope_factor)
    expanded = _linear(c, w["kv_b"], lower).reshape(s, heads, d_nope + d_v)
    k_n, v = expanded[..., :d_nope], expanded[..., d_nope:]
    scale = mscale * mscale * (d_nope + d_rope) ** -0.5
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
        if allowed is not None:
            mask &= jax.lax.dynamic_slice_in_dim(allowed, start, block, 0)
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("htj,jhd->thd", jax.nn.softmax(scores, -1), v,
                          precision=HIGHEST)

    out = jax.lax.map(some_rows, (
        q_n.reshape(s // block, block, heads, d_nope),
        q_r.reshape(s // block, block, heads, d_rope), starts))
    return _linear(out.reshape(s, heads * d_v), w["o"], lower)


def routing(score, bias, *, top_k, n_group, topk_group, normalise, scale):
    """(chosen experts [S, k], their gates [S, k]) of sigmoid scores [S, E]
    under `noaux_tc`. Departure: outside the kept groups -inf, not 0."""
    s, experts = score.shape
    choice = score + bias.astype(jnp.float32)
    if n_group > 1:
        grouped = choice.reshape(s, n_group, experts // n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)
        _, best = jax.lax.top_k(group_score, topk_group)
        kept = jnp.zeros((s, n_group), bool).at[
            jnp.arange(s)[:, None], best].set(True)
        choice = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(
            s, experts)
    _, top_index = jax.lax.top_k(choice, top_k)
    gates = jnp.take_along_axis(score, top_index, -1)
    if normalise:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    return top_index, gates * scale


@functools.partial(jax.jit, static_argnames=(
    "top_k", "n_group", "topk_group", "offset", "normalise", "scale",
    "shared", "lower"))
def experts(x, w, *, top_k, n_group, topk_group, offset, normalise, scale,
            shared=True, lower=None):
    """moe(x) + shared(x) on rmsnorm'ed x [S, D]: the held experts, one
    after the other, each over every token and masked by its gate."""
    score = jax.nn.sigmoid(_linear(x, w["router"], lower))
    top_index, gates = routing(
        score, w["router_bias"], top_k=top_k, n_group=n_group,
        topk_group=topk_group, normalise=normalise, scale=scale)
    held = w["w_in"].shape[0]

    def one(total, inputs):
        w_in, w_out, expert = inputs
        gate = jnp.sum(jnp.where(top_index == expert, gates, 0.0), -1)
        return total + gate[:, None] * _swiglu(x, w_in, w_out, lower), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (w["w_in"], w["w_out"], offset + jnp.arange(held)))
    if not shared:
        return routed
    return routed + _swiglu(x, w["shared_in"], w["shared_out"], lower)


@functools.partial(jax.jit, static_argnames=("lower",))
def dense(x, w, *, lower=None):
    return _linear(jax.nn.silu(_linear(x, w["dense_gate"], lower))
                   * _linear(x, w["dense_up"], lower), w["dense_down"], lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def head(x, final_norm, w_head, *, eps, lower=None):
    return _linear(_rmsnorm(x, final_norm, eps), w_head, lower)


ATTENTION_LEAVES = ("q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "o")
INDEX_LEAVES = ("index_q", "index_k", "index_k_scale", "index_k_bias", "index_w")
DENSE_LEAVES = ("dense_gate", "dense_up", "dense_down")
EXPERT_LEAVES = ("router", "router_bias", "w_in", "w_out", "shared_in",
                 "shared_out")


def routing_of(sizes: dict) -> dict:
    for key, value in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc")):
        if sizes[key] != value:
            raise ValueError(f"{key} {sizes[key]!r}")
    return {"top_k": sizes["num_experts_per_tok"],
            "n_group": sizes["n_group"], "topk_group": sizes["topk_group"],
            "offset": int(sizes.get("routed_expert_offset", 0)),
            "normalise": bool(sizes["norm_topk_prob"]),
            "scale": float(sizes["routed_scaling_factor"])}


def hidden(weights, tokens, sizes, lower=None):
    """The last layer's output [S, D] of the full forward over `tokens`.
    Departure: the multi-token-prediction layer is not run."""
    eps = float(sizes["rms_norm_eps"])
    first_dense = sizes["first_k_dense_replace"]
    frequencies, rope_factor, mscale = rotary_of(sizes)
    about = {"heads": sizes["num_attention_heads"],
             "d_nope": sizes["qk_nope_head_dim"],
             "d_rope": sizes["qk_rope_head_dim"], "d_v": sizes["v_head_dim"],
             "frequencies": frequencies, "rope_factor": rope_factor,
             "mscale": mscale}
    x = weights["embedding"][tokens].astype(jnp.float32)
    for index in range(sizes["num_hidden_layers"]):
        w = {n: weights[n][index] for n in ATTENTION_LEAVES}
        normed = _rmsnorm(x, weights["attn_norm"][index], eps)
        allowed = None
        if x.shape[0] > sizes["index_topk"]:
            # the attention's own c_q, which the indexer reads
            c_q = _rmsnorm(_linear(normed, w["q_a"], lower), w["q_norm"], eps)
            allowed = selected(
                normed, c_q, {n: weights[n][index] for n in INDEX_LEAVES},
                index_heads=sizes["index_n_heads"], frequencies=frequencies,
                rope_factor=rope_factor, top_k=sizes["index_topk"], eps=eps,
                lower=lower)
        x = x + latent_attention(normed, w, allowed, eps=eps, lower=lower,
                                 **about)
        normed = _rmsnorm(x, weights["ffn_norm"][index], eps)
        if index < first_dense:
            x = x + dense(normed, {n: weights[n][index] for n in DENSE_LEAVES},
                          lower=lower)
        else:
            x = x + experts(
                normed, {n: weights[n][index - first_dense]
                         for n in EXPERT_LEAVES},
                lower=lower, **routing_of(sizes))
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
