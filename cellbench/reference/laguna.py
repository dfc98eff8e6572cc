"""Plain reference of the `laguna` decoder (Laguna-XS.2's language model), in
float32 `jax.numpy` at the highest matmul precision: no cache, no ring, no
kernels, no batching. It imports nothing of the program under test. A leaf
may be held in a narrower type that holds its values exactly (check.py): it
is taken up to float32 where it is used, a matrix, an expert or a row at a
time, never a stack of experts whole. `d` = hidden_size, eps = rms_norm_eps.

For layer `l` of the first `num_hidden_layers`, `H = num_attention_heads_per_
layer[l]`, on the stream `x` [S, d]:

    u  = rmsnorm(x)
    q  = u W_q [S, H, 128]    k = u W_k [S, 8, 128]    v = u W_v [S, 8, 128]
    q, k = R_l(q), R_l(k)
    a_h = softmax(q_h k_g^T / sqrt(128) + M_l) v_g          g = h // (H / 8)
    o  = concat_h(sigmoid(u W_g)_h * a_h) W_o               W_g [d, H]
    h1 = x + o
    y  = h1 + F_l(rmsnorm(h1))
    logits = rmsnorm(y_L) @ W_head                          (untied head)

`M_l`: `layer_types[l] == "full_attention"`: every `j <= t`;
`"sliding_attention"`: `t - j < sliding_window` (512 keys with the query
itself: the library's `k > q - sliding_window`).
`R_l`, from `rope_parameters[layer_types[l]]`: the first `partial_rotary_
factor x head_dim` numbers of a head turn, the rest pass; `rope_type:
"default"`: `f_i = theta^(-2i / n)`; `"yarn"` (arXiv:2309.00071, as
`transformers` computes it): `corr(b) = n ln(original_max / (2 pi b)) /
(2 ln theta)`, `low = floor(corr(beta_fast))`, `high = ceil(corr(beta_slow))`
(5 and 16 at the published values), `r_i = clip((i - low) / (high - low), 0,
1)`, `f'_i = r_i f_i / factor + (1 - r_i) f_i`, and cos and sin are
multiplied by `attention_factor`.
`F_l`, `mlp_layer_types[l] == "dense"`: a SwiGLU of `intermediate_size`;
`"sparse"`: `z = u2 W_r` (`num_experts` wide), the `num_experts_per_tok`
largest, `g = moe_routed_scaling_factor x softmax` over those logits alone,
`F = sum_i g_i E_i(u2) + E_shared(u2)`, every `E` a SiLU SwiGLU.

Conventions the published config leaves open, as the configuration file's
`assumed` grounds them: the gate is per head, a sigmoid, of the attention's
own normed input; the routing is a softmax renormalised over the chosen; q
and k are not normalised and the shared expert has no gate; SiLU; pre-norm
residual order; the window counts the query. *rope* turns the pairs
(2i, 2i + 1), this repo's convention, where the library turns the halves
(i, i + n / 2): a permutation of seeded columns of `W_q` and `W_k`, the same
in the program.

Queries go a block at a time, so that the scores of 6144 tokens x 64 heads
fit beside the weights.

`lower="int8"` is the control: the same forward with both operands of every
linear layer (the router and the gate too) rounded to 8-bit integers, weights
per output channel and activations per token, the nearest precision below
the bfloat16 that the configuration states.
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


def _swiglu(x, w_in, w_out, lower):
    a, b = jnp.split(_linear(x, w_in, lower), 2, axis=-1)
    return _linear(jax.nn.silu(a) * b, w_out, lower)


def yarn_range(n, theta, original_max, beta_fast, beta_slow):
    """(low, high) of YaRN's ramp over the `n / 2` pairs."""
    def corr(turns):
        return n * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    return max(math.floor(corr(beta_fast)), 0), \
        min(math.ceil(corr(beta_slow)), n - 1)


def rotary_recipe(sizes: dict, kind: str) -> tuple:
    """(n, frequencies of the n / 2 pairs, attention factor) of a layer kind,
    hashable."""
    told = sizes["rope_parameters"][kind]
    n = int(round(told["partial_rotary_factor"] * sizes["head_dim"]))
    theta = float(told["rope_theta"])
    own = [theta ** (-2.0 * i / n) for i in range(n // 2)]
    if told["rope_type"] == "default":
        return n, tuple(own), 1.0
    if told["rope_type"] != "yarn":
        raise ValueError(f"rope_type {told['rope_type']!r}")
    low, high = yarn_range(n, theta, told["original_max_position_embeddings"],
                           told["beta_fast"], told["beta_slow"])
    ramp = [min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
            for i in range(n // 2)]
    return n, tuple(r * f / told["factor"] + (1.0 - r) * f
                    for r, f in zip(ramp, own)), \
        float(told["attention_factor"])


def _rotate(x, recipe):
    """x [S, H, D] at positions 0..S-1: the pairs (2i, 2i + 1) of the first
    n numbers turned by cos and sin times the factor, the rest passed."""
    n, freqs, factor = recipe
    s = x.shape[0]
    angles = jnp.arange(s, dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    x1, x2 = x[..., 0:n:2], x[..., 1:n:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                       axis=-1).reshape(x.shape[:-1] + (n,))
    return jnp.concatenate([turned, x[..., n:]], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "window", "recipe", "gated", "lower"))
def attention(u, w, *, heads, kv_heads, head_dim, window, recipe, gated=True,
              lower=None):
    """One attention on rmsnorm'ed u [S, d] (S a multiple of the query block
    or below it). `window` 0: every `j <= t`; else `t - j < window`."""
    s = u.shape[0]
    q = _rotate(_linear(u, w["q"], lower).reshape(s, heads, head_dim), recipe)
    k = _rotate(_linear(u, w["k"], lower).reshape(s, kv_heads, head_dim),
                recipe)
    v = _linear(u, w["v"], lower).reshape(s, kv_heads, head_dim)
    group = heads // kv_heads
    block = min(QUERY_BLOCK, s)
    starts = jnp.arange(s // block) * block
    keys = jnp.arange(s)[None, :]

    def some_rows(args):
        q_block, start = args
        rows = (start + jnp.arange(block))[:, None]
        mask = rows >= keys
        if window:
            mask &= rows - keys < window
        grouped = q_block.reshape(block, kv_heads, group, head_dim)
        scores = jnp.einsum("tgrd,jgd->grtj", grouped, k,
                            precision=HIGHEST) * head_dim ** -0.5
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return jnp.einsum("grtj,jgd->tgrd", jax.nn.softmax(scores, -1), v,
                          precision=HIGHEST).reshape(block, heads, head_dim)

    out = jax.lax.map(some_rows, (
        q.reshape(s // block, block, heads, head_dim), starts))
    out = out.reshape(s, heads, head_dim)
    if gated:
        out = out * jax.nn.sigmoid(_linear(u, w["gate"], lower))[..., None]
    return _linear(out.reshape(s, heads * head_dim), w["o"], lower)


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "lower"))
def moe(u, w, *, top_k, scale, lower=None):
    """The expert layer on rmsnorm'ed u [S, d]: the experts one after the
    other, each over every token and masked by its gate, then the shared
    expert."""
    top_logits, top_index = jax.lax.top_k(_linear(u, w["router"], lower), top_k)
    gates = scale * jax.nn.softmax(top_logits, -1)

    def one(total, inputs):
        w_in, w_out, expert = inputs
        gate = jnp.sum(jnp.where(top_index == expert, gates, 0.0), -1)
        return total + gate[:, None] * _swiglu(u, w_in, w_out, lower), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (w["w_in"], w["w_out"], jnp.arange(w["w_in"].shape[0])))
    return routed + _swiglu(u, w["shared_in"], w["shared_out"], lower)


@functools.partial(jax.jit, static_argnames=("lower",))
def dense(x, w_gate, w_up, w_down, *, lower=None):
    return _linear(jax.nn.silu(_linear(x, w_gate, lower))
                   * _linear(x, w_up, lower), w_down, lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def head(x, final_norm, w_head, *, eps, lower=None):
    return _linear(_rmsnorm(x, final_norm, eps), w_head, lower)


ATTENTION_LEAVES = ("q", "k", "v", "gate", "o")
EXPERT_LEAVES = ("router", "w_in", "w_out", "shared_in", "shared_out")
DENSE_LEAVES = ("dense_gate", "dense_up", "dense_down")


def places(sizes: dict) -> list:
    """Per layer: (attention kind, place among the layers of that kind, ffn
    kind, place among the layers of that kind). The weight table lists an
    attention's leaves over the layers of its kind (the sliding kind's under
    `swa_`) and a feed-forward's over the layers of its kind."""
    seen, out = {}, []
    depth = sizes["num_hidden_layers"]
    for kind, ffn in zip(sizes["layer_types"][:depth],
                         sizes["mlp_layer_types"][:depth]):
        out.append((kind, seen.get(kind, 0), ffn, seen.get(ffn, 0)))
        seen[kind] = seen.get(kind, 0) + 1
        seen[ffn] = seen.get(ffn, 0) + 1
    return out


def layer(weights, x, index, sizes, lower=None, **wrong):
    """One layer on the stream x [S, d]. `wrong` is the tests': a reference
    with one thing other than stated (`window`: the sliding layers';
    `gated`; `recipes`: layer kind -> `rotary_recipe`; `routed_scale`)."""
    eps = float(sizes["rms_norm_eps"])
    kind, nth, ffn, ffn_nth = places(sizes)[index]
    pre = "swa_" if kind == SLIDING else ""
    about = dict(
        heads=sizes["num_attention_heads_per_layer"][index],
        kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        window=wrong.get("window", sizes["sliding_window"])
        if kind == SLIDING else 0,
        recipe=wrong.get("recipes", {}).get(kind)
        or rotary_recipe(sizes, kind),
        gated=wrong.get("gated", True))
    u = _rmsnorm(x, weights["attn_norm"][index], eps)
    x = x + attention(
        u, {n: weights[pre + n][nth] for n in ATTENTION_LEAVES},
        lower=lower, **about)
    u = _rmsnorm(x, weights["ffn_norm"][index], eps)
    if ffn == "dense":
        return x + dense(u, *(weights[n][ffn_nth] for n in DENSE_LEAVES),
                         lower=lower)
    return x + moe(
        u, {n: weights[n][ffn_nth] for n in EXPERT_LEAVES},
        top_k=sizes["num_experts_per_tok"],
        scale=float(wrong.get("routed_scale",
                              sizes["moe_routed_scaling_factor"])),
        lower=lower)


def hidden(weights, tokens, sizes, lower=None, **wrong):
    """The last layer's output [S, d] of the full forward over `tokens`."""
    x = weights["embedding"][tokens].astype(jnp.float32)
    for index in range(sizes["num_hidden_layers"]):
        x = layer(weights, x, index, sizes, lower, **wrong)
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


def logits(weights, tokens, sizes, rows, lower=None, **wrong):
    """Logits [len(rows), V] of the full forward over `tokens` [S], at the
    positions `rows`. Layer by layer, so that it fits beside the weights.
    The tokens are padded at the end to one of a few lengths (the forward is
    causal: no row sees the padding), so that a run compiles few shapes."""
    tokens = jnp.pad(tokens, (0, padded_length(tokens.shape[0]) - tokens.shape[0]))
    with jax.default_matmul_precision("highest"):
        x = hidden(weights, tokens, sizes, lower, **wrong)
        return head(x[rows], weights["final_norm"], weights["head"],
                    eps=float(sizes["rms_norm_eps"]), lower=lower)
