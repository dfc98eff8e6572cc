"""Autoregressive generation with KV-cache decoding.

Inference for the decoder family: one prefill pass writes the prompt into
each layer's KV cache, then a decode loop samples and extends the cache —
O(1) attention work per new token instead of re-running the full
sequence. Greedy, temperature, top-k, and top-p (nucleus) sampling.

`generate` is a thin wrapper over the persistent compiled engine
(`models.decode_engine.DecodeEngine`): prefill and the on-device decode
loop are compiled once per shape bucket and reused across calls, the
token loop runs as one `lax.while_loop` (EOS early-exit included — zero
host syncs per token), and the KV cache is donated. `generate_legacy`
keeps the original per-call-jit host loop for A/B benchmarking and
equivalence tests.

No reference analog (tf-yarn is a training launcher); provided because a
complete model family needs an inference path.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _sample(logits, rng, temperature: float, top_k: Optional[int],
            top_p: Optional[float] = None):
    """logits [B, V] -> token ids [B]."""
    with jax.named_scope("sample"):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / temperature
        if top_k is not None or top_p is not None:
            # One descending sort serves both filters — a second full-vocab
            # sort per decode token would double the hot-path sort cost.
            sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
            if top_k is not None:
                # top_k >= vocab keeps everything; unclamped it would index
                # past the sorted row's end.
                k = max(1, min(int(top_k), logits.shape[-1]))
                kth = sorted_desc[:, k - 1][:, None]
                logits = jnp.where(logits < kth, -1e30, logits)
                # Mirror the mask in sorted space so top_p renormalizes over
                # the top_k-filtered distribution (value-based: ties at the
                # threshold survive in both views).
                sorted_desc = jnp.where(sorted_desc < kth, -1e30, sorted_desc)
            if top_p is not None:
                # Nucleus sampling: keep the smallest probability-sorted
                # prefix whose mass reaches top_p; the keep-mask scatters
                # back by comparing each logit to the cutoff logit
                # (sort+cumsum, no gather/scatter ops — XLA-clean).
                probs = jax.nn.softmax(sorted_desc, axis=-1)
                cumulative = jnp.cumsum(probs, axis=-1)
                # Positions strictly past the nucleus; the first token
                # always stays (cumulative >= top_p only AFTER including it).
                in_nucleus = cumulative - probs < top_p
                cutoff_idx = jnp.maximum(jnp.sum(in_nucleus, axis=-1) - 1, 0)
                cutoff = jnp.take_along_axis(
                    sorted_desc, cutoff_idx[:, None], axis=-1)
                logits = jnp.where(logits < cutoff, -1e30, logits)
        return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def generate(
    model,
    params,
    prompt_tokens,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    seed: int = 0,
    eos_token: Optional[int] = None,
):
    """Extend `prompt_tokens` [B, P] by up to `max_new_tokens`.

    `params` are unboxed variables ({"params": ...}); the KV cache is
    created by the prefill apply (sized config.max_seq_len) and updated
    in place (donated) by the compiled decode loop. Returns
    [B, P + max_new_tokens] int32 (positions after an eos_token, if
    given, repeat eos).

    All prompts in a batch share length P (the prefill writes one cache
    offset for the whole batch). For ragged prompts, bucket requests by
    length (inference.py batches this way) — left-padding with per-row
    cache offsets is not supported.

    Calls route through the module-level `DecodeEngine` for `model`
    (`decode_engine.get_engine`): repeated calls in the same shape
    bucket reuse one compiled prefill + decode program. When the batch
    is padded up to a bucket, sampled (temperature > 0) draws for the
    real rows can differ from an unpadded call — the categorical noise
    is shaped by the padded batch — and low-precision compute (bf16) can
    flip near-tied greedy argmaxes because the padded shape compiles to
    a different fusion; construct a `DecodeEngine` with custom
    `batch_buckets` when exact reproducibility across batch sizes
    matters.
    """
    from tf_yarn_tpu.models.decode_engine import get_engine

    return get_engine(model).generate(
        params,
        prompt_tokens,
        max_new_tokens,
        temperature=temperature,
        top_k=top_k,
        top_p=top_p,
        seed=seed,
        eos_token=eos_token,
    )


def generate_legacy(
    model,
    params,
    prompt_tokens,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    seed: int = 0,
    eos_token: Optional[int] = None,
):
    """The original host-driven decode loop: a fresh jitted step closure
    per call and one device→host sync per token (`bool(finished.all())`).
    Kept as the A/B baseline for the engine (benchmarks/run.py decode's
    `percall_jit` variant) and as the reference the engine's bucketing
    must reproduce exactly (tests/test_decode_engine.py)."""
    prompt_tokens = jnp.asarray(prompt_tokens, jnp.int32)
    b, prompt_len = prompt_tokens.shape
    cfg = model.config
    if prompt_len + max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds config.max_seq_len ({cfg.max_seq_len}) — the KV cache size"
        )
    if max_new_tokens == 0:
        return prompt_tokens
    # Host-restored checkpoints arrive as numpy; numpy leaves break traced
    # indexing inside the jitted step, so promote everything to jnp once.
    params = jax.tree_util.tree_map(jnp.asarray, params)
    rng = jax.random.PRNGKey(seed)

    # Prefill: one pass over the prompt, cache created and filled.
    logits, state = model.apply(
        params, prompt_tokens, decode=True, mutable=["cache"]
    )
    cache = state["cache"]
    rng, prefill_rng = jax.random.split(rng)
    next_token = _sample(
        logits[:, -1], prefill_rng, temperature, top_k, top_p)

    @jax.jit
    def step(cache, token, rng):
        logits, state = model.apply(
            {**params, "cache": cache}, token[:, None], decode=True,
            mutable=["cache"],
        )
        return state["cache"], _sample(
            logits[:, -1], rng, temperature, top_k, top_p)

    tokens = [next_token]
    finished = jnp.zeros((b,), bool) if eos_token is not None else None
    for i in range(max_new_tokens - 1):
        rng, step_rng = jax.random.split(rng)
        cache, next_token = step(cache, tokens[-1], step_rng)
        if eos_token is not None:
            finished = finished | (tokens[-1] == eos_token)
            next_token = jnp.where(finished, eos_token, next_token)
            if bool(finished.all()):
                tokens.extend(
                    [jnp.full((b,), eos_token, jnp.int32)]
                    * (max_new_tokens - 1 - i)
                )
                break
        tokens.append(next_token)
    generated = jnp.stack(tokens[:max_new_tokens], axis=1)
    return jnp.concatenate([prompt_tokens, generated], axis=1)
