"""Attention implementations and the dispatch seam.

The hot op of the transformer family. Three interchangeable backends, all
the same signature — [B, S, H, D] q, [B, S_kv, H_kv, D] k/v, GQA via
H_kv <= H — selected by `TransformerConfig.attention_impl`:

* ``"xla"``   — einsum + softmax; XLA fuses it well on the MXU and it runs
  everywhere (CPU test rig included). The correctness reference.
* ``"flash"`` — pallas blockwise-softmax kernel (tf_yarn_tpu/ops/
  flash_attention.py), HBM-friendly for long sequences on TPU.
* ``"ring"``  — sequence-parallel ring attention over the `sp` mesh axis
  (tf_yarn_tpu/parallel/ring_attention.py) for sequences longer than one
  chip's HBM can hold.
* ``"ulysses"`` — all-to-all sequence parallelism over `sp`
  (tf_yarn_tpu/parallel/ulysses.py): re-shard seq->heads, full-sequence
  attention per head shard, re-shard back. ``"ulysses_flash"`` runs the
  pallas flash kernel as the per-shard inner attention.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _repeat_kv(key: jax.Array, value: jax.Array, n_rep: int):
    if n_rep == 1:
        return key, value
    with jax.named_scope("attention/repeat_kv"):
        key = jnp.repeat(key, n_rep, axis=2)
        value = jnp.repeat(value, n_rep, axis=2)
    return key, value


def xla_attention(
    query: jax.Array,
    key: jax.Array,
    value: jax.Array,
    *,
    causal: bool = True,
    softmax_scale: float | None = None,
    segment_offset: int = 0,
    key_padding_mask: jax.Array | None = None,
) -> jax.Array:
    """Reference attention: q [B,S,H,D], k/v [B,Skv,Hkv,D] -> [B,S,H,D].

    `segment_offset` shifts the causal mask for sequence-sharded callers
    (ring attention evaluates blocks whose global positions start there).
    `key_padding_mask` [B, S_kv] (1/True = real token) hides padded keys
    from every query — the encoder-family batching contract.
    Softmax runs in f32 regardless of input dtype — the bf16-safe pattern.
    """
    b, s_q, n_heads, head_dim = query.shape
    _, s_kv, n_kv, _ = key.shape
    # GQA without a copy: query head h is member h % rep of group h // rep
    # (the order `_repeat_kv` gives), so the heads view as [H_kv, rep] and
    # contract against K/V as the cache holds them. Only the logits carry
    # both the query's head count and the keys' length.
    rep = n_heads // n_kv
    grouped = query.reshape(b, s_q, n_kv, rep, head_dim)
    scale = softmax_scale if softmax_scale is not None else head_dim**-0.5
    # Scopes, not spans: metadata on the device operations, so a profile
    # names scores, softmax and values apart (docs/Observability.md).
    with jax.named_scope("attention/scores"):
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", grouped, key) * scale
        logits = logits.astype(jnp.float32)
        neg_inf = jnp.finfo(jnp.float32).min
        if causal:
            q_pos = jnp.arange(s_q)[:, None] + segment_offset
            k_pos = jnp.arange(s_kv)[None, :]
            mask = q_pos >= k_pos
            logits = jnp.where(mask[None, None, None], logits, neg_inf)
        if key_padding_mask is not None:
            # [B,1,1,1,Skv] over the logits' [B,Hkv,rep,S,Skv].
            keep = key_padding_mask.astype(bool)[:, None, None, None, :]
            logits = jnp.where(keep, logits, neg_inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(query.dtype)
    with jax.named_scope("attention/values"):
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, value)
        if key_padding_mask is not None:
            # A fully-padded row (no real keys) would otherwise get a
            # silent uniform softmax over finfo.min logits — finite
            # garbage. Zero those rows' outputs instead: [B,1,1,1,1]
            # broadcast over out's [B,S,Hkv,rep,D].
            has_any_key = jnp.any(keep, axis=-1, keepdims=True)
            out = jnp.where(has_any_key, out, jnp.zeros((), out.dtype))
        out = out.reshape(b, s_q, n_heads, head_dim)
    return out


def attention(query, key, value, *, impl: str = "xla", causal: bool = True,
              key_padding_mask=None, softmax_scale: float | None = None):
    """Dispatch to the configured backend. `key_padding_mask` is an
    xla-impl feature (the flash/ring/ulysses kernels have no arbitrary-
    mask path — their masking is structural/causal); passing one there
    raises rather than silently attending to padding."""
    known = ("xla", "flash", "ring", "ulysses", "ulysses_flash")
    if key_padding_mask is not None and impl in known[1:]:
        raise NotImplementedError(
            f"key_padding_mask is not supported by attention impl "
            f"{impl!r}; use impl='xla' for padded-batch encoders (or "
            "strip padding before a kernel impl)"
        )
    if softmax_scale is not None and impl in known[1:]:
        raise NotImplementedError(
            f"softmax_scale is not supported by attention impl {impl!r} "
            "(the kernels scale by head_dim**-0.5); use impl='xla'"
        )
    if impl == "flash":
        from tf_yarn_tpu.ops.flash_attention import flash_attention

        return flash_attention(query, key, value, causal=causal)
    if impl == "ring":
        from tf_yarn_tpu.parallel.ring_attention import ring_attention_sharded

        return ring_attention_sharded(query, key, value, causal=causal)
    if impl in ("ulysses", "ulysses_flash"):
        from tf_yarn_tpu.parallel.ulysses import ulysses_attention_sharded

        return ulysses_attention_sharded(
            query, key, value, causal=causal,
            inner="flash" if impl == "ulysses_flash" else "xla",
        )
    if impl != "xla":
        raise ValueError(
            f"unknown attention impl {impl!r}; "
            "use xla | flash | ring | ulysses | ulysses_flash"
        )
    return xla_attention(query, key, value, causal=causal,
                         key_padding_mask=key_padding_mask,
                         softmax_scale=softmax_scale)
