"""Persistent compiled decode engine: cached jit + on-device EOS loop.

`models.generate.generate` paid three per-call taxes: a *fresh* jitted
step closure per call (its compile cache died with the call), one host
round-trip per generated token (`bool(finished.all())`), and a compiled
shape per (batch, prompt-len) a caller happened to send. `DecodeEngine`
removes all three:

* **Cached AOT compiles.** Prefill is lowered+compiled once per
  (batch-bucket, prompt-bucket) and the decode loop once per
  batch-bucket; executables live on the engine and are reused across
  batches. Every compile is logged with its key so recompile storms are
  visible, and `stats` counts compiles vs cache hits.

* **On-device decode loop.** The whole token loop is ONE
  `jax.lax.while_loop` inside ONE compiled program: sampling, KV-cache
  append, EOS-finished masking, and the all-finished early-exit
  condition are all traced. Zero device→host transfers per token — the
  only sync is the caller reading the finished sequences. The KV cache
  and the output token buffer are donated (`donate_argnums`), so each
  step updates HBM in place instead of double-buffering the cache.

* **Shape bucketing.** Batch is padded UP to the next configured bucket
  (pad rows are sliced back out). Prompt length is floor-bucketed:
  prefill runs at the largest bucket <= P and the remaining P-F prompt
  tokens are teacher-forced through the device loop (their K/V appended,
  their sampled tokens discarded). Unlike right-padding the prompt, the
  replay is *exact* — cache contents, RoPE positions, and the RNG stream
  match the unbucketed path, so outputs are identical to
  `generate_legacy` — while recompiles stay bounded by the bucket grid.

The loop-trip-count inputs (actual replay length, max_new_tokens, the
eos id, the PRNG seed) are traced scalars, so they never force a
recompile; only shapes and the sampling configuration (temperature /
top_k / top_p are baked into the traced program) key the cache.

* **Tensor-parallel decode.** Constructed with a ``mesh``
  (docs/Serving.md "Tensor-parallel decode"), the engine serves a model
  bigger than one chip's HBM: params place by the transformer's
  logical-axis rules (attention heads / MLP hidden / vocab over the
  ``tp`` mesh axis), every slot KV cache and the paged block pool shard
  their kv-heads axis over ``tp`` (`kv_partition_spec` /
  `pool_partition_spec` — each device holds 1/tp of every slot and
  every block), and all the compiled programs lower with explicit
  in/out shardings so the XLA partitioner inserts the attention-output
  and MLP down-projection all-reduces from the placements alone. No
  scheduler logic changes: still ONE program and one host sync per
  tick, tables/lengths/tokens still traced, and emitted token streams
  identical to the single-device path (float logits agree to roundoff —
  the partitioned matmuls reduce in a different grouping; the emitted
  ints are the tested contract, as with speculative decoding below).

* **Paged KV slots.** The serving grid's dense per-slot caches (each a
  full `max_seq_len` allocation, mostly padding for short requests) have
  a paged alternative: ONE global pool of fixed-size KV blocks
  (`make_paged_pool`) plus a per-slot block table. The compiled
  `paged_step` gathers each slot's dense cache view from the pool by its
  block table, runs the exact same per-slot model step, and
  scatter-appends the new K/V row into the slot's current block — all
  inside one program, zero host syncs per tick. Because the gathered
  view holds the identical values the dense slot cache would (positions
  beyond a slot's length are masked to exactly-zero weight by the
  attention mask), the fp paged path is BIT-IDENTICAL to the dense path
  and to `generate_legacy`. Free/allocate is host-side free-list
  bookkeeping (`serving/paging.py`); there is no per-eviction device
  program at all. `pack_prefill` splices a bucketed-prefill result into
  a slot's blocks; int8 KV composes transparently (the pool stores
  whatever leaves the model's cache has — int8 values + scales
  included).
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from tf_yarn_tpu import telemetry
from tf_yarn_tpu.models.generate import _sample
from tf_yarn_tpu.models.spec import verify_window

_logger = logging.getLogger(__name__)

# Bucket grids: batch is ceil-padded, prompt is floor-bucketed (see
# module docstring). Sizes outside the grid fall back to exact-shape
# compiles, logged as unbucketed.
DEFAULT_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64)
DEFAULT_PROMPT_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)
# The output token buffer is sized in multiples of this, so max_new_tokens
# only recompiles when it crosses a multiple, not on every value.
DEFAULT_TOKEN_BUCKET = 64


def build_prefill_fn(model):
    """(params, prompt [B, F]) -> (cache, last-position logits [B, V])."""

    def prefill(params, prompt):
        logits, state = model.apply(
            params, prompt, decode=True, mutable=["cache"]
        )
        return state["cache"], logits[:, -1]

    return prefill


def build_decode_fn(model, temperature: float, top_k: Optional[int],
                    top_p: Optional[float], has_eos: bool, has_rest: bool):
    """The single-program decode loop, shared by the engine and the
    analysis jaxpr entry points.

    has_rest=True signature:
        fn(params, cache, rest, rest_len, num_new, rng, eos_id, out)
    has_rest=False signature (prompt hit a bucket exactly — the first
    token is sampled from the prefill logits, outside the loop):
        fn(params, cache, last_logits, num_new, rng, eos_id, out)

    `rest_len`, `num_new`, `eos_id` are traced scalars; `out` is the
    preallocated token buffer [B, T] (pre-filled with eos when has_eos,
    so the early-exit tail is already correct). Returns (filled buffer,
    final cache): the caller donates `cache` and `out`, and returning
    the cache gives XLA the output to alias the donated input against —
    the loop carry then updates the prefill cache's HBM in place instead
    of copying it into the program.

    Loop-step semantics mirror generate_legacy exactly, including the
    RNG split chain: replay steps (t < rest_len-1) consume no RNG; the
    step at t == rest_len-1 samples the first generated token with the
    first split (generate_legacy's prefill sample); each later step
    advances the chain once.
    """

    def step_apply(params, cache, token):
        logits, state = model.apply(
            {**params, "cache": cache}, token[:, None], decode=True,
            mutable=["cache"],
        )
        return state["cache"], logits[:, -1]

    def make_loop(params, cache, rest, r, rng, eos_id, out,
                  first_emitted, total):
        w = rest.shape[1] if has_rest else 1
        t_max = out.shape[1]

        def cond(carry):
            _cache, cur, _rng, finished, t, _out = carry
            alive = t < total
            if has_eos:
                # cur is only an emitted token once generation started;
                # during replay the exit check must stay off.
                done = jnp.all(finished | (cur == eos_id))
                alive = alive & ((t < r) | ~done)
            return alive

        def body(carry):
            cache, cur, rng, finished, t, out = carry
            if has_rest:
                col = jax.lax.dynamic_slice_in_dim(
                    rest, jnp.clip(t, 0, w - 1), 1, axis=1
                )[:, 0]
                token_in = jnp.where(t < r, col, cur)
            else:
                token_in = cur
            cache, logits = step_apply(params, cache, token_in)
            # Replay steps before the last consume no RNG and emit
            # nothing — the split chain stays aligned with the
            # unbucketed path's one-split-per-sample.
            do_sample = t >= r - 1
            next_rng, sample_key = jax.random.split(rng)
            rng = jnp.where(do_sample, next_rng, rng)
            sampled = _sample(logits, sample_key, temperature, top_k, top_p)
            if has_eos:
                # Generation steps after the first: a row that already
                # emitted eos keeps emitting eos.
                finished = jnp.where(
                    t >= r, finished | (cur == eos_id), finished
                )
                emit = jnp.where(finished, eos_id, sampled)
            else:
                emit = sampled
            cur = jnp.where(do_sample, emit, cur)
            k = jnp.clip(t - r + 1, 0, t_max - 1)
            written = jax.lax.dynamic_update_slice(
                out, emit[:, None].astype(out.dtype), (0, k)
            )
            out = jnp.where(do_sample, written, out)
            return cache, cur, rng, finished, t + 1, out

        b = out.shape[0]
        finished0 = jnp.zeros((b,), bool)
        carry = (cache, first_emitted, rng, finished0,
                 jnp.asarray(0, jnp.int32), out)
        cache, _cur, _rng, _fin, _t, out = jax.lax.while_loop(
            cond, body, carry
        )
        return out, cache

    if has_rest:
        def decode(params, cache, rest, rest_len, num_new, rng, eos_id, out):
            b = out.shape[0]
            cur0 = jnp.zeros((b,), jnp.int32)
            total = rest_len + num_new - 1
            return make_loop(params, cache, rest, rest_len, rng,
                             eos_id, out, cur0, total)
    else:
        def decode(params, cache, last_logits, num_new, rng, eos_id, out):
            rng, first_key = jax.random.split(rng)
            first = _sample(last_logits, first_key, temperature, top_k, top_p)
            out = jax.lax.dynamic_update_slice(
                out, first[:, None].astype(out.dtype), (0, 0)
            )
            zero = jnp.asarray(0, jnp.int32)
            return make_loop(params, cache, None, zero, rng,
                             eos_id, out, first, num_new - 1)

    return decode


def build_step_fn(model, temperature: float, top_k: Optional[int],
                  top_p: Optional[float]):
    """The continuous-batching slot step, shared by the engine and the
    analysis jaxpr entry point (`models.decode_engine.step`).

        fn(params, slot_cache, tokens, rngs, sample_mask)
            -> (slot_cache, emitted [S], rngs)

    ONE compiled program advances EVERY slot of a serving grid by one
    token. `slot_cache` is the per-slot KV grid (leading slot axis; each
    element a batch-1 decode cache with its own `cache_index`, so slots
    sit at independent positions — the per-slot offsets the shared batch
    cache of `decode_loop` cannot express). `tokens` [S] are this tick's
    inputs: a forced prompt token while a slot replays its prompt
    remainder, else the slot's last emitted token. `sample_mask` [S] is
    the traced active mask: masked-off slots (free, or mid-replay) run
    the same device program — the KV append is the point for replay
    slots, garbage for free ones — but consume no RNG and pass their
    input token through, so each slot's split chain stays bit-aligned
    with generate_legacy's one-split-per-sample. The step that consumes
    a request's LAST prompt token has sample_mask on: its output is the
    first generated token, sampled with the first split — exactly
    generate_legacy's prefill sample.
    """

    def step(params, slot_cache, tokens, rngs, sample_mask):
        def one_slot(cache, token, rng, do_sample):
            logits, state = model.apply(
                {**params, "cache": cache}, token[None, None], decode=True,
                mutable=["cache"],
            )
            next_rng, sample_key = jax.random.split(rng)
            sampled = _sample(
                logits[:, -1], sample_key, temperature, top_k, top_p
            )[0]
            emitted = jnp.where(do_sample, sampled, token)
            rng = jnp.where(do_sample, next_rng, rng)
            return state["cache"], emitted, rng

        return jax.vmap(one_slot)(slot_cache, tokens, rngs, sample_mask)

    return step


# --------------------------------------------------------------------------
# Speculative decoding: the windowed verify steps
# --------------------------------------------------------------------------
#
# One spec tick advances a slot by a VARIABLE number of tokens: the
# target model scores all `width` window positions (replay prefix +
# last token + drafts) in one batched forward, `verify_window`
# (models/spec.py) keeps exactly the prefix the sequential path would
# have emitted, and only the accepted positions become valid KV. The
# forward writes all `width` K/V rows — rejected-draft rows land beyond
# the slot's valid length, where every decode-attention path masks them
# to zero weight and the next tick's window overwrites them — so
# acceptance never needs a device-side KV rollback. Emitted token
# streams are identical to generate_legacy (token-matching acceptance);
# note the windowed forward compiles to a different fusion than the
# one-token step, so float *logits* agree to roundoff, not bitwise —
# the emitted ints are the contract, and the tests pin them.


def _index_leaf_value(cache, max_seq_len: int):
    """The slot's pre-apply position, read from any index leaf (a cache
    leaf with no seq axis; all index leaves carry the same scalar)."""
    for leaf in jax.tree_util.tree_leaves(cache):
        if _seq_axis(leaf.shape, max_seq_len) is None:
            return leaf.reshape(-1)[0].astype(jnp.int32)
    raise ValueError("cache has no index leaf — unknown cache layout")


def _with_index(cache, new_index, max_seq_len: int):
    """Rewrite every index leaf to `new_index` (the accepted length),
    leaving KV leaves untouched."""

    def leaf(value):
        if _seq_axis(value.shape, max_seq_len) is None:
            return jnp.full(value.shape, new_index, value.dtype)
        return value

    return jax.tree_util.tree_map(leaf, cache)


def build_spec_step_fn(model, width: int, temperature: float,
                       top_k: Optional[int], top_p: Optional[float]):
    """The dense speculative slot step, shared by the engine and the
    analysis jaxpr entry point (`models.decode_engine.spec_step`).

        fn(params, slot_cache, tokens [S, W], n_known [S], eos_ids [S],
           rngs [S, 2], active [S])
            -> (slot_cache, emitted [S, W], counts [S], rngs)

    ONE compiled program advances every slot up to W tokens: per slot,
    the target model scores the whole window in one forward (K/V for
    all W positions appended at the slot's cache_index), verify_window
    computes the emitted prefix, and the slot's cache_index is rewritten
    to `old_index + n_known + n_emitted` — the accepted length — so
    rejected rows are dead weight the next window overwrites. Inactive
    slots (active=False) emit nothing, consume no RNG, and keep their
    cache_index; their garbage window rows land in their own (free)
    cache and are overwritten at the next admission. tokens / n_known /
    eos_ids are traced, so tick-to-tick changes never recompile.

    This program is ALSO the chunk-apply for chunked prefill
    (docs/Serving.md "Chunked prefill"): a window whose tokens are all
    pending prompt tokens (n_known == W) is a teacher-forced chunk —
    the forward appends W prompt positions of KV and emits nothing.
    The scheduler widens W to max(spec_k + 1, prefill_chunk); it is a
    compile-key dimension, fixed per grid, so chunking adds zero
    recompiles.
    """
    max_seq_len = model.config.max_seq_len

    def spec_step(params, slot_cache, tokens, n_known, eos_ids, rngs,
                  active):
        def one_slot(cache, toks, known, eos_id, rng, act):
            idx = _index_leaf_value(cache, max_seq_len)
            logits, state = model.apply(
                {**params, "cache": cache}, toks[None, :], decode=True,
                mutable=["cache"],
            )
            emitted, count, rng = verify_window(
                logits[0], toks, known, eos_id, rng, act,
                temperature, top_k, top_p,
            )
            n_valid = jnp.where(act, known + count, 0)
            cache = _with_index(state["cache"], idx + n_valid, max_seq_len)
            return cache, emitted, count, rng

        return jax.vmap(one_slot)(
            slot_cache, tokens, n_known, eos_ids, rngs, active
        )

    return spec_step


# --------------------------------------------------------------------------
# Paged KV layout: pool avals + the compiled gather/scatter programs
# --------------------------------------------------------------------------

def _seq_axis(shape: Tuple[int, ...], max_seq_len: int) -> Optional[int]:
    """Index of the cache leaf's sequence axis (the one sized
    max_seq_len), or None for non-KV leaves (cache_index). Raises on an
    ambiguous layout — a config where some other cache dimension equals
    max_seq_len needs a different block_size/max_seq_len split, not a
    silent guess."""
    matches = [i for i, dim in enumerate(shape) if dim == max_seq_len]
    if len(matches) > 1:
        raise ValueError(
            f"ambiguous KV cache leaf {shape}: {len(matches)} axes equal "
            f"max_seq_len={max_seq_len}; the paged layout needs exactly one"
        )
    return matches[0] if matches else None


def _decode_cache_aval(model, params):
    """Abstract batch-1 decode cache (the slot row shape). Works with
    traced or concrete params — eval_shape never touches the device."""
    return jax.eval_shape(
        build_prefill_fn(model), params,
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
    )[0]


def paged_pool_avals(row_aval, num_blocks: int, block_size: int,
                     max_seq_len: int):
    """The pool pytree's avals: every KV leaf's seq axis becomes
    (num_blocks, block_size); index leaves (no seq axis) become None —
    per-slot positions travel as the step's `lengths` argument instead
    of living in the cache."""
    if max_seq_len % block_size:
        raise ValueError(
            f"block_size={block_size} must divide max_seq_len={max_seq_len}"
        )

    def leaf(aval):
        ax = _seq_axis(aval.shape, max_seq_len)
        if ax is None:
            if not jnp.issubdtype(aval.dtype, jnp.integer):
                raise ValueError(
                    f"cache leaf {aval.shape}/{aval.dtype} has no "
                    f"max_seq_len={max_seq_len} axis and is not an index "
                    "leaf — unknown cache layout for paging"
                )
            return None
        shape = aval.shape[:ax] + (num_blocks, block_size) + aval.shape[ax + 1:]
        return jax.ShapeDtypeStruct(shape, aval.dtype)

    return jax.tree_util.tree_map(leaf, row_aval)


def _is_none(x) -> bool:
    return x is None


def _is_named_sharding(sharding) -> bool:
    from jax.sharding import NamedSharding

    return isinstance(sharding, NamedSharding)


def _gather_slot_cache(pool, row_aval, table, length, max_seq_len):
    """One slot's dense cache view: KV leaves gathered from the pool by
    the block table (and reshaped back to the dense seq axis), index
    leaves filled with the slot's length. Values beyond `length` are
    stale pool garbage — every decode-attention path masks positions >=
    cache_index to exactly-zero weight, so the view is value-identical
    to a dense slot cache where it matters (bit-identity relies on
    this)."""

    def leaf(pool_leaf, aval):
        if pool_leaf is None:
            return jnp.full(aval.shape, length, aval.dtype)
        ax = _seq_axis(aval.shape, max_seq_len)
        return jnp.take(pool_leaf, table, axis=ax).reshape(aval.shape)

    with jax.named_scope("attention/kv_gather"):
        return jax.tree_util.tree_map(
            leaf, pool, row_aval, is_leaf=_is_none
        )


def build_paged_step_fn(model, block_size: int, temperature: float,
                        top_k: Optional[int], top_p: Optional[float]):
    """The paged continuous-batching step, shared by the engine and the
    analysis jaxpr entry point (`models.decode_engine.paged_step`).

        fn(params, pool, tables, lengths, tokens, rngs, sample_mask)
            -> (pool, emitted [S], rngs)

    ONE compiled program advances every slot one token against the
    global block pool: per slot, gather its dense cache view through its
    block-table row, run the identical per-slot model step
    `build_step_fn` runs (same sampling, same RNG discipline — masked
    slots consume no RNG and pass their token through), then
    scatter-append the freshly written K/V row into block
    `table[length // block_size]` at offset `length % block_size`.
    `tables`/`lengths` are traced values — tick-to-tick table changes
    never recompile. Inactive slots carry an all-zero table row and
    length 0, so their (meaningless) write lands in the reserved trash
    block 0 and can never corrupt a live slot.
    """
    max_seq_len = model.config.max_seq_len

    def step(params, pool, tables, lengths, tokens, rngs, sample_mask):
        row_aval = _decode_cache_aval(model, params)

        def one_slot(table, length, token, rng, do_sample):
            cache = _gather_slot_cache(
                pool, row_aval, table, length, max_seq_len
            )
            logits, state = model.apply(
                {**params, "cache": cache}, token[None, None], decode=True,
                mutable=["cache"],
            )
            next_rng, sample_key = jax.random.split(rng)
            sampled = _sample(
                logits[:, -1], sample_key, temperature, top_k, top_p
            )[0]
            emitted = jnp.where(do_sample, sampled, token)
            rng = jnp.where(do_sample, next_rng, rng)

            def new_row(leaf, aval):
                ax = _seq_axis(aval.shape, max_seq_len)
                if ax is None:
                    return None
                return jax.lax.dynamic_slice_in_dim(leaf, length, 1, axis=ax)

            with jax.named_scope("attention/kv_write"):
                rows = jax.tree_util.tree_map(
                    new_row, state["cache"], row_aval
                )
            return emitted, rng, rows

        emitted, rngs, rows = jax.vmap(
            one_slot, in_axes=(0, 0, 0, 0, 0)
        )(tables, lengths, tokens, rngs, sample_mask)

        slots = tables.shape[0]

        def write(pool_leaf, slot_rows, aval):
            if pool_leaf is None:
                return None
            ax = _seq_axis(aval.shape, max_seq_len)
            for s in range(slots):
                block = tables[s, lengths[s] // block_size]
                offset = lengths[s] % block_size
                update = jnp.expand_dims(slot_rows[s], ax)
                starts = [jnp.asarray(0, jnp.int32)] * pool_leaf.ndim
                starts[ax] = block
                starts[ax + 1] = offset
                pool_leaf = jax.lax.dynamic_update_slice(
                    pool_leaf, update.astype(pool_leaf.dtype), tuple(starts)
                )
            return pool_leaf

        with jax.named_scope("attention/kv_write"):
            pool_out = jax.tree_util.tree_map(
                write, pool, rows, row_aval, is_leaf=_is_none
            )
        return pool_out, emitted, rngs

    return step


DECODE_ATTENTION_MODES = ("gather", "fused")


def _prune_none_tree(tree):
    """The pool tree minus its None (elided index) entries — the shape
    flax accepts as the `kv_pool` variable collection (its nested dict
    structure mirrors the cache collection by construction)."""
    if isinstance(tree, dict):
        out = {}
        for key, value in tree.items():
            pruned = _prune_none_tree(value)
            if pruned is None or (isinstance(pruned, dict) and not pruned):
                continue
            out[key] = pruned
        return out
    return tree


def _merge_pool_tree(pool, updated):
    """Fold the model's updated `kv_pool` collection back into the
    engine's pool structure (None index leaves restored in place)."""
    if pool is None:
        return None
    if isinstance(pool, dict):
        return {
            key: _merge_pool_tree(
                value, updated[key] if key in updated else None
            )
            for key, value in pool.items()
        }
    return pool if updated is None else updated


def build_paged_spec_step_fn(model, block_size: int, width: int,
                             temperature: float, top_k: Optional[int],
                             top_p: Optional[float],
                             decode_attention: str = "gather"):
    """The paged speculative slot step, shared by the engine and the
    analysis jaxpr entry point (`models.decode_engine.paged_spec_step`).

        fn(params, pool, tables, lengths, tokens [S, W], n_known [S],
           eos_ids [S], rngs [S, 2], active [S])
            -> (pool, emitted [S, W], counts [S], rngs)

    Same verify semantics as `build_spec_step_fn` over the block pool;
    the slot's valid length is the HOST's `lengths` bookkeeping (it
    advances by n_known + n_emitted after the tick), so the program
    itself needs no index fixup. All `width` freshly written K/V rows
    scatter back at logical positions length..length+W-1 — rows beyond
    a slot's reserved blocks hit table entries 0 and land in the trash
    block, so rejected drafts can never touch another slot's KV. Like
    the dense twin, this doubles as the chunk-apply for chunked prefill:
    an all-known window (n_known == W) writes W prompt rows through the
    block table and emits nothing.

    `decode_attention` picks the attention implementation inside the
    verify forward:

    * ``"gather"`` — materialize each slot's dense cache view from the
      pool (exactly `paged_step`'s path) and run the model's standard
      decode attention over it. Reference semantics.
    * ``"fused"`` — int8 pools only: the model's decode attention reads
      the block pool DIRECTLY through `paged_int8_window_attention`
      (ops/decode_attention.py — block tables ride in SMEM via scalar
      prefetch), the window's K/V rows quantize and scatter into the
      pool before the kernel runs, and no dense per-slot view is ever
      materialized. Numerics differ from the gather path only by
      reduction order (tolerance-tested).
    """
    if decode_attention not in DECODE_ATTENTION_MODES:
        raise ValueError(
            f"decode_attention must be one of {DECODE_ATTENTION_MODES}, "
            f"got {decode_attention!r}"
        )
    max_seq_len = model.config.max_seq_len

    if decode_attention == "fused":
        if getattr(model.config, "kv_cache_dtype", None) != "int8":
            raise ValueError(
                "decode_attention='fused' reads the int8 block pool "
                "directly (paged_int8_window_attention); it requires "
                "kv_cache_dtype='int8'"
            )

        def spec_step_fused(params, pool, tables, lengths, tokens,
                            n_known, eos_ids, rngs, active):
            logits, state = model.apply(
                {**params, "kv_pool": _prune_none_tree(pool)},
                tokens, decode=True, paged_ctx=(tables, lengths),
                mutable=["kv_pool"],
            )
            pool_out = _merge_pool_tree(pool, dict(state["kv_pool"]))

            def vw(row_logits, toks, known, eos_id, rng, act):
                return verify_window(
                    row_logits, toks, known, eos_id, rng, act,
                    temperature, top_k, top_p,
                )

            emitted, counts, rngs = jax.vmap(vw)(
                logits, tokens, n_known, eos_ids, rngs, active
            )
            return pool_out, emitted, counts, rngs

        return spec_step_fused

    def spec_step(params, pool, tables, lengths, tokens, n_known,
                  eos_ids, rngs, active):
        row_aval = _decode_cache_aval(model, params)
        blocks_per_slot = tables.shape[1]

        def one_slot(table, length, toks, known, eos_id, rng, act):
            cache = _gather_slot_cache(
                pool, row_aval, table, length, max_seq_len
            )
            logits, state = model.apply(
                {**params, "cache": cache}, toks[None, :], decode=True,
                mutable=["cache"],
            )
            emitted, count, rng = verify_window(
                logits[0], toks, known, eos_id, rng, act,
                temperature, top_k, top_p,
            )

            def new_rows(leaf, aval):
                ax = _seq_axis(aval.shape, max_seq_len)
                if ax is None:
                    return None
                return jax.lax.dynamic_slice_in_dim(
                    leaf, length, width, axis=ax
                )

            with jax.named_scope("attention/kv_write"):
                rows = jax.tree_util.tree_map(
                    new_rows, state["cache"], row_aval
                )
            return emitted, count, rng, rows

        emitted, counts, rngs, rows = jax.vmap(one_slot)(
            tables, lengths, tokens, n_known, eos_ids, rngs, active
        )

        slots = tables.shape[0]

        def write(pool_leaf, slot_rows, aval):
            if pool_leaf is None:
                return None
            ax = _seq_axis(aval.shape, max_seq_len)
            for s in range(slots):
                for w in range(width):
                    pos = lengths[s] + w
                    logical = pos // block_size
                    # Beyond the table (a rejected row past the slot's
                    # reservation): route to the trash block.
                    block = jnp.where(
                        logical < blocks_per_slot,
                        tables[s, jnp.clip(logical, 0, blocks_per_slot - 1)],
                        0,
                    )
                    offset = pos % block_size
                    update = jnp.expand_dims(
                        jax.lax.slice_in_dim(
                            slot_rows[s], w, w + 1, axis=ax
                        ),
                        ax,
                    )
                    starts = [jnp.asarray(0, jnp.int32)] * pool_leaf.ndim
                    starts[ax] = block
                    starts[ax + 1] = offset
                    pool_leaf = jax.lax.dynamic_update_slice(
                        pool_leaf, update.astype(pool_leaf.dtype),
                        tuple(starts),
                    )
            return pool_leaf

        with jax.named_scope("attention/kv_write"):
            pool_out = jax.tree_util.tree_map(
                write, pool, rows, row_aval, is_leaf=_is_none
            )
        return pool_out, emitted, counts, rngs

    return spec_step


def build_pack_prefill_fn(model, block_size: int, prefill_len: int):
    """The prefill->pool splice program: write positions [0, prefill_len)
    of a freshly prefilled batch-1 cache into the slot's first
    ceil(prefill_len / block_size) blocks.

        fn(pool, block_ids, row_cache) -> pool

    `block_ids` values are traced (different slots reuse one compiled
    program); `prefill_len` is static (one program per prefill bucket).
    """
    max_seq_len = model.config.max_seq_len
    n_pack = -(-prefill_len // block_size)

    def pack(pool, block_ids, row_cache):
        def leaf(pool_leaf, row_leaf):
            if pool_leaf is None:
                return None
            ax = _seq_axis(row_leaf.shape, max_seq_len)
            if ax is None:
                return pool_leaf
            for j in range(n_pack):
                width = min(block_size, prefill_len - j * block_size)
                chunk = jax.lax.slice_in_dim(
                    row_leaf, j * block_size, j * block_size + width, axis=ax
                )
                if width < block_size:
                    pad = [(0, 0)] * chunk.ndim
                    pad[ax] = (0, block_size - width)
                    chunk = jnp.pad(chunk, pad)
                chunk = jnp.expand_dims(chunk, ax)
                starts = [jnp.asarray(0, jnp.int32)] * pool_leaf.ndim
                starts[ax] = block_ids[j]
                pool_leaf = jax.lax.dynamic_update_slice(
                    pool_leaf, chunk.astype(pool_leaf.dtype), tuple(starts)
                )
            return pool_leaf

        return jax.tree_util.tree_map(
            leaf, pool, row_cache, is_leaf=_is_none
        )

    return pack


def build_extract_blocks_fn(model, row_aval):
    """The swap-out gather program: read W pool blocks in one bulk op.

        fn(pool, block_ids) -> payload

    `block_ids` is a traced (W,) int32 vector (W static from its
    shape), so ONE compiled program serves every suspend regardless of
    which physical blocks a slot holds — the scheduler pads short id
    vectors with the trash block and discards those rows host-side.
    The payload pytree mirrors the pool (index leaves stay None) with
    the block axis narrowed to W, in the pool's own dtype — an int8
    pool swaps as quantized bytes. Pure gather: no host callbacks
    (TYA103), so the only host hop is the caller's `device_get`.
    """
    max_seq_len = model.config.max_seq_len

    def extract(pool, block_ids):
        def leaf(pool_leaf, aval):
            if pool_leaf is None:
                return None
            ax = _seq_axis(aval.shape, max_seq_len)
            return jnp.take(pool_leaf, block_ids, axis=ax)

        return jax.tree_util.tree_map(leaf, pool, row_aval,
                                      is_leaf=_is_none)

    return extract


def build_inject_blocks_fn(model, row_aval):
    """The swap-in scatter program, inverse of `build_extract_blocks_fn`:

        fn(pool, block_ids, payload) -> pool

    Writes payload row j into physical block `block_ids[j]` (traced
    values, static width) via the same dynamic_update_slice splice as
    `build_pack_prefill_fn`. The pool is donated by the engine wrapper
    so resume updates HBM in place. Rows the scheduler does not want
    re-injected (prefix-cache hits re-attached by lookup, padding) are
    aimed at the trash block, whose content is garbage by contract.
    """
    max_seq_len = model.config.max_seq_len

    def inject(pool, block_ids, payload):
        def leaf(pool_leaf, aval, pay_leaf):
            if pool_leaf is None:
                return None
            ax = _seq_axis(aval.shape, max_seq_len)
            for j in range(block_ids.shape[0]):
                chunk = jax.lax.slice_in_dim(pay_leaf, j, j + 1, axis=ax)
                starts = [jnp.asarray(0, jnp.int32)] * pool_leaf.ndim
                starts[ax] = block_ids[j]
                pool_leaf = jax.lax.dynamic_update_slice(
                    pool_leaf, chunk.astype(pool_leaf.dtype), tuple(starts)
                )
            return pool_leaf

        return jax.tree_util.tree_map(leaf, pool, row_aval, payload,
                                      is_leaf=_is_none)

    return inject


def cache_nbytes(tree) -> int:
    """Resident bytes of a cache pytree (dense slot grid or paged pool;
    None leaves — elided index leaves — count zero). GLOBAL bytes: a
    tp-sharded tree's per-device share is `tree_nbytes_per_device`."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = 1
        for dim in leaf.shape:
            size *= dim
        total += size * jnp.dtype(leaf.dtype).itemsize
    return total


def tree_nbytes_per_device(tree) -> int:
    """Resident bytes of a pytree on EACH device: sharded leaves count
    one shard (`Sharding.shard_shape`), replicated/host leaves count
    whole. With no mesh this equals `cache_nbytes` — the number the
    `serving/kv_cache_hbm_bytes_per_device` gauge and the tp HBM
    accounting tests read."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = tuple(leaf.shape)
        sharding = getattr(leaf, "sharding", None)
        if sharding is not None and hasattr(sharding, "shard_shape"):
            shape = sharding.shard_shape(shape)
        size = 1
        for dim in shape:
            size *= dim
        total += size * jnp.dtype(leaf.dtype).itemsize
    return total


# --------------------------------------------------------------------------
# Tensor-parallel decode: the KV placement rule
# --------------------------------------------------------------------------
#
# Under a tp mesh (docs/Serving.md "Tensor-parallel decode") the slot
# KV lives sharded: every cache leaf's kv-heads axis — the axis right
# after the sequence axis in the model's [*, seq, kv_heads, head_dim]
# cache layout (scales ride as [*, seq, kv_heads, 1]) — splits over the
# `tp` mesh axis, so each device holds 1/tp of every slot's cache (and
# of every paged block). Index leaves and layouts whose heads dim does
# not divide stay replicated. Weights place through the transformer's
# EXISTING logical-axis rules (parallel/sharding.py LOGICAL_RULES):
# attention heads + MLP hidden + vocab over tp, the rest replicated on
# a serving mesh — XLA then inserts the attention-output and MLP
# down-projection all-reduces from the shardings alone; no step-program
# logic changes.


def kv_partition_spec(shape: Tuple[int, ...], max_seq_len: int, tp: int):
    """PartitionSpec for a DENSE cache leaf (prefill row, slot row, or
    slot grid — the rule anchors on the seq axis, so the extra leading
    slot/layer axes need no special casing)."""
    from jax.sharding import PartitionSpec

    from tf_yarn_tpu.parallel.mesh import AXIS_TP

    if tp <= 1:
        return PartitionSpec()
    ax = _seq_axis(shape, max_seq_len)
    if ax is None:
        return PartitionSpec()
    heads = ax + 1
    if heads >= len(shape) or shape[heads] % tp:
        return PartitionSpec()
    spec = [None] * len(shape)
    spec[heads] = AXIS_TP
    return PartitionSpec(*spec)


def pool_partition_spec(row_shape: Tuple[int, ...], max_seq_len: int,
                        tp: int):
    """The same heads-axis rule for a PAGED pool leaf, whose seq axis
    was split into (num_blocks, block_size) — computed from the dense
    ROW leaf's shape (the pool shape cannot anchor on max_seq_len), with
    every axis after the split shifted one right."""
    from jax.sharding import PartitionSpec

    from tf_yarn_tpu.parallel.mesh import AXIS_TP

    if tp <= 1:
        return PartitionSpec()
    ax = _seq_axis(row_shape, max_seq_len)
    if ax is None:
        return PartitionSpec()
    heads = ax + 1
    if heads >= len(row_shape) or row_shape[heads] % tp:
        return PartitionSpec()
    spec = [None] * (len(row_shape) + 1)
    spec[heads + 1] = AXIS_TP
    return PartitionSpec(*spec)


def _ceil_bucket(value: int, buckets: Tuple[int, ...]) -> Optional[int]:
    for b in sorted(buckets):
        if b >= value:
            return b
    return None


def _floor_bucket(value: int, buckets: Tuple[int, ...]) -> Optional[int]:
    best = None
    for b in sorted(buckets):
        if b <= value:
            best = b
    return best


class DecodeEngine:
    """Persistent compiled generation for one model (see module docstring).

    Thread-safe for the compile cache; concurrent `generate` calls are
    serialized only while looking up / inserting executables.
    """

    def __init__(
        self,
        model,
        batch_buckets: Tuple[int, ...] = DEFAULT_BATCH_BUCKETS,
        prompt_buckets: Tuple[int, ...] = DEFAULT_PROMPT_BUCKETS,
        token_bucket: int = DEFAULT_TOKEN_BUCKET,
        mesh=None,
    ):
        if token_bucket < 1:
            raise ValueError(f"token_bucket must be >= 1, got {token_bucket}")
        self.model = model
        # Tensor-parallel decode (docs/Serving.md): with a mesh, params
        # place by the model's logical-axis annotations, slot KV shards
        # its kv-heads axis over tp, and every compiled program lowers
        # with explicit in/out shardings so XLA inserts the TP
        # collectives — validated HERE, before any trace, so a bad tp
        # config fails with a config error instead of a partitioner one.
        self.mesh = mesh
        self.tp_degree = 1
        self._rep_sharding = None
        self._param_shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from tf_yarn_tpu.parallel import sharding as sharding_lib
            from tf_yarn_tpu.parallel.mesh import AXIS_TP, mesh_axis_size

            config = getattr(model, "config", None)
            if config is None or not hasattr(config, "max_seq_len"):
                raise ValueError(
                    "DecodeEngine(mesh=...) needs a model with "
                    "config.max_seq_len — the KV sharding rule anchors "
                    "on the cache's sequence axis"
                )
            self.tp_degree = int(mesh_axis_size(mesh, AXIS_TP))
            for name in ("n_heads", "n_kv_heads"):
                value = getattr(config, name, None)
                if value is not None and value % self.tp_degree:
                    raise ValueError(
                        f"model config {name}={value} does not divide "
                        f"over tp={self.tp_degree} — tensor-parallel "
                        "decode shards attention (and the KV cache) by "
                        "heads; pick a tp that divides both head counts"
                    )
            self._rep_sharding = NamedSharding(mesh, PartitionSpec())
            try:
                abstract = jax.eval_shape(
                    lambda r, t: model.init(r, t),
                    jax.ShapeDtypeStruct((2,), jnp.uint32),
                    jax.ShapeDtypeStruct((1, 8), jnp.int32),
                )
            except Exception as exc:
                raise ValueError(
                    "DecodeEngine(mesh=...) could not abstractly init "
                    f"{type(model).__name__} to read its logical-axis "
                    f"annotations: {type(exc).__name__}: {exc}"
                ) from exc
            self._param_shardings = sharding_lib.tree_shardings(
                mesh, abstract
            )
        self.batch_buckets = tuple(sorted(set(batch_buckets)))
        self.prompt_buckets = tuple(sorted(set(prompt_buckets)))
        self.token_bucket = int(token_bucket)
        # One rest-buffer width for every bucketed prompt interval keeps
        # the decode program shared across prompt buckets: the replay
        # remainder is at most the widest gap in the grid.
        gaps = [b2 - b1 for b1, b2 in zip(self.prompt_buckets,
                                          self.prompt_buckets[1:])]
        self._rest_width = max(gaps) if gaps else 1
        self._prefill: Dict[tuple, Any] = {}
        self._decode: Dict[tuple, Any] = {}
        self._step: Dict[tuple, Any] = {}
        self._lock = threading.Lock()
        self.stats = {
            "calls": 0,
            "prefill_compiles": 0,
            "decode_compiles": 0,
            "step_compiles": 0,
            "prefill_cache_hits": 0,
            "decode_cache_hits": 0,
            "step_cache_hits": 0,
            "paged_step_compiles": 0,
            "paged_step_cache_hits": 0,
            "pack_compiles": 0,
            "pack_cache_hits": 0,
            "spec_step_compiles": 0,
            "spec_step_cache_hits": 0,
            "paged_spec_step_compiles": 0,
            "paged_spec_step_cache_hits": 0,
            "extract_compiles": 0,
            "extract_cache_hits": 0,
            "inject_compiles": 0,
            "inject_cache_hits": 0,
            "unbucketed_shapes": 0,
            "oversize_batch_chunks": 0,
        }
        self._paged_step: Dict[tuple, Any] = {}
        self._pack: Dict[tuple, Any] = {}
        self._spec_step: Dict[tuple, Any] = {}
        self._paged_spec_step: Dict[tuple, Any] = {}
        self._extract: Dict[tuple, Any] = {}
        self._inject: Dict[tuple, Any] = {}

        # Slot-grid splice helpers (continuous batching): donated, so the
        # grid updates HBM in place instead of copying the whole KV store
        # per admission/retirement.
        def _insert(grid, row, slot):
            return jax.tree_util.tree_map(
                lambda buf, r: jax.lax.dynamic_update_index_in_dim(
                    buf, r.astype(buf.dtype), slot, 0
                ),
                grid, row,
            )

        def _evict(grid, slot):
            return jax.tree_util.tree_map(
                lambda buf: jax.lax.dynamic_update_index_in_dim(
                    buf, jnp.zeros(buf.shape[1:], buf.dtype), slot, 0
                ),
                grid,
            )

        self._insert_jit = jax.jit(_insert, donate_argnums=(0,))
        self._evict_jit = jax.jit(_evict, donate_argnums=(0,))

    # -- bucket selection --------------------------------------------------

    def select_buckets(self, batch: int, prompt_len: int) -> Tuple[int, int]:
        """(padded batch, prefill length) for an incoming [B, P] batch.

        Batch pads UP (extra rows are discarded); prompt floors DOWN
        (the remainder replays through the decode loop). Out-of-grid
        sizes return themselves — an exact-shape, logged compile.
        """
        b_bucket = _ceil_bucket(batch, self.batch_buckets) or batch
        p_bucket = _floor_bucket(prompt_len, self.prompt_buckets) or prompt_len
        # A remainder wider than the rest buffer (prompt beyond the
        # grid) cannot replay — prefill the exact length instead.
        if prompt_len - p_bucket > self._rest_width:
            p_bucket = prompt_len
        return b_bucket, p_bucket

    def _params_fingerprint(self, params) -> int:
        leaves, treedef = jax.tree_util.tree_flatten(params)
        return hash((treedef, tuple(
            (tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves
        )))

    # -- tensor-parallel placement -----------------------------------------

    def _place_params(self, params):
        """Every public entry's param normalization: host arrays become
        device arrays, and under a mesh every leaf lands on the
        placement the model's logical-axis annotations assign (a no-op
        transfer-wise once placed — sharded restores arrive here
        already placed by inference.shard_restored_params)."""
        params = jax.tree_util.tree_map(jnp.asarray, params)
        if self.mesh is None:
            return params

        def _place(leaf, sharding):
            if getattr(leaf, "sharding", None) == sharding:
                return leaf
            return jax.device_put(leaf, sharding)

        try:
            return jax.tree_util.tree_map(
                _place, params, self._param_shardings
            )
        except ValueError as exc:
            raise ValueError(
                "params do not match the model's init structure — "
                f"cannot place them on the tp mesh: {exc}"
            ) from exc

    def _shardings_of(self, tree):
        """The committed shardings of a concrete tree (the donated
        grid/pool argument): used as the program's matching OUT
        shardings so the donated buffer aliases instead of copying.
        Host/numpy leaves read as replicated."""
        return jax.tree_util.tree_map(
            lambda leaf: (
                leaf.sharding
                if _is_named_sharding(getattr(leaf, "sharding", None))
                else self._rep_sharding
            ),
            tree,
        )

    def _arg_shardings(self, args) -> tuple:
        """Per-argument in_shardings for a sharded program lowering:
        committed mesh placements pass through (params, the KV
        grid/pool), everything else — the scheduler's per-tick numpy
        tables/lengths/tokens/rngs/masks — is replicated."""
        return tuple(self._shardings_of(arg) for arg in args)

    def _jit(self, fn, args, donate=(), out_shardings=None):
        """jax.jit wired for this engine's mesh: explicit in/out
        shardings under tensor parallelism (XLA inserts the TP
        collectives from these alone), the plain single-device jit
        otherwise."""
        if self.mesh is None:
            return jax.jit(fn, donate_argnums=donate)
        kwargs: Dict[str, Any] = {
            "donate_argnums": donate,
            "in_shardings": self._arg_shardings(args),
        }
        if out_shardings is not None:
            kwargs["out_shardings"] = out_shardings
        return jax.jit(fn, **kwargs)

    def _kv_shardings(self, avals):
        """NamedSharding tree for a DENSE cache tree (row, grid, or
        prefill output) under this engine's mesh: kv-heads axis over
        tp (kv_partition_spec)."""
        from jax.sharding import NamedSharding

        max_seq_len = self.model.config.max_seq_len
        return jax.tree_util.tree_map(
            lambda aval: NamedSharding(
                self.mesh,
                kv_partition_spec(
                    tuple(aval.shape), max_seq_len, self.tp_degree
                ),
            ),
            avals,
        )

    # -- compile cache -----------------------------------------------------

    def _compiled(self, cache_dict, key, stat_prefix, build):
        registry = telemetry.get_registry()
        with self._lock:
            compiled = cache_dict.get(key)
            if compiled is not None:
                self.stats[f"{stat_prefix}_cache_hits"] += 1
                registry.counter(
                    "decode_engine/cache_hits", kind=stat_prefix
                ).inc()
                return compiled
        # Compile outside the lock (slow); a racing duplicate compile is
        # harmless — last writer wins, both executables are equivalent.
        with telemetry.span(
            "decode_engine/compile", kind=stat_prefix, key=str(key)
        ) as sp:
            compiled = build()
        registry.counter("decode_engine/compiles", kind=stat_prefix).inc()
        registry.histogram(
            "decode_engine/compile_seconds", kind=stat_prefix
        ).observe(sp.duration)
        with self._lock:
            cache_dict[key] = compiled
            self.stats[f"{stat_prefix}_compiles"] += 1
            _logger.info(
                "decode-engine compiled %s program for key=%s "
                "(%d %s compiles, %d cached)",
                stat_prefix, key, self.stats[f"{stat_prefix}_compiles"],
                stat_prefix, len(cache_dict),
            )
        return compiled

    def _compiled_prefill(self, params, prompt, fp):
        """(cache, last-position logits) through the compile cache; the
        exact [B, F] shape keys the cache — callers pick bucketed
        shapes."""
        b, f = prompt.shape
        prefill_key = (b, f, fp)
        prefill_fn = build_prefill_fn(self.model)
        prefill_args = (params, prompt)
        def build():
            out_shardings = None
            if self.mesh is not None:
                # Pin the fresh cache SHARDED at the source: everything
                # downstream (insert_slot, pack_prefill) then propagates
                # the placement instead of guessing it. The eval_shape
                # runs only on a compile miss — not per admission.
                cache_avals, _logits_aval = jax.eval_shape(
                    prefill_fn, *prefill_args
                )
                out_shardings = (
                    self._kv_shardings(cache_avals), self._rep_sharding,
                )
            return self._jit(
                prefill_fn, prefill_args, out_shardings=out_shardings
            ).lower(*prefill_args).compile()

        compiled = self._compiled(
            self._prefill, prefill_key, "prefill", build,
        )
        # Dispatch-side span: async device futures, so this times the
        # enqueue (host cost), not the device compute — the XLA profiler
        # owns the device side.
        with telemetry.span("decode_engine/prefill", batch=b, prompt=f):
            return compiled(*prefill_args)

    # -- continuous-batching slot API --------------------------------------
    #
    # The serving scheduler (tf_yarn_tpu/serving/scheduler.py) keeps a
    # fixed grid of `max_slots` decode slots, each backed by a persistent
    # batch-1 KV cache with its own cache_index. Admission prefills a
    # request's prompt through the SAME bucketed prefill programs
    # `generate` uses and splices the result into a free slot; every tick
    # then advances all slots one token in one compiled `step` program.

    def slot_prefill_len(self, prompt_len: int) -> int:
        """Prefill length for a slot admission: the largest prompt bucket
        that still leaves >= 1 prompt token to replay through `step` (the
        step consuming the LAST prompt token samples the first generated
        token — generate_legacy's prefill sample — so the final prompt
        position always goes through the step program). 0 = no prefill:
        the whole prompt replays token-by-token from an empty slot."""
        if prompt_len <= 1:
            return 0
        return _floor_bucket(prompt_len - 1, self.prompt_buckets) or 0

    def prefill(self, params, prompt):
        """Public compiled prefill: [B, F] prompt -> (cache, last
        logits). B/F key the compile cache directly."""
        params = self._place_params(params)
        prompt = jnp.asarray(prompt, jnp.int32)
        return self._compiled_prefill(
            params, prompt, self._params_fingerprint(params)
        )

    def make_slot_cache(self, params, max_slots: int):
        """Zeroed per-slot KV grid: every leaf of the model's decode
        cache stacked along a new leading slot axis (batch-1 per slot,
        per-slot cache_index). Shapes come from an abstract prefill —
        nothing runs on the device except the zeros allocation."""
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        params = self._place_params(params)
        cache_avals = jax.eval_shape(
            build_prefill_fn(self.model), params,
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        )[0]

        def build():
            return jax.tree_util.tree_map(
                lambda leaf: jnp.zeros(
                    (max_slots,) + leaf.shape, leaf.dtype
                ),
                cache_avals,
            )

        if self.mesh is None:
            return build()
        # Sharded zeros straight onto the mesh — each device allocates
        # only its 1/tp shard, no full-grid staging anywhere.
        grid_avals = jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(
                (max_slots,) + leaf.shape, leaf.dtype
            ),
            cache_avals,
        )
        return jax.jit(
            build, out_shardings=self._kv_shardings(grid_avals)
        )()

    def insert_slot(self, slot_cache, slot: int, row_cache):
        """Splice a freshly prefilled batch-1 cache (cache_index
        included) into slot `slot`. The grid is donated: HBM updates in
        place. The old grid reference is consumed — use the return."""
        return self._insert_jit(
            slot_cache, row_cache, jnp.asarray(slot, jnp.int32)
        )

    def evict_slot(self, slot_cache, slot: int):
        """Zero slot `slot` (KV content and cache_index), returning the
        donated grid. Freeing is host-side bookkeeping — this exists so
        a retired slot's stale cache can never leak into a later
        admission path that skips prefill (slot_prefill_len == 0)."""
        return self._evict_jit(slot_cache, jnp.asarray(slot, jnp.int32))

    def step(
        self,
        params,
        slot_cache,
        tokens,
        rngs,
        sample_mask,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
    ):
        """Advance every slot of the grid one token in ONE compiled
        program (build_step_fn). Compiled once per (grid size, sampling
        config, params fingerprint); the KV grid and the per-slot rng
        buffer are donated. Returns (slot_cache, emitted [S], rngs)."""
        # Everything the host does before the device has anything to do
        # (docs/Serving.md "Where a tick's time goes").
        with telemetry.span("decode_engine/step_args"):
            params = self._place_params(params)
            tokens = jnp.asarray(tokens, jnp.int32)
            rngs = jnp.asarray(rngs, jnp.uint32)
            sample_mask = jnp.asarray(sample_mask, bool)
            fp = self._params_fingerprint(params)
            slots = int(tokens.shape[0])
            step_key = (slots, float(temperature), top_k, top_p, fp)
            step_fn = build_step_fn(self.model, temperature, top_k, top_p)
            step_args = (params, slot_cache, tokens, rngs, sample_mask)
            out_shardings = None
            if self.mesh is not None:
                out_shardings = (
                    self._shardings_of(slot_cache), self._rep_sharding,
                    self._rep_sharding,
                )
            compiled = self._compiled(
                self._step, step_key, "step",
                lambda: self._jit(
                    step_fn, step_args, donate=(1, 3),
                    out_shardings=out_shardings,
                ).lower(*step_args).compile(),
            )
        with telemetry.span("decode_engine/step", slots=slots):
            return compiled(*step_args)

    def spec_step(
        self,
        params,
        slot_cache,
        tokens,
        n_known,
        eos_ids,
        rngs,
        active,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
    ):
        """Advance every slot up to W = tokens.shape[1] tokens in ONE
        compiled speculative program (build_spec_step_fn). Compiled once
        per (grid size, window width, sampling config, params
        fingerprint) — tokens / n_known / eos_ids are traced, so the
        drafts changing every tick never recompiles. The KV grid and the
        rng buffer are donated. Returns (slot_cache, emitted [S, W],
        counts [S], rngs)."""
        with telemetry.span("decode_engine/step_args"):
            params = self._place_params(params)
            tokens = jnp.asarray(tokens, jnp.int32)
            n_known = jnp.asarray(n_known, jnp.int32)
            eos_ids = jnp.asarray(eos_ids, jnp.int32)
            rngs = jnp.asarray(rngs, jnp.uint32)
            active = jnp.asarray(active, bool)
            slots, width = (int(tokens.shape[0]), int(tokens.shape[1]))
            fp = self._params_fingerprint(params)
            key = ("spec", slots, width, float(temperature), top_k, top_p, fp)
            fn = build_spec_step_fn(self.model, width, temperature, top_k, top_p)
            args = (params, slot_cache, tokens, n_known, eos_ids, rngs, active)
            out_shardings = None
            if self.mesh is not None:
                out_shardings = (
                    self._shardings_of(slot_cache), self._rep_sharding,
                    self._rep_sharding, self._rep_sharding,
                )
            compiled = self._compiled(
                self._spec_step, key, "spec_step",
                lambda: self._jit(
                    fn, args, donate=(1, 5), out_shardings=out_shardings,
                ).lower(*args).compile(),
            )
        with telemetry.span("decode_engine/spec_step", slots=slots,
                            width=width):
            return compiled(*args)

    # -- paged KV slot API ---------------------------------------------------
    #
    # The paged layout (module docstring): a global pool of fixed-size
    # KV blocks + per-slot block tables, gathered/scattered INSIDE the
    # compiled programs. The host-side free-list/refcount/prefix
    # bookkeeping lives in tf_yarn_tpu/serving/paging.py; the scheduler
    # composes both.

    def make_paged_pool(self, params, num_blocks: int, block_size: int):
        """Zeroed global KV block pool: every KV leaf of the model's
        decode cache with its seq axis split into (num_blocks,
        block_size); index leaves are elided (None) — positions travel
        as `paged_step`'s traced `lengths`. Block 0 is the reserved
        trash block (serving/paging.py). Nothing runs on the device
        except the zeros allocation."""
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is reserved), "
                f"got {num_blocks}"
            )
        params = self._place_params(params)
        row_avals = _decode_cache_aval(self.model, params)
        avals = paged_pool_avals(
            row_avals, num_blocks, block_size,
            self.model.config.max_seq_len,
        )

        def build():
            return jax.tree_util.tree_map(
                lambda aval: (None if aval is None
                              else jnp.zeros(aval.shape, aval.dtype)),
                avals, is_leaf=_is_none,
            )

        if self.mesh is None:
            return build()
        # Sharded pool: every block's kv-heads axis splits over tp, so
        # each device holds 1/tp of EVERY block (pool_partition_spec —
        # the pool shape itself cannot anchor on max_seq_len, the row
        # aval supplies the axis).
        from jax.sharding import NamedSharding

        max_seq_len = self.model.config.max_seq_len
        shardings = jax.tree_util.tree_map(
            lambda aval, row: (
                None if aval is None else NamedSharding(
                    self.mesh,
                    pool_partition_spec(
                        tuple(row.shape), max_seq_len, self.tp_degree
                    ),
                )
            ),
            avals, row_avals, is_leaf=_is_none,
        )
        return jax.jit(build, out_shardings=shardings)()

    def max_blocks_per_slot(self, block_size: int) -> int:
        """Block-table width: a slot grown to max_seq_len holds exactly
        this many blocks."""
        max_seq_len = self.model.config.max_seq_len
        if max_seq_len % block_size:
            raise ValueError(
                f"block_size={block_size} must divide "
                f"max_seq_len={max_seq_len}"
            )
        return max_seq_len // block_size

    def pack_prefill(self, pool, block_ids, row_cache, prefill_len: int,
                     block_size: int):
        """Splice a prefilled batch-1 cache's first `prefill_len`
        positions into `block_ids` (ceil(prefill_len/block_size) ids,
        traced values — one compiled program per prefill bucket). The
        pool is donated: HBM updates in place; use the return."""
        block_ids = jnp.asarray(block_ids, jnp.int32)
        n_pack = -(-prefill_len // block_size)
        if block_ids.shape != (n_pack,):
            raise ValueError(
                f"pack_prefill needs {n_pack} block ids for "
                f"prefill_len={prefill_len}, got shape {block_ids.shape}"
            )
        key = ("pack", prefill_len, block_size,
               self._tree_fingerprint(pool))
        pack_fn = build_pack_prefill_fn(self.model, block_size, prefill_len)
        args = (pool, block_ids, row_cache)
        out_shardings = self._shardings_of(pool) if self.mesh is not None \
            else None
        compiled = self._compiled(
            self._pack, key, "pack",
            lambda: self._jit(
                pack_fn, args, donate=(0,), out_shardings=out_shardings,
            ).lower(*args).compile(),
        )
        with telemetry.span("decode_engine/pack_prefill",
                            prefill=prefill_len):
            return compiled(*args)

    def extract_blocks(self, params, pool, block_ids, block_size: int):
        """Gather `block_ids` (traced (W,) values — W fixed at the
        block-table width keeps this at ONE compile key per pool
        layout) pool rows into a dense payload pytree for a bulk
        `jax.device_get`. Read-only: the pool is NOT donated. Padding
        ids should aim at the trash block; their payload rows are
        garbage the caller discards."""
        params = self._place_params(params)
        block_ids = jnp.asarray(block_ids, jnp.int32)
        width = int(block_ids.shape[0])
        key = ("extract", width, block_size, self._tree_fingerprint(pool))
        args = (pool, block_ids)

        def _build():
            # The row aval costs a whole-model eval_shape trace — only
            # pay it on the compile miss, never on the per-swap hit
            # path (a suspend must cost one gather, not one trace).
            row_aval = _decode_cache_aval(self.model, params)
            fn = build_extract_blocks_fn(self.model, row_aval)
            return self._jit(fn, args).lower(*args).compile()

        compiled = self._compiled(self._extract, key, "extract", _build)
        with telemetry.span("decode_engine/extract_blocks", blocks=width):
            return compiled(*args)

    def inject_blocks(self, params, pool, block_ids, payload,
                      block_size: int):
        """Scatter a swap payload (same pytree `extract_blocks`
        produced, host or device arrays) back into physical blocks
        `block_ids`. The pool is donated — HBM updates in place; use
        the return. Rows that must not land (prefix-cache hits, pad)
        are aimed at the trash block."""
        params = self._place_params(params)
        block_ids = jnp.asarray(block_ids, jnp.int32)
        width = int(block_ids.shape[0])
        key = ("inject", width, block_size, self._tree_fingerprint(pool))
        payload = jax.tree_util.tree_map(
            lambda leaf: None if leaf is None else jnp.asarray(leaf),
            payload, is_leaf=_is_none,
        )
        args = (pool, block_ids, payload)

        def _build():
            # Same hit-path discipline as extract_blocks: the model
            # trace behind the row aval runs once per layout, not once
            # per resume.
            row_aval = _decode_cache_aval(self.model, params)
            fn = build_inject_blocks_fn(self.model, row_aval)
            out_shardings = self._shardings_of(pool) \
                if self.mesh is not None else None
            return self._jit(
                fn, args, donate=(0,), out_shardings=out_shardings,
            ).lower(*args).compile()

        compiled = self._compiled(self._inject, key, "inject", _build)
        with telemetry.span("decode_engine/inject_blocks", blocks=width):
            return compiled(*args)

    def paged_step(
        self,
        params,
        pool,
        tables,
        lengths,
        tokens,
        rngs,
        sample_mask,
        block_size: int,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
    ):
        """Advance every slot one token against the block pool in ONE
        compiled program (build_paged_step_fn). Compiled once per (grid
        size, pool shape, block size, sampling config, params
        fingerprint); tables/lengths/tokens are traced, so per-tick
        table changes never recompile. The pool and the rng buffer are
        donated. Returns (pool, emitted [S], rngs)."""
        with telemetry.span("decode_engine/step_args"):
            params = self._place_params(params)
            tables = jnp.asarray(tables, jnp.int32)
            lengths = jnp.asarray(lengths, jnp.int32)
            tokens = jnp.asarray(tokens, jnp.int32)
            rngs = jnp.asarray(rngs, jnp.uint32)
            sample_mask = jnp.asarray(sample_mask, bool)
            slots = int(tokens.shape[0])
            key = (slots, tuple(tables.shape), block_size, float(temperature),
                   top_k, top_p, self._params_fingerprint(params),
                   self._tree_fingerprint(pool))
            step_fn = build_paged_step_fn(
                self.model, block_size, temperature, top_k, top_p
            )
            args = (params, pool, tables, lengths, tokens, rngs, sample_mask)
            out_shardings = None
            if self.mesh is not None:
                out_shardings = (
                    self._shardings_of(pool), self._rep_sharding,
                    self._rep_sharding,
                )
            compiled = self._compiled(
                self._paged_step, key, "paged_step",
                lambda: self._jit(
                    step_fn, args, donate=(1, 5), out_shardings=out_shardings,
                ).lower(*args).compile(),
            )
        with telemetry.span("decode_engine/paged_step", slots=slots):
            return compiled(*args)

    def paged_spec_step(
        self,
        params,
        pool,
        tables,
        lengths,
        tokens,
        n_known,
        eos_ids,
        rngs,
        active,
        block_size: int,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        decode_attention: str = "gather",
    ):
        """Advance every slot up to W = tokens.shape[1] tokens against
        the block pool in ONE compiled speculative program
        (build_paged_spec_step_fn; `decode_attention` picks the gather
        vs fused-kernel verify forward). tables / lengths / tokens /
        n_known / eos_ids are traced — per-tick changes never recompile.
        The pool and the rng buffer are donated. Returns (pool, emitted
        [S, W], counts [S], rngs)."""
        if decode_attention == "fused" and self.tp_degree > 1:
            raise ValueError(
                "decode_attention='fused' cannot run tensor-parallel "
                "yet: paged_int8_window_attention reads the whole block "
                "pool inside one pallas kernel and cannot read a "
                f"tp={self.tp_degree}-sharded pool; use "
                "decode_attention='gather' (XLA shards the gather "
                "path), or tp=1"
            )
        with telemetry.span("decode_engine/step_args"):
            params = self._place_params(params)
            tables = jnp.asarray(tables, jnp.int32)
            lengths = jnp.asarray(lengths, jnp.int32)
            tokens = jnp.asarray(tokens, jnp.int32)
            n_known = jnp.asarray(n_known, jnp.int32)
            eos_ids = jnp.asarray(eos_ids, jnp.int32)
            rngs = jnp.asarray(rngs, jnp.uint32)
            active = jnp.asarray(active, bool)
            slots, width = (int(tokens.shape[0]), int(tokens.shape[1]))
            key = ("paged_spec", slots, width, tuple(tables.shape), block_size,
                   decode_attention, float(temperature), top_k, top_p,
                   self._params_fingerprint(params),
                   self._tree_fingerprint(pool))
            fn = build_paged_spec_step_fn(
                self.model, block_size, width, temperature, top_k, top_p,
                decode_attention=decode_attention,
            )
            args = (params, pool, tables, lengths, tokens, n_known, eos_ids,
                    rngs, active)
            out_shardings = None
            if self.mesh is not None:
                out_shardings = (
                    self._shardings_of(pool), self._rep_sharding,
                    self._rep_sharding, self._rep_sharding,
                )
            compiled = self._compiled(
                self._paged_spec_step, key, "paged_spec_step",
                lambda: self._jit(
                    fn, args, donate=(1, 7), out_shardings=out_shardings,
                ).lower(*args).compile(),
            )
        with telemetry.span("decode_engine/paged_spec_step", slots=slots,
                            width=width):
            return compiled(*args)

    def _tree_fingerprint(self, tree) -> int:
        leaves = jax.tree_util.tree_leaves(tree)
        return hash(tuple(
            (tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves
        ))

    # -- compiled-artifact introspection -----------------------------------
    #
    # The HLO analysis engine (tf_yarn_tpu/analysis/hlo_engine.py) audits
    # what this engine actually compiled: the cache keys prove tick-to-tick
    # host inputs stayed traced (TYA205 recompile-churn — a key that varies
    # across ticks means something that should be a traced value became a
    # static one), and the executables themselves carry the optimized HLO
    # (collective census, donation aliasing).

    def _program_caches(self) -> Dict[str, Dict[tuple, Any]]:
        return {
            "prefill": self._prefill,
            "decode": self._decode,
            "step": self._step,
            "paged_step": self._paged_step,
            "pack": self._pack,
            "spec_step": self._spec_step,
            "paged_spec_step": self._paged_spec_step,
            "extract": self._extract,
            "inject": self._inject,
        }

    def program_keys(self) -> Dict[str, List[tuple]]:
        """Every compile-cache key per program kind, in insertion order.
        One key per kind across a serving run is the recompile-free
        contract the paged/spec tick programs promise (tables / lengths /
        tokens are traced); `stats` carries the matching
        `{kind}_compiles` counters."""
        with self._lock:
            return {
                kind: list(cache)
                for kind, cache in self._program_caches().items()
            }

    def compiled_programs(self) -> Dict[str, Dict[tuple, Any]]:
        """The compiled executables per kind keyed exactly like
        `program_keys` — each exposes the optimized HLO via
        `.as_text()`, which is what the TYA2xx compiled-artifact rules
        read (input_output_alias map, collective ops)."""
        with self._lock:
            return {
                kind: dict(cache)
                for kind, cache in self._program_caches().items()
            }

    # -- the public entry point --------------------------------------------

    def generate(
        self,
        params,
        prompt_tokens,
        max_new_tokens: int,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        seed: int = 0,
        eos_token: Optional[int] = None,
    ):
        """Drop-in `generate`: [B, P] -> [B, P + max_new_tokens] int32."""
        prompt = jnp.asarray(prompt_tokens, jnp.int32)
        b, prompt_len = prompt.shape
        cfg = self.model.config
        if prompt_len + max_new_tokens > cfg.max_seq_len:
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds config.max_seq_len ({cfg.max_seq_len}) — the KV "
                "cache size"
            )
        if max_new_tokens == 0:
            return prompt
        max_batch = self.batch_buckets[-1] if self.batch_buckets else None
        if max_batch is not None and b > max_batch:
            # Chunk through the largest bucket instead of compiling a
            # one-off unbucketed program for every oversized batch size.
            # Greedy outputs are identical either way (rows are
            # independent); at temperature > 0 each chunk draws from its
            # own seed-`seed` chain, matching a direct call on that
            # chunk — the same documented caveat batch padding already
            # carries (categorical noise is shaped by the device batch).
            with self._lock:
                self.stats["oversize_batch_chunks"] += 1
            telemetry.get_registry().counter(
                "decode_engine/oversize_batch_chunks"
            ).inc()
            _logger.info(
                "decode-engine: batch %d exceeds largest bucket %d — "
                "chunking into %d calls", b, max_batch,
                -(-b // max_batch),
            )
            chunks = [
                self.generate(
                    params, prompt[i:i + max_batch], max_new_tokens,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    seed=seed, eos_token=eos_token,
                )
                for i in range(0, b, max_batch)
            ]
            return jnp.concatenate(chunks, axis=0)
        params = self._place_params(params)
        fp = self._params_fingerprint(params)
        with self._lock:
            self.stats["calls"] += 1
        telemetry.get_registry().counter("decode_engine/calls").inc()

        b_bucket, f = self.select_buckets(b, prompt_len)
        if b_bucket != (_ceil_bucket(b, self.batch_buckets) or -1) \
                or f != (_floor_bucket(prompt_len, self.prompt_buckets) or -1):
            with self._lock:
                self.stats["unbucketed_shapes"] += 1
            telemetry.get_registry().counter(
                "decode_engine/unbucketed_shapes"
            ).inc()
            _logger.info(
                "decode-engine: shape (B=%d, P=%d) outside the bucket grid "
                "— exact-shape compile", b, prompt_len,
            )
        if b_bucket > b:
            # Pad rows participate in every device op and are sliced
            # away at the end; repeating a real row keeps them on the
            # same numeric path as genuine inputs.
            pad = jnp.broadcast_to(prompt[-1:], (b_bucket - b, prompt_len))
            prompt_padded = jnp.concatenate([prompt, pad], axis=0)
        else:
            prompt_padded = prompt
        rest_len = prompt_len - f
        has_rest = rest_len > 0
        has_eos = eos_token is not None

        cache, last_logits = self._compiled_prefill(
            params, prompt_padded[:, :f], fp
        )

        t_max = -(-max_new_tokens // self.token_bucket) * self.token_bucket
        out0 = jnp.full(
            (b_bucket, t_max),
            eos_token if has_eos else 0,
            jnp.int32,
        )
        rng = jax.random.PRNGKey(seed)
        num_new = jnp.asarray(max_new_tokens, jnp.int32)
        eos_id = jnp.asarray(eos_token if has_eos else -1, jnp.int32)

        decode_key = (b_bucket, t_max, has_rest, has_eos, float(temperature),
                      top_k, top_p, fp)
        if has_rest:
            rest = jnp.zeros((b_bucket, self._rest_width), jnp.int32)
            rest = jax.lax.dynamic_update_slice(
                rest, prompt_padded[:, f:], (0, 0)
            )
            decode_args = (params, cache, rest,
                           jnp.asarray(rest_len, jnp.int32), num_new, rng,
                           eos_id, out0)
            donate = (1, 7)
        else:
            decode_args = (params, cache, last_logits, num_new, rng, eos_id,
                           out0)
            donate = (1, 6)
        decode_fn = build_decode_fn(
            self.model, temperature, top_k, top_p, has_eos, has_rest
        )
        decode_out_shardings = None
        if self.mesh is not None:
            decode_out_shardings = (
                self._rep_sharding, self._shardings_of(cache),
            )
        compiled_decode = self._compiled(
            self._decode, decode_key, "decode",
            lambda: self._jit(
                decode_fn, decode_args, donate=donate,
                out_shardings=decode_out_shardings,
            ).lower(*decode_args).compile(),
        )
        # The returned final cache exists only to give the donated input
        # cache an output to alias; dropping it frees the HBM.
        with telemetry.span("decode_engine/decode", batch=b_bucket):
            out, _cache = compiled_decode(*decode_args)
        generated = out[:b, :max_new_tokens]
        return jnp.concatenate([prompt, generated], axis=1)


# --------------------------------------------------------------------------
# Module-level engine registry: `generate()` routes every caller through
# a shared engine per model, so repeated calls — including the thin
# compatibility wrapper's — hit the compile cache.
# --------------------------------------------------------------------------

_ENGINES: Dict[Any, DecodeEngine] = {}
_ENGINES_LOCK = threading.Lock()


def get_engine(model, mesh=None) -> DecodeEngine:
    """The shared engine for `model` (flax modules hash by structure, so
    equal configs share one engine; unhashable models fall back to
    identity). `mesh` keys the registry too — a tensor-parallel engine
    and a single-device engine for the same model are distinct programs
    and must not share compile caches."""
    try:
        key = (model, mesh)
        hash(key)
    except TypeError:
        key = (id(model), mesh)
    with _ENGINES_LOCK:
        engine = _ENGINES.get(key)
        if engine is None:
            engine = _ENGINES[key] = DecodeEngine(model, mesh=mesh)
        return engine


def clear_engines() -> None:
    """Drop every cached engine (tests; frees compiled executables)."""
    with _ENGINES_LOCK:
        _ENGINES.clear()
