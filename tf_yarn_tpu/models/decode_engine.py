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
  ``tp`` mesh axis), the prefill's row cache and the paged block pool shard
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

* **Paged KV slots.** The serving grid keeps its KV in ONE global pool
  of fixed-size blocks (`make_paged_pool`) plus a per-slot block table.
  The compiled `paged_step` runs the model once over all slots' tokens
  with the pool as its `kv_pool` collection: each attention layer writes
  the token's K/V row into the slot's current block and reads the slot's
  keys and values through its block table
  (`ops.decode_attention.paged_decode_attention`: on a TPU a kernel that
  reads the live blocks only, elsewhere and under `tp` a gather of the
  table + `xla_attention`) — all inside one program, zero host syncs per
  tick. Positions beyond a slot's length get exactly-zero weight, so the
  plain read attends over the values a batch-1 decode cache of that
  request would hold: the fp path emits `generate_legacy`'s tokens.
  A slot's input token and rng row are the step before's outputs, still
  on the device, unless the host forces its own (`_feed`: a prompt token
  in replay, the row a slot was admitted with), so the scheduler launches
  a step before it has read the one before. Free/allocate is host-side
  free-list bookkeeping (`serving/paging.py`); there is no per-eviction
  device program at all. `pack_prefill` splices a bucketed-prefill result into
  a slot's blocks (which bucket, and how many of its rows are the prompt's:
  `slot_prefill_len`; the bucket above the prompt, padded, where the model's
  prompt rows do not depend on later tokens, `ceiling_prefill`, else the one
  below, whole); int8 KV composes transparently (the pool stores
  whatever leaves the model's cache has — int8 values + scales
  included).

* **Cache leaves by kind.** A model says what each leaf of its decode
  cache is (`model.serving_contract().leaf_kinds`: name -> kind): ``paged`` by
  token (keys, values: the pool above, with the leaf's sequence axis
  given from the end of its shape), held once a ``slot`` (a recurrent
  state, a convolution's tail: an array `[max_slots, ...]` beside the
  pool, `make_slot_state`), a ``ring`` (a window layer's last rows, row
  `p % len` holding position p: held once a slot like state, and named
  apart because it is a bounded span of a token sequence, not a summary of
  it), or the slot's ``index``. A paged leaf need not have a head axis
  (a latent row `[seq, width]`), and widths may differ leaf by leaf. A
  model with slot or ring leaves is stepped by `paged_state_step`: the same
  ONE call of the model over all slots' tokens, the state as its `cache`
  collection beside the pool, read and written in place, and what the
  expert layers (and the cache reads, where a model counts them) counted
  returned beside the tokens. `write_slot_state` puts a prefill's final
  state (or zeros) into a slot at admission. Which step a model gets
  (`counted_step`) and whether anything of it is held once a slot
  (`slot_state_leaves`) are two questions: a model whose every leaf is
  paged and whose layers count (the contract's `counts`) takes the same
  step with an empty state, and nothing that moves whole blocks stands
  aside for it.
"""

from __future__ import annotations

import functools
import logging
import threading
import weakref
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tf_yarn_tpu import telemetry
from tf_yarn_tpu.models.generate import _sample
from tf_yarn_tpu.models.spec import verify_window
from tf_yarn_tpu.models.moe import stack_counts
from tf_yarn_tpu.models.transformer import PagedContext, prefill_key_pairs
from tf_yarn_tpu.models.trunk import contract_of

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
    """(params, prompt [B, F]) -> (cache, last-position logits [B, V]).
    For a model whose contract says `takes_prompt_len` (it writes a `ring`
    from the rows that end there), a third argument: the prompt's true
    length, a traced scalar (None = all F tokens are the prompt's). Every
    other model's prefill is a program of the params and the tokens alone."""

    def run(params, prompt, **told):
        logits, state = model.apply(
            params, prompt, decode=True, mutable=["cache"], **told)
        return state["cache"], logits[:, -1]

    if contract_of(model).takes_prompt_len:
        def prefill(params, prompt, prompt_len=None):
            return run(params, prompt, prompt_len=prompt_len)
    else:
        def prefill(params, prompt):
            return run(params, prompt)
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


# --------------------------------------------------------------------------
# Paged KV pool: its avals + the compiled gather/scatter programs
# --------------------------------------------------------------------------

PAGED, SLOT, RING, INDEX = "paged", "slot", "ring", "index"
# Kinds held once a slot, as `[max_slots, ...]` arrays beside the pool.
HELD_A_SLOT = (SLOT, RING)


class LeafLayout:
    """What one cache leaf is: its kind, its name, and for a paged leaf
    the sequence axis of the batch-1 row (a non-negative index). A plain
    object, so that a tree of them has one leaf a cache leaf."""

    __slots__ = ("kind", "axis", "name")

    def __init__(self, kind: str, axis: Optional[int], name: str):
        self.kind, self.axis, self.name = kind, axis, name

    def __repr__(self):
        return f"LeafLayout({self.kind!r}, {self.axis!r}, {self.name!r})"


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", getattr(path[-1], "name", path[-1])))


def cache_layout(model, row_aval):
    """A tree like `row_aval` (the batch-1 decode cache) of `LeafLayout`s,
    from what the model declares (its contract's `leaf_kinds`: leaf name ->
    (kind, sequence axis from the end of the shape)). A leaf the model does
    not name, a paged leaf whose declared axis is not `max_seq_len` long, or
    a model without a contract is an error: nothing is guessed."""
    kinds = contract_of(model).leaf_kinds
    max_seq_len = model.config.max_seq_len

    def leaf(path, aval):
        name = _leaf_name(path)
        if name not in kinds:
            raise ValueError(
                f"cache leaf {name!r} {tuple(aval.shape)} is not among "
                f"those {type(model).__name__}.serving_contract() names: "
                f"{sorted(kinds)}"
            )
        kind, axis = kinds[name]
        if kind not in (PAGED, SLOT, RING, INDEX):
            raise ValueError(f"cache leaf {name!r}: unknown kind {kind!r}")
        if kind != PAGED:
            return LeafLayout(kind, None, name)
        axis = len(aval.shape) + axis if axis < 0 else axis
        if not 0 <= axis < len(aval.shape) or aval.shape[axis] != max_seq_len:
            raise ValueError(
                f"cache leaf {name!r} {tuple(aval.shape)} is declared paged "
                f"along axis {axis}, which is not max_seq_len={max_seq_len} "
                "long"
            )
        return LeafLayout(PAGED, axis, name)

    return jax.tree_util.tree_map_with_path(leaf, row_aval)


def slot_state_names(layout) -> Tuple[str, ...]:
    """Names of the leaves held once a slot (state and rings), in tree
    order, without repeats: empty for a model whose whole cache is paged."""
    names = []
    for leaf in jax.tree_util.tree_leaves(layout):
        if leaf.kind in HELD_A_SLOT and leaf.name not in names:
            names.append(leaf.name)
    return tuple(names)


def _decode_cache_aval(model, params):
    """Abstract batch-1 decode cache (the slot row shape). Works with
    traced or concrete params — eval_shape never touches the device."""
    return jax.eval_shape(
        build_prefill_fn(model), params,
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
    )[0]


def paged_pool_avals(model, row_aval, num_blocks: int, block_size: int):
    """The pool pytree's avals, by the layout the model declares
    (`cache_layout`): every paged leaf's seq axis becomes (num_blocks,
    block_size); index leaves become None — per-slot positions travel as
    the step's `lengths` argument instead of living in the cache — and so
    do leaves held once a slot, which live beside the pool
    (`make_slot_state`)."""
    max_seq_len = model.config.max_seq_len
    if max_seq_len % block_size:
        raise ValueError(
            f"block_size={block_size} must divide max_seq_len={max_seq_len}"
        )

    def leaf(aval, lay):
        if lay.kind != PAGED:
            return None
        ax = lay.axis
        shape = aval.shape[:ax] + (num_blocks, block_size) + aval.shape[ax + 1:]
        return jax.ShapeDtypeStruct(shape, aval.dtype)

    return jax.tree_util.tree_map(
        leaf, row_aval, cache_layout(model, row_aval))


def _is_none(x) -> bool:
    return x is None


def _is_named_sharding(sharding) -> bool:
    from jax.sharding import NamedSharding

    return isinstance(sharding, NamedSharding)


def _gather_slot_cache(pool, row_aval, layout, table, length):
    """One slot's batch-1 cache view: KV leaves gathered from the pool by
    the block table (and reshaped back to one `max_seq_len` seq axis),
    index leaves filled with the slot's length. Values beyond `length`
    are stale pool garbage — every decode-attention path masks positions
    >= cache_index to exactly-zero weight, so the view is value-identical
    to the request's own decode cache where it matters (bit-identity
    with `generate_legacy` relies on this)."""

    def leaf(pool_leaf, aval, lay):
        if pool_leaf is None:
            return jnp.full(aval.shape, length, aval.dtype)
        return jnp.take(pool_leaf, table, axis=lay.axis).reshape(aval.shape)

    with jax.named_scope("attention/kv_gather"):
        return jax.tree_util.tree_map(
            leaf, pool, row_aval, layout, is_leaf=_is_none
        )


def _sample_slots(logits, tokens, rngs, sample_mask, temperature: float,
                  top_k: Optional[int], top_p: Optional[float]):
    """Every slot's next token from its row of `logits` [S, V]: a
    masked-off slot consumes no RNG and passes its input token through, so
    each slot's split chain stays bit-aligned with generate_legacy's
    one-split-per-sample. -> (emitted [S], rngs)."""

    def sample(row_logits, token, rng, do_sample):
        next_rng, sample_key = jax.random.split(rng)
        sampled = _sample(
            row_logits[None], sample_key, temperature, top_k, top_p
        )[0]
        return (jnp.where(do_sample, sampled, token),
                jnp.where(do_sample, next_rng, rng))

    return jax.vmap(sample)(logits, tokens, rngs, sample_mask)


def all_forced(tokens, rngs):
    """The one-token step's five feed arguments `(emitted, rngs, tokens,
    rng_rows, forced)` for a caller that holds every slot's token and rng
    row on the host (the tests, the analysis entries, a step run once at
    start-up): every slot forced, so nothing is taken from a step before."""
    tokens = np.asarray(tokens, np.int32)
    rngs = np.asarray(rngs, np.uint32)
    return tokens, rngs, tokens, rngs, np.ones(tokens.shape, bool)


def feed_avals(slots: int):
    """`all_forced`'s five as shapes and types (programs lowered without
    arrays)."""
    tokens = jax.ShapeDtypeStruct((slots,), jnp.int32)
    rows = jax.ShapeDtypeStruct((slots, 2), jnp.uint32)
    return tokens, rows, tokens, rows, jax.ShapeDtypeStruct((slots,), bool)


def _feed(emitted, rngs, tokens, rng_rows, forced):
    """A step's input tokens and rng rows, from where they are: the step
    before left `emitted` and `rngs` on the device, and the host sends
    what only it knows under `forced` (both one-token builders)."""
    return (jnp.where(forced, tokens, emitted),
            jnp.where(forced[:, None], rng_rows, rngs))


def _step_host_args(tables, lengths, emitted, rngs, tokens, rng_rows,
                    forced, sample_mask):
    """The one-token step's arguments after its trees, each with its type
    (`DecodeEngine._paged_program`'s `host`)."""
    return ((tables, jnp.int32), (lengths, jnp.int32), (emitted, jnp.int32),
            (rngs, jnp.uint32), (tokens, jnp.int32), (rng_rows, jnp.uint32),
            (forced, bool), (sample_mask, bool))


def build_paged_step_fn(model, block_size: int, temperature: float,
                        top_k: Optional[int], top_p: Optional[float],
                        with_logits: bool = False,
                        paged_kernel: Optional[bool] = None):
    """The paged continuous-batching step, shared by the engine and the
    analysis jaxpr entry point (`models.decode_engine.paged_step`).

        fn(params, pool, tables, lengths, emitted, rngs, tokens, rng_rows,
           forced, sample_mask) -> (pool, emitted [S], rngs)

    ONE compiled program advances every slot one token against the
    global block pool: the model is applied once over the slots' tokens
    [S, 1] with the pool as its `kv_pool` collection and (tables,
    lengths) as `paged_ctx`; each attention layer writes the token's K/V
    row into block `table[length // block_size]` at offset
    `length % block_size` and attends through the table
    (`paged_decode_attention`). `paged_kernel` says how the pool is read:
    None leaves it to the backend and the pool's shape (the kernel on a
    TPU), False is the plain gather (the engine's choice under `tp`).
    A slot's input token and rng row come from where they are (`_feed`):
    `emitted` [S] and `rngs` [S, 2] are what the step before returned,
    still on the device, so a slot that feeds back its last token needs
    nothing of the host and the next step can be launched before this
    one is read; under `forced` [S] the host's `tokens` [S] and
    `rng_rows` [S, 2] take their place: a prompt token while a slot
    replays its prompt remainder, a resumed stream's last token, zero for
    a free slot, and the rng row the slot was admitted or resumed with
    (replay samples nothing, so that row is the slot's own until its
    first sampled step, after which it is no longer forced).
    `sample_mask` [S] is the traced active mask: masked-off slots
    (free, or mid-replay) run the same device program — the KV append is
    the point for replay slots, garbage for free ones — but consume no
    RNG and pass their input token through, so each slot's split chain
    stays bit-aligned with generate_legacy's one-split-per-sample. The
    step that consumes a request's LAST prompt token has sample_mask on:
    its output is the first generated token, sampled with the first
    split — exactly generate_legacy's prefill sample.
    `tables`/`lengths` are traced values — tick-to-tick table changes
    never recompile. Inactive slots carry an all-zero table row and
    length 0, so their (meaningless) write lands in the reserved trash
    block 0 and can never corrupt a live slot. `with_logits` appends the
    step's logits [S, V] to what is returned (the tests compare the two
    ways to read the pool on them).
    """
    del block_size  # the pool's own shape says it

    def step(params, pool, tables, lengths, emitted, rngs, tokens, rng_rows,
             forced, sample_mask):
        tokens, rngs = _feed(emitted, rngs, tokens, rng_rows, forced)
        row_aval = _decode_cache_aval(model, params)
        _refuse_slot_state(cache_layout(model, row_aval),
                           "paged_step (use paged_state_step)")
        logits, new = model.apply(
            {**params, "kv_pool": _prune_none_tree(pool)}, tokens[:, None],
            decode=True,
            paged_ctx=PagedContext(tables, lengths, paged_kernel),
            mutable=["kv_pool"],
        )
        emitted, rngs = _sample_slots(
            logits[:, -1], tokens, rngs, sample_mask, temperature, top_k,
            top_p)
        out = (_merge_pool_tree(pool, dict(new["kv_pool"])), emitted, rngs)
        return out + (logits[:, -1],) if with_logits else out

    return step


def _refuse_slot_state(layout, feature: str):
    """A program that carries keys and values only may not run a model
    that also holds state once a slot: it would be silently wrong."""
    names = slot_state_names(layout)
    if names:
        raise ValueError(
            f"{feature} carries no per-slot state, and the model holds "
            f"{', '.join(names)} once a slot"
        )


def _new_rows(cache, layout, length, width: int):
    """The `width` rows a call just wrote at `length` into each paged leaf
    of one slot's cache view; None for the other leaves."""

    def leaf(value, lay):
        if lay.kind != PAGED:
            return None
        return jax.lax.dynamic_slice_in_dim(value, length, width,
                                            axis=lay.axis)

    return jax.tree_util.tree_map(leaf, cache, layout)


def build_paged_state_step_fn(model, block_size: int, temperature: float,
                              top_k: Optional[int], top_p: Optional[float],
                              with_logits: bool = False,
                              paged_kernel: Optional[bool] = None):
    """The paged step of a model that also holds state once a slot
    (`cache_layout`: `slot` leaves — a recurrent state, a convolution's
    tail):

        fn(params, pool, state, tables, lengths, emitted, rngs, tokens,
           rng_rows, forced, sample_mask)
            -> (pool, state, emitted [S], rngs, counts)

    `build_paged_step_fn`'s one call of the model over all slots' tokens
    (so that its expert layers see the step's tokens as one batch), with
    the state as the model's `cache` collection beside the pool. `state`
    holds the slot leaves as `[S, ...]` arrays (None elsewhere); they are
    read and written in place (donated). A free slot runs along on
    whatever its state holds and writes its row to the trash block;
    admission overwrites both (`write_slot_state`).
    `counts` stacks what the model's layers counted into `moe_stats` for
    the active slots (table row not all trash), a row a layer
    (`moe.stack_counts`; the columns are `moe.ExpertRow`'s), and rides back
    with `emitted`. A model whose attention layers count what they read
    (`cache_stats`, summed over layers: one vector, named by the contract's
    `reads`) has it appended as a sixth output. Where a slot's token and rng row come from (`_feed`), sampling
    and the RNG discipline are `build_paged_step_fn`'s. `with_logits`
    appends the step's logits [S, V] last (the tests compare them with a
    reference).
    """
    del block_size  # the pool's own shape says it

    def step(params, pool, state, tables, lengths, emitted, rngs, tokens,
             rng_rows, forced, sample_mask):
        tokens, rngs = _feed(emitted, rngs, tokens, rng_rows, forced)
        active = tables[:, 0] != 0
        logits, new = model.apply(
            {**params, "cache": _prune_none_tree(state),
             "kv_pool": _prune_none_tree(pool)},
            tokens[:, None], decode=True, count_mask=active,
            paged_ctx=PagedContext(tables, lengths, paged_kernel),
            mutable=["cache", "kv_pool", "moe_stats", "cache_stats"],
        )
        emitted, rngs = _sample_slots(
            logits[:, -1], tokens, rngs, sample_mask, temperature, top_k,
            top_p)
        out = (_merge_pool_tree(pool, dict(new["kv_pool"])),
               _merge_pool_tree(state, dict(new["cache"])),
               emitted, rngs, stack_counts(new.get("moe_stats", {})))
        reads = jax.tree_util.tree_leaves(new.get("cache_stats", {}))
        if reads:
            out += (jnp.sum(jnp.stack(reads), axis=0),)
        return out + (logits[:, -1],) if with_logits else out

    return step


# --------------------------------------------------------------------------
# Speculative decoding: the windowed verify step
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
                value, None if updated is None else updated.get(key)
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

    ONE compiled program advances every slot up to W tokens: per slot,
    the target model scores the whole window in one forward over the
    slot's gathered cache view, and verify_window computes the emitted
    prefix. The slot's valid length is the HOST's `lengths` bookkeeping
    (it advances by n_known + n_emitted after the tick), so the program
    itself needs no index fixup. All `width` freshly written K/V rows
    scatter back at logical positions length..length+W-1 — rows beyond
    a slot's reserved blocks hit table entries 0 and land in the trash
    block, so rejected drafts can never touch another slot's KV.
    Inactive slots (active=False) emit nothing and consume no RNG.
    tokens / n_known / eos_ids are traced, so tick-to-tick changes
    never recompile.

    This program is ALSO the chunk-apply for chunked prefill
    (docs/Serving.md "Chunked prefill"): a window whose tokens are all
    pending prompt tokens (n_known == W) is a teacher-forced chunk —
    the forward writes W prompt rows through the block table and emits
    nothing. The scheduler widens W to max(spec_k + 1, prefill_chunk);
    it is a compile-key dimension, fixed per grid, so chunking adds zero
    recompiles.

    `decode_attention` picks the attention implementation inside the
    verify forward:

    * ``"gather"`` — materialize each slot's batch-1 cache view from the
      pool (exactly `paged_step`'s path) and run the model's standard
      decode attention over it. Reference semantics.
    * ``"fused"`` — int8 pools only: the model's decode attention reads
      the block pool DIRECTLY through `paged_int8_window_attention`
      (ops/decode_attention.py — block tables ride in SMEM via scalar
      prefetch), the window's K/V rows quantize and scatter into the
      pool before the kernel runs, and no gathered per-slot view is ever
      materialized. Numerics differ from the gather path only by
      reduction order (tolerance-tested).
    """
    if decode_attention not in DECODE_ATTENTION_MODES:
        raise ValueError(
            f"decode_attention must be one of {DECODE_ATTENTION_MODES}, "
            f"got {decode_attention!r}"
        )
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
                tokens, decode=True,
                paged_ctx=PagedContext(tables, lengths, True),
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
        layout = cache_layout(model, row_aval)
        _refuse_slot_state(layout, "the speculative / chunked window")
        blocks_per_slot = tables.shape[1]

        def one_slot(table, length, toks, known, eos_id, rng, act):
            cache = _gather_slot_cache(pool, row_aval, layout, table, length)
            logits, state = model.apply(
                {**params, "cache": cache}, toks[None, :], decode=True,
                mutable=["cache"],
            )
            emitted, count, rng = verify_window(
                logits[0], toks, known, eos_id, rng, act,
                temperature, top_k, top_p,
            )

            with jax.named_scope("attention/kv_write"):
                rows = _new_rows(state["cache"], layout, length, width)
            return emitted, count, rng, rows

        emitted, counts, rngs, rows = jax.vmap(one_slot)(
            tables, lengths, tokens, n_known, eos_ids, rngs, active
        )

        slots = tables.shape[0]

        def write(pool_leaf, slot_rows, lay):
            if pool_leaf is None:
                return None
            ax = lay.axis
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
                write, pool, rows, layout, is_leaf=_is_none
            )
        return pool_out, emitted, counts, rngs

    return spec_step


def _write_blocks(pool_leaf, block_ids, blocks, axis: int):
    """`blocks` [..., W, block_size, ...] into the pool's blocks
    `block_ids` [W] along `axis`: one scatter of whole blocks a leaf,
    whatever W (a program of one `dynamic_update_slice` a block took 33 s
    to trace and compile for 128 blocks of 16 leaves). Ids may repeat only
    where they aim at the trash block, whose content is garbage."""
    at = (slice(None),) * axis + (block_ids,)
    return pool_leaf.at[at].set(blocks.astype(pool_leaf.dtype))


def build_pack_prefill_fn(model, block_size: int, prefill_len: int):
    """The prefill->pool splice program: write positions [0, prefill_len)
    of a freshly prefilled batch-1 cache into the slot's first
    ceil(prefill_len / block_size) blocks.

        fn(pool, block_ids, row_cache) -> pool

    `block_ids` values are traced (different slots reuse one compiled
    program); `prefill_len` is static (one program per prefill bucket).
    An id may aim at the reserved trash block 0, so that its rows land
    nowhere: the blocks past the rows an admission keeps of a padded
    bucket (`DecodeEngine.slot_prefill_len`).
    """
    n_pack = -(-prefill_len // block_size)

    def pack(pool, block_ids, row_cache):
        layout = cache_layout(model, row_cache)

        def leaf(pool_leaf, row_leaf, lay):
            if pool_leaf is None:
                return None
            ax = lay.axis
            rows = jax.lax.slice_in_dim(row_leaf, 0, prefill_len, axis=ax)
            pad = [(0, 0)] * rows.ndim
            pad[ax] = (0, n_pack * block_size - prefill_len)
            chunks = jnp.pad(rows, pad).reshape(
                rows.shape[:ax] + (n_pack, block_size) + rows.shape[ax + 1:])
            return _write_blocks(pool_leaf, block_ids, chunks, ax)

        return jax.tree_util.tree_map(
            leaf, pool, row_cache, layout, is_leaf=_is_none
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
    layout = cache_layout(model, row_aval)
    _refuse_slot_state(layout, "extract_blocks (suspend, /v1/blocks export)")

    def extract(pool, block_ids):
        def leaf(pool_leaf, lay):
            if pool_leaf is None:
                return None
            return jnp.take(pool_leaf, block_ids, axis=lay.axis)

        return jax.tree_util.tree_map(leaf, pool, layout,
                                      is_leaf=_is_none)

    return extract


def build_inject_blocks_fn(model, row_aval):
    """The swap-in scatter program, inverse of `build_extract_blocks_fn`:

        fn(pool, block_ids, payload) -> pool

    Writes payload row j into physical block `block_ids[j]` (traced
    values, static width) by the same whole-block scatter as
    `build_pack_prefill_fn` (`_write_blocks`). The pool is donated by the
    engine wrapper so resume updates HBM in place. Rows the scheduler does not want
    re-injected (prefix-cache hits re-attached by lookup, padding) are
    aimed at the trash block, whose content is garbage by contract.
    """
    layout = cache_layout(model, row_aval)
    _refuse_slot_state(layout, "inject_blocks (resume, /v1/blocks import)")

    def inject(pool, block_ids, payload):
        def leaf(pool_leaf, lay, pay_leaf):
            if pool_leaf is None:
                return None
            return _write_blocks(pool_leaf, block_ids, pay_leaf, lay.axis)

        return jax.tree_util.tree_map(leaf, pool, layout, payload,
                                      is_leaf=_is_none)

    return inject


def cache_nbytes(tree) -> int:
    """Resident bytes of a cache pytree (the paged pool, per-slot state;
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
# not divide stay replicated; a paged leaf with no head axis at all (a
# latent row) is refused by name. Weights place through the transformer's
# EXISTING logical-axis rules (parallel/sharding.py LOGICAL_RULES):
# attention heads + MLP hidden + vocab over tp, the rest replicated on
# a serving mesh — XLA then inserts the attention-output and MLP
# down-projection all-reduces from the shardings alone; no step-program
# logic changes.


def kv_partition_spec(shape: Tuple[int, ...], lay: LeafLayout, tp: int):
    """PartitionSpec for a leaf of the prefill's row cache, whose `lay`
    is its leaf of `cache_layout` over the SAME tree — the model declares
    the seq axis from the end of the shape, so extra leading layer axes
    need no special casing. Leaves that are not paged by token stay
    replicated."""
    return _heads_over_tp(len(shape), shape, lay, tp, shift=0)


def pool_partition_spec(row_shape: Tuple[int, ...], lay: LeafLayout,
                        tp: int):
    """The same heads-axis rule for a PAGED pool leaf, whose seq axis
    was split into (num_blocks, block_size) — computed from the
    ROW leaf's shape and layout, with every axis after the split shifted
    one right."""
    return _heads_over_tp(len(row_shape) + 1, row_shape, lay, tp, shift=1)


def _heads_over_tp(ndim: int, shape, lay: LeafLayout, tp: int, shift: int):
    from jax.sharding import PartitionSpec

    from tf_yarn_tpu.parallel.mesh import AXIS_TP

    if tp <= 1 or lay.kind != PAGED:
        return PartitionSpec()
    heads = lay.axis + 1
    if heads >= len(shape) - 1:
        raise ValueError(
            f"cache leaf {lay.name!r} {tuple(shape)} has no head axis after "
            f"its sequence axis to shard over tp={tp} (a latent or index "
            "row); it is refused, not silently replicated on every device"
        )
    if shape[heads] % tp:
        return PartitionSpec()
    spec = [None] * ndim
    spec[heads + shift] = AXIS_TP
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
        # place by the model's logical-axis annotations, the KV pool shards
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
        # All the engine reads of the model's class; refused if missing.
        self.contract = contract_of(model)
        self._prefill: Dict[tuple, Any] = {}
        self._decode: Dict[tuple, Any] = {}
        self._lock = threading.Lock()
        self._placed_seen = None  # _placed: (treedef, weak leaves, fp)
        # slot_state_leaves by params fingerprint: a server asks three
        # times at construction, and each ask is a trace of the model.
        self._state_leaf_names: Dict[int, Tuple[str, ...]] = {}
        self.stats = {
            "calls": 0,
            "prefill_compiles": 0,
            "decode_compiles": 0,
            "prefill_cache_hits": 0,
            "decode_cache_hits": 0,
            "paged_step_compiles": 0,
            "paged_step_cache_hits": 0,
            "pack_compiles": 0,
            "pack_cache_hits": 0,
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
        self._paged_kernels: Dict[int, str] = {}  # paged_attention_kernel
        self._pack: Dict[tuple, Any] = {}
        self._paged_spec_step: Dict[tuple, Any] = {}
        self._extract: Dict[tuple, Any] = {}
        self._inject: Dict[tuple, Any] = {}

        # Per-slot state beside the block pool (write_slot_state): row
        # splices over a tree that is None where a leaf is paged; donated,
        # so the state updates HBM in place.
        def _write_state(state, rows, slot):
            return jax.tree_util.tree_map(
                lambda buf, r: None if buf is None
                else jax.lax.dynamic_update_index_in_dim(
                    buf, r.astype(buf.dtype), slot, 0),
                state, rows, is_leaf=_is_none,
            )

        def _zero_state(state, slot):
            return jax.tree_util.tree_map(
                lambda buf: None if buf is None
                else jax.lax.dynamic_update_index_in_dim(
                    buf, jnp.zeros(buf.shape[1:], buf.dtype), slot, 0),
                state, is_leaf=_is_none,
            )

        self._write_state_jit = jax.jit(_write_state, donate_argnums=(0,))
        self._zero_state_jit = jax.jit(_zero_state, donate_argnums=(0,))

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

    def hold_params(self, params):
        """The tree a server holds and hands to every program from here
        on: `params` as restored (and, under a mesh, placed), with each
        leaf the model only ever reads through a convert to a narrower
        float type converted once (`models/param_types.py`: the rule is
        read off the model's own jaxpr). The tree handed in is used up.
        Call it before the first program is built: the compile keys
        carry the leaves' types."""
        from tf_yarn_tpu.models import param_types

        with telemetry.span("serving/cast_params") as cast_span:
            held, narrowed, before, after = param_types.narrow(
                self.model, params)
            cast_span.args.update(
                leaves=narrowed, bytes_before=before, bytes_after=after)
        with self._lock:
            self.stats["param_bytes"] = after
            self.stats["params_narrowed"] = narrowed
        return held

    def _params_fingerprint(self, params) -> int:
        leaves, treedef = jax.tree_util.tree_flatten(params)
        return hash((treedef, tuple(
            (tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves
        )))

    def _placed(self, params):
        """(`_place_params(params)`, its fingerprint), remembered for the
        tree seen last: a server hands the same placed tree to every
        step, and walking its leaves again each tick kept a v5e's host
        for 6.5 ms of a 36 ms tick with the device idle (PERF.md, PR 29).
        Remembered only when placing changed no leaf, so a hit hands the
        caller's own tree back; the leaves are held weakly, so an id is
        never taken for another array's."""
        leaves, treedef = jax.tree_util.tree_flatten(params)
        seen = self._placed_seen
        if seen is not None and seen[0] == treedef \
                and len(seen[1]) == len(leaves) \
                and all(ref() is leaf for ref, leaf in zip(seen[1], leaves)):
            return params, seen[2]
        placed = self._place_params(params)
        fp = self._params_fingerprint(placed)
        if all(a is b for a, b in
               zip(leaves, jax.tree_util.tree_leaves(placed))):
            try:
                self._placed_seen = (
                    treedef, [weakref.ref(leaf) for leaf in leaves], fp)
            except TypeError:  # a leaf no weak reference can hold
                self._placed_seen = None
        return placed, fp

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
        """NamedSharding tree for the prefill's row cache under this
        engine's mesh: kv-heads axis over tp (kv_partition_spec)."""
        from jax.sharding import NamedSharding

        return jax.tree_util.tree_map(
            lambda aval, lay: NamedSharding(
                self.mesh,
                kv_partition_spec(tuple(aval.shape), lay, self.tp_degree),
            ),
            avals, cache_layout(self.model, avals),
        )

    # -- compile cache -----------------------------------------------------

    def _compiled(self, cache_dict, key, stat_prefix, build):
        with self._lock:
            compiled = cache_dict.get(key)
            if compiled is not None:
                self.stats[f"{stat_prefix}_cache_hits"] += 1
                return compiled
        # Compile outside the lock (slow); a racing duplicate compile is
        # harmless — last writer wins, both executables are equivalent.
        with telemetry.span(
            "decode_engine/compile", kind=stat_prefix, key=str(key)
        ) as sp:
            compiled = build()
        registry = telemetry.get_registry()
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

    def _compiled_prefill(self, params, prompt, fp, length=None):
        """(cache, last-position logits) through the compile cache; the
        exact [B, F] shape keys the cache — callers pick bucketed
        shapes. `length` (None = F) is how many of the F tokens are the
        prompt's: a traced argument of the program, and only of a model's
        that `takes_prompt_len`, so a bucket stays one program."""
        b, f = prompt.shape
        prefill_key = (b, f, fp)
        prefill_args = (params, prompt)
        if self.contract.takes_prompt_len:
            prefill_args += (
                np.asarray(f if length is None else length, np.int32),)
        def build():
            prefill_fn = build_prefill_fn(self.model)
            out_shardings = None
            if self.mesh is not None:
                # Pin the fresh cache SHARDED at the source: pack_prefill
                # downstream then propagates the placement instead of
                # guessing it. The eval_shape runs only on a compile
                # miss — not per admission.
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
    # fixed grid of `max_slots` decode slots, each a block-table row and
    # a length over the paged pool below. Admission prefills a request's
    # prompt through the SAME bucketed prefill programs `generate` uses
    # and packs the result into the slot's blocks; every tick then
    # advances all slots one token in one compiled `paged_step` program.

    def slot_prefill_len(self, prompt_len: int,
                         ceiling: bool = False) -> Tuple[int, int]:
        """(bucket, kept) for a slot admission: the prefill program to run
        (its prompt bucket) and how many of its rows are the prompt's. The
        step consuming the LAST prompt token samples the first generated
        token (generate_legacy's prefill sample), so at most
        prompt_len - 1 rows are kept and the rest of the prompt replays
        through the step. The floor rule takes the largest bucket at or
        under prompt_len - 1 and keeps all of it. The `ceiling` rule
        (`ceiling_prefill` says whether this model may take it) takes the
        bucket at or above prompt_len - 1, padded past the prompt, and
        keeps prompt_len - 1 rows: one token replays. It falls back to the
        floor where no bucket that the cache can hold lies above. (0, 0) =
        no prefill: the whole prompt replays from an empty slot."""
        if prompt_len <= 1:
            return 0, 0
        kept = prompt_len - 1
        if ceiling:
            bucket = _ceil_bucket(kept, self.prompt_buckets)
            if bucket and bucket <= self.model.config.max_seq_len:
                return bucket, kept
        bucket = _floor_bucket(kept, self.prompt_buckets) or 0
        return bucket, bucket

    def ceiling_prefill(self, params) -> bool:
        """Whether an admission may prefill the bucket ABOVE its prompt and
        keep the true length (`slot_prefill_len`): only where a row of the
        prefill's cache cannot depend on the tokens after it, so that the
        pad leaves the kept rows what they would have been. The model's
        contract says so (`rows_causal`), and what it holds once a slot
        must be what the prefill left where the PROMPT ends: a `ring`,
        which a model that `takes_prompt_len` writes there. A `slot` leaf (a
        recurrent state, a convolution's tail) is what the prefill left at
        the end of its bucket, and keeps the floor rule."""
        contract = self.contract
        if not contract.rows_causal:
            return False
        held = self.slot_state_leaves(params)
        if not held:
            return True
        return contract.takes_prompt_len and all(
            contract.leaf_kinds[name][0] == RING for name in held)

    def prefill_key_pairs(self, bucket: int, kept: int) -> Tuple[int, int]:
        """(formed, visible) query-key pairs, a head, of the attention of
        one `prefill` of `bucket` tokens that keeps `kept`
        (`transformer.prefill_key_pairs`, over the contract's
        `prefill_layers`). Host arithmetic, no device read."""
        return prefill_key_pairs(
            bucket, kept, self.contract.prefill_layers,
            told=self.contract.takes_prompt_len)

    def prefill(self, params, prompt, length=None):
        """Public compiled prefill: [B, F] prompt -> (cache, last
        logits). B/F key the compile cache directly. `length`: how many
        of the F tokens are the prompt's, the rest pad (None = all); a
        model with rings writes them where the prompt ends."""
        params = self._place_params(params)
        prompt = jnp.asarray(prompt, jnp.int32)
        return self._compiled_prefill(
            params, prompt, self._params_fingerprint(params), length
        )

    # -- paged KV slot API ---------------------------------------------------
    #
    # The paged pool (module docstring): a global pool of fixed-size
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
            self.model, row_avals, num_blocks, block_size
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
        # the row aval and its declared layout supply the axis).
        from jax.sharding import NamedSharding

        shardings = jax.tree_util.tree_map(
            lambda aval, row, lay: (
                None if aval is None else NamedSharding(
                    self.mesh,
                    pool_partition_spec(
                        tuple(row.shape), lay, self.tp_degree
                    ),
                )
            ),
            avals, row_avals, cache_layout(self.model, row_avals),
            is_leaf=_is_none,
        )
        return jax.jit(build, out_shardings=shardings)()

    # -- state held once a slot ---------------------------------------------

    def counted_step(self, params) -> bool:
        """Whether the one-token step of this model is `paged_state_step`:
        it holds leaves once a slot, which only that step carries, or its
        contract says `counts`: its layers count what they routed and read,
        and only that step returns the counts. `paged_step` serves every
        other model."""
        return self.contract.counts or bool(self.slot_state_leaves(params))

    def slot_state_leaves(self, params) -> Tuple[str, ...]:
        """Names of the cache leaves the model holds once a slot (a
        recurrent state, a convolution's tail); empty for a model whose
        whole cache is paged by token. Abstract: nothing runs."""
        params = self._place_params(params)
        fp = self._params_fingerprint(params)
        if fp not in self._state_leaf_names:
            self._state_leaf_names[fp] = slot_state_names(cache_layout(
                self.model, _decode_cache_aval(self.model, params)))
        return self._state_leaf_names[fp]

    def make_slot_state(self, params, max_slots: int):
        """Zeroed per-slot state beside the block pool: every `slot` and
        `ring` leaf of the model's decode cache as `[max_slots, *row
        shape]`, None for the paged and index leaves."""
        if self.mesh is not None:
            raise ValueError(
                "per-slot state is not placed on a tensor-parallel mesh "
                f"yet: {', '.join(self.slot_state_leaves(params))} would "
                "need a sharding rule of their own"
            )
        params = self._place_params(params)
        row_avals = _decode_cache_aval(self.model, params)
        return jax.tree_util.tree_map(
            lambda aval, lay: (
                jnp.zeros((max_slots,) + aval.shape, aval.dtype)
                if lay.kind in HELD_A_SLOT else None
            ),
            row_avals, cache_layout(self.model, row_avals),
        )

    def cache_bytes_by_kind(self, params, pool, state=None) -> Dict[str, int]:
        """Resident bytes of the pool and the per-slot arrays, by the kind
        the model declares for each leaf (`paged`, `slot`, `ring`)."""
        params = self._place_params(params)
        layout = cache_layout(
            self.model, _decode_cache_aval(self.model, params))
        total: Dict[str, int] = {}
        for tree in (pool, state):
            # Both mirror the layout, None where a leaf lives elsewhere.
            for lay, leaf in zip(
                    jax.tree_util.tree_leaves(layout),
                    jax.tree_util.tree_leaves(tree, is_leaf=_is_none)):
                if leaf is not None:
                    total[lay.kind] = total.get(lay.kind, 0) \
                        + cache_nbytes(leaf)
        return total

    def write_slot_state(self, state, slot: int, row_cache=None):
        """Put a prefilled batch-1 cache's `slot` leaves (its final state)
        into row `slot` of the state arrays, or zeros where nothing was
        prefilled, so that a reused slot never runs on its predecessor's
        state. `state` is donated: use the return."""
        with telemetry.span("decode_engine/state_write", slot=slot,
                            zero=row_cache is None):
            if row_cache is None:
                return self._zero_state_jit(
                    state, jnp.asarray(slot, jnp.int32))
            rows = jax.tree_util.tree_map(
                lambda held, row: None if held is None else row,
                state, row_cache, is_leaf=_is_none,
            )
            return self._write_state_jit(
                state, rows, jnp.asarray(slot, jnp.int32))

    def paged_state_step(
        self,
        params,
        pool,
        state,
        tables,
        lengths,
        emitted,
        rngs,
        tokens,
        rng_rows,
        forced,
        sample_mask,
        block_size: int,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
    ):
        """`paged_step` for a model with per-slot state
        (build_paged_state_step_fn): one call of the model over all slots'
        tokens; the pool, the state and the rng buffer are donated.
        Returns (pool, state, emitted [S], rngs, counts), and the model's
        cache reads after them where it counts them."""
        slots = int(jnp.shape(tokens)[0])
        compiled, args = self._paged_program(
            self._paged_step, "paged_step",
            ("state", slots, tuple(jnp.shape(tables)), block_size,
             float(temperature), top_k, top_p),
            lambda kernel: build_paged_state_step_fn(
                self.model, block_size, temperature, top_k, top_p,
                paged_kernel=kernel),
            params, (pool, state),
            _step_host_args(tables, lengths, emitted, rngs, tokens, rng_rows,
                            forced, sample_mask),
            donate=(1, 2, 6), replicated_outs=3, kernel_for=pool,
        )
        with telemetry.span("decode_engine/paged_step", slots=slots):
            return compiled(*args)

    def _paged_program(self, programs, stat, key, build, params, trees,
                       host, donate, replicated_outs, kernel_for=None):
        """What the host does before the device has anything to do, for
        every paged step alike (span `decode_engine/step_args`): place
        the params, upload the tick's host arrays (`host`: (value, dtype)
        pairs, after the device-resident `trees` in the program's
        arguments; one that is a device array already, as the one-token
        step's fed-back `emitted` and `rngs`, stays where it is), key the
        compile cache by `key` + the params' and the
        trees' fingerprints, and compile on a miss (`build()` makes the
        step function; the trees come back first among its outputs, then
        `replicated_outs` small ones). The one-token steps name their
        pool as `kernel_for`: which `paged_decode_attention` the step is
        compiled with is chosen here, under the same span, joins `key`,
        and is what `build` is called with. Returns (compiled, args)."""
        with telemetry.span("decode_engine/step_args"):
            if kernel_for is not None:
                kernel = self.paged_attention_kernel(kernel_for)
                key += (kernel,)
                build = functools.partial(build, kernel)
            params, fp = self._placed(params)
            # Host arrays go to the program as they are: it uploads them
            # in its own call, which costs a tenth of five `jnp.asarray`s.
            args = (params,) + tuple(trees) + tuple(
                jnp.asarray(value, dtype) if isinstance(value, jax.Array)
                else np.asarray(value, dtype) for value, dtype in host)
            key = key + (fp,) + tuple(
                self._tree_fingerprint(tree) for tree in trees)
            out_shardings = None
            if self.mesh is not None:
                out_shardings = tuple(
                    self._shardings_of(tree) for tree in trees
                ) + (self._rep_sharding,) * replicated_outs
            compiled = self._compiled(
                programs, key, stat,
                lambda: self._jit(
                    build(), args, donate=donate,
                    out_shardings=out_shardings,
                ).lower(*args).compile(),
            )
        return compiled, args

    def paged_attention_kernel(self, pool) -> bool:
        """Which implementation of `paged_decode_attention` the one-token
        step over `pool` is compiled with, from what can be seen here: the
        kernel where the backend and the pool's leaves admit it
        (`paged_kernel_serves`) and the pool lies whole on one device;
        the plain gather elsewhere (under `tp` XLA shards it over KV
        heads, and a Pallas call cannot be partitioned). `/stats` names
        it: `decode_engine.paged_attention` ("model" where the pool's rows
        have no head axis and the model's own attention reads them; a
        model that hands such rows to the same op as one KV head says so
        in its contract, `pool_rows_are_one_kv_head`, and gets the
        choice)."""
        from tf_yarn_tpu.ops.decode_attention import paged_kernel_serves

        # Every tick: decided once a pool layout.
        fp = self._tree_fingerprint(pool)
        how = self._paged_kernels.get(fp)
        if how is None:
            leaves = jax.tree_util.tree_leaves(pool)
            one_head = self.contract.pool_rows_are_one_kv_head
            if any(leaf.ndim < 5 for leaf in leaves) and not one_head:
                # [1, NB, bs, heads, dim] has a head axis; a leaf without
                # one is read by its model's own attention, which this
                # choice does not reach.
                how = "model"
            elif self.tp_degree == 1 and all(
                    paged_kernel_serves(jax.ShapeDtypeStruct(
                        # a row the model reads as one KV head of its width
                        leaf.shape[-3:-1] + (1,) + leaf.shape[-1:]
                        if leaf.ndim < 5 else leaf.shape[-4:], leaf.dtype))
                    for leaf in leaves
                    if leaf.shape[-1] > 1):  # a scale leaf follows its values
                how = "kernel"
            else:
                how = "plain"
            self._paged_kernels[fp] = how
        with self._lock:
            self.stats["paged_attention"] = how
        return how == "kernel"

    def paged_attention_chunk(self, block_size: int) -> int:
        """Tokens at a time the one-token step's attention reads a slot's
        keys: the kernel's chunk (a slot reads its length rounded up to
        it), or the whole table's `max_seq_len` on the plain path and
        under the int8 kernel, whose grid visits every block of the table
        (`/stats` `kv_read_token_steps`)."""
        from tf_yarn_tpu.ops.decode_attention import paged_chunk_tokens

        config = self.model.config
        if self.stats.get("paged_attention") != "kernel" \
                or getattr(config, "kv_cache_dtype", None) == "int8":
            return config.max_seq_len
        return paged_chunk_tokens(
            block_size, self.max_blocks_per_slot(block_size))

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
        layout) pool rows into a contiguous payload pytree for a bulk
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
        emitted,
        rngs,
        tokens,
        rng_rows,
        forced,
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
        table changes never recompile. `emitted` and `rngs` are the step
        before's (device arrays: nothing is uploaded for them), the rest
        the host's; the pool and `rngs` are donated, `emitted` is not
        (its reader comes after this launch). Returns (pool, emitted [S],
        rngs), none of them waited for."""
        slots = int(jnp.shape(tokens)[0])
        compiled, args = self._paged_program(
            self._paged_step, "paged_step",
            (slots, tuple(jnp.shape(tables)), block_size, float(temperature),
             top_k, top_p),
            lambda kernel: build_paged_step_fn(
                self.model, block_size, temperature, top_k, top_p,
                paged_kernel=kernel),
            params, (pool,),
            _step_host_args(tables, lengths, emitted, rngs, tokens, rng_rows,
                            forced, sample_mask),
            donate=(1, 5), replicated_outs=2, kernel_for=pool,
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
        slots, width = (int(dim) for dim in jnp.shape(tokens))
        compiled, args = self._paged_program(
            self._paged_spec_step, "paged_spec_step",
            ("paged_spec", slots, width, tuple(jnp.shape(tables)),
             block_size, decode_attention, float(temperature), top_k, top_p),
            lambda: build_paged_spec_step_fn(
                self.model, block_size, width, temperature, top_k, top_p,
                decode_attention=decode_attention),
            params, (pool,),
            ((tables, jnp.int32), (lengths, jnp.int32), (tokens, jnp.int32),
             (n_known, jnp.int32), (eos_ids, jnp.int32),
             (rngs, jnp.uint32), (active, bool)),
            donate=(1, 7), replicated_outs=3,
        )
        with telemetry.span("decode_engine/paged_spec_step", slots=slots,
                            width=width):
            return compiled(*args)

    def _tree_fingerprint(self, tree) -> int:
        # Every tick, for the pool and the state: the dtype hashes as it
        # is (its name took 8 us a leaf to build).
        leaves = jax.tree_util.tree_leaves(tree)
        return hash(tuple(
            (tuple(leaf.shape), leaf.dtype) for leaf in leaves
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
            "paged_step": self._paged_step,
            "pack": self._pack,
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
