"""DecodeEngine: the cached-compile, on-device-loop serving path.

The engine's contract is strict: whatever bucketing/padding it applies,
outputs must be *identical* to the legacy host-loop `generate_legacy`
(the replay-based prompt bucketing is exact — no masking
approximations), repeated same-bucket calls must hit the compile cache
(exactly one compilation per bucket), and the traced decode loop must
contain zero per-token host syncs.
"""

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tf_yarn_tpu.models import transformer
from tf_yarn_tpu.models.decode_engine import (
    DecodeEngine,
    all_forced,
    build_decode_fn,
    build_paged_step_fn,
    build_prefill_fn,
    cache_layout,
    cache_nbytes,
    clear_engines,
    feed_avals,
    get_engine,
    paged_pool_avals,
)
from tf_yarn_tpu.models.generate import generate, generate_legacy
from tf_yarn_tpu.serving.request import SamplingParams
from tests.fakes import admit_prefill


def _model_and_params(seed=0, **cfg_overrides):
    # f32 compute: strict output equality across bucket-padded shapes
    # must not hinge on bf16 near-ties flipping under a different XLA
    # fusion (shape changes recompile, and low precision can flip a
    # near-tied argmax — documented in generate()).
    defaults = dict(
        scan_layers=False, remat=False, max_seq_len=64, dtype=jnp.float32
    )
    defaults.update(cfg_overrides)
    cfg = transformer.TransformerConfig.tiny(**defaults)
    model = transformer.Transformer(cfg)
    tokens = jnp.zeros((2, 8), jnp.int32)
    params = nn.meta.unbox(model.init(jax.random.PRNGKey(seed), tokens))
    return model, params


def _engine(model, **overrides):
    defaults = dict(batch_buckets=(2, 4), prompt_buckets=(8, 16, 32))
    defaults.update(overrides)
    return DecodeEngine(model, **defaults)


@pytest.mark.parametrize(
    "batch,prompt_len",
    [
        (2, 12),  # bucketed prompt: prefill 8, replay 4
        (2, 8),   # exact bucket hit: no replay
        (3, 12),  # batch padded 3 -> 4
        (1, 5),   # below the grid: exact-shape fallback
    ],
)
def test_bucketed_outputs_match_legacy(batch, prompt_len):
    model, params = _model_and_params()
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(0, 256, (batch, prompt_len)), jnp.int32)
    engine = _engine(model)
    out = engine.generate(params, prompt, 6, temperature=0.0)
    ref = generate_legacy(model, params, prompt, 6, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_sampled_bucketed_output_matches_legacy():
    # The replay region consumes no RNG, so the engine's split chain
    # lines up with the legacy path and sampled draws match exactly
    # (batch on a bucket boundary: padding reshapes categorical noise).
    model, params = _model_and_params()
    rng = np.random.RandomState(1)
    prompt = jnp.asarray(rng.randint(0, 256, (2, 13)), jnp.int32)
    engine = _engine(model)
    kwargs = dict(temperature=1.0, top_k=8, top_p=0.9, seed=7)
    out = engine.generate(params, prompt, 6, **kwargs)
    ref = generate_legacy(model, params, prompt, 6, **kwargs)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_exactly_one_compilation_per_bucket():
    model, params = _model_and_params()
    engine = _engine(model)
    rng = np.random.RandomState(2)

    # Three prompt lengths inside the same [8, 16) bucket interval.
    for prompt_len in (9, 10, 11):
        prompt = jnp.asarray(rng.randint(0, 256, (2, prompt_len)), jnp.int32)
        engine.generate(params, prompt, 4, temperature=0.0)
    assert engine.stats["prefill_compiles"] == 1
    assert engine.stats["decode_compiles"] == 1
    assert engine.stats["prefill_cache_hits"] == 2
    assert engine.stats["decode_cache_hits"] == 2
    assert engine.stats["unbucketed_shapes"] == 0

    # New prompt bucket: one more prefill compile, but the decode-loop
    # program is shared across prompt buckets (the rest buffer has one
    # engine-wide width) — still exactly one decode compilation.
    prompt = jnp.asarray(rng.randint(0, 256, (2, 17)), jnp.int32)
    engine.generate(params, prompt, 4, temperature=0.0)
    assert engine.stats["prefill_compiles"] == 2
    assert engine.stats["decode_compiles"] == 1

    # Repeat of the first bucket: all cache hits, no new compiles.
    prompt = jnp.asarray(rng.randint(0, 256, (2, 10)), jnp.int32)
    engine.generate(params, prompt, 4, temperature=0.0)
    assert engine.stats["prefill_compiles"] == 2
    assert engine.stats["decode_compiles"] == 1


def test_max_new_tokens_bucketed_by_token_bucket():
    model, params = _model_and_params()
    engine = _engine(model, token_bucket=16)
    prompt = jnp.zeros((2, 9), jnp.int32)
    engine.generate(params, prompt, 5, temperature=0.0)
    # 5 and 7 share the 16-wide output buffer; the trip count is a
    # traced scalar, so no recompile.
    engine.generate(params, prompt, 7, temperature=0.0)
    assert engine.stats["decode_compiles"] == 1
    # 20 crosses the buffer bucket: one new program.
    engine.generate(params, prompt, 20, temperature=0.0)
    assert engine.stats["decode_compiles"] == 2


def test_on_device_eos_early_exit_matches_host_loop():
    model, params = _model_and_params()
    prompt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    greedy = generate_legacy(model, params, prompt, 8, temperature=0.0)
    eos = int(greedy[0, 2])  # row 0 finishes immediately, row 1 later
    engine = _engine(model)
    out = engine.generate(params, prompt, 8, temperature=0.0, eos_token=eos)
    ref = generate_legacy(
        model, params, prompt, 8, temperature=0.0, eos_token=eos
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # Early-exit fill: everything after row 0's first eos repeats eos.
    assert set(np.asarray(out[0, 2:]).tolist()) == {eos}


def test_int8_kv_cache_through_engine_matches_legacy():
    model, params = _model_and_params(kv_cache_dtype="int8")
    rng = np.random.RandomState(3)
    prompt = jnp.asarray(rng.randint(0, 256, (2, 12)), jnp.int32)
    engine = _engine(model)
    out = engine.generate(params, prompt, 6, temperature=0.0)
    ref = generate_legacy(model, params, prompt, 6, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_decode_loop_traces_with_zero_host_syncs():
    """The acceptance check, by jaxpr inspection: the whole decode is a
    single `while_loop` program containing no host-callback or
    device-transfer primitive — nothing to round-trip per token."""
    from tf_yarn_tpu.analysis.jaxpr_engine import (
        _HOST_CALLBACK_PRIMITIVES,
        _walk_jaxpr,
    )

    model, params = _model_and_params()
    prefill = build_prefill_fn(model)
    prompt_aval = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    cache, _logits = jax.eval_shape(prefill, params, prompt_aval)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    rng_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
    out_aval = jax.ShapeDtypeStruct((2, 16), jnp.int32)

    for has_rest in (True, False):
        fn = build_decode_fn(
            model, temperature=0.0, top_k=None, top_p=None,
            has_eos=True, has_rest=has_rest,
        )
        if has_rest:
            args = (params, cache, jax.ShapeDtypeStruct((2, 8), jnp.int32),
                    scalar, scalar, rng_aval, scalar, out_aval)
        else:
            args = (params, cache, jax.ShapeDtypeStruct((2, 256), jnp.float32),
                    scalar, rng_aval, scalar, out_aval)
        closed = jax.make_jaxpr(fn)(*args)
        prims = [eqn.primitive.name for eqn in _walk_jaxpr(closed.jaxpr)]
        assert "while" in prims
        assert not set(prims) & _HOST_CALLBACK_PRIMITIVES, sorted(
            set(prims) & _HOST_CALLBACK_PRIMITIVES
        )


def test_decode_runs_in_one_device_execution():
    """Runtime twin of the jaxpr check: generating N tokens executes
    exactly two compiled programs (prefill + decode loop), not N."""
    model, params = _model_and_params()
    engine = _engine(model)
    prompt = jnp.zeros((2, 10), jnp.int32)
    engine.generate(params, prompt, 8, temperature=0.0)  # compile
    before = dict(engine.stats)
    engine.generate(params, prompt, 8, temperature=0.0)
    assert engine.stats["prefill_compiles"] == before["prefill_compiles"]
    assert engine.stats["decode_compiles"] == before["decode_compiles"]
    assert engine.stats["prefill_cache_hits"] == before["prefill_cache_hits"] + 1
    assert engine.stats["decode_cache_hits"] == before["decode_cache_hits"] + 1


def test_generate_wrapper_routes_through_shared_engine():
    clear_engines()
    model, params = _model_and_params()
    prompt = jnp.zeros((2, 9), jnp.int32)
    out = generate(model, params, prompt, 4, temperature=0.0)
    assert out.shape == (2, 13)
    generate(model, params, prompt, 4, temperature=0.0)
    stats = get_engine(model).stats
    assert stats["calls"] == 2
    assert stats["decode_compiles"] == 1
    # An equal model (same config) shares the engine — the wrapper's
    # whole point: every caller gets the cached-compile path.
    model_again = transformer.Transformer(model.config)
    assert get_engine(model_again) is get_engine(model)
    clear_engines()


def test_oversized_batch_chunks_through_largest_bucket():
    """Regression: a batch beyond the largest bucket used to silently
    compile a one-off unbucketed program. Now it chunks through the
    largest bucket: outputs stay identical to the legacy path (greedy
    rows are independent) and NO unbucketed compile happens — every
    compiled shape is a bucket."""
    model, params = _model_and_params()
    engine = _engine(model)  # batch buckets (2, 4): largest is 4
    rng = np.random.RandomState(4)
    prompt = jnp.asarray(rng.randint(0, 256, (10, 10)), jnp.int32)
    out = engine.generate(params, prompt, 5, temperature=0.0)
    ref = generate_legacy(model, params, prompt, 5, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert engine.stats["unbucketed_shapes"] == 0
    assert engine.stats["oversize_batch_chunks"] == 1
    # 10 rows -> chunks of 4, 4, 2: exactly the b=4 and b=2 bucket
    # programs, and the repeated b=4 chunk hits the cache.
    assert engine.stats["prefill_compiles"] == 2
    assert engine.stats["prefill_cache_hits"] == 1


def test_pack_prefill_touches_only_its_own_blocks():
    """A prefill packed into one slot's blocks writes exactly those
    blocks — and again after they are freed and handed out anew, in
    another order, to another prompt: every other block of the pool,
    another slot's rows among them, is bit-unchanged."""
    model, params = _model_and_params()
    engine = _engine(model, batch_buckets=(1, 2, 4),
                     prompt_buckets=(4, 8, 16))
    bs, num_blocks = 8, 9
    row_aval = jax.eval_shape(
        build_prefill_fn(model), params,
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
    )[0]
    layout = cache_layout(model, row_aval)
    # A pool full of other slots' rows: nothing in it is zero by luck.
    rng_np = np.random.RandomState(9)
    pool = jax.tree_util.tree_map(
        lambda leaf: None if leaf is None else jnp.asarray(
            rng_np.standard_normal(leaf.shape), leaf.dtype),
        engine.make_paged_pool(params, num_blocks, bs),
        is_leaf=lambda x: x is None,
    )

    axis = {  # leaf -> its sequence axis, for the paged leaves
        jax.tree_util.keystr(path): lay.axis
        for path, lay in jax.tree_util.tree_leaves_with_path(layout)
        if lay.kind == "paged"
    }

    def blocks(tree):
        """{leaf: host array, block axis first} of a pool."""
        return {
            jax.tree_util.keystr(path): np.moveaxis(
                np.asarray(leaf), axis[jax.tree_util.keystr(path)], 0)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
        }

    def row_blocks(row_cache, n):
        """The first `n` blocks of a prefilled batch-1 cache, cut the
        same way."""
        out = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(row_cache):
            name = jax.tree_util.keystr(path)
            if name not in axis:
                continue
            ax, leaf = axis[name], np.asarray(leaf)
            cut = leaf.reshape(
                leaf.shape[:ax] + (-1, bs) + leaf.shape[ax + 1:])
            out[name] = np.moveaxis(cut, ax, 0)[:n]
        return out

    for seed, ids in ((1, [3, 5]), (2, [5, 3])):  # freed, then reused
        before = blocks(pool)
        prompt = jnp.asarray(
            np.random.RandomState(seed).randint(0, 256, (1, 16)), jnp.int32)
        row, _logits = engine.prefill(params, prompt)
        want = row_blocks(row, len(ids))
        pool = engine.pack_prefill(
            pool, np.asarray(ids, np.int32), row, 16, bs)
        after = blocks(pool)
        assert set(after) == set(before) == set(want) and len(after) >= 2
        others = [b for b in range(num_blocks) if b not in ids]
        for name, leaf in after.items():
            np.testing.assert_array_equal(
                leaf[others], before[name][others], err_msg=name)
            np.testing.assert_array_equal(
                leaf[ids], want[name], err_msg=name)
            assert not np.array_equal(leaf[ids], before[name][ids])
    assert engine.stats["pack_compiles"] == 1  # one bucket, ids traced


@pytest.mark.parametrize("bucket", [8, 32])
def test_pack_program_is_one_scatter_a_leaf_whatever_the_bucket(bucket):
    """The pack program does not grow with its bucket: one scatter of whole
    blocks a paged leaf (an update a block, unrolled, took 33 s to trace
    and compile at 2048 rows of 16 leaves, behind the first long prompt)."""
    from tf_yarn_tpu.models.decode_engine import build_pack_prefill_fn

    model, params = _model_and_params()
    engine = _engine(model, batch_buckets=(1,))
    bs = 4
    pool = engine.make_paged_pool(params, 9, bs)
    row = jax.eval_shape(
        build_prefill_fn(model), params,
        jax.ShapeDtypeStruct((1, bucket), jnp.int32))[0]
    jaxpr = jax.make_jaxpr(build_pack_prefill_fn(model, bs, bucket))(
        pool, jnp.zeros((bucket // bs,), jnp.int32), row)
    names = [eqn.primitive.name for eqn in jaxpr.jaxpr.eqns]
    assert names.count("scatter") == len(jax.tree_util.tree_leaves(pool))
    assert "dynamic_update_slice" not in names
    assert len(names) < 12 * names.count("scatter")


def _drive_paged_slots(model, engine, params, prompts, seeds, max_new,
                       sampling, block_size, fed=False, pad=0):
    """Drive make_paged_pool/pack_prefill/paged_step by hand (the
    scheduler's device contract: the prefill bucket and the rows kept of
    it by the rule the engine reads off the model, the prompt padded to
    the bucket with `pad`) and return each slot's emitted stream.
    Physical blocks are handed out in an interleaved order on purpose —
    correctness must come from the block TABLE, not from contiguity.
    `fed`: a decoding slot's token and rng row stay on the device from
    one step to the next, as the scheduler leaves them, and the host
    forces only what it alone knows (replayed prompt tokens and the rows
    the slots started with); otherwise every step takes all of both from
    the host."""
    slots = len(prompts)
    max_blocks = engine.max_blocks_per_slot(block_size)
    num_blocks = 1 + slots * max_blocks
    pool = engine.make_paged_pool(params, num_blocks, block_size)
    # Interleaved physical ids: slot 0 gets 1, 1+slots, 1+2*slots, ...
    tables = np.zeros((slots, max_blocks), np.int32)
    for s in range(slots):
        tables[s] = 1 + s + slots * np.arange(max_blocks)
    lengths = np.zeros((slots,), np.int32)
    rngs = np.zeros((slots, 2), np.uint32)
    pending, last, emitted_all = [], np.zeros((slots,), np.int32), []
    ceiling = engine.ceiling_prefill(params)
    for slot, (prompt, seed) in enumerate(zip(prompts, seeds)):
        pool, _row, _bucket, prefill_len = admit_prefill(
            engine, params, pool, prompt, tables[slot], block_size,
            ceiling, pad)
        lengths[slot] = prefill_len
        pending.append(list(prompt[prefill_len:]))
        rngs[slot] = np.asarray(jax.random.PRNGKey(seed))
        emitted_all.append([])

    device = (np.zeros((slots,), np.int32), np.zeros((slots, 2), np.uint32))
    for _ in range(max_new + max(len(p) for p in pending)):
        tokens = np.zeros((slots,), np.int32)
        mask = np.zeros((slots,), bool)
        forced = np.ones((slots,), bool)
        step_lengths = np.array(lengths)
        for slot in range(slots):
            if len(emitted_all[slot]) >= max_new:
                step_lengths[slot] = 0  # finished slot rides along inactive
                continue
            if pending[slot]:
                tokens[slot] = pending[slot][0]
                mask[slot] = len(pending[slot]) == 1
            else:
                tokens[slot] = last[slot]
                mask[slot] = True
                forced[slot] = False
        if not mask.any():
            break
        finished = [len(e) >= max_new for e in emitted_all]
        step_tables = np.array(tables)
        step_tables[finished] = 0  # inactive rows write the trash block
        feed = (*device, tokens, rngs, forced) if fed \
            else all_forced(tokens, rngs)
        pool, *device = engine.paged_step(
            params, pool, step_tables, step_lengths, *feed, mask,
            block_size=block_size, **sampling,
        )
        emitted = np.asarray(device[0])
        if not fed:
            rngs = np.array(device[1])
        for slot in range(slots):
            if finished[slot]:
                continue
            lengths[slot] += 1
            if pending[slot]:
                sampled = len(pending[slot]) == 1
                pending[slot].pop(0)
                if not sampled:
                    continue
            emitted_all[slot].append(int(emitted[slot]))
            last[slot] = emitted[slot]
    return emitted_all


@pytest.mark.parametrize("fed", [False, True], ids=["host", "fed_back"])
def test_paged_step_grid_matches_legacy_per_request(fed):
    """The paged serving contract: slots at different prompt lengths and
    seeds, block tables pointing at interleaved physical blocks, prompts
    split across prefill-pack + replay — every per-request stream is
    BIT-IDENTICAL to generate_legacy, including sampled RNG chains,
    whether a slot's token and rng row pass through the host between
    steps or stay on the device."""
    model, params = _model_and_params()
    engine = _engine(model, batch_buckets=(1, 2, 4),
                     prompt_buckets=(4, 8, 16))
    rng_np = np.random.RandomState(6)
    prompts = [
        jnp.asarray(rng_np.randint(0, 256, (5,)), jnp.int32),  # prefill 4
        jnp.asarray(rng_np.randint(0, 256, (9,)), jnp.int32),  # prefill 8
        jnp.asarray(rng_np.randint(0, 256, (3,)), jnp.int32),  # 2 of 4 kept
    ]
    seeds = [0, 7, 3]
    max_new = 6
    sampling = dict(temperature=1.0, top_k=8, top_p=0.9)
    # block_size 8 with prefill 4: pack_prefill covers the partial-block
    # path too.
    emitted_all = _drive_paged_slots(
        model, engine, params, prompts, seeds, max_new, sampling,
        block_size=8, fed=fed,
    )
    for slot, (prompt, seed) in enumerate(zip(prompts, seeds)):
        ref = generate_legacy(
            model, params, prompt[None], max_new, seed=seed, **sampling
        )
        assert emitted_all[slot] == np.asarray(
            ref
        )[0, len(prompt):].tolist(), f"slot {slot}"
    # One grid configuration = ONE compiled paged step program, reused
    # every tick.
    assert engine.stats["paged_step_compiles"] == 1
    assert engine.stats["paged_step_cache_hits"] >= max_new - 1
    # Two prefill buckets -> two pack programs (4-token partial block,
    # 8-token full block), each compiled once.
    assert engine.stats["pack_compiles"] == 2


def test_paged_step_int8_matches_int8_legacy():
    """The pool stores whatever leaves the model's cache has — int8
    values and scales page identically, and the stream stays bit-equal
    to the int8 legacy path (the paging machinery adds no error of its
    own; int8-vs-fp accuracy is test_int8_prefill_logits_close_to_fp)."""
    model, params = _model_and_params(kv_cache_dtype="int8")
    engine = _engine(model, batch_buckets=(1, 2, 4),
                     prompt_buckets=(4, 8, 16))
    rng_np = np.random.RandomState(7)
    prompts = [jnp.asarray(rng_np.randint(0, 256, (9,)), jnp.int32),
               jnp.asarray(rng_np.randint(0, 256, (5,)), jnp.int32)]
    emitted_all = _drive_paged_slots(
        model, engine, params, prompts, [0, 1], 5,
        dict(temperature=0.0), block_size=8,
    )
    for slot, prompt in enumerate(prompts):
        ref = generate_legacy(model, params, prompt[None], 5,
                              temperature=0.0)
        assert emitted_all[slot] == np.asarray(
            ref
        )[0, len(prompt):].tolist(), f"slot {slot}"


@pytest.mark.parametrize("prompt_len,ceiling,want", [
    (1, True, (0, 0)),      # the one token goes through the step
    (2, True, (8, 1)),      # under the least bucket: padded up to it
    (8, True, (8, 7)),      # one under a bucket
    (9, True, (8, 8)),      # b + 1 lands on b under both rules
    (9, False, (8, 8)),
    (10, True, (16, 9)),    # one over
    (10, False, (8, 8)),
    (33, True, (32, 32)),
    (34, True, (32, 32)),   # the bucket above (128) is past the context
    (60, True, (32, 32)),
    (8, False, (0, 0)),     # the floor rule finds nothing under 7
])
def test_slot_prefill_len_takes_the_bucket_above_and_keeps_the_prompt(
        prompt_len, ceiling, want):
    model, _params = _model_and_params()  # context 64
    engine = _engine(model, prompt_buckets=(8, 16, 32, 128))
    assert engine.slot_prefill_len(prompt_len, ceiling) == want
    if not ceiling:
        assert engine.slot_prefill_len(prompt_len) == want  # the default


def test_only_a_model_whose_prompt_rows_are_causal_takes_the_ceiling_rule():
    """The engine reads the rule off the model's contract: a dense causal
    transformer says its prompt rows do not depend on later tokens; one
    with the capacity-bounded `MoEMlp` (capacity is counted over the tokens
    of the call, pads included) says they do; a model that says nothing is
    refused by the name of what it lacks, not served by the floor rule."""
    model, params = _model_and_params()
    assert _engine(model).ceiling_prefill(params) is True
    moe, moe_params = _model_and_params(moe_experts=4)
    assert moe.serving_contract().rows_causal is False
    assert _engine(moe).ceiling_prefill(moe_params) is False

    class Silent:
        config = model.config

    with pytest.raises(ValueError, match=r"Silent.*serving_contract\(\)"):
        _engine(Silent())


def _declares(base, **fields):
    """`base` with its contract's `fields` replaced."""

    class Declares(base):
        def serving_contract(self):
            return dataclasses.replace(
                base.serving_contract(self), **fields)

    return Declares


def _ring_model(kind):
    """Tiny models that hold leaves once a slot, and what they declare."""
    from tf_yarn_tpu.models import hybrid, laguna, latent

    Silent = _declares(laguna.LagunaLM, rows_causal=False)
    Untold = _declares(laguna.LagunaLM, takes_prompt_len=False)
    NotCausalHybrid = _declares(hybrid.HybridLM, rows_causal=False)

    class RingAsState(laguna.LagunaLM):
        def serving_contract(self):
            contract = super().serving_contract()
            return dataclasses.replace(contract, leaf_kinds={
                **contract.leaf_kinds, "window_key": ("slot", None)})

    grouped = laguna.LagunaConfig.tiny(dtype=jnp.float32)
    mixed = hybrid.HybridConfig.tiny(dtype=jnp.float32)
    return {
        "rings of keys and values": lambda: laguna.LagunaLM(grouped),
        "a ring of latents": lambda: latent.LatentLM(
            latent.LatentConfig.tiny(dtype=jnp.float32)),
        "rings, and says its rows are not causal": lambda: Silent(grouped),
        "a ring declared as state": lambda: RingAsState(grouped),
        "rings, and a prefill not told its length": lambda: Untold(grouped),
        "a state and a tail": lambda: hybrid.HybridLM(mixed),
        "a state and a tail, rows not causal":
            lambda: NotCausalHybrid(mixed),
    }[kind]()


@pytest.mark.parametrize("kind,held,want", [
    ("rings of keys and values", ("window_key", "window_value"), True),
    ("a ring of latents", ("window_latent",), True),
    ("rings, and says its rows are not causal",
     ("window_key", "window_value"), False),
    ("a ring declared as state", ("window_key", "window_value"), False),
    ("rings, and a prefill not told its length",
     ("window_key", "window_value"), False),
    ("a state and a tail", ("conv_state", "ssm_state"), False),
    ("a state and a tail, rows not causal", ("conv_state", "ssm_state"),
     False),
])
def test_a_model_that_holds_leaves_takes_the_ceiling_by_their_kind(
        kind, held, want):
    """The ceiling rule for a model that holds leaves once a slot: its
    contract says its prompt rows are causal, every such leaf is a `ring`,
    and its prefill is told the prompt's length so that the ring is written
    where the prompt ends. A `slot` leaf (a state, a convolution's tail)
    keeps the floor whatever the model says; nothing is read off a name."""
    model = _ring_model(kind)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    engine = DecodeEngine(model, prompt_buckets=(8, 16, 32))
    assert engine.slot_state_leaves(params) == held
    assert engine.ceiling_prefill(params) is want
    # the scheduler takes the engine's word
    from tf_yarn_tpu.serving.scheduler import SlotScheduler

    scheduler = SlotScheduler(engine, params, max_slots=2, block_size=8)
    response = scheduler.submit(list(range(1, 12)), SamplingParams(
        max_new_tokens=2))
    for _ in range(50):
        if response.done:
            break
        scheduler.tick()
    stats = scheduler.stats()
    scheduler.close()
    # 11 tokens: 10 kept of 16 and one replayed, or 8 whole and 3 replayed
    assert (stats["prefills_ceiling"], stats["prefills_floor"],
            stats["prefill_pad_tokens"], stats["prefill_tokens"]) == (
        (1, 0, 6, 1) if want else (0, 1, 0, 3))


_CEILING_BLOCK = 4


@functools.lru_cache(maxsize=None)
def _ceiling_rig():
    """One model, engine and logits step for the ceiling-rule tests below:
    blocks of 4 under buckets of 4, 8 and 16, so that a padded bucket has
    blocks past the kept rows."""
    model, params = _model_and_params()
    engine = _engine(model, batch_buckets=(1, 2), prompt_buckets=(4, 8, 16))
    step = jax.jit(build_paged_step_fn(
        model, _CEILING_BLOCK, 0.0, None, None, with_logits=True))
    return model, params, engine, step


# At, one under and one over the buckets 8 and 16 (the rows kept are the
# prompt's less one), and one under the least.
@pytest.mark.parametrize("prompt_len", [3, 8, 9, 10, 16, 17])
def test_the_pad_of_a_ceiling_prefill_is_invisible(prompt_len):
    """Two admissions of one prompt through the bucket above it, padded
    with different tokens, leave the kept rows bitwise equal (one program;
    no kept row sees the pad under the causal mask) and close to an
    exact-length prefill's; the blocks past the kept rows aim at the trash
    block, so no block but the slot's own and block 0 changes; and the
    step that takes the prompt's last token gives the logits of one full
    forward: the first generated token's."""
    model, params, engine, step = _ceiling_rig()
    bs, num_blocks = _CEILING_BLOCK, 12
    prompt = np.random.RandomState(prompt_len).randint(0, 256, (prompt_len,))
    owned = [7, 3, 9, 5, 2]
    bucket, kept = engine.slot_prefill_len(prompt_len, True)
    assert kept == prompt_len - 1 and bucket == min(
        b for b in (4, 8, 16) if b >= kept)
    n_owned = -(-kept // bs)

    def fresh_pool():
        # Other slots' rows everywhere: nothing is zero by luck.
        rng_np = np.random.RandomState(9)
        return jax.tree_util.tree_map(
            lambda leaf: None if leaf is None else jnp.asarray(
                rng_np.standard_normal(leaf.shape), leaf.dtype),
            engine.make_paged_pool(params, num_blocks, bs),
            is_leaf=lambda x: x is None)

    def by_block(pool):
        # [1, num_blocks, bs, kv_heads, head_dim] -> block axis first
        return [np.moveaxis(np.asarray(leaf), -4, 0)
                for leaf in jax.tree_util.tree_leaves(pool)]

    before = by_block(fresh_pool())
    pools = []
    for pad in (0, 255):
        pool, _row, got_bucket, got_kept = admit_prefill(
            engine, params, fresh_pool(), prompt, owned, bs, True, pad)
        assert (got_bucket, got_kept) == (bucket, kept)
        pools.append(pool)
    exact = engine.prefill(params, prompt[None, :kept])[0]
    untouched = [b for b in range(1, num_blocks) if b not in owned[:n_owned]]
    for first, second, was, row in zip(
            by_block(pools[0]), by_block(pools[1]), before,
            (leaf for leaf in jax.tree_util.tree_leaves(exact)
             if leaf.ndim > 1)):
        np.testing.assert_array_equal(first[untouched], was[untouched])
        np.testing.assert_array_equal(second[untouched], was[untouched])
        rows = [np.concatenate([tree[b] for b in owned[:n_owned]], axis=-3)
                for tree in (first, second)]
        np.testing.assert_array_equal(
            rows[0][..., :kept, :, :], rows[1][..., :kept, :, :])
        want = np.asarray(row)[..., :kept, :, :]
        np.testing.assert_allclose(
            rows[0][..., :kept, :, :], want, atol=1e-5 * np.abs(want).max())
    # The prompt's last token through the step, over the padded admission.
    tables = np.zeros((1, engine.max_blocks_per_slot(bs)), np.int32)
    tables[0, :len(owned)] = owned
    tokens = np.asarray([prompt[-1]], np.int32)
    *_rest, logits = step(
        params, pools[1], jnp.asarray(tables), jnp.asarray([kept], jnp.int32),
        *all_forced(tokens, np.zeros((1, 2), np.uint32)),
        jnp.ones((1,), bool))
    want = np.asarray(model.apply(params, jnp.asarray(prompt[None])))[0, -1]
    np.testing.assert_allclose(
        np.asarray(logits)[0], want, atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("prompt_len", [3, 8, 9, 10, 16, 17, 18])
def test_ceiling_prefill_streams_match_legacy(prompt_len):
    """A slot admitted through the bucket above its prompt (18: no bucket
    above, the floor and two replayed tokens), beside one at another
    length, emits generate_legacy's stream, sampled chain included,
    whatever the pad holds."""
    model, params, engine, _step = _ceiling_rig()
    rng_np = np.random.RandomState(100 + prompt_len)
    prompts = [jnp.asarray(rng_np.randint(0, 256, (n,)), jnp.int32)
               for n in (prompt_len, 6)]
    sampling = dict(temperature=1.0, top_k=8, top_p=0.9)
    emitted_all = _drive_paged_slots(
        model, engine, params, prompts, [4, 2], 5, sampling,
        block_size=_CEILING_BLOCK, fed=True, pad=255)
    for slot, (prompt, seed) in enumerate(zip(prompts, [4, 2])):
        ref = generate_legacy(
            model, params, prompt[None], 5, seed=seed, **sampling)
        assert emitted_all[slot] == np.asarray(
            ref)[0, len(prompt):].tolist(), f"slot {slot}"


def test_int8_prefill_logits_close_to_fp():
    """Parity tolerance for the int8 KV path against fp: same prompt,
    same weights, prefill logits within quantization noise."""
    model_fp, params = _model_and_params()
    model_int8, _ = _model_and_params(kv_cache_dtype="int8")
    prompt = jnp.asarray(
        np.random.RandomState(8).randint(0, 256, (1, 12)), jnp.int32
    )
    engine_fp = _engine(model_fp)
    engine_int8 = _engine(model_int8)
    _row, logits_fp = engine_fp.prefill(params, prompt)
    _row, logits_int8 = engine_int8.prefill(params, prompt)
    diff = np.abs(np.asarray(logits_fp) - np.asarray(logits_int8)).max()
    scale = np.abs(np.asarray(logits_fp)).max()
    assert diff <= 0.05 * scale + 1e-3, (
        f"int8 prefill logits diverge from fp: max diff {diff} vs "
        f"logit scale {scale}"
    )


def test_paged_pool_layout_and_hbm_accounting():
    """Pool leaves replace the seq axis with (num_blocks, block_size);
    index leaves are elided; a pool sized below every slot at full
    context is proportionally smaller in bytes — the layout's entire
    point."""
    model, params = _model_and_params()
    engine = _engine(model)
    max_seq = model.config.max_seq_len  # 64
    slots, bs = 4, 8
    row = jax.eval_shape(
        build_prefill_fn(model), params,
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
    )[0]
    full_context_bytes = slots * cache_nbytes(row)
    full = engine.make_paged_pool(params, slots * (max_seq // bs) + 1, bs)
    half = engine.make_paged_pool(params, slots * (max_seq // bs) // 2, bs)
    leaves = [l for l in jax.tree_util.tree_leaves(full)]
    assert leaves, "pool has no KV leaves"
    for leaf in leaves:
        assert bs in leaf.shape
    # cache_index leaves are gone from the pool (positions travel as the
    # step's traced lengths instead).
    assert len(leaves) < len(jax.tree_util.tree_leaves(row))
    half_bytes = cache_nbytes(half)
    full_bytes = cache_nbytes(full)
    assert half_bytes < full_bytes
    # Same token capacity costs the same KV bytes (+1 trash block);
    # fewer blocks = proportionally less resident HBM than a full
    # context a slot.
    assert half_bytes < full_context_bytes
    # aval helper agrees with the concrete pool
    avals = paged_pool_avals(model, row, slots * (max_seq // bs) + 1, bs)
    concrete = jax.tree_util.tree_leaves(full)
    abstract = [a for a in jax.tree_util.tree_leaves(avals)]
    assert [l.shape for l in concrete] == [a.shape for a in abstract]


def test_paged_step_traces_with_zero_host_syncs():
    """Jaxpr twin for the paged serving step: gather, model step, and
    scatter-append in ONE program with no host-callback or transfer
    primitive — the zero-host-syncs-per-tick acceptance bar."""
    from tf_yarn_tpu.analysis.jaxpr_engine import (
        _HOST_CALLBACK_PRIMITIVES,
        _walk_jaxpr,
    )

    model, params = _model_and_params()
    row = jax.eval_shape(
        build_prefill_fn(model), params,
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
    )[0]
    bs = 8
    pool = paged_pool_avals(model, row, 9, bs)
    slots, mb = 2, model.config.max_seq_len // bs
    fn = build_paged_step_fn(model, bs, temperature=1.0, top_k=4, top_p=0.9)
    closed = jax.make_jaxpr(fn)(
        params, pool,
        jax.ShapeDtypeStruct((slots, mb), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32),
        *feed_avals(slots),
        jax.ShapeDtypeStruct((slots,), jnp.bool_),
    )
    prims = {eqn.primitive.name for eqn in _walk_jaxpr(closed.jaxpr)}
    assert not prims & _HOST_CALLBACK_PRIMITIVES, sorted(
        prims & _HOST_CALLBACK_PRIMITIVES
    )
    # The table indirection is real: the program gathers and scatters.
    assert "gather" in prims
    assert "scatter" in prims


def test_paged_pool_validates():
    model, params = _model_and_params()
    engine = _engine(model)
    with pytest.raises(ValueError, match="divide"):
        engine.make_paged_pool(params, 9, 7)  # 64 % 7 != 0
    with pytest.raises(ValueError, match="num_blocks"):
        engine.make_paged_pool(params, 1, 8)
    with pytest.raises(ValueError, match="divide"):
        engine.max_blocks_per_slot(7)
    assert engine.max_blocks_per_slot(8) == 8


def test_engine_validates_like_generate():
    model, params = _model_and_params()
    engine = _engine(model)
    with pytest.raises(ValueError, match="max_seq_len"):
        engine.generate(params, jnp.zeros((1, 60), jnp.int32), 10)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    out = engine.generate(params, prompt, 0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(prompt))


def test_placed_tree_is_remembered_by_its_leaves_not_by_its_shapes():
    """A server hands the same placed tree to every step: the engine
    walks it once. A hit needs the very leaves (held weakly), so a
    tree of new arrays of the same shapes, or one of host arrays that
    placing had to upload, is placed and fingerprinted again."""
    model, params = _model_and_params()
    engine = _engine(model)
    placed, fp = engine._placed(params)
    assert fp == engine._params_fingerprint(params)
    walks = []
    engine._place_params = lambda tree, inner=engine._place_params: (
        walks.append(1), inner(tree))[1]
    again, fp_again = engine._placed(params)
    assert again is params and fp_again == fp and not walks
    # The same leaves under a new dict: still a hit, the caller's tree.
    shallow = jax.tree_util.tree_map(lambda leaf: leaf, params)
    assert engine._placed(shallow)[0] is shallow and not walks
    # New arrays of the same shapes and values: placed again.
    twin = jax.tree_util.tree_map(lambda leaf: leaf + 0, params)
    assert engine._placed(twin)[1] == fp and len(walks) == 1
    assert engine._placed(twin)[0] is twin and len(walks) == 1
    # Host arrays are uploaded by every placing, so never remembered.
    host = jax.tree_util.tree_map(np.asarray, params)
    for count in (2, 3):
        out, _ = engine._placed(host)
        assert len(walks) == count
        assert all(isinstance(leaf, jax.Array)
                   for leaf in jax.tree_util.tree_leaves(out))
    # And the remembered tree's arrays are not kept alive by it.
    del twin
    assert engine._placed_seen is None or any(
        ref() is None for ref in engine._placed_seen[1])


def test_paged_attention_kernel_is_chosen_from_backend_shape_and_sharding(
        monkeypatch):
    """No setting picks the paged step's read: on this CPU it is the plain
    gather; where the backend is a TPU (steered here, in the test) the
    kernel serves a pool it can tile and stands aside for one it cannot,
    and for any pool that is sharded over `tp`. `/stats` names the choice
    and `paged_attention_chunk` follows it."""
    from tf_yarn_tpu.ops import _rowwise

    engine = _engine(transformer.Transformer(
        transformer.TransformerConfig.tiny(max_seq_len=4096)))
    leaf = lambda dim: {"k": jax.ShapeDtypeStruct(
        (1, 65, 16, 8, dim), jnp.bfloat16)}
    assert engine.paged_attention_kernel(leaf(128)) is False
    assert engine.stats["paged_attention"] == "plain"
    assert engine.paged_attention_chunk(16) == 4096

    monkeypatch.setattr(_rowwise, "default_interpret", lambda: False)
    on_tpu = _engine(transformer.Transformer(
        transformer.TransformerConfig.tiny(max_seq_len=4096)))
    assert on_tpu.paged_attention_kernel(leaf(64)) is False
    assert on_tpu.paged_attention_kernel(leaf(128)) is True
    assert on_tpu.stats["paged_attention"] == "kernel"
    assert on_tpu.paged_attention_chunk(16) == 128  # 8 pages of 16 tokens
    on_tpu.tp_degree = 2  # what DecodeEngine(mesh=...) reads off its mesh
    on_tpu._paged_kernels.clear()
    assert on_tpu.paged_attention_kernel(leaf(128)) is False
    assert on_tpu.stats["paged_attention"] == "plain"
    assert on_tpu.paged_attention_chunk(16) == 4096
