"""Chunked prefill: admission never stalls the decode tick
(docs/Serving.md "Chunked prefill").

Three layers, matching the serving test house style:

* **Knob validation** — scheduler + ServingExperiment reject bad
  ``prefill_chunk``/``prefill_budget_per_tick`` combinations with
  errors naming the knob; "auto" resolves from the engine's prompt
  buckets; ``context_limit`` reserves the window headroom.
* **Fake engines** — deterministic windowed fakes (the sum%97
  arithmetic of test_serving/test_spec) pin the tick-level contracts:
  chunked admission runs NO prefill program, chunked streams equal the
  blocking path's exactly, decode slots emit EVERY tick while a
  2000-token prompt admits (the no-stall contract), the budget pauses
  chunking slots round-robin, a mid-PREFILL eviction releases blocks
  exactly once, and the paged path registers prefix blocks
  incrementally as chunks complete.
* **Real engine on CPU** — the acceptance bars: chunked greedy AND
  sampled streams are BIT-IDENTICAL to ``generate_legacy`` (tier-1
  representative), with the int8 / prefix-hit / spec compositions and
  the long-prompt e2e beside it.
"""

import time

import numpy as np
import pytest

from tests.fakes import FakePagedWindowedEngine, fake_scheduler
from tf_yarn_tpu import telemetry
from tf_yarn_tpu.serving import SamplingParams, SlotScheduler
from tf_yarn_tpu.serving.request import FINISH_DEADLINE


def _drive(scheduler, responses, max_ticks=3000):
    for used in range(1, max_ticks + 1):
        scheduler.tick()
        if all(r.done for r in responses):
            return used
    raise AssertionError(f"not drained after {max_ticks} ticks")


def _run_streams(scheduler, workload):
    """Submit (prompt, params) pairs, drive to completion, return the
    per-request token streams."""
    responses = [scheduler.submit(p, params) for p, params in workload]
    _drive(scheduler, responses)
    return [r.result(timeout=1) for r in responses]


# --------------------------------------------------------------------------
# knob validation + auto resolution + headroom
# --------------------------------------------------------------------------

def test_scheduler_validates_chunked_knobs():
    engine = FakePagedWindowedEngine()
    with pytest.raises(ValueError, match="prefill_chunk"):
        fake_scheduler(engine, prefill_chunk=-2)
    with pytest.raises(ValueError, match="prefill_budget_per_tick"):
        fake_scheduler(engine, prefill_budget_per_tick=8)
    with pytest.raises(ValueError, match="window width"):
        fake_scheduler(engine, prefill_chunk=8, prefill_budget_per_tick=4)
    # spec widens the window past the chunk; the budget must cover it.
    with pytest.raises(ValueError, match="window width"):
        fake_scheduler(engine, prefill_chunk=2, spec_k=5,
                       prefill_budget_per_tick=3)


def test_prefill_chunk_auto_resolves_from_prompt_buckets():
    scheduler = fake_scheduler(
        FakePagedWindowedEngine(buckets=(4, 8)), prefill_chunk="auto",
    )
    assert scheduler.prefill_chunk == 8

    # No buckets exposed: "auto" falls back to the spec window.
    engine = FakePagedWindowedEngine()
    engine.prompt_buckets = ()
    scheduler = fake_scheduler(engine, prefill_chunk="auto", spec_k=3)
    assert scheduler.prefill_chunk == 4


def test_context_limit_reserves_chunk_window_headroom():
    scheduler = fake_scheduler(
        FakePagedWindowedEngine(max_seq_len=32), max_slots=1,
        prefill_chunk=8,
    )
    assert scheduler.context_limit == 32 - 7
    with pytest.raises(ValueError, match="headroom"):
        scheduler.submit([1] * 20, SamplingParams(max_new_tokens=6))
    scheduler.submit([1] * 20, SamplingParams(max_new_tokens=5))


def test_serving_experiment_chunked_fields_validate():
    from tf_yarn_tpu.experiment import ServingExperiment

    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingExperiment(model=None, model_dir="x", prefill_chunk=-1)
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingExperiment(model=None, model_dir="x", prefill_chunk="big")
    with pytest.raises(ValueError, match="prefill_budget_per_tick"):
        ServingExperiment(model=None, model_dir="x",
                          prefill_budget_per_tick=16)
    with pytest.raises(ValueError, match="prefill_budget_per_tick"):
        ServingExperiment(model=None, model_dir="x", prefill_chunk=8,
                          prefill_budget_per_tick=0)
    experiment = ServingExperiment(
        model=None, model_dir="x", prefill_chunk="auto",
        prefill_budget_per_tick=64,
    )
    assert experiment.prefill_chunk == "auto"


# --------------------------------------------------------------------------
# the fake: blocking-identical streams, the no-stall contract, budget
# --------------------------------------------------------------------------

_WORKLOAD = [
    ([1, 2, 3, 4, 5], SamplingParams(max_new_tokens=3)),
    (list(range(1, 22)), SamplingParams(max_new_tokens=4)),  # 21 tokens
    ([7, 8], SamplingParams(max_new_tokens=2, eos_token=30)),
]


def test_chunked_streams_match_blocking_and_skip_prefill_program():
    blocking = fake_scheduler(FakePagedWindowedEngine(), max_slots=3)
    expected = _run_streams(blocking, _WORKLOAD)

    engine = FakePagedWindowedEngine()
    chunked = fake_scheduler(engine, max_slots=3, prefill_chunk=4)
    assert _run_streams(chunked, _WORKLOAD) == expected
    # Chunked admission runs NO device program of its own: the slot
    # starts at length 0 of fresh blocks and the prompt replays in
    # windows — the engine sees the pool made and windowed steps only.
    assert {c[0] for c in engine.calls} == {"make_pool", "paged_spec_step"}
    # ONE window shape for the whole run — no recompile keys
    # tick-to-tick (the TYA205 contract, at the fake seam).
    shapes = {c[1].shape for c in engine.calls
              if c[0] == "paged_spec_step"}
    assert shapes == {(3, 4)}


def test_decode_slots_emit_every_tick_while_2k_prompt_admits():
    """THE no-stall contract: a decoding slot keeps emitting on every
    single tick while a 2000-token prompt chunks through admission on
    the other slot."""
    engine = FakePagedWindowedEngine(max_seq_len=2048)
    scheduler = fake_scheduler(
        engine, max_slots=2, prefill_chunk=8, prefill_budget_per_tick=8,
    )
    decode = scheduler.submit([1, 2], SamplingParams(max_new_tokens=300))
    scheduler.tick()  # admits; consumes [1, 2], emits the first token
    long_prompt = [1] * 2000
    long = scheduler.submit(long_prompt, SamplingParams(max_new_tokens=1))
    admit_tick = scheduler._ticks + 1
    while long.first_token_at is None:
        scheduler.tick()
        assert scheduler._ticks < 2000, "long prompt never finished"
    first_emit_tick = scheduler._ticks
    # 2000 prompt tokens at 8/tick = 250 chunking ticks.
    assert first_emit_tick - admit_tick + 1 == 250
    # The decode slot emitted on EVERY one of those ticks.
    ticks = [t for t in scheduler.trace
             if admit_tick <= t["tick"] <= first_emit_tick]
    assert len(ticks) == 250
    assert all(
        t.get("accepted", {}).get(decode.request.id) == 1 for t in ticks
    )
    # Arithmetic held through the interleave: the long request's one
    # token is the whole-prompt sum mod 97.
    assert long.result(timeout=1) == [sum(long_prompt) % 97]
    scheduler.close()


def test_prefill_budget_pauses_chunking_slots_round_robin():
    engine = FakePagedWindowedEngine(max_seq_len=64)
    scheduler = fake_scheduler(
        engine, max_slots=2, prefill_chunk=4, prefill_budget_per_tick=4,
    )
    workload = [
        (list(range(1, 41)), SamplingParams(max_new_tokens=2)),
        (list(range(2, 42)), SamplingParams(max_new_tokens=2)),
    ]
    streams = _run_streams(scheduler, workload)

    blocking = fake_scheduler(
        FakePagedWindowedEngine(max_seq_len=64), max_slots=2)
    assert streams == _run_streams(blocking, workload)

    # While BOTH slots were chunking, the 4-token budget admitted
    # exactly one 4-token window per tick (the other slot paused:
    # masked off), and the rotation strictly alternated — 2x40 prompt
    # tokens at 4/tick = at least 19 solo-advance ticks, no starvation.
    advanced = []
    for call in engine.calls:
        if call[0] != "paged_spec_step":
            continue
        _, _tokens, n_known, active = call
        if active.sum() == 1 and n_known[int(np.argmax(active))] > 0:
            advanced.append(int(np.argmax(active)))
    assert len(advanced) >= 19
    assert all(a != b for a, b in zip(advanced, advanced[1:]))
    assert set(advanced) == {0, 1}
    scheduler.close()


def test_chunked_stats_and_token_counters():
    registry = telemetry.get_registry()
    before_prefill = registry.counter("serving/prefill_tokens_total").value
    before_decode = registry.counter("serving/decode_tokens_total").value
    scheduler = fake_scheduler(
        FakePagedWindowedEngine(), max_slots=1, prefill_chunk=4,
        prefill_budget_per_tick=8,
    )
    prompt = list(range(1, 12))  # 11 tokens
    response = scheduler.submit(prompt, SamplingParams(max_new_tokens=3))
    _drive(scheduler, [response])
    stats = scheduler.stats()
    assert stats["prefill_chunk"] == 4
    assert stats["prefill_budget_per_tick"] == 8
    # Every prompt token was consumed through the windowed replay, and
    # every emitted token was counted as decode.
    assert stats["prefill_tokens"] == len(prompt)
    assert stats["decode_tokens"] == 3
    assert registry.counter("serving/prefill_tokens_total").value \
        - before_prefill == len(prompt)
    assert registry.counter("serving/decode_tokens_total").value \
        - before_decode == 3
    # The response recorded per-token arrival times (the bench's ITL
    # series), and the histogram saw the gaps.
    assert len(response.token_times) == 3
    assert len(response.inter_token_gaps_s()) == 2
    assert registry.histogram(
        "serving/inter_token_latency_ms"
    ).summary()["count"] >= 2
    scheduler.close()


# --------------------------------------------------------------------------
# the pool: incremental prefix registration + exactly-once eviction
# --------------------------------------------------------------------------

def _paged_chunked(max_slots=2, num_blocks=None, **kwargs):
    engine = FakePagedWindowedEngine()
    return engine, fake_scheduler(
        engine, max_slots=max_slots, num_blocks=num_blocks, **kwargs
    )


def test_paged_chunked_matches_blocking_and_registers_incrementally():
    workload = [
        (list(range(1, 13)), SamplingParams(max_new_tokens=3)),  # 12 tok
        ([5, 6], SamplingParams(max_new_tokens=2)),
    ]
    _, blocking = _paged_chunked()
    expected = _run_streams(blocking, workload)

    engine, chunked = _paged_chunked(prefill_chunk=4)
    assert _run_streams(chunked, workload) == expected
    kinds = [c[0] for c in engine.calls]
    assert "prefill" not in kinds and "pack" not in kinds
    # 12 prompt tokens at block_size 4 -> 3 whole blocks registered as
    # the chunks completed (one prefix entry per whole-block length).
    stats = chunked.stats()
    assert stats["prefix_cache"]["entries"] == 3

    # A repeat of the long prompt admits through the shared blocks: the
    # lookup cap (len - 1) hits the 2-block/8-token prefix.
    repeat = chunked.submit(workload[0][0], SamplingParams(max_new_tokens=3))
    _drive(chunked, [repeat])
    assert repeat.result(timeout=1) == expected[0]
    assert chunked.stats()["prefix_cache"]["hits"] >= 1
    chunked.close()


def test_mid_prefill_deadline_eviction_releases_blocks_exactly_once():
    """The bugfix bar: a request evicted mid-PREFILL releases its
    reserved blocks and its refcounted prefix-cache shares exactly once
    — a double release would raise inside the tick (failing the tick
    and incrementing serving/tick_errors_total), a leak would strand
    used blocks after retirement."""
    registry = telemetry.get_registry()
    errors_before = registry.counter("serving/tick_errors_total").value
    engine, scheduler = _paged_chunked(
        max_slots=1, prefill_chunk=4, prefill_budget_per_tick=4,
    )
    prompt = list(range(1, 25))  # 24 tokens = 6 blocks of prompt
    victim = scheduler.submit(
        prompt, SamplingParams(max_new_tokens=2), timeout_s=0.05,
    )
    scheduler.tick()  # admit + first chunk
    scheduler.tick()  # second chunk: 8 tokens filled, 2 blocks registered
    mid = scheduler.stats()
    assert not victim.done
    assert mid["prefix_cache"]["entries"] == 2
    assert mid["block_pool"]["used_blocks"] > 2
    time.sleep(0.08)
    scheduler.tick()
    assert victim.finish_reason == FINISH_DEADLINE
    after = scheduler.stats()
    # The slot's own references are gone; ONLY the prefix cache's
    # 2 shared blocks stay resident, each at refcount 1.
    assert after["block_pool"]["used_blocks"] == 2
    assert after["prefix_cache"]["entries"] == 2
    # Exactly-once: every remaining reference is the prefix cache's own
    # (one per entry containing the block) — the slot's are all gone; a
    # double release would have raised mid-tick, a leak would leave a
    # higher refcount here.
    import collections
    pool = scheduler._blocks
    cache_refs = collections.Counter(
        bid for entry in scheduler._prefix._entries.values()
        for bid in entry
    )
    assert {b: pool.refcount(b) for b in cache_refs} == dict(cache_refs)
    assert registry.counter("serving/tick_errors_total").value \
        == errors_before
    # The freed capacity is really free: the same prompt admits again
    # through the cached prefix and completes.
    repeat = scheduler.submit(prompt, SamplingParams(max_new_tokens=2))
    _drive(scheduler, [repeat])
    assert scheduler.stats()["prefix_cache"]["hits"] >= 1
    scheduler.close()


def test_mid_prefill_shutdown_eviction_releases_blocks_exactly_once():
    registry = telemetry.get_registry()
    errors_before = registry.counter("serving/tick_errors_total").value
    engine, scheduler = _paged_chunked(max_slots=1, prefill_chunk=4)
    victim = scheduler.submit(
        list(range(1, 25)), SamplingParams(max_new_tokens=2),
    )
    scheduler.tick()
    scheduler.tick()
    assert not victim.done
    scheduler.close()
    assert victim.finish_reason == "shutdown"
    after = scheduler.stats()
    assert after["block_pool"]["used_blocks"] \
        == after["prefix_cache"]["cached_blocks"]
    assert registry.counter("serving/tick_errors_total").value \
        == errors_before


# --------------------------------------------------------------------------
# real engine on CPU: bit-identity bars
# --------------------------------------------------------------------------

def _tiny_stack(max_slots=2, kv_cache_dtype="bf16", max_seq_len=64,
                engine=None, block_size=8, **scheduler_kwargs):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from tf_yarn_tpu.models import transformer
    from tf_yarn_tpu.models.decode_engine import DecodeEngine

    if engine is None:
        cfg = transformer.TransformerConfig.tiny(
            scan_layers=False, remat=False, max_seq_len=max_seq_len,
            dtype=jnp.float32, kv_cache_dtype=kv_cache_dtype,
        )
        model = transformer.Transformer(cfg)
        params = nn.meta.unbox(
            model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
        )
        engine = DecodeEngine(
            model, batch_buckets=(1, 2, 4), prompt_buckets=(4, 8, 16)
        )
        engine._test_params = params
    model = engine.model
    params = engine._test_params
    scheduler = SlotScheduler(
        engine, params, max_slots=max_slots, block_size=block_size,
        **scheduler_kwargs
    )
    return model, params, engine, scheduler


def _legacy_stream(model, params, prompt, max_new, eos=None, **sampling):
    import jax.numpy as jnp

    from tf_yarn_tpu.models.generate import generate_legacy

    out = generate_legacy(
        model, params, jnp.asarray([prompt], jnp.int32), max_new,
        eos_token=eos, **sampling,
    )
    row = np.asarray(out)[0, len(prompt):].tolist()
    if eos is not None and eos in row:
        row = row[:row.index(eos) + 1]
    return row


def test_chunked_real_engine_greedy_and_sampled_match_legacy():
    """The tier-1 bit-identity bar: chunked
    prefill streams — mixed prompt lengths under a live budget — are
    IDENTICAL to generate_legacy, greedy and sampled RNG chains alike,
    with ONE windowed program compiled and the blocking prefill
    programs never built."""
    model, params, engine, scheduler = _tiny_stack(
        max_slots=2, prefill_chunk=4, prefill_budget_per_tick=8,
    )
    try:
        rng = np.random.RandomState(0)
        prompts = [
            rng.randint(0, 256, (9,)).tolist(),
            rng.randint(0, 256, (5,)).tolist(),
            rng.randint(0, 256, (2,)).tolist(),
        ]
        max_news = (8, 6, 4)
        responses = [
            scheduler.submit(p, SamplingParams(max_new_tokens=m))
            for p, m in zip(prompts, max_news)
        ]
        _drive(scheduler, responses)
        for prompt, max_new, response in zip(prompts, max_news, responses):
            assert response.result(timeout=1) == _legacy_stream(
                model, params, prompt, max_new
            )
        assert engine.stats["paged_spec_step_compiles"] == 1
        assert engine.stats["prefill_compiles"] == 0
    finally:
        scheduler.close()

    sampling = dict(temperature=0.8, top_k=20)
    model, params, engine, scheduler = _tiny_stack(
        max_slots=2, prefill_chunk=4, engine=engine, **sampling,
    )
    try:
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, 256, (9,)).tolist(),
                   rng.randint(0, 256, (5,)).tolist()]
        seeds = [3, 11]
        responses = [
            scheduler.submit(p, SamplingParams(
                max_new_tokens=6, seed=s, **sampling))
            for p, s in zip(prompts, seeds)
        ]
        _drive(scheduler, responses)
        for prompt, seed, response in zip(prompts, seeds, responses):
            assert response.result(timeout=1) == _legacy_stream(
                model, params, prompt, 6, seed=seed, **sampling,
            )
    finally:
        scheduler.close()


@pytest.mark.parametrize("scheduler_kwargs, kv_cache_dtype, reference", [
    # fp: bit-identical to legacy, prefix hit included below.
    ({}, "bf16", "legacy"),
    # int8: chunked must equal the BLOCKING path bit-for-bit (int8
    # quantization differs from legacy's rounding only in ways the
    # blocking scheduler already carries).
    ({}, "int8", "blocking"),
    # spec composition: drafts ride the widened window, stream still
    # exact.
    ({"spec_k": 2}, "bf16", "legacy"),
])
def test_chunked_composition_matrix_streams_identical(scheduler_kwargs,
                                                      kv_cache_dtype,
                                                      reference):
    workload_rng = np.random.RandomState(7)
    prompts = [
        workload_rng.randint(0, 256, (17,)).tolist(),
        ([7, 9, 11] * 4)[:10],  # repeat structure: n-gram drafts land
        workload_rng.randint(0, 256, (2,)).tolist(),
    ]
    max_news = (6, 8, 4)
    workload = list(zip(prompts, max_news))

    def run(**extra):
        model, params, engine, scheduler = _tiny_stack(
            max_slots=2, kv_cache_dtype=kv_cache_dtype,
            **scheduler_kwargs, **extra,
        )
        try:
            responses = [
                scheduler.submit(p, SamplingParams(max_new_tokens=m))
                for p, m in workload
            ]
            _drive(scheduler, responses)
            streams = [r.result(timeout=1) for r in responses]
            # The prefix-hit composition: repeat the long prompt through
            # the (incrementally registered) shared blocks.
            repeat = scheduler.submit(
                prompts[0], SamplingParams(max_new_tokens=max_news[0])
            )
            _drive(scheduler, [repeat])
            assert repeat.result(timeout=1) == streams[0]
            if extra.get("prefill_chunk"):
                assert scheduler.stats()["prefix_cache"]["hits"] >= 1
            return model, params, streams
        finally:
            scheduler.close()

    model, params, chunked = run(
        prefill_chunk=4, prefill_budget_per_tick=8
    )
    if reference == "legacy":
        expected = [
            _legacy_stream(model, params, p, m) for p, m in workload
        ]
    else:
        _model, _params, expected = run()
    assert chunked == expected


@pytest.mark.slow
def test_chunked_long_prompt_e2e_no_stall_and_identical():
    """Long-prompt e2e on the real engine: a 512-token prompt chunks
    through admission while a short decode-bound request streams — the
    decode slot emits on every tick of the chunking phase, and both
    streams equal generate_legacy."""
    model, params, engine, scheduler = _tiny_stack(
        max_slots=2, max_seq_len=640, prefill_chunk=64,
        prefill_budget_per_tick=64,
    )
    try:
        rng = np.random.RandomState(11)
        short_prompt = rng.randint(0, 256, (3,)).tolist()
        long_prompt = rng.randint(0, 256, (512,)).tolist()
        short = scheduler.submit(
            short_prompt, SamplingParams(max_new_tokens=24)
        )
        for _ in range(4):  # short is decoding before the long arrives
            scheduler.tick()
        long = scheduler.submit(long_prompt, SamplingParams(max_new_tokens=4))
        admit_tick = scheduler._ticks + 1
        _drive(scheduler, [short, long], max_ticks=200)
        assert short.result(timeout=1) == _legacy_stream(
            model, params, short_prompt, 24
        )
        assert long.result(timeout=1) == _legacy_stream(
            model, params, long_prompt, 4
        )
        # 512 tokens at 64/tick = 8 chunking ticks; the short slot
        # (alive well past them: 24 tokens, one per tick) emitted on
        # EVERY one.
        chunk_ticks = [
            t for t in scheduler.trace
            if admit_tick <= t["tick"] < admit_tick + 8
        ]
        assert len(chunk_ticks) == 8
        assert all(
            t.get("accepted", {}).get(short.request.id, 0) >= 1
            for t in chunk_ticks
        )
        assert engine.stats["paged_spec_step_compiles"] == 1
    finally:
        scheduler.close()
