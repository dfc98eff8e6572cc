"""Online serving: continuous-batching scheduler + HTTP frontend.

Two layers of coverage, matching the subsystem's design seam:

* The :class:`SlotScheduler` is a pure host-side state machine whose
  only device contract is the engine's paged methods — so the unit
  tests drive it with a deterministic fake engine (tests/fakes.py) and
  assert the tick-by-tick trace (admit/prefill/step/retire ordering,
  free-list reuse, deadline eviction, backpressure) with no device in
  sight.
* The end-to-end tests run the REAL stack on CPU: tiny f32 transformer,
  DecodeEngine over the paged pool, scheduler loop, threaded HTTP
  frontend — and hold the acceptance bar: concurrent requests' token
  streams are bit-identical to `generate_legacy`, and a slot freed by an early-EOS
  request is re-admitted before the longest request finishes.
"""

import http.client
import json
import threading
import time

import numpy as np
import pytest

from tests.fakes import (
    FakePagedEngine,
    assert_pipelined_equals_settled,
    fake_scheduler,
)
from tf_yarn_tpu.serving import (
    FINISH_DEADLINE,
    FINISH_EOS,
    FINISH_ERROR,
    FINISH_LENGTH,
    AdmissionQueue,
    BlockPool,
    PrefixCache,
    QueueFull,
    Request,
    SamplingParams,
    ServingServer,
    SlotScheduler,
)


# --------------------------------------------------------------------------
# request layer
# --------------------------------------------------------------------------

def test_sampling_params_validate():
    with pytest.raises(ValueError, match="max_new_tokens"):
        SamplingParams(max_new_tokens=0)


def test_request_validates_and_tracks_deadline():
    with pytest.raises(ValueError, match="prompt"):
        Request(prompt=())
    with pytest.raises(ValueError, match="timeout_s"):
        Request(prompt=(1,), timeout_s=0)
    request = Request(prompt=(1, 2), timeout_s=60.0)
    assert not request.expired()
    assert Request(prompt=(1,)).deadline is None


def test_admission_queue_backpressure_and_priority():
    queue = AdmissionQueue(capacity=2, retry_after_s=2.5)
    low = queue.submit(Request(prompt=(1,), priority=0))
    high = queue.submit(Request(prompt=(2,), priority=5))
    with pytest.raises(QueueFull) as excinfo:
        queue.submit(Request(prompt=(3,)))
    assert excinfo.value.retry_after_s == 2.5
    # Priority order out, FIFO within a priority.
    assert queue.pop()[1] is high
    assert queue.pop()[1] is low
    assert queue.pop() is None


def test_response_streams_then_finishes():
    request = Request(prompt=(1,))
    queue = AdmissionQueue()
    response = queue.submit(request)
    seen = []

    def consume():
        for token in response.tokens():
            seen.append(token)

    thread = threading.Thread(target=consume)
    thread.start()
    response._push(11)
    response._push(12)
    response._finish(FINISH_LENGTH)
    thread.join(timeout=5)
    assert seen == [11, 12]
    assert response.result(timeout=1) == [11, 12]
    assert response.finish_reason == FINISH_LENGTH
    assert response.ttft_s is not None and response.ttft_s >= 0


# --------------------------------------------------------------------------
# scheduler unit tests: a deterministic fake engine, no device
# --------------------------------------------------------------------------

def _drive(scheduler, responses, max_ticks=200):
    """Tick until every response finished; returns ticks used."""
    for used in range(1, max_ticks + 1):
        scheduler.tick()
        if all(r.done for r in responses):
            return used
    raise AssertionError(f"not drained after {max_ticks} ticks")


def test_fake_engine_tick_trace_admit_prefill_step_retire_order():
    engine = FakePagedEngine()
    scheduler = fake_scheduler(engine, max_slots=2)
    # prompt [1..5]: prefill bucket 4 -> cache 1+2+3+4=10, replay [5];
    # the first step consumes 5 -> cache 15 -> emits 15.
    response = scheduler.submit(
        [1, 2, 3, 4, 5], SamplingParams(max_new_tokens=3)
    )
    _drive(scheduler, [response])
    # 15, then 15+15=30, then 30+30=60 (emitted tokens feed back).
    assert response.result(timeout=1) == [15, 30, 60]
    assert response.finish_reason == FINISH_LENGTH
    kinds = [c[0] for c in engine.calls]
    # Admission device work strictly precedes the first step.
    assert kinds[:3] == ["make_pool", "prefill", "pack"]
    assert kinds.count("paged_step") == 3
    assert scheduler.trace[0]["admitted"] == [response.request.id]
    assert scheduler.trace[-1]["retired"] == [
        (response.request.id, FINISH_LENGTH)
    ]


def test_fake_engine_eos_and_whole_prompt_replay():
    engine = FakePagedEngine()
    scheduler = fake_scheduler(engine, max_slots=1)
    # prompt [7, 8]: prompt_len-1 = 1 < min bucket -> NO prefill, whole
    # prompt replays from length 0 of fresh blocks: tick1 consumes 7
    # (masked off), tick2 consumes 8 and emits (7+8)=15.
    response = scheduler.submit(
        [7, 8], SamplingParams(max_new_tokens=8, eos_token=30)
    )
    _drive(scheduler, [response])
    # 15 -> 15+15=30 = eos: stream is [15, 30], finish_reason eos.
    assert response.result(timeout=1) == [15, 30]
    assert response.finish_reason == FINISH_EOS
    kinds = [c[0] for c in engine.calls]
    assert "prefill" not in kinds and "pack" not in kinds


def test_free_list_reuses_slot_on_next_tick():
    from tf_yarn_tpu import telemetry

    engine = FakePagedEngine()
    scheduler = fake_scheduler(engine, max_slots=2)
    reuse = telemetry.get_registry().counter("serving/slot_reuse_total")
    reused_before = reuse.value
    # short finishes in 1 generated token; long runs for 6.
    short = scheduler.submit([1, 2, 3, 4, 5],
                             SamplingParams(max_new_tokens=1))
    long = scheduler.submit([2, 2, 2, 2, 2],
                            SamplingParams(max_new_tokens=6))
    waiting = scheduler.submit([3, 3, 3, 3, 3],
                               SamplingParams(max_new_tokens=1))
    _drive(scheduler, [short, long, waiting])
    trace = list(scheduler.trace)
    retire_tick = next(
        t["tick"] for t in trace
        if (short.request.id, FINISH_LENGTH) in t["retired"]
    )
    admit_tick = next(
        t["tick"] for t in trace if waiting.request.id in t["admitted"]
    )
    long_tick = next(
        t["tick"] for t in trace
        if (long.request.id, FINISH_LENGTH) in t["retired"]
    )
    # The freed slot is reused on the VERY NEXT tick, long still running.
    assert admit_tick == retire_tick + 1
    assert long_tick > admit_tick
    # Both early requests ran in slot grid of 2 -> the third admission
    # reused a previously-used slot.
    assert len([c for c in engine.calls if c[0] == "pack"]) == 3
    assert reuse.value - reused_before == 1


def test_deadline_evicts_active_slot_and_queued_request():
    engine = FakePagedEngine()
    scheduler = fake_scheduler(engine, max_slots=1)
    active = scheduler.submit(
        [1, 2, 3, 4, 5], SamplingParams(max_new_tokens=20),
        timeout_s=0.05,
    )
    queued = scheduler.submit(
        [1, 2], SamplingParams(max_new_tokens=1), timeout_s=0.05,
    )
    scheduler.tick()  # admits `active`, `queued` stays queued
    assert not active.done and not queued.done
    time.sleep(0.08)
    scheduler.tick()
    assert active.finish_reason == FINISH_DEADLINE
    # The queued request died in the queue without ever taking a slot.
    scheduler.tick()
    assert queued.finish_reason == FINISH_DEADLINE
    assert [rid for entry in scheduler.trace
            for rid in entry["admitted"]] == [active.request.id]
    assert scheduler.stats()["block_pool"]["used_blocks"] == \
        scheduler.stats()["prefix_cache"]["cached_blocks"]


def test_backpressure_rejection_and_sampling_mismatch():
    scheduler = fake_scheduler(
        FakePagedEngine(), max_slots=1, queue_capacity=1,
        retry_after_s=3.0,
    )
    scheduler.submit([1, 2], SamplingParams(max_new_tokens=1))
    with pytest.raises(QueueFull) as excinfo:
        scheduler.submit([3, 4], SamplingParams(max_new_tokens=1))
    assert excinfo.value.retry_after_s == 3.0
    with pytest.raises(ValueError, match="temperature"):
        scheduler.submit(
            [1, 2], SamplingParams(max_new_tokens=1, temperature=0.7)
        )


def test_close_fails_inflight_requests_as_shutdown():
    scheduler = fake_scheduler(FakePagedEngine(), max_slots=1)
    active = scheduler.submit([1, 2, 3, 4, 5],
                              SamplingParams(max_new_tokens=20))
    queued = scheduler.submit([1, 2], SamplingParams(max_new_tokens=1))
    scheduler.tick()
    scheduler.close()
    assert active.finish_reason == "shutdown"
    assert queued.finish_reason == "shutdown"


# --------------------------------------------------------------------------
# the block pool: host-side bookkeeping, pressure, the prefix cache
# --------------------------------------------------------------------------

def test_block_pool_refcounts_and_free_list():
    pool = BlockPool(num_blocks=5, block_size=4)
    assert pool.free_blocks == 4  # block 0 reserved (trash)
    a = pool.allocate(2)
    assert sorted(a) == [1, 2] and pool.used_blocks == 2
    assert pool.allocate(3) is None  # only 2 left
    pool.retain([a[0]])
    assert pool.release([a[0]]) == 0  # still one ref
    assert pool.release(a) == 2  # both free now
    assert pool.free_blocks == 4
    with pytest.raises(ValueError, match="free block"):
        pool.release([1])


def test_prefix_cache_longest_hit_register_and_lru_eviction():
    pool = BlockPool(num_blocks=9, block_size=4)
    cache = PrefixCache(pool, capacity=2)
    prompt = tuple(range(10))
    ids = pool.allocate(3)  # covers 10 tokens at bs=4 (2 full + partial)
    # Only FULL blocks are shared: 8 tokens -> 2 blocks, one entry per
    # whole-block prefix length (k=1 and k=2) so shorter shared
    # prefixes hit too; block 0 of the prompt is pinned by both.
    assert cache.register(prompt, 9, ids)
    assert cache.entries == 2
    assert cache.cached_blocks == 2
    assert pool.refcount(ids[0]) == 3 and pool.refcount(ids[2]) == 1
    # Longest hit capped by max_tokens (must leave >= 1 token to replay).
    covered, hit = cache.lookup(prompt, max_tokens=len(prompt) - 1)
    assert covered == 8 and hit == ids[:2]
    covered, hit = cache.lookup(prompt[:6], max_tokens=5)
    assert covered == 4 and hit == ids[:1]
    assert cache.lookup((99, 98, 97, 96), max_tokens=3) == (0, [])
    assert cache.hits == 2 and cache.misses == 1
    # The request retires: its own refs go, the cache's survive.
    pool.release(ids)
    assert pool.refcount(ids[0]) == 2 and pool.free_blocks == 6
    # LRU eviction under pressure frees the cached blocks.
    freed = cache.evict_for(pool.num_blocks - 1)
    assert freed == 2 and cache.entries == 0
    assert pool.free_blocks == 8


def _paged_scheduler(max_slots=2, num_blocks=None, **kwargs):
    engine = FakePagedEngine()
    return engine, fake_scheduler(
        engine, max_slots=max_slots, num_blocks=num_blocks, **kwargs
    )


def test_paged_retirement_is_host_side_bookkeeping():
    """The tick-trace request again (prefill bucket 4 -> 10, replay 5 ->
    15, then 30, 60): retirement runs NO device program — the engine
    sees the pool made, one prefill packed and three steps, nothing
    else — and leaves only the block the prefix cache shares."""
    engine, scheduler = _paged_scheduler()
    response = scheduler.submit(
        [1, 2, 3, 4, 5], SamplingParams(max_new_tokens=3)
    )
    _drive(scheduler, [response])
    assert response.result(timeout=1) == [15, 30, 60]
    kinds = [c[0] for c in engine.calls]
    assert kinds == ["make_pool", "prefill", "pack"] + ["paged_step"] * 3
    # All blocks released on retire (none shareable: prefill 4 = 1 full
    # block, kept by the prefix cache).
    stats = scheduler.stats()
    assert stats["kv_layout"] == "paged"
    assert stats["block_pool"]["used_blocks"] == \
        stats["prefix_cache"]["cached_blocks"] == 1


def test_paged_admission_holds_until_blocks_free():
    """Pool pressure: the second request cannot reserve its blocks, so
    it is HELD (not dropped, not crashing the tick) and admitted on the
    tick after the first retires and frees them."""
    # Requests need ceil((5 + 3 - 1)/4) = 2 blocks each; pool holds 3
    # usable — the second must wait for the first's retirement.
    engine, scheduler = _paged_scheduler(
        max_slots=2, num_blocks=4, prefix_cache_capacity=0,
    )
    first = scheduler.submit([1, 2, 3, 4, 5],
                             SamplingParams(max_new_tokens=3))
    second = scheduler.submit([2, 2, 2, 2, 2],
                              SamplingParams(max_new_tokens=3))
    _drive(scheduler, [first, second])
    assert first.result(timeout=1) == [15, 30, 60]
    # Same arithmetic as a fresh grid: its cache never saw slot 1's data.
    assert second.result(timeout=1) == [10, 20, 40]
    trace = list(scheduler.trace)
    retire1 = next(t["tick"] for t in trace
                   if (first.request.id, FINISH_LENGTH) in t["retired"])
    admit2 = next(t["tick"] for t in trace
                  if second.request.id in t["admitted"])
    assert admit2 == retire1 + 1
    # Both requests decoded correctly with only 3 usable blocks, where
    # two slots at full context would reserve 16.


def test_paged_prefix_hit_skips_prefill_and_shares_blocks():
    """Two requests with the same prompt: the second admission does NO
    prefill/pack device work — its leading table entries are the
    refcounted shared blocks — and its stream is identical."""
    engine, scheduler = _paged_scheduler(max_slots=1)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5]  # prefill 8 = 2 full blocks
    first = scheduler.submit(prompt, SamplingParams(max_new_tokens=2))
    _drive(scheduler, [first])
    prefills_before = [c for c in engine.calls if c[0] == "prefill"]
    assert len(prefills_before) == 1
    second = scheduler.submit(prompt, SamplingParams(max_new_tokens=2))
    _drive(scheduler, [second])
    assert [c for c in engine.calls if c[0] == "prefill"] == prefills_before
    assert second.result(timeout=1) == first.result(timeout=1)
    stats = scheduler.stats()
    assert stats["prefix_cache"]["hits"] == 1
    assert stats["prefix_cache"]["cached_blocks"] == 2
    from tf_yarn_tpu import telemetry

    assert telemetry.get_registry().counter(
        "serving/prefix_cache_hits_total"
    ).value >= 1


class _LoudPadEngine(FakePagedEngine):
    """The scheduler pads a prefill with token 0, which the fake's sums
    would not show: a pad row holds 1000 here, so a step that read one
    would emit another stream."""

    def prefill(self, params, prompt, length=None):
        row, logits = super().prefill(params, prompt, length)
        return np.where(row == 0, 1000, row), logits


# Buckets 4 and 16 over blocks of 4: at, one under and one over each (the
# rows kept are the prompt's less one); 18 and 20 have no bucket above.
@pytest.mark.parametrize("prompt_len", [2, 4, 5, 6, 9, 16, 17, 18, 20])
def test_ceiling_admission_keeps_the_true_length_and_trashes_the_pad(
        prompt_len):
    """An engine that says its model may take the ceiling rule: the
    admission runs the bucket above the prompt, keeps all of the prompt
    but its last token, aims every block past the kept rows at the trash
    block, replays one token, and serves the stream the floor rule
    serves, to a neighbour in the next slot too; the counters say so."""
    prompt = list(range(1, prompt_len + 1))
    neighbour_prompt = [9, 8, 7, 6, 5]
    streams = {}
    for name, engine in (
            ("floor", FakePagedEngine(buckets=(4, 16))),
            ("ceiling", _LoudPadEngine(buckets=(4, 16), ceiling=True))):
        scheduler = fake_scheduler(engine, max_slots=2)
        neighbour = scheduler.submit(
            neighbour_prompt, SamplingParams(max_new_tokens=10))
        scheduler.tick()
        response = scheduler.submit(prompt, SamplingParams(max_new_tokens=3))
        scheduler.tick()
        own = list(scheduler._slots[1].blocks)
        _drive(scheduler, [neighbour, response])
        streams[name] = (neighbour.result(timeout=1),
                         response.result(timeout=1))
    assert streams["ceiling"] == streams["floor"]
    bucket, kept = engine.slot_prefill_len(prompt_len, True)
    above = prompt_len - 1 <= 16
    assert (bucket, kept) == (
        (4 if prompt_len <= 5 else 16, prompt_len - 1) if above else (16, 16))
    prefills = [c[1] for c in engine.calls if c[0] == "prefill"]
    packs = [c[1] for c in engine.calls if c[0] == "pack"]
    assert prefills == [(1, 4), (1, bucket)]  # the neighbour's, then ours
    n_owned = -(-kept // 4)
    assert packs[1] == tuple(own[:n_owned]) + (0,) * (bucket // 4 - n_owned)
    assert not set(packs[1]) & set(packs[0])
    stats = scheduler.stats()
    # The neighbour kept 4 of the bucket of 4 and replayed its last token.
    assert stats["prefills_ceiling"] + stats["prefills_floor"] == 2
    assert stats["prefills_floor"] == (0 if above else 1)
    assert stats["prefill_pad_tokens"] == bucket - kept
    assert stats["prefilled_tokens"] == 4 + kept
    assert stats["prefill_tokens"] == 1 + prompt_len - kept
    assert stats["ahead_prefill_tokens"] <= 4 + bucket


def test_a_scheduler_keeps_the_floor_rule_unless_its_engine_says_otherwise():
    """Neither an engine that cannot say (no `ceiling_prefill`) nor one
    that says no gets a padded prefill: the bucket below, kept whole, and
    the rest replayed, as before."""

    class Mute(FakePagedEngine):
        ceiling_prefill = None

    for engine in (FakePagedEngine(), Mute()):
        scheduler = fake_scheduler(engine, max_slots=1)
        response = scheduler.submit(
            [1, 2, 3, 4, 5, 6, 7], SamplingParams(max_new_tokens=2))
        _drive(scheduler, [response])
        stats = scheduler.stats()
        assert [c[1] for c in engine.calls if c[0] == "prefill"] == [(1, 4)]
        assert (stats["prefills_ceiling"], stats["prefills_floor"],
                stats["prefill_pad_tokens"], stats["prefilled_tokens"],
                stats["prefill_tokens"]) == (0, 1, 0, 4, 3)


def test_paged_prefix_eviction_under_pool_pressure():
    """A cached prefix is evicted (LRU) when a new request needs its
    blocks — the cache trades reuse for admission, never blocks it."""
    # Pool: 5 usable blocks. First request: 2 blocks, both full ->
    # cached on retire. Second (different prompt): needs 4 blocks ->
    # must evict the cached prefix to fit.
    engine, scheduler = _paged_scheduler(max_slots=1, num_blocks=6)
    first = scheduler.submit([1, 2, 3, 4, 5, 6, 7, 8, 9],
                             SamplingParams(max_new_tokens=2))
    _drive(scheduler, [first])
    assert scheduler.stats()["prefix_cache"]["cached_blocks"] == 2
    second = scheduler.submit([9, 8, 7, 6, 5, 4, 3, 2, 1],
                              SamplingParams(max_new_tokens=7))
    _drive(scheduler, [second])
    stats = scheduler.stats()
    assert second.finish_reason == FINISH_LENGTH
    # The old prompt's entries are gone; the new request's own prefix
    # entries (k=1, k=2) took their place.
    assert stats["prefix_cache"]["entries"] == 2


def test_paged_submit_rejects_impossible_request():
    _engine, scheduler = _paged_scheduler(max_slots=1, num_blocks=3)
    with pytest.raises(ValueError, match="KV blocks"):
        # Needs ceil((9 + 8 - 1)/4) = 4 blocks; the pool holds 2 usable.
        scheduler.submit(list(range(9)), SamplingParams(max_new_tokens=8))


def test_tick_error_fails_inflight_and_loop_survives():
    """A tick exception must fail the in-flight requests as `error` and
    leave the scheduler serving — not kill the loop thread."""
    engine = FakePagedEngine()
    scheduler = fake_scheduler(engine, max_slots=1)
    boom = {"armed": True}
    original = engine.paged_step

    def exploding_step(*args, **kwargs):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected device failure")
        return original(*args, **kwargs)

    engine.paged_step = exploding_step
    scheduler.start()
    try:
        failed = scheduler.submit([1, 2, 3, 4, 5],
                                  SamplingParams(max_new_tokens=3))
        failed.result(timeout=30)
        assert failed.finish_reason == FINISH_ERROR
        # The loop survived: the next request decodes normally.
        ok = scheduler.submit([1, 2, 3, 4, 5],
                              SamplingParams(max_new_tokens=3))
        assert ok.result(timeout=30) == [15, 30, 60]
    finally:
        scheduler.close()


# --------------------------------------------------------------------------
# the one-token pipeline: step N+1 is launched before step N is read
# --------------------------------------------------------------------------

def test_pipelined_streams_equal_settled_streams_on_the_fake():
    """Replay, decode, admissions into freed slots, `max_new_tokens` and
    `eos_token` endings and a deadline with a step in flight: the streams
    are those of the serial order (tests/fakes.py
    `assert_pipelined_equals_settled`), here where every emission can be
    reckoned by hand."""
    engine = FakePagedEngine()
    stats = assert_pipelined_equals_settled(
        fake_scheduler(engine, max_slots=2), vocab=97)
    assert stats["pipeline_settles"]["nothing_to_launch"] >= 1
    assert stats["steps"] == len(
        [c for c in engine.calls if c[0] == "paged_step"])


def test_pipelined_streams_equal_settled_streams_on_paged_step():
    """The same on the compiled `paged_step` of the tiny transformer,
    sampled, so that the rng rows that stay on the device are part of
    what is compared; one program serves both orders."""
    _model, _params, engine, scheduler = _tiny_serving_stack(
        max_slots=2, temperature=1.0, top_k=8)
    assert_pipelined_equals_settled(scheduler)
    assert engine.stats["paged_step_compiles"] == 1


@pytest.mark.parametrize("seed, words", [
    (0, [0, 0]), (1, [0, 1]), (2 ** 31, [0, 2 ** 31]),
    (2 ** 32 + 5, [0, 5]), (-1, [0, 2 ** 32 - 1]),
])
def test_host_made_key_equals_jax_prngkey(seed, words):
    """`_prng_key` dispatches nothing: it writes down what
    `jax.random.PRNGKey` computes, which this holds it to (the words are
    those of a JAX without 64-bit integers, as the tests run)."""
    import jax

    from tf_yarn_tpu.serving.scheduler import _prng_key

    key = _prng_key(seed)
    assert key.dtype == np.uint32 and type(key) is np.ndarray
    assert key.tolist() == np.asarray(jax.random.PRNGKey(seed)).tolist()
    assert key.tolist() == words


@pytest.mark.parametrize("seed, words", [
    (1, [0, 1]), (2 ** 32 + 5, [1, 5]),
    (-1, [2 ** 32 - 1, 2 ** 32 - 1]),
])
def test_host_made_key_equals_jax_prngkey_with_64_bit_integers(seed, words):
    """Where JAX holds 64-bit integers the seed's upper word is the key's
    first: the other branch of `_prng_key`."""
    import jax

    from tf_yarn_tpu.serving.scheduler import _prng_key

    with jax.enable_x64(True):
        key = _prng_key(seed)
        assert key.tolist() == np.asarray(jax.random.PRNGKey(seed)).tolist()
    assert key.dtype == np.uint32 and key.tolist() == words


def test_a_prng_the_grid_cannot_hold_is_refused_at_start_up():
    """Not inside a tick, where it would fail every live stream once an
    admission."""
    import jax

    with jax.default_prng_impl("rbg"):
        with pytest.raises(ValueError, match="threefry2x32"):
            fake_scheduler(FakePagedEngine(), max_slots=2)


def test_a_launch_keeps_its_uploads_when_the_host_arrays_move_on():
    """The scheduler advances `_lengths` (and at an admission `_tables`,
    `_rngs`) right after a launch, while a backend that aliases host
    memory may not have read the step's arguments yet: every launch is
    handed copies of its own."""
    engine = FakePagedEngine()
    scheduler = fake_scheduler(engine, max_slots=2)
    real_step, seen = engine.paged_step, []

    def recording_step(params, pool, tables, lengths, emitted, rngs, tokens,
                       rng_rows, forced, mask, **kwargs):
        uploads = (tables, lengths, tokens, rng_rows, forced, mask)
        seen.append((uploads, [np.array(a) for a in uploads]))
        return real_step(params, pool, tables, lengths, emitted, rngs,
                         tokens, rng_rows, forced, mask, **kwargs)

    engine.paged_step = recording_step
    responses = [
        scheduler.submit([1, 2, 3, 4, 5 + i], SamplingParams(max_new_tokens=4))
        for i in range(3)
    ]
    _drive(scheduler, responses)
    assert len(seen) >= 6
    for uploads, as_launched in seen:
        for array, snapshot in zip(uploads, as_launched):
            np.testing.assert_array_equal(array, snapshot)
        for array in uploads:
            assert not any(np.shares_memory(array, mine) for mine in (
                scheduler._tables, scheduler._lengths, scheduler._rngs))


def test_a_deadline_with_a_step_in_flight_drops_that_steps_token():
    engine = FakePagedEngine()
    scheduler = fake_scheduler(engine, max_slots=1)
    doomed = scheduler.submit([1, 2, 3, 4, 5],
                              SamplingParams(max_new_tokens=20))
    waiting = scheduler.submit([2, 2, 2, 2, 2],
                               SamplingParams(max_new_tokens=2))
    scheduler.tick()  # launches 15
    scheduler.tick()  # launches 30, reads 15
    assert doomed.token_times and scheduler._flight is not None
    doomed.request.timeout_s = 1e-9
    # The slot and its blocks go to `waiting` in the tick that retires
    # `doomed`, with the dropped step still unread at the admission.
    scheduler.tick()
    assert doomed.finish_reason == FINISH_DEADLINE
    assert doomed.result(timeout=1) == [15]
    assert scheduler.trace[-1]["admitted"] == [waiting.request.id]
    _drive(scheduler, [waiting])
    assert waiting.result(timeout=1) == [10, 20]


def test_close_and_prefix_export_settle_a_step_in_flight():
    """Whoever needs the slots as the device left them empties the
    pipeline by name: block shipping between ticks, and shutdown, which
    hands over what the device had finished before it says `shutdown`."""
    engine, scheduler = _paged_scheduler(max_slots=1)
    response = scheduler.submit([3, 1, 4, 1, 5, 9, 2, 6, 5],
                                SamplingParams(max_new_tokens=20))
    scheduler.tick()
    scheduler.tick()
    assert scheduler._flight is not None and len(response.token_times) == 1
    wire = scheduler.export_hot_prefixes()
    assert wire["n_blocks"] == 2  # the prompt's two whole blocks
    assert scheduler._flight is None and len(response.token_times) == 2
    assert scheduler.stats()["pipeline_settles"]["control_op"] == 1
    scheduler.tick()
    assert scheduler._flight is not None
    scheduler.close()
    assert response.finish_reason == "shutdown"
    assert len(response.result(timeout=1)) == 3
    stats = scheduler.stats()
    assert stats["pipeline_settles"]["shutdown"] == 1
    assert stats["steps"] == 3 and stats["steps_ahead"] == 1
    # the fake's arithmetic: each emission doubles the cache's sum
    assert response.result(timeout=1) == [36, 72, 144 % 97]


# --------------------------------------------------------------------------
# end-to-end on CPU: real engine, real scheduler loop, real HTTP
# --------------------------------------------------------------------------

def _tiny_serving_stack(max_slots=2, kv_cache_dtype="bf16", block_size=8,
                        **scheduler_kwargs):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from tf_yarn_tpu.models import transformer
    from tf_yarn_tpu.models.decode_engine import DecodeEngine

    cfg = transformer.TransformerConfig.tiny(
        scan_layers=False, remat=False, max_seq_len=64, dtype=jnp.float32,
        kv_cache_dtype=kv_cache_dtype,
    )
    model = transformer.Transformer(cfg)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    )
    engine = DecodeEngine(
        model, batch_buckets=(1, 2, 4), prompt_buckets=(4, 8, 16)
    )
    scheduler = SlotScheduler(
        engine, params, max_slots=max_slots, block_size=block_size,
        **scheduler_kwargs
    )
    return model, params, engine, scheduler


def _post(port, body, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            "POST", "/v1/generate", json.dumps(body),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def _legacy_stream(model, params, prompt, max_new, eos=None):
    """generate_legacy's per-request token stream: the generated row,
    truncated at the first eos inclusive (the serving stream stops
    there; legacy pads repeated eos to full width)."""
    import jax.numpy as jnp

    from tf_yarn_tpu.models.generate import generate_legacy

    out = generate_legacy(
        model, params, jnp.asarray([prompt], jnp.int32), max_new,
        temperature=0.0, eos_token=eos,
    )
    row = np.asarray(out)[0, len(prompt):].tolist()
    if eos is not None and eos in row:
        row = row[:row.index(eos) + 1]
    return row


@pytest.mark.slow  # tier-1 budget: the HTTP e2e is represented by
# test_run_serving_task_body_advertises_and_serves (the stack through
# the real frontend) + the engine-level legacy parity in
# test_short_and_padded_admissions_match_legacy; the HTTP-streams-match-legacy
# bar stays in tier-1 via test_kv_oversubscription.py::
# test_http_suspend_resume_stream_matches_legacy_fp_greedy.
def test_http_end_to_end_matches_legacy_with_slot_reuse():
    """The acceptance bar: 3 concurrent requests with different prompt
    and output lengths through the real HTTP frontend produce token
    streams bit-identical to generate_legacy, while the slot freed by
    the early-EOS request is re-admitted before the longest request
    finishes (asserted from the scheduler tick trace)."""
    model, params, _engine, scheduler = _tiny_serving_stack(max_slots=2)
    scheduler.start()
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    try:
        rng = np.random.RandomState(0)
        prompts = [
            rng.randint(0, 256, (5,)).tolist(),
            rng.randint(0, 256, (9,)).tolist(),
            rng.randint(0, 256, (3,)).tolist(),
        ]
        # eos for request 0 = its first greedy token: finishes at once.
        eos0 = _legacy_stream(model, params, prompts[0], 8)[0]
        bodies = [
            {"prompt": prompts[0], "max_new_tokens": 8, "eos_token": eos0},
            {"prompt": prompts[1], "max_new_tokens": 12},
            {"prompt": prompts[2], "max_new_tokens": 6},
        ]
        results = {}

        def call(index):
            results[index] = _post(server.port, bodies[index])

        threads = [
            threading.Thread(target=call, args=(i,)) for i in range(3)
        ]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=300)
        request_ids = {}
        for index, body in enumerate(bodies):
            status, _headers, raw = results[index]
            assert status == 200, raw
            payload = json.loads(raw)
            expected = _legacy_stream(
                model, params, body["prompt"], body["max_new_tokens"],
                body.get("eos_token"),
            )
            assert payload["tokens"] == expected, index
            request_ids[index] = payload["request_id"]
        assert json.loads(results[0][2])["finish_reason"] == "eos"
        assert json.loads(results[1][2])["finish_reason"] == "length"

        # Slot-reuse ordering from the tick trace: request 0 retires,
        # some request is admitted into the freed slot on a LATER tick,
        # and the 12-token request finishes after that admission.
        trace = list(scheduler.trace)
        retire0 = next(
            t["tick"] for t in trace
            if (request_ids[0], "eos") in t["retired"]
        )
        late_admits = [
            t["tick"] for t in trace if t["tick"] > retire0 and t["admitted"]
        ]
        long_finish = next(
            t["tick"] for t in trace
            if (request_ids[1], "length") in t["retired"]
        )
        assert late_admits, "no admission after the early-EOS retire"
        assert late_admits[0] < long_finish
        from tf_yarn_tpu import telemetry

        assert telemetry.get_registry().counter(
            "serving/slot_reuse_total"
        ).value >= 1
    finally:
        server.stop()
        scheduler.close()


def test_paged_http_end_to_end_matches_legacy_with_prefix_hit():
    """The paged acceptance bar: concurrent requests through the real
    HTTP frontend — with a pool sized BELOW every slot at full
    context — produce token streams bit-identical to
    generate_legacy; a follow-up request repeating a prompt admits
    through the prefix cache (no second prefill) and still matches."""
    model, params, engine, scheduler = _tiny_serving_stack(
        max_slots=2,
        # Every slot at full context would be 2 * 64/8 + 1 = 17; run tighter.
        num_blocks=11,
    )
    scheduler.start()
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    try:
        rng = np.random.RandomState(3)
        prompts = [
            rng.randint(0, 256, (5,)).tolist(),
            rng.randint(0, 256, (9,)).tolist(),
            rng.randint(0, 256, (3,)).tolist(),
        ]
        bodies = [
            {"prompt": prompts[0], "max_new_tokens": 8},
            {"prompt": prompts[1], "max_new_tokens": 12},
            {"prompt": prompts[2], "max_new_tokens": 6},
        ]
        results = {}

        def call(index):
            results[index] = _post(server.port, bodies[index])

        threads = [
            threading.Thread(target=call, args=(i,)) for i in range(3)
        ]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=300)
        for index, body in enumerate(bodies):
            status, _headers, raw = results[index]
            assert status == 200, raw
            expected = _legacy_stream(
                model, params, body["prompt"], body["max_new_tokens"]
            )
            assert json.loads(raw)["tokens"] == expected, index

        # Repeat request 1's prompt: its prefill (8 tokens = 1 block at
        # block_size 8) is in the prefix cache — the admission skips
        # prefill and the stream stays bit-identical.
        prefill_calls = engine.stats["prefill_compiles"] \
            + engine.stats["prefill_cache_hits"]
        status, _headers, raw = _post(server.port, bodies[1])
        assert status == 200
        assert json.loads(raw)["tokens"] == _legacy_stream(
            model, params, prompts[1], 12
        )
        assert (engine.stats["prefill_compiles"]
                + engine.stats["prefill_cache_hits"]) == prefill_calls

        # /stats exposes the paged telemetry surface.
        conn = http.client.HTTPConnection(
            "127.0.0.1", server.port, timeout=30
        )
        conn.request("GET", "/stats")
        stats = json.loads(conn.getresponse().read())
        conn.close()
        assert stats["kv_layout"] == "paged"
        assert stats["kv_cache_hbm_bytes"] > 0
        assert stats["block_pool"]["num_blocks"] == 11
        assert stats["prefix_cache"]["hits"] >= 1
        assert stats["decode_engine"]["paged_step_compiles"] == 1
    finally:
        server.stop()
        scheduler.close()


# [11]: the prefill_len == 0 admission path, the whole prompt (its one
# token) through the step from an empty slot. [11, 23]: shorter than the
# smallest bucket, so one kept row in a bucket of 4 and the last token
# replayed. The others: one under, at and one over the bucket of 8.
@pytest.mark.parametrize("prompt", [
    [11], [11, 23], list(range(30, 38)), list(range(30, 39)),
    list(range(30, 40))], ids=lambda p: f"len{len(p)}")
def test_short_and_padded_admissions_match_legacy(prompt):
    """The scheduler over the real engine, which reads the ceiling rule
    off this dense causal model: streams stay bit-equal to
    generate_legacy, including when the slot was dirtied by an earlier
    longer request (stale rows past the kept ones, beside the pad's)."""
    model, params, _engine, scheduler = _tiny_serving_stack(max_slots=1)
    try:
        # Dirty the single slot first so the replay-from-empty path has
        # to prove it does not inherit stale cache state.
        dirty = scheduler.submit([7] * 9, SamplingParams(max_new_tokens=4))
        for _ in range(400):
            scheduler.tick()
            if dirty.done:
                break
        response = scheduler.submit(
            prompt, SamplingParams(max_new_tokens=6)
        )
        for _ in range(400):
            scheduler.tick()
            if response.done:
                break
        assert response.result(timeout=1) == _legacy_stream(
            model, params, prompt, 6
        )
        stats = scheduler.stats()
        # The dirtying request kept 8 of the bucket of 8; then ours.
        kept = len(prompt) - 1
        bucket = min([b for b in (4, 8, 16) if b >= kept]) if kept else 0
        assert stats["prefills_ceiling"] == 1 + bool(kept)
        assert stats["prefills_floor"] == 0
        assert stats["prefill_pad_tokens"] == bucket - kept
        assert stats["prefilled_tokens"] == 8 + kept
        assert stats["prefill_tokens"] == 1 + 1  # one replayed token each
    finally:
        scheduler.close()


@pytest.mark.slow  # tier-1 keeps int8 parity at the engine level
# (test_paged_step_int8_matches_int8_legacy); this serving-layer twin
# runs in the full sweep
def test_paged_int8_serving_matches_int8_legacy():
    """int8 KV through the paged serving stack: the pool pages the int8
    values + scales leaves transparently and streams stay bit-equal to
    the int8 legacy path (int8-vs-fp accuracy itself is bounded by
    tests/test_decode_engine.py::test_int8_prefill_logits_close_to_fp)."""
    model, params, _engine, scheduler = _tiny_serving_stack(
        max_slots=2, kv_cache_dtype="int8",
    )
    try:
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, 256, (9,)).tolist(),
                   rng.randint(0, 256, (5,)).tolist()]
        responses = [
            scheduler.submit(p, SamplingParams(max_new_tokens=5))
            for p in prompts
        ]
        for _ in range(400):
            scheduler.tick()
            if all(r.done for r in responses):
                break
        for prompt, response in zip(prompts, responses):
            assert response.result(timeout=1) == _legacy_stream(
                model, params, prompt, 5
            )
    finally:
        scheduler.close()


def test_context_overflow_rejected_400_and_loop_survives():
    """Regression: a prompt + max_new_tokens beyond max_seq_len must be
    rejected 400 AT ADMISSION — the engine's ValueError used to fire
    mid-tick inside the scheduler thread and could kill the serving
    loop. After the rejection the server must still serve."""
    model, params, _engine, scheduler = _tiny_serving_stack(max_slots=1)
    scheduler.start()
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    try:
        # max_seq_len is 64: 30 prompt + 40 new = 70 overflows.
        status, _headers, raw = _post(
            server.port, {"prompt": [1] * 30, "max_new_tokens": 40}
        )
        assert status == 400, raw
        assert b"context limit" in raw
        # Direct submits are guarded too (not just the HTTP layer).
        with pytest.raises(ValueError, match="max_seq_len"):
            scheduler.submit([1] * 30, SamplingParams(max_new_tokens=40))
        # The loop is alive: a well-formed request round-trips.
        prompt = [1, 2, 3]
        status, _headers, raw = _post(
            server.port, {"prompt": prompt, "max_new_tokens": 3}
        )
        assert status == 200, raw
        assert json.loads(raw)["tokens"] == _legacy_stream(
            model, params, prompt, 3
        )
    finally:
        server.stop()
        scheduler.close()


def test_http_streaming_backpressure_health_and_stats():
    model, params, _engine, scheduler = _tiny_serving_stack(
        max_slots=1, queue_capacity=1, retry_after_s=2.0,
    )
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    try:
        prompt = [1, 2, 3]
        expected = _legacy_stream(model, params, prompt, 4)

        # Backpressure, made deterministic: the scheduler loop is NOT
        # running yet, so the held request stays queued — the single
        # queue seat is provably occupied when the second arrives.
        held = {}
        hold = threading.Thread(
            target=lambda: held.update(
                zip(("status", "headers", "raw"),
                    _post(server.port,
                          {"prompt": prompt, "max_new_tokens": 4}))
            )
        )
        hold.start()
        deadline = time.monotonic() + 30
        while scheduler.queue.depth < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert scheduler.queue.depth == 1
        status, headers, raw = _post(
            server.port, {"prompt": prompt, "max_new_tokens": 4}
        )
        assert status == 429, raw
        assert headers.get("Retry-After") == "2"
        assert json.loads(raw)["retry_after_s"] == 2.0

        # Start the loop: the held request drains and succeeds.
        scheduler.start()
        hold.join(timeout=300)
        assert held["status"] == 200
        assert json.loads(held["raw"])["tokens"] == expected

        # Streaming: chunked JSON lines, one per token, then a summary.
        conn = http.client.HTTPConnection(
            "127.0.0.1", server.port, timeout=300
        )
        conn.request(
            "POST", "/v1/generate",
            json.dumps({"prompt": prompt, "max_new_tokens": 4,
                        "stream": True}),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        lines = [json.loads(line) for line in resp.read().splitlines()]
        conn.close()
        assert [l["token"] for l in lines if "token" in l] == expected
        assert lines[-1]["done"] and lines[-1]["finish_reason"] == "length"

        # Bad request: sampling-config mismatch -> 400, not a recompile.
        status, _headers, raw = _post(
            server.port,
            {"prompt": prompt, "max_new_tokens": 4, "temperature": 0.9},
        )
        assert status == 400 and b"temperature" in raw

        # Health + stats.
        conn = http.client.HTTPConnection(
            "127.0.0.1", server.port, timeout=30
        )
        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        assert health["status"] == "ok"
        conn.request("GET", "/stats")
        stats = json.loads(conn.getresponse().read())
        conn.close()
        assert stats["max_slots"] == 1
        assert stats["decode_engine"]["paged_step_compiles"] >= 1
        assert stats["ticks"] >= 1
    finally:
        server.stop()
        scheduler.close()


def test_healthz_reports_draining_not_ok_after_drain_notice():
    """Regression: /healthz kept answering {"status": "ok"} after the
    preemption-drain notice fired — the window where a load balancer
    (the fleet router's registry) keeps routing to a replica about to
    vanish. Both drain signals must flip it: the scheduler's drain flag
    (run_serving sets it on its poll) and the preemption flag itself
    (visible the instant the signal lands, before any poll)."""
    from tf_yarn_tpu import preemption

    def healthz(port):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        try:
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read())
        finally:
            conn.close()

    scheduler = fake_scheduler(FakePagedEngine(), max_slots=1)
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    try:
        status, health = healthz(server.port)
        assert status == 200 and health["status"] == "ok"
        assert scheduler.stats()["draining"] is False
        scheduler.drain()
        status, health = healthz(server.port)
        assert status == 200 and health["status"] == "draining"
        assert scheduler.stats()["draining"] is True
    finally:
        server.stop()
        scheduler.close()

    # The raw preemption flag flips /healthz too — no poll loop needed.
    scheduler = fake_scheduler(FakePagedEngine(), max_slots=1)
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    try:
        assert healthz(server.port)[1]["status"] == "ok"
        preemption.request()
        try:
            assert healthz(server.port)[1]["status"] == "draining"
        finally:
            preemption.reset()
    finally:
        server.stop()
        scheduler.close()


def test_run_serving_task_body_advertises_and_serves(monkeypatch):
    """The serving task body end-to-end: restore (patched), engine,
    scheduler, frontend, KV endpoint advertisement, preemption-drain
    shutdown — the path tasks/serving.py drives."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from tf_yarn_tpu import inference as inference_mod
    from tf_yarn_tpu import preemption
    from tf_yarn_tpu.coordination.kv import InProcessKV
    from tf_yarn_tpu.experiment import ServingExperiment
    from tf_yarn_tpu.models import transformer
    from tf_yarn_tpu.models.decode_engine import clear_engines
    from tf_yarn_tpu.serving.server import run_serving
    from tf_yarn_tpu.topologies import TaskKey

    cfg = transformer.TransformerConfig.tiny(
        scan_layers=False, remat=False, max_seq_len=64, dtype=jnp.float32
    )
    model = transformer.Transformer(cfg)
    variables = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), jnp.zeros((2, 5), jnp.int32))
    )
    monkeypatch.setattr(
        inference_mod, "_restore_params",
        lambda model_dir, step: (variables, 3),
    )
    clear_engines()

    class _Runtime:
        kv = InProcessKV()
        task_key = TaskKey("serving", 0)
        task = "serving:0"

    runtime = _Runtime()
    experiment = ServingExperiment(
        model=model, model_dir="/nonexistent-restore-is-patched",
        host="127.0.0.1", max_slots=2,
    )
    result = {}

    def serve():
        result["stats"] = run_serving(experiment, runtime=runtime)

    thread = threading.Thread(target=serve)
    thread.start()
    try:
        endpoint = runtime.kv.wait_str(
            "serving:0/serving_endpoint", timeout=60
        )
        port = int(endpoint.rsplit(":", 1)[1])
        prompt = [1, 2, 3]
        status, _headers, raw = _post(
            port, {"prompt": prompt, "max_new_tokens": 3}
        )
        assert status == 200
        assert json.loads(raw)["tokens"] == _legacy_stream(
            model, variables, prompt, 3
        )
    finally:
        preemption.request()  # the drain flag run_serving polls
        thread.join(timeout=120)
        preemption.reset()
    assert not thread.is_alive()
    assert result["stats"]["ckpt_step"] == 3
    assert result["stats"]["endpoint"].endswith(str(port))
    clear_engines()


def test_serving_experiment_validates():
    from tf_yarn_tpu.experiment import ServingExperiment

    with pytest.raises(ValueError, match="max_slots"):
        ServingExperiment(model=None, model_dir="x", max_slots=0)
    with pytest.raises(ValueError, match="queue_capacity"):
        ServingExperiment(model=None, model_dir="x", queue_capacity=0)
    with pytest.raises(ValueError, match="serve_seconds"):
        ServingExperiment(model=None, model_dir="x", serve_seconds=-1)
    with pytest.raises(ValueError, match="block_size"):
        ServingExperiment(model=None, model_dir="x", block_size=0)
    with pytest.raises(ValueError, match="num_blocks"):
        ServingExperiment(model=None, model_dir="x", num_blocks=1)
    with pytest.raises(ValueError, match="prefix_cache_capacity"):
        ServingExperiment(model=None, model_dir="x",
                          prefix_cache_capacity=-1)


def test_scheduler_has_one_layout():
    """The KV layout is not a setting: the constructors refuse the old
    knob, the wire still says what the layout is, and the dense slot
    grid's programs are gone from the engine."""
    import dataclasses

    from tf_yarn_tpu.experiment import ServingExperiment
    from tf_yarn_tpu.models import decode_engine

    with pytest.raises(TypeError, match="kv_layout"):
        fake_scheduler(FakePagedEngine(), kv_layout="paged")
    with pytest.raises(TypeError, match="kv_layout"):
        ServingExperiment(model=None, model_dir="x", kv_layout="paged")
    assert "kv_layout" not in {
        f.name for f in dataclasses.fields(ServingExperiment)}
    assert fake_scheduler(FakePagedEngine()).stats()["kv_layout"] == "paged"
    for gone in ("build_step_fn", "build_spec_step_fn"):
        assert not hasattr(decode_engine, gone)
    for gone in ("make_slot_cache", "insert_slot", "evict_slot", "step",
                 "spec_step"):
        assert not hasattr(decode_engine.DecodeEngine, gone)


# --------------------------------------------------------------------------
# launcher wiring
# --------------------------------------------------------------------------

def test_serving_task_type_wiring():
    from tf_yarn_tpu import _env
    from tf_yarn_tpu.backends import PRIMARY_TASK_TYPES
    from tf_yarn_tpu.topologies import check_topology, serving_topology

    assert _env.gen_task_module("serving") == "tf_yarn_tpu.tasks.serving"
    assert (
        _env.gen_task_module("serving", "my.custom.module")
        == "my.custom.module"
    )
    # A crashed server must fail (and relaunch) the run.
    assert "serving" in PRIMARY_TASK_TYPES
    specs = serving_topology(instances=3, chips_per_host=1)
    check_topology(specs)  # serving-only topologies are valid
    assert specs["serving"].instances == 3
    with pytest.raises(ValueError, match="instances"):
        serving_topology(instances=0)
