"""KV oversubscription: host-RAM block swap + SLO-tiered scheduling.

Three layers, mirroring tests/test_serving.py's seam:

* Host-side units with no device in sight: the :class:`HostBlockStore`
  capacity ledger, the load-aware :class:`RetryAfterEstimator`, and SLO
  tier ordering/caps through the :class:`AdmissionQueue`.
* The suspend/resume lifecycle on the deterministic fake paged engine:
  a lower-tier stream is swapped out to host RAM under pool pressure,
  an interactive request takes its blocks, and the parked stream
  resumes BIT-IDENTICAL to an uninterrupted run — including through a
  prefix-cache hit whose physical blocks changed while it was parked.
  The refcount invariant (every block's refcount == slot occupancy +
  prefix-entry membership) is asserted after the storm.
* End-to-end on CPU through the real HTTP frontend: a suspended-then-
  resumed stream matches `generate_legacy` token for token, with the
  sampled + int8 matrix behind the `slow` marker (the in-suite fp
  greedy run is the representative).
"""

import http.client
import json
import threading
import time

import numpy as np
import pytest

from tf_yarn_tpu.serving import (
    FINISH_DEADLINE,
    FINISH_LENGTH,
    FINISH_SHUTDOWN,
    AdmissionQueue,
    HostBlockStore,
    QueueFull,
    Request,
    RetryAfterEstimator,
    SamplingParams,
    ServingServer,
    SlotScheduler,
    tier_rank,
)
from tf_yarn_tpu.serving.paging import TRASH_BLOCK

from tests.fakes import FakePagedEngine, fake_scheduler
from tests.test_serving import (
    _drive,
    _legacy_stream,
    _paged_scheduler,
    _post,
    _tiny_serving_stack,
)


# --------------------------------------------------------------------------
# HostBlockStore: the host-RAM capacity ledger
# --------------------------------------------------------------------------

def test_host_block_store_accounting_and_errors():
    store = HostBlockStore(capacity_blocks=4, block_size=8)
    assert store.free_blocks == 4 and store.used_blocks == 0
    assert store.can_hold(4) and not store.can_hold(5)
    store.put("a", 3, payload={"kv": "opaque"})
    assert "a" in store and store.entries == 1
    assert store.used_blocks == 3 and store.free_blocks == 1
    # Duplicate key and over-capacity are bookkeeping bugs, not policy.
    with pytest.raises(ValueError, match="already holds"):
        store.put("a", 1, payload=None)
    with pytest.raises(ValueError, match="over capacity"):
        store.put("b", 2, payload=None)
    store.put("b", 1, payload=None)
    n_blocks, payload = store.pop("a")
    assert n_blocks == 3 and payload == {"kv": "opaque"}
    assert "a" not in store and store.free_blocks == 3
    # A zero-block entry (suspended before any KV landed) is legal.
    store.put("c", 0, payload=None)
    assert store.pop("c") == (0, None)
    with pytest.raises(ValueError, match="capacity_blocks"):
        HostBlockStore(capacity_blocks=-1, block_size=8)
    with pytest.raises(ValueError, match="block_size"):
        HostBlockStore(capacity_blocks=4, block_size=0)


# --------------------------------------------------------------------------
# Load-aware Retry-After
# --------------------------------------------------------------------------

def test_retry_after_estimator_rate_floor_and_window():
    est = RetryAfterEstimator(floor_s=2.0, window_s=10.0)
    # No retirements observed -> the static floor, any depth.
    assert est.estimate(5, now=100.0) == 2.0
    est.record_retire("standard", now=100.0)
    est.record_retire("batch", now=104.0)
    # Rate counts ALL tiers: 2 events / 10s window = 0.2/s.
    assert est.retire_rate(now=105.0) == pytest.approx(0.2)
    # depth / rate, clamped to the floor.
    assert est.estimate(4, now=105.0) == pytest.approx(20.0)
    assert est.estimate(0, now=105.0) == 2.0
    # Events age out of the sliding window -> back to the floor.
    assert est.retire_rate(now=120.0) == 0.0
    assert est.estimate(4, now=120.0) == 2.0
    with pytest.raises(ValueError, match="window_s"):
        RetryAfterEstimator(window_s=0)
    with pytest.raises(ValueError, match="tier"):
        est.record_retire("bulk")


def test_queue_full_hint_scales_with_tier_depth_over_retire_rate():
    est = RetryAfterEstimator(floor_s=1.0, window_s=10.0)
    queue = AdmissionQueue(capacity=2, retry_after_s=1.0, estimator=est)
    queue.submit(Request(prompt=(1,), tier="interactive"))
    queue.submit(Request(prompt=(2,), tier="batch"))
    now = time.monotonic()
    est.record_retire("standard", now=now)
    est.record_retire("standard", now=now)  # rate = 0.2/s
    # A batch reject queues behind BOTH entries: 2 / 0.2 = 10s; an
    # interactive reject only behind its own tier's peer: 1 / 0.2 = 5s.
    with pytest.raises(QueueFull) as exc:
        queue.submit(Request(prompt=(3,), tier="batch"))
    assert exc.value.retry_after_s == pytest.approx(10.0, rel=0.05)
    with pytest.raises(QueueFull) as exc:
        queue.submit(Request(prompt=(4,), tier="interactive"))
    assert exc.value.retry_after_s == pytest.approx(5.0, rel=0.05)
    # retry_hint mirrors the same computation for the tier-cap path.
    assert queue.retry_hint(
        Request(prompt=(5,), tier="batch")
    ) == pytest.approx(10.0, rel=0.05)


def test_http_429_retry_after_header_tracks_recent_retire_rate():
    """The 429's Retry-After must reflect queue depth over the recent
    retire rate — not the static hint — once retirements are flowing,
    and clamp back to the static floor when the rate is high."""
    scheduler = fake_scheduler(
        FakePagedEngine(), max_slots=1, queue_capacity=1,
        retry_after_s=2.0,
    )
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    try:
        # The loop is NOT running: the first request provably occupies
        # the single queue seat when the second arrives.
        scheduler.submit([1, 2, 3], SamplingParams(max_new_tokens=1))
        # 4 retirements in the 30s window -> rate 4/30, 1 ahead ->
        # estimate 7.5s (above the 2.0 floor).
        for _ in range(4):
            scheduler._estimator.record_retire()
        status, headers, raw = _post(
            server.port, {"prompt": [1, 2, 3], "max_new_tokens": 1}
        )
        assert status == 429, raw
        assert json.loads(raw)["retry_after_s"] == pytest.approx(
            7.5, rel=0.05
        )
        assert headers.get("Retry-After") == "7"
        # Flood the window with retirements: the estimate falls below
        # the static floor and clamps to it.
        for _ in range(300):
            scheduler._estimator.record_retire()
        status, headers, raw = _post(
            server.port, {"prompt": [1, 2, 3], "max_new_tokens": 1}
        )
        assert status == 429, raw
        assert json.loads(raw)["retry_after_s"] == 2.0
        assert headers.get("Retry-After") == "2"
    finally:
        server.stop()
        scheduler.close()


# --------------------------------------------------------------------------
# SLO tiers: ordering, caps, validation
# --------------------------------------------------------------------------

def test_tier_ordering_beats_priority_across_tiers():
    assert tier_rank("interactive") > tier_rank("standard") > \
        tier_rank("batch")
    with pytest.raises(ValueError, match="tier"):
        tier_rank("bulk")
    queue = AdmissionQueue(capacity=8)
    batch_hi = queue.submit(Request(prompt=(1,), tier="batch", priority=9))
    standard = queue.submit(Request(prompt=(2,)))
    interactive = queue.submit(
        Request(prompt=(3,), tier="interactive", priority=0)
    )
    batch_lo = queue.submit(Request(prompt=(4,), tier="batch"))
    # Tier first; priority settles ties only WITHIN a tier.
    assert [queue.pop()[1] for _ in range(4)] == [
        interactive, standard, batch_hi, batch_lo
    ]


def test_tier_cap_bounds_in_system_footprint_and_releases_on_retire():
    engine, scheduler = _paged_scheduler(tier_caps={"batch": 1})
    first = scheduler.submit(
        [1, 2, 3, 4, 5], SamplingParams(max_new_tokens=3), tier="batch"
    )
    with pytest.raises(QueueFull):
        scheduler.submit(
            [2, 2, 2, 2, 2], SamplingParams(max_new_tokens=3), tier="batch"
        )
    # Other tiers are untouched by batch's cap.
    standard = scheduler.submit(
        [3, 3, 3, 3, 3], SamplingParams(max_new_tokens=3)
    )
    _drive(scheduler, [first, standard])
    # The retirement released the cap: batch admits again.
    again = scheduler.submit(
        [4, 4, 4, 4, 4], SamplingParams(max_new_tokens=3), tier="batch"
    )
    _drive(scheduler, [again])
    assert again.finish_reason == FINISH_LENGTH
    stats = scheduler.stats()
    assert stats["tiers"]["caps"] == {"batch": 1}
    assert stats["tiers"]["inflight"] == {}


def test_unknown_tier_rejected_at_submit():
    _engine, scheduler = _paged_scheduler()
    with pytest.raises(ValueError, match="tier"):
        scheduler.submit(
            [1, 2, 3], SamplingParams(max_new_tokens=1), tier="bulk"
        )


def test_serving_experiment_validates_oversubscription_knobs():
    from tf_yarn_tpu.experiment import ServingExperiment

    ok = ServingExperiment(
        model=None, model_dir="/tmp/x", kv_host_blocks=8,
        tier_caps={"batch": 4},
    )
    assert ok.kv_host_blocks == 8
    with pytest.raises(ValueError, match="kv_host_blocks"):
        ServingExperiment(model=None, model_dir="/tmp/x", kv_host_blocks=-1)
    with pytest.raises(ValueError, match="tier"):
        ServingExperiment(
            model=None, model_dir="/tmp/x", tier_caps={"bulk": 4}
        )
    with pytest.raises(ValueError, match="cap"):
        ServingExperiment(
            model=None, model_dir="/tmp/x", tier_caps={"batch": -1}
        )


# --------------------------------------------------------------------------
# Suspend / resume on the fake paged engine
# --------------------------------------------------------------------------

def _oversubscribed(max_slots=2, num_blocks=5, kv_host_blocks=8, **kwargs):
    """Pool of (num_blocks - 1) usable blocks: one 8-token/6-new request
    needs ceil(13/4) = 4 — exactly the default pool, so a second stream
    of any tier must either wait or displace the first."""
    return _paged_scheduler(
        max_slots=max_slots, num_blocks=num_blocks,
        kv_host_blocks=kv_host_blocks, **kwargs,
    )


BATCH_PROMPT = [1, 2, 3, 4, 5, 6, 7, 8]
INTER_PROMPT = [2, 3, 4, 5, 6, 7, 8, 9]


def _solo_stream(prompt, max_new=6, tier="batch"):
    """The uninterrupted reference: same request, fresh uncontended
    scheduler."""
    _engine, scheduler = _oversubscribed()
    response = scheduler.submit(
        prompt, SamplingParams(max_new_tokens=max_new), tier=tier
    )
    _drive(scheduler, [response])
    return response.result(timeout=1)


def test_interactive_suspends_batch_then_resumes_bit_identical():
    """The tentpole contract: under pool pressure the interactive
    request SUSPENDS the batch stream (swap-out to host) instead of
    queueing behind it; the batch stream resumes after the interactive
    retires and its tokens are bit-identical to an uninterrupted run."""
    engine, scheduler = _oversubscribed()
    batch = scheduler.submit(
        BATCH_PROMPT, SamplingParams(max_new_tokens=6), tier="batch"
    )
    for _ in range(3):
        scheduler.tick()
    assert not batch.done
    interactive = scheduler.submit(
        INTER_PROMPT, SamplingParams(max_new_tokens=6), tier="interactive"
    )
    _drive(scheduler, [batch, interactive])
    assert batch.result(timeout=1) == _solo_stream(BATCH_PROMPT)
    assert interactive.result(timeout=1) == _solo_stream(
        INTER_PROMPT, tier="interactive"
    )
    # The interactive stream was served FIRST: it retired before the
    # displaced batch stream.
    retire_order = [
        rid for t in scheduler.trace for (rid, _reason) in t["retired"]
    ]
    assert retire_order.index(interactive.request.id) < \
        retire_order.index(batch.request.id)
    stats = scheduler.stats()
    assert stats["swap"] == {
        "suspends": 1, "resumes": 1,
        # length 7 at suspension -> 2 valid blocks out; no prefix hit
        # on resume -> the same 2 back in.
        "swap_out_blocks": 2, "swap_in_blocks": 2,
    }
    # 2 streams in flight on 1 stream's worth of device blocks.
    assert stats["peak_streams"] == 2
    assert stats["host_block_store"]["used_blocks"] == 0
    assert stats["suspended_streams"] == {}
    kinds = [c[0] for c in engine.calls]
    assert kinds.count("extract") == 1 and kinds.count("inject") == 1
    # The batch stream's third step was still unread when the interactive
    # request needed its blocks: the suspension read it first, by name.
    assert stats["pipeline_settles"]["suspend"] == 1


def test_a_stream_suspended_mid_decode_resumes_on_its_own_token_and_row():
    """A stream parked after its first sampled step holds its rng row on
    the device and its last token in a step the host had not read: the
    suspension settles, saves both, and the resumed stream's first step
    takes them from the host again (`refeed`), not another request's
    from the device."""
    engine, scheduler = _oversubscribed()
    batch = scheduler.submit(
        BATCH_PROMPT, SamplingParams(max_new_tokens=6), tier="batch"
    )
    for _ in range(5):  # four replayed tokens, then one more sampled step
        scheduler.tick()
    assert len(batch.token_times) == 1 and scheduler._flight is not None
    assert not scheduler._rngs.any()  # the rows the host admitted with
    scheduler._fed[1][0] = (7, 9)  # the fake's device row, as a step left it
    interactive = scheduler.submit(
        INTER_PROMPT, SamplingParams(max_new_tokens=6), tier="interactive"
    )
    scheduler.tick()
    (parked,) = scheduler._suspended
    assert parked.rng.tolist() == [7, 9]
    assert len(batch.token_times) == 2  # the settle delivered the second
    _drive(scheduler, [batch, interactive])
    assert batch.result(timeout=1) == _solo_stream(BATCH_PROMPT)
    assert [7, 9] in scheduler._rngs.tolist()  # forced at the resume


def test_without_host_blocks_pressure_holds_instead_of_suspending():
    """kv_host_blocks=0 (the default) preserves hold-until-free: same
    pressure, no suspend, the interactive request waits for retirement."""
    engine, scheduler = _oversubscribed(kv_host_blocks=0)
    batch = scheduler.submit(
        BATCH_PROMPT, SamplingParams(max_new_tokens=6), tier="batch"
    )
    for _ in range(3):
        scheduler.tick()
    interactive = scheduler.submit(
        INTER_PROMPT, SamplingParams(max_new_tokens=6), tier="interactive"
    )
    _drive(scheduler, [batch, interactive])
    retire_order = [
        rid for t in scheduler.trace for (rid, _reason) in t["retired"]
    ]
    # Held, not displaced: batch finishes first, no swap machinery ran.
    assert retire_order.index(batch.request.id) < \
        retire_order.index(interactive.request.id)
    assert "swap" not in scheduler.stats()
    kinds = [c[0] for c in engine.calls]
    assert "extract" not in kinds and "inject" not in kinds


def test_victim_is_youngest_of_lowest_tier():
    """Two batch streams + pressure: the YOUNGEST batch stream (least
    sunk work) is the victim, never the interactive peer."""
    engine, scheduler = _paged_scheduler(
        max_slots=3, num_blocks=9, kv_host_blocks=16,
    )
    older = scheduler.submit(
        BATCH_PROMPT, SamplingParams(max_new_tokens=6), tier="batch"
    )
    scheduler.tick()
    younger = scheduler.submit(
        [8, 7, 6, 5, 4, 3, 2, 1], SamplingParams(max_new_tokens=6),
        tier="batch",
    )
    scheduler.tick()
    interactive = scheduler.submit(
        INTER_PROMPT, SamplingParams(max_new_tokens=6), tier="interactive"
    )
    scheduler.tick()
    assert [e.request.id for e in scheduler._suspended] == \
        [younger.request.id]
    _drive(scheduler, [older, younger, interactive])
    assert scheduler.stats()["swap"]["suspends"] == 1
    # All three streams match their uncontended selves.
    solo = _solo_stream([8, 7, 6, 5, 4, 3, 2, 1])
    assert younger.result(timeout=1) == solo


def test_deadline_retires_suspended_stream_and_frees_host_blocks():
    engine, scheduler = _oversubscribed()
    batch = scheduler.submit(
        BATCH_PROMPT, SamplingParams(max_new_tokens=6), tier="batch",
        timeout_s=0.15,
    )
    for _ in range(3):
        scheduler.tick()
    interactive = scheduler.submit(
        INTER_PROMPT, SamplingParams(max_new_tokens=6), tier="interactive"
    )
    scheduler.tick()
    assert len(scheduler._suspended) == 1
    time.sleep(0.2)
    scheduler.tick()
    assert batch.done and batch.finish_reason == FINISH_DEADLINE
    stats = scheduler.stats()
    assert stats["host_block_store"]["used_blocks"] == 0
    assert stats["host_block_store"]["entries"] == 0
    _drive(scheduler, [interactive])


def test_close_fails_suspended_stream_as_shutdown():
    engine, scheduler = _oversubscribed()
    batch = scheduler.submit(
        BATCH_PROMPT, SamplingParams(max_new_tokens=6), tier="batch"
    )
    for _ in range(3):
        scheduler.tick()
    scheduler.submit(
        INTER_PROMPT, SamplingParams(max_new_tokens=6), tier="interactive"
    )
    scheduler.tick()
    assert len(scheduler._suspended) == 1
    scheduler.close()
    assert batch.finish_reason == FINISH_SHUTDOWN
    assert scheduler.stats()["host_block_store"]["entries"] == 0


# --------------------------------------------------------------------------
# Prefix cache x swap pressure
# --------------------------------------------------------------------------

def _refcount_invariant(scheduler):
    """Every non-trash block's refcount == (1 if held by an active
    slot's table) + (number of prefix entries containing it)."""
    pool = scheduler._blocks
    membership = {}
    for ids in scheduler._prefix._entries.values():
        for block in ids:
            membership[block] = membership.get(block, 0) + 1
    slot_holds = {}
    for state in scheduler._slots:
        if state is not None and state.blocks:
            for block in state.blocks:
                slot_holds[block] = slot_holds.get(block, 0) + 1
    for block in range(1, pool.num_blocks):
        expected = membership.get(block, 0) + slot_holds.get(block, 0)
        assert pool.refcount(block) == expected, (
            f"block {block}: refcount {pool.refcount(block)} != "
            f"{expected} (prefix {membership.get(block, 0)} + slots "
            f"{slot_holds.get(block, 0)})"
        )


class _GuardedPagedEngine(FakePagedEngine):
    """Asserts at swap-in time that NO payload row lands in a block a
    live prefix-cache entry still references — the sharing invariant
    under a suspend/resume/evict storm."""

    scheduler = None

    def inject_blocks(self, params, pool, block_ids, payload, block_size):
        cached = {
            block
            for ids in self.scheduler._prefix._entries.values()
            for block in ids
        }
        targets = {int(b) for b in np.asarray(block_ids)} - {TRASH_BLOCK}
        assert not (targets & cached), (
            f"swap-in into prefix-cached block(s) {targets & cached}"
        )
        return super().inject_blocks(
            params, pool, block_ids, payload, block_size
        )


def test_suspend_resume_prefix_storm_keeps_refcounts_and_streams():
    """The storm: a stream admitted through its own prefix registration
    is suspended (its cache entries evicted to feed the interactive
    admission), the SAME prompt is re-registered under new physical
    blocks by the interactive stream, and the parked stream resumes
    THROUGH that re-registered prefix — swap-in splices only the
    non-shared tail rows, never a cached block, and the stream stays
    bit-identical. Refcounts equal prefix-membership + slot occupancy
    at every checkpoint."""
    engine = _GuardedPagedEngine()
    scheduler = SlotScheduler(
        engine, params=None, max_slots=2, block_size=4,
        num_blocks=5, max_seq_len=32, kv_host_blocks=8,
    )
    engine.scheduler = scheduler
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5]  # prefill 8 = 2 full blocks
    batch = scheduler.submit(
        prompt, SamplingParams(max_new_tokens=6), tier="batch"
    )
    for _ in range(3):
        scheduler.tick()
    _refcount_invariant(scheduler)
    # Same prompt, interactive: its admission first evicts the (shared,
    # slot-held) prefix entries — freeing nothing — then suspends the
    # batch stream, then prefills and RE-REGISTERS the prefix under new
    # physical blocks.
    interactive = scheduler.submit(
        prompt, SamplingParams(max_new_tokens=6), tier="interactive"
    )
    scheduler.tick()
    assert len(scheduler._suspended) == 1
    _refcount_invariant(scheduler)
    stats = scheduler.stats()
    # The suspended stream's payload took ALL its valid blocks out —
    # shared prefix rows included — so it survives any later eviction.
    assert stats["swap"]["swap_out_blocks"] == 3  # ceil(11 / 4)
    assert stats["suspended_streams"] == {"batch": 1}
    _drive(scheduler, [batch, interactive])
    _refcount_invariant(scheduler)
    # Resume went THROUGH the re-registered prefix: only the non-shared
    # tail row was spliced back in.
    injects = [c for c in engine.calls if c[0] == "inject"]
    assert len(injects) == 1
    non_trash = [b for b in injects[0][1] if b != TRASH_BLOCK]
    assert len(non_trash) == 1
    stats = scheduler.stats()
    assert stats["swap"]["swap_in_blocks"] == 1
    assert stats["prefix_cache"]["hits"] >= 1
    # Both streams bit-identical to their uncontended selves.
    solo = _solo_stream(prompt)
    assert batch.result(timeout=1) == solo
    assert interactive.result(timeout=1) == solo


def test_storm_with_disjoint_prompts_and_eviction_pressure():
    """Disjoint prompts: the interactive admission must evict the
    retired first stream's cache entries AND suspend the active batch
    stream; resume re-injects every valid block (no prefix to share).
    The refcount invariant holds after the full churn."""
    engine, scheduler = _paged_scheduler(
        max_slots=2, num_blocks=5, kv_host_blocks=8,
    )
    warm = scheduler.submit(
        [9, 9, 9, 9, 9], SamplingParams(max_new_tokens=1)
    )
    _drive(scheduler, [warm])  # leaves a 1-block prefix entry behind
    assert scheduler.stats()["prefix_cache"]["cached_blocks"] == 1
    batch = scheduler.submit(
        [5, 5, 5, 5, 5], SamplingParams(max_new_tokens=4), tier="batch"
    )
    for _ in range(2):
        scheduler.tick()
    interactive = scheduler.submit(
        BATCH_PROMPT, SamplingParams(max_new_tokens=6), tier="interactive"
    )
    _drive(scheduler, [batch, interactive])
    _refcount_invariant(scheduler)
    stats = scheduler.stats()
    assert stats["swap"]["suspends"] == 1 and stats["swap"]["resumes"] == 1
    # No shared prefix for the parked prompt: blocks out == blocks in.
    assert stats["swap"]["swap_out_blocks"] == \
        stats["swap"]["swap_in_blocks"]
    engine2, solo_scheduler = _paged_scheduler(
        max_slots=2, num_blocks=5, kv_host_blocks=8,
    )
    warm2 = solo_scheduler.submit(
        [9, 9, 9, 9, 9], SamplingParams(max_new_tokens=1)
    )
    _drive(solo_scheduler, [warm2])
    ref = solo_scheduler.submit(
        [5, 5, 5, 5, 5], SamplingParams(max_new_tokens=4), tier="batch"
    )
    _drive(solo_scheduler, [ref])
    assert batch.result(timeout=1) == ref.result(timeout=1)


# --------------------------------------------------------------------------
# End-to-end on CPU: real engine, real HTTP, oversubscribed pool
# --------------------------------------------------------------------------

def _run_oversubscribed_http(kv_cache_dtype="bf16", temperature=0.0,
                             seed=7):
    """Serve one long batch request + one interactive request on a pool
    that holds only the batch stream; returns (batch_tokens,
    interactive_tokens, solo_batch_tokens, stats, model, params).

    The solo reference is the SAME stack configuration with no
    interactive contender — the suspended-then-resumed stream must be
    bit-identical to it (greedy or sampled; the rng row survives the
    swap verbatim)."""
    batch_body = {
        "prompt": [3, 1, 4, 1, 5, 9, 2, 6, 5], "max_new_tokens": 20,
        "tier": "batch", "temperature": temperature, "seed": seed,
    }
    inter_body = {
        "prompt": [2, 7, 1, 8, 2], "max_new_tokens": 8,
        "tier": "interactive", "temperature": temperature, "seed": seed,
    }

    def build():
        # batch needs ceil((9 + 20 - 1)/8) = 4 blocks = the whole
        # usable pool; interactive needs 2 -> displacement.
        return _tiny_serving_stack(
            max_slots=2, num_blocks=5,
            kv_host_blocks=8, temperature=temperature,
            kv_cache_dtype=kv_cache_dtype,
        )

    # Uncontended reference run.
    model, params, _engine, solo = build()
    solo.start()
    solo_server = ServingServer(solo, "127.0.0.1", 0)
    solo_server.start()
    try:
        status, _headers, raw = _post(solo_server.port, batch_body)
        assert status == 200, raw
        solo_tokens = json.loads(raw)["tokens"]
    finally:
        solo_server.stop()
        solo.close()

    model, params, _engine, scheduler = build()
    scheduler.start()
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    results = {}
    try:
        thread = threading.Thread(
            target=lambda: results.update(batch=_post(server.port,
                                                      batch_body))
        )
        thread.start()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if scheduler.stats()["active_slots"] >= 1:
                break
            time.sleep(0.01)
        assert scheduler.stats()["active_slots"] >= 1
        results["inter"] = _post(server.port, inter_body)
        thread.join(timeout=300)
        stats = scheduler.stats()
        status, _headers, raw = results["batch"]
        assert status == 200, raw
        batch_tokens = json.loads(raw)["tokens"]
        status, _headers, raw = results["inter"]
        assert status == 200, raw
        inter_tokens = json.loads(raw)["tokens"]
        return (batch_tokens, inter_tokens, solo_tokens, stats, model,
                params)
    finally:
        server.stop()
        scheduler.close()


def test_http_suspend_resume_stream_matches_legacy_fp_greedy():
    """The in-suite acceptance representative: fp greedy through the
    real HTTP frontend — the displaced batch stream is suspended to
    host RAM and resumed, and its tokens are bit-identical both to the
    uncontended serving run AND to generate_legacy."""
    batch_tokens, inter_tokens, solo_tokens, stats, model, params = \
        _run_oversubscribed_http()
    assert stats["swap"]["suspends"] >= 1
    assert stats["swap"]["resumes"] >= 1
    assert stats["swap"]["swap_out_blocks"] >= 1
    assert batch_tokens == solo_tokens
    assert batch_tokens == _legacy_stream(
        model, params, [3, 1, 4, 1, 5, 9, 2, 6, 5], 20
    )
    assert inter_tokens == _legacy_stream(
        model, params, [2, 7, 1, 8, 2], 8
    )
    # One compiled program per swap direction, regardless of churn.
    assert stats["decode_engine"]["extract_compiles"] == 1
    assert stats["decode_engine"]["inject_compiles"] == 1
    # The telemetry surface carries the swap counters.
    from tf_yarn_tpu import telemetry

    registry = telemetry.get_registry()
    assert registry.counter("serving/swap_out_blocks_total").value >= 1
    assert registry.counter("serving/swap_in_blocks_total").value >= 1


@pytest.mark.slow  # the fp greedy in-suite run above is the
# representative; the sampled + int8 corners run in the full sweep
@pytest.mark.parametrize("kv_cache_dtype,temperature", [
    ("bf16", 0.8),   # sampled: the rng chain must survive the swap
    ("int8", 0.0),   # int8 pool: payload swaps as quantized bytes
    ("int8", 0.8),
])
def test_http_suspend_resume_matrix_bit_identical(kv_cache_dtype,
                                                  temperature):
    batch_tokens, _inter, solo_tokens, stats, _model, _params = \
        _run_oversubscribed_http(
            kv_cache_dtype=kv_cache_dtype, temperature=temperature
        )
    assert stats["swap"]["suspends"] >= 1
    assert batch_tokens == solo_tokens


def test_http_tier_validation_and_stats_surface():
    """Unknown tier -> 400 before any admission; /stats exposes the
    host-block-store / tier surface when oversubscription is on."""
    engine, scheduler = _oversubscribed(tier_caps={"interactive": 4})
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    try:
        status, _headers, raw = _post(
            server.port,
            {"prompt": [1, 2, 3], "max_new_tokens": 2, "tier": "bulk"},
        )
        assert status == 400 and b"tier" in raw
        conn = http.client.HTTPConnection(
            "127.0.0.1", server.port, timeout=30
        )
        conn.request("GET", "/stats")
        stats = json.loads(conn.getresponse().read())
        conn.close()
        assert stats["host_block_store"] == {
            "capacity_blocks": 8, "used_blocks": 0, "free_blocks": 8,
            "entries": 0,
        }
        assert stats["tiers"]["caps"] == {"interactive": 4}
        assert stats["swap"] == {
            "suspends": 0, "resumes": 0, "swap_out_blocks": 0,
            "swap_in_blocks": 0,
        }
        assert stats["retire_rate_per_s"] == 0.0
    finally:
        server.stop()
        scheduler.close()
