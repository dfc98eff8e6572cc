"""What the serving path says about its own time (docs/Observability.md,
docs/Serving.md "Where a tick's time goes").

* The scheduler's thread is always under a named span, and `serving/step`
  is tiled by launch / sync / emit.
* Every request leaves one `serving/request` record under the caller's id
  (and one `serving/admission` if it took a slot), whatever way it was
  admitted and however it ended, and its parts add up to its time to a
  first token.
* `/stats` counts work where it happens; `POST /debug/profile` is the one
  profiler hook.
"""

import glob
import http.client
import json
import os
import statistics
import threading
import time

import pytest

from tests.fakes import (
    FakeAsyncEngine,
    FakePagedEngine,
    FakePagedWindowedEngine,
    fake_scheduler,
)
from tests.test_serving import (
    _drive,
    _post,
    _tiny_serving_stack,
)
from tf_yarn_tpu import telemetry
from tf_yarn_tpu.serving import (
    QueueFull,
    SamplingParams,
    ServingServer,
)
from tf_yarn_tpu.telemetry import spans as spans_lib


@pytest.fixture(autouse=True)
def _clean_ring():
    telemetry.get_tracer().clear()
    yield


def _records(name):
    return [s for s in telemetry.get_tracer().records() if s.name == name]


def _by_request(name):
    out = {}
    for span in _records(name):
        out.setdefault(span.args["request_id"], []).append(span)
    return out


# --------------------------------------------------------------------------
# (a) spans have identity
# --------------------------------------------------------------------------

def test_span_ids_nest_across_threads_and_records_sit_on_no_stack():
    tracer = spans_lib.Tracer()
    seen = {}

    both_alive = threading.Barrier(2)  # or one ident may serve both

    def work(tag):
        both_alive.wait(timeout=30)
        with tracer.span(f"{tag}/outer") as outer:
            with tracer.span("inner") as first:
                pass
            with tracer.span("inner") as second:
                with tracer.span("leaf") as leaf:
                    pass
        seen[tag] = (outer, first, second, leaf)
        both_alive.wait(timeout=30)

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join()
    record = tracer.record("life", spans_lib.now() - 1.0, 1.0, request_id="x")
    ids = [s.id for s in tracer.records()]
    assert len(set(ids)) == len(ids) == 9
    for outer, first, second, leaf in seen.values():
        assert outer.parent_id is None and outer.parent is None
        # Two spans of one name under one parent are told apart by id.
        assert first.parent_id == second.parent_id == outer.id
        assert first.id != second.id and first.parent == outer.name
        assert leaf.parent_id == second.id and leaf.parent == "inner"
        assert leaf.thread_id == outer.thread_id
    assert seen["a"][0].thread_id != seen["b"][0].thread_id
    assert record.depth == spans_lib.RECORD_DEPTH and record.parent_id is None
    assert record.thread_name == spans_lib.RECORD_THREAD
    assert record.duration == 1.0 and record.args == {"request_id": "x"}
    payload = record.to_json()
    assert payload["id"] == record.id and payload["parent_id"] is None
    event = next(e for e in tracer.chrome_events() if e.get("name") == "leaf")
    assert {"id", "parent_id", "depth"} <= set(event["args"])


# --------------------------------------------------------------------------
# (b) the scheduler's thread is always under a span
# --------------------------------------------------------------------------

TOP_LEVEL = {"serving/control_ops", "serving/tick", "serving/publish",
             "serving/idle_wait"}
STEP_PARTS = ["serving/step_launch", "serving/step_sync", "serving/step_emit"]
LAUNCH_PARTS = ["serving/launch_inputs", "decode_engine/step_args",
                "decode_engine/paged_step", "serving/launch_account"]


def _end(span):
    return span.start + span.duration


def _children(records):
    by_parent = {}
    for span in sorted(records, key=lambda s: s.start):
        by_parent.setdefault(span.parent_id, []).append(span)
    return by_parent


def _assert_tiled(parents, by_parent, names, boundary_us):
    """The tiling, held by structure and not by a share of a fake engine's
    time: each parent's children are `names`, in that order, each starts
    at or after its predecessor's end and lies inside the parent; and what
    the children leave uncovered (the spans' own enter and exit) is, for
    the median parent, under `boundary_us` microseconds a boundary. A
    median: one preempted thread on a loaded machine moves no bound."""
    uncovered = []
    for parent in parents:
        children = by_parent[parent.id]
        assert [c.name for c in children] == names
        edge = parent.start
        for child in children:
            assert child.start >= edge, (parent.name, child.name)
            edge = _end(child)
        assert edge <= _end(parent) + 1e-7, parent.name
        uncovered.append(parent.duration - sum(c.duration for c in children))
    assert min(uncovered) > -1e-7
    assert statistics.median(uncovered) / (len(names) + 1) \
        < boundary_us * 1e-6, (names, statistics.median(uncovered))


def test_scheduler_thread_spans_tile_its_time_and_step_children_tile_step():
    engine = FakePagedEngine(max_seq_len=64)
    real_step = engine.paged_step

    def slow_step(*args, **kwargs):  # a step worth measuring: 2 ms
        time.sleep(0.002)
        return real_step(*args, **kwargs)

    engine.paged_step = slow_step
    scheduler = fake_scheduler(engine, max_slots=2)
    scheduler.start()
    try:
        responses = [
            scheduler.submit([i + 1] * (3 + i),
                             SamplingParams(max_new_tokens=40))
            for i in range(4)
        ]
        for response in responses:
            response.result(timeout=60)
        time.sleep(0.12)  # two idle waits
    finally:
        scheduler.close()
    records = telemetry.get_tracer().records()
    tid = next(s.thread_id for s in records if s.name == "serving/tick")
    mine = sorted((s for s in records if s.thread_id == tid),
                  key=lambda s: s.start)
    top = [s for s in mine if s.depth == 0]
    assert {s.name for s in top} == TOP_LEVEL
    # The thread is always under one of the four: each begins where its
    # predecessor ended, and what lies between two of them (the loop's own
    # lines) is microseconds, whichever two they are.
    gaps = {}
    for a, b in zip(top, top[1:]):
        assert b.start >= _end(a), (a.name, b.name)
        gaps.setdefault((a.name, b.name), []).append(b.start - _end(a))
    assert ("serving/publish", "serving/control_ops") in gaps
    assert ("serving/idle_wait", "serving/control_ops") in gaps
    for pair, between in gaps.items():
        assert statistics.median(between) < 100e-6, (pair, between)

    steps = [s for s in mine if s.name == "serving/step"]
    assert len(steps) >= 80
    by_parent = _children(mine)
    for step in steps:
        assert step.args["tick"] == next(
            s for s in top if s.id == step.parent_id).args["tick"]
    _assert_tiled(steps, by_parent, STEP_PARTS, boundary_us=50)
    # The fake engine opens no span of its own: the launch's two stand
    # around its call (with the real engine's two between them they tile
    # the launch: the test below).
    launching = [by_parent[step.id][0] for step in steps
                 if by_parent[step.id][0].args["slots"]]
    for launch in launching:
        first, last = by_parent[launch.id]
        assert (first.name, last.name) == (LAUNCH_PARTS[0], LAUNCH_PARTS[3])
        assert launch.start <= first.start and _end(first) <= last.start
        assert _end(last) <= _end(launch) + 1e-7
        # The 2 ms call is what they leave uncovered.
        assert last.start - _end(first) >= 0.002 * 0.9
    # Every token and every retirement is accounted to an emit span.
    emits = [s for s in mine if s.name == "serving/step_emit"]
    assert sum(s.args["tokens"] for s in emits) == 4 * 40
    assert sum(s.args["retired"] for s in emits) == 4
    # The order inside a step: the launch is of the NEXT model step, the
    # sync and the emit are of the one launched a tick before. So the
    # first step launches with nothing unread and emits nothing, nearly
    # every later one launches ahead of its read, and the last reads with
    # nothing left to launch.
    launches = [by_parent[step.id][0] for step in steps]
    launched = [s for s in launches if s.args["slots"]]
    assert launches[0].args["ahead"] == 0
    assert by_parent[steps[0].id][2].args["tokens"] == 0
    assert launches[-1].args["slots"] == 0
    assert by_parent[steps[-1].id][2].args["tokens"] >= 1
    ahead = sum(s.args["ahead"] for s in launched)
    assert ahead >= 0.9 * len(launched)
    stats = scheduler.stats()
    assert (stats["steps"], stats["steps_ahead"]) == (len(launched), ahead)
    assert stats["pipeline_settles"]["nothing_to_launch"] >= 1
    # The tick histogram still observes the tick span, nothing wider.
    hist = telemetry.get_registry().histogram("serving/tick_seconds")
    assert hist.count >= len(steps)
    # The thread's time in three parts, from the spans' own durations:
    # under `step_sync`, under `idle_wait`, and the host's.
    covered = sum(s.duration for s in top)
    parts = (stats["host_seconds"] + stats["sync_wait_seconds"]
             + stats["idle_wait_seconds"])
    assert parts == pytest.approx(covered, abs=5e-6)
    assert stats["idle_wait_seconds"] == pytest.approx(sum(
        s.duration for s in top if s.name == "serving/idle_wait"), abs=2e-6)
    assert stats["sync_wait_seconds"] == pytest.approx(sum(
        s.duration for s in mine if s.name == "serving/step_sync"
        and "step" in s.args), abs=2e-6)
    # This engine answers inside its call: the host set every tick's pace.
    assert stats["host_seconds"] > 0.9 * (covered
                                          - stats["idle_wait_seconds"])


def test_step_launch_is_tiled_by_its_four_children():
    _model, _params, _engine, scheduler = _tiny_serving_stack(max_slots=2)
    responses = [scheduler.submit([5, 6, 7], SamplingParams(max_new_tokens=12)),
                 scheduler.submit([8, 9], SamplingParams(max_new_tokens=12))]
    _drive(scheduler, responses)
    records = telemetry.get_tracer().records()
    launches = [s for s in records if s.name == "serving/step_launch"
                and s.args["slots"]]
    assert len(launches) >= 12
    # What is left between them is the engine's choice of the kernel before
    # its `step_args` and the spans' own enter and exit.
    _assert_tiled(launches, _children(records), LAUNCH_PARTS, boundary_us=60)
    # A tick that launched nothing has a launch span with no children.
    idle = [s for s in records if s.name == "serving/step_launch"
            and not s.args["slots"]]
    assert idle and all(s.id not in _children(records) for s in idle)


def test_a_model_steps_three_spans_share_its_number_across_two_ticks():
    scheduler = fake_scheduler(FakePagedEngine(), max_slots=2)
    responses = [scheduler.submit([i + 1] * (3 + i),
                                  SamplingParams(max_new_tokens=9 + i))
                 for i in range(3)]
    _drive(scheduler, responses)
    tick_of = {s.id: s.args["tick"] for s in _records("serving/step")}
    ticks = {}
    for name in STEP_PARTS:
        for span in _records(name):
            if span.args.get("step") is not None:
                assert name not in ticks.setdefault(span.args["step"], {})
                ticks[span.args["step"]][name] = tick_of[span.parent_id]
    stats = scheduler.stats()
    # Numbered from 1, each launched once and read once.
    assert sorted(ticks) == list(range(1, stats["steps"] + 1))
    assert stats["steps_read"] == stats["steps"] >= 11
    for step, seen in ticks.items():
        launch, sync, emit = (seen[name] for name in STEP_PARTS)
        # The `tick` on `serving/step` joins a launch to the read of
        # ANOTHER step; `step` joins the three of one.
        assert sync == emit == launch + 1, (step, seen)
    # A tick's launch and read are of consecutive steps.
    by_parent = _children(telemetry.get_tracer().records())
    for span in _records("serving/step"):
        launch, sync, _emit = by_parent[span.id]
        if launch.args["step"] and sync.args.get("step"):
            assert launch.args["step"] == sync.args["step"] + 1


def test_engine_step_args_span_sits_under_launch():
    _model, _params, _engine, scheduler = _tiny_serving_stack(max_slots=2)
    response = scheduler.submit([5, 6, 7], SamplingParams(max_new_tokens=3))
    _drive(scheduler, [response])
    records = telemetry.get_tracer().records()
    # A tick that only reads the step in flight has a launch span that
    # launched nothing (`slots` 0) and made no call of the engine.
    launches = {s.id for s in records if s.name == "serving/step_launch"
                and s.args["slots"]}
    args = [s for s in records if s.name == "decode_engine/step_args"]
    calls = [s for s in records if s.name == "decode_engine/paged_step"]
    assert len(args) == len(calls) == len(launches) >= 3
    assert all(s.parent_id in launches for s in args + calls)
    # The compile of the first step is inside step_args, not the call.
    compiles = [s for s in records if s.name == "decode_engine/compile"
                and s.args["kind"] == "paged_step"]
    assert compiles and compiles[0].parent == "decode_engine/step_args"


# --------------------------------------------------------------------------
# (c) one id and one record per request
# --------------------------------------------------------------------------

def _check_parts(record):
    args = record.args
    parts = args["queue_wait_ms"] + args["prefill_ms"] + args["replay_ms"]
    assert parts == pytest.approx(args["ttft_ms"], abs=1e-6)
    assert 0 <= args["ttft_ms"] <= record.duration * 1e3 + 1e-6


def test_every_finish_reason_leaves_one_record_under_the_callers_id():
    scheduler = fake_scheduler(FakePagedEngine(), max_slots=1,
                               queue_capacity=2)
    # `length`, and `eos` at the first emission (sum of prompt % 97).
    length = scheduler.submit([1, 2, 3, 4, 5, 6],
                              SamplingParams(max_new_tokens=3),
                              trace_id="r-length")
    eos = scheduler.submit([7, 8], SamplingParams(max_new_tokens=9,
                                                  eos_token=15),
                           trace_id="r-eos")
    # Refused: the queue holds two, the slot none yet.
    with pytest.raises(QueueFull):
        scheduler.submit([9], SamplingParams(max_new_tokens=2),
                         trace_id="r-refused")
    with pytest.raises(ValueError):
        scheduler.submit([9], SamplingParams(max_new_tokens=2,
                                             temperature=0.7),
                         trace_id="r-unservable")
    _drive(scheduler, [length, eos])
    assert length.finish_reason == "length" and eos.finish_reason == "eos"
    # Deadline in the queue (never admitted), and in a slot.
    blocker = scheduler.submit([1] * 5, SamplingParams(max_new_tokens=20),
                               trace_id="r-slot-deadline", timeout_s=0.05)
    queued = scheduler.submit([2, 3], SamplingParams(max_new_tokens=2),
                              trace_id="r-queue-deadline", timeout_s=0.02)
    # Two ticks: the first launches the blocker's first sampled step, the
    # second reads it. A deadline that fell between them would drop the
    # token with the slot (tests/test_serving.py), and no first token.
    scheduler.tick()
    scheduler.tick()
    time.sleep(0.06)
    _drive(scheduler, [blocker, queued])
    # No id from the caller: the program's own.
    anonymous = scheduler.submit([4, 4], SamplingParams(max_new_tokens=1))
    _drive(scheduler, [anonymous])

    requests = _by_request("serving/request")
    admissions = _by_request("serving/admission")
    first_tokens = _by_request("serving/first_token")
    assert all(len(v) == 1 for v in requests.values())
    assert all(len(v) == 1 for v in admissions.values())
    finishes = {k: v[0].args["finish"] for k, v in requests.items()}
    own = str(anonymous.request.id)
    assert finishes == {
        "r-length": "length", "r-eos": "eos", "r-refused": "refused",
        "r-unservable": "refused", "r-slot-deadline": "deadline",
        "r-queue-deadline": "deadline", own: "length",
    }
    assert set(admissions) == {"r-length", "r-eos", "r-slot-deadline", own}
    assert set(first_tokens) == set(admissions)
    for rid in ("r-length", "r-eos", own):
        _check_parts(requests[rid][0])
        assert first_tokens[rid][0].duration == 0.0
        assert first_tokens[rid][0].args["ttft_ms"] == pytest.approx(
            requests[rid][0].args["ttft_ms"])
    done = requests["r-length"][0].args
    # Prompt of 6: bucket 4 through the prefill program, 2 replayed.
    assert (done["prompt_tokens"], done["prefilled"], done["hit_tokens"],
            done["replayed"], done["emitted"], done["slot"]) == \
        (6, 4, 0, 2, 3, 0)
    admitted = admissions["r-length"][0]
    assert admitted.duration == 0.0 and admitted.args["replay"] == 2
    assert admitted.args["queue_wait_ms"] == pytest.approx(
        done["queue_wait_ms"])
    never = requests["r-queue-deadline"][0].args
    assert never["ttft_ms"] is None and never["slot"] is None
    assert never["queue_wait_ms"] == pytest.approx(
        requests["r-queue-deadline"][0].duration * 1e3)
    # The record spans submit -> finish on the tracer's clock.
    record = requests["r-slot-deadline"][0]
    assert record.args["emitted"] > 0 and record.duration >= 0.05
    prefill = next(s for s in _records("serving/prefill")
                   if s.args["request_id"] == "r-length")
    assert prefill.args["request"] == length.request.id
    # The program that ran and the rows of it that were the prompt's (the
    # fake keeps the floor rule: the bucket of 4, whole); `prefill` is what
    # the benchmark's readers take for the tokens in the cache at admission.
    assert (prefill.args["bucket"], prefill.args["kept"],
            prefill.args["prefill"]) == (4, 4, 4)


def test_prefix_hit_and_chunked_prefill_keep_every_request_under_its_id():
    # Prefix hit: the second request shares the first's 8-token block
    # prefix and opens no serving/prefill span — a join by order fails.
    scheduler = fake_scheduler(FakePagedEngine(), max_slots=2)
    shared = [3, 1, 4, 1, 5, 9, 2, 6]
    first = scheduler.submit(shared + [5, 3], SamplingParams(max_new_tokens=2),
                             trace_id="hit-a")
    _drive(scheduler, [first])
    second = scheduler.submit(shared + [7, 7, 7],
                              SamplingParams(max_new_tokens=2),
                              trace_id="hit-b")
    _drive(scheduler, [second])
    assert len(_records("serving/prefill")) == 1
    admissions = _by_request("serving/admission")
    assert admissions["hit-a"][0].args["prefilled"] == 8
    hit = admissions["hit-b"][0].args
    assert (hit["hit_tokens"], hit["prefilled"], hit["replay"]) == (8, 0, 3)
    assert scheduler.stats()["prefilled_tokens"] == 8
    # prefill_tokens keeps its meaning: prompt tokens replayed by the step.
    assert scheduler.stats()["prefill_tokens"] == 2 + 3

    # Chunked prefill: no blocking prefill at all, on any request.
    telemetry.get_tracer().clear()
    chunked = fake_scheduler(FakePagedWindowedEngine(), max_slots=2,
                             prefill_chunk=4)
    responses = [
        chunked.submit([i + 1] * (6 + i), SamplingParams(max_new_tokens=3),
                       trace_id=f"chunk-{i}")
        for i in range(3)
    ]
    _drive(chunked, responses, max_ticks=500)
    assert not _records("serving/prefill")
    admissions = _by_request("serving/admission")
    requests = _by_request("serving/request")
    assert set(admissions) == set(requests) == {
        "chunk-0", "chunk-1", "chunk-2"}
    for i in range(3):
        args = requests[f"chunk-{i}"][0].args
        assert len(requests[f"chunk-{i}"]) == 1
        assert (args["prefilled"], args["replayed"], args["emitted"]) == \
            (0, 6 + i, 3)
        _check_parts(requests[f"chunk-{i}"][0])
    assert chunked.stats()["prefilled_tokens"] == 0


# --------------------------------------------------------------------------
# (d) counters where the work happens
# --------------------------------------------------------------------------

def test_kv_token_steps_equals_a_hand_count():
    scheduler = fake_scheduler(FakePagedEngine(), max_slots=2,
                               prefix_cache_capacity=0)
    # Prompt 6 -> 4 prefilled, 2 replayed, 3 emitted: steps read caches of
    # 4, 5 (replay; the second emits), 6, 7 (decode) tokens = 4 steps.
    # Prompt 3 -> 0 prefilled: steps read 0, 1, 2 (replay), 3 (decode).
    a = scheduler.submit([1, 2, 3, 4, 5, 6], SamplingParams(max_new_tokens=3))
    b = scheduler.submit([7, 8, 9], SamplingParams(max_new_tokens=2))
    _drive(scheduler, [a, b])
    stats = scheduler.stats()
    assert stats["slot_steps"] == 4 + 4
    assert stats["kv_token_steps"] == (4 + 5 + 6 + 7) + (0 + 1 + 2 + 3)
    assert stats["prefilled_tokens"] == 4
    assert stats["prefill_tokens"] == 2 + 3   # replayed through the step
    assert stats["decode_tokens"] == 3 + 2


@pytest.mark.parametrize("chunk", [None, 4], ids=["whole_table", "chunks"])
def test_kv_read_token_steps_follow_what_the_engine_says_it_reads(chunk):
    """The same two requests as above. An engine that says nothing of
    its read (the fakes, the speculative window) reads `max_seq_len` a
    slot-step; one that reads live chunks (`paged_attention_chunk`: the
    kernel) reads each slot's length and this token's own row, rounded
    up to the chunk."""
    engine = FakePagedEngine()
    asked = []
    if chunk:
        engine.paged_attention_chunk = \
            lambda block_size: asked.append(block_size) or chunk
    scheduler = fake_scheduler(engine, max_slots=2, prefix_cache_capacity=0)
    a = scheduler.submit([1, 2, 3, 4, 5, 6], SamplingParams(max_new_tokens=3))
    b = scheduler.submit([7, 8, 9], SamplingParams(max_new_tokens=2))
    _drive(scheduler, [a, b])
    stats = scheduler.stats()
    assert stats["kv_token_steps"] == (4 + 5 + 6 + 7) + (0 + 1 + 2 + 3)
    if not chunk:
        assert stats["kv_read_token_steps"] == 8 * engine.max_seq_len
        return
    assert asked and set(asked) == {4}  # the scheduler's block size
    # Lengths 5, 6, 7, 8 and 1, 2, 3, 4 attended over, up to chunks of 4.
    assert stats["kv_read_token_steps"] == (8 + 8 + 8 + 8) + (4 + 4 + 4 + 4)


def test_slow_steps_are_counted_with_their_launch_and_sync():
    engine = FakePagedEngine()
    real_step = engine.paged_step
    calls = {"n": 0}

    def step(*args, **kwargs):
        calls["n"] += 1
        time.sleep(0.1 if calls["n"] == 20 else 0.005)
        return real_step(*args, **kwargs)

    engine.paged_step = step
    scheduler = fake_scheduler(engine, max_slots=1)
    response = scheduler.submit([1, 2], SamplingParams(max_new_tokens=30))
    _drive(scheduler, [response])
    stats = scheduler.stats()
    assert stats["slow_steps"] >= 1  # a loaded machine may add its own
    slowest = stats["slowest_step"]
    assert slowest["tick"] == 20 and slowest["ms"] >= 100
    # The fake engine's call is all of it: the host was stuck before the
    # dispatch returned, not under the sync or after it.
    assert slowest["launch_ms"] >= 100 > slowest["sync_ms"] + slowest["emit_ms"]
    assert stats["slow_step_seconds"] >= slowest["ms"] / 1e3 - 1e-5
    step_span = next(s for s in _records("serving/step")
                     if s.args["tick"] == 20)
    assert step_span.duration * 1e3 == pytest.approx(slowest["ms"])


# --------------------------------------------------------------------------
# (d') a pipelined tick accounts for itself
# --------------------------------------------------------------------------

def _syncs():
    """The `serving/step_sync` spans that read a step, in step order."""
    return sorted((s for s in _records("serving/step_sync")
                   if "step" in s.args), key=lambda s: s.args["step"])


def test_a_read_behind_a_blocking_prefill_is_an_ahead_interval_and_no_stall():
    """A device of 4 ms a step and 40 ms a prefill. One stream decodes; a
    second is admitted through the prefill program: the step launched
    after it runs behind the prefill and its pack, and its read says so."""
    engine = FakeAsyncEngine(step_s=0.004, prefill_s=0.04, max_seq_len=128)
    scheduler = fake_scheduler(engine, max_slots=2)
    first = scheduler.submit([1, 2, 3], SamplingParams(max_new_tokens=60))
    for _ in range(20):
        scheduler.tick()
    before = scheduler.stats()
    # (Of 19: a loaded machine may bring the host late to a read or two.)
    assert before["ahead_intervals"] == 0 and before["clean_intervals"] >= 10
    second = scheduler.submit(list(range(1, 10)),
                              SamplingParams(max_new_tokens=3))
    _drive(scheduler, [first, second])
    stats = scheduler.stats()
    behind = [s for s in _syncs() if s.args["ahead_programs"]]
    assert len(behind) == 1
    (read,) = behind
    # The prefill and its pack, and the 8 prompt tokens the bucket took.
    assert read.args["ahead_programs"] >= 2
    assert read.args["ahead_prefill_tokens"] == 8
    assert read.args["paced"] == "device" and read.duration >= 0.03
    assert (stats["ahead_intervals"], stats["ahead_prefill_tokens"]) == (1, 8)
    # Read end to read end: the prefill and one step (less what a loaded
    # machine woke the read before it late).
    assert stats["ahead_interval_seconds"] >= 0.04 * 0.9
    # ... and it fed none of the clean intervals, whose mean stays a step.
    clean = stats["clean_interval_seconds"] / stats["clean_intervals"]
    assert stats["clean_intervals"] >= 30
    assert 0.004 * 0.9 <= clean < 0.02, clean
    excess = stats["ahead_interval_seconds"] - clean
    assert excess >= 0.025
    # Ten times the median tick, and no stall: the tally does not judge it.
    step = next(s for s in _records("serving/step")
                if s.id == read.parent_id)
    assert step.duration > 5 * clean
    slowest = stats["slowest_step"]
    assert slowest is None or slowest["step"] != read.args["step"]
    assert stats["slow_step_seconds"] < step.duration \
        or stats["slow_steps"] >= 2  # a loaded machine may add its own


@pytest.mark.parametrize("kind", ["device", "host", "serial"])
def test_a_read_says_who_paced_it(kind):
    if kind == "serial":  # the windowed step: launched and read in a tick
        scheduler = fake_scheduler(FakePagedWindowedEngine(), max_slots=2,
                                   prefill_chunk=4)
    else:
        engine = FakeAsyncEngine(step_s=0.02) if kind == "device" \
            else FakePagedEngine()  # its results are ready at once
        scheduler = fake_scheduler(engine, max_slots=2)
    responses = [scheduler.submit([i + 1] * (3 + i),
                                  SamplingParams(max_new_tokens=8))
                 for i in range(2)]
    _drive(scheduler, responses)
    reads = _syncs()
    stats = scheduler.stats()
    assert [s.args["step"] for s in reads] == \
        list(range(1, stats["steps"] + 1))
    paced = [s.args["paced"] for s in reads]
    if kind == "serial":
        assert set(paced) == {"serial"}
        assert [s.args["step"] for s in _records("serving/step_launch")] \
            == [s.args["step"] for s in reads]
        # It feeds none of the read-to-read counters.
        assert stats["steps_read"] == stats["clean_intervals"] == 0
        return
    assert stats["steps_read"] == len(reads) >= 8
    if kind == "device":
        # (A loaded machine may bring the host 20 ms late to a read.)
        late = paced.count("host")
        assert late <= 0.2 * len(reads) and stats["steps_host_paced"] == late
        assert statistics.median(s.duration for s in reads) > 0.01
        # Every read but the first closes an interval of one 20 ms step.
        assert len(reads) - 1 - 2 * late <= stats["clean_intervals"] \
            <= len(reads) - 1
        mean = stats["clean_interval_seconds"] / stats["clean_intervals"]
        assert 0.02 * 0.9 <= mean < 0.05
    else:
        # (A loaded machine may hold the thread inside a read.)
        assert paced.count("host") >= 0.9 * len(reads)
        assert stats["steps_host_paced"] == paced.count("host")
        assert stats["clean_intervals"] <= 1


# The instruments the serving path leaves in the registry after one served
# request, names with labels: as the tree before PR 39 left them, but for
# `decode_engine/cache_hits{kind}` (a copy of `/stats`
# `decode_engine.*_cache_hits`, which stays).
METRICS_AFTER_ONE_REQUEST = {
    "decode_engine/compile_seconds{kind=pack}",
    "decode_engine/compile_seconds{kind=paged_step}",
    "decode_engine/compile_seconds{kind=prefill}",
    "decode_engine/compiles{kind=pack}",
    "decode_engine/compiles{kind=paged_step}",
    "decode_engine/compiles{kind=prefill}",
    "serving/active_slots",
    "serving/block_pool_free_blocks",
    "serving/block_pool_used_blocks",
    "serving/decode_tokens_total",
    "serving/free_slots",
    "serving/inter_token_latency_ms",
    "serving/kv_cache_hbm_bytes_per_device{layout=paged}",
    "serving/kv_cache_hbm_bytes{layout=paged}",
    "serving/prefill_tokens_total",
    "serving/prefix_cache_blocks",
    "serving/prefix_cache_entries",
    "serving/prefix_cache_hit_rate",
    "serving/queue_depth",
    "serving/queue_wait_seconds",
    "serving/request_seconds",
    "serving/requests_admitted_total",
    "serving/requests_completed_total{reason=length}",
    "serving/requests_total",
    "serving/state_hbm_bytes",
    "serving/tick_seconds",
    "serving/ticks_total",
    "serving/tp_degree",
    "serving/ttft_seconds",
    "serving/ttft_seconds{tier=standard}",
}


def test_metrics_names_and_labels_are_the_set_they_were():
    from tf_yarn_tpu.telemetry.registry import _format_key

    telemetry.get_registry().clear()
    _model, _params, engine, scheduler = _tiny_serving_stack(max_slots=2)
    response = scheduler.submit([1, 2, 3, 4, 5, 6],
                                SamplingParams(max_new_tokens=4))
    _drive(scheduler, [response])
    names = {_format_key(*key) for key, _ in telemetry.get_registry().items()}
    assert names == METRICS_AFTER_ONE_REQUEST
    assert engine.stats["paged_step_cache_hits"] >= 3
    text = telemetry.render_prometheus()
    assert "cache_hits" not in text and "serving_tick_seconds" in text
    # The handles looked up at construction are the registry's own.
    assert telemetry.get_registry().counter("serving/ticks_total").value \
        == scheduler.stats()["ticks"]
    assert telemetry.get_registry().histogram(
        "serving/inter_token_latency_ms").count == 3


def test_stats_over_http_carry_the_ticks_account_and_the_rings_drops():
    scheduler = fake_scheduler(FakePagedEngine(), max_slots=1)
    scheduler.start()
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    try:
        status, _headers, _raw = _post(
            server.port, {"prompt": [1, 2], "max_new_tokens": 3})
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=30)
        conn.request("GET", "/stats")
        stats = json.loads(conn.getresponse().read())
        conn.close()
    finally:
        server.stop()
        scheduler.close()
    assert status == 200
    assert stats["spans_evicted"] == telemetry.get_tracer().evicted_total()
    assert {"steps_read", "steps_host_paced", "host_seconds",
            "sync_wait_seconds", "idle_wait_seconds", "clean_intervals",
            "clean_interval_seconds", "ahead_intervals",
            "ahead_interval_seconds", "ahead_prefill_tokens", "slow_steps",
            "slow_step_seconds", "slowest_step"} <= set(stats)
    assert stats["steps_read"] == stats["steps"] == 4
    assert stats["host_seconds"] > 0 and stats["idle_wait_seconds"] > 0


# --------------------------------------------------------------------------
# the profiler hook
# --------------------------------------------------------------------------

def _post_json(port, path, body, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        response = conn.getresponse()
        return response.status, json.loads(response.read())
    finally:
        conn.close()


def test_debug_profile_writes_an_xplane_and_refuses_a_second(tmp_path,
                                                              monkeypatch):
    monkeypatch.delenv("TPU_YARN_PROFILE", raising=False)
    _model, _params, _engine, scheduler = _tiny_serving_stack(max_slots=2)
    scheduler.start()
    server = ServingServer(scheduler, "127.0.0.1", 0,
                           profile_dir=str(tmp_path / "profile"))
    server.start()
    results = {}
    try:
        # Compiled before the capture starts: the 1.5 s window is for
        # model steps, and a cold step program can take longer than that.
        status, _headers, _raw = _post(
            server.port, {"prompt": [1, 2, 3], "max_new_tokens": 2})
        assert status == 200

        def capture():
            results["first"] = _post_json(
                server.port, "/debug/profile", {"seconds": 1.5})

        thread = threading.Thread(target=capture)
        thread.start()
        deadline = time.monotonic() + 30
        while not telemetry.profile.active():
            assert time.monotonic() < deadline
            time.sleep(0.01)
        status, payload = _post_json(
            server.port, "/debug/profile", {"seconds": 0.1})
        assert status == 409 and "running" in payload["error"]
        # Model steps while the capture runs are annotated by tick.
        status, _headers, _raw = _post(
            server.port, {"prompt": [1, 2, 3], "max_new_tokens": 4})
        assert status == 200
        thread.join(timeout=120)
    finally:
        server.stop()
        scheduler.close()
    status, payload = results["first"]
    assert status == 200, payload
    assert payload["dir"] == str(tmp_path / "profile")
    assert payload["seconds"] >= 1.5
    assert glob.glob(os.path.join(
        payload["dir"], "plugins", "profile", "*", "*.xplane.pb"))
    assert not telemetry.profile.active()
    span = _records("telemetry/profile")[0]
    assert span.args["sync_perf_s"] == payload["sync_perf_s"]
    assert span.start <= payload["sync_perf_s"] <= span.start + span.duration

    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(
        payload["dir"], "plugins", "profile", "*", "*.xplane.pb"))[-1]
    names = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name in (telemetry.profile.SYNC_ANNOTATION,
                                  "serving/step"):
                    names.setdefault(event.name, []).append(
                        dict(event.stats))
    assert len(names[telemetry.profile.SYNC_ANNOTATION]) == 1
    ticks = [int(stats["tick"]) for stats in names["serving/step"]]
    assert len(ticks) >= 4 and ticks == sorted(ticks)


def test_debug_profile_without_a_directory_answers_409(monkeypatch):
    monkeypatch.delenv("TPU_YARN_PROFILE", raising=False)
    scheduler = fake_scheduler(FakePagedEngine(), max_slots=1)
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    try:
        status, payload = _post_json(server.port, "/debug/profile", {})
    finally:
        server.stop()
        scheduler.close()
    assert status == 409 and "TPU_YARN_PROFILE" in payload["error"]


def test_submit_span_carries_the_programs_id_where_none_came():
    scheduler = fake_scheduler(FakePagedEngine(), max_slots=1)
    scheduler.start()
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    try:
        status, _headers, raw = _post(
            server.port, {"prompt": [1, 2], "max_new_tokens": 2})
    finally:
        server.stop()
        scheduler.close()
    assert status == 200
    own = str(json.loads(raw)["request_id"])
    submit = _records("serving/submit")[0]
    assert submit.args["request_id"] == own
    assert {own} == set(_by_request("serving/request")) == \
        set(_by_request("serving/admission"))


# --------------------------------------------------------------------------
# (e) scopes on the model's device operations
# --------------------------------------------------------------------------

SCOPES = ("embed", "norm", "attention/qkv", "attention/rope",
          "attention/kv_write", "attention/kv_gather", "attention/scores",
          "attention/values", "attention/out", "mlp", "lm_head", "sample")


def test_paged_step_operations_carry_every_scope():
    import jax
    import jax.numpy as jnp

    from tf_yarn_tpu.models import decode_engine, transformer

    cfg = transformer.TransformerConfig.tiny(
        scan_layers=False, remat=False, max_seq_len=32, dtype=jnp.float32,
        n_heads=4, n_kv_heads=2)
    model = transformer.Transformer(cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    import flax.linen as nn

    params = nn.meta.unbox(params)
    row = decode_engine._decode_cache_aval(model, params)
    pool = decode_engine.paged_pool_avals(model, row, 9, 4)
    step = decode_engine.build_paged_step_fn(model, 4, 0.0, None, None)
    slots = 2
    lowered = jax.jit(step).lower(
        params, pool,
        jax.ShapeDtypeStruct((slots, cfg.max_seq_len // 4), jnp.int32),
        jax.ShapeDtypeStruct((slots,), jnp.int32),
        *decode_engine.feed_avals(slots),
        jax.ShapeDtypeStruct((slots,), bool),
    )
    import re

    from cellbench import scopes

    text = lowered.as_text(debug_info=True)
    paths = {scopes.path_of(name)
             for name in re.findall(r'loc\("(jit\(step\)[^"]*)"', text)}
    for scope in SCOPES:
        assert any(scopes.under(path, tuple(scope.split("/")))
                   for path in paths), scope
    # Flax's own module path stands around them; transforms wrap the rest
    # (`vmap(attention/kv_gather)`), which the reader's paths see through.
    assert any(scopes.under(path, ("layer_0", "block", "attn", "attention",
                                   "scores")) for path in paths)
