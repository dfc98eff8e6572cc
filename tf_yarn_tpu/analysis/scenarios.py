"""Deterministic lockset scenarios over the REAL hot objects.

Each scenario builds the genuine production objects (SlotScheduler +
BlockPool + PrefixCache, MicroBatchScheduler, ReplicaRegistry,
CheckpointWriter, MetricsRegistry + Tracer), instruments them with
:class:`racecheck.RaceTracer`, and drives them from several threads —
**strictly sequentially** (spawn one phase thread, join it, spawn the
next). The lockset machine keys on thread identity, not interleaving,
so the suite detects every guard-discipline violation while being
deterministic by construction: no sleeps, no timing races, no flake.

Device engines are replaced by the same pure-host fakes the serving/
ranking test suites use (the scheduler contract is engine-agnostic);
everything else is the real code under audit.

``allow=`` entries suppress known-benign candidate races — single-
writer advisory counters read by ``stats()`` without a lock (an int
rebind is atomic under the GIL; a stale read costs one snapshot, not
correctness). Every entry here is justified in docs/StaticAnalysis.md
and surfaces as a suppressed finding, never silently.
"""

from __future__ import annotations

import threading
from typing import Callable, List

import numpy as np

from tf_yarn_tpu.analysis.racecheck import RaceTracer, Scenario

_ADVISORY = (
    "single-writer advisory counter: written only by the scheduler "
    "thread, read lock-free by stats()/healthz snapshots (atomic int "
    "rebind under the GIL; a stale read skews one snapshot)"
)


def _phase(name: str, body: Callable[[], None]) -> None:
    """Run `body` on a fresh named thread and join it, re-raising any
    exception — sequential phases, distinct thread identities."""
    error: List[BaseException] = []

    def wrapper():
        try:
            body()
        except BaseException as exc:  # noqa: TYA008 - re-raised below
            error.append(exc)

    thread = threading.Thread(target=wrapper, name=name, daemon=True)
    thread.start()
    thread.join(timeout=60.0)
    if thread.is_alive():
        raise RuntimeError(f"scenario phase {name} wedged")
    if error:
        raise error[0]


# --------------------------------------------------------------------------
# Pure-host fake engines (the scheduler contracts, no device)
# --------------------------------------------------------------------------

class _FakePagedEngine:
    """SlotScheduler's PAGED device contract with host state: the pool
    is a (num_blocks, block_size) int64 token store; a sampled step
    emits ``sum(consumed tokens) % 97`` (same arithmetic as the serving
    test fakes, so behaviour under instrumentation is comparable)."""

    def __init__(self, buckets=(4, 8), max_seq_len=32):
        self.buckets = tuple(sorted(buckets))
        self.max_seq_len = max_seq_len

    def slot_prefill_len(self, prompt_len, ceiling=False):
        best = 0
        for bucket in self.buckets:
            if bucket <= prompt_len - 1:
                best = bucket
        return best, best

    def make_paged_pool(self, params, num_blocks, block_size):
        return np.zeros((num_blocks, block_size), np.int64)

    def prefill(self, params, prompt, length=None):
        return np.asarray(prompt[0], np.int64), None

    def pack_prefill(self, pool, block_ids, row_cache, prefill_len,
                     block_size):
        pool = pool.copy()
        for pos in range(prefill_len):
            block = block_ids[pos // block_size]
            pool[block, pos % block_size] = row_cache[pos]
        return pool

    def paged_step(self, params, pool, tables, lengths, emitted, rngs,
                   tokens, rng_rows, forced, sample_mask, block_size,
                   temperature=0.0, top_k=None, top_p=None):
        forced = np.asarray(forced)
        tokens = np.where(forced, tokens, emitted)
        rngs = np.where(forced[:, None], rng_rows, rngs)
        pool = np.array(pool)
        tables = np.asarray(tables)
        lengths = np.asarray(lengths)
        emitted = np.array(tokens, np.int32)
        for slot in range(len(tokens)):
            length = int(lengths[slot])
            pool[tables[slot, length // block_size],
                 length % block_size] = tokens[slot]
            if sample_mask[slot]:
                total = 0
                for pos in range(length + 1):
                    total += pool[tables[slot, pos // block_size],
                                  pos % block_size]
                emitted[slot] = total % 97
        return pool, emitted, rngs

    def extract_blocks(self, params, pool, block_ids, block_size):
        return np.asarray(pool)[np.asarray(block_ids)].copy()

    def inject_blocks(self, params, pool, block_ids, payload,
                      block_size):
        pool = np.array(pool)
        payload = np.asarray(payload)
        for j, block in enumerate(np.asarray(block_ids)):
            pool[block] = payload[j]
        return pool


class _FakeRankEngine:
    """MicroBatchScheduler's engine contract with host state: score =
    sum of a row's categorical ids, mod 7."""

    batch_buckets = (8,)
    n_tables = 3
    stats: dict = {}

    def place_params(self, params):
        return params

    def feature_arrays(self, cat, dense):
        cat = np.asarray(cat, np.int32)
        if cat.ndim != 2 or cat.shape[1] != self.n_tables:
            raise ValueError(f"cat must be [batch, {self.n_tables}]")
        return cat, None

    def rank(self, params, cat, dense=None):
        return (np.asarray(cat).sum(axis=1) % 7).astype(np.float32)


def make_paged_scheduler():
    """The traced-vs-plain overhead guard builds the identical scheduler
    twice; keep construction in one place."""
    from tf_yarn_tpu.serving.scheduler import SlotScheduler

    return SlotScheduler(
        _FakePagedEngine(), params=None, max_slots=2,
        block_size=4, max_seq_len=32,
    )


def drive_paged_scheduler(scheduler, prompts, max_new_tokens=3,
                          max_ticks=200):
    """Submit `prompts`, tick until every response finishes; returns the
    responses (deterministic emission — the overhead guard compares
    them across traced/plain runs)."""
    from tf_yarn_tpu.serving.request import SamplingParams

    responses = [
        scheduler.submit(list(prompt),
                         SamplingParams(max_new_tokens=max_new_tokens))
        for prompt in prompts
    ]
    for _ in range(max_ticks):
        scheduler.tick()
        if all(response.done for response in responses):
            return responses
    raise RuntimeError(f"scheduler not drained after {max_ticks} ticks")


# --------------------------------------------------------------------------
# Scenario drivers
# --------------------------------------------------------------------------

def _slot_scheduler(tracer: RaceTracer) -> None:
    """SlotScheduler + BlockPool + PrefixCache ticking with admissions
    and stats snapshots arriving from other threads — the serving hot
    path under continuous batching."""
    scheduler = make_paged_scheduler()
    tracer.watch(scheduler, "scheduler")
    tracer.watch(scheduler.queue, "queue")
    tracer.watch(scheduler._blocks, "pool")
    tracer.watch(scheduler._prefix, "prefix")

    responses: list = []

    def submit(count):
        def body():
            for index in range(count):
                responses.append(drive_submit(index))
        return body

    def drive_submit(index):
        from tf_yarn_tpu.serving.request import SamplingParams

        return scheduler.submit(
            [1, 2, 3, 4, 5 + index],
            SamplingParams(max_new_tokens=3),
        )

    def tick_until_done():
        for _ in range(200):
            scheduler.tick()
            if all(response.done for response in responses):
                return
        raise RuntimeError("scheduler not drained")

    _phase("race-submit-a", submit(2))
    _phase("race-tick-a", tick_until_done)
    _phase("race-stats", lambda: scheduler.stats())
    _phase("race-submit-b", submit(1))
    _phase("race-tick-b", tick_until_done)
    _phase("race-stats-b", lambda: scheduler.stats())


def _suspend_resume(tracer: RaceTracer) -> None:
    """SlotScheduler with a host tier under KV oversubscription: a
    batch-tier stream is suspended (blocks swapped to the host store)
    to admit an interactive request, then resumed after it retires —
    tiered submits, swap ticks and stats snapshots on distinct threads
    cover the suspend/resume lifecycle's lock discipline."""
    from tf_yarn_tpu.serving.request import SamplingParams
    from tf_yarn_tpu.serving.scheduler import SlotScheduler

    scheduler = SlotScheduler(
        _FakePagedEngine(), params=None, max_slots=2,
        block_size=4, max_seq_len=32,
        num_blocks=5, kv_host_blocks=16,
        tier_caps={"batch": 2, "interactive": 2},
    )
    tracer.watch(scheduler, "scheduler")
    tracer.watch(scheduler.queue, "queue")
    tracer.watch(scheduler._blocks, "pool")
    tracer.watch(scheduler._prefix, "prefix")
    tracer.watch(scheduler._host_store, "host_store")

    responses: list = []

    def submit(prompt, tier):
        def body():
            responses.append(scheduler.submit(
                list(prompt), SamplingParams(max_new_tokens=6), tier=tier,
            ))
        return body

    def tick(count):
        def body():
            for _ in range(count):
                scheduler.tick()
        return body

    def tick_until_done():
        for _ in range(200):
            scheduler.tick()
            if all(response.done for response in responses):
                return
        raise RuntimeError("oversubscribed scheduler not drained")

    _phase("race-submit-batch", submit(range(1, 9), "batch"))
    _phase("race-tick-batch", tick(3))
    _phase("race-submit-interactive", submit(range(2, 10), "interactive"))
    _phase("race-tick-swap", tick_until_done)
    _phase("race-stats", lambda: scheduler.stats())
    if not scheduler.stats()["swap"]["suspends"]:
        raise RuntimeError("scenario never exercised a suspend")


def _prefill_ship(tracer: RaceTracer) -> None:
    """Disaggregated prefill under concurrent ships: PrefillWorker
    builds wires on per-connection threads (its ONE lock is the whole
    discipline), PrefillClient ships/imports from a frontend handler
    thread while the decode scheduler ticks and stats snapshot from
    others — the serving/prefill.py hot path end to end, minus the
    HTTP socket (the ``post=`` seam calls the worker directly)."""
    import json

    from tf_yarn_tpu.serving.prefill import (
        PrefillClient,
        PrefillTierConfig,
        PrefillWorker,
    )
    from tf_yarn_tpu.serving.scheduler import SlotScheduler
    from tf_yarn_tpu.serving.server import encode_block_wire

    worker = PrefillWorker(_FakePagedEngine(), params=None, block_size=4)
    scheduler = SlotScheduler(
        _FakePagedEngine(), params=None, max_slots=2,
        block_size=4, max_seq_len=32,
    )

    def post(endpoint, prompt, timeout_s):
        return json.dumps(
            encode_block_wire(worker.prefill_prompt(prompt))
        ).encode()

    client = PrefillClient(
        PrefillTierConfig(offload_threshold=5, endpoint="127.0.0.1:1"),
        scheduler, block_size=4, post=post,
    )
    tracer.watch(worker, "worker")
    tracer.watch(worker._blocks, "worker_pool")
    tracer.watch(worker._prefix, "worker_prefix")
    tracer.watch(client, "client")
    tracer.watch(scheduler, "scheduler")
    tracer.watch(scheduler._blocks, "pool")
    tracer.watch(scheduler._prefix, "prefix")

    prompt = list(range(1, 10))
    outcomes: list = []
    _phase("race-ship",
           lambda: outcomes.append(client.maybe_ship(prompt)))
    _phase("race-prefill-b",
           lambda: worker.prefill_prompt(list(range(2, 11))))
    _phase("race-drive",
           lambda: drive_paged_scheduler(scheduler, [prompt]))
    _phase("race-stats", lambda: (worker.stats(), client.stats(),
                                  scheduler.stats()))
    # Re-shipping the same content from yet another handler thread must
    # stop at the client's memo — no second import races the live grid
    # (imports ride the scheduler control queue; hand-driven here, the
    # importing caller IS the de-facto scheduler thread).
    _phase("race-ship-b",
           lambda: outcomes.append(client.maybe_ship(prompt)))
    _phase("race-worker-stats", lambda: worker.stats())
    if outcomes != ["shipped", "already_shipped"]:
        raise RuntimeError(f"unexpected ship outcomes: {outcomes}")


def _micro_batch(tracer: RaceTracer) -> None:
    """MicroBatchScheduler under concurrent /v1/rank-style submits,
    ticks and stats — the ranking hot path."""
    from tf_yarn_tpu.ranking.scheduler import MicroBatchScheduler

    scheduler = MicroBatchScheduler(
        _FakeRankEngine(), params=None, max_batch=4, max_wait_ms=0.0,
    )
    tracer.watch(scheduler, "scheduler")
    tracer.watch(scheduler.queue, "queue")

    responses: list = []

    def submit(count):
        def body():
            for index in range(count):
                responses.append(scheduler.submit(
                    [[index + 1, 2, 3], [4, 5, index + 6]]
                ))
        return body

    def tick_until_done():
        for _ in range(100):
            scheduler.tick()
            if all(response.done for response in responses):
                return
        raise RuntimeError("ranking scheduler not drained")

    _phase("race-submit-a", submit(2))
    _phase("race-tick-a", tick_until_done)
    _phase("race-stats", lambda: scheduler.stats())
    _phase("race-submit-b", submit(1))
    _phase("race-tick-b", tick_until_done)
    _phase("race-stats-b", lambda: scheduler.stats())


def _registry(tracer: RaceTracer) -> None:
    """ReplicaRegistry refresh vs report_failure vs policy reads — the
    router's view of the fleet. healthy() hands out copies made under
    the registry lock, so the policy's lock-free load reads can never
    touch a replica the refresher is mutating (the PR 16 fix)."""
    from tf_yarn_tpu import event
    from tf_yarn_tpu.coordination.kv import InProcessKV
    from tf_yarn_tpu.fleet.policy import LeastLoadedPolicy, RoundRobinPolicy
    from tf_yarn_tpu.fleet.registry import ReplicaRegistry

    kv = InProcessKV()
    tasks = ["serving:0", "serving:1"]
    for index, task in enumerate(tasks):
        kv.put_str(f"{task}/{event.SERVING_ENDPOINT}",
                   f"127.0.0.1:{9000 + index}")

    def probe(endpoint):
        return {"status": "ok", "queue_depth": int(endpoint[-1]) % 3,
                "active_slots": 1}

    registry = ReplicaRegistry(
        kv, tasks, probe=probe, probe_interval_s=0.0,
    )
    tracer.watch(registry, "registry")
    _phase("race-refresh-a", lambda: registry.refresh(force=True))
    for task in tasks:
        tracer.watch(registry.get(task), f"replica[{task}]")

    def fail_one():
        registry.report_failure(tasks[0], ConnectionError("boom"))

    def policy_reads():
        round_robin = RoundRobinPolicy()
        least_loaded = LeastLoadedPolicy()
        for _ in range(4):
            healthy = registry.healthy()
            if healthy:
                round_robin.pick(healthy)
                least_loaded.pick(healthy)
            registry.snapshot()

    _phase("race-fail", fail_one)
    _phase("race-refresh-b", lambda: registry.refresh(force=True))
    _phase("race-policy", policy_reads)
    _phase("race-inflight",
           lambda: registry.note_inflight(tasks[1], 1))
    _phase("race-policy-b", policy_reads)


def _fleet_monitor(tracer: RaceTracer) -> None:
    """FleetMonitor scrape-and-merge vs router-handler aggregate()
    reads on distinct threads, including the degradation path (a
    failed scrape falling back to last-good, marked stale). Expected
    fully clean: every monitor-state access goes through its lock and
    aggregate() hands out deep copies."""
    from tf_yarn_tpu import event
    from tf_yarn_tpu.coordination.kv import InProcessKV
    from tf_yarn_tpu.fleet.monitor import FleetMonitor
    from tf_yarn_tpu.fleet.registry import ReplicaRegistry
    from tf_yarn_tpu.telemetry.exposition import STATS_SCHEMA_VERSION
    from tf_yarn_tpu.telemetry.registry import Histogram

    kv = InProcessKV()
    tasks = ["serving:0", "serving:1"]
    for index, task in enumerate(tasks):
        kv.put_str(f"{task}/{event.SERVING_ENDPOINT}",
                   f"127.0.0.1:{9100 + index}")

    def probe(endpoint):
        return {"status": "ok", "queue_depth": 0, "active_slots": 1}

    registry = ReplicaRegistry(
        kv, tasks, probe=probe, probe_interval_s=0.0,
    )

    down: set = set()

    def scrape(endpoint):
        if endpoint in down:
            raise ConnectionError("scrape target down")
        hist = Histogram()
        for step in range(1, 4):
            hist.observe(0.01 * step)
        return {
            "schema_version": STATS_SCHEMA_VERSION,
            "signals": {
                "version": 1,
                "histograms": {
                    "serving/ttft_seconds": hist.to_signal(window=False),
                },
                "scalars": {},
            },
        }

    monitor = FleetMonitor(
        registry, scrape=scrape, interval_s=0.0,
        slo={"ttft_p95_s": 0.5},
    )
    tracer.watch(monitor, "monitor")

    _phase("race-refresh", lambda: registry.refresh(force=True))
    _phase("race-scrape-a", lambda: monitor.poll_once())
    _phase("race-handler-a", lambda: monitor.aggregate())
    _phase("race-down", lambda: down.add("127.0.0.1:9100"))
    _phase("race-scrape-b", lambda: monitor.poll_once())
    _phase("race-handler-b", lambda: monitor.aggregate())
    aggregate = monitor.aggregate()
    if aggregate["status"] != "ok" or not aggregate["stale_replicas"]:
        raise RuntimeError("scenario never exercised stale degradation")


def _fleet_autoscaler(tracer: RaceTracer) -> None:
    """FleetAutoscaler poll cycles racing monitor scrapes, registry
    refresh/ejects, and router-handler stats() reads — the elastic
    serving decision plane. Expected fully clean: the autoscaler's
    inputs are per-call copies (registry.snapshot, monitor.aggregate),
    it plans and records under its own lock, and actuation/warm-start
    HTTP runs with NO lock held (a slow peer pull must never serialize
    against a /stats read)."""
    from tf_yarn_tpu import event
    from tf_yarn_tpu.coordination.kv import InProcessKV
    from tf_yarn_tpu.fleet.autoscaler import AutoscalePolicy, FleetAutoscaler
    from tf_yarn_tpu.fleet.monitor import FleetMonitor
    from tf_yarn_tpu.fleet.registry import ReplicaRegistry
    from tf_yarn_tpu.telemetry.exposition import STATS_SCHEMA_VERSION
    from tf_yarn_tpu.telemetry.registry import Histogram

    kv = InProcessKV()
    tasks = ["serving:0", "serving:1"]
    for index, task in enumerate(tasks):
        kv.put_str(f"{task}/{event.SERVING_ENDPOINT}",
                   f"127.0.0.1:{9200 + index}")

    def probe(endpoint):
        return {"status": "ok", "queue_depth": 0, "active_slots": 1}

    registry = ReplicaRegistry(
        kv, tasks, probe=probe, probe_interval_s=0.0,
    )

    def scrape(endpoint):
        hist = Histogram()
        for step in range(1, 4):
            hist.observe(0.1 * step)  # p95 ~0.3s: over the trigger
        return {
            "schema_version": STATS_SCHEMA_VERSION,
            "signals": {
                "version": 1,
                "histograms": {
                    "serving/ttft_seconds": hist.to_signal(window=False),
                },
                "scalars": {},
            },
        }

    monitor = FleetMonitor(registry, scrape=scrape, interval_s=0.0)
    autoscaler = FleetAutoscaler(
        registry,
        monitor,
        {"generate": AutoscalePolicy(
            min_replicas=1, max_replicas=4,
            scale_out_queue_depth=None, scale_out_p95_s=0.05,
            scale_in_load=None, cooldown_cycles=0,
        )},
        actuate=lambda kind, current, target, reason: True,
        fetch_blocks=lambda endpoint: b"{}",
        push_blocks=lambda endpoint, body: {"imported_blocks": 1,
                                            "registered_entries": 1},
    )
    tracer.watch(autoscaler, "autoscaler")

    _phase("race-refresh-a", lambda: registry.refresh(force=True))
    _phase("race-scrape-a", lambda: monitor.poll_once())
    _phase("race-autoscale-a", lambda: autoscaler.poll_once())
    _phase("race-eject", lambda: registry.report_failure(
        tasks[0], ConnectionError("preempted")))
    _phase("race-autoscale-b", lambda: autoscaler.poll_once())
    # The relaunched incarnation re-advertises the SAME KV key at a NEW
    # port: refresh probes the new address and re-admits (readmissions
    # += 1), and the next cycle sees the endpoint change and
    # warm-starts it from its peer through the injected seams.
    _phase("race-relaunch", lambda: kv.put_str(
        f"{tasks[0]}/{event.SERVING_ENDPOINT}", "127.0.0.1:9300"))
    _phase("race-refresh-b", lambda: registry.refresh(force=True))
    _phase("race-scrape-b", lambda: monitor.poll_once())
    _phase("race-autoscale-c", lambda: autoscaler.poll_once())
    _phase("race-stats", lambda: autoscaler.stats())
    stats = autoscaler.stats()
    if not stats["scale_events"]:
        raise RuntimeError("scenario never exercised a scale decision")
    if not any("imported_blocks" in w for w in stats["warm_starts"]):
        raise RuntimeError("scenario never exercised a peer warm start")


def _metrics_and_spans(tracer: RaceTracer) -> None:
    """A private MetricsRegistry + Tracer under multi-thread increments,
    span recording and flush — expected fully clean (every instrument
    is lock-guarded); this scenario is the false-positive guard for the
    tracer itself."""
    from tf_yarn_tpu.telemetry.registry import MetricsRegistry
    from tf_yarn_tpu.telemetry.spans import Tracer

    registry = MetricsRegistry()
    spans = Tracer(capacity=128)
    counter = registry.counter("race/total")
    histogram = registry.histogram("race/seconds")
    tracer.watch(registry, "metrics")
    tracer.watch(spans, "spans")
    tracer.watch(counter, "counter")
    tracer.watch(histogram, "histogram")

    def produce():
        for index in range(5):
            counter.inc()
            histogram.observe(0.1 * index)
            registry.gauge("race/depth").set(index)
            with spans.span("race/work", index=index):
                pass

    def flush():
        registry.snapshot()
        spans.records()

    _phase("race-produce-a", produce)
    _phase("race-flush", flush)
    _phase("race-produce-b", produce)
    _phase("race-flush-b", flush)


def _checkpoint_writer(tracer: RaceTracer) -> None:
    """CheckpointWriter save/finalize overlap: the train thread submits
    saves (including the re-save-same-tree path that waits on the async
    checkpointer) while the internal finalizer thread walks the same
    object — the PR 9 orbax check-then-join regression surface."""
    import tempfile

    from tf_yarn_tpu.checkpoint import CheckpointWriter

    state = {"w": np.zeros((4,), np.float32)}
    with tempfile.TemporaryDirectory(prefix="race-ckpt-") as tmp:
        writer = CheckpointWriter(keep_last_n=2)
        tracer.watch(writer, "writer")
        try:
            def saves():
                writer.save(tmp, 1, state)
                # Same tree re-saved: exercises the wait-for-previous
                # path (the original orbax race site) on this thread
                # while the finalizer may hold the ckptr lock.
                writer.save(tmp, 1, state)
                writer.wait()

            _phase("race-train", saves)
            _phase("race-train-b", lambda: (writer.save(tmp, 2, state),
                                            writer.wait()))
        finally:
            writer.close()


def default_scenarios() -> List[Scenario]:
    """The tier-1 / CLI suite: every driver is deterministic and fast.
    allow= justifications are documented in docs/StaticAnalysis.md
    ("Concurrency engine: suppressions")."""
    return [
        Scenario(
            "serving.slot_scheduler", _slot_scheduler,
            allow=(
                ("scheduler._ticks", _ADVISORY),
                ("scheduler._prefill_tokens", _ADVISORY),
                ("scheduler._decode_tokens", _ADVISORY),
                ("scheduler._peak_streams", _ADVISORY),
                ("scheduler._prefilled_tokens", _ADVISORY),
                ("scheduler._prefills_ceiling", _ADVISORY),
                ("scheduler._prefills_floor", _ADVISORY),
                ("scheduler._prefill_pad_tokens", _ADVISORY),
                ("scheduler._prefill_keys_formed", _ADVISORY),
                ("scheduler._prefill_keys_visible", _ADVISORY),
                ("scheduler._kv_token_steps", _ADVISORY),
                ("scheduler._kv_read_token_steps", _ADVISORY),
                ("scheduler._slot_steps", _ADVISORY),
                ("scheduler._steps", _ADVISORY),
                ("scheduler._steps_ahead", _ADVISORY),
                ("scheduler._steps_read", _ADVISORY),
                ("scheduler._steps_host_paced", _ADVISORY),
                ("scheduler._host_seconds", _ADVISORY),
                ("scheduler._sync_wait_seconds", _ADVISORY),
                ("scheduler._idle_wait_seconds", _ADVISORY),
                ("scheduler._clean_intervals", _ADVISORY),
                ("scheduler._clean_interval_seconds", _ADVISORY),
                ("scheduler._ahead_intervals", _ADVISORY),
                ("scheduler._ahead_interval_seconds", _ADVISORY),
                ("scheduler._ahead_prefill_tokens", _ADVISORY),
                ("scheduler._slow_steps", _ADVISORY),
                ("scheduler._slow_step_seconds", _ADVISORY),
                ("scheduler._slowest_step", _ADVISORY),
                ("prefix.hits", _ADVISORY),
                ("prefix.misses", _ADVISORY),
            ),
        ),
        Scenario(
            "serving.suspend_resume", _suspend_resume,
            allow=(
                ("scheduler._ticks", _ADVISORY),
                ("scheduler._prefill_tokens", _ADVISORY),
                ("scheduler._decode_tokens", _ADVISORY),
                ("scheduler._peak_streams", _ADVISORY),
                ("scheduler._prefilled_tokens", _ADVISORY),
                ("scheduler._prefills_ceiling", _ADVISORY),
                ("scheduler._prefills_floor", _ADVISORY),
                ("scheduler._prefill_pad_tokens", _ADVISORY),
                ("scheduler._prefill_keys_formed", _ADVISORY),
                ("scheduler._prefill_keys_visible", _ADVISORY),
                ("scheduler._kv_token_steps", _ADVISORY),
                ("scheduler._kv_read_token_steps", _ADVISORY),
                ("scheduler._slot_steps", _ADVISORY),
                ("scheduler._steps", _ADVISORY),
                ("scheduler._steps_ahead", _ADVISORY),
                ("scheduler._steps_read", _ADVISORY),
                ("scheduler._steps_host_paced", _ADVISORY),
                ("scheduler._host_seconds", _ADVISORY),
                ("scheduler._sync_wait_seconds", _ADVISORY),
                ("scheduler._idle_wait_seconds", _ADVISORY),
                ("scheduler._clean_intervals", _ADVISORY),
                ("scheduler._clean_interval_seconds", _ADVISORY),
                ("scheduler._ahead_intervals", _ADVISORY),
                ("scheduler._ahead_interval_seconds", _ADVISORY),
                ("scheduler._ahead_prefill_tokens", _ADVISORY),
                ("scheduler._slow_steps", _ADVISORY),
                ("scheduler._slow_step_seconds", _ADVISORY),
                ("scheduler._slowest_step", _ADVISORY),
                ("scheduler._suspends", _ADVISORY),
                ("scheduler._resumes", _ADVISORY),
                ("scheduler._swap_out_blocks", _ADVISORY),
                ("scheduler._swap_in_blocks", _ADVISORY),
                ("host_store._used", _ADVISORY),
                ("prefix.hits", _ADVISORY),
                ("prefix.misses", _ADVISORY),
            ),
        ),
        # No allow= entries: every shared field in the prefill tier is
        # lock-guarded (worker lock / client lock), and the single
        # import rides the scheduler control queue.
        Scenario("serving.prefill_ship", _prefill_ship),
        Scenario(
            "ranking.micro_batch", _micro_batch,
            allow=(
                ("scheduler._ticks", _ADVISORY),
                ("scheduler._rows_scored", _ADVISORY),
            ),
        ),
        Scenario("fleet.registry", _registry),
        Scenario("fleet.monitor", _fleet_monitor),
        Scenario("fleet.autoscaler", _fleet_autoscaler),
        Scenario("telemetry.metrics_spans", _metrics_and_spans),
        Scenario("checkpoint.writer", _checkpoint_writer),
    ]
