"""Disaggregated prefill/decode: the `prefill` task tier.

Layers mirroring tests/test_serving.py's seam:

* :class:`PrefillWorker` wire units on the deterministic fake paged
  engine: longest-first entries, private prefix-cache reuse, empty-wire
  degradation (short bucket, exhausted pool), validation.
* :class:`PrefillServer` over real HTTP: the ``/v1/prefill`` protocol,
  fleet-compatible ``/healthz`` / ``/stats``, drain surfacing.
* :class:`PrefillClient` two-stage dispatch through the ``post=`` /
  ``resolver=`` seams: the full degradation ladder (below-threshold,
  memo, no-replica, quarantine backoff, empty wire, import refusal) —
  every rung ends in local prefill, never an error.
* `/v1/blocks` export hardening (scheduler side): stale entries whose
  blocks hit refcount zero are dropped, donor blocks are pinned against
  reallocation for the duration of the extract, and a hammer drives
  export against LRU eviction pressure on the live scheduler thread.
* Registry/router integration: `prefill_endpoint` advertisements are
  discovered as KIND_PREFILL; preempted-mid-ship and scale-from-zero
  both degrade to bit-identical local serving with zero failures.
* End-to-end on CPU: real engines on BOTH sides of real HTTP — a long
  prompt through a real prefill replica streams bit-identical to
  local-prefill serving (and `generate_legacy`), with ZERO decode-side
  prefill compiles for the shipped span; the sampled + int8 matrix and
  the kill-mid-run degradation run behind the `slow` marker (the fp
  greedy run is the in-suite representative).
"""

import http.client
import json
import threading
import time

import numpy as np
import pytest

from tf_yarn_tpu import event, telemetry
from tf_yarn_tpu.coordination.kv import InProcessKV
from tf_yarn_tpu.fleet.registry import (
    KIND_GENERATE,
    KIND_PREFILL,
    ReplicaRegistry,
)
from tf_yarn_tpu.serving import (
    PrefillClient,
    PrefillServer,
    PrefillTierConfig,
    PrefillWorker,
    SamplingParams,
    ServingServer,
    SlotScheduler,
    kv_prefill_resolver,
    parse_prefill_tier,
)
from tf_yarn_tpu.serving.paging import prefix_keys
from tf_yarn_tpu.serving.server import decode_block_wire, encode_block_wire

from tests.fakes import FakePagedEngine
from tests.test_serving import (
    _drive,
    _legacy_stream,
    _paged_scheduler,
    _post,
)


# --------------------------------------------------------------------------
# PrefillTierConfig / parse_prefill_tier
# --------------------------------------------------------------------------

def test_parse_prefill_tier_validates_fields():
    tier = parse_prefill_tier({"offload_threshold": 128, "backoff_s": 1.0})
    assert tier.offload_threshold == 128 and tier.endpoint is None
    assert parse_prefill_tier(tier) is tier
    with pytest.raises(ValueError, match="offload_threshold"):
        parse_prefill_tier({"offload_threshold": 0})
    with pytest.raises(ValueError, match="timeout_s"):
        parse_prefill_tier({"timeout_s": 0.0})
    with pytest.raises(ValueError, match="num_blocks"):
        parse_prefill_tier({"num_blocks": 1})
    with pytest.raises(ValueError):  # unknown field names the key
        parse_prefill_tier({"offload_tokens": 5})
    with pytest.raises(ValueError, match="dict"):
        parse_prefill_tier([128])


# --------------------------------------------------------------------------
# PrefillWorker on the fake paged engine: wire shape + cache reuse
# --------------------------------------------------------------------------

def _fake_worker(**kwargs):
    engine = FakePagedEngine()  # buckets (4, 8), max_seq_len 32
    worker = PrefillWorker(engine, params=None, block_size=4, **kwargs)
    return engine, worker


def test_worker_wire_longest_first_and_scheduler_round_trip():
    """prompt [1..9]: bucket 8 -> 2 whole blocks. The wire carries one
    entry per prefix length, LONGEST FIRST (the receiver's hot-first
    clipping must keep the full span), and importing it into a decode
    scheduler reproduces the local-prefill stream with NO decode-side
    prefill call."""
    engine, worker = _fake_worker()
    prompt = list(range(1, 10))
    wire = worker.prefill_prompt(prompt)
    assert wire["schema_version"] == 1 and wire["block_size"] == 4
    assert wire["n_blocks"] == 2 and wire["group_width"] == 8
    keys = prefix_keys(prompt, 4, 2)
    assert [entry["key"] for entry in wire["entries"]] == [
        keys[1].hex(), keys[0].hex()
    ]
    assert [len(entry["blocks"]) for entry in wire["entries"]] == [2, 1]
    # The fake pool stores tokens: the shipped rows ARE the prompt's
    # first 8 tokens, in block order.
    leaves = wire["groups"][0]["leaves"]
    shipped = np.concatenate(
        [np.asarray(leaf)[:2].reshape(-1) for leaf in leaves]
    )
    assert shipped.tolist() == prompt[:8]
    # Wire blocks survive the JSON encode/decode round trip verbatim.
    decoded = decode_block_wire(
        json.loads(json.dumps(encode_block_wire(wire)))
    )
    assert decoded["entries"] == wire["entries"]

    # Local-prefill reference stream.
    _ref_engine, ref_scheduler = _paged_scheduler()
    ref = ref_scheduler.submit(prompt, SamplingParams(max_new_tokens=3))
    _drive(ref_scheduler, [ref])

    # Import, then serve the same prompt: identical stream, no prefill.
    decode_engine, scheduler = _paged_scheduler()
    result = scheduler.import_prefixes(decoded)
    assert result["imported_blocks"] == 2
    assert result["registered_entries"] == 2
    response = scheduler.submit(prompt, SamplingParams(max_new_tokens=3))
    _drive(scheduler, [response])
    assert response.result(timeout=1) == ref.result(timeout=1)
    kinds = [c[0] for c in decode_engine.calls]
    assert "prefill" not in kinds and "pack" not in kinds
    assert worker.stats()["exported_blocks"] == 2


def test_worker_prefix_cache_reuses_computed_blocks():
    engine, worker = _fake_worker()
    prompt = list(range(1, 10))
    first = worker.prefill_prompt(prompt)
    second = worker.prefill_prompt(prompt)
    assert second["entries"] == first["entries"]
    # One engine prefill, one pack: the repeat came from the worker's
    # own prefix cache (the request-level refs were dropped both times).
    kinds = [c[0] for c in engine.calls]
    assert kinds.count("prefill") == 1 and kinds.count("pack") == 1
    snap = worker.stats()
    assert snap["prefill_requests"] == 2
    assert snap["prefill_cache_hits"] == 1
    assert snap["block_pool"]["used_blocks"] == \
        snap["prefix_cache"]["cached_blocks"]


def test_worker_empty_wire_below_bucket_and_pool_exhausted():
    # prompt_len 4: largest bucket <= 3 is none -> no whole block.
    _engine, worker = _fake_worker()
    wire = worker.prefill_prompt([5, 6, 7, 8])
    assert wire["n_blocks"] == 0 and wire["entries"] == []
    # A 2-block pool (1 usable) cannot hold the 2-block pack: empty
    # wire, NOT an exception — the decode side just prefills locally.
    _engine, tiny = _fake_worker(num_blocks=2)
    wire = tiny.prefill_prompt(list(range(1, 10)))
    assert wire["n_blocks"] == 0
    assert tiny.stats()["block_pool"]["used_blocks"] == 0


def test_worker_validation_errors():
    with pytest.raises(ValueError, match="empty prompt"):
        _fake_worker()[1].prefill_prompt([])
    with pytest.raises(ValueError, match="max_seq_len"):
        _fake_worker()[1].prefill_prompt(list(range(40)))
    with pytest.raises(ValueError, match="divide"):
        PrefillWorker(FakePagedEngine(), params=None, block_size=5)
    with pytest.raises(ValueError, match="max_seq_len"):
        PrefillWorker(object(), params=None, block_size=4)


# --------------------------------------------------------------------------
# PrefillServer: the /v1/prefill HTTP protocol
# --------------------------------------------------------------------------

def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _post_prefill(port, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request(
            "POST", "/v1/prefill", json.dumps(body),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def test_prefill_server_http_protocol_and_drain():
    _engine, worker = _fake_worker()
    server = PrefillServer(worker)
    server.start()
    try:
        status, raw = _get(server.port, "/healthz")
        health = json.loads(raw)
        assert status == 200 and health["status"] == "ok"
        assert health["kind"] == "prefill"
        # The registry's generic load accounting reads these fields.
        assert health["queue_depth"] == 0 and health["active_slots"] == 0

        status, raw = _post_prefill(server.port,
                                    {"prompt": list(range(1, 10))})
        assert status == 200
        wire = decode_block_wire(json.loads(raw))
        assert wire["n_blocks"] == 2
        assert isinstance(wire["groups"][0]["leaves"][0], np.ndarray)

        status, raw = _post_prefill(server.port, {"prompt": []})
        assert status == 400 and b"empty" in raw
        status, raw = _post_prefill(server.port, {"nope": 1})
        assert status == 400
        status, _raw = _get(server.port, "/nope")
        assert status == 404

        status, raw = _get(server.port, "/stats")
        snap = json.loads(raw)
        assert status == 200 and snap["kind"] == "prefill"
        assert snap["prefill_requests"] == 1
        assert "signals" in snap

        status, raw = _get(server.port, "/metrics")
        assert status == 200
        assert b"serving_prefill_requests_total" in raw

        # Drain flips /healthz so the fleet registry ejects the replica
        # before the socket dies (the preemption handoff).
        worker.drain()
        status, raw = _get(server.port, "/healthz")
        assert json.loads(raw)["status"] == "draining"
    finally:
        server.stop()


# --------------------------------------------------------------------------
# PrefillClient: the degradation ladder through the post=/resolver= seams
# --------------------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def _wire_post(worker):
    """A post= seam answering from a live in-process worker."""
    calls = []

    def post(endpoint, prompt, timeout_s):
        calls.append(endpoint)
        return json.dumps(
            encode_block_wire(worker.prefill_prompt(prompt))
        ).encode()

    post.calls = calls
    return post


def _client(scheduler, post, resolver=None, clock=None, **cfg):
    cfg.setdefault("offload_threshold", 5)
    config = PrefillTierConfig(**cfg)
    if resolver is None and config.endpoint is None:
        config = PrefillTierConfig(**{**cfg, "endpoint": "127.0.0.1:1"})
    return PrefillClient(
        config, scheduler, block_size=4, resolver=resolver,
        clock=clock or _Clock(), post=post,
    )


def test_client_ships_and_admission_skips_the_shipped_span():
    _worker_engine, worker = _fake_worker()
    decode_engine, scheduler = _paged_scheduler()
    post = _wire_post(worker)
    client = _client(scheduler, post)
    prompt = list(range(1, 10))
    assert client.maybe_ship(prompt) == "shipped"
    response = scheduler.submit(prompt, SamplingParams(max_new_tokens=3))
    _drive(scheduler, [response])
    assert "prefill" not in [c[0] for c in decode_engine.calls]
    snap = client.stats()
    assert snap["ships"] == 1 and snap["shipped_blocks"] == 2
    assert snap["shipped_wire_bytes"] > 0
    assert snap["local_fallbacks"] == 0
    registry = telemetry.get_registry()
    assert registry.counter("serving/shipped_blocks_total").value >= 2


def test_client_below_threshold_and_memo_skip_the_hop():
    _worker_engine, worker = _fake_worker()
    _decode_engine, scheduler = _paged_scheduler()
    post = _wire_post(worker)
    client = _client(scheduler, post)
    # Below threshold: the post seam is never dialed.
    assert client.maybe_ship([1, 2, 3]) == "below_threshold"
    assert post.calls == []
    prompt = list(range(1, 10))
    assert client.maybe_ship(prompt) == "shipped"
    # Same content again: the local prefix cache already holds the
    # span — re-shipping would be pure waste.
    assert client.maybe_ship(prompt) == "already_shipped"
    assert len(post.calls) == 1


def test_client_no_replica_falls_back_then_rechecks_after_ttl():
    _decode_engine, scheduler = _paged_scheduler()
    _worker_engine, worker = _fake_worker()
    post = _wire_post(worker)
    clock = _Clock()
    endpoints = [None]

    def resolver():
        return endpoints[0]

    client = _client(scheduler, post, resolver=resolver, clock=clock,
                     resolve_ttl_s=2.0)
    prompt = list(range(1, 10))
    # Scale-from-zero: immediate local fallback, and the None
    # resolution is CACHED — requests inside the TTL do not re-scan.
    assert client.maybe_ship(prompt) == "no_replica"
    endpoints[0] = "127.0.0.1:7201"
    assert client.maybe_ship(prompt) == "no_replica"
    clock.now += 2.5  # TTL expired: the tier scaled up meanwhile
    assert client.maybe_ship(prompt) == "shipped"
    assert client.stats()["local_fallbacks"] == 2


def test_client_ship_failure_quarantines_then_recovers():
    _decode_engine, scheduler = _paged_scheduler()
    _worker_engine, worker = _fake_worker()
    clock = _Clock()
    good = _wire_post(worker)
    failures = {"n": 0}

    def post(endpoint, prompt, timeout_s):
        if failures["n"] > 0:
            failures["n"] -= 1
            raise ConnectionError("replica preempted mid-ship")
        return good(endpoint, prompt, timeout_s)

    client = _client(scheduler, post, clock=clock, backoff_s=5.0)
    failures["n"] = 1
    prompt = list(range(1, 10))
    assert client.maybe_ship(prompt) == "ship_failed"
    # Quarantined: the next request does not even dial.
    assert client.maybe_ship(prompt) == "backoff"
    clock.now += 6.0
    assert client.maybe_ship(prompt) == "shipped"
    assert client.stats()["local_fallbacks"] == 2


def test_client_empty_wire_falls_back_without_quarantine():
    _decode_engine, scheduler = _paged_scheduler()
    # 1 usable block: the worker's pool cannot hold any 2-block pack.
    _worker_engine, worker = _fake_worker(num_blocks=2)
    post = _wire_post(worker)
    client = _client(scheduler, post)
    prompt = list(range(1, 10))
    assert client.maybe_ship(prompt) == "empty_wire"
    # A healthy-but-full tier is NOT quarantined and the prompt is NOT
    # memoized — the next request tries again.
    assert client.maybe_ship(prompt) == "empty_wire"
    assert len(post.calls) == 2


def test_client_import_refusal_falls_back():
    _decode_engine, scheduler = _paged_scheduler()  # block_size 4
    engine = FakePagedEngine()
    worker = PrefillWorker(engine, params=None, block_size=8)
    post = _wire_post(worker)
    # Client keyed at the WORKER's block size so the ship proceeds; the
    # scheduler then refuses the mismatched wire.
    config = PrefillTierConfig(offload_threshold=5,
                               endpoint="127.0.0.1:1")
    client = PrefillClient(config, scheduler, block_size=8, post=post)
    assert client.maybe_ship(list(range(1, 18))) == "import_failed"
    assert client.stats()["local_fallbacks"] == 1


def test_client_never_raises():
    _decode_engine, scheduler = _paged_scheduler()
    client = _client(scheduler, post=None)
    # Unconvertible prompt tokens: swallowed, counted, local prefill.
    assert client.maybe_ship(["not", "tokens", "at", "all", "x", "y"]) \
        == "error"


# --------------------------------------------------------------------------
# /v1/blocks export hardening: eviction pressure mid-export (satellite)
# --------------------------------------------------------------------------

def _populated_scheduler():
    """A hand-driven paged scheduler whose prefix cache holds the
    2-block entry chain for prompt [1..9]."""
    engine, scheduler = _paged_scheduler()
    prompt = list(range(1, 10))
    response = scheduler.submit(prompt, SamplingParams(max_new_tokens=2))
    _drive(scheduler, [response])
    return engine, scheduler, prompt


def test_export_drops_stale_entries_with_freed_blocks(monkeypatch):
    """A stale export view can name an entry whose blocks were evicted
    (refcount 0) between the snapshot and the extract: the export must
    DROP it — shipping those rows under the old content key would
    poison every peer's cache — and must not crash retaining a free
    block."""
    engine, scheduler, prompt = _populated_scheduler()
    real_entries = scheduler._prefix.export_entries(None)
    # A block that is free right now (never part of the live entry).
    free_block = scheduler._blocks.allocate(1)[0]
    scheduler._blocks.release([free_block])
    stale = [(b"\xde\xad" * 8, [free_block])]
    monkeypatch.setattr(
        scheduler._prefix, "export_entries",
        lambda limit: list(real_entries) + stale,
    )
    wire = scheduler.export_hot_prefixes()
    shipped_keys = {entry["key"] for entry in wire["entries"]}
    assert (b"\xde\xad" * 8).hex() not in shipped_keys
    assert shipped_keys == {key.hex() for key, _ids in real_entries}
    assert wire["n_blocks"] == 2


def test_export_pins_donor_blocks_against_reallocation():
    """The refcount-zero race armed for real: mid-extract, evict every
    prefix entry and pack garbage into whatever the pool will hand out.
    With donors retained for the extract's duration the allocator can
    NEVER hand their ids back, so the shipped rows are the original
    KV — importing them into a peer reproduces the local stream."""
    engine, scheduler, prompt = _populated_scheduler()
    real_extract = engine.extract_blocks
    armed = {"fired": False}

    def hostile_extract(params, pool, block_ids, block_size):
        if not armed["fired"]:
            armed["fired"] = True
            # The eviction storm: release every cache ref, then grab
            # and overwrite as many blocks as the free list will give.
            scheduler._prefix.evict_for(scheduler._blocks.num_blocks)
            grabbed = []
            while True:
                got = scheduler._blocks.allocate(1)
                if got is None:
                    break
                grabbed.extend(got)
                scheduler._pool[got[0], :] = -99
            donors = [int(b) for b in np.asarray(block_ids)
                      if int(b) != 0]
            assert not set(donors) & set(grabbed), (
                "allocator handed out a donor block mid-export"
            )
            scheduler._blocks.release(grabbed)
        return real_extract(params, pool, block_ids, block_size)

    engine.extract_blocks = hostile_extract
    wire = scheduler.export_hot_prefixes()
    assert armed["fired"] and wire["n_blocks"] == 2
    shipped = np.concatenate([
        np.asarray(leaf)[:2].reshape(-1)
        for leaf in wire["groups"][0]["leaves"]
    ])
    assert shipped.tolist() == prompt[:8]  # not a -99 in sight

    # The receiving side serves the shipped span bit-identically.
    peer_engine, peer = _paged_scheduler()
    peer.import_prefixes(wire)
    response = peer.submit(prompt, SamplingParams(max_new_tokens=2))
    _drive(peer, [response])
    _ref_engine, ref = _paged_scheduler()
    ref_response = ref.submit(prompt, SamplingParams(max_new_tokens=2))
    _drive(ref, [ref_response])
    assert response.result(timeout=1) == ref_response.result(timeout=1)


def test_export_hammer_under_live_eviction_pressure():
    """Exports from a foreign thread against a LIVE scheduler loop
    churning a pool small enough that every admission evicts: every
    wire must be internally consistent (no dangling block indices, no
    exceptions), and the streams must stay correct throughout."""
    engine = FakePagedEngine()
    scheduler = SlotScheduler(
        engine, params=None, max_slots=2,
        block_size=4, num_blocks=7, max_seq_len=32,
        queue_capacity=64,
    )
    scheduler.start()
    errors = []
    stop = threading.Event()

    def hammer():
        try:
            while not stop.is_set():
                wire = scheduler.export_hot_prefixes()
                group_total = sum(
                    int(g["n_blocks"]) for g in wire["groups"]
                )
                assert group_total == wire["n_blocks"]
                for entry in wire["entries"]:
                    assert all(
                        0 <= i < wire["n_blocks"]
                        for i in entry["blocks"]
                    )
        except BaseException as exc:  # surfaced to the main thread
            errors.append(exc)

    thread = threading.Thread(target=hammer)
    thread.start()
    try:
        rng = np.random.RandomState(7)
        for round_no in range(30):
            prompts = [
                rng.randint(1, 90, (9,)).tolist() for _ in range(2)
            ]
            responses = [
                scheduler.submit(p, SamplingParams(max_new_tokens=2))
                for p in prompts
            ]
            for prompt, response in zip(prompts, responses):
                got = response.result(timeout=30)
                expected = (sum(prompt[:8]) + prompt[8]) % 97
                assert got[0] == expected, round_no
    finally:
        stop.set()
        thread.join(timeout=30)
        scheduler.close()
    assert not errors, errors[0]


# --------------------------------------------------------------------------
# registry + router integration: discovery and the fallback ladder
# --------------------------------------------------------------------------

def test_registry_discovers_prefill_kind():
    from tests.test_fleet import OK, ProbeScript

    kv = InProcessKV()
    probe = ProbeScript()
    event.serving_endpoint_event(kv, "serving:0", "127.0.0.1:7301")
    event.prefill_endpoint_event(kv, "prefill:0", "127.0.0.1:7302")
    probe.set("127.0.0.1:7301", OK)
    probe.set("127.0.0.1:7302", {**OK, "kind": "prefill"})
    registry = ReplicaRegistry(kv, probe=probe, probe_interval_s=0.0)
    healthy = registry.refresh(force=True)
    assert {r.task for r in healthy} == {"serving:0", "prefill:0"}
    assert registry.get("prefill:0").kind == KIND_PREFILL
    assert registry.get("serving:0").kind == KIND_GENERATE
    # The kind restriction keeps generate traffic off the prefill tier.
    assert [r.task for r in registry.healthy(kind=KIND_PREFILL)] == [
        "prefill:0"
    ]
    assert [r.task for r in registry.healthy(kind=KIND_GENERATE)] == [
        "serving:0"
    ]


def test_kv_resolver_round_robins_and_skips_tombstones():
    kv = InProcessKV()
    event.prefill_endpoint_event(kv, "prefill:0", "127.0.0.1:7401")
    event.prefill_endpoint_event(kv, "prefill:1", "127.0.0.1:7402")
    resolve = kv_prefill_resolver(kv)
    picks = {resolve(), resolve()}
    assert picks == {"127.0.0.1:7401", "127.0.0.1:7402"}
    # A stopped replica's advertisement is tombstoned out.
    event.heartbeat_stopped_event(kv, "prefill:1")
    assert {resolve(), resolve()} == {"127.0.0.1:7401"}
    event.heartbeat_stopped_event(kv, "prefill:0")
    assert resolve() is None


def _fake_http_stack(client_config=None, kv=None, resolver=None):
    """A real ServingServer over the fake paged engine, with an
    optional PrefillClient wired the way run_serving wires it."""
    engine, scheduler = _paged_scheduler()
    client = None
    if client_config is not None:
        client = PrefillClient(
            client_config, scheduler, block_size=4, kv=kv,
            resolver=resolver,
        )
    scheduler.start()
    server = ServingServer(scheduler, "127.0.0.1", 0,
                           prefill_client=client)
    server.start()
    return engine, scheduler, server, client


def test_http_preempted_mid_ship_degrades_bit_identical():
    """A prefill replica that dies between resolution and the POST: the
    request lands 200 with the LOCAL-prefill stream (bit-identical),
    and the tier is quarantined instead of failing requests."""
    prompt = list(range(1, 10))
    body = {"prompt": prompt, "max_new_tokens": 3}

    _e, local_sched, local_server, _c = _fake_http_stack()
    try:
        status, _h, raw = _post(local_server.port, body)
        assert status == 200
        local_tokens = json.loads(raw)["tokens"]
    finally:
        local_server.stop()
        local_sched.close()

    # The advertised replica is gone before the ship: a real connect
    # error on a port nothing listens on.
    _worker_engine, worker = _fake_worker()
    dead = PrefillServer(worker)
    dead.start()
    dead_endpoint = dead.endpoint
    dead.stop()
    config = PrefillTierConfig(
        offload_threshold=5, endpoint=dead_endpoint, timeout_s=2.0,
    )
    _e, scheduler, server, client = _fake_http_stack(config)
    try:
        status, _h, raw = _post(server.port, body)
        assert status == 200
        assert json.loads(raw)["tokens"] == local_tokens
        assert client.stats()["local_fallbacks"] == 1
        assert client.stats()["ships"] == 0
    finally:
        server.stop()
        scheduler.close()


def test_http_scale_from_zero_immediate_local_fallback_no_503():
    """No prefill replica has EVER advertised: requests flow at once
    through local prefill — no 503, no retry loop, no latency cliff."""
    prompt = list(range(1, 10))
    body = {"prompt": prompt, "max_new_tokens": 3}

    _e, local_sched, local_server, _c = _fake_http_stack()
    try:
        status, _h, raw = _post(local_server.port, body)
        local_tokens = json.loads(raw)["tokens"]
    finally:
        local_server.stop()
        local_sched.close()

    kv = InProcessKV()  # empty: the tier is scaled to zero
    config = PrefillTierConfig(offload_threshold=5)
    _e, scheduler, server, client = _fake_http_stack(config, kv=kv)
    try:
        status, _h, raw = _post(server.port, body)
        assert status == 200
        assert json.loads(raw)["tokens"] == local_tokens
        assert client.stats()["local_fallbacks"] == 1
    finally:
        server.stop()
        scheduler.close()


# --------------------------------------------------------------------------
# End-to-end on CPU: real engines both sides, real HTTP, bit-identical
# --------------------------------------------------------------------------

LONG_PROMPT = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3, 8, 4]


def _tiny_disagg_parts(kv_cache_dtype="bf16"):
    """One tiny model + params and a factory for INDEPENDENT engines:
    the decode-side compile accounting (`prefill_compiles == 0` for
    shipped spans) is only meaningful when the prefill replica runs its
    own engine instance."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from tf_yarn_tpu.models import transformer
    from tf_yarn_tpu.models.decode_engine import DecodeEngine

    cfg = transformer.TransformerConfig.tiny(
        scan_layers=False, remat=False, max_seq_len=64,
        dtype=jnp.float32, kv_cache_dtype=kv_cache_dtype,
    )
    model = transformer.Transformer(cfg)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    )

    def make_engine():
        return DecodeEngine(
            model, batch_buckets=(1, 2, 4), prompt_buckets=(4, 8, 16)
        )

    return model, params, make_engine


def _run_disagg_http(bodies, kv_cache_dtype="bf16", temperature=0.0,
                     kill_after_first=False, **extra_sched_kwargs):
    """Serve `bodies` through a decode stack whose PrefillClient pulls
    from a REAL prefill replica over HTTP, and the same bodies through
    an identical local-only stack. Extra keyword args (e.g. spec_k)
    reach BOTH SlotScheduler constructions. Returns (disagg_payloads,
    local_payloads, decode_engine, client, worker, model, params)."""
    model, params, make_engine = _tiny_disagg_parts(kv_cache_dtype)
    sched_kwargs = dict(
        block_size=8, temperature=temperature,
        **extra_sched_kwargs,
    )

    local_payloads = []
    local_sched = SlotScheduler(
        make_engine(), params, max_slots=2, **sched_kwargs
    )
    local_sched.start()
    local_server = ServingServer(local_sched, "127.0.0.1", 0)
    local_server.start()
    try:
        for body in bodies:
            status, _h, raw = _post(local_server.port, body)
            assert status == 200, raw
            local_payloads.append(json.loads(raw))
    finally:
        local_server.stop()
        local_sched.close()

    worker = PrefillWorker(make_engine(), params, block_size=8)
    prefill_server = PrefillServer(worker)
    prefill_server.start()
    decode_engine = make_engine()
    scheduler = SlotScheduler(
        decode_engine, params, max_slots=2, **sched_kwargs
    )
    client = PrefillClient(
        PrefillTierConfig(
            offload_threshold=16, endpoint=prefill_server.endpoint,
            timeout_s=60.0, backoff_s=0.2,
        ),
        scheduler, block_size=8,
    )
    scheduler.start()
    server = ServingServer(scheduler, "127.0.0.1", 0,
                           prefill_client=client)
    server.start()
    payloads = []
    stopped = False
    try:
        for i, body in enumerate(bodies):
            status, _h, raw = _post(server.port, body)
            assert status == 200, raw
            payloads.append(json.loads(raw))
            if kill_after_first and i == 0:
                prefill_server.stop()
                stopped = True
        return (payloads, local_payloads, decode_engine, client, worker,
                model, params)
    finally:
        server.stop()
        scheduler.close()
        if not stopped:
            prefill_server.stop()


def test_http_disagg_stream_matches_local_fp_greedy_no_decode_prefill():
    """The in-suite acceptance representative: a long prompt through a
    REAL prefill replica over real HTTP streams bit-identical to local-
    prefill serving AND generate_legacy, with the decode engine never
    compiling (or running) a prefill program — the shipped span covered
    it — and blocks counted on the ship telemetry."""
    body = {"prompt": LONG_PROMPT, "max_new_tokens": 8}
    payloads, local_payloads, decode_engine, client, worker, model, \
        params = _run_disagg_http([body])
    assert payloads[0]["tokens"] == local_payloads[0]["tokens"]
    assert payloads[0]["tokens"] == _legacy_stream(
        model, params, LONG_PROMPT, 8
    )
    # The whole point of the tier: decode-side prefill never ran.
    assert decode_engine.stats["prefill_compiles"] == 0
    snap = client.stats()
    assert snap["ships"] == 1 and snap["shipped_blocks"] == 2
    assert snap["local_fallbacks"] == 0
    assert worker.stats()["prefill_requests"] == 1
    registry = telemetry.get_registry()
    assert registry.counter("serving/shipped_blocks_total").value >= 2
    assert registry.counter(
        "serving/shipped_wire_bytes_total"
    ).value >= snap["shipped_wire_bytes"]
    assert registry.counter(
        "serving/prefill_offload_total", outcome="shipped"
    ).value >= 1


@pytest.mark.slow  # the fp greedy run above is the representative; the
# sampled + int8 corners (and their prefill_compiles == 0 bars) ride
# the full sweep
@pytest.mark.parametrize("kv_cache_dtype,temperature", [
    ("bf16", 0.8),   # sampled: the rng chain must survive the offload
    ("int8", 0.0),   # int8 pool: blocks ride the wire quantized
    ("int8", 0.8),
])
def test_http_disagg_matrix_bit_identical(kv_cache_dtype, temperature):
    body = {
        "prompt": LONG_PROMPT, "max_new_tokens": 8,
        "temperature": temperature, "seed": 11,
    }
    payloads, local_payloads, decode_engine, client, _worker, _model, \
        _params = _run_disagg_http(
            [body], kv_cache_dtype=kv_cache_dtype,
            temperature=temperature,
        )
    assert payloads[0]["tokens"] == local_payloads[0]["tokens"]
    assert decode_engine.stats["prefill_compiles"] == 0
    assert client.stats()["ships"] == 1


@pytest.mark.slow  # the fp greedy representative carries the tier-1
# bar; speculation composing with shipped spans rides the full sweep
def test_http_disagg_spec_stream_matches_local():
    """spec_k > 0 composes with the shipped span: the decode replica
    admits through the imported blocks (prefill_compiles == 0) and its
    speculative stream is bit-identical to the local spec stack and to
    generate_legacy."""
    body = {"prompt": LONG_PROMPT, "max_new_tokens": 8}
    payloads, local_payloads, decode_engine, client, _worker, model, \
        params = _run_disagg_http([body], spec_k=3)
    assert payloads[0]["tokens"] == local_payloads[0]["tokens"]
    assert payloads[0]["tokens"] == _legacy_stream(
        model, params, LONG_PROMPT, 8
    )
    assert decode_engine.stats["prefill_compiles"] == 0
    assert client.stats()["ships"] == 1


@pytest.mark.slow  # real-stack double build; the fake-engine
# preempted-mid-ship test carries the fallback bar in-suite
def test_http_disagg_kill_mid_run_degrades_with_zero_failures():
    """Kill the prefill replica between requests: the next long prompt
    serves 200 via local prefill, bit-identical to the local stack —
    zero failed requests across the outage."""
    other_long = list(reversed(LONG_PROMPT))
    bodies = [
        {"prompt": LONG_PROMPT, "max_new_tokens": 6},
        {"prompt": other_long, "max_new_tokens": 6},
    ]
    payloads, local_payloads, decode_engine, client, _worker, _model, \
        _params = _run_disagg_http(bodies, kill_after_first=True)
    assert [p["tokens"] for p in payloads] == [
        p["tokens"] for p in local_payloads
    ]
    snap = client.stats()
    assert snap["ships"] == 1  # first shipped, second fell back
    assert snap["local_fallbacks"] >= 1
    # The shipped span still never touched the decode prefill program;
    # the fallback request compiled it locally — exactly once.
    assert decode_engine.stats["prefill_compiles"] == 1


def test_stats_surface_exposes_prefill_offload():
    """/stats on a decode replica carries the prefill_offload block
    when the tier is configured (the monitor scrapes it fleet-wide)."""
    config = PrefillTierConfig(offload_threshold=5)
    _e, scheduler, server, _client = _fake_http_stack(
        config, resolver=lambda: None,
    )
    try:
        conn = http.client.HTTPConnection(
            "127.0.0.1", server.port, timeout=30
        )
        try:
            conn.request("GET", "/stats")
            snap = json.loads(conn.getresponse().read())
        finally:
            conn.close()
        assert snap["prefill_offload"]["offload_threshold"] == 5
        assert snap["prefill_offload"]["ships"] == 0
    finally:
        server.stop()
        scheduler.close()
