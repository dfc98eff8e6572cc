"""Peer warm start: the prefix-block transfer protocol.

A freshly (re)admitted generate replica primes its prefix cache from a
live peer — ``GET /v1/blocks`` on the donor, ``POST /v1/blocks`` on the
newcomer. Coverage mirrors the subsystem's seams:

* `PrefixCache.export_entries` / `register_imported`: the cache-level
  donor and receiver halves (MRU-first order, refcount discipline).
* `SlotScheduler.export_hot_prefixes` / `import_prefixes` on the
  deterministic fake paged engine: the roundtrip installs the donor's
  blocks under the same content addresses, the receiver's streams stay
  bit-identical to a cold replica's, re-import is a no-op, and
  geometry/layout mismatches are refused.
* `/v1/blocks` over real HTTP between two ServingServers (still the
  fake engine — fast), including the 400/409 refusal paths.
* One slow-marked e2e on the REAL stack (tiny transformer, DecodeEngine
  paged grid) holding the acceptance bar: the warm-started replica's
  streams are bit-identical to the cold replica's and its first
  hot-prefix request HITS. The fake-engine roundtrip above is its
  in-tier-1 representative.
"""

import http.client
import json

import numpy as np
import pytest

from tests.fakes import FakePagedEngine
from tests.test_serving import _drive, _paged_scheduler
from tf_yarn_tpu import telemetry
from tf_yarn_tpu.serving import (
    BlockPool,
    PrefixCache,
    SamplingParams,
    ServingServer,
)
from tf_yarn_tpu.serving.server import decode_block_wire, encode_block_wire


# --------------------------------------------------------------------------
# cache-level halves
# --------------------------------------------------------------------------

def test_export_entries_mru_first_with_limit():
    pool = BlockPool(num_blocks=12, block_size=4)
    cache = PrefixCache(pool, capacity=8)
    hot = tuple(range(8))
    cold = tuple(range(100, 108))
    ids_cold = pool.allocate(2)
    ids_hot = pool.allocate(2)
    assert cache.register(cold, 8, ids_cold)
    assert cache.register(hot, 8, ids_hot)
    # A lookup touch moves `cold`'s one-block entry (4 of its 8 tokens,
    # the longest hit leaving >= 1 token to replay under max_tokens=7)
    # back to the MRU end.
    cache.lookup(cold, max_tokens=7)
    exported = cache.export_entries()
    # Hot end first: the donor ships its most valuable entries before
    # any receiver-side clipping truncates the tail.
    assert exported[0][1] == ids_cold[:1]
    assert [ids for _, ids in cache.export_entries(limit=1)] \
        == [exported[0][1]]
    assert cache.export_entries(limit=0) == []
    with pytest.raises(ValueError, match="limit"):
        cache.export_entries(limit=-1)
    # Export is a view: no refcount change (allocation + the k=1 and
    # k=2 cache entries each hold one reference on the first block).
    assert pool.refcount(ids_hot[0]) == 3


def test_register_imported_retains_and_dedupes():
    pool = BlockPool(num_blocks=8, block_size=4)
    cache = PrefixCache(pool, capacity=4)
    ids = pool.allocate(2)
    key = b"\x01" * 16
    assert cache.register_imported(key, ids)
    assert pool.refcount(ids[0]) == 2  # import allocation + cache
    # Same content address again (a second warm-start pull): dedupe.
    assert not cache.register_imported(key, ids)
    assert pool.refcount(ids[0]) == 2
    # The import path drops its allocation reference afterwards; the
    # cache's reference keeps the blocks resident.
    pool.release(ids)
    assert pool.refcount(ids[0]) == 1
    assert cache.cached_blocks == 2
    assert not PrefixCache(pool, capacity=0).register_imported(b"k", [])


# --------------------------------------------------------------------------
# scheduler roundtrip on the fake paged engine
# --------------------------------------------------------------------------

def _served_donor(prompt=(1, 2, 3, 4, 5), max_new=3):
    """A donor scheduler that served `prompt` once: its prefix cache
    holds the prompt's full blocks, exactly what a live replica has."""
    engine, scheduler = _paged_scheduler()
    response = scheduler.submit(
        list(prompt), SamplingParams(max_new_tokens=max_new)
    )
    _drive(scheduler, [response])
    return engine, scheduler, response.result(timeout=1)


def test_export_import_roundtrip_streams_bit_identical():
    _, donor, donor_stream = _served_donor()
    wire = donor.export_hot_prefixes()
    assert wire["schema_version"] == 1
    assert wire["block_size"] == 4
    assert wire["n_blocks"] == 1  # prefill 4 = one full shared block
    assert len(wire["entries"]) == 1
    # Receiver: a cold replica installs the snapshot.
    _, receiver = _paged_scheduler()
    result = receiver.import_prefixes(wire)
    assert result == {"imported_blocks": 1, "registered_entries": 1,
                      "skipped_entries": 0}
    # Re-import of the same snapshot is a no-op: the content addresses
    # are already cached (idempotent warm start).
    again = receiver.import_prefixes(wire)
    assert again["registered_entries"] == 0
    # The warm receiver's stream is BIT-IDENTICAL to the cold donor's,
    # and its admission hit the imported prefix (no cold prefill).
    response = receiver.submit([1, 2, 3, 4, 5],
                               SamplingParams(max_new_tokens=3))
    _drive(receiver, [response])
    assert response.result(timeout=1) == donor_stream == [15, 30, 60]
    stats = receiver.stats()["prefix_cache"]
    assert stats["hits"] >= 1
    counters = telemetry.get_registry().snapshot()
    assert counters.get("serving/prefix_export_blocks_total", 0) >= 1
    assert counters.get("serving/prefix_import_blocks_total", 0) >= 1


def test_import_clips_hot_first_when_pool_is_small():
    # Donor served two distinct prompts: 2 cached entries, 2 blocks.
    _, donor, _ = _served_donor()
    response = donor.submit([9, 8, 7, 6, 5],
                            SamplingParams(max_new_tokens=2))
    _drive(donor, [response])
    wire = donor.export_hot_prefixes()
    assert wire["n_blocks"] == 2
    # Receiver pool: 2 blocks total, 1 is the reserved trash block, and
    # capacity for exactly 1 import — the hottest entry wins, the tail
    # is clipped (skipped_entries reports it).
    _, receiver = _paged_scheduler(num_blocks=2)
    assert receiver.stats()["block_pool"]["free_blocks"] == 1
    result = receiver.import_prefixes(wire)
    assert result["imported_blocks"] >= 1
    assert result["registered_entries"] >= 1
    assert result["skipped_entries"] >= 1
    assert (result["registered_entries"] + result["skipped_entries"]
            == len(wire["entries"]))


def test_import_refuses_block_size_mismatch():
    _, donor, _ = _served_donor()
    wire = donor.export_hot_prefixes()
    foreign = dict(wire, block_size=16)
    _, receiver = _paged_scheduler()
    with pytest.raises(ValueError, match="block_size"):
        receiver.import_prefixes(foreign)


def test_block_wire_codec_roundtrips_ndarrays_and_nones():
    _, donor, _ = _served_donor()
    wire = donor.export_hot_prefixes()
    wire["groups"][0]["leaves"].append(None)  # quantization-scale slot
    encoded = encode_block_wire(wire)
    json.dumps(encoded)  # JSON-ready, no ndarray leaks
    decoded = decode_block_wire(json.loads(json.dumps(encoded)))
    assert decoded["entries"] == wire["entries"]
    assert decoded["groups"][0]["leaves"][-1] is None
    np.testing.assert_array_equal(
        decoded["groups"][0]["leaves"][0], wire["groups"][0]["leaves"][0]
    )
    assert decoded["groups"][0]["leaves"][0].dtype \
        == wire["groups"][0]["leaves"][0].dtype


# --------------------------------------------------------------------------
# the HTTP protocol between two servers
# --------------------------------------------------------------------------

def _get(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _post_raw(port, path, body, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def test_http_blocks_pull_push_between_replicas():
    _, donor, donor_stream = _served_donor()
    _, receiver = _paged_scheduler()
    donor_server = ServingServer(donor, "127.0.0.1", 0)
    receiver_server = ServingServer(receiver, "127.0.0.1", 0)
    donor_server.start()
    receiver_server.start()
    try:
        status, payload = _get(donor_server.port, "/v1/blocks")
        assert status == 200
        status, result = _post_raw(
            receiver_server.port, "/v1/blocks", payload
        )
        assert status == 200
        installed = json.loads(result)
        assert installed["imported_blocks"] == 1
        assert installed["registered_entries"] == 1
        # The primed receiver replays the donor's stream bit-for-bit.
        response = receiver.submit([1, 2, 3, 4, 5],
                                   SamplingParams(max_new_tokens=3))
        _drive(receiver, [response])
        assert response.result(timeout=1) == donor_stream
        assert receiver.stats()["prefix_cache"]["hits"] >= 1
        # limit=N caps the export; bad limit is a 400.
        status, body = _get(donor_server.port, "/v1/blocks?limit=0")
        assert status == 200 and json.loads(body)["n_blocks"] == 0
        status, _body = _get(donor_server.port, "/v1/blocks?limit=x")
        assert status == 400
        # Garbage wire: 400 (decode), geometry mismatch: 409 (refusal).
        status, _body = _post_raw(receiver_server.port, "/v1/blocks",
                                  b"not json")
        assert status == 400
        foreign = json.loads(payload)
        foreign["block_size"] = 16
        status, body = _post_raw(receiver_server.port, "/v1/blocks",
                                 json.dumps(foreign).encode())
        assert status == 409 and b"block_size" in body
    finally:
        donor_server.stop()
        receiver_server.stop()


# --------------------------------------------------------------------------
# real stack (slow): numeric fidelity through extract/inject + base64
# --------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 budget: the warm-start roundtrip + HTTP
# protocol are represented above on the deterministic fake paged engine;
# this adds the REAL DecodeEngine extract/inject + bf16-over-base64
# numeric-fidelity bar on the tiny transformer.
def test_real_stack_warm_started_replica_streams_bit_identical():
    from tests.test_serving import _legacy_stream, _tiny_serving_stack

    model, params, _engine, donor = _tiny_serving_stack(
        max_slots=2, block_size=4, num_blocks=32,
    )
    _model2, _params2, _engine2, receiver = _tiny_serving_stack(
        max_slots=2, block_size=4, num_blocks=32,
    )
    donor.start()
    receiver.start()
    try:
        rng = np.random.RandomState(7)
        prompt = rng.randint(0, 256, (9,)).tolist()
        expected = _legacy_stream(model, params, prompt, 6)
        warmup = donor.submit(prompt, SamplingParams(max_new_tokens=6))
        assert warmup.result(timeout=120) == expected
        wire = decode_block_wire(json.loads(json.dumps(
            encode_block_wire(donor.export_hot_prefixes())
        )))
        assert wire["n_blocks"] >= 1
        result = receiver.import_prefixes(wire)
        assert result["imported_blocks"] >= 1
        assert result["registered_entries"] >= 1
        # The warm replica's stream is BIT-IDENTICAL to legacy (and so
        # to any cold replica), served through the imported blocks.
        hits_before = receiver.stats()["prefix_cache"]["hits"]
        warmed = receiver.submit(prompt, SamplingParams(max_new_tokens=6))
        assert warmed.result(timeout=120) == expected
        assert receiver.stats()["prefix_cache"]["hits"] > hits_before
    finally:
        donor.close()
        receiver.close()
