"""Coordination-store tests: in-process KV, TCP server, and protocol.

Covers the surface the reference exercises through skein's KV plus our
extensions (events log, incr). Mirrors the reference's dict-KV test style
(reference: tests/test_client.py:43-50) but also runs the real server.
"""

import threading
import time

import pytest

from tf_yarn_tpu.coordination import (
    InProcessKV,
    KVClient,
    KVTimeoutError,
    start_server,
)
from tf_yarn_tpu.coordination.server_factory import (
    native_binary,
    start_native_server,
)

# Built from the committed source at collection; None without a toolchain.
_NATIVE = native_binary() is not None


@pytest.fixture(
    params=["inprocess", "tcp"]
    + (["native"] if _NATIVE else [])
)
def kv(request):
    if request.param == "inprocess":
        yield InProcessKV()
    elif request.param == "tcp":
        server = start_server()
        try:
            yield KVClient(server.endpoint)
        finally:
            server.stop()
    else:
        server = start_native_server()
        assert server is not None, "native coordd failed to start"
        try:
            yield KVClient(server.endpoint)
        finally:
            server.stop()


def test_native_server_identifies_itself():
    if not _NATIVE:
        pytest.skip("this host cannot build coordd")
    server = start_native_server()
    try:
        assert KVClient(server.endpoint).ping() == "coordd"
    finally:
        server.stop()


def test_put_get_roundtrip(kv):
    assert kv.get("missing") is None
    kv.put("a", b"\x00\xffbinary")
    assert kv.get("a") == b"\x00\xffbinary"
    kv.put_str("b", "text")
    assert kv.get_str("b") == "text"


def test_wait_returns_existing_value(kv):
    kv.put("ready", b"v")
    assert kv.wait("ready", timeout=1.0) == b"v"


def test_wait_blocks_until_put(kv):
    result = {}

    def waiter():
        result["value"] = kv.wait("later", timeout=10.0)

    thread = threading.Thread(target=waiter)
    thread.start()
    time.sleep(0.1)
    kv.put("later", b"arrived")
    thread.join(timeout=5.0)
    assert result["value"] == b"arrived"


def test_wait_timeout(kv):
    with pytest.raises(KVTimeoutError):
        kv.wait("never", timeout=0.1)


def test_events_log(kv):
    kv.put("x", b"1")
    kv.put("y", b"2")
    events, nxt = kv.events(0)
    assert [k for _, k in events] == ["x", "y"]
    kv.put("z", b"3")
    events, nxt2 = kv.events(nxt)
    assert [k for _, k in events] == ["z"]
    assert nxt2 == nxt + 1


def test_keys_prefix(kv):
    kv.put("task:0/init", b"")
    kv.put("task:0/start", b"")
    kv.put("other", b"")
    assert kv.keys("task:0/") == ["task:0/init", "task:0/start"]


def test_incr_atomic(kv):
    assert kv.incr("counter") == 1
    assert kv.incr("counter", 5) == 6
    assert kv.get("counter") == b"6"


def test_incr_concurrent(kv):
    def bump():
        for _ in range(20):
            kv.incr("ticket")

    threads = [threading.Thread(target=bump) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert kv.get("ticket") == b"80"


def test_delete(kv):
    kv.put("gone", b"x")
    kv.delete("gone")
    assert kv.get("gone") is None


def test_large_value(kv):
    blob = b"q" * (2 * 1024 * 1024)
    kv.put("big", blob)
    assert kv.get("big") == blob


def test_many_concurrent_waiters(kv):
    # A barrier-like burst: 12 threads block on distinct keys, one thread
    # publishes them all; every waiter must wake with its own value.
    results = {}

    def waiter(i):
        results[i] = kv.wait(f"burst/{i}", timeout=15.0)

    threads = [threading.Thread(target=waiter, args=(i,)) for i in range(12)]
    for t in threads:
        t.start()
    time.sleep(0.2)
    for i in range(12):
        kv.put(f"burst/{i}", f"v{i}".encode())
    for t in threads:
        t.join(timeout=10.0)
    assert results == {i: f"v{i}".encode() for i in range(12)}


def test_pooled_read_timeout_does_not_hang():
    # A server that accepts connections but never replies: a bounded
    # pooled read must surface an error instead of parking the client
    # forever (a worker stuck here would never reach the preemption
    # drain poll — ADVICE r2).
    import socket as socket_mod

    from tf_yarn_tpu.coordination.kv import KVClient

    silent = socket_mod.socket()
    silent.bind(("127.0.0.1", 0))
    silent.listen(4)
    host, port = silent.getsockname()
    try:
        client = KVClient(f"{host}:{port}", read_timeout=1.0)
        t0 = time.time()
        with pytest.raises(OSError):
            client.get("anything")
        # One timeout + one idempotent retry, both bounded.
        assert time.time() - t0 < 10.0
        client.close()
    finally:
        silent.close()


def test_kv_timeout_classified_transient(kv):
    # The coordination timeout is an infra flake: the failure taxonomy
    # must retry it, never charge it to user code.
    from tf_yarn_tpu.resilience import FailureKind, classify_exception

    with pytest.raises(KVTimeoutError) as excinfo:
        kv.wait("never-published", timeout=0.05)
    assert classify_exception(excinfo.value) is FailureKind.TRANSIENT
    # The driver-side heuristic agrees when only traceback text survives
    # (legacy stop payloads without a kind marker).
    from tf_yarn_tpu.resilience import classify_stop_payload

    kind, _ = classify_stop_payload(
        "Traceback (most recent call last):\n...\n"
        f"KVTimeoutError: {excinfo.value}"
    )
    assert kind is FailureKind.TRANSIENT


def test_kv_chaos_delay_injection():
    # TPU_YARN_FAULT kv_delay=p,secs lands in the client wrapper: every
    # request pays the injected latency at p=1.0, deterministically.
    from tf_yarn_tpu.coordination.kv import KVClient, start_server
    from tf_yarn_tpu.resilience import chaos

    server = start_server()
    try:
        client = KVClient(server.endpoint)
        client.put("warm", b"1")  # connection setup outside the timing
        chaos.configure("kv_delay=1.0,0.08", seed=0)
        t0 = time.monotonic()
        client.put("k", b"v")
        assert client.get("k") == b"v"
        assert time.monotonic() - t0 >= 0.16
    finally:
        chaos.reset()
        server.stop()


def test_keepalive_enabled_on_pooled_socket():
    import socket as socket_mod

    from tf_yarn_tpu.coordination.kv import KVClient, start_server

    server = start_server()
    try:
        client = KVClient(server.endpoint)
        client.get("whatever")  # force the pooled connection open
        sock = client._sock
        assert sock is not None
        assert (
            sock.getsockopt(socket_mod.SOL_SOCKET, socket_mod.SO_KEEPALIVE) == 1
        )
        client.close()
    finally:
        server.stop()
