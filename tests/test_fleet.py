"""Serving fleet: replica registry, balancing policies, router task.

Three layers, matching the subsystem's seams:

* **Policies** are pure selection over replica lists — driven with a
  fake registry and asserted deterministically.
* **The registry** is a host-side state machine over the coordination
  KV plus an injectable ``/healthz`` probe — the discovery-race tests
  (endpoint advertised before the replica is healthy, beat-then-silent
  heartbeats, draining, tombstones, KV flakes) run with fake probes and
  an in-process KV, no HTTP in sight.
* **The router** forwards over real HTTP — fake upstream replicas pin
  the failover wire behavior (429 → another replica, connect error →
  eject + another replica, mid-stream death → classified error line,
  empty fleet → 503 + Retry-After), and the end-to-end test holds the
  acceptance bar: two REAL serving replicas behind one router produce
  streams bit-identical to `generate_legacy`, and killing one replica
  mid-run ejects it while subsequent requests succeed on the survivor.
"""

import http.client
import json
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from tf_yarn_tpu import event
from tf_yarn_tpu.coordination.kv import InProcessKV
from tf_yarn_tpu.fleet import (
    EJECTED,
    HEALTHY,
    PENDING,
    STOPPED,
    LeastLoadedPolicy,
    Replica,
    ReplicaRegistry,
    RoundRobinPolicy,
    RouterServer,
    make_policy,
)
from tf_yarn_tpu.resilience.taxonomy import FailureKind


# --------------------------------------------------------------------------
# balancing policies on a fake registry
# --------------------------------------------------------------------------

class FakeRegistry:
    """The policies' registry contract: just a healthy set."""

    def __init__(self, replicas):
        self.replicas = replicas

    def healthy(self):
        return [r for r in self.replicas if r.state == HEALTHY]


def _replica(task, load=0, state=HEALTHY):
    replica = Replica(task, endpoint=f"127.0.0.1:{9000}")
    replica.state = state
    replica.queue_depth = load
    return replica


def test_round_robin_policy_cycles_deterministically():
    registry = FakeRegistry(
        [_replica("serving:1"), _replica("serving:0"), _replica("serving:2")]
    )
    policy = RoundRobinPolicy()
    picks = [policy.pick(registry.healthy()).task for _ in range(6)]
    # Task order, cycling, regardless of the list order handed in.
    assert picks == ["serving:0", "serving:1", "serving:2"] * 2
    # Exclusion re-maps the cycle over the remaining candidates.
    assert policy.pick(
        registry.healthy(), exclude={"serving:0", "serving:2"}
    ).task == "serving:1"
    assert policy.pick(
        registry.healthy(), exclude={"serving:0", "serving:1", "serving:2"}
    ) is None


def test_least_loaded_policy_picks_min_load_and_tiebreaks():
    a = _replica("serving:0", load=3)
    b = _replica("serving:1", load=1)
    c = _replica("serving:2", load=1)
    registry = FakeRegistry([a, b, c])
    policy = LeastLoadedPolicy()
    # Min load wins; ties break by task order (deterministic).
    assert policy.pick(registry.healthy()).task == "serving:1"
    # The router's in-flight count feeds the load signal between polls.
    b.inflight = 5
    assert policy.pick(registry.healthy()).task == "serving:2"
    assert policy.pick(
        registry.healthy(), exclude={"serving:2"}
    ).task == "serving:0"
    assert policy.pick(registry.healthy(),
                       exclude={r.task for r in (a, b, c)}) is None


def test_make_policy_names_and_unknown():
    assert make_policy("round_robin").name == "round_robin"
    assert make_policy("least_loaded").name == "least_loaded"
    with pytest.raises(ValueError, match="unknown routing policy"):
        make_policy("random")


# --------------------------------------------------------------------------
# replica registry: discovery races, ejection, re-admission
# --------------------------------------------------------------------------

class ProbeScript:
    """An injectable /healthz probe the tests steer per endpoint."""

    def __init__(self):
        self.responses = {}  # endpoint -> dict | Exception

    def set(self, endpoint, response):
        self.responses[endpoint] = response

    def __call__(self, endpoint):
        response = self.responses.get(
            endpoint, ConnectionRefusedError(f"no probe script for {endpoint}")
        )
        if isinstance(response, Exception):
            raise response
        return dict(response)


OK = {"status": "ok", "queue_depth": 0, "active_slots": 0}


def test_registry_holds_admission_until_first_healthy_probe():
    """The discovery race: the endpoint event lands BEFORE the replica
    answers /healthz (it is still compiling) — the registry must keep it
    out of rotation until the first healthy probe, without counting the
    cold probes as ejections."""
    kv = InProcessKV()
    probe = ProbeScript()
    event.serving_endpoint_event(kv, "serving:0", "127.0.0.1:7001")
    # tasks=None: discovery by KV scan, the launcher-less mode.
    registry = ReplicaRegistry(kv, probe=probe, probe_interval_s=0.0)
    probe.set("127.0.0.1:7001", ConnectionRefusedError("still booting"))
    assert registry.refresh(force=True) == []
    replica = registry.get("serving:0")
    assert replica.state == PENDING and replica.ejections == 0
    # Several cold polls change nothing.
    registry.refresh(force=True)
    assert registry.get("serving:0").state == PENDING
    # First healthy probe admits it; that is an admission, NOT a
    # re-admission.
    probe.set("127.0.0.1:7001", OK)
    healthy = registry.refresh(force=True)
    assert [r.task for r in healthy] == ["serving:0"]
    assert registry.get("serving:0").readmissions == 0


def test_registry_ejects_unreachable_and_readmits_on_recovery():
    kv = InProcessKV()
    probe = ProbeScript()
    event.serving_endpoint_event(kv, "serving:0", "127.0.0.1:7002")
    registry = ReplicaRegistry(
        kv, tasks=["serving:0"], probe=probe, probe_interval_s=0.0
    )
    probe.set("127.0.0.1:7002", OK)
    assert len(registry.refresh(force=True)) == 1
    probe.set("127.0.0.1:7002", ConnectionResetError("gone"))
    assert registry.refresh(force=True) == []
    replica = registry.get("serving:0")
    assert replica.state == EJECTED
    assert replica.eject_reason == "unreachable"
    assert replica.ejections == 1
    probe.set("127.0.0.1:7002", OK)
    assert len(registry.refresh(force=True)) == 1
    assert replica.state == HEALTHY and replica.readmissions == 1
    snap = registry.snapshot()
    assert snap["ejections_total"] == 1
    assert snap["readmissions_total"] == 1
    from tf_yarn_tpu import telemetry

    metrics = telemetry.get_registry()
    assert metrics.counter(
        "fleet/replica_ejections_total", reason="unreachable"
    ).value >= 1
    assert metrics.counter("fleet/replica_readmissions_total").value >= 1
    assert metrics.gauge("fleet/healthy_replicas").value == 1


def test_registry_ejects_draining_replica_before_socket_dies():
    """The preemption-drain handoff: /healthz still answers (the socket
    is alive) but reports "draining" — the registry must eject NOW, not
    when the connection finally refuses."""
    kv = InProcessKV()
    probe = ProbeScript()
    event.serving_endpoint_event(kv, "serving:0", "127.0.0.1:7003")
    registry = ReplicaRegistry(
        kv, tasks=["serving:0"], probe=probe, probe_interval_s=0.0
    )
    probe.set("127.0.0.1:7003", OK)
    registry.refresh(force=True)
    probe.set("127.0.0.1:7003", {**OK, "status": "draining"})
    assert registry.refresh(force=True) == []
    replica = registry.get("serving:0")
    assert replica.state == EJECTED and replica.eject_reason == "draining"


def test_registry_heartbeat_silence_ejects_tombstone_stops():
    """Beat-then-silent ejects even while /healthz still answers (a
    wedged scheduler thread can keep a socket alive — the watchdog
    posture); a fresh beat re-admits; the clean-stop tombstone removes
    the replica as finished, never as dead."""
    kv = InProcessKV()
    probe = ProbeScript()
    event.serving_endpoint_event(kv, "serving:0", "127.0.0.1:7004")
    probe.set("127.0.0.1:7004", OK)
    registry = ReplicaRegistry(
        kv, tasks=["serving:0"], probe=probe, probe_interval_s=0.0,
        dead_heartbeat_s=5.0,
    )
    # Never-beat is not flagged (it may still be restoring/compiling).
    assert len(registry.refresh(force=True)) == 1
    event.heartbeat_event(kv, "serving:0", timestamp=time.time() - 60.0)
    assert registry.refresh(force=True) == []
    replica = registry.get("serving:0")
    assert replica.state == EJECTED
    assert replica.eject_reason == "heartbeat_silent"
    event.heartbeat_event(kv, "serving:0")  # recovery: beating again
    assert len(registry.refresh(force=True)) == 1
    assert replica.readmissions == 1
    event.heartbeat_stopped_event(kv, "serving:0")
    assert registry.refresh(force=True) == []
    assert replica.state == STOPPED
    assert replica.ejections == 1  # finishing is not an ejection


def test_registry_kv_flake_keeps_previous_state():
    class FlakyKV:
        def __init__(self, kv):
            self._kv = kv
            self.fail = False

        def get_str(self, key):
            if self.fail:
                raise ConnectionError("coordination link down")
            return self._kv.get_str(key)

        def keys(self, prefix=""):
            return self._kv.keys(prefix)

    inner = InProcessKV()
    kv = FlakyKV(inner)
    probe = ProbeScript()
    event.serving_endpoint_event(inner, "serving:0", "127.0.0.1:7005")
    probe.set("127.0.0.1:7005", OK)
    registry = ReplicaRegistry(
        kv, tasks=["serving:0"], probe=probe, probe_interval_s=0.0
    )
    assert len(registry.refresh(force=True)) == 1
    kv.fail = True
    # One flaky poll degrades the view, it does not evict the fleet.
    assert len(registry.refresh(force=True)) == 1
    assert registry.get("serving:0").state == HEALTHY


def test_registry_report_failure_ejects_immediately():
    kv = InProcessKV()
    probe = ProbeScript()
    event.serving_endpoint_event(kv, "serving:0", "127.0.0.1:7006")
    probe.set("127.0.0.1:7006", OK)
    registry = ReplicaRegistry(
        kv, tasks=["serving:0"], probe=probe, probe_interval_s=3600.0
    )
    registry.refresh(force=True)
    registry.report_failure("serving:0", ConnectionResetError("mid-request"))
    replica = registry.get("serving:0")
    assert replica.state == EJECTED
    assert replica.eject_reason == "request_transient"
    assert registry.healthy() == []
    # The probe clock was cleared: the next (rate-limited) refresh
    # probes for recovery immediately instead of in an hour.
    assert replica.last_probe_at is None
    assert len(registry.refresh()) == 1


def test_registry_relaunch_at_new_port_replaces_stale_endpoint():
    """Satellite regression (the autoscaler's relaunch path): a replica
    preempted and relaunched re-advertises the SAME task key with a NEW
    host:port. The registry must adopt the new endpoint in the refresh
    that sees it — probing the stale port would keep a live, healthy
    incarnation out of rotation forever — and the recovery must count
    as a readmission."""
    kv = InProcessKV()
    probe = ProbeScript()
    event.serving_endpoint_event(kv, "serving:0", "127.0.0.1:7010")
    probe.set("127.0.0.1:7010", OK)
    registry = ReplicaRegistry(
        kv, tasks=["serving:0"], probe=probe, probe_interval_s=0.0
    )
    assert len(registry.refresh(force=True)) == 1
    # Preemption: the old port dies, the replica is ejected.
    probe.set("127.0.0.1:7010", ConnectionResetError("preempted"))
    assert registry.refresh(force=True) == []
    assert registry.get("serving:0").state == EJECTED
    # The relaunched incarnation advertises the same KV key at a new
    # port. The old port still refuses — only the new one is alive.
    event.serving_endpoint_event(kv, "serving:0", "127.0.0.1:7011")
    probe.set("127.0.0.1:7011", OK)
    healthy = registry.refresh(force=True)
    replica = registry.get("serving:0")
    assert [r.task for r in healthy] == ["serving:0"]
    assert replica.endpoint == "127.0.0.1:7011"
    assert replica.state == HEALTHY
    assert replica.readmissions == 1


def test_registry_endpoint_change_while_healthy_is_a_relaunch():
    """A rolling relaunch the registry never saw die: the endpoint
    changes while the replica is HEALTHY. The stale endpoint must leave
    rotation immediately (PENDING until the new port's first healthy
    probe — the discovery race all over again), counted as a relaunch,
    not a readmission."""
    kv = InProcessKV()
    probe = ProbeScript()
    event.serving_endpoint_event(kv, "serving:0", "127.0.0.1:7012")
    probe.set("127.0.0.1:7012", OK)
    registry = ReplicaRegistry(
        kv, tasks=["serving:0"], probe=probe, probe_interval_s=0.0
    )
    assert len(registry.refresh(force=True)) == 1
    event.serving_endpoint_event(kv, "serving:0", "127.0.0.1:7013")
    probe.set("127.0.0.1:7013", ConnectionRefusedError("still booting"))
    assert registry.refresh(force=True) == []
    replica = registry.get("serving:0")
    assert replica.endpoint == "127.0.0.1:7013"
    assert replica.state == PENDING
    assert replica.relaunches == 1
    probe.set("127.0.0.1:7013", OK)
    assert len(registry.refresh(force=True)) == 1
    # First healthy probe at the new port is an ADMISSION of the new
    # incarnation, not a re-admission of the old one.
    assert replica.readmissions == 0


# --------------------------------------------------------------------------
# router over fake upstream replicas: the failover wire behavior
# --------------------------------------------------------------------------

def _fake_upstream(generate):
    """A minimal replica: /healthz ok, POST /v1/generate delegated to
    `generate(handler, body)`. Returns (httpd, endpoint)."""

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):
            pass

        def _json(self, status, payload, headers=()):
            body = (json.dumps(payload) + "\n").encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for key, value in headers:
                self.send_header(key, value)
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            self._json(200, {"status": "ok", "queue_depth": 0,
                             "active_slots": 0})

        def do_POST(self):
            length = int(self.headers.get("Content-Length", "0"))
            body = json.loads(self.rfile.read(length) or b"{}")
            generate(self, body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    httpd.daemon_threads = True
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    return httpd, f"127.0.0.1:{httpd.server_address[1]}"


def _canned_ok(tokens):
    def generate(handler, body):
        handler._json(200, {"tokens": list(tokens),
                            "finish_reason": "length",
                            "request_id": 0, "ttft_s": 0.001})

    return generate


def _always_busy(retry_after=3):
    def generate(handler, body):
        handler._json(
            429, {"error": "queue full", "retry_after_s": retry_after},
            headers=(("Retry-After", str(retry_after)),),
        )

    return generate


def _abrupt_streamer(n_lines=2):
    def generate(handler, body):
        handler.send_response(200)
        handler.send_header("Content-Type", "application/jsonl")
        handler.send_header("Transfer-Encoding", "chunked")
        handler.end_headers()
        for index in range(n_lines):
            data = (json.dumps({"token": index}) + "\n").encode()
            handler.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))
            handler.wfile.flush()
        # Die mid-stream: FIN without the terminating chunk — the
        # router's readline raises, exactly like a killed replica.
        handler.connection.shutdown(socket.SHUT_WR)
        handler.close_connection = True

    return generate


def _registry_over(endpoints, **kwargs):
    """A registry whose probes are scripted healthy for `endpoints`
    (task -> endpoint)."""
    kv = InProcessKV()
    probe = ProbeScript()
    for task, endpoint in endpoints.items():
        event.serving_endpoint_event(kv, task, endpoint)
        probe.set(endpoint, OK)
    registry = ReplicaRegistry(
        kv, tasks=sorted(endpoints), probe=probe,
        probe_interval_s=kwargs.pop("probe_interval_s", 0.0), **kwargs,
    )
    registry.refresh(force=True)
    return registry, probe


def _post(port, body, timeout=120, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            "POST", "/v1/generate", json.dumps(body),
            {"Content-Type": "application/json", **(headers or {})},
        )
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def _get(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def _get_text(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read().decode()
    finally:
        conn.close()


def test_router_fails_over_429_to_another_replica():
    busy_httpd, busy_ep = _fake_upstream(_always_busy(retry_after=3))
    ok_httpd, ok_ep = _fake_upstream(_canned_ok([5, 6, 7]))
    registry, _probe = _registry_over(
        {"serving:0": busy_ep, "serving:1": ok_ep}
    )
    router = RouterServer(
        registry, make_policy("round_robin"), "127.0.0.1", 0, retries=2,
    )
    router.start()
    try:
        status, _headers, raw = _post(
            router.port, {"prompt": [1, 2], "max_new_tokens": 3}
        )
        assert status == 200, raw
        assert json.loads(raw)["tokens"] == [5, 6, 7]
        stats = router.stats()
        assert stats["routed_requests"]["serving:0"]["busy"] == 1
        assert stats["routed_requests"]["serving:1"]["ok"] == 1
        from tf_yarn_tpu import telemetry

        assert telemetry.get_registry().counter(
            "fleet/routed_requests_total",
            replica="serving:1", outcome="ok",
        ).value >= 1
    finally:
        router.stop()
        busy_httpd.shutdown()
        ok_httpd.shutdown()


def test_router_connect_error_fails_over_and_ejects():
    # A dead endpoint: bind a port, then close it so connections refuse.
    probe_sock = socket.socket()
    probe_sock.bind(("127.0.0.1", 0))
    dead_port = probe_sock.getsockname()[1]
    probe_sock.close()
    ok_httpd, ok_ep = _fake_upstream(_canned_ok([9]))
    registry, _probe = _registry_over(
        {"serving:0": f"127.0.0.1:{dead_port}", "serving:1": ok_ep}
    )
    router = RouterServer(
        registry, make_policy("round_robin"), "127.0.0.1", 0, retries=2,
    )
    router.start()
    try:
        status, _headers, raw = _post(
            router.port, {"prompt": [1], "max_new_tokens": 1}
        )
        assert status == 200, raw
        assert json.loads(raw)["tokens"] == [9]
        # The dead replica was ejected by the observed failure: the next
        # request routes straight to the survivor.
        assert [r.task for r in registry.healthy()] == ["serving:1"]
        assert registry.get("serving:0").state == EJECTED
        status, _headers, raw = _post(
            router.port, {"prompt": [2], "max_new_tokens": 1}
        )
        assert status == 200
        stats = router.stats()
        assert stats["routed_requests"]["serving:0"]["connect_error"] == 1
        assert stats["routed_requests"]["serving:1"]["ok"] == 2
    finally:
        router.stop()
        ok_httpd.shutdown()


def test_router_503_with_retry_after_when_no_replica_healthy():
    kv = InProcessKV()
    probe = ProbeScript()  # nothing advertised, nothing healthy
    registry = ReplicaRegistry(kv, tasks=[], probe=probe)
    router = RouterServer(
        registry, make_policy("least_loaded"), "127.0.0.1", 0,
        retries=1, retry_after_s=2.0,
    )
    router.start()
    try:
        status, headers, raw = _post(
            router.port, {"prompt": [1], "max_new_tokens": 1}
        )
        assert status == 503, raw
        assert headers.get("Retry-After") == "2"
        payload = json.loads(raw)
        assert payload["retry_after_s"] == 2.0
        assert "no generate replica" in payload["error"]
        assert router.stats()["routed_requests"]["-"]["no_replica"] == 1
    finally:
        router.stop()


def test_router_empty_fleet_retry_after_reflects_autoscaler_eta():
    """Scale-from-zero 503s: with an autoscaler attached, an EMPTY
    generate pool is capacity that is coming, so the honest Retry-After
    is the autoscaler's (clamped) launch ETA, not the fixed shed hint —
    and the payload carries the ETA explicitly."""
    from tf_yarn_tpu.fleet import AutoscalePolicy, FleetAutoscaler

    kv = InProcessKV()
    probe = ProbeScript()  # nothing advertised, nothing healthy
    registry = ReplicaRegistry(kv, tasks=[], probe=probe)
    autoscaler = FleetAutoscaler(
        registry, None,
        {"generate": AutoscalePolicy(max_replicas=2)},
        launch_eta_s=37.0,
    )
    router = RouterServer(
        registry, make_policy("least_loaded"), "127.0.0.1", 0,
        retries=1, retry_after_s=2.0, autoscaler=autoscaler,
    )
    router.start()
    try:
        status, headers, raw = _post(
            router.port, {"prompt": [1], "max_new_tokens": 1}
        )
        assert status == 503, raw
        assert headers.get("Retry-After") == "37"
        payload = json.loads(raw)
        assert payload["retry_after_s"] == 37.0
        assert payload["scale_out_eta_s"] == 37.0
        # The hint is the validated, CLAMPED knob: a misconfigured ETA
        # cannot park clients for an hour.
        from tf_yarn_tpu.fleet.autoscaler import LAUNCH_ETA_CEILING_S

        assert FleetAutoscaler(
            registry, None, {"generate": AutoscalePolicy(max_replicas=2)},
            launch_eta_s=10 ** 6,
        ).launch_eta_hint() == LAUNCH_ETA_CEILING_S
        # /stats surfaces the autoscaler block alongside the fleet view.
        status, stats = _get(router.port, "/stats")
        assert status == 200
        assert stats["autoscaler"]["launch_eta_s"] == 37.0
        assert stats["autoscaler"]["policies"]["generate"]["max_replicas"] \
            == 2
    finally:
        router.stop()


def test_router_midstream_death_classified_and_next_request_reroutes():
    """The mid-stream ejection race: the 200 is on the wire when the
    replica dies, so the stream must END with a classified error line
    (no silent truncation, no retry garbling the token stream), the
    replica must be ejected, and the NEXT request must route to the
    survivor."""
    dying_httpd, dying_ep = _fake_upstream(_abrupt_streamer(n_lines=2))
    ok_httpd, ok_ep = _fake_upstream(_canned_ok([4, 2]))
    registry, _probe = _registry_over(
        {"serving:0": dying_ep, "serving:1": ok_ep}
    )
    router = RouterServer(
        registry, make_policy("round_robin"), "127.0.0.1", 0, retries=2,
    )
    router.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", router.port,
                                          timeout=60)
        conn.request(
            "POST", "/v1/generate",
            json.dumps({"prompt": [1, 2], "max_new_tokens": 8,
                        "stream": True}),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        lines = [json.loads(line) for line in resp.read().splitlines()]
        conn.close()
        # The two tokens that made it, then the classified error line.
        assert [l["token"] for l in lines if "token" in l] == [0, 1]
        tail = lines[-1]
        assert tail["done"] and tail["finish_reason"] == "error"
        assert tail["failure_kind"] in {k.value for k in FailureKind}
        assert "serving:0" in tail["error"]
        # Ejected by the observed failure; the next request reroutes.
        assert registry.get("serving:0").state == EJECTED
        status, _headers, raw = _post(
            router.port, {"prompt": [1], "max_new_tokens": 2}
        )
        assert status == 200
        assert json.loads(raw)["tokens"] == [4, 2]
        assert router.stats()["routed_requests"]["serving:0"][
            "stream_error"] == 1
    finally:
        router.stop()
        dying_httpd.shutdown()
        ok_httpd.shutdown()


def test_router_passes_deterministic_4xx_through_verbatim():
    def bad_request(handler, body):
        handler._json(400, {"error": "prompt too long"})

    bad_httpd, bad_ep = _fake_upstream(bad_request)
    registry, _probe = _registry_over({"serving:0": bad_ep})
    router = RouterServer(
        registry, make_policy("round_robin"), "127.0.0.1", 0, retries=3,
    )
    router.start()
    try:
        status, _headers, raw = _post(
            router.port, {"prompt": [1] * 999, "max_new_tokens": 1}
        )
        # A user error is FATAL_USER-shaped: passed through, not retried
        # into every replica.
        assert status == 400
        assert json.loads(raw)["error"] == "prompt too long"
        assert router.stats()["routed_requests"]["serving:0"][
            "upstream_400"] == 1
    finally:
        router.stop()
        bad_httpd.shutdown()


def test_router_healthz_and_stats_surface():
    ok_httpd, ok_ep = _fake_upstream(_canned_ok([1]))
    registry, _probe = _registry_over({"serving:0": ok_ep})
    router = RouterServer(
        registry, make_policy("least_loaded"), "127.0.0.1", 0,
    )
    router.start()
    try:
        status, health = _get(router.port, "/healthz")
        assert status == 200
        assert health["status"] == "ok"
        assert health["role"] == "router"
        assert health["healthy_replicas"] == 1
        status, stats = _get(router.port, "/stats")
        assert status == 200
        assert stats["policy"] == "least_loaded"
        assert stats["healthy_replicas"] == 1
        assert stats["replicas"]["serving:0"]["state"] == HEALTHY
        assert "routed_requests" in stats
        assert stats["ejections_total"] == 0
    finally:
        router.stop()
        ok_httpd.shutdown()


# --------------------------------------------------------------------------
# the router task body (tasks/router.py drives run_router)
# --------------------------------------------------------------------------

def test_run_router_task_body_advertises_and_routes():
    from tf_yarn_tpu import preemption
    from tf_yarn_tpu.experiment import ServingExperiment
    from tf_yarn_tpu.fleet.router import run_router
    from tf_yarn_tpu.topologies import TaskInstance, TaskKey

    upstream_httpd, upstream_ep = _fake_upstream(_canned_ok([3, 1, 4]))
    kv = InProcessKV()
    event.serving_endpoint_event(kv, "serving:0", upstream_ep)
    event.heartbeat_event(kv, "serving:0")

    class _Runtime:
        pass

    runtime = _Runtime()
    runtime.kv = kv
    runtime.task_key = TaskKey("router", 0)
    runtime.task = "router:0"
    runtime.cluster_tasks = [
        TaskInstance(TaskKey("serving", 0), 1),
        TaskInstance(TaskKey("router", 0), 1),
    ]
    experiment = ServingExperiment(
        model=None, model_dir="/unused-router-never-restores",
        router_host="127.0.0.1", router_probe_interval_s=0.05,
        router_policy="round_robin",
    )
    result = {}

    def route():
        result["stats"] = run_router(experiment, runtime=runtime)

    thread = threading.Thread(target=route)
    thread.start()
    try:
        endpoint = kv.wait_str("router:0/router_endpoint", timeout=60)
        port = int(endpoint.rsplit(":", 1)[1])
        status, _headers, raw = _post(
            port, {"prompt": [1, 2], "max_new_tokens": 3}
        )
        assert status == 200
        assert json.loads(raw)["tokens"] == [3, 1, 4]
        status, stats = _get(port, "/stats")
        assert stats["healthy_replicas"] == 1
        assert stats["routed_requests"]["serving:0"]["ok"] == 1
    finally:
        preemption.request()  # the drain flag run_router polls
        thread.join(timeout=60)
        preemption.reset()
        upstream_httpd.shutdown()
    assert not thread.is_alive()
    assert result["stats"]["endpoint"].endswith(str(port))
    assert result["stats"]["policy"] == "round_robin"


# --------------------------------------------------------------------------
# launcher wiring
# --------------------------------------------------------------------------

def test_router_task_type_wiring():
    from tf_yarn_tpu import _env
    from tf_yarn_tpu.backends import PRIMARY_TASK_TYPES
    from tf_yarn_tpu.topologies import (
        NodeLabel,
        TaskSpec,
        check_topology,
        fleet_topology,
    )

    assert _env.gen_task_module("router") == "tf_yarn_tpu.tasks.router"
    assert (
        _env.gen_task_module("router", "my.custom.module")
        == "my.custom.module"
    )
    # A crashed router must fail (and relaunch) the run.
    assert "router" in PRIMARY_TASK_TYPES
    specs = fleet_topology(nb_replicas=3, chips_per_host=1)
    assert specs["serving"].instances == 3
    assert specs["router"].instances == 1
    assert specs["router"].label is NodeLabel.CPU
    # A router with zero serving replicas can never serve: reject at
    # topology build, not at 3am when the fleet launches empty.
    with pytest.raises(
        ValueError, match="at least one serving or rank replica"
    ):
        check_topology({
            "chief": TaskSpec(instances=1, chips_per_host=1,
                              label=NodeLabel.TPU),
            "router": TaskSpec(instances=1),
        })
    with pytest.raises(ValueError, match="cannot reserve chips"):
        check_topology({
            "serving": TaskSpec(instances=1, chips_per_host=1,
                                label=NodeLabel.TPU),
            "router": TaskSpec(instances=1, chips_per_host=1,
                               label=NodeLabel.TPU),
        })


def test_serving_experiment_router_knobs_validate():
    from tf_yarn_tpu.experiment import ServingExperiment

    assert ServingExperiment(
        model=None, model_dir="x"
    ).router_policy == "least_loaded"
    with pytest.raises(ValueError, match="router_policy"):
        ServingExperiment(model=None, model_dir="x", router_policy="random")
    with pytest.raises(ValueError, match="router_retries"):
        ServingExperiment(model=None, model_dir="x", router_retries=-1)
    with pytest.raises(ValueError, match="router_probe_interval_s"):
        ServingExperiment(model=None, model_dir="x",
                          router_probe_interval_s=0)


# --------------------------------------------------------------------------
# end-to-end on CPU: 2 REAL serving replicas + 1 router
# --------------------------------------------------------------------------

def _tiny_fleet(n_replicas=2, max_slots=2):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from tf_yarn_tpu.models import transformer
    from tf_yarn_tpu.models.decode_engine import DecodeEngine
    from tf_yarn_tpu.serving import ServingServer, SlotScheduler

    cfg = transformer.TransformerConfig.tiny(
        scan_layers=False, remat=False, max_seq_len=64, dtype=jnp.float32
    )
    model = transformer.Transformer(cfg)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    )
    # ONE engine shared by all replicas: compiled programs are per
    # (shape, config), so the fleet pays each compile once.
    engine = DecodeEngine(
        model, batch_buckets=(1, 2, 4), prompt_buckets=(4, 8, 16)
    )
    kv = InProcessKV()
    replicas = []
    for index in range(n_replicas):
        scheduler = SlotScheduler(engine, params, max_slots=max_slots)
        scheduler.start()
        server = ServingServer(scheduler, "127.0.0.1", 0)
        server.start()
        task = f"serving:{index}"
        event.serving_endpoint_event(kv, task, server.endpoint)
        event.heartbeat_event(kv, task)
        replicas.append({"task": task, "scheduler": scheduler,
                         "server": server})
    registry = ReplicaRegistry(
        kv, tasks=[r["task"] for r in replicas], probe_interval_s=0.05
    )
    registry.refresh(force=True)
    return model, params, kv, replicas, registry


def _legacy_stream(model, params, prompt, max_new, eos=None):
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


def test_fleet_end_to_end_matches_legacy_and_survives_replica_kill():
    """The acceptance bar: 2 real serving replicas + 1 router on CPU.
    Concurrent requests THROUGH the router return streams bit-identical
    to `generate_legacy`; killing one replica mid-run ejects it and
    every subsequent request succeeds on the survivor."""
    model, params, _kv, replicas, registry = _tiny_fleet(n_replicas=2)
    assert len(registry.healthy()) == 2
    router = RouterServer(
        registry, make_policy("round_robin"), "127.0.0.1", 0, retries=3,
    )
    router.start()
    try:
        rng = np.random.RandomState(7)
        prompts = [
            rng.randint(0, 256, (5,)).tolist(),
            rng.randint(0, 256, (9,)).tolist(),
            rng.randint(0, 256, (3,)).tolist(),
            rng.randint(0, 256, (6,)).tolist(),
        ]
        bodies = [
            {"prompt": prompts[0], "max_new_tokens": 6},
            {"prompt": prompts[1], "max_new_tokens": 8},
            {"prompt": prompts[2], "max_new_tokens": 4},
            {"prompt": prompts[3], "max_new_tokens": 5},
        ]
        results = {}

        def call(index):
            results[index] = _post(router.port, bodies[index], timeout=300)

        threads = [
            threading.Thread(target=call, args=(i,)) for i in range(4)
        ]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=600)
        for index, body in enumerate(bodies):
            status, _headers, raw = results[index]
            assert status == 200, raw
            assert json.loads(raw)["tokens"] == _legacy_stream(
                model, params, body["prompt"], body["max_new_tokens"]
            ), index
        # Both replicas actually served (round-robin over 4 requests).
        routed = router.stats()["routed_requests"]
        assert routed["serving:0"]["ok"] >= 1
        assert routed["serving:1"]["ok"] >= 1

        # Streaming through the router: chunked lines, bit-identical.
        conn = http.client.HTTPConnection("127.0.0.1", router.port,
                                          timeout=300)
        conn.request(
            "POST", "/v1/generate",
            json.dumps({"prompt": prompts[0], "max_new_tokens": 6,
                        "stream": True}),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        lines = [json.loads(line) for line in resp.read().splitlines()]
        conn.close()
        assert [l["token"] for l in lines if "token" in l] == \
            _legacy_stream(model, params, prompts[0], 6)
        assert lines[-1]["done"] and lines[-1]["finish_reason"] == "length"

        # KILL replica 0: its frontend refuses connections from here on.
        replicas[0]["server"].stop()
        replicas[0]["scheduler"].close()
        # Subsequent requests all succeed on the survivor — the first
        # may transit the dead replica (connect error -> failover +
        # ejection), later ones route straight to serving:1.
        for body in bodies[:3]:
            status, _headers, raw = _post(router.port, body, timeout=300)
            assert status == 200, raw
            assert json.loads(raw)["tokens"] == _legacy_stream(
                model, params, body["prompt"], body["max_new_tokens"]
            )
        assert [r.task for r in registry.healthy()] == ["serving:1"]
        assert registry.get("serving:0").state == EJECTED
        stats = router.stats()
        assert stats["ejections_total"] >= 1
        assert stats["routed_requests"]["serving:1"]["ok"] >= 3
    finally:
        router.stop()
        for replica in replicas[1:]:
            replica["server"].stop()
            replica["scheduler"].close()


def test_fleet_observability_plane_end_to_end():
    """The observability acceptance bar: 2 real replicas + router +
    FleetMonitor under concurrent traffic. The router's /metrics serves
    a fleet-merged serving/ttft_seconds p95 equal to the pooled
    per-replica bucket merge — asserted against an oracle recomputed
    from the raw TTFT timings (within HIST_ALPHA relative error) — and
    one X-Request-Id appears in BOTH the router's span records and the
    owning replica's scheduler trace ring for the same request."""
    import re

    from tf_yarn_tpu import telemetry
    from tf_yarn_tpu.fleet import FleetMonitor
    from tf_yarn_tpu.telemetry.registry import HIST_ALPHA

    model, params, _kv, replicas, registry = _tiny_fleet(n_replicas=2)
    monitor = FleetMonitor(
        registry, interval_s=0.2, slo={"ttft_p95_s": 60.0})
    router = RouterServer(
        registry, make_policy("round_robin"), "127.0.0.1", 0, retries=3,
        monitor=monitor,
    )
    router.start()
    monitor.start()
    metrics = telemetry.get_registry()
    try:
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, 256, (n,)).tolist()
                   for n in (4, 7, 3, 6, 5, 8)]

        def routed_count_reaches(n):
            """Bounded wait for the router's own histogram: it observes a
            request after it has answered it."""
            routed = metrics.histogram("fleet/routed_request_seconds",
                                       path="/v1/generate", outcome="ok")
            deadline = time.monotonic() + 30
            while routed.count < n and time.monotonic() < deadline:
                time.sleep(0.01)
            return routed.count

        # Warm the (shared) engine through the router so compiles land
        # outside the measured window, then reset the process registry:
        # the sketch under test starts empty. The registry is the
        # process's: earlier tests of this file have left their own
        # routed requests in it, so the wait counts from where it stands.
        routed_before = routed_count_reaches(0)
        warm = [threading.Thread(target=_post, args=(
            router.port, {"prompt": p, "max_new_tokens": 4}, 300,
        )) for p in prompts[:4]]
        for t in warm:
            t.start()
        for t in warm:
            t.join(timeout=600)
        # Let the four warm observations land before the registry is
        # emptied, or a late one is counted with the eight below.
        assert routed_count_reaches(routed_before + len(warm)) \
            == routed_before + len(warm)
        metrics.clear()

        # Spy on the shared TTFT histogram: every raw server-side TTFT
        # observation is the oracle the merged sketch must reproduce.
        hist = metrics.histogram("serving/ttft_seconds")
        raw_ttft = []
        real_observe = hist.observe
        hist.observe = lambda value: (raw_ttft.append(float(value)),
                                      real_observe(value))[-1]

        # Concurrent traffic; half the callers supply their own
        # X-Request-Id, the rest let the router mint one.
        results = {}

        def call(index):
            body = {"prompt": prompts[index % len(prompts)],
                    "max_new_tokens": 4 + index % 3}
            headers = ({"X-Request-Id": f"req-caller-{index}"}
                       if index % 2 else None)
            results[index] = _post(router.port, body, 300, headers)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        del hist.observe  # un-spy before the final scrape settles
        assert len(results) == 8
        # The same for the eight: the last answer can arrive before the
        # router has observed it, and the scrapes below read the count.
        assert routed_count_reaches(8) == 8
        rids = {}
        for index, (status, headers, raw) in results.items():
            assert status == 200, raw
            rids[index] = headers["X-Request-Id"]
        # Caller-supplied ids are honored verbatim; minted ones are
        # unique req-<hex>.
        assert rids[1] == "req-caller-1" and rids[3] == "req-caller-3"
        assert all(rid.startswith("req-") for rid in rids.values())
        assert len(set(rids.values())) == 8

        # A deterministic final cycle AFTER all traffic: both scrapes
        # see the complete windowed sketch.
        aggregate = monitor.poll_once()
        assert aggregate["status"] == "ok"
        assert aggregate["contributing_replicas"] == 2
        assert aggregate["stale_replicas"] == 0
        merged = aggregate["histograms"]["serving/ttft_seconds"]
        # In-process replicas share ONE registry, so each /stats ships
        # the same sketch and the pooled merge is every raw timing
        # twice — which leaves every quantile untouched.
        assert merged["count"] == 2 * len(raw_ttft)
        pooled = sorted(raw_ttft * 2)
        oracle_p95 = pooled[int(0.95 * (len(pooled) - 1))]
        assert abs(merged["p95"] - oracle_p95) / oracle_p95 <= HIST_ALPHA

        # The router's /metrics serves the SAME fleet-merged p95.
        status, headers, text = _get_text(router.port, "/metrics")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        match = re.search(
            r'^fleet_serving_ttft_seconds\{agg="p95"\} (\S+)$',
            text, re.M)
        assert match, text
        assert float(match.group(1)) == merged["p95"]
        assert abs(float(match.group(1)) - oracle_p95) / oracle_p95 \
            <= HIST_ALPHA
        # Satellite: the router's own request histogram, in /metrics...
        assert re.search(
            r'fleet_routed_request_seconds_count\{outcome="ok",'
            r'path="/v1/generate"\} 8.0', text), text
        # ...and in /stats signals, next to the embedded fleet aggregate.
        status, stats = _get(router.port, "/stats")
        assert status == 200
        assert stats["schema_version"] == telemetry.STATS_SCHEMA_VERSION
        assert stats["signals"]["version"] == telemetry.SIGNALS_VERSION
        routed_sig = stats["signals"]["histograms"][
            "fleet/routed_request_seconds{outcome=ok,path=/v1/generate}"]
        assert routed_sig["count"] == 8
        assert stats["fleet"]["status"] == "ok"
        assert stats["fleet"]["slo"]["ttft_p95_s"]["status"] == "ok"
        # Replica /healthz now carries the payload schema version, and
        # the registry parsed it off the probe.
        status, health = _get(router.port, "/healthz")
        assert health["schema_version"] == telemetry.STATS_SCHEMA_VERSION
        assert registry.get("serving:0").schema_version == \
            telemetry.STATS_SCHEMA_VERSION

        # Cross-task tracing: one request id, BOTH sides. The router's
        # span records it...
        rid = rids[1]
        spans = telemetry.get_tracer().records()
        router_spans = [s for s in spans if s.name == "router/route"
                        and s.args.get("request_id") == rid]
        assert len(router_spans) == 1
        # ...the owning replica's submit span tags it...
        submit_spans = [s for s in spans if s.name == "serving/submit"
                        and s.args.get("request_id") == rid]
        assert len(submit_spans) == 1
        # ...and the owning replica's scheduler trace ring carries it
        # against the scheduler-local request id — on EXACTLY one
        # replica (the one the router routed to).
        owners = [
            r["task"] for r in replicas
            if any(rid in entry.get("trace", {}).values()
                   for entry in list(r["scheduler"].trace))
        ]
        assert len(owners) == 1
        # Every request id made it into some trace ring.
        ring_ids = {
            trace_id
            for r in replicas
            for entry in list(r["scheduler"].trace)
            for trace_id in entry.get("trace", {}).values()
        }
        assert set(rids.values()) <= ring_ids
    finally:
        monitor.stop()
        router.stop()
        for replica in replicas:
            replica["server"].stop()
            replica["scheduler"].close()


# --------------------------------------------------------------------------
# fleet monitor under churn: join/leave mid-scrape never tears the view
# --------------------------------------------------------------------------

def _signals_payload(values):
    from tf_yarn_tpu.telemetry.exposition import (
        SIGNALS_VERSION,
        STATS_SCHEMA_VERSION,
    )
    from tf_yarn_tpu.telemetry.registry import Histogram

    hist = Histogram()
    for value in values:
        hist.observe(value)
    return {
        "schema_version": STATS_SCHEMA_VERSION,
        "signals": {
            "version": SIGNALS_VERSION,
            "histograms": {
                "serving/ttft_seconds": hist.to_signal(window=False),
            },
            "scalars": {},
        },
    }


def test_monitor_churn_mid_scrape_reads_see_complete_aggregates():
    """Fleet churn DURING a scrape cycle — a replica ejected and a new
    one advertised while the monitor is halfway through its endpoint
    list — must never tear the aggregate: a concurrent reader sees the
    previous cycle's COMPLETE view until the new one is swapped in
    whole, and the in-flight cycle still merges exactly the healthy set
    it captured at its start."""
    from tf_yarn_tpu.fleet import FleetMonitor

    kv = InProcessKV()
    probe = ProbeScript()
    for index, port in enumerate((7020, 7021)):
        event.serving_endpoint_event(kv, f"serving:{index}",
                                     f"127.0.0.1:{port}")
        probe.set(f"127.0.0.1:{port}", OK)
    registry = ReplicaRegistry(kv, probe=probe, probe_interval_s=0.0)
    registry.refresh(force=True)
    mid_scrape = {}

    def scrape(endpoint):
        if endpoint == "127.0.0.1:7020":
            if not mid_scrape:
                # Churn lands mid-cycle: serving:1 leaves (preempted,
                # its probe now refuses so the refresh keeps it out)
                # and serving:2 joins — while THIS scrape is on the
                # wire.
                probe.set("127.0.0.1:7021", ConnectionResetError("gone"))
                registry.report_failure(
                    "serving:1", ConnectionResetError("preempted"))
                event.serving_endpoint_event(kv, "serving:2",
                                             "127.0.0.1:7022")
                probe.set("127.0.0.1:7022", OK)
                registry.refresh(force=True)
                # The reader's view mid-churn: the last complete
                # aggregate.
                mid_scrape["aggregate"] = monitor.aggregate()
            return _signals_payload([0.1] * 5)
        if endpoint == "127.0.0.1:7021":
            return _signals_payload([0.2] * 5)
        return _signals_payload([0.3] * 7)

    monitor = FleetMonitor(registry, scrape=scrape, interval_s=0.01)
    first = monitor.poll_once()
    assert first["status"] == "ok" and first["cycle"] == 1
    assert set(first["replicas"]) == {"serving:0", "serving:1"}
    assert first["histograms"]["serving/ttft_seconds"]["count"] == 10
    # The mid-scrape read was cycle 1's view, complete — not a torn
    # half-merge of the in-flight cycle 1 (the reader observed the
    # initial no_data placeholder, whole).
    torn = mid_scrape["aggregate"]
    assert torn["status"] == "no_data" and "histograms" not in torn
    # Cycle 2 runs over the POST-churn healthy set: the leaver is gone
    # from the merge, the joiner contributes.
    second = monitor.poll_once()
    assert second["cycle"] == 2
    assert set(second["replicas"]) == {"serving:0", "serving:2"}
    assert second["histograms"]["serving/ttft_seconds"]["count"] == 12


def test_monitor_aggregate_reads_are_consistent_under_concurrent_churn():
    """Hammer `aggregate()` from a reader thread while scrape cycles
    interleave with registry churn: every snapshot the reader observes
    must be internally consistent (status/histograms agree, replica
    views whole, cycle monotone) — deep-copied swaps, never a dict
    mid-mutation."""
    from tf_yarn_tpu.fleet import FleetMonitor

    kv = InProcessKV()
    probe = ProbeScript()
    endpoints = {f"serving:{i}": f"127.0.0.1:{7030 + i}" for i in range(3)}
    for task, endpoint in endpoints.items():
        event.serving_endpoint_event(kv, task, endpoint)
        probe.set(endpoint, OK)
    registry = ReplicaRegistry(kv, probe=probe, probe_interval_s=0.0)
    registry.refresh(force=True)
    monitor = FleetMonitor(
        registry, scrape=lambda endpoint: _signals_payload([0.1, 0.2]),
        interval_s=0.001,
    )
    stop = threading.Event()
    snapshots = []

    def read():
        while not stop.is_set():
            snapshots.append(monitor.aggregate())

    reader = threading.Thread(target=read)
    reader.start()
    try:
        for round_index in range(8):
            # Leave and rejoin a replica between cycles; scrape twice.
            probe.set(endpoints["serving:1"],
                      ConnectionResetError("flap")
                      if round_index % 2 else OK)
            registry.refresh(force=True)
            monitor.poll_once()
    finally:
        stop.set()
        reader.join(timeout=10)
    assert snapshots
    last_cycle = 0
    for snap in snapshots:
        assert snap["status"] in ("no_data", "ok")
        cycle = snap.get("cycle", 0)
        assert cycle >= last_cycle  # swapped whole, in order
        last_cycle = cycle
        if snap["status"] == "ok":
            merged = snap["histograms"]["serving/ttft_seconds"]
            # Whole-cycle counts only: every contributing replica ships
            # 2 observations, so a torn half-merge cannot pass.
            assert merged["count"] % 2 == 0 and merged["count"] > 0
            for view in snap["replicas"].values():
                assert "stale" in view and "legacy" in view
        else:
            assert "histograms" not in snap


# --------------------------------------------------------------------------
# autoscaled fleet end-to-end: burn -> scale out -> preempt -> warm re-admit
# --------------------------------------------------------------------------

def _paged_replica(engine, params, kv, task, max_slots=2):
    from tf_yarn_tpu.serving import ServingServer, SlotScheduler

    scheduler = SlotScheduler(
        engine, params, max_slots=max_slots,
        block_size=4, num_blocks=32, max_seq_len=64,
    )
    scheduler.start()
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    event.serving_endpoint_event(kv, task, server.endpoint)
    event.heartbeat_event(kv, task)
    return {"task": task, "scheduler": scheduler, "server": server}


def _tiny_paged_fleet_parts():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from tf_yarn_tpu.models import transformer
    from tf_yarn_tpu.models.decode_engine import DecodeEngine

    cfg = transformer.TransformerConfig.tiny(
        scan_layers=False, remat=False, max_seq_len=64, dtype=jnp.float32
    )
    model = transformer.Transformer(cfg)
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    )
    engine = DecodeEngine(
        model, batch_buckets=(1, 2, 4), prompt_buckets=(4, 8, 16)
    )
    return model, params, engine


def test_autoscaled_fleet_scales_out_and_warm_starts_readmission():
    """The self-healing loop end-to-end on the REAL stack (tier-1
    representative of the chaos-driven bench A/B below): an SLO burn
    scales the generate pool out; the newcomer is warm-started over
    real /v1/blocks HTTP from the veteran; a preempted replica
    relaunched at a NEW port is re-admitted and warm-started from the
    survivor; every warm replica's stream is BIT-IDENTICAL to legacy
    and its first hot-prefix request HITS the imported cache."""
    from tf_yarn_tpu import telemetry
    from tf_yarn_tpu.fleet import AutoscalePolicy, FleetAutoscaler

    model, params, engine = _tiny_paged_fleet_parts()
    kv = InProcessKV()
    fleet = {"serving:0": _paged_replica(engine, params, kv, "serving:0")}
    registry = ReplicaRegistry(kv, probe_interval_s=0.0)
    registry.refresh(force=True)
    assert [r.task for r in registry.healthy()] == ["serving:0"]

    burn = {"slo": {"ttft": {"metric": "serving/ttft_seconds",
                             "status": "violated"}}}

    class BurnMonitor:  # the autoscaler's monitor contract
        def aggregate(self):
            return dict(burn)

    def actuate(kind, current, target, reason):
        if kind != "generate":
            return False
        for index in range(current, target):
            task = f"serving:{index}"
            fleet[task] = _paged_replica(engine, params, kv, task)
        return True

    autoscaler = FleetAutoscaler(
        registry, BurnMonitor(),
        {"generate": AutoscalePolicy(
            min_replicas=1, max_replicas=2, scale_out_queue_depth=None,
            scale_in_load=None, cooldown_cycles=0,
        )},
        actuate=actuate, launch_eta_s=5.0,
    )
    metrics = telemetry.get_registry()
    scale_before = metrics.counter(
        "fleet/scale_events_total", kind="generate", direction="out"
    ).value
    blocks_before = metrics.counter("fleet/warm_start_blocks_total").value
    try:
        # Heat the veteran: one served prompt, bit-identical to legacy.
        rng = np.random.RandomState(11)
        prompt = rng.randint(0, 256, (9,)).tolist()
        expected = _legacy_stream(model, params, prompt, 6)
        body = {"prompt": prompt, "max_new_tokens": 6}
        status, _headers, raw = _post(
            fleet["serving:0"]["server"].port, body, timeout=300)
        assert status == 200, raw
        assert json.loads(raw)["tokens"] == expected

        # Cycle 1: first sight records the veteran; the burn scales out.
        report = autoscaler.poll_once()
        assert report["actuated"][0]["reason"] == "slo_burn_ttft"
        assert report["warm_starts"] == []  # newcomer not admitted yet
        assert metrics.counter(
            "fleet/scale_events_total", kind="generate", direction="out"
        ).value == scale_before + 1
        registry.refresh(force=True)  # admit the newcomer
        assert len(registry.healthy()) == 2

        # Cycle 2: the newcomer is healthy at a never-seen endpoint —
        # warm-started from the veteran over real /v1/blocks HTTP.
        report = autoscaler.poll_once()
        warm = [w for w in report["warm_starts"]
                if w["task"] == "serving:1"]
        assert warm and warm[0]["imported_blocks"] >= 1, report
        hits_before = fleet["serving:1"]["scheduler"].stats()[
            "prefix_cache"]["hits"]
        status, _headers, raw = _post(
            fleet["serving:1"]["server"].port, body, timeout=300)
        assert status == 200, raw
        assert json.loads(raw)["tokens"] == expected
        assert fleet["serving:1"]["scheduler"].stats()[
            "prefix_cache"]["hits"] > hits_before

        # PREEMPTION: the veteran dies; relaunch advertises the SAME
        # task at a NEW port; the registry re-admits at the new
        # endpoint and the autoscaler warm-starts it from the survivor.
        fleet["serving:0"]["server"].stop()
        fleet["serving:0"]["scheduler"].close()
        registry.report_failure(
            "serving:0", ConnectionResetError("preempted"))
        assert [r.task for r in registry.healthy()] == ["serving:1"]
        fleet["serving:0"] = _paged_replica(engine, params, kv,
                                            "serving:0")
        registry.refresh(force=True)
        replica = registry.get("serving:0")
        assert replica.state == HEALTHY
        assert replica.endpoint == fleet["serving:0"]["server"].endpoint
        assert replica.readmissions == 1
        report = autoscaler.poll_once()
        warm = [w for w in report["warm_starts"]
                if w["task"] == "serving:0"]
        assert warm and warm[0]["imported_blocks"] >= 1, report
        status, _headers, raw = _post(
            fleet["serving:0"]["server"].port, body, timeout=300)
        assert status == 200, raw
        assert json.loads(raw)["tokens"] == expected
        assert fleet["serving:0"]["scheduler"].stats()[
            "prefix_cache"]["hits"] >= 1
        assert metrics.counter(
            "fleet/warm_start_blocks_total").value >= blocks_before + 2
        # The history names both warm starts (autoscaler /stats block).
        warmed_tasks = {w["task"] for w in autoscaler.stats()
                        ["warm_starts"] if "imported_blocks" in w}
        assert warmed_tasks == {"serving:0", "serving:1"}
    finally:
        for entry in fleet.values():
            entry["server"].stop()
            entry["scheduler"].close()


@pytest.mark.slow  # tier-1 budget: represented by
# test_autoscaled_fleet_scales_out_and_warm_starts_readmission (the
# same loop, driven deterministically); this runs the full chaos-driven
# A/B — seeded Poisson trace with a mid-run rate step + one injected
# preemption/relaunch — static fleet vs autoscaled fleet.
def test_bench_fleet_autoscale_ab_heals_with_streams_match():
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "tpu_yarn_bench_suite_fleet_autoscale_test",
        os.path.join(repo, "benchmarks", "run.py"),
    )
    suite = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(suite)
    result = suite.bench_fleet(tpu=False, autoscale=True)
    rows = result["rows"]
    for name in ("static", "autoscaled"):
        assert rows[name].get("error") is None, rows[name]
        assert rows[name]["dropped"] == 0  # zero dropped streams
        assert rows[name]["readmissions"] >= 1  # the relaunch landed
    auto = rows["autoscaled"]
    assert auto["scale_events"] >= 1
    assert auto["warm_start_pulls"] >= 1
    assert auto["replicas_final"] > rows["static"]["replicas_final"]
    # Bit-identity across arms AND vs the pre-trace reference stream.
    assert result["streams_match"] is True
    assert "violation_delta" in result


# --------------------------------------------------------------------------
# the fleet bench reports aggregate throughput per replica count
# --------------------------------------------------------------------------

def test_bench_fleet_reports_scaling_rows():
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "tpu_yarn_bench_suite_fleet_test",
        os.path.join(repo, "benchmarks", "run.py"),
    )
    suite = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(suite)
    result = suite.bench_fleet(
        tpu=False, replica_counts=(1, 2), n_requests=3
    )
    rows = result["rows"]
    for name in ("r1", "r2"):
        assert name in rows, result
        assert rows[name].get("error") is None, rows[name]
        assert rows[name]["completed"] == 3
        assert rows[name]["tokens_per_sec"] > 0
        assert rows[name]["routed_ok"] == 3
        assert "ttft_p95_ms" in rows[name]
        # The observability plane's scrape-merged numbers ride along.
        assert rows[name]["fleet_ttft_p95_ms"] > 0
        assert rows[name]["monitor_cycles"] >= 1
        assert rows[name]["monitor_scrape_wall_ms"] >= 0
    assert rows["r2"]["healthy_replicas"] == 2
    # The scaling ratio is REPORTED (its value is rig-dependent: on one
    # shared CPU the replicas contend, on real chips they scale).
    assert "scaling_r2_vs_r1" in result
