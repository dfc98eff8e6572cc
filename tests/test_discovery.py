"""TPU-VM worker discovery and the coordinator advertise address."""

import json
import subprocess
from unittest import mock

import pytest

from tf_yarn_tpu import discovery
from tf_yarn_tpu.backends import LocalBackend, SshBackend, TpuVmHost
from tf_yarn_tpu.client import _advertised_endpoint


@pytest.fixture(autouse=True)
def _clear_ambient_tpu_env(monkeypatch):
    # A TPU host's image pre-sets TPU worker env vars (localhost); discovery
    # gives env highest priority by design, so tests start clean.
    for var in (discovery.ENV_WORKER_HOSTS, "TPU_PROCESS_ADDRESSES",
                "TPU_WORKER_HOSTNAMES"):
        monkeypatch.delenv(var, raising=False)


def test_hosts_from_env_override(monkeypatch):
    monkeypatch.setenv(discovery.ENV_WORKER_HOSTS, "10.0.0.1, 10.0.0.2,10.0.0.3")
    hosts = discovery.discover_tpu_vm_hosts()
    assert [(h.hostname, h.worker_index) for h in hosts] == [
        ("10.0.0.1", 0), ("10.0.0.2", 1), ("10.0.0.3", 2),
    ]


def test_hosts_from_gke_env(monkeypatch):
    monkeypatch.setattr(discovery, "_get_metadata", lambda *a, **k: None)
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "t-0.ns,t-1.ns")
    hosts = discovery.discover_tpu_vm_hosts()
    assert [h.hostname for h in hosts] == ["t-0.ns", "t-1.ns"]


def test_metadata_outranks_ambient_env(monkeypatch):
    # Images pre-set localhost-ish ambient vars; real metadata must win.
    monkeypatch.setenv("TPU_PROCESS_ADDRESSES", "localhost:8476")
    monkeypatch.setattr(
        discovery, "_get_metadata",
        lambda key, timeout=2.0: "v:0:10.0.0.9"
        if key == "worker-network-endpoints" else None,
    )
    hosts = discovery.discover_tpu_vm_hosts()
    assert [h.hostname for h in hosts] == ["10.0.0.9"]


def test_hosts_from_metadata(monkeypatch):
    # worker-network-endpoints: ip is the third ':'-field (the layout
    # jax._src.clusters.cloud_tpu_cluster parses).
    monkeypatch.setattr(
        discovery, "_get_metadata",
        lambda key, timeout=2.0: (
            "v2-8:0:10.164.0.2,v2-8:1:10.164.0.3"
            if key == "worker-network-endpoints" else None
        ),
    )
    hosts = discovery.discover_tpu_vm_hosts()
    assert [h.hostname for h in hosts] == ["10.164.0.2", "10.164.0.3"]


def test_hosts_from_gcloud(monkeypatch):
    monkeypatch.setattr(discovery, "_get_metadata", lambda *a, **k: None)
    payload = {"networkEndpoints": [
        {"ipAddress": "10.0.1.1"}, {"ipAddress": "10.0.1.2"},
    ]}

    def fake_run(cmd, **kwargs):
        assert cmd[:5] == ["gcloud", "compute", "tpus", "tpu-vm", "describe"]
        assert "--zone" in cmd
        result = mock.Mock()
        result.stdout = json.dumps(payload).encode()
        return result

    monkeypatch.setattr(subprocess, "run", fake_run)
    hosts = discovery.discover_tpu_vm_hosts("my-tpu", zone="us-central2-b")
    assert [h.hostname for h in hosts] == ["10.0.1.1", "10.0.1.2"]


def test_discovery_exhausted_raises(monkeypatch):
    monkeypatch.setattr(discovery, "_get_metadata", lambda *a, **k: None)
    with pytest.raises(RuntimeError, match="TPU_YARN_WORKER_HOSTS"):
        discovery.discover_tpu_vm_hosts()


def test_advertise_explicit_hostport():
    backend = SshBackend(hosts=[TpuVmHost("h", 0)])
    assert _advertised_endpoint(
        "127.0.0.1:9000", backend, "10.1.2.3:1234"
    ) == "10.1.2.3:1234"
    # Bare host keeps the server's port.
    assert _advertised_endpoint(
        "127.0.0.1:9000", backend, "10.1.2.3"
    ) == "10.1.2.3:9000"


def test_advertise_remote_loopback_rewritten(monkeypatch):
    from tf_yarn_tpu import client as client_lib

    monkeypatch.setattr(client_lib, "_routable_host", lambda: "10.9.8.7")
    backend = SshBackend(hosts=[TpuVmHost("h", 0)])
    assert _advertised_endpoint("0.0.0.0:9000", backend, None) == "10.9.8.7:9000"
    assert _advertised_endpoint("127.0.0.1:9000", backend, None) == "10.9.8.7:9000"
    # An explicitly routable bind is passed through untouched.
    assert _advertised_endpoint("10.0.0.5:9000", backend, None) == "10.0.0.5:9000"


def test_advertise_local_backend_unchanged():
    assert _advertised_endpoint(
        "127.0.0.1:9000", LocalBackend(), None
    ) == "127.0.0.1:9000"


def test_ssh_backend_resolves_hosts_via_discovery(monkeypatch):
    monkeypatch.setenv(discovery.ENV_WORKER_HOSTS, "a,b")
    backend = SshBackend()  # no hosts given
    hosts = backend._resolve_hosts()
    assert [h.hostname for h in hosts] == ["a", "b"]
