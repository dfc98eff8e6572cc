"""Start the run's coordination server: native coordd, else Python.

The native server (tf_yarn_tpu/native/coordd.cc) speaks the same wire
protocol as :class:`~tf_yarn_tpu.coordination.kv.KVServer`. Its binary
is never committed: `native_binary` builds it from the committed source
with the committed Makefile whenever it is missing or older than the
source, so which server a run uses follows from the checkout and the
host's toolchain, never from a file left on disk. ``TPU_YARN_COORDD=
python`` asks for the Python server by name.
"""

from __future__ import annotations

import logging
import os
import socket
import subprocess
import time
from typing import Optional

from tf_yarn_tpu.coordination.kv import KVClient, KVServer

_logger = logging.getLogger(__name__)

NATIVE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "native")
)
NATIVE_BINARY = os.path.join(NATIVE_DIR, "coordd")


class NativeServer:
    """Handle on a spawned coordd process, same surface as KVServer."""

    def __init__(self, proc: subprocess.Popen, host: str, port: int) -> None:
        self._proc = proc
        self._host = host
        self._port = port

    @property
    def endpoint(self) -> str:
        return f"{self._host}:{self._port}"

    def stop(self) -> None:
        try:
            KVClient(self.endpoint).shutdown_server()
        except Exception:
            _logger.debug(
                "coordd graceful shutdown request failed; terminating",
                exc_info=True,
            )
        if self._proc.poll() is None:
            self._proc.terminate()
        try:
            self._proc.wait(timeout=5)
        except subprocess.TimeoutExpired:  # pragma: no cover
            self._proc.kill()


def _free_port(host: str) -> int:
    with socket.socket() as sock:
        sock.bind((host, 0))
        return sock.getsockname()[1]


def native_binary() -> Optional[str]:
    """Path of an up-to-date coordd, built now if need be; None where
    the host cannot build it (no make or compiler, read-only install)."""
    import fcntl

    try:
        # One build at a time: concurrent drivers share the checkout.
        with open(os.path.join(NATIVE_DIR, "Makefile")) as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            build = subprocess.run(
                ["make", "-C", NATIVE_DIR, "coordd"],
                capture_output=True, text=True, timeout=300,
            )
    except (OSError, subprocess.TimeoutExpired) as exc:
        _logger.warning("cannot build native coordd: %s", exc)
        return None
    if build.returncode != 0:
        _logger.warning(
            "building native coordd failed (rc=%d): %s",
            build.returncode, build.stderr.strip()[-500:],
        )
        return None
    return NATIVE_BINARY


def start_native_server(host: str = "127.0.0.1") -> Optional[NativeServer]:
    binary = native_binary()
    if binary is None:
        return None
    port = _free_port(host)
    # Its one line of greeting would land in the driver's own output.
    proc = subprocess.Popen(
        [binary, host, str(port)], stdout=subprocess.DEVNULL
    )
    client = KVClient(f"{host}:{port}", connect_timeout=1.0)
    for _ in range(50):
        try:
            if client.ping() == "coordd":
                _logger.info("native coordd serving on %s:%d", host, port)
                return NativeServer(proc, host, port)
        except (ConnectionError, OSError, RuntimeError):
            # Startup probe, not a retry loop: a fixed 0.1s cadence against
            # a process we just spawned locally is the point (bounded at
            # 50 probes = 5s); backoff would only slow detection.
            time.sleep(0.1)  # noqa: TYA011
    proc.terminate()
    _logger.warning("native coordd failed to come up; falling back to Python")
    return None


def start_best_server(host: str = "127.0.0.1"):
    if os.environ.get("TPU_YARN_COORDD", "auto") != "python":
        native = start_native_server(host)
        if native is not None:
            return native
    return KVServer(host).start()


def server_kind(server) -> str:
    """What `start_best_server` returned, for run reports."""
    return "coordd" if isinstance(server, NativeServer) else "python"
