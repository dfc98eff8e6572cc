"""Launcher plumbing: `run_on_tpu` on a thread of a parent that stays off
JAX, the task's environment, and small HTTP helpers. (The launch class
follows chip_smoke.py's; see PERF.md, Open questions.)"""

from __future__ import annotations

import contextlib
import http.client
import json
import os
import signal
import socket
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, "cellbench_cache")
ASK_AGAIN_S = 3.0  # between two looks at whether a SIGTERM was heard


def task_env(run_dir: str) -> dict:
    """What every process of a run is started with. The compile cache sits
    at one fixed place inside the checkout, whatever the machine says, with
    no size limit that would evict between runs and no least compile time."""
    return {
        "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        "JAX_COMPILATION_CACHE_DIR": os.path.join(CACHE_DIR, "jax"),
        "JAX_COMPILATION_CACHE_MAX_SIZE": "-1",
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "TPU_YARN_TRACE": os.path.join(run_dir, "spans"),
    }


class Launch:
    """`run_on_tpu` on a thread, so that this process can talk to the task
    while it runs. `stop` ends the task the way a TPU VM does, with SIGTERM,
    and raises what the run raised; leaving the block otherwise kills it."""

    def __init__(self, experiment_fn, task_specs, name, run_dir):
        from tf_yarn_tpu.backends import LocalBackend
        from tf_yarn_tpu.client import run_on_tpu

        class Backend(LocalBackend):
            handle = None

            def launch(self, services, log_dir):
                self.log_dir = log_dir
                self.handle = super().launch(services, log_dir)
                return self.handle

        self.backend = Backend()
        self.error = None

        def run():
            try:
                run_on_tpu(experiment_fn, task_specs, name=name,
                           backend=self.backend, env=task_env(run_dir))
            except BaseException as exc:  # noqa: B036 — raised by check()
                self.error = exc

        self._thread = threading.Thread(target=run, name=name)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._thread.is_alive() and self.backend.handle is not None:
            self.backend.handle.kill()
        self._thread.join(timeout=60)

    def check(self):
        if self.error is not None:
            raise self.error
        if not self._thread.is_alive():
            raise RuntimeError("the task ended before it was told to stop"
                               + self.log_tail())

    def log_tail(self, n: int = 30) -> str:
        out = []
        log_dir = getattr(self.backend, "log_dir", None)
        if log_dir and os.path.isdir(log_dir):
            for name in sorted(os.listdir(log_dir)):
                if name.endswith(".log"):
                    with open(os.path.join(log_dir, name), errors="replace") as fh:
                        out.append(f"\n--- {name}\n" + "".join(fh.readlines()[-n:]))
        return "".join(out)

    def stop(self, port=None, timeout=120.0):
        """SIGTERM to every task, then wait. The signal can miss the program's
        handler (PERF.md, Open questions: the kernel may hand it to a thread
        other than the main one, which alone runs Python's handlers and sleeps
        in an untimed join). So while the server at `port` still answers
        `/healthz` with "ok", which reads the flag that the handler sets, the
        signal is sent again. `self.stopping` keeps how often it was sent."""
        pids = self.backend.handle.pids()
        self.stopping = {"pids": pids, "sent": 0}
        deadline, heard = time.monotonic() + timeout, False
        while self._thread.is_alive() and time.monotonic() < deadline:
            if not heard:
                for pid in pids.values():
                    with contextlib.suppress(ProcessLookupError):
                        os.kill(pid, signal.SIGTERM)
                self.stopping["sent"] += 1
            self._thread.join(timeout=ASK_AGAIN_S)
            heard = port is None or draining(port)
        if self._thread.is_alive():
            raise RuntimeError(
                f"the task did not stop within {timeout:.0f}s of SIGTERM, "
                f"sent {self.stopping['sent']} times to {pids}")
        if self.error is not None:
            raise self.error


def draining(port) -> bool:
    """Whether the server's SIGTERM handler has run: it drains, or is gone."""
    try:
        return http_json(port, "GET", "/healthz", timeout=5.0)["status"] != "ok"
    except (OSError, http.client.HTTPException, RuntimeError, ValueError):
        return True


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def http_json(port, method, path, body=None, timeout=600.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, None if body is None else json.dumps(body),
                     {"Content-Type": "application/json"})
        response = conn.getresponse()
        payload = json.loads(response.read())
    finally:
        conn.close()
    if response.status != 200:
        raise RuntimeError(f"{method} {path} -> {response.status}: {payload}")
    return payload


def wait_healthy(launch, port, timeout=900.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        launch.check()
        try:
            http_json(port, "GET", "/healthz", timeout=5.0)
            return
        except (OSError, http.client.HTTPException):
            time.sleep(0.25)
    raise TimeoutError(f"nothing healthy on port {port} after {timeout:.0f}s")
