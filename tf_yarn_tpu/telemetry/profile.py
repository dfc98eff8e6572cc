"""The one place that starts and stops the XLA profiler.

The device half of the observability story (the host half is
:mod:`~tf_yarn_tpu.telemetry.spans`): a `jax.profiler` capture, tied to
the span clock. Every capture writes a ``tf_yarn_tpu/clock_sync``
`TraceAnnotation` whose body reads the span clock, so a reader can put
the trace's device operations and the program's host spans on one axis
(annotation start on the trace's clock == the returned reading on the
span clock). The capture itself is recorded as a ``telemetry/profile``
span when it stops.

Callers: the train loop's profile window (``TPU_YARN_PROFILE``,
training.py) and the serving frontend's ``POST /debug/profile``
(serving/server.py). One capture at a time per process — the profiler
is a process-wide singleton — and a second `start` raises
:class:`ProfileBusy`.

jax is imported inside the functions: the rest of this package is
host-only and must stay importable without it.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from typing import Any, Dict, Optional

from tf_yarn_tpu.telemetry import spans

_logger = logging.getLogger(__name__)

PROFILE_ENV = "TPU_YARN_PROFILE"
SYNC_ANNOTATION = "tf_yarn_tpu/clock_sync"
MAX_CAPTURE_SECONDS = 120.0

_lock = threading.Lock()
_capture: Optional[Dict[str, Any]] = None  # dir, sync, started, stopping


class ProfileBusy(RuntimeError):
    """A capture is already running in this process."""


def active() -> bool:
    return _capture is not None


def start(directory: str, python_tracer: bool = False) -> float:
    """Start a capture into `directory`; returns the span-clock reading
    taken inside the ``tf_yarn_tpu/clock_sync`` annotation.
    `python_tracer` also records Python frames (the train loop's
    whole-run default; far too heavy beside a serving tick)."""
    global _capture
    from jax import profiler

    with _lock:
        if _capture is not None:
            raise ProfileBusy(
                f"a profiler capture into {_capture['dir']} is running"
            )
        if python_tracer:
            profiler.start_trace(directory)
        else:
            options = profiler.ProfileOptions()
            options.python_tracer_level = 0
            profiler.start_trace(directory, profiler_options=options)
        started = spans.now()
        with profiler.TraceAnnotation(SYNC_ANNOTATION):
            sync = spans.now()
        _capture = {"dir": directory, "sync": sync, "started": started}
    _logger.info("profiler capture started -> %s", directory)
    return sync


def stop() -> Optional[Dict[str, Any]]:
    """Stop the running capture and write it out (seconds for a long
    one). Returns ``{"dir", "sync_perf_s", "seconds"}``, or None when
    none was running."""
    global _capture
    from jax import profiler

    with _lock:
        capture = _capture
        if capture is None or capture.get("stopping"):
            return None
        capture["stopping"] = True  # a `start` meanwhile is refused
    stopped = spans.now()
    try:
        profiler.stop_trace()
    finally:
        with _lock:
            _capture = None
    seconds = stopped - capture["started"]
    spans.get_tracer().record(
        "telemetry/profile", capture["started"], seconds,
        dir=capture["dir"], sync_perf_s=capture["sync"],
    )
    _logger.info("profiler trace written to %s", capture["dir"])
    return {"dir": capture["dir"], "sync_perf_s": capture["sync"],
            "seconds": seconds}


def capture(directory: str, seconds: float) -> Dict[str, Any]:
    """One bounded capture: start, sleep `seconds`, stop."""
    seconds = min(max(float(seconds), 0.0), MAX_CAPTURE_SECONDS)
    start(directory)
    try:
        time.sleep(seconds)
    finally:
        result = stop()
    return result


def annotation(name: str, **args: Any):
    """A `TraceAnnotation` in the profiler's own host plane while a
    capture started here is running, else a no-op: the serving tick
    numbers its model steps with it, next to the device's executions."""
    if _capture is None:
        return contextlib.nullcontext()
    from jax import profiler

    return profiler.TraceAnnotation(name, **args)
