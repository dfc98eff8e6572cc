"""Span tracer: nested, thread-aware host-side timing.

The host half of the observability story (the device half is the XLA
profiler capture, training._ProfileWindow): every host-side phase of a
run — input wait, step dispatch, checkpoint save, inference pipeline
stages — is wrapped in a `span(...)` context manager. Spans are
`perf_counter`-based (monotonic — wall-clock NTP steps corrupted the
old `time.time()` timers), nest per thread, and land in bounded
in-memory rings, one a span name, so tracing is always on, can never
grow a long run's memory, and a name written every few milliseconds
(the serving tick's) never evicts one written once (a compile, the
restore). What a ring drops is counted (`Tracer.evicted`).

Sinks/exports:

* **Chrome/Perfetto trace.** ``TPU_YARN_TRACE=<dir>`` makes the run
  entry points (train loop, `run_inference`) write
  ``trace_<task>.json`` in Chrome ``trace_event`` format on exit —
  load it in https://ui.perfetto.dev (or chrome://tracing) next to the
  XLA profiler capture from ``TPU_YARN_PROFILE``.
* **JSONL stream.** ``TPU_YARN_TRACE_JSONL=1`` (with ``TPU_YARN_TRACE``
  set) additionally streams every completed span as one JSON line to
  ``spans_<task>.jsonl`` — survives a SIGKILL that the end-of-run
  exporter would not.

All of this is strictly host-side: no jax import, nothing that can leak
into a jit trace (the analysis checker's TYA002/TYA003 gate stays the
proof — tests/test_analysis.py lints this package and every
instrumented call site).
"""

from __future__ import annotations

import collections
import itertools
import json
import logging
import operator
import os
import re
import threading
import time
from typing import Any, Callable, Dict, List, Optional

_logger = logging.getLogger(__name__)

TRACE_ENV = "TPU_YARN_TRACE"
TRACE_JSONL_ENV = "TPU_YARN_TRACE_JSONL"
TRACE_BUFFER_ENV = "TPU_YARN_TRACE_BUFFER"
# Spans kept of ONE name (what `TPU_YARN_TRACE_BUFFER` overrides). The
# serving tick writes each of its names at most once a tick, and the
# benchmark reads a 51 s window after a 16 s lead-in and a warm-up: at a
# 3 ms tick that is 22,400 spans of a name, held here with room (164 s
# of a 5 ms tick, 98 s of a 3 ms one).
DEFAULT_CAPACITY = 32_768
# Names with a ring of their own; the names after them share one ring
# under `OVERFLOW`, so memory is bounded by (MAX_NAMES + 1) rings
# whatever a caller formats into a name. A serving task has 25 names, 13
# of them written once a tick.
MAX_NAMES = 128
OVERFLOW = "*"

_clock = time.perf_counter  # monotonic; patchable in tests
_SPAN_IDS = itertools.count(1)  # next() is atomic under the GIL
_SPAN_ORDER = operator.attrgetter("order")

# Depth and thread of a span inserted by `Tracer.record`: it sat on no
# thread's stack, so "the deepest span over an instant" never picks it
# over a span that did.
RECORD_DEPTH = -1
RECORD_THREAD = "records"


def now() -> float:
    """The tracer's clock: what `Tracer.record` start times are on."""
    return _clock()


class Span:
    """One completed (or in-flight) span. Mutable: the context manager
    hands it to the with-block so callers can read ``.duration`` right
    after the block (the train loop's interval breakdown does)."""

    __slots__ = ("name", "category", "args", "start", "duration",
                 "thread_id", "thread_name", "depth", "parent", "id",
                 "parent_id", "order")

    def __init__(self, name: str, category: str, args: Dict[str, Any],
                 depth: int, parent: Optional["Span"]) -> None:
        self.name = name
        self.category = category
        self.args = args
        self.depth = depth
        # The enclosing span on this thread: its name (as ever) and its
        # id, unique in the process, so that two spans of one name are
        # told apart.
        self.parent = parent.name if parent is not None else None
        self.parent_id = parent.id if parent is not None else None
        self.id = next(_SPAN_IDS)
        self.order = 0  # its place among the completed spans, once it is one
        thread = threading.current_thread()
        self.thread_id = thread.ident or 0
        self.thread_name = thread.name
        self.duration = 0.0
        self.start = _clock()

    def to_json(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "cat": self.category,
            "start": self.start,
            "dur": self.duration,
            "tid": self.thread_id,
            "thread": self.thread_name,
            "depth": self.depth,
            "parent": self.parent,
            "id": self.id,
            "parent_id": self.parent_id,
            "args": self.args,
        }


class _SpanContext:
    """Class-based context manager (not contextlib) so exceptions —
    including StopIteration from a timed ``next()`` — propagate without
    generator-throw subtleties."""

    __slots__ = ("_tracer", "_name", "_category", "_args", "span")

    def __init__(self, tracer: "Tracer", name: str, category: str,
                 args: Dict[str, Any]) -> None:
        self._tracer = tracer
        self._name = name
        self._category = category
        self._args = args

    def __enter__(self) -> Span:
        self.span = self._tracer._begin(self._name, self._category, self._args)
        return self.span

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._tracer._end(self.span, error=exc_type is not None)
        return False


class Tracer:
    """Ring-buffered span recorder; thread-safe, one per process by
    default (module-level :func:`get_tracer`). `capacity` is the number
    of spans kept of one name: each of the first `MAX_NAMES` names has a
    ring of its own and no name evicts another's."""

    def __init__(self, capacity: Optional[int] = None) -> None:
        if capacity is None:
            try:
                capacity = int(os.environ.get(TRACE_BUFFER_ENV, "")
                               or DEFAULT_CAPACITY)
            except ValueError:
                capacity = DEFAULT_CAPACITY
        self.capacity = max(1, capacity)
        self._rings: Dict[str, "collections.deque[Span]"] = {}
        # Spans dropped, by ring (a name, or `OVERFLOW`): never reset,
        # so a reader of two snapshots sees what went between them.
        self.evicted: Dict[str, int] = {}
        self._completed = 0
        self._lock = threading.Lock()
        self._local = threading.local()
        self._sinks: List[Callable[[Span], None]] = []

    # -- recording ---------------------------------------------------------

    def span(self, name: str, category: str = "host", **args: Any):
        """Context manager timing its body; yields the :class:`Span`."""
        return _SpanContext(self, name, category, args)

    def record(self, name: str, start: float, duration: float,
               category: str = "host", **args: Any) -> Span:
        """Insert a finished span whose life crossed ticks and threads
        (a request from submit to finish). `start` is a reading of this
        module's clock (:func:`now`), never `time.monotonic()`. The span
        belongs to no thread's stack: `RECORD_DEPTH`, no parent, and the
        `RECORD_THREAD` row of the Chrome export."""
        span = Span(name, category, args, depth=RECORD_DEPTH, parent=None)
        span.start = start
        span.duration = duration
        span.thread_id = 0
        span.thread_name = RECORD_THREAD
        self._append(span)
        return span

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _begin(self, name: str, category: str, args: Dict[str, Any]) -> Span:
        stack = self._stack()
        span = Span(name, category, args, depth=len(stack),
                    parent=stack[-1] if stack else None)
        stack.append(span)
        return span

    def _end(self, span: Span, error: bool = False) -> None:
        span.duration = _clock() - span.start
        if error:
            span.args = dict(span.args, error=True)
        stack = self._stack()
        if stack and stack[-1] is span:
            stack.pop()
        self._append(span)

    def _append(self, span: Span) -> None:
        with self._lock:
            key = span.name
            ring = self._rings.get(key)
            if ring is None:
                if len(self._rings) >= MAX_NAMES:
                    key = OVERFLOW
                    ring = self._rings.get(key)
                if ring is None:
                    ring = self._rings[key] = collections.deque(
                        maxlen=self.capacity)
                    self.evicted.setdefault(key, 0)
            if len(ring) == self.capacity:
                self.evicted[key] += 1  # the append drops the oldest
            self._completed = span.order = self._completed + 1
            ring.append(span)
            sinks = list(self._sinks)
        for sink in sinks:
            try:
                sink(span)
            except Exception:
                _logger.warning("span sink failed", exc_info=True)

    # -- inspection --------------------------------------------------------

    def records(self) -> List[Span]:
        """Every span kept, in the order they were completed (the one
        ring's order, as ever: a parent after its children)."""
        with self._lock:
            kept = [span for ring in self._rings.values() for span in ring]
        kept.sort(key=_SPAN_ORDER)  # outside the lock: the writers go on
        return kept

    def evicted_total(self) -> int:
        with self._lock:
            return sum(self.evicted.values())

    def clear(self) -> None:
        """Drop the spans kept (the eviction counts are a history and
        stay)."""
        with self._lock:
            self._rings.clear()

    # -- sinks -------------------------------------------------------------

    def add_sink(self, sink: Callable[[Span], None]) -> None:
        with self._lock:
            self._sinks.append(sink)

    def remove_sink(self, sink: Callable[[Span], None]) -> None:
        with self._lock:
            if sink in self._sinks:
                self._sinks.remove(sink)

    def jsonl_sink(self, path: str):
        """Stream completed spans to `path` as JSON lines; returns a
        zero-arg close function that detaches the sink and closes the
        file."""
        fh = open(path, "a", encoding="utf-8")
        write_lock = threading.Lock()

        def sink(span: Span) -> None:
            line = json.dumps(span.to_json(), sort_keys=True)
            with write_lock:
                fh.write(line + "\n")
                fh.flush()

        self.add_sink(sink)

        def close() -> None:
            self.remove_sink(sink)
            with write_lock:
                fh.close()

        return close

    # -- Chrome trace_event export -----------------------------------------

    def chrome_events(self) -> List[Dict[str, Any]]:
        """The spans kept as Chrome ``trace_event`` dicts ("X" complete
        events + "M" thread-name metadata)."""
        pid = os.getpid()
        events: List[Dict[str, Any]] = []
        thread_names: Dict[int, str] = {}
        for span in self.records():
            thread_names.setdefault(span.thread_id, span.thread_name)
            events.append({
                "name": span.name,
                "cat": span.category,
                "ph": "X",
                "ts": span.start * 1e6,   # microseconds
                "dur": span.duration * 1e6,
                "pid": pid,
                "tid": span.thread_id,
                "args": dict(span.args, depth=span.depth, id=span.id,
                             parent_id=span.parent_id),
            })
        meta = [
            {"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
             "args": {"name": name}}
            for tid, name in sorted(thread_names.items())
        ]
        return meta + events

    def export_chrome_trace(self, path: str) -> str:
        payload = {
            "traceEvents": self.chrome_events(),
            "displayTimeUnit": "ms",
        }
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(payload, fh)
        return path


# --------------------------------------------------------------------------
# Process-global tracer + env-driven export
# --------------------------------------------------------------------------

_GLOBAL_TRACER = Tracer()
_JSONL_OPEN: Dict[str, Callable[[], None]] = {}
_JSONL_LOCK = threading.Lock()


def get_tracer() -> Tracer:
    return _GLOBAL_TRACER


def span(name: str, category: str = "host", **args: Any):
    """``with telemetry.span("train/input_wait") as sp: ...`` on the
    process-global tracer."""
    return _GLOBAL_TRACER.span(name, category=category, **args)


def trace_dir() -> Optional[str]:
    return os.environ.get(TRACE_ENV) or None


def _safe_task(task: Any) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]+", "-", str(task)) or "task"


def export_trace(task: Any = "local",
                 tracer: Optional[Tracer] = None) -> Optional[str]:
    """Write ``<TPU_YARN_TRACE>/trace_<task>.json`` (Chrome trace_event
    JSON) from the spans kept; no-op (returns None) when the env var is
    unset. Idempotent — later calls overwrite with the fuller buffer."""
    directory = trace_dir()
    if not directory:
        return None
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"trace_{_safe_task(task)}.json")
    (tracer or _GLOBAL_TRACER).export_chrome_trace(path)
    _logger.info("telemetry trace written to %s", path)
    return path


def enable_env_jsonl(task: Any = "local") -> Optional[str]:
    """Attach a streaming JSONL sink (``spans_<task>.jsonl`` under
    ``TPU_YARN_TRACE``) when ``TPU_YARN_TRACE_JSONL`` is truthy.
    Idempotent per path; returns the path or None when disabled."""
    directory = trace_dir()
    flag = os.environ.get(TRACE_JSONL_ENV, "").lower()
    if not directory or flag in ("", "0", "false", "no"):
        return None
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"spans_{_safe_task(task)}.jsonl")
    with _JSONL_LOCK:
        if path not in _JSONL_OPEN:
            _JSONL_OPEN[path] = _GLOBAL_TRACER.jsonl_sink(path)
    return path


def close_jsonl_sinks() -> None:
    """Detach + close every env-opened JSONL sink (tests)."""
    with _JSONL_LOCK:
        closers = list(_JSONL_OPEN.values())
        _JSONL_OPEN.clear()
    for close in closers:
        close()
