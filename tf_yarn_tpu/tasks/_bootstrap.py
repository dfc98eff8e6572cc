"""Shared task-program bootstrap/shutdown.

The per-container prologue/epilogue the reference spreads over
`_prepare_container` / `_shutdown_container` (reference:
tensorflow/tasks/tf_task_common.py:21-99): connect to the coordination
service, publish start-time + log-location events, and on the way out
publish the stop event (with traceback payload on failure) + stop-time,
exiting nonzero so the backend's process status agrees with the events.
"""

from __future__ import annotations

import logging
import sys
from contextlib import contextmanager
from typing import Iterator, List, NamedTuple, Optional

from tf_yarn_tpu import _task_commons, compile_cache, event
from tf_yarn_tpu.coordination.kv import KVClient
from tf_yarn_tpu.topologies import TaskInstance, TaskKey

_logger = logging.getLogger(__name__)


class TaskRuntime(NamedTuple):
    kv: KVClient
    task_key: TaskKey
    task: str  # "type:id"
    cluster_tasks: List[TaskInstance]
    n_try: int


def init_runtime(need_cluster: bool = True) -> TaskRuntime:
    _task_commons.setup_logging()
    _logger.info("compile cache at %s", compile_cache.enable())
    kv = _task_commons.connect_kv()
    task_key = _task_commons.get_task_key()
    task = task_key.to_kv_str()
    _task_commons.setup_task_logs(kv, task)
    cluster_tasks = _task_commons.get_cluster_tasks(kv) if need_cluster else []
    return TaskRuntime(kv, task_key, task, cluster_tasks, _task_commons.n_try())


@contextmanager
def reporting_shutdown(runtime: TaskRuntime) -> Iterator[None]:
    """Publish stop/stop-time events no matter how the body ends; re-exit
    nonzero on failure so ClusterHandle.status() sees FAILED too."""
    failure: Optional[BaseException] = None
    try:
        yield
    except BaseException as exc:  # noqa: B036 — report then re-raise
        failure = exc
    finally:
        event.stop_event(runtime.kv, runtime.task, failure)
        event.stop_time_event(runtime.kv, runtime.task)
    if failure is not None:
        _logger.exception("task %s failed", runtime.task, exc_info=failure)
        sys.exit(1)


def wait_for_all_stops(
    runtime: TaskRuntime, timeout_per_task: float = 3600.0
) -> None:
    """Barrier on every cluster task's `stop` event — the reference's
    shutdown barrier that keeps side-cars alive until training ends
    (reference: tf_task_common.py:102-118)."""
    for instance in runtime.cluster_tasks:
        peer = instance.to_kv_str()
        if peer != runtime.task:
            event.wait(runtime.kv, f"{peer}/{event.STOP}", timeout=timeout_per_task)
