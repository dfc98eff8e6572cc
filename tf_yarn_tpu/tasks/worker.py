"""Default task program for chief/worker tasks.

The analog of the reference's `_independent_workers_task` (reference:
tensorflow/tasks/_independent_workers_task.py:17-47): bootstrap, pull the
experiment from the KV store, dispatch on its type, run the training
function in a MonitoredThread, and report lifecycle events throughout.

Dispatch (grown as experiment adapters land):
* `tf_yarn_tpu.experiment` types (JaxExperiment & friends) — the JAX/pjit
  train loop (see tf_yarn_tpu.training).
* a plain callable — invoked with no args (escape hatch).
For the function-of-rank mode use
``custom_task_module="tf_yarn_tpu.tasks.distributed"``.
"""

from __future__ import annotations

import logging

from tf_yarn_tpu import _task_commons, event, telemetry
from tf_yarn_tpu._internal import MonitoredThread
from tf_yarn_tpu.tasks import _bootstrap

_logger = logging.getLogger(__name__)


def _maybe_init_jax_distributed(runtime: _bootstrap.TaskRuntime) -> None:
    """Multi-host JAX bootstrap. Must run before anything touches devices —
    the ordering constraint SURVEY.md §7 ranks as hard part 3 (the analog of
    TF_CONFIG-before-Estimator, _independent_workers_task.py:22-24). The
    coordinator is our KV-elected master (reference choose_master,
    _task_commons.py:95-108) — jax.distributed's coordinator replaces
    nothing here: the KV service stays the control plane, this only wires
    process discovery for multi-host XLA."""
    import os

    primaries = sorted(
        (ti for ti in runtime.cluster_tasks if ti.key.type in ("chief", "worker")),
        key=lambda ti: (0 if ti.key.type == "chief" else 1, ti.key.id),
    )
    if len(primaries) <= 1 or os.environ.get("TPU_YARN_NO_JAX_DIST"):
        return
    if any(ti.nb_proc != 1 for ti in primaries):
        raise ValueError(
            "JAX experiments need nb_proc_per_worker=1 (one JAX process "
            "drives all local chips); use tasks.distributed for "
            "multi-process-per-host jobs"
        )
    # hold=True: jax.distributed's gRPC coordinator binds with SO_REUSEPORT
    # on Linux, so the reservation can stay open across its bind — no
    # window for another process to steal the elected port.
    addr = _task_commons.choose_master(
        runtime.kv, runtime.task_key, runtime.cluster_tasks, hold=True
    )
    process_id = [ti.key for ti in primaries].index(runtime.task_key)
    import jax

    platform = os.environ.get("TPU_YARN_PLATFORM")
    if platform:  # narrow backend selection before any distributed setup
        jax.config.update("jax_platforms", platform)
    try:
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=len(primaries),
            process_id=process_id,
        )
    finally:
        # Coordinator (or its failure) has the port now; drop the hold.
        _task_commons.release_master_reservation()
    _logger.info(
        "jax.distributed up: process %d/%d, coordinator %s",
        process_id, len(primaries), addr,
    )


def _run_experiment(runtime: _bootstrap.TaskRuntime, experiment) -> None:
    from tf_yarn_tpu import experiment as experiment_mod

    if isinstance(experiment, experiment_mod.EXPERIMENT_TYPES):
        _maybe_init_jax_distributed(runtime)
        experiment_mod.run_experiment(runtime, experiment)
    elif callable(experiment):
        experiment()
    else:
        raise TypeError(
            f"unsupported experiment type {type(experiment)!r}; expected one "
            f"of {experiment_mod.EXPERIMENT_TYPES} or a callable (for raw "
            "fn-of-rank jobs use custom_task_module="
            '"tf_yarn_tpu.tasks.distributed")'
        )


def main() -> None:
    from tf_yarn_tpu import preemption

    # Main thread, before the train thread exists: SIGTERM (the TPU-VM
    # preemption notice) sets the drain flag the train loop polls.
    preemption.install()
    runtime = _bootstrap.init_runtime()
    with _bootstrap.reporting_shutdown(runtime):
        experiment = _task_commons.get_experiment(runtime.kv)
        event.start_event(runtime.kv, runtime.task)
        event.train_eval_start_event(runtime.kv, runtime.task)
        # Run in a MonitoredThread so the captured exception carries the
        # training stack, as in the reference (tf_task_common.py:56-74).
        thread = MonitoredThread(
            target=_run_experiment,
            args=(runtime, experiment),
            name=f"train-{runtime.task}",
        )
        # Liveness + metrics beacon for the whole experiment: the chief
        # reads {task}/heartbeat ages (utils.metrics.task_heartbeats), the
        # driver's watchdog turns silence past TPU_YARN_DEAD_TASK_SECS
        # into a LOST_TASK failure, and the {task}/metrics registry
        # snapshot rides along. TPU_YARN_HEARTBEAT_SECS=0 disables; a
        # clean stop publishes a heartbeat.stopped tombstone.
        with telemetry.Heartbeat(
            runtime.kv, runtime.task,
            every=telemetry.heartbeat.every_from_env(),
            registry=telemetry.get_registry(),
        ):
            thread.start()
            thread.join()
        event.train_eval_stop_event(runtime.kv, runtime.task)
        if thread.exception is not None:
            raise thread.exception


if __name__ == "__main__":
    main()
