"""Continuous (side-car) evaluation over the checkpoint stream.

Port of the reference's evaluator loop (reference: tensorflow/tasks/
evaluator_task.py:18-158): poll the experiment's model_dir, evaluate every
checkpoint exactly once, stop when the final-step checkpoint is done or
nothing new has appeared for the idle timeout. Evaluated-set persistence
uses `eval-done-<step>.json` marker files next to the checkpoints — the
role the reference's tf-events parsing plays (evaluator_task.py:46-51,
tensorflow/metrics.py:74-100) without a TF dependency.

Health metrics broadcast to the KV store match the reference's monitored
set (evaluator_metrics.py:12-17): awake_time_ratio,
eval_step_mean_duration, last_training_step, nb_eval_steps — polled and
logged driver-side by utils.evaluator_metrics.EvaluatorMetricsLogger.

Placement: the evaluator is a CPU task (SURVEY.md §7 hard part 5 — TPU
hosts are symmetric, so the driver pins TPU_YARN_PLATFORM=cpu in its env).
"""

from __future__ import annotations

import json
import logging
import os
import time
from typing import Optional, Set

import jax

from tf_yarn_tpu import checkpoint as ckpt_lib
from tf_yarn_tpu import event
from tf_yarn_tpu import fs as fs_lib
from tf_yarn_tpu.experiment import as_core_experiment
from tf_yarn_tpu.parallel import mesh as mesh_lib
from tf_yarn_tpu.tasks import _bootstrap
from tf_yarn_tpu.training import build_eval_step, evaluate
from tf_yarn_tpu.utils import mlflow

_logger = logging.getLogger(__name__)

DEFAULT_IDLE_TIMEOUT_SECS = 20 * 60  # reference: evaluator_task.py:21-23
DEFAULT_POLL_SECS = 10.0


EVAL_DONE_DIR = "eval-done"  # bookkeeping lives out of checkpoint listings


def _marker_steps(directory: str) -> Set[int]:
    done: Set[int] = set()
    for entry, _is_dir in fs_lib.listdir(directory):
        if entry.startswith("eval-done-") and entry.endswith(".json"):
            try:
                done.add(int(entry[len("eval-done-"):-len(".json")]))
            except ValueError:
                continue
    return done


def _evaluated_steps(model_dir: str) -> Set[int]:
    # Markers written before the subdirectory move lived at the model_dir
    # root; honor both so resuming against an old run doesn't re-evaluate
    # (and re-emit metrics for) every checkpoint. model_dir may be any fs
    # URI (the reference lists its HDFS model_dir the same way,
    # evaluator_task.py:38-51).
    return _marker_steps(fs_lib.join(model_dir, EVAL_DONE_DIR)) | _marker_steps(
        model_dir
    )


def _mark_evaluated(model_dir: str, step: int, metrics: dict) -> None:
    marker = fs_lib.join(model_dir, EVAL_DONE_DIR, f"eval-done-{step}.json")
    fs_lib.write_text(marker, json.dumps(metrics))


def evaluate_checkpoint(
    model, loss_fn, model_dir: str, step: int, eval_input_fn, eval_steps: int,
    rng_seed: int = 0,
) -> dict:
    """Host-restore ckpt-<step> and evaluate it on `eval_input_fn`
    (Estimator.evaluate's one-shot path; the side-car loop keeps its own
    copy with a pre-built jitted eval_step so repeated checkpoints reuse
    one compilation)."""
    from tf_yarn_tpu.training import TrainState, build_eval_step, evaluate

    state = ckpt_lib.restore_checkpoint_host(model_dir, step)
    params = state["params"] if isinstance(state, dict) else state.params
    eval_state = TrainState(step=0, params=params, opt_state=())
    eval_step = jax.jit(build_eval_step(model, loss_fn))
    return evaluate(
        eval_step, eval_state, eval_input_fn, lambda b: b, eval_steps,
        jax.random.PRNGKey(rng_seed),
    )


def continuous_eval(
    runtime: Optional[_bootstrap.TaskRuntime],
    experiment,
    poll_secs: float = DEFAULT_POLL_SECS,
    idle_timeout_secs: Optional[float] = None,
) -> dict:
    """Evaluate checkpoints as they appear; returns last metrics."""
    if idle_timeout_secs is None:
        idle_timeout_secs = float(
            os.environ.get("TPU_YARN_EVAL_IDLE_TIMEOUT", DEFAULT_IDLE_TIMEOUT_SECS)
        )
    # The launcher starts the evaluator as a CPU side-car
    # (TPU_YARN_PLATFORM=cpu): only that backend may start here.
    mesh_lib.select_devices()
    core = as_core_experiment(experiment)
    if not core.model_dir:
        raise ValueError("continuous evaluation needs an experiment model_dir")
    fs_lib.check_model_dir_placement(core.model_dir)
    eval_input_fn = core.eval_input_fn or core.train_input_fn
    eval_step = jax.jit(build_eval_step(core.model, core.loss_fn))
    rng = jax.random.PRNGKey(core.train_params.seed)

    # One callable or a sequence of them (the reference's exporters is a
    # list, evaluator_task.py:103-121).
    if core.exporters is None:
        exporter_fns = []
    elif callable(core.exporters):
        exporter_fns = [core.exporters]
    else:
        exporter_fns = list(core.exporters)

    done = _evaluated_steps(core.model_dir)
    final_step = core.train_params.train_steps
    last_metrics: dict = {}
    last_new = time.time()
    awake_time = 0.0
    start_time = time.time()
    nb_eval_steps = 0
    n_try = runtime.n_try if runtime is not None else 0

    def broadcast_health(eval_elapsed: float, n_batches: int, step: int) -> None:
        if runtime is None:
            return
        total = max(time.time() - start_time, 1e-9)
        stats = {
            "awake_time_ratio": f"{awake_time / total:.4f}",
            "eval_step_mean_duration": f"{eval_elapsed / max(n_batches, 1):.4f}",
            "last_training_step": str(step),
            "nb_eval_steps": str(nb_eval_steps),
        }
        for key, value in stats.items():
            event.broadcast(runtime.kv, f"{runtime.task}/{key}", value)

    while True:
        pending = [
            s for s in ckpt_lib.list_checkpoint_steps(core.model_dir) if s not in done
        ]
        for step in pending:
            t0 = time.time()
            try:
                # Host (numpy) restore: the training mesh's sharded save
                # must be readable on the evaluator's single CPU device.
                state = ckpt_lib.restore_checkpoint_host(core.model_dir, step)
            except Exception as exc:  # partially-written ckpt; retry next poll
                _logger.warning("could not restore ckpt-%d yet: %s", step, exc)
                continue

            from tf_yarn_tpu.training import TrainState

            params = state["params"] if isinstance(state, dict) else state.params
            eval_state = TrainState(step=0, params=params, opt_state=())

            # Count actually-consumed eval batches (the input may be
            # shorter than eval_steps) so the health metrics stay honest.
            consumed = {"n": 0}

            def counted_input():
                for batch in eval_input_fn():
                    consumed["n"] += 1
                    yield batch

            # Evaluator runs single-device (CPU): identity globalizer.
            metrics = evaluate(
                eval_step,
                eval_state,
                counted_input,
                lambda b: b,
                core.train_params.eval_steps,
                rng,
            )
            elapsed = time.time() - t0
            awake_time += elapsed
            nb_eval_steps += consumed["n"]
            for exporter in exporter_fns:
                # Post-eval export hooks (reference: eval_spec.exporters
                # run after each evaluation, evaluator_task.py:103-121).
                try:
                    exporter(params, metrics, step)
                except Exception:
                    _logger.exception("exporter failed for ckpt-%d", step)
            last_metrics = metrics
            done.add(step)
            last_new = time.time()
            _mark_evaluated(core.model_dir, step, metrics)
            _logger.info("evaluated ckpt-%d: %s (%.1fs)", step, metrics, elapsed)
            for key, value in metrics.items():
                mlflow.log_metric(f"eval_{key}_{n_try}", value, step=step)
            broadcast_health(elapsed, consumed["n"], step)

        if any(s >= final_step for s in done):
            _logger.info("final checkpoint (step %d) evaluated; stopping", final_step)
            break
        if _training_finished(runtime):
            # Training ended early (input exhausted before train_steps):
            # re-list to catch a final checkpoint written just before the
            # stop event, then finish without the 20-min idle wait.
            remaining = [
                s
                for s in ckpt_lib.list_checkpoint_steps(core.model_dir)
                if s not in done
            ]
            if not remaining:
                _logger.info("training stopped and no pending ckpts; stopping")
                break
        if time.time() - last_new > idle_timeout_secs:
            _logger.info("no new checkpoint for %.0fs; stopping", idle_timeout_secs)
            break
        time.sleep(poll_secs)
    return last_metrics


def _training_finished(runtime: Optional[_bootstrap.TaskRuntime]) -> bool:
    """True when every chief/worker has broadcast its stop event."""
    if runtime is None:
        return False
    primaries = [
        ti for ti in runtime.cluster_tasks if ti.key.type in ("chief", "worker")
    ]
    return bool(primaries) and all(
        runtime.kv.get(f"{ti.to_kv_str()}/{event.STOP}") is not None
        for ti in primaries
    )
