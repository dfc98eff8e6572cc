"""Driver: `run_on_tpu` — submit an experiment onto a TPU slice and await it.

TPU-native rebuild of the reference launcher (reference: tf_yarn/client.py:
299-466 `run_on_yarn`, 179-270 `_setup_skein_cluster`, 527-631
`_execute_and_await_termination`, 633-739 event aggregation & metrics).
The differences are architectural, not cosmetic:

* No YARN: a pluggable :class:`~tf_yarn_tpu.backends.SliceBackend` places
  task programs on hosts (subprocesses locally, ssh across a TPU pod).
* No skein AM: the driver starts the in-repo coordination service
  (native ``coordd`` when built, Python otherwise) and tears it down with
  the run.
* The experiment crosses to tasks exactly as in the reference: cloudpickled
  through the KV store (reference: client.py:281,536).
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import logging
import os
import tempfile
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, List, Optional, Union

import cloudpickle

from tf_yarn_tpu import _env, constants, event, resilience, telemetry
from tf_yarn_tpu._internal import MonitoredThread
from tf_yarn_tpu.resilience import (
    Deadline,
    ElasticPolicy,
    FailureKind,
    HeartbeatWatchdog,
    RetryPolicy,
)
from tf_yarn_tpu.backends import (
    FAILED,
    KILLED,
    PRIMARY_TASK_TYPES,
    RUNNING,
    ClusterHandle,
    LocalBackend,
    ServiceSpec,
    SliceBackend,
)
from tf_yarn_tpu.coordination import KVClient, KVStore
from tf_yarn_tpu.coordination.server_factory import start_best_server
from tf_yarn_tpu.topologies import (
    TaskSpec,
    TaskSpecs,
    check_topology,
    single_server_topology,
)
from tf_yarn_tpu.utils import mlflow
from tf_yarn_tpu.utils.evaluator_metrics import EvaluatorMetricsLogger
from tf_yarn_tpu.utils.metrics import (
    Metrics,
    OneShotMetricsLogger,
    TaskOutcome,
    handle_events,
)

_logger = logging.getLogger(__name__)

ExperimentFn = Callable[[], object]


class RunFailed(Exception):
    """Raised when the experiment fails (reference: client.py:89-90).
    Carries the attempt's :class:`~tf_yarn_tpu.resilience.FailureKind`
    so callers (and the retry loop) can act on *why*, plus the tasks
    that died without a lifecycle close (`lost_tasks`) so the elastic
    resize path can count the hosts that actually went away."""

    def __init__(
        self,
        message: str,
        kind: Optional[FailureKind] = None,
        lost_tasks: Optional[List[str]] = None,
    ):
        super().__init__(message)
        self.kind = kind
        self.lost_tasks = list(lost_tasks or [])


@dataclass
class SliceCluster:
    """A running cluster: coordination service + launched tasks
    (the reference's SkeinCluster, client.py:53-59)."""

    server: object
    kv: KVStore
    handle: ClusterHandle
    cluster_tasks: List[str]
    log_dir: str
    event_listener: Optional[MonitoredThread] = None
    events: Dict[str, Dict[str, str]] = field(default_factory=dict)


def _setup_cluster_spec(task_specs: TaskSpecs, kv: KVStore) -> List[str]:
    """Post the cluster layout; evaluator/tensorboard are side-cars and not
    part of the training cluster (reference: client.py:170-176)."""
    instances = [
        (f"{task_type}:{task_id}", spec.nb_proc_per_worker)
        for task_type, spec in task_specs.items()
        if task_type not in ("evaluator", "tensorboard")
        for task_id in range(spec.instances)
    ]
    kv.put_str(constants.KV_CLUSTER_INSTANCES, json.dumps(instances))
    return [task for task, _ in instances]


def _setup_task_env(
    task_specs: TaskSpecs,
    endpoint: str,
    log_dir: str,
    n_try: int,
    env: Dict[str, str],
    custom_task_module: Optional[str],
    pre_script_hook: str,
    files: Optional[Dict[str, str]] = None,
) -> Dict[str, ServiceSpec]:
    """Build one ServiceSpec per task type (reference: client.py:108-133
    `_setup_task_env` + 210-240 service construction)."""
    services: Dict[str, ServiceSpec] = {}
    for task_type, spec in task_specs.items():
        if spec.instances == 0:
            continue
        task_env = dict(env)
        task_env[constants.ENV_COORDINATOR] = endpoint
        task_env[constants.ENV_N_TRY] = str(n_try)
        task_env[constants.ENV_LOG_DIR] = log_dir
        task_env[constants.ENV_NB_PROC] = str(spec.nb_proc_per_worker)
        # MLflow context crosses to tasks via env, as in the reference
        # (client.py:124-133) — but only when mlflow is really active (the
        # reference's `if mlflow.use_mlflow:` bug is fixed here, SURVEY §2.6).
        if mlflow.use_mlflow():
            task_env.setdefault("MLFLOW_RUN_ID", mlflow.active_run_id())
            tracking_uri = mlflow.get_tracking_uri()
            if tracking_uri:
                task_env.setdefault("MLFLOW_TRACKING_URI", tracking_uri)
        if task_type == "evaluator":
            # CPU side-car: never grabs the slice's chips (SURVEY §7 hard
            # part 5 — placement the reference got free from YARN labels).
            task_env.setdefault("TPU_YARN_PLATFORM", "cpu")
        if task_type == "tensorboard":
            if spec.tb_model_dir:
                task_env.setdefault("TB_MODEL_DIR", spec.tb_model_dir)
            if spec.tb_extra_args:
                task_env.setdefault("TB_EXTRA_ARGS", spec.tb_extra_args)
            task_env.setdefault(
                "TB_TERMINATION_TIMEOUT_SECONDS",
                str(spec.tb_termination_timeout_seconds),
            )
        services[task_type] = ServiceSpec(
            module=_env.gen_task_module(task_type, custom_task_module),
            instances=spec.instances,
            env=task_env,
            nb_proc=spec.nb_proc_per_worker,
            pre_script_hook=pre_script_hook,
            files=dict(files or {}),
            chips_per_host=spec.chips_per_host,
        )
    return services


def _start_event_listener(cluster: SliceCluster) -> MonitoredThread:
    """Tail the KV event log and record last-seen stage per task
    (reference: `_aggregate_events`, client.py:633-657)."""

    def listen() -> None:
        cursor = 0
        while cluster.handle.status() == RUNNING:
            tail, cursor = cluster.kv.events(cursor)
            for _, key in tail:
                task, _, stage = key.rpartition("/")
                if task:
                    value = cluster.kv.get_str(key) or ""
                    cluster.events.setdefault(task, {})[stage] = value
                    _logger.info("event %s = %.80s", key, value)
            time.sleep(0.5)

    thread = MonitoredThread(target=listen, name="event-listener", daemon=True)
    thread.start()
    return thread


def _routable_host() -> str:
    """This machine's address as other hosts see it. The UDP connect trick
    picks the interface with a default route (no packet is sent)."""
    import socket

    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as sock:
            sock.connect(("8.8.8.8", 80))
            return sock.getsockname()[0]
    except OSError:
        return socket.getfqdn()


def _advertised_endpoint(
    server_endpoint: str, backend: SliceBackend, coordinator_advertise: Optional[str]
) -> str:
    """The coordinator address tasks dial. Remote backends must not be
    handed the bind host when it's loopback/wildcard — they would connect
    to *their own* localhost and hang (ADVICE r1: client.py:350)."""
    host, _, port = server_endpoint.rpartition(":")
    if coordinator_advertise:
        if ":" in coordinator_advertise:
            return coordinator_advertise
        return f"{coordinator_advertise}:{port}"
    if getattr(backend, "is_remote", True) and host in (
        "127.0.0.1", "localhost", "0.0.0.0", "",
    ):
        routable = _routable_host()
        _logger.info(
            "advertising coordinator as %s:%s to remote tasks "
            "(bind address %s is not routable)", routable, port, host,
        )
        return f"{routable}:{port}"
    return server_endpoint


def _setup_cluster(
    task_specs: TaskSpecs,
    backend: SliceBackend,
    n_try: int,
    env: Dict[str, str],
    custom_task_module: Optional[str],
    pre_script_hook: str,
    name: str,
    coordinator_bind: str,
    files: Optional[Dict[str, str]] = None,
    coordinator_advertise: Optional[str] = None,
) -> SliceCluster:
    log_dir = tempfile.mkdtemp(prefix=f"{name}-logs-")
    server = start_best_server(host=coordinator_bind)
    if getattr(backend, "is_remote", True):
        # Tasks land on other machines: let fs.check_model_dir_placement
        # fail fast on host-local model_dirs (shared mounts opt out via
        # TPU_YARN_ALLOW_LOCAL_MODEL_DIR=1).
        env = dict(env)
        env.setdefault("TPU_YARN_REMOTE_BACKEND", "1")
    try:
        kv = KVClient(server.endpoint)
        services = _setup_task_env(
            task_specs,
            _advertised_endpoint(server.endpoint, backend, coordinator_advertise),
            log_dir,
            n_try,
            env,
            custom_task_module,
            pre_script_hook,
            files,
        )
        cluster_tasks = _setup_cluster_spec(task_specs, kv)
        handle = backend.launch(services, log_dir)
    except Exception:
        server.stop()
        raise
    cluster = SliceCluster(
        server=server,
        kv=kv,
        handle=handle,
        cluster_tasks=cluster_tasks,
        log_dir=log_dir,
    )
    cluster.event_listener = _start_event_listener(cluster)
    return cluster


def _execute_and_await_termination(
    cluster: SliceCluster,
    serialized_fn: bytes,
    n_try: int,
    poll_every_secs: float,
    eval_monitor_log_thresholds: Optional[Dict[str, tuple]] = None,
    deadline: Optional[Deadline] = None,
    dead_task_secs: Optional[float] = None,
) -> Metrics:
    """Post the experiment, poll to completion, fold events into Metrics
    (reference: client.py:527-631).

    `deadline` is the run's ONE monotonic budget, shared across retries
    (created once in run_on_tpu — recomputing it per attempt let
    nb_retries=3 run 4x the requested timeout). `dead_task_secs` arms the
    heartbeat watchdog: a task that beat once and then went silent that
    long fails the attempt as LOST_TASK within a poll interval, instead
    of hanging until the deadline."""
    cluster.kv.put(constants.KV_EXPERIMENT_FN, serialized_fn)

    evaluator_logger = EvaluatorMetricsLogger(
        [t for t in cluster.handle.tasks() if t.type == "evaluator"],
        cluster.kv,
        n_try=n_try,
        log_thresholds=eval_monitor_log_thresholds,
    )
    from tf_yarn_tpu.utils.tensorboard_utils import url_event_name

    tb_url_logger = OneShotMetricsLogger(
        cluster.kv,
        [
            (url_event_name(key.to_kv_str()), "tensorboard URL")
            for key in cluster.handle.tasks()
            if key.type == "tensorboard"
        ]
        # Serving replicas advertise their HTTP endpoint the same way
        # (tf_yarn_tpu.serving): surface each once in the driver log.
        + [
            (
                event.serving_endpoint_event_name(key.to_kv_str()),
                "serving endpoint",
            )
            for key in cluster.handle.tasks()
            if key.type == "serving"
        ]
        # Ranking replicas likewise (tf_yarn_tpu.ranking) — distinct
        # key suffix, because it doubles as the capability declaration
        # the fleet registry reads.
        + [
            (
                event.rank_endpoint_event_name(key.to_kv_str()),
                "rank endpoint",
            )
            for key in cluster.handle.tasks()
            if key.type == "rank"
        ]
        # And the fleet router's — the one endpoint clients dial in a
        # fleet topology (tf_yarn_tpu.fleet).
        + [
            (
                event.router_endpoint_event_name(key.to_kv_str()),
                "router endpoint",
            )
            for key in cluster.handle.tasks()
            if key.type == "router"
        ],
        n_try,
    )

    watchdog = None
    if dead_task_secs:
        watchdog = HeartbeatWatchdog(
            cluster.kv, cluster.cluster_tasks, dead_task_secs
        )
    status = RUNNING
    lost_tasks: List[str] = []
    while status == RUNNING:
        time.sleep(poll_every_secs)
        status = cluster.handle.status()
        evaluator_logger.log()
        tb_url_logger.log()
        if status != RUNNING:
            break
        if watchdog is not None:
            lost_tasks = watchdog.poll()
            if lost_tasks:
                # Wedged-but-alive worker (host gone, partition, livelock):
                # fail the attempt in seconds as LOST_TASK instead of
                # burning the rest of the budget waiting on the deadline.
                _logger.error(
                    "heartbeat watchdog: %s silent > %.0fs; killing attempt",
                    lost_tasks, dead_task_secs,
                )
                telemetry.get_registry().counter(
                    "driver/lost_tasks_total"
                ).inc(len(lost_tasks))
                cluster.handle.kill()
                status = KILLED
                break
        if deadline is not None and deadline.expired():
            # Hung cluster (deadlocked collective, stuck host): kill it so
            # the retry loop / caller gets control back.
            _logger.error(
                "run exceeded its %.0fs global budget; killing",
                deadline.seconds,
            )
            cluster.handle.kill()
            status = KILLED
            break

    if hasattr(cluster.handle, "reap_sidecars"):
        cluster.handle.reap_sidecars()
    if cluster.event_listener is not None:
        cluster.event_listener.join(timeout=5.0)

    all_tasks = [key.to_kv_str() for key in cluster.handle.tasks()]
    metrics, outcomes = handle_events(cluster.kv, all_tasks)
    _log_run_outcome(cluster, status, outcomes)
    metrics.log_mlflow(n_try)

    # Only training tasks gate run success; a misconfigured side-car must
    # not turn a finished run into a failure (backends.PRIMARY_TASK_TYPES).
    failures = {
        t: o
        for t, o in outcomes.items()
        if o.status == "FAILED" and t.split(":", 1)[0] in PRIMARY_TASK_TYPES
    }
    if failures:
        _print_failed_task_logs(cluster, failures)
    sidecar_failures = {
        t: o
        for t, o in outcomes.items()
        if o.status == "FAILED" and t not in failures
    }
    for task, outcome in sidecar_failures.items():
        _logger.warning(
            "side-car %s failed (run not affected): %s",
            task,
            outcome.exception.strip().splitlines()[-1],
        )
    if status != "SUCCEEDED" or failures:
        kind = _attempt_kind(outcomes, failures, lost_tasks)
        details = "\n".join(
            f"{task}: {outcome.exception}" for task, outcome in failures.items()
        )
        if lost_tasks:
            details = (
                f"heartbeat-silent tasks declared lost: {lost_tasks}\n"
                + details
            )
        raise RunFailed(
            f"run final status {status} (classified {kind.value}); "
            f"failed tasks: {sorted(failures) or 'none reported'}\n{details}",
            kind=kind,
            lost_tasks=_lost_primaries(outcomes, lost_tasks),
        )
    return metrics


def _lost_primaries(
    outcomes: Dict[str, TaskOutcome], lost_tasks: List[str]
) -> List[str]:
    """Primary tasks that died without a lifecycle close — what the
    elastic resize path sizes the shrink off. When the watchdog fired,
    its heartbeat-silent set is the PRECISE answer (the driver's
    subsequent handle.kill() leaves every wedged survivor looking
    equally stop-event-less); otherwise the attempt died organically and
    the started-but-never-stopped primaries are exactly the silent
    deaths (SIGKILL, host gone)."""
    if lost_tasks:
        return sorted(set(lost_tasks))
    return sorted(
        task
        for task, outcome in outcomes.items()
        if outcome.status == "KILLED"
        and task.split(":", 1)[0] in PRIMARY_TASK_TYPES
    )


def _attempt_kind(
    outcomes: Dict[str, TaskOutcome],
    failures: Dict[str, TaskOutcome],
    lost_tasks: List[str],
) -> FailureKind:
    """Fold per-task failure kinds into the attempt's dominant kind (the
    retry policy's input): FATAL_USER anywhere beats everything (a
    relaunch reproduces it), a preemption explains collateral losses on
    the same slice, and primaries killed without a stop event are lost
    tasks — counted even when OTHER tasks did report failures, because a
    surviving worker's collateral crash (its collective peer vanished,
    so it dies with a ConnectionError classified TRANSIENT) must not
    mask the lost host that caused it."""
    kinds = [FailureKind.LOST_TASK] * bool(lost_tasks)
    kinds.extend(
        outcome.kind or FailureKind.TRANSIENT for outcome in failures.values()
    )
    kinds.extend(
        FailureKind.LOST_TASK
        for task, outcome in outcomes.items()
        if outcome.status == "KILLED"
        and task.split(":", 1)[0] in PRIMARY_TASK_TYPES
    )
    return resilience.worst(kinds) or FailureKind.TRANSIENT


def _print_failed_task_logs(
    cluster: SliceCluster, failures: Dict[str, TaskOutcome], tail_lines: int = 25
) -> None:
    """Surface the tail of each failed task's log in the driver output —
    the role of the reference's end-of-run log collection
    (`_get_app_logs`, client.py:748-763)."""
    logs = cluster.handle.logs()
    for task in sorted(failures):
        path = logs.get(task)
        if not path or not os.path.exists(path):
            continue
        try:
            from collections import deque

            with open(path, "r", errors="replace") as fh:
                tail = list(deque(fh, maxlen=tail_lines))  # O(tail) memory
        except OSError:
            continue
        _logger.error(
            "---- last %d log lines of failed %s (%s) ----\n%s",
            len(tail), task, path, "".join(tail).rstrip(),
        )


def _log_run_outcome(
    cluster: SliceCluster, status: str, outcomes: Dict[str, TaskOutcome]
) -> None:
    """Print per-task outcome + log locations, archive to MLflow (reference:
    client.py:577-589 log harvest + 605-617 `_save_logs_to_mlflow`)."""
    logs = cluster.handle.logs()
    lines = [f"final status: {status}"]
    for task in sorted(outcomes):
        outcome = outcomes[task]
        lines.append(f"  {task}: {outcome.status}  logs: {logs.get(task, '?')}")
        if outcome.exception:
            lines.append(f"    {outcome.exception.strip().splitlines()[-1]}")
    summary = "\n".join(lines)
    _logger.info("%s", summary)
    mlflow.save_text_to_mlflow(summary, "tpu_yarn_run_outcome")


def run_on_tpu(
    experiment_fn: ExperimentFn,
    task_specs: Optional[TaskSpecs] = None,
    *,
    name: str = "tpu_yarn",
    backend: Optional[SliceBackend] = None,
    custom_task_module: Optional[str] = None,
    env: Optional[Dict[str, str]] = None,
    files: Optional[Dict[str, str]] = None,
    pre_script_hook: str = "",
    env_staging_dir: Optional[str] = None,
    ship_code: Optional[bool] = None,
    requirements=None,
    wheels_dir: Optional[str] = None,
    nb_retries: int = 0,
    retry_policy: Optional[RetryPolicy] = None,
    elastic_policy: Optional[
        Union[ElasticPolicy, Dict[str, ElasticPolicy]]
    ] = None,
    poll_every_secs: float = 0.5,
    timeout_secs: Optional[float] = None,
    dead_task_secs: Optional[float] = None,
    coordinator_bind: str = "127.0.0.1",
    coordinator_advertise: Optional[str] = None,
    eval_monitor_log_thresholds: Optional[Dict[str, tuple]] = None,
) -> Optional[Metrics]:
    """Run `experiment_fn` on a TPU slice (reference `run_on_yarn`,
    client.py:299-466 — but with classified, budgeted retries in place
    of its blind loop, client.py:431-466; docs/Resilience.md).

    Failure handling: each failed attempt is classified (TRANSIENT /
    PREEMPTED / LOST_TASK / FATAL_USER — `tf_yarn_tpu.resilience`) from
    the tasks' stop events. `nb_retries=N` grants N retries *per
    retryable kind* with exponential decorrelated-jitter backoff
    (preemptions relaunch immediately; deterministic user errors consume
    zero retries and raise at once). Pass `retry_policy` for explicit
    budgets/backoff. `timeout_secs` is ONE monotonic budget over the
    whole run, retries included. `dead_task_secs` (default: the
    TPU_YARN_DEAD_TASK_SECS env) arms the heartbeat watchdog: a task
    heartbeat-silent that long fails the attempt as LOST_TASK within a
    poll interval.

    Elastic resize (`elastic_policy=`, docs/Resilience.md "Elastic
    training"): with an :class:`~tf_yarn_tpu.resilience.ElasticPolicy`,
    a capacity failure (PREEMPTED / LOST_TASK) RESIZES the relaunch
    instead of re-requesting the full topology — the 'worker' task
    type's instance count shrinks to the surviving hosts (never below
    ``min_workers``), the train loop refits the declared mesh onto the
    devices the smaller attempt actually has and reshards the restored
    checkpoint onto it, and per-host input shares rescale so the global
    batch and the data order stay fixed. A later relaunch for any
    non-capacity kind grows back to ``max_workers``. Retries still come
    out of `retry_policy`'s budgets; the resize only changes WHAT
    relaunches. A dict ``{task_type: ElasticPolicy}`` resizes OTHER task
    types the same way — ``{"serving": ...}`` / ``{"rank": ...}`` is the
    relaunch actuator behind the fleet autoscaler (docs/Fleet.md
    "Autoscaling & self-healing"): a preempted replica relaunches on the
    surviving count, re-advertises its new endpoint, and the router's
    registry re-admits it. A bare policy means ``{"worker": policy}``.

    `experiment_fn` is a zero-arg closure returning one of the experiment
    types in `tf_yarn_tpu.experiment` (or, with the `distributed` task
    module, a function of local_rank). It is cloudpickled to every task;
    use :func:`get_safe_experiment_fn` when the closure must not capture
    the driver's module state.

    Environment shipping (the reference always ships the interpreter env,
    client.py:421-424): with a remote backend the project code travels to
    every worker automatically — via `packaging.ship_env` staged on
    `env_staging_dir` when given (a URI every worker can read: gs://,
    hdfs://, an NFS path), else streamed over the backend's own file
    channel (`packaging.ship_files`, no shared filesystem needed). Workers
    need only a bare interpreter + the deps baked into the TPU VM image.
    `ship_code=False` opts out (code pre-provisioned via `remote_prefix`);
    `ship_code=True` forces shipping even on a local backend.

    Third-party deps absent from the TPU VM image travel too (the
    reference pex-ships the whole interpreter env, client.py:421-424):
    `requirements` (pip specs or a requirements.txt path) resolves
    driver-side into a wheelhouse — staged next to the code zips, or
    streamed over the file channel — that workers `pip install
    --no-index` before unpickling the experiment. `wheels_dir` supplies
    pre-downloaded wheels instead of `pip download` (air-gapped
    drivers). Without either, a missing import fails fast on the worker
    naming the module. A driver whose OS/CPython differs from the TPU
    VM image should pre-resolve with
    `packaging.build_wheelhouse(platform=..., python_version=...)` and
    pass the result as `wheels_dir`.
    """
    task_specs = dict(task_specs) if task_specs else single_server_topology()
    check_topology(task_specs)
    backend = backend or LocalBackend()
    if getattr(backend, "is_remote", True) and coordinator_bind == "127.0.0.1":
        # Remote tasks must be able to dial in: listen on every interface
        # and advertise a routable address (ADVICE r1).
        coordinator_bind = "0.0.0.0"
    env = dict(env or {})
    files = dict(files or {})
    if ship_code is None:
        ship_code = getattr(backend, "is_remote", True)
    if (requirements is not None or wheels_dir is not None) and not ship_code:
        raise ValueError(
            "requirements=/wheels_dir= travel with the shipped env; "
            "they have no effect with ship_code=False"
        )
    if ship_code:
        from tf_yarn_tpu import packaging

        if env_staging_dir is not None:
            ship_hook = packaging.ship_env(
                env_staging_dir, requirements=requirements,
                wheels_dir=wheels_dir,
                # Install wheels under the interpreter that will run the
                # task, so pip's compatibility tags match it.
                python=getattr(backend, "python", None) or "python3",
            )
            pre_script_hook = (
                f"{ship_hook} && {pre_script_hook}" if pre_script_hook
                else ship_hook
            )
        else:
            ship_entries = packaging.ship_files(
                requirements=requirements, wheels_dir=wheels_dir)
            for ship_name, ship_src in ship_entries.items():
                files.setdefault(ship_name, ship_src)
    serialized_fn = cloudpickle.dumps(experiment_fn)

    policy = retry_policy or RetryPolicy.from_nb_retries(nb_retries)
    elastic_policies = _normalize_elastic(elastic_policy, task_specs)
    current_counts = {
        task_type: task_specs[task_type].instances
        for task_type in elastic_policies
    }
    # ONE monotonic budget for the whole run: created before the first
    # attempt, never recomputed (the old per-attempt time.time() deadline
    # let nb_retries=3 run 4x timeout_secs, and NTP steps could stretch
    # any attempt).
    deadline = Deadline.after(timeout_secs)
    if dead_task_secs is None:
        dead_task_secs = resilience.dead_task_secs_from_env()

    n_try = 0
    while True:
        cluster: Optional[SliceCluster] = None
        try:
            cluster = _setup_cluster(
                task_specs,
                backend,
                n_try,
                env,
                custom_task_module,
                pre_script_hook,
                name,
                coordinator_bind,
                files,
                coordinator_advertise,
            )
            return _execute_and_await_termination(
                cluster,
                serialized_fn,
                n_try,
                poll_every_secs,
                eval_monitor_log_thresholds,
                deadline,
                dead_task_secs,
            )
        except KeyboardInterrupt:
            _shutdown_on_exception(cluster, KILLED)
            raise
        except Exception as exc:
            _shutdown_on_exception(cluster, FAILED)
            kind = (
                exc.kind
                if isinstance(exc, RunFailed) and exc.kind is not None
                # Driver-side failures (cluster setup, coordination):
                # classified from the exception itself.
                else resilience.classify_exception(exc)
            )
            delay = policy.next_delay(kind)
            if delay is None:
                _logger.error(
                    "attempt %d failed (%s); not retrying (budget for "
                    "%s: %d, spent: %d)", n_try, kind.value, kind.value,
                    policy.budgets.get(kind, 0), policy.spent(kind),
                )
                raise
            if deadline is not None and deadline.remaining() <= delay:
                _logger.error(
                    "attempt %d failed (%s) but the global %.0fs budget "
                    "is exhausted; not retrying", n_try, kind.value,
                    deadline.seconds,
                )
                raise
            _logger.exception(
                "run attempt %d failed (%s); retrying in %.1fs",
                n_try, kind.value, delay,
            )
            telemetry.get_registry().counter(
                "driver/retries_total", kind=kind.value
            ).inc()
            _note_lost_to_backend(backend, exc)
            for task_type, type_policy in elastic_policies.items():
                # Resize-not-retry: a capacity failure relaunches the
                # elastic task types on the surviving hosts instead of
                # blocking on full capacity; any other retryable failure
                # is the moment to grow back. Each elastic type resizes
                # independently — a lost serving replica must not shrink
                # the worker pool.
                lost_count = sum(
                    1
                    for task in getattr(exc, "lost_tasks", None) or []
                    if task.split(":", 1)[0] == task_type
                )
                new_count = type_policy.plan_resize(
                    kind, current_counts[task_type], lost_tasks=lost_count
                )
                if new_count is None:
                    continue
                direction = (
                    "shrink" if new_count < current_counts[task_type]
                    else "grow"
                )
                _logger.warning(
                    "elastic resize (%s): relaunching with %d %s tasks "
                    "(was %d) after %s",
                    direction, new_count, task_type,
                    current_counts[task_type], kind.value,
                )
                telemetry.get_registry().counter(
                    "driver/elastic_resizes_total", direction=direction
                ).inc()
                current_counts[task_type] = new_count
                task_specs = dict(task_specs)
                task_specs[task_type] = dataclasses.replace(
                    task_specs[task_type], instances=new_count
                )
                env = dict(env)
                count_var, max_var = constants.elastic_env_vars(task_type)
                env[count_var] = str(new_count)
                env[max_var] = str(type_policy.max_workers)
            if delay:
                time.sleep(delay)
            n_try += 1
            continue
        finally:
            if cluster is not None:
                try:
                    cluster.server.stop()
                except Exception:  # pragma: no cover - best-effort teardown
                    _logger.debug("coordination server stop failed",
                                  exc_info=True)


def _normalize_elastic(
    elastic_policy, task_specs
) -> Dict[str, ElasticPolicy]:
    """The elastic band(s) as ``{task_type: ElasticPolicy}``, validated
    against the topology. A bare policy keeps PR 8's worker-only
    surface (-> ``{"worker": policy}``); a dict makes any task type
    elastic — ``serving`` / ``rank`` replica pools for the fleet
    autoscaler's relaunch path. Raises ValueError on a type missing
    from the topology or an initial count outside its band."""
    if elastic_policy is None:
        return {}
    if isinstance(elastic_policy, ElasticPolicy):
        policies = {"worker": elastic_policy}
    elif isinstance(elastic_policy, dict):
        policies = dict(elastic_policy)
    else:
        raise ValueError(
            "elastic_policy must be an ElasticPolicy or a "
            f"{{task_type: ElasticPolicy}} dict, got {elastic_policy!r}"
        )
    for task_type, type_policy in policies.items():
        if not isinstance(type_policy, ElasticPolicy):
            raise ValueError(
                f"elastic_policy[{task_type!r}] must be an ElasticPolicy, "
                f"got {type_policy!r}"
            )
        if task_type not in task_specs \
                or task_specs[task_type].instances < 1:
            raise ValueError(
                f"elastic_policy resizes the {task_type!r} task type; "
                f"the topology needs a {task_type!r} spec with instances "
                ">= 1 (chief and side-cars are never resized)"
            )
        count = task_specs[task_type].instances
        if not (
            type_policy.min_workers <= count <= type_policy.max_workers
        ):
            raise ValueError(
                f"initial {task_type} count {count} outside the "
                f"elastic band [{type_policy.min_workers}, "
                f"{type_policy.max_workers}]"
            )
    return policies


def _note_lost_to_backend(backend, exc: Exception) -> None:
    """Feed the failed attempt's lost tasks (SIGKILLed / heartbeat-
    silent, carried on RunFailed.lost_tasks) back to the backend before
    the relaunch, so host-placing backends (SshBackend) can blacklist
    the dead machines from the next attempt's host list. Best-effort:
    placement hygiene must never turn a retryable failure fatal."""
    lost = getattr(exc, "lost_tasks", None) or []
    note = getattr(backend, "note_lost_tasks", None)
    if not lost or note is None:
        return
    try:
        note(list(lost))
    except Exception:  # pragma: no cover - diagnostics only
        _logger.exception("backend.note_lost_tasks failed; continuing")


def _shutdown_on_exception(cluster: Optional[SliceCluster], status: str) -> None:
    """Kill outstanding tasks on driver exception / Ctrl-C (reference:
    `_shutdown_on_exception`, client.py:508-524)."""
    if cluster is None:
        return
    try:
        if cluster.handle.status() == RUNNING:
            _logger.warning("shutting down run as %s", status)
            cluster.handle.kill()
    except Exception:  # pragma: no cover - best-effort teardown
        _logger.exception("error during shutdown")


def get_safe_experiment_fn(full_fn_name: str, *args) -> ExperimentFn:
    """Reference the experiment function by module path so the pickle holds
    no driver-env objects (reference: client.py:472-495)."""
    module_name, _, fn_name = full_fn_name.rpartition(".")
    if not module_name:
        raise ValueError(
            f"expected 'package.module.function', got {full_fn_name!r}"
        )

    def _load_and_call(module_name: str, fn_name: str, *inner_args):
        module = importlib.import_module(module_name)
        return getattr(module, fn_name)(*inner_args)

    return partial(_load_and_call, module_name, fn_name, *args)
