"""The pjit train-and-evaluate loop — the framework's data plane.

This replaces *all three* of the reference's training data planes
(SURVEY.md §2.5): ParameterServerStrategy gRPC (tensorflow/cluster.py:
53-67), Horovod/Gloo rings (gloo_allred_task.py), and DDP/NCCL
(pytorch/tasks/worker.py) — with one compiled XLA program over a named
device mesh. Gradients never leave the step function: the sharded loss →
grad → update chain is jitted once, and XLA inserts the ICI collectives
(allreduce over dp, reduce-scatter/all-gather over fsdp, etc.) that the
shardings imply.

TPU-first design points:
* Everything hot is inside one `jax.jit` with donated state (no
  host↔device ping-pong per step; HBM re-use for the optimizer update).
* Static shapes: the input pipeline must yield fixed-shape batches
  (drop-last semantics; the compile-shape hazard the reference only
  warns about, pytorch/experiment.py:10-15, is enforced here).
* Batches land sharded via `jax.make_array_from_process_local_data`, so
  the same loop serves single-process and multi-host runs.
* bfloat16 matmuls are the model's concern (the zoo defaults to bf16
  compute / f32 params); the loop is dtype-agnostic.
"""

from __future__ import annotations

import contextlib
import logging
import os
import time
from typing import Any, Dict, NamedTuple, Optional

import jax
import numpy as np
import optax

from tf_yarn_tpu import checkpoint as ckpt_lib
from tf_yarn_tpu import (
    constants,
    event,
    fs as fs_lib,
    preemption,
    resilience,
    telemetry,
)
from tf_yarn_tpu.experiment import CoreExperiment
from tf_yarn_tpu.parallel import mesh as mesh_lib
from tf_yarn_tpu.parallel import sharding as sharding_lib
from tf_yarn_tpu.utils import flops as flops_lib
from tf_yarn_tpu.utils import mlflow

_logger = logging.getLogger(__name__)


class TrainState(NamedTuple):
    """Minimal train state; a plain pytree so sharding specs apply leaf-wise."""

    step: jax.Array
    params: Any
    opt_state: Any


def _default_init_fn(model):
    def init_fn(rng, batch):
        features = {k: v for k, v in batch.items() if k != "y"}
        if len(features) == 1:
            return model.init(rng, next(iter(features.values())))
        return model.init(rng, **features)

    return init_fn


def _named_shardings(mesh, abstract_tree):
    return sharding_lib.tree_shardings(mesh, abstract_tree)


def make_batch_globalizer(mesh):
    """Return fn placing a host-local numpy batch as a global sharded array.

    In multi-host runs each process feeds its local slice of the global
    batch; single-process runs feed the whole thing. `
    make_array_from_process_local_data` handles both layouts.
    """
    shardings_by_ndim: Dict[int, jax.sharding.NamedSharding] = {}

    def globalize(batch: Dict[str, np.ndarray]):
        # Spanned + histogrammed, not in the interval breakdown: with the
        # prefetch pipeline this runs on the producer thread, overlapped
        # with device compute — its cost is real but not wall-serial.
        with telemetry.span("train/globalize") as sp:
            out = {}
            for key, value in batch.items():
                value = np.asarray(value)
                shard = shardings_by_ndim.get(value.ndim)
                if shard is None:
                    shard = mesh_lib.batch_sharding(
                        mesh, extra_batch_dims=value.ndim - 1
                    )
                    shardings_by_ndim[value.ndim] = shard
                out[key] = jax.make_array_from_process_local_data(shard, value)
        telemetry.get_registry().histogram(
            "train/globalize_seconds"
        ).observe(sp.duration)
        return out

    return globalize


def _loss_caller(loss_fn):
    """Normalize the loss contract to (model, params, batch, rng, train=...).

    Zoo losses take `train` and flip dropout off for evaluation; 4-arg
    user losses keep working (train is dropped)."""
    import inspect

    try:
        accepts_train = "train" in inspect.signature(loss_fn).parameters
    except (TypeError, ValueError):  # builtins / partials without signature
        accepts_train = False
    if accepts_train:
        return loss_fn
    return lambda model, params, batch, rng, train=True: loss_fn(
        model, params, batch, rng
    )


def build_train_step(model, loss_fn, optimizer, grad_accum_steps: int = 1):
    loss_fn = _loss_caller(loss_fn)

    def _grads(params, batch, rng):
        return jax.value_and_grad(
            lambda p: loss_fn(model, p, batch, rng, train=True), has_aux=True
        )(params)

    def train_step(state: TrainState, batch, base_rng):
        rng = jax.random.fold_in(base_rng, state.step)
        if grad_accum_steps == 1:
            (loss, aux), grads = _grads(state.params, batch, rng)
        else:
            # Sequential microbatches inside the jitted step: scan keeps
            # one microbatch of activations live at a time; the averaged
            # gradient is mathematically the full-batch gradient.
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape(
                    grad_accum_steps, x.shape[0] // grad_accum_steps, *x.shape[1:]
                ),
                batch,
            )

            import operator

            import jax.numpy as jnp

            def body(carry, inp):
                micro_idx, micro_batch = inp
                grads_acc, loss_acc, aux_acc = carry
                # Independent dropout per microbatch (same rng would
                # correlate masks across the accumulation).
                (loss, aux), grads = _grads(
                    state.params, micro_batch, jax.random.fold_in(rng, micro_idx)
                )
                grads_acc = jax.tree_util.tree_map(operator.add, grads_acc, grads)
                aux_acc = jax.tree_util.tree_map(operator.add, aux_acc, aux)
                return (grads_acc, loss_acc + loss, aux_acc), None

            first = jax.tree_util.tree_map(lambda leaf: leaf[0], micro)
            (loss0, aux0), grads0 = _grads(
                state.params, first, jax.random.fold_in(rng, 0)
            )
            rest = jax.tree_util.tree_map(lambda leaf: leaf[1:], micro)
            (grads_sum, loss_sum, aux_sum), _ = jax.lax.scan(
                body, (grads0, loss0, aux0),
                (jnp.arange(1, grad_accum_steps), rest),
            )
            scale = 1.0 / grad_accum_steps
            grads = jax.tree_util.tree_map(lambda g: g * scale, grads_sum)
            loss = loss_sum * scale
            aux = jax.tree_util.tree_map(lambda a: a * scale, aux_sum)
            if "perplexity" in aux:
                # exp(mean) not mean(exp): keep perplexity consistent with
                # the accum=1 path (Jensen gap otherwise).
                aux["perplexity"] = jnp.exp(loss)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, **aux}
        return TrainState(state.step + 1, params, opt_state), metrics

    return train_step


def build_eval_step(model, loss_fn):
    loss_fn = _loss_caller(loss_fn)

    def eval_step(state: TrainState, batch, base_rng):
        loss, aux = loss_fn(model, state.params, batch, base_rng, train=False)
        return {"loss": loss, **aux}

    return eval_step


class _IntervalBreakdown:
    """Host-side step-time attribution over one report interval.

    The main loop thread accumulates named components (input_wait,
    step_dispatch, device_wait, checkpoint_save, eval) between hook
    reports; `report()` closes the interval, attributing whatever the
    components didn't cover to ``host_other`` (preemption polls,
    profiler toggles, loop bookkeeping) so the parts always sum to the
    interval wall time — the MLPerf-style attribution that turns
    "steps/sec dropped" into "input wait grew 40%"."""

    def __init__(self, clock=None) -> None:
        self._clock = clock or time.perf_counter
        self._acc: Dict[str, float] = {}
        self._t_start = self._clock()

    def add(self, component: str, seconds: float) -> None:
        self._acc[component] = self._acc.get(component, 0.0) + seconds

    def report(self) -> Dict[str, float]:
        """Close the interval: components + host_other + interval_wall."""
        now = self._clock()
        wall = max(now - self._t_start, 1e-9)
        parts = dict(self._acc)
        parts["host_other"] = max(0.0, wall - sum(parts.values()))
        parts["interval_wall"] = wall
        self._acc = {}
        self._t_start = now
        return parts


class _StepsPerSecondHook:
    """Chief-only throughput reporting (reference StepPerSecondHook,
    tensorflow/metrics.py:18-38): KV broadcast + MLflow + log, now built
    on the telemetry metrics registry (every report lands in process-
    global gauges under ``train/*`` and the whole registry snapshot is
    flushed to the log/MLflow/KV on the same cadence).

    Beyond the reference's steps/sec, every report carries samples/sec,
    tokens/sec (sequence batches) and **MFU** when the XLA cost analysis
    and chip peak are known — so every run, not just bench.py, records
    how much of the hardware it used.

    Timing uses a monotonic clock (perf_counter): the old wall-clock
    ``time.time()`` deltas were corrupted by NTP steps, skewing
    steps/sec and everything derived from it (tokens/sec, MFU)."""

    def __init__(self, runtime, every: int, n_try: int = 0,
                 resume_step: int = 0, flops_per_step: Optional[float] = None,
                 samples_per_step: Optional[int] = None,
                 tokens_per_step: Optional[int] = None,
                 peak_flops: Optional[float] = None,
                 clock=None) -> None:
        self._runtime = runtime
        self._every = max(1, every)
        self._n_try = n_try
        self._clock = clock or time.perf_counter
        self._t0 = self._clock()
        # Start counting at the resume step, or the first report after a
        # checkpoint restore would be inflated by resume_step/elapsed.
        self._step0 = resume_step
        self._flops_per_step = flops_per_step
        self._samples_per_step = samples_per_step
        self._tokens_per_step = tokens_per_step
        self._peak_flops = peak_flops
        self._interval_samples = 0

    def record_batch(self, n_samples: Optional[int]) -> None:
        """Count the actual batch size of a step, so intervals containing
        ragged (epoch-tail) batches report true samples/tokens/MFU rather
        than full-batch assumptions."""
        self._interval_samples += (
            n_samples if n_samples is not None else (self._samples_per_step or 0)
        )

    def after_step(self, step: int, metrics: Dict[str, Any],
                   force: bool = False,
                   breakdown: Optional[Dict[str, float]] = None) -> None:
        if step % self._every != 0 and not force:
            return
        now = self._clock()
        elapsed = max(now - self._t0, 1e-9)
        n_steps = step - self._step0
        interval_samples = self._interval_samples
        self._t0, self._step0 = now, step
        self._interval_samples = 0
        loss = metrics.get("loss")
        report: Dict[str, float] = {}
        if n_steps > 0:
            steps_per_sec = n_steps / elapsed
            # Fraction of assumed-full work actually done this interval
            # (tokens and batch-dim FLOPs both scale with the sample
            # count).
            full = (self._samples_per_step or 0) * n_steps
            work_frac = (
                interval_samples / full
                if full and interval_samples
                else 1.0
            )
            report["steps_per_sec"] = steps_per_sec
            if self._samples_per_step:
                report["samples_per_sec"] = (
                    steps_per_sec * self._samples_per_step * work_frac
                )
            if self._tokens_per_step:
                report["tokens_per_sec"] = (
                    steps_per_sec * self._tokens_per_step * work_frac
                )
            mfu_value = flops_lib.mfu(
                self._flops_per_step, steps_per_sec * work_frac,
                self._peak_flops
            )
            if mfu_value is not None:
                report["mfu"] = mfu_value
        # else: a forced flush landed on an interval with zero completed
        # steps (e.g. final step coinciding with the last report) — every
        # rate would be 0/epsilon garbage, so rate metrics are skipped
        # entirely rather than reported as 0 to MLflow.
        registry = telemetry.get_registry()
        registry.counter("train/steps_total").inc(n_steps)
        if interval_samples:
            registry.counter("train/samples_total").inc(interval_samples)
        for key, value in report.items():
            registry.gauge(f"train/{key}").set(value)
        if breakdown:
            for component, seconds in breakdown.items():
                registry.gauge(
                    "train/interval_seconds", component=component
                ).set(seconds)
        _logger.info(
            "step %d: loss=%s %s", step, loss,
            " ".join(f"{k}={v:.3f}" for k, v in report.items()),
        )
        for key, value in report.items():
            mlflow.log_metric(f"{key}_{self._n_try}", value, step=step)
        if self._runtime is not None:
            for key, value in report.items():
                event.broadcast(
                    self._runtime.kv,
                    f"{self._runtime.task}/{key}",
                    f"{value:.6g}",
                )
            event.broadcast(
                self._runtime.kv, f"{self._runtime.task}/last_training_step", str(step)
            )
        # Registry snapshot → log (debug) + MLflow + one {task}/metrics
        # KV payload, chief-aggregated like last_training_step.
        telemetry.flush_metrics(
            registry, step=step,
            kv=self._runtime.kv if self._runtime is not None else None,
            task=self._runtime.task if self._runtime is not None else None,
        )


def _preempt_agreed(state) -> bool:
    """Whether ALL hosts should drain now. SIGTERM delivery is per-host
    and skewed; a host draining alone would start a multi-host checkpoint
    save (a collective) its peers never join — deadlock until the grace
    window's SIGKILL. Every host calls this on the same step cadence
    (`drain_poll_every`; the SPMD loop keeps step counters in lockstep),
    so the allgather is safe and the max makes one host's flag everyone's
    decision.

    The block_until_ready is load-bearing: dispatched train steps are
    async, and posting the host-side allgather while a step's own
    collectives are still in flight interleaves two collectives on one
    Gloo/ICI channel — the peers then see mismatched op sequences
    ("Received data size doesn't match expected size"). Draining local
    dispatch first makes every process's per-channel order
    [steps..., allgather], identically.

    The guards short-circuiting this call (input_exhausted,
    step < train_steps) are host-uniform by the same SPMD contract the
    train step's own collectives already depend on: equal per-host batch
    counts and one shared train_steps. A host whose stream ran short
    would desynchronize the *training* collectives regardless of this
    check — uneven shards must be evened by the input pipeline
    (drop-last semantics, as data/parquet.py does)."""
    import jax

    if jax.process_count() == 1:
        return preemption.requested()
    from jax.experimental import multihost_utils

    jax.block_until_ready(state)
    flags = multihost_utils.process_allgather(
        np.int32(preemption.requested())
    )
    return bool(np.max(flags))


def _make_input_iter(input_fn, start_step: int, logger):
    """Build the train iterator, passing `start_step` to input_fns that
    declare it (opt-in input resume — the role tf.data checkpointing
    plays for the reference's Estimator input_fns).

    Two further opt-in keywords, `host_index` / `num_hosts`, receive this
    process's slot in the current world: an input_fn that declares them
    yields its CONTIGUOUS 1/num_hosts share of a fixed global batch
    (rows [host_index*B/num_hosts : (host_index+1)*B/num_hosts] — the
    layout `make_array_from_process_local_data` assembles). When an
    elastic resize changes the host count, each survivor's share
    rescales while the global batch size and the data order stay fixed
    — the determinism contract of docs/Resilience.md "Elastic
    training"."""
    import inspect

    try:
        params = inspect.signature(input_fn).parameters
    except (TypeError, ValueError):
        params = {}
    kwargs = {}
    if "start_step" in params:
        kwargs["start_step"] = start_step
    elif start_step:
        logger.info(
            "input_fn takes no start_step: input restarts from the "
            "beginning at resume step %d (declare start_step to skip "
            "already-consumed data)", start_step,
        )
    if "host_index" in params:
        kwargs["host_index"] = jax.process_index()
    if "num_hosts" in params:
        kwargs["num_hosts"] = jax.process_count()
    return iter(input_fn(**kwargs))


class _ProfileWindow:
    """jax.profiler capture controlled by env:

    * ``TPU_YARN_PROFILE=<dir>`` — capture a trace into <dir>. Whole run
      by default (the round-2 behavior).
    * ``TPU_YARN_PROFILE_STEPS="A:B"`` — capture only steps [A, B), so a
      long job's trace stays downloadable/readable (a 50k-step run's
      full trace is gigabytes). Either bound may be empty ("100:" =
      from 100 to the end). The train loop treats the window edges as
      host boundaries, so steps_per_loop chunks never step over them —
      the captured range is exact.

    ``on_step(next_step)`` is called before the loop and after every
    step advance; start/stop happen there and in the loop's cleanup.
    """

    def __init__(self):
        self.dir = os.environ.get(telemetry.profile.PROFILE_ENV)
        self.start_step = 0
        self.stop_step = None
        self.active = False
        window = os.environ.get("TPU_YARN_PROFILE_STEPS", "")
        if window:
            start, _, stop = window.partition(":")
            try:
                # Parse both bounds BEFORE assigning either: a typo in
                # one must not leave a half-applied window after the
                # "ignoring" warning.
                parsed_start = int(start) if start else 0
                parsed_stop = int(stop) if stop else None
            except ValueError:
                _logger.warning(
                    "ignoring malformed TPU_YARN_PROFILE_STEPS=%r "
                    "(want 'A:B', e.g. '100:110')", window)
            else:
                if parsed_stop is not None and parsed_stop <= parsed_start:
                    # An inverted/empty window selects no steps: the old
                    # behavior accepted it silently and never captured.
                    # Same posture as a malformed window: warn, capture
                    # the whole run.
                    _logger.warning(
                        "ignoring TPU_YARN_PROFILE_STEPS=%r: stop_step "
                        "(%d) <= start_step (%d) selects no steps; "
                        "capturing the whole run instead",
                        window, parsed_stop, parsed_start)
                else:
                    self.start_step = parsed_start
                    self.stop_step = parsed_stop

    def boundaries(self):
        """Absolute steps where capture toggles — the train loop keeps
        steps_per_loop chunks from crossing them, so a window strictly
        inside a chunk can't be silently skipped."""
        if not self.dir:
            return ()
        return tuple(
            b for b in (self.start_step, self.stop_step)
            if b is not None and b > 0
        )

    def on_step(self, next_step: int, state=None) -> None:
        if not self.dir:
            return
        in_window = next_step >= self.start_step and (
            self.stop_step is None or next_step < self.stop_step)
        if in_window and not self.active:
            telemetry.profile.start(self.dir, python_tracer=True)
            self.active = True
            _logger.info("profiler capture started at step %d", next_step)
        elif self.active and not in_window:
            self.stop(state)

    def stop(self, state=None) -> None:
        if not self.active:
            return
        if state is not None:
            # Flush in-flight device work so the trace covers it.
            jax.block_until_ready(state.params)
        telemetry.profile.stop()
        self.active = False


class _UploadingTbWriter:
    """SummaryWriter against a remote model_dir: write event files to a
    local spool, upload the tree incrementally at checkpoint boundaries
    and finally on close (the reference's TB-logs-to-fs pattern,
    pytorch/tasks/worker.py:145-152). Everything except the upload
    lifecycle delegates to the wrapped writer, so user hooks holding the
    writer can call add_histogram/add_text/... unchanged."""

    def __init__(self, writer, spool_dir: str, target_uri: str):
        self._writer = writer
        self._spool_dir = spool_dir
        self._target_uri = target_uri
        self._closed = False

    def __getattr__(self, name):
        # Only reached when normal lookup fails — i.e. every SummaryWriter
        # method we don't wrap (add_histogram, add_text, flush, ...).
        return getattr(self._writer, name)

    def upload(self):
        """Push the spool to the remote dir now. Called at checkpoint
        boundaries so a SIGKILL costs at most one checkpoint interval of
        TB events, not the whole run. Event files are append-only, so
        re-copying the tree is idempotent."""
        self._writer.flush()
        try:
            fs_lib.upload_dir(self._spool_dir, self._target_uri)
        except Exception:
            _logger.exception("TB log upload to %s failed", self._target_uri)

    def close(self):
        if self._closed:
            return
        self._closed = True
        self._writer.close()
        try:
            fs_lib.upload_dir(self._spool_dir, self._target_uri)
        except Exception:
            _logger.exception("TB log upload to %s failed", self._target_uri)


def _make_tb_writer(model_dir: Optional[str]):
    if not model_dir:
        return None
    try:
        from torch.utils.tensorboard import SummaryWriter

        if fs_lib.is_local(model_dir):
            return SummaryWriter(log_dir=f"{fs_lib.local_path(model_dir)}/tb")
        import tempfile

        spool = tempfile.mkdtemp(prefix="tpu-yarn-tb-")
        return _UploadingTbWriter(
            SummaryWriter(log_dir=spool), spool, fs_lib.join(model_dir, "tb")
        )
    except Exception:  # tensorboard optional, as in the reference
        return None


def train_and_evaluate(
    core: CoreExperiment,
    runtime=None,
    devices=None,
) -> Dict[str, float]:
    """Run the full train/eval/checkpoint loop; returns final metrics.

    The driver-visible lifecycle (train_eval timer events, steps/sec
    broadcasts) matches the reference's `_execute_dispatched_function`
    surface (tf_task_common.py:38-74) so run Metrics keep working.
    """
    # The loop registers its mesh for the mesh-aware ops; it must not
    # outlive the run.
    with mesh_lib.use_mesh(mesh_lib.current_mesh()):
        return _train_and_evaluate(core, runtime, devices)


def _train_and_evaluate(core: CoreExperiment, runtime, devices):
    # Telemetry identity for this run: the launcher task when present
    # ("worker:0"), a stable local name otherwise. TPU_YARN_TRACE=<dir>
    # writes trace_<task>.json (Chrome trace_event) on exit — see
    # docs/Observability.md.
    telemetry_task = runtime.task if runtime is not None else "train"
    telemetry.enable_env_jsonl(telemetry_task)
    params_cfg = core.train_params
    mesh_spec = core.mesh_spec
    n_avail = (
        len(devices) if devices is not None else len(mesh_lib.select_devices())
    )
    declared_spec = mesh_spec
    elastic_resized = False
    if mesh_spec is None:
        mesh_spec = mesh_lib.MeshSpec.auto(n_avail)
    elif (
        os.environ.get(constants.ENV_ELASTIC_WORKERS)
        and mesh_spec.total_devices != n_avail
    ):
        # Elastic relaunch on resized capacity (docs/Resilience.md): the
        # experiment keeps declaring ONE logical mesh; this attempt owns
        # a different device count, so refit the data axes onto what is
        # actually here. Without the driver's elastic env the mismatch
        # still fails loudly below — a silently smaller mesh on a
        # non-elastic run would hide a broken reservation.
        mesh_spec = mesh_lib.resize_mesh_spec(mesh_spec, n_avail)
        elastic_resized = True
        _logger.warning(
            "elastic: declared mesh %s refit onto %d devices -> %s",
            declared_spec, n_avail, mesh_spec,
        )
    mesh = mesh_lib.build_mesh(mesh_spec, devices)
    mesh_lib.set_current_mesh(mesh)
    _logger.info(
        "mesh %s over %d devices", dict(zip(mesh.axis_names, mesh.devices.shape)),
        mesh.devices.size,
    )
    # Capacity gauges ride every registry flush (docs/Observability.md):
    # mesh_devices is the mesh this attempt computes on; degraded=1 says
    # an elastic resize is running below the full worker count.
    _degraded = 0.0
    if elastic_resized or os.environ.get(constants.ENV_ELASTIC_WORKERS):
        try:
            _degraded = float(
                int(os.environ.get(constants.ENV_ELASTIC_WORKERS, 0))
                < int(os.environ.get(constants.ENV_ELASTIC_MAX_WORKERS, 0))
            )
        except ValueError:
            _degraded = 0.0
    registry = telemetry.get_registry()
    registry.gauge("train/mesh_devices").set(float(mesh.devices.size))
    registry.gauge("train/degraded").set(_degraded)

    # Resume-aware input: discover the resume step BEFORE building the
    # iterator, and hand it to input_fns that opt in with a `start_step`
    # parameter so they can skip already-consumed data (the tf.data-
    # checkpoint role; the state restore itself happens under the mesh
    # below). Input_fns without the parameter restart from the beginning —
    # correct for stateless/synthetic streams, logged for the rest.
    input_resume_step = 0
    if core.model_dir:
        fs_lib.check_model_dir_placement(core.model_dir)
        # Verified discovery: a corrupt newest checkpoint is quarantined
        # HERE, before the input iterator is built, so the input-resume
        # step and the step restore_latest lands on below cannot diverge.
        input_resume_step = ckpt_lib.latest_verified_step(core.model_dir) or 0
    train_iter = _make_input_iter(
        core.train_input_fn, input_resume_step, _logger
    )
    with telemetry.span("train/first_batch"):
        first_batch = next(train_iter)
    init_fn = core.init_fn or _default_init_fn(core.model)
    rng = jax.random.PRNGKey(params_cfg.seed)
    init_rng, train_rng = jax.random.split(rng)

    globalize = make_batch_globalizer(mesh)
    first_global = globalize(first_batch)

    def init_state(init_rng, batch):
        variables = init_fn(init_rng, batch)
        params = sharding_lib.unbox_params(variables)
        opt_state = core.optimizer.init(params)
        return TrainState(np.int32(0), params, opt_state)

    def init_state_boxed(init_rng, batch):
        # Annotation-preserving twin of init_state: flax Partitioned boxes
        # are pytree nodes, so optax's zeros_like trees keep the boxes (and
        # their logical names) on every param-shaped optimizer slot.
        variables = init_fn(init_rng, batch)
        opt_state = core.optimizer.init(variables)
        return TrainState(np.int32(0), variables, opt_state)

    # Sharding decisions come from the boxed abstract state: annotated
    # leaves (params + matching optimizer slots) follow LOGICAL_RULES, the
    # rest gets FSDP inference / replication. Each box collapses to one
    # spec leaf, so the spec tree matches the *unboxed* runtime state.
    abstract_boxed = jax.eval_shape(init_state_boxed, init_rng, first_global)
    state_shardings = _named_shardings(mesh, abstract_boxed)

    # Param init runs OUTSIDE the ambient mesh context below: flax
    # unboxes Partitioned params inside `init` and, when a global mesh is
    # defined, emits sharding constraints with the raw logical names
    # ("embed", "mlp", ...) — which are not physical mesh axes here (our
    # LOGICAL_RULES translates them; sharding.unbox_params documents the
    # same hazard). Placement does not need the context either way: the
    # out_shardings below are explicit NamedShardings carrying the mesh.
    with telemetry.span("train/init"):
        init_jit = jax.jit(init_state, out_shardings=state_shardings)
        state = init_jit(init_rng, first_global)

    with mesh, contextlib.ExitStack() as _cleanup:
        # Registered first => runs last: the Chrome-trace export (no-op
        # without TPU_YARN_TRACE) sees every span, including the cleanup
        # callbacks', on success, crash and preemption paths alike.
        _cleanup.callback(telemetry.export_trace, telemetry_task)

        resume_step = 0
        ckpt_writer = None
        if core.model_dir:
            with telemetry.span("train/restore_latest"):
                restored, step = ckpt_lib.restore_latest(
                    core.model_dir, target=state
                )
            if restored is not None:
                # Orbax restores into `state`'s shardings (already the
                # THIS-attempt mesh); reshard_state re-places any leaf
                # that came back host-side or on a stale layout — the
                # bit-exact data movement an elastic resume relies on
                # (values never change, only placement). Targets are the
                # run's state_shardings (from the BOXED abstract state);
                # recomputing from the unboxed restore would lose the
                # logical-axis placements.
                state = sharding_lib.reshard_state(
                    restored, mesh,
                    old_spec=declared_spec if elastic_resized else None,
                    shardings=state_shardings,
                )
                resume_step = int(step)
                _logger.info("resumed from checkpoint step %d", resume_step)
            # Async writer: save() returns once the state is snapshotted to
            # host; serialization+commit overlap the next train steps.
            ckpt_writer = ckpt_lib.CheckpointWriter(params_cfg.keep_last_n)
            _cleanup.callback(ckpt_writer.close)

        step_fn_raw = build_train_step(
            core.model, core.loss_fn, core.optimizer,
            grad_accum_steps=params_cfg.grad_accum_steps,
        )
        train_step_jit = jax.jit(
            step_fn_raw,
            donate_argnums=(0,),
            out_shardings=(state_shardings, None),
        )
        # AOT-compile: the loop calls the compiled executable directly and
        # its XLA cost analysis prices one step for the MFU report.
        with telemetry.span("train/compile_train_step"):
            train_step = train_step_jit.lower(
                state, first_global, train_rng
            ).compile()

        # steps_per_loop > 1: a second executable scanning a whole block of
        # steps over stacked batches, so per-step dispatch amortizes away.
        steps_per_loop = max(1, params_cfg.steps_per_loop)
        # Cadences that actually surface to the host this run (mirrors the
        # trigger conditions in the loop below).
        host_cadences = [
            c for c in (
                params_cfg.log_every_steps,
                params_cfg.checkpoint_every_steps if core.model_dir else None,
                params_cfg.eval_every_steps if core.eval_input_fn else None,
            ) if c
        ]
        # Multi-host preemption agreement costs a pipeline drain + allgather
        # (see _preempt_agreed) — polling it every step defeats async
        # dispatch. Poll on a host-uniform cadence instead: the configured
        # knob, else the smallest host cadence (those boundaries already
        # surface to the host). Single-host keeps per-step flag checks
        # (they're a local read, and reaction time matters under SIGTERM).
        # Range validation lives in TrainParams.__post_init__ (fail at
        # construction, before restore/compile). With no configured knob
        # and no host cadences at all (log_every_steps=0, no model_dir,
        # no eval) there is no natural poll boundary — fall back to
        # polling every step rather than crash or never poll.
        if params_cfg.drain_poll_every_steps is not None:
            drain_poll_every = params_cfg.drain_poll_every_steps
        else:
            drain_poll_every = min(host_cadences, default=1)
        multi_host = jax.process_count() > 1
        if multi_host and drain_poll_every >= params_cfg.train_steps:
            _logger.warning(
                "drain_poll_every_steps=%d >= train_steps=%d: preemption "
                "is never polled mid-run; a SIGTERM will only be honored "
                "by the grace-window SIGKILL",
                drain_poll_every, params_cfg.train_steps,
            )
        if multi_host:
            # steps_per_loop chunking must also stop at drain boundaries,
            # or a chunk could step over the poll step entirely.
            host_cadences.append(drain_poll_every)
        if steps_per_loop > 1:
            # Chunks never cross host boundaries (nor the end of the run),
            # so a longer chunk would simply never execute while still
            # paying the largest compile of the run.
            cap = min(host_cadences
                      + [max(1, params_cfg.train_steps - resume_step)])
            if steps_per_loop > cap:
                _logger.warning(
                    "steps_per_loop=%d exceeds the smallest host cadence / "
                    "remaining steps (%d); clamping", steps_per_loop, cap,
                )
                steps_per_loop = cap
        multi_step = None
        stacked_shardings = None
        if steps_per_loop > 1:
            from jax.sharding import NamedSharding, PartitionSpec

            def _stack_sharding(leaf):
                spec = getattr(leaf.sharding, "spec", PartitionSpec())
                return NamedSharding(mesh, PartitionSpec(None, *spec))

            stacked_shardings = jax.tree_util.tree_map(
                _stack_sharding, first_global
            )
            stacked_abstract = jax.tree_util.tree_map(
                lambda leaf, sh: jax.ShapeDtypeStruct(
                    (steps_per_loop,) + leaf.shape, leaf.dtype, sharding=sh
                ),
                first_global, stacked_shardings,
            )

            def run_chunk(state, stacked, rng):
                def body(s, b):
                    return step_fn_raw(s, b, rng)
                state, ms = jax.lax.scan(body, state, stacked)
                # Last step's metrics: chunks end exactly on log boundaries.
                return state, jax.tree_util.tree_map(lambda x: x[-1], ms)

            multi_step = jax.jit(
                run_chunk, donate_argnums=(0,),
                out_shardings=(state_shardings, None),
            ).lower(state, stacked_abstract, train_rng).compile()

            # Stacking must happen INSIDE jit: multi-host global Arrays are
            # not fully addressable, so eager per-op dispatch on them
            # raises; a jitted stack with explicit out_shardings works on
            # one process and many alike.
            def _stack(*bs):
                import jax.numpy as jnp

                return jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *bs
                )

            stack_batches = jax.jit(
                _stack, out_shardings=stacked_shardings
            )
        flops_per_step = flops_lib.model_train_flops(
            core.model, first_global, train_step,
            n_devices=int(mesh.devices.size),
        )
        eval_step = jax.jit(build_eval_step(core.model, core.loss_fn))

        samples_per_step, tokens_per_step = flops_lib.batch_counts(first_global)
        hook = _StepsPerSecondHook(
            runtime, params_cfg.log_every_steps,
            n_try=runtime.n_try if runtime is not None else 0,
            resume_step=resume_step,
            flops_per_step=flops_per_step,
            samples_per_step=samples_per_step,
            tokens_per_step=tokens_per_step,
            peak_flops=flops_lib.peak_flops_per_chip(mesh.devices.flat[0]),
        )
        tb_writer = _make_tb_writer(core.model_dir)
        if tb_writer is not None:
            # On the cleanup stack, not just the happy path: for remote
            # model_dirs close() is what uploads the spooled event files,
            # and a crashed/preempted run must not lose them.
            _cleanup.callback(tb_writer.close)

        metrics_host: Dict[str, float] = {}
        from tf_yarn_tpu.data.prefetch import prefetch

        # Tracing (SURVEY §5: reference has coarse timers only; the
        # idiomatic TPU upgrade is a jax.profiler capture per host),
        # optionally windowed to a step range so long jobs stay readable.
        profile = _ProfileWindow()
        profile.on_step(resume_step)

        batch_iter = prefetch(
            train_iter, place_fn=globalize, depth=2, name="train"
        )
        batch = first_global
        # Steps already handed to the async writer: a SECOND save of the
        # same step (final save landing on a checkpoint boundary, drain
        # on one) would have orbax replace the tree WHILE the first
        # save's manifest finalizer is still hashing it — the finalizer
        # reads files the re-save just deleted.
        last_saved_step = resume_step if resume_step else None
        breakdown = _IntervalBreakdown()
        expected_shapes = tuple(
            a.shape for a in jax.tree_util.tree_leaves(first_global)
        )
        warned_ragged = False
        step = resume_step
        input_exhausted = False

        def record(b):
            leaves = jax.tree_util.tree_leaves(b)
            hook.record_batch(leaves[0].shape[0] if leaves else None)

        def pull_batch():
            """next(batch_iter) timed as input wait — the starvation
            signal: a healthy prefetch returns instantly, a starved one
            blocks here for the producer. StopIteration propagates (the
            span still records; only the breakdown skips the final,
            empty pull)."""
            with telemetry.span("train/input_wait") as sp:
                b = next(batch_iter)
            breakdown.add("input_wait", sp.duration)
            return b

        def run_single(state, b):
            nonlocal warned_ragged
            shapes = tuple(a.shape for a in jax.tree_util.tree_leaves(b))
            record(b)
            if shapes == expected_shapes:
                with telemetry.span("train/step_dispatch") as sp:
                    out = train_step(state, b, train_rng)
                breakdown.add("step_dispatch", sp.duration)
                return out
            # Ragged batch (e.g. epoch tail): the AOT executable is
            # shape-locked, fall back to the retracing jit path.
            if not warned_ragged:
                warned_ragged = True
                _logger.warning(
                    "batch shapes changed mid-run; recompiling. Use "
                    "fixed-size batches (drop the epoch tail) on TPU."
                )
            with telemetry.span("train/step_dispatch", ragged=True) as sp:
                out = train_step_jit(state, b, train_rng)
            breakdown.add("step_dispatch", sp.duration)
            return out

        def next_host_boundary(at):
            """First step > `at` where the loop must surface to the host."""
            boundary = params_cfg.train_steps
            for every in host_cadences:
                boundary = min(boundary, (at // every + 1) * every)
            for absolute in profile.boundaries():
                # Profiler toggles are absolute steps, not cadences; a
                # chunk must not step over one or the window would be
                # skipped/shifted.
                if absolute > at:
                    boundary = min(boundary, absolute)
            return boundary

        try:
            while step < params_cfg.train_steps:
                ran_chunk = False
                if (
                    multi_step is not None
                    and next_host_boundary(step) - step >= steps_per_loop
                ):
                    chunk = [batch]
                    while len(chunk) < steps_per_loop:
                        try:
                            chunk.append(pull_batch())
                        except StopIteration:
                            input_exhausted = True
                            break
                    uniform = all(
                        tuple(a.shape for a in jax.tree_util.tree_leaves(b))
                        == expected_shapes
                        for b in chunk
                    )
                    if len(chunk) == steps_per_loop and uniform:
                        with telemetry.span(
                            "train/step_dispatch", steps=steps_per_loop
                        ) as sp:
                            stacked = stack_batches(*chunk)
                            for b in chunk:
                                record(b)
                            state, metrics = multi_step(
                                state, stacked, train_rng
                            )
                        breakdown.add("step_dispatch", sp.duration)
                        step += steps_per_loop
                        ran_chunk = True
                    else:
                        # Short/ragged tail: drain what was pulled one by
                        # one (host events can't fall inside — the chunk
                        # window sat strictly before the next boundary).
                        for b in chunk:
                            state, metrics = run_single(state, b)
                            step += 1
                        ran_chunk = True
                if not ran_chunk:
                    state, metrics = run_single(state, batch)
                    step += 1
                profile.on_step(step, state)
                # Deterministic fault injection at the host boundary
                # (TPU_YARN_FAULT crash_at_step / sigterm_at_step): a
                # cached no-op when chaos is unarmed. SIGTERM lands in
                # the preemption flag and drains through the poll below;
                # an injected crash propagates like any runtime abort.
                resilience.chaos.on_train_step(step)
                if (
                    not input_exhausted
                    and step < params_cfg.train_steps
                    # Host-uniform poll cadence: every host computes the
                    # same `step % drain_poll_every`, so either all post
                    # the agreement allgather at this step or none do.
                    and (not multi_host or step % drain_poll_every == 0)
                    and _preempt_agreed(state)
                ):
                    # First thing at the host boundary — before eval/log
                    # work that could outlive the SIGTERM grace window.
                    # A flag raised during the final step falls through to
                    # normal completion instead (the run IS done; failing
                    # it would burn a relaunch to restore a finished
                    # checkpoint). SIGTERM grace window (TPU-VM
                    # preemption): persist progress, then fail the attempt
                    # as retryable — the driver's nb_retries relaunch
                    # resumes from this step.
                    _logger.warning(
                        "preemption drain at step %d: saving checkpoint", step
                    )
                    if core.model_dir:
                        with telemetry.span(
                            "train/checkpoint_save", step=step, drain=True
                        ):
                            if step != last_saved_step:
                                ckpt_writer.save(core.model_dir, step, state)
                                last_saved_step = step
                            ckpt_writer.wait()
                    raise preemption.Preempted(
                        f"preempted at step {step}"
                        + (
                            f"; checkpoint saved to {core.model_dir}"
                            if core.model_dir
                            else " (no model_dir: progress lost)"
                        )
                    )
                if (
                    (params_cfg.log_every_steps
                     and step % params_cfg.log_every_steps == 0)
                    or step == params_cfg.train_steps
                ):
                    # Drain outstanding device work before reading the
                    # metrics: attributed as device_wait (the compute
                    # backlog async dispatch hid from the host so far).
                    with telemetry.span("train/device_wait") as sp:
                        metrics = jax.block_until_ready(metrics)
                    breakdown.add("device_wait", sp.duration)
                    metrics_host = {k: float(v) for k, v in metrics.items()}
                    hook.after_step(
                        step, metrics_host,
                        force=step == params_cfg.train_steps,
                        breakdown=breakdown.report(),
                    )
                    if tb_writer is not None:
                        for key, value in metrics_host.items():
                            tb_writer.add_scalar(f"train/{key}", value, step)
                if (
                    params_cfg.checkpoint_every_steps
                    and step % params_cfg.checkpoint_every_steps == 0
                    and core.model_dir
                    and step != last_saved_step
                ):
                    with telemetry.span("train/checkpoint_save", step=step) as sp:
                        ckpt_writer.save(core.model_dir, step, state)
                        last_saved_step = step
                    breakdown.add("checkpoint_save", sp.duration)
                    if isinstance(tb_writer, _UploadingTbWriter):
                        # TB events survive a SIGKILL up to the last
                        # checkpoint boundary, like the model state does.
                        tb_writer.upload()
                if (
                    params_cfg.eval_every_steps
                    and core.eval_input_fn
                    and step % params_cfg.eval_every_steps == 0
                ):
                    with telemetry.span("train/eval", step=step) as sp:
                        eval_metrics = evaluate(
                            eval_step, state, core.eval_input_fn, globalize,
                            params_cfg.eval_steps, train_rng,
                        )
                    breakdown.add("eval", sp.duration)
                    _logger.info("eval @ step %d: %s", step, eval_metrics)
                    if tb_writer is not None:
                        for key, value in eval_metrics.items():
                            tb_writer.add_scalar(f"eval/{key}", value, step)
                if step < params_cfg.train_steps:
                    if input_exhausted:
                        _logger.info("input exhausted at step %d", step)
                        break
                    try:
                        batch = pull_batch()
                    except StopIteration:
                        _logger.info("input exhausted at step %d", step)
                        break
        finally:
            # Unblock the prefetch producer and drop staged device batches.
            batch_iter.close()
            profile.stop(state)

        if not metrics_host:
            # Loop never ran (restored checkpoint already at train_steps):
            # still report the model's current loss instead of {}.
            metrics_host = {
                k: float(v) for k, v in eval_step(state, batch, train_rng).items()
            }
        if core.model_dir:
            with telemetry.span("train/checkpoint_save", step=step, final=True):
                # Skip the re-save when the cadence already saved this
                # exact step (the wait still drains its commit).
                if step != last_saved_step:
                    ckpt_writer.save(core.model_dir, step, state)
                    last_saved_step = step
                ckpt_writer.wait()
        if core.eval_input_fn:
            with telemetry.span("train/eval", final=True):
                final_eval = evaluate(
                    eval_step, state, core.eval_input_fn, globalize,
                    params_cfg.eval_steps, train_rng,
                )
            metrics_host.update({f"eval_{k}": v for k, v in final_eval.items()})
        # tb_writer closes (and, for remote model_dirs, uploads) via the
        # _cleanup stack on both the happy and the exception path.
    return metrics_host


def evaluate(eval_step, state, eval_input_fn, globalize, max_steps, rng):
    totals: Dict[str, float] = {}
    count = 0
    for batch in eval_input_fn():
        metrics = eval_step(state, globalize(batch), rng)
        for key, value in metrics.items():
            totals[key] = totals.get(key, 0.0) + float(value)
        count += 1
        if count >= max_steps:
            break
    return {k: v / max(count, 1) for k, v in totals.items()}
