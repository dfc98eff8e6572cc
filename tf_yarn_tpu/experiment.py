"""Experiment types users return from their `experiment_fn`.

The reference ships three experiment shapes (SURVEY.md §2.2): Estimator
`Experiment` (tensorflow/experiment.py:6-14), `KerasExperiment`
(keras_experiment.py:5-11) and `PytorchExperiment` (pytorch/experiment.py:
30-56). This module supplies their TPU-native counterparts plus the
first-class JAX shape, all normalizing into one `CoreExperiment` consumed
by the pjit train loop (tf_yarn_tpu/training.py):

* :class:`JaxExperiment` — flax model + optax optimizer + loss, the
  flagship path.
* :class:`ExperimentSpec` (+ :class:`Estimator`, :class:`TrainSpec`,
  :class:`EvalSpec`) — the Estimator-style triple for users porting
  `Experiment(estimator, train_spec, eval_spec)` code.
* :class:`KerasExperiment` — model/model_dir/train_params/input_data_fn
  shape for users porting Keras jobs.
* `PytorchExperiment` lives in tf_yarn_tpu/pytorch.py (torch-xla path).

Loss contract everywhere: ``loss_fn(model, params, batch, rng) ->
(scalar_loss, aux_metrics_dict)`` with ``batch`` a dict of arrays.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterator, NamedTuple, Optional, Tuple

from tf_yarn_tpu.parallel.mesh import AXIS_TP, MeshSpec

Batch = Dict[str, Any]
LossFn = Callable[..., Any]  # (model, params, batch, rng) -> (loss, aux)
# Zero-arg factory of batch iterators. A train input_fn may also declare
# a `start_step` keyword: on checkpoint resume the train loop passes the
# resume step so the pipeline can skip already-consumed data (opt-in
# input resume; see training._make_input_iter).
InputFn = Callable[[], Iterator[Batch]]


@dataclasses.dataclass
class TrainParams:
    """Loop control knobs (the analog of the reference's
    train_spec/eval_spec scalars + KerasExperiment train_params)."""

    train_steps: int
    eval_every_steps: Optional[int] = None
    eval_steps: int = 10
    checkpoint_every_steps: Optional[int] = None
    # Completed checkpoints beyond the newest N are deleted (Estimator
    # keep_max semantics). None = keep everything.
    keep_last_n: Optional[int] = 5
    log_every_steps: int = 10
    seed: int = 0
    # Split each global batch into N sequential microbatches, averaging
    # gradients before the single optimizer update (HBM for batch size).
    # Global batch must divide by N x the data-axis sharding.
    grad_accum_steps: int = 1
    # Run N train steps inside ONE jitted program (lax.scan over a stacked
    # batch block) between host events — amortizes per-step dispatch the
    # way TF's steps-per-loop does. Host work (logging, checkpoints, eval)
    # still happens on its configured cadence: chunks never cross those
    # boundaries. Costs N staged batches of extra HBM.
    steps_per_loop: int = 1
    # Multi-host preemption agreement (a device-pipeline drain + cross-host
    # allgather) polls every N steps; None = the smallest host cadence
    # above (log/checkpoint/eval). Lower = faster SIGTERM reaction, higher
    # = less per-step sync overhead. Single-host polls are a flag read and
    # ignore this. See docs/Performance.md "Preemption polling".
    drain_poll_every_steps: Optional[int] = None

    def __post_init__(self) -> None:
        # Fail at construction, before any restore/compile work — the
        # reference's validator posture (topologies validate task specs at
        # build time, /root/reference/tf_yarn/topologies.py:97-128). A
        # value of 0 would otherwise be masked by an `or`-fallback and a
        # negative one would silently disable the SIGTERM drain poll.
        if self.train_steps < 1:
            raise ValueError(
                f"train_steps must be >= 1, got {self.train_steps}")
        if self.steps_per_loop < 1:
            raise ValueError(
                f"steps_per_loop must be >= 1, got {self.steps_per_loop}")
        if self.grad_accum_steps < 1:
            raise ValueError(
                f"grad_accum_steps must be >= 1, got {self.grad_accum_steps}")
        if (self.drain_poll_every_steps is not None
                and self.drain_poll_every_steps < 1):
            raise ValueError(
                "drain_poll_every_steps must be >= 1, got "
                f"{self.drain_poll_every_steps}")
        for name in ("eval_every_steps", "checkpoint_every_steps",
                     "keep_last_n"):
            value = getattr(self, name)
            if value is not None and value < 1:
                raise ValueError(f"{name} must be >= 1, got {value}")
        if self.log_every_steps < 0:
            raise ValueError(
                f"log_every_steps must be >= 0, got {self.log_every_steps}")
        if self.eval_steps < 1:
            raise ValueError(
                f"eval_steps must be >= 1, got {self.eval_steps}")


@dataclasses.dataclass
class JaxExperiment:
    """The TPU-first experiment: everything the train loop needs to pjit.

    `init_fn(rng, batch) -> params` defaults to `model.init(rng, batch)`
    for single-input models; the model zoo's `make_experiment` helpers set
    it explicitly.
    """

    model: Any
    optimizer: Any
    loss_fn: LossFn
    train_input_fn: InputFn
    train_params: TrainParams
    model_dir: Optional[str] = None
    eval_input_fn: Optional[InputFn] = None
    init_fn: Optional[Callable] = None
    mesh_spec: Optional[MeshSpec] = None
    # exporters(params, metrics, step): run by the side-car evaluator
    # after each checkpoint's evaluation.
    exporters: Optional[Callable] = None


class Estimator:
    """Estimator-style shim: owns model/loss/optimizer/model_dir (the role
    of tf.estimator.Estimator in reference experiment.py:6-14)."""

    def __init__(
        self,
        model: Any,
        loss_fn: LossFn,
        optimizer: Any,
        model_dir: Optional[str] = None,
        init_fn: Optional[Callable] = None,
        mesh_spec: Optional[MeshSpec] = None,
    ) -> None:
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.model_dir = model_dir
        self.init_fn = init_fn
        self.mesh_spec = mesh_spec

    @property
    def config(self) -> Dict[str, Any]:  # parity: Experiment.config property
        return {"model_dir": self.model_dir}

    def train(self, input_fn: InputFn, max_steps: int, **train_params) -> Dict:
        """In-process training (tf.estimator.Estimator.train familiarity;
        distributed runs go through run_on_tpu with an ExperimentSpec)."""
        import dataclasses as _dc

        from tf_yarn_tpu import training

        spec = ExperimentSpec(
            estimator=self,
            train_spec=TrainSpec(input_fn=input_fn, max_steps=max_steps),
        )
        core = as_core_experiment(spec)
        if train_params:  # unknown keys raise TypeError, not silence
            core.train_params = _dc.replace(core.train_params, **train_params)
        return training.train_and_evaluate(core)

    def evaluate(self, input_fn: InputFn, steps: int = 10) -> Dict:
        """Evaluate the latest checkpoint in model_dir on `input_fn`."""
        from tf_yarn_tpu import checkpoint as ckpt_lib
        from tf_yarn_tpu.evaluation import evaluate_checkpoint

        if not self.model_dir:
            raise ValueError("evaluate() needs a model_dir with checkpoints")
        step = ckpt_lib.latest_checkpoint_step(self.model_dir)
        if step is None:
            raise ValueError(f"no checkpoints in {self.model_dir}")
        return evaluate_checkpoint(
            self.model, self.loss_fn, self.model_dir, step, input_fn, steps
        )


class TrainSpec(NamedTuple):
    input_fn: InputFn
    max_steps: int


class EvalSpec(NamedTuple):
    input_fn: Optional[InputFn] = None
    steps: int = 10
    throttle_secs: int = 30  # side-car evaluator poll cadence
    start_delay_secs: int = 0
    every_steps: Optional[int] = None  # in-loop eval cadence (None = end only)
    # Called by the side-car evaluator after each checkpoint's evaluation:
    # exporters(params, metrics, step) — the reference's
    # eval_spec.exporters hook (evaluator_task.py:103-121), e.g. to write
    # a serving copy of the best weights.
    exporters: Optional[Callable] = None


class ExperimentSpec(NamedTuple):
    """`Experiment(estimator, train_spec, eval_spec)` parity
    (reference: tensorflow/experiment.py:6-14)."""

    estimator: Estimator
    train_spec: TrainSpec
    eval_spec: Optional[EvalSpec] = None

    @property
    def config(self) -> Dict[str, Any]:
        return self.estimator.config

    @property
    def model_dir(self) -> Optional[str]:
        return self.estimator.model_dir


@dataclasses.dataclass
class KerasExperiment:
    """Keras-shaped experiment (reference: keras_experiment.py:5-11 —
    model, model_dir, train_params, input_data_fn, target_data_fn,
    validation_data_fn), extended with the optimizer/loss a compiled Keras
    model would carry internally."""

    model: Any
    model_dir: Optional[str]
    train_params: TrainParams
    input_data_fn: InputFn
    optimizer: Any
    loss_fn: LossFn
    target_data_fn: Optional[Callable] = None
    validation_data_fn: Optional[InputFn] = None
    init_fn: Optional[Callable] = None
    mesh_spec: Optional[MeshSpec] = None


@dataclasses.dataclass
class InferenceExperiment:
    """Batch-inference job: load a checkpoint, run KV-cache generation
    over an input stream, write results.

    No reference analog (tf-yarn launches training only); completes the
    model lifecycle train → checkpoint → batch inference on the same
    launcher. `input_fn` yields dict batches with "tokens" [B, P] int32
    (fixed shapes per batch — XLA recompiles per new shape) and any extra
    keys to echo into the output records (e.g. ids). An `input_fn` may
    declare (shard, num_shards) keywords to split the stream across task
    instances. Results land as JSON lines at `output_path` (suffixed
    `-<task_id>` when there are multiple instances)."""

    model: Any
    model_dir: str
    input_fn: InputFn
    output_path: str
    max_new_tokens: int = 128
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token: Optional[int] = None
    step: Optional[int] = None  # checkpoint step; None = latest
    # Multi-instance jobs whose input_fn ignores (shard, num_shards) fail
    # fast unless duplication of the full stream is explicitly intended.
    allow_duplicate_stream: bool = False
    # Pipeline depths (inference.run_inference): `prefetch_depth` input
    # batches staged ahead of the device, and `writer_depth` decoded
    # batches queued to the background JSONL writer before the producer
    # blocks. Both >= 1 (validated at construction — a 0 would silently
    # serialize the pipeline stage instead of disabling it).
    prefetch_depth: int = 2
    writer_depth: int = 8

    def __post_init__(self) -> None:
        for name in ("prefetch_depth", "writer_depth"):
            value = getattr(self, name)
            if value < 1:
                raise ValueError(f"{name} must be >= 1, got {value}")
        if self.max_new_tokens < 0:
            raise ValueError(
                f"max_new_tokens must be >= 0, got {self.max_new_tokens}"
            )


@dataclasses.dataclass
class ServingExperiment:
    """Online-serving job: load a checkpoint, serve ``/v1/generate``
    with continuous batching until stopped (tf_yarn_tpu/serving/,
    docs/Serving.md). The online counterpart of InferenceExperiment —
    same restore path, but requests arrive over HTTP into a bounded
    admission queue and decode on a fixed grid of ``max_slots``
    persistent KV slots instead of as whole-stream batches.

    ``temperature``/``top_k``/``top_p`` configure the ONE compiled
    slot-step program; requests carrying different values are rejected
    with a 400 (per-request ``max_new_tokens``/``seed``/``eos_token``
    stay free). ``serve_seconds=None`` serves until the task is killed
    or a preemption notice arrives (the normal production posture).

    The slots' KV lives in a global pool of ``block_size``-token blocks
    with per-slot block tables and a shared prompt-prefix cache
    (docs/Serving.md; fp outputs stay bit-identical to
    ``generate_legacy``). ``num_blocks=None`` sizes the pool for every
    slot at full context; shrink it to realize the HBM saving
    (``prefix_cache_capacity=0`` disables prefix sharing).

    ``mesh_spec`` turns on TENSOR-PARALLEL decode (docs/Serving.md
    "Tensor-parallel decode"): ``MeshSpec(tp=N)`` places the replica's
    weights by the transformer's logical-axis rules and shards the slot
    KV (the paged block pool) by kv-heads over the ``tp``
    mesh axis, so a model bigger than one chip's HBM serves online —
    still ONE compiled program and one host sync per tick. Serving
    shards tensor-parallel only: every other mesh axis must stay 1 (use
    the fleet router for replica parallelism). Config errors — a head
    count not divisible by tp, or ``decode_attention="fused"`` with
    tp > 1 (the pallas kernel cannot read a sharded pool yet) — fail
    HERE, at build time, not as an opaque trace-time partitioner error.
    """

    model: Any
    model_dir: str
    host: str = "0.0.0.0"
    port: int = 0  # 0 = ephemeral; the bound port is advertised via KV
    max_slots: int = 8
    queue_capacity: int = 64
    retry_after_s: float = 1.0
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    step: Optional[int] = None  # checkpoint step; None = latest
    serve_seconds: Optional[float] = None
    block_size: int = 16
    num_blocks: Optional[int] = None
    prefix_cache_capacity: int = 256
    # Speculative decoding (docs/Serving.md "Speculative decoding"):
    # ``spec_k`` drafts per slot per tick (0 = exact path, the
    # default), proposed by ``spec_draft`` ("ngram" self-draft, or a
    # callable ``(context, k) -> tokens`` — the draft-model hook) and
    # verified in one windowed forward; emitted streams are identical
    # to the exact path, each request just lands up to spec_k + 1
    # tokens per tick. ``decode_attention="fused"`` runs the paged
    # verify forward's attention on the paged-int8 pallas kernel
    # (requires an int8 KV cache).
    spec_k: int = 0
    spec_draft: Any = "ngram"
    decode_attention: str = "gather"
    # Chunked prefill (docs/Serving.md "Chunked prefill"):
    # ``prefill_chunk`` splits admission prefill into teacher-forced
    # windows of that many prompt tokens riding the same compiled step
    # decode runs, so a 2k-token prompt never stalls in-flight streams.
    # 0 (the default) keeps the blocking admission prefill; "auto"
    # picks the engine's largest prompt bucket (or the spec window when
    # larger). ``prefill_budget_per_tick`` caps the prompt tokens
    # replayed per tick across all slots (None = unlimited; the
    # scheduler requires it >= the window width so chunking slots can
    # always advance).
    prefill_chunk: Any = 0
    prefill_budget_per_tick: Optional[int] = None
    # KV oversubscription (docs/Serving.md "KV oversubscription & SLO
    # tiers"): ``kv_host_blocks`` > 0 backs the paged pool with that
    # many host-RAM blocks — under pool pressure the scheduler swaps
    # the lowest-SLO-tier active stream out to the host tier (bit-
    # identical on resume) instead of holding admissions; 2x the
    # device pool is the ROADMAP sizing. ``tier_caps`` maps tier name
    # ("interactive"/"standard"/"batch") -> max in-system requests for
    # that tier (queued + active + suspended); a tier at its cap
    # answers 429.
    kv_host_blocks: int = 0
    tier_caps: Optional[Dict[str, int]] = None
    # Tensor-parallel decode (docs/Serving.md "Tensor-parallel decode"):
    # MeshSpec(tp=N) shards this replica's weights and slot KV across N
    # devices. None (default) = single-device decode, exactly as before.
    mesh_spec: Optional[MeshSpec] = None
    # Fleet-router knobs (tf_yarn_tpu/fleet/, docs/Fleet.md), read only
    # by the ``router`` task in a `fleet_topology` — serving replicas
    # ignore them. ``router_policy`` picks the balancing policy
    # ("round_robin" or "least_loaded"); ``router_retries`` budgets the
    # per-request failover loop (connect errors / 429s move to another
    # replica); ``router_probe_interval_s`` paces /healthz probes.
    router_host: str = "0.0.0.0"
    router_port: int = 0
    router_policy: str = "least_loaded"
    router_retries: int = 2
    router_probe_interval_s: float = 1.0
    # Declared service-level objectives (docs/Observability.md "Fleet
    # observability plane"), e.g. ``{"interactive_ttft_p95_s": 0.5}``:
    # each replica evaluates them over its recent latency window
    # (slo/attainment gauges + slo/burn_total counters), and the
    # router's FleetMonitor evaluates the same objectives fleet-wide
    # over the merged histograms — the canary-rollback trigger.
    slo: Optional[Dict[str, float]] = None
    # Fleet autoscaling (tf_yarn_tpu/fleet/autoscaler.py, docs/Fleet.md
    # "Autoscaling & self-healing"), read only by the ``router`` task:
    # ``autoscale`` maps replica kind ('generate' / 'rank') to an
    # AutoscalePolicy field dict, e.g.
    # ``{"generate": {"min_replicas": 1, "max_replicas": 4}}``; None
    # (default) = no autoscaler side-car. ``autoscale_launch_eta_s`` is
    # how long a scaled-out replica takes to become routable — the
    # Retry-After an EMPTY pool's 503 carries (clamped to
    # [LAUNCH_ETA_FLOOR_S, LAUNCH_ETA_CEILING_S]).
    # ``autoscale_warm_start`` primes (re-)admitted generate replicas'
    # prefix caches from a live peer via /v1/blocks.
    autoscale: Optional[Dict[str, Dict]] = None
    autoscale_launch_eta_s: float = 15.0
    autoscale_warm_start: bool = True
    # Disaggregated prefill (docs/Serving.md "Disaggregated prefill"):
    # PrefillTierConfig field dict, e.g. ``{"offload_threshold": 256}``.
    # When set, /v1/generate pulls long
    # prompts' KV blocks from the ``prefill`` task tier before
    # submitting; None (default) = always prefill locally. Also the
    # experiment read by the ``prefill`` task itself (tasks/prefill.py).
    prefill_tier: Optional[Dict] = None

    def __post_init__(self) -> None:
        if self.max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {self.max_slots}")
        if self.queue_capacity < 1:
            raise ValueError(
                f"queue_capacity must be >= 1, got {self.queue_capacity}"
            )
        if self.serve_seconds is not None and self.serve_seconds <= 0:
            raise ValueError(
                f"serve_seconds must be > 0 or None, got {self.serve_seconds}"
            )
        if self.block_size < 1:
            raise ValueError(
                f"block_size must be >= 1, got {self.block_size}"
            )
        if self.num_blocks is not None and self.num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 or None, got {self.num_blocks}"
            )
        if self.prefix_cache_capacity < 0:
            raise ValueError(
                f"prefix_cache_capacity must be >= 0, got "
                f"{self.prefix_cache_capacity}"
            )
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {self.spec_k}")
        if self.spec_draft is not None and not callable(self.spec_draft) \
                and self.spec_draft != "ngram":
            raise ValueError(
                "spec_draft must be 'ngram', a callable "
                f"(context, k) -> tokens, or None; got {self.spec_draft!r}"
            )
        if self.decode_attention not in ("gather", "fused"):
            raise ValueError(
                f"decode_attention must be 'gather' or 'fused', got "
                f"{self.decode_attention!r}"
            )
        chunked = self.prefill_chunk not in (0, None)
        if chunked and self.prefill_chunk != "auto" and (
            not isinstance(self.prefill_chunk, int)
            or self.prefill_chunk < 1
        ):
            raise ValueError(
                "prefill_chunk must be 0/None (blocking admission "
                "prefill), 'auto', or an int >= 1; got "
                f"{self.prefill_chunk!r}"
            )
        if self.prefill_budget_per_tick is not None:
            if not chunked:
                raise ValueError(
                    "prefill_budget_per_tick needs chunked prefill: set "
                    "prefill_chunk >= 1 or 'auto' (with blocking "
                    "admission there is no per-tick prefill to budget)"
                )
            if self.prefill_budget_per_tick < 1:
                raise ValueError(
                    "prefill_budget_per_tick must be >= 1 or None, got "
                    f"{self.prefill_budget_per_tick}"
                )
        if self.kv_host_blocks < 0:
            raise ValueError(
                f"kv_host_blocks must be >= 0, got {self.kv_host_blocks}"
            )
        if self.tier_caps is not None:
            from tf_yarn_tpu.serving.request import tier_rank

            for name, cap in self.tier_caps.items():
                tier_rank(name)  # ValueError on an unknown tier name
                if not isinstance(cap, int) or cap < 0:
                    raise ValueError(
                        f"tier_caps[{name!r}] must be an int >= 0, "
                        f"got {cap!r}"
                    )
        if self.mesh_spec is not None:
            # Reject bad TP configs HERE — before any restore/trace —
            # with errors that name the knob, not the XLA partitioner's
            # symptom. The device-availability check happens where the
            # devices are (parallel.mesh.select_devices raises "need N
            # devices, have M" when the serving task builds the mesh).
            spec = self.mesh_spec
            other = {
                name: size
                for name, size in zip(spec.axis_names, spec.axis_sizes)
                if name != AXIS_TP and size != 1
            }
            if other:
                raise ValueError(
                    f"serving shards tensor-parallel only: mesh_spec "
                    f"axes {other} must be 1 (replica parallelism is "
                    "the fleet router's job — docs/Fleet.md)"
                )
            tp = spec.tp
            config = getattr(self.model, "config", None)
            if tp > 1:
                for name in ("n_heads", "n_kv_heads"):
                    value = getattr(config, name, None)
                    if value is not None and value % tp:
                        raise ValueError(
                            f"mesh_spec tp={tp} does not divide the "
                            f"model's {name}={value}; tensor-parallel "
                            "decode shards attention (and the KV "
                            "cache) by heads"
                        )
                if self.decode_attention == "fused":
                    raise ValueError(
                        f"decode_attention='fused' cannot run with "
                        f"mesh_spec tp={tp}: the paged-int8 pallas "
                        "kernel reads the whole block pool in one "
                        "program and cannot read a sharded pool yet; "
                        "use decode_attention='gather' or tp=1"
                    )
        if self.router_policy not in ("round_robin", "least_loaded"):
            raise ValueError(
                f"router_policy must be 'round_robin' or 'least_loaded', "
                f"got {self.router_policy!r}"
            )
        if self.router_retries < 0:
            raise ValueError(
                f"router_retries must be >= 0, got {self.router_retries}"
            )
        if self.router_probe_interval_s <= 0:
            raise ValueError(
                f"router_probe_interval_s must be > 0, got "
                f"{self.router_probe_interval_s}"
            )
        if self.slo is not None:
            from tf_yarn_tpu.telemetry.slo import parse_slo

            try:
                parse_slo(self.slo)
            except ValueError as exc:
                raise ValueError(f"slo: {exc}") from exc
        if self.autoscale is not None:
            from tf_yarn_tpu.fleet.autoscaler import parse_autoscale

            try:
                parse_autoscale(self.autoscale)
            except ValueError as exc:
                raise ValueError(f"autoscale: {exc}") from exc
        if not self.autoscale_launch_eta_s > 0:
            raise ValueError(
                f"autoscale_launch_eta_s must be > 0, got "
                f"{self.autoscale_launch_eta_s}"
            )
        if self.prefill_tier is not None:
            from tf_yarn_tpu.serving.prefill import parse_prefill_tier

            try:
                parse_prefill_tier(self.prefill_tier)
            except ValueError as exc:
                raise ValueError(f"prefill_tier: {exc}") from exc


@dataclasses.dataclass
class RankingExperiment:
    """Online-ranking job: load (or deterministically init) DLRM-class
    params and serve ``/v1/rank`` with fill-or-timeout micro-batching
    until stopped (tf_yarn_tpu/ranking/, docs/Ranking.md). The second
    serving workload class: stateless, latency-bound feature batches —
    no KV cache, no slots, capacity freed every tick.

    ``max_batch``/``max_wait_ms`` are the micro-batch policy: tick when
    the queued rows fill ``max_batch`` OR the oldest waiter has aged
    ``max_wait_ms`` (0 = tick on arrival; `benchmarks/run.py rank`
    sweeps the trade). ``model_dir=None`` serves a deterministic
    ``init_seed`` init instead of a checkpoint (demos, tests — any peer
    with the same model + seed reproduces the params bit-for-bit).

    ``mesh_spec`` turns on EMBEDDING-SHARDED inference: MeshSpec(tp=N)
    splits the stacked embedding table's rows over N devices through
    ``parallel.sharding.RANKING_RULES`` (dense/MLP replicated), XLA
    inserting the lookup collectives — the serving twin of the
    reference's PS-sharded weight table. Ranking shards tensor-parallel
    only, and tp must divide ``sum(table_sizes)``; both fail HERE with
    the knob's name, before any params load.
    """

    model: Any
    model_dir: Optional[str] = None
    host: str = "0.0.0.0"
    port: int = 0  # 0 = ephemeral; the bound port is advertised via KV
    max_batch: int = 32
    max_wait_ms: float = 2.0
    queue_capacity: int = 256
    retry_after_s: float = 0.5
    batch_buckets: Optional[Tuple[int, ...]] = None
    warmup: bool = True
    init_seed: int = 0
    step: Optional[int] = None  # checkpoint step; None = latest
    serve_seconds: Optional[float] = None
    mesh_spec: Optional[MeshSpec] = None

    def __post_init__(self) -> None:
        if self.max_batch < 1:
            raise ValueError(
                f"max_batch must be >= 1, got {self.max_batch}"
            )
        if self.max_wait_ms < 0:
            raise ValueError(
                f"max_wait_ms must be >= 0, got {self.max_wait_ms}"
            )
        if self.queue_capacity < 1:
            raise ValueError(
                f"queue_capacity must be >= 1, got {self.queue_capacity}"
            )
        if self.serve_seconds is not None and self.serve_seconds <= 0:
            raise ValueError(
                f"serve_seconds must be > 0 or None, got "
                f"{self.serve_seconds}"
            )
        if self.batch_buckets is not None and (
            not self.batch_buckets or min(self.batch_buckets) < 1
        ):
            raise ValueError(
                f"batch_buckets must be a non-empty tuple of positive "
                f"sizes or None, got {self.batch_buckets!r}"
            )
        config = getattr(self.model, "config", None)
        if config is None or not hasattr(config, "table_sizes"):
            raise ValueError(
                "RankingExperiment.model must be a DLRM-class model "
                "exposing config.table_sizes (the ranking engine reads "
                "it for feature validation and table sharding)"
            )
        if self.mesh_spec is not None:
            # Same posture as ServingExperiment: bad TP configs fail at
            # build time with the knob's name, not as a partitioner
            # symptom after the restore.
            spec = self.mesh_spec
            other = {
                name: size
                for name, size in zip(spec.axis_names, spec.axis_sizes)
                if name != AXIS_TP and size != 1
            }
            if other:
                raise ValueError(
                    f"ranking shards tensor-parallel only: mesh_spec "
                    f"axes {other} must be 1 (replica parallelism is "
                    "the fleet router's job — docs/Fleet.md)"
                )
            total = int(sum(config.table_sizes))
            if spec.tp > 1 and total % spec.tp:
                raise ValueError(
                    f"mesh_spec tp={spec.tp} does not divide the stacked "
                    f"embedding table's {total} rows "
                    "(sum(model.config.table_sizes)) — each device must "
                    "hold an equal table shard"
                )


@dataclasses.dataclass
class CoreExperiment:
    """Normalized form consumed by training.train_and_evaluate."""

    model: Any
    optimizer: Any
    loss_fn: LossFn
    train_input_fn: InputFn
    train_params: TrainParams
    model_dir: Optional[str]
    eval_input_fn: Optional[InputFn]
    init_fn: Optional[Callable]
    mesh_spec: Optional[MeshSpec]
    # exporters(params, metrics, step): evaluator post-eval hook.
    exporters: Optional[Callable] = None


def _merge_input_targets(experiment: KerasExperiment) -> InputFn:
    """Zip Keras-style separate feature/target streams into batch dicts."""

    def input_fn():
        targets = experiment.target_data_fn() if experiment.target_data_fn else None
        for features in experiment.input_data_fn():
            batch = dict(features) if isinstance(features, dict) else {"x": features}
            if targets is not None:
                try:
                    batch["y"] = next(targets)
                except StopIteration:  # targets exhausted -> epoch over (PEP 479)
                    return
            yield batch

    return input_fn


def as_core_experiment(experiment: Any) -> CoreExperiment:
    if isinstance(experiment, JaxExperiment):
        return CoreExperiment(
            model=experiment.model,
            optimizer=experiment.optimizer,
            loss_fn=experiment.loss_fn,
            train_input_fn=experiment.train_input_fn,
            train_params=experiment.train_params,
            model_dir=experiment.model_dir,
            eval_input_fn=experiment.eval_input_fn,
            init_fn=experiment.init_fn,
            mesh_spec=experiment.mesh_spec,
            exporters=experiment.exporters,
        )
    if isinstance(experiment, ExperimentSpec):
        estimator = experiment.estimator
        eval_spec = experiment.eval_spec
        params = TrainParams(
            train_steps=experiment.train_spec.max_steps,
            eval_every_steps=eval_spec.every_steps if eval_spec else None,
            eval_steps=eval_spec.steps if eval_spec else 10,
        )
        return CoreExperiment(
            model=estimator.model,
            optimizer=estimator.optimizer,
            loss_fn=estimator.loss_fn,
            train_input_fn=experiment.train_spec.input_fn,
            train_params=params,
            model_dir=estimator.model_dir,
            eval_input_fn=eval_spec.input_fn if eval_spec else None,
            init_fn=estimator.init_fn,
            mesh_spec=estimator.mesh_spec,
            exporters=eval_spec.exporters if eval_spec else None,
        )
    if isinstance(experiment, KerasExperiment):
        return CoreExperiment(
            model=experiment.model,
            optimizer=experiment.optimizer,
            loss_fn=experiment.loss_fn,
            train_input_fn=_merge_input_targets(experiment),
            train_params=experiment.train_params,
            model_dir=experiment.model_dir,
            eval_input_fn=experiment.validation_data_fn,
            init_fn=experiment.init_fn,
            mesh_spec=experiment.mesh_spec,
        )
    raise TypeError(f"cannot normalize experiment of type {type(experiment)!r}")


EXPERIMENT_TYPES = (
    JaxExperiment, ExperimentSpec, KerasExperiment, InferenceExperiment,
    ServingExperiment, RankingExperiment,
)


def run_experiment(runtime, experiment: Any) -> None:
    """Entry used by the task programs (tasks/worker.py, serving.py,
    rank.py)."""
    from tf_yarn_tpu import telemetry
    from tf_yarn_tpu.parallel import mesh as mesh_lib

    # Whatever the experiment, it runs on the platform this process was
    # started for, or not at all.
    mesh_lib.select_devices()
    task = runtime.task if runtime is not None else "local"
    try:
        # Root span: the whole experiment body nests under it in the
        # exported trace (TPU_YARN_TRACE), restore/compile/loop alike.
        with telemetry.span(
            "experiment/run", kind=type(experiment).__name__
        ):
            if isinstance(experiment, InferenceExperiment):
                from tf_yarn_tpu import inference

                inference.run_inference(experiment, runtime=runtime)
                return
            if isinstance(experiment, ServingExperiment):
                from tf_yarn_tpu.serving.server import run_serving

                run_serving(experiment, runtime=runtime)
                return
            if isinstance(experiment, RankingExperiment):
                from tf_yarn_tpu.ranking.server import run_ranking

                run_ranking(experiment, runtime=runtime)
                return
            from tf_yarn_tpu import training

            training.train_and_evaluate(
                as_core_experiment(experiment), runtime=runtime
            )
    finally:
        # Re-export so the root span (closed just now, after the runner's
        # own export) is present; no-op without TPU_YARN_TRACE.
        telemetry.export_trace(task)
