"""Batch inference: checkpoint -> KV-cache generation -> JSONL records.

The runner behind `InferenceExperiment` (tf_yarn_tpu/experiment.py): the
train → checkpoint → generate lifecycle on the same launcher, task
programs and coordination the training path uses. No reference analog
(tf-yarn launches training only).

The loop is a three-stage pipeline so the device never idles on host
I/O: `data.prefetch.prefetch` stages input batches ahead on a background
thread, the compiled decode engine (`models.generate.generate` →
`DecodeEngine`) generates, and a bounded background writer thread drains
finished sequences to JSONL — the device_get that materializes each
batch's tokens happens on the writer thread, overlapped with the next
batch's decode (JAX async dispatch returns device futures to the main
thread).

Sharding across task instances is the input_fn's choice: declare
``(shard, num_shards)`` keywords to receive this task's slice of the
stream; instance outputs are suffixed ``-<task_id>`` so they never
collide on a shared filesystem.
"""

from __future__ import annotations

import inspect
import io
import json
import logging
import queue
import threading
import time
from typing import Optional

import numpy as np

from tf_yarn_tpu import checkpoint as ckpt_lib
from tf_yarn_tpu import fs as fs_lib
from tf_yarn_tpu import telemetry

_logger = logging.getLogger(__name__)


def _accepts_sharding(input_fn) -> bool:
    try:
        params = inspect.signature(input_fn).parameters
    except (TypeError, ValueError):
        params = {}
    return "shard" in params and "num_shards" in params


def _check_sharding_contract(input_fn, num_shards: int, allow_duplicate: bool):
    """Launcher-level contract, not a warning: N instances silently
    re-processing the full stream N times is the failure mode the
    reference's topology validators exist to prevent. Checked BEFORE the
    checkpoint restore so a misconfigured job fails in milliseconds, not
    after minutes of weight loading."""
    if _accepts_sharding(input_fn) or num_shards <= 1:
        return
    if not allow_duplicate:
        raise ValueError(
            f"{num_shards} inference instances but input_fn takes no "
            "(shard, num_shards) keywords: every instance would process "
            "the FULL stream and write duplicate records. Declare the "
            "keywords to split the stream, or set "
            "allow_duplicate_stream=True if duplication is intended."
        )
    _logger.warning(
        "input_fn takes no (shard, num_shards): every task instance "
        "processes the FULL stream (allow_duplicate_stream=True)."
    )


def _call_input_fn(input_fn, shard: int, num_shards: int):
    if _accepts_sharding(input_fn):
        return input_fn(shard=shard, num_shards=num_shards)
    return input_fn()


def _pipeline_depth(experiment, name: str, default: int) -> int:
    """Pipeline-depth knob as a validated int. `InferenceExperiment`
    carries these as real validated fields; the getattr default keeps
    duck-typed experiment objects (tests, user shims predating the
    fields) working — but an explicit invalid value fails loudly here
    instead of silently wedging a queue."""
    value = int(getattr(experiment, name, default))
    if value < 1:
        raise ValueError(f"{name} must be >= 1, got {value}")
    return value


def _restore_params(model_dir: str, step: Optional[int]):
    """Host-restore the checkpointed TrainState and keep its params:
    topology-independent (restore_checkpoint_host), so an inference job
    can run on a different device count than training used."""
    if step is None:
        step = ckpt_lib.latest_checkpoint_step(model_dir)
        if step is None:
            raise FileNotFoundError(f"no ckpt-<step> under {model_dir}")
    state = ckpt_lib.restore_checkpoint_host(model_dir, step)
    params = state["params"] if isinstance(state, dict) else state.params
    # TrainState.params as checkpointed is already the full flax variables
    # dict ({"params": ...}) — see training.py init_state — so return it
    # as-is; re-wrapping would double-nest and break model.apply.
    return params, step


def shard_restored_params(model, variables, mesh):
    """The SHARDED restore path (docs/Serving.md "Tensor-parallel
    decode"): place a host-restored variables dict onto `mesh` with the
    placements the model's logical-axis annotations assign.

    Checkpoints store raw arrays — the flax Partitioned boxes (and so
    the logical names "heads"/"mlp"/"vocab"/...) are gone by restore
    time — so the names come from an abstract re-init of the model
    (`jax.eval_shape`, no FLOPs, no device memory) and map through
    parallel.sharding.LOGICAL_RULES exactly as training placement does.
    Every leaf lands as one `device_put`; a variables dict that does
    not match the model's init structure fails loudly here, before any
    compile."""
    import jax
    import jax.numpy as jnp

    from tf_yarn_tpu.parallel import sharding as sharding_lib

    try:
        abstract = jax.eval_shape(
            lambda rng, tokens: model.init(rng, tokens),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((1, 8), jnp.int32),
        )
    except Exception as exc:
        raise ValueError(
            f"cannot abstractly init {type(model).__name__} to recover "
            f"its logical-axis annotations for the sharded restore: "
            f"{type(exc).__name__}: {exc}"
        ) from exc
    return sharding_lib.shard_like_annotated(mesh, abstract, variables)


class _JsonlWriter:
    """Bounded background JSONL writer (stage 3 of the pipeline).

    The main thread enqueues (tokens, sequences, extras) with
    `sequences` still a device array: the device_get that blocks on the
    decode happens HERE, overlapped with the next batch's prefill/decode
    on the main thread. The queue bound keeps finished batches from
    piling up in HBM when the filesystem is slow; a dead writer never
    deadlocks the producer (it drains without processing and the error
    re-raises on the next `put`/`close`).

    Also the token accountant: `real_tokens` counts each row's generated
    tokens up to and including its first eos — the repeated-eos tail the
    early-exit fill produces is *padding*, not generation — while
    `padded_tokens` keeps the full-width figure.
    """

    def __init__(self, out, eos_token: Optional[int], depth: int):
        self._out = out
        self._eos = eos_token
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._exc: Optional[BaseException] = None
        self.records = 0
        self.real_tokens = 0
        self.padded_tokens = 0
        self.write_seconds = 0.0
        self.max_queue_depth = 0
        self._thread = threading.Thread(
            target=self._run, name="inference-writer", daemon=True
        )
        self._thread.start()

    def qsize(self) -> int:
        return self._q.qsize()

    def _write_batch(self, tokens, sequences, extras) -> None:
        sequences = np.asarray(sequences)  # blocks on the device here
        tokens = np.asarray(tokens)
        prompt_len = tokens.shape[1]
        generated = sequences[:, prompt_len:]
        for row in range(sequences.shape[0]):
            record = {
                "prompt": tokens[row].tolist(),
                "tokens": generated[row].tolist(),
            }
            for key, value in extras.items():
                record[key] = np.asarray(value[row]).tolist()
            self._out.write(json.dumps(record) + "\n")
            self.records += 1
        self.padded_tokens += int(generated.size)
        if self._eos is None:
            self.real_tokens += int(generated.size)
        else:
            hit = generated == self._eos
            # First eos per row counts (the model generated it); the
            # repeated-eos fill after it does not. Rows with no eos are
            # all real.
            first = np.where(
                hit.any(axis=1), hit.argmax(axis=1) + 1, generated.shape[1]
            )
            self.real_tokens += int(first.sum())

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            if self._exc is not None:
                continue  # drain so the producer never blocks
            try:
                # Spanned on the writer thread: overlapped with the next
                # batch's decode on the main thread, so this is I/O the
                # pipeline hides — visible in the trace, not in elapsed.
                with telemetry.span("inference/write_batch") as sp:
                    self._write_batch(*item)
                self.write_seconds += sp.duration
                telemetry.get_registry().histogram(
                    "inference/stage_seconds", stage="write"
                ).observe(sp.duration)
            except BaseException as exc:  # noqa: BLE001 - re-raised in put/close
                self._exc = exc

    def put(self, tokens, sequences, extras) -> None:
        if self._exc is not None:
            raise self._exc
        self._q.put((tokens, sequences, extras))
        depth = self._q.qsize()
        self.max_queue_depth = max(self.max_queue_depth, depth)
        telemetry.get_registry().gauge(
            "inference/writer_queue_depth"
        ).set(depth)

    def close(self) -> None:
        """Flush the queue, stop the thread, re-raise any writer error."""
        self._q.put(None)
        self._thread.join()
        if self._exc is not None:
            raise self._exc


def run_inference(experiment, runtime=None) -> dict:
    """Generate for every batch of the (sharded) input stream; returns
    summary stats ({"records", "batches", "tokens_per_sec",
    "padded_tokens_per_sec", ...})."""
    from tf_yarn_tpu.data.prefetch import prefetch
    from tf_yarn_tpu.models.decode_engine import get_engine
    from tf_yarn_tpu.models.generate import generate

    shard, num_shards = 0, 1
    telemetry_task = "inference"
    if runtime is not None:
        shard = runtime.task_key.id
        num_shards = sum(
            1 for ti in runtime.cluster_tasks if ti.key.type == runtime.task_key.type
        )
        telemetry_task = getattr(
            runtime, "task",
            f"{runtime.task_key.type}:{runtime.task_key.id}",
        )
    telemetry.enable_env_jsonl(telemetry_task)
    allow_duplicate = getattr(experiment, "allow_duplicate_stream", False)
    _check_sharding_contract(experiment.input_fn, num_shards, allow_duplicate)
    fs_lib.check_model_dir_placement(experiment.model_dir)
    with telemetry.span("inference/restore_params"):
        variables, step = _restore_params(experiment.model_dir, experiment.step)
    # The engine `generate` routes through, before its first program.
    variables = get_engine(experiment.model).hold_params(variables)
    _logger.info(
        "inference from ckpt-%d, shard %d/%d -> %s",
        step, shard, num_shards, experiment.output_path,
    )

    out_path = experiment.output_path
    if num_shards > 1:
        out_path = f"{out_path}-{shard}"

    registry = telemetry.get_registry()
    stage_seconds = {"input_wait": 0.0, "decode": 0.0, "writer_put": 0.0}
    batches = 0
    # Monotonic clock: throughput over a wall-clock (time.time) interval
    # was corrupted by NTP steps mid-job.
    t0 = time.perf_counter()
    _end = object()
    # output_path may be any fs URI (gs://, hdfs://, ...) — results land
    # where the fleet can read them, like every other model_dir artifact.
    with io.TextIOWrapper(fs_lib.open_output(out_path), encoding="utf-8") as out:
        writer = _JsonlWriter(
            out, experiment.eos_token,
            depth=_pipeline_depth(experiment, "writer_depth", 8),
        )
        try:
            # Stage 1: input batches staged ahead on a background thread;
            # stage 2 (this thread): the compiled decode engine — generate
            # returns an async device future, so the put below does not
            # wait for the decode to finish.
            stream = prefetch(
                _call_input_fn(experiment.input_fn, shard, num_shards),
                depth=_pipeline_depth(experiment, "prefetch_depth", 2),
                name="inference",
            )
            while True:
                # Blocked here = stage 1 starved (the prefetch queue-depth
                # gauge pins at 0); blocked in put = stage 3 backed up.
                with telemetry.span("inference/input_wait") as sp_in:
                    batch = next(stream, _end)
                if batch is _end:
                    break
                stage_seconds["input_wait"] += sp_in.duration
                registry.histogram(
                    "inference/stage_seconds", stage="input_wait"
                ).observe(sp_in.duration)
                tokens = np.asarray(batch["tokens"], np.int32)
                with telemetry.span(
                    "inference/decode", batch_index=batches
                ) as sp_dec:
                    sequences = generate(
                        experiment.model,
                        variables,
                        tokens,
                        max_new_tokens=experiment.max_new_tokens,
                        temperature=experiment.temperature,
                        top_k=experiment.top_k,
                        top_p=getattr(experiment, "top_p", None),
                        eos_token=experiment.eos_token,
                    )
                stage_seconds["decode"] += sp_dec.duration
                registry.histogram(
                    "inference/stage_seconds", stage="decode"
                ).observe(sp_dec.duration)
                extras = {
                    key: np.asarray(value)
                    for key, value in batch.items()
                    if key != "tokens"
                }
                with telemetry.span("inference/writer_put") as sp_put:
                    writer.put(tokens, sequences, extras)
                stage_seconds["writer_put"] += sp_put.duration
                registry.histogram(
                    "inference/stage_seconds", stage="writer_put"
                ).observe(sp_put.duration)
                batches += 1
        except BaseException:
            # Don't mask the pipeline error with a writer error; best-
            # effort flush of what already decoded.
            try:
                writer.close()
            except BaseException:  # noqa: BLE001,TYA011 - original error wins
                pass
            telemetry.export_trace(telemetry_task)
            raise
        writer.close()
    elapsed = max(time.perf_counter() - t0, 1e-9)
    stage_seconds["write"] = writer.write_seconds
    stats = {
        "records": writer.records,
        "batches": batches,
        "ckpt_step": step,
        # Real throughput: per-row tokens up to the first eos. The
        # repeated-eos fill after the on-device early exit is reported
        # separately — counting it as generated inflated the number.
        "tokens_per_sec": round(writer.real_tokens / elapsed, 2),
        "padded_tokens_per_sec": round(writer.padded_tokens / elapsed, 2),
        # Per-stage wall attribution of the three-stage pipeline ("write"
        # runs on the writer thread, overlapped with decode) + how far
        # the bounded writer queue ever backed up.
        "stage_seconds": {k: round(v, 4) for k, v in stage_seconds.items()},
        "writer_queue_depth_max": writer.max_queue_depth,
    }
    from tf_yarn_tpu.models.decode_engine import get_engine

    # Compile-cache visibility: a recompile storm (unbucketed shapes from
    # a ragged input_fn) shows up right in the job stats.
    stats["decode_engine"] = dict(get_engine(experiment.model).stats)
    _logger.info("inference done: %s", stats)
    telemetry.flush_metrics(
        registry,
        kv=getattr(runtime, "kv", None),
        task=telemetry_task if runtime is not None else None,
    )
    telemetry.export_trace(telemetry_task)
    return stats
