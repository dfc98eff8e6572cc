"""Throughput measurement harness.

One timed jitted-train-step loop shared by bench.py (the driver's single
headline metric) and benchmarks/run.py (the per-config BASELINE.json
suite). Mirrors what the reference measures — steps/sec and wall time
(reference: tensorflow/metrics.py:35-38, client.py:699-731) — expressed
as samples/sec/chip.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Any, Dict, Optional


@contextlib.contextmanager
def kernel_bwd_env(enabled: bool):
    """Scoped TPU_YARN_NORM_KERNEL_BWD toggle for A/B variants
    (ops/_rowwise.default_kernel_bwd reads it at trace time; every
    measure_throughput builds a fresh jit, so it takes effect). RESTORES
    the caller's prior value — an operator's global override must
    survive into the rest of a bench suite."""
    import os

    prior = os.environ.get("TPU_YARN_NORM_KERNEL_BWD")
    os.environ["TPU_YARN_NORM_KERNEL_BWD"] = "1" if enabled else "0"
    try:
        yield
    finally:
        if prior is None:
            os.environ.pop("TPU_YARN_NORM_KERNEL_BWD", None)
        else:
            os.environ["TPU_YARN_NORM_KERNEL_BWD"] = prior

_logger = logging.getLogger(__name__)


def measure_throughput(
    model: Any,
    loss_fn: Any,
    optimizer: Any,
    batch: Dict[str, Any],
    mesh_spec=None,
    steps: int = 20,
    init_fn=None,
    devices=None,
    flops_per_step: Optional[float] = None,
) -> Dict[str, float]:
    """Time `steps` jitted train steps; returns throughput stats.

    batch: host numpy arrays (leading dim = global batch).
    flops_per_step: optional *per-chip* model FLOPs for one step (e.g.
    utils.flops.transformer_train_flops(...) / n_devices); defaults to
    XLA's cost analysis of the compiled program.

    Warmup is one full (untimed) execution of the same `steps`-long
    program — there is no separate warmup knob since the scan makes every
    execution identical.
    """
    import jax
    import numpy as np

    from tf_yarn_tpu.parallel.mesh import (
        MeshSpec,
        build_mesh,
        select_devices,
        use_mesh,
    )
    from tf_yarn_tpu.utils import flops as flops_lib
    from tf_yarn_tpu.parallel.sharding import tree_shardings, unbox_params
    from tf_yarn_tpu.training import TrainState, build_train_step

    if devices is None:
        devices = select_devices()
    if mesh_spec is None:
        mesh_spec = MeshSpec.auto(len(devices))
    mesh = build_mesh(mesh_spec, devices)
    rng = jax.random.PRNGKey(0)
    sample = next(iter(batch.values()))
    batch_size = int(np.asarray(sample).shape[0])

    if init_fn is None:
        def init_fn(rng, batch):
            features = {k: v for k, v in batch.items() if k != "y"}
            if len(features) == 1:
                return model.init(rng, next(iter(features.values())))
            return model.init(rng, **features)

    def init_state(rng, batch):
        variables = init_fn(rng, batch)
        params = unbox_params(variables)
        return TrainState(np.int32(0), params, optimizer.init(params))

    def init_boxed(rng, batch):
        variables = init_fn(rng, batch)
        return TrainState(np.int32(0), variables, optimizer.init(variables))

    placed = {k: jax.device_put(np.asarray(v)) for k, v in batch.items()}
    abstract = jax.eval_shape(init_boxed, rng, placed)
    shardings = tree_shardings(mesh, abstract)
    # Init before entering the ambient mesh: flax's in-init unbox would
    # otherwise constrain with raw logical axis names (see
    # sharding.unbox_params); out_shardings are explicit NamedShardings.
    state = jax.jit(init_state, out_shardings=shardings)(rng, placed)

    with mesh, use_mesh(mesh):
        step_core = build_train_step(model, loss_fn, optimizer)

        # The measured loop runs *inside* one jitted program (lax.scan over
        # `steps` train steps), so one dispatch covers the timed region.
        def run_steps(state, batch, rng):
            def body(carry, _):
                state, rng = carry
                rng, step_rng = jax.random.split(rng)
                state, metrics = step_core(state, batch, step_rng)
                return (state, rng), metrics["loss"]
            (state, _), losses = jax.lax.scan(
                body, (state, rng), None, length=steps
            )
            return state, losses[-1]

        t0 = time.perf_counter()
        run_fn = jax.jit(
            run_steps, donate_argnums=(0,), out_shardings=(shardings, None)
        ).lower(state, placed, rng).compile()
        if flops_per_step is None:
            # Transformer family: analytic count (inner layer scans and
            # pallas kernels defeat cost analysis). Others: XLA cost
            # analysis of the compiled program — the steps-scan body is
            # counted once, so the program total IS one step's flops.
            flops_per_step = flops_lib.model_train_flops(
                model, batch, compiled=run_fn, n_devices=len(devices)
            )
        # Warmup call (also verifies the donated-state round trip).
        state, loss = jax.block_until_ready(run_fn(state, placed, rng))
        compile_time = time.perf_counter() - t0

        t0 = time.perf_counter()
        state, loss = jax.block_until_ready(run_fn(state, placed, rng))
        elapsed = time.perf_counter() - t0
        final_loss = float(loss)

    samples_per_sec = steps * batch_size / elapsed
    result = {
        "samples_per_sec": samples_per_sec,
        "samples_per_sec_per_chip": samples_per_sec / len(devices),
        "steps_per_sec": steps / elapsed,
        "step_time_ms": 1000 * elapsed / steps,
        "compile_plus_warmup_s": compile_time,
        "n_devices": float(len(devices)),
        "final_loss": final_loss,
    }
    if flops_per_step:
        # Per-device program FLOPs (post-partitioning): chip-level MFU.
        result["model_flops_per_step_per_chip"] = flops_per_step
        mfu = flops_lib.mfu(
            flops_per_step, result["steps_per_sec"],
            flops_lib.peak_flops_per_chip(devices[0]),
        )
        if mfu is not None:
            result["mfu"] = mfu
    return result
