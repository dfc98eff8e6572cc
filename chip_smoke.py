"""Train -> checkpoint -> serve on a real TPU chip, through the entry points
a user calls (`run_on_tpu` -> `LocalBackend` -> task program -> engine), at
the full width of the flagship decoder (vocab 32000, d_model 1024, 8 layers,
16 query / 8 KV heads, d_ff 4096, 2048 context; ~190 M parameters, bf16
compute). The quickest proof that the system still starts on the chip.

    python chip_smoke.py               # one chip; the driver's check
    python chip_smoke.py --four-chips  # what exists only across chips

One chip: every pallas kernel of the two paths against its reference, a few
train steps on one seeded batch with a checkpoint, then that checkpoint
served three ways (bf16 paged, int8 KV through the gather path, int8 KV
through the fused kernel), each stream compared with `generate_legacy`.
Four chips: the train steps sharded fsdp=2 x tp=2 against one chip, a tp=4
serving replica against tp=1, and four one-chip replicas behind the router.

This process never imports JAX: a process that has started the TPU backend
holds the chip and no child could take it. Whatever needs the device runs in
a task the launcher starts or in a `--child` of this script, one at a time.
Any phase that fails ends the run with a non-zero exit code. The last line of
standard output is `{"ok": true, "device": {...}}`, the device as the
children that held it reported it; it is printed only if that device is a
TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from tf_yarn_tpu import compile_cache  # noqa: E402
from tf_yarn_tpu.backends import LocalBackend  # noqa: E402
from tf_yarn_tpu.client import run_on_tpu  # noqa: E402
from tf_yarn_tpu.topologies import (  # noqa: E402
    NodeLabel,
    TaskSpec,
    fleet_topology,
)

# The flagship of bench.py and benchmarks/run.py, and how this script drives
# it. `TINY` is the same path at TransformerConfig.tiny width, for the CPU
# tests (tests/test_chip_smoke.py).
FLAGSHIP = dict(
    model=dict(vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
               n_kv_heads=8, d_ff=4096, max_seq_len=2048, remat=False),
    batch=8, seq=1024, steps=6, max_slots=8,
    # (prompt tokens, new tokens) of each request.
    requests=((64, 32), (128, 48), (256, 64), (128, 40)),
)
TINY = dict(
    model=dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
               n_kv_heads=2, d_ff=128, max_seq_len=128, remat=False),
    batch=4, seq=32, steps=4, max_slots=4,
    requests=((9, 6), (17, 8)),
)
# Four chips, one program: losses may differ from one chip's by the order in
# which partial sums meet (bf16 matmuls reduced across tp, gradients across
# fsdp). Stated, not tuned: a wrong sharding rule misses it by far more.
SHARDED_LOSS_RTOL = 2e-2


def log(message: str) -> None:
    print(message, flush=True)


# --------------------------------------------------------------------------
# Experiments: built inside the task that runs them.
# --------------------------------------------------------------------------

def model_config(shape, **overrides):
    from tf_yarn_tpu.models.transformer import TransformerConfig

    # scan_layers=False names the parameters layer_0..layer_N; training and
    # serving must agree on it to share a checkpoint.
    return TransformerConfig(**shape["model"], scan_layers=False, **overrides)


def train_experiment_fn(shape, model_dir, seed, mesh_axes=None,
                        report_memory=False):
    """The variant the old records call best: flash attention, fused norms,
    unrolled layers; a handful of steps on ONE seeded batch, so the loss has
    to fall."""

    def experiment_fn():
        import numpy as np

        from tf_yarn_tpu.models.transformer import make_experiment
        from tf_yarn_tpu.parallel.mesh import MeshSpec

        config = model_config(shape, attention_impl="flash", fused_norms=True)
        tokens = np.random.RandomState(seed).randint(
            0, config.vocab_size, (shape["batch"], shape["seq"]),
            dtype=np.int32,
        )

        def input_fn():
            pulls = 0
            while True:
                pulls += 1
                if report_memory and pulls == 4:
                    # Steps are in flight: parameters and optimizer state
                    # are where they will stay.
                    import jax

                    print("DEVICE_MEMORY " + json.dumps([
                        (d.memory_stats() or {}).get("bytes_in_use")
                        for d in jax.local_devices()
                    ]), flush=True)
                yield {"tokens": tokens}

        return make_experiment(
            config, model_dir=model_dir, train_steps=shape["steps"],
            batch_size=shape["batch"], seq_len=shape["seq"],
            input_fn=input_fn, seed=seed, log_every_steps=1,
            # No axes: one device, whatever else the process can see.
            mesh_spec=MeshSpec(**(mesh_axes or {})),
        )

    return experiment_fn


def serving_experiment_fn(shape, model_dir, port, kv_cache_dtype,
                          decode_attention, tp=1, router_port=0):
    def experiment_fn():
        from tf_yarn_tpu.experiment import ServingExperiment
        from tf_yarn_tpu.models.transformer import Transformer
        from tf_yarn_tpu.parallel.mesh import MeshSpec

        # Serving runs the configuration's defaults (XLA norms; decode
        # never reaches attention_impl). The parameters are the same.
        return ServingExperiment(
            model=Transformer(
                model_config(shape, kv_cache_dtype=kv_cache_dtype)
            ),
            model_dir=model_dir, host="127.0.0.1", port=port,
            max_slots=shape["max_slots"], decode_attention=decode_attention,
            mesh_spec=MeshSpec(tp=tp) if tp > 1 else None,
            router_host="127.0.0.1", router_port=router_port,
        )

    return experiment_fn


# --------------------------------------------------------------------------
# Launcher plumbing
# --------------------------------------------------------------------------

class _Backend(LocalBackend):
    """LocalBackend that remembers what it launched: the log directory (the
    losses are in the worker's log) and the handle (to stop a server the way
    a TPU VM does, with SIGTERM)."""

    handle = None
    log_dir = None

    def launch(self, services, log_dir):
        self.log_dir = log_dir
        self.handle = super().launch(services, log_dir)
        return self.handle


def _task_env(workdir):
    return {
        "TPU_YARN_TRACE": os.path.join(workdir, "trace"),
        "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }


def chip_task(chips: int) -> TaskSpec:
    return TaskSpec(instances=1, chips_per_host=chips, label=NodeLabel.TPU)


class Launch:
    """`run_on_tpu` on a thread, so that this process can talk to the tasks
    while they run. `stop` ends them cleanly and re-raises what the run
    raised; leaving the block any other way kills them."""

    def __init__(self, experiment_fn, task_specs, name, workdir):
        self.backend = _Backend()
        self.error = None
        self._thread = threading.Thread(
            target=self._run, name=name, args=(experiment_fn, task_specs),
            kwargs=dict(name=name, backend=self.backend,
                        env=_task_env(workdir)),
        )

    def _run(self, *args, **kwargs):
        try:
            run_on_tpu(*args, **kwargs)
        except BaseException as exc:  # noqa: B036 — re-raised by check()
            self.error = exc

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._thread.is_alive() and self.backend.handle is not None:
            self.backend.handle.kill()
        self._thread.join(timeout=60)

    def check(self):
        if self.error is not None:
            raise self.error
        if not self._thread.is_alive():
            raise RuntimeError("the run ended before it was told to stop")

    def stop(self, timeout=120.0):
        for pid in self.backend.handle.pids().values():
            with contextlib.suppress(ProcessLookupError):
                os.kill(pid, signal.SIGTERM)  # the preemption notice: drain
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            raise RuntimeError(f"tasks did not stop within {timeout:.0f}s")
        if self.error is not None:
            raise self.error


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def http_json(port, method, path, body=None, timeout=600.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, None if body is None else json.dumps(body),
                     {"Content-Type": "application/json"})
        response = conn.getresponse()
        payload = json.loads(response.read())
    finally:
        conn.close()
    if response.status != 200:
        raise RuntimeError(f"{method} {path} -> {response.status}: {payload}")
    return payload


def wait_healthy(launch, port, timeout=600.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        launch.check()
        try:
            http_json(port, "GET", "/healthz", timeout=5.0)
            return
        except (OSError, http.client.HTTPException):
            time.sleep(0.5)
    raise TimeoutError(f"nothing healthy on port {port} after {timeout:.0f}s")


def span_seconds(workdir, task, name) -> float:
    """Wall seconds the task spent in spans called `name` (its exported
    telemetry trace)."""
    path = os.path.join(workdir, "trace", f"trace_{task}.json")
    with open(path) as fh:
        events = json.load(fh)["traceEvents"]
    return sum(e["dur"] for e in events if e.get("name") == name) / 1e6


def run_child(name, payload, timeout=900.0):
    """One phase that needs the device in this process's stead: a child that
    takes the chip, prints its result as its last line, and exits."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", name],
        input=json.dumps(payload), capture_output=True, text=True,
        timeout=timeout,
    )
    lines = proc.stdout.strip().splitlines()
    for line in lines[:-1]:
        log(f"  [{name}] {line}")
    if proc.returncode != 0:
        raise RuntimeError(
            f"child {name!r} exited {proc.returncode}:\n"
            + "\n".join(lines[-3:] + proc.stderr.strip().splitlines()[-15:])
        )
    return json.loads(lines[-1])


# --------------------------------------------------------------------------
# Phases
# --------------------------------------------------------------------------

def train_phase(shape, workdir, seed, name="train", chips=1, mesh_axes=None):
    """A few steps through the `worker` task, a checkpoint at the end."""
    model_dir = os.path.join(workdir, name, "model")
    backend = _Backend()
    began = time.monotonic()
    run_on_tpu(
        train_experiment_fn(shape, model_dir, seed, mesh_axes,
                            report_memory=mesh_axes is not None),
        {"worker": chip_task(chips)},
        name=f"chip_smoke_{name}", backend=backend,
        env=_task_env(os.path.join(workdir, name)),
    )
    wall = time.monotonic() - began
    with open(os.path.join(backend.log_dir, "worker-0.log")) as fh:
        text = fh.read()
    losses = [float(m) for m in re.findall(r"step \d+: loss=(\S+)", text)]
    memory = re.search(r"DEVICE_MEMORY (\[.*\])", text)
    steps = shape["steps"]
    manifest = os.path.join(model_dir, f"ckpt-{steps}", "MANIFEST.json")
    result = {
        "losses": losses,
        "compile_s": round(span_seconds(
            os.path.join(workdir, name), "worker-0",
            "train/compile_train_step"), 1),
        "wall_s": round(wall, 1),
        "model_dir": model_dir,
        "device_memory": json.loads(memory.group(1)) if memory else None,
    }
    log(f"{name}: losses {losses} compile {result['compile_s']}s "
        f"wall {result['wall_s']}s")
    if len(losses) != steps or not all(
        loss == loss and abs(loss) != float("inf") for loss in losses
    ):
        raise AssertionError(f"want {steps} finite losses, got {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall on a fixed batch: {losses}")
    if not os.path.exists(manifest):
        raise AssertionError(f"no checkpoint manifest at {manifest}")
    return result


def make_requests(shape, seed):
    """Greedy requests, token ids made from `seed` (no numpy here: this
    process stays light)."""
    import random

    vocab = shape["model"]["vocab_size"]
    bodies = []
    for index, (prompt_len, new_tokens) in enumerate(shape["requests"]):
        tokens = random.Random(f"{seed}/{index}")
        bodies.append({
            "prompt": [tokens.randrange(vocab) for _ in range(prompt_len)],
            "max_new_tokens": new_tokens,
        })
    return bodies


def ask_all(port, bodies):
    """POST every body to /v1/generate at once; replies in order."""
    replies = [None] * len(bodies)
    errors = []

    def ask(index):
        try:
            replies[index] = http_json(port, "POST", "/v1/generate",
                                       bodies[index])
        except Exception as exc:  # raised below, on the calling thread
            errors.append(exc)

    threads = [threading.Thread(target=ask, args=(i,))
               for i in range(len(bodies))]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join()
    if errors:
        raise errors[0]
    return replies


def check_replies(shape, bodies, replies):
    vocab = shape["model"]["vocab_size"]
    for body, reply in zip(bodies, replies):
        tokens = reply["tokens"]
        if len(tokens) != body["max_new_tokens"]:
            raise AssertionError(
                f"asked for {body['max_new_tokens']} tokens, got "
                f"{len(tokens)}: {reply}")
        if not all(isinstance(t, int) and 0 <= t < vocab for t in tokens):
            raise AssertionError(f"token outside the vocabulary: {tokens}")
        if not reply.get("finish_reason"):
            raise AssertionError(f"reply without a finish reason: {reply}")


def n_compiles(stats) -> int:
    return sum(count for key, count in stats["decode_engine"].items()
               if key.endswith("_compiles"))


def serve_phase(shape, workdir, model_dir, seed, name, kv_cache_dtype="bf16",
                decode_attention="gather", chips=1, tp=1):
    """The checkpoint behind `POST /v1/generate`, through the `serving`
    task: warm up every shape, then a few requests at once, none of which
    may compile."""
    port = free_port()
    began = time.monotonic()
    bodies = make_requests(shape, seed)
    warmup = [dict(body, max_new_tokens=2)
              for body in make_requests(shape, seed + 1)]
    with Launch(
        serving_experiment_fn(shape, model_dir, port, kv_cache_dtype,
                              decode_attention, tp=tp),
        {"serving": chip_task(chips)},
        f"chip_smoke_{name}", os.path.join(workdir, name),
    ) as launch:
        wait_healthy(launch, port)
        for body in warmup:
            http_json(port, "POST", "/v1/generate", body)
        warm = http_json(port, "GET", "/stats")
        replies = ask_all(port, bodies)
        stats = http_json(port, "GET", "/stats")
        launch.stop()
    check_replies(shape, bodies, replies)
    result = {
        "name": name, "kv_cache_dtype": kv_cache_dtype,
        "bodies": bodies, "streams": [r["tokens"] for r in replies],
        "device": stats["device"], "compile_cache": stats["compile_cache"],
        "compiles": n_compiles(warm),
        "compiles_after_warmup": n_compiles(stats) - n_compiles(warm),
        "compile_s": round(span_seconds(
            os.path.join(workdir, name), "serving-0",
            "decode_engine/compile"), 1),
        "kv_bytes": stats["kv_cache_hbm_bytes"],
        "kv_bytes_per_device": stats["kv_cache_hbm_bytes_per_device"],
        "wall_s": round(time.monotonic() - began, 1),
    }
    log(f"{name}: {len(replies)} requests answered at full length; "
        f"{result['compiles']} compiles in {result['compile_s']}s, "
        f"{result['compiles_after_warmup']} after warm-up; compile cache "
        f"{result['compile_cache']}; KV {result['kv_bytes']} B "
        f"({result['kv_bytes_per_device']} B on each of "
        f"{stats['device']['count']} devices); wall {result['wall_s']}s")
    if result["compiles_after_warmup"]:
        raise AssertionError(
            f"{name}: {result['compiles_after_warmup']} compiles after "
            f"warm-up: {stats['decode_engine']}")
    return result


def fleet_phase(shape, workdir, model_dir, seed, replicas=4):
    """`fleet_topology`: one-chip serving replicas and a router on one host.
    Every replica must have its own chip; every request through the router
    must be answered."""
    router_port = free_port()
    bodies = make_requests(shape, seed) * 2
    with Launch(
        # port=0: each replica binds its own; the router finds them through
        # the coordination service.
        serving_experiment_fn(shape, model_dir, 0, "bf16", "gather",
                              router_port=router_port),
        fleet_topology(nb_replicas=replicas, chips_per_host=1),
        "chip_smoke_fleet", os.path.join(workdir, "fleet"),
    ) as launch:
        wait_healthy(launch, router_port)
        deadline = time.monotonic() + 600.0
        while True:
            launch.check()
            fleet = http_json(router_port, "GET", "/stats")
            if fleet["healthy_replicas"] == replicas:
                break
            if time.monotonic() > deadline:
                raise TimeoutError(f"replicas not healthy: {fleet}")
            time.sleep(1.0)
        replies = ask_all(router_port, bodies)
        fleet = http_json(router_port, "GET", "/stats")
        devices = {}
        for task, replica in fleet["replicas"].items():
            port = int(replica["endpoint"].rpartition(":")[2])
            devices[task] = http_json(port, "GET", "/stats")["device"]
        launch.stop()
    check_replies(shape, bodies, replies)
    log(f"fleet: {len(replies)} requests through the router answered; "
        f"routed {fleet['routed_requests']}")
    for task, device in sorted(devices.items()):
        log(f"fleet: {task} on {device}")
    chips = [d["visible_chips"] for d in devices.values()]
    if any(d["platform"] != "tpu" or d["count"] != 1
           for d in devices.values()) or len(set(chips)) != replicas:
        raise AssertionError(
            f"want {replicas} replicas on {replicas} different chips: "
            f"{devices}")
    return {"devices": devices, "streams": [r["tokens"] for r in replies]}


def report_matches(label, bodies, streams, reference) -> int:
    """Print, per request, whether `streams` equal `reference`; the number
    that do not. A mismatch is a finding (near-tied bf16 logits may flip an
    argmax between two compiled programs), not a failure."""
    mismatches = 0
    for index, (body, got, want) in enumerate(
        zip(bodies, streams, reference)
    ):
        same = got == want
        first = next((i for i, (a, b) in enumerate(zip(got, want)) if a != b),
                     None)
        log(f"{label}: request {index} (prompt {len(body['prompt'])}, "
            f"{body['max_new_tokens']} new) "
            + ("matches" if same else f"DIFFERS from token {first}"))
        mismatches += not same
    return mismatches


# --------------------------------------------------------------------------
# Children: the phases that use JAX themselves.
# --------------------------------------------------------------------------

def child_kernels(payload):
    """Every pallas kernel of the train and serve paths, one compiled call
    at the run's shapes, against its reference in float32 at the highest
    matmul precision, under the tolerances tests/test_ops.py and
    tests/test_attention.py use for bf16. Where the reference grows past 1
    the error is taken relative to its largest value: a bf16 result carries
    eight bits, whatever its size."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tf_yarn_tpu.ops import decode_attention as da
    from tf_yarn_tpu.ops.attention import xla_attention
    from tf_yarn_tpu.ops.flash_attention import flash_attention
    from tf_yarn_tpu.ops.quantize import dequantize_int8, quantize_int8
    from tf_yarn_tpu.ops.rmsnorm import rmsnorm, rmsnorm_reference
    from tf_yarn_tpu.parallel.mesh import device_report, select_devices

    select_devices()  # the platform this was started for, or an error
    shape = payload["shape"]
    model = shape["model"]
    heads, kv_heads = model["n_heads"], model["n_kv_heads"]
    head_dim = model["d_model"] // heads
    batch, seq, slots = shape["batch"], shape["seq"], shape["max_slots"]
    cache_len, block = model["max_seq_len"], 16
    max_blocks = cache_len // block
    rng = np.random.RandomState(payload["seed"])
    bf16 = jnp.bfloat16

    def normal(*dims, dtype=bf16):
        return jnp.asarray(rng.randn(*dims).astype(np.float32), dtype)

    def f32(x):
        return np.asarray(x, np.float32)

    results = []

    def check(name, got, want, atol):
        errors = [
            float(np.abs(f32(g) - f32(w)).max()
                  / max(1.0, np.abs(f32(w)).max()))
            for g, w in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want))
        ]
        finite = all(np.isfinite(f32(g)).all()
                     for g in jax.tree_util.tree_leaves(got))
        results.append({"kernel": name, "max_err": max(errors),
                        "atol": atol, "ok": finite and max(errors) <= atol})
        print(f"kernel {name}: max error {max(errors):.3g} (atol {atol})"
              + ("" if results[-1]["ok"] else "  FAILED"), flush=True)

    def exact(fn):
        """`fn` on float32 copies of its arguments, nothing rounded."""
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*(a.astype(jnp.float32) for a in args))
        return jax.jit(run)

    # Flash attention, forward and backward, against XLA attention.
    q = normal(batch, seq, heads, head_dim)
    k = normal(batch, seq, kv_heads, head_dim)
    v = normal(batch, seq, kv_heads, head_dim)

    def attention_loss(fn):
        return lambda q, k, v: fn(q, k, v, causal=True).astype(
            jnp.float32).sum()

    check("flash_forward",
          jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
              q, k, v),
          exact(lambda q, k, v: xla_attention(q, k, v, causal=True))(
              q, k, v), 2e-2)
    check("flash_backward",
          jax.jit(jax.grad(attention_loss(flash_attention), (0, 1, 2)))(
              q, k, v),
          exact(jax.grad(attention_loss(xla_attention), (0, 1, 2)))(
              q, k, v), 2e-2)

    # RMSNorm, forward and dx.
    x = normal(batch, seq, model["d_model"])
    scale = jnp.asarray(rng.rand(model["d_model"]).astype(np.float32))
    check("rmsnorm_forward", jax.jit(rmsnorm)(x, scale),
          exact(rmsnorm_reference)(x, scale), 2e-2)
    check("rmsnorm_dx",
          jax.jit(jax.grad(lambda x: rmsnorm(x, scale, kernel_bwd=True)
                           .astype(jnp.float32).sum()))(x),
          exact(jax.grad(lambda x: rmsnorm_reference(x, scale).sum()))(x),
          5e-2)

    # Quantize: per-row scale keeps the error within half a step
    # (tests/test_ops.py test_quantize_int8_roundtrip).
    rows = normal(1, seq, kv_heads, head_dim, dtype=jnp.float32) * 3.0
    values, scales = jax.jit(quantize_int8)(rows)
    half_step = np.abs(f32(dequantize_int8(values, scales)) - f32(rows)) \
        / f32(scales)
    check("quantize_int8", half_step.max(), 0.0, 0.51)

    # The three decode kernels against XLA attention over the dequantized
    # cache, each slot at its own length.
    pool_k = normal(slots * max_blocks + 1, block, kv_heads, head_dim,
                    dtype=jnp.float32)
    pool_v = normal(slots * max_blocks + 1, block, kv_heads, head_dim,
                    dtype=jnp.float32)
    (kq, ks), (vq, vs) = jax.jit(quantize_int8)(pool_k), \
        jax.jit(quantize_int8)(pool_v)
    tables = 1 + rng.permutation(slots * max_blocks).reshape(
        slots, max_blocks).astype(np.int32)
    lengths = np.linspace(1, cache_len - 4, slots).astype(np.int32)

    def dense(pool_q, pool_s, slot):  # one slot's cache, dequantized
        return dequantize_int8(pool_q, pool_s)[tables[slot]].reshape(
            1, cache_len, kv_heads, head_dim)

    def reference(query, slot, length):  # query [W, H, D] ending at length
        width = query.shape[0]
        return xla_attention(
            query[None], dense(kq, ks, slot)[:, :length],
            dense(vq, vs, slot)[:, :length], causal=True,
            segment_offset=length - width,
        )[0]

    query = normal(slots, heads, head_dim)
    check("paged_int8_decode_attention",
          jax.jit(da.paged_int8_decode_attention)(
              query, kq, ks, vq, vs, tables, lengths),
          exact(lambda query: jnp.stack([
              reference(query[s][None], s, int(lengths[s]))[0]
              for s in range(slots)]))(query), 2e-2)
    check("int8_decode_attention",
          jax.jit(lambda query: jnp.stack([
              da.int8_decode_attention(
                  query[s][None], kq[tables[s]].reshape(
                      1, cache_len, kv_heads, head_dim),
                  ks[tables[s]].reshape(1, cache_len, kv_heads, 1),
                  vq[tables[s]].reshape(1, cache_len, kv_heads, head_dim),
                  vs[tables[s]].reshape(1, cache_len, kv_heads, 1),
                  int(lengths[s]))[0] for s in range(slots)]))(query),
          exact(lambda query: jnp.stack([
              reference(query[s][None], s, int(lengths[s]))[0]
              for s in range(slots)]))(query), 2e-2)
    width = 4
    window = normal(slots, width, heads, head_dim)
    check("paged_int8_window_attention",
          jax.jit(da.paged_int8_window_attention)(
              window, kq, ks, vq, vs, tables, lengths),
          exact(lambda window: jnp.stack([
              reference(window[s], s, int(lengths[s]) + width)
              for s in range(slots)]))(window), 2e-2)

    return {"device": device_report(), "kernels": results,
            "compile_cache": compile_cache.stats()}


def child_reference(payload):
    """`generate_legacy` from the same checkpoint on the same chip, for each
    request of each KV cache dtype that was served."""
    import numpy as np

    from tf_yarn_tpu import inference
    from tf_yarn_tpu.models.generate import generate_legacy
    from tf_yarn_tpu.models.transformer import Transformer
    from tf_yarn_tpu.parallel.mesh import device_report, select_devices

    select_devices()
    variables, _step = inference._restore_params(payload["model_dir"], None)
    streams = {}
    for kv_cache_dtype in payload["kv_cache_dtypes"]:
        model = Transformer(
            model_config(payload["shape"], kv_cache_dtype=kv_cache_dtype)
        )
        streams[kv_cache_dtype] = []
        for body in payload["bodies"]:
            prompt = np.asarray([body["prompt"]], np.int32)
            began = time.monotonic()
            out = generate_legacy(model, variables, prompt,
                                  body["max_new_tokens"])
            streams[kv_cache_dtype].append(
                np.asarray(out)[0, prompt.shape[1]:].tolist())
            print(f"reference {kv_cache_dtype}: request "
                  f"{len(streams[kv_cache_dtype]) - 1} done "
                  f"({time.monotonic() - began:.1f}s)", flush=True)
    return {"device": device_report(), "streams": streams}


CHILDREN = {"kernels": child_kernels, "reference": child_reference}


# --------------------------------------------------------------------------
# The two runs
# --------------------------------------------------------------------------

def one_chip(shape, workdir, seed):
    devices = []
    began = time.monotonic()
    kernels = run_child("kernels", {"shape": shape, "seed": seed})
    devices.append(kernels["device"])
    log(f"kernels: {len(kernels['kernels'])} checked on "
        f"{kernels['device']} in {time.monotonic() - began:.1f}s")
    failed = [k for k in kernels["kernels"] if not k["ok"]]
    if failed:
        raise AssertionError(f"kernels outside tolerance: {failed}")

    train = train_phase(shape, workdir, seed)
    log(f"compile cache after train: {compile_cache.entries()} entries")

    served = [
        serve_phase(shape, workdir, train["model_dir"], seed, name,
                    kv_cache_dtype=kv, decode_attention=attention)
        for name, kv, attention in (
            ("serve_bf16_paged", "bf16", "gather"),
            ("serve_int8_gather", "int8", "gather"),
            ("serve_int8_fused", "int8", "fused"),
        )
    ]
    devices.extend(s["device"] for s in served)
    if not any(s["compile_cache"]["hits"] for s in served[1:]):
        raise AssertionError(
            "no later server found a program in the compile cache: "
            f"{[s['compile_cache'] for s in served]}")

    began = time.monotonic()
    reference = run_child("reference", {
        "shape": shape, "model_dir": train["model_dir"],
        "bodies": served[0]["bodies"], "kv_cache_dtypes": ["bf16", "int8"],
    }, timeout=1200.0)
    devices.append(reference["device"])
    mismatches = sum(
        report_matches(f"{s['name']} vs generate_legacy", s["bodies"],
                       s["streams"], reference["streams"][s["kv_cache_dtype"]])
        for s in served
    )
    log(f"reference: {mismatches} of {len(served) * len(served[0]['bodies'])} "
        f"streams differ from generate_legacy "
        f"({time.monotonic() - began:.1f}s)")
    return devices


def four_chips(shape, workdir, seed):
    """Only what exists across chips, and what each is compared with."""
    devices = []
    # (a) the train steps sharded fsdp=2 x tp=2 in one process, against the
    # same steps on one chip.
    single = train_phase(shape, workdir, seed, name="train_one_chip")
    sharded = train_phase(shape, workdir, seed, name="train_fsdp2_tp2",
                          chips=4, mesh_axes={"fsdp": 2, "tp": 2})
    memory = sharded["device_memory"]
    log(f"train_fsdp2_tp2: bytes in use on each device {memory}")
    if not memory or len(memory) != 4 or min(memory) < 0.5 * max(memory):
        raise AssertionError(
            f"state is not spread over four devices: {memory}")
    for one, four in zip(single["losses"], sharded["losses"]):
        if abs(one - four) > SHARDED_LOSS_RTOL * abs(one):
            raise AssertionError(
                f"sharded losses {sharded['losses']} leave one chip's "
                f"{single['losses']} by more than {SHARDED_LOSS_RTOL:.0%}")
    log(f"train_fsdp2_tp2: losses within {SHARDED_LOSS_RTOL:.0%} of one "
        "chip's")

    # (c) four one-chip replicas behind the router, every request sent
    # twice. Each replica is the tp=1 server that (b) is compared with.
    model_dir = single["model_dir"]
    fleet = fleet_phase(shape, workdir, model_dir, seed)
    devices.extend(fleet["devices"].values())
    bodies = make_requests(shape, seed)
    one_chip_streams = fleet["streams"][:len(bodies)]
    report_matches("fleet, second copy vs first", bodies,
                   fleet["streams"][len(bodies):], one_chip_streams)

    # (b) a tensor-parallel replica over all four chips.
    tp4 = serve_phase(shape, workdir, model_dir, seed, "serve_tp4",
                      chips=4, tp=4)
    devices.append(tp4["device"])
    if tp4["device"]["count"] != 4 \
            or tp4["kv_bytes_per_device"] * 4 != tp4["kv_bytes"]:
        raise AssertionError(
            f"the tp=4 pool is not a quarter on each device: {tp4}")
    report_matches("serve_tp4 vs a one-chip replica", bodies,
                   tp4["streams"], one_chip_streams)
    return devices


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run only the paths that span four chips")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--child", choices=sorted(CHILDREN),
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child:
        compile_cache.enable()
        print(json.dumps(CHILDREN[args.child](json.load(sys.stdin))),
              flush=True)
        return 0

    log(f"compile cache: {compile_cache.export()} holds "
        f"{compile_cache.entries()} entries")
    from tf_yarn_tpu.coordination import server_factory

    server = server_factory.start_best_server()
    log(f"coordination server: {server_factory.server_kind(server)}")
    server.stop()
    began = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as workdir:
        run = four_chips if args.four_chips else one_chip
        devices = run(FLAGSHIP, workdir, args.seed)
    log(f"compile cache: {compile_cache.entries()} entries now; "
        f"total wall {time.monotonic() - began:.1f}s")
    if any(d["platform"] != "tpu" for d in devices):
        raise AssertionError(f"a phase ran off the TPU: {devices}")
    print(json.dumps({"ok": True, "device": {
        "platform": "tpu", "kind": devices[0]["kind"],
        # As the process that saw the most of them reported it.
        "count": max(d["count"] for d in devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
