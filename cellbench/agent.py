"""The benchmark's hand inside the task that holds the chip.

`run_on_tpu` unpickles the experiment function in the task's own process,
and only that process can make arrays on the chip, trace it or read its
memory. So the function that builds the experiment also (1) hands the
server weights made on the device from the seed where it would restore a
checkpoint, and (2) starts a small HTTP side door through which the parent,
which stays off JAX, starts and stops the profiler and reads the device,
the live arrays and the program's host spans. Nothing here changes what the
timed path does.

The parent reaches the door at 127.0.0.1:<agent_port>:
  GET  /device        platform, kind, count, peak bytes, live arrays
  GET  /spans         the program's host spans (perf_counter seconds)
  POST /trace/start   start the profiler, tie its clock to perf_counter
  POST /trace/stop    stop it
  POST /trace/reduce  body {"window": [start, end]} -> cellbench.trace.reduce
"""

from __future__ import annotations

import importlib
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer


def adapter(config: dict):
    """`cellbench/programs/<config["program"]>.py`: `model(config, context,
    overrides)` builds the program's model, `plain_name(path)` names a leaf
    of its tree as the configuration's weight table does."""
    return importlib.import_module("cellbench.programs." + config["program"])


def build_model(config: dict):
    """The program's model of a configuration, as its adapter builds it."""
    return adapter(config).model(
        config, config["serving"]["context"], config.get("model", {}))


def program_variables(model, config: dict, seed: int):
    """The seeded weights in the program's own tree and types."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from cellbench import weights as weights_lib

    abstract = meta.unbox(jax.eval_shape(
        lambda rng, tokens: model.init(rng, tokens),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((1, 8), jnp.int32),
    ))

    plain_name = adapter(config).plain_name
    leaves = jax.tree_util.tree_leaves_with_path(abstract)
    dtypes = {plain_name(path)[0]: leaf.dtype for path, leaf in leaves}
    flat = weights_lib.make(config, seed, dtypes)

    def place(path, leaf):
        name, layer = plain_name(path)
        value = flat[name]
        if isinstance(value, list):  # one array a layer
            value = jnp.stack(value) if layer is None else value[layer]
        if value.shape != leaf.shape or value.dtype != leaf.dtype:
            raise ValueError(
                f"{name}: the program wants {leaf.shape} {leaf.dtype}, "
                f"the seeded weights are {value.shape} {value.dtype}")
        return value

    return jax.tree_util.tree_map_with_path(place, abstract)


def serving_experiment(spec: dict):
    """Built in the task: the ServingExperiment of one configuration."""
    from tf_yarn_tpu import inference, telemetry
    from tf_yarn_tpu.experiment import ServingExperiment

    Door(spec["agent_port"], spec["trace_dir"]).start()
    config = spec["config"]
    model = build_model(config)

    def seeded(model_dir, step):
        with telemetry.span("cellbench/seeded_weights"):
            import jax

            variables = program_variables(model, config, spec["seed"])
            jax.block_until_ready(variables)
        return variables, 0

    # run_serving has no other way in for parameters than a checkpoint.
    inference._restore_params = seeded
    serving = {k: v for k, v in config["serving"].items() if k != "context"}
    return ServingExperiment(
        model=model, model_dir=spec["run_dir"], host="127.0.0.1",
        port=spec["port"], **serving,
    )


class Door:
    def __init__(self, port: int, trace_dir: str):
        self.port, self.trace_dir = port, trace_dir
        self.sync_perf_s = None

    def start(self):
        door = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):
                pass

            def _reply(self, payload, status=200):
                body = json.dumps(payload).encode()
                self.send_response(status)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _serve(self, route):
                length = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(length) or b"{}")
                try:
                    self._reply(route(body))
                except Exception as exc:  # the parent reports it
                    self._reply({"error": f"{type(exc).__name__}: {exc}"}, 500)

            def do_GET(self):
                self._serve({"/device": door.device,
                             "/spans": door.spans}[self.path])

            def do_POST(self):
                self._serve({"/trace/start": door.trace_start,
                             "/trace/stop": door.trace_stop,
                             "/trace/reduce": door.trace_reduce}[self.path])

        server = ThreadingHTTPServer(("127.0.0.1", self.port), Handler)
        threading.Thread(target=server.serve_forever, name="cellbench-door",
                         daemon=True).start()

    # -- routes -------------------------------------------------------------

    def device(self, _body):
        import jax

        devices = jax.local_devices()
        stats = [d.memory_stats() or {} for d in devices]
        # A step donates the KV pool: while the scheduler's thread is inside
        # that call the old pool is gone and the new one not yet there. So
        # look a few times, some milliseconds apart, and keep the most seen.
        live = {}
        for _ in range(5):
            seen = {}
            for array in jax.live_arrays():
                key = (tuple(array.shape), str(array.dtype))
                seen[key] = seen.get(key, 0) + 1
            for key, n in seen.items():
                live[key] = max(live.get(key, 0), n)
            time.sleep(0.007)
        return {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(
                (s.get("peak_bytes_in_use", 0) for s in stats), default=0),
            "bytes_limit": max((s.get("bytes_limit", 0) for s in stats),
                               default=0),
            "live_arrays": [{"shape": list(shape), "dtype": dtype, "count": n}
                            for (shape, dtype), n in live.items()],
        }

    def spans(self, _body):
        from tf_yarn_tpu import telemetry

        return {"now": time.perf_counter(), "spans": [
            {"name": s.name, "start": s.start, "dur": s.duration,
             "depth": s.depth, "parent": s.parent, "tid": s.thread_id,
             "args": {k: v for k, v in s.args.items()
                      if isinstance(v, (int, float, str, bool, type(None)))}}
            for s in telemetry.get_tracer().records()
        ]}

    def trace_start(self, _body):
        import jax

        from cellbench.trace import SYNC_NAME

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # device operations and annotations only
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation(SYNC_NAME):
            self.sync_perf_s = time.perf_counter()
        return {"sync_perf_s": self.sync_perf_s}

    def trace_stop(self, _body):
        import jax

        stopped = time.perf_counter()  # writing the trace out takes seconds
        jax.profiler.stop_trace()
        return {"stopped_perf_s": stopped}

    def trace_reduce(self, body):
        from cellbench import trace

        return trace.reduce(trace.load_xplane(self.trace_dir),
                            self.spans(None)["spans"],
                            self.sync_perf_s, tuple(body["window"]))
