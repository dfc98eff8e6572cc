"""Threaded HTTP JSON frontend + the `serving` task body.

Stdlib only (`http.server`), because the TPU VM image carries no web
framework and the protocol is deliberately tiny:

* ``POST /v1/generate`` — body ``{"prompt": [ids], "max_new_tokens": N,
  "seed": S, "eos_token": E, "priority": P, "timeout_s": T,
  "tier": "interactive"|"standard"|"batch", "stream": bool}``.
  Non-streamed: one JSON reply with the full token list. ``"stream":
  true``: a chunked response of one JSON line per token as the
  scheduler emits it, closed by a ``{"done": true, ...}`` summary
  line — time-to-first-token is the scheduler's, not the drain's. A
  full admission queue — or a tier at its admission cap — answers 429
  with a ``Retry-After`` header computed from queue depth over the
  recent retire rate (backpressure, not buffering); an unservable
  request (sampling-config mismatch, context overflow, unknown tier)
  answers 400.
* ``GET /healthz`` — liveness for load balancers and the watchdog's
  human twin.
* ``GET /stats`` — the scheduler snapshot + decode-engine compile
  stats as JSON.
* ``GET /v1/blocks[?limit=N]`` / ``POST /v1/blocks`` — the fleet
  warm-start protocol (docs/Fleet.md): GET exports the hottest prefix-
  cache entries with their KV block payloads (blake2b content keys,
  base64 ndarray leaves — int8 pools ship quantized); POST installs a
  peer's export into the local pool + prefix cache. Paged layout only
  (409 otherwise).
* ``POST /debug/profile`` — body ``{"seconds": S}``: one XLA profiler
  capture of S seconds (at most 120) through `telemetry.profile`, into
  ``TPU_YARN_PROFILE`` or ``<model_dir>/profile``; answers ``{"dir",
  "sync_perf_s", "seconds"}`` once the trace is written, 409 while
  another capture runs (docs/Observability.md).

`run_serving` is the task program body (tasks/serving.py): restore the
checkpoint exactly as batch inference does, build the shared
DecodeEngine, start the scheduler loop + frontend, advertise the
endpoint through the KV store for discovery, and serve until the
deadline/SIGTERM-drain/duration says stop.
"""

from __future__ import annotations

import base64
import json
import logging
import os
import socket
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

import numpy as np

from tf_yarn_tpu import compile_cache, telemetry
from tf_yarn_tpu.parallel.mesh import device_report
from tf_yarn_tpu.serving.request import (
    DEFAULT_TIER,
    QueueFull,
    SamplingParams,
)
from tf_yarn_tpu.serving.scheduler import SlotScheduler

_logger = logging.getLogger(__name__)


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        # Extension dtypes (bfloat16 …) resolve through ml_dtypes, which
        # jax ships; plain numpy alone raises for them.
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def encode_block_wire(wire: dict) -> dict:
    """JSON-ready copy of a scheduler `export_hot_prefixes` snapshot:
    each payload leaf becomes ``{"dtype", "shape", "b64"}`` (None
    leaves stay null) — an int8 pool's quantized bytes ship as-is, the
    4x wire saving for free."""
    out = dict(wire)
    groups = []
    for group in wire.get("groups") or []:
        leaves = []
        for leaf in group["leaves"]:
            if leaf is None:
                leaves.append(None)
                continue
            arr = np.ascontiguousarray(leaf)
            leaves.append({
                "dtype": str(arr.dtype),
                "shape": list(arr.shape),
                "b64": base64.b64encode(arr.tobytes()).decode("ascii"),
            })
        groups.append({"n_blocks": int(group["n_blocks"]),
                       "leaves": leaves})
    out["groups"] = groups
    return out


def decode_block_wire(wire: dict) -> dict:
    """Inverse of `encode_block_wire`: rebuild numpy payload leaves for
    `SlotScheduler.import_prefixes`."""
    out = dict(wire)
    groups = []
    for group in wire.get("groups") or []:
        leaves = []
        for leaf in group["leaves"]:
            if leaf is None:
                leaves.append(None)
                continue
            arr = np.frombuffer(
                base64.b64decode(leaf["b64"]), dtype=_np_dtype(leaf["dtype"])
            ).reshape(leaf["shape"])
            leaves.append(arr)
        groups.append({"n_blocks": int(group["n_blocks"]),
                       "leaves": leaves})
    out["groups"] = groups
    return out


class ServingServer:
    """The HTTP frontend over one SlotScheduler. Request handling is
    per-connection threaded (ThreadingHTTPServer), so a slow streaming
    client never blocks admissions."""

    def __init__(self, scheduler: SlotScheduler, host: str = "127.0.0.1",
                 port: int = 0, *, slo_evaluator=None, prefill_client=None,
                 profile_dir: Optional[str] = None):
        handler = _make_handler(scheduler, slo_evaluator, prefill_client,
                                profile_dir)
        self._httpd = ThreadingHTTPServer((host, port), handler)
        self._httpd.daemon_threads = True
        self._lifecycle = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self.scheduler = scheduler
        self.slo_evaluator = slo_evaluator
        self.prefill_client = prefill_client

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def endpoint(self) -> str:
        host = self._httpd.server_address[0]
        return f"{host}:{self.port}"

    def start(self) -> str:
        with self._lifecycle:
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._httpd.serve_forever, name="serving-http",
                    daemon=True,
                )
                self._thread.start()
        _logger.info("serving frontend listening on %s", self.endpoint)
        return self.endpoint

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        # Snapshot-under-lock so concurrent stop() calls can't both join
        # a half-cleared reference; the join itself stays outside the
        # lock (never block other lifecycle calls on a 10s wait).
        with self._lifecycle:
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=10.0)


def _make_handler(scheduler: SlotScheduler, slo_evaluator=None,
                  prefill_client=None, profile_dir: Optional[str] = None):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):  # stdlib logs to stderr per hit
            _logger.debug("http %s", fmt % args)

        # -- helpers ---------------------------------------------------

        def _json(self, status: int, payload: dict, headers=()) -> None:
            body = (json.dumps(payload) + "\n").encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for key, value in headers:
                self.send_header(key, value)
            self.end_headers()
            self.wfile.write(body)

        def _chunk(self, payload: dict) -> None:
            data = (json.dumps(payload) + "\n").encode()
            self.wfile.write(b"%x\r\n%s\r\n" % (len(data), data))
            self.wfile.flush()

        # -- routes ----------------------------------------------------

        def do_GET(self):
            path, _, query = self.path.partition("?")
            if path == "/v1/blocks":
                try:
                    params = urllib.parse.parse_qs(query)
                    limit = (int(params["limit"][0])
                             if "limit" in params else None)
                except (TypeError, ValueError) as exc:
                    self._json(400, {"error": f"bad limit: {exc}"})
                    return
                try:
                    wire = scheduler.export_hot_prefixes(limit)
                except ValueError as exc:
                    # A model with per-slot state: the warm-start
                    # protocol does not apply to this replica.
                    self._json(409, {"error": str(exc)})
                    return
                self._json(200, encode_block_wire(wire))
                return
            if self.path == "/healthz":
                from tf_yarn_tpu import preemption

                snap = scheduler.stats()
                # Regression (see tests): this used to report "ok" even
                # after the preemption-drain notice fired — the window
                # where a load balancer keeps sending to a replica that
                # is about to vanish. Consulting the signal flag
                # directly (not just the scheduler flag run_serving
                # sets on its next poll) closes the race to the instant
                # the notice lands; the fleet router's registry ejects
                # "draining" replicas before they stop accepting.
                draining = bool(
                    snap.get("draining")
                ) or preemption.requested()
                self._json(200, {
                    "schema_version": telemetry.STATS_SCHEMA_VERSION,
                    "status": "draining" if draining else "ok",
                    "active_slots": snap["active_slots"],
                    "queue_depth": snap["queue_depth"],
                })
            elif self.path == "/stats":
                payload = {
                    "schema_version": telemetry.STATS_SCHEMA_VERSION,
                    **scheduler.stats(),
                    "device": device_report(),
                    "compile_cache": compile_cache.stats(),
                    # What the span rings dropped since the process
                    # began: a reader of the spans knows what it lacks.
                    "spans_evicted": telemetry.get_tracer().evicted_total(),
                    "signals": telemetry.signals_block(
                        prefixes=("serving/", "slo/", "telemetry/"),
                    ),
                }
                if slo_evaluator is not None:
                    payload["slo"] = slo_evaluator.report()
                if prefill_client is not None:
                    payload["prefill_offload"] = prefill_client.stats()
                self._json(200, payload)
            elif self.path == "/metrics":
                body = telemetry.render_prometheus().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 telemetry.PROMETHEUS_CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self._json(404, {"error": f"unknown path {self.path}"})

        def do_POST(self):
            if self.path == "/v1/blocks":
                try:
                    length = int(self.headers.get("Content-Length", "0"))
                    wire = decode_block_wire(
                        json.loads(self.rfile.read(length) or b"{}")
                    )
                except Exception as exc:
                    self._json(400, {"error": f"bad block wire: {exc}"})
                    return
                try:
                    result = scheduler.import_prefixes(wire)
                except Exception as exc:
                    # Geometry mismatch (different block_size, foreign
                    # pool structure): refuse, keep serving.
                    self._json(409, {"error": str(exc)})
                    return
                self._json(200, result)
                return
            if self.path == "/debug/profile":
                self._profile()
                return
            if self.path != "/v1/generate":
                self._json(404, {"error": f"unknown path {self.path}"})
                return
            try:
                length = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(length) or b"{}")
                prompt = body["prompt"]
                params = SamplingParams(
                    max_new_tokens=int(body.get("max_new_tokens", 128)),
                    temperature=float(
                        body.get("temperature", scheduler.temperature)
                    ),
                    top_k=body.get("top_k", scheduler.top_k),
                    top_p=body.get("top_p", scheduler.top_p),
                    seed=int(body.get("seed", 0)),
                    eos_token=body.get("eos_token"),
                )
            except (KeyError, TypeError, ValueError) as exc:
                self._json(400, {"error": f"bad request: {exc}"})
                return
            # Context-overflow rejection AT ADMISSION: a prompt +
            # max_new_tokens beyond the slot KV size can never decode —
            # the engine's ValueError would otherwise first fire
            # mid-tick inside the scheduler thread. 400 here keeps the
            # serving loop untouched.
            limit = scheduler.context_limit
            if len(prompt) + params.max_new_tokens > limit:
                self._json(400, {
                    "error": (
                        f"prompt ({len(prompt)}) + max_new_tokens "
                        f"({params.max_new_tokens}) exceeds this server's "
                        f"context limit ({limit})"
                    ),
                })
                return
            timeout_s = body.get("timeout_s")
            # Two-stage dispatch (docs/Serving.md "Disaggregated
            # prefill"): pull the prompt's KV blocks from the prefill
            # tier BEFORE submitting, on THIS per-connection thread —
            # the scheduler tick never waits on the hop, and admission's
            # prefix hit then skips the shipped span. maybe_ship never
            # raises: every failure mode degrades to local prefill.
            if prefill_client is not None:
                prefill_client.maybe_ship(prompt)
            # Cross-task tracing: the router (or any caller) supplies
            # X-Request-Id; it tags this replica's submit span and the
            # scheduler's trace-ring entries, and echoes back.
            trace_id = self.headers.get("X-Request-Id") or None
            try:
                with telemetry.span(
                    "serving/submit", request_id=trace_id,
                    prompt_tokens=len(prompt),
                ) as submit_span:
                    response = scheduler.submit(
                        prompt, params,
                        priority=int(body.get("priority", 0)),
                        timeout_s=timeout_s,
                        tier=str(body.get("tier", DEFAULT_TIER)),
                        trace_id=trace_id,
                    )
                    # The program's own id where the caller sent none:
                    # one id on every span and record of this request.
                    submit_span.args["request_id"] = \
                        response.request.public_id
            except QueueFull as exc:
                # Backpressure crosses the wire as a 429 + Retry-After:
                # the client sheds or retries, the server never buffers
                # past its bound.
                self._json(
                    429,
                    {"error": str(exc), "retry_after_s": exc.retry_after_s},
                    headers=(("Retry-After",
                              str(max(1, int(exc.retry_after_s)))),),
                )
                return
            except ValueError as exc:
                self._json(400, {"error": str(exc)})
                return

            if body.get("stream"):
                self.send_response(200)
                self.send_header("Content-Type", "application/jsonl")
                self.send_header("Transfer-Encoding", "chunked")
                if trace_id:
                    self.send_header("X-Request-Id", trace_id)
                self.end_headers()
                try:
                    for token in response.tokens():
                        self._chunk({"token": token})
                    self._chunk({
                        "done": True,
                        "finish_reason": response.finish_reason,
                        "request_id": response.request.id,
                        "n_tokens": len(response.result(timeout=0.0)),
                    })
                    self.wfile.write(b"0\r\n\r\n")
                    self.wfile.flush()
                except (BrokenPipeError, ConnectionResetError):
                    _logger.info(
                        "client dropped streaming request %d",
                        response.request.id,
                    )
                return

            # Non-streamed: wait for the whole generation. The wait is
            # bounded by the request's own deadline when it has one; a
            # small margin covers the scheduler's retire latency.
            wait = timeout_s + 5.0 if timeout_s else None
            try:
                tokens = response.result(timeout=wait)
            except TimeoutError as exc:
                self._json(504, {"error": str(exc)})
                return
            self._json(200, {
                "tokens": tokens,
                "finish_reason": response.finish_reason,
                "request_id": response.request.id,
                "ttft_s": response.ttft_s,
            }, headers=(
                (("X-Request-Id", trace_id),) if trace_id else ()
            ))

        def _profile(self):
            directory = os.environ.get(telemetry.profile.PROFILE_ENV) \
                or profile_dir
            if not directory:
                self._json(409, {"error": (
                    "nowhere to write a profile: set "
                    f"{telemetry.profile.PROFILE_ENV} or serve from a "
                    "model_dir"
                )})
                return
            try:
                length = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(length) or b"{}")
                seconds = float(body.get("seconds", 1.0))
            except (TypeError, ValueError) as exc:
                self._json(400, {"error": f"bad request: {exc}"})
                return
            try:
                # On this connection's thread: the answer comes when the
                # trace is on disk, and the scheduler never waits for it.
                result = telemetry.profile.capture(directory, seconds)
            except telemetry.profile.ProfileBusy as exc:
                self._json(409, {"error": str(exc)})
                return
            self._json(200, result)

    return Handler


def _routable_host() -> str:
    """This machine's address as other hosts see it (the UDP-connect
    trick client.py uses for the coordinator; no packet is sent)."""
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as sock:
            sock.connect(("8.8.8.8", 80))
            return sock.getsockname()[0]
    except OSError:
        return socket.getfqdn()


def advertised_endpoint(bind_host: str, port: int) -> str:
    """The address peers should dial for a frontend bound on
    `bind_host:port` — wildcard/loopback binds advertise a routable
    interface instead."""
    if bind_host in ("0.0.0.0", "", "::"):
        return f"{_routable_host()}:{port}"
    return f"{bind_host}:{port}"


def run_serving(experiment, runtime=None) -> dict:
    """Task body for a ServingExperiment: restore → engine → scheduler →
    frontend → advertise → serve. Returns the final stats snapshot."""
    from tf_yarn_tpu import event, fs as fs_lib, inference, preemption
    from tf_yarn_tpu.models.decode_engine import get_engine

    telemetry_task = "serving"
    if runtime is not None:
        telemetry_task = getattr(
            runtime, "task",
            f"{runtime.task_key.type}:{runtime.task_key.id}",
        )
    telemetry.enable_env_jsonl(telemetry_task)
    fs_lib.check_model_dir_placement(experiment.model_dir)
    # Tensor-parallel decode: build the replica's mesh BEFORE the
    # restore, so a device shortfall fails in milliseconds ("need N
    # devices, have M"), not after minutes of weight loading.
    mesh = None
    mesh_spec = getattr(experiment, "mesh_spec", None)
    if mesh_spec is not None and mesh_spec.total_devices > 1:
        from tf_yarn_tpu.parallel import mesh as mesh_lib

        with telemetry.span("serving/build_mesh",
                            devices=mesh_spec.total_devices):
            mesh = mesh_lib.build_mesh(
                mesh_spec,
                mesh_lib.select_devices(mesh_spec.total_devices),
            )
        _logger.info(
            "serving tensor-parallel: tp=%d over %d devices",
            mesh_spec.tp, mesh_spec.total_devices,
        )
    with telemetry.span("serving/restore_params"):
        variables, step = inference._restore_params(
            experiment.model_dir, experiment.step
        )
    if mesh is not None:
        # The sharded restore path: logical-axis placements recovered
        # from an abstract re-init, one device_put per leaf.
        with telemetry.span("serving/shard_params"):
            variables = inference.shard_restored_params(
                experiment.model, variables, mesh
            )
    engine = get_engine(experiment.model, mesh=mesh)
    # Before the first program is built: each matrix in the type the
    # step reads it in, the wide originals let go (docs/Serving.md).
    variables = engine.hold_params(variables)
    scheduler = SlotScheduler(
        engine,
        variables,
        max_slots=experiment.max_slots,
        temperature=experiment.temperature,
        top_k=experiment.top_k,
        top_p=experiment.top_p,
        queue_capacity=experiment.queue_capacity,
        retry_after_s=experiment.retry_after_s,
        block_size=experiment.block_size,
        num_blocks=experiment.num_blocks,
        prefix_cache_capacity=experiment.prefix_cache_capacity,
        spec_k=experiment.spec_k,
        spec_draft=experiment.spec_draft,
        decode_attention=experiment.decode_attention,
        prefill_chunk=experiment.prefill_chunk,
        prefill_budget_per_tick=experiment.prefill_budget_per_tick,
        kv_host_blocks=experiment.kv_host_blocks,
        tier_caps=experiment.tier_caps,
    )
    slo_evaluator = None
    if getattr(experiment, "slo", None):
        slo_evaluator = telemetry.SloEvaluator(
            telemetry.parse_slo(experiment.slo)
        )
    prefill_client = None
    if getattr(experiment, "prefill_tier", None) is not None:
        from tf_yarn_tpu.serving.prefill import (
            PrefillClient,
            parse_prefill_tier,
        )

        prefill_client = PrefillClient(
            parse_prefill_tier(experiment.prefill_tier),
            scheduler,
            block_size=experiment.block_size,
            kv=getattr(runtime, "kv", None),
        )
    server = ServingServer(
        scheduler, experiment.host, experiment.port,
        slo_evaluator=slo_evaluator, prefill_client=prefill_client,
        profile_dir=(os.path.join(experiment.model_dir, "profile")
                     if experiment.model_dir else None),
    )
    scheduler.start()
    endpoint = server.start()
    advertised = advertised_endpoint(experiment.host, server.port)
    if runtime is not None:
        # Discovery: clients (and the driver's one-shot logger) read the
        # endpoint from the KV store instead of guessing ports.
        event.serving_endpoint_event(runtime.kv, runtime.task, advertised)
    _logger.info(
        "serving ckpt-%d on %s (advertised %s): max_slots=%d, queue=%d",
        step, endpoint, advertised, experiment.max_slots,
        experiment.queue_capacity,
    )

    deadline = (
        time.monotonic() + experiment.serve_seconds
        if experiment.serve_seconds is not None else None
    )
    from tf_yarn_tpu.resilience import chaos

    serve_began = time.monotonic()
    try:
        while True:
            if chaos.on_replica_poll(
                telemetry_task, time.monotonic() - serve_began
            ):
                # Injected preemption notice (TPU_YARN_FAULT
                # preempt_replica_at): same drain path as the real flag.
                preemption.request()
            if preemption.requested():
                _logger.info("serving task draining on preemption notice")
                scheduler.drain()  # surfaced in /healthz + /stats
                break
            if deadline is not None and time.monotonic() >= deadline:
                _logger.info(
                    "serve_seconds=%.1f elapsed; shutting down",
                    experiment.serve_seconds,
                )
                break
            if slo_evaluator is not None:
                slo_evaluator.maybe_evaluate()
            time.sleep(0.2)
    finally:
        server.stop()
        scheduler.close()
        stats = {"endpoint": advertised, "ckpt_step": step,
                 **scheduler.stats()}
        _logger.info("serving done: %s", stats)
        telemetry.flush_metrics(
            telemetry.get_registry(),
            kv=getattr(runtime, "kv", None),
            task=telemetry_task if runtime is not None else None,
        )
        telemetry.export_trace(telemetry_task)
    return stats
