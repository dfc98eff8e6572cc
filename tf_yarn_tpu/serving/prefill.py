"""Disaggregated prefill/decode: the `prefill` task tier.

Prefill is compute-bound and bursty; decode is memory-bound and steady.
Co-locating them sizes every replica for both. This module splits them
across machines over the content-addressed block wire PR 19 built:

* :class:`PrefillWorker` — a DecodeEngine + params + a PRIVATE paged
  pool/prefix cache that runs ONLY bucketed prefill (no decode loop, no
  slot grid): `prefill_prompt` reuses `DecodeEngine.prefill` +
  `pack_prefill` and returns the whole-block span as a `/v1/blocks`-
  style wire dict (blake2b content keys, payload leaves in the pool's
  dtype — an int8 pool's quantized blocks ride as int8, the ~3x wire
  saving for free).
* :class:`PrefillServer` — the HTTP frontend (``POST /v1/prefill``,
  plus ``/healthz`` / ``/stats`` / ``/metrics`` so the fleet registry,
  monitor and autoscaler treat prefill replicas like any other kind).
* :class:`PrefillClient` — the decode-side orchestrator: `/v1/generate`
  still lands on a generate replica, which PULLS from the prefill tier
  (two-stage dispatch) — ship the prompt, install the returned blocks
  as prefix-cache entries via `SlotScheduler.import_prefixes`, and let
  admission's prefix hit skip the shipped span. EVERY failure mode
  (no replica advertised, replica preempted mid-ship, bad wire, import
  refusal) degrades to local prefill — never an error, and streams stay
  bit-identical because the shipped blocks hold the exact KV local
  prefill would have computed.
* :func:`run_prefill` — the `prefill` task body (tasks/prefill.py).

Locking: the worker's pool/cache bookkeeping (serving/paging.py is
lock-free by design — scheduler-thread-only there) is guarded by ONE
worker lock, because PrefillServer handles requests on per-connection
threads. The client guards its memo/backoff/counter state with its own
lock and keeps HTTP I/O outside it, so a slow ship never serializes
other handler threads.
"""

from __future__ import annotations

import dataclasses
import http.client
import json
import logging
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from tf_yarn_tpu import telemetry
from tf_yarn_tpu.serving.paging import (
    TRASH_BLOCK,
    BlockPool,
    PrefixCache,
    prefix_keys,
)
from tf_yarn_tpu.serving.scheduler import _none_leaf, _to_host
from tf_yarn_tpu.serving.server import (
    advertised_endpoint,
    decode_block_wire,
    encode_block_wire,
)

_logger = logging.getLogger(__name__)

# Shipping a prompt costs one HTTP round trip + one import control op;
# below this many prompt tokens the local prefill is cheaper than the
# hop (docs/Serving.md "Offload-threshold tuning").
DEFAULT_OFFLOAD_THRESHOLD = 64

# The client-side memo of shipped content keys is bounded; on overflow
# it resets (worst case: a prefix re-ships once).
_SHIPPED_MEMO_CAP = 4096


@dataclasses.dataclass(frozen=True)
class PrefillTierConfig:
    """`ServingExperiment(prefill_tier=...)` knobs (docs/Serving.md)."""

    # Prompts shorter than this many tokens never pay the network hop.
    offload_threshold: int = DEFAULT_OFFLOAD_THRESHOLD
    # Static prefill endpoint ("host:port"). None: discover via the
    # `{task}/prefill_endpoint` KV advertisement.
    endpoint: Optional[str] = None
    # Per-ship HTTP budget; a slower replica is treated as down.
    timeout_s: float = 10.0
    # After a failed ship the tier is quarantined this long — every
    # request in the window prefills locally without re-dialing.
    backoff_s: float = 5.0
    # How long a KV endpoint resolution (including "none advertised")
    # is trusted before re-scanning.
    resolve_ttl_s: float = 2.0
    # Pool size for PREFILL replicas (run_prefill); None derives a
    # default from the block-table width.
    num_blocks: Optional[int] = None

    def __post_init__(self):
        if self.offload_threshold < 1:
            raise ValueError(
                f"offload_threshold must be >= 1, got "
                f"{self.offload_threshold}"
            )
        for knob in ("timeout_s", "backoff_s", "resolve_ttl_s"):
            if not float(getattr(self, knob)) > 0:
                raise ValueError(
                    f"{knob} must be > 0, got {getattr(self, knob)}"
                )
        if self.num_blocks is not None and self.num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2, got {self.num_blocks}"
            )


def parse_prefill_tier(spec) -> PrefillTierConfig:
    """Validate a ``prefill_tier=`` experiment knob (dict of
    `PrefillTierConfig` fields, or a ready config). Raises ValueError
    naming the offending key, in the experiment-validation style."""
    if isinstance(spec, PrefillTierConfig):
        return spec
    if not isinstance(spec, dict):
        raise ValueError(
            "prefill_tier must be a dict of PrefillTierConfig fields "
            f"(or a PrefillTierConfig), got {spec!r}"
        )
    try:
        return PrefillTierConfig(**spec)
    except TypeError as exc:
        raise ValueError(str(exc)) from None


# --------------------------------------------------------------------------
# The prefill replica: worker + HTTP frontend + task body
# --------------------------------------------------------------------------

class PrefillWorker:
    """Bucketed prefill into a private paged pool, exported as wire.

    One lock serializes all pool/cache mutation: requests arrive on
    per-connection HTTP threads and serving/paging.py carries no
    locking of its own. Repeated prompts (or prompts sharing a prefix)
    hit the worker's own PrefixCache and export without recomputing.
    """

    def __init__(self, engine, params, *, block_size: int,
                 num_blocks: Optional[int] = None,
                 prefix_cache_capacity: int = 256,
                 max_seq_len: Optional[int] = None):
        self.engine = engine
        self.params = params
        self._block_size = int(block_size)
        if max_seq_len is None:
            config = getattr(getattr(engine, "model", None), "config", None)
            max_seq_len = getattr(
                config, "max_seq_len", getattr(engine, "max_seq_len", None)
            )
        if max_seq_len is None:
            raise ValueError(
                "PrefillWorker needs max_seq_len — from "
                "engine.model.config.max_seq_len or the kwarg"
            )
        self._max_seq_len = int(max_seq_len)
        if self._max_seq_len % self._block_size:
            raise ValueError(
                f"block_size={block_size} must divide "
                f"max_seq_len={max_seq_len}"
            )
        self._blocks_per_slot = self._max_seq_len // self._block_size
        if num_blocks is None:
            # Room for a few distinct max-length prompts' blocks on top
            # of the reserved trash block; the prefix cache recycles the
            # rest under LRU pressure.
            num_blocks = 4 * self._blocks_per_slot + 1
        self._lock = threading.Lock()
        self._pool = engine.make_paged_pool(params, num_blocks, block_size)
        self._blocks = BlockPool(num_blocks, block_size)
        self._prefix = PrefixCache(self._blocks, prefix_cache_capacity)
        self._registry = telemetry.get_registry()
        self._requests = 0
        self._cache_hits = 0
        self._exported_blocks = 0
        self._draining = False

    # -- request path (HTTP handler threads) -------------------------------

    def prefill_prompt(self, prompt) -> Dict:
        """Run bucketed prefill for `prompt` and return the block wire
        for its whole-block span (empty wire when the bucket leaves no
        whole block, or the pool cannot cover the request — the decode
        side then simply prefills locally)."""
        prompt = [int(token) for token in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > self._max_seq_len:
            raise ValueError(
                f"prompt ({len(prompt)} tokens) exceeds this prefill "
                f"replica's max_seq_len ({self._max_seq_len})"
            )
        start = time.monotonic()
        with self._lock:
            wire, outcome = self._prefill_locked(prompt)
        self._registry.counter(
            "serving/prefill_requests_total", outcome=outcome,
        ).inc()
        self._registry.histogram("serving/prefill_build_seconds").observe(
            time.monotonic() - start
        )
        return wire

    def _prefill_locked(self, prompt):
        self._requests += 1
        # The floor rule: the tier ships whole blocks of a bucket it kept
        # whole, and the decode side replays the rest.
        prefill_len, _kept = self.engine.slot_prefill_len(len(prompt))
        whole = prefill_len // self._block_size
        if whole < 1:
            return self._empty_wire(), "short"
        covered, hit_ids = self._prefix.lookup(
            prompt, whole * self._block_size
        )
        if covered == whole * self._block_size:
            # lookup does not retain; protect the blocks for the export.
            ids = list(hit_ids)
            self._blocks.retain(ids)
            self._cache_hits += 1
            outcome = "cached"
        else:
            ids = self._compute_blocks(prompt, prefill_len)
            if ids is None:
                return self._empty_wire(), "pool_full"
            outcome = "computed"
        try:
            wire = self._export(prompt, whole, ids[:whole])
        finally:
            # Drop this request's references; the prefix cache keeps the
            # whole blocks alive for the next sharer, a partial pack
            # tail frees immediately.
            self._blocks.release(ids)
        self._exported_blocks += wire["n_blocks"]
        return wire, outcome

    def _compute_blocks(self, prompt, prefill_len: int):
        n_pack = -(-prefill_len // self._block_size)
        if n_pack > self._blocks.free_blocks:
            self._prefix.evict_for(n_pack)
        ids = self._blocks.allocate(n_pack)
        if ids is None:
            return None
        # Exactly the scheduler's blocking-admission prefill (bit-for-
        # bit the KV a local prefill would compute with these params).
        row_cache, _logits = self.engine.prefill(
            self.params,
            np.asarray(prompt[:prefill_len], np.int32)[None, :],
        )
        self._pool = self.engine.pack_prefill(
            self._pool, np.asarray(ids, np.int32), row_cache,
            prefill_len, self._block_size,
        )
        self._prefix.register(prompt, prefill_len, ids)
        return ids

    def _export(self, prompt, whole: int, ids) -> Dict:
        """The `/v1/blocks` wire for one prompt's whole-block prefix:
        one entry per prefix length, LONGEST FIRST so the receiver's
        hot-first clipping keeps the full span under pool pressure."""
        keys = prefix_keys(prompt, self._block_size, whole)
        index = {block: j for j, block in enumerate(ids)}
        entries = [
            {"key": keys[k - 1].hex(),
             "blocks": [index[block] for block in ids[:k]]}
            for k in range(whole, 0, -1)
        ]
        width = self._blocks_per_slot
        groups: List[Dict] = []
        for group_start in range(0, len(ids), width):
            chunk = list(ids[group_start:group_start + width])
            ids_arr = np.full((width,), TRASH_BLOCK, np.int32)
            ids_arr[:len(chunk)] = chunk
            payload = _to_host(self.engine.extract_blocks(
                self.params, self._pool, ids_arr, self._block_size
            ))
            leaves, _ = jax.tree_util.tree_flatten(
                payload, is_leaf=_none_leaf
            )
            groups.append({"n_blocks": len(chunk), "leaves": leaves})
        return {
            "schema_version": 1,
            "block_size": self._block_size,
            "group_width": width,
            "n_blocks": len(ids),
            "entries": entries,
            "groups": groups,
        }

    def _empty_wire(self) -> Dict:
        return {
            "schema_version": 1,
            "block_size": self._block_size,
            "group_width": self._blocks_per_slot,
            "n_blocks": 0,
            "entries": [],
            "groups": [],
        }

    # -- observability ------------------------------------------------------

    def drain(self) -> None:
        with self._lock:
            self._draining = True

    def stats(self) -> Dict:
        with self._lock:
            snap = {
                "kind": "prefill",
                "draining": self._draining,
                "prefill_requests": self._requests,
                "prefill_cache_hits": self._cache_hits,
                "exported_blocks": self._exported_blocks,
                "block_size": self._block_size,
                "block_pool": {
                    "num_blocks": self._blocks.num_blocks,
                    "free_blocks": self._blocks.free_blocks,
                    "used_blocks": self._blocks.used_blocks,
                },
                "prefix_cache": {
                    "entries": self._prefix.entries,
                    "cached_blocks": self._prefix.cached_blocks,
                    "hits": self._prefix.hits,
                    "misses": self._prefix.misses,
                },
            }
        engine_stats = getattr(self.engine, "stats", None)
        if isinstance(engine_stats, dict):
            snap["decode_engine"] = dict(engine_stats)
        return snap


class PrefillServer:
    """HTTP frontend over one PrefillWorker (per-connection threaded,
    like ServingServer — a slow decode replica pulling a large wire
    never blocks other ships)."""

    def __init__(self, worker: PrefillWorker, host: str = "127.0.0.1",
                 port: int = 0):
        handler = _make_prefill_handler(worker)
        self._httpd = ThreadingHTTPServer((host, port), handler)
        self._httpd.daemon_threads = True
        self._lifecycle = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self.worker = worker

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
                    target=self._httpd.serve_forever, name="prefill-http",
                    daemon=True,
                )
                self._thread.start()
        _logger.info("prefill frontend listening on %s", self.endpoint)
        return self.endpoint

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        with self._lifecycle:
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=10.0)


def _make_prefill_handler(worker: PrefillWorker):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):
            _logger.debug("http %s", fmt % args)

        def _json(self, status: int, payload: dict) -> None:
            body = (json.dumps(payload) + "\n").encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path == "/healthz":
                from tf_yarn_tpu import preemption

                snap = worker.stats()
                draining = bool(
                    snap.get("draining")
                ) or preemption.requested()
                # queue_depth/active_slots keep the registry's generic
                # load accounting happy; a prefill replica has neither.
                self._json(200, {
                    "schema_version": telemetry.STATS_SCHEMA_VERSION,
                    "status": "draining" if draining else "ok",
                    "kind": "prefill",
                    "queue_depth": 0,
                    "active_slots": 0,
                })
            elif self.path == "/stats":
                self._json(200, {
                    "schema_version": telemetry.STATS_SCHEMA_VERSION,
                    **worker.stats(),
                    "signals": telemetry.signals_block(
                        prefixes=("serving/", "slo/", "telemetry/"),
                    ),
                })
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
            if self.path != "/v1/prefill":
                self._json(404, {"error": f"unknown path {self.path}"})
                return
            try:
                length = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(length) or b"{}")
                prompt = [int(token) for token in body["prompt"]]
            except (KeyError, TypeError, ValueError) as exc:
                self._json(400, {"error": f"bad request: {exc}"})
                return
            try:
                wire = worker.prefill_prompt(prompt)
            except ValueError as exc:
                self._json(400, {"error": str(exc)})
                return
            self._json(200, encode_block_wire(wire))

    return Handler


def run_prefill(experiment, runtime=None) -> dict:
    """Task body for the `prefill` task type: restore → engine →
    PrefillWorker → frontend → advertise `{task}/prefill_endpoint` →
    serve until preemption-drain/deadline. Returns the final stats."""
    from tf_yarn_tpu import event, fs as fs_lib, inference, preemption
    from tf_yarn_tpu.models.decode_engine import get_engine

    tier = parse_prefill_tier(experiment.prefill_tier or {})
    telemetry_task = "prefill"
    if runtime is not None:
        telemetry_task = getattr(
            runtime, "task",
            f"{runtime.task_key.type}:{runtime.task_key.id}",
        )
    telemetry.enable_env_jsonl(telemetry_task)
    fs_lib.check_model_dir_placement(experiment.model_dir)
    from tf_yarn_tpu.parallel import mesh as mesh_lib

    mesh_lib.select_devices()  # the platform this task was started for
    mesh = None
    mesh_spec = getattr(experiment, "mesh_spec", None)
    if mesh_spec is not None and mesh_spec.total_devices > 1:
        with telemetry.span("prefill/build_mesh",
                            devices=mesh_spec.total_devices):
            mesh = mesh_lib.build_mesh(
                mesh_spec,
                mesh_lib.select_devices(mesh_spec.total_devices),
            )
    with telemetry.span("prefill/restore_params"):
        variables, step = inference._restore_params(
            experiment.model_dir, experiment.step
        )
    if mesh is not None:
        with telemetry.span("prefill/shard_params"):
            variables = inference.shard_restored_params(
                experiment.model, variables, mesh
            )
    engine = get_engine(experiment.model, mesh=mesh)
    variables = engine.hold_params(variables)
    worker = PrefillWorker(
        engine, variables,
        block_size=experiment.block_size,
        num_blocks=tier.num_blocks or experiment.num_blocks,
        prefix_cache_capacity=experiment.prefix_cache_capacity,
    )
    server = PrefillServer(worker, experiment.host, experiment.port)
    endpoint = server.start()
    advertised = advertised_endpoint(experiment.host, server.port)
    if runtime is not None:
        event.prefill_endpoint_event(runtime.kv, runtime.task, advertised)
    _logger.info(
        "prefill ckpt-%d on %s (advertised %s): block_size=%d",
        step, endpoint, advertised, experiment.block_size,
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
                preemption.request()
            if preemption.requested():
                _logger.info("prefill task draining on preemption notice")
                worker.drain()  # surfaced in /healthz + /stats
                break
            if deadline is not None and time.monotonic() >= deadline:
                _logger.info(
                    "serve_seconds=%.1f elapsed; shutting down",
                    experiment.serve_seconds,
                )
                break
            time.sleep(0.2)
    finally:
        server.stop()
        stats = {"endpoint": advertised, "ckpt_step": step,
                 **worker.stats()}
        _logger.info("prefill done: %s", stats)
        telemetry.flush_metrics(
            telemetry.get_registry(),
            kv=getattr(runtime, "kv", None),
            task=telemetry_task if runtime is not None else None,
        )
        telemetry.export_trace(telemetry_task)
    return stats


# --------------------------------------------------------------------------
# The decode-side orchestrator
# --------------------------------------------------------------------------

def _http_post_prefill(endpoint: str, prompt: List[int],
                       timeout_s: float) -> bytes:
    """POST the prompt to a prefill replica; raw response body on 200,
    raises (ConnectionError family) otherwise. The default transport —
    tests inject fakes through the ``post=`` seam."""
    host, _, port = endpoint.rpartition(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout_s)
    try:
        conn.request(
            "POST", "/v1/prefill", json.dumps({"prompt": prompt}),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        payload = resp.read()
        if resp.status != 200:
            raise ConnectionError(
                f"/v1/prefill on {endpoint} answered {resp.status}"
            )
        return payload
    finally:
        conn.close()


def kv_prefill_resolver(kv) -> Callable[[], Optional[str]]:
    """Discover prefill replicas the way the fleet registry does — scan
    KV for ``*/prefill_endpoint`` advertisements, skip tombstoned tasks
    — and hand out endpoints round-robin across advertisers."""
    state = {"next": 0}

    def resolve() -> Optional[str]:
        from tf_yarn_tpu import event

        suffix = f"/{event.PREFILL_ENDPOINT}"
        try:
            keys = sorted(
                key for key in kv.keys("") if key.endswith(suffix)
            )
        except Exception:
            return None
        endpoints = []
        for key in keys:
            task = key[:-len(suffix)]
            try:
                stopped = (
                    kv.get_str(f"{task}/{event.HEARTBEAT_STOPPED}")
                    is not None
                    or kv.get_str(f"{task}/{event.STOP}") is not None
                )
                endpoint = None if stopped else kv.get_str(key)
            except Exception:
                _logger.debug(
                    "skipping unreadable prefill advertisement %s",
                    key, exc_info=True,
                )
                continue
            if endpoint:
                endpoints.append(endpoint)
        if not endpoints:
            return None
        pick = endpoints[state["next"] % len(endpoints)]
        state["next"] += 1
        return pick

    return resolve


class PrefillClient:
    """Two-stage dispatch from a decode replica: ship a long prompt to
    the prefill tier, install the returned blocks, and let the local
    admission's prefix hit skip the shipped span.

    `maybe_ship` NEVER raises and never blocks the scheduler tick — it
    runs on the frontend's per-connection handler thread, before
    `scheduler.submit`; the import itself rides the scheduler control
    path. The degradation ladder (docs/Serving.md): below-threshold →
    no hop; no replica advertised → local prefill; ship/import failure
    → quarantine the tier `backoff_s` and prefill locally; in every
    case the stream is bit-identical to local-prefill serving.
    """

    def __init__(self, config: PrefillTierConfig, scheduler, *,
                 block_size: int, kv=None, resolver=None,
                 clock=time.monotonic, post=None):
        self._config = config
        self._scheduler = scheduler
        self._block_size = int(block_size)
        self._resolver = resolver
        if self._resolver is None and kv is not None:
            self._resolver = kv_prefill_resolver(kv)
        self._clock = clock
        self._post = post or _http_post_prefill
        self._lock = threading.Lock()
        self._shipped_keys: set = set()
        self._quarantine_until = 0.0
        self._resolved: Optional[str] = None
        self._resolved_at: Optional[float] = None
        self._ships = 0
        self._shipped_blocks = 0
        self._shipped_wire_bytes = 0
        self._local_fallbacks = 0
        self._registry = telemetry.get_registry()

    # -- the two-stage dispatch (frontend handler threads) ------------------

    def maybe_ship(self, prompt) -> str:
        """Best-effort prefill offload for one request; returns the
        outcome label (the `serving/prefill_offload_total` counter's
        ``outcome=``). Never raises."""
        try:
            return self._ship([int(token) for token in prompt])
        except Exception:
            _logger.warning(
                "prefill offload failed unexpectedly; prefilling locally",
                exc_info=True,
            )
            self._count("error", fallback=True)
            return "error"

    def _ship(self, prompt: List[int]) -> str:
        config = self._config
        max_k = max(0, (len(prompt) - 1) // self._block_size)
        if len(prompt) < config.offload_threshold or max_k < 1:
            # Not an offload candidate — no counter: short prompts are
            # the common case and would drown the outcome signal.
            return "below_threshold"
        # One content key identifies the longest whole-block prefix this
        # prompt could ship (the same blake2b chain the caches use on
        # both sides) — once shipped, later requests hit the LOCAL
        # prefix cache and the hop is pure waste.
        key = prefix_keys(prompt, self._block_size, max_k)[-1]
        now = self._clock()
        with self._lock:
            if key in self._shipped_keys:
                skip = "already_shipped"
            elif now < self._quarantine_until:
                skip = "backoff"
            else:
                skip = None
        if skip is not None:
            self._count(skip, fallback=(skip == "backoff"))
            return skip
        endpoint = self._resolve(now)
        if endpoint is None:
            # Scale-from-zero (or scaled-to-zero) tier: immediate local
            # prefill, never a 503.
            self._count("no_replica", fallback=True)
            return "no_replica"
        started = self._clock()
        try:
            payload = self._post(endpoint, prompt, config.timeout_s)
            wire = decode_block_wire(json.loads(payload))
        except Exception as exc:
            # Replica preempted / unreachable / bad wire mid-ship: the
            # request prefills locally and the tier backs off.
            _logger.info(
                "prefill replica %s failed (%s); prefilling locally",
                endpoint, exc,
            )
            with self._lock:
                self._quarantine_until = self._clock() + config.backoff_s
                self._resolved = None
                self._resolved_at = None
            self._count("ship_failed", fallback=True)
            return "ship_failed"
        if not wire.get("n_blocks"):
            # The replica could not help (bucket left no whole block,
            # pool exhausted): local prefill, no quarantine — the tier
            # is healthy, this prompt just is not shippable right now.
            self._count("empty_wire", fallback=True)
            return "empty_wire"
        try:
            result = self._scheduler.import_prefixes(wire)
        except Exception as exc:
            _logger.warning(
                "shipped prefix import refused (%s); prefilling locally",
                exc,
            )
            self._count("import_failed", fallback=True)
            return "import_failed"
        elapsed = self._clock() - started
        imported = int(result.get("imported_blocks", 0))
        with self._lock:
            if len(self._shipped_keys) >= _SHIPPED_MEMO_CAP:
                self._shipped_keys.clear()
            self._shipped_keys.add(key)
            self._ships += 1
            self._shipped_blocks += imported
            self._shipped_wire_bytes += len(payload)
        self._registry.counter("serving/shipped_blocks_total").inc(imported)
        self._registry.counter(
            "serving/shipped_wire_bytes_total"
        ).inc(len(payload))
        self._registry.histogram(
            "serving/prefill_ship_seconds"
        ).observe(max(0.0, elapsed))
        self._count("shipped")
        return "shipped"

    def _resolve(self, now: float) -> Optional[str]:
        config = self._config
        if config.endpoint:
            return config.endpoint
        if self._resolver is None:
            return None
        with self._lock:
            if (self._resolved_at is not None
                    and now - self._resolved_at < config.resolve_ttl_s):
                return self._resolved
        try:
            endpoint = self._resolver()
        except Exception:
            endpoint = None
        with self._lock:
            self._resolved = endpoint
            self._resolved_at = now
        return endpoint

    def _count(self, outcome: str, fallback: bool = False) -> None:
        self._registry.counter(
            "serving/prefill_offload_total", outcome=outcome,
        ).inc()
        if fallback:
            with self._lock:
                self._local_fallbacks += 1

    def stats(self) -> Dict:
        with self._lock:
            return {
                "offload_threshold": self._config.offload_threshold,
                "ships": self._ships,
                "shipped_blocks": self._shipped_blocks,
                "shipped_wire_bytes": self._shipped_wire_bytes,
                "local_fallbacks": self._local_fallbacks,
            }
