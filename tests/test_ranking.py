"""Online ranking subsystem: RankEngine buckets, micro-batch scheduler,
HTTP frontend, path-aware fleet routing, and the `rank` task body.

The parity contract pinned here (docs/Ranking.md "Correctness"): served
scores are bitwise-equal to a DIRECT JITTED forward of the same model —
`jax.jit(model.apply)` — on the unpadded batch. Ceil-padding to a batch
bucket must be bit-invisible because every DLRM op is row-independent.
(Eager `model.apply` is NOT the reference: XLA fuses the jitted program
differently and the two drift by ~1 ulp, which is exactly why the
engine's compiled program is compared against another compiled program.)
"""

import http.client
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import flax.linen as nn  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tf_yarn_tpu import event  # noqa: E402
from tf_yarn_tpu.coordination.kv import InProcessKV  # noqa: E402
from tf_yarn_tpu.models.dlrm import DLRM, DLRMConfig  # noqa: E402
from tf_yarn_tpu.models.rank_engine import RankEngine  # noqa: E402
from tf_yarn_tpu.ranking.scheduler import (  # noqa: E402
    FINISH_COMPLETE,
    MicroBatchScheduler,
)
from tf_yarn_tpu.ranking.server import RankServer, run_ranking  # noqa: E402
from tf_yarn_tpu.serving.request import (  # noqa: E402
    FINISH_DEADLINE,
    FINISH_ERROR,
    QueueFull,
)

# float32 end to end so "bitwise equal" is meaningful across programs.
F32 = DLRMConfig.tiny(dtype=jnp.float32)


def _init_params(model, seed=0):
    cfg = model.config
    cat = jnp.zeros((1, len(cfg.table_sizes)), jnp.int32)
    args = (cat,) if not cfg.n_dense else (
        cat, jnp.zeros((1, cfg.n_dense), jnp.float32)
    )
    return nn.meta.unbox(model.init(jax.random.PRNGKey(seed), *args))


def _features(batch, seed=0, cfg=F32):
    rng = np.random.RandomState(seed)
    cat = rng.randint(
        0, max(cfg.table_sizes), (batch, len(cfg.table_sizes))
    ).astype(np.int32)
    dense = rng.standard_normal((batch, cfg.n_dense)).astype(np.float32)
    return cat, dense


def _direct_scores(model, params, cat, dense=None):
    """The parity reference: a jitted direct forward (module docstring)."""
    args = (jnp.asarray(cat),)
    if dense is not None:
        args = args + (jnp.asarray(dense),)
    out = jax.jit(model.apply)(params, *args)
    return np.asarray(out, np.float32).squeeze(-1)


def _tree_nbytes(params):
    return sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(params)
    )


# --------------------------------------------------------------------------
# RankEngine: bucket grid, compile cache, padding parity
# --------------------------------------------------------------------------

def test_rank_engine_requires_table_config():
    class NotADLRM:
        pass

    with pytest.raises(ValueError, match="config.table_sizes"):
        RankEngine(NotADLRM())


def test_select_bucket_ceils_to_grid():
    engine = RankEngine(DLRM(F32), batch_buckets=(4, 8, 16))
    assert engine.select_bucket(1) == 4
    assert engine.select_bucket(4) == 4
    assert engine.select_bucket(5) == 8
    assert engine.select_bucket(16) == 16
    # Beyond the grid: the exact size compiles (logged, counted).
    assert engine.select_bucket(17) == 17


def test_exactly_one_compile_per_bucket():
    """The compiled-program discipline: batches 1, 3, 4 share the one
    bucket-4 executable; only crossing a bucket boundary compiles again;
    an off-grid batch compiles its exact shape and says so in stats."""
    model = DLRM(F32)
    params = _init_params(model)
    engine = RankEngine(model, batch_buckets=(4, 8))

    for batch in (1, 3, 4):
        cat, dense = _features(batch, seed=batch)
        assert engine.rank(params, cat, dense).shape == (batch,)
    assert engine.stats["forward_compiles"] == 1
    assert engine.stats["forward_cache_hits"] == 2
    assert engine.stats["calls"] == 3

    cat, dense = _features(5, seed=5)
    engine.rank(params, cat, dense)
    assert engine.stats["forward_compiles"] == 2
    assert engine.stats["unbucketed_shapes"] == 0

    cat, dense = _features(9, seed=9)
    engine.rank(params, cat, dense)
    assert engine.stats["forward_compiles"] == 3
    assert engine.stats["unbucketed_shapes"] == 1

    keys = engine.program_keys()["forward"]
    assert len(keys) == 3
    assert sorted(key[0] for key in keys) == [4, 8, 9]


# Across compiled programs (another bucket, the unpadded direct forward) XLA
# may add a row's products in another order: a unit or two in the last place
# of float32 (6e-8 at these scores). A bfloat16 forward is 1e-3 away.
F32_ACROSS_PROGRAMS = dict(rtol=0, atol=1e-6)


def test_ceil_padding_is_bitwise_invisible():
    """One program, so bit for bit: whatever fills the rows of a bucket
    past the batch (the engine's zeros, or other requests' rows), the
    batch's own rows come out with the same bits."""
    model = DLRM(F32)
    params = _init_params(model)
    engine = RankEngine(model, batch_buckets=(8,))

    for batch in (1, 3, 5):
        cat, dense = _features(batch, seed=batch)
        got = engine.rank(params, cat, dense)
        assert got.dtype == np.float32 and got.shape == (batch,)
        for seed in (100, 101):
            fill_cat, fill_dense = _features(8 - batch, seed=seed)
            full = engine.rank(params, np.concatenate([cat, fill_cat]),
                               np.concatenate([dense, fill_dense]))
            np.testing.assert_array_equal(full[:batch], got)
    assert engine.stats["forward_compiles"] == 1  # one program scored all


def test_ceil_padding_matches_the_direct_forward_in_float32():
    """Different compiled programs, so a float32 reference and a tolerance
    that a lower precision fails: engine scores on every batch size against
    the jitted direct forward of the unpadded batch, and the same rows
    through DIFFERENT buckets."""
    model = DLRM(F32)
    params = _init_params(model)
    engine = RankEngine(model, batch_buckets=(8,))

    for batch in (1, 3, 5):
        cat, dense = _features(batch, seed=batch)
        want = _direct_scores(model, params, cat, dense)
        np.testing.assert_allclose(
            engine.rank(params, cat, dense), want, **F32_ACROSS_PROGRAMS)
        # the tolerance tells precisions apart
        narrow = _direct_scores(
            DLRM(DLRMConfig.tiny(dtype=jnp.bfloat16)), params, cat, dense)
        assert np.abs(narrow - want).max() > 100 * F32_ACROSS_PROGRAMS["atol"]

    # Cross-bucket: bucket-4 vs bucket-8 executables.
    small = RankEngine(model, batch_buckets=(4,))
    cat, dense = _features(3, seed=42)
    np.testing.assert_allclose(
        small.rank(params, cat, dense), engine.rank(params, cat, dense),
        **F32_ACROSS_PROGRAMS)


def test_feature_validation_messages():
    model = DLRM(F32)
    engine = RankEngine(model)
    cat, dense = _features(2)
    with pytest.raises(ValueError, match=r"cat must be \[batch, 4\]"):
        engine.feature_arrays(cat[:, :3], dense)
    with pytest.raises(ValueError, match="carried none"):
        engine.feature_arrays(cat, None)
    with pytest.raises(ValueError, match=r"dense must be \[batch, 4\]"):
        engine.feature_arrays(cat, dense[:, :2])
    with pytest.raises(ValueError, match="empty batch"):
        engine.rank(_init_params(model), cat[:0], dense[:0])


def test_dense_free_model_round_trip():
    """n_dense=0 models take cat only; a dense payload is a 400-class
    error and the no-dense forward still hits bitwise parity."""
    cfg = DLRMConfig.tiny(n_dense=0, dtype=jnp.float32)
    model = DLRM(cfg)
    params = _init_params(model)
    engine = RankEngine(model, batch_buckets=(4,))
    cat, dense = _features(3, cfg=cfg)
    with pytest.raises(ValueError, match="takes no dense features"):
        engine.feature_arrays(cat, np.zeros((3, 2), np.float32))
    np.testing.assert_array_equal(
        engine.rank(params, cat), _direct_scores(model, params, cat)
    )


def test_warmup_compiles_every_bucket():
    model = DLRM(F32)
    params = _init_params(model)
    engine = RankEngine(model, batch_buckets=(1, 2, 4))
    assert engine.warmup(params) == 3
    assert engine.stats["forward_compiles"] == 3
    cat, dense = _features(3)
    engine.rank(params, cat, dense)
    assert engine.stats["forward_compiles"] == 3  # served from cache

    capped = RankEngine(model, batch_buckets=(1, 2, 4))
    assert capped.warmup(params, max_batch=2) == 2


# --------------------------------------------------------------------------
# RankEngine: tensor-parallel embedding sharding
# --------------------------------------------------------------------------

def test_tp2_shards_tables_and_matches_unsharded():
    """MeshSpec(tp=2): the stacked [256, 8] table splits 128 rows per
    device (PartitionSpec('tp', None) via RANKING_RULES), the dense
    stack replicates — so per-device bytes are total - emb/2 exactly —
    and the sharded program's scores are bitwise-equal to the
    single-device engine's."""
    from jax.sharding import PartitionSpec

    from tf_yarn_tpu.parallel.mesh import MeshSpec, build_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    model = DLRM(F32)
    params = _init_params(model)
    mesh = build_mesh(MeshSpec(tp=2), jax.devices()[:2])

    engine = RankEngine(model, batch_buckets=(4, 8), mesh=mesh)
    assert engine.tp_degree == 2
    placed = engine.place_params(params)
    table = placed["params"]["embedding"]
    assert table.shape == (256, 8)
    assert table.sharding.spec == PartitionSpec("tp", None)
    shard_shapes = {
        shard.data.shape for shard in table.addressable_shards
    }
    assert shard_shapes == {(128, 8)}

    total = _tree_nbytes(params)
    emb = 256 * 8 * np.dtype(np.float32).itemsize
    per_device = engine.params_nbytes_per_device(params)
    assert per_device == total - emb // 2

    baseline = RankEngine(model, batch_buckets=(4, 8))
    for batch in (1, 5):
        cat, dense = _features(batch, seed=batch)
        np.testing.assert_array_equal(
            engine.rank(params, cat, dense),
            baseline.rank(params, cat, dense),
        )


def test_tp_misconfiguration_fails_with_knob_names():
    from tf_yarn_tpu.parallel.mesh import MeshSpec, build_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    model = DLRM(F32)
    dp_mesh = build_mesh(MeshSpec(dp=2), jax.devices()[:2])
    with pytest.raises(ValueError, match="tensor-parallel only"):
        RankEngine(model, mesh=dp_mesh)
    # 256 table rows do not split over tp=3.
    tp3 = build_mesh(MeshSpec(tp=3), jax.devices()[:3])
    with pytest.raises(ValueError, match="does not divide"):
        RankEngine(model, mesh=tp3)


# --------------------------------------------------------------------------
# MicroBatchScheduler: fill-or-timeout, admission, resilience
# --------------------------------------------------------------------------

def _built_scheduler(max_batch=4, max_wait_ms=1000.0, **kwargs):
    model = DLRM(F32)
    params = _init_params(model)
    engine = RankEngine(model, batch_buckets=(4, 8))
    scheduler = MicroBatchScheduler(
        engine, params, max_batch=max_batch, max_wait_ms=max_wait_ms,
        **kwargs,
    )
    return model, params, engine, scheduler


def test_scheduler_fill_triggers_tick_and_coalesces():
    """Two 2-row submits fill max_batch=4: ONE tick, ONE engine call,
    each response getting its own rows' scores — bitwise-equal to the
    direct forward of each request's own features."""
    model, params, engine, scheduler = _built_scheduler()
    cat_a, dense_a = _features(2, seed=1)
    cat_b, dense_b = _features(2, seed=2)
    resp_a = scheduler.submit(cat_a, dense_a)
    resp_b = scheduler.submit(cat_b, dense_b)
    ready, _delay = scheduler._ready(time.monotonic())
    assert ready  # fill half: no waiting max_wait_ms=1000
    assert scheduler.tick() is True

    assert resp_a.finish_reason == FINISH_COMPLETE
    assert resp_b.finish_reason == FINISH_COMPLETE
    np.testing.assert_array_equal(
        np.asarray(resp_a.result(), np.float32),
        _direct_scores(model, params, cat_a, dense_a),
    )
    np.testing.assert_array_equal(
        np.asarray(resp_b.result(), np.float32),
        _direct_scores(model, params, cat_b, dense_b),
    )
    assert engine.stats["calls"] == 1
    snap = scheduler.stats()
    assert snap["ticks"] == 1
    assert snap["rows_scored"] == 4
    assert snap["avg_batch_rows"] == 4.0
    assert snap["rank_engine"]["forward_compiles"] == 1


def test_scheduler_timeout_half_serves_partial_batches():
    """A lone 2-row request never fills max_batch=8 — the max_wait_ms
    timeout ticks it out anyway."""
    model, params, engine, scheduler = _built_scheduler(
        max_batch=8, max_wait_ms=20.0
    )
    engine.warmup(params, max_batch=4)  # keep the tick compile-free
    scheduler.start()
    try:
        cat, dense = _features(2, seed=7)
        response = scheduler.submit(cat, dense)
        scores = response.result(timeout=30)
        assert response.finish_reason == FINISH_COMPLETE
        np.testing.assert_array_equal(
            np.asarray(scores, np.float32),
            _direct_scores(model, params, cat, dense),
        )
    finally:
        scheduler.close()


def test_scheduler_admission_rejects_before_the_loop():
    """Malformed features die at submit (the frontend's 400) — the
    ticking loop never sees them and keeps serving valid traffic."""
    model, params, engine, scheduler = _built_scheduler()
    cat, dense = _features(2)
    with pytest.raises(ValueError, match=r"cat must be \[batch, 4\]"):
        scheduler.submit(cat[:, :2], dense)
    with pytest.raises(ValueError, match="carried none"):
        scheduler.submit(cat, None)
    big_cat, big_dense = _features(5)
    with pytest.raises(ValueError, match="coalesces at most max_batch=4"):
        scheduler.submit(big_cat, big_dense)
    with pytest.raises(ValueError, match="empty feature batch"):
        scheduler.submit(cat[:0], dense[:0])

    # Nothing was admitted; the next valid request scores normally.
    response = scheduler.submit(cat, dense)
    scheduler.tick()
    assert response.finish_reason == FINISH_COMPLETE
    assert scheduler.stats()["queue_depth"] == 0

    with pytest.raises(ValueError, match="largest batch bucket"):
        MicroBatchScheduler(engine, params, max_batch=16)


def test_scheduler_loop_survives_engine_failure():
    """A tick that explodes fails its in-flight requests as `error` and
    the loop keeps ticking — the next request completes."""
    model, params, engine, scheduler = _built_scheduler(max_wait_ms=0.0)
    engine.warmup(params, max_batch=4)
    real_rank = engine.rank
    state = {"failures": 0}

    def flaky(params_, cat, dense=None):
        if state["failures"] == 0:
            state["failures"] += 1
            raise RuntimeError("injected tick failure")
        return real_rank(params_, cat, dense)

    engine.rank = flaky
    scheduler.start()
    try:
        cat, dense = _features(2, seed=3)
        doomed = scheduler.submit(cat, dense)
        doomed.result(timeout=30)
        assert doomed.finish_reason == FINISH_ERROR
        assert state["failures"] == 1

        healthy = scheduler.submit(cat, dense)
        scores = healthy.result(timeout=30)
        assert healthy.finish_reason == FINISH_COMPLETE
        np.testing.assert_array_equal(
            np.asarray(scores, np.float32),
            _direct_scores(model, params, cat, dense),
        )
    finally:
        scheduler.close()


def test_scheduler_evicts_expired_requests_at_pop():
    model, params, engine, scheduler = _built_scheduler()
    cat, dense = _features(1)
    expired = scheduler.submit(cat, dense, timeout_s=0.02)
    time.sleep(0.06)
    fresh = scheduler.submit(cat, dense, timeout_s=60)
    scheduler.tick()
    assert expired.finish_reason == FINISH_DEADLINE
    assert expired.result() == []  # never scored
    assert fresh.finish_reason == FINISH_COMPLETE
    assert len(fresh.result()) == 1


def test_scheduler_queue_full_backpressure():
    model, params, engine, scheduler = _built_scheduler(
        queue_capacity=1, retry_after_s=2.5
    )
    cat, dense = _features(1)
    first = scheduler.submit(cat, dense)
    with pytest.raises(QueueFull) as info:
        scheduler.submit(cat, dense)
    assert info.value.retry_after_s == 2.5
    scheduler.tick()
    assert first.finish_reason == FINISH_COMPLETE
    # Capacity freed by the tick: admission works again.
    assert scheduler.submit(cat, dense) is not None


def test_scheduler_holds_overflow_for_next_tick_fifo():
    """A request that would overflow max_batch is held — ordered ahead
    of the queue — and scored by the NEXT tick, never split."""
    model, params, engine, scheduler = _built_scheduler(max_batch=4)
    cat3, dense3 = _features(3, seed=1)
    cat2, dense2 = _features(2, seed=2)
    resp3 = scheduler.submit(cat3, dense3)
    resp2 = scheduler.submit(cat2, dense2)
    scheduler.tick()
    assert resp3.finish_reason == FINISH_COMPLETE
    assert resp2.finish_reason is None  # held, not dropped
    assert scheduler.stats()["queued_rows"] == 2
    scheduler.tick()
    assert resp2.finish_reason == FINISH_COMPLETE
    np.testing.assert_array_equal(
        np.asarray(resp2.result(), np.float32),
        _direct_scores(model, params, cat2, dense2),
    )


# --------------------------------------------------------------------------
# RankServer: the HTTP frontend
# --------------------------------------------------------------------------

def _post(port, path, body, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        raw = body if isinstance(body, (bytes, str)) else json.dumps(body)
        conn.request("POST", path, raw,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def _get(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def test_rank_server_http_round_trip_and_errors():
    from tf_yarn_tpu import preemption

    model, params, engine, scheduler = _built_scheduler(max_wait_ms=0.0)
    engine.warmup(params, max_batch=4)
    server = RankServer(scheduler, "127.0.0.1", 0)
    scheduler.start()
    server.start()
    try:
        cat, dense = _features(3, seed=11)
        status, _headers, raw = _post(
            server.port, "/v1/rank",
            {"cat": cat.tolist(), "dense": dense.tolist()},
        )
        assert status == 200
        payload = json.loads(raw)
        want = _direct_scores(model, params, cat, dense)
        # JSON floats round-trip float32 values exactly through float64.
        assert payload["scores"] == [float(value) for value in want]
        assert payload["finish_reason"] == FINISH_COMPLETE
        assert isinstance(payload["request_id"], int)

        # Admission-time 400s: wrong arity, missing cat, broken JSON.
        status, _h, raw = _post(
            server.port, "/v1/rank",
            {"cat": cat[:, :2].tolist(), "dense": dense.tolist()},
        )
        assert status == 400
        assert "cat must be [batch, 4]" in json.loads(raw)["error"]
        status, _h, raw = _post(
            server.port, "/v1/rank", {"dense": dense.tolist()}
        )
        assert status == 400
        assert "bad request" in json.loads(raw)["error"]
        status, _h, raw = _post(server.port, "/v1/rank", b"{not json")
        assert status == 400

        status, _h, _raw = _post(server.port, "/v1/generate", {})
        assert status == 404

        status, health = _get(server.port, "/healthz")
        assert status == 200 and health["status"] == "ok"
        status, stats = _get(server.port, "/stats")
        assert status == 200
        assert stats["rank_engine"]["forward_compiles"] >= 1
        assert stats["tp_degree"] == 1

        # The raw preemption flag flips /healthz before the task loop
        # even polls it (router ejection latency).
        preemption.request()
        try:
            assert _get(server.port, "/healthz")[1]["status"] == "draining"
        finally:
            preemption.reset()
    finally:
        server.stop()
        scheduler.close()

    # The scheduler loop survived every malformed request above.
    assert scheduler.stats()["rank_engine"]["calls"] >= 1


def test_rank_server_429_backpressure():
    model, params, engine, scheduler = _built_scheduler(queue_capacity=1)
    # Loop NOT started: the queued request pins the queue at capacity.
    cat, dense = _features(1)
    scheduler.submit(cat, dense)
    server = RankServer(scheduler, "127.0.0.1", 0)
    server.start()
    try:
        status, headers, raw = _post(
            server.port, "/v1/rank",
            {"cat": cat.tolist(), "dense": dense.tolist()},
        )
        assert status == 429
        assert "Retry-After" in headers
        assert json.loads(raw)["retry_after_s"] == 0.5
    finally:
        server.stop()
        scheduler.close()


# --------------------------------------------------------------------------
# the rank task body
# --------------------------------------------------------------------------

def test_run_ranking_task_body_advertises_and_serves():
    """tasks/rank.py's program end-to-end in-process: checkpointless
    seeded init, engine, scheduler, frontend, `rank_endpoint` KV
    advertisement, preemption-drain shutdown — and the served scores
    bitwise-equal a local jitted forward from the SAME seed."""
    from tf_yarn_tpu import preemption
    from tf_yarn_tpu.experiment import RankingExperiment
    from tf_yarn_tpu.topologies import TaskKey

    model = DLRM(F32)
    experiment = RankingExperiment(
        model=model, model_dir=None, host="127.0.0.1",
        max_batch=4, max_wait_ms=0.0, batch_buckets=(1, 2, 4),
        warmup=False,
    )

    class _Runtime:
        kv = InProcessKV()
        task_key = TaskKey("rank", 0)
        task = "rank:0"

    runtime = _Runtime()
    result = {}

    def serve():
        result["stats"] = run_ranking(experiment, runtime=runtime)

    thread = threading.Thread(target=serve)
    thread.start()
    try:
        endpoint = runtime.kv.wait_str("rank:0/rank_endpoint", timeout=60)
        port = int(endpoint.rsplit(":", 1)[1])
        cat, dense = _features(3, seed=21)
        status, _headers, raw = _post(
            port, "/v1/rank",
            {"cat": cat.tolist(), "dense": dense.tolist()},
        )
        assert status == 200
        params = _init_params(model, seed=experiment.init_seed)
        want = _direct_scores(model, params, cat, dense)
        assert json.loads(raw)["scores"] == [float(v) for v in want]
    finally:
        preemption.request()  # the drain flag run_ranking polls
        thread.join(timeout=120)
        preemption.reset()
    assert not thread.is_alive()
    stats = result["stats"]
    assert stats["ckpt_step"] == -1  # checkpointless init
    assert stats["endpoint"].endswith(str(port))
    assert stats["draining"] is True
    assert stats["rows_scored"] == 3


def test_ranking_experiment_validates():
    from tf_yarn_tpu.experiment import RankingExperiment
    from tf_yarn_tpu.parallel.mesh import MeshSpec

    model = DLRM(F32)
    with pytest.raises(ValueError, match="max_batch"):
        RankingExperiment(model=model, max_batch=0)
    with pytest.raises(ValueError, match="max_wait_ms"):
        RankingExperiment(model=model, max_wait_ms=-1)
    with pytest.raises(ValueError, match="queue_capacity"):
        RankingExperiment(model=model, queue_capacity=0)
    with pytest.raises(ValueError, match="serve_seconds"):
        RankingExperiment(model=model, serve_seconds=0)
    with pytest.raises(ValueError, match="batch_buckets"):
        RankingExperiment(model=model, batch_buckets=())
    with pytest.raises(ValueError, match="config.table_sizes"):
        RankingExperiment(model=object())
    with pytest.raises(ValueError, match="tensor-parallel only"):
        RankingExperiment(model=model, mesh_spec=MeshSpec(dp=2, tp=2))
    with pytest.raises(ValueError, match="does not divide"):
        RankingExperiment(model=model, mesh_spec=MeshSpec(tp=3))
    assert RankingExperiment(model=model).max_batch == 32


def test_rank_task_type_wiring():
    from tf_yarn_tpu import _env
    from tf_yarn_tpu.backends import PRIMARY_TASK_TYPES
    from tf_yarn_tpu.topologies import (
        ALL_TASK_TYPES,
        check_topology,
        mixed_fleet_topology,
        ranking_topology,
    )

    assert _env.gen_task_module("rank") == "tf_yarn_tpu.tasks.rank"
    assert "rank" in PRIMARY_TASK_TYPES
    assert "rank" in ALL_TASK_TYPES

    specs = ranking_topology(instances=2, chips_per_host=2)
    assert specs["rank"].instances == 2
    check_topology(specs)
    with pytest.raises(ValueError, match="instances"):
        ranking_topology(instances=0)

    mixed = mixed_fleet_topology(nb_serving=1, nb_rank=2)
    assert set(mixed) == {"serving", "rank", "router"}
    assert mixed["router"].instances == 1
    check_topology(mixed)
    with pytest.raises(ValueError, match="each kind"):
        mixed_fleet_topology(nb_serving=1, nb_rank=0)


# --------------------------------------------------------------------------
# path-aware fleet dispatch: /v1/rank never lands on a generate replica
# --------------------------------------------------------------------------

def _fake_replica(respond):
    """A wire-level fake: /healthz ok; every POST delegated to
    `respond(handler, body)` (the real path travels via handler.path)."""

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):
            pass

        def _json(self, status, payload):
            body = (json.dumps(payload) + "\n").encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            self._json(200, {"status": "ok", "queue_depth": 0,
                             "active_slots": 0})

        def do_POST(self):
            length = int(self.headers.get("Content-Length", "0"))
            body = json.loads(self.rfile.read(length) or b"{}")
            respond(self, body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    httpd.daemon_threads = True
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, f"127.0.0.1:{httpd.server_address[1]}"


def test_registry_discovers_replica_kinds_from_kv_scan():
    from tf_yarn_tpu.fleet.registry import (
        KIND_GENERATE,
        KIND_RANK,
        ReplicaRegistry,
    )

    kv = InProcessKV()
    event.serving_endpoint_event(kv, "serving:0", "127.0.0.1:7101")
    event.rank_endpoint_event(kv, "rank:0", "127.0.0.1:7102")
    probe = {
        "127.0.0.1:7101": {"status": "ok", "queue_depth": 0},
        "127.0.0.1:7102": {"status": "ok", "queue_depth": 0},
    }
    registry = ReplicaRegistry(
        kv, probe=lambda endpoint: dict(probe[endpoint]),
        probe_interval_s=0.0,
    )
    registry.refresh(force=True)

    assert {r.task for r in registry.healthy()} == {"serving:0", "rank:0"}
    assert [r.task for r in registry.healthy(kind=KIND_RANK)] == ["rank:0"]
    assert [r.task for r in registry.healthy(kind=KIND_GENERATE)] == [
        "serving:0"
    ]
    kinds = {
        task: row["kind"]
        for task, row in registry.snapshot()["replicas"].items()
    }
    assert kinds == {"serving:0": KIND_GENERATE, "rank:0": KIND_RANK}


def test_registry_resolves_kind_for_explicit_task_lists():
    """With launcher-provided `tasks=` there is no KV scan to reveal the
    kind — the registry infers it from WHICH endpoint key the replica
    actually advertised."""
    from tf_yarn_tpu.fleet.registry import KIND_RANK, ReplicaRegistry

    kv = InProcessKV()
    event.rank_endpoint_event(kv, "rank:0", "127.0.0.1:7103")
    registry = ReplicaRegistry(
        kv, tasks=["rank:0"],
        probe=lambda endpoint: {"status": "ok", "queue_depth": 0},
        probe_interval_s=0.0,
    )
    registry.refresh(force=True)
    (replica,) = registry.healthy()
    assert replica.kind == KIND_RANK
    assert replica.endpoint == "127.0.0.1:7103"


def test_router_dispatches_by_path_in_a_mixed_fleet():
    """The mixed-fleet regression the registry kinds exist for: with a
    generate replica and a rank replica both healthy, every /v1/rank
    request lands on the rank replica and every /v1/generate request on
    the generate replica — never crossed, counted at the wire."""
    from tf_yarn_tpu.fleet.registry import ReplicaRegistry
    from tf_yarn_tpu.fleet.router import RouterServer

    hits = {"generate": 0, "rank": 0}

    def generate(handler, body):
        hits["generate"] += 1
        handler._json(200, {"tokens": [1, 2], "finish_reason": "length",
                            "request_id": 0, "ttft_s": 0.001})

    def rank(handler, body):
        hits["rank"] += 1
        assert handler.path == "/v1/rank"  # path forwarded verbatim
        handler._json(200, {"scores": [0.5] * len(body["cat"]),
                            "finish_reason": "complete", "request_id": 1})

    gen_httpd, gen_ep = _fake_replica(generate)
    rank_httpd, rank_ep = _fake_replica(rank)
    kv = InProcessKV()
    event.serving_endpoint_event(kv, "serving:0", gen_ep)
    event.rank_endpoint_event(kv, "rank:0", rank_ep)
    probe = {gen_ep: {"status": "ok", "queue_depth": 0},
             rank_ep: {"status": "ok", "queue_depth": 0}}
    registry = ReplicaRegistry(
        kv, probe=lambda endpoint: dict(probe[endpoint]),
        probe_interval_s=0.0,
    )
    registry.refresh(force=True)
    router = RouterServer(registry, host="127.0.0.1", port=0)
    router.start()
    try:
        for index in range(3):
            status, _h, raw = _post(
                router.port, "/v1/rank", {"cat": [[index]]}
            )
            assert status == 200
            assert json.loads(raw)["scores"] == [0.5]
        status, _h, raw = _post(
            router.port, "/v1/generate", {"prompt": [1]}
        )
        assert status == 200
        assert json.loads(raw)["tokens"] == [1, 2]
        assert hits == {"generate": 1, "rank": 3}

        status, _h, _raw = _post(router.port, "/v1/score", {})
        assert status == 404

        status, health = _get(router.port, "/healthz")
        assert health["healthy_by_kind"] == {"generate": 1, "rank": 1}
    finally:
        router.stop()
        gen_httpd.shutdown()
        rank_httpd.shutdown()


def test_router_503_names_the_missing_kind():
    """A generate-only fleet answers /v1/rank with 503 — routed to no
    one, and the error names the kind so the operator knows WHICH
    replica pool is empty."""
    from tf_yarn_tpu.fleet.registry import ReplicaRegistry
    from tf_yarn_tpu.fleet.router import RouterServer

    hits = {"generate": 0}

    def generate(handler, body):
        hits["generate"] += 1
        handler._json(200, {"tokens": [9], "finish_reason": "length",
                            "request_id": 0, "ttft_s": 0.001})

    httpd, endpoint = _fake_replica(generate)
    kv = InProcessKV()
    event.serving_endpoint_event(kv, "serving:0", endpoint)
    registry = ReplicaRegistry(
        kv, probe=lambda _ep: {"status": "ok", "queue_depth": 0},
        probe_interval_s=0.0,
    )
    registry.refresh(force=True)
    router = RouterServer(registry, host="127.0.0.1", port=0,
                          retry_after_s=2.0)
    router.start()
    try:
        status, headers, raw = _post(router.port, "/v1/rank",
                                     {"cat": [[1]]})
        assert status == 503
        assert "no rank replica" in json.loads(raw)["error"]
        assert "Retry-After" in headers
        assert hits["generate"] == 0  # never mis-routed as a fallback
    finally:
        router.stop()
        httpd.shutdown()


# --------------------------------------------------------------------------
# the heavy end-to-end: real tp=2 replica behind the router
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_rank_fleet_end_to_end_tp2():
    """The acceptance topology in one process: a REAL rank replica
    (run_ranking, checkpointless init, MeshSpec(tp=2) embedding
    sharding) plus a fake generate replica behind the path-aware
    router. Concurrent /v1/rank requests through the router come back
    bitwise-equal to a direct jitted forward, the table provably lives
    1/tp per device, and generate traffic still reaches its own pool."""
    from tf_yarn_tpu import preemption
    from tf_yarn_tpu.experiment import RankingExperiment
    from tf_yarn_tpu.fleet.registry import ReplicaRegistry, http_probe
    from tf_yarn_tpu.fleet.router import RouterServer
    from tf_yarn_tpu.parallel.mesh import MeshSpec
    from tf_yarn_tpu.topologies import TaskKey

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")

    model = DLRM(F32)
    experiment = RankingExperiment(
        model=model, model_dir=None, host="127.0.0.1",
        max_batch=8, max_wait_ms=1.0, batch_buckets=(1, 2, 4, 8),
        warmup=True, mesh_spec=MeshSpec(tp=2),
    )

    class _Runtime:
        kv = InProcessKV()
        task_key = TaskKey("rank", 0)
        task = "rank:0"

    runtime = _Runtime()
    result = {}
    thread = threading.Thread(
        target=lambda: result.update(
            stats=run_ranking(experiment, runtime=runtime)
        )
    )
    thread.start()

    def generate(handler, body):
        handler._json(200, {"tokens": [7], "finish_reason": "length",
                            "request_id": 0, "ttft_s": 0.001})

    gen_httpd, gen_ep = _fake_replica(generate)
    router = None
    try:
        rank_ep = runtime.kv.wait_str("rank:0/rank_endpoint", timeout=120)
        event.serving_endpoint_event(runtime.kv, "serving:0", gen_ep)
        registry = ReplicaRegistry(
            runtime.kv, probe=http_probe, probe_interval_s=0.0
        )
        registry.refresh(force=True)
        assert {r.task for r in registry.healthy()} == {
            "serving:0", "rank:0"
        }
        router = RouterServer(registry, host="127.0.0.1", port=0)
        router.start()

        # tp accounting straight off the live replica's /stats.
        rank_port = int(rank_ep.rsplit(":", 1)[1])
        _status, stats = _get(rank_port, "/stats")
        assert stats["tp_degree"] == 2
        params = _init_params(model, seed=experiment.init_seed)
        emb = 256 * 8 * np.dtype(np.float32).itemsize
        assert stats["params_hbm_bytes_per_device"] == (
            _tree_nbytes(params) - emb // 2
        )

        # Concurrent clients through the router, varied batch sizes.
        batches = [1, 3, 4, 2, 5, 1, 2, 3]
        outcomes = [None] * len(batches)

        def client(index, batch):
            cat, dense = _features(batch, seed=100 + index)
            status, _h, raw = _post(
                router.port, "/v1/rank",
                {"cat": cat.tolist(), "dense": dense.tolist()},
            )
            outcomes[index] = (status, json.loads(raw), cat, dense)

        threads = [
            threading.Thread(target=client, args=(index, batch))
            for index, batch in enumerate(batches)
        ]
        for worker in threads:
            worker.start()
        for worker in threads:
            worker.join(timeout=240)
        for status, payload, cat, dense in outcomes:
            assert status == 200
            assert payload["finish_reason"] == FINISH_COMPLETE
            want = _direct_scores(model, params, cat, dense)
            assert payload["scores"] == [float(value) for value in want]

        # Generate traffic still reaches the generate pool.
        status, _h, raw = _post(router.port, "/v1/generate",
                                {"prompt": [1]})
        assert status == 200
        assert json.loads(raw)["tokens"] == [7]

        _status, snap = _get(rank_port, "/stats")
        assert snap["rows_scored"] == sum(batches)
        assert snap["requests_total"] == len(batches)
        # Micro-batching happened: fewer ticks than requests.
        assert snap["ticks"] <= len(batches)
    finally:
        preemption.request()
        thread.join(timeout=240)
        preemption.reset()
        if router is not None:
            router.stop()
        gen_httpd.shutdown()
    assert not thread.is_alive()
    assert result["stats"]["draining"] is True
