"""Tensor-parallel online decode (docs/Serving.md "Tensor-parallel
decode").

The acceptance bar, held on the forced host-platform device rig
(conftest gives 8 virtual CPU devices): a tp=2 `DecodeEngine` behind
the REAL serving stack produces per-request token streams BIT-IDENTICAL
to single-device `generate_legacy` — greedy AND sampled RNG chains,
prefix-cache hit, whole-prompt replay, and
spec_k > 0 — while each device holds 1/tp of every slot's KV (exact)
and ~1/tp of the weights (wk/wv and the norms replicate by the logical
rules). The compiled tick program must contain the TP all-reduces the
shardings imply and stay host-callback-free; bad TP configs must fail
at build with errors that name the knob.
"""

import http.client
import json
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _mesh(tp=2):
    from tf_yarn_tpu.parallel.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec(tp=tp), jax.devices()[:tp])


# One model + params + ENGINE per (mesh-or-not), shared across the
# tests in this module: engines are built to be shared (that is the
# compile cache's point), so every test paying its own prefill/step
# compiles would only re-spend tier-1 wall time.
_SHARED = {}


def _tiny_stack(mesh=None, block_size=8, **scheduler_kwargs):
    """Tiny f32 transformer + (optionally sharded) params + a FRESH
    scheduler over the module-shared engine."""
    import flax.linen as nn

    from tf_yarn_tpu import inference
    from tf_yarn_tpu.models import transformer
    from tf_yarn_tpu.models.decode_engine import DecodeEngine
    from tf_yarn_tpu.serving import SlotScheduler

    key = "tp" if mesh is not None else "single"
    if key not in _SHARED:
        cfg = transformer.TransformerConfig.tiny(
            scan_layers=False, remat=False, max_seq_len=64,
            dtype=jnp.float32,
        )
        model = transformer.Transformer(cfg)
        params = nn.meta.unbox(
            model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
        )
        placed = params
        if mesh is not None:
            placed = inference.shard_restored_params(model, params, mesh)
        engine = DecodeEngine(
            model, batch_buckets=(1, 2, 4), prompt_buckets=(4, 8, 16),
            mesh=mesh,
        )
        _SHARED[key] = (model, params, placed, engine)
    model, params, placed, engine = _SHARED[key]
    scheduler = SlotScheduler(
        engine, placed, max_slots=2, block_size=block_size,
        **scheduler_kwargs
    )
    return model, params, engine, scheduler


def _legacy_stream(model, params, prompt, max_new, eos=None, **sampling):
    from tf_yarn_tpu.models.generate import generate_legacy

    out = generate_legacy(
        model, params, jnp.asarray([prompt], jnp.int32), max_new,
        eos_token=eos, **sampling,
    )
    row = np.asarray(out)[0, len(prompt):].tolist()
    if eos is not None and eos in row:
        row = row[:row.index(eos) + 1]
    return row


def _post(port, body, timeout=300):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            "POST", "/v1/generate", json.dumps(body),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


# --------------------------------------------------------------------------
# validation: bad TP configs fail at build, with errors naming the knob
# --------------------------------------------------------------------------

def test_serving_experiment_rejects_bad_tp_configs():
    from tf_yarn_tpu.experiment import ServingExperiment
    from tf_yarn_tpu.models import transformer
    from tf_yarn_tpu.parallel.mesh import MeshSpec

    model = transformer.Transformer(transformer.TransformerConfig.tiny())

    def build(**overrides):
        kwargs = dict(model=model, model_dir="/tmp/x")
        kwargs.update(overrides)
        return ServingExperiment(**kwargs)

    # tp must divide the head counts (tiny: n_heads=4, n_kv_heads=2).
    with pytest.raises(ValueError, match="n_heads=4"):
        build(mesh_spec=MeshSpec(tp=3))
    with pytest.raises(ValueError, match="n_kv_heads=2"):
        build(mesh_spec=MeshSpec(tp=4))
    # Serving shards tensor-parallel only.
    with pytest.raises(ValueError, match="tensor-parallel only"):
        build(mesh_spec=MeshSpec(dp=2))
    # The fused pallas kernel cannot read a sharded pool.
    with pytest.raises(ValueError, match="fused"):
        build(
            mesh_spec=MeshSpec(tp=2), decode_attention="fused",
        )
    # tp=1 (or None) stays valid — the single-device path.
    build(mesh_spec=MeshSpec(tp=1))
    build()


def test_engine_and_scheduler_reject_bad_tp_at_build():
    from tf_yarn_tpu.models import transformer
    from tf_yarn_tpu.models.decode_engine import DecodeEngine
    from tf_yarn_tpu.parallel.mesh import select_devices
    from tf_yarn_tpu.serving import SlotScheduler

    mesh = _mesh(tp=2)
    # Indivisible kv heads fail at ENGINE construction, before any trace.
    odd = transformer.Transformer(
        transformer.TransformerConfig.tiny(n_kv_heads=1, n_heads=4)
    )
    with pytest.raises(ValueError, match="n_kv_heads=1"):
        DecodeEngine(odd, mesh=mesh)
    # A model without a config cannot anchor the KV sharding rule.
    with pytest.raises(ValueError, match="config.max_seq_len"):
        DecodeEngine(object(), mesh=mesh)
    # More mesh devices than exist: the clear device-availability error.
    with pytest.raises(ValueError, match="need 999 devices"):
        select_devices(999)

    # fused x tp fails at SCHEDULER build (and again in the engine),
    # not at trace time inside the tick thread.
    class _TpStub:
        tp_degree = 2

    with pytest.raises(ValueError, match="sharded block pool"):
        SlotScheduler(
            _TpStub(), None, max_slots=1,
            decode_attention="fused", max_seq_len=64, block_size=8,
        )


# --------------------------------------------------------------------------
# bit-parity: tp=2 streams identical to single-device generate_legacy
# --------------------------------------------------------------------------

@pytest.mark.slow  # heaviest TP e2e variant; tier-1 keeps the paged
# prefix-hit e2e + mesh-spec e2e + tp spec decode as TP representatives
def test_tp_http_sampled_streams_match_legacy():
    """tp=2 block pool through the REAL HTTP frontend: concurrent
    SAMPLED requests (distinct seeds) stream bit-identically to
    single-device generate_legacy — the sampled chain proves the
    sharded program consumes the per-slot RNG exactly like the
    unsharded one (greedy parity rides on the paged test)."""
    from tf_yarn_tpu.serving import ServingServer

    sampling = dict(temperature=1.0, top_k=8)
    model, params, engine, scheduler = _tiny_stack(
        mesh=_mesh(), num_blocks=17, **sampling
    )
    scheduler.start()
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()
    try:
        rng = np.random.RandomState(0)
        prompts = [
            rng.randint(0, 256, (5,)).tolist(),
            rng.randint(0, 256, (9,)).tolist(),
        ]
        bodies = [
            {"prompt": prompts[0], "max_new_tokens": 6, "seed": 0,
             **sampling},
            {"prompt": prompts[1], "max_new_tokens": 8, "seed": 7,
             **sampling},
        ]
        results = {}

        def call(index):
            results[index] = _post(server.port, bodies[index])

        threads = [
            threading.Thread(target=call, args=(i,))
            for i in range(len(bodies))
        ]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=300)
        for index, body in enumerate(bodies):
            status, raw = results[index]
            assert status == 200, raw
            expected = _legacy_stream(
                model, params, body["prompt"], body["max_new_tokens"],
                seed=body["seed"], **sampling,
            )
            assert json.loads(raw)["tokens"] == expected, index
        assert scheduler.stats()["tp_degree"] == 2
    finally:
        server.stop()
        scheduler.close()


def test_tp_paged_greedy_prefix_hit_and_replay_match_legacy():
    """tp=2 PAGED pool: greedy streams match legacy; a repeated prompt
    admits through the prefix cache (no second prefill) over SHARED
    sharded blocks and still matches; a 2-token prompt exercises the
    whole-prompt-replay path (prefill_len == 0) against the sharded
    trash-block pool."""
    model, params, engine, scheduler = _tiny_stack(
        mesh=_mesh(), num_blocks=17,
    )
    scheduler.start()
    try:
        from tf_yarn_tpu.serving import SamplingParams

        prompt = list(range(40, 57))  # prefill 16 = two full blocks
        short = [3, 5]
        first = scheduler.submit(
            prompt, SamplingParams(max_new_tokens=5)
        ).result(timeout=300)
        again = scheduler.submit(
            prompt, SamplingParams(max_new_tokens=5)
        ).result(timeout=300)
        replay = scheduler.submit(
            short, SamplingParams(max_new_tokens=4)
        ).result(timeout=300)
        expected = _legacy_stream(model, params, prompt, 5)
        assert first == expected
        assert again == expected
        assert replay == _legacy_stream(model, params, short, 4)
        stats = scheduler.stats()
        assert stats["prefix_cache"]["hits"] >= 1
        assert stats["tp_degree"] == 2
        # A sharded pool is read by the plain gather, whole tables a step.
        assert stats["decode_engine"]["paged_attention"] == "plain"
        assert stats["kv_read_token_steps"] == \
            stats["slot_steps"] * model.config.max_seq_len
        # ONE greedy paged step program for the whole run — tick-to-tick
        # table changes never recompiled under the mesh either (the
        # engine is module-shared: another test's sampling config is
        # another key).
        greedy = [key for key in engine.program_keys()["paged_step"]
                  if key[3:6] == (0.0, None, None)]
        assert len(greedy) == 1
    finally:
        scheduler.close()


def test_tp_spec_decode_matches_legacy():
    """tp=2 + spec_k=2 (paged): the windowed verify forward runs
    sharded, and the emitted stream — variable tokens per tick — still
    equals generate_legacy on a repeated-structure prompt the n-gram
    drafter can exploit."""
    model, params, engine, scheduler = _tiny_stack(
        mesh=_mesh(), num_blocks=17,
        spec_k=2,
    )
    scheduler.start()
    try:
        from tf_yarn_tpu.serving import SamplingParams

        prompt = ([7, 9, 11] * 4)[:10]
        out = scheduler.submit(
            prompt, SamplingParams(max_new_tokens=8)
        ).result(timeout=300)
        assert out == _legacy_stream(model, params, prompt, 8)
        assert scheduler.stats()["spec"]["proposed_tokens"] > 0
    finally:
        scheduler.close()


@pytest.mark.slow
def test_tp_chunked_prefill_matches_legacy():
    """tp=2 + chunked prefill (paged): admission replays the prompt in
    teacher-forced windows through the sharded program — the stream
    still equals single-device generate_legacy, and a repeat of the
    prompt admits through the incrementally registered prefix blocks."""
    model, params, engine, scheduler = _tiny_stack(
        mesh=_mesh(), num_blocks=17,
        prefill_chunk=4, prefill_budget_per_tick=8,
    )
    scheduler.start()
    try:
        from tf_yarn_tpu.serving import SamplingParams

        prompt = np.random.RandomState(3).randint(
            0, 256, (17,)
        ).tolist()
        expected = _legacy_stream(model, params, prompt, 8)
        out = scheduler.submit(
            prompt, SamplingParams(max_new_tokens=8)
        ).result(timeout=300)
        assert out == expected
        repeat = scheduler.submit(
            prompt, SamplingParams(max_new_tokens=8)
        ).result(timeout=300)
        assert repeat == expected
        stats = scheduler.stats()
        assert stats["prefill_chunk"] == 4
        assert stats["prefix_cache"]["hits"] >= 1
    finally:
        scheduler.close()


def test_run_serving_with_mesh_spec_serves_sharded_e2e(monkeypatch):
    """The full task body with mesh_spec=MeshSpec(tp=2): mesh built,
    restore SHARDED by the logical rules (inference.
    shard_restored_params), engine placed on the mesh, endpoint
    advertised — and the HTTP stream still equals single-device
    generate_legacy, with /stats reporting the tp surface."""
    import flax.linen as nn

    from tf_yarn_tpu import inference as inference_mod
    from tf_yarn_tpu import preemption
    from tf_yarn_tpu.coordination.kv import InProcessKV
    from tf_yarn_tpu.experiment import ServingExperiment
    from tf_yarn_tpu.models import transformer
    from tf_yarn_tpu.models.decode_engine import clear_engines
    from tf_yarn_tpu.parallel.mesh import MeshSpec
    from tf_yarn_tpu.serving.server import run_serving
    from tf_yarn_tpu.topologies import TaskKey

    cfg = transformer.TransformerConfig.tiny(
        scan_layers=False, remat=False, max_seq_len=64, dtype=jnp.float32
    )
    model = transformer.Transformer(cfg)
    variables = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), jnp.zeros((2, 5), jnp.int32))
    )
    monkeypatch.setattr(
        inference_mod, "_restore_params",
        lambda model_dir, step: (variables, 3),
    )
    clear_engines()
    # Seed the engine registry with the module-shared engine: equal
    # config + equal mesh means get_engine would build an identical
    # engine anyway, and sharing it lets run_serving hit the already-
    # compiled paged_step instead of re-spending tier-1 wall time.
    if "tp" in _SHARED:
        from tf_yarn_tpu.models import decode_engine as de

        shared_engine = _SHARED["tp"][3]
        with de._ENGINES_LOCK:
            de._ENGINES[(model, shared_engine.mesh)] = shared_engine

    class _Runtime:
        kv = InProcessKV()
        task_key = TaskKey("serving", 0)
        task = "serving:0"

    runtime = _Runtime()
    experiment = ServingExperiment(
        model=model, model_dir="/nonexistent-restore-is-patched",
        host="127.0.0.1", max_slots=2, block_size=8,
        mesh_spec=MeshSpec(tp=2),
    )
    result = {}

    def serve():
        result["stats"] = run_serving(experiment, runtime=runtime)

    thread = threading.Thread(target=serve)
    thread.start()
    try:
        endpoint = runtime.kv.wait_str(
            "serving:0/serving_endpoint", timeout=60
        )
        port = int(endpoint.rsplit(":", 1)[1])
        prompt = [1, 2, 3]
        status, raw = _post(port, {"prompt": prompt, "max_new_tokens": 3})
        assert status == 200
        assert json.loads(raw)["tokens"] == _legacy_stream(
            model, variables, prompt, 3
        )
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        try:
            conn.request("GET", "/stats")
            stats = json.loads(conn.getresponse().read())
        finally:
            conn.close()
        assert stats["tp_degree"] == 2
        assert stats["kv_cache_hbm_bytes_per_device"] * 2 == \
            stats["kv_cache_hbm_bytes"]
    finally:
        preemption.request()  # the drain flag run_serving polls
        thread.join(timeout=120)
        preemption.reset()
    assert not thread.is_alive()
    assert result["stats"]["ckpt_step"] == 3
    assert result["stats"]["tp_degree"] == 2
    clear_engines()


# --------------------------------------------------------------------------
# HBM accounting + the compiled program's collectives
# --------------------------------------------------------------------------

def test_tp_hbm_accounting_weights_and_kv_near_half():
    """Per-device residency at tp=2 vs tp=1: the slot KV (the block
    pool, and the prefill's row cache that is packed into it) lands at
    EXACTLY 1/2 for the sharded leaves (the row's per-layer cache_index
    scalars replicate), and the weights at ~1/2 (wk/wv and the norms replicate
    by LOGICAL_RULES, a small constant fraction of a tiny config)."""
    from tf_yarn_tpu.models.decode_engine import (
        cache_nbytes,
        tree_nbytes_per_device,
    )

    mesh = _mesh()
    model, params, engine, scheduler = _tiny_stack(mesh=mesh)
    try:
        _model, _params, engine1, scheduler1 = _tiny_stack(mesh=None)
        try:
            tp1 = scheduler1.stats()
            tp2 = scheduler.stats()
            assert tp1["tp_degree"] == 1
            assert tp2["tp_degree"] == 2
            # Same GLOBAL pool bytes; half of it per device under tp=2.
            assert tp2["kv_cache_hbm_bytes"] == tp1["kv_cache_hbm_bytes"]
            assert (
                tp2["kv_cache_hbm_bytes_per_device"]
                == tp1["kv_cache_hbm_bytes_per_device"] // 2
            )
            # The prefill's row cache: sharded KV leaves exactly halve;
            # the index scalars (4 bytes a layer) replicate.
            row, _logits = engine.prefill(
                scheduler.params, np.zeros((1, 8), np.int32))
            per_dev = tree_nbytes_per_device(row)
            total = cache_nbytes(row)
            assert total // 2 <= per_dev <= total // 2 + 1024
            # Weights: sharded by the logical rules; wk/wv + norms
            # replicate, so per-device lands near (not exactly) half.
            w_total = cache_nbytes(params)
            w_per_dev = tree_nbytes_per_device(scheduler.params)
            assert w_per_dev < 0.62 * w_total, (w_per_dev, w_total)
        finally:
            scheduler1.close()
    finally:
        scheduler.close()


def test_tp_step_program_has_allreduce_and_no_host_callbacks():
    """The sharded tick program's two guardrails: the compiled HLO
    contains the TP all-reduces the shardings imply (the attention
    output / MLP down-projection reductions), and the traced program is
    host-callback-free — one device program per tick, no per-tick
    round-trips smuggled in by the partitioning."""
    from tf_yarn_tpu.analysis.jaxpr_engine import (
        _HOST_CALLBACK_PRIMITIVES,
        _walk_jaxpr,
        check_entry,
        default_entry_points,
    )
    from tf_yarn_tpu.serving import SamplingParams

    model, params, engine, scheduler = _tiny_stack(
        mesh=_mesh(), num_blocks=17)
    scheduler.start()
    try:
        scheduler.submit(
            [1, 2, 3], SamplingParams(max_new_tokens=2)
        ).result(timeout=300)
    finally:
        scheduler.close()
    # The engine is module-shared, so earlier tests' sampling configs
    # may sit in the cache too — EVERY compiled step program must carry
    # the TP collectives.
    assert engine.stats["paged_step_compiles"] >= 1
    for compiled in engine._paged_step.values():
        assert "all-reduce" in compiled.as_text(), \
            "no TP collective in a sharded step program"

    # The analysis twins: both sharded DECODE entries trace clean on
    # this rig (the rank engine's sharded twin has its own coverage in
    # test_analysis / test_ranking).
    entries = {
        e.name: e for e in default_entry_points()
        if "sharded" in e.name and "decode_engine" in e.name
    }
    assert set(entries) == {
        "models.decode_engine.sharded_paged_step",
        "models.decode_engine.sharded_chunk_apply",
    }
    for entry in entries.values():
        findings, counts = check_entry(entry)
        assert findings == [], entry.name
        assert counts, entry.name

    # Jaxpr-level host-callback check on the exact step builder.
    from tf_yarn_tpu.models.decode_engine import (
        _decode_cache_aval,
        build_paged_step_fn,
        feed_avals,
        paged_pool_avals,
    )

    abstract = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
        scheduler.params,
    )
    pool = paged_pool_avals(
        model, _decode_cache_aval(model, abstract), 17, 8)
    closed = jax.make_jaxpr(
        build_paged_step_fn(model, 8, 0.0, None, None)
    )(
        abstract, pool,
        jax.ShapeDtypeStruct((2, 8), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32),
        *feed_avals(2),
        jax.ShapeDtypeStruct((2,), jnp.bool_),
    )
    prims = {eqn.primitive.name for eqn in _walk_jaxpr(closed.jaxpr)}
    assert not prims & _HOST_CALLBACK_PRIMITIVES
