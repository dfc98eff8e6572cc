"""Telemetry layer: span tracer (nesting, threads, ring buffer, Chrome
trace export, JSONL sink), metrics registry (instruments, labels,
snapshot, flush to MLflow/KV), heartbeats — plus the end-to-end
acceptance path: a short CPU training run and a `run_inference` under
``TPU_YARN_TRACE`` produce valid Chrome trace_event JSON with the
nested step-time/pipeline spans, and the registry snapshot carries the
step-time breakdown, decode-engine counters and checkpoint durations."""

import json
import threading
import time

import numpy as np
import pytest

from tf_yarn_tpu import telemetry
from tf_yarn_tpu.coordination import InProcessKV
from tf_yarn_tpu.telemetry.registry import MetricsRegistry
from tf_yarn_tpu.telemetry import spans as spans_lib
from tf_yarn_tpu.telemetry.spans import Tracer


# --- spans ----------------------------------------------------------------

def test_span_nesting_depth_and_parent():
    tracer = Tracer()
    with tracer.span("outer"):
        with tracer.span("middle"):
            with tracer.span("inner"):
                pass
    by_name = {s.name: s for s in tracer.records()}
    assert by_name["outer"].depth == 0 and by_name["outer"].parent is None
    assert by_name["middle"].depth == 1 and by_name["middle"].parent == "outer"
    assert by_name["inner"].depth == 2 and by_name["inner"].parent == "middle"
    # Completion order: innermost first (spans record when they close).
    assert [s.name for s in tracer.records()] == ["inner", "middle", "outer"]
    assert all(s.duration >= 0 for s in tracer.records())


def test_span_duration_and_containment():
    tracer = Tracer()
    with tracer.span("outer"):
        with tracer.span("inner"):
            time.sleep(0.02)
    inner, outer = tracer.records()
    # Deflaked (PR 7 verification flake): sleep() and the span's
    # perf_counter are different clocks — sleep(0.02) can measure a hair
    # under 0.02 on the span clock, so the bound asserts half the slept
    # time, which still proves the duration is real.
    assert inner.duration >= 0.01
    assert outer.duration >= inner.duration
    assert outer.start <= inner.start
    assert inner.start + inner.duration <= outer.start + outer.duration + 1e-6


def test_span_threads_have_independent_stacks():
    tracer = Tracer()
    barrier = threading.Barrier(2)

    def work(name):
        with tracer.span(f"{name}-outer"):
            barrier.wait(timeout=5)
            with tracer.span(f"{name}-inner"):
                pass

    threads = [threading.Thread(target=work, args=(n,)) for n in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    by_name = {s.name: s for s in tracer.records()}
    # Nesting is per thread: each inner's parent is its OWN outer even
    # though both threads were inside spans simultaneously.
    assert by_name["a-inner"].parent == "a-outer"
    assert by_name["b-inner"].parent == "b-outer"
    assert by_name["a-inner"].thread_id != by_name["b-inner"].thread_id


def test_span_exception_propagates_and_records():
    tracer = Tracer()
    with pytest.raises(StopIteration):
        with tracer.span("pull"):
            next(iter([]))
    (span,) = tracer.records()
    assert span.name == "pull"
    assert span.args.get("error") is True


def test_ring_buffer_bounds_memory():
    tracer = Tracer(capacity=4)
    for i in range(10):
        with tracer.span("s", i=i):
            pass
    kept = [s.args["i"] for s in tracer.records()]
    assert kept == [6, 7, 8, 9]  # the newest 4 of a name survive


TICK_NAMES = tuple(f"tick/part{i}" for i in range(11))


def test_a_span_written_once_outlives_300k_spans_of_eleven_other_names(
        monkeypatch):
    """The serving tick writes eleven names every few milliseconds; the
    compile it started with is what the benchmark reads after the window.
    No name evicts another's."""
    monkeypatch.delenv(spans_lib.TRACE_BUFFER_ENV, raising=False)
    tracer = Tracer()
    with tracer.span("decode_engine/compile", kind="paged_step") as first:
        pass
    for i in range(300_000):
        tracer.record(TICK_NAMES[i % 11], float(i), 0.0)
    records = tracer.records()
    assert records[0] is first
    # 27,273 of a name: under the bound a name, so nothing went at all.
    assert len(records) == 300_001 and tracer.evicted_total() == 0
    assert [s.order for s in records] == list(range(1, 300_002))


def test_ring_memory_is_bounded_by_constants_and_the_env_means_spans_a_name(
        monkeypatch):
    monkeypatch.setenv(spans_lib.TRACE_BUFFER_ENV, "3")
    tracer = Tracer()
    assert tracer.capacity == 3
    monkeypatch.setenv(spans_lib.TRACE_BUFFER_ENV, "not a number")
    assert Tracer().capacity == spans_lib.DEFAULT_CAPACITY
    # More names than rings: the names past MAX_NAMES share one ring, so
    # whatever a caller formats into a name, at most (MAX_NAMES + 1) x
    # capacity spans are held.
    names = [f"n{i}" for i in range(spans_lib.MAX_NAMES + 40)]
    for _ in range(5):
        for name in names:
            tracer.record(name, 0.0, 0.0)
    records = tracer.records()
    assert len(records) == (spans_lib.MAX_NAMES + 1) * 3
    assert len(tracer._rings) == spans_lib.MAX_NAMES + 1
    by_name = {}
    for span in records:
        by_name[span.name] = by_name.get(span.name, 0) + 1
    assert all(by_name[name] == 3 for name in names[:spans_lib.MAX_NAMES])
    # The shared ring holds the newest three of the 40 late names.
    assert [s.name for s in records if s.name in names[spans_lib.MAX_NAMES:]] \
        == names[-3:]
    # The bound that sizes the default: the benchmark's window, lead-in
    # and warm-up at a 3 ms tick, with room.
    assert spans_lib.DEFAULT_CAPACITY * 0.003 >= 51 + 16 + 20


def test_evictions_are_counted_by_name_and_survive_a_clear():
    tracer = Tracer(capacity=4)
    for i in range(10):
        tracer.record("often", float(i), 0.0)
    tracer.record("once", 0.0, 0.0)
    assert tracer.evicted == {"often": 6, "once": 0}
    assert tracer.evicted_total() == 6
    assert [s.name for s in tracer.records()].count("often") == 4
    tracer.clear()
    assert tracer.records() == [] and tracer.evicted_total() == 6
    tracer.record("often", 0.0, 0.0)
    assert tracer.evicted["often"] == 6 and len(tracer.records()) == 1
    # Past MAX_NAMES the shared ring's drops are counted under its key.
    crowded = Tracer(capacity=1)
    for i in range(spans_lib.MAX_NAMES + 3):
        crowded.record(f"n{i}", 0.0, 0.0)
    assert crowded.evicted[spans_lib.OVERFLOW] == 2
    assert crowded.evicted_total() == 2


def test_chrome_trace_schema_roundtrip(tmp_path):
    tracer = Tracer()
    with tracer.span("parent", category="test", step=3):
        with tracer.span("child"):
            pass
    path = str(tmp_path / "trace.json")
    tracer.export_chrome_trace(path)
    payload = json.loads(open(path).read())
    events = payload["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    meta = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in complete} == {"parent", "child"}
    for e in complete:
        assert set(e) >= {"name", "cat", "ph", "ts", "dur", "pid", "tid"}
        assert e["ts"] >= 0 and e["dur"] >= 0
    # Thread-name metadata present for the recording thread.
    assert meta and meta[0]["name"] == "thread_name"
    # Nesting containment in trace units (µs).
    child = next(e for e in complete if e["name"] == "child")
    parent = next(e for e in complete if e["name"] == "parent")
    assert parent["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1.0
    assert parent["args"]["step"] == 3


def test_jsonl_sink_streams_completed_spans(tmp_path):
    tracer = Tracer()
    path = str(tmp_path / "spans.jsonl")
    close = tracer.jsonl_sink(path)
    with tracer.span("a", step=1):
        with tracer.span("b"):
            pass
    close()
    with tracer.span("after-close"):  # must NOT be streamed
        pass
    lines = [json.loads(line) for line in open(path)]
    assert [rec["name"] for rec in lines] == ["b", "a"]
    assert lines[1]["args"] == {"step": 1}
    assert all(rec["dur"] >= 0 for rec in lines)


def test_export_trace_env_gate(tmp_path, monkeypatch):
    monkeypatch.delenv("TPU_YARN_TRACE", raising=False)
    assert telemetry.export_trace("nope") is None
    monkeypatch.setenv("TPU_YARN_TRACE", str(tmp_path))
    telemetry.get_tracer().clear()
    with telemetry.span("x"):
        pass
    path = telemetry.export_trace("worker:0")
    assert path == str(tmp_path / "trace_worker-0.json")  # ':' sanitized
    assert json.loads(open(path).read())["traceEvents"]


# --- registry -------------------------------------------------------------

def test_registry_instruments_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("requests", route="a").inc()
    reg.counter("requests", route="a").inc(2)
    reg.counter("requests", route="b").inc()
    reg.gauge("depth").set(7)
    hist = reg.histogram("latency", op="save")
    for v in (1.0, 3.0, 2.0):
        hist.observe(v)
    snap = reg.snapshot()
    assert snap["requests{route=a}"] == 3
    assert snap["requests{route=b}"] == 1
    assert snap["depth"] == 7
    assert snap["latency_count{op=save}"] == 3
    assert snap["latency_sum{op=save}"] == pytest.approx(6.0)
    assert snap["latency_mean{op=save}"] == pytest.approx(2.0)
    assert snap["latency_min{op=save}"] == 1.0
    assert snap["latency_max{op=save}"] == 3.0
    assert snap["latency_last{op=save}"] == 2.0


def test_registry_type_conflict_and_counter_monotonicity():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.counter("x").inc(-1)


def test_registry_clear():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.clear()
    assert reg.snapshot() == {}


def test_flush_metrics_to_mlflow_and_kv(monkeypatch):
    from tf_yarn_tpu.utils import mlflow as mlflow_lib

    logged = {}
    monkeypatch.setattr(
        mlflow_lib, "log_metric",
        lambda key, value, step=None: logged.setdefault(key, (value, step)),
    )
    reg = MetricsRegistry()
    reg.gauge("train/interval_seconds", component="input_wait").set(0.25)
    reg.counter("train/steps_total").inc(10)
    kv = InProcessKV()
    snap = telemetry.flush_metrics(reg, step=10, kv=kv, task="worker:0")
    # KV: one {task}/metrics JSON payload, chief-parseable.
    payload = json.loads(kv.get_str("worker:0/metrics"))
    assert payload == snap
    assert payload["train/steps_total"] == 10
    assert payload["train/interval_seconds{component=input_wait}"] == 0.25
    # MLflow: keys sanitized of label punctuation, step threaded through.
    assert logged["train/interval_seconds.component.input_wait"] == (0.25, 10)
    assert logged["train/steps_total"] == (10, 10)


def test_collect_task_metrics_roundtrip():
    from tf_yarn_tpu.utils.metrics import collect_task_metrics

    reg = MetricsRegistry()
    reg.gauge("g").set(1.5)
    kv = InProcessKV()
    telemetry.flush_metrics(reg, kv=kv, task="worker:1", to_mlflow=False)
    kv.put_str("worker:2/metrics", "not json")
    collected = collect_task_metrics(kv, ["worker:1", "worker:2", "worker:3"])
    assert collected == {"worker:1": {"g": 1.5}}


# --- heartbeat ------------------------------------------------------------

def test_heartbeat_broadcasts_and_ages():
    from tf_yarn_tpu.utils.metrics import stopped_heartbeats, task_heartbeats

    kv = InProcessKV()
    reg = MetricsRegistry()
    reg.gauge("depth").set(3)
    with telemetry.Heartbeat(kv, "worker:0", every=0.05, registry=reg) as hb:
        # Generous deadline (deflake): the beat thread can be starved
        # well past 2 * every on a loaded CI box.
        deadline = time.time() + 30
        while hb.beats < 2 and time.time() < deadline:
            time.sleep(0.01)
        # Alive (no tombstone yet): the age is a liveness signal.
        ts = float(kv.get_str("worker:0/heartbeat"))
        ages = task_heartbeats(kv, ["worker:0", "worker:9"], now=ts + 4.0)
        assert ages["worker:0"] == pytest.approx(4.0)
        assert ages["worker:9"] is None  # never beat
    assert hb.beats >= 2
    assert abs(time.time() - ts) < 60
    # Registry snapshot rode along on the beat.
    assert json.loads(kv.get_str("worker:0/metrics"))["depth"] == 3
    # Clean stop published the tombstone: finished, not dead — the task
    # leaves the liveness view instead of showing a growing age.
    assert kv.get_str("worker:0/heartbeat.stopped") is not None
    assert "worker:0" not in task_heartbeats(kv, ["worker:0"], now=ts + 999)
    assert stopped_heartbeats(kv, ["worker:0", "worker:9"]) == ["worker:0"]


def test_heartbeat_disabled_with_nonpositive_cadence():
    hb = telemetry.Heartbeat(InProcessKV(), "worker:0", every=0)
    assert not hb.enabled
    hb.start()
    time.sleep(0.02)
    hb.stop()
    assert hb.beats == 0


# --- end-to-end: the acceptance path --------------------------------------

def _train_mnist(tmp_path, steps=6):
    from tf_yarn_tpu.experiment import as_core_experiment
    from tf_yarn_tpu.models import mnist
    from tf_yarn_tpu.parallel.mesh import MeshSpec, select_devices
    from tf_yarn_tpu.training import train_and_evaluate

    experiment = mnist.make_experiment(
        model_dir=str(tmp_path),
        train_steps=steps,
        batch_size=32,
        feature_dim=16,
        num_classes=4,
        mesh_spec=MeshSpec(fsdp=8),
        log_every_steps=3,
        checkpoint_every_steps=3,
    )
    experiment.model = mnist.DenseClassifier(hidden_sizes=(16,), num_classes=4)
    return train_and_evaluate(
        as_core_experiment(experiment), devices=select_devices(8, platform="cpu")
    )


def test_training_trace_and_registry_end_to_end(tmp_path, monkeypatch):
    trace_dir = tmp_path / "traces"
    monkeypatch.setenv("TPU_YARN_TRACE", str(trace_dir))
    telemetry.get_tracer().clear()
    telemetry.get_registry().clear()
    _train_mnist(tmp_path / "model")

    path = trace_dir / "trace_train.json"
    assert path.exists()
    events = json.loads(path.read_text())["traceEvents"]
    names = {e["name"] for e in events if e.get("ph") == "X"}
    assert {
        "train/first_batch", "train/compile_train_step", "train/input_wait",
        "train/step_dispatch", "train/device_wait", "train/checkpoint_save",
        "train/globalize", "checkpoint/save_submit",
    } <= names
    # Nested: checkpoint/save_submit sits inside a train/checkpoint_save.
    saves = [e for e in events if e.get("name") == "train/checkpoint_save"]
    submits = [e for e in events if e.get("name") == "checkpoint/save_submit"]
    assert any(
        s["ts"] <= sub["ts"] <= s["ts"] + s["dur"] + 1.0
        for s in saves for sub in submits
    )

    snap = telemetry.get_registry().snapshot()
    # Step-time breakdown gauges, checkpoint durations, throughput.
    assert "train/interval_seconds{component=step_dispatch}" in snap
    assert "train/interval_seconds{component=interval_wall}" in snap
    assert "checkpoint/seconds_count{op=save_submit}" in snap
    assert snap["train/steps_total"] == 6
    assert snap["train/steps_per_sec"] > 0
    assert snap["prefetch/queue_depth{pipeline=train}"] >= 0


def test_inference_trace_and_registry_end_to_end(tmp_path, monkeypatch):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from tf_yarn_tpu import inference as inference_mod
    from tf_yarn_tpu.experiment import InferenceExperiment
    from tf_yarn_tpu.models import transformer
    from tf_yarn_tpu.models.decode_engine import clear_engines

    trace_dir = tmp_path / "traces"
    monkeypatch.setenv("TPU_YARN_TRACE", str(trace_dir))
    telemetry.get_tracer().clear()
    telemetry.get_registry().clear()
    clear_engines()

    cfg = transformer.TransformerConfig.tiny(max_seq_len=32)
    model = transformer.Transformer(cfg)
    variables = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), jnp.zeros((2, 5), jnp.int32))
    )
    monkeypatch.setattr(
        inference_mod, "_restore_params", lambda model_dir, step: (variables, 1)
    )

    def stream():
        rng = np.random.RandomState(0)
        for _ in range(2):
            yield {"tokens": rng.randint(0, 256, (2, 5)).astype(np.int32)}

    stats = inference_mod.run_inference(InferenceExperiment(
        model=model,
        model_dir=str(tmp_path / "model"),
        input_fn=stream,
        output_path=str(tmp_path / "out.jsonl"),
        max_new_tokens=3,
        temperature=0.0,
    ))
    assert stats["records"] == 4
    assert set(stats["stage_seconds"]) == {
        "input_wait", "decode", "writer_put", "write"
    }
    assert all(v >= 0 for v in stats["stage_seconds"].values())
    assert stats["writer_queue_depth_max"] >= 1

    path = trace_dir / "trace_inference.json"
    assert path.exists()
    events = json.loads(path.read_text())["traceEvents"]
    names = {e["name"] for e in events if e.get("ph") == "X"}
    assert {
        "inference/restore_params", "inference/input_wait",
        "inference/decode", "inference/writer_put", "inference/write_batch",
        "decode_engine/compile", "decode_engine/prefill",
        "decode_engine/decode",
    } <= names
    # decode_engine spans nest under the pipeline's decode stage.
    decodes = [e for e in events if e.get("name") == "inference/decode"]
    prefills = [e for e in events if e.get("name") == "decode_engine/prefill"]
    assert any(
        d["ts"] <= p["ts"] <= d["ts"] + d["dur"] + 1.0
        for d in decodes for p in prefills
    )

    snap = telemetry.get_registry().snapshot()
    assert snap["decode_engine/calls"] == 2
    assert snap["decode_engine/compiles{kind=prefill}"] >= 1
    # The hits are the engine's own tally (`/stats` `decode_engine.*`);
    # the registry holds no copy of them.
    from tf_yarn_tpu.models.decode_engine import get_engine

    assert get_engine(model).stats["prefill_cache_hits"] >= 1
    assert not any(k.startswith("decode_engine/cache_hits") for k in snap)
    assert "inference/stage_seconds_sum{stage=decode}" in snap
    assert "decode_engine/compile_seconds_sum{kind=decode}" in snap


def test_jsonl_env_sink_end_to_end(tmp_path, monkeypatch):
    monkeypatch.setenv("TPU_YARN_TRACE", str(tmp_path))
    monkeypatch.setenv("TPU_YARN_TRACE_JSONL", "1")
    try:
        path = telemetry.enable_env_jsonl("worker:1")
        assert path == str(tmp_path / "spans_worker-1.jsonl")
        with telemetry.span("streamed"):
            pass
        lines = [json.loads(line) for line in open(path)]
        assert any(rec["name"] == "streamed" for rec in lines)
    finally:
        telemetry.close_jsonl_sinks()
