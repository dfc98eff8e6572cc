"""The fifth architecture as it stands in the benchmark: the `laguna` stage
(configs/laguna_xs2_serve_1chip.json and the files it names) run whole through
`run_on_tpu` at a tiny size on the CPU — sound `correct: true`, the int8
control and an altered token `correct: false` — and the names and arrows of
the entries its cell brought."""

import json
import os
import sys

import cloudpickle
import pytest

import control_run
from cellbench import agent, run, serve, weights
from cellbench.opcount import laguna_paged_read, laguna_step
from cellbench.readers import scope_roofline
from tiny_bench import BENCH, REAL, ROOT

HERE = os.path.join(ROOT, "cellbench")
CELL, CONFIG = "laguna_codeagent_backlog", "laguna_xs2_serve_1chip"
AGENT = [
    "step_attention_share.agent", "step_moe_share.agent",
    "step_mlp_share.agent", "moe_experts_touched.agent",
    "moe_load_max_over_mean.agent", "prefill_share.agent",
    "step_roofline.agent", "cache_read_over_live.agent", "kv_cache_gb.agent",
    "window_read_share.agent", "paged_read_roofline.agent"]


def _bench():
    """tiny_bench's two cells and a third: the tiny stage under the tiny
    closed loop, listed wherever the real cell is."""
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(
        {"name": "tiny_laguna", "file": "cellbench/tests/data/tiny_laguna.json"})
    bench["workloads"].append({"name": "tiny_agent", "config": "tiny_laguna",
                               "traffic": "test_tiny_backlog", "chips": 1})
    real = {m["name"]: m for m in REAL["end_to_end"] + REAL["per_layer"]}
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in real[metric["name"]].get("workloads", []):
            metric["workloads"].append("tiny_agent")
    return bench


def test_the_stage_is_served_and_correct_on_the_cpu():
    line = run.run_cell("tiny_agent", 3_000_000_042, 5.0, True,
                        require_chip=False, bench=_bench())
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["compared"]["compared_tokens"]["value"] >= 400
    metrics = line["metrics"]
    # what the program counts reads the same on any device: all 16 experts
    # are held, 4 slots x top 2 reach at most 8 of them a layer-step
    assert 1.0 <= metrics["moe_experts_touched.agent"]["value"] <= 8.0
    assert metrics["moe_load_max_over_mean.agent"]["value"] >= 1.0
    # the plain read takes the whole table of 128 on the two full layers,
    # the three rings their 16 rows: more than what is live
    assert metrics["cache_read_over_live.agent"]["value"] > 1.0
    # rings 3 x 16 rows a slot-step against 2 x 128 off the pool
    assert metrics["window_read_share.agent"]["value"] == pytest.approx(
        100 * 3 * 16 / (3 * 16 + 2 * 128))
    # two full layers' K and V over 4 slots x 128 tokens and the trash block
    # of 16, three sliding layers' rings of 16 rows a slot; [2, 16] float32
    assert metrics["kv_cache_gb.agent"]["value"] == pytest.approx(
        (2 * 2 * (4 * 128 + 16) + 3 * 2 * 4 * 16) * 32 * 4 * 1e-9)
    assert 0 < metrics["prefill_share.agent"]["value"] < 100
    assert metrics["engine_compiles_in_window.backlog"]["value"] == 0
    # no kernel reads the pool off the TPU: that share has nothing to read
    assert "paged_read_roofline.agent" not in metrics
    with open(os.path.join(ROOT, "cellbench_cache", "runs",
                           "tiny_agent-3000000042-1", "run.json")) as fh:
        record = json.load(fh)
    assert sorted(c["index"] for c in record["calls"]
                  if c["status"] != "refused") == list(range(len(record["calls"])))
    stats = record["stats_close"]
    assert stats["state_leaves"] == ["window_key", "window_value"]
    # `/stats` shows paged and ring bytes both, and ring reads apart
    assert set(stats["cache_bytes_by_kind"]) == {"paged", "ring"}
    assert stats["pool_read_token_steps"] > 0 < stats["window_read_token_steps"]
    assert stats["prefix_skipped_stateful"] > 0
    assert stats["decode_engine"]["paged_attention"] == "plain"
    need = laguna_step.count(record)
    assert need is not None and need["pool_rows_a_step"] > 0 < need["window_rows_a_step"]
    assert need["bytes"] > need["weight_bytes"] + need["cache_bytes"] > 0


def test_lower_precision_is_not_correct(monkeypatch):
    monkeypatch.setattr(serve, "run_check", control_run.control_check)
    line = run.run_cell("tiny_agent", 3_000_000_019, 5.0, False,
                        require_chip=False, bench=_bench())
    compared = line["compared"]
    assert line["correct"] is False and line["failed"] == 0
    assert compared["compared_tokens"]["value"] >= compared["compared_tokens"]["limit"]
    assert any(compared[k]["value"] > compared[k]["limit"]
               for k in ("gap_mean", "gap_p99"))


def _altered_token_experiment(spec):
    """Built in the task in the sound experiment's place: every token is
    altered where the step produces it."""
    from tf_yarn_tpu.models.decode_engine import DecodeEngine

    sound, vocab = DecodeEngine.paged_state_step, spec["config"]["vocab_size"]

    def broken(self, *args, **kwargs):
        pool, state, emitted, *rest = sound(self, *args, **kwargs)
        return (pool, state, (emitted + 1) % vocab, *rest)

    DecodeEngine.paged_state_step = broken
    return agent.serving_experiment(spec)


def test_an_altered_token_is_not_correct(monkeypatch):
    # the task cannot import this module: its function travels by value
    cloudpickle.register_pickle_by_value(sys.modules[__name__])
    monkeypatch.setattr(agent, "serving_experiment", _altered_token_experiment)
    line = run.run_cell("tiny_agent", 3_000_000_023, 5.0, False,
                        require_chip=False, bench=_bench())
    assert line["correct"] is False
    compared = line["compared"]
    assert compared["gap_mean"]["value"] > compared["gap_mean"]["limit"]


def test_the_cell_and_its_entries():
    """The new entries stand together, each lists the cell alone and moves
    what it reports; the cell shares what the other backlog cells share."""
    cell, = [c for c in REAL["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "codeagent_backlog", 1)
    config, = [c for c in REAL["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["source"] == run.load_json(
        HERE, "configs", CONFIG + ".json")["source"]
    assert not [c for c in REAL["workloads"] if c["chips"] == 4]
    tokens, = [m for m in REAL["end_to_end"] if m["name"] == "serve_tokens_per_s"]
    assert CELL in tokens["workloads"]
    names = [m["name"] for m in REAL["per_layer"]]
    new = [m for m in REAL["per_layer"] if m["name"].endswith(".agent")]
    assert [m["name"] for m in new] == AGENT
    first = names.index(AGENT[0])
    assert REAL["per_layer"][first:first + len(new)] == new  # side by side
    for metric in new:
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "serve_tokens_per_s"
    files = {name: run.metric_file(name) for name in AGENT}
    assert files["step_roofline.agent"]["args"]["opcount"] == "laguna_step"
    assert files["paged_read_roofline.agent"] == {
        "reader": "scope_roofline", "args": {
            "programs": ["jit_step"], "scope": "attention/paged_kernel",
            "opcount": "laguna_paged_read"}}
    assert files["cache_read_over_live.agent"]["args"] == {
        "numerator": ["pool_read_token_steps", "window_read_token_steps"],
        "denominator": ["pool_live_token_steps", "window_live_token_steps"],
        "scale": 1.0}
    assert files["window_read_share.agent"]["args"]["numerator"] == \
        ["window_read_token_steps"]
    assert files["kv_cache_gb.agent"]["args"]["key"] == "cache_hbm_bytes"
    # by the stem's file that is there
    for name in AGENT[:6]:
        assert not os.path.exists(os.path.join(HERE, "metrics", name + ".json"))
    assert files["step_mlp_share.agent"]["args"]["needs"] == "attention"
    shared = [m["name"] for m in REAL["per_layer"]
              if CELL in m["workloads"] and not m["name"].endswith(".agent")]
    longcat = [m["name"] for m in REAL["per_layer"]
               if "longcat_reasoning_backlog" in m["workloads"]
               and not m["name"].endswith(".reason")]
    assert shared == longcat and all(n.endswith(".backlog") or n in (
        "launch_ready_s", "weights_s", "engine_compile_s", "warmup_s",
        "backlog_itl_p95_ms") for n in shared)


def test_the_configuration_keeps_every_published_size():
    sizes = run.load_json(HERE, "configs", CONFIG + ".json")
    assert sizes["reduced"] == ["num_hidden_layers"]
    assert sizes["num_hidden_layers"] == 5
    assert sizes["published"]["num_hidden_layers"] == 40
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row, = [r for r in map(json.loads, fh) if r["name"] == "Laguna-XS.2"]
    assert sizes["source"] == row["source_url"]
    for key, value in row["config"].items():
        # no width, no expert count, no vocabulary and no list is cut
        assert sizes[key] == value or key == "num_hidden_layers", key
    assert sizes["serving"] == {"context": 6144, "max_slots": 64,
                                "queue_capacity": 128}
    mix = run.load_json(HERE, "traffic", "codeagent_backlog.json")
    assert sizes["serving"]["queue_capacity"] >= mix["callers"]
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] == \
        sizes["serving"]["context"]
    assert sizes["check"]["least_tokens"] >= 1000
    for inferred in ("gate", "routing", "no q/k norm, no shared-expert gate",
                     "activation", "residual order", "window", "rope",
                     "layers held", "float32", "torch_dtype"):
        assert inferred in sizes["assumed"], inferred
    assert "8 pipeline stages" in sizes["deployment"]
    # the reckoning of the file's `memory`: 3.870 B parameters
    table = weights.table(sizes)
    count = sum(_elements(shape) for shape, _ in table.values())
    assert 3.869e9 < count < 3.871e9
    assert table["q"][0] == (2, 2048, 48 * 128)
    assert table["swa_q"][0] == (3, 2048, 64 * 128)
    assert table["gate"][0] == (2, 2048, 48)
    assert table["swa_gate"][0] == (3, 2048, 64)
    assert table["w_in"][0] == (4, 256, 2048, 1024)
    assert table["dense_up"][0] == (1, 2048, 8192)
    assert table["head"][0] == (2048, 100352)
    model = agent.build_model(sizes).config
    assert model.layer_types == (
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention")
    assert model.heads == (48, 64, 64, 64, 48)
    assert model.mlp_types == ("dense",) + ("sparse",) * 4
    assert (model.num_experts_here, model.routed_scale, model.window) == \
        (256, 2.5, 512)


def _elements(shape):
    n = 1
    for dim in shape:
        n *= dim
    return n


def test_the_traffic_is_the_issues():
    mix = run.load_json(HERE, "traffic", "codeagent_backlog.json")
    assert mix["driver"] == "serve_closed_loop" and mix["callers"] == 96
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.8, "min": 128, "max": 4096}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 768,
                                    "sigma": 0.6, "min": 128, "max": 2048}
    assert (mix["lead_in_s"], mix["block"], mix["order_seed"]) == (16, 32, 42)
    assert mix["warmup_prompt_lengths"] == [65, 129, 257, 513, 1025, 2049]


def _made(steps=1000):
    sizes = run.load_json(HERE, "configs", CONFIG + ".json")

    def live(shape, dtype="bfloat16"):
        return {"shape": list(shape), "dtype": dtype, "count": 1}

    arrays = []
    for name, (shape, _) in weights.table(sizes).items():
        single = name in ("embedding", "head", "final_norm")
        matrix = len(shape) - (not single) >= 2
        arrays.append(live(shape if single else shape[1:],
                           "bfloat16" if matrix else "float32"))
    arrays.append(live((1, 64 * 384 + 1, 16, 8, 128)))
    arrays.append(live((64, 1, 512, 8, 128)))
    return {
        "config": sizes, "device": {"live_arrays": arrays, "kind": "TPU v5 lite"},
        "stats_open": dict.fromkeys(laguna_step.COUNTERS, 0),
        "stats_close": {
            "moe_layer_steps": 4 * steps, "moe_experts_touched": 4 * 221 * steps,
            "moe_assignments_here": 4 * 512 * steps, "slot_steps": 64 * steps,
            "pool_live_token_steps": 2 * 64 * 1500 * steps,
            "window_live_token_steps": 3 * 64 * 512 * steps},
    }


def test_what_a_step_needs_is_counted_from_the_run():
    """Against a hand count: 64 slots at 1500 live tokens, 221 of 256
    experts touched a layer."""
    made = _made()
    need = laguna_step.count(made)
    assert need["active_slots"] == 64 and need["experts_touched_a_step"] == 4 * 221
    outside = (2 * 29.46e6 + 3 * 37.88e6 + 50.33e6
               + 4 * (0.524e6 + 3.146e6) + 205.5e6)
    experts = 4 * 221 * 3.146e6
    # 2 B an element: about 0.79 GB outside the experts, 5.56 GB of experts
    assert need["weight_bytes"] == pytest.approx(2 * (outside + experts), rel=2e-3)
    row = 2 * 8 * 128 * 2                       # a key and a value, bfloat16
    rows = 2 * 64 * 1500 + 3 * 64 * 512
    assert need["cache_bytes"] == (rows + 64 * 5) * row
    assert need["bytes"] == pytest.approx(
        need["weight_bytes"] + need["cache_bytes"] + 64 * 2048 * 2)
    attend = 4 * 128 * (2 * 64 * 1500 * 48 + 3 * 64 * 512 * 64)
    matrices = 2 * 64 * outside + 2 * 4 * 512 * 3.146e6
    assert need["flops"] == pytest.approx(attend + matrices, rel=2e-3)
    read = laguna_paged_read.count(made)
    assert read["bytes"] == 2 * 64 * 1500 * row   # as stored, once
    assert read["flops"] == 2 * 64 * 1500 * 48 * 4 * 128
    made["stats_close"].pop("pool_live_token_steps")
    # a program without the counters
    assert laguna_step.count(made) is None
    assert laguna_paged_read.count(made) is None


def test_the_kernels_share_reads_its_scope_or_nothing():
    made = dict(_made(), trace={"modules": {"jit_step(123)": (100, 1.2)}},
                peaks=run.load_json(HERE, "peaks.json"))
    paths = {
        "step/LagunaLM/layer_0/attn/attention/paged_kernel": 0.1,
        "step/LagunaLM/layer_4/attn/attention/paged_kernel": 0.1,
        "step/LagunaLM/layer_1/attn/attention/window_read": 0.3,
        "step/LagunaLM/layer_1/moe/experts": 1.0}
    made["scope_seconds:jit_step"] = paths
    about = dict(programs=["jit_step"], scope="attention/paged_kernel",
                 opcount="laguna_paged_read")
    # 0.786 GB a step at 819 GB/s is 0.96 ms; the kernels took 2 ms a step
    assert scope_roofline.read(made, **about) == pytest.approx(
        100 * (2 * 64 * 1500 * 4096 / 819e9) / 0.002)
    made["scope_seconds:jit_step"] = {k: v for k, v in paths.items()
                                      if "paged_kernel" not in k}
    assert scope_roofline.read(made, **about) is None  # the plain gather
    made["scope_seconds:jit_step"] = paths
    made["stats_close"].pop("pool_live_token_steps")
    assert scope_roofline.read(made, **about) is None  # nothing to count
    assert scope_roofline.read({"trace": None}, **about) is None
