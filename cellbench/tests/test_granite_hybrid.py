"""The second architecture as it stands in the benchmark: the
`granitemoehybrid` share (configs/granite4h_small_serve_1chip.json and the
files it names) run whole through `run_on_tpu` at a tiny size on the CPU —
sound `correct: true`, the int8 control `correct: false` — and the names and
arrows of the entries its cell brought."""

import json
import os

import pytest

import control_run
from cellbench import run, serve, weights
from cellbench.opcount import granite_step
from cellbench.readers import stats_field
from tiny_bench import BENCH, REAL, ROOT

HERE = os.path.join(ROOT, "cellbench")
CELL, CONFIG = "granite4h_longgen_backlog", "granite4h_small_serve_1chip"


def _bench():
    """tiny_bench's two cells and a third: the tiny share under the tiny
    closed loop, listed wherever the real cell is."""
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(
        {"name": "tiny_granite", "file": "cellbench/tests/data/tiny_granite.json"})
    bench["workloads"].append({"name": "tiny_longgen", "config": "tiny_granite",
                               "traffic": "test_tiny_backlog", "chips": 1})
    real = {m["name"]: m for m in REAL["end_to_end"] + REAL["per_layer"]}
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in real[metric["name"]].get("workloads", []):
            metric["workloads"].append("tiny_longgen")
    return bench


def test_the_share_is_served_and_correct_on_the_cpu():
    line = run.run_cell("tiny_longgen", 3_000_000_029, 5.0, True,
                        require_chip=False, bench=_bench())
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["compared"]["compared_tokens"]["value"] >= 400
    metrics = line["metrics"]
    # what the program counts reads the same on any device
    assert metrics["moe_held_share.longgen"]["value"] == pytest.approx(50.0, abs=15)
    assert 1.0 <= metrics["moe_experts_touched.longgen"]["value"] <= 4.0
    assert metrics["moe_load_max_over_mean.longgen"]["value"] >= 1.0
    assert metrics["state_gb.longgen"]["value"] == pytest.approx(
        4 * 2 * 4 * (16 * 8 * 16 + 3 * 160) * 1e-9)
    assert metrics["engine_compiles_in_window.backlog"]["value"] == 0
    with open(os.path.join(ROOT, "cellbench_cache", "runs",
                           "tiny_longgen-3000000029-1", "run.json")) as fh:
        record = json.load(fh)
    stats = record["stats_close"]
    assert stats["state_leaves"] == ["conv_state", "ssm_state"]
    # (read from another thread: an admission may be between the two)
    assert abs(stats["prefix_skipped_stateful"] - stats["state_resets"]) <= 1
    assert stats["state_resets"] > 50
    assert stats["prefix_cache"]["hits"] == 0


def test_lower_precision_is_not_correct(monkeypatch):
    monkeypatch.setattr(serve, "run_check", control_run.control_check)
    line = run.run_cell("tiny_longgen", 3_000_000_019, 5.0, False,
                        require_chip=False, bench=_bench())
    compared = line["compared"]
    assert line["correct"] is False and line["failed"] == 0
    assert compared["compared_tokens"]["value"] >= compared["compared_tokens"]["limit"]
    assert any(compared[k]["value"] > compared[k]["limit"]
               for k in ("gap_mean", "gap_p99"))


def test_the_cell_and_its_entries():
    cell, = [c for c in REAL["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longgen_backlog", 1)
    assert len(REAL["workloads"]) == 3 and len(REAL["configs"]) == 2
    assert not [c for c in REAL["workloads"] if c["chips"] == 4]
    tokens, = [m for m in REAL["end_to_end"] if m["name"] == "serve_tokens_per_s"]
    assert tokens["workloads"] == ["mistral7b_chat_backlog", CELL]
    new = [m for m in REAL["per_layer"] if m["name"].endswith(".longgen")]
    assert [m["name"] for m in new] == [
        "step_roofline.longgen", "step_moe_share.longgen",
        "step_ssm_share.longgen", "moe_held_share.longgen",
        "moe_load_max_over_mean.longgen", "moe_experts_touched.longgen",
        "state_gb.longgen"]
    assert REAL["per_layer"][-len(new):] == new  # appended
    for metric in new:
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "serve_tokens_per_s"
    assert run.metric_file("step_roofline.longgen")["args"]["opcount"] == "granite_step"
    shared = [m["name"] for m in REAL["per_layer"]
              if CELL in m["workloads"] and not m["name"].endswith(".longgen")]
    assert all(n.endswith(".backlog") or n in (
        "launch_ready_s", "weights_s", "engine_compile_s", "warmup_s",
        "backlog_itl_p95_ms") for n in shared)
    # the dense MLP and the Llama-shaped step's needs are not this model's
    assert not {"step_mlp_share.backlog", "step_roofline.backlog"} & set(shared)


def test_the_configuration_keeps_every_published_width():
    sizes = run.load_json(HERE, "configs", CONFIG + ".json")
    assert sizes["reduced"] == ["num_hidden_layers", "num_local_experts_here",
                                "vocab_size"]
    assert (sizes["num_hidden_layers"], sizes["num_local_experts_here"],
            sizes["vocab_size"]) == (10, 18, 25088)
    assert sizes["published"]["num_hidden_layers"] == 40
    period = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert sizes["layer_types"] == period * 4  # whole, as published
    assert sizes["layer_types"][:sizes["num_hidden_layers"]] == period
    published = {
        "hidden_size": 4096, "intermediate_size": 768,
        "shared_intermediate_size": 1536, "num_local_experts": 72,
        "num_experts_per_tok": 10, "num_attention_heads": 32,
        "num_key_value_heads": 8, "mamba_n_heads": 128, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_d_conv": 4, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_chunk_size": 256,
        "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 16,
        "position_embedding_type": "nope", "tie_word_embeddings": True,
    }
    assert {k: sizes[k] for k in published} == published
    assert sizes["serving"] == {"context": 4096, "max_slots": 32}
    # the reckoning of the file's `memory`: 2.956 B parameters
    count = sum(_elements(shape) for shape, _ in weights.table(sizes).values())
    assert 2.95e9 < count < 2.96e9


def _elements(shape):
    n = 1
    for dim in shape:
        n *= dim
    return n


def test_the_traffic_is_the_issues():
    mix = run.load_json(HERE, "traffic", "longgen_backlog.json")
    assert mix["driver"] == "serve_closed_loop" and mix["callers"] == 80
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 128,
                                    "sigma": 0.8, "min": 32, "max": 1024}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 512,
                                    "sigma": 0.6, "min": 128, "max": 2048}
    assert (mix["lead_in_s"], mix["block"], mix["order_seed"]) == (16, 32, 29)
    # the issue's six, letter for letter (a 32-token prompt prefills the
    # 16-token bucket, which these do not warm: PERF.md, PR 29)
    assert mix["warmup_prompt_lengths"] == [33, 65, 129, 257, 513, 1025]
    assert "warmup_note" not in mix


def test_what_a_step_needs_is_counted_from_the_run():
    sizes = run.load_json(HERE, "configs", CONFIG + ".json")

    def live(shape, dtype="bfloat16"):
        return {"shape": list(shape), "dtype": dtype, "count": 1}

    arrays = []
    for name, (shape, _) in weights.table(sizes).items():
        matrix = len(shape) - (name != "embedding") >= 2 and name != "conv_w"
        one = shape if name in ("embedding", "final_norm") else shape[1:]
        arrays.append(live(one, "bfloat16" if matrix else "float32"))
    arrays += [live((32, 1, 128, 64, 128), "float32"),
               live((32, 1, 3, 8448), "float32"),
               live((1, 8193, 16, 8, 128))]
    spans = [{"name": "serving/step", "start": 10.0 + 0.03 * i, "dur": 0.02,
              "args": {}} for i in range(100)]
    made = {
        "config": sizes, "device": {"live_arrays": arrays},
        "trace_window": (10.0, 13.0), "spans": spans + [
            {"name": "serving/submit", "start": 1.0, "dur": 0.0,
             "args": {"request_id": "cell-0"}},
            {"name": "serving/prefill", "start": 9.0, "dur": 0.1,
             "args": {"prefill": 64}}],
        "calls": [{"index": 0, "prompt_tokens": 100, "max_new_tokens": 500}],
        "stats_open": {"moe_experts_touched": 0, "moe_layer_steps": 0,
                       "moe_assignments_here": 0},
        "stats_close": {"moe_experts_touched": 17 * 1000, "moe_layer_steps": 1000,
                        "moe_assignments_here": 2500},
    }
    need = granite_step.count(made)
    assert need["active_slots"] == 1.0 and need["experts_touched_a_step"] == 170
    # 2.956 B parameters less one of 18 experts in each of 10 layers, 2 B each
    assert need["weight_bytes"] == pytest.approx(2 * (2.956e9 - 10 * 9.44e6), rel=2e-3)
    assert need["state_bytes"] == 9 * 2 * (128 * 64 * 128 + 3 * 8448) * 4
    assert need["bytes"] > need["weight_bytes"] + need["state_bytes"]
    made["stats_close"].pop("moe_layer_steps")
    assert granite_step.count(made) is None  # a program without the counters


def test_a_stats_field_is_read_or_nothing():
    made = {"stats_close": {"state_bytes": 1_237_155_840}}
    assert stats_field.read(made, "state_bytes", 1e-9) == pytest.approx(1.23715584)
    assert stats_field.read(made, "no_such_field") is None
