"""The third architecture as it stands in the benchmark: the `dots3_note`
share (configs/dots3_note_serve_1chip.json and the files it names) run whole
through `run_on_tpu` at a tiny size on the CPU — sound `correct: true`, the
int8 control `correct: false` — and the names and arrows of the entries its
cell brought."""

import json
import os

import pytest

import control_run
from cellbench import run, serve, weights
from cellbench.opcount import dots3_step
from cellbench.readers import span_window_share, stats_share
from tiny_bench import BENCH, REAL, ROOT

HERE = os.path.join(ROOT, "cellbench")
CELL, CONFIG = "dots3_longdoc_backlog", "dots3_note_serve_1chip"


def _bench():
    """tiny_bench's two cells and a third: the tiny share under the tiny
    closed loop, listed wherever the real cell is."""
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(
        {"name": "tiny_dots3", "file": "cellbench/tests/data/tiny_dots3.json"})
    bench["workloads"].append({"name": "tiny_longdoc", "config": "tiny_dots3",
                               "traffic": "test_tiny_backlog", "chips": 1})
    real = {m["name"]: m for m in REAL["end_to_end"] + REAL["per_layer"]}
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in real[metric["name"]].get("workloads", []):
            metric["workloads"].append("tiny_longdoc")
    return bench


def test_the_share_is_served_and_correct_on_the_cpu():
    line = run.run_cell("tiny_longdoc", 3_000_000_034, 5.0, True,
                        require_chip=False, bench=_bench())
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["compared"]["compared_tokens"]["value"] >= 400
    metrics = line["metrics"]
    # what the program counts reads the same on any device
    assert metrics["moe_held_share.longdoc"]["value"] == pytest.approx(50.0, abs=15)
    assert 1.0 <= metrics["moe_experts_touched.longdoc"]["value"] <= 8.0
    assert metrics["moe_load_max_over_mean.longdoc"]["value"] >= 1.0
    assert 0 < metrics["index_selected_share.longdoc"]["value"] <= 100.0
    # a chunk of 32 index keys, 24 latent rows and a ring of 16 a slot-step,
    # of short requests: more read than is live
    assert metrics["cache_read_over_live.longdoc"]["value"] > 1.0
    # two full layers' rows (24 + 16 numbers) over 4 slots x 128 tokens and
    # the trash block of 16, and three rings of 16 rows x 40 a slot; float32
    pool = 2 * (4 * 128 + 16) * (24 + 16) * 4
    assert metrics["latent_cache_gb.longdoc"]["value"] == pytest.approx(
        (pool + 3 * 4 * 16 * 40 * 4) * 1e-9)
    assert 0 < metrics["prefill_share.longdoc"]["value"] < 100
    assert metrics["engine_compiles_in_window.backlog"]["value"] == 0
    with open(os.path.join(ROOT, "cellbench_cache", "runs",
                           "tiny_longdoc-3000000034-1", "run.json")) as fh:
        record = json.load(fh)
    # the queue holds every caller: none is turned away, so the list is
    # served in its order, lead-in included
    assert sorted(c["index"] for c in record["calls"]
                  if c["status"] != "refused") == list(range(len(record["calls"])))
    stats = record["stats_close"]
    assert stats["state_leaves"] == ["window_latent"]
    assert set(stats["cache_bytes_by_kind"]) == {"paged", "ring"}
    # (read from another thread: an admission may be between the two)
    assert abs(stats["prefix_skipped_stateful"] - stats["state_resets"]) <= 1
    assert stats["state_resets"] > 50
    assert stats["prefix_cache"]["hits"] == 0
    need = dots3_step.count(record)
    assert need is not None and need["selected_rows_a_step"] > 0
    assert need["bytes"] > need["weight_bytes"] + need["cache_bytes"] > 0


def test_lower_precision_is_not_correct(monkeypatch):
    monkeypatch.setattr(serve, "run_check", control_run.control_check)
    line = run.run_cell("tiny_longdoc", 3_000_000_019, 5.0, False,
                        require_chip=False, bench=_bench())
    compared = line["compared"]
    assert line["correct"] is False and line["failed"] == 0
    assert compared["compared_tokens"]["value"] >= compared["compared_tokens"]["limit"]
    assert any(compared[k]["value"] > compared[k]["limit"]
               for k in ("gap_mean", "gap_p99"))


def test_the_cell_and_its_entries():
    cell, = [c for c in REAL["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longdoc_backlog", 1)
    assert not [c for c in REAL["workloads"] if c["chips"] == 4]
    tokens, = [m for m in REAL["end_to_end"] if m["name"] == "serve_tokens_per_s"]
    assert CELL in tokens["workloads"]
    new = [m for m in REAL["per_layer"] if m["name"].endswith(".longdoc")]
    assert [m["name"] for m in new] == [
        "step_roofline.longdoc", "step_latent_share.longdoc",
        "step_indexer_share.longdoc", "step_moe_share.longdoc",
        "index_selected_share.longdoc", "cache_read_over_live.longdoc",
        "moe_held_share.longdoc", "moe_load_max_over_mean.longdoc",
        "moe_experts_touched.longdoc", "latent_cache_gb.longdoc",
        "prefill_share.longdoc"]
    for metric in new:
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "serve_tokens_per_s"
    assert run.metric_file("step_roofline.longdoc")["args"]["opcount"] == "dots3_step"
    shared = [m["name"] for m in REAL["per_layer"]
              if CELL in m["workloads"] and not m["name"].endswith(".longdoc")]
    assert all(n.endswith(".backlog") or n in (
        "launch_ready_s", "weights_s", "engine_compile_s", "warmup_s",
        "backlog_itl_p95_ms") for n in shared)
    # no operation of this model is under `attention` (which the dense
    # layer's `mlp` share needs too), no view is gathered, and the
    # Llama-shaped step's needs are not this model's
    assert not {"step_attention_share.backlog", "step_mlp_share.backlog",
                "step_kv_gather_share.backlog", "step_roofline.backlog"} \
        & set(shared)


def test_the_configuration_keeps_every_published_width():
    sizes = run.load_json(HERE, "configs", CONFIG + ".json")
    assert sizes["reduced"] == ["num_hidden_layers", "n_routed_experts_here",
                                "vocab_size"]
    assert (sizes["num_hidden_layers"], sizes["n_routed_experts_here"],
            sizes["vocab_size"]) == (5, 16, 19008)
    assert sizes["published"]["num_hidden_layers"] == 46
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert sizes["layer_types"] == ["full_attention"] * 2 + period * 11  # whole
    assert sizes["layer_types"][:5] == ["full_attention"] * 2 + period[:3]
    published = {
        "hidden_size": 5120, "intermediate_size": 13824,
        "moe_intermediate_size": 1536, "n_routed_experts": 256,
        "num_experts_per_tok": 8, "n_shared_experts": 1,
        "num_attention_heads": 128, "q_lora_rank": 1024, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "rope_theta": 80000000, "swa_num_attention_heads": 64,
        "swa_q_lora_rank": 1024, "swa_kv_lora_rank": 1024,
        "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
        "swa_v_head_dim": 128, "swa_rope_theta": 50000,
        "sliding_window_size": 513, "index_n_heads": 64, "index_head_dim": 128,
        "index_topk": 2048, "first_k_dense_replace": 1,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "apply_mla_qkv_lora_rescale": True, "attention_gate_type": "headwise",
        "tie_word_embeddings": False, "rms_norm_eps": 1e-05,
    }
    assert {k: sizes[k] for k in published} == published
    assert sizes["serving"] == {"context": 6144, "max_slots": 64,
                                "queue_capacity": 128}
    # the queue holds every caller: none is turned away while the slots fill
    mix = run.load_json(HERE, "traffic", "longdoc_backlog.json")
    assert sizes["serving"]["queue_capacity"] >= mix["callers"]
    for inferred in ("apply_mla_qkv_lora_rescale", "attention_gate_type",
                     "indexer", "routing", "rope", "sliding_window_size",
                     "float32", "torch_dtype", "serving.context",
                     "serving.max_slots", "serving.queue_capacity", "not run"):
        assert inferred in sizes["assumed"], inferred
    # the reckoning of the file's `memory`: 2.577 B parameters
    count = sum(_elements(shape) for shape, _ in weights.table(sizes).values())
    assert 2.57e9 < count < 2.58e9


def _elements(shape):
    n = 1
    for dim in shape:
        n *= dim
    return n


def test_the_traffic_is_the_issues():
    mix = run.load_json(HERE, "traffic", "longdoc_backlog.json")
    assert mix["driver"] == "serve_closed_loop" and mix["callers"] == 112
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 2048,
                                    "sigma": 0.5, "min": 512, "max": 4096}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 384,
                                    "sigma": 0.6, "min": 64, "max": 1536}
    assert (mix["lead_in_s"], mix["block"], mix["order_seed"]) == (16, 32, 34)
    assert mix["warmup_prompt_lengths"] == [257, 513, 1025, 2049]


def test_what_a_step_needs_is_counted_from_the_run():
    sizes = run.load_json(HERE, "configs", CONFIG + ".json")

    def live(shape, dtype="bfloat16"):
        return {"shape": list(shape), "dtype": dtype, "count": 1}

    arrays = []
    for name, (shape, _) in weights.table(sizes).items():
        single = name in ("embedding", "head", "final_norm")
        matrix = len(shape) - (not single) >= 2
        arrays.append(live(shape if single else shape[1:],
                           "bfloat16" if matrix else "float32"))
    arrays += [live((1, 24577, 16, 576)), live((1, 24577, 16, 128)),
               live((64, 1, 528, 1088))]
    steps = 1000
    made = {
        "config": sizes, "device": {"live_arrays": arrays},
        "stats_open": dict.fromkeys(dots3_step.COUNTERS, 0),
        "stats_close": {
            "moe_layer_steps": 4 * steps, "moe_experts_touched": 4 * 13 * steps,
            "moe_assignments_here": 4 * 32 * steps, "slot_steps": 64 * steps,
            "index_live_token_steps": 2 * 64 * 3000 * steps,
            "index_selected_token_steps": 2 * 64 * 2048 * steps,
            "window_live_token_steps": 3 * 64 * 513 * steps},
    }
    need = dots3_step.count(made)
    assert need["active_slots"] == 64 and need["experts_touched_a_step"] == 52
    # 2.577 B parameters less the embedding's 97.3 M and 3 of 16 experts
    # untouched in each of 4 layers, 2 B each
    assert need["weight_bytes"] == pytest.approx(
        2 * (2.5772e9 - 19008 * 5120 - 4 * 3 * 23.59e6), rel=2e-3)
    rows = 2 * 64 * (3000 * 128 + 2048 * 576) + 3 * 64 * 513 * 1088
    written = 64 * (2 * (576 + 128) + 3 * 1088)
    assert need["cache_bytes"] == 2 * (rows + written)
    assert need["bytes"] == need["weight_bytes"] + need["cache_bytes"] \
        + 64 * 5120 * 2
    # the absorbed product over the selected rows is on the chip's ridge
    attend = 2 * 64 * 2048 * 128 * (4 * 512 + 2 * 64)
    assert need["flops"] > attend > 0.5e11
    made["stats_close"].pop("index_selected_token_steps")
    assert dots3_step.count(made) is None  # a program without the counters


def test_the_new_readers_read_or_nothing():
    made = {"stats_open": {"a": 1, "b": 10}, "stats_close": {"a": 4, "b": 16}}
    assert stats_share.read(made, ["a"], ["b"]) == pytest.approx(50.0)
    assert stats_share.read(made, ["a"], ["b", "b"], scale=1.0) == pytest.approx(0.25)
    assert stats_share.read(made, ["a"], ["no_such"]) is None
    assert stats_share.read(made, ["a"], ["a", "b"]) == pytest.approx(100 * 3 / 9)
    made = {"window": (10.0, 20.0), "spans": [
        {"name": "serving/prefill", "start": 11.0, "dur": 1.5, "args": {}},
        {"name": "serving/prefill", "start": 19.5, "dur": 1.0, "args": {}},
        {"name": "serving/step", "start": 12.0, "dur": 5.0, "args": {}}]}
    assert span_window_share.read(made, "serving/prefill") == pytest.approx(15.0)
    assert span_window_share.read(made, "serving/none") is None
