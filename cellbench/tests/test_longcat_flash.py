"""The fourth architecture as it stands in the benchmark: the `LongCat-Flash`
share (configs/longcat_flash_serve_1chip.json and the files it names) run
whole through `run_on_tpu` at a tiny size on the CPU — sound `correct: true`,
the int8 control `correct: false` — and the names and arrows of the entries
its cell brought."""

import json
import os

import pytest

import control_run
from cellbench import run, serve, weights
from cellbench.opcount import longcat_latent_read, longcat_step
from cellbench.readers import scope_roofline
from tiny_bench import BENCH, REAL, ROOT

HERE = os.path.join(ROOT, "cellbench")
CELL, CONFIG = "longcat_reasoning_backlog", "longcat_flash_serve_1chip"
REASON = [
    "step_roofline.reason", "step_latent_share.reason",
    "step_moe_share.reason", "step_mlp_share.reason",
    "moe_held_share.reason", "moe_zero_share.reason",
    "moe_load_max_over_mean.reason", "moe_experts_touched.reason",
    "latent_cache_gb.reason", "cache_read_over_live.reason",
    "prefill_share.reason", "latent_read_roofline.reason"]


def _bench():
    """tiny_bench's two cells and a third: the tiny share under the tiny
    closed loop, listed wherever the real cell is."""
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(
        {"name": "tiny_longcat", "file": "cellbench/tests/data/tiny_longcat.json"})
    bench["workloads"].append({"name": "tiny_reason", "config": "tiny_longcat",
                               "traffic": "test_tiny_backlog", "chips": 1})
    real = {m["name"]: m for m in REAL["end_to_end"] + REAL["per_layer"]}
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in real[metric["name"]].get("workloads", []):
            metric["workloads"].append("tiny_reason")
    return bench


def test_the_share_is_served_and_correct_on_the_cpu():
    line = run.run_cell("tiny_reason", 3_000_000_037, 5.0, True,
                        require_chip=False, bench=_bench())
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["compared"]["compared_tokens"]["value"] >= 400
    metrics = line["metrics"]
    # what the program counts reads the same on any device: 8 of the
    # router's 24 outputs are held here and 8 return their input
    assert metrics["moe_held_share.reason"]["value"] == pytest.approx(33.3, abs=12)
    assert metrics["moe_zero_share.reason"]["value"] == pytest.approx(33.3, abs=12)
    assert 1.0 <= metrics["moe_experts_touched.reason"]["value"] <= 8.0
    assert metrics["moe_load_max_over_mean.reason"]["value"] >= 1.0
    # the plain gather reads the whole table of 128 for requests far shorter
    assert metrics["cache_read_over_live.reason"]["value"] > 1.0
    # four sublayers' rows (16 + 8 numbers) over 4 slots x 128 tokens and
    # the trash block of 16; float32; nothing held once a slot
    assert metrics["latent_cache_gb.reason"]["value"] == pytest.approx(
        4 * (4 * 128 + 16) * 24 * 4 * 1e-9)
    assert 0 < metrics["prefill_share.reason"]["value"] < 100
    assert metrics["engine_compiles_in_window.backlog"]["value"] == 0
    # no kernel reads the pool off the TPU: that share has nothing to read
    assert "latent_read_roofline.reason" not in metrics
    with open(os.path.join(ROOT, "cellbench_cache", "runs",
                           "tiny_reason-3000000037-1", "run.json")) as fh:
        record = json.load(fh)
    # the queue holds every caller: none is turned away, so the list is
    # served in its order, lead-in included
    assert sorted(c["index"] for c in record["calls"]
                  if c["status"] != "refused") == list(range(len(record["calls"])))
    stats = record["stats_close"]
    assert stats["state_leaves"] == [] and stats["state_resets"] == 0
    assert set(stats["cache_bytes_by_kind"]) == {"paged"}
    assert stats["prefix_skipped_stateful"] == 0
    assert stats["decode_engine"]["paged_attention"] == "plain"
    assert stats["decode_engine"]["params_narrowed"] == 0
    need = longcat_step.count(record)
    assert need is not None and need["latent_rows_a_step"] > 0
    assert need["bytes"] > need["weight_bytes"] + need["cache_bytes"] > 0


def test_lower_precision_is_not_correct(monkeypatch):
    monkeypatch.setattr(serve, "run_check", control_run.control_check)
    line = run.run_cell("tiny_reason", 3_000_000_019, 5.0, False,
                        require_chip=False, bench=_bench())
    compared = line["compared"]
    assert line["correct"] is False and line["failed"] == 0
    assert compared["compared_tokens"]["value"] >= compared["compared_tokens"]["limit"]
    assert any(compared[k]["value"] > compared[k]["limit"]
               for k in ("gap_mean", "gap_p99"))


def test_the_cell_and_its_entries():
    cell, = [c for c in REAL["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "reasoning_backlog", 1)
    config, = [c for c in REAL["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["num_layers", "n_routed_experts_here",
                                 "vocab_size"]
    assert config["source"] == run.load_json(
        HERE, "configs", CONFIG + ".json")["source"]
    assert not [c for c in REAL["workloads"] if c["chips"] == 4]
    tokens, = [m for m in REAL["end_to_end"] if m["name"] == "serve_tokens_per_s"]
    assert tokens["workloads"][-1] == CELL
    new = [m for m in REAL["per_layer"] if m["name"].endswith(".reason")]
    assert [m["name"] for m in new] == REASON
    assert REAL["per_layer"][-len(REASON):] == new  # at the end of the list
    for metric in new:
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "serve_tokens_per_s"
    files = {name: run.metric_file(name) for name in REASON}
    assert files["step_roofline.reason"]["args"]["opcount"] == "longcat_step"
    assert files["latent_read_roofline.reason"] == {
        "reader": "scope_roofline", "args": {
            "programs": ["jit_step"], "scope": "attention/paged_kernel",
            "opcount": "longcat_latent_read"}}
    assert files["moe_zero_share.reason"]["reader"] == "stats_ratio"
    assert files["cache_read_over_live.reason"]["args"]["numerator"] == \
        ["latent_read_token_steps"]
    assert files["step_mlp_share.reason"]["args"]["needs"] == "latent"
    shared = [m["name"] for m in REAL["per_layer"]
              if CELL in m["workloads"] and not m["name"].endswith(".reason")]
    dots3 = [m["name"] for m in REAL["per_layer"]
             if "dots3_longdoc_backlog" in m["workloads"]
             and not m["name"].endswith(".longdoc")]
    assert shared == dots3 and all(n.endswith(".backlog") or n in (
        "launch_ready_s", "weights_s", "engine_compile_s", "warmup_s",
        "backlog_itl_p95_ms") for n in shared)


def test_the_configuration_keeps_every_published_width():
    sizes = run.load_json(HERE, "configs", CONFIG + ".json")
    assert sizes["reduced"] == ["num_layers", "n_routed_experts_here",
                                "vocab_size"]
    assert (sizes["num_layers"], sizes["n_routed_experts_here"],
            sizes["vocab_size"]) == (4, 16, 16384)
    assert sizes["published"]["num_layers"] == 28
    assert sizes["published"]["n_routed_experts_here"] == 512
    assert sizes["published"]["vocab_size"] == 131072
    published = {
        "attention_bias": False, "hidden_size": 6144, "ffn_hidden_size": 12288,
        "expert_ffn_hidden_size": 2048, "num_attention_heads": 64,
        "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12,
    }
    assert {k: sizes[k] for k in published} == published
    assert sizes["serving"] == {"context": 4096, "max_slots": 64,
                                "queue_capacity": 128}
    mix = run.load_json(HERE, "traffic", "reasoning_backlog.json")
    # the queue holds every caller, and the longest request fits a slot
    assert sizes["serving"]["queue_capacity"] >= mix["callers"]
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] == \
        sizes["serving"]["context"]
    for inferred in ("layer", "identity term", "routing", "router", "rope",
                     "float32", "torch_dtype", "serving.context",
                     "serving.max_slots", "serving.queue_capacity", "not run"):
        assert inferred in sizes["assumed"], inferred
    assert "32 chips" in sizes["deployment"] and "224" in sizes["deployment"]
    # the reckoning of the file's `memory`: 5.173 B parameters, and the
    # router 768 wide
    table = weights.table(sizes)
    count = sum(_elements(shape) for shape, _ in table.values())
    assert 5.17e9 < count < 5.18e9
    assert table["router"][0] == (4, 6144, 768)
    assert table["w_in"][0] == (4, 16, 6144, 4096)
    assert table["q_a"][0][0] == table["dense_up"][0][0] == 8  # two a layer


def _elements(shape):
    n = 1
    for dim in shape:
        n *= dim
    return n


def test_the_traffic_is_the_issues():
    mix = run.load_json(HERE, "traffic", "reasoning_backlog.json")
    assert mix["driver"] == "serve_closed_loop" and mix["callers"] == 96
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 384,
                                    "sigma": 0.8, "min": 64, "max": 1536}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 1024,
                                    "sigma": 0.5, "min": 256, "max": 2560}
    assert (mix["lead_in_s"], mix["block"], mix["order_seed"]) == (16, 32, 37)
    assert mix["warmup_prompt_lengths"] == [33, 65, 129, 257, 513, 1025]


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
    arrays.append(live((1, 16385, 16, 640)))
    return {
        "config": sizes, "device": {"live_arrays": arrays, "kind": "TPU v5 lite"},
        "stats_open": dict.fromkeys(longcat_step.COUNTERS, 0),
        "stats_close": {
            "moe_layer_steps": 4 * steps, "moe_experts_touched": 4 * 10 * steps,
            "moe_assignments_here": 4 * 16 * steps, "slot_steps": 64 * steps,
            "latent_live_token_steps": 8 * 64 * 1000 * steps},
    }


def test_what_a_step_needs_is_counted_from_the_run():
    made = _made()
    need = longcat_step.count(made)
    assert need["active_slots"] == 64 and need["experts_touched_a_step"] == 40
    # 5.173 B parameters less the embedding's 100.66 M and 6 of 16 experts
    # untouched in each of 4 layers, 2 B each: the issue's 8.4 GB a step
    assert need["weight_bytes"] == pytest.approx(
        2 * (5.1727e9 - 16384 * 6144 - 4 * 6 * 37.75e6), rel=2e-3)
    assert 8.3e9 < need["weight_bytes"] < 8.5e9
    rows = 8 * 64 * 1000 * 576
    assert need["cache_bytes"] == 2 * (rows + 64 * 8 * 576)
    assert need["bytes"] == need["weight_bytes"] + need["cache_bytes"] \
        + 64 * 6144 * 2
    attend = 8 * 64 * 1000 * 64 * (4 * 512 + 2 * 64)
    assert need["flops"] > attend > 0.5e11
    read = longcat_latent_read.count(made)
    assert read["bytes"] == 8 * 64 * 1000 * 640 * 2  # as stored: whole lanes
    assert read["flops"] == 8 * 64 * 1000 * 64 * 4 * 640
    made["stats_close"].pop("latent_live_token_steps")
    # a program without the counters
    assert longcat_step.count(made) is None
    assert longcat_latent_read.count(made) is None


def test_the_kernels_share_reads_its_scope_or_nothing(monkeypatch):
    made = dict(_made(), trace={"modules": {"jit_step(123)": (100, 1.2)}},
                peaks=run.load_json(HERE, "peaks.json"))
    paths = {
        "step/LongcatLM/layer_0/attn_0/latent/read/attention/paged_kernel": 0.1,
        "step/LongcatLM/layer_0/attn_1/latent/read/attention/paged_kernel": 0.1,
        "step/LongcatLM/layer_0/moe/experts": 1.0}
    made["scope_seconds:jit_step"] = paths
    about = dict(programs=["jit_step"], scope="attention/paged_kernel",
                 opcount="longcat_latent_read")
    # 0.655 GB a step at 819 GB/s is 0.8 ms; the kernels took 2 ms a step
    assert scope_roofline.read(made, **about) == pytest.approx(
        100 * (8 * 64 * 1000 * 640 * 2 / 819e9) / 0.002)
    made["scope_seconds:jit_step"] = {k: v for k, v in paths.items()
                                      if "paged_kernel" not in k}
    assert scope_roofline.read(made, **about) is None  # the plain gather
    made["scope_seconds:jit_step"] = paths
    made["stats_close"].pop("latent_live_token_steps")
    assert scope_roofline.read(made, **about) is None  # nothing to count
    assert scope_roofline.read({"trace": None}, **about) is None
