"""The sixth architecture as it stands in the benchmark: the `deepseek_v32`
share (configs/deepseek_v32_serve_1chip.json and the files it names) run
whole through `run_on_tpu` at a tiny size on the CPU — sound `correct:
true`, the int8 control and an altered token `correct: false` — and the
names and arrows of the entries its cell brought."""

import json
import os
import sys

import cloudpickle
import pytest

import control_run
from cellbench import agent, run, serve, weights
from cellbench.opcount import dsv32_index_read, dsv32_step
from cellbench.readers import scope_roofline, stats_share
from tiny_bench import BENCH, REAL, ROOT

HERE = os.path.join(ROOT, "cellbench")
CELL, CONFIG = "dsv32_reasoning_backlog", "deepseek_v32_serve_1chip"
REDUCED = ["num_hidden_layers", "first_k_dense_replace",
           "n_routed_experts_here", "vocab_size"]
# The benchmark holds 128 per-layer entries at most and held 125: three are
# new, and the quantities that an accepted entry's file already reads are
# reported under that entry, the cell's name appended to its list.
NEW = ["step_roofline.longreason", "indexer_read_roofline.longreason",
       "index_sorted_over_live.longreason"]
BORROWED = [
    "step_latent_share.longdoc", "step_indexer_share.longdoc",
    "step_moe_share.longdoc", "index_selected_share.longdoc",
    "cache_read_over_live.longdoc", "moe_held_share.longdoc",
    "moe_load_max_over_mean.longdoc", "moe_experts_touched.longdoc",
    "latent_cache_gb.longdoc", "prefill_share.longdoc",
    "step_mlp_share.reason"]


def _bench():
    """tiny_bench's two cells and a third: the tiny share under the tiny
    closed loop, listed wherever the real cell is."""
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(
        {"name": "tiny_dsv32", "file": "cellbench/tests/data/tiny_dsv32.json"})
    bench["workloads"].append({"name": "tiny_longreason", "config": "tiny_dsv32",
                               "traffic": "test_tiny_backlog", "chips": 1})
    real = {m["name"]: m for m in REAL["end_to_end"] + REAL["per_layer"]}
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in real[metric["name"]].get("workloads", []):
            metric["workloads"].append("tiny_longreason")
    return bench


def test_the_share_is_served_and_correct_on_the_cpu():
    line = run.run_cell("tiny_longreason", 3_000_000_044, 5.0, True,
                        require_chip=False, bench=_bench())
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["compared"]["compared_tokens"]["value"] >= 300
    metrics = line["metrics"]
    # what the program counts reads the same on any device: 8 of 16 experts
    # held, 4 slots x top 3 reach at most 8 of them a layer-step
    assert 1.0 <= metrics["moe_experts_touched.longdoc"]["value"] <= 8.0
    assert 0 < metrics["moe_held_share.longdoc"]["value"] < 100
    assert metrics["moe_load_max_over_mean.longdoc"]["value"] >= 1.0
    # requests of 9 to 160 tokens against a top-k of 24: keys are discarded
    assert 0 < metrics["index_selected_share.longdoc"]["value"] < 100
    # the sort is as wide as the table of 160, whatever is live
    assert metrics["index_sorted_over_live.longreason"]["value"] > 1.0
    assert metrics["cache_read_over_live.longdoc"]["value"] > 0
    # three layers' latent rows of 24 and index keys of 16 over 4 slots x 160
    # tokens and the trash block of 16; float32
    assert metrics["latent_cache_gb.longdoc"]["value"] == pytest.approx(
        3 * (4 * 160 + 16) * (24 + 16) * 4 * 1e-9)
    assert 0 < metrics["prefill_share.longdoc"]["value"] < 100
    assert metrics["engine_compiles_in_window.backlog"]["value"] == 0
    # no device trace of a TPU: the shares of a roofline have nothing to read
    assert "indexer_read_roofline.longreason" not in metrics
    with open(os.path.join(ROOT, "cellbench_cache", "runs",
                           "tiny_longreason-3000000044-1", "run.json")) as fh:
        record = json.load(fh)
    assert sorted(c["index"] for c in record["calls"]
                  if c["status"] != "refused") == list(range(len(record["calls"])))
    stats = record["stats_close"]
    assert stats["state_leaves"] == []
    assert set(stats["cache_bytes_by_kind"]) == {"paged"}
    assert stats["index_sorted_token_steps"] >= stats["index_read_token_steps"] \
        >= stats["index_live_token_steps"] > 0
    assert stats["prefix_skipped_stateful"] == 0
    assert stats["decode_engine"]["paged_attention"] == "model"
    need = dsv32_step.count(record)
    assert need is not None and need["selected_rows_a_step"] > 0
    assert need["bytes"] > need["weight_bytes"] + need["cache_bytes"] > 0
    read = dsv32_index_read.count(record)
    assert 0 < read["bytes"] < need["bytes"] and 0 < read["flops"] < need["flops"]


def test_lower_precision_is_not_correct(monkeypatch):
    monkeypatch.setattr(serve, "run_check", control_run.control_check)
    line = run.run_cell("tiny_longreason", 3_000_000_019, 5.0, False,
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
    line = run.run_cell("tiny_longreason", 3_000_000_023, 5.0, False,
                        require_chip=False, bench=_bench())
    assert line["correct"] is False
    compared = line["compared"]
    assert compared["gap_mean"]["value"] > compared["gap_mean"]["limit"]


def test_the_cell_and_its_entries():
    """The new entries stand together, each lists the cell alone and moves
    what it reports; the borrowed ones list it after the cell they came
    with; the cell shares what the other backlog cells share."""
    cell, = [c for c in REAL["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longreason_backlog", 1)
    config, = [c for c in REAL["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == REDUCED
    assert config["source"] == run.load_json(
        HERE, "configs", CONFIG + ".json")["source"]
    assert not [c for c in REAL["workloads"] if c["chips"] == 4]
    tokens, = [m for m in REAL["end_to_end"] if m["name"] == "serve_tokens_per_s"]
    assert CELL in tokens["workloads"]
    assert len(REAL["per_layer"]) <= 128
    names = [m["name"] for m in REAL["per_layer"]]
    new = [m for m in REAL["per_layer"] if m["name"].endswith(".longreason")]
    assert [m["name"] for m in new] == NEW
    first = names.index(NEW[0])
    assert REAL["per_layer"][first:first + len(new)] == new  # side by side
    for metric in new:
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "serve_tokens_per_s"
        if metric["name"].endswith("_roofline.longreason"):
            assert (metric["unit"], metric["source"]) == ("%", "device_trace")
    declared = {m["name"]: m for m in REAL["per_layer"]}
    for name in BORROWED:
        first_cell = {"longdoc": "dots3_longdoc_backlog",
                      "reason": "longcat_reasoning_backlog"}[name.rsplit(".", 1)[1]]
        assert declared[name]["workloads"] == [first_cell, CELL]
        assert declared[name]["moves"] == "serve_tokens_per_s"
    files = {name: run.metric_file(name) for name in NEW + BORROWED}
    assert files["step_roofline.longreason"] == {
        "reader": "roofline", "args": {"programs": ["jit_step"],
                                       "opcount": "dsv32_step"}}
    assert files["indexer_read_roofline.longreason"] == {
        "reader": "scope_roofline", "args": {
            "programs": ["jit_step"], "scope": "indexer",
            "opcount": "dsv32_index_read"}}
    assert files["index_sorted_over_live.longreason"] == {
        "reader": "stats_share", "args": {
            "numerator": ["index_sorted_token_steps"],
            "denominator": ["index_live_token_steps"], "scale": 1.0}}
    # by the stem's file where the suffix has none of its own
    for name in NEW[1:]:
        assert not os.path.exists(os.path.join(HERE, "metrics", name + ".json"))
    assert files["step_mlp_share.reason"]["args"]["needs"] == "latent"
    assert files["index_selected_share.longdoc"]["args"]["numerator"] == \
        ["index_selected_token_steps"]
    shared = [m["name"] for m in REAL["per_layer"]
              if CELL in m["workloads"] and m["name"] not in NEW + BORROWED]
    laguna = [m["name"] for m in REAL["per_layer"]
              if "laguna_codeagent_backlog" in m["workloads"]
              and not m["name"].endswith(".agent")]
    assert shared == laguna and all(n.endswith(".backlog") or n in (
        "launch_ready_s", "weights_s", "engine_compile_s", "warmup_s",
        "backlog_itl_p95_ms") for n in shared)


def test_the_configuration_keeps_every_published_width():
    sizes = run.load_json(HERE, "configs", CONFIG + ".json")
    assert sizes["reduced"] == REDUCED
    cut = {"num_hidden_layers": (61, 5), "first_k_dense_replace": (3, 1),
           "n_routed_experts_here": (256, 16), "vocab_size": (129280, 16160)}
    for key, (published, here) in cut.items():
        assert (sizes["published"][key], sizes[key]) == (published, here)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as fh:
        row, = [r for r in map(json.loads, fh) if r["name"] == "DeepSeek-V3.2"]
    assert sizes["source"] == row["source_url"]
    for key, value in row["config"].items():
        # no width, no expert count of the router, no group is cut
        assert sizes[key] == value or key in cut, key
    assert sizes["serving"] == {"context": 12288, "max_slots": 32}
    mix = run.load_json(HERE, "traffic", "longreason_backlog.json")
    assert mix["callers"] <= 64                        # the default queue
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] == \
        sizes["serving"]["context"]
    assert (sizes["check"]["sample"], sizes["check"]["least_tokens"]) == (3, 3000)
    for inferred in ("rope", "indexer", "routing", "no gate, no rescale",
                     "float32", "torch_dtype", "q_b", "serving.context",
                     "serving.max_slots", "vision", "not run"):
        assert inferred in sizes["assumed"], inferred
    assert "16 chips share each layer" in sizes["deployment"]
    # the reckoning of the file's `memory`: 4.64 B parameters
    table = weights.table(sizes)
    count = sum(_elements(shape) for shape, _ in table.values())
    assert 4.635e9 < count < 4.636e9
    assert table["q_b"][0] == (5, 1536, 128 * 192)
    assert table["kv_a"][0] == (5, 7168, 576)
    assert table["index_q"][0] == (5, 1536, 64 * 128)
    assert table["dense_up"][0] == (1, 7168, 18432)
    assert table["router"][0] == (4, 7168, 256)
    assert table["w_in"][0] == (4, 16, 7168, 4096)
    assert table["head"][0] == (7168, 16160)
    assert "gate" not in table
    model = agent.build_model(sizes).config
    assert model.layer_types == ("full_attention",) * 5
    assert (model.gated, model.rescale_latents, model.first_dense) == ((), False, 1)
    assert (model.n_group, model.topk_group, model.routed_scale,
            model.num_experts, model.num_experts_here) == (8, 4, 2.5, 256, 16)
    assert model.full.rotary.correction_range() == (10, 23)
    assert model.full.mscale == pytest.approx(1.3689, abs=1e-4)
    assert (model.index_topk, model.max_seq_len, model.stored_width(
        "full_attention")) == (2048, 12288, 640)


def _elements(shape):
    n = 1
    for dim in shape:
        n *= dim
    return n


def test_the_traffic_is_the_issues():
    mix = run.load_json(HERE, "traffic", "longreason_backlog.json")
    assert mix["driver"] == "serve_closed_loop" and mix["callers"] == 40
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 1536,
                                    "sigma": 0.3, "min": 1024, "max": 2048}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 3584,
                                    "sigma": 0.7, "min": 1024, "max": 10240}
    assert (mix["lead_in_s"], mix["block"], mix["order_seed"]) == (45, 40, 44)
    assert mix["warmup_prompt_lengths"] == [1025, 2049]


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
    arrays.append(live((1, 32 * 768 + 1, 16, 640)))
    arrays.append(live((1, 32 * 768 + 1, 16, 128)))
    return {
        "config": sizes, "device": {"live_arrays": arrays, "kind": "TPU v5 lite"},
        "stats_open": dict.fromkeys(
            dsv32_step.COUNTERS + ("moe_layer_steps",), 0),
        "stats_close": {
            "moe_layer_steps": 4 * steps, "moe_experts_touched": 4 * 10 * steps,
            "moe_assignments_here": 4 * 16 * steps, "slot_steps": 32 * steps,
            "index_live_token_steps": 5 * 32 * 4500 * steps,
            "index_selected_token_steps": 5 * 32 * 2048 * steps},
    }


def test_what_a_step_needs_is_counted_from_the_run():
    """Against a hand count: 32 slots at 4500 live tokens, 10 of 16 held
    experts touched a layer."""
    made = _made()
    need = dsv32_step.count(made)
    assert need["active_slots"] == 32 and need["experts_touched_a_step"] == 40
    attention = 187.1e6 + 13.96e6
    outside = 5 * attention + 396.4e6 + 4 * (1.835e6 + 44.04e6) + 115.8e6
    # 2 B an element: 3.40 GB outside the experts, 3.52 GB of experts
    assert need["weight_bytes"] == pytest.approx(
        2 * (outside + 40 * 44.04e6), rel=2e-3)
    keys, rows = 5 * 32 * 4500, 5 * 32 * 2048
    assert need["cache_bytes"] == keys * 128 * 2 + rows * 576 * 2 \
        + 32 * 5 * (576 + 128) * 2
    assert need["bytes"] == pytest.approx(
        need["weight_bytes"] + need["cache_bytes"] + 32 * 7168 * 2)
    assert need["flops"] == pytest.approx(
        2 * 32 * outside + 2 * 4 * 16 * 44.04e6
        + keys * 64 * (2 * 128 + 2) + rows * 128 * (4 * 512 + 2 * 64), rel=2e-3)
    read = dsv32_index_read.count(made)
    matrices = 5 * 13.96e6
    assert read["bytes"] == pytest.approx(
        2 * matrices + keys * 128 * 2 + rows * 576 * 2, rel=2e-3)
    assert read["flops"] == pytest.approx(
        2 * 32 * matrices + keys * 64 * 258, rel=2e-3)
    made["stats_close"].pop("index_live_token_steps")
    # a program without the counters
    assert dsv32_step.count(made) is None
    assert dsv32_index_read.count(made) is None


def test_the_reads_share_and_the_sorts_width_read_or_nothing():
    made = dict(_made(), trace={"modules": {"jit_step(123)": (100, 3.5)}},
                peaks=run.load_json(HERE, "peaks.json"))
    paths = {
        "step/LatentLM/layer_0/attn/indexer/scores": 0.4,
        "step/LatentLM/layer_0/attn/indexer/topk": 0.5,
        "step/LatentLM/layer_3/attn/indexer/gather": 0.3,
        "step/LatentLM/layer_1/attn/latent/scores": 0.2,
        "step/LatentLM/layer_1/moe/groups": 0.1}
    made["scope_seconds:jit_step"] = paths
    about = dict(programs=["jit_step"], scope="indexer",
                 opcount="dsv32_index_read")
    need = dsv32_index_read.count(made)
    # 0.71 GB a step at 819 GB/s is 0.87 ms; the scope took 12 ms a step
    assert scope_roofline.read(made, **about) == pytest.approx(
        100 * (need["bytes"] / 819e9) / 0.012)
    assert 5 < scope_roofline.read(made, **about) < 10
    made["scope_seconds:jit_step"] = {k: v for k, v in paths.items()
                                      if "indexer" not in k}
    assert scope_roofline.read(made, **about) is None  # no such scope
    assert scope_roofline.read({"trace": None}, **about) is None
    args = run.metric_file("index_sorted_over_live.longreason")["args"]
    made["stats_open"]["index_sorted_token_steps"] = 0
    made["stats_close"]["index_sorted_token_steps"] = 5 * 32 * 12288 * 1000
    assert stats_share.read(made, **args) == pytest.approx(12288 / 4500)
    parent = {"stats_open": {"index_live_token_steps": 0},
              "stats_close": {"index_live_token_steps": 9}}
    assert stats_share.read(parent, **args) is None  # no such counter
