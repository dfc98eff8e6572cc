"""A second architecture comes as new files and new entries alone
(README.md, "Adding without editing"): in a copy of the benchmark under a
temporary directory, a weight table whose names and initialisers differ, a
program adapter for those names, a reference, a configuration with a
list-valued key, and a cell. The cell is served, traced and compared through
`run_cell(require_chip=False)`, and no file that was there has changed."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

from tiny_bench import BENCH, ROOT

HERE = os.path.join(ROOT, "cellbench")
SKIP = shutil.ignore_patterns("__pycache__", ".pytest_cache")

TABLE = '''
    """The Llama-shaped table under other names, its norm scales positive by
    two initialisers the first table has no use for."""
    import math

    SINGLE = ("tok_embeddings", "output", "norm")


    def shapes(config):
        d, f, v = config["hidden_size"], config["intermediate_size"], config["vocab_size"]
        layers = len(config["layer_types"])
        q = config["num_attention_heads"] * config["head_dim"]
        kv = config["num_key_value_heads"] * config["head_dim"]
        return {
            "tok_embeddings": ((v, d), 0.02),
            "output": ((d, v), 0.02),
            "norm": ((d,), ("constant", 1.0)),
            "attention_norm": ((layers, d), ("log_uniform", 0.5, 2.0)),
            "ffn_norm": ((layers, d), ("log_uniform", 0.8, 1.25)),
            "q_proj": ((layers, d, q), 1 / math.sqrt(d)),
            "k_proj": ((layers, d, kv), 1 / math.sqrt(d)),
            "v_proj": ((layers, d, kv), 1 / math.sqrt(d)),
            "o_proj": ((layers, q, d), 1 / math.sqrt(q)),
            "gate_proj": ((layers, d, f), 1 / math.sqrt(d)),
            "up_proj": ((layers, d, f), 1 / math.sqrt(d)),
            "down_proj": ((layers, f, d), 1 / math.sqrt(f)),
        }
'''

ADAPTER = '''
    """The program's Transformer, as deep as `layer_types` is long; its leaves
    under the other table's names."""
    from cellbench.programs import transformer

    NAMES = {"embedding": "tok_embeddings", "lm_head": "output",
             "final_norm": "norm", "attn_norm": "attention_norm",
             "mlp_norm": "ffn_norm", "wq": "q_proj", "wk": "k_proj",
             "wv": "v_proj", "wo": "o_proj", "w_gate": "gate_proj",
             "w_up": "up_proj", "w_down": "down_proj"}


    def model(config, context, overrides):
        kinds = config["layer_types"]  # arrives only if lists do
        if not isinstance(kinds, list) or set(kinds) != {"attention"}:
            raise ValueError(f"layer_types: {kinds!r}")
        if config["position_embedding_type"] != "rope":
            raise ValueError("position_embedding_type")
        return transformer.model(dict(config, num_hidden_layers=len(kinds)),
                                 context, overrides)


    def plain_name(path):
        name, layer = transformer.plain_name(path)
        return NAMES[name], layer
'''

REFERENCE = '''
    """The same equations as reference/mistral.py, read from the other
    table's names. It imports nothing of the program."""
    from cellbench.reference import mistral

    NAMES = {"tok_embeddings": "embedding", "output": "lm_head",
             "norm": "final_norm", "attention_norm": "attn_norm",
             "ffn_norm": "mlp_norm", "q_proj": "wq", "k_proj": "wk",
             "v_proj": "wv", "o_proj": "wo", "gate_proj": "w_gate",
             "up_proj": "w_up", "down_proj": "w_down"}


    def logits(weights, tokens, sizes, rows, lower=None):
        sizes = dict(sizes, num_hidden_layers=len(sizes["layer_types"]))
        return mistral.logits({NAMES[k]: v for k, v in weights.items()},
                              tokens, sizes, rows, lower=lower)
'''


def _files(root):
    out = {}
    for folder, _, names in os.walk(root):
        if "__pycache__" in folder or ".pytest_cache" in folder:
            continue
        for name in names:
            path = os.path.join(folder, name)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = fh.read()
    return out


def test_a_second_architecture_is_new_files_and_entries(tmp_path):
    before = _files(HERE)
    root = str(tmp_path)
    shutil.copytree(HERE, os.path.join(root, "cellbench"), ignore=SKIP)
    os.symlink(os.path.join(ROOT, "tf_yarn_tpu"), os.path.join(root, "tf_yarn_tpu"))
    with open(os.path.join(HERE, "tests", "data", "tiny_serve.json")) as fh:
        config = json.load(fh)
    del config["num_hidden_layers"]
    config.update(name="other_tiny", weights="renamed", program="renamed",
                  layer_types=["attention", "attention"],
                  position_embedding_type="rope")
    config["check"] = dict(config["check"], reference="renamed")
    new = {
        "configs/other_tiny.json": json.dumps(config),
        "weight_tables/renamed.py": textwrap.dedent(TABLE),
        "programs/renamed.py": textwrap.dedent(ADAPTER),
        "reference/renamed.py": textwrap.dedent(REFERENCE),
    }
    for name, text in new.items():
        with open(os.path.join(root, "cellbench", name), "x") as fh:
            fh.write(text)
    # The entries: a configuration, a cell, and the cell's name appended to
    # the lists of the end-to-end metric it reports and of the per-layer
    # entries it shares; nothing else of those entries changes.
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(
        {"name": "other_tiny", "file": "cellbench/configs/other_tiny.json"})
    bench["workloads"].append({"name": "other_backlog", "config": "other_tiny",
                               "traffic": "test_tiny_backlog", "chips": 1})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "tiny_backlog" in metric.get("workloads", []):
            metric["workloads"].append("other_backlog")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    env = dict(os.environ, PYTHONPATH=root, JAX_PLATFORMS="cpu")
    script = ("import json; from cellbench import run; print(json.dumps("
              "run.run_cell('other_backlog', 3_000_000_029, 5.0, True, "
              "require_chip=False)))")
    proc = subprocess.run([sys.executable, "-c", script], cwd=root, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["compared"]["compared_tokens"]["value"] >= 400
    assert line["metrics"]["engine_compiles_in_window.backlog"]["value"] == 0
    assert line["metrics"]["weights_s"]["value"] > 0
    with open(os.path.join(root, "cellbench_cache", "runs",
                           "other_backlog-3000000029-1", "run.json")) as fh:
        record = json.load(fh)
    assert record["config"]["layer_types"] == ["attention", "attention"]
    assert len(record["calls"]) > 50
    assert all(c["sent"] < record["window"][1] for c in record["calls"])
    # Nothing that was there has changed, here or in the copy.
    assert _files(HERE) == before
    after = _files(os.path.join(root, "cellbench"))
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == set(new)
