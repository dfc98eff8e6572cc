"""BENCHMARK.json and the files it names keep to the contract's letters and
lengths, and every per-layer metric sits in cells that report what it moves."""

import hashlib
import importlib
import json
import os
import pickle
import re
import sys
import types

import numpy as np

from cellbench import agent, check, run, serve, weights

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "cellbench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    BENCH = json.load(fh)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_names_units_and_lines():
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for config in BENCH["configs"]:
        assert NAME.match(config["name"]) and _line(config["why"]) and _line(config["source"])
        assert all(NAME.match(k) for k in config["reduced"])
        assert os.path.exists(os.path.join(ROOT, config["file"]))
    for cell in BENCH["workloads"]:
        assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
        assert _line(cell["why"]) and cell["chips"] in (1, 4)
    for metric in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(metric["name"]), metric["name"]
        assert UNIT.match(metric["unit"]), metric["unit"]
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for metric in BENCH["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0 < metric["bound"] <= 0.1
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert 1 <= BENCH["run_seconds"] <= 51
    four = [c for c in BENCH["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_every_cell_is_found_by_name():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for cell in BENCH["workloads"]:
        assert cell["config"] in configs
        with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as fh:
            mix = json.load(fh)
        importlib.import_module("cellbench.drivers." + mix["driver"])
        sizes = run.load_json(ROOT, configs[cell["config"]]["file"])
        assert set(weights.table(sizes)) >= {"embedding"}
        assert callable(agent.adapter(sizes).model)
        assert callable(agent.adapter(sizes).plain_name)
        importlib.import_module("cellbench.reference." + sizes["check"]["reference"])
    pairs = [(c["config"], c["traffic"]) for c in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {c["config"] for c in BENCH["workloads"]}
    assert used == set(configs)


def test_configuration_files_state_their_cuts():
    for config in BENCH["configs"]:
        with open(os.path.join(ROOT, config["file"])) as fh:
            sizes = json.load(fh)
        assert sizes["reduced"] == config["reduced"]
        assert sizes["source"] == config["source"]
        for key in sizes["reduced"]:
            assert not re.search(r"(_dim$|_rank$|_size$|head)", key), key  # no width
            assert sizes["published"][key] != sizes[key]


def _reporting(metric_name):
    """Cells that report an end-to-end metric."""
    metric = next(m for m in BENCH["end_to_end"] if m["name"] == metric_name)
    return set(metric.get("workloads", [c["name"] for c in BENCH["workloads"]]))


def test_per_layer_metrics_move_what_their_cells_report():
    cells = {c["name"] for c in BENCH["workloads"]}
    layers = set()
    for metric in BENCH["per_layer"]:
        assert set(metric["workloads"]) <= cells
        assert set(metric["workloads"]) <= _reporting(metric["moves"]), metric["name"]
        assert _line(metric["layer"])
        layers.add(metric["layer"])
        own = run.metric_file(metric["name"])
        assert set(own) <= {"reader", "args"}, metric["name"]  # the rest is BENCHMARK.json's
        reader = importlib.import_module("cellbench.readers." + own["reader"])
        assert callable(reader.read)
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%"
    for cell in cells:
        assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
        assert any(cell in m["workloads"] for m in BENCH["per_layer"])
        assert any(cell in _reporting(m["name"]) for m in BENCH["end_to_end"]
                   if m["name"] != "setup_s")


def test_the_entry_names_no_cell_configuration_or_metric():
    with open(os.path.join(HERE, "run.py")) as fh:
        source = fh.read()
    names = [c["name"] for c in BENCH["configs"] + BENCH["workloads"]
             + BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [c["traffic"] for c in BENCH["workloads"]]
    assert not [n for n in names if n in source]


def test_an_unknown_device_kind_is_an_error():
    import pytest

    from cellbench.readers import roofline

    run = {"trace": {"modules": {}}, "device": {"kind": "TPU v99"}, "peaks": {}}
    with pytest.raises(KeyError):
        roofline.read(run, ["jit_step"], "decode_step")
    with open(os.path.join(HERE, "peaks.json")) as fh:
        peaks = json.load(fh)
    for kind, row in peaks.items():
        assert row["source"] and row["bf16_flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0


# -- an architecture is found by name: weight table and program adapter ------

with open(os.path.join(HERE, "tests", "data", "tiny_serve.json")) as fh:
    TINY = json.load(fh)
with open(os.path.join(HERE, "tests", "data", "as_before.json")) as fh:
    AS_BEFORE = json.load(fh)


def _sha256(leaves):
    digest = hashlib.sha256()
    for leaf in leaves:
        digest.update(np.asarray(leaf, np.float32).tobytes())
    return digest.hexdigest()


def _flat(made):
    return [leaf for value in made.values()
            for leaf in (value if isinstance(value, list) else [value])]


def test_seeded_leaves_are_what_they_were():
    """Recorded on the tree before the table moved to a file of its own
    (PR 28): every leaf of the tiny configuration, bit for bit."""
    import jax.numpy as jnp

    was = AS_BEFORE["leaves"]
    made = weights.make(TINY, AS_BEFORE["seed"])
    assert list(made) == list(was["per_name"])  # the order is the key's index
    for name, value in made.items():
        leaves = value if isinstance(value, list) else [value]
        assert _sha256(leaves)[:16] == was["per_name"][name], name
    assert _sha256(_flat(made)) == was["sha256"]
    kept = weights.make(TINY, AS_BEFORE["seed"],
                        {"wq": jnp.bfloat16, "embedding": jnp.bfloat16})
    assert kept["wq"][0].dtype == jnp.bfloat16
    assert _sha256(_flat(kept)) == was["sha256_wq_and_embedding_bfloat16"]


def test_the_comparison_reads_a_stored_sample_as_it_did():
    reference = importlib.import_module(
        "cellbench.reference." + TINY["check"]["reference"])
    made = weights.make(TINY, AS_BEFORE["seed"])
    for lower, was in ((None, AS_BEFORE["check"]), ("int8", AS_BEFORE["check_int8"])):
        now = check.summary(check.gaps(reference, made, TINY,
                                       AS_BEFORE["samples"], lower=lower))
        assert now["tokens"] == was["tokens"]
        for name, value in was.items():
            assert abs(now[name] - value) <= 1e-6 * max(1.0, abs(value)), name


def _module(name, **members):
    module = types.ModuleType(name)
    vars(module).update(members)
    return module


def test_a_table_names_its_initialisers(monkeypatch):
    """What a state-space layer needs: positive rates uniform in the
    logarithm, a constant, and a function of the table's own."""
    import jax

    def ramp(key, shape):
        return jax.numpy.arange(shape[0], dtype="float32")

    monkeypatch.setitem(sys.modules, "cellbench.weight_tables.other", _module(
        "cellbench.weight_tables.other", SINGLE=("skip", "ramp"),
        shapes=lambda config: {
            "rate": ((config["layers"], 64), ("log_uniform", 0.001, 0.1)),
            "skip": ((8,), ("constant", 1.0)),
            "proj": ((config["layers"], 8, 4), 0.5),
            "ramp": ((5,), ramp),
        }))
    config = {"weights": "other", "layers": 3}
    made = weights.make(config, 3_000_000_019)
    assert len(made["rate"]) == 3 and made["rate"][0].shape == (64,)
    rates = np.stack(made["rate"])
    assert (rates >= 0.001).all() and (rates <= 0.1).all()
    assert rates.min() < 0.003 and rates.max() > 0.03  # spread over the decades
    assert not (rates[0] == rates[1]).all()
    assert (np.asarray(made["skip"]) == 1.0).all()
    assert made["proj"][2].shape == (8, 4)
    assert list(np.asarray(made["ramp"])) == [0, 1, 2, 3, 4]
    again = weights.make(config, 3_000_000_019)
    assert (np.stack(again["rate"]) == rates).all()
    assert (np.stack(weights.make(config, 3_000_000_020)["rate"]) != rates).any()


def test_the_whole_configuration_reaches_the_adapter(monkeypatch):
    """Lists and strings too, through the pickle that carries the task's
    function: `layer_types` decides what the adapter builds."""
    built = []
    monkeypatch.setitem(sys.modules, "cellbench.programs.other", _module(
        "cellbench.programs.other",
        model=lambda config, context, overrides: built.append(
            (config, context, overrides)) or "the model",
        plain_name=lambda path: ("leaf", None)))
    config = dict(TINY, program="other", layer_types=["mamba", "attention"],
                  position_embedding_type="nope")
    spec = serve.task_spec({"config": config, "seed": 5, "run_dir": "/nowhere"},
                           1, 2)
    spec = pickle.loads(pickle.dumps(spec))
    assert agent.build_model(spec["config"]) == "the model"
    (seen, context, overrides), = built
    assert seen["layer_types"] == ["mamba", "attention"]
    assert seen["position_embedding_type"] == "nope"
    assert seen["serving"] == TINY["serving"] and context == 128
    assert overrides == {"scan_layers": False}
    assert agent.adapter(spec["config"]).plain_name(()) == ("leaf", None)
