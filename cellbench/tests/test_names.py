"""BENCHMARK.json and the files it names keep to the contract's letters and
lengths, and every per-layer metric sits in cells that report what it moves."""

import importlib
import json
import os
import re

from cellbench import run

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
