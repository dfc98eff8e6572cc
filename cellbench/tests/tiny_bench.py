"""BENCHMARK.json at a size the CPU holds: the real metrics over two tiny
cells (tests/data/tiny_serve.json, traffic/test_tiny_*.json)."""

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    REAL = json.load(fh)
TINY = {"mistral7b_chat_steady": "tiny_steady", "mistral7b_chat_backlog": "tiny_backlog"}


def _tiny(metric):
    if "workloads" not in metric:
        return metric
    return dict(metric, workloads=[TINY[w] for w in metric["workloads"] if w in TINY])


BENCH = {
    "configs": [{"name": "tiny", "file": "cellbench/tests/data/tiny_serve.json"}],
    "workloads": [
        {"name": "tiny_steady", "config": "tiny", "traffic": "test_tiny_steady", "chips": 1},
        {"name": "tiny_backlog", "config": "tiny", "traffic": "test_tiny_backlog", "chips": 1},
    ],
    "end_to_end": [_tiny(m) for m in REAL["end_to_end"]],
    "per_layer": [_tiny(m) for m in REAL["per_layer"]],
}
