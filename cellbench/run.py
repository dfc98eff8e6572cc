"""Run one cell of BENCHMARK.json once.

    python cellbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name: the workload's entry
in BENCHMARK.json names a configuration (its `file`) and a traffic mix
(`cellbench/traffic/<traffic>.json`), the traffic names its driver
(`cellbench/drivers/<driver>.py`), the configuration names its weight table,
its program adapter and its reference (`cellbench/weight_tables/`,
`programs/`, `reference/`), and each per-layer metric has a file
`cellbench/metrics/<name>.json` that names its reader
(`cellbench/readers/<reader>.py`) and the reader's arguments; a metric
`<quantity>.<cell suffix>` without a file of its own takes
`cellbench/metrics/<quantity>.json`. Nothing here knows a cell, a
configuration or a metric. The last line of standard output is the result.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "cellbench")


def load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def metric_file(name: str) -> dict:
    """`reader` and `args` of a per-layer metric: its own file, or the file
    of the quantity it is one cell's reading of (the name up to the last
    dot), so that a new cell's reading is a new entry and no copied file."""
    for stem in (name, name.rpartition(".")[0]):
        path = os.path.join(HERE, "metrics", stem + ".json")
        if stem and os.path.exists(path):
            return load_json(path)
    raise SystemExit(f"no file under cellbench/metrics for {name!r}")


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            config = next(c for c in bench["configs"]
                          if c["name"] == cell["config"])
            return dict(cell, config_file=config["file"])
    raise SystemExit(f"BENCHMARK.json has no workload {workload!r}")


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, bench=None) -> dict:
    """One run; returns the result line as a dict. `require_chip=False` and
    `bench` are for the benchmark's own tests, at tiny sizes: the command
    has no such switches."""
    bench = bench or load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(bench, workload)
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    driver = importlib.import_module("cellbench.drivers." + traffic["driver"])
    run = driver.run({
        "name": workload, "seed": int(seed), "seconds": float(seconds),
        "trace": bool(trace), "chips": cell["chips"],
        "require_chip": require_chip,
        "config": load_json(ROOT, cell["config_file"]), "traffic": traffic,
        "process_start": PROCESS_START,
        "run_dir": os.path.join(
            ROOT, "cellbench_cache", "runs",
            f"{workload}-{int(seed)}-{int(bool(trace))}"),
    })
    metrics = {}
    if trace:
        run["peaks"] = load_json(HERE, "peaks.json")
        for declared in bench["per_layer"]:
            if not applies(declared, workload):
                continue
            metric = metric_file(declared["name"])
            reader = importlib.import_module(
                "cellbench.readers." + metric["reader"])
            value = reader.read(run, **metric.get("args", {}))
            if value is not None and math.isfinite(value):
                metrics[declared["name"]] = {"value": value,
                                             "unit": declared["unit"]}
    else:
        for declared in bench["end_to_end"]:
            if applies(declared, workload):
                metrics[declared["name"]] = {
                    "value": run["end_to_end"][declared["name"]],
                    "unit": declared["unit"]}
    compared = run["compared"]
    numbers = {k: v for k, v in compared.items() if "limit" in v}
    correct = bool(numbers) and all(
        (v["value"] >= v["limit"]) if v.get("at_least")
        else (v["value"] <= v["limit"]) for v in numbers.values())
    device = run["device"]
    line = {
        "correct": correct, "attempted": run["attempted"],
        "failed": run["failed"], "metrics": metrics,
        "device": {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"],
                   "memory_peak_bytes": device["memory_peak_bytes"]},
    }
    if trace and run.get("trace"):
        line["device"]["busy_s"] = run["trace"]["busy_s"]
        line["device"]["window_s"] = run["trace"]["window_s"]
        line["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                             "idle_gaps": run["trace"]["idle_gaps"]}
    line["workload"] = workload
    line["seed"] = int(seed)
    line["notes"] = run["notes"]
    line["compared"] = compared
    # The whole record of the run, for whoever has to look inside it.
    run.pop("peaks", None)
    with open(os.path.join(run["run_dir"], "run.json"), "w") as fh:
        json.dump({"line": line, **run}, fh)
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    for name, entry in line["compared"].items():
        if "limit" in entry:
            print(f"compared {name}: {entry['value']} limit {entry['limit']}",
                  file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
