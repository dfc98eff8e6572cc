"""The benchmark's own fast tests, in tier-1: the readers of what the
program says about its time (cellbench/tests/test_readers_tracing.py), the
contract's names and arrows with the entries this repo has, the trace
reduction and the traffic generator. The whole runs through `run_on_tpu`
(cellbench/tests/test_control.py, test_broken_path.py; minutes) stay out:
`python -m pytest cellbench/tests` runs them."""

from cellbench.tests.test_names import *  # noqa: F401,F403
from cellbench.tests.test_readers_tracing import *  # noqa: F401,F403
from cellbench.tests.test_trace import *  # noqa: F401,F403
from cellbench.tests.test_traffic import *  # noqa: F401,F403


def test_configuration_files_state_their_cuts():  # noqa: F811
    """The imported test's pattern takes every key that ends in `_size` for
    a width, and so refuses the sliced `vocab_size` that cellbench/README.md
    ("a share of a deployment") tells a share's file to list. A PR that adds
    a configuration may not edit the benchmark's files (PERF.md, Open
    questions asks a `benchmark` PR to), so tier-1 keeps that pattern,
    exempts that one key by name, and adds the widths of a state-space
    mixer, which the pattern does not reach; every cut key is stated beside
    its published value."""
    import json
    import os
    import re

    from cellbench.tests.test_names import BENCH, ROOT

    width = re.compile(
        r"(_dim$|_rank$|_size$|head|expand|per_tok|d_state|d_conv)")
    for config in BENCH["configs"]:
        with open(os.path.join(ROOT, config["file"])) as fh:
            sizes = json.load(fh)
        assert sizes["reduced"] == config["reduced"]
        assert sizes["source"] == config["source"]
        for key in sizes["reduced"]:
            assert key == "vocab_size" or not width.search(key), key
            assert sizes["published"][key] != sizes[key]


def test_every_new_quantity_is_declared_in_its_cells_with_a_file():  # noqa: F811
    """The imported test (PR 25's, of its own entries) holds them to be the
    LAST of `per_layer` and to list one cell each, which no later PR that
    appends an entry, or a cell's name to a list (cellbench/README.md,
    "Adding without editing"), can keep. Tier-1 holds them to what stays
    true: each is declared with a file, side by side as they were appended,
    its first cell the one it was written for, and moving what that cell reports."""
    import json
    import os

    from cellbench.tests import test_readers_tracing as theirs

    with open(os.path.join(theirs.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    declared = {m["name"]: m for m in bench["per_layer"]}
    expected = [f"{q}.{s}" for q in theirs.NEW for s in ("steady", "backlog")] + \
        [f"{q}.steady" for q in theirs.STEADY_ONLY]
    assert set(expected) <= set(declared)
    # Nothing was put between them: they still stand side by side.
    names = [m["name"] for m in bench["per_layer"]]
    first = min(names.index(n) for n in expected)
    assert set(names[first:first + len(expected)]) == set(expected)
    for name in expected:
        entry, suffix = declared[name], name.rsplit(".", 1)[1]
        assert entry["workloads"][0] == "mistral7b_chat_" + suffix
        assert entry["moves"] == {"steady": "itl_p90_ms",
                                  "backlog": "serve_tokens_per_s"}[suffix]
        assert set(theirs.run_lib.metric_file(name)) <= {"reader", "args"}


def test_step_ahead_share_is_declared_as_data_and_silent_without_its_counters():
    """PR 38's per-layer metric: one data file for an accepted reader, two
    entries at the end of `per_layer` (the steady cell's moves `itl_p90_ms`,
    the four closed loops' `serve_tokens_per_s`). The reader gives the share
    of one-token steps launched while the step before was unread, and nothing
    (no error) for a program whose `/stats` has no such counters, as the
    parent's."""
    import json
    import os

    from cellbench.readers import stats_share
    from cellbench.tests import test_readers_tracing as theirs

    with open(os.path.join(theirs.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    steady, backlog = bench["per_layer"][-2:]
    closed_loops = [c["name"] for c in bench["workloads"]
                    if c["name"].endswith("_backlog")]
    assert steady == {
        "name": "step_ahead_share.steady", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "server and scheduler",
        "moves": "itl_p90_ms", "workloads": ["mistral7b_chat_steady"]}
    assert backlog == dict(
        steady, name="step_ahead_share.backlog", moves="serve_tokens_per_s",
        workloads=closed_loops)
    assert len(closed_loops) == 4
    for name in (steady["name"], backlog["name"]):
        assert theirs.run_lib.metric_file(name) == {
            "reader": "stats_share", "args": {
                "numerator": ["steps_ahead"], "denominator": ["steps"]}}
    args = theirs.run_lib.metric_file(steady["name"])["args"]
    run = {"stats_open": {"steps": 100, "steps_ahead": 90, "ticks": 101},
           "stats_close": {"steps": 1100, "steps_ahead": 1070, "ticks": 1111}}
    assert stats_share.read(run, **args) == 98.0
    parent = {"stats_open": {"ticks": 101}, "stats_close": {"ticks": 1111}}
    assert stats_share.read(parent, **args) is None
    idle = {"stats_open": run["stats_open"], "stats_close": run["stats_open"]}
    assert stats_share.read(idle, **args) is None
