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


def _bench():
    import json
    import os

    from cellbench.tests import test_readers_tracing as theirs

    with open(os.path.join(theirs.ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _side_by_side(bench, names):
    """The entries of `names`, which stand together in `per_layer` in that
    order wherever a later PR's entries put them: never "the last"."""
    declared = [m["name"] for m in bench["per_layer"]]
    first = declared.index(names[0])
    assert declared[first:first + len(names)] == list(names)
    return bench["per_layer"][first:first + len(names)]


def test_step_ahead_share_is_declared_as_data_and_silent_without_its_counters():
    """PR 38's per-layer metric: one data file for an accepted reader, two
    entries side by side in `per_layer` (the steady cell's moves
    `itl_p90_ms`, the four closed loops' `serve_tokens_per_s`). The reader
    gives the share of one-token steps launched while the step before was
    unread, and nothing (no error) for a program whose `/stats` has no such
    counters, as the parent's."""
    from cellbench.readers import stats_share
    from cellbench.tests import test_readers_tracing as theirs

    bench = _bench()
    steady, backlog = _side_by_side(
        bench, ["step_ahead_share.steady", "step_ahead_share.backlog"])
    closed_loops = [c["name"] for c in bench["workloads"]
                    if c["name"].endswith("_backlog")]
    assert steady == {
        "name": "step_ahead_share.steady", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "server and scheduler",
        "moves": "itl_p90_ms", "workloads": ["mistral7b_chat_steady"]}
    assert backlog == dict(
        steady, name="step_ahead_share.backlog", moves="serve_tokens_per_s",
        workloads=closed_loops)
    assert len(closed_loops) >= 4  # four when it came; a later cell appends
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


# --------------------------------------------------------------------------
# PR 39: a pipelined tick accounts for itself. Ten quantities, each read in
# the steady cell and in the four closed loops; a data file each, three new
# readers. Hand-made runs: a window of 50 s in which the program counted
# 10,000 ticks, and the same run as the parent commit would give it (the
# spans and counters it has, none of PR 39's).
# --------------------------------------------------------------------------

ACCOUNT = {
    # quantity: (unit, better, source, reader, value on the run below)
    "tick_host_ms": ("ms", "lower", "program_counter", "stats_share", 3.8),
    "tick_host_paced_share": ("%", "lower", "program_counter",
                              "stats_share", 1.5),
    "step_interval_ms": ("ms", "lower", "program_counter", "stats_share",
                         4.0),
    "queued_ahead_share": ("%", "lower", "program_counter",
                           "stats_excess_share", 19.2),
    "stall_steps": ("count", "lower", "program_counter", "stats_count", 3.0),
    "stall_seconds": ("s", "lower", "program_counter", "stats_count", 0.25),
    "step_dispatch_ms": ("ms", "lower", "program_span", "span_mean_ms", 1.5),
    "launch_inputs_ms": ("ms", "lower", "program_span", "span_mean_ms",
                         0.25),
    "launch_account_ms": ("ms", "lower", "program_span", "span_mean_ms",
                          0.5),
    "span_window_coverage": ("%", "higher", "program_span", "span_coverage",
                             100.0),
}


def _account_run():
    opened = {"ticks": 2000, "steps_read": 2000, "steps_host_paced": 40,
              "host_seconds": 7.0, "clean_intervals": 1800,
              "clean_interval_seconds": 7.5, "ahead_intervals": 100,
              "ahead_interval_seconds": 2.0, "ahead_prefill_tokens": 90000,
              "slow_steps": 1, "slow_step_seconds": 0.5, "spans_evicted": 0}
    closed = {"ticks": 12000, "steps_read": 12000, "steps_host_paced": 190,
              "host_seconds": 45.0, "clean_intervals": 10800,
              "clean_interval_seconds": 43.5, "ahead_intervals": 700,
              "ahead_interval_seconds": 14.0, "ahead_prefill_tokens": 700000,
              "slow_steps": 4, "slow_step_seconds": 0.75,
              "spans_evicted": 0}
    spans = []
    for start in (90.0, 100.0, 120.0, 149.0):
        spans += [
            {"name": "serving/tick", "start": start, "dur": 0.005},
            {"name": "decode_engine/paged_step", "start": start,
             "dur": 0.0015},
            {"name": "serving/launch_inputs", "start": start,
             "dur": 0.00025},
            {"name": "serving/launch_account", "start": start,
             "dur": 0.0005},
        ]
    return {"stats_open": opened, "stats_close": closed, "spans": spans,
            "window": [100.0, 150.0]}


def _as_the_parent(run):
    """The same run from a program without PR 39: one ring (no
    `spans_evicted`), the old tally under `slow_steps`, no launch children,
    none of the account's counters; `decode_engine/paged_step` it has."""
    kept = ("ticks", "slow_steps", "slow_step_seconds")
    return {
        "stats_open": {k: run["stats_open"][k] for k in kept},
        "stats_close": {k: run["stats_close"][k] for k in kept},
        "spans": [s for s in run["spans"]
                  if not s["name"].startswith("serving/launch_")],
        "window": run["window"],
    }


def test_the_ticks_account_is_declared_side_by_side_in_both_kinds_of_cell():
    from cellbench.tests import test_readers_tracing as theirs

    bench = _bench()
    names = [f"{q}.{s}" for q in ACCOUNT for s in ("steady", "backlog")]
    entries = _side_by_side(bench, names)
    closed_loops = [c["name"] for c in bench["workloads"]
                    if c["name"].endswith("_backlog")]
    for entry in entries:
        quantity, suffix = entry["name"].rsplit(".", 1)
        unit, better, source, reader, _value = ACCOUNT[quantity]
        assert entry == {
            "name": entry["name"], "unit": unit, "better": better,
            "source": source, "layer": "server and scheduler",
            "moves": {"steady": "itl_p90_ms",
                      "backlog": "serve_tokens_per_s"}[suffix],
            "workloads": {"steady": ["mistral7b_chat_steady"],
                          "backlog": closed_loops}[suffix]}
        # One file a quantity serves both of its entries.
        metric = theirs.run_lib.metric_file(entry["name"])
        assert set(metric) == {"reader", "args"}
        assert metric["reader"] == reader
    # They follow PR 38's two, which stand where they stood.
    declared = [m["name"] for m in bench["per_layer"]]
    assert declared.index(names[0]) == \
        declared.index("step_ahead_share.backlog") + 1


import pytest  # noqa: E402


@pytest.mark.parametrize("quantity", sorted(ACCOUNT))
def test_a_quantity_of_the_ticks_account_reads_its_number_and_is_silent_on_the_parent(
        quantity):
    import importlib

    from cellbench.tests import test_readers_tracing as theirs

    metric = theirs.run_lib.metric_file(quantity + ".backlog")
    reader = importlib.import_module("cellbench.readers." + metric["reader"])
    run = _account_run()
    assert reader.read(run, **metric["args"]) == pytest.approx(
        ACCOUNT[quantity][4])
    parent = reader.read(_as_the_parent(run), **metric["args"])
    if quantity == "step_dispatch_ms":
        # The span was there before PR 39 and no metric read it.
        assert parent == pytest.approx(1.5)
    else:
        assert parent is None


def test_stats_excess_share_is_what_the_queued_work_added_to_the_window():
    from cellbench.readers import stats_excess_share

    args = dict(seconds="ahead_interval_seconds", count="ahead_intervals",
                base_seconds="clean_interval_seconds",
                base_count="clean_intervals")
    run = _account_run()
    # 600 intervals took 12 s where 600 clean ones take 600 x 4 ms = 2.4 s:
    # 9.6 s of a 50 s window.
    assert stats_excess_share.read(run, **args) == pytest.approx(19.2)
    nothing_ahead = _account_run()
    for key in ("ahead_intervals", "ahead_interval_seconds"):
        nothing_ahead["stats_close"][key] = nothing_ahead["stats_open"][key]
    assert stats_excess_share.read(nothing_ahead, **args) == 0.0
    no_base = _account_run()
    no_base["stats_close"]["clean_intervals"] = \
        no_base["stats_open"]["clean_intervals"]
    assert stats_excess_share.read(no_base, **args) is None
    assert stats_excess_share.read(_as_the_parent(run), **args) is None


def test_stats_count_is_a_growth_and_needs_the_counters_that_gave_it_its_meaning():
    from cellbench.readers import stats_count

    run = _account_run()
    assert stats_count.read(run, ["slow_steps"]) == 3.0
    assert stats_count.read(run, ["slow_steps", "ahead_intervals"]) == 603.0
    assert stats_count.read(run, ["slow_step_seconds"],
                            needs=["steps_read"]) == pytest.approx(0.25)
    parent = _as_the_parent(run)
    # The parent's `slow_steps` counted admissions: without `needs` it would
    # be read as stalls.
    assert stats_count.read(parent, ["slow_steps"]) == 3.0
    assert stats_count.read(parent, ["slow_steps"],
                            needs=["steps_read"]) is None
    assert stats_count.read(parent, ["no_such_counter"]) is None


def test_span_coverage_says_how_much_of_the_window_the_rings_still_hold():
    from cellbench.readers import span_coverage

    run = _account_run()
    assert span_coverage.read(run, "serving/tick") == 100.0
    # A ring that dropped spans, but none of the window's.
    run["stats_close"]["spans_evicted"] = 5000
    assert span_coverage.read(run, "serving/tick") == 100.0
    # One that dropped the window's first 20 of 50 seconds.
    late = dict(run, spans=[s for s in run["spans"] if s["start"] >= 120.0])
    assert span_coverage.read(late, "serving/tick") == pytest.approx(60.0)
    # Everything it kept is from after the window: nothing of it is held.
    after = dict(run, spans=[{"name": "serving/tick", "start": 151.0,
                              "dur": 0.005}])
    assert span_coverage.read(after, "serving/tick") == 0.0
    # It dropped nothing, so nothing is missing wherever the first span is.
    late["stats_close"] = dict(run["stats_close"], spans_evicted=0)
    assert span_coverage.read(late, "serving/tick") == 100.0
    assert span_coverage.read(run, "no/such_span") is None
    assert span_coverage.read(_as_the_parent(run), "serving/tick") is None


# --------------------------------------------------------------------------
# PR 41: which prefill bucket an admission takes and how much of it is kept.
PREFILL_RULE = {
    # quantity: (better, numerator, denominator, value on the run below)
    "prefill_ceiling_share": (
        "higher", ["prefills_ceiling"],
        ["prefills_ceiling", "prefills_floor"], 100.0),
    "prefill_pad_share": (
        "lower", ["prefill_pad_tokens"],
        ["prefilled_tokens", "prefill_pad_tokens"], 28.0),
}


@pytest.mark.parametrize("quantity", sorted(PREFILL_RULE))
def test_a_quantity_of_the_prefill_rule_is_data_and_silent_on_the_parent(
        quantity):
    """Two data files for an accepted reader, two entries each at the end
    of `per_layer` (the steady cell's moves `itl_p90_ms`, the four closed
    loops' `serve_tokens_per_s`, as `sched_replay_share.*`, which they
    should move). The reader gives how often an admission took the bucket
    above its prompt and what share of the rows it ran were pad; 0 % of
    admissions for a model on the floor rule; and nothing (no error) for a
    program whose `/stats` has no such counters, as the parent's."""
    from cellbench.readers import stats_share
    from cellbench.tests import test_readers_tracing as theirs

    better, numerator, denominator, value = PREFILL_RULE[quantity]
    bench = _bench()
    steady, backlog = _side_by_side(
        bench, [quantity + ".steady", quantity + ".backlog"])
    replay = {m["name"]: m for m in bench["per_layer"]
              if m["name"].startswith("sched_replay_share.")}
    for entry, suffix in ((steady, ".steady"), (backlog, ".backlog")):
        assert entry == dict(
            replay["sched_replay_share" + suffix], name=quantity + suffix,
            better=better)
        assert theirs.run_lib.metric_file(entry["name"]) == {
            "reader": "stats_share",
            "args": {"numerator": numerator, "denominator": denominator}}
    # Four closed loops when it came; a later cell appends its name.
    assert backlog["workloads"][:4] == [
        "mistral7b_chat_backlog", "granite4h_longgen_backlog",
        "dots3_longdoc_backlog", "longcat_reasoning_backlog"]
    # The four stand together (not "last": a later PR appends its own), the
    # share of admissions before the share of pad.
    _side_by_side(bench, [name + suffix for name in sorted(PREFILL_RULE)
                          for suffix in (".steady", ".backlog")])
    args = theirs.run_lib.metric_file(steady["name"])["args"]
    opened = {"prefills_ceiling": 10, "prefills_floor": 0,
              "prefilled_tokens": 3000, "prefill_pad_tokens": 1000}
    run = {"stats_open": opened,
           "stats_close": {"prefills_ceiling": 460, "prefills_floor": 0,
                           "prefilled_tokens": 165000,
                           "prefill_pad_tokens": 64000}}
    assert stats_share.read(run, **args) == pytest.approx(value)
    floor = {"stats_open": dict(opened, prefills_ceiling=0, prefills_floor=10,
                                prefill_pad_tokens=0),
             "stats_close": {"prefills_ceiling": 0, "prefills_floor": 460,
                             "prefilled_tokens": 165000,
                             "prefill_pad_tokens": 0}}
    assert stats_share.read(floor, **args) == 0.0
    parent = {"stats_open": {"prefilled_tokens": 3000},
              "stats_close": {"prefilled_tokens": 165000}}
    assert stats_share.read(parent, **args) is None
