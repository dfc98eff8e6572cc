"""The benchmark's tests of its second architecture, in tier-1 the way
tests/test_cellbench_readers.py brings the others: the tiny
`granitemoehybrid` share run whole through `run_on_tpu` on the CPU (sound
`correct: true`, the int8 control `correct: false`), the cell's entries,
the configuration's widths, the traffic, the step's needs."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "cellbench", "tests"))

from cellbench.tests.test_granite_hybrid import *  # noqa: E402,F401,F403


def test_the_cell_and_its_entries():  # noqa: F811
    """The imported test (PR 29's, of its own entries) holds the benchmark to
    three cells and two configurations, its `.longgen` entries to be the LAST
    of `per_layer` and `serve_tokens_per_s` to list two cells, which no later
    PR that adds a cell (cellbench/README.md, "Adding without editing") can
    keep, and which a PR that adds one may not edit (PERF.md, Open questions
    asks a `benchmark` PR to). Tier-1 holds them to what stays true: the cell
    as it was, its entries side by side as they were appended, each listing
    it first and moving what it reports."""
    from cellbench import run
    from cellbench.tests.test_granite_hybrid import CELL, CONFIG, REAL

    cell, = [c for c in REAL["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longgen_backlog", 1)
    assert [c["name"] for c in REAL["workloads"]][:3] == [
        "mistral7b_chat_steady", "mistral7b_chat_backlog", CELL]
    assert not [c for c in REAL["workloads"] if c["chips"] == 4]
    tokens, = [m for m in REAL["end_to_end"] if m["name"] == "serve_tokens_per_s"]
    assert tokens["workloads"][:2] == ["mistral7b_chat_backlog", CELL]
    names = [m["name"] for m in REAL["per_layer"]]
    new = [m for m in REAL["per_layer"] if m["name"].endswith(".longgen")]
    assert [m["name"] for m in new] == [
        "step_roofline.longgen", "step_moe_share.longgen",
        "step_ssm_share.longgen", "moe_held_share.longgen",
        "moe_load_max_over_mean.longgen", "moe_experts_touched.longgen",
        "state_gb.longgen"]
    first = names.index(new[0]["name"])
    assert REAL["per_layer"][first:first + len(new)] == new  # side by side
    for metric in new:
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "serve_tokens_per_s"
    assert run.metric_file("step_roofline.longgen")["args"]["opcount"] == "granite_step"
    shared = [m["name"] for m in REAL["per_layer"]
              if CELL in m["workloads"] and not m["name"].endswith(".longgen")]
    assert all(n.endswith(".backlog") or n in (
        "launch_ready_s", "weights_s", "engine_compile_s", "warmup_s",
        "backlog_itl_p95_ms") for n in shared)
    assert not {"step_mlp_share.backlog", "step_roofline.backlog"} & set(shared)
