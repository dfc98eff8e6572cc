"""The benchmark's tests of its fourth architecture, in tier-1 the way
tests/test_cellbench_dots3.py brings the third: the tiny `LongCat-Flash`
share run whole through `run_on_tpu` on the CPU (sound `correct: true`, the
int8 control `correct: false`), the cell's entries, the configuration's
widths, the traffic, the step's needs, the new reader."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "cellbench", "tests"))

from cellbench.tests.test_longcat_flash import *  # noqa: E402,F401,F403


def test_the_cell_and_its_entries():  # noqa: F811
    """The imported test (PR 37's, of its own entries) holds its `.reason`
    entries to be the LAST of `per_layer` and the cell to share exactly what
    `dots3_longdoc_backlog` shares, which no later PR that appends an entry
    (cellbench/README.md, "Adding without editing") can keep, and which a PR
    that adds one may not edit. Tier-1 holds them to what stays true, as
    tests/test_cellbench_granite.py does for the second architecture: the
    cell as it was, its entries side by side as they were appended, each
    listing it alone (one of them a later cell after it, by name) and moving
    what it reports. Every other assertion of the
    imported test stands here letter for letter."""
    from cellbench import run
    from cellbench.tests.test_longcat_flash import (
        CELL, CONFIG, HERE, REAL, REASON)

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
    assert CELL in tokens["workloads"]
    names = [m["name"] for m in REAL["per_layer"]]
    new = [m for m in REAL["per_layer"] if m["name"].endswith(".reason")]
    assert [m["name"] for m in new] == REASON
    first = names.index(REASON[0])
    assert REAL["per_layer"][first:first + len(new)] == new  # side by side
    for metric in new:
        # `step_mlp_share.reason` reads a dense layer's `mlp` scope in a step
        # with `latent` scopes: DeepSeek-V3.2's cell, which came when the
        # benchmark had room for three entries more, is listed after this one
        also = ["dsv32_reasoning_backlog"] \
            if metric["name"] == "step_mlp_share.reason" else []
        assert metric["workloads"] == [CELL] + also
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
