"""The benchmark's tests of its third architecture, in tier-1 the way
tests/test_cellbench_granite.py brings the second: the tiny `dots3_note`
share run whole through `run_on_tpu` on the CPU (sound `correct: true`, the
int8 control `correct: false`), the cell's entries, the configuration's
widths, the traffic, the step's needs, the new readers."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "cellbench", "tests"))

from cellbench.tests.test_dots3_note import *  # noqa: E402,F401,F403


def test_the_cell_and_its_entries():  # noqa: F811
    """The imported test (PR 34's, of its own entries) holds each `.longdoc`
    entry to list this cell alone. The benchmark holds 128 per-layer entries
    at most and held 125 when DeepSeek-V3.2's cell came, whose step is this
    model's full layer five times: ten of these entries now list that cell
    after this one (cellbench/README.md, "Adding without editing": a PR that
    adds a cell appends its name to the entries it shares). Tier-1 holds
    them to that and to nothing looser: this cell first, then that one or
    none; every other assertion of the imported test stands here letter for
    letter."""
    from cellbench import run
    from cellbench.tests.test_dots3_note import CELL, CONFIG, REAL

    cell, = [c for c in REAL["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "longdoc_backlog", 1)
    assert not [c for c in REAL["workloads"] if c["chips"] == 4]
    tokens, = [m for m in REAL["end_to_end"] if m["name"] == "serve_tokens_per_s"]
    assert CELL in tokens["workloads"]
    new = [m for m in REAL["per_layer"] if m["name"].endswith(".longdoc")]
    assert [m["name"] for m in new] == [
        "step_roofline.longdoc", "step_latent_share.longdoc",
        "step_indexer_share.longdoc", "step_moe_share.longdoc",
        "index_selected_share.longdoc", "cache_read_over_live.longdoc",
        "moe_held_share.longdoc", "moe_load_max_over_mean.longdoc",
        "moe_experts_touched.longdoc", "latent_cache_gb.longdoc",
        "prefill_share.longdoc"]
    for metric in new:
        # the step's needs are each model's own: that entry stays this cell's
        also = [] if metric["name"] == "step_roofline.longdoc" \
            else ["dsv32_reasoning_backlog"]
        assert metric["workloads"] == [CELL] + also
        assert metric["moves"] == "serve_tokens_per_s"
    assert run.metric_file("step_roofline.longdoc")["args"]["opcount"] == "dots3_step"
    shared = [m["name"] for m in REAL["per_layer"]
              if CELL in m["workloads"] and not m["name"].endswith(".longdoc")]
    assert all(n.endswith(".backlog") or n in (
        "launch_ready_s", "weights_s", "engine_compile_s", "warmup_s",
        "backlog_itl_p95_ms") for n in shared)
    # no operation of this model is under `attention` (which the dense
    # layer's `mlp` share needs too), no view is gathered, and the
    # Llama-shaped step's needs are not this model's
    assert not {"step_attention_share.backlog", "step_mlp_share.backlog",
                "step_kv_gather_share.backlog", "step_roofline.backlog"} \
        & set(shared)
