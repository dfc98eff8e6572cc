"""A whole run on the CPU at a tiny size, skipping only the harness's look for
a chip: sound, it comes out correct; with a token altered where the step
produces it, it comes out not correct."""

import sys

import cloudpickle
import pytest

from cellbench import agent, run

from tiny_bench import BENCH


@pytest.mark.parametrize("cell,seed", [("tiny_steady", 3_000_000_007),
                                       ("tiny_backlog", 12)])
def test_sound_run_is_correct(cell, seed):
    line = run.run_cell(cell, seed, 5.0, False, require_chip=False, bench=BENCH)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {m["name"] for m in BENCH["end_to_end"]
                                    if cell in m.get("workloads", [cell])}
    assert list(line)[-1] == "compared"


def test_traced_run_reports_per_layer_metrics_only():
    line = run.run_cell("tiny_steady", 21, 5.0, True, require_chip=False, bench=BENCH)
    declared = {m["name"] for m in BENCH["per_layer"] if "tiny_steady" in m["workloads"]}
    assert set(line["metrics"]) <= declared
    assert line["metrics"]["ttft_members.steady"]["value"] == 100  # 2/3 of round(30.0 * 5)
    assert line["metrics"]["engine_compiles_in_window.steady"]["value"] == 0


def _altered_token_experiment(spec):
    """Built in the task in the sound experiment's place: every token is
    altered where the step produces it."""
    from tf_yarn_tpu.models.decode_engine import DecodeEngine

    sound, vocab = DecodeEngine.paged_step, spec["config"]["vocab_size"]

    def broken(self, *args, **kwargs):
        pool, emitted, rngs = sound(self, *args, **kwargs)
        return pool, (emitted + 1) % vocab, rngs

    DecodeEngine.paged_step = broken
    return agent.serving_experiment(spec)


def test_altered_token_is_not_correct(monkeypatch):
    # the task cannot import this module: its function travels by value
    cloudpickle.register_pickle_by_value(sys.modules[__name__])
    monkeypatch.setattr(agent, "serving_experiment", _altered_token_experiment)
    line = run.run_cell("tiny_backlog", 12, 5.0, False, require_chip=False,
                        bench=BENCH)
    assert line["correct"] is False
    compared = line["compared"]
    assert compared["gap_mean"]["value"] > compared["gap_mean"]["limit"]
