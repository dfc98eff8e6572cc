"""The control of `correct`, at a size a test can hold: a whole run through
the harness in which the reference in the precision below the
configuration's (8-bit integers for bfloat16) stands in the program's place
where the comparison is made. It has to come out not correct, by a limit that
the sound run of the same seed keeps (tests/test_broken_path.py). On the
chip, at the cell's own size, `tests/control_run.py` makes the same run; the
readings are in PERF.md."""

import json
import os

import pytest

import control_run
from cellbench import run, serve, weights
from tiny_bench import BENCH

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(DATA, "tiny_serve.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("cell,seed", [("tiny_backlog", 12),
                                       ("tiny_backlog", 3_000_000_019),
                                       ("tiny_steady", 2**31 + 5)])
def test_lower_precision_is_not_correct(monkeypatch, cell, seed):
    monkeypatch.setattr(serve, "run_check", control_run.control_check)
    line = run.run_cell(cell, seed, 5.0, False, require_chip=False, bench=BENCH)
    compared = line["compared"]
    assert line["correct"] is False and line["failed"] == 0
    assert compared["compared_tokens"]["value"] >= compared["compared_tokens"]["limit"]
    assert any(compared[k]["value"] > compared[k]["limit"]
               for k in ("gap_mean", "gap_p99"))


def test_weights_are_a_function_of_the_seed(tiny):
    a, b = weights.make(tiny, 3_000_000_019), weights.make(tiny, 3_000_000_019)
    c = weights.make(tiny, 3_000_000_020)
    assert bool((a["embedding"] == b["embedding"]).all())
    assert all(bool((x == y).all()) for x, y in zip(a["wq"], b["wq"]))
    assert not bool((a["wq"][0] == c["wq"][0]).all())
    assert not bool((a["wq"][0] == a["wq"][1]).all())
    assert len(a["wq"]) == 2 and a["wq"][0].shape == (64, 64)
    assert a["w_down"][1].shape == (128, 64) and a["final_norm"].shape == (64,)
