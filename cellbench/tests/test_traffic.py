"""The request list is a function of (traffic file, seed); what a run
measures is a function of the file and the seconds alone."""

import glob
import os

import pytest

from cellbench import traffic
from cellbench.serve import percentile

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILES = sorted(glob.glob(os.path.join(HERE, "traffic", "*.json")))
SEEDS = (0, 7, 2**31 + 12345, 3_999_999_999)


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_same_seed_same_list(path):
    mix = traffic.load(path)
    assert traffic.requests(mix, 32768, 11, 51) == traffic.requests(mix, 32768, 11, 51)
    assert traffic.requests(mix, 32768, 11, 51) != traffic.requests(mix, 32768, 12, 51)


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_measured_set_does_not_move_with_the_seed(path):
    mix = traffic.load(path)
    lists = [traffic.requests(mix, 32768, seed, 51) for seed in SEEDS]
    sizes = traffic.counts(mix, 51)
    for group, n in sizes.items():
        histograms = {tuple(traffic.histogram(items, group)) for items in lists}
        assert len(histograms) == 1, group
        assert len(histograms.pop()) == n
        outputs = {tuple(sorted(r["max_new_tokens"] for r in items
                                if r["group"] == group)) for items in lists}
        assert len(outputs) == 1, group


def test_members_are_the_first_two_thirds_in_due_order():
    mix = traffic.load(os.path.join(HERE, "traffic", "chat_steady.json"))
    items = traffic.requests(mix, 32768, 5, 51)
    sizes = traffic.counts(mix, 51)
    n = round(mix["rate_per_s"] * 51)
    assert sizes["member"] == 2 * n // 3 and sizes["member"] + sizes["tail"] == n
    window = [r for r in items if r["group"] in ("member", "tail")]
    assert [r["due_s"] for r in window] == sorted(r["due_s"] for r in window)
    assert all(0 <= r["due_s"] < 51 for r in window)
    assert [r["group"] for r in window] == \
        ["member"] * sizes["member"] + ["tail"] * sizes["tail"]
    assert all(-mix["lead_in_s"] <= r["due_s"] < 0
               for r in items if r["group"] == "lead")


def test_a_batch_list_is_the_same_job_under_every_seed():
    mix = traffic.load(os.path.join(HERE, "traffic", "chat_backlog.json"))
    a, b = (traffic.requests(mix, 32768, seed, 51) for seed in (1, 2**31 + 9))
    assert [(len(r["prompt"]), r["max_new_tokens"]) for r in a] == \
        [(len(r["prompt"]), r["max_new_tokens"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    for start in range(0, len(a), mix["block"]):
        block = a[start:start + mix["block"]]
        assert sorted(len(r["prompt"]) for r in block) == \
            traffic.strata(mix["prompt_tokens"], len(block))


def test_lengths_keep_to_the_clip():
    for path in FILES:
        mix = traffic.load(path)
        for r in traffic.requests(mix, 32768, 3, 51):
            assert mix["prompt_tokens"]["min"] <= len(r["prompt"]) <= mix["prompt_tokens"]["max"]
            assert mix["output_tokens"]["min"] <= r["max_new_tokens"] <= mix["output_tokens"]["max"]
            assert all(0 <= t < 32768 for t in r["prompt"])


def test_percentile_sorts_misses_last():
    assert percentile([3.0, 1.0, 2.0], 0.5) == 2.0
    assert percentile([1.0, 2.0, 3.0], 0.5, misses=2) == 3.0
    assert percentile([1.0, 2.0], 0.5, misses=2) == float("inf")
    assert percentile([1.0], 0.5, misses=2) == float("inf")
    assert percentile(list(range(1, 21)), 0.95) == 19
    assert percentile(list(range(1, 20)), 0.95, misses=1) == 19
    assert percentile(list(range(1, 19)), 0.95, misses=2) == float("inf")
