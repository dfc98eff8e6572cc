"""The reduction from a trace to numbers, on a trace small enough to work
out by hand and on one recorded on a TPU v5e (tests/data/recorded_trace.json:
five calls of a jitted scan of four matmuls)."""

import json
import os

import pytest

from cellbench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# Two calls of jit_step: a while of 6 ms holding two fusions of 2 ms, then a
# copy of 1 ms; 3 ms idle between the calls; 1 ms idle before the first.
OPS = [
    ("%while.1 = (s32[]) while(...)", 1.000, 0.006),
    ("%fusion.7 = bf16[16,128]{1,0} fusion(...)", 1.001, 0.002),
    ("%fusion.7 = bf16[16,128]{1,0} fusion(...)", 1.003, 0.002),
    ("%copy.3 = f32[8]{0} copy(...)", 1.006, 0.001),
    ("%while.1 = (s32[]) while(...)", 1.010, 0.006),
    ("%fusion.7 = bf16[16,128]{1,0} fusion(...)", 1.011, 0.002),
    ("%fusion.7 = bf16[16,128]{1,0} fusion(...)", 1.013, 0.002),
    ("%copy.3 = f32[8]{0} copy(...)", 1.016, 0.001),
]
MODULES = [("jit_step(123)", 1.000, 0.007), ("jit_step(123)", 1.010, 0.007)]
SPANS = [
    {"name": "serving/tick", "start": 0.9985, "dur": 0.0095, "depth": 0},
    {"name": "serving/step", "start": 0.9998, "dur": 0.0077, "depth": 1},
    {"name": "serving/tick", "start": 1.0082, "dur": 0.0085, "depth": 0},
    {"name": "serving/admit", "start": 1.0083, "dur": 0.0015, "depth": 1},
]


def test_busy_is_the_union_of_operations():
    assert trace.busy_seconds(OPS, (0.999, 1.017)) == pytest.approx(0.014)
    assert trace.busy_seconds(OPS, (1.002, 1.012)) == pytest.approx(0.007)
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


def test_an_operation_is_counted_without_what_ran_inside_it():
    totals = trace.self_times(OPS, MODULES)
    assert totals["jit_step/fusion_bf16_16_128_"] == pytest.approx(0.008)
    assert totals["jit_step/while_s32__"] == pytest.approx(0.004)
    assert totals["jit_step/copy_f32_8_"] == pytest.approx(0.002)
    assert sum(totals.values()) == pytest.approx(0.014)


def test_time_per_program():
    assert trace.module_times(MODULES) == {"jit_step": (2, pytest.approx(0.014))}
    assert trace.module_times(MODULES, (1.005, 1.02)) == \
        {"jit_step": (1, pytest.approx(0.007))}


def test_idle_gaps_go_to_the_deepest_host_span():
    gaps = trace.idle_gaps(OPS, SPANS, (0.999, 1.018))
    assert gaps["serving/tick"] == pytest.approx(0.001)       # 0.999..1.000
    assert gaps["serving/admit"] == pytest.approx(0.003)      # 1.007..1.010
    assert gaps["no_host_span"] == pytest.approx(0.001)       # 1.017..1.018
    assert sum(gaps.values()) + trace.busy_seconds(OPS, (0.999, 1.018)) == \
        pytest.approx(0.019)


def test_reduce_ties_the_two_clocks():
    loaded = {"devices": [{"name": "/device:TPU:0", "ops": OPS,
                           "modules": MODULES}], "sync_s": 0.5}
    # the host's clock read 100.5 at the annotation: host = trace + 100
    spans = [dict(s, start=s["start"] + 100) for s in SPANS]
    out = trace.reduce(loaded, spans, 100.5, (100.999, 101.018))
    assert out["window_s"] == pytest.approx(0.019)
    assert out["busy_s"] == pytest.approx(0.014)
    assert out["modules"] == {"jit_step": [2, pytest.approx(0.014)]}
    assert out["device_ops"][0][0] == "jit_step/fusion_bf16_16_128_"
    assert dict(out["idle_gaps"])["serving/admit"] == pytest.approx(0.003)


def test_recorded_trace():
    with open(os.path.join(DATA, "recorded_trace.json")) as fh:
        loaded = json.load(fh)
    ops = [tuple(o) for o in loaded["devices"][0]["ops"]]
    modules = [tuple(m) for m in loaded["devices"][0]["modules"]]
    assert len(modules) == 5 and {trace.module_name(m[0]) for m in modules} == {"jit_step"}
    totals = trace.self_times(ops, modules)
    # 4 matmul fusions in each of 5 calls do nearly all the work
    assert max(totals, key=totals.get) == "jit_step/fusion_bf16_16_1024_"
    calls, seconds = trace.module_times(modules)["jit_step"]
    assert calls == 5
    # the trace rounds to nanoseconds, so nested operations overlap a little
    assert sum(totals.values()) == pytest.approx(
        trace.busy_seconds(ops, (0.0, 1.0)), rel=0.1)
    assert sum(totals.values()) <= seconds
    assert totals["jit_step/while_s32__"] < 0.1 * totals["jit_step/fusion_bf16_16_1024_"]
    assert loaded["sync_s"] == pytest.approx(0.045575988)
