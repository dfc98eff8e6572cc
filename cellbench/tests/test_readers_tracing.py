"""The readers of what the program says about its own time (PR 25), on runs
small enough to work out by hand and on the recorded TPU trace. A reader is
`read(run, **args) -> number or None`: a number whenever the run has what it
reads (0.0 for a count of nothing), None where the program has no such span,
counter or scope — as the commit before this one has not."""

import json
import os

import pytest

from cellbench import run as run_lib
from cellbench import scopes, trace
from cellbench.readers import (
    idle_named_share,
    request_records,
    scope_share,
    slow_spans,
    span_mean_ms,
    stats_quotient,
)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
COARSE = ["no_host_span", "serving/request", "serving/tick", "serving/step"]


def span(name, start, dur, depth=0, **args):
    return {"name": name, "start": start, "dur": dur, "depth": depth,
            "args": args}


# -- idle time under names too coarse to act on ------------------------------

# One tick of 10 ms: the device runs 1.002..1.008; before it the host is in
# step_launch > step_args, after it in step_emit, then publish.
OPS = [("%fusion.1 = bf16[4]{0} fusion()", 1.002, 0.006)]
FINE = [
    span("serving/tick", 1.000, 0.0095),
    span("serving/step", 1.0005, 0.0088, 1),
    span("serving/step_launch", 1.0006, 0.0020, 2),
    span("decode_engine/step_args", 1.0007, 0.0012, 3),
    span("serving/step_sync", 1.0027, 0.0054, 2),
    span("serving/step_emit", 1.0082, 0.0010, 2),
    span("serving/publish", 1.0096, 0.0004),
    # A request's record lies over all of it, on no thread's stack: the
    # deepest-span rule must never prefer it.
    span("serving/request", 0.5, 2.0, -1, request_id="cell-0"),
]
COARSE_SPANS = [s for s in FINE if s["name"] in ("serving/tick", "serving/step")]


def reduced(spans, window=(1.000, 1.010)):
    loaded = {"devices": [{"name": "/device:TPU:0", "ops": OPS, "modules": []}],
              "sync_s": 0.0}
    return {"trace": trace.reduce(loaded, spans, 0.0, window)}


def test_idle_goes_to_the_new_spans_and_the_coarse_share_falls():
    before, after = reduced(COARSE_SPANS), reduced(FINE)
    # Both gaps (1.000..1.002, 1.008..1.010) have their middle in the step.
    assert dict(before["trace"]["idle_gaps"]) == {
        "serving/step": pytest.approx(0.004)}
    assert idle_named_share.read(before, COARSE) == pytest.approx(100.0)
    gaps = dict(after["trace"]["idle_gaps"])
    # 1.000..1.002 has its middle in step_args; 1.008..1.010 in step_emit.
    assert gaps == {"decode_engine/step_args": pytest.approx(0.002),
                    "serving/step_emit": pytest.approx(0.002)}
    assert idle_named_share.read(after, COARSE) == 0.0
    assert idle_named_share.read({"trace": None}, COARSE) is None


def test_a_record_alone_over_a_gap_is_named_not_lost():
    only = reduced([FINE[-1]])
    assert dict(only["trace"]["idle_gaps"]) == {
        "serving/request": pytest.approx(0.004)}


def test_coarse_share_on_the_recorded_trace():
    with open(os.path.join(DATA, "recorded_trace.json")) as fh:
        loaded = json.load(fh)
    ops = [tuple(o) for o in loaded["devices"][0]["ops"]]
    first, last = min(o[1] for o in ops), max(o[1] + o[2] for o in ops)
    window = (first - 0.001, last + 0.001)
    idle = window[1] - window[0] - trace.busy_seconds(ops, window)
    # The host under `serving/step` from the second call on, under a
    # finer span before it: the first millisecond and the gaps up to the
    # second call go to the finer name.
    second_call = loaded["devices"][0]["modules"][1][1]
    spans = [span("serving/step_launch", window[0], second_call - window[0], 2),
             span("serving/step", second_call, window[1] - second_call, 1)]
    run = {"trace": trace.reduce(loaded, spans, loaded["sync_s"],
                                 window)}
    assert run["trace"]["window_s"] - run["trace"]["busy_s"] == pytest.approx(idle)
    gaps = dict(run["trace"]["idle_gaps"])
    share = idle_named_share.read(run, COARSE)
    assert share == pytest.approx(100.0 * gaps["serving/step"] / idle)
    assert 0.0 < share < 100.0
    assert idle_named_share.read(run, ["serving/step_launch"]) == \
        pytest.approx(100.0 * gaps["serving/step_launch"] / idle)


# -- spans: means, slow steps --------------------------------------------------

def test_the_steps_parts_add_up_and_slow_steps_are_counted():
    spans, t = [], 10.0
    for tick in range(20):
        step = 0.2 if tick == 7 else 0.010
        spans.append(span("serving/step", t, step, 1, tick=tick))
        spans.append(span("serving/step_launch", t, 0.002, 2))
        spans.append(span("serving/step_sync", t + 0.002, step - 0.003, 2))
        spans.append(span("serving/step_emit", t + step - 0.001, 0.001, 2))
        t += step + 0.001
    run = {"spans": spans, "window": [9.0, t + 1.0]}
    parts = sum(span_mean_ms.read(run, f"serving/step_{p}")
                for p in ("launch", "sync", "emit"))
    assert parts == pytest.approx(span_mean_ms.read(run, "serving/step"))
    assert slow_spans.read(run, "serving/step", "count") == 1.0
    assert slow_spans.read(run, "serving/step", "longest_ms") == pytest.approx(200.0)
    quiet = {"spans": [s for s in spans if s["dur"] < 0.1], "window": run["window"]}
    assert slow_spans.read(quiet, "serving/step", "count") == 0.0  # not None
    assert slow_spans.read(quiet, "serving/step", "longest_ms") == pytest.approx(10.0)
    old = {"spans": [], "window": run["window"]}
    assert slow_spans.read(old, "serving/step", "count") is None
    assert span_mean_ms.read(old, "serving/step_launch") is None


def test_live_tokens_per_slot_is_a_plain_quotient():
    run = {"stats_open": {"kv_token_steps": 1000, "slot_steps": 10},
           "stats_close": {"kv_token_steps": 4000, "slot_steps": 20}}
    assert stats_quotient.read(run, "kv_token_steps", "slot_steps") == 300.0
    parent = {"stats_open": {"ticks": 1}, "stats_close": {"ticks": 9}}
    assert stats_quotient.read(parent, "kv_token_steps", "slot_steps") is None
    still = {"stats_open": run["stats_open"], "stats_close": run["stats_open"]}
    assert stats_quotient.read(still, "kv_token_steps", "slot_steps") is None


# -- requests, by id ------------------------------------------------------------

def call(index, due, first, group="member"):
    return {"index": index, "group": group, "due": due, "sent": due + 0.001,
            "first": first, "prompt_tokens": 40, "max_new_tokens": 8}


def request_run():
    """Five members due a second apart. Member 1 is a prefix hit and member
    3 a chunked admission: neither has a `serving/prefill` span, so pairing
    submits with prefills by order would give member 3 member 2's admission.
    Member 4 has no first token inside the window."""
    calls, spans = [], []
    parts = {0: (10.0, 30.0, 400.0), 1: (20.0, 0.0, 100.0),
             2: (15.0, 50.0, 900.0), 3: (40.0, 0.0, 2500.0),
             4: (12.0, 20.0, None)}
    for index, (queue, prefill, replay) in parts.items():
        due = 100.0 + index
        admitted = due + 0.002 + queue / 1e3
        first = None if replay is None else \
            admitted + (prefill + replay) / 1e3 + 0.003  # + the way back
        calls.append(call(index, due, first))
        spans.append(span("serving/submit", due + 0.002, 0.0001,
                          request_id=f"cell-{index}"))
        spans.append(span("serving/admission", admitted, 0.0, -1,
                          request_id=f"cell-{index}", queue_wait_ms=queue))
        if prefill:
            spans.append(span("serving/prefill", admitted, prefill / 1e3, 2,
                              request=index + 7, prefill=32))
        if replay is not None:
            spans.append(span(
                "serving/first_token", first - 0.003, 0.0, -1,
                request_id=f"cell-{index}", queue_wait_ms=queue,
                prefill_ms=prefill, replay_ms=replay,
                ttft_ms=queue + prefill + replay))
    # Someone else's request, under its own id, never the client's.
    spans.append(span("serving/first_token", 101.5, 0.0, -1, request_id="17",
                      queue_wait_ms=1.0, prefill_ms=1.0, replay_ms=1.0,
                      ttft_ms=3.0))
    return {"calls": calls, "spans": spans, "window": [99.0, 106.0]}


def test_ttft_parts_are_the_median_members_and_add_up():
    run = request_run()
    # Client TTFTs: 0: 445, 1: 125, 2: 970, 3: 2545 ms, 4: miss.
    # Ranked 1, 0, 2, 3, miss: the median of five is member 2.
    assert request_records.read(run, "queue_wait_ms") == 15.0
    assert request_records.read(run, "prefill_ms") == 50.0
    assert request_records.read(run, "replay_ms") == 900.0
    total = sum(request_records.read(run, p)
                for p in ("queue_wait_ms", "prefill_ms", "replay_ms"))
    client = (run["calls"][2]["first"] - run["calls"][2]["due"]) * 1e3
    assert total == pytest.approx(request_records.read(run, "ttft_ms"))
    assert total == pytest.approx(client, abs=6.0)  # due -> submit, and back
    # An even count: the mean of the two middle members (0 and 2).
    run["calls"] = run["calls"][:4]
    assert request_records.read(run, "replay_ms") == pytest.approx(650.0)


def test_admission_wait_is_joined_by_id_not_by_order():
    run = request_run()
    waits = sorted(2.0 + q for q in (10.0, 20.0, 15.0, 40.0, 12.0))
    assert request_records.read(run, "admit_wait", q=0.9) == pytest.approx(waits[-1])
    assert request_records.read(run, "admit_wait", q=0.5) == pytest.approx(waits[2])
    # The pairing by order is wrong on this very run: three prefill spans
    # for five admissions.
    from cellbench.readers import _spans

    by_order = _spans.admissions(run)
    assert sorted(by_order) == [0, 1, 2] and \
        by_order[1][0] == pytest.approx(102.017)  # member 2's, given to 1


def test_request_readers_return_none_on_a_program_without_records():
    run = request_run()
    run["spans"] = [s for s in run["spans"] if s["name"] in
                    ("serving/submit", "serving/prefill")]
    assert request_records.read(run, "queue_wait_ms") is None
    assert request_records.read(run, "admit_wait") is None


# -- device time by scope ---------------------------------------------------------

def test_scope_paths_drop_wrappers_and_types():
    assert scopes.path_of(
        "jit(step)/vmap(Transformer)/layer_3/block/attn/attention/scores/"
        "dot_general:") == ("step", "Transformer", "layer_3", "block", "attn",
                            "attention", "scores", "dot_general")
    assert scopes.path_of(
        "jit(step)/vmap(attention/kv_gather)/jit(_take)/gather:") == \
        ("step", "attention", "kv_gather", "_take", "gather")
    assert scopes.path_of("") == () and scopes.path_of("gather:") == ("gather",)
    assert scopes.under(("a", "attention", "scores"), ("attention",))
    assert scopes.under(("a", "attention", "kv_gather", "x"),
                        ("attention", "kv_gather"))
    assert not scopes.under(("block", "mlp_norm", "norm"), ("mlp",))
    assert not scopes.under(("attention", "x", "kv_gather"),
                            ("attention", "kv_gather"))


def scoped_trace(shape):
    """Two calls of jit_step and one of jit_prefill. `shape` only renames
    operations, as a change of a tensor's shape does."""
    def op(scope, start, dur):
        return (scopes.path_of(f"jit(step)/vmap(Transformer)/layer_0/block/{scope}:"),
                start, dur, f"%fusion = bf16[{shape}] fusion()")

    ops = []
    for base in (1.000, 1.100):
        ops += [op("attn/attention/scores/dot_general", base, 0.030),
                op("attn/attention/values/dot_general", base + 0.030, 0.010),
                ((), base + 0.040, 0.010, "%copy = copy()"),   # no scope
                (scopes.path_of("jit(step)/vmap(attention/kv_gather)/jit(_take)/gather:"),
                 base + 0.050, 0.020, f"%gather = bf16[{shape}] gather()"),
                op("mlp/mlp/w_up/dot_general", base + 0.070, 0.020),
                op("mlp_norm/norm/mul", base + 0.090, 0.010)]
    ops.append((scopes.path_of("jit(prefill)/attention/scores/dot:"), 1.300, 0.5,
                "%other = fusion()"))
    modules = [("jit_step(1)", 1.000, 0.100), ("jit_step(1)", 1.100, 0.100),
               ("jit_prefill(2)", 1.300, 0.5)]
    return [o[:3] for o in ops], modules, [o[3] for o in ops]


def test_shares_are_read_from_scopes_not_from_shapes(monkeypatch):
    shares = {}
    for shape in ("16,4096,8,4,128", "16,512,8,128"):
        ops, modules, names = scoped_trace(shape)
        assert any(shape in n for n in names)
        monkeypatch.setattr(scopes, "load", lambda directory: (ops, modules))
        run = {"trace": {"busy_s": 1.0}, "run_dir": "/nowhere"}
        shares[shape] = [scope_share.read(run, ["jit_step"], scope, "attention")
                         for scope in ("attention", "attention/kv_gather", "mlp")]
        json.dumps(run)  # what the reader keeps in the run's record is plain
    first, second = shares.values()
    assert first == second == [pytest.approx(60.0), pytest.approx(20.0),
                               pytest.approx(20.0)]


def test_a_program_without_scopes_gives_no_share(monkeypatch):
    ops, modules, _ = scoped_trace("4")
    flax_only = [(tuple(p for p in path if p not in ("attention", "kv_gather")),
                  s, d) for path, s, d in ops]
    monkeypatch.setattr(scopes, "load", lambda directory: (flax_only, modules))
    run = {"trace": {"busy_s": 1.0}, "run_dir": "/nowhere"}
    # Flax alone names `mlp`; the share still waits for the program's scopes.
    assert scope_share.read(run, ["jit_step"], "mlp", "attention") is None
    assert scope_share.read(dict(run, trace=None), ["jit_step"], "mlp") is None
    monkeypatch.setattr(scopes, "load", lambda directory: None)
    assert scope_share.read({"trace": {"busy_s": 1.0}, "run_dir": "/nowhere"},
                            ["jit_step"], "attention", "attention") is None


def test_self_time_leaves_out_what_ran_inside():
    ops = [(("step", "while"), 1.000, 0.010),
           (("step", "attention", "scores"), 1.001, 0.004),
           (("step", "mlp"), 1.005, 0.004)]
    totals = scopes.self_seconds(ops, [("jit_step(1)", 1.0, 0.010)], ["jit_step"])
    assert totals == {("step", "while"): pytest.approx(0.002),
                      ("step", "attention", "scores"): pytest.approx(0.004),
                      ("step", "mlp"): pytest.approx(0.004)}


def test_load_reads_the_scope_from_an_xplane_file(tmp_path):
    pb2 = scopes._xplane_pb2()
    assert pb2 is not None
    space = pb2.XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].name = scopes.SCOPE_STAT
    plane.stat_metadata[2].name = "jit(step)/vmap(attention/kv_gather)/gather:"
    for key, name in ((1, "%fusion.3 = bf16[16,128] fusion()"),
                      (2, "%gather.1 = bf16[8] gather()"),
                      (3, "%copy.1 = copy()"), (4, "jit_step(77)")):
        plane.event_metadata[key].name = name
    stat = plane.event_metadata[1].stats.add(metadata_id=1)
    stat.str_value = "jit(step)/vmap(Transformer)/layer_0/block/mlp/mlp/dot_general:"
    plane.event_metadata[2].stats.add(metadata_id=1).ref_value = 2
    ops = plane.lines.add(name="XLA Ops", timestamp_ns=2_000_000_000)
    for key, offset_ps, duration_ps in ((1, 0, 3_000_000), (2, 3_000_000, 1_000_000),
                                        (3, 4_000_000, 500_000)):
        ops.events.add(metadata_id=key, offset_ps=offset_ps, duration_ps=duration_ps)
    modules = plane.lines.add(name="XLA Modules", timestamp_ns=2_000_000_000)
    modules.events.add(metadata_id=4, offset_ps=0, duration_ps=5_000_000)
    space.planes.add(name="/host:CPU")
    target = tmp_path / "plugins" / "profile" / "2026_01_01"
    target.mkdir(parents=True)
    (target / "host.xplane.pb").write_bytes(space.SerializeToString())
    loaded_ops, loaded_modules = scopes.load(str(tmp_path))
    assert loaded_modules == [("jit_step(77)", pytest.approx(2.0), pytest.approx(5e-6))]
    assert [o[0] for o in loaded_ops] == [
        ("step", "Transformer", "layer_0", "block", "mlp", "mlp", "dot_general"),
        ("step", "attention", "kv_gather", "gather"), ()]
    assert loaded_ops[1][1:] == (pytest.approx(2.000003), pytest.approx(1e-6))
    assert scopes.load(str(tmp_path / "nothing")) is None


# -- the entries ----------------------------------------------------------------------

NEW = ("idle_coarse_share", "step_launch_ms", "step_sync_ms", "step_emit_ms",
       "tick_publish_ms", "step_args_ms", "slow_steps", "slowest_step_ms",
       "kv_live_tokens_per_slot", "step_attention_share",
       "step_kv_gather_share", "step_mlp_share")
STEADY_ONLY = ("ttft_queue_p50_ms", "ttft_prefill_p50_ms",
               "ttft_replay_p50_ms", "admit_wait_p90_ms")


def test_every_new_quantity_is_declared_in_its_cells_with_a_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    declared = {m["name"]: m for m in bench["per_layer"]}
    expected = [f"{q}.{s}" for q in NEW for s in ("steady", "backlog")] + \
        [f"{q}.steady" for q in STEADY_ONLY]
    assert set(expected) <= set(declared)
    # Appended: what was there stays where it was.
    assert [m["name"] for m in bench["per_layer"]][-len(expected):] == \
        [n for n in declared if n in set(expected)]
    for name in expected:
        entry = declared[name]
        cell = "mistral7b_chat_" + name.rsplit(".", 1)[1]
        assert entry["workloads"] == [cell]
        assert entry["moves"] == {"steady": "itl_p90_ms",
                                  "backlog": "serve_tokens_per_s"}[name.rsplit(".", 1)[1]]
        assert set(run_lib.metric_file(name)) <= {"reader", "args"}
