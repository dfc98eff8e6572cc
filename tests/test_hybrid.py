"""The hybrid model (models/hybrid.py: a Mamba-2 or attention mixer per
layer, a dropless top-k expert layer after each) against the plain reference
the benchmark keeps (cellbench/reference/granite_hybrid.py), on logits, at a
tiny size on the CPU with seeded weights; and the serving engine's two kinds
of state: keys and values paged by token, the recurrent state held once a
slot.

Tolerances. The tiny model runs with `dtype=float32`, so program and
reference do the same float32 arithmetic in another order. The table's
embedding is narrow (weight_tables/granite_hybrid.py says why), so logits
have a standard deviation of 0.006 and reach 0.02: they agree to 1.3e-8 in
the full forward, and 5e-7 leaves room for longer sums. bfloat16 where
float32 is stated moves logits by 2e-5 (the scan's inputs or state, dt) to
3e-3 (the router's logits) and fails every comparison here. What the
serving dtype does to those leaves is pinned by dtype, not by tolerance
(`test_float32_where_stated_under_bfloat16`)."""

import dataclasses
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import agent, weights
from cellbench.reference import granite_hybrid as reference
from tf_yarn_tpu.models import hybrid
from tf_yarn_tpu.models.decode_engine import (
    DecodeEngine,
    all_forced,
    build_paged_state_step_fn,
    _decode_cache_aval,
    cache_layout,
    clear_engines,
    kv_partition_spec,
    paged_pool_avals,
    pool_partition_spec,
)
from tf_yarn_tpu.models.moe import DroplessMoE
from tf_yarn_tpu.serving.request import SamplingParams
from tf_yarn_tpu.serving.scheduler import SlotScheduler

from tests.fakes import admit_prefill, assert_pipelined_equals_settled

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOLERANCE = 5e-7  # float32 both sides, sums in another order (see above)
SEED = 3_000_000_029
BLOCK = 8
BUCKETS = (8, 16, 32)


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(ROOT, "cellbench", "tests", "data",
                           "tiny_granite.json")) as fh:
        sizes = json.load(fh)
    sizes["model"] = {"dtype": jnp.float32, "param_dtype": jnp.float32}
    model = agent.build_model(sizes)
    return {
        "sizes": sizes, "model": model,
        "variables": agent.program_variables(model, sizes, SEED),
        "weights": weights.make(sizes, SEED),
    }


def _reference_logits(tiny, tokens, rows):
    return np.asarray(reference.logits(
        tiny["weights"], jnp.asarray(tokens, jnp.int32), tiny["sizes"],
        jnp.asarray(rows, jnp.int32)))


def test_full_forward_matches_reference(tiny):
    tokens = np.random.default_rng(1).integers(0, 256, 37)
    got = tiny["model"].apply(tiny["variables"], jnp.asarray(tokens)[None])[0]
    want = _reference_logits(tiny, tokens, np.arange(37))
    assert np.abs(want).max() > 0.01  # the comparison is of something
    np.testing.assert_allclose(np.asarray(got), want, atol=TOLERANCE, rtol=0)


def test_float32_where_stated_under_bfloat16(tiny):
    """At the serving dtype the state, the conv tail and every vector stay
    float32; only matrices and activations are bfloat16."""
    sizes = dict(tiny["sizes"], model={})
    model = agent.build_model(sizes)
    variables = agent.program_variables(model, sizes, SEED)
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): leaf.dtype
            for path, leaf in jax.tree_util.tree_leaves_with_path(variables)}
    for name, dtype in flat.items():
        vector = name.rsplit("/", 1)[-1] in (
            "scale", "A_log", "D", "dt_bias", "conv_w", "conv_b", "norm")
        assert dtype == (jnp.float32 if vector else jnp.bfloat16), name
    cache = jax.eval_shape(
        lambda v, t: model.apply(v, t, decode=True, mutable=["cache"])[1],
        variables, jax.ShapeDtypeStruct((1, 8), jnp.int32))["cache"]
    for layer in cache.values():
        for leaf in ("ssm_state", "conv_state"):
            if "mamba" in layer:
                assert layer["mamba"][leaf].dtype == jnp.float32


@pytest.mark.parametrize("tokens,chunk,from_zero", [
    (24, 8, True),    # whole chunks
    (25, 8, False),   # just over a chunk, from a state
    (23, 8, False),   # just under
    (5, 8, True),     # shorter than a chunk
    (64, 16, False),
])
def test_chunked_scan_matches_one_token_update(tokens, chunk, from_zero):
    """The prefill path (a chunk at a time) and the decode path (a token at
    a time) are one recurrence: same outputs, same final state. float32,
    so 1e-5 of the largest value; a bfloat16 matmul inside the chunk would
    miss by 1e-2."""
    rng = np.random.default_rng(tokens)
    heads, p, n = 4, 8, 16
    x = jnp.asarray(rng.normal(size=(2, tokens, heads, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.5, (2, tokens, heads)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, heads), jnp.float32)
    b = jnp.asarray(rng.normal(size=(2, tokens, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(2, tokens, n)), jnp.float32)
    state = jnp.zeros((2, heads, p, n), jnp.float32) if from_zero else \
        jnp.asarray(rng.normal(size=(2, heads, p, n)), jnp.float32)
    y_scan, end_scan = hybrid.ssd_scan(x, dt, a, b, c, state, chunk)
    ys, end = [], state
    for t in range(tokens):
        y, end = hybrid.ssm_update(x[:, t], dt[:, t], a, b[:, t], c[:, t], end)
        ys.append(y)
    want = np.asarray(jnp.stack(ys, 1))
    scale = np.abs(want).max()
    np.testing.assert_allclose(np.asarray(y_scan), want, atol=1e-5 * scale, rtol=0)
    np.testing.assert_allclose(np.asarray(end_scan), np.asarray(end),
                               atol=1e-5 * np.abs(np.asarray(end)).max(), rtol=0)


class _Grid:
    """The engine's paged pool and state slots, driven by hand the way the
    scheduler drives them, with the step's logits read out."""

    def __init__(self, tiny, slots=3):
        self.tiny, self.slots = tiny, slots
        self.engine = DecodeEngine(tiny["model"], prompt_buckets=BUCKETS)
        variables = tiny["variables"]
        self.per_slot = 128 // BLOCK
        self.pool = self.engine.make_paged_pool(
            variables, slots * self.per_slot + 1, BLOCK)
        self.state = self.engine.make_slot_state(variables, slots)
        self.tables = np.zeros((slots, self.per_slot), np.int32)
        self.lengths = np.zeros((slots,), np.int32)
        self.rngs = np.zeros((slots, 2), np.uint32)
        self.step = jax.jit(build_paged_state_step_fn(
            tiny["model"], BLOCK, 0.0, None, None, with_logits=True))

    def admit(self, slot, prompt):
        variables = self.tiny["variables"]
        blocks = 1 + slot * self.per_slot + np.arange(self.per_slot)
        # The rule the engine reads off this model: the floor (the state is
        # what a prefill left at the END of its bucket).
        self.pool, row, _bucket, prefill = admit_prefill(
            self.engine, variables, self.pool, prompt, blocks, BLOCK,
            self.engine.ceiling_prefill(variables))
        self.state = self.engine.write_slot_state(self.state, slot, row)
        self.tables[slot] = blocks
        self.lengths[slot] = prefill
        return prefill

    def retire(self, slot):
        self.tables[slot] = 0
        self.lengths[slot] = 0

    def advance(self, tokens_by_slot):
        """One step with the given token in each named slot; logits by slot."""
        tokens = np.zeros((self.slots,), np.int32)
        for slot, token in tokens_by_slot.items():
            tokens[slot] = token
        self.pool, self.state, _emitted, self.rngs, counts, logits = self.step(
            self.tiny["variables"], self.pool, self.state,
            jnp.asarray(self.tables), jnp.asarray(self.lengths),
            *all_forced(tokens, self.rngs), jnp.zeros((self.slots,), bool))
        # Read (and so wait) before the host arrays change: on the CPU
        # `jnp.asarray` may alias them, and the step runs asynchronously.
        logits, counts = np.asarray(logits), np.asarray(counts)
        for slot in tokens_by_slot:
            self.lengths[slot] += 1
        return logits, counts

    def run(self, slot, sequence, prompt_len):
        """Admit `sequence[:prompt_len]`, then feed the rest a token a
        step; logits of every step, for positions prefill .. len - 1."""
        prefill = self.admit(slot, sequence[:prompt_len])
        rows = [self.advance({slot: sequence[t]})[0][slot]
                for t in range(prefill, len(sequence))]
        return prefill, np.stack(rows)


# Prompt lengths on, just over and just under a prefill bucket (8, 16, 32:
# the prefill takes the largest bucket below the length) and a chunk of the
# scan (8); 8 and 5 prefill nothing and start from a zeroed state.
@pytest.mark.parametrize("prompt_len", [5, 8, 9, 15, 16, 17, 31, 32, 33, 41])
def test_prefill_replay_decode_match_reference(tiny, prompt_len):
    """Bucketed prefill into the pool and the state slot, then replay and
    decode a token a step through the paged step, against ONE full forward
    of the reference over the same tokens."""
    sequence = np.random.default_rng(prompt_len).integers(0, 256, prompt_len + 9)
    grid = _Grid(tiny)
    prefill, got = grid.run(1, sequence, prompt_len)
    assert prefill == max([b for b in BUCKETS if b < prompt_len], default=0)
    want = _reference_logits(tiny, sequence, np.arange(prefill, len(sequence)))
    np.testing.assert_allclose(got, want, atol=TOLERANCE, rtol=0)


def test_slots_step_together_and_a_reused_slot_starts_clean(tiny):
    """Two requests in two slots at different positions, stepped together,
    equal each alone; then a third through a slot that held another: equal
    to it alone in a fresh grid (exactly: the same program on the same
    numbers, the predecessor's state and rows gone), and to the reference."""
    rng = np.random.default_rng(5)
    first, second, third = (rng.integers(0, 256, n) for n in (30, 21, 14))
    grid = _Grid(tiny)
    p1, p2 = grid.admit(0, first[:20]), grid.admit(2, second[:11])
    got1, got2 = [], []
    for t in range(10):
        logits, counts = grid.advance({0: first[p1 + t], 2: second[p2 + t]})
        got1.append(logits[0])
        got2.append(logits[2])
    # two active slots, three expert layers, top 3: 6 assignments a layer
    assert counts.shape == (3, 1 + 4) and (counts[:, 0] == 6).all()
    assert (counts[:, 1:].sum(1) <= 6).all()
    for got, sequence, start in ((got1, first, p1), (got2, second, p2)):
        want = _reference_logits(tiny, sequence[:start + 10],
                                 np.arange(start, start + 10))
        np.testing.assert_allclose(np.stack(got), want, atol=TOLERANCE, rtol=0)
    grid.retire(0)
    _, reused = grid.run(0, third, 6)        # nothing prefilled: zero state
    _, alone = _Grid(tiny).run(0, third, 6)
    np.testing.assert_array_equal(reused, alone)
    want = _reference_logits(tiny, third, np.arange(0, len(third)))
    np.testing.assert_allclose(reused, want, atol=TOLERANCE, rtol=0)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips share a layer of 8 experts, 2 each. Each share returns its
    own experts' part of the sum plus the shared expert, which all compute
    alike; the four routed parts and the shared expert counted once are the
    uncut layer of the reference."""
    rng = np.random.default_rng(7)
    d, experts, width, shared, top_k = 32, 8, 16, 24, 3
    w = {
        "router": jnp.asarray(rng.normal(size=(d, experts)), jnp.float32),
        "w_in": jnp.asarray(rng.normal(size=(experts, d, 2 * width)) / 6, jnp.float32),
        "w_out": jnp.asarray(rng.normal(size=(experts, width, d)) / 4, jnp.float32),
        "shared_in": jnp.asarray(rng.normal(size=(d, 2 * shared)) / 6, jnp.float32),
        "shared_out": jnp.asarray(rng.normal(size=(shared, d)) / 5, jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(19, d)), jnp.float32)
    uncut = np.asarray(reference.experts(x, w, top_k=top_k, offset=0))
    only_shared = np.asarray(reference._swiglu(
        x, w["shared_in"], w["shared_out"], None))
    total = np.zeros_like(uncut)
    for share in range(4):
        held = slice(2 * share, 2 * share + 2)
        layer = DroplessMoE(
            num_experts=experts, num_experts_here=2, expert_offset=2 * share,
            top_k=top_k, d_expert=width, d_shared=shared,
            dtype=jnp.float32, param_dtype=jnp.float32)
        params = {"params": {
            "router": w["router"], "w_in": w["w_in"][held],
            "w_out": w["w_out"][held], "shared_in": w["shared_in"],
            "shared_out": w["shared_out"]}}
        out, stats = layer.apply(params, x, jnp.ones((19,), bool),
                                 mutable=["moe_stats"])
        counts = np.asarray(stats["moe_stats"]["counts"][0])
        assert counts[0] == 19 * top_k
        total += np.asarray(out) - only_shared
        # and the reference's own share, given the same two experts
        mine = reference.experts(
            x, dict(w, w_in=w["w_in"][held], w_out=w["w_out"][held]),
            top_k=top_k, offset=2 * share)
        np.testing.assert_allclose(np.asarray(out), np.asarray(mine),
                                   atol=2e-5, rtol=0)
    # outputs of magnitude 1 here (the test's own weights), float32 sums
    # in another order: 2e-5; a bfloat16 matmul would miss by 1e-2
    np.testing.assert_allclose(total + only_shared, uncut, atol=2e-5, rtol=0)


# -- the scheduler with a stateful model ------------------------------------


def _scheduler(tiny, **kwargs):
    engine = DecodeEngine(tiny["model"], prompt_buckets=BUCKETS)
    kwargs.setdefault("block_size", BLOCK)
    return SlotScheduler(engine, tiny["variables"], **kwargs)


def _serve(scheduler, prompts, new_tokens=6):
    responses = [scheduler.submit(
        list(map(int, p)), SamplingParams(max_new_tokens=new_tokens))
        for p in prompts]
    for _ in range(2000):
        if all(r.done for r in responses):
            break
        scheduler.tick()
    return [r.result(timeout=1) for r in responses]


def test_scheduler_serves_through_reused_slots(tiny):
    """Five requests through two slots give what each gives alone, and the
    counters say what happened: a state write an admission, the prefix
    cache standing aside each time, the experts' tally."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, n) for n in (9, 20, 5, 33, 17)]
    together = _scheduler(tiny, max_slots=2)
    served = _serve(together, prompts)
    for prompt, tokens in zip(prompts, served):
        assert _serve(_scheduler(tiny, max_slots=1), [prompt]) == [tokens]
    stats = together.stats()
    assert stats["state_leaves"] == ["conv_state", "ssm_state"]
    # The state is what a prefill left at the end of its bucket: the floor
    # rule (8, 16, nothing, 32, 16 prefilled whole, the rest replayed).
    assert together.engine.ceiling_prefill(tiny["variables"]) is False
    assert (stats["prefills_ceiling"], stats["prefills_floor"],
            stats["prefill_pad_tokens"], stats["prefilled_tokens"],
            stats["prefill_tokens"]) == (0, 4, 0, 72, 1 + 4 + 5 + 1 + 1)
    assert stats["state_resets"] == 5 and stats["prefix_skipped_stateful"] == 5
    assert stats["prefix_cache"]["entries"] == 0
    assert stats["prefix_cache"]["hits"] == 0
    # per slot: ssm_state 16 x 8 x 16 and conv_state 3 x 160, float32, in
    # each of two mamba layers
    assert stats["state_bytes"] == 2 * 2 * 4 * (16 * 8 * 16 + 3 * 160)
    # a layer-step a launched step, not a tick: a tick that only reads the
    # step in flight launches none
    assert stats["moe_layer_steps"] == 3 * stats["steps"]
    assert stats["steps"] < stats["ticks"]
    assert stats["moe_assignments"] == 3 * 3 * stats["slot_steps"]
    assert 0 < stats["moe_assignments_here"] < stats["moe_assignments"]
    assert 0 < stats["moe_experts_touched_per_layer_step"] <= 4
    assert stats["moe_load_max_over_mean"] >= 1.0
    together.close()


def test_pipelined_streams_equal_settled_streams(tiny):
    """`paged_state_step` launched before the step before is read (the state, the counts
    ride back a step late): the streams of the serial order, sampled, on
    one compiled program (tests/fakes.py)."""
    scheduler = _scheduler(tiny, max_slots=2, temperature=1.0, top_k=8)
    assert_pipelined_equals_settled(scheduler)
    assert scheduler.engine.stats["paged_step_compiles"] == 1
    scheduler.close()


def test_same_prompt_twice_gets_no_prefix_hit(tiny):
    prompt = np.random.default_rng(2).integers(0, 256, 24)
    scheduler = _scheduler(tiny, max_slots=2)
    first, second = _serve(scheduler, [prompt]), _serve(scheduler, [prompt])
    assert first == second
    assert scheduler.stats()["prefix_skipped_stateful"] == 2
    assert scheduler.stats()["prefilled_tokens"] == 2 * 16
    scheduler.close()


@pytest.mark.parametrize("kwargs,feature", [
    ({"kv_host_blocks": 8}, "suspend / resume"),
    ({"prefill_chunk": 4}, "chunked prefill"),
    ({"spec_k": 2}, "speculative step"),
])
def test_what_does_not_carry_the_state_is_refused_by_name(tiny, kwargs, feature):
    with pytest.raises(ValueError) as refused:
        _scheduler(tiny, max_slots=2, **kwargs)
    assert feature in str(refused.value)
    assert "ssm_state" in str(refused.value)


@pytest.mark.parametrize("call", ["export_hot_prefixes", "import_prefixes"])
def test_block_shipping_is_refused_by_name(tiny, call):
    scheduler = _scheduler(tiny, max_slots=1)
    with pytest.raises(ValueError, match="/v1/blocks.*ssm_state"):
        getattr(scheduler, call)(*([] if call.startswith("export") else [{}]))
    scheduler.close()


def test_engine_programs_that_carry_no_state_refuse(tiny):
    engine = DecodeEngine(tiny["model"], prompt_buckets=BUCKETS)
    variables = tiny["variables"]
    pool = engine.make_paged_pool(variables, 9, BLOCK)
    zeros = np.zeros((2,), np.int32)
    with pytest.raises(ValueError, match="paged_step.*conv_state, ssm_state"):
        engine.paged_step(variables, pool, np.zeros((2, 16), np.int32), zeros,
                          *all_forced(zeros, np.zeros((2, 2), np.uint32)),
                          np.zeros((2,), bool), block_size=BLOCK)
    with pytest.raises(ValueError, match="extract_blocks.*ssm_state"):
        engine.extract_blocks(variables, pool, np.zeros((16,), np.int32), BLOCK)


def test_a_model_must_name_its_cache_leaves(tiny):
    class Unnamed:
        config = tiny["model"].config

        def serving_contract(self):
            return dataclasses.replace(
                tiny["model"].serving_contract(),
                leaf_kinds={"cached_key": ("paged", -3)})

    row = {"attn": {"cached_key": jax.ShapeDtypeStruct((1, 128, 2, 16), jnp.float32),
                    "running_mean": jax.ShapeDtypeStruct((1, 128), jnp.float32)}}
    with pytest.raises(ValueError, match="running_mean.*not among"):
        cache_layout(Unnamed(), row)
    with pytest.raises(ValueError, match="does not declare"):
        cache_layout(object(), row)


def test_abstract_pool_and_placement_follow_the_declared_layout(tiny):
    """The analysis paths (`paged_pool_avals`, the partition specs) read
    the model's layout, as the engine does: a state leaf is no pool leaf
    and stays replicated, a key leaf is paged along the axis declared and
    splits its heads (the axis after it), whatever leads the shape."""
    model, variables = tiny["model"], tiny["variables"]
    row = _decode_cache_aval(model, variables)
    layout = cache_layout(model, row)
    pool = paged_pool_avals(model, row, 9, BLOCK)
    flat = lambda tree: jax.tree_util.tree_leaves(  # noqa: E731
        tree, is_leaf=lambda x: x is None)
    kinds = {"paged": 0, "slot": 0, "index": 0}
    for lay, aval, pooled in zip(flat(layout), flat(row), flat(pool)):
        kinds[lay.kind] += 1
        if lay.kind != "paged":
            assert pooled is None
            assert kv_partition_spec(aval.shape, lay, 2) == \
                jax.sharding.PartitionSpec()
            continue
        assert pooled.shape == aval.shape[:lay.axis] + (9, BLOCK) \
            + aval.shape[lay.axis + 1:]
        heads = aval.shape[lay.axis + 1]
        assert heads % 2 == 0
        assert kv_partition_spec(aval.shape, lay, 2)[lay.axis + 1] == "tp"
        assert pool_partition_spec(aval.shape, lay, 2)[lay.axis + 2] == "tp"
        assert kv_partition_spec(aval.shape, lay, heads + 1) == \
            jax.sharding.PartitionSpec()
    assert all(kinds.values()), kinds
    with pytest.raises(ValueError, match="must divide"):
        paged_pool_avals(model, row, 9, 7)
    # a grid of slots: the declaration counts the axis from the end
    grid = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((3,) + a.shape, a.dtype), row)
    for lay, lay_row in zip(flat(cache_layout(model, grid)), flat(layout)):
        assert lay.kind == lay_row.kind
        assert lay.axis == (None if lay_row.axis is None else lay_row.axis + 1)


@pytest.mark.parametrize("broken,reason", [
    ("paged_state_step", "did not compile or run"),
    ("make_slot_state", "no room for the state"),
])
def test_run_serving_fails_at_start_up_with_the_reason(monkeypatch, tiny,
                                                       broken, reason):
    """State slots that do not fit, or a step the compiler refuses, stop
    `run_serving` before it listens: no endpoint is advertised, and the
    error says what and why."""
    from tf_yarn_tpu import inference as inference_mod
    from tf_yarn_tpu.coordination.kv import InProcessKV
    from tf_yarn_tpu.experiment import ServingExperiment
    from tf_yarn_tpu.serving.server import run_serving
    from tf_yarn_tpu.topologies import TaskKey

    monkeypatch.setattr(inference_mod, "_restore_params",
                        lambda model_dir, step: (tiny["variables"], 1))

    def refuse(self, *args, **kwargs):
        raise MemoryError("RESOURCE_EXHAUSTED: 7.1G of 6.9G")

    monkeypatch.setattr(DecodeEngine, broken, refuse)
    clear_engines()

    class _Runtime:
        kv = InProcessKV()
        task_key = TaskKey("serving", 0)
        task = "serving:0"

    experiment = ServingExperiment(
        model=tiny["model"], model_dir="/nonexistent-restore-is-patched",
        host="127.0.0.1", max_slots=2, block_size=BLOCK)
    failure = {}

    def serve():
        try:
            run_serving(experiment, runtime=_Runtime())
        except Exception as exc:
            failure["error"] = exc

    thread = threading.Thread(target=serve)
    thread.start()
    thread.join(timeout=120)
    clear_engines()
    assert not thread.is_alive()
    message = str(failure["error"])
    assert "serving cannot start" in message and reason in message
    assert "ssm_state" in message and "RESOURCE_EXHAUSTED" in message
    with pytest.raises(Exception):
        _Runtime.kv.wait_str("serving:0/serving_endpoint", timeout=0.2)
