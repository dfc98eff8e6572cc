"""`DroplessMoE`'s two schedules of one sum at a call of few tokens
(models/moe.py): one product over every held expert, and a loop over the
held experts that some token chose (`touched_experts`), which the layer
takes where the shapes say that the matrices it skips outweigh what an
iteration costs (`loops_over_touched`). The loop against the one product on
routings made by hand; the rule over the benchmark's configurations; and the
loop through the engine and the scheduler on the two tiny models whose
full-size steps take it, with the module's constant set so that the tiny
shapes take it too.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import agent, weights
from cellbench.reference import deepseek_v32, longcat_flash
from tf_yarn_tpu.models import moe
from tf_yarn_tpu.models.decode_engine import (
    DEFAULT_PROMPT_BUCKETS,
    DecodeEngine,
    all_forced,
    build_paged_state_step_fn,
)
from tf_yarn_tpu.serving.request import SamplingParams
from tf_yarn_tpu.serving.scheduler import SlotScheduler
from tests.fakes import admit_prefill

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(ROOT, "cellbench", "tests", "data")
CONFIGS = os.path.join(ROOT, "cellbench", "configs")
TOLERANCE = 5e-5  # float32 both sides, sums in another order: the tiny
#                   models' own files hold their steps to it
SEED = 3_000_000_046
BLOCK = 8
BUCKETS = (8, 16, 32)

# -- the loop against the one product -----------------------------------------

EXPERTS, D = 8, 48


def _routed(choices, outputs=EXPERTS, seed=5):
    """x [T, D] and a router [D, outputs] under which token t chooses
    exactly `choices[t]`: a token's first `outputs` features are 8 at its
    choices and 0 elsewhere and the router copies them into the logits; the
    other features are noise that the router does not see."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(len(choices), D))
    x[:, :outputs] = 0.0
    for token, chosen in enumerate(choices):
        x[token, list(chosen)] = 8.0
    router = np.zeros((D, outputs))
    router[np.arange(outputs), np.arange(outputs)] = 1.0
    return x, router


def _layer(shape, choices, dtype, **more):
    """(layer, variables, x): top-2 of `EXPERTS`, routed as `choices` say."""
    layer = moe.DroplessMoE(
        num_experts=EXPERTS, top_k=2, d_expert=16, dtype=jnp.dtype(dtype),
        param_dtype=jnp.dtype(dtype), **shape, **more)
    x, router = _routed(choices, EXPERTS + shape.get("num_zero_experts", 0))
    x = jnp.asarray(x, dtype)
    params = layer.init(jax.random.key(7), x)["params"]
    return layer, {"params": dict(
        params, router=jnp.asarray(router, dtype))}, x


# name: (the layer's shape, each token's choices, count_mask or None,
#        the held experts that a counted token chose)
CASES = {
    "no_expert_touched": (
        dict(num_experts_here=4), [(5, 6), (4, 7), (6, 7)], None, 0),
    "one_expert": (
        dict(num_experts_here=4), [(1, 6), (1, 5), (7, 1)], None, 1),
    "every_expert": (
        dict(num_experts_here=4), [(0, 1), (2, 3), (3, 0), (5, 6)], None, 4),
    "a_token_chooses_two_held": (
        dict(num_experts_here=4), [(0, 3), (5, 6), (7, 4)], None, 2),
    "zero_experts_in_the_router": (
        dict(num_experts_here=4, scoring="softmax_all", num_zero_experts=2,
             routed_scale=6.0),
        [(2, 8), (8, 9), (9, 3), (6, 7)], None, 2),
    "expert_offset": (
        dict(num_experts_here=4, expert_offset=2),
        [(0, 1), (2, 7), (1, 5), (6, 0)], None, 2),
    "groups": (
        dict(num_experts_here=8, scoring="sigmoid", n_group=2, topk_group=1,
             routed_scale=2.5),
        [(1, 2), (5, 6), (0, 2), (7, 5)], None, 6),
    "count_mask_leaves_slots_out": (
        dict(num_experts_here=4), [(0, 5), (1, 2), (3, 6), (2, 7)],
        [True, False, False, True], 2),
    "count_mask_marks_nothing": (
        dict(num_experts_here=4), [(0, 1), (2, 3)], [False, False], 0),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_loop_over_touched_experts_equals_the_one_product(
        monkeypatch, case, dtype):
    """The same sum on both schedules: float32 to 1e-5, bfloat16 to one
    rounding of the largest output (the products narrow at the same places;
    the experts' float32 partial sums are added in another order). The
    counts are the same array, and the loop multiplied exactly the held
    experts that a counted token chose. Where a `count_mask` leaves tokens
    out (a step's free slots), the loop runs for the marked tokens'
    experts alone: the marked rows are the one product's, the others are
    finite and nobody's."""
    shape, choices, mask, touched = CASES[case]
    outputs = EXPERTS + shape.get("num_zero_experts", 0)
    layer, variables, x = _layer(shape, choices, dtype, d_shared=16)
    mask = None if mask is None else jnp.asarray(mask)
    apply = jax.jit(lambda v, x: layer.apply(
        v, x, mask, mutable=["moe_stats"]))

    expert_bytes = 3 * D * 16 * jnp.dtype(dtype).itemsize
    about = (shape["num_experts_here"], len(choices), 2, outputs, expert_bytes)
    assert not moe.loops_over_touched(*about)
    whole, counted = apply(variables, x)
    monkeypatch.setattr(moe, "LOOP_SKIPS_BYTES", 0)
    assert moe.loops_over_touched(*about)
    looped, counted_too = jax.jit(lambda v, x: layer.apply(
        v, x, mask, mutable=["moe_stats"]))(variables, x)

    whole, looped = (np.asarray(v.astype(jnp.float32)) for v in (whole, looped))
    rows = slice(None) if mask is None else np.asarray(mask)
    atol = 1e-5 if dtype == "float32" else 2.0 ** -8 * np.abs(whole).max()
    np.testing.assert_allclose(looped[rows], whole[rows], atol=atol, rtol=0)
    assert np.isfinite(looped).all()
    if mask is None:
        assert counted == {} and counted_too == {}
        return
    stats, stats_too = counted["moe_stats"], counted_too["moe_stats"]
    assert set(stats) == {"counts"} and set(stats_too) == {"counts", "streamed"}
    np.testing.assert_array_equal(stats["counts"][0], stats_too["counts"][0])
    reached = np.asarray(stats["counts"][0])[1:1 + shape["num_experts_here"]]
    assert int(stats_too["streamed"][0]) == (reached > 0).sum() == touched


@pytest.mark.parametrize("case", [c for c in CASES if CASES[c][2] is None])
def test_the_loop_multiplies_what_the_routing_touched(monkeypatch, case):
    """With nothing counted the loop still runs over the experts that some
    token chose, every token's: the trip count `touched_experts` returns is
    the routing's, whatever the layer sows."""
    shape, choices, _mask, touched = CASES[case]
    seen = []
    inner = moe.touched_experts

    def watched(x, w_in, w_out, weights, marked, dtype):
        out, trips = inner(x, w_in, w_out, weights, marked, dtype)
        seen.append(trips)
        return out, trips

    monkeypatch.setattr(moe, "LOOP_SKIPS_BYTES", 0)
    monkeypatch.setattr(moe, "touched_experts", watched)
    layer, variables, x = _layer(shape, choices, "float32")
    del seen[:]  # `init` ran the layer too, under its own router
    layer.apply(variables, x)
    assert [int(trips) for trips in seen] == [touched]


# -- the rule, over the benchmark's configurations ----------------------------

# configuration: (its step's slots -> form, then the prefill buckets that
# its traffic's prompts take -> form). dsv32's traffic sends 1024-2048
# token prompts and LongCat's 64-1536: the buckets below those are the
# engine's own grid, which a shorter prompt would take.
FORMS = {
    "granite4h_small_serve_1chip": [
        (32, "one")] + [(b, "one") for b in (32, 64, 128, 256, 512, 1024)],
    "dots3_note_serve_1chip": [
        (64, "one")] + [(b, "one") for b in (512, 1024, 2048, 4096)],
    "longcat_flash_serve_1chip": [
        (64, "loop"), (128, "one"), (256, "one"), (512, "one"), (1024, "one"),
        (2048, "one")],
    "laguna_xs2_serve_1chip": [
        (64, "one"), (128, "one"), (256, "one"), (512, "one"),
        (1024, "sorted"), (2048, "sorted"), (4096, "sorted")],
    "deepseek_v32_serve_1chip": [
        (32, "loop"), (64, "one"), (128, "one"), (1024, "one"), (2048, "one")],
}


@pytest.mark.parametrize("name,tokens,form", [
    (name, tokens, form) for name, rows in FORMS.items()
    for tokens, form in rows])
def test_the_form_comes_from_the_shapes_of_each_configuration(
        monkeypatch, name, tokens, form):
    """What `DroplessMoE` does at the benchmark's own sizes (traced, nothing
    run): the 32-slot step of DeepSeek-V3.2's share and the 64-slot step and
    64-token prefill of LongCat's loop over the touched experts; granite's,
    dots3's and Laguna's steps and every prefill of 128 tokens or more are
    what they were."""
    with open(os.path.join(CONFIGS, name + ".json")) as fh:
        sizes = json.load(fh)
    assert tokens == sizes["serving"]["max_slots"] \
        or tokens in DEFAULT_PROMPT_BUCKETS
    config = agent.build_model(sizes).config
    took = []

    def stand_in(form, outputs):
        def record(x, *_args):
            took.append(form)
            out = jnp.zeros(x.shape, jnp.float32)
            return (out, jnp.int32(0)) if outputs == 2 else out
        return record

    monkeypatch.setattr(moe, "touched_experts", stand_in("loop", 2))
    monkeypatch.setattr(moe, "sorted_experts", stand_in("sorted", 1))
    layer = moe.DroplessMoE(
        num_experts=config.num_experts,
        num_experts_here=config.num_experts_here,
        top_k=config.experts_per_token, d_expert=config.d_expert,
        num_zero_experts=getattr(config, "num_zero_experts", 0),
        scoring="softmax_all", dtype=config.dtype,
        param_dtype=config.param_dtype)
    x = jax.ShapeDtypeStruct((tokens, config.d_model), config.dtype)
    jax.eval_shape(lambda x: layer.init_with_output(jax.random.key(0), x)[0], x)
    assert (took or ["one"]) == [form]


def test_the_rule_is_the_bytes_an_iteration_is_expected_to_skip():
    """`u / (1 - u)` of an expert an iteration, `u` the chance that no token
    chooses an expert: 50 MB at DeepSeek-V3.2's step, 43 MB at LongCat's,
    7 MB at dots3's, 0.9 MB at Laguna's, 0.2 MB at granite's, against a
    threshold of 16 MB; and never where the tokens are sorted."""
    def skipped(tokens, top_k, outputs, expert_bytes):
        u = (1 - top_k / outputs) ** tokens
        return u / (1 - u) * expert_bytes / 1e6

    shapes = {  # held, tokens, top_k, outputs, bytes of one expert
        "dsv32": (16, 32, 8, 256, 3 * 7168 * 2048 * 2),
        "longcat": (16, 64, 12, 768, 3 * 6144 * 2048 * 2),
        "dots3": (16, 64, 8, 256, 3 * 5120 * 1536 * 2),
        "laguna": (256, 64, 8, 256, 3 * 2048 * 512 * 2),
        "granite": (18, 32, 10, 72, 3 * 4096 * 768 * 2),
    }
    megabytes = {name: skipped(*about[1:]) for name, about in shapes.items()}
    assert [round(megabytes[name], 1) for name in shapes] == \
        [50.0, 43.4, 7.1, 0.9, 0.2]
    threshold = moe.LOOP_SKIPS_BYTES / 1e6
    for name, about in shapes.items():
        assert moe.loops_over_touched(*about) == (megabytes[name] > threshold)
        # room both ways: no configuration within a factor of two of it
        assert not threshold / 2 < megabytes[name] < threshold * 2
    assert not moe.loops_over_touched(256, 1024, 8, 256, 2 ** 40)


# -- through the engine and the scheduler -------------------------------------

MODELS = {
    "tiny_dsv32": (deepseek_v32, {"index_chunk": 32}),
    "tiny_longcat": (longcat_flash, {}),
}


@pytest.fixture(scope="module", params=list(MODELS))
def looped(request):
    """A tiny model with its own engine and jitted step, compiled while the
    threshold is nothing, so that every call of its expert layers (the
    step, the prefill buckets) takes the loop."""
    reference, more = MODELS[request.param]
    with open(os.path.join(DATA, request.param + ".json")) as fh:
        sizes = json.load(fh)
    sizes["model"] = {"dtype": jnp.float32, "param_dtype": jnp.float32,
                      "query_block": 16, "row_multiple": 8, **more}
    model = agent.build_model(sizes)
    variables = agent.program_variables(model, sizes, SEED)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "LOOP_SKIPS_BYTES", 0)
        yield {
            "sizes": sizes, "model": model, "variables": variables,
            "weights": weights.make(sizes, SEED), "reference": reference,
            "engine": DecodeEngine(model, prompt_buckets=BUCKETS),
            "step": jax.jit(build_paged_state_step_fn(
                model, BLOCK, 0.0, None, None, with_logits=True)),
        }


def _reference_logits(looped, tokens, rows):
    padded = np.zeros(-(-len(tokens) // 128) * 128, np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(looped["reference"].logits(
        looped["weights"], jnp.asarray(padded), looped["sizes"],
        jnp.asarray(rows)))


def test_the_looped_step_matches_the_reference_and_counts_its_trips(looped):
    """Prefill, replay and decode of two slots of three (the third free)
    with the loop in every expert layer: the step's logits are the
    reference's, `counts` is one wider, and its last entry is the number of
    held experts that the two active slots' tokens reached."""
    engine, variables = looped["engine"], looped["variables"]
    config = looped["model"].config
    slots, per_slot = 3, config.max_seq_len // BLOCK
    pool = engine.make_paged_pool(variables, slots * per_slot + 1, BLOCK)
    state = engine.make_slot_state(variables, slots)
    tables = np.zeros((slots, per_slot), np.int32)
    lengths = np.zeros((slots,), np.int32)
    rngs = np.zeros((slots, 2), np.uint32)
    rng = np.random.default_rng(5)
    sequences = {0: rng.integers(0, 256, 45), 2: rng.integers(0, 256, 30)}
    for slot, prompt_len in ((0, 26), (2, 11)):
        blocks = 1 + slot * per_slot + np.arange(per_slot)
        pool, _row, _bucket, kept = admit_prefill(
            engine, variables, pool, sequences[slot][:prompt_len], blocks,
            BLOCK, engine.ceiling_prefill(variables))
        tables[slot], lengths[slot] = blocks, kept
    starts = {slot: int(lengths[slot]) for slot in sequences}
    got = {slot: [] for slot in sequences}
    held = config.num_experts_here
    zero = bool(getattr(config, "num_zero_experts", 0))
    for t in range(12):
        tokens = np.zeros((slots,), np.int32)
        for slot, sequence in sequences.items():
            tokens[slot] = sequence[starts[slot] + t]
        pool, state, _emitted, rngs, counts, _reads, logits = looped["step"](
            variables, pool, state, jnp.asarray(tables), jnp.asarray(lengths),
            *all_forced(tokens, rngs), jnp.zeros((slots,), bool))
        counts, logits = np.asarray(counts), np.asarray(logits)
        assert counts.shape[1] == 1 + held + zero + 1
        np.testing.assert_array_equal(
            counts[:, -1], (counts[:, 1:1 + held] > 0).sum(axis=1))
        assert (counts[:, 0] == 2 * config.experts_per_token).all()
        for slot in sequences:
            got[slot].append(logits[slot])
            lengths[slot] += 1
    for slot, sequence in sequences.items():
        start = starts[slot]
        want = _reference_logits(
            looped, sequence[:start + 12], np.arange(start, start + 12))
        np.testing.assert_allclose(
            np.stack(got[slot]), want, atol=TOLERANCE, rtol=0)


def test_the_scheduler_tallies_streamed_beside_touched(looped):
    """Three requests through two slots (so some steps run with a slot
    free): the tokens are the reference's first choices, and `/stats`
    counts as streamed exactly the experts it counts as touched."""
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, 256, n) for n in (9, 33, 17)]
    scheduler = SlotScheduler(
        looped["engine"], looped["variables"], block_size=BLOCK, max_slots=2)
    responses = [scheduler.submit(
        list(map(int, p)), SamplingParams(max_new_tokens=10)) for p in prompts]
    for _ in range(2000):
        if all(r.done for r in responses):
            break
        scheduler.tick()
    for prompt, response in zip(prompts, responses):
        tokens = np.asarray(response.result(timeout=1))
        sequence = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
        want = _reference_logits(
            looped, sequence, np.arange(len(prompt) - 1, len(sequence)))
        chosen = want[np.arange(len(tokens)), tokens]
        assert (want.max(-1) - chosen).max() <= TOLERANCE
    stats = scheduler.stats()
    scheduler.close()
    held = looped["model"].config.num_experts_here
    assert 0 < stats["moe_experts_streamed"] == stats["moe_experts_touched"] \
        < held * stats["moe_layer_steps"]
    assert stats["moe_experts_streamed_per_layer_step"] == \
        stats["moe_experts_touched_per_layer_step"]
    assert len(stats["moe_tokens_by_expert"]) == held
    if getattr(looped["model"].config, "num_zero_experts", 0):
        assert stats["moe_assignments_zero"] > 0
