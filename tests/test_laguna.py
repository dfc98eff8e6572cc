"""The `laguna` decoder (models/laguna.py: Laguna-XS.2's shape) against its
plain reference (cellbench/reference/laguna.py) at a tiny size on the CPU:
grouped-query attention whose head count, window, rotary recipe and cache
kind differ by layer, a per-head output gate, an expert layer that holds all
of its experts. Both sides float32, so they differ by sums in another order.
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import agent, weights
from cellbench.reference import laguna as reference
from tf_yarn_tpu.models import laguna, moe
from tf_yarn_tpu.models.decode_engine import (
    DecodeEngine,
    _decode_cache_aval,
    all_forced,
    build_paged_state_step_fn,
    build_paged_step_fn,
    cache_layout,
    paged_pool_avals,
)
from tf_yarn_tpu.models.transformer import ATTENTION_READS, PagedContext
from tf_yarn_tpu.serving.request import SamplingParams
from tf_yarn_tpu.serving.scheduler import SlotScheduler

from tests.fakes import admit_prefill, assert_pipelined_equals_settled

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(ROOT, "cellbench", "tests", "data")
# float32 both sides, sums in another order, through a router whose logits
# spread by 3 (a gate moves by three times a logit's rounding)
TOLERANCE = 2e-4
SEED = 3_000_000_042
BLOCK = 8
BUCKETS = (8, 16, 32)
WINDOW, RING, CONTEXT = 8, 16, 128


def _sizes(**model):
    with open(os.path.join(DATA, "tiny_laguna.json")) as fh:
        sizes = json.load(fh)
    sizes["model"] = {"dtype": jnp.float32, "param_dtype": jnp.float32,
                      "query_block": 16, **model}
    return sizes


@pytest.fixture(scope="module")
def tiny():
    sizes = _sizes()
    model = agent.build_model(sizes)
    config = model.config
    assert (config.window, config.max_seq_len, config.heads,
            config.n_kv_heads, config.num_experts, config.experts_per_token
            ) == (WINDOW, CONTEXT, (4, 8, 8, 8, 4), 2, 16, 2)
    # One engine and one jitted step for the whole file.
    return {
        "sizes": sizes, "model": model,
        "variables": agent.program_variables(model, sizes, SEED),
        "weights": weights.make(sizes, SEED),
        "forward": jax.jit(model.apply),
        "engine": DecodeEngine(model, prompt_buckets=BUCKETS),
        "step": jax.jit(build_paged_state_step_fn(
            model, BLOCK, 0.0, None, None, with_logits=True)),
    }


def _reference_logits(tiny, tokens, rows, **wrong):
    padded = np.zeros(-(-len(tokens) // 128) * 128, np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(reference.logits(
        tiny["weights"], jnp.asarray(padded), tiny["sizes"],
        jnp.asarray(rows), **wrong))


# Below, at and above the window of 8 and the ring of 16; 77 is not a
# multiple of the query block of 16.
@pytest.mark.parametrize("length", [7, 8, 9, 16, 17, 33, 77, 128])
def test_full_forward_matches_reference(tiny, length):
    tokens = np.random.default_rng(length).integers(0, 256, length)
    got = tiny["forward"](tiny["variables"], jnp.asarray(tokens)[None])[0]
    want = _reference_logits(tiny, tokens, np.arange(length))
    np.testing.assert_allclose(np.asarray(got), want, atol=TOLERANCE, rtol=0)


# The prompt ends inside the first block of 8 queries, at its end, inside a
# later block, and at the call's end (21 rows: three blocks, the last short).
@pytest.mark.parametrize("prompt_len", [1, 8, 13, 21])
@pytest.mark.parametrize("window", [0, 5])
def test_query_blocks_past_the_prompt_are_not_computed(prompt_len, window):
    """Told where the prompt ends, a prefill's attention over its own
    tokens gives the prompt's rows what it gives them untold, and leaves
    the blocks that hold only pad zero."""
    from tf_yarn_tpu.models.transformer import own_token_attention

    rng = np.random.default_rng(prompt_len)
    s, block = 21, 8
    q = jnp.asarray(rng.normal(size=(2, s, 4, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, s, 2, 16)), jnp.float32)
            for _ in range(2))
    untold = np.asarray(own_token_attention(
        q, k, v, window=window, query_block=block))
    told = np.asarray(jax.jit(lambda n: own_token_attention(
        q, k, v, window=window, query_block=block, prompt_len=n))(prompt_len))
    computed = min(s, -(-prompt_len // block) * block)
    # outputs of magnitude 3, float32 sums in another order
    np.testing.assert_allclose(told[:, :computed], untold[:, :computed],
                               atol=1e-5, rtol=0)
    assert not told[:, computed:].any() and untold[:, -1].any()


def test_yarn_ramp_is_the_published_one():
    """low / high = 5 / 16 at the published values, in the program and in
    the reference; both give the same frequencies."""
    published = laguna.LagunaConfig()
    assert published.full_rotary.correction_range() == (5, 16)
    assert reference.yarn_range(64, 5e5, 4096, 64, 1) == (5, 16)
    with open(os.path.join(ROOT, "cellbench", "configs",
                           "laguna_xs2_serve_1chip.json")) as fh:
        sizes = json.load(fh)
    for kind, recipe in (("full_attention", published.full_rotary),
                         ("sliding_attention", published.sliding_rotary)):
        n, freqs, factor = reference.rotary_recipe(sizes, kind)
        assert (n, factor) == (recipe.rotary_dim, recipe.attention_factor)
        np.testing.assert_allclose(recipe.inv_freq(), freqs, rtol=1e-6)
    own = 5e5 ** (-np.arange(32) / 32.0)
    got = published.full_rotary.inv_freq()
    np.testing.assert_allclose(got[:6], own[:6], rtol=1e-6)       # kept
    np.testing.assert_allclose(got[16:], own[16:] / 64, rtol=1e-6)  # a 64th
    assert (got[6:16] < own[6:16]).all() and (got[6:16] > own[6:16] / 64).all()
    assert published.sliding_rotary.inv_freq().shape == (64,)


class _Grid:
    """The engine's paged pool and ring slots, driven by hand the way the
    scheduler drives them, with the step's logits read out."""

    def __init__(self, tiny, slots=3):
        self.tiny, self.slots = tiny, slots
        self.engine = tiny["engine"]
        variables = tiny["variables"]
        self.per_slot = CONTEXT // BLOCK
        self.pool = self.engine.make_paged_pool(
            variables, slots * self.per_slot + 1, BLOCK)
        self.state = self.engine.make_slot_state(variables, slots)
        self.tables = np.zeros((slots, self.per_slot), np.int32)
        self.lengths = np.zeros((slots,), np.int32)
        self.rngs = np.zeros((slots, 2), np.uint32)
        self.step = tiny["step"]

    def admit(self, slot, prompt):
        variables = self.tiny["variables"]
        blocks = 1 + slot * self.per_slot + np.arange(self.per_slot)
        # The ceiling rule: the prefill is told where the prompt ends in
        # its bucket and writes the rings from the rows that end there.
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
        tokens = np.zeros((self.slots,), np.int32)
        for slot, token in tokens_by_slot.items():
            tokens[slot] = token
        self.pool, self.state, _emitted, self.rngs, counts, reads, logits = \
            self.step(
                self.tiny["variables"], self.pool, self.state,
                jnp.asarray(self.tables), jnp.asarray(self.lengths),
                *all_forced(tokens, self.rngs),
                jnp.zeros((self.slots,), bool))
        logits, counts, reads = (np.asarray(v) for v in (logits, counts, reads))
        for slot in tokens_by_slot:
            self.lengths[slot] += 1
        return logits, counts, dict(zip(ATTENTION_READS, reads.tolist()))

    def run(self, slot, sequence, prompt_len):
        prefill = self.admit(slot, sequence[:prompt_len])
        rows = [self.advance({slot: sequence[t]})[0][slot]
                for t in range(prefill, len(sequence))]
        return prefill, np.stack(rows)


def _kept(prompt_len):
    """The rows an admission keeps: all of the prompt but its last token in
    the bucket above (the ceiling rule), or, past the largest bucket, the
    bucket below, whole."""
    kept = prompt_len - 1
    return kept if 0 < kept <= BUCKETS[-1] else \
        max([b for b in BUCKETS if b <= kept], default=0)


# Prompt lengths on, just over and just under a prefill bucket (8, 16, 32),
# the window (8) and the ring (16); 1 prefills nothing and starts from zeroed
# rings; 41 has no bucket above it and keeps the one below. Each decodes 19
# more: past the window and, from 9 on, past a turn of the ring.
@pytest.mark.parametrize("prompt_len", [1, 5, 8, 9, 10, 16, 17, 18, 31, 32,
                                        33, 41])
def test_prefill_replay_decode_match_reference(tiny, prompt_len):
    """Bucketed prefill into the pool and the slot's rings, then replay and
    decode a token a step through the paged step (the pool read on the full
    layers, the ring on the sliding ones), against ONE full forward of the
    reference over the same tokens."""
    sequence = np.random.default_rng(prompt_len).integers(
        0, 256, prompt_len + 19)
    grid = _Grid(tiny)
    prefill, got = grid.run(1, sequence, prompt_len)
    assert prefill == _kept(prompt_len)
    assert prefill == {1: 0, 41: 32}.get(prompt_len, prompt_len - 1)
    want = _reference_logits(tiny, sequence, np.arange(prefill, len(sequence)))
    np.testing.assert_allclose(got, want, atol=TOLERANCE, rtol=0)


def _rings(row):
    """name -> the ring leaves of a prefill's row cache, in layer order."""
    found = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(row):
        name = getattr(path[-1], "key", str(path[-1]))
        if name in ("window_key", "window_value"):
            found.setdefault(name, []).append(np.asarray(leaf))
    return found


# Rows kept below, at and above the ring of 16, in a bucket under the ring
# (8), the ring's size (16) and over it (32), with and without pad.
@pytest.mark.parametrize("kept,bucket", [
    (5, 8), (8, 8), (9, 16), (15, 16), (16, 16), (17, 32), (24, 32), (32, 32)])
def test_a_padded_prefill_leaves_the_ring_of_the_prompt_alone(
        tiny, kept, bucket):
    """The rings after a prefill of `kept` tokens padded to their bucket
    are, row for row, the rings after a prefill of exactly `kept` tokens
    (an engine whose one bucket is `kept`): position p at row p % 16 for
    the last 16 positions below `kept`, zero where there is none, and
    nothing that changes with what the pad holds."""
    engine, variables = tiny["engine"], tiny["variables"]
    prompt = np.random.default_rng(kept).integers(1, 256, kept)
    assert engine.slot_prefill_len(kept + 1, True) == (bucket, kept)
    padded = {}
    for pad in (0, 255):
        tokens = np.full((1, bucket), pad, np.int32)
        tokens[0, :kept] = prompt
        padded[pad] = _rings(engine.prefill(variables, tokens, kept)[0])
    exact = _rings(DecodeEngine(tiny["model"], prompt_buckets=(kept,)).prefill(
        variables, prompt[None].astype(np.int32))[0])
    assert sorted(exact) == ["window_key", "window_value"]
    held = np.zeros((RING,), bool)
    held[np.arange(max(0, kept - RING), kept) % RING] = True
    for name, layers in exact.items():
        assert len(layers) == 3
        for layer, want in enumerate(layers):
            assert want.shape == (1, RING, 2, 16)
            np.testing.assert_array_equal(
                padded[0][name][layer], padded[255][name][layer])
            np.testing.assert_allclose(
                padded[0][name][layer], want, atol=1e-5, rtol=0)
            rows = np.abs(padded[0][name][layer][0]).sum(axis=(1, 2)) > 0
            np.testing.assert_array_equal(rows, held)


class _FloorEngine(DecodeEngine):
    """The parent's rule for a model with rings: the bucket below, whole."""

    def ceiling_prefill(self, params):
        return False


def test_ceiling_streams_equal_floor_streams(tiny):
    """Greedy streams of requests admitted under the ceiling rule (one
    token replayed) equal those of the same requests under the floor rule
    (the bucket below, the rest replayed): 41 has no bucket above it and
    takes the floor under both."""
    rng = np.random.default_rng(43)
    prompts = [rng.integers(0, 256, n) for n in (5, 10, 17, 18, 31, 33, 41, 24)]
    streams = {}
    for rule, engine in (
            ("ceiling", tiny["engine"]),
            ("floor", _FloorEngine(tiny["model"], prompt_buckets=BUCKETS))):
        scheduler = _scheduler(tiny, engine, max_slots=3)
        streams[rule] = _serve(scheduler, prompts, new_tokens=12)
        stats = scheduler.stats()
        scheduler.close()
        assert stats["prefills_ceiling"] == (7 if rule == "ceiling" else 0)
        # (the floor rule finds no bucket under 5's four rows: no prefill)
        assert stats["prefills_floor"] == (1 if rule == "ceiling" else 7)
        # one token a request replays, but for 41's 9; the floor replays
        # every token past the bucket below
        assert stats["prefill_tokens"] == (
            7 + 9 if rule == "ceiling" else 5 + 2 + 1 + 2 + 15 + 1 + 9 + 8)
    assert streams["ceiling"] == streams["floor"]


def test_a_sequence_far_past_window_and_ring_reads_nothing_stale(tiny):
    """120 tokens: the ring of 16 rows turns over seven times, and a slot
    that held a longer request before holds stale rows everywhere."""
    rng = np.random.default_rng(23)
    before, sequence = rng.integers(0, 256, 126), rng.integers(0, 256, 120)
    grid = _Grid(tiny)
    grid.run(1, before, 40)
    grid.retire(1)
    prefill, got = grid.run(1, sequence, 33)
    want = _reference_logits(tiny, sequence, np.arange(prefill, len(sequence)))
    np.testing.assert_allclose(got, want, atol=TOLERANCE, rtol=0)


def test_slots_step_together_and_a_reused_slot_starts_clean(tiny):
    """Two requests in two slots at different lengths, stepped together,
    equal the reference each; then a third through a slot that held another.
    The step's counters say what it read, pool and rings apart."""
    rng = np.random.default_rng(5)
    first, second, third = (rng.integers(0, 256, n) for n in (60, 31, 14))
    grid = _Grid(tiny)
    p1, p2 = grid.admit(0, first[:40]), grid.admit(2, second[:11])
    got1, got2 = [], []
    for t in range(20):
        logits, counts, reads = grid.advance(
            {0: first[p1 + t], 2: second[p2 + t]})
        got1.append(logits[0])
        got2.append(logits[2])
    # two active slots, four expert layers (layer 0 is dense), top 2 of 16
    assert counts.shape == (4, 1 + 16) and (counts[:, 0] == 4).all()
    assert (counts[:, 1:].sum(axis=1) == 4).all()
    # the last step: slot 0 at 51 + 1 live rows (39 rows have no bucket
    # above: 32 kept), slot 2 at 29 + 1 (10 kept of 16); two full
    # layers read the whole table of 128 (the plain read, off the TPU); three
    # window layers read their ring of 16, 8 rows of it in the window
    assert reads == {
        "pool_live": 2 * (52 + 30), "pool_read": 2 * 2 * CONTEXT,
        "window_live": 3 * (8 + 8), "window_read": 3 * 2 * RING}
    for got, sequence, start in ((got1, first, p1), (got2, second, p2)):
        want = _reference_logits(tiny, sequence[:start + 20],
                                 np.arange(start, start + 20))
        np.testing.assert_allclose(np.stack(got), want, atol=TOLERANCE, rtol=0)
    grid.retire(0)
    # 5 rows kept of a bucket of 8: the ring's other rows are written zero;
    # then nothing prefilled: zero rings
    for prompt_len in (6, 1):
        kept, reused = grid.run(0, third, prompt_len)
        _, alone = _Grid(tiny).run(0, third, prompt_len)
        np.testing.assert_allclose(reused, alone, atol=1e-6, rtol=0)
        want = _reference_logits(tiny, third, np.arange(kept, len(third)))
        np.testing.assert_allclose(reused, want, atol=TOLERANCE, rtol=0)
        grid.retire(0)


def _swapped(sizes):
    pair = json.loads(json.dumps(sizes["rope_parameters"]))
    pair["full_attention"]["rope_theta"], pair["sliding_attention"]["rope_theta"] = \
        pair["sliding_attention"]["rope_theta"], pair["full_attention"]["rope_theta"]
    told = dict(sizes, rope_parameters=pair)
    return {kind: reference.rotary_recipe(told, kind)
            for kind in ("full_attention", "sliding_attention")}


def _no_factor(sizes):
    n, freqs, _ = reference.rotary_recipe(sizes, "full_attention")
    return {"full_attention": (n, freqs, 1.0)}


@pytest.mark.parametrize("name,wrong", [
    ("window 9", lambda sizes: {"window": 9}),
    ("no gate", lambda sizes: {"gated": False}),
    ("no attention factor", lambda sizes: {"recipes": _no_factor(sizes)}),
    ("the two thetas swapped", lambda sizes: {"recipes": _swapped(sizes)}),
    ("routed_scale 1", lambda sizes: {"routed_scale": 1.0}),
])
def test_a_reference_with_one_thing_wrong_fails_the_comparison(tiny, name, wrong):
    """The comparison of `test_prefill_replay_decode_match_reference` tells
    the model from one with a window one wider, without its gate, without
    YaRN's attention factor, with the two kinds' thetas swapped, or with the
    routed gates unscaled."""
    sequence = np.random.default_rng(17).integers(0, 256, 17 + 19)
    prefill, got = _Grid(tiny).run(1, sequence, 17)
    rows = np.arange(prefill, len(sequence))
    right = _reference_logits(tiny, sequence, rows)
    np.testing.assert_allclose(got, right, atol=TOLERANCE, rtol=0)
    other = _reference_logits(tiny, sequence, rows, **wrong(tiny["sizes"]))
    assert np.abs(got - other).max() > 50 * TOLERANCE, name


# -- the expert layer ---------------------------------------------------------


@pytest.mark.parametrize("held,tokens,top_k,sorts", [
    (18, 1024, 10, False),      # granite's share over its largest bucket
    (16, 2048, 8, False),       # dots3's
    (16, 4096, 8, False),       # dots3's largest program (no cell runs it)
    (16, 2048, 12, False),      # LongCat's
    (256, 64, 8, False),        # every expert of a Laguna layer, a decode step
    (256, 512, 8, False),       # 3.6 times the sorted rows: one product
    (256, 1024, 8, True),       # a prefill with every expert held: 6.4 times
    (256, 2048, 8, True),
])
def test_the_expert_layers_form_comes_from_the_shapes(held, tokens, top_k, sorts):
    assert moe.sorts_by_expert(held, tokens, top_k) is sorts


@pytest.mark.parametrize("held,offset", [(16, 0), (8, 4)])
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid", "softmax_all"])
def test_sorted_experts_equal_every_expert_over_every_token(
        monkeypatch, scoring, held, offset):
    """The form a prefill over many held experts takes (tokens sorted by
    expert, each computed by its own experts alone) gives what the one
    product over all held experts gives, on the three routings, with every
    expert held and with a share (assignments to experts not held fall
    out), and counts the same. 300 tokens x 3 choices over tiles of 128
    rows: experts with more than a tile, with a part of one, and padding."""
    layer = moe.DroplessMoE(
        num_experts=16, num_experts_here=held, expert_offset=offset, top_k=3,
        d_expert=32, d_shared=32, scoring=scoring, routed_scale=2.5,
        num_zero_experts=4 if scoring == "softmax_all" else 0,
        dtype=jnp.float32, param_dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(300, 64)), jnp.float32)
    variables = layer.init(jax.random.key(3), x)
    mask = jnp.ones((300,), bool)
    assert not moe.sorts_by_expert(held, 300, 3)
    whole, counted = layer.apply(variables, x, mask, mutable=["moe_stats"])
    monkeypatch.setattr(moe, "ROWS_OVER", 0)
    assert moe.sorts_by_expert(held, 300, 3)
    sorted_, counted_too = layer.apply(variables, x, mask, mutable=["moe_stats"])
    np.testing.assert_allclose(np.asarray(sorted_), np.asarray(whole),
                               atol=5e-6, rtol=0)
    np.testing.assert_array_equal(
        *(np.asarray(jax.tree_util.tree_leaves(c)[0])
          for c in (counted, counted_too)))


def test_the_softmax_routing_applies_its_scale():
    """`routed_scale` on the `softmax` scoring (it was applied on the other
    two only): the routed part times 2.5, the shared expert once."""
    about = dict(num_experts=8, num_experts_here=8, top_k=2, d_expert=16,
                 dtype=jnp.float32, param_dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(12, 32)), jnp.float32)
    plain = moe.DroplessMoE(**about)
    variables = plain.init(jax.random.key(4), x)
    routed = plain.apply(variables, x)
    scaled = moe.DroplessMoE(routed_scale=2.5, **about).apply(variables, x)
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(routed),
                               atol=1e-5, rtol=0)


# -- `Attention` as it was ------------------------------------------------------


@pytest.mark.parametrize("case", ["tiny_serve.float32", "tiny_serve.bfloat16",
                                  "tiny_granite.float32",
                                  "tiny_granite.bfloat16"])
def test_attention_defaults_are_bit_for_bit_the_parent(case):
    """`transformer.Attention` with none of its new properties named: the
    plain forward, a bucketed prefill (last logits and every cache leaf) and
    one paged step after it (logits and every pool leaf) of Mistral's and
    granite's tiny configurations, bit for bit what the parent commit gave
    (digests recorded there, tests/fixtures/attention_as_before.json, with a
    probe that tells another machine's arithmetic apart)."""
    with open(os.path.join(ROOT, "tests", "fixtures",
                           "attention_as_before.json")) as fh:
        before = json.load(fh)
    probe = jax.jit(lambda x: jax.nn.softmax(jnp.tanh(x @ x.T) @ x, -1))(
        jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)), jnp.float32))
    if hashlib.sha256(np.asarray(probe).tobytes()).hexdigest() != before["probe"]:
        pytest.skip("another machine's float arithmetic: digests do not carry")

    def sha(*arrays):
        digest = hashlib.sha256()
        for value in arrays:
            digest.update(np.asarray(
                jnp.asarray(value).astype(jnp.float32)).tobytes())
        return digest.hexdigest()

    name, dtype = case.split(".")
    with open(os.path.join(DATA, name + ".json")) as fh:
        sizes = json.load(fh)
    sizes["model"] = dict(sizes.get("model", {}), dtype=dtype, param_dtype=dtype)
    model = agent.build_model(sizes)
    variables = agent.program_variables(model, sizes, 42)
    tokens = jnp.asarray(
        np.random.default_rng(42).integers(0, 256, (2, 40)), jnp.int32)
    assert sha(jax.jit(model.apply)(variables, tokens)) == before[case]["forward"]
    engine = DecodeEngine(model, prompt_buckets=BUCKETS)
    row, logits = engine.prefill(variables, np.asarray(tokens[:1, :32]))
    assert sha(logits, *jax.tree_util.tree_leaves(row)) == before[case]["prefill"]
    slots, per = 2, model.config.max_seq_len // BLOCK
    pool = engine.make_paged_pool(variables, slots * per + 1, BLOCK)
    pool = engine.pack_prefill(
        pool, (1 + np.arange(4)).astype(np.int32), row, 32, BLOCK)
    tables = np.zeros((slots, per), np.int32)
    tables[0] = 1 + np.arange(per)
    host = (jnp.asarray(tables), jnp.asarray(np.array([32, 0], np.int32)),
            *all_forced(np.array([7, 0], np.int32),
                        np.zeros((slots, 2), np.uint32)),
            jnp.zeros((slots,), bool))
    if engine.counted_step(variables):
        state = engine.write_slot_state(
            engine.make_slot_state(variables, slots), 0, row)
        got = jax.jit(build_paged_state_step_fn(
            model, BLOCK, 0.0, None, None, with_logits=True))(
            variables, pool, state, *host)
    else:
        got = jax.jit(build_paged_step_fn(
            model, BLOCK, 0.0, None, None, with_logits=True))(
            variables, pool, *host)
    assert sha(got[-1][0], *jax.tree_util.tree_leaves(got[0])) == \
        before[case]["step"]


def test_float32_where_stated_under_bfloat16():
    """At the serving dtype the matrices, the pool and the rings are
    bfloat16; norm scales stay float32; `hold_params` finds nothing to
    narrow."""
    sizes = _sizes(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    model = agent.build_model(sizes)
    variables = agent.program_variables(model, sizes, SEED)
    for path, leaf in jax.tree_util.tree_leaves_with_path(variables):
        names = [getattr(k, "key", str(k)) for k in path]
        assert leaf.dtype == (jnp.float32 if names[-1] == "scale"
                              else jnp.bfloat16), names
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            _decode_cache_aval(model, variables)):
        name = getattr(path[-1], "key", str(path[-1]))
        assert leaf.dtype == (jnp.int32 if name == "cache_index"
                              else jnp.bfloat16), name
    logits = jax.jit(model.apply)(variables, jnp.zeros((1, 9), jnp.int32))
    assert logits.dtype == jnp.float32 and bool(jnp.isfinite(logits).all())
    engine = DecodeEngine(model, prompt_buckets=BUCKETS)
    engine.hold_params(variables)
    assert engine.stats["params_narrowed"] == 0


# -- the leaves as the engine sees them --------------------------------------


def test_leaves_are_declared_and_a_ring_does_not_grow_with_context(tiny):
    model, variables = tiny["model"], tiny["variables"]
    row = _decode_cache_aval(model, variables)
    layout = cache_layout(model, row)
    flat = lambda tree: jax.tree_util.tree_leaves(  # noqa: E731
        tree, is_leaf=lambda x: x is None)
    pool = paged_pool_avals(model, row, 9, BLOCK)
    seen = {}
    for lay, aval, pooled in zip(flat(layout), flat(row), flat(pool)):
        seen.setdefault(lay.name, (lay.kind, aval.shape))
        if lay.kind == "paged":
            assert pooled.shape == (1, 9, BLOCK) + aval.shape[-2:]
        else:
            assert pooled is None
    # a ring has a head axis: [1, rows, KV heads, head size]
    assert seen == {
        "cached_key": ("paged", (1, CONTEXT, 2, 16)),
        "cached_value": ("paged", (1, CONTEXT, 2, 16)),
        "window_key": ("ring", (1, RING, 2, 16)),
        "window_value": ("ring", (1, RING, 2, 16)),
        "cache_index": ("index", ()),
    }
    engine = tiny["engine"]
    assert engine.slot_state_leaves(variables) == ("window_key", "window_value")
    # every leaf held once a slot is a ring, written where the prompt ends
    assert engine.ceiling_prefill(variables) is True
    assert engine.counted_step(variables) is True

    def by_kind(context):
        sizes = dict(_sizes(), serving={"context": context, "max_slots": 4})
        engine = DecodeEngine(agent.build_model(sizes), prompt_buckets=BUCKETS)
        return engine.cache_bytes_by_kind(
            variables,
            engine.make_paged_pool(variables, 4 * context // BLOCK + 1, BLOCK),
            engine.make_slot_state(variables, 4))

    short, long = by_kind(CONTEXT), by_kind(4 * CONTEXT)
    # three sliding layers x K and V x 4 slots x 16 rows x 2 x 16, float32
    assert short["ring"] == long["ring"] == 3 * 2 * 4 * RING * 32 * 4
    assert long["paged"] > 3.9 * short["paged"]
    assert set(short) == {"paged", "ring"}


# -- the scheduler ------------------------------------------------------------


def _scheduler(tiny, engine=None, **kwargs):
    kwargs.setdefault("block_size", BLOCK)
    return SlotScheduler(engine or tiny["engine"], tiny["variables"], **kwargs)


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
    """Five requests through two slots give what the reference puts first;
    the counters say what happened: a ring write an admission, the prefix
    cache standing aside each time, the cache bytes by kind, the reads of
    the pool and of the rings apart."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, n) for n in (9, 40, 5, 33, 17)]
    together = _scheduler(tiny, max_slots=2)
    served = _serve(together, prompts, new_tokens=30)
    for prompt, tokens in zip(prompts, served):
        sequence = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
        want = _reference_logits(
            tiny, sequence, np.arange(len(prompt) - 1, len(sequence)))
        gaps = want.max(-1) - want[np.arange(len(tokens)), tokens]
        assert gaps.max() <= TOLERANCE
    stats = together.stats()
    assert stats["state_leaves"] == ["window_key", "window_value"]
    assert (stats["prefills_ceiling"], stats["prefills_floor"],
            stats["prefill_pad_tokens"], stats["prefilled_tokens"],
            stats["prefill_tokens"]) == (4, 1, 4, 92, 1 + 8 + 1 + 1 + 1)
    assert stats["state_resets"] == 5 and stats["prefix_skipped_stateful"] == 5
    assert stats["prefix_cache"]["entries"] == 0
    assert stats["block_pool"]["used_blocks"] == 0
    ring = 3 * 2 * 2 * RING * 32 * 4
    paged = 2 * 2 * (2 * CONTEXT + BLOCK) * 32 * 4
    assert stats["cache_bytes_by_kind"] == {"paged": paged, "ring": ring}
    assert stats["state_bytes"] == ring and stats["kv_cache_hbm_bytes"] == paged
    assert stats["cache_hbm_bytes"] == ring + paged
    steps = stats["slot_steps"]
    assert stats["moe_layer_steps"] == 4 * stats["steps"]
    assert stats["moe_assignments"] == stats["moe_assignments_here"] \
        == 4 * 2 * steps
    # the tokens that reached each of the 16 experts, over layers and steps
    assert len(stats["moe_tokens_by_expert"]) == 16
    assert sum(stats["moe_tokens_by_expert"]) == stats["moe_assignments_here"]
    # every slot-step: two full layers' live rows and the whole table read
    # (the plain read), three windows' rings
    assert stats["pool_live_token_steps"] == 2 * (stats["kv_token_steps"] + steps)
    assert stats["pool_read_token_steps"] == 2 * CONTEXT * steps
    assert stats["window_read_token_steps"] == 3 * RING * steps
    assert 0 < stats["window_live_token_steps"] <= 3 * WINDOW * steps
    read = stats["pool_read_token_steps"] + stats["window_read_token_steps"]
    assert read / 5 - stats["steps"] <= stats["kv_read_token_steps"] <= read / 5
    together.close()


def test_pipelined_streams_equal_settled_streams(tiny):
    scheduler = _scheduler(tiny, max_slots=2, temperature=1.0, top_k=8)
    assert_pipelined_equals_settled(scheduler)
    scheduler.close()


def test_same_prompt_twice_gets_no_prefix_hit(tiny):
    prompt = np.random.default_rng(2).integers(0, 256, 24)
    scheduler = _scheduler(tiny, max_slots=2)
    first, second = _serve(scheduler, [prompt]), _serve(scheduler, [prompt])
    assert first == second
    assert scheduler.stats()["prefix_skipped_stateful"] == 2
    assert scheduler.stats()["prefilled_tokens"] == 2 * 23
    scheduler.close()


@pytest.mark.parametrize("kwargs,feature", [
    ({"kv_host_blocks": 8}, "suspend / resume"),
    ({"prefill_chunk": 4}, "chunked prefill"),
    ({"spec_k": 2}, "speculative step"),
    ({"decode_attention": "fused"}, "decode_attention='fused'"),
])
def test_what_does_not_carry_the_rings_is_refused_by_name(tiny, kwargs, feature):
    with pytest.raises(ValueError) as refused:
        _scheduler(tiny, max_slots=2, **kwargs)
    assert feature in str(refused.value)
    assert "window_key, window_value" in str(refused.value)


def test_tensor_parallel_and_int8_are_refused_by_name(tiny):
    class _Tp2(DecodeEngine):
        tp_degree = 2

    engine = _Tp2(tiny["model"], prompt_buckets=BUCKETS)
    engine.tp_degree = 2
    with pytest.raises(ValueError, match="tensor-parallel.*window_key"):
        SlotScheduler(engine, tiny["variables"], block_size=BLOCK)
    with pytest.raises(ValueError, match="kv_cache_dtype='int8'.*ring"):
        laguna.LagunaConfig.tiny(kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="one entry a layer"):
        laguna.LagunaConfig.tiny(heads=(4, 8))


@pytest.mark.parametrize("call", ["export_hot_prefixes", "import_prefixes"])
def test_block_shipping_is_refused_by_name(tiny, call):
    scheduler = _scheduler(tiny, max_slots=1)
    with pytest.raises(ValueError, match="/v1/blocks.*window_key"):
        getattr(scheduler, call)(*([] if call.startswith("export") else [{}]))
    scheduler.close()


def test_engine_programs_that_carry_no_ring_refuse(tiny):
    engine = DecodeEngine(tiny["model"], prompt_buckets=BUCKETS)
    variables = tiny["variables"]
    pool = engine.make_paged_pool(variables, 9, BLOCK)
    zeros = np.zeros((2,), np.int32)
    with pytest.raises(ValueError, match="paged_step.*window_key"):
        engine.paged_step(variables, pool, np.zeros((2, 16), np.int32), zeros,
                          *all_forced(zeros, np.zeros((2, 2), np.uint32)),
                          np.zeros((2,), bool), block_size=BLOCK)
    with pytest.raises(ValueError, match="extract_blocks.*window_key"):
        engine.extract_blocks(variables, pool, np.zeros((16,), np.int32), BLOCK)
    with pytest.raises(ValueError, match="speculative.*window_key"):
        engine.paged_spec_step(
            variables, pool, np.zeros((2, 16), np.int32), zeros,
            np.zeros((2, 3), np.int32), zeros, zeros,
            np.zeros((2, 2), np.uint32), np.zeros((2,), bool), block_size=BLOCK)
    # and the model itself, handed a window of tokens over the pool
    ctx = PagedContext(jnp.zeros((2, 16), jnp.int32), jnp.zeros((2,), jnp.int32))
    with pytest.raises(NotImplementedError):
        jax.eval_shape(lambda: tiny["model"].apply(
            variables, jnp.zeros((2, 3), jnp.int32), decode=True,
            paged_ctx=ctx, mutable=["cache", "kv_pool"]))


def test_the_step_is_proved_once_at_construction(tiny):
    scheduler = _scheduler(
        tiny, DecodeEngine(tiny["model"], prompt_buckets=BUCKETS), max_slots=2)
    assert scheduler.engine.stats["paged_step_compiles"] == 1
    # the pool's leaves have a head axis: the choice is the engine's, and
    # off the TPU it is the plain read
    assert scheduler.engine.stats["paged_attention"] == "plain"
    _serve(scheduler, [np.arange(12)])
    assert scheduler.engine.stats["paged_step_compiles"] == 1
    scheduler.close()
