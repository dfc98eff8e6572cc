"""The latent-attention model (models/latent.py: a latent cache in two
widths, a learned key selection on the full layers, window layers, a
sigmoid-routed expert layer) against the plain reference the benchmark keeps
(cellbench/reference/dots3_note.py), on logits, at a tiny size on the CPU with
seeded weights; and the serving engine's leaves for it: latent and index rows
paged by token with no head axis, the window layers' rows in a ring held once
a slot.

Tolerances. The tiny model runs with `dtype=float32`, so program and
reference do the same float32 arithmetic in another order (the absorbed
products above all). Logits have a standard deviation near 1 and reach 4;
they agree to 1e-5 in every path, and 5e-5 leaves room for longer sums.
bfloat16 where float32 is stated moves logits by 1e-3 (a norm) to 1e-1 (the
router's scores, the indexer's: another expert, another key) and fails every
comparison here (`test_bfloat16_where_float32_is_stated_fails`)."""

import functools
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import agent, weights
from cellbench.reference import dots3_note as reference
from tf_yarn_tpu.models import latent
from tf_yarn_tpu.models.decode_engine import (
    DecodeEngine,
    _decode_cache_aval,
    all_forced,
    build_paged_state_step_fn,
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
TOLERANCE = 5e-5  # float32 both sides, sums in another order (see above)
SEED = 3_000_000_034
BLOCK = 8
BUCKETS = (8, 16, 32)
WINDOW, RING, TOPK, CONTEXT = 9, 16, 24, 128


def _sizes(**model):
    with open(os.path.join(ROOT, "cellbench", "tests", "data",
                           "tiny_dots3.json")) as fh:
        sizes = json.load(fh)
    sizes["model"] = {"dtype": jnp.float32, "param_dtype": jnp.float32,
                      "query_block": 16, "index_chunk": 32, "row_multiple": 8,
                      **model}
    return sizes


@pytest.fixture(scope="module")
def tiny():
    sizes = _sizes()
    model = agent.build_model(sizes)
    assert (model.config.window, model.config.ring_len,
            model.config.index_topk, model.config.max_seq_len) == \
        (WINDOW, RING, TOPK, CONTEXT)
    # One engine and one jitted step for the whole file: every grid and
    # scheduler below would otherwise compile the same programs again.
    return {
        "sizes": sizes, "model": model,
        "variables": agent.program_variables(model, sizes, SEED),
        "weights": weights.make(sizes, SEED),
        "forward": jax.jit(model.apply),
        "engine": DecodeEngine(model, prompt_buckets=BUCKETS),
        "step": jax.jit(build_paged_state_step_fn(
            model, BLOCK, 0.0, None, None, with_logits=True)),
    }


def _reference_logits(tiny, tokens, rows, lower=None):
    padded = np.zeros(-(-len(tokens) // 128) * 128, np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(reference.logits(
        tiny["weights"], jnp.asarray(padded), tiny["sizes"],
        jnp.asarray(rows), lower=lower))


# Below, at and above `index_topk` = 24 (a 25th key is the first dropped) and
# the window of 9; 77 is not a multiple of the query block of 16.
@pytest.mark.parametrize("length", [7, 9, 10, 24, 25, 26, 77, 128])
def test_full_forward_matches_reference(tiny, length):
    tokens = np.random.default_rng(length).integers(0, 256, length)
    got = tiny["forward"](tiny["variables"], jnp.asarray(tokens)[None])[0]
    want = _reference_logits(tiny, tokens, np.arange(length))
    np.testing.assert_allclose(np.asarray(got), want, atol=TOLERANCE, rtol=0)


def test_absorbed_path_matches_expanded():
    """One token against cached rows, never expanded, equals the last row
    of the expanded path over the same rows; with keys masked out too."""
    sizes = latent.AttentionSizes(4, 32, 16, 16, 8, 16, 8e7)
    rng = np.random.default_rng(3)
    s = 21
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q_n, q_r = normal(2, s, 4, 16), normal(2, s, 4, 8)
    rows, w_kvb = normal(2, s, 24), normal(16, 4, 32) / 4
    want = latent.expanded_attention(
        q_n, q_r, rows, w_kvb, sizes, query_block=8, dtype=jnp.float32)
    got = latent.absorbed_attention(
        q_n[:, -1], q_r[:, -1], rows, jnp.ones((2, s), bool), w_kvb, sizes,
        dtype=jnp.float32)
    # outputs of magnitude 3, float32 sums in another order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, -1]),
                               atol=1e-5, rtol=0)
    windowed = latent.expanded_attention(
        q_n, q_r, rows, w_kvb, sizes, window=5, query_block=8,
        dtype=jnp.float32)
    got = latent.absorbed_attention(
        q_n[:, -1], q_r[:, -1], rows, jnp.arange(s)[None, :] > s - 1 - 5,
        w_kvb, sizes, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(windowed[:, -1]),
                               atol=1e-5, rtol=0)
    assert np.abs(np.asarray(windowed - want)).max() > 1e-2


# The prompt ends inside the first block of 8 queries, at its end, inside a
# later block, and at the call's end (21 rows: three blocks, the last short).
@pytest.mark.parametrize("prompt_len", [1, 8, 13, 21])
@pytest.mark.parametrize("window", [0, 5])
def test_query_blocks_past_the_prompt_are_not_computed(prompt_len, window):
    """Told where the prompt ends, the expanded path gives the prompt's rows
    what it gives them untold, and leaves the blocks that hold only pad
    zero: their attention is not paid for."""
    sizes = latent.AttentionSizes(4, 32, 16, 16, 8, 16, 8e7)
    rng = np.random.default_rng(prompt_len)
    s, block = 21, 8
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q_n, q_r = normal(2, s, 4, 16), normal(2, s, 4, 8)
    rows, w_kvb = normal(2, s, 24), normal(16, 4, 32) / 4
    about = dict(window=window, query_block=block, dtype=jnp.float32)
    untold = np.asarray(latent.expanded_attention(
        q_n, q_r, rows, w_kvb, sizes, **about))
    told = np.asarray(jax.jit(lambda n: latent.expanded_attention(
        q_n, q_r, rows, w_kvb, sizes, prompt_len=n, **about))(prompt_len))
    computed = min(s, -(-prompt_len // block) * block)
    # outputs of magnitude 3, float32 sums in another order
    np.testing.assert_allclose(told[:, :computed], untold[:, :computed],
                               atol=1e-5, rtol=0)
    assert not told[:, computed:].any() and untold[:, -1].any()


def test_top_k_mask_is_top_k_with_its_ties():
    """The mask the expanded path keeps is `jax.lax.top_k`'s set, of equal
    scores the earlier, also where fewer than k keys are live."""
    rng = np.random.default_rng(0)
    score = jnp.asarray(rng.integers(0, 6, (7, 40)), jnp.float32)  # many ties
    score = score.at[2, 5:].set(-jnp.inf)
    for k in (1, 5, 17, 40):
        _, index = jax.lax.top_k(score, k)
        want = np.zeros((7, 40), bool)
        want[np.arange(7)[:, None], np.asarray(index)] = True
        np.testing.assert_array_equal(
            np.asarray(latent.top_k_mask(score, k)), want)


def test_a_cached_row_is_stored_in_whole_lanes():
    real = latent.LatentConfig()
    assert (real.full.row_width, real.stored_width(latent.FULL)) == (576, 640)
    assert (real.sliding.row_width, real.stored_width(latent.SLIDING)) == \
        (1088, 1152)
    assert real.ring_len == 528
    small = latent.LatentConfig.tiny(row_multiple=16)
    assert small.stored_width(latent.FULL) == 32
    model = latent.LatentLM(small)
    variables = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    cache = jax.jit(lambda v, t: model.apply(
        v, t, decode=True, mutable=["cache"])[1]["cache"])(
        variables, jnp.ones((1, 12), jnp.int32))
    rows = np.asarray(cache["layer_0"]["attn"]["latent"], np.float32)
    assert rows.shape == (1, 64, 32)
    assert np.abs(rows[0, :12, :24]).min() > 0 and not rows[0, :, 24:].any()


def test_selected_keys_are_the_references(tiny):
    """Token by token from an empty cache, the keys each full layer's step
    selects are the reference's row of its mask: same sets at every
    position past `index_topk`, with the margin at the boundary reported
    (a near-tie would show as a differing set, and is counted, not waved
    through)."""
    model, variables, sizes = tiny["model"], tiny["variables"], tiny["sizes"]
    tokens = np.random.default_rng(17).integers(0, 256, 64)
    eps = float(sizes["rms_norm_eps"])
    flat = tiny["weights"]
    # the reference's masks of the two leading full layers
    x = flat["embedding"][jnp.asarray(tokens)]
    masks = []
    about = reference.kind_sizes(sizes, reference.FULL)
    for layer in range(2):
        w = {n: flat[n][layer] for n in reference.ATTENTION_LEAVES}
        normed = reference._rmsnorm(x, flat["attn_norm"][layer], eps)
        c_q = about["alpha_q"] * reference._rmsnorm(
            normed @ w["q_a"], w["q_norm"], eps)
        masks.append(np.asarray(reference.selected(
            normed, c_q, {n: flat[n][layer] for n in reference.INDEX_LEAVES},
            index_heads=sizes["index_n_heads"],
            rope_dim=sizes["qk_rope_head_dim"], theta=about["theta"],
            top_k=TOPK, eps=eps)))
        if layer == 0:
            x = reference.hidden(
                flat, jnp.asarray(tokens), dict(sizes, num_hidden_layers=1))
    @jax.jit
    def step(cache, token):
        return model.apply(
            {**variables, "cache": cache}, token[None, None], decode=True,
            mutable=["cache", "intermediates"])[1]

    cache = jax.tree_util.tree_map(
        lambda aval: jnp.zeros(aval.shape, aval.dtype),
        _decode_cache_aval(model, variables))
    differing = 0
    for t, token in enumerate(tokens):
        state = step(cache, jnp.asarray(token, jnp.int32))
        cache = state["cache"]
        for layer in range(2):
            # rows of the dense cache seen as a pool: one block of CONTEXT
            chosen, = state["intermediates"][f"layer_{layer}"]["attn"]["selected"]
            chosen = set(np.asarray(chosen)[0].tolist()) - {-1}
            assert all(0 <= row < CONTEXT for row in chosen)
            want = set(np.flatnonzero(masks[layer][t]).tolist())
            assert len(chosen) == min(t + 1, TOPK)
            differing += chosen != want
    assert differing == 0


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
        # The rule the engine reads off this model: the ceiling (the
        # prefill is told where the prompt ends in its bucket and writes
        # the ring from the rows that end there).
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
        """One step with the given token in each named slot; logits by
        slot, the experts' counts and the cache reads."""
        tokens = np.zeros((self.slots,), np.int32)
        for slot, token in tokens_by_slot.items():
            tokens[slot] = token
        self.pool, self.state, _emitted, self.rngs, counts, reads, logits = \
            self.step(
                self.tiny["variables"], self.pool, self.state,
                jnp.asarray(self.tables), jnp.asarray(self.lengths),
                *all_forced(tokens, self.rngs),
                jnp.zeros((self.slots,), bool))
        # Read (and so wait) before the host arrays change: on the CPU
        # `jnp.asarray` may alias them, and the step runs asynchronously.
        logits, counts, reads = (np.asarray(v) for v in (logits, counts, reads))
        for slot in tokens_by_slot:
            self.lengths[slot] += 1
        return logits, counts, dict(zip(latent.READS, reads.tolist()))

    def run(self, slot, sequence, prompt_len):
        """Admit `sequence[:prompt_len]`, then feed the rest a token a
        step; logits of every step, for positions prefill .. len - 1."""
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


# Prompt lengths on, just over and just under a prefill bucket (8, 16, 32:
# the prefill takes the bucket above the length less one and keeps that many
# rows), the window (9: 8, 9, 10) and `index_topk` (24: the 25th token is the
# first to select, and the bucket of 32 selects at prefill, over its pad); 1
# prefills nothing and starts from zeroed rings; 41 has no bucket above it and
# keeps the one below. Each decodes 9 more.
@pytest.mark.parametrize("prompt_len", [1, 5, 8, 9, 10, 15, 16, 17, 23, 24,
                                        25, 26, 31, 32, 33, 41])
def test_prefill_replay_decode_match_reference(tiny, prompt_len):
    """Bucketed prefill (the expanded path) into the pool and the slot's
    rings, then replay and decode a token a step through the paged step (the
    absorbed path, the selection, the ring), against ONE full forward of the
    reference over the same tokens."""
    sequence = np.random.default_rng(prompt_len).integers(0, 256, prompt_len + 9)
    grid = _Grid(tiny)
    prefill, got = grid.run(1, sequence, prompt_len)
    assert prefill == _kept(prompt_len)
    assert prefill == {1: 0, 41: 32}.get(prompt_len, prompt_len - 1)
    want = _reference_logits(tiny, sequence, np.arange(prefill, len(sequence)))
    np.testing.assert_allclose(got, want, atol=TOLERANCE, rtol=0)


def _rings(row):
    """The ring leaves of a prefill's row cache, in layer order."""
    return [np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_leaves_with_path(row)
            if getattr(path[-1], "key", str(path[-1])) == "window_latent"]


# Rows kept below, at and above the ring of 16, in a bucket under the ring
# (8), the ring's size (16) and over it (32: the prefill that selects), with
# and without pad.
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
    held = np.zeros((RING,), bool)
    held[np.arange(max(0, kept - RING), kept) % RING] = True
    assert len(exact) == 3  # the three sliding layers
    for layer, want in enumerate(exact):
        assert want.shape[:2] == (1, RING)
        np.testing.assert_array_equal(padded[0][layer], padded[255][layer])
        np.testing.assert_allclose(padded[0][layer], want, atol=1e-5, rtol=0)
        rows = np.abs(padded[0][layer][0]).sum(axis=1) > 0
        np.testing.assert_array_equal(rows, held)


class _FloorEngine(DecodeEngine):
    """The parent's rule for a model with rings: the bucket below, whole."""

    def ceiling_prefill(self, params):
        return False


def test_ceiling_streams_equal_floor_streams(tiny):
    """Greedy streams of requests admitted under the ceiling rule (one
    token replayed; 26 to 33 select at prefill, over the pad) equal those
    of the same requests under the floor rule (the bucket below, the rest
    replayed): 41 has no bucket above it and takes the floor under both."""
    rng = np.random.default_rng(43)
    prompts = [rng.integers(0, 256, n) for n in (5, 10, 17, 18, 26, 31, 33, 41)]
    streams = {}
    for rule, engine in (
            ("ceiling", tiny["engine"]),
            ("floor", _FloorEngine(tiny["model"], prompt_buckets=BUCKETS))):
        scheduler = _scheduler(tiny, engine, max_slots=3)
        streams[rule] = _serve(scheduler, prompts, new_tokens=12)
        stats = scheduler.stats()
        scheduler.close()
        # (the floor rule finds no bucket under 5's four rows: no prefill)
        assert (stats["prefills_ceiling"], stats["prefills_floor"]) == (
            (7, 1) if rule == "ceiling" else (0, 7))
        # one token a request replays, but for 41's 9; the floor replays
        # every token past the bucket below
        assert stats["prefill_tokens"] == (
            7 + 9 if rule == "ceiling" else 5 + 2 + 1 + 2 + 10 + 15 + 1 + 9)
    assert streams["ceiling"] == streams["floor"]


def test_a_sequence_far_past_window_and_ring_reads_nothing_stale(tiny):
    """120 tokens: the ring of 16 rows turns over seven times, the selection
    drops most keys, and a slot that held a longer request before holds
    stale rows everywhere. Equal to the reference at every position."""
    rng = np.random.default_rng(23)
    before, sequence = rng.integers(0, 256, 126), rng.integers(0, 256, 120)
    grid = _Grid(tiny)
    grid.run(1, before, 40)
    grid.retire(1)
    prefill, got = grid.run(1, sequence, 33)
    want = _reference_logits(tiny, sequence, np.arange(prefill, len(sequence)))
    np.testing.assert_allclose(got, want, atol=TOLERANCE, rtol=0)


def test_slots_step_together_and_a_reused_slot_starts_clean(tiny):
    """Two requests in two slots at different positions, stepped together,
    equal each alone; then a third through a slot that held another: equal
    to it alone in a fresh grid and to the reference. The step's counters
    say what it read."""
    rng = np.random.default_rng(5)
    first, second, third = (rng.integers(0, 256, n) for n in (50, 21, 14))
    grid = _Grid(tiny)
    p1, p2 = grid.admit(0, first[:40]), grid.admit(2, second[:11])
    got1, got2 = [], []
    for t in range(10):
        logits, counts, reads = grid.advance(
            {0: first[p1 + t], 2: second[p2 + t]})
        got1.append(logits[0])
        got2.append(logits[2])
    # two active slots, four expert layers (layer 0 is dense), top 3
    assert counts.shape == (4, 1 + 8) and (counts[:, 0] == 6).all()
    # the last step: slot 0 at 41 + 1 live rows (39 rows have no bucket
    # above: 32 kept), slot 2 at 19 + 1 (10 kept of 16); two full
    # layers select min(live, 24) and gather 24 rows a slot; the index keys
    # go a chunk of 32 at a time as far as the longest slot reaches (64)
    # and are sorted as wide as a slot's table, the context of 128;
    # three window layers read their ring of 16, 9 rows of it in the window
    assert reads == {
        "index_live": 2 * (42 + 20), "index_selected": 2 * (24 + 20),
        "index_read": 2 * 2 * 64, "latent_read": 2 * 2 * 24,
        "index_sorted": 2 * 2 * 128,
        "window_live": 3 * (9 + 9), "window_read": 3 * 2 * 16}
    for got, sequence, start in ((got1, first, p1), (got2, second, p2)):
        want = _reference_logits(tiny, sequence[:start + 10],
                                 np.arange(start, start + 10))
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


@pytest.mark.parametrize("experts,shares,n_group,topk_group", [
    (16, 4, 1, 1), (32, 16, 8, 4)])
def test_the_shares_add_up_to_the_uncut_layer(experts, shares, n_group,
                                               topk_group):
    """Four chips share a layer of 16 experts, 4 each, under sigmoid scores,
    a correction bias, normalised gates and a scale; or sixteen share 32
    experts in 8 groups of which a token chooses inside the best 4, two
    experts (half a group) each, as DeepSeek-V3.2's sixteen share 256. Each
    share returns its own experts' part of the sum plus the shared expert,
    which all compute alike; the routed parts and the shared expert counted
    once are the uncut layer of the reference."""
    from cellbench.reference import deepseek_v32

    rng = np.random.default_rng(7)
    d, width, top_k, scale = 32, 16, 3, 2.5
    each = experts // shares
    w = {
        "router": jnp.asarray(rng.normal(size=(d, experts)) / 5, jnp.float32),
        "router_bias": jnp.asarray(rng.normal(size=(experts,)) / 10, jnp.float32),
        "w_in": jnp.asarray(rng.normal(size=(experts, d, 2 * width)) / 6, jnp.float32),
        "w_out": jnp.asarray(rng.normal(size=(experts, width, d)) / 4, jnp.float32),
        "shared_in": jnp.asarray(rng.normal(size=(d, 2 * width)) / 6, jnp.float32),
        "shared_out": jnp.asarray(rng.normal(size=(width, d)) / 4, jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(19, d)), jnp.float32)
    about = dict(top_k=top_k, normalise=True, scale=scale)
    layer_of = reference.experts
    if n_group > 1:
        # the family whose router has groups keeps the reference that does
        layer_of = functools.partial(deepseek_v32.experts, n_group=n_group,
                                     topk_group=topk_group)
        # and the groups decide: over all experts others are chosen
        assert np.abs(np.asarray(layer_of(x, w, offset=0, **about))
                      - np.asarray(reference.experts(x, w, offset=0, **about))
                      ).max() > 1e-2
    uncut = np.asarray(layer_of(x, w, offset=0, **about))
    # the bias decides: without it other experts are chosen
    assert np.abs(uncut - np.asarray(layer_of(
        x, dict(w, router_bias=jnp.zeros((experts,))), offset=0, **about))
    ).max() > 1e-2
    only_shared = np.asarray(reference._swiglu(
        x, w["shared_in"], w["shared_out"], None))
    total = np.zeros_like(uncut)
    for share in range(shares):
        held = slice(each * share, each * share + each)
        layer = DroplessMoE(
            num_experts=experts, num_experts_here=each,
            expert_offset=each * share, top_k=top_k, d_expert=width,
            d_shared=width, scoring="sigmoid", norm_topk=True,
            routed_scale=scale, n_group=n_group, topk_group=topk_group,
            dtype=jnp.float32, param_dtype=jnp.float32)
        params = {"params": {
            "router": w["router"], "router_bias": w["router_bias"],
            "w_in": w["w_in"][held], "w_out": w["w_out"][held],
            "shared_in": w["shared_in"], "shared_out": w["shared_out"]}}
        out, stats = layer.apply(params, x, jnp.ones((19,), bool),
                                 mutable=["moe_stats"])
        counts = np.asarray(stats["moe_stats"]["counts"][0])
        assert counts[0] == 19 * top_k
        total += np.asarray(out) - only_shared
        # and the reference's own share, given the same experts
        mine = layer_of(
            x, dict(w, w_in=w["w_in"][held], w_out=w["w_out"][held]),
            offset=each * share, **about)
        np.testing.assert_allclose(np.asarray(out), np.asarray(mine),
                                   atol=2e-5, rtol=0)
    # outputs of magnitude 1 here (the test's own weights), float32 sums
    # in another order: 2e-5; a bfloat16 matmul would miss by 1e-2
    np.testing.assert_allclose(total + only_shared, uncut, atol=2e-5, rtol=0)
    assert np.abs(total).max() > 0.1


@pytest.mark.parametrize("what", ["index_scores", "router", "norm"])
def test_bfloat16_where_float32_is_stated_fails(tiny, monkeypatch, what):
    """The tolerance is tight enough to fail a lower precision where the
    model states float32: the indexer's scores, the router's, a norm."""
    if what == "index_scores":
        plain = latent.index_scores
        monkeypatch.setattr(latent, "index_scores", lambda q, w, k: plain(
            q.astype(jnp.bfloat16), w.astype(jnp.bfloat16), k))
    elif what == "router":
        plain = jax.nn.sigmoid
        monkeypatch.setattr(latent.nn, "sigmoid", lambda x: plain(
            x.astype(jnp.bfloat16).astype(jnp.float32)))
    else:
        plain = jax.lax.rsqrt
        monkeypatch.setattr(jax.lax, "rsqrt", lambda x: plain(
            x.astype(jnp.bfloat16)).astype(jnp.float32))
    tokens = np.random.default_rng(1).integers(0, 256, 77)
    # a function of its own: jit's cache holds the unpatched trace
    got = jax.jit(lambda v, t: tiny["model"].apply(v, t))(
        tiny["variables"], jnp.asarray(tokens)[None])[0]
    want = _reference_logits(tiny, tokens, np.arange(77))
    assert np.abs(np.asarray(got) - want).max() > 10 * TOLERANCE


def test_float32_where_stated_under_bfloat16():
    """At the serving dtype the matrices and the cached rows are bfloat16;
    norm scales, the index keys' LayerNorm and the router's bias stay
    float32, and the scores that decide a choice come out float32."""
    sizes = _sizes(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    model = agent.build_model(sizes)
    variables = agent.program_variables(model, sizes, SEED)
    for path, leaf in jax.tree_util.tree_leaves_with_path(variables):
        names = [getattr(k, "key", str(k)) for k in path]
        vector = names[-1] in ("scale", "bias", "router_bias")
        assert leaf.dtype == (jnp.float32 if vector else jnp.bfloat16), names
    cache = _decode_cache_aval(model, variables)
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        name = getattr(path[-1], "key", str(path[-1]))
        assert leaf.dtype == (jnp.int32 if name == "cache_index"
                              else jnp.bfloat16), name
    q, w, k = (jnp.ones(shape, jnp.bfloat16)
               for shape in ((3, 4, 16), (3, 4), (5, 16)))
    assert latent.index_scores(q, w, k).dtype == jnp.float32
    logits = jax.jit(model.apply)(variables, jnp.zeros((1, 9), jnp.int32))
    assert logits.dtype == jnp.float32 and bool(jnp.isfinite(logits).all())


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
            # [1, seq, width]: no head axis; widths differ leaf by leaf
            assert pooled.shape == (1, 9, BLOCK, aval.shape[-1])
            assert kv_partition_spec(aval.shape, lay, 1) == \
                jax.sharding.PartitionSpec()
            for spec in (kv_partition_spec, pool_partition_spec):
                with pytest.raises(ValueError, match=f"{lay.name}.*no head axis"):
                    spec(aval.shape, lay, 2)
        else:
            assert pooled is None
    assert seen == {
        "latent": ("paged", (1, CONTEXT, 16 + 8)),
        "index_key": ("paged", (1, CONTEXT, 16)),
        "window_latent": ("ring", (1, RING, 32 + 8)),
        "cache_index": ("index", ()),
    }
    engine = DecodeEngine(model, prompt_buckets=BUCKETS)
    assert engine.slot_state_leaves(variables) == ("window_latent",)
    # the one leaf held once a slot is a ring, written where the prompt ends
    assert engine.ceiling_prefill(variables) is True

    def by_kind(context):
        sizes = dict(_sizes(), serving={"context": context, "max_slots": 4})
        longer = agent.build_model(sizes)
        engine = DecodeEngine(longer, prompt_buckets=BUCKETS)
        return engine.cache_bytes_by_kind(
            variables, engine.make_paged_pool(variables, 4 * context // BLOCK + 1, BLOCK),
            engine.make_slot_state(variables, 4))

    short, long = by_kind(CONTEXT), by_kind(4 * CONTEXT)
    # three sliding layers x 4 slots x 16 rows x 40 numbers, float32
    assert short["ring"] == long["ring"] == 3 * 4 * RING * 40 * 4
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
    """Five requests through two slots give what each gives alone and what
    the reference puts first; the counters say what happened: a ring write
    an admission, the prefix cache standing aside each time, the blocks
    back, the cache bytes by kind, the reads."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, n) for n in (9, 40, 5, 33, 17)]
    together = _scheduler(tiny, max_slots=2)
    served = _serve(together, prompts, new_tokens=30)
    for prompt, tokens in zip(prompts, served):
        assert _serve(_scheduler(tiny, max_slots=2), [prompt], 30) == [tokens]
        sequence = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
        want = _reference_logits(
            tiny, sequence, np.arange(len(prompt) - 1, len(sequence)))
        gaps = want.max(-1) - want[np.arange(len(tokens)), tokens]
        assert gaps.max() <= TOLERANCE
    stats = together.stats()
    assert stats["state_leaves"] == ["window_latent"]
    # The ring is written where the prompt ends: the ceiling rule (8, 4 of
    # 8, 32, 16 kept and one token replayed; 40 has no bucket above its 39
    # rows: 32 prefilled whole under the floor rule, 8 replayed).
    assert (stats["prefills_ceiling"], stats["prefills_floor"],
            stats["prefill_pad_tokens"], stats["prefilled_tokens"],
            stats["prefill_tokens"]) == (4, 1, 4, 92, 1 + 8 + 1 + 1 + 1)
    assert stats["state_resets"] == 5 and stats["prefix_skipped_stateful"] == 5
    assert stats["prefix_cache"]["entries"] == 0
    assert stats["prefix_cache"]["hits"] == 0
    assert stats["block_pool"]["used_blocks"] == 0
    ring = 3 * 2 * RING * 40 * 4
    paged = 2 * (2 * CONTEXT + BLOCK) * (24 + 16) * 4
    assert stats["cache_bytes_by_kind"] == {"paged": paged, "ring": ring}
    assert stats["state_bytes"] == ring and stats["kv_cache_hbm_bytes"] == paged
    assert stats["cache_hbm_bytes"] == ring + paged
    steps = stats["slot_steps"]
    # a layer-step a launched step, not a tick: a tick that only reads the
    # step in flight launches none
    assert stats["moe_layer_steps"] == 4 * stats["steps"]
    assert stats["steps"] < stats["ticks"]
    assert stats["moe_assignments"] == 4 * 3 * steps
    # every slot-step: two full layers' live rows, three windows' rings
    assert stats["index_live_token_steps"] == 2 * (stats["kv_token_steps"] + steps)
    assert stats["window_read_token_steps"] == 3 * RING * steps
    assert stats["latent_read_token_steps"] == 2 * TOPK * steps
    assert 0 < stats["index_selected_token_steps"] <= stats["index_live_token_steps"]
    assert stats["index_selected_token_steps"] <= stats["latent_read_token_steps"]
    assert stats["window_live_token_steps"] <= 3 * WINDOW * steps
    # a layer's rows read of a slot's sequence, summed a step and floored
    read = sum(stats[k + "_read_token_steps"] for k in ("index", "latent", "window"))
    assert read / 5 - stats["steps"] <= stats["kv_read_token_steps"] <= read / 5
    together.close()


def test_pipelined_streams_equal_settled_streams(tiny):
    """`paged_state_step` launched before the step before is read (the rings, the counts and the reads
    ride back a step late): the streams of the serial order, sampled, on
    one compiled program (tests/fakes.py)."""
    compiled = tiny["engine"].stats["paged_step_compiles"]  # the file's engine
    scheduler = _scheduler(tiny, max_slots=2, temperature=1.0, top_k=8)
    assert_pipelined_equals_settled(scheduler)
    assert scheduler.engine.stats["paged_step_compiles"] == compiled + 1
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
def test_what_does_not_carry_the_leaves_is_refused_by_name(tiny, kwargs, feature):
    with pytest.raises(ValueError) as refused:
        _scheduler(tiny, max_slots=2, **kwargs)
    assert feature in str(refused.value)
    assert "window_latent" in str(refused.value)


def test_tensor_parallel_and_int8_are_refused_by_name(tiny):
    class _Tp2(DecodeEngine):
        tp_degree = 2

    engine = _Tp2(tiny["model"], prompt_buckets=BUCKETS)
    engine.tp_degree = 2
    with pytest.raises(ValueError, match="tensor-parallel.*window_latent"):
        SlotScheduler(engine, tiny["variables"], block_size=BLOCK)
    with pytest.raises(ValueError, match="kv_cache_dtype='int8'.*no head axis"):
        latent.LatentConfig.tiny(kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        agent.build_model(_sizes(kv_cache_dtype="int8"))


@pytest.mark.parametrize("call", ["export_hot_prefixes", "import_prefixes"])
def test_block_shipping_is_refused_by_name(tiny, call):
    scheduler = _scheduler(tiny, max_slots=1)
    with pytest.raises(ValueError, match="/v1/blocks.*window_latent"):
        getattr(scheduler, call)(*([] if call.startswith("export") else [{}]))
    scheduler.close()


def test_engine_programs_that_carry_no_ring_refuse(tiny):
    engine = DecodeEngine(tiny["model"], prompt_buckets=BUCKETS)
    variables = tiny["variables"]
    pool = engine.make_paged_pool(variables, 9, BLOCK)
    zeros = np.zeros((2,), np.int32)
    with pytest.raises(ValueError, match="paged_step.*window_latent"):
        engine.paged_step(variables, pool, np.zeros((2, 16), np.int32), zeros,
                          *all_forced(zeros, np.zeros((2, 2), np.uint32)),
                          np.zeros((2,), bool), block_size=BLOCK)
    with pytest.raises(ValueError, match="extract_blocks.*window_latent"):
        engine.extract_blocks(variables, pool, np.zeros((16,), np.int32), BLOCK)
    with pytest.raises(ValueError, match="speculative.*window_latent"):
        engine.paged_spec_step(
            variables, pool, np.zeros((2, 16), np.int32), zeros,
            np.zeros((2, 3), np.int32), zeros, zeros,
            np.zeros((2, 2), np.uint32), np.zeros((2,), bool), block_size=BLOCK)
    # and the model itself, handed a window of tokens over the pool
    with pytest.raises(NotImplementedError, match="one token a slot"):
        jax.eval_shape(lambda: tiny["model"].apply(
            variables, jnp.zeros((2, 3), jnp.int32), decode=True,
            paged_ctx=latent_paged_ctx(), mutable=["cache", "kv_pool"]))


def latent_paged_ctx():
    from tf_yarn_tpu.models.transformer import PagedContext

    return PagedContext(jnp.zeros((2, 16), jnp.int32), jnp.zeros((2,), jnp.int32))


@pytest.mark.parametrize("broken,reason", [
    ("paged_state_step", "did not compile or run"),
    ("make_slot_state", "no room for the state"),
])
def test_run_serving_fails_at_start_up_with_the_reason(monkeypatch, tiny,
                                                       broken, reason):
    """A server for this model proves its step at construction: rings that
    do not fit, or a step the compiler refuses, stop `run_serving` before it
    listens: no endpoint is advertised, and the error says what and why."""
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
    assert "window_latent" in message and "RESOURCE_EXHAUSTED" in message
    with pytest.raises(Exception):
        _Runtime.kv.wait_str("serving:0/serving_endpoint", timeout=0.2)


def test_the_step_is_proved_once_at_construction(tiny):
    scheduler = _scheduler(
        tiny, DecodeEngine(tiny["model"], prompt_buckets=BUCKETS), max_slots=2)
    assert scheduler.engine.stats["paged_step_compiles"] == 1
    assert scheduler.engine.stats["paged_attention"] == "model"
    _serve(scheduler, [np.arange(12)])
    assert scheduler.engine.stats["paged_step_compiles"] == 1
    scheduler.close()
