"""DeepSeek-V3.2's layer on `models/latent.py` and `models/moe.py` — every
layer `full_attention` under the indexer's top-k, no gate and no rescale,
YaRN's frequencies with `mscale`^2 on the softmax scale, sigmoid routing
inside the best groups — against the plain reference the benchmark keeps
(cellbench/reference/deepseek_v32.py), on logits, at a tiny size on the CPU
with seeded weights; and the serving engine and scheduler for a latent model
that selects and holds nothing once a slot.

Tolerances. The tiny model runs with `dtype=float32`, so program and
reference do the same float32 arithmetic in another order (the absorbed
products above all). Logits have a standard deviation near 1 and reach 4;
they agree to 7e-6 in every path, and 5e-5 leaves room for longer sums (it
is `tests/test_latent.py`'s, for the same attention). The reference in int8,
the precision below the one the configuration states, moves logits by 0.1 to
3 and fails every comparison here; so does each piece of the layer's
mathematics left out (`test_mathematics_left_out_fails`). The context of 160
is 6.7 times `index_topk` = 24: the selection discards five keys of six at
the end of a slot, more than the benchmark's cell ever does."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import agent, weights
from cellbench.reference import deepseek_v32 as reference
from tf_yarn_tpu.models import latent
from tf_yarn_tpu.models.decode_engine import (
    DecodeEngine,
    _decode_cache_aval,
    all_forced,
    build_paged_state_step_fn,
)
from tf_yarn_tpu.models.moe import DroplessMoE, within_best_groups
from tf_yarn_tpu.models.transformer import PagedContext, RotaryRecipe
from tf_yarn_tpu.serving.request import SamplingParams
from tf_yarn_tpu.serving.scheduler import SlotScheduler
from tests.fakes import admit_prefill

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(ROOT, "cellbench", "tests", "data")
TOLERANCE = 5e-5  # float32 both sides, sums in another order (see above)
SEED = 3_000_000_044
BLOCK = 8
BUCKETS = (8, 16, 32)
CONTEXT, LAYERS, TOPK, TOP_K, HELD, WIDTH = 160, 3, 24, 3, 8, 24


def _sizes(**model):
    with open(os.path.join(DATA, "tiny_dsv32.json")) as fh:
        sizes = json.load(fh)
    sizes["model"] = {"dtype": jnp.float32, "param_dtype": jnp.float32,
                      "query_block": 16, "index_chunk": 32, "row_multiple": 8,
                      **model}
    return sizes


@pytest.fixture(scope="module")
def tiny():
    sizes = _sizes()
    model = agent.build_model(sizes)
    config = model.config
    assert (config.n_layers, config.index_topk, config.experts_per_token,
            config.num_experts_here, config.max_seq_len,
            config.stored_width(latent.FULL)) == \
        (LAYERS, TOPK, TOP_K, HELD, CONTEXT, WIDTH)
    assert (config.gated, config.rescale_latents, config.n_group,
            config.topk_group) == ((), False, 4, 2)
    # One engine and one jitted step for the whole file: every grid and
    # scheduler below would otherwise compile the same programs again.
    variables = agent.program_variables(model, sizes, SEED)
    engine = DecodeEngine(model, prompt_buckets=BUCKETS)
    return {
        "sizes": sizes, "model": model, "variables": variables,
        "weights": weights.make(sizes, SEED),
        "forward": jax.jit(model.apply), "engine": engine,
        "step": jax.jit(build_paged_state_step_fn(
            model, BLOCK, 0.0, None, None, with_logits=True)),
    }


def _reference_logits(tiny, tokens, rows, lower=None):
    padded = np.zeros(-(-len(tokens) // 128) * 128, np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(reference.logits(
        tiny["weights"], jnp.asarray(padded), tiny["sizes"],
        jnp.asarray(rows), lower=lower))


# -- the forward pass ---------------------------------------------------------


# Below, at and above `index_topk` = 24 (a 25th key is the first dropped);
# 77 is not a multiple of the query block of 16; 160 is the context.
@pytest.mark.parametrize("length", [1, 7, 24, 25, 26, 77, 160])
def test_full_forward_matches_reference(tiny, length):
    tokens = np.random.default_rng(length).integers(0, 256, length)
    got = tiny["forward"](tiny["variables"], jnp.asarray(tokens)[None])[0]
    want = _reference_logits(tiny, tokens, np.arange(length))
    np.testing.assert_allclose(np.asarray(got), want, atol=TOLERANCE, rtol=0)
    lower = _reference_logits(tiny, tokens, np.arange(length), lower="int8")
    assert np.abs(np.asarray(got) - lower).max() > 100 * TOLERANCE


@pytest.mark.parametrize("what", [
    "gate", "rescale", "one_group", "plain_rope", "no_mscale", "all_keys"])
def test_mathematics_left_out_fails(tiny, what):
    """What tells this family's layer from dots3-note's, and what a faster
    program might drop, each moves the logits far past the tolerance: the
    head-wise gate and the rescales left on, the choice made over all
    experts, plain RoPE, the softmax scale without `mscale`^2, every key
    attended."""
    config = tiny["model"].config
    variables = tiny["variables"]
    plain = dataclasses.replace(config.full, rotary=None)
    change = {
        "gate": dict(gated=(latent.FULL,)),
        "rescale": dict(rescale_latents=True),
        "one_group": dict(n_group=1, topk_group=1),
        "plain_rope": dict(full=plain),
        "no_mscale": dict(full=dataclasses.replace(config.full, mscale=1.0)),
        "all_keys": dict(index_topk=CONTEXT),
    }[what]
    model = latent.LatentLM(dataclasses.replace(config, **change))
    tokens = np.random.default_rng(1).integers(0, 256, 77)
    if what == "gate":
        # the gate's matrices, which this family's table does not have
        fresh = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
        variables = jax.tree_util.tree_map(lambda x: x, variables)
        for layer in range(LAYERS):
            variables["params"][f"layer_{layer}"]["attn"]["gate"] = \
                fresh["params"][f"layer_{layer}"]["attn"]["gate"]
    got = jax.jit(lambda v, t: model.apply(v, t))(
        variables, jnp.asarray(tokens)[None])[0]
    want = _reference_logits(tiny, tokens, np.arange(77))
    assert np.abs(np.asarray(got) - want).max() > 100 * TOLERANCE


# -- YaRN ---------------------------------------------------------------------


def _yarn64(n, theta, factor, original, beta_fast, beta_slow):
    """YaRN's frequencies in float64 numpy, from the paper's equations."""
    def dim_of(turns):
        return n * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(dim_of(beta_fast)), 0), \
        min(math.ceil(dim_of(beta_slow)), n - 1)
    own = theta ** (-np.arange(0, n, 2, dtype=np.float64) / n)
    blend = np.clip((np.arange(n // 2) - low) / max(high - low, 0.001), 0, 1)
    return own * (1 - blend) + own / factor * blend, (low, high)


@pytest.mark.parametrize("n,original", [(64, 4096), (8, 32), (16, 64)])
def test_yarn_in_latent_rope_is_the_recipes_and_float64_numpys(n, original):
    """`latent.rope` under a YaRN recipe turns by `RotaryRecipe.inv_freq`,
    which is the reference's own table and a float64 numpy YaRN; at the
    published sizes the pairs below 10 keep their frequency and those from
    23 on turn a 40th as fast."""
    recipe = RotaryRecipe(1e4, n, factor=40.0, original_max=original,
                          beta_fast=32.0, beta_slow=1.0)
    want, (low, high) = _yarn64(n, 1e4, 40.0, original, 32.0, 1.0)
    assert recipe.correction_range() == (low, high)
    np.testing.assert_array_equal(recipe.inv_freq(), want.astype(np.float32))
    scaling = {"factor": 40, "original_max_position_embeddings": original,
               "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
    np.testing.assert_array_equal(
        reference.yarn_frequencies(n, 1e4, scaling), recipe.inv_freq())
    if n == 64:
        own = 1e4 ** (-np.arange(0, 64, 2) / 64)
        assert (low, high) == (10, 23)
        np.testing.assert_allclose(want[:11], own[:11], rtol=1e-12)
        np.testing.assert_allclose(want[23:], own[23:] / 40, rtol=1e-12)
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.normal(size=(2, 5, 3, n)), jnp.float32)
    positions = jnp.asarray(rng.integers(0, 12288, (2, 5)), jnp.int32)
    got = np.asarray(latent.rope(x, positions, recipe))
    angles = np.asarray(positions, np.float64)[:, :, None, None] \
        * want.astype(np.float32).astype(np.float64)
    x64 = np.asarray(x, np.float64)
    turned = np.stack(
        [x64[..., 0::2] * np.cos(angles) - x64[..., 1::2] * np.sin(angles),
         x64[..., 1::2] * np.cos(angles) + x64[..., 0::2] * np.sin(angles)],
        axis=-1).reshape(x.shape)
    # float32 angles of up to 12288 radians carry 1e-3 of a radian
    np.testing.assert_allclose(got, turned, atol=5e-3, rtol=0)
    # and is another function than plain RoPE at the same theta
    plain = np.asarray(latent.rope(x, positions, RotaryRecipe(1e4, n)))
    assert np.abs(got - plain).max() > 0.1
    with pytest.raises(ValueError, match="recipe of"):
        latent.rope(x[..., :n // 2], positions, recipe)


def test_the_kinds_recipe_and_scale():
    """A kind without `rotary` turns by plain RoPE at its theta over
    whatever it is asked, a kind with one by YaRN over the same; the softmax
    scale carries `mscale`^2 and is what it was at 1."""
    plain = latent.AttentionSizes(4, 32, 16, 16, 8, 16, 8e7)
    assert plain.recipe(8) == RotaryRecipe(8e7, 8)
    assert plain.softmax_scale == (16 + 8) ** -0.5
    yarn = dataclasses.replace(
        plain, rotary=RotaryRecipe(1e4, 8, factor=40.0, original_max=32),
        mscale=0.1 * math.log(40.0) + 1.0)
    assert yarn.recipe(4) == RotaryRecipe(1e4, 4, factor=40.0, original_max=32)
    assert yarn.softmax_scale == pytest.approx(1.3689 ** 2 / math.sqrt(24), rel=1e-4)
    with pytest.raises(ValueError, match="gated"):
        latent.LatentConfig.tiny(gated=(latent.PLAIN,))


# -- group-limited routing ----------------------------------------------------


def _numpy_routing(score, bias, top_k, n_group, topk_group, scale):
    """The published rule, read off in numpy: (chosen [k], gates [k]) of one
    token; of equal entries the earlier wins, in groups and in experts."""
    choice = (score + bias).astype(np.float32)
    grouped = choice.reshape(n_group, -1)
    group_score = np.sort(grouped, -1)[:, -2:].sum(-1, dtype=np.float32)
    best = np.argsort(-group_score, kind="stable")[:topk_group]
    inside = np.where(np.isin(np.arange(n_group), best)[:, None], grouped,
                      -np.inf).reshape(-1)
    chosen = np.argsort(-inside, kind="stable")[:top_k]
    gates = score[chosen] / score[chosen].sum() * scale
    return chosen, gates


@pytest.mark.parametrize("case", ["random", "ties", "bias_decides",
                                  "negative_inside"])
def test_group_limited_routing_is_the_published_rule(case):
    """`DroplessMoE` under `n_group` 8 / `topk_group` 4 chooses and gates as
    a numpy reading of `noaux_tc`: random scores; scores on a grid of
    eighths, so that groups and experts tie and the earlier must win; a bias
    that moves the choice and not the gates; and a kept group whose entries
    are negative under the bias, which still beat everything outside (-inf
    there, not 0)."""
    rng = np.random.default_rng(5)
    tokens, d, experts, top_k, n_group, topk_group, scale = 29, 16, 32, 5, 8, 4, 2.5
    x = rng.normal(size=(tokens, d)).astype(np.float32)
    router = (rng.normal(size=(d, experts)) / 4).astype(np.float32)
    bias = (rng.normal(size=(experts,)) / 50).astype(np.float32)
    if case == "ties":
        # logits of +-k ln 3 at most: few distinct scores, and ties
        x = np.eye(d, dtype=np.float32)[rng.integers(0, d, tokens)]
        router = (np.log(3.0) * rng.integers(-1, 2, (d, experts))).astype(np.float32)
        bias = np.zeros((experts,), np.float32)
    elif case == "bias_decides":
        bias = (rng.normal(size=(experts,)) / 2).astype(np.float32)
    elif case == "negative_inside":
        bias = np.where(np.arange(experts) < 16, -2.0, -5.0).astype(np.float32)
    layer = DroplessMoE(
        num_experts=experts, num_experts_here=experts, top_k=top_k, d_expert=8,
        scoring="sigmoid", norm_topk=True, routed_scale=scale,
        n_group=n_group, topk_group=topk_group, dtype=jnp.float32,
        param_dtype=jnp.float32)
    # experts that return the sum of their input: the output says who
    # was chosen with what gate
    w_in = np.zeros((experts, d, 16), np.float32)
    w_in[:, :, 8:] = 1.0 / 8              # b = mean-ish of x, a = 0
    params = {"params": {
        "router": jnp.asarray(router), "router_bias": jnp.asarray(bias),
        "w_in": jnp.asarray(w_in),
        "w_out": jnp.zeros((experts, 8, d), jnp.float32)}}
    _, stats = layer.apply(params, jnp.asarray(x), jnp.ones((tokens,), bool),
                           mutable=["moe_stats"])
    counts = np.asarray(stats["moe_stats"]["counts"][0])[1:]
    score = np.asarray(jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x, router, precision=jax.lax.Precision.HIGHEST)))
    want_counts = np.zeros((experts,), np.int64)
    ties = 0
    for t in range(tokens):
        chosen, gates = _numpy_routing(score[t], bias, top_k, n_group,
                                       topk_group, scale)
        want_counts[chosen] += 1
        choice = score[t] + bias
        ties += len(np.unique(choice)) < experts
        # the reference's reading is the same
        index, gate = reference.routing(
            jnp.asarray(score[t:t + 1]), jnp.asarray(bias), top_k=top_k,
            n_group=n_group, topk_group=topk_group, normalise=True, scale=scale)
        assert np.asarray(index)[0].tolist() == chosen.tolist()
        np.testing.assert_allclose(np.asarray(gate)[0], gates, rtol=1e-6)
        if case == "negative_inside":
            # groups 0..3 are kept (-2 + s beats -5 + s): never an expert of
            # the other half, though every kept entry is below 0
            assert chosen.max() < 16 and choice[chosen].max() < 0
    np.testing.assert_array_equal(counts, want_counts)
    assert (ties > 0) == (case == "ties")
    masked = np.asarray(within_best_groups(
        jnp.asarray(score + bias), n_group, topk_group))
    assert (np.isfinite(masked).reshape(tokens, n_group, -1).all(-1).sum(-1)
            == topk_group).all()


def test_groups_are_the_sigmoid_scorings_and_must_hold_the_choice():
    x = jnp.zeros((3, 8), jnp.float32)
    for kwargs in (dict(scoring="softmax"), dict(n_group=3),
                   dict(topk_group=5), dict(top_k=9, topk_group=2)):
        layer = DroplessMoE(**{**dict(
            num_experts=16, num_experts_here=16, top_k=3, d_expert=8,
            scoring="sigmoid", n_group=4, topk_group=2), **kwargs})
        with pytest.raises(ValueError, match="n_group"):
            layer.init(jax.random.PRNGKey(0), x)


def test_the_shares_of_a_whole_layer_add_up(tiny):
    """Through the program's block, whose attention every share runs whole:
    the two halves of the tiny model's 16 experts (two of its four groups
    each), shared expert counted once, against the reference's layer with
    all 16 and with none."""
    sizes = dict(tiny["sizes"], n_routed_experts_here=16, num_hidden_layers=2)
    table = weights.make(sizes, SEED)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 19, 64)), jnp.float32)
    eps = float(sizes["rms_norm_eps"])
    normed = reference._rmsnorm(x[0], table["ffn_norm"][1], eps)
    about = reference.routing_of(sizes)
    w = {n: table[n][0] for n in reference.EXPERT_LEAVES}
    uncut = np.asarray(reference.experts(normed, w, **about))
    shared = np.asarray(reference._swiglu(
        normed, w["shared_in"], w["shared_out"], None))
    total = np.zeros_like(uncut)
    for offset in (0, 8):
        layer = DroplessMoE(
            num_experts=16, num_experts_here=8, expert_offset=offset, top_k=3,
            d_expert=32, d_shared=32, scoring="sigmoid", norm_topk=True,
            routed_scale=2.5, n_group=4, topk_group=2, dtype=jnp.float32,
            param_dtype=jnp.float32)
        params = {"params": dict(w, w_in=w["w_in"][offset:offset + 8],
                                 w_out=w["w_out"][offset:offset + 8])}
        total += np.asarray(layer.apply(params, normed)) - shared
    np.testing.assert_allclose(total + shared, uncut, atol=TOLERANCE, rtol=0)
    assert np.abs(total).max() > 0.05


# -- the selection ------------------------------------------------------------


def test_selected_keys_are_the_references(tiny):
    """Token by token from an empty cache, the keys every layer's step
    selects are the reference's row of its mask, to the end of the context:
    24 of 160 there."""
    model, variables, sizes = tiny["model"], tiny["variables"], tiny["sizes"]
    tokens = np.random.default_rng(17).integers(0, 256, CONTEXT)
    eps = float(sizes["rms_norm_eps"])
    flat = tiny["weights"]
    frequencies, rope_factor, _ = reference.rotary_of(sizes)
    masks = []
    # the reference goes a block of 128 queries at a time: pad at the end
    padded = jnp.pad(jnp.asarray(tokens), (0, 256 - CONTEXT))
    for layer in range(2):
        x = flat["embedding"][padded] if layer == 0 else reference.hidden(
            flat, padded, dict(sizes, num_hidden_layers=layer))
        w = {n: flat[n][layer] for n in reference.ATTENTION_LEAVES}
        normed = reference._rmsnorm(x, flat["attn_norm"][layer], eps)
        c_q = reference._rmsnorm(normed @ w["q_a"], w["q_norm"], eps)
        masks.append(np.asarray(reference.selected(
            normed, c_q, {n: flat[n][layer] for n in reference.INDEX_LEAVES},
            index_heads=sizes["index_n_heads"], frequencies=frequencies,
            rope_factor=rope_factor, top_k=TOPK, eps=eps))[:CONTEXT, :CONTEXT])

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
            chosen, = state["intermediates"][f"layer_{layer}"]["attn"]["selected"]
            chosen = set(np.asarray(chosen)[0].tolist()) - {-1}
            assert len(chosen) == min(t + 1, TOPK)
            differing += chosen != set(np.flatnonzero(masks[layer][t]).tolist())
    assert differing == 0


# -- through the engine's pool ------------------------------------------------


class _Grid:
    """The engine's paged pool, driven by hand the way the scheduler drives
    it, with the step's logits read out. Nothing is held once a slot."""

    def __init__(self, tiny, slots=3):
        self.tiny, self.slots = tiny, slots
        self.engine = tiny["engine"]
        variables = tiny["variables"]
        self.per_slot = CONTEXT // BLOCK
        self.pool = self.engine.make_paged_pool(
            variables, slots * self.per_slot + 1, BLOCK)
        self.state = self.engine.make_slot_state(variables, slots)
        assert jax.tree_util.tree_leaves(self.state) == []
        self.tables = np.zeros((slots, self.per_slot), np.int32)
        self.lengths = np.zeros((slots,), np.int32)
        self.rngs = np.zeros((slots, 2), np.uint32)

    def admit(self, slot, prompt):
        variables = self.tiny["variables"]
        blocks = 1 + slot * self.per_slot + np.arange(self.per_slot)
        self.pool, _row, _bucket, prefill = admit_prefill(
            self.engine, variables, self.pool, prompt, blocks, BLOCK,
            self.engine.ceiling_prefill(variables))
        self.tables[slot] = blocks
        self.lengths[slot] = prefill
        return prefill

    def advance(self, tokens_by_slot):
        tokens = np.zeros((self.slots,), np.int32)
        for slot, token in tokens_by_slot.items():
            tokens[slot] = token
        self.pool, self.state, _emitted, self.rngs, counts, reads, logits = \
            self.tiny["step"](
                self.tiny["variables"], self.pool, self.state,
                jnp.asarray(self.tables), jnp.asarray(self.lengths),
                *all_forced(tokens, self.rngs),
                jnp.zeros((self.slots,), bool))
        logits, counts, reads = (np.asarray(v) for v in (logits, counts, reads))
        for slot in tokens_by_slot:
            self.lengths[slot] += 1
        return logits, counts, dict(zip(latent.READS, reads.tolist()))

    def run(self, slot, sequence, prompt_len):
        prefill = self.admit(slot, sequence[:prompt_len])
        rows = [self.advance({slot: sequence[t]})[0][slot]
                for t in range(prefill, len(sequence))]
        return prefill, np.stack(rows)


def test_every_leaf_is_paged_and_the_ceiling_rule_holds(tiny):
    engine, variables = tiny["engine"], tiny["variables"]
    assert engine.slot_state_leaves(variables) == ()
    assert engine.ceiling_prefill(variables) is True
    assert engine.counted_step(variables) is True
    cache = _decode_cache_aval(tiny["model"], variables)
    names = sorted({getattr(path[-1], "key", str(path[-1]))
                    for path, _ in jax.tree_util.tree_leaves_with_path(cache)})
    assert names == ["cache_index", "index_key", "latent"]
    pool = engine.make_paged_pool(variables, 9, BLOCK)
    shapes = sorted(leaf.shape for leaf in jax.tree_util.tree_leaves(pool))
    assert shapes == [(1, 9, BLOCK, 16)] * LAYERS + [(1, 9, BLOCK, WIDTH)] * LAYERS
    assert engine.cache_bytes_by_kind(variables, pool) == {
        "paged": LAYERS * 9 * BLOCK * (16 + WIDTH) * 4}


# Prompt lengths on, just over and just under a prefill bucket (8, 16, 32: the
# prefill takes the bucket above the length less one and keeps that many
# rows) and `index_topk` (24: the 25th token is the first to select, and the
# bucket of 32 selects at prefill, over its pad); 41 has no bucket above it
# and keeps the one below. Each decodes 9 more.
@pytest.mark.parametrize("prompt_len", [1, 8, 9, 16, 17, 24, 25, 26, 32, 33, 41])
def test_prefill_replay_decode_match_reference(tiny, prompt_len):
    """Bucketed prefill (the expanded path) into the pool, then replay and
    decode a token a step through the paged step (the absorbed path over
    the selected rows), against ONE full forward of the reference."""
    sequence = np.random.default_rng(prompt_len).integers(0, 256, prompt_len + 9)
    prefill, got = _Grid(tiny).run(1, sequence, prompt_len)
    assert prefill == {1: 0, 41: 32}.get(prompt_len, prompt_len - 1)
    want = _reference_logits(tiny, sequence, np.arange(prefill, len(sequence)))
    np.testing.assert_allclose(got, want, atol=TOLERANCE, rtol=0)


def test_a_slot_grown_to_six_times_its_top_k_matches_reference(tiny):
    """The benchmark's regime in small: a prompt of 20 decoded to the
    context's end, 160 tokens, where the step scores 160 keys and keeps 24.
    Every step's logits against one forward of the reference, and against
    its int8 control, which fails."""
    sequence = np.random.default_rng(160).integers(0, 256, CONTEXT)
    grid = _Grid(tiny)
    prefill, got = grid.run(0, sequence, 20)
    want = _reference_logits(tiny, sequence, np.arange(prefill, CONTEXT))
    np.testing.assert_allclose(got, want, atol=TOLERANCE, rtol=0)
    lower = _reference_logits(tiny, sequence, np.arange(prefill, CONTEXT),
                              lower="int8")
    assert np.abs(got - lower).mean() > 100 * TOLERANCE
    # attending every key instead moves the late logits: the selection is live
    config = dataclasses.replace(tiny["model"].config, index_topk=CONTEXT)
    every = jax.jit(latent.LatentLM(config).apply)(
        tiny["variables"], jnp.asarray(sequence)[None])[0]
    assert np.abs(np.asarray(every)[-40:] - want[-40:]).max() > 100 * TOLERANCE


def test_slots_step_together_and_the_reads_are_counted(tiny):
    """Two slots of different lengths in one step; the step's counters: the
    keys live, read (a chunk of 32 at a time as far as the longest slot
    reaches), selected, gathered, and sorted — as wide as the slot's whole
    table, which is what `index_sorted` is there to show."""
    rng = np.random.default_rng(3)
    first, second = rng.integers(0, 256, 70), rng.integers(0, 256, 30)
    grid = _Grid(tiny)
    p1, p2 = grid.admit(0, first[:33]), grid.admit(2, second[:11])
    assert (p1, p2) == (32, 10)
    got1, got2 = [], []
    for t in range(10):
        logits, counts, reads = grid.advance(
            {0: first[p1 + t], 2: second[p2 + t]})
        got1.append(logits[0])
        got2.append(logits[2])
    # two active slots, two expert layers (layer 0 is dense), top 3
    assert counts.shape == (2, 1 + HELD) and (counts[:, 0] == 2 * TOP_K).all()
    # the last step: slot 0 at 41 + 1 live rows, slot 2 at 19 + 1
    assert reads == {
        "index_live": LAYERS * (42 + 20),
        "index_selected": LAYERS * (TOPK + 20),
        "index_read": LAYERS * 2 * 64, "latent_read": LAYERS * 2 * TOPK,
        "index_sorted": LAYERS * 2 * CONTEXT,
        "window_live": 0, "window_read": 0}
    for got, sequence, start in ((got1, first, p1), (got2, second, p2)):
        want = _reference_logits(tiny, sequence[:start + 10],
                                 np.arange(start, start + 10))
        np.testing.assert_allclose(np.stack(got), want, atol=TOLERANCE, rtol=0)


def test_the_steps_scopes_name_the_groups_and_the_indexer(tiny):
    """`moe/groups` and the indexer's five scopes are on the step's
    operations, where the benchmark's shares read them."""
    engine, variables = tiny["engine"], tiny["variables"]
    pool = engine.make_paged_pool(variables, 9, BLOCK)
    zeros = np.zeros((2,), np.int32)
    text = tiny["step"].lower(
        variables, pool, engine.make_slot_state(variables, 2),
        jnp.zeros((2, 4), jnp.int32), jnp.asarray(zeros),
        *all_forced(zeros, np.zeros((2, 2), np.uint32)),
        jnp.zeros((2,), bool)).as_text(debug_info=True)
    for scope in ("moe/groups", "moe/router", "indexer/q", "indexer/k",
                  "indexer/scores", "indexer/topk", "indexer/gather",
                  "latent/absorb"):
        assert scope in text, scope
    assert "latent/gate" not in text


def test_float32_where_stated_under_bfloat16():
    """At the serving dtype the matrices and the cached rows are bfloat16;
    norm scales, the index keys' LayerNorm and the router's bias stay
    float32."""
    sizes = _sizes(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    model = agent.build_model(sizes)
    variables = agent.program_variables(model, sizes, SEED)
    for path, leaf in jax.tree_util.tree_leaves_with_path(variables):
        names = [getattr(k, "key", str(k)) for k in path]
        vector = names[-1] in ("scale", "router_bias") \
            or names[-2] == "index_k_norm"
        assert leaf.dtype == (jnp.float32 if vector else jnp.bfloat16), names
        assert names[-1] != "gate"
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            _decode_cache_aval(model, variables)):
        name = getattr(path[-1], "key", str(path[-1]))
        assert leaf.dtype == (jnp.int32 if name == "cache_index"
                              else jnp.bfloat16), name
    logits = jax.jit(model.apply)(variables, jnp.zeros((1, 30), jnp.int32))
    assert logits.dtype == jnp.float32 and bool(jnp.isfinite(logits).all())


# -- the scheduler ------------------------------------------------------------


def _scheduler(tiny, engine=None, **kwargs):
    kwargs.setdefault("block_size", BLOCK)
    return SlotScheduler(engine or tiny["engine"], tiny["variables"], **kwargs)


def _serve(scheduler, prompts, new_tokens=6):
    responses = [scheduler.submit(
        list(map(int, p)), SamplingParams(max_new_tokens=new_tokens))
        for p in prompts]
    for _ in range(4000):
        if all(r.done for r in responses):
            break
        scheduler.tick()
    return [r.result(timeout=1) for r in responses]


def _first_choices(tiny, prompt, tokens):
    sequence = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    want = _reference_logits(
        tiny, sequence, np.arange(len(prompt) - 1, len(sequence)))
    return want.max(-1) - want[np.arange(len(tokens)), tokens]


def test_scheduler_serves_through_reused_slots(tiny):
    """Four requests through two slots, decoded past the top-k, give what
    each gives alone and what the reference puts first; `/stats` says what
    happened, the new counter included."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, n) for n in (9, 40, 33, 17)]
    together = _scheduler(tiny, max_slots=2)
    served = _serve(together, prompts, new_tokens=40)
    for prompt, tokens in zip(prompts[:2], served):
        assert _serve(_scheduler(tiny, max_slots=2), [prompt], 40) == [tokens]
    for prompt, tokens in zip(prompts, served):
        assert _first_choices(tiny, prompt, np.asarray(tokens)).max() <= TOLERANCE
    stats = together.stats()
    assert (stats["prefills_ceiling"], stats["prefills_floor"]) == (3, 1)
    assert stats["state_leaves"] == [] and stats["state_bytes"] == 0
    assert stats["state_resets"] == 0 and stats["prefix_skipped_stateful"] == 0
    assert stats["block_pool"]["used_blocks"] == \
        stats["prefix_cache"]["cached_blocks"] > 0
    paged = LAYERS * (2 * CONTEXT + BLOCK) * (WIDTH + 16) * 4
    assert stats["cache_bytes_by_kind"] == {"paged": paged}
    assert stats["cache_hbm_bytes"] == stats["kv_cache_hbm_bytes"] == paged
    steps = stats["slot_steps"]
    assert stats["moe_layer_steps"] == (LAYERS - 1) * stats["steps"]
    assert stats["moe_assignments"] == (LAYERS - 1) * TOP_K * steps
    assert 0 < stats["moe_assignments_here"] < stats["moe_assignments"]
    # every slot-step of every layer: the live keys scored, `index_topk`
    # rows gathered, the whole table sorted
    assert stats["index_live_token_steps"] == \
        LAYERS * (stats["kv_token_steps"] + steps)
    assert stats["latent_read_token_steps"] == LAYERS * TOPK * steps
    assert stats["index_sorted_token_steps"] == LAYERS * CONTEXT * steps
    assert stats["index_live_token_steps"] <= stats["index_read_token_steps"] \
        <= stats["index_sorted_token_steps"]
    # requests of 49 to 80 tokens: most slot-steps run past the top-k
    assert 0 < stats["index_selected_token_steps"] \
        < 0.8 * stats["index_live_token_steps"]
    assert stats["window_live_token_steps"] == 0
    assert stats["decode_engine"]["paged_attention"] == "model"
    together.close()


def test_the_same_prompt_again_is_served_through_a_prefix_hit(tiny):
    """Nothing of this model is held once a slot, so the prefix cache is on
    for it: the second request starts on the first's blocks (latent rows and
    index keys both), prefills nothing, and is served the same tokens."""
    prompt = np.random.default_rng(2).integers(0, 256, 30)
    scheduler = _scheduler(tiny, max_slots=2)
    first, second = _serve(scheduler, [prompt], 12), _serve(scheduler, [prompt], 12)
    assert first == second
    assert _first_choices(tiny, prompt, np.asarray(second[0])).max() <= TOLERANCE
    stats = scheduler.stats()
    assert stats["prefix_cache"]["hits"] == 1
    assert stats["prefix_skipped_stateful"] == 0
    # the first kept 29 rows of the bucket of 32 and offered their three
    # whole blocks; the second hit those 24 tokens and replayed 6
    assert stats["prefilled_tokens"] == 29
    assert stats["prefill_tokens"] == 1 + 6
    scheduler.close()


def test_suspend_resume_and_block_shipping_move_whole_blocks(tiny):
    """The host swap tier and /v1/blocks' export and import gather and
    scatter whole blocks of both paged leaves: a suspended stream resumes to
    the tokens it gives alone, and exported prefixes prime another server."""
    rng = np.random.default_rng(17)
    batch, urgent = rng.integers(0, 256, 20), rng.integers(0, 256, 20)
    alone = [_serve(_scheduler(tiny, max_slots=2), [p], 12)[0]
             for p in (batch, urgent)]
    # room for one request: ceil((20 + 12) / 8) = 4 blocks and the trash block
    scheduler = _scheduler(tiny, max_slots=2, num_blocks=5, kv_host_blocks=8)
    slow = scheduler.submit(list(map(int, batch)),
                            SamplingParams(max_new_tokens=12), tier="batch")
    for _ in range(4):
        scheduler.tick()
    fast = scheduler.submit(list(map(int, urgent)),
                            SamplingParams(max_new_tokens=12), tier="interactive")
    for _ in range(2000):
        if slow.done and fast.done:
            break
        scheduler.tick()
    assert [slow.result(timeout=1), fast.result(timeout=1)] == alone
    swap = scheduler.stats()["swap"]
    assert swap["suspends"] == 1 and swap["resumes"] == 1
    scheduler.close()
    donor = _scheduler(tiny, max_slots=2)
    assert _serve(donor, [urgent], 12) == [alone[1]]
    wire = donor.export_hot_prefixes()
    assert wire["n_blocks"] == 2  # the 19 prefilled tokens' whole blocks
    donor.close()
    other = _scheduler(tiny, max_slots=2)
    assert other.import_prefixes(wire)["imported_blocks"] == wire["n_blocks"]
    assert _serve(other, [urgent], 12) == [alone[1]]
    assert other.stats()["prefix_cache"]["hits"] == 1
    other.close()


@pytest.mark.parametrize("kwargs,reason", [
    ({"prefill_chunk": 4}, "reads one token a slot"),
    ({"spec_k": 2}, "reads one token a slot"),
    ({"decode_attention": "fused"}, "requires kv_cache_dtype='int8'")])
def test_the_windowed_paths_are_refused_by_name_at_construction(
        tiny, kwargs, reason):
    """Chunked prefill and the speculative step hand a layer several tokens
    a slot over a cache that is already there: `LatentAttention` refuses
    that by name; the fused window reads an int8 pool, which `LatentConfig`
    refuses. The server proves its step before it serves, so the refusal
    stops the construction."""
    with pytest.raises(RuntimeError) as refused:
        _scheduler(tiny, DecodeEngine(tiny["model"], prompt_buckets=BUCKETS),
                   max_slots=2, **kwargs)
    message = str(refused.value)
    assert "serving cannot start" in message and reason in message


def test_tensor_parallel_and_int8_are_refused_by_name(tiny):
    from tf_yarn_tpu.parallel.mesh import MeshSpec, build_mesh

    engine = DecodeEngine(
        tiny["model"], prompt_buckets=BUCKETS,
        mesh=build_mesh(MeshSpec(tp=2), jax.devices()[:2]))
    with pytest.raises(ValueError, match="no head axis.*tp=2"):
        SlotScheduler(engine, tiny["variables"], block_size=BLOCK)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        agent.build_model(_sizes(kv_cache_dtype="int8"))
    with pytest.raises(NotImplementedError, match="one token a slot"):
        jax.eval_shape(lambda: tiny["model"].apply(
            tiny["variables"], jnp.zeros((2, 3), jnp.int32), decode=True,
            paged_ctx=PagedContext(jnp.zeros((2, 20), jnp.int32),
                                   jnp.zeros((2,), jnp.int32)),
            mutable=["cache", "kv_pool"]))


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "softmax"), ("topk_method", "greedy"), ("n_group", 3),
    ("rope_scaling", {"type": "linear", "factor": 40}),
    ("attention_bias", True), ("model_type", "deepseek_v3")])
def test_the_adapter_refuses_what_it_does_not_implement_by_key(key, value):
    with pytest.raises(ValueError, match=key.split(".")[0]):
        agent.build_model(dict(_sizes(), **{key: value}))
