"""The shortcut-connected latent model (models/longcat.py: two latent
attention sublayers with a cache leaf each, two dense feed-forwards and an
expert branch that leaves the stream at one sublayer and joins it a sublayer
later, a softmax router wider than the experts that exist) against the plain
reference the benchmark keeps (cellbench/reference/longcat_flash.py), on
logits, at a tiny size on the CPU with seeded weights; and the serving
engine and scheduler for a model whose every cache leaf is paged by token
and has no head axis.

Tolerances. The tiny model runs with `dtype=float32`, so program and
reference do the same float32 arithmetic in another order (the absorbed
products above all). Logits have a standard deviation near 1 and reach 4;
they agree to 3e-6 in every path, and 5e-5 leaves room for longer sums (it
is `tests/test_latent.py`'s, for the same attention). The reference in int8,
the precision below the one the configuration states, moves logits by 0.1 to
0.6 and fails every comparison here; so does each piece of the layer's
mathematics left out (`test_mathematics_left_out_fails`)."""

import dataclasses
import hashlib
import http.client
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import agent, weights
from cellbench.reference import longcat_flash as reference
from tf_yarn_tpu.models import latent, longcat
from tf_yarn_tpu.models.decode_engine import (
    DecodeEngine,
    _decode_cache_aval,
    all_forced,
    build_paged_state_step_fn,
    cache_layout,
    kv_partition_spec,
    paged_pool_avals,
    pool_partition_spec,
)
from tf_yarn_tpu.models.moe import DroplessMoE
from tf_yarn_tpu.models.transformer import PagedContext
from tf_yarn_tpu.serving.request import SamplingParams
from tf_yarn_tpu.serving.scheduler import SlotScheduler
from tests.fakes import admit_prefill

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(ROOT, "cellbench", "tests", "data")
TOLERANCE = 5e-5  # float32 both sides, sums in another order (see above)
SEED = 3_000_000_037
BLOCK = 8
BUCKETS = (8, 16, 32)
CONTEXT, LAYERS, TOP_K, HELD, WIDTH = 128, 2, 4, 8, 24


def _sizes(**model):
    with open(os.path.join(DATA, "tiny_longcat.json")) as fh:
        sizes = json.load(fh)
    sizes["model"] = {"dtype": jnp.float32, "param_dtype": jnp.float32,
                      "query_block": 16, "row_multiple": 8, **model}
    return sizes


@pytest.fixture(scope="module")
def tiny():
    sizes = _sizes()
    model = agent.build_model(sizes)
    config = model.config
    assert (config.n_layers, config.experts_per_token, config.num_experts_here,
            config.max_seq_len, config.stored_width(latent.PLAIN)) == \
        (LAYERS, TOP_K, HELD, CONTEXT, WIDTH)
    # One engine and one jitted step for the whole file: every grid and
    # scheduler below would otherwise compile the same programs again.
    variables = agent.program_variables(model, sizes, SEED)
    engine = DecodeEngine(model, prompt_buckets=BUCKETS)
    return {
        "sizes": sizes, "model": model, "variables": variables,
        "weights": weights.make(sizes, SEED),
        "forward": jax.jit(model.apply),
        "engine": engine,
        "step": jax.jit(build_paged_state_step_fn(
            model, BLOCK, 0.0, None, None, with_logits=True)),
        # The engine's reading of the model (every leaf paged, every row a
        # function of the tokens before it): True, held below.
        "ceiling": engine.ceiling_prefill(variables),
    }


def _kept(prompt_len):
    """Rows an admission keeps: all of the prompt but its last token where
    a bucket (8, 16, 32) lies at or above that, else the largest bucket."""
    return prompt_len - 1 if prompt_len - 1 <= max(BUCKETS) else max(BUCKETS)


def _reference_logits(tiny, tokens, rows, lower=None):
    padded = np.zeros(-(-len(tokens) // 128) * 128, np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(reference.logits(
        tiny["weights"], jnp.asarray(padded), tiny["sizes"],
        jnp.asarray(rows), lower=lower))


# -- the forward pass ---------------------------------------------------------


# 77 is not a multiple of the query block of 16; 128 is the context.
@pytest.mark.parametrize("length", [1, 7, 16, 17, 77, 128])
def test_full_forward_matches_reference(tiny, length):
    tokens = np.random.default_rng(length).integers(0, 256, length)
    got = tiny["forward"](tiny["variables"], jnp.asarray(tokens)[None])[0]
    want = _reference_logits(tiny, tokens, np.arange(length))
    np.testing.assert_allclose(np.asarray(got), want, atol=TOLERANCE, rtol=0)
    lower = _reference_logits(tiny, tokens, np.arange(length), lower="int8")
    assert np.abs(np.asarray(got) - lower).max() > 100 * TOLERANCE


@pytest.mark.parametrize("kernel", [False, True])
def test_absorbed_path_matches_expanded_on_the_plain_kind(kernel):
    """One token against the pool's rows, read through the table as one KV
    head and never expanded (`_step_plain`'s products: the plain gather, and
    the kernel that walks the table, interpreted), equals the last row of
    the expanded path over the same rows."""
    from tf_yarn_tpu.ops.decode_attention import paged_decode_attention

    sizes = latent.AttentionSizes(4, 32, 16, 16, 8, 16, 1e7)
    rng = np.random.default_rng(3)
    s, slots, per_slot = 21, 2, 4
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q_n, q_r = normal(slots, s, 4, 16), normal(slots, s, 4, 8)
    rows, w_kvb = normal(slots, s, WIDTH), normal(16, 4, 32) / 4
    want = latent.expanded_attention(
        q_n, q_r, rows, w_kvb, sizes, query_block=8, dtype=jnp.float32)
    # the rows into a pool of blocks of 8, each slot's in its own order
    tables = np.asarray([[5, 2, 7, 0], [1, 6, 3, 0]], np.int32)
    pool = np.full((9, BLOCK, 1, WIDTH), np.nan, np.float32)
    padded = np.zeros((slots, per_slot * BLOCK, WIDTH), np.float32)
    padded[:, :s] = np.asarray(rows)
    for slot in range(slots):
        for nth, block in enumerate(tables[slot, :3]):
            pool[block, :, 0] = padded[slot, nth * BLOCK:(nth + 1) * BLOCK]
    pool = jnp.asarray(np.nan_to_num(pool)) if not kernel else jnp.asarray(pool)
    query = latent.absorb_query(q_n[:, -1], q_r[:, -1], w_kvb, sizes, WIDTH,
                                jnp.float32)
    mixed = paged_decode_attention(
        query, pool, pool, jnp.asarray(tables),
        jnp.full((slots,), s, jnp.int32), (16 + 8) ** -0.5, kernel=kernel)
    got = latent.expand_values(mixed, w_kvb, sizes, jnp.float32)
    # outputs of magnitude 3, float32 sums in another order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, -1]),
                               atol=1e-5, rtol=0)
    # and the dense path's own composition of the same three
    again = latent.absorbed_attention(
        q_n[:, -1], q_r[:, -1], rows, jnp.ones((slots, s), bool), w_kvb,
        sizes, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(again),
                               atol=1e-5, rtol=0)


# -- the expert branch --------------------------------------------------------


def _branch(rng, d=32, experts=32, zeros=16, width=16):
    w = {
        "router": jnp.asarray(rng.normal(size=(d, experts + zeros)) / 4, jnp.float32),
        "router_bias": jnp.asarray(
            rng.normal(size=(experts + zeros,)) / 400, jnp.float32),
        "w_in": jnp.asarray(rng.normal(size=(experts, d, 2 * width)) / 6, jnp.float32),
        "w_out": jnp.asarray(rng.normal(size=(experts, width, d)) / 4, jnp.float32),
    }
    return w, jnp.asarray(rng.normal(size=(23, d)), jnp.float32)


def _layer(experts, held, offset, zeros, top_k=6, width=16, **more):
    return DroplessMoE(
        num_experts=experts, num_experts_here=held, expert_offset=offset,
        top_k=top_k, d_expert=width, scoring="softmax_all", norm_topk=False,
        routed_scale=6.0, num_zero_experts=zeros, dtype=jnp.float32,
        param_dtype=jnp.float32, **more)


def test_the_shares_add_up_to_the_uncut_layer():
    """32 chips share a layer's 32 experts, one each, under a router 48
    wide. Each share returns its own expert's part of the sum plus the
    identity term, which all compute alike from the router they all hold;
    the 32 routed parts and the identity term counted once are the uncut
    branch of the reference, and so is the whole layer."""
    rng = np.random.default_rng(7)
    w, u = _branch(rng)
    about = dict(top_k=6, experts=32, scale=6.0)
    uncut = np.asarray(reference.moe(u, w, offset=0, **about))
    none_held = dict(w, w_in=w["w_in"][:0], w_out=w["w_out"][:0])
    identity = np.asarray(reference.moe(u, none_held, offset=0, **about))
    # a multiple of u a token, and not a small one
    ratio = identity / np.asarray(u)
    assert np.abs(ratio - ratio[:, :1]).max() < 1e-5 and ratio[:, 0].mean() > 0.3
    # the bias decides: without it other outputs are chosen
    assert np.abs(uncut - np.asarray(reference.moe(
        u, dict(w, router_bias=jnp.zeros((48,))), offset=0, **about))).max() > 1e-2
    total, zero_counts = np.zeros_like(uncut), []
    for share in range(32):
        held = slice(share, share + 1)
        params = {"params": {
            "router": w["router"], "router_bias": w["router_bias"],
            "w_in": w["w_in"][held], "w_out": w["w_out"][held]}}
        out, stats = _layer(32, 1, share, 16).apply(
            params, u, jnp.ones((23,), bool), mutable=["moe_stats"])
        counts = np.asarray(stats["moe_stats"]["counts"][0])
        assert counts.shape == (1 + 1 + 1,) and counts[0] == 23 * 6
        zero_counts.append(int(counts[-1]))
        total += np.asarray(out) - identity
        # and the reference's own share, given the same expert
        mine = reference.moe(
            u, dict(w, w_in=w["w_in"][held], w_out=w["w_out"][held]),
            offset=share, **about)
        np.testing.assert_allclose(np.asarray(out), np.asarray(mine),
                                   atol=2e-5, rtol=0)
    # every share counts the same assignments to zero-compute experts: a
    # third of the router's outputs, about a third of the choices
    assert len(set(zero_counts)) == 1 and 20 < zero_counts[0] < 23 * 6 - 20
    # outputs of magnitude 1 here (the test's own weights), float32 sums
    # in another order: 2e-5; a bfloat16 matmul would miss by 1e-2
    np.testing.assert_allclose(total + identity, uncut, atol=2e-5, rtol=0)
    assert np.abs(total).max() > 0.1 and np.abs(identity).max() > 0.1


def test_the_shares_of_a_whole_layer_add_up(tiny):
    """The same through the layer, whose other sublayers every share runs
    whole: the two halves of the tiny model's 16 experts, each through the
    program's block with the identity term, against the reference's layer
    with all 16 and with none."""
    sizes = dict(tiny["sizes"], n_routed_experts_here=16, num_layers=1)
    table = weights.make(sizes, SEED)
    h = jnp.asarray(np.random.default_rng(5).normal(size=(1, 19, 64)), jnp.float32)
    uncut = np.asarray(reference.layer(table, h[0], 0, sizes))
    none = np.asarray(reference.layer(
        dict(table, w_in=[table["w_in"][0][:0]], w_out=[table["w_out"][0][:0]]),
        h[0], 0, sizes))
    total = np.zeros_like(uncut)
    for offset in (0, 8):
        share = dict(tiny["sizes"], routed_expert_offset=offset)
        model = agent.build_model(share)
        variables = agent.program_variables(model, share, SEED)
        layer = variables["params"]["layer_0"]
        layer["moe"]["w_in"] = table["w_in"][0][offset:offset + 8]
        layer["moe"]["w_out"] = table["w_out"][0][offset:offset + 8]
        block = longcat.ShortcutBlock(model.config)
        got = np.asarray(block.apply({"params": layer}, h)[0])
        total += got - none
    np.testing.assert_allclose(total + none, uncut, atol=TOLERANCE, rtol=0)
    assert np.abs(total).max() > 0.05


def test_all_zero_and_no_zero_choices():
    """A token whose every choice is a zero-compute expert gets `6 sum p_i`
    times itself back and no matrix's product; one that chose none gets no
    multiple of itself."""
    rng = np.random.default_rng(9)
    w, u = _branch(rng)
    layer = _layer(32, 32, 0, 16)
    about = dict(top_k=6, experts=32, offset=0, scale=6.0)

    def run(bias):
        params = {"params": dict(w, router_bias=jnp.asarray(bias, jnp.float32))}
        out, stats = layer.apply(params, u, jnp.ones((23,), bool),
                                 mutable=["moe_stats"])
        return np.asarray(out), np.asarray(stats["moe_stats"]["counts"][0])

    only_zero = np.where(np.arange(48) >= 32, 10.0, 0.0)
    out, counts = run(only_zero)
    p = jax.nn.softmax(u @ w["router"], -1)
    chosen = jax.lax.top_k(p + only_zero, 6)[1]
    assert int(chosen.min()) >= 32
    want = 6.0 * jnp.take_along_axis(p, chosen, -1).sum(-1)[:, None] * u
    np.testing.assert_allclose(out, np.asarray(want), atol=1e-5, rtol=0)
    assert counts[-1] == 23 * 6 and counts[1:-1].sum() == 0
    out, counts = run(-only_zero)
    assert counts[-1] == 0 and counts[1:-1].sum() == 23 * 6
    routed = reference.moe(u, dict(w, router_bias=jnp.asarray(
        -only_zero, jnp.float32)), identity=False, **about)
    np.testing.assert_allclose(out, np.asarray(routed), atol=2e-5, rtol=0)


@pytest.mark.parametrize("what", ["identity", "renormalised", "joined_early"])
def test_mathematics_left_out_fails(tiny, monkeypatch, what):
    """Each piece of the layer that a faster program might drop moves the
    logits far past the tolerance: the identity term left out, the gates
    renormalised over the chosen twelve, the branch joined a sublayer
    early."""
    config = tiny["model"].config
    if what == "identity":
        # the same router, 24 wide, whose last 8 outputs are experts held
        # elsewhere: what they would add is left out
        config = dataclasses.replace(
            config, num_experts=24, num_zero_experts=0)
    elif what == "renormalised":
        config = dataclasses.replace(config, norm_topk=True)
    else:
        class JoinedEarly(longcat.ShortcutBlock):
            """The block with the branch added where it was computed."""

            @longcat.nn.compact
            def __call__(self, h, call=longcat.LayerCall()):
                count_mask, paged_ctx = call.count_mask, call.paged_ctx
                cfg = self.config
                norm = lambda name: longcat.RMSNorm(  # noqa: E731
                    cfg.norm_config(), name=name)
                attend = lambda i, x: latent.LatentAttention(  # noqa: E731
                    cfg, latent.PLAIN, self.decode, name=f"attn_{i}")(
                    norm(f"attn_norm_{i}")(x), paged_ctx, count_mask)
                dense = lambda i, x: longcat.SwiGLU(  # noqa: E731
                    cfg.dense_config(), name=f"dense_{i}")(x)
                h = h + attend(0, h)
                u = norm("ffn_norm_0")(h)
                branch = DroplessMoE(
                    num_experts=cfg.num_experts,
                    num_experts_here=cfg.num_experts_here,
                    top_k=cfg.experts_per_token, d_expert=cfg.d_expert,
                    scoring="softmax_all", norm_topk=False,
                    routed_scale=cfg.routed_scale,
                    num_zero_experts=cfg.num_zero_experts, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name="moe",
                )(u.reshape(-1, u.shape[-1])).reshape(u.shape)
                h = h + dense(0, u) + branch          # a sublayer early
                h = h + attend(1, h)
                return h + dense(1, norm("ffn_norm_1")(h))

        monkeypatch.setattr(longcat, "ShortcutBlock", JoinedEarly)
    model = longcat.LongcatLM(config)
    tokens = np.random.default_rng(1).integers(0, 256, 77)
    got = jax.jit(lambda v, t: model.apply(v, t))(
        tiny["variables"], jnp.asarray(tokens)[None])[0]
    want = _reference_logits(tiny, tokens, np.arange(77))
    assert np.abs(np.asarray(got) - want).max() > 100 * TOLERANCE


@pytest.mark.parametrize("case", ["tiny_granite.float32", "tiny_granite.bfloat16",
                                  "tiny_dots3.float32", "tiny_dots3.bfloat16"])
def test_no_zero_experts_is_bit_for_bit_the_parent(case):
    """`DroplessMoE` with `num_zero_experts = 0` and the scorings it had,
    and `LatentAttention`'s two kinds: the logits and the experts' counts of
    granite's and dots3's tiny configurations, bit for bit what the parent
    commit gave (digests recorded there, tests/fixtures/moe_as_before.json,
    with a probe that tells another machine's arithmetic apart)."""
    with open(os.path.join(ROOT, "tests", "fixtures", "moe_as_before.json")) as fh:
        before = json.load(fh)
    probe = jax.jit(lambda x: jax.nn.softmax(jnp.tanh(x @ x.T) @ x, -1))(
        jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)), jnp.float32))
    if hashlib.sha256(np.asarray(probe).tobytes()).hexdigest() != before["probe"]:
        pytest.skip("another machine's float arithmetic: digests do not carry")
    name, dtype = case.split(".")
    with open(os.path.join(DATA, name + ".json")) as fh:
        sizes = json.load(fh)
    sizes["model"] = dict(sizes["model"], dtype=dtype, param_dtype=dtype)
    model = agent.build_model(sizes)
    variables = agent.program_variables(model, sizes, 37)
    tokens = jnp.asarray(
        np.random.default_rng(37).integers(0, 256, (2, 40)), jnp.int32)
    logits, stats = jax.jit(lambda v, t: model.apply(
        v, t, count_mask=jnp.ones((80,), bool), mutable=["moe_stats"]))(
        variables, tokens)
    counts = np.stack([np.asarray(c) for c in
                       jax.tree_util.tree_leaves(stats["moe_stats"])])
    assert hashlib.sha256(np.asarray(logits.astype(jnp.float32)).tobytes()
                          ).hexdigest() == before[case]["logits"]
    assert hashlib.sha256(counts.astype(np.int64).tobytes()
                          ).hexdigest() == before[case]["counts"]


def test_float32_where_stated_under_bfloat16():
    """At the serving dtype the matrices and the cached rows are bfloat16;
    norm scales and the router's bias stay float32; `hold_params` finds
    nothing to narrow."""
    sizes = _sizes(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    model = agent.build_model(sizes)
    variables = agent.program_variables(model, sizes, SEED)
    for path, leaf in jax.tree_util.tree_leaves_with_path(variables):
        names = [getattr(k, "key", str(k)) for k in path]
        vector = names[-1] in ("scale", "router_bias")
        assert leaf.dtype == (jnp.float32 if vector else jnp.bfloat16), names
    cache = _decode_cache_aval(model, variables)
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        name = getattr(path[-1], "key", str(path[-1]))
        assert leaf.dtype == (jnp.int32 if name == "cache_index"
                              else jnp.bfloat16), name
    logits = jax.jit(model.apply)(variables, jnp.zeros((1, 9), jnp.int32))
    assert logits.dtype == jnp.float32 and bool(jnp.isfinite(logits).all())
    engine = DecodeEngine(model, prompt_buckets=BUCKETS)
    held = engine.hold_params(variables)
    assert engine.stats["params_narrowed"] == 0
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(held),
                                      jax.tree_util.tree_leaves(variables)))


# -- through the engine's pool ------------------------------------------------


class _Grid:
    """The engine's paged pool, driven by hand the way the scheduler drives
    it, with the step's logits read out. Nothing is held once a slot: the
    state beside the pool is a tree of nothing."""

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
        self.step = tiny["step"]

    def admit(self, slot, prompt, shared=(), pad=0):
        """Prefill into the slot's own blocks, as the scheduler admits (the
        bucket above the prompt, padded with `pad`, all but the last token
        kept); with `shared` (blocks of another slot that hold this
        prompt's first tokens) nothing is prefilled and the slot starts at
        their end, as after a prefix hit."""
        variables = self.tiny["variables"]
        blocks = 1 + slot * self.per_slot + np.arange(self.per_slot)
        if len(shared):
            blocks[:len(shared)] = shared
            prefill = len(shared) * BLOCK
        else:
            self.pool, _row, self.bucket, prefill = admit_prefill(
                self.engine, variables, self.pool, prompt, blocks, BLOCK,
                self.tiny["ceiling"], pad)
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
        return logits, counts, dict(zip(self.engine.contract.reads, reads.tolist()))

    def run(self, slot, sequence, prompt_len, shared=(), pad=0):
        """Admit `sequence[:prompt_len]`, then feed the rest a token a
        step; logits of every step, for positions prefill .. len - 1."""
        prefill = self.admit(slot, sequence[:prompt_len], shared, pad)
        rows = [self.advance({slot: sequence[t]})[0][slot]
                for t in range(prefill, len(sequence))]
        return prefill, np.stack(rows)


# Prompt lengths on, just over and just under a prefill bucket (8, 16, 32:
# the prefill takes the bucket at or above the length less one and keeps
# that many rows, so the step takes up at the prompt's last token); 41 has
# no bucket above and prefills 32, replaying 9. Each decodes 9 more.
@pytest.mark.parametrize("prompt_len", [5, 8, 9, 10, 16, 17, 24, 32, 33, 41])
def test_prefill_replay_decode_match_reference(tiny, prompt_len):
    """Bucketed prefill (the expanded path) into the pool, padded past the
    prompt, then the prompt's last token (where no bucket lies above, its
    remainder) and the decode a token a step through the paged step (the
    absorbed path, read through the table), against ONE full forward of the
    reference over the same tokens: the first row is the first generated
    token's logits. The same forward in int8 is far from both."""
    sequence = np.random.default_rng(prompt_len).integers(0, 256, prompt_len + 9)
    grid = _Grid(tiny)
    prefill, got = grid.run(1, sequence, prompt_len, pad=prompt_len)
    assert prefill == _kept(prompt_len)
    assert grid.bucket == min(b for b in BUCKETS if b >= prefill)
    rows = np.arange(prefill, len(sequence))
    want = _reference_logits(tiny, sequence, rows)
    np.testing.assert_allclose(got, want, atol=TOLERANCE, rtol=0)
    lower = _reference_logits(tiny, sequence, rows, lower="int8")
    assert np.abs(got - lower).max() > 100 * TOLERANCE


# One under, at and one over a bucket (the rows kept are the prompt's less
# one), and under the least bucket.
@pytest.mark.parametrize("prompt_len", [4, 16, 17, 18])
def test_the_pad_of_a_ceiling_prefill_is_invisible(tiny, prompt_len):
    """Two admissions of one prompt, padded to its bucket with different
    tokens, leave the kept rows of all four leaves bitwise equal (one
    program; a latent row is a function of the tokens before it, and a
    dropless expert takes each token alone); every block but the slot's
    own and the trash block stays as it was."""
    assert tiny["ceiling"] is True
    prompt = np.random.default_rng(prompt_len).integers(0, 256, prompt_len)
    grids = [_Grid(tiny), _Grid(tiny)]
    for grid, pad in zip(grids, (0, 255)):
        assert grid.admit(1, prompt, pad=pad) == prompt_len - 1
    kept = prompt_len - 1
    own = grids[0].tables[1, :-(-kept // BLOCK)]
    others = np.setdiff1d(np.arange(1, 3 * grids[0].per_slot + 1), own)
    leaves = [[np.asarray(leaf)[0] for leaf in
               jax.tree_util.tree_leaves(grid.pool)] for grid in grids]
    assert len(leaves[0]) == 2 * LAYERS
    for first, second in zip(*leaves):
        rows = [leaf[own].reshape(-1, WIDTH)[:kept] for leaf in (first, second)]
        np.testing.assert_array_equal(rows[0], rows[1])
        assert np.abs(rows[0]).max() > 0
        assert not first[others].any() and not second[others].any()


def test_a_long_sequence_in_a_slot_that_held_a_longer_one(tiny):
    """120 tokens in a slot whose blocks held a longer request before:
    stale rows everywhere past the slot's length, none read."""
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
    say what it routed and read."""
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
    # two active slots, two expert layers, top 4; the held experts' loads
    # and the zero-compute experts' share are what is left of 8 choices
    assert counts.shape == (LAYERS, 1 + HELD + 1) and (counts[:, 0] == 8).all()
    assert ((counts[:, 1:].sum(axis=1) <= 8) & (counts[:, -1] >= 0)).all()
    # the last step: slot 0 at 41 + 1 live rows, slot 2 at 19 + 1, in each
    # of four sublayers; the plain gather reads the whole table
    assert (p1, p2) == (32, 10)
    assert reads == {"latent_live": 4 * (42 + 20),
                     "latent_read": 4 * 2 * CONTEXT}
    for got, sequence, start in ((got1, first, p1), (got2, second, p2)):
        want = _reference_logits(tiny, sequence[:start + 10],
                                 np.arange(start, start + 10))
        np.testing.assert_allclose(np.stack(got), want, atol=TOLERANCE, rtol=0)
    grid.retire(0)
    kept, reused = grid.run(0, third, 6)     # 5 kept of the bucket of 8
    _, alone = _Grid(tiny).run(0, third, 6)
    np.testing.assert_allclose(reused, alone, atol=1e-6, rtol=0)
    want = _reference_logits(tiny, third, np.arange(kept, len(third)))
    np.testing.assert_allclose(reused, want, atol=TOLERANCE, rtol=0)


def test_the_two_sublayers_leaves_never_alias(tiny):
    """Two leaves of one name under one layer: rows written into sublayer
    0's leaf change what sublayer 0 reads and nothing of sublayer 1's leaf;
    the step after reads each its own."""
    sequence = np.random.default_rng(3).integers(0, 256, 30)
    grid = _Grid(tiny)
    prefill = grid.admit(1, sequence[:20])
    logits = grid.advance({1: sequence[prefill]})[0][1]
    leaves = {name: grid.pool["layer_0"][name]["latent"]
              for name in ("attn_0", "attn_1")}
    assert leaves["attn_0"] is not leaves["attn_1"]
    assert leaves["attn_0"].shape == leaves["attn_1"].shape == \
        (1, 3 * CONTEXT // BLOCK + 1, BLOCK, WIDTH)
    live = 1 + 1 * grid.per_slot + np.arange(3)  # the slot's first blocks
    rows0, rows1 = (np.asarray(leaves[n])[0, live] for n in ("attn_0", "attn_1"))
    assert np.abs(rows0 - rows1).max() > 0.1  # each its own weights' rows

    def again(pool):
        twin = _Grid(tiny)
        twin.pool, twin.tables, twin.lengths = pool, grid.tables.copy(), \
            np.where(np.arange(3) == 1, prefill, 0).astype(np.int32)
        return twin.advance({1: sequence[prefill]})[0][1], twin.pool

    copy = jax.tree_util.tree_map(jnp.copy, grid.pool)
    same, _ = again(jax.tree_util.tree_map(jnp.copy, grid.pool))
    np.testing.assert_allclose(same, logits, atol=1e-6, rtol=0)
    # overwrite sublayer 0's rows of the slot: its read moves the logits,
    # sublayer 1's leaf comes back untouched but for the step's own row
    copy["layer_0"]["attn_0"]["latent"] = \
        copy["layer_0"]["attn_0"]["latent"].at[0, live].add(1.0)
    moved, after = again(copy)
    assert np.abs(moved - logits).max() > 100 * TOLERANCE
    written = np.asarray(after["layer_0"]["attn_1"]["latent"])[0, live]
    at = (prefill // BLOCK, prefill % BLOCK)
    mask = np.ones(written.shape[:2], bool)
    mask[at] = False
    np.testing.assert_array_equal(written[mask], rows1[mask])


def test_a_prefix_hit_gives_the_logits_of_a_miss(tiny):
    """The prefix cache moves whole blocks and nothing else, and a latent
    row is a function of the tokens before it: a slot that starts on
    another's first two blocks, as after a hit of 16 tokens, replays the
    rest and gives the logits a slot that prefilled its own gives."""
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, 256, 29)
    tail_a, tail_b = rng.integers(0, 256, 6), rng.integers(0, 256, 6)
    grid = _Grid(tiny)
    donor = np.concatenate([prompt, tail_a])
    grid.run(0, donor, 29)
    hit = grid.tables[0, :2].copy()
    sequence = np.concatenate([prompt[:20], tail_b])   # shares 20 tokens
    start, through_hit = grid.run(2, sequence, 20, shared=hit)
    assert start == 16
    own, missed = _Grid(tiny).run(2, sequence, 20)     # prefills 19 itself
    assert own == 19
    np.testing.assert_allclose(through_hit[own - start:], missed,
                               atol=1e-5, rtol=0)
    want = _reference_logits(tiny, sequence, np.arange(16, len(sequence)))
    np.testing.assert_allclose(through_hit, want, atol=TOLERANCE, rtol=0)
    # the donor's blocks are as they were: it goes on as if alone
    more = grid.advance({0: 7})[0][0]
    alone = _Grid(tiny)
    alone.run(0, donor, 29)
    np.testing.assert_allclose(more, alone.advance({0: 7})[0][0],
                               atol=1e-6, rtol=0)


# -- the leaves as the engine sees them --------------------------------------


def test_every_leaf_is_paged_and_has_no_head_axis(tiny):
    model, variables = tiny["model"], tiny["variables"]
    row = _decode_cache_aval(model, variables)
    layout = cache_layout(model, row)
    flat = lambda tree: jax.tree_util.tree_leaves(  # noqa: E731
        tree, is_leaf=lambda x: x is None)
    pool = paged_pool_avals(model, row, 9, BLOCK)
    paged = 0
    for lay, aval, pooled in zip(flat(layout), flat(row), flat(pool)):
        if lay.kind == "paged":
            paged += 1
            assert (lay.name, aval.shape) == ("latent", (1, CONTEXT, WIDTH))
            assert pooled.shape == (1, 9, BLOCK, WIDTH)
            assert kv_partition_spec(aval.shape, lay, 1) == \
                jax.sharding.PartitionSpec()
            for spec in (kv_partition_spec, pool_partition_spec):
                with pytest.raises(ValueError, match="latent.*no head axis"):
                    spec(aval.shape, lay, 2)
        else:
            assert (lay.kind, lay.name, pooled) == ("index", "cache_index", None)
    assert paged == 2 * LAYERS
    engine = DecodeEngine(model, prompt_buckets=BUCKETS)
    assert engine.slot_state_leaves(variables) == ()
    assert engine.counted_step(variables) is True
    pool = engine.make_paged_pool(variables, 4 * CONTEXT // BLOCK + 1, BLOCK)
    state = engine.make_slot_state(variables, 4)
    by_kind = engine.cache_bytes_by_kind(variables, pool, state)
    assert by_kind == {"paged": 2 * LAYERS * (4 * CONTEXT + BLOCK) * WIDTH * 4}
    # the rows are one KV head of their width to the shared op: its choice
    # is the engine's, the plain gather off the TPU
    assert engine.paged_attention_kernel(pool) is False
    assert engine.stats["paged_attention"] == "plain"
    assert engine.paged_attention_chunk(BLOCK) == CONTEXT


def test_a_transformer_keeps_the_plain_step():
    from tf_yarn_tpu.models.transformer import Transformer, TransformerConfig

    model = Transformer(TransformerConfig.tiny(scan_layers=False))
    variables = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    engine = DecodeEngine(model, prompt_buckets=BUCKETS)
    assert engine.counted_step(variables) is False


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


def _first_choices(tiny, prompt, tokens):
    sequence = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    want = _reference_logits(
        tiny, sequence, np.arange(len(prompt) - 1, len(sequence)))
    return want.max(-1) - want[np.arange(len(tokens)), tokens]


def test_scheduler_serves_through_reused_slots(tiny):
    """Five requests through two slots give what each gives alone and what
    the reference puts first; the counters say what happened."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 256, n) for n in (9, 40, 5, 33, 17)]
    together = _scheduler(tiny, max_slots=2)
    served = _serve(together, prompts, new_tokens=30)
    for prompt, tokens in zip(prompts, served):
        assert _serve(_scheduler(tiny, max_slots=2), [prompt], 30) == [tokens]
        assert _first_choices(tiny, prompt, tokens).max() <= TOLERANCE
    stats = together.stats()
    # 9, 5, 33 and 17 took the bucket at or above their length less one and
    # replayed their last token; 40 has no bucket above 39: the floor, 32
    assert (stats["prefills_ceiling"], stats["prefills_floor"]) == (4, 1)
    assert stats["prefill_pad_tokens"] == 8 - 4
    assert stats["prefilled_tokens"] == 8 + 32 + 4 + 32 + 16
    assert stats["prefill_tokens"] == 4 * 1 + (40 - 32)
    assert stats["state_leaves"] == [] and stats["state_bytes"] == 0
    assert stats["state_resets"] == 0 and stats["prefix_skipped_stateful"] == 0
    assert stats["block_pool"]["used_blocks"] == \
        stats["prefix_cache"]["cached_blocks"] > 0
    paged = 2 * LAYERS * (2 * CONTEXT + BLOCK) * WIDTH * 4
    assert stats["cache_bytes_by_kind"] == {"paged": paged}
    assert stats["cache_hbm_bytes"] == stats["kv_cache_hbm_bytes"] == paged
    steps = stats["slot_steps"]
    # a layer-step a launched step, not a tick: a tick that only reads the
    # step in flight launches none
    assert stats["moe_layer_steps"] == LAYERS * stats["steps"]
    assert stats["steps"] < stats["ticks"]
    assert stats["moe_assignments"] == LAYERS * TOP_K * steps
    # 8 of the router's 24 outputs are held here and 8 return their input
    assert 0 < stats["moe_assignments_here"] < stats["moe_assignments"]
    assert 0 < stats["moe_assignments_zero"] < stats["moe_assignments"]
    assert stats["moe_assignments_here"] + stats["moe_assignments_zero"] \
        < stats["moe_assignments"]
    assert 0 < stats["moe_experts_touched_per_layer_step"] <= HELD
    # every slot-step: four sublayers' live rows, read as the whole table
    assert stats["latent_live_token_steps"] == \
        2 * LAYERS * (stats["kv_token_steps"] + steps)
    assert stats["latent_read_token_steps"] == 2 * LAYERS * CONTEXT * steps
    assert stats["kv_read_token_steps"] == CONTEXT * steps
    together.close()


@pytest.mark.parametrize("prompt_len", [15, 16, 18])
def test_scheduler_tokens_past_a_padded_prefill_are_the_references(
        tiny, prompt_len):
    """One under, at and one over a bucket: the scheduler's greedy tokens
    behind an admission that padded the prompt to the bucket above are the
    reference's first choices, the first generated token included."""
    prompt = np.random.default_rng(40 + prompt_len).integers(0, 256, prompt_len)
    scheduler = _scheduler(tiny, max_slots=2)
    tokens, = _serve(scheduler, [prompt], new_tokens=8)
    assert _first_choices(tiny, prompt, np.asarray(tokens)).max() <= TOLERANCE
    stats = scheduler.stats()
    bucket = 16 if prompt_len <= 17 else 32
    assert (stats["prefills_ceiling"], stats["prefilled_tokens"],
            stats["prefill_pad_tokens"], stats["prefill_tokens"]) == \
        (1, prompt_len - 1, bucket - (prompt_len - 1), 1)
    scheduler.close()


def test_the_same_prompt_again_is_served_through_a_prefix_hit(tiny):
    """Nothing of this model is held once a slot, so the prefix cache is on
    for it: the second request starts on the first's blocks, prefills
    nothing, and is served the same tokens, the reference's first choices."""
    prompt = np.random.default_rng(2).integers(0, 256, 24)
    scheduler = _scheduler(tiny, max_slots=2)
    first, second = _serve(scheduler, [prompt]), _serve(scheduler, [prompt])
    assert first == second
    assert _first_choices(tiny, prompt, np.asarray(second[0])).max() <= TOLERANCE
    stats = scheduler.stats()
    assert stats["prefix_cache"]["hits"] == 1
    assert stats["prefix_skipped_stateful"] == 0
    # the first kept 23 rows of the bucket of 32 and offered their two
    # whole blocks; the second hit those 16 tokens and replayed 8
    assert stats["prefilled_tokens"] == 23
    assert (stats["prefills_ceiling"], stats["prefills_floor"],
            stats["prefill_pad_tokens"]) == (1, 0, 32 - 23)
    assert stats["prefill_tokens"] == 1 + 8
    scheduler.close()


def test_suspend_resume_and_block_shipping_move_whole_blocks(tiny):
    """The host swap tier and /v1/blocks' export and import gather and
    scatter whole blocks of every paged leaf: a suspended stream resumes to
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
    that by name (it would take them for a prefill from nothing); the fused
    window reads an int8 pool, which `LatentConfig` refuses. A server for
    this model proves the step its ticks will take before it serves, so the
    refusal stops the construction."""
    with pytest.raises(RuntimeError) as refused:
        _scheduler(tiny, DecodeEngine(tiny["model"], prompt_buckets=BUCKETS),
                   max_slots=2, **kwargs)
    message = str(refused.value)
    assert "serving cannot start" in message and "windowed step" in message
    assert reason in message and "every cache leaf paged" in message


def test_tensor_parallel_and_int8_are_refused_by_name(tiny):
    from tf_yarn_tpu.parallel.mesh import MeshSpec, build_mesh

    engine = DecodeEngine(
        tiny["model"], prompt_buckets=BUCKETS,
        mesh=build_mesh(MeshSpec(tp=2), jax.devices()[:2]))
    with pytest.raises(ValueError, match="latent.*no head axis.*tp=2"):
        SlotScheduler(engine, tiny["variables"], block_size=BLOCK)
    with pytest.raises(ValueError, match="kv_cache_dtype='int8'.*no head axis"):
        longcat.LongcatConfig.tiny(kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        agent.build_model(_sizes(kv_cache_dtype="int8"))
    with pytest.raises(ValueError, match="every layer of this model"):
        longcat.LongcatConfig.tiny(layer_types=(latent.FULL, latent.PLAIN))
    # and the model itself, handed a window of tokens over the pool
    with pytest.raises(NotImplementedError, match="one token a slot"):
        jax.eval_shape(lambda: tiny["model"].apply(
            tiny["variables"], jnp.zeros((2, 3), jnp.int32), decode=True,
            paged_ctx=PagedContext(jnp.zeros((2, 16), jnp.int32),
                                   jnp.zeros((2,), jnp.int32)),
            mutable=["cache", "kv_pool"]))


def test_the_step_is_proved_once_at_construction(tiny):
    scheduler = _scheduler(
        tiny, DecodeEngine(tiny["model"], prompt_buckets=BUCKETS), max_slots=2)
    assert scheduler.engine.stats["paged_step_compiles"] == 1
    assert scheduler.engine.stats["paged_attention"] == "plain"
    _serve(scheduler, [np.arange(12)])
    assert scheduler.engine.stats["paged_step_compiles"] == 1
    scheduler.close()


def test_http_end_to_end_through_two_reused_slots(tiny):
    """`/v1/generate` over the real frontend, four requests through two
    slots: each is served the reference's first choices, a reused slot never
    reads its predecessor's rows, and `/stats` carries the new counters."""
    from tf_yarn_tpu.serving.server import ServingServer

    scheduler = _scheduler(tiny, max_slots=2)
    scheduler.start()
    server = ServingServer(scheduler, "127.0.0.1", 0)
    server.start()

    def call(method, path, body=None):
        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=120)
        try:
            conn.request(method, path, body and json.dumps(body),
                         {"Content-Type": "application/json"})
            response = conn.getresponse()
            return response.status, json.loads(response.read())
        finally:
            conn.close()

    try:
        rng = np.random.default_rng(19)
        prompts = [rng.integers(0, 256, n) for n in (37, 9, 21, 12)]
        results = {}

        def post(index):
            results[index] = call("POST", "/v1/generate", {
                "prompt": prompts[index].tolist(), "max_new_tokens": 20})

        threads = [threading.Thread(target=post, args=(i,)) for i in range(4)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=300)
        for index, prompt in enumerate(prompts):
            status, body = results[index]
            assert status == 200 and len(body["tokens"]) == 20
            assert _first_choices(
                tiny, prompt, np.asarray(body["tokens"])).max() <= TOLERANCE
        status, stats = call("GET", "/stats")
        assert status == 200
        for key in ("moe_assignments", "moe_assignments_here",
                    "moe_assignments_zero", "moe_experts_touched",
                    "latent_live_token_steps", "latent_read_token_steps",
                    "cache_hbm_bytes", "cache_bytes_by_kind"):
            assert key in stats, key
        assert stats["state_leaves"] == []
        assert stats["decode_engine"]["paged_attention"] == "plain"
        assert stats["decode_engine"]["params_narrowed"] == 0 \
            if "params_narrowed" in stats["decode_engine"] else True
    finally:
        server.stop()
        scheduler.close()
