"""A prefill's attention forms scores only over the keys its block of queries
can see (`transformer.key_span`): a window's span in a sliding layer, the
call's own tokens in the Llama-shaped block. Held here: the span against a
brute-force reading of the masks; `own_token_attention` and
`expanded_attention` against the form they had before (scores over every key
of the call, masked: written out below as the parent commit had it), in value
where the span engages and to the letter of the jaxpr where it does not (no
window, a selecting layer, a call no longer than the span); a Llama-shaped
prefill from an empty cache against attention over the whole cache it makes;
the shapes of what a prefill forms; and the host's count of the query-key
pairs formed and visible (`prefill_key_pairs`, `/stats`)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tf_yarn_tpu import telemetry
from tf_yarn_tpu.models import latent, transformer
from tf_yarn_tpu.models.decode_engine import DecodeEngine
from tf_yarn_tpu.models.hybrid import HybridConfig, HybridLM
from tf_yarn_tpu.models.laguna import LagunaConfig, LagunaLM
from tf_yarn_tpu.models.longcat import LongcatConfig, LongcatLM
from tf_yarn_tpu.models.transformer import (
    Attention,
    Transformer,
    TransformerConfig,
    key_span,
    map_query_blocks,
    own_token_attention,
    prefill_key_pairs,
    span_width,
)
from tf_yarn_tpu.serving import SamplingParams, SlotScheduler

# -- the span -----------------------------------------------------------------


# `s` below, at and above each width (128 for the small windows over blocks
# of 8; 384 / 768 for 513 over 8 / 256), and not a multiple of the block.
@pytest.mark.parametrize("s", [100, 128, 300, 768, 1000, 2048])
@pytest.mark.parametrize("block", [8, 256])
@pytest.mark.parametrize("window", [0, 5, 8, 513])
def test_the_span_holds_every_key_a_block_can_see(window, block, s):
    block = min(block, s)
    width = span_width(s, block, window)
    needed = block + window - 1
    if not window or s <= -(-needed // 128) * 128:
        assert width == s
    else:
        assert width % 128 == 0 and needed <= width < needed + 128 and width < s
    keys = np.arange(s)
    for start in range(0, s, block):
        offset, same = key_span(start, s, block, window)
        offset = int(offset)
        assert same == width and 0 <= offset <= s - width
        at = np.arange(start, min(start + block, s))[:, None]
        seen = at >= keys
        if window:
            seen &= at - keys < window
        seen = np.flatnonzero(seen.any(axis=0))
        assert offset <= seen.min() and seen.max() < offset + width


# -- the attention as it was ---------------------------------------------------


def _own_token_attention_before(q, k, v, *, window=0, softmax_scale=None,
                                query_block=256, prompt_len=None):
    """`own_token_attention` of the parent commit (bcc1cda): scores over
    every key of the call, masked."""
    batch, s, heads, dim = q.shape
    n_kv = k.shape[2]
    scale = dim ** -0.5 if softmax_scale is None else softmax_scale
    block = min(query_block, s)
    pad = -s % block
    nb = (s + pad) // block
    grouped = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)]).reshape(
        batch, nb, block, n_kv, heads // n_kv, dim)
    keys = jnp.arange(s)[None, :]

    def some_rows(args):
        start, q_block = args
        at = (start + jnp.arange(block))[:, None]
        mask = at >= keys
        if window:
            mask &= at - keys < window
        with jax.named_scope("attention/scores"):
            scores = jnp.einsum("bqgrd,bkgd->bgrqk", q_block, k,
                                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(mask, scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        with jax.named_scope("attention/values"):
            return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v,
                              preferred_element_type=jnp.float32)

    out = map_query_blocks(
        some_rows, (jnp.arange(nb) * block, jnp.moveaxis(grouped, 1, 0)),
        block, prompt_len)
    return jnp.moveaxis(out, 0, 1).reshape(
        batch, nb * block, heads, dim)[:, :s]


def _expanded_attention_before(q_n, q_r, rows, w_kvb, sizes, *, window=0,
                               select=None, query_block=256,
                               dtype=jnp.bfloat16, prompt_len=None):
    """`latent.expanded_attention` of the parent commit (bcc1cda)."""
    batch, s, heads, _ = q_n.shape
    c = rows[..., :sizes.kv_rank]
    k_r = rows[..., sizes.kv_rank:sizes.row_width]
    with jax.named_scope("latent/kv"):
        expanded = jnp.einsum("bjr,rhf->bjhf", c, w_kvb)
        k_n, v = expanded[..., :sizes.d_nope], expanded[..., sizes.d_nope:]
    block = min(query_block, s)
    pad = -s % block
    nb = (s + pad) // block

    def blocks(value):
        value = jnp.pad(value, [(0, 0), (0, pad)] + [(0, 0)] * (value.ndim - 2))
        return jnp.moveaxis(
            value.reshape((batch, nb, block) + value.shape[2:]), 1, 0)

    scale = sizes.softmax_scale
    keys = jnp.arange(s)[None, :]

    def some_rows(args):
        start, qn_block, qr_block, *index_block = args
        at = (start + jnp.arange(block))[:, None]
        mask = jnp.broadcast_to(at >= keys, (batch, block, s))
        if window:
            mask &= at - keys < window
        if select is not None:
            with jax.named_scope("indexer/scores"):
                score = jnp.where(
                    mask, latent.index_scores(*index_block, select[2]),
                    -jnp.inf)
            with jax.named_scope("indexer/topk"):
                mask &= latent.top_k_mask(score, min(select[3], s))
        with jax.named_scope("latent/scores"):
            scores = (jnp.einsum("bthd,bjhd->bhtj", qn_block, k_n,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bthd,bjd->bhtj", qr_block, k_r,
                                   preferred_element_type=jnp.float32)) * scale
            scores = jnp.where(mask[:, None], scores, -jnp.inf)
            weights = jax.nn.softmax(scores, axis=-1)
        with jax.named_scope("latent/values"):
            return jnp.einsum("bhtj,bjhd->bthd", weights.astype(dtype), v,
                              preferred_element_type=jnp.float32)

    inputs = [jnp.arange(nb) * block, blocks(q_n.astype(dtype)),
              blocks(q_r.astype(dtype))]
    if select is not None:
        inputs += [blocks(select[0]), blocks(select[1])]
    out = map_query_blocks(some_rows, tuple(inputs), block, prompt_len)
    return jnp.moveaxis(out, 0, 1).reshape(
        batch, nb * block, heads, sizes.d_v)[:, :s]


def _normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def _grouped_inputs(s, seed=0):
    rng = np.random.default_rng(seed)
    return (_normal(rng, 2, s, 4, 16), _normal(rng, 2, s, 2, 16),
            _normal(rng, 2, s, 2, 16))


SIZES = latent.AttentionSizes(4, 32, 16, 16, 8, 16, 8e7)


def _latent_inputs(s, seed=0):
    rng = np.random.default_rng(seed)
    return (_normal(rng, 2, s, 4, 16), _normal(rng, 2, s, 4, 8),
            _normal(rng, 2, s, 24), _normal(rng, 16, 4, 32) / 4)


def _select(s, top_k, seed=1):
    rng = np.random.default_rng(seed)
    return (_normal(rng, 2, s, 2, 8), _normal(rng, 2, s, 2),
            _normal(rng, 2, s, 8), top_k)


# (s, window, block): the span is 128 of 300 and of 1000 keys (1000 is not a
# multiple of 8 x 128 nor of 256), 768 of 1000; prompts that end inside the
# first block, inside a later one, and at the call's end.
ENGAGED = [(300, 5, 8), (1000, 8, 8), (1000, 513, 256)]


@pytest.mark.parametrize("told", [None, 3, 0.6, 1.0])
@pytest.mark.parametrize("s,window,block", ENGAGED)
def test_own_token_attention_over_the_span_is_the_masked_form(
        s, window, block, told):
    q, k, v = _grouped_inputs(s)
    assert span_width(s, block, window) < s
    about = dict(window=window, query_block=block)
    want = np.asarray(_own_token_attention_before(q, k, v, **about))
    if told is None:
        got, computed = own_token_attention(q, k, v, **about), s
    else:
        told = told if isinstance(told, int) else int(told * s)
        got = jax.jit(lambda n: own_token_attention(
            q, k, v, prompt_len=n, **about))(told)
        computed = min(s, -(-told // block) * block)
    got = np.asarray(got)
    # outputs of magnitude 1, float32 sums over fewer exact zeros
    np.testing.assert_allclose(got[:, :computed], want[:, :computed],
                               atol=1e-5, rtol=0)
    assert not got[:, computed:].any() and np.isfinite(got).all()


@pytest.mark.parametrize("told", [None, 3, 0.6, 1.0])
@pytest.mark.parametrize("s,window,block", ENGAGED)
def test_expanded_attention_over_the_span_is_the_masked_form(
        s, window, block, told):
    q_n, q_r, rows, w_kvb = _latent_inputs(s)
    about = dict(window=window, query_block=block, dtype=jnp.float32)
    want = np.asarray(_expanded_attention_before(
        q_n, q_r, rows, w_kvb, SIZES, **about))
    if told is None:
        got, computed = latent.expanded_attention(
            q_n, q_r, rows, w_kvb, SIZES, **about), s
    else:
        told = told if isinstance(told, int) else int(told * s)
        got = jax.jit(lambda n: latent.expanded_attention(
            q_n, q_r, rows, w_kvb, SIZES, prompt_len=n, **about))(told)
        computed = min(s, -(-told // block) * block)
    got = np.asarray(got)
    # outputs of magnitude 3
    np.testing.assert_allclose(got[:, :computed], want[:, :computed],
                               atol=1e-5, rtol=0)
    assert not got[:, computed:].any() and np.isfinite(got).all()


def _text(fn, *args):
    return str(jax.make_jaxpr(fn)(*args))


# The whole call is the span: no window (the Llama-shaped block, a full
# layer), or a call no longer than `block + window - 1` in whole lanes.
@pytest.mark.parametrize("told", [False, True])
@pytest.mark.parametrize("s,window,block", [
    (300, 0, 8), (1024, 0, 256), (128, 5, 8), (768, 513, 256), (100, 8, 8)])
def test_where_the_span_is_the_call_the_jaxpr_is_the_parents(
        s, window, block, told):
    assert span_width(s, block, window) == s
    about = dict(window=window, query_block=block)
    length = (jnp.asarray(s - 3, jnp.int32),) if told else ()
    q, k, v = _grouped_inputs(s)

    def now(q, k, v, *n):
        return own_token_attention(q, k, v, prompt_len=n[0] if n else None,
                                   **about)

    def before(q, k, v, *n):
        return _own_token_attention_before(
            q, k, v, prompt_len=n[0] if n else None, **about)

    assert _text(now, q, k, v, *length) == _text(before, q, k, v, *length)
    q_n, q_r, rows, w_kvb = _latent_inputs(s)

    def now(q_n, q_r, rows, w_kvb, *n):
        return latent.expanded_attention(
            q_n, q_r, rows, w_kvb, SIZES, dtype=jnp.float32,
            prompt_len=n[0] if n else None, **about)

    def before(q_n, q_r, rows, w_kvb, *n):
        return _expanded_attention_before(
            q_n, q_r, rows, w_kvb, SIZES, dtype=jnp.float32,
            prompt_len=n[0] if n else None, **about)

    args = (q_n, q_r, rows, w_kvb, *length)
    assert _text(now, *args) == _text(before, *args)


# A selecting layer (dots3's and DeepSeek-V3.2's full layers past
# `index_topk`) is left alone: its top-k needs every causal key's score,
# with a window beside it too.
@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("s,block,top_k", [(300, 8, 24), (1000, 256, 128)])
def test_a_selecting_layer_has_the_parents_jaxpr(s, block, top_k, window):
    q_n, q_r, rows, w_kvb = _latent_inputs(s)
    q_index, weight, keys, top_k = _select(s, top_k)

    def now(q_n, q_r, rows, w_kvb, q_index, weight, keys):
        return latent.expanded_attention(
            q_n, q_r, rows, w_kvb, SIZES, window=window, query_block=block,
            select=(q_index, weight, keys, top_k), dtype=jnp.float32)

    def before(q_n, q_r, rows, w_kvb, q_index, weight, keys):
        return _expanded_attention_before(
            q_n, q_r, rows, w_kvb, SIZES, window=window, query_block=block,
            select=(q_index, weight, keys, top_k), dtype=jnp.float32)

    args = (q_n, q_r, rows, w_kvb, q_index, weight, keys)
    assert _text(now, *args) == _text(before, *args)


# -- the Llama-shaped block ------------------------------------------------------


def _shapes(jaxpr):
    """Every array a jaxpr makes, loops and calls included."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield tuple(var.aval.shape)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _shapes(inner)


CONTEXT = 56    # no other axis of the block below is as long


def _llama_block(dtype, **about):
    cfg = TransformerConfig.tiny(
        d_model=32, n_heads=4, n_kv_heads=2, max_seq_len=CONTEXT,
        dtype=dtype, param_dtype=dtype)
    return cfg, Attention(cfg, decode=True, **about)


# One block of queries, and three with the last short (the block is 256
# where the layer names none: `query_block` 8 stands for it at this size).
@pytest.mark.parametrize("query_block", [0, 8])
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_a_llama_prefill_attends_over_its_own_tokens(dtype, atol, query_block):
    """A prefill from an empty cache against attention over the whole cache
    it has just made (`xla_attention`, what a call onto a cache that is
    already there still runs: the same module handed an empty cache): the
    same output, and the cache it returns bit for bit."""
    _, layer = _llama_block(dtype, query_block=query_block)
    s = 21
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, s, 32)), dtype)
    positions = jnp.broadcast_to(jnp.arange(s), (2, s))
    variables = layer.init(jax.random.key(0), x, positions)
    params = {"params": variables["params"]}
    run = jax.jit(lambda v: layer.apply(v, x, positions, mutable=["cache"]))
    out, made = run(params)
    empty = jax.tree_util.tree_map(jnp.zeros_like, made["cache"])
    over_cache, made_too = run({**params, "cache": empty})
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(over_cache, np.float32),
                               atol=atol, rtol=0)
    before = jax.tree_util.tree_leaves_with_path(made_too["cache"])
    after = jax.tree_util.tree_leaves_with_path(made["cache"])
    assert [path for path, _ in after] == [path for path, _ in before]
    for (path, mine), (_, theirs) in zip(after, before):
        assert mine.dtype == theirs.dtype, path
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    assert int(made["cache"]["cache_index"]) == s
    # The two paths are told apart by their shapes: only the second forms
    # an array as long as the cache beside the cache leaves themselves.
    leaf = (2, CONTEXT, 2, 8)
    fresh = set(_shapes(jax.make_jaxpr(run)(params).jaxpr)) - {leaf}
    onto = set(_shapes(jax.make_jaxpr(run)(
        {**params, "cache": empty}).jaxpr)) - {leaf}
    assert not any(CONTEXT in shape for shape in fresh)
    assert any(CONTEXT in shape for shape in onto)


def test_a_call_onto_a_cache_and_the_int8_cache_keep_the_cache_path():
    """What still attends over the cache: a continuation (more than one
    token onto a cache that is already there), and the int8 cache."""
    cfg, layer = _llama_block(jnp.float32)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(1, 12, 32)),
                    jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(12), (1, 12))
    variables = layer.init(jax.random.key(0), x, positions)
    params = {"params": variables["params"]}
    whole, _ = layer.apply(params, x, positions, mutable=["cache"])
    first, state = layer.apply(params, x[:, :7], positions[:, :7],
                               mutable=["cache"])
    rest, state = layer.apply({**params, "cache": state["cache"]}, x[:, 7:],
                              positions[:, 7:], mutable=["cache"])
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([first, rest], axis=1)), np.asarray(whole),
        atol=1e-5, rtol=0)
    assert int(state["cache"]["cache_index"]) == 12
    int8 = Attention(dataclasses.replace(cfg, kv_cache_dtype="int8"),
                     decode=True)
    shapes = set(_shapes(jax.make_jaxpr(lambda v: int8.apply(
        v, x, positions, mutable=["cache"]))(params).jaxpr))
    assert (1, 2, 2, 12, CONTEXT) in shapes     # [B, Hkv, rep, S, context]


@pytest.mark.parametrize("bucket", [1024, 2048])
def test_a_sliding_layer_forms_no_bucket_long_scores(bucket):
    """Past 768 tokens a window of 512 over blocks of 256 queries forms
    [.., 256, 768] scores, whatever the bucket; the full layer beside it
    forms [.., 256, bucket]."""
    cfg = TransformerConfig.tiny(
        d_model=32, n_heads=4, n_kv_heads=2, max_seq_len=4096,
        dtype=jnp.float32, param_dtype=jnp.float32)
    x = jax.ShapeDtypeStruct((1, bucket, 32), jnp.float32)
    positions = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
    length = jax.ShapeDtypeStruct((), jnp.int32)

    def shapes(window):
        layer = Attention(cfg, decode=True, window=window, query_block=256)
        variables = jax.eval_shape(
            lambda x, p: layer.init(jax.random.key(0), x, p), x, positions)

        def prefill(params, x, positions, n):
            return layer.apply({"params": params}, x, positions,
                               prompt_len=n, mutable=["cache"])

        return set(_shapes(jax.make_jaxpr(prefill)(
            variables["params"], x, positions, length).jaxpr))

    sliding, full = shapes(512), shapes(0)
    assert (1, 2, 2, 256, 768) in sliding
    assert not any(len(shape) == 5 and shape[-1] == bucket for shape in sliding)
    assert (1, 2, 2, 256, bucket) in full


def test_a_latent_sliding_layer_forms_no_bucket_long_scores():
    s = 2048
    avals = [jax.ShapeDtypeStruct(shape, jnp.float32) for shape in (
        (1, s, 4, 16), (1, s, 4, 8), (1, s, 24), (16, 4, 32))]

    def shapes(window):
        return set(_shapes(jax.make_jaxpr(
            lambda *args: latent.expanded_attention(
                *args, SIZES, window=window, query_block=256,
                dtype=jnp.float32))(*avals).jaxpr))

    sliding, full = shapes(513), shapes(0)
    assert (1, 4, 256, 768) in sliding and (1, 4, 256, s) in full
    assert not any(len(shape) == 4 and shape[-1] == s for shape in sliding)


# -- the host's count -----------------------------------------------------------


def _counted_by_hand(s, kept, layers, told):
    """Pair by pair: every (query row, key) of every computed block's span,
    and every pair a prompt row's masks let through."""
    formed = visible = 0
    for window, query_block in layers:
        block = min(query_block or s, s)
        for start in range(0, s, block):
            if told and start >= kept:
                continue
            offset, width = key_span(start, s, block, window)
            formed += sum(1 for _ in range(block)
                          for _ in range(int(offset), int(offset) + width))
        for t in range(kept):
            visible += sum(1 for j in range(t + 1)
                           if not window or t - j < window)
    return formed, visible


MODELS = {
    "laguna": lambda: LagunaLM(LagunaConfig.tiny(
        window=8, query_block=16, max_seq_len=512)),
    "dots3": lambda: latent.LatentLM(latent.LatentConfig.tiny(max_seq_len=512)),
    "longcat": lambda: LongcatLM(LongcatConfig.tiny(max_seq_len=512)),
    "llama": lambda: Transformer(TransformerConfig.tiny(max_seq_len=512)),
    "hybrid": lambda: HybridLM(HybridConfig.tiny()),
}


# Two buckets a model, one at which a window's span is the call and one at
# which it is shorter; the prompt ends inside a block.
@pytest.mark.parametrize("bucket,kept", [(128, 77), (256, 201)])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_hosts_count_is_the_count_by_hand(name, bucket, kept):
    model = MODELS[name]()
    engine = DecodeEngine(model, prompt_buckets=(128, 256))
    layers = engine.contract.prefill_layers
    told = name in ("laguna", "dots3")
    assert engine.contract.takes_prompt_len == told
    windows = {"laguna": [0, 8, 8, 8, 0], "llama": [0, 0], "hybrid": [0],
               "longcat": [0] * 4}
    if name in windows:
        assert [window for window, _ in layers] == windows[name]
    else:
        assert sorted({window for window, _ in layers}) == [
            0, model.config.window]
    got = engine.prefill_key_pairs(bucket, kept)
    assert got == prefill_key_pairs(bucket, kept, layers, told)
    assert got == _counted_by_hand(bucket, kept, layers, told)
    formed, visible = got
    assert formed >= visible > 0


def test_the_llama_ratio_is_what_the_issue_reckoned():
    """Mistral at the 512 bucket: the parent formed 512 x 4096 pairs a head
    and layer over 511 x 512 / 2 visible ones, 16 times; now 512 x 512, 2."""
    formed, visible = prefill_key_pairs(
        512, 511, ((0, transformer.PREFILL_QUERY_BLOCK),) * 8, told=False)
    assert (formed, visible) == (8 * 512 * 512, 8 * 511 * 512 // 2)
    assert round(8 * 512 * 4096 / visible) == 16 and round(formed / visible) == 2


def test_stats_count_the_pairs_of_two_admissions():
    model = Transformer(TransformerConfig.tiny(
        scan_layers=False, dtype=jnp.float32, param_dtype=jnp.float32))
    variables = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    engine = DecodeEngine(model, prompt_buckets=(8, 16, 32))
    scheduler = SlotScheduler(engine, variables, max_slots=2, block_size=8)
    telemetry.get_tracer().clear()
    try:
        assert scheduler.stats()["prefill_keys_formed"] == 0
        rng = np.random.default_rng(2)
        responses = [scheduler.submit(
            rng.integers(1, 256, n).tolist(), SamplingParams(max_new_tokens=2))
            for n in (13, 30)]
        for _ in range(200):
            if all(r.done for r in responses):
                break
            scheduler.tick()
        stats = scheduler.stats()
    finally:
        scheduler.close()
    # the ceiling rule: buckets 16 and 32 keep 12 and 29 rows; two layers
    assert (stats["prefills_ceiling"], stats["prefilled_tokens"]) == (2, 41)
    assert stats["prefill_keys_formed"] == 2 * (16 * 16 + 32 * 32)
    assert stats["prefill_keys_visible"] == 2 * (12 * 13 // 2 + 29 * 30 // 2)
    spans = [s for s in telemetry.get_tracer().records()
             if s.name == "serving/prefill"]
    assert [(s.args["bucket"], s.args["kept"], s.args["prefill_keys_formed"],
             s.args["prefill_keys_visible"]) for s in spans] == [
        (16, 12, 2 * 16 * 16, 12 * 13), (32, 29, 2 * 32 * 32, 29 * 30)]
