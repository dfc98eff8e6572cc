"""Attention-backend correctness: flash (pallas, interpret on CPU) and
ring (shard_map over sp) must match the XLA reference exactly enough."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tf_yarn_tpu.ops.attention import attention, xla_attention
from tf_yarn_tpu.ops.flash_attention import flash_attention
from tf_yarn_tpu.parallel import mesh as mesh_lib
from tf_yarn_tpu.parallel.mesh import MeshSpec, build_mesh, select_devices
from tf_yarn_tpu.parallel.ring_attention import ring_attention_sharded


def _qkv(b=2, s=64, h=4, hkv=4, d=16, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3, dtype)
    return mk(b, s, h, d), mk(b, s, hkv, d), mk(b, s, hkv, d)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_xla(causal):
    q, k, v = _qkv()
    ref = xla_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_gqa():
    q, k, v = _qkv(h=8, hkv=2)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_indivisible_seq_rejected():
    q, k, v = _qkv(s=60)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, block_q=32, block_k=32)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_matches_xla(causal):
    q, k, v = _qkv(s=32)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        return (out * jnp.cos(out)).sum()  # non-trivial cotangent

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    ref_grads = jax.grad(
        lambda q, k, v: (lambda o: (o * jnp.cos(o)).sum())(
            xla_attention(q, k, v, causal=causal)
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-4)


def test_flash_backward_gqa():
    q, k, v = _qkv(s=64, h=8, hkv=2)

    def loss(fn):
        def inner(q, k, v):
            return fn(q, k, v).sum()
        return inner

    grads = jax.grad(
        loss(lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=32, block_k=32)),
        argnums=(0, 1, 2),
    )(q, k, v)
    ref_grads = jax.grad(
        loss(lambda q, k, v: xla_attention(q, k, v, causal=True)), argnums=(0, 1, 2)
    )(q, k, v)
    for g, r in zip(grads, ref_grads):
        assert g.shape == r.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_cross_lengths(causal):
    # s_q != s_kv (encoder-decoder shape); the causal case has kv blocks
    # entirely beyond the last q row (dead-block index clamping).
    rng = np.random.RandomState(3)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3)
    q, k, v = mk(2, 32, 4, 16), mk(2, 64, 4, 16), mk(2, 64, 4, 16)

    grads = jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=16, block_k=32
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    ref_grads = jax.grad(
        lambda q, k, v: xla_attention(q, k, v, causal=causal).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_xla_sp8(causal):
    devices = select_devices(8, platform="cpu")
    mesh = build_mesh(MeshSpec(sp=8), devices)
    mesh_lib.set_current_mesh(mesh)
    try:
        q, k, v = _qkv(b=2, s=64, h=4, d=16)
        ref = xla_attention(q, k, v, causal=causal)
        out = ring_attention_sharded(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    finally:
        mesh_lib.set_current_mesh(None)


def test_ring_attention_mixed_mesh_gqa():
    devices = select_devices(8, platform="cpu")
    mesh = build_mesh(MeshSpec(dp=2, sp=2, tp=2), devices)
    mesh_lib.set_current_mesh(mesh)
    try:
        q, k, v = _qkv(b=4, s=32, h=4, hkv=2, d=8)
        ref = xla_attention(q, k, v, causal=True)
        out = ring_attention_sharded(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    finally:
        mesh_lib.set_current_mesh(None)


def test_ring_attention_no_mesh_falls_back():
    mesh_lib.set_current_mesh(None)
    q, k, v = _qkv(s=16)
    ref = xla_attention(q, k, v, causal=True)
    out = ring_attention_sharded(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_xla_sp8(causal):
    from tf_yarn_tpu.parallel.ulysses import ulysses_attention_sharded

    devices = select_devices(8, platform="cpu")
    mesh = build_mesh(MeshSpec(sp=8), devices)
    mesh_lib.set_current_mesh(mesh)
    try:
        q, k, v = _qkv(b=2, s=64, h=8, d=16)
        ref = xla_attention(q, k, v, causal=causal)
        out = ulysses_attention_sharded(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    finally:
        mesh_lib.set_current_mesh(None)


def test_ulysses_mixed_mesh_gqa_expands_kv():
    # Per sp-shard, hkv (2/tp = 1) does not divide sp=2 — exercises the
    # GQA expand-then-split path.
    from tf_yarn_tpu.parallel.ulysses import ulysses_attention_sharded

    devices = select_devices(8, platform="cpu")
    mesh = build_mesh(MeshSpec(dp=2, sp=2, tp=2), devices)
    mesh_lib.set_current_mesh(mesh)
    try:
        q, k, v = _qkv(b=4, s=32, h=4, hkv=2, d=8)
        ref = xla_attention(q, k, v, causal=True)
        out = ulysses_attention_sharded(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    finally:
        mesh_lib.set_current_mesh(None)


def test_ulysses_flash_inner_matches_xla():
    # attention_impl="ulysses_flash": the pallas kernel (interpret mode on
    # CPU) runs inside each head shard after the all_to_all.
    devices = select_devices(8, platform="cpu")
    mesh = build_mesh(MeshSpec(dp=2, sp=4), devices)
    mesh_lib.set_current_mesh(mesh)
    try:
        q, k, v = _qkv(b=2, s=128, h=4, d=16)
        ref = xla_attention(q, k, v, causal=True)
        out = attention(q, k, v, impl="ulysses_flash", causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    finally:
        mesh_lib.set_current_mesh(None)


def test_ulysses_no_mesh_falls_back():
    from tf_yarn_tpu.parallel.ulysses import ulysses_attention_sharded

    mesh_lib.set_current_mesh(None)
    q, k, v = _qkv(s=16)
    ref = xla_attention(q, k, v, causal=True)
    out = ulysses_attention_sharded(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_transformer_with_ulysses_attention_trains():
    from tf_yarn_tpu.experiment import as_core_experiment
    from tf_yarn_tpu.models import transformer
    from tf_yarn_tpu.training import train_and_evaluate

    cfg = transformer.TransformerConfig.tiny(attention_impl="ulysses")
    exp = transformer.make_experiment(
        cfg, train_steps=4, batch_size=4, seq_len=32,
        mesh_spec=MeshSpec(dp=2, sp=4),
    )
    metrics = train_and_evaluate(
        as_core_experiment(exp), devices=select_devices(8, platform="cpu")
    )
    assert np.isfinite(metrics["loss"])


def test_attention_dispatcher():
    q, k, v = _qkv(s=32)
    ref = xla_attention(q, k, v, causal=True)
    out = attention(q, k, v, impl="flash", causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    with pytest.raises(ValueError, match="unknown attention impl"):
        attention(q, k, v, impl="nope")


def test_transformer_with_ring_attention_trains():
    from tf_yarn_tpu.experiment import as_core_experiment
    from tf_yarn_tpu.models import transformer
    from tf_yarn_tpu.training import train_and_evaluate

    cfg = transformer.TransformerConfig.tiny(attention_impl="ring")
    exp = transformer.make_experiment(
        cfg, train_steps=4, batch_size=4, seq_len=32,
        mesh_spec=MeshSpec(dp=2, sp=4),
    )
    metrics = train_and_evaluate(
        as_core_experiment(exp), devices=select_devices(8, platform="cpu")
    )
    assert np.isfinite(metrics["loss"])


@pytest.mark.parametrize("layout", [{"dp": 8}, {"dp": 4, "tp": 2}])
def test_flash_attention_runs_per_shard_under_the_run_mesh(layout):
    """Under the run's mesh the flash kernels run on each device's own
    shard (forward AND the custom_vjp backward) instead of XLA
    replicating the opaque custom calls: the batch splits over the data
    axes and, where both head counts divide, the heads over tp — GQA
    groups staying with their KV heads."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tf_yarn_tpu.ops.attention import attention
    from tf_yarn_tpu.ops.flash_attention import flash_attention
    from tf_yarn_tpu.parallel import mesh as mesh_lib

    devices = mesh_lib.select_devices(8, platform="cpu")
    mesh = Mesh(np.array(devices).reshape(tuple(layout.values())),
                tuple(layout))
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(8, 64, 4, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(8, 64, 2, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(8, 64, 2, 16).astype(np.float32))
    sh = NamedSharding(mesh, P("dp", None, None, None))
    qs, ks, vs = (jax.device_put(t, sh) for t in (q, k, v))

    def loss(fn):
        # Weighted, so that every head's gradient differs.
        w = jnp.arange(4, dtype=jnp.float32)[None, None, :, None] + 1.0
        return lambda q, k, v: (fn(q, k, v) * w).sum()

    with mesh_lib.use_mesh(mesh):
        out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
            qs, ks, vs)
        grads = jax.jit(jax.grad(loss(
            lambda q, k, v: flash_attention(q, k, v, causal=True)),
            argnums=(0, 1, 2)))(qs, ks, vs)
    assert out.sharding.spec[0] == "dp", out.sharding
    assert grads[0].sharding.spec[0] == "dp", grads[0].sharding
    if "tp" in layout:
        assert out.sharding.spec[2] == "tp", out.sharding
    ref = attention(q, k, v, impl="xla", causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)
    grefs = jax.grad(loss(
        lambda q, k, v: attention(q, k, v, impl="xla", causal=True)),
        argnums=(0, 1, 2))(q, k, v)
    for grad, gref in zip(grads, grefs):
        np.testing.assert_allclose(
            np.asarray(grad), np.asarray(gref), atol=2e-2)


@pytest.mark.parametrize("causal", [True, False])
def test_key_padding_mask_matches_truncated(causal):
    """Masked-out trailing keys must be invisible: queries over the real
    prefix produce the same output as attention over the truncated
    sequence (the padded-batch encoder contract)."""
    q, k, v = _qkv(b=2, s=64)
    real = 40
    mask = jnp.zeros((2, 64), jnp.int32).at[:, :real].set(1)
    full = attention(q, k, v, impl="xla", causal=causal,
                     key_padding_mask=mask)
    trunc = attention(q[:, :real], k[:, :real], v[:, :real], impl="xla",
                      causal=causal)
    np.testing.assert_allclose(
        np.asarray(full[:, :real]), np.asarray(trunc), atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_fully_padded_row_outputs_zero(causal):
    """A batch row whose key_padding_mask is all zeros has no real keys:
    its outputs must be exactly zero, not the silent uniform softmax
    over finfo.min logits (ADVICE r5 item 4). Rows with real keys are
    unaffected."""
    q, k, v = _qkv(b=3, s=16)
    mask = jnp.ones((3, 16), jnp.int32).at[1].set(0)  # row 1 fully padded
    out = attention(q, k, v, impl="xla", causal=causal,
                    key_padding_mask=mask)
    np.testing.assert_array_equal(np.asarray(out[1]), 0.0)
    # the live rows match a run without the dead row
    ref = attention(q[::2], k[::2], v[::2], impl="xla", causal=causal,
                    key_padding_mask=mask[::2])
    np.testing.assert_allclose(np.asarray(out[::2]), np.asarray(ref),
                               atol=1e-6)


def test_key_padding_mask_rejected_on_kernel_impls():
    q, k, v = _qkv()
    mask = jnp.ones((2, 64), jnp.int32)
    for impl in ("flash", "ring", "ulysses"):
        with pytest.raises(NotImplementedError, match="key_padding_mask"):
            attention(q, k, v, impl=impl, key_padding_mask=mask)


# --------------------------------------------------------------------------
# The GQA contraction of `xla_attention`: query heads viewed as
# [H_kv, rep] against K/V as the cache holds them, no repeated copy.
# --------------------------------------------------------------------------

def _repeat_reference(q, k, v, *, causal, offset=0, mask=None, scale=None):
    """Plain float32 attention with the KV heads repeated explicitly: query
    head h reads KV head h // rep."""
    q, k, v = (np.asarray(x, np.float32) for x in (q, k, v))
    rep = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, rep, axis=2), np.repeat(v, rep, axis=2)
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    logits = np.einsum("bqhd,bkhd->bhqk", q, k) * scale
    keep = np.ones(logits.shape, bool)
    if causal:
        q_pos = np.arange(q.shape[1])[:, None] + offset
        keep &= (q_pos >= np.arange(k.shape[1])[None, :])[None, None]
    if mask is not None:
        keep &= np.asarray(mask, bool)[:, None, None, :]
    logits = np.where(keep, logits, -np.inf)
    top = np.max(logits, axis=-1, keepdims=True)
    weights = np.exp(logits - np.where(np.isfinite(top), top, 0.0))
    total = weights.sum(axis=-1, keepdims=True)
    probs = np.divide(weights, total, out=np.zeros_like(weights),
                      where=total > 0)  # a row with no key at all: zeros
    return np.einsum("bhqk,bkhd->bqhd", probs, v)


def _padding(batch, s_kv):
    """Row 0 whole, row 1 with a padded tail, the last row fully padded."""
    mask = np.ones((batch, s_kv), bool)
    mask[1, s_kv - 5:] = False
    mask[-1] = False
    return mask


# name -> (S_q, S_kv, keyword arguments of xla_attention)
_GQA_CASES = {
    "decode": (1, 24, dict(causal=True, segment_offset=17)),
    "prefill_against_cache": (5, 24, dict(causal=True, segment_offset=7)),
    "square_causal": (16, 16, dict(causal=True)),
    "square_full": (16, 16, dict(causal=False)),
    "padding_mask": (16, 16, dict(causal=False,
                                  key_padding_mask=_padding(3, 16))),
    "causal_padding_mask": (16, 16, dict(causal=True,
                                         key_padding_mask=_padding(3, 16))),
    "softmax_scale": (16, 16, dict(causal=True, softmax_scale=0.05)),
}


def _gqa_inputs(s_q, s_kv, rep, dtype, seed=30):
    """8 query heads, all different, over 8 // rep KV heads, all different:
    a wrong (group, member) order reads another head's keys and fails."""
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)
    return mk(3, s_q, 8, 16), mk(3, s_kv, 8 // rep, 16), mk(3, s_kv, 8 // rep, 16)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("rep", [1, 4, 8])
@pytest.mark.parametrize("case", sorted(_GQA_CASES))
def test_xla_attention_matches_explicit_repeat(case, rep, dtype):
    s_q, s_kv, kwargs = _GQA_CASES[case]
    q, k, v = _gqa_inputs(s_q, s_kv, rep, dtype)
    out = jax.jit(lambda q, k, v: xla_attention(q, k, v, **kwargs))(q, k, v)
    assert out.shape == q.shape and out.dtype == q.dtype
    ref = _repeat_reference(
        q, k, v, causal=kwargs["causal"],
        offset=kwargs.get("segment_offset", 0),
        mask=kwargs.get("key_padding_mask"),
        scale=kwargs.get("softmax_scale"))
    # float32: only the order of the sums differs (16- and 24-term dots of
    # unit normals), a few ulps of values up to ~3. bfloat16: the same
    # mathematics with the logits, the probabilities and the output each
    # rounded to an 8-bit mantissa (2**-8 relative): logits reach ~4 here,
    # so a logit is off by up to 0.016 and a weight by 1.6 %; a wrong head
    # order is off by ~1.
    atol = 1e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), ref, atol=atol,
                               rtol=0)
    if "key_padding_mask" in kwargs:
        assert not np.asarray(out, np.float32)[-1].any()  # fully padded row


@pytest.mark.parametrize("rep", [2, 4, 8])
def test_gqa_head_order_is_group_major(rep):
    """KV head g holds the constant g + 1 as its values, so query head h
    must come out as exactly h // rep + 1 whatever its scores are."""
    q, k, _ = _gqa_inputs(1, 24, rep, jnp.float32)
    n_kv = 8 // rep
    v = jnp.broadcast_to(
        jnp.arange(1, n_kv + 1, dtype=jnp.float32)[None, None, :, None],
        k.shape)
    out = xla_attention(q, k, v, causal=True, segment_offset=23)
    want = np.broadcast_to(
        (np.arange(8) // rep + 1.0)[None, None, :, None], out.shape)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)


@pytest.mark.parametrize("rep", [1, 4, 8])
def test_xla_attention_gqa_gradients_match_explicit_repeat(rep):
    """Square causal GQA, the training shape: gradients with respect to q, k
    and v against the explicit-repeat mathematics in jax.numpy (its dK and
    dV sum the rep members of a group; the contraction does it inside)."""
    q, k, v = _gqa_inputs(16, 16, rep, jnp.float32)

    def repeated(q, k, v):
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        probs = jax.nn.softmax(
            jnp.where(mask[None, None], logits, jnp.finfo(jnp.float32).min))
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    loss = lambda fn: lambda q, k, v: (
        lambda o: (o * jnp.cos(o)).sum())(fn(q, k, v))  # non-trivial cotangent
    grads = jax.grad(
        loss(lambda q, k, v: xla_attention(q, k, v, causal=True)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(repeated), argnums=(0, 1, 2))(q, k, v)
    for got, ref in zip(grads, want):
        assert got.shape == ref.shape
        # float32 on both sides; only the order of the sums differs.
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


# --------------------------------------------------------------------------
# Structural guard: in the serving steps' jaxprs nothing but the logits and
# the probabilities has both the query's head count and the cache's length.
# --------------------------------------------------------------------------

# Sizes no other dimension of the tiny models takes, so a match is a match:
# 10 query heads over 2 KV heads (rep 5), a 48-token cache, head_dim 8.
_G_HEADS, _G_KV, _G_SEQ, _G_DIM = 10, 2, 48, 8
_G_REP = _G_HEADS // _G_KV
_G_SLOTS, _G_BLOCK = 3, 4


def _all_shapes(jaxpr):
    """(primitive, shape) of every intermediate, nested jaxprs included."""
    from tf_yarn_tpu.analysis.jaxpr_engine import _walk_jaxpr

    for eqn in _walk_jaxpr(jaxpr):
        for var in eqn.outvars:
            if hasattr(var.aval, "shape"):
                yield eqn.primitive.name, tuple(var.aval.shape)


def _head_count_over_cache(jaxpr):
    """Intermediates shaped by the query's head count (whole, or as
    [H_kv, rep]) and the cache's length that are not logits-shaped
    ([..., H_kv, rep, S_q, S_kv], no head_dim)."""
    found = []
    for name, shape in _all_shapes(jaxpr):
        grouped = any(shape[i:i + 2] == (_G_KV, _G_REP)
                      for i in range(len(shape) - 1))
        if _G_SEQ not in shape or not (grouped or _G_HEADS in shape):
            continue
        if shape[-1] == _G_SEQ and _G_DIM not in shape:
            continue  # logits, mask, probabilities
        found.append((name, shape))
    return found


def _paged_avals(model):
    """(params, pool, row, the step's arguments after its trees) as shapes:
    3 slots of 48 tokens in blocks of 4."""
    from tf_yarn_tpu.models import decode_engine

    params = nn.meta.unbox(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))
    params = {"params": params["params"]}
    row = decode_engine._decode_cache_aval(model, params)
    per_slot = _G_SEQ // _G_BLOCK
    pool = decode_engine.paged_pool_avals(
        model, row, _G_SLOTS * per_slot + 1, _G_BLOCK)
    host = (jax.ShapeDtypeStruct((_G_SLOTS, per_slot), jnp.int32),  # tables
            jax.ShapeDtypeStruct((_G_SLOTS,), jnp.int32),           # lengths
            *decode_engine.feed_avals(_G_SLOTS),       # tokens and rng rows
            jax.ShapeDtypeStruct((_G_SLOTS,), bool))                # mask
    return params, pool, row, host


def _paged_step_jaxpr():
    from tf_yarn_tpu.models import decode_engine, transformer

    model = transformer.Transformer(transformer.TransformerConfig.tiny(
        scan_layers=False, remat=False, max_seq_len=_G_SEQ, dtype=jnp.float32,
        d_model=_G_HEADS * _G_DIM, n_heads=_G_HEADS, n_kv_heads=_G_KV))
    params, pool, _, host = _paged_avals(model)
    step = decode_engine.build_paged_step_fn(model, _G_BLOCK, 0.0, None, None)
    return jax.make_jaxpr(step)(params, pool, *host).jaxpr


def _paged_state_step_jaxpr():
    from tf_yarn_tpu.models import decode_engine, hybrid

    model = hybrid.HybridLM(hybrid.HybridConfig(
        vocab_size=64, d_model=_G_HEADS * _G_DIM, max_seq_len=_G_SEQ,
        layer_types=("mamba", "attention"), n_heads=_G_HEADS,
        n_kv_heads=_G_KV, mamba_heads=4, mamba_head_dim=40, mamba_d_state=16,
        mamba_chunk=8, num_experts=4, num_experts_here=4,
        experts_per_token=2, d_expert=24, d_shared=24, dtype=jnp.float32,
        param_dtype=jnp.float32))
    params, pool, row, host = _paged_avals(model)
    state = jax.tree_util.tree_map(
        lambda lay, aval: jax.ShapeDtypeStruct(
            (_G_SLOTS,) + tuple(aval.shape), aval.dtype)
        if lay.kind == decode_engine.SLOT else None,
        decode_engine.cache_layout(model, row), row)
    step = decode_engine.build_paged_state_step_fn(
        model, _G_BLOCK, 0.0, None, None)
    return jax.make_jaxpr(step)(params, pool, state, *host).jaxpr


def _repeated_attention_jaxpr():
    """What the guard is for: the contraction over an explicit repeat."""
    from tf_yarn_tpu.ops.attention import _repeat_kv

    def repeated(q, k, v):
        k, v = _repeat_kv(k, v, _G_REP)
        probs = jax.nn.softmax(jnp.einsum("bqhd,bkhd->bhqk", q, k))
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    kv = jax.ShapeDtypeStruct((_G_SLOTS, _G_SEQ, _G_KV, _G_DIM), jnp.float32)
    return jax.make_jaxpr(repeated)(
        jax.ShapeDtypeStruct((_G_SLOTS, 1, _G_HEADS, _G_DIM), jnp.float32),
        kv, kv).jaxpr


@pytest.mark.parametrize("program,copies", [
    (_paged_step_jaxpr, False),
    (_paged_state_step_jaxpr, False),
    (_repeated_attention_jaxpr, True),  # the guard sees what it guards
], ids=["paged_step", "hybrid_paged_state_step", "explicit_repeat"])
def test_no_query_head_copy_of_the_cache_view(program, copies):
    jaxpr = program()
    shapes = [shape for _, shape in _all_shapes(jaxpr)]
    # The program is the one meant: it has the logits, grouped.
    assert copies or any(
        shape[-4:] == (_G_KV, _G_REP, 1, _G_SEQ) for shape in shapes)
    found = _head_count_over_cache(jaxpr)
    assert bool(found) == copies, found


# --------------------------------------------------------------------------
# paged_decode_attention: the one-token paged step's read of the pool, the
# kernel (interpret mode here) against the plain gather and against f32.
# --------------------------------------------------------------------------

_P_BLOCK, _P_TABLE = 16, 16          # 16 blocks of 16: a 256-token table
_P_CHUNK = 8 * _P_BLOCK              # the kernel's 8 pages a loop trip
# One slot a length: empty, one token, around a block's edge, around a
# chunk's edge, the whole table. Slot 0 besides is a free slot: its table
# row is all trash block.
_P_LENGTHS = (0, 0, 1, 15, 16, 17, _P_CHUNK - 1, _P_CHUNK, _P_CHUNK + 1,
              _P_BLOCK * _P_TABLE)


def _paged_case(n_heads, n_kv, head_dim, garbage, seed=32):
    """Slots of `_P_LENGTHS` over a pool whose tables point at shuffled
    physical blocks; every position no slot holds is `garbage`."""
    rng = np.random.RandomState(seed)
    slots = len(_P_LENGTHS)
    nb = slots * _P_TABLE + 1
    tables = (rng.permutation(nb - 1)[:slots * _P_TABLE] + 1).reshape(
        slots, _P_TABLE).astype(np.int32)
    tables[0] = 0
    pools = []
    for _ in range(2):
        pool = np.full((nb, _P_BLOCK, n_kv, head_dim), garbage, np.float32)
        for slot, length in enumerate(_P_LENGTHS):
            for pos in range(length):
                pool[tables[slot, pos // _P_BLOCK], pos % _P_BLOCK] = \
                    rng.randn(n_kv, head_dim)
        pools.append(jnp.asarray(pool, jnp.bfloat16))
    query = jnp.asarray(rng.randn(slots, n_heads, head_dim), jnp.bfloat16)
    return query, pools[0], pools[1], jnp.asarray(tables), \
        jnp.asarray(_P_LENGTHS, jnp.int32)


def _paged_reference(query, key_pool, value_pool, tables, lengths, scale):
    """The same attention in float32 numpy over the live positions only."""
    q, kp, vp = (np.asarray(x, np.float32)
                 for x in (query, key_pool, value_pool))
    slots, n_heads, head_dim = q.shape
    rep = n_heads // kp.shape[2]
    out = np.zeros(q.shape, np.float32)
    for slot in range(slots):
        n = int(lengths[slot])
        if not n:
            continue
        rows = np.asarray(tables[slot])
        k = kp[rows].reshape(-1, *kp.shape[2:])[:n]
        v = vp[rows].reshape(-1, *vp.shape[2:])[:n]
        for head in range(n_heads):
            logits = k[:, head // rep] @ q[slot, head] * scale
            weights = np.exp(logits - logits.max())
            out[slot, head] = weights / weights.sum() @ v[:, head // rep]
    return out


@pytest.mark.parametrize("scale", [None, 1.0 / 24], ids=["sqrt", "given"])
# The plain path weights a dead row by an exact 0 and adds it: large finite
# garbage must not reach the output, NaN would (0 * NaN). The kernel zeroes
# dead rows of V and takes both.
@pytest.mark.parametrize("kernel,garbage", [
    (True, 3e4), (True, float("nan")), (False, 3e4),
], ids=["kernel-big", "kernel-nan", "plain-big"])
@pytest.mark.parametrize("heads", [(32, 8, 128), (4, 4, 16)],
                         ids=["32over8", "4over4"])
def test_paged_decode_attention_reads_live_positions_only(
        heads, kernel, garbage, scale):
    from tf_yarn_tpu.ops.decode_attention import paged_decode_attention

    n_heads, n_kv, head_dim = heads
    query, key_pool, value_pool, tables, lengths = _paged_case(
        n_heads, n_kv, head_dim, garbage)
    out = jax.jit(lambda *args: paged_decode_attention(
        *args, scale, kernel=kernel))(
        query, key_pool, value_pool, tables, lengths)
    assert out.shape == query.shape and out.dtype == query.dtype
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    # Nothing to attend over: zeros, not a uniform softmax over garbage.
    assert not out[:2].any()
    ref = _paged_reference(
        query, key_pool, value_pool, tables, lengths,
        head_dim ** -0.5 if scale is None else scale)
    # bfloat16 in and out, f32 softmax. The kernel keeps its logits in f32
    # and rounds the weights and the output to an 8-bit mantissa (2**-8
    # relative, of values up to ~3 at length 1): 2e-2. The plain path also
    # rounds the logits to bfloat16 before the softmax (PR 30's cases:
    # logits reach ~4, a weight is off by 1.6 %): 4e-2. A position past
    # the length, a foreign KV head or another slot's block is off by ~1.
    np.testing.assert_allclose(out, ref, atol=2e-2 if kernel else 4e-2,
                               rtol=0)


@pytest.mark.parametrize("heads", [(32, 8, 128), (4, 4, 16)],
                         ids=["32over8", "4over4"])
def test_paged_kernel_matches_plain_read(heads):
    """The two implementations of the one op, against each other: the sum
    of their distances to f32 above bounds it (6e-2); read here it is the
    plain path's bfloat16 logits, 4e-2."""
    from tf_yarn_tpu.ops.decode_attention import paged_decode_attention

    args = _paged_case(*heads, garbage=3e4)
    outs = [np.asarray(paged_decode_attention(*args, kernel=kernel),
                       np.float32) for kernel in (True, False)]
    np.testing.assert_allclose(outs[0], outs[1], atol=4e-2, rtol=0)


def test_paged_kernel_serves_nothing_off_the_tpu(monkeypatch):
    """The choice is the code's: on this CPU the plain read serves whatever
    the pool's shape, and only a shape the kernel can tile is its."""
    from tf_yarn_tpu.ops import _rowwise, decode_attention

    leaf = jax.ShapeDtypeStruct((65, 16, 8, 128), jnp.bfloat16)
    assert not decode_attention.paged_kernel_serves(leaf)
    monkeypatch.setattr(_rowwise, "default_interpret", lambda: False)
    assert decode_attention.paged_kernel_serves(leaf)
    narrow = jax.ShapeDtypeStruct((65, 16, 8, 64), jnp.bfloat16)
    assert not decode_attention.paged_kernel_serves(narrow)
    f32 = jax.ShapeDtypeStruct((65, 16, 8, 128), jnp.float32)
    assert not decode_attention.paged_kernel_serves(f32)
    assert decode_attention.paged_chunk_tokens(16, 256) == 128
    assert decode_attention.paged_chunk_tokens(4, 2) == 8


def _step_logits(builder, model, extra, paged_kernel):
    """One step of `builder`'s program over three slots (free, 5 and 21
    tokens held) of a random pool: (logits, the pool it wrote)."""
    from tf_yarn_tpu.models import decode_engine

    rng = np.random.RandomState(7)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)))
    params = {"params": params["params"]}
    row = decode_engine._decode_cache_aval(model, params)
    block, slots = 4, 3
    per_slot = model.config.max_seq_len // block
    pool = jax.tree_util.tree_map(
        lambda aval: None if aval is None else jnp.asarray(
            rng.randn(*aval.shape), aval.dtype),
        decode_engine.paged_pool_avals(
            model, row, slots * per_slot + 1, block),
        is_leaf=lambda x: x is None)
    tables = (rng.permutation(slots * per_slot) + 1).reshape(
        slots, per_slot).astype(np.int32)
    tables[0] = 0
    step = jax.jit(builder(model, block, 0.0, None, None, with_logits=True,
                           paged_kernel=paged_kernel))
    out = step(params, pool, *extra(row, slots), tables,
               np.asarray([0, 5, 21], np.int32),
               *decode_engine.all_forced(np.asarray([3, 4, 5], np.int32),
                                         np.zeros((slots, 2), np.uint32)),
               np.ones((slots,), bool))
    return np.asarray(out[-1], np.float32), out[0]


def _tiny_transformer_step():
    from tf_yarn_tpu.models import decode_engine, transformer

    model = transformer.Transformer(transformer.TransformerConfig.tiny(
        scan_layers=False, remat=False, max_seq_len=32))
    return decode_engine.build_paged_step_fn, model, lambda row, slots: ()


def _tiny_hybrid_step():
    from tf_yarn_tpu.models import decode_engine, hybrid

    model = hybrid.HybridLM(hybrid.HybridConfig.tiny(max_seq_len=32))

    def state(row, slots):
        return (jax.tree_util.tree_map(
            lambda lay, aval: jnp.zeros((slots,) + tuple(aval.shape),
                                        aval.dtype)
            if lay.kind == decode_engine.SLOT else None,
            decode_engine.cache_layout(model, row), row),)

    return decode_engine.build_paged_state_step_fn, model, state


@pytest.mark.parametrize("case", [_tiny_transformer_step, _tiny_hybrid_step],
                         ids=["paged_step", "paged_state_step"])
def test_paged_step_logits_agree_between_kernel_and_plain_read(case):
    """Each builder's whole step on its tiny preset (bfloat16), the kernel
    forced (interpret mode) against the plain read: the same rows written
    to the same blocks, and logits that differ by what two bfloat16
    attention outputs differ by (4e-2 an output above, through a tiny
    model's remaining layers onto logits of size ~1: 5e-2)."""
    builder, model, extra = case()
    (kernel_logits, kernel_pool), (plain_logits, plain_pool) = (
        _step_logits(builder, model, extra, paged_kernel)
        for paged_kernel in (True, False))
    assert np.isfinite(kernel_logits).all()
    np.testing.assert_allclose(kernel_logits[1:], plain_logits[1:],
                               atol=5e-2, rtol=0)
    # Layer 0 writes the same rows whoever reads them afterwards.
    first = jax.tree_util.tree_leaves(kernel_pool)[0]
    np.testing.assert_array_equal(
        np.asarray(first, np.float32),
        np.asarray(jax.tree_util.tree_leaves(plain_pool)[0], np.float32))
