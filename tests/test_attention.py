"""Attention-backend correctness: flash (pallas, interpret on CPU) and
ring (shard_map over sp) must match the XLA reference exactly enough."""

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
