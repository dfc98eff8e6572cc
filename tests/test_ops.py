"""Kernel tests: fused rmsnorm vs reference, forward and backward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tf_yarn_tpu.ops.rmsnorm import rmsnorm, rmsnorm_reference


@pytest.mark.parametrize("shape", [(4, 64), (2, 8, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_reference(shape, dtype):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)
    scale = jnp.asarray(rng.rand(shape[-1]).astype(np.float32))
    out = rmsnorm(x, scale)
    ref = rmsnorm_reference(x, scale)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
    )
    assert out.dtype == x.dtype


@pytest.mark.parametrize("kernel_bwd", [True, False])
@pytest.mark.parametrize("shape", [(4, 32), (3, 7, 48), (5, 33)])
def test_rmsnorm_grad_matches_reference(kernel_bwd, shape):
    """Both backward paths (fused dx kernel / recompute-through-reference)
    against jax.grad of the reference, including non-divisible rows."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    scale = jnp.asarray(rng.rand(shape[-1]).astype(np.float32))
    # A non-trivial cotangent: .sum() alone would hide dx terms that
    # only differ under row-varying upstream gradients.
    w = jnp.asarray(rng.randn(*shape).astype(np.float32))
    g1 = jax.grad(
        lambda x, s: (rmsnorm(x, s, kernel_bwd=kernel_bwd) * w).sum(),
        argnums=(0, 1))(x, scale)
    g2 = jax.grad(
        lambda x, s: (rmsnorm_reference(x, s) * w).sum(),
        argnums=(0, 1))(x, scale)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_rmsnorm_kernel_bwd_bf16():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(4, 64).astype(np.float32), jnp.bfloat16)
    scale = jnp.asarray(rng.rand(64).astype(np.float32))
    g1 = jax.grad(lambda x: rmsnorm(x, scale, kernel_bwd=True)
                  .astype(jnp.float32).sum())(x)
    g2 = jax.grad(lambda x: rmsnorm_reference(x, scale)
                  .astype(jnp.float32).sum())(x)
    assert g1.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(g1, np.float32), np.asarray(g2, np.float32), atol=5e-2)


@pytest.mark.parametrize("shape,groups", [
    ((2, 8, 8, 32), 4),   # NHWC, the resnet case
    ((3, 16), 4),         # [B, C] degenerate spatial
    ((2, 4, 4, 6), 3),    # C/G = 2, the worst lane case the matmul avoids
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_groupnorm_matches_reference_and_flax(shape, groups, dtype):
    import flax.linen as nn

    from tf_yarn_tpu.ops.groupnorm import groupnorm, groupnorm_reference

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)
    scale = jnp.asarray(rng.rand(shape[-1]).astype(np.float32))
    bias = jnp.asarray(rng.randn(shape[-1]).astype(np.float32) * 0.1)
    out = groupnorm(x, scale, bias, groups, eps=1e-6)
    ref = groupnorm_reference(x, scale, bias, groups, eps=1e-6)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
    )
    assert out.dtype == x.dtype
    # And the reference itself matches flax's GroupNorm semantics.
    gn = nn.GroupNorm(num_groups=groups, epsilon=1e-6,
                      use_bias=True, use_scale=True)
    variables = {"params": {"scale": scale, "bias": bias}}
    flax_out = gn.apply(variables, x.astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(ref, np.float32), np.asarray(flax_out, np.float32),
        atol=2e-2,
    )


def test_groupnorm_grad_matches_reference():
    from tf_yarn_tpu.ops.groupnorm import groupnorm, groupnorm_reference

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 4, 4, 16).astype(np.float32))
    scale = jnp.asarray(rng.rand(16).astype(np.float32))
    bias = jnp.asarray(rng.randn(16).astype(np.float32) * 0.1)
    g1 = jax.grad(
        lambda x, s, b: groupnorm(x, s, b, 4).sum(), argnums=(0, 1, 2)
    )(x, scale, bias)
    g2 = jax.grad(
        lambda x, s, b: groupnorm_reference(x, s, b, 4).sum(),
        argnums=(0, 1, 2),
    )(x, scale, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_groupnorm_fallback_on_indivisible_channels():
    from tf_yarn_tpu.ops.groupnorm import groupnorm, groupnorm_reference

    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(2, 4, 4, 6).astype(np.float32))
    scale, bias = jnp.ones((6,)), jnp.zeros((6,))
    # 6 channels / 4 groups: both entry points reject loudly instead of
    # silently regrouping.
    with pytest.raises(ValueError, match="divide"):
        groupnorm_reference(x, scale, bias, 4)
    with pytest.raises(ValueError, match="divide"):
        groupnorm(x, scale, bias, 4)


def test_groupnorm_no_nan_on_near_constant_input():
    """One-pass variance must clamp at zero: a large-mean, tiny-spread
    group rounds E[x^2]-mean^2 negative in f32 and rsqrt would emit NaN
    (found by review; reference is two-pass and immune)."""
    from tf_yarn_tpu.ops.groupnorm import groupnorm, groupnorm_reference

    rng = np.random.RandomState(3)
    x = jnp.asarray(
        1000.0 + 1e-3 * rng.randn(1, 8, 8, 32).astype(np.float32))
    scale, bias = jnp.ones((32,)), jnp.zeros((32,))
    out = groupnorm(x, scale, bias, 4, eps=1e-6)
    ref = groupnorm_reference(x, scale, bias, 4, eps=1e-6)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    assert np.isfinite(np.asarray(ref, np.float32)).all()


@pytest.mark.parametrize("shape", [(4, 64), (2, 8, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_layernorm_matches_reference_and_flax(shape, dtype):
    import flax.linen as nn

    from tf_yarn_tpu.ops.layernorm import layernorm, layernorm_reference

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)
    scale = jnp.asarray(rng.rand(shape[-1]).astype(np.float32))
    bias = jnp.asarray(rng.randn(shape[-1]).astype(np.float32) * 0.1)
    out = layernorm(x, scale, bias, eps=1e-12)
    ref = layernorm_reference(x, scale, bias, eps=1e-12)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
    )
    assert out.dtype == x.dtype
    ln = nn.LayerNorm(epsilon=1e-12)
    flax_out = ln.apply(
        {"params": {"scale": scale, "bias": bias}}, x.astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(ref, np.float32), np.asarray(flax_out, np.float32),
        atol=2e-2,
    )


@pytest.mark.parametrize("kernel_bwd", [True, False])
@pytest.mark.parametrize("shape", [(4, 32), (3, 7, 48), (5, 33)])
def test_layernorm_grad_matches_reference(kernel_bwd, shape):
    from tf_yarn_tpu.ops.layernorm import layernorm, layernorm_reference

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    scale = jnp.asarray(rng.rand(shape[-1]).astype(np.float32))
    bias = jnp.asarray(rng.randn(shape[-1]).astype(np.float32) * 0.1)
    w = jnp.asarray(rng.randn(*shape).astype(np.float32))
    g1 = jax.grad(
        lambda x, s, b: (layernorm(x, s, b, kernel_bwd=kernel_bwd) * w).sum(),
        argnums=(0, 1, 2)
    )(x, scale, bias)
    g2 = jax.grad(
        lambda x, s, b: (layernorm_reference(x, s, b) * w).sum(),
        argnums=(0, 1, 2)
    )(x, scale, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.fixture
def run_mesh():
    """Register a mesh as the run's (what the train loop does), for the
    test only."""
    from tf_yarn_tpu.parallel import mesh as mesh_lib

    with mesh_lib.use_mesh(None):
        yield mesh_lib.set_current_mesh


def test_norm_kernel_bwd_partitions_under_pjit(run_mesh):
    """The fused dx kernels run per shard under the run's mesh like the
    forward (same row split, with the cotangent as a second row
    operand), and dscale/dbias cross-shard sums match the reference."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tf_yarn_tpu.ops.layernorm import layernorm, layernorm_reference
    from tf_yarn_tpu.ops.rmsnorm import rmsnorm, rmsnorm_reference
    from tf_yarn_tpu.parallel.mesh import select_devices

    devices = select_devices(8, platform="cpu")
    mesh = Mesh(np.array(devices).reshape(4, 2), ("dp", "tp"))
    run_mesh(mesh)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(8, 16, 32).astype(np.float32))
    scale = jnp.asarray(rng.rand(32).astype(np.float32))
    bias = jnp.asarray(rng.randn(32).astype(np.float32) * 0.1)
    xs = jax.device_put(x, NamedSharding(mesh, P("dp", "tp", None)))
    ss = jax.device_put(scale, NamedSharding(mesh, P(None)))
    bs = jax.device_put(bias, NamedSharding(mesh, P(None)))

    g1 = jax.jit(jax.grad(
        lambda x, s: rmsnorm(x, s, kernel_bwd=True).sum(), argnums=(0, 1)
    ))(xs, ss)
    g2 = jax.grad(
        lambda x, s: rmsnorm_reference(x, s).sum(), argnums=(0, 1)
    )(x, scale)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)
    # dx keeps the row sharding.
    assert g1[0].sharding.spec[0] == "dp", g1[0].sharding

    g1 = jax.jit(jax.grad(
        lambda x, s, b: layernorm(x, s, b, kernel_bwd=True).sum(),
        argnums=(0, 1, 2)
    ))(xs, ss, bs)
    g2 = jax.grad(
        lambda x, s, b: layernorm_reference(x, s, b).sum(), argnums=(0, 1, 2)
    )(x, scale, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("kernel_bwd", [True, False])
@pytest.mark.parametrize("shape,groups", [
    ((2, 8, 8, 32), 4),   # NHWC, the resnet case
    ((3, 16), 4),         # [B, C] degenerate spatial
    ((2, 4, 4, 6), 3),    # C/G = 2
])
def test_groupnorm_grad_matches_reference(kernel_bwd, shape, groups):
    from tf_yarn_tpu.ops.groupnorm import groupnorm, groupnorm_reference

    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    scale = jnp.asarray(rng.rand(shape[-1]).astype(np.float32))
    bias = jnp.asarray(rng.randn(shape[-1]).astype(np.float32) * 0.1)
    w = jnp.asarray(rng.randn(*shape).astype(np.float32))
    g1 = jax.grad(
        lambda x, s, b: (groupnorm(
            x, s, b, groups, eps=1e-5, kernel_bwd=kernel_bwd) * w).sum(),
        argnums=(0, 1, 2))(x, scale, bias)
    g2 = jax.grad(
        lambda x, s, b: (groupnorm_reference(
            x, s, b, groups, eps=1e-5) * w).sum(),
        argnums=(0, 1, 2))(x, scale, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_groupnorm_kernel_bwd_bf16():
    from tf_yarn_tpu.ops.groupnorm import groupnorm, groupnorm_reference

    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(2, 4, 4, 32).astype(np.float32), jnp.bfloat16)
    scale = jnp.asarray(rng.rand(32).astype(np.float32))
    bias = jnp.asarray(rng.randn(32).astype(np.float32) * 0.1)
    g1 = jax.grad(lambda x: groupnorm(x, scale, bias, 4, kernel_bwd=True)
                  .astype(jnp.float32).sum())(x)
    g2 = jax.grad(lambda x: groupnorm_reference(x, scale, bias, 4)
                  .astype(jnp.float32).sum())(x)
    assert g1.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(g1, np.float32), np.asarray(g2, np.float32), atol=5e-2)


def test_groupnorm_grad_fallback_paths():
    """Empty batch and non-divisible channels route around the kernel
    (identity / reference) but must still differentiate cleanly."""
    from tf_yarn_tpu.ops.groupnorm import groupnorm

    scale = jnp.ones((16,))
    bias = jnp.zeros((16,))
    gx, gs, gb = jax.grad(
        lambda x, s, b: groupnorm(x, s, b, 4, kernel_bwd=True).sum(),
        argnums=(0, 1, 2)
    )(jnp.zeros((0, 4, 4, 16)), scale, bias)
    assert gx.shape == (0, 4, 4, 16)
    assert gs.shape == (16,) and gb.shape == (16,)

    # 18 % 4 != 0 -> ValueError from the reference, not a kernel crash.
    import pytest as _pytest

    with _pytest.raises(ValueError, match="groups"):
        groupnorm(jnp.zeros((2, 4, 4, 18)), jnp.ones((18,)),
                  jnp.zeros((18,)), 4)


def test_groupnorm_kernel_bwd_partitions_under_pjit(run_mesh):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tf_yarn_tpu.ops.groupnorm import groupnorm, groupnorm_reference
    from tf_yarn_tpu.parallel.mesh import select_devices

    devices = select_devices(8, platform="cpu")
    mesh = Mesh(np.array(devices).reshape(4, 2), ("dp", "tp"))
    run_mesh(mesh)
    rng = np.random.RandomState(5)
    img = jnp.asarray(rng.randn(8, 4, 4, 16).astype(np.float32))
    scale = jnp.asarray(rng.rand(16).astype(np.float32))
    bias = jnp.asarray(rng.randn(16).astype(np.float32) * 0.1)
    img_s = jax.device_put(img, NamedSharding(mesh, P("dp")))
    ss = jax.device_put(scale, NamedSharding(mesh, P(None)))
    bs = jax.device_put(bias, NamedSharding(mesh, P(None)))
    g1 = jax.jit(jax.grad(
        lambda x, s, b: groupnorm(x, s, b, 4, kernel_bwd=True).sum(),
        argnums=(0, 1, 2)))(img_s, ss, bs)
    g2 = jax.grad(
        lambda x, s, b: groupnorm_reference(x, s, b, 4).sum(),
        argnums=(0, 1, 2))(img, scale, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)
    assert g1[0].sharding.spec[0] == "dp", g1[0].sharding


def test_norm_kernel_bwd_empty_batch():
    from tf_yarn_tpu.ops.layernorm import layernorm
    from tf_yarn_tpu.ops.rmsnorm import rmsnorm

    scale = jnp.ones((16,))
    bias = jnp.zeros((16,))
    gx, gs = jax.grad(
        lambda x, s: rmsnorm(x, s, kernel_bwd=True).sum(), argnums=(0, 1)
    )(jnp.zeros((0, 16)), scale)
    assert gx.shape == (0, 16) and gs.shape == (16,)
    gx, gs, gb = jax.grad(
        lambda x, s, b: layernorm(x, s, b, kernel_bwd=True).sum(),
        argnums=(0, 1, 2)
    )(jnp.zeros((0, 16)), scale, bias)
    assert gx.shape == (0, 16) and gs.shape == (16,) and gb.shape == (16,)


def test_rowwise_norms_partition_under_pjit(run_mesh):
    """Under the run's mesh the fused norms run per-shard instead of
    being replicated as opaque custom calls: output keeps the batch
    sharding, values match the reference, and a feature-dim (tp)
    sharding on the activation is resharded rather than miscomputed."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tf_yarn_tpu.ops.layernorm import layernorm, layernorm_reference
    from tf_yarn_tpu.ops.rmsnorm import rmsnorm, rmsnorm_reference
    from tf_yarn_tpu.parallel.mesh import select_devices

    devices = select_devices(8, platform="cpu")
    mesh = Mesh(np.array(devices).reshape(4, 2), ("dp", "tp"))
    run_mesh(mesh)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 16, 32).astype(np.float32))
    scale = jnp.asarray(rng.rand(32).astype(np.float32))
    bias = jnp.asarray(rng.randn(32).astype(np.float32) * 0.1)

    xs = jax.device_put(x, NamedSharding(mesh, P("dp", "tp", None)))
    ss = jax.device_put(scale, NamedSharding(mesh, P(None)))
    bs = jax.device_put(bias, NamedSharding(mesh, P(None)))

    out = jax.jit(rmsnorm)(xs, ss)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(rmsnorm_reference(x, scale)), atol=1e-5)
    assert out.sharding.spec[0] == "dp", out.sharding

    out = jax.jit(layernorm)(xs, ss, bs)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(layernorm_reference(x, scale, bias)), atol=1e-5)

    # Feature-dim sharded activation: the kernel's spec keeps the last
    # dim whole (a reshard), never a wrong per-shard reduction.
    x_tp = jax.device_put(x, NamedSharding(mesh, P("dp", None, "tp")))
    out = jax.jit(rmsnorm)(x_tp, ss)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(rmsnorm_reference(x, scale)), atol=1e-5)

    # GroupNorm shards the batch dim; a spatially-sharded input must be
    # resharded, not reduced per-shard (its stats span H, W).
    from tf_yarn_tpu.ops.groupnorm import groupnorm, groupnorm_reference

    img = jnp.asarray(rng.randn(8, 4, 4, 16).astype(np.float32))
    gscale = jnp.asarray(rng.rand(16).astype(np.float32))
    gbias = jnp.asarray(rng.randn(16).astype(np.float32) * 0.1)
    img_s = jax.device_put(
        img, NamedSharding(mesh, P("dp", "tp", None, None)))
    out = jax.jit(lambda x, s, b: groupnorm(x, s, b, 4))(
        img_s, jax.device_put(gscale, NamedSharding(mesh, P(None))),
        jax.device_put(gbias, NamedSharding(mesh, P(None))))
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(groupnorm_reference(img, gscale, gbias, 4)), atol=1e-5)


def test_kernels_handle_empty_batch():
    """An empty eval shard / drained batch must flow through every pallas
    entry point as an empty result, not a ZeroDivisionError or a
    slice-size crash (review finding, round 4)."""
    from tf_yarn_tpu.ops.decode_attention import int8_decode_attention
    from tf_yarn_tpu.ops.flash_attention import flash_attention
    from tf_yarn_tpu.ops.groupnorm import groupnorm
    from tf_yarn_tpu.ops.layernorm import layernorm
    from tf_yarn_tpu.ops.quantize import quantize_int8
    from tf_yarn_tpu.ops.rmsnorm import rmsnorm

    assert rmsnorm(jnp.zeros((0, 16)), jnp.ones((16,))).shape == (0, 16)
    assert layernorm(
        jnp.zeros((0, 16)), jnp.ones((16,)), jnp.zeros((16,))
    ).shape == (0, 16)
    assert groupnorm(
        jnp.zeros((0, 4, 4, 8)), jnp.ones((8,)), jnp.zeros((8,)), 4
    ).shape == (0, 4, 4, 8)
    values, scales = quantize_int8(jnp.zeros((0, 16)))
    assert values.shape == (0, 16) and scales.shape == (0, 1)
    assert flash_attention(
        jnp.zeros((0, 8, 2, 4)), jnp.zeros((0, 8, 2, 4)),
        jnp.zeros((0, 8, 2, 4)),
    ).shape == (0, 8, 2, 4)
    # Nonempty query over an EMPTY kv sequence (drained cross-attention
    # source) is defined as zeros, not a zero-extent-grid crash.
    assert flash_attention(
        jnp.zeros((2, 8, 2, 4)), jnp.zeros((2, 0, 2, 4)),
        jnp.zeros((2, 0, 2, 4)), causal=False,
    ).shape == (2, 8, 2, 4)
    out = int8_decode_attention(
        jnp.zeros((0, 2, 4)),
        jnp.zeros((0, 8, 2, 4), jnp.int8), jnp.zeros((0, 8, 2, 1)),
        jnp.zeros((0, 8, 2, 4), jnp.int8), jnp.zeros((0, 8, 2, 1)),
        jnp.int32(0),
    )
    assert out.shape == (0, 2, 4)


def test_quantize_int8_roundtrip():
    from tf_yarn_tpu.ops.quantize import dequantize_int8, quantize_int8

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 64).astype(np.float32) * 3.0)
    values, scales = quantize_int8(x)
    assert values.dtype == jnp.int8
    assert scales.shape == (16, 1)
    recovered = dequantize_int8(values, scales)
    # Per-row scale keeps quantization error within half a step.
    max_err = np.abs(np.asarray(recovered) - np.asarray(x)).max()
    step = float(np.asarray(scales).max())
    assert max_err <= step * 0.51 + 1e-6


def test_quantize_int8_batched_shape():
    from tf_yarn_tpu.ops.quantize import quantize_int8

    x = jnp.ones((2, 8, 32))
    values, scales = quantize_int8(x)
    assert values.shape == (2, 8, 32)
    assert scales.shape == (2, 8, 1)


def test_transformer_with_fused_norms():
    from tf_yarn_tpu.models import transformer

    cfg = transformer.TransformerConfig.tiny(fused_norms=True, scan_layers=False,
                                             remat=False)
    model = transformer.Transformer(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    out = model.apply(variables, tokens)

    cfg2 = transformer.TransformerConfig.tiny(fused_norms=False, scan_layers=False,
                                              remat=False)
    ref = transformer.Transformer(cfg2).apply(variables, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_int8_decode_attention_matches_xla():
    # Kernel correctness isolated from quantization error: the reference
    # attends over the DEQUANTIZED cache, so outputs must match to
    # reduction-order noise.
    from tf_yarn_tpu.ops.attention import xla_attention
    from tf_yarn_tpu.ops.decode_attention import int8_decode_attention
    from tf_yarn_tpu.ops.quantize import dequantize_int8, quantize_int8

    B, S, H, Hkv, D = 2, 256, 8, 4, 64
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
    kq, ks = quantize_int8(k)
    vq, vs = quantize_int8(v)
    k_deq = dequantize_int8(kq, ks, jnp.float32)
    v_deq = dequantize_int8(vq, vs, jnp.float32)

    for length in (1, 96, 173, 256):
        out = int8_decode_attention(q, kq, ks, vq, vs, length, block_k=64)
        ref = xla_attention(
            q[:, None], k_deq[:, :length], v_deq[:, :length],
            causal=True, segment_offset=length - 1,
        )[:, 0]
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-4,
            err_msg=f"length={length}",
        )


def test_int8_decode_attention_odd_cache_length():
    # Non-power-of-two S must keep full-width tiles (padded trailing
    # block), not collapse block_k to gcd(S, block) — and stay exact.
    from tf_yarn_tpu.ops.attention import xla_attention
    from tf_yarn_tpu.ops.decode_attention import int8_decode_attention
    from tf_yarn_tpu.ops.quantize import dequantize_int8, quantize_int8

    B, S, H, Hkv, D = 1, 200, 4, 2, 64
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
    kq, ks = quantize_int8(k)
    vq, vs = quantize_int8(v)
    k_deq = dequantize_int8(kq, ks, jnp.float32)
    v_deq = dequantize_int8(vq, vs, jnp.float32)
    for length in (1, 64, 130, 200):
        out = int8_decode_attention(q, kq, ks, vq, vs, length, block_k=64)
        ref = xla_attention(
            q[:, None], k_deq[:, :length], v_deq[:, :length],
            causal=True, segment_offset=length - 1,
        )[:, 0]
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-4,
            err_msg=f"length={length}",
        )


def test_int8_decode_attention_gqa_group_mapping():
    # Each q-head group must read ITS kv head: make kv heads wildly
    # different scales and check groups diverge accordingly.
    from tf_yarn_tpu.ops.decode_attention import int8_decode_attention
    from tf_yarn_tpu.ops.quantize import quantize_int8

    B, S, H, Hkv, D = 1, 128, 4, 2, 64
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    v = np.zeros((B, S, Hkv, D), np.float32)
    v[:, :, 0] = 1.0
    v[:, :, 1] = -3.0
    k = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
    kq, ks = quantize_int8(k)
    vq, vs = quantize_int8(jnp.asarray(v))
    out = np.asarray(int8_decode_attention(q, kq, ks, vq, vs, 128, block_k=64))
    # Heads 0-1 (group of kv head 0) average v=1; heads 2-3 see v=-3.
    np.testing.assert_allclose(out[0, :2], 1.0, atol=2e-2)
    np.testing.assert_allclose(out[0, 2:], -3.0, atol=6e-2)


def test_quantize_int8_grouped_roundtrip_and_shapes():
    # Per-block KV scales: one scale per group of rows (the paged pool's
    # per-(block, head) layout); the shared scale is the group's loudest
    # row, so the error bound is half that coarser step.
    from tf_yarn_tpu.ops.quantize import (
        dequantize_int8_grouped,
        quantize_int8_grouped,
    )

    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(4, 64, 16).astype(np.float32) * 2.0)
    values, scales = quantize_int8_grouped(x, group_rows=8)
    assert values.shape == x.shape and values.dtype == jnp.int8
    assert scales.shape == (4, 8, 1)  # 64 rows / 8 per group
    recovered = dequantize_int8_grouped(values, scales, group_rows=8)
    max_err = np.abs(np.asarray(recovered) - np.asarray(x)).max()
    step = float(np.asarray(scales).max())
    assert max_err <= step * 0.51 + 1e-6
    with pytest.raises(ValueError, match="group_rows"):
        quantize_int8_grouped(x, group_rows=0)
    with pytest.raises(ValueError, match="divide"):
        quantize_int8_grouped(x, group_rows=7)


def _build_paged_int8_pool(rng, slots, max_blocks, num_blocks, block_size,
                           n_kv, head_dim, per_block_scales=False):
    """Random dense caches scattered into a shuffled pool; returns the
    pool pieces + tables + the dense per-slot quantized reference."""
    from tf_yarn_tpu.ops.quantize import quantize_int8, quantize_int8_grouped

    dense_k = rng.randn(slots, max_blocks * block_size, n_kv,
                        head_dim).astype(np.float32)
    dense_v = rng.randn(slots, max_blocks * block_size, n_kv,
                        head_dim).astype(np.float32)
    tables = rng.permutation(
        np.arange(1, num_blocks)
    )[:slots * max_blocks].reshape(slots, max_blocks).astype(np.int32)
    sb = 1 if per_block_scales else block_size
    kp = np.zeros((num_blocks, block_size, n_kv, head_dim), np.int8)
    vp = np.zeros_like(kp)
    ksp = np.zeros((num_blocks, sb, n_kv, 1), np.float32)
    vsp = np.zeros_like(ksp)
    dense_quant = []
    for s in range(slots):
        if per_block_scales:
            # one scale per (block, head): group the block's rows.
            kq = np.zeros_like(dense_k[s], dtype=np.int8)
            ks = np.zeros((max_blocks * block_size, n_kv, 1), np.float32)
            vq = np.zeros_like(kq)
            vs = np.zeros_like(ks)
            for j in range(max_blocks):
                rows = slice(j * block_size, (j + 1) * block_size)
                for h in range(n_kv):
                    qv, qs = quantize_int8_grouped(
                        jnp.asarray(dense_k[s, rows, h])[None], block_size
                    )
                    kq[rows, h] = np.asarray(qv)[0]
                    ks[rows, h, 0] = float(np.asarray(qs)[0, 0, 0])
                    ksp[tables[s, j], 0, h, 0] = float(
                        np.asarray(qs)[0, 0, 0])
                    qv, qs = quantize_int8_grouped(
                        jnp.asarray(dense_v[s, rows, h])[None], block_size
                    )
                    vq[rows, h] = np.asarray(qv)[0]
                    vs[rows, h, 0] = float(np.asarray(qs)[0, 0, 0])
                    vsp[tables[s, j], 0, h, 0] = float(
                        np.asarray(qs)[0, 0, 0])
                kp[tables[s, j]] = kq[rows]
                vp[tables[s, j]] = vq[rows]
            dense_quant.append((kq, ks, vq, vs))
        else:
            kq, ks = quantize_int8(jnp.asarray(dense_k[s]))
            vq, vs = quantize_int8(jnp.asarray(dense_v[s]))
            for j in range(max_blocks):
                rows = slice(j * block_size, (j + 1) * block_size)
                kp[tables[s, j]] = np.asarray(kq)[rows]
                vp[tables[s, j]] = np.asarray(vq)[rows]
                ksp[tables[s, j]] = np.asarray(ks)[rows]
                vsp[tables[s, j]] = np.asarray(vs)[rows]
            dense_quant.append((np.asarray(kq), np.asarray(ks),
                                np.asarray(vq), np.asarray(vs)))
    return kp, ksp, vp, vsp, tables, dense_quant


def test_paged_int8_decode_attention_matches_dense_kernel():
    """The paged kernel walks each slot's block table (SMEM scalar
    prefetch) over a shuffled physical pool and must equal the dense
    int8 kernel on the gathered cache — table indirection only, no new
    math."""
    from tf_yarn_tpu.ops.decode_attention import (
        int8_decode_attention,
        paged_int8_decode_attention,
    )

    slots, H, Hkv, D = 3, 8, 4, 64
    block_size, max_blocks, num_blocks = 32, 4, 14
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(slots, H, D), jnp.float32)
    lengths = np.array([1, 70, 128], np.int32)
    kp, ksp, vp, vsp, tables, dense = _build_paged_int8_pool(
        rng, slots, max_blocks, num_blocks, block_size, Hkv, D
    )
    out = paged_int8_decode_attention(
        q, jnp.asarray(kp), jnp.asarray(ksp), jnp.asarray(vp),
        jnp.asarray(vsp), jnp.asarray(tables), jnp.asarray(lengths),
    )
    for s in range(slots):
        kq, ks, vq, vs = dense[s]
        ref = int8_decode_attention(
            q[s:s + 1], jnp.asarray(kq)[None], jnp.asarray(ks)[None],
            jnp.asarray(vq)[None], jnp.asarray(vs)[None],
            int(lengths[s]), block_k=block_size,
        )
        np.testing.assert_allclose(
            np.asarray(out)[s], np.asarray(ref)[0], atol=1e-5,
            err_msg=f"slot {s}",
        )


def test_paged_int8_decode_attention_per_block_scales():
    """sb=1 scale pools (quantize_int8_grouped per block+head) broadcast
    inside the kernel; reference = dequantized dense attention."""
    from tf_yarn_tpu.ops.attention import xla_attention
    from tf_yarn_tpu.ops.decode_attention import paged_int8_decode_attention

    slots, H, Hkv, D = 2, 4, 2, 64
    block_size, max_blocks, num_blocks = 32, 2, 6
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(slots, H, D), jnp.float32)
    lengths = np.array([40, 64], np.int32)
    kp, ksp, vp, vsp, tables, dense = _build_paged_int8_pool(
        rng, slots, max_blocks, num_blocks, block_size, Hkv, D,
        per_block_scales=True,
    )
    out = paged_int8_decode_attention(
        q, jnp.asarray(kp), jnp.asarray(ksp), jnp.asarray(vp),
        jnp.asarray(vsp), jnp.asarray(tables), jnp.asarray(lengths),
    )
    for s in range(slots):
        kq, ks, vq, vs = dense[s]
        L = int(lengths[s])
        k_deq = kq.astype(np.float32) * ks
        v_deq = vq.astype(np.float32) * vs
        ref = xla_attention(
            q[s:s + 1][:, None], jnp.asarray(k_deq[None, :L]),
            jnp.asarray(v_deq[None, :L]), causal=True, segment_offset=L - 1,
        )[:, 0]
        np.testing.assert_allclose(
            np.asarray(out)[s], np.asarray(ref)[0], atol=1e-4,
            err_msg=f"slot {s}",
        )


def test_paged_int8_window_attention_matches_per_position_kernel():
    """The speculative-window wrapper: query (slot, w) must equal the
    single-token paged kernel at effective length lengths[s] + w + 1 —
    virtual-slot expansion only, no new math. Also pins the causal
    window semantics: position w sees exactly the prefix plus the
    window rows up to itself."""
    from tf_yarn_tpu.ops.decode_attention import (
        paged_int8_decode_attention,
        paged_int8_window_attention,
    )

    slots, width, H, Hkv, D = 2, 3, 8, 4, 64
    block_size, max_blocks, num_blocks = 32, 4, 14
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(slots, width, H, D), jnp.float32)
    # lengths = valid prefix BEFORE the window; the window rows
    # (positions lengths..lengths+width-1) are already in the pool here
    # (the builder fills every block with data).
    lengths = np.array([17, 60], np.int32)
    kp, ksp, vp, vsp, tables, _dense = _build_paged_int8_pool(
        rng, slots, max_blocks, num_blocks, block_size, Hkv, D
    )
    out = paged_int8_window_attention(
        q, jnp.asarray(kp), jnp.asarray(ksp), jnp.asarray(vp),
        jnp.asarray(vsp), jnp.asarray(tables), jnp.asarray(lengths),
    )
    assert out.shape == (slots, width, H, D)
    for s in range(slots):
        for w in range(width):
            ref = paged_int8_decode_attention(
                q[s, w][None], jnp.asarray(kp), jnp.asarray(ksp),
                jnp.asarray(vp), jnp.asarray(vsp),
                jnp.asarray(tables[s:s + 1]),
                jnp.asarray([int(lengths[s]) + w + 1], np.int32),
            )
            np.testing.assert_allclose(
                np.asarray(out)[s, w], np.asarray(ref)[0], atol=1e-5,
                err_msg=f"slot {s} window {w}",
            )
