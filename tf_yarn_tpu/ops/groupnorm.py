"""Fused GroupNorm — pallas TPU kernel (NHWC).

GroupNorm is the resnet family's norm (models/resnet.py: no cross-step
running stats, pure train step). XLA lowers it as separate reduce /
rsqrt / broadcast-multiply HLOs, re-reading the activation from HBM for
the stats pass and again for the normalize pass — at resnet50's early
stages that traffic is a material slice of step time
(docs/ResNetMFU.md hypothesis 2). This kernel reads each [H*W, C] slab
once into VMEM, computes per-group stats and the normalized output on
the VPU/MXU, and writes once.

Lane-friendly group reduction: instead of reshaping [HW, C] ->
[HW, G, C/G] (which would demote the lane dim to C/G, as small as 2),
per-channel sums are folded into per-group sums with a [C, G] one-hot
assignment matmul, and group stats broadcast back with its transpose —
the MXU does the bookkeeping and the lane dim stays C.

Backward (kernel_bwd=True, default): dx in one fused pass — per
(batch, group) the vjp is the layernorm formula
``dx = inv·(gs − mean_g(gs) − norm·mean_g(gs·norm))``, computed on the
same [HW, C] slab blocking with the same assignment-matmul group
bookkeeping; dscale/dbias are cross-batch XLA reductions (see
ops/rmsnorm.py for why they cannot live in the kernel under pjit).
kernel_bwd=False / TPU_YARN_NORM_KERNEL_BWD=0 keeps the
recompute-through-reference vjp — the A/B knob.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _norm32(x, groups: int, eps: float):
    """f32 normalized activation (no scale/bias), x's shape — the
    XLA-side stats definition (two-pass variance), shared by the
    reference and the kernel-backward's dscale path. The pallas kernels
    use the one-pass-clamped _slab_group_stats instead (sum/sumsq fit
    the slab layout); the two agree to f32 rounding."""
    b, c = x.shape[0], x.shape[-1]
    xg = x.astype(jnp.float32).reshape(b, -1, groups, c // groups)
    mean = jnp.mean(xg, axis=(1, 3), keepdims=True)
    var = jnp.mean((xg - mean) ** 2, axis=(1, 3), keepdims=True)
    return ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)


def groupnorm_reference(x, scale, bias, groups: int, eps: float = 1e-5):
    """[..., H, W, C] (or any [..., C]) GroupNorm matching flax
    nn.GroupNorm semantics: stats over all non-batch dims within each
    channel group."""
    if x.shape[-1] % groups:
        raise ValueError(
            f"channels ({x.shape[-1]}) must divide into groups ({groups})")
    norm = _norm32(x, groups, eps)
    return (norm * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _slab_group_stats(x2d, assign, spread, groups: int, eps: float):
    """(mean_c, inv_c), each [1, C], for one [HW, C] slab — the in-kernel
    stats definition, shared by the forward and dx kernels. Per-channel
    sums fold into per-group stats via the [C, G] assignment matmul (lane
    dim stays C) and broadcast back through its [G, C] twin. Every
    operand stays 2-D: Mosaic has no matmul for a rank-1 side."""
    hw, c = x2d.shape
    n = jnp.float32(hw * (c // groups))
    mean_g = _dot(jnp.sum(x2d, axis=0, keepdims=True), assign) / n  # [1, G]
    # One-pass variance can round negative under f32 cancellation (large
    # mean, tiny spread: ulp at 1e6 is ~0.06); clamp like flax's
    # use_fast_variance path or rsqrt(negative) poisons the slab with NaN.
    var_g = jnp.maximum(
        _dot(jnp.sum(x2d * x2d, axis=0, keepdims=True), assign) / n
        - mean_g * mean_g, 0.0)
    inv_g = jax.lax.rsqrt(var_g + eps)
    return _dot(mean_g, spread), _dot(inv_g, spread)


def _groupnorm_kernel(x_ref, scale_ref, bias_ref, o_ref, *,
                      groups: int, eps: float):
    x = x_ref[...].astype(jnp.float32)  # [1, HW, C] block: one batch elem
    hw, c = x.shape[-2], x.shape[-1]
    x2d = x.reshape(hw, c)
    assign, spread = _group_assign(c, groups)
    mean_c, inv_c = _slab_group_stats(x2d, assign, spread, groups, eps)
    y = (x2d - mean_c) * inv_c
    y = y * scale_ref[...].astype(jnp.float32)[None, :]
    y = y + bias_ref[...].astype(jnp.float32)[None, :]
    o_ref[...] = y.reshape(x.shape).astype(o_ref.dtype)


def _groupnorm_local(x, scale, bias, groups, eps, interpret):
    """The per-shard pallas call over [B_local, HW, C]."""
    b, c = x.shape[0], x.shape[-1]
    hw = 1
    for dim in x.shape[1:-1]:
        hw *= dim
    if b == 0:
        return x
    x3 = x.reshape(b, hw, c)
    out = pl.pallas_call(
        functools.partial(_groupnorm_kernel, groups=groups, eps=eps),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hw, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((c,), lambda i: (0,)),
            pl.BlockSpec((c,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((1, hw, c), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hw, c), x.dtype),
        interpret=interpret,
    )(x3, scale, bias)
    return out.reshape(x.shape)


def _per_batch_shard(local_fn, *args):
    """`local_fn` on each device's own images: the batch dim splits over
    the data axes (each shard norms its own images), spatial + channel
    dims stay whole — the per-(batch, group) reduction spans them."""
    from tf_yarn_tpu.ops._rowwise import batch_spec, per_shard

    return per_shard(
        local_fn, args,
        lambda mesh: tuple(batch_spec(mesh, a.shape) for a in args),
        lambda mesh: batch_spec(mesh, args[0].shape),
    )


def _groupnorm_forward(x, scale, bias, groups, eps, interpret):
    return _per_batch_shard(
        lambda x, scale, bias: _groupnorm_local(
            x, scale, bias, groups, eps, interpret),
        x, scale, bias,
    )


def _group_assign(c: int, groups: int):
    """One-hot channel<->group maps built from iota (no gathers, no
    in-kernel transpose): ([C, G] folding channels into groups, [G, C]
    spreading group values back over their channels)."""
    cg = c // groups

    def one_hot(shape, chan_dim):
        chan = jax.lax.broadcasted_iota(jnp.int32, shape, chan_dim)
        grp = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - chan_dim)
        return (chan // cg == grp).astype(jnp.float32)

    return one_hot((c, groups), 0), one_hot((groups, c), 1)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _groupnorm_bwd_dx_kernel(x_ref, g_ref, scale_ref, o_ref, *,
                             groups: int, eps: float):
    x = x_ref[...].astype(jnp.float32)  # [1, HW, C]: one batch element
    hw, c = x.shape[-2], x.shape[-1]
    x2d = x.reshape(hw, c)
    g2d = g_ref[...].astype(jnp.float32).reshape(hw, c)
    gs = g2d * scale_ref[...].astype(jnp.float32)[None, :]
    assign, spread = _group_assign(c, groups)
    n = jnp.float32(hw * (c // groups))
    mean_c, inv_c = _slab_group_stats(x2d, assign, spread, groups, eps)
    norm = (x2d - mean_c) * inv_c

    def group_mean(t):  # [HW, C] -> its group's mean on every channel
        return _dot(_dot(jnp.sum(t, axis=0, keepdims=True), assign) / n,
                    spread)

    dx = inv_c * (gs - group_mean(gs) - norm * group_mean(gs * norm))
    o_ref[...] = dx.reshape(x.shape).astype(o_ref.dtype)


def _groupnorm_bwd_dx_local(x, g, scale, groups, eps, interpret):
    """Per-shard pallas call over [B_local, HW, C] slabs of x AND g."""
    b, c = x.shape[0], x.shape[-1]
    hw = 1
    for dim in x.shape[1:-1]:
        hw *= dim
    if b == 0:
        return x
    slab = pl.BlockSpec((1, hw, c), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_groupnorm_bwd_dx_kernel, groups=groups, eps=eps),
        grid=(b,),
        in_specs=[slab, slab, pl.BlockSpec((c,), lambda i: (0,))],
        out_specs=slab,
        out_shape=jax.ShapeDtypeStruct((b, hw, c), x.dtype),
        interpret=interpret,
    )(x.reshape(b, hw, c), g.reshape(b, hw, c), scale)
    return out.reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _groupnorm(x, scale, bias, groups, eps, interpret, kernel_bwd):
    return _groupnorm_forward(x, scale, bias, groups, eps, interpret)


def _groupnorm_fwd(x, scale, bias, groups, eps, interpret, kernel_bwd):
    return (_groupnorm_forward(x, scale, bias, groups, eps, interpret),
            (x, scale, bias))


def _groupnorm_bwd(groups, eps, interpret, kernel_bwd, residuals, g):
    x, scale, bias = residuals
    if not kernel_bwd:
        _, vjp = jax.vjp(
            lambda x, s, b: groupnorm_reference(x, s, b, groups, eps),
            x, scale, bias,
        )
        return vjp(g)
    dx = _per_batch_shard(
        lambda x, g, scale: _groupnorm_bwd_dx_local(
            x, g, scale, groups, eps, interpret),
        x, g, scale,
    )
    # dscale/dbias: cross-batch sums, XLA-fused (auto-psum under pjit).
    b, c = x.shape[0], x.shape[-1]
    norm = _norm32(x, groups, eps).reshape(b, -1, c)
    g32 = g.astype(jnp.float32).reshape(b, -1, c)
    dscale = jnp.sum(g32 * norm, axis=(0, 1)).astype(scale.dtype)
    dbias = jnp.sum(g32, axis=(0, 1)).astype(bias.dtype)
    return dx, dscale, dbias


_groupnorm.defvjp(_groupnorm_fwd, _groupnorm_bwd)

# One batch element's [HW, C] slab must fit VMEM alongside the f32
# compute copies; past this, fall back to XLA (resnet50 slabs are <=4MB).
_MAX_SLAB_BYTES = 8 * 1024 * 1024


def groupnorm(
    x: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    groups: int,
    eps: float = 1e-5,
    interpret: Optional[bool] = None,
    kernel_bwd: Optional[bool] = None,
) -> jax.Array:
    """Fused GroupNorm over the channel (last) dim; differentiable.
    Falls back to the XLA reference when a batch element's slab would
    not fit VMEM or channels don't divide into groups. `kernel_bwd`
    selects the fused dx kernel (default; env TPU_YARN_NORM_KERNEL_BWD=0
    flips it) vs recompute-through-reference backward."""
    from tf_yarn_tpu.ops._rowwise import default_interpret, default_kernel_bwd

    c = x.shape[-1]
    hw = 1
    for dim in x.shape[1:-1]:
        hw *= dim
    if x.shape[0] == 0:  # empty batch: a (0,)-grid pallas_call is invalid
        return x
    if c % groups or hw == 0 or hw * c * 4 > _MAX_SLAB_BYTES:
        return groupnorm_reference(x, scale, bias, groups, eps)
    if interpret is None:
        interpret = default_interpret()
    if kernel_bwd is None:
        kernel_bwd = default_kernel_bwd()
    # The bwd kernel streams TWO slabs (x and the cotangent) plus f32
    # intermediates per block — roughly double the forward footprint, so
    # it gets half the slab budget; beyond it the backward falls back to
    # the XLA recompute while the forward stays fused.
    kernel_bwd = kernel_bwd and (hw * c * 4 * 2 <= _MAX_SLAB_BYTES)
    return _groupnorm(x, scale, bias, groups, eps, interpret, kernel_bwd)
