"""Fused RMSNorm — pallas TPU kernel, forward and backward.

Forward: one VMEM round-trip per row block instead of the separate
square/mean/rsqrt/mul HLOs: x is read once, reduced and scaled in f32 on
the VPU, and written once in the storage dtype.

Backward (kernel_bwd=True, default): dx in one fused pass — the hand
vjp ``dx = r·(g·s) − x·r³·mean(g·s·x)`` keeps both rowwise reductions
in VMEM, reading x and g once and writing dx once. dx is row-local
given the replicated scale, so it shards under the SAME rowwise rule as
the forward. dscale = Σ_rows g·x·r is a cross-row (and under pjit
cross-shard) reduction, left to an XLA fusion outside the per-shard
kernel — jnp.sum over the sharded rows inserts the psum. kernel_bwd=False
keeps the
recompute-through-reference vjp for A/B (docs/Performance.md derives
the expected gap).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def rmsnorm_reference(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (norm * scale.astype(jnp.float32)).astype(x.dtype)


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    scaled = x * jax.lax.rsqrt(var + eps) * scale_ref[...].astype(jnp.float32)
    o_ref[...] = scaled.astype(o_ref.dtype)


def _make_rmsnorm_kernel(eps: float):
    return functools.partial(_rmsnorm_kernel, eps=eps)


def _rmsnorm_forward(x, scale, eps: float, block_rows: int, interpret: bool):
    # Under the run's mesh the kernel runs on each device's own rows
    # (ops/_rowwise.per_shard); plain rowwise pallas elsewhere.
    from tf_yarn_tpu.ops._rowwise import sharded_rowwise_call

    return sharded_rowwise_call(
        _make_rmsnorm_kernel(eps), block_rows, interpret, x, (scale,)
    )


def _rmsnorm_bwd_dx_kernel(x_ref, g_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    gs = g * scale_ref[...].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    proj = jnp.mean(gs * x, axis=-1, keepdims=True)
    o_ref[...] = (r * gs - x * (r * r * r) * proj).astype(o_ref.dtype)


def _make_rmsnorm_bwd_dx_kernel(eps: float):
    return functools.partial(_rmsnorm_bwd_dx_kernel, eps=eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _rmsnorm(x, scale, eps, block_rows, interpret, kernel_bwd):
    return _rmsnorm_forward(x, scale, eps, block_rows, interpret)


def _rmsnorm_fwd(x, scale, eps, block_rows, interpret, kernel_bwd):
    return _rmsnorm_forward(x, scale, eps, block_rows, interpret), (x, scale)


def _rmsnorm_bwd(eps, block_rows, interpret, kernel_bwd, residuals, g):
    x, scale = residuals
    if not kernel_bwd:
        _, vjp = jax.vjp(lambda x, s: rmsnorm_reference(x, s, eps), x, scale)
        return vjp(g)
    from tf_yarn_tpu.ops._rowwise import sharded_rowwise_call

    dx = sharded_rowwise_call(
        _make_rmsnorm_bwd_dx_kernel(eps), block_rows, interpret, x,
        (scale,), row_operands=(g,),
    )
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    reduce_axes = tuple(range(x.ndim - 1))
    dscale = jnp.sum(g32 * x32 * r, axis=reduce_axes).astype(scale.dtype)
    return dx, dscale


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm(
    x: jax.Array,
    scale: jax.Array,
    eps: float = 1e-5,
    block_rows: int = 256,
    interpret: Optional[bool] = None,
    kernel_bwd: Optional[bool] = None,
) -> jax.Array:
    """Fused RMSNorm over the last dim; differentiable. `kernel_bwd`
    selects the fused dx kernel (default; env TPU_YARN_NORM_KERNEL_BWD=0
    flips it) vs recompute-through-reference backward — the A/B knob."""
    from tf_yarn_tpu.ops._rowwise import default_interpret, default_kernel_bwd

    if interpret is None:
        interpret = default_interpret()
    if kernel_bwd is None:
        kernel_bwd = default_kernel_bwd()
    return _rmsnorm(x, scale, eps, block_rows, interpret, kernel_bwd)
