"""Fused LayerNorm — pallas TPU kernel.

The bert family's norm (models/bert.py: post-LN encoder, 2 norms/layer
plus the embedding norm). Same single-VMEM-round-trip structure as
ops/rmsnorm.py with the extra mean subtraction and bias; variance is
computed two-pass on the in-VMEM block (mean first, then centered
squares), so there is no E[x²]−mean² cancellation to clamp.

Backward (kernel_bwd=True, default): dx in one fused pass via the hand
vjp ``dx = r·(gs − mean(gs) − norm·mean(gs·norm))`` with all three
rowwise reductions in VMEM; dscale/dbias are cross-row XLA reductions
(see ops/rmsnorm.py for the sharding reasoning). kernel_bwd=False keeps
the recompute-through-reference vjp — the A/B knob; ops/groupnorm.py
carries the same formula per (batch, group) on its slab blocking.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def layernorm_reference(x, scale, bias, eps: float = 1e-12):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    centered = x32 - mean
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    norm = centered * jax.lax.rsqrt(var + eps)
    return (norm * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _layernorm_kernel(x_ref, scale_ref, bias_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    centered = x - mean
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    y = centered * jax.lax.rsqrt(var + eps)
    y = y * scale_ref[...].astype(jnp.float32)
    y = y + bias_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _make_layernorm_kernel(eps: float):
    return functools.partial(_layernorm_kernel, eps=eps)


def _layernorm_forward(x, scale, bias, eps, block_rows, interpret):
    # Under the run's mesh the kernel runs on each device's own rows
    # (ops/_rowwise.per_shard); plain rowwise pallas elsewhere.
    from tf_yarn_tpu.ops._rowwise import sharded_rowwise_call

    return sharded_rowwise_call(
        _make_layernorm_kernel(eps), block_rows, interpret, x, (scale, bias)
    )


def _layernorm_bwd_dx_kernel(x_ref, g_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    gs = g * scale_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    centered = x - mean
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    norm = centered * r
    dx = r * (gs
              - jnp.mean(gs, axis=-1, keepdims=True)
              - norm * jnp.mean(gs * norm, axis=-1, keepdims=True))
    o_ref[...] = dx.astype(o_ref.dtype)


def _make_layernorm_bwd_dx_kernel(eps: float):
    return functools.partial(_layernorm_bwd_dx_kernel, eps=eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _layernorm(x, scale, bias, eps, block_rows, interpret, kernel_bwd):
    return _layernorm_forward(x, scale, bias, eps, block_rows, interpret)


def _layernorm_fwd(x, scale, bias, eps, block_rows, interpret, kernel_bwd):
    return (_layernorm_forward(x, scale, bias, eps, block_rows, interpret),
            (x, scale, bias))


def _layernorm_bwd(eps, block_rows, interpret, kernel_bwd, residuals, g):
    x, scale, bias = residuals
    if not kernel_bwd:
        _, vjp = jax.vjp(
            lambda x, s, b: layernorm_reference(x, s, b, eps), x, scale, bias)
        return vjp(g)
    from tf_yarn_tpu.ops._rowwise import sharded_rowwise_call

    dx = sharded_rowwise_call(
        _make_layernorm_bwd_dx_kernel(eps), block_rows, interpret, x,
        (scale,), row_operands=(g,),
    )
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    centered = x32 - mean
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    norm = centered * jax.lax.rsqrt(var + eps)
    reduce_axes = tuple(range(x.ndim - 1))
    dscale = jnp.sum(g32 * norm, axis=reduce_axes).astype(scale.dtype)
    dbias = jnp.sum(g32, axis=reduce_axes).astype(bias.dtype)
    return dx, dscale, dbias


_layernorm.defvjp(_layernorm_fwd, _layernorm_bwd)


def layernorm(
    x: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    eps: float = 1e-12,
    block_rows: int = 256,
    interpret: Optional[bool] = None,
    kernel_bwd: Optional[bool] = None,
) -> jax.Array:
    """Fused LayerNorm over the last dim; differentiable. `kernel_bwd`
    selects the fused dx kernel (default; env TPU_YARN_NORM_KERNEL_BWD=0
    flips it) vs recompute-through-reference backward — the A/B knob."""
    from tf_yarn_tpu.ops._rowwise import default_interpret, default_kernel_bwd

    if interpret is None:
        interpret = default_interpret()
    if kernel_bwd is None:
        kernel_bwd = default_kernel_bwd()
    return _layernorm(x, scale, bias, eps, block_rows, interpret, kernel_bwd)
