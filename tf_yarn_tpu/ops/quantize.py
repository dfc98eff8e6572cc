"""Int8 quantization kernels (pallas): per-row symmetric scale.

The quantization pattern from the TPU kernel playbook (/opt/skills/guides/
pallas_guide.md §Patterns: Quantization Kernels): per-row abs-max scales,
int8 values rounded to nearest. The int8 KV cache's write path
(models/transformer.py).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quantize_kernel(x_ref, values_ref, scales_ref):
    x = x_ref[...].astype(jnp.float32)
    abs_max = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(abs_max, 1e-8) / 127.0
    values_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    scales_ref[...] = scale.astype(jnp.float32)


def quantize_int8(
    x: jax.Array,
    *,
    block_rows: int = 256,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """x [..., d] -> (int8 values [..., d], f32 scales [..., 1])."""
    import math

    if interpret is None:
        from tf_yarn_tpu.ops._rowwise import default_interpret

        interpret = default_interpret()
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for dim in orig_shape[:-1]:
        rows *= dim
    if rows == 0:  # empty batch: 0 % 0 below would raise
        return (jnp.zeros(orig_shape, jnp.int8),
                jnp.zeros(orig_shape[:-1] + (1,), jnp.float32))
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    if rows % block_rows:
        block_rows = math.gcd(rows, block_rows)
    values, scales = pl.pallas_call(
        _quantize_kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0))],
        out_specs=(
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((rows, d), jnp.int8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ),
        interpret=interpret,
    )(x2)
    return (
        values.reshape(orig_shape),
        scales.reshape(*orig_shape[:-1], 1),
    )


def dequantize_int8(values: jax.Array, scales: jax.Array, dtype=jnp.float32):
    return (values.astype(jnp.float32) * scales).astype(dtype)


def quantize_int8_grouped(
    x: jax.Array,
    group_rows: int,
    **kwargs,
) -> Tuple[jax.Array, jax.Array]:
    """Per-GROUP symmetric int8: x [..., R, d] -> (int8 values [..., R, d],
    f32 scales [..., R/group_rows, 1]) — one abs-max scale shared by every
    `group_rows` consecutive rows.

    With ``group_rows = kv block size`` this is the paged KV cache's
    per-block scale layout: 1/group_rows the scale storage (and scale
    stream traffic) of the per-row layout, traded against a coarser
    quantization step — the whole block shares its loudest row's scale
    (see `paged_int8_decode_attention`). Implemented as a reshape around
    the same pallas kernel: a group of rows IS one long row.
    """
    if group_rows < 1:
        raise ValueError(f"group_rows must be >= 1, got {group_rows}")
    *lead, rows, d = x.shape
    if rows % group_rows:
        raise ValueError(
            f"rows ({rows}) must divide by group_rows ({group_rows})"
        )
    grouped = x.reshape(*lead, rows // group_rows, group_rows * d)
    values, scales = quantize_int8(grouped, **kwargs)
    return values.reshape(x.shape), scales


def dequantize_int8_grouped(
    values: jax.Array, scales: jax.Array, group_rows: int,
    dtype=jnp.float32,
):
    """Inverse of `quantize_int8_grouped`: values [..., R, d] + scales
    [..., R/group_rows, 1] -> [..., R, d]."""
    *lead, rows, d = values.shape
    grouped = values.reshape(*lead, rows // group_rows, group_rows * d)
    return dequantize_int8(grouped, scales, dtype).reshape(values.shape)
