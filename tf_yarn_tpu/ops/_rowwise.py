"""Shared scaffolding for the pallas kernels: row-wise blocking for the
norms, and `per_shard`, which runs any kernel on each device's own shard
under the run's mesh.

rmsnorm and layernorm reduce over the last dim only, so they share the
same blocking: flatten leading dims to rows, tile rows into VMEM blocks
(gcd fallback keeps the grid small on almost-divisible shapes), broadcast
the [d]-shaped parameter vectors to every block. Keeping this in one
place means a fix to the mechanics (block sizing, interpret default)
lands in every kernel at once. groupnorm blocks per batch element (its
reduction spans the spatial dims too) and uses only `per_shard`.
"""

from __future__ import annotations

import math

import jax
from jax.experimental import pallas as pl


def default_interpret() -> bool:
    """pallas interpret mode everywhere but real TPU (CPU tests)."""
    return jax.default_backend() != "tpu"


def default_kernel_bwd() -> bool:
    """Fused dx backward kernels on by default; TPU_YARN_NORM_KERNEL_BWD=0
    reverts to the recompute-through-reference vjp (the A/B knob — an env
    seam instead of a config field so duck-typed model configs need no
    new field; read at trace time, so benchmarks toggling it re-jit)."""
    import os

    return os.environ.get("TPU_YARN_NORM_KERNEL_BWD", "1") != "0"


def rowwise_call(kernel, x, vectors, block_rows: int, interpret: bool,
                 row_operands=()):
    """Run `kernel(x_block, *row_blocks, *vector_refs, o_ref)` over row
    blocks of x.

    x: [..., d]; row_operands: extra arrays of x's shape blocked the same
    way (a backward pass's cotangent rides here); vectors: [d]-shaped
    operands shared by every block. Returns an array of x's shape and
    dtype.
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for dim in orig_shape[:-1]:
        rows *= dim
    if rows == 0:
        return x  # empty batch: nothing to normalize (0 % 0 would raise)
    x2 = x.reshape(rows, d)
    extra = [r.reshape(rows, d) for r in row_operands]
    block_rows = min(block_rows, rows)
    if rows % block_rows:
        # Largest divisor <= block_rows keeps the grid small for
        # almost-divisible shapes (vs collapsing straight to 1 row/step).
        block_rows = math.gcd(rows, block_rows)
    row_spec = pl.BlockSpec((block_rows, d), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[row_spec] * (1 + len(extra))
        + [pl.BlockSpec((d,), lambda i: (0,)) for _ in vectors],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x2, *extra, *vectors)
    return out.reshape(orig_shape)


def run_mesh():
    """The mesh a kernel should be mapped over: the one the train loop
    registered (parallel.mesh.current_mesh), or None where there is
    nothing to map — no mesh, one device, or a caller that is already
    per-shard (inside its own shard_map)."""
    from tf_yarn_tpu.parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None or mesh.devices.size == 1:
        return None
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return mesh


def dividing(mesh, axes, dim: int):
    """`axes` (mesh axis names) as a PartitionSpec entry for a dimension
    of size `dim`: the axes themselves where their joint size divides
    it, else None — a dimension that cannot be split stays whole on
    every device, which is slower and still right."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = tuple(a for a in axes if sizes.get(a, 1) > 1)
    joint = math.prod(sizes[a] for a in axes)
    if not axes or dim % joint:
        return None
    return axes if len(axes) > 1 else axes[0]


def per_shard(local_fn, args, in_specs, out_specs):
    """Run a pallas kernel on each device's own shard of `args`.

    XLA cannot partition a Mosaic custom call: left alone under pjit it
    gathers the whole activation onto every device first. `shard_map`
    over the run's mesh (`run_mesh`) hands each device its shard
    instead; `in_specs(mesh)` / `out_specs(mesh)` say how the operands
    and results lie, and an operand that arrives otherwise is resharded
    by XLA. (The first version wrapped the kernels in
    `custom_partitioning`; libtpu's compiler has no partitioner for it —
    "Custom emitter for CustomSPMDPartitioning not found" — so nothing
    sharded ever compiled for a TPU.)

    Differentiation never reaches the shard_map: callers keep it inside
    a custom_vjp forward whose backward is another `per_shard` call plus
    plain XLA reductions, so cross-shard sums (dscale, dbias) are
    pjit's to insert.
    """
    mesh = run_mesh()
    if mesh is None:
        return local_fn(*args)
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs(mesh),
        out_specs=out_specs(mesh), check_vma=False,
    )(*args)


def rows_spec(mesh, shape):
    """How a [..., d] activation lies on the mesh: the leading (batch)
    dim over the data axes, a second (sequence) dim over `sp`, the
    feature dim whole — row-wise kernels reduce over it."""
    from jax.sharding import PartitionSpec

    from tf_yarn_tpu.parallel.mesh import AXIS_SP, BATCH_AXES

    spec = [None] * len(shape)
    if len(shape) >= 2:
        spec[0] = dividing(mesh, BATCH_AXES, shape[0])
    if len(shape) >= 3:
        spec[1] = dividing(mesh, (AXIS_SP,), shape[1])
    return PartitionSpec(*spec)


def batch_spec(mesh, shape):
    """Only the leading (batch) dim split, over the data axes."""
    from jax.sharding import PartitionSpec

    from tf_yarn_tpu.parallel.mesh import BATCH_AXES

    spec = [None] * len(shape)
    if len(shape) >= 2:  # a [c] parameter vector stays whole everywhere
        spec[0] = dividing(mesh, BATCH_AXES, shape[0])
    return PartitionSpec(*spec)


def sharded_rowwise_call(kernel, block_rows: int, interpret: bool, x,
                         vectors, row_operands=()):
    """`rowwise_call` on each device's rows: x and the x-shaped
    `row_operands` (a backward pass's cotangent) split by `rows_spec`,
    which leaves the [d] parameter `vectors` whole everywhere."""
    n_rows = 1 + len(row_operands)
    args = (x, *row_operands, *vectors)

    def local_fn(x, *rest):
        return rowwise_call(kernel, x, rest[n_rows - 1:], block_rows,
                            interpret, row_operands=rest[:n_rows - 1])

    return per_shard(
        local_fn, args,
        lambda mesh: tuple(rows_spec(mesh, a.shape) for a in args),
        lambda mesh: rows_spec(mesh, x.shape),
    )
