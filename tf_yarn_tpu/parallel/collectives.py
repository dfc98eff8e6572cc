"""Collective helpers + ICI bandwidth microbenchmark.

The data-plane primitives that replace the reference's Horovod/Gloo rings
and NCCL (SURVEY.md §2.4): thin, named wrappers over XLA collectives so
user code inside shard_map reads like the intent, plus the allreduce
bandwidth microbench (ROADMAP.md A0 wants ICI in the peaks table).
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def all_reduce_mean(x, axis_name: str):
    return jax.lax.pmean(x, axis_name)


def all_reduce_sum(x, axis_name: str):
    return jax.lax.psum(x, axis_name)


def reduce_scatter(x, axis_name: str, scatter_axis: int = 0):
    return jax.lax.psum_scatter(
        x, axis_name, scatter_dimension=scatter_axis, tiled=True
    )


def all_gather(x, axis_name: str, gather_axis: int = 0):
    return jax.lax.all_gather(x, axis_name, axis=gather_axis, tiled=True)


def ring_shift(x, axis_name: str, shift: int = 1):
    """Rotate shards `shift` hops around the axis ring (ppermute)."""
    n = jax.lax.psum(1, axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


def allreduce_bandwidth(
    size_mb: float = 64.0,
    iters: int = 10,
    devices: Optional[Sequence] = None,
    axis: str = "x",
) -> Dict[str, float]:
    """Measure allreduce algorithmic bandwidth over all local devices.

    Returns {gbps, elapsed_s, size_mb, n_devices}. Algorithmic bandwidth =
    2*(n-1)/n * bytes / time (ring allreduce cost model).
    """
    from jax.sharding import Mesh, NamedSharding

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if n < 2:
        # Single chip: no interconnect to measure; report memory-bound copy.
        n = 1
    mesh = Mesh(np.asarray(devices), (axis,))
    # Each device contributes a full `size_mb` message (the quantity the
    # ring-allreduce cost model 2*(n-1)/n * M is defined over).
    msg_elems = int(size_mb * 1e6 / 4)
    x = jnp.ones((max(n, 1), msg_elems), jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P(axis, None)))

    def one(s):
        return jax.lax.psum(s, axis) * (1.0 / max(n, 1))

    # All `iters` reductions chain inside ONE jitted program, so one
    # dispatch covers the whole timed region.
    @jax.jit
    def run(x):
        def body(_, acc):
            return jax.shard_map(
                one, mesh=mesh, in_specs=P(axis, None),
                out_specs=P(axis, None), check_vma=False,
            )(acc)
        return jax.lax.fori_loop(0, iters, body, x)

    jax.block_until_ready(run(x))  # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(run(x))
    elapsed = (time.perf_counter() - t0) / iters
    msg_bytes = msg_elems * 4
    algo_factor = 2 * (n - 1) / n if n > 1 else 1.0
    gbps = algo_factor * msg_bytes / elapsed / 1e9
    return {
        "gbps": gbps,
        "elapsed_s": elapsed,
        "size_mb": msg_bytes / 1e6,
        "n_devices": float(len(devices)),
        # Honest label: with one device there is no interconnect — the
        # number is an HBM-bound on-chip reduction, not ICI bandwidth.
        "mode": "ici_allreduce" if n > 1 else "single_chip_hbm_copy",
    }
