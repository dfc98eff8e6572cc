"""Collective-helper tests on the 8-device CPU mesh."""

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from tf_yarn_tpu.parallel import collectives
from tf_yarn_tpu.parallel.mesh import MeshSpec, build_mesh, select_devices


def _mesh8():
    return build_mesh(MeshSpec(dp=8), select_devices(8, platform="cpu"))


def test_allreduce_and_gather_helpers():
    mesh = _mesh8()
    x = np.arange(16, dtype=np.float32).reshape(8, 2)

    def body(s):
        total = collectives.all_reduce_sum(s, "dp")
        gathered = collectives.all_gather(s, "dp", gather_axis=0)
        return total, gathered

    total, gathered = jax.shard_map(
        body, mesh=mesh, in_specs=P("dp", None),
        out_specs=(P("dp", None), P("dp", None)), check_vma=False,
    )(x)
    np.testing.assert_allclose(np.asarray(total)[0], x.sum(axis=0))
    # Every shard gathered the full array.
    np.testing.assert_allclose(np.asarray(gathered)[:8], x)


def test_ring_shift():
    mesh = _mesh8()
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    out = jax.shard_map(
        lambda s: collectives.ring_shift(s, "dp", 1),
        mesh=mesh, in_specs=P("dp", None), out_specs=P("dp", None),
        check_vma=False,
    )(x)
    np.testing.assert_allclose(np.asarray(out).ravel(), np.roll(np.arange(8), 1))


def test_allreduce_bandwidth_smoke():
    result = collectives.allreduce_bandwidth(
        size_mb=1.0, iters=2, devices=select_devices(8, platform="cpu")
    )
    assert result["gbps"] > 0
    assert result["n_devices"] == 8
