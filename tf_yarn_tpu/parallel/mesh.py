"""Device mesh: the TPU-native replacement for cluster process topologies.

The reference's parallelism is process-shaped (PS tasks, Horovod rings,
DDP ranks — SURVEY.md §2.5); on TPU parallelism is *mesh-shaped*: a named
`jax.sharding.Mesh` over the slice's chips, with XLA inserting collectives
over ICI wherever shardings demand it. One MeshSpec covers every strategy
the reference ships (data parallelism in its three guises) plus the ones it
lacks (FSDP/ZeRO, tensor, sequence/context, expert, pipeline) — strategies
become axis assignments, not separate code paths.

Axes (any may be 1, i.e. disabled):

* ``dp``   — pure data parallelism: params replicated, batch sharded.
* ``fsdp`` — data parallelism with params/optimizer sharded (ZeRO-3).
* ``tp``   — tensor parallelism (megatron-style row/col sharding).
* ``sp``   — sequence/context parallelism (ring attention over this axis).
* ``ep``   — expert parallelism for MoE layers.
* ``pp``   — pipeline stages.

Mesh axis order is (pp, dp, fsdp, sp, tp, ep): the fastest-varying axes
(tp/ep) map to directly-wired ICI neighbors, which is where the
bandwidth-hungry collectives live.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

AXIS_PP = "pp"
AXIS_DP = "dp"
AXIS_FSDP = "fsdp"
AXIS_SP = "sp"
AXIS_TP = "tp"
AXIS_EP = "ep"

# Batch dimension shards over every data-like axis.
BATCH_AXES = (AXIS_DP, AXIS_FSDP)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Parallelism layout for a run; crosses driver → tasks via the KV store
    (constants.KV_MESH_SPEC)."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1
    pp: int = 1

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return (AXIS_PP, AXIS_DP, AXIS_FSDP, AXIS_SP, AXIS_TP, AXIS_EP)

    @property
    def axis_sizes(self) -> Tuple[int, ...]:
        return (self.pp, self.dp, self.fsdp, self.sp, self.tp, self.ep)

    @property
    def total_devices(self) -> int:
        return math.prod(self.axis_sizes)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, raw: str) -> "MeshSpec":
        return cls(**json.loads(raw))

    @classmethod
    def auto(cls, n_devices: int) -> "MeshSpec":
        """Default layout: all devices on the fsdp axis — synchronous DP
        with sharded optimizer state, the TPU answer to all three of the
        reference's DP modes (SURVEY.md §2.5)."""
        return cls(fsdp=n_devices)


def resize_mesh_spec(spec: MeshSpec, n_devices: int) -> MeshSpec:
    """Refit `spec` onto `n_devices` for an elastic resize
    (docs/Resilience.md "Elastic training").

    The model axes (tp, sp, ep, pp) are PRESERVED: shrinking them would
    change parameter placement legality (a tp=4 layer cannot become tp=3)
    and is never what losing a data-parallel host means. Only the data
    axes rescale: fsdp keeps as much of its sharding as still divides
    (optimizer-state memory is why fsdp exists), dp absorbs the rest —
    so a `dp=4, fsdp=2` mesh on 4 surviving devices becomes
    `dp=2, fsdp=2`, and on 2 devices `dp=1, fsdp=2`.

    Raises ValueError when `n_devices` cannot host the model axes (not
    divisible by tp*sp*ep*pp) — that loss is not elastically absorbable;
    the caller should fail the run rather than silently change the
    model's parallelism.
    """
    model = spec.tp * spec.sp * spec.ep * spec.pp
    if n_devices < 1:
        raise ValueError(f"cannot build a mesh over {n_devices} devices")
    if n_devices % model:
        raise ValueError(
            f"elastic resize to {n_devices} devices cannot preserve the "
            f"model axes (tp={spec.tp} sp={spec.sp} ep={spec.ep} "
            f"pp={spec.pp} need multiples of {model}); this capacity loss "
            "is not absorbable by shrinking data parallelism"
        )
    data = n_devices // model
    fsdp = math.gcd(spec.fsdp, data)
    return dataclasses.replace(spec, dp=data // fsdp, fsdp=fsdp)


def select_devices(n: Optional[int] = None, platform: Optional[str] = None):
    """Devices for the mesh, on the platform this process was started
    for: TPU chips unless `TPU_YARN_PLATFORM` (or the `platform` arg)
    names another — `cpu` is the virtual multi-device test rig. A
    process meant for a chip whose JAX came up on anything else fails
    here, with the reason, before a model or a kernel is built: on the
    CPU every pallas kernel would run in interpret mode and the run
    would "pass" without the device."""
    import jax

    platform = platform or os.environ.get("TPU_YARN_PLATFORM") or "tpu"
    n_virtual = os.environ.get("TPU_YARN_VIRTUAL_DEVICES")
    if n_virtual and "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""
    ):
        # Must land before the CPU backend initializes in this process;
        # crossing the driver→task boundary via env is the supported way to
        # get a multi-device CPU rig in task subprocesses.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_virtual}"
        )
    if platform != "tpu":
        # Asked for by name: start that backend only, so that a CPU
        # side-car never takes the host's chips.
        jax.config.update("jax_platforms", platform)
        devices = jax.devices(platform)
    else:
        # JAX_PLATFORMS decides what JAX may start; it is not overridden.
        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise RuntimeError(
                f"this process was started for a TPU chip "
                f"(TPU_YARN_PLATFORM={os.environ.get('TPU_YARN_PLATFORM')!r}"
                f") but JAX came up on {devices[0].platform!r} "
                f"({len(devices)} x {devices[0].device_kind}; "
                f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). "
                "Either no chip is attached or visible to this process, "
                "another process holds it, or the TPU backend failed to "
                "start (see the log above). To run on virtual CPU devices "
                "say so by name: TPU_YARN_PLATFORM=cpu"
            )
    if n is not None:
        if len(devices) < n:
            raise ValueError(
                f"need {n} devices, have {len(devices)} ({platform or 'default'})"
            )
        n_proc = jax.process_count()
        if n_proc > 1 and n < len(devices):
            # Multi-host: the mesh must span every process (a mesh with no
            # addressable device on some host cannot place that host's
            # data). Take n/n_proc of each process's devices, in process
            # order.
            if n % n_proc:
                raise ValueError(
                    f"{n} mesh devices cannot spread evenly over "
                    f"{n_proc} processes"
                )
            per_proc = n // n_proc
            by_proc: dict = {}
            for device in devices:
                by_proc.setdefault(device.process_index, []).append(device)
            devices = [
                d
                for pid in sorted(by_proc)
                for d in by_proc[pid][:per_proc]
            ]
            if len(devices) != n:
                raise ValueError(
                    f"processes contribute unevenly: wanted {per_proc} "
                    f"devices from each of {n_proc} processes"
                )
        else:
            devices = devices[:n]
    return devices


def device_report() -> Dict[str, object]:
    """The device this process runs on, as JAX reports it — what every
    result names, so that a number from the CPU rig cannot pass for a
    chip's. `visible_chips` is the host chips the launcher gave this
    process (None: all of them); JAX numbers its own devices from 0 in
    every process, so two replicas on one host differ only there."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
    }


def _slice_ids(devices) -> List[int]:
    """slice_index per device (multi-slice TPU pods expose it; everything
    else counts as one slice)."""
    return [getattr(d, "slice_index", 0) for d in devices]


def order_devices_for_slices(
    spec: MeshSpec, devices: Sequence, slice_ids: Sequence[int]
) -> list:
    """Reorder `devices` so slice boundaries align with the outer mesh
    axes (pure logic; unit-testable with stub devices).

    The outer axes (pp, then dp) must absorb the slice boundaries so only
    their infrequent collectives cross DCN, while fsdp/sp/tp/ep stay
    inside a slice on ICI (the scaling-book recipe; SURVEY.md §5 "data
    plane ... DCN collectives across slices"). Requires the leading pp*dp
    product to be divisible by the slice count.
    """
    if len(slice_ids) != len(devices):
        raise ValueError(
            f"slice_ids ({len(slice_ids)}) must match devices ({len(devices)})"
        )
    n_slices = len(set(slice_ids))
    if n_slices <= 1:
        return list(devices)
    outer = spec.pp * spec.dp
    if outer % n_slices:
        raise ValueError(
            f"multi-slice mesh needs pp*dp ({spec.pp}*{spec.dp}) "
            f"divisible by the slice count {n_slices} so cross-DCN "
            "traffic stays on the outer axes"
        )
    per_slice = len(devices) // n_slices
    grouped: Dict[int, list] = {}
    for device, sid in zip(devices, slice_ids):
        grouped.setdefault(sid, []).append(device)
    if any(len(group) != per_slice for group in grouped.values()):
        raise ValueError("slices contribute unequal device counts")
    return [d for sid in sorted(grouped) for d in grouped[sid]]


def build_mesh(
    spec: MeshSpec,
    devices: Optional[Sequence] = None,
    *,
    slice_ids: Optional[Sequence[int]] = None,
):
    """Build the named Mesh for `spec`.

    Single-slice (the common case): row-major assignment — the fastest-
    varying axes (tp/ep) land on directly-wired ICI neighbors.

    Multi-slice pods: devices carrying distinct `slice_index` are grouped
    so slice boundaries align with the outer (pp, dp) axes — see
    `order_devices_for_slices`. `slice_ids` overrides the per-device
    attribute (virtual-slice testing on platforms without one).
    """
    from jax.sharding import Mesh

    if devices is None:
        devices = select_devices(spec.total_devices)
    if len(devices) != spec.total_devices:
        raise ValueError(
            f"MeshSpec wants {spec.total_devices} devices "
            f"({dict(zip(spec.axis_names, spec.axis_sizes))}), got {len(devices)}"
        )
    if slice_ids is None:
        slice_ids = _slice_ids(devices)
    devices = order_devices_for_slices(spec, devices, slice_ids)
    mesh_devices = np.asarray(devices).reshape(spec.axis_sizes)
    return Mesh(mesh_devices, spec.axis_names)


def mesh_axis_size(mesh, axis: str) -> int:
    """Size of one named axis in a built Mesh (1 when the axis is absent
    or disabled) — the tp-degree lookup serving's sharded decode engine
    and its telemetry share."""
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)


def batch_sharding(mesh, extra_batch_dims: int = 0):
    """NamedSharding for a [global_batch, ...] input: batch over dp+fsdp,
    remaining dims replicated (sequence sharding is applied inside models
    via logical rules, not on input placement)."""
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(BATCH_AXES, *([None] * extra_batch_dims)))


def replicated_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


def local_device_count() -> int:
    import jax

    return jax.local_device_count()


# The run's active mesh, registered by the train loop so mesh-aware ops
# (ring attention's shard_map, the pallas kernels' per-shard mapping) can
# find it from inside model code without threading the mesh through
# every module signature.
_CURRENT_MESH = None


def set_current_mesh(mesh) -> None:
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


def current_mesh():
    return _CURRENT_MESH


@contextlib.contextmanager
def use_mesh(mesh):
    """`mesh` as the run's mesh for the block; whatever was registered
    before comes back after it, so one run cannot leak its mesh into the
    next thing the process traces."""
    previous = current_mesh()
    set_current_mesh(mesh)
    try:
        yield mesh
    finally:
        set_current_mesh(previous)
