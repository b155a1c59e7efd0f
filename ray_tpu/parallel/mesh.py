"""Device-mesh construction over ICI topology.

TPU-native replacement for the reference's process-group bootstrapping
(reference: python/ray/train/torch/config.py:62 _setup_torch_process_group
— TCP rendezvous + NCCL): here the "process group" is a jax.sharding.Mesh.
`mesh_utils.create_device_mesh` lays logical axes onto the physical
ICI torus so that the innermost (most-communicating) axes get nearest-
neighbor links; the outermost `dcn` axis spans slices over DCN
(multi-slice data parallelism).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .plan import ParallelPlan


@dataclass(frozen=True)
class MeshSpec:
    """A named mesh request; resolved to a jax Mesh via `make_mesh`."""

    plan: ParallelPlan
    devices: Optional[Tuple] = None  # explicit device list (tests)

    def resolve(self):
        return make_mesh(self.plan, devices=self.devices)


def mesh_devices(n: Optional[int] = None, *, platform: Optional[str] = None):
    """Pick devices for a mesh: real TPU chips if present, else CPU
    (virtual devices under --xla_force_host_platform_device_count)."""
    import jax

    devs = jax.devices(platform) if platform else jax.devices()
    if n is not None:
        if len(devs) < n:
            raise ValueError(
                f"Need {n} devices, only {len(devs)} available "
                f"({[d.platform for d in devs[:3]]}...)")
        devs = devs[:n]
    return devs


def make_mesh(plan: ParallelPlan, *, devices: Optional[Sequence] = None):
    """Build a jax.sharding.Mesh shaped by the plan.

    On TPU, uses mesh_utils.create_device_mesh for ICI-aware placement
    (innermost axes ↔ nearest-neighbor links) and raises what it raises:
    a silent reshape would hide a wrong ICI layout on a real host. On
    CPU (tests), a plain reshape of the device list.
    """
    import jax
    from jax.sharding import Mesh

    n = plan.num_devices
    if devices is None:
        devices = mesh_devices(n)
    devices = list(devices)[:n]
    if len(devices) != n:
        raise ValueError(
            f"{plan.describe()} needs {n} devices, got {len(devices)}")

    shape = plan.mesh_shape
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils
        arr = mesh_utils.create_device_mesh(
            shape, devices=devices, allow_split_physical_axes=True)
    else:
        arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, plan.mesh_axis_names)


def best_effort_device_count() -> int:
    import jax

    return len(jax.devices())


def slice_topology() -> List[dict]:
    """Describe the local TPU topology (slice/host/chip coordinates),
    the scheduler's input for SliceAffinity gang placement
    (reference models TPU metadata in _private/accelerators/tpu.py:13-46;
    here it comes straight from the jax device objects)."""
    import jax

    out = []
    for d in jax.devices():
        out.append({
            "id": d.id,
            "platform": d.platform,
            "process_index": getattr(d, "process_index", 0),
            "coords": tuple(getattr(d, "coords", ()) or ()),
            "slice_index": getattr(d, "slice_index", 0),
        })
    return out
