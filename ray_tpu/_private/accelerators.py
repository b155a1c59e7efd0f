"""TPU accelerator discovery.

Capability-equivalent of the reference's TPU support
(reference: python/ray/_private/accelerators/tpu.py — resource "TPU",
TPU_VISIBLE_CHIPS visibility control :13-46, accelerator-type and pod
name discovery from GKE/GCE metadata env, per-pod custom resources like
"TPU-v4-16-head"): reads the libtpu/GKE environment so the scheduler
can size the "TPU" resource and gang-schedule onto slices without
probing jax (which would grab the chips).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

# Env contract (set by GKE TPU webhooks / xla runtime):
VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"     # e.g. "v5p-64"
WORKER_ID_ENV = "TPU_WORKER_ID"                   # host index in the pod
WORKER_HOSTNAMES_ENV = "TPU_WORKER_HOSTNAMES"     # csv of pod hosts
TPU_NAME_ENV = "TPU_NAME"                         # pod/slice name
CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"  # e.g. "2,2,1"


# Published per-chip peaks, keyed by jax's `device_kind`: the one table
# every utilisation or roofline number in this repo divides by. A chip
# that is not here is an error, not a default — add its row with its
# source.
CHIP_PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, \"TPU v5e\" (per chip)",
    },
}


def chip_peaks(device) -> Dict[str, object]:
    """The CHIP_PEAKS row of a jax device; raises for an unknown kind."""
    kind = device.device_kind
    if kind not in CHIP_PEAKS:
        raise ValueError(
            f"no published peaks for device kind {kind!r} (platform "
            f"{device.platform!r}); known: {sorted(CHIP_PEAKS)} — add "
            "its row to ray_tpu/_private/accelerators.py CHIP_PEAKS")
    return CHIP_PEAKS[kind]


def get_visible_chips() -> Optional[List[str]]:
    """Chip ids this process may use; None = unrestricted. An EMPTY env
    value means ZERO chips (the CUDA_VISIBLE_DEVICES contract — '' is a
    restriction, not an absence of one; reference: tpu.py
    get_current_process_visible_accelerator_ids)."""
    v = os.environ.get(VISIBLE_CHIPS_ENV)
    if v is None:
        return None
    return [c.strip() for c in v.split(",") if c.strip() != ""]


def set_visible_chips(chip_ids: List[str]) -> None:
    """Restrict this process to the given chips (reference:
    tpu.py set_current_process_visible_accelerator_ids)."""
    os.environ[VISIBLE_CHIPS_ENV] = ",".join(str(c) for c in chip_ids)


def num_chips_per_host() -> int:
    """Chips on this host, for a process that must NOT touch jax (a
    node daemon: its workers own the chips): the visibility list wins
    (the CUDA_VISIBLE_DEVICES analog — a restricted process must not
    advertise the whole host), then the host bounds env (e.g.
    "2,2,1" → 4), then probing jax; 0 if undiscoverable."""
    visible = get_visible_chips()
    if visible is not None:
        return len(visible)
    bounds = os.environ.get(CHIPS_PER_HOST_BOUNDS_ENV)
    if bounds:
        n = 1
        try:
            for d in bounds.split(","):
                n *= int(d)
            return n
        except ValueError:
            pass
    return _jax_chip_count()


def num_chips_driven() -> int:
    """Chips THIS process computes on, for the one process that owns
    them (the local runtime's driver): the visibility list, which
    libtpu honours, else what the jax client sees. The host bounds env
    is not consulted: it describes the host TYPE, and a one-chip
    container on a four-chip host type still reads "2,2,1"."""
    visible = get_visible_chips()
    if visible is not None:
        return len(visible)
    return _jax_chip_count()


def _jax_chip_count() -> int:
    try:
        import jax

        return len([d for d in jax.local_devices()
                    if d.platform != "cpu"])
    except Exception:  # noqa: BLE001
        return 0


def accelerator_type() -> Optional[str]:
    """"v5p-64"-style type string (reference: tpu.py
    get_current_node_accelerator_type via GCE metadata; here env-only —
    zero egress)."""
    return os.environ.get(ACCELERATOR_TYPE_ENV) or None


def pod_name() -> Optional[str]:
    return os.environ.get(TPU_NAME_ENV) or None


def worker_id() -> int:
    try:
        return int(os.environ.get(WORKER_ID_ENV, "0"))
    except ValueError:
        return 0


def pod_worker_count() -> int:
    hosts = os.environ.get(WORKER_HOSTNAMES_ENV, "")
    return len([h for h in hosts.split(",") if h.strip()]) or 1


def pod_resources() -> Dict[str, float]:
    """Custom resources advertising pod membership (reference:
    tpu.py — "TPU-<type>-head" on worker 0 plus a per-pod name
    resource, used for gang placement of one job per slice)."""
    out: Dict[str, float] = {}
    acc = accelerator_type()
    name = pod_name()
    if acc:
        out[f"TPU-{acc}"] = 1.0
        if worker_id() == 0:
            out[f"TPU-{acc}-head"] = 1.0
    if name:
        out[f"TPU-pod-{name}"] = 1.0
    return out
