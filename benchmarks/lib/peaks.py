"""Published peaks, keyed by `device_kind`. A device that is not here is
an error, not a default. Copied from `ray_tpu/_private/accelerators.py`
`CHIP_PEAKS` (sound since PR 22) so that no later PR can move it."""

from __future__ import annotations

from typing import Any, Dict

PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, \"TPU v5e\" (per chip)",
    },
}


def peaks_for(device_kind: str) -> Dict[str, Any]:
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
