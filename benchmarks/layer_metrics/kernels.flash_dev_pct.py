"""Kernels: share of the devices' busy time spent in the pallas flash
attention kernels (forward, dq, dkv), found by the names their events
carry in the trace."""

from lib import kernels


def read(metric, m):
    tr = m.get("trace")
    if tr is None or not tr.busy_total_s:
        return None
    return 100.0 * tr.ops_matching(kernels.FLASH_EVENTS) / tr.busy_total_s
