"""Parallel: time in which a collective runs on a device while no
compute operation does, as a share of the traced window, averaged over
the devices."""


def read(metric, m):
    tr = m.get("trace")
    if tr is None or not tr.window_s or not tr.devices:
        return None
    return 100.0 * tr.collective_exposed_s / tr.window_s
