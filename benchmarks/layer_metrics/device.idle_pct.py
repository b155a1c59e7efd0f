"""Device: 1 - union of the intervals in which an operation ran on the
device / traced window, averaged over the chips used."""


def read(metric, m):
    tr = m.get("trace")
    if tr is None or not tr.window_s or not tr.busy_s:
        return None
    return 100.0 * (1.0 - tr.busy_mean_s / tr.window_s)
