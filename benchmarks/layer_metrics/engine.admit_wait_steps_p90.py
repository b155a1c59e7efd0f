"""Engine: decode steps that stood between a request and the device
when the engine first touched it (`GenRequest.steps_waited`), 90th
percentile over the measured requests. A run that forked admits some
request a block later than its twin, and this moves by that block."""

from lib import stats


def read(metric, m):
    vals = [r.req.steps_waited for r in m.get("rows", [])
            if getattr(r.req, "admit_tick", -1) >= 0]
    return stats.percentile(vals, 90)
