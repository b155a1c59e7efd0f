"""Engine: wait in the admission queue (`GenRequest.queue_s`), 90th
percentile over the measured requests, ms."""

from lib import stats


def read(metric, m):
    vals = [r.req.queue_s * 1e3 for r in m.get("rows", []) if r.req.admit_ts]
    return stats.percentile(vals, 90)
