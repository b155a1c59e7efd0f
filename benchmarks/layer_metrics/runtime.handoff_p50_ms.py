"""Runtime (entry, threads): from the engine stamping a request's first
token to the client reading it, median, ms. Both clocks are
`time.monotonic` of one process."""

from lib import stats


def read(metric, m):
    vals = [(r.first - r.req.first_token_ts) * 1e3 for r in m.get("rows", [])
            if r.first and r.req.first_token_ts]
    return stats.percentile(vals, 50)
