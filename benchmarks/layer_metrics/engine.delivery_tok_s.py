"""Engine: tokens a second from the first delivery in the window to the
last (`lib/stats.delivery_rate`): the window's rate without the lump of
tokens that each edge cuts through. Steadier than `serve_out_tok_s`
and blind to a stall at either edge, so it only stands beside it."""

from lib import stats


def read(metric, m):
    ctx = m["ctx"]
    return stats.delivery_rate([(t, n) for t, n in m.get("token_stamps", [])
                                if ctx.t_open <= t < ctx.t_close])
