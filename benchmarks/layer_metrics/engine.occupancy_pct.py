"""Engine: slots holding a request, as a share of all slots, averaged
over the client's samples inside the window."""


def read(metric, m):
    ctx = m["ctx"]
    vals = [a for t, a, _w in m.get("samples", [])
            if ctx.t_open <= t < ctx.t_close]
    if not vals or not m.get("slots"):
        return None
    return 100.0 * sum(vals) / len(vals) / m["slots"]
