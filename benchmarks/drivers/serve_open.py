"""Open loop: independent users. Requests are sent on the traffic file's
schedule whatever the engine does, and each is timed from when it was
due. A lead-in of the same trace plays during set-up; the requests due
inside the window are measured and drained after it closes, while later
arrivals keep the load as it was."""

from __future__ import annotations

import time
from typing import Any, Dict

from lib import serving


def play(ctx, built: Dict[str, Any], lead_in_s: float, drain_limit_s: float,
         seconds: float, opener) -> Dict[str, Any]:
    """One pass over the trace from its start. `opener()` is called when
    the lead-in ends and returns the window's opening time."""
    engine, trace = built["engine"], built["trace"]
    client = serving.Client(engine, trace, built["prompts"])
    t_zero = time.monotonic()
    t_open = None
    t_close = t_zero + lead_in_s + seconds
    measured = []
    while True:
        now = time.monotonic()
        if t_open is None and now >= t_zero + lead_in_s:
            t_open = opener()
            t_close = t_open + seconds
            client.ticks_open = engine.decode_ticks
        while client.cursor < len(trace) \
                and t_zero + trace[client.cursor].due_s <= now:
            due = t_zero + trace[client.cursor].due_s
            with ctx.span("submit"):
                row = client.submit_next(due)
            if t_open is not None and t_open <= due < t_close:
                measured.append(row)
        with ctx.span("client_poll"):
            client.poll()
        if t_open is not None and now >= t_close:
            if not client.ticks_close:
                client.ticks_close = engine.decode_ticks
            if all(r.done for r in measured) \
                    or now >= t_close + drain_limit_s:
                break
        nxt = t_zero + trace[client.cursor].due_s \
            if client.cursor < len(trace) else now + serving.POLL_S
        with ctx.span("generator_wait"):
            time.sleep(max(0.0, min(serving.POLL_S, nxt - now)))
    return {"client": client, "measured": measured}


def run(ctx, devs) -> Dict[str, Any]:
    tr = ctx.spec.traffic
    built = serving.build(ctx, devs)
    played = play(ctx, built, float(tr["lead_in_s"]),
                  float(tr["drain_limit_s"]), ctx.seconds, ctx.open_window)
    ctx.close_window()
    return serving.finish(
        ctx, built, played["client"], played["measured"],
        {"rate_req_s": tr["rate_req_s"],
         "in_flight_by_third": serving.in_flight_by_third(
             played["client"], ctx.t_open, ctx.t_close)})
