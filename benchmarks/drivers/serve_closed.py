"""Closed loop: `clients` callers, each sending the next request of one
fixed list the moment its last one returns. A lead-in plays during
set-up. The traffic file's `measure` says which requests count:
`sent_in_window` (tails: they are drained after the window closes) or
`ended_in_window` (throughput: nothing to drain)."""

from __future__ import annotations

import time
from typing import Any, Dict

from lib import serving


def run(ctx, devs) -> Dict[str, Any]:
    tr = ctx.spec.traffic
    built = serving.build(ctx, devs)
    engine = built["engine"]
    client = serving.Client(engine, built["trace"], built["prompts"])
    lead_in, drain = float(tr["lead_in_s"]), float(tr["drain_limit_s"])
    t_zero = time.monotonic()
    t_open, t_close = None, t_zero + lead_in + ctx.seconds
    measured = []
    by_end = tr.get("measure", "sent_in_window") == "ended_in_window"
    for _ in range(int(tr["clients"])):
        client.submit_next()
    while True:
        now = time.monotonic()
        if t_open is None and now >= t_zero + lead_in:
            t_open = ctx.open_window()
            t_close = t_open + ctx.seconds
            client.ticks_open = engine.decode_ticks
        with ctx.span("client_poll"):
            ended = client.poll()
        closing = t_open is not None and now >= t_close
        if closing and not client.ticks_close:
            client.ticks_close = engine.decode_ticks
        for old in ended:
            # The caller whose request ended sends its next one, also
            # while the window drains: the load stays as it was.
            with ctx.span("submit"):
                row = client.submit_next()
            if t_open is None or not t_open <= now < t_close:
                continue
            measured.append(old if by_end else row)
        if closing and (by_end or all(r.done for r in measured)
                        or now >= t_close + drain):
            break
        with ctx.span("generator_wait"):
            time.sleep(serving.POLL_S)
    ctx.close_window()
    return serving.finish(ctx, built, client, measured,
                          {"clients": tr["clients"]})
