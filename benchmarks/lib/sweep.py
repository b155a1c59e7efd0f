"""`run.py --sweep r1,r2,...`: an open-loop cell's trace played at a
rising ladder of rates in one process, each rung with the cell's own
lead-in, window and drain limit. For each rate it prints the growth of
the backlog over the window and the two tails, and writes the knee (the
highest rate at which the backlog at window close is no larger than at
window open) and 0.8 x knee to `benchmarks/sweeps/<cell>.json`. The
traffic file's `rate_req_s` is that number, written in by hand: a run
never searches for its rate.

The backlog is the requests waiting for a slot, averaged over EDGE_S at
each end of the window. The number in flight (waiting and being
answered) is printed beside it by thirds of the window, to show whether
the lead-in reached the steady state; it swings by several requests
with the arrivals and is no test of the knee. The ladder stops at the
first rate that fails: every rate below the knee has passed."""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List

from . import harness, serving, traffic
from .spec import Spec

EDGE_S = 8.0      # backlog is averaged over this long at each end
SLACK = 1.0       # requests of growth that still count as none


def run_sweep(root: str, workload: str, seed: int, seconds: float,
              rates: List[float], t_start: float, rehearse: bool = False
              ) -> int:
    spec = Spec(root, workload)
    if spec.traffic["driver"] != "serve_open":
        raise SystemExit("--sweep is for open-loop cells")
    ctx = harness.Context(spec, seed, seconds, False, t_start, rehearse)
    devs = ctx.devices()
    ctx.install_counters()
    built = serving.build(ctx, devs)
    driver = spec.load_module("drivers", "serve_open")
    tr = spec.traffic
    rows: List[Dict[str, Any]] = []

    def opener() -> float:
        ctx.t_open = time.monotonic()
        ctx.t_close = ctx.t_open + seconds
        return ctx.t_open

    for rate in sorted(rates):
        built["trace"] = traffic.make_trace(tr, rate=rate)
        played = driver.play(ctx, built, float(tr["lead_in_s"]),
                             float(tr["drain_limit_s"]), seconds, opener)
        client = played["client"]
        s = serving.summarise(ctx, client, played["measured"])
        t0, t1 = ctx.t_open, ctx.t_close
        grow = serving.mean_backlog(client, t1 - EDGE_S, t1, True) \
            - serving.mean_backlog(client, t0, t0 + EDGE_S, True)
        row = {"rate_req_s": rate, "backlog_growth": grow,
               "in_flight_by_third": serving.in_flight_by_third(
                   client, t0, t1),
               "failed": sum(not r.ok for r in played["measured"]),
               **s["end_to_end"], **s["info"]}
        row["sustained"] = bool(grow <= SLACK and not row["failed"])
        rows.append(row)
        ctx.log(**row)
        if not row["sustained"]:
            break
        # Let the engine run empty before the next rate.
        deadline = time.monotonic() + 120
        while client.inflight and time.monotonic() < deadline:
            client.poll()
            time.sleep(serving.POLL_S)
    serving.stop_engine(built["engine"])
    steady = [r["rate_req_s"] for r in rows if r["sustained"]]
    knee = max(steady) if steady else None
    out = {"cell": workload, "seed": seed, "window_s": seconds,
           "lead_in_s": tr["lead_in_s"],
           "drain_limit_s": tr["drain_limit_s"],
           "edge_s": EDGE_S, "slack_requests": SLACK,
           "device": harness.device_report(devs, None),
           "rehearsal": rehearse, "rates": rows, "knee_req_s": knee,
           "chosen_rate_req_s": round(0.8 * knee, 2) if knee else None}
    os.makedirs(spec.path("sweeps"), exist_ok=True)
    with open(spec.path("sweeps", workload + ".json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("cell", "knee_req_s",
                                          "chosen_rate_req_s")}))
    return 0
