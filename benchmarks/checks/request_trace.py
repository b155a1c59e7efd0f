#!/usr/bin/env python3
"""Cut a stretch out of a traced run's profile and keep it as the plain
lists `lib/progspans.read_profile` returns, small enough to sit beside
the tests (`tests/benchmark/recorded_request_trace.*.json.gz`).

    python3 benchmarks/run.py --workload <cell> --seed <n> --trace 1
    python3 benchmarks/checks/request_trace.py --workload <cell> \\
        --start-s 1.0 --length-s 1.5 --out chiprun_out/<name>.json.gz

The stretch begins 2 ms before the first `engine.submit` at or after
`--start-s` into the traced window. Kept: the `ray_tpu:*` spans within
0.2 s of it, device 0's module events from 0.2 s before it to a second
after it, and device 0's operations over the same span as the merged
intervals in which any ran (all `lib/reqpath.py` takes from them), each
named `busy`. `expect` is `lib/reqpath.reduce_paths` of what was kept.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import harness, progspans, reqpath, xplane  # noqa: E402

BEFORE_NS, AFTER_NS = 0.2e9, 1.0e9


def cut(raw, start_s: float, length_s: float):
    t0, _ = raw["window"]
    t0 += start_s * 1e9
    submits = sorted(s.start for s in raw["spans"]
                     if s.name == reqpath.SUBMIT and s.start >= t0)
    if submits:
        t0 = submits[0] - 2e6
    t1 = t0 + length_s * 1e9
    lo, hi = t0 - BEFORE_NS, t1 + AFTER_NS
    ops, modules = reqpath._device0(raw)
    busy = xplane.merged([(s, s + d) for _, s, d in ops
                          if s + d > lo and s < hi])
    return {
        "window": [t0, t1],
        "spans": [[s.name, s.start, s.dur, s.thread, s.stats]
                  for s in raw["spans"]
                  if s.end > lo and s.start < t1 + BEFORE_NS],
        "devices": {"/device:TPU:0": {
            "ops": [["busy", s, e - s] for s, e in busy],
            "modules": [list(m) for m in modules
                        if m[1] + m[2] > lo and m[1] < hi]}}}


def as_raw(kept):
    """A kept stretch as `read_profile` would return it."""
    dev = kept["devices"]["/device:TPU:0"]
    return {"window": tuple(kept["window"]),
            "spans": [progspans.Span(*s) for s in kept["spans"]],
            "devices": {"/device:TPU:0": {
                "ops": [tuple(o) for o in dev["ops"]],
                "modules": [tuple(m) for m in dev["modules"]]}},
            "scopes": {}}


def load(path: str):
    """(the stretch as `read_profile` would return it, the file)."""
    with gzip.open(path, "rt") as f:
        kept = json.load(f)
    return as_raw(kept), kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--start-s", type=float, default=1.0)
    ap.add_argument("--length-s", type=float, default=1.5)
    ap.add_argument("--note", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    path = xplane.find_xplane(os.path.join(
        ROOT, harness.OUT_DIR, args.workload, "trace"))
    if path is None:
        raise SystemExit(f"no trace of {args.workload}: run it with "
                         "--trace 1 first")
    kept = cut(progspans.read_profile(path), args.start_s, args.length_s)
    kept["from"] = (f"{args.workload}, --trace 1, {args.length_s} s of the "
                    f"traced stretch from {args.start_s} s in. {args.note}")
    # Of what a test will load, not of the profile it was cut from.
    reduced = reqpath.reduce_paths(as_raw(json.loads(json.dumps(kept))))
    kept["expect"] = reduced.summary()
    kept["expect_requests"] = reduced.requests
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(kept, f)
    print(json.dumps({"out": args.out, "bytes": os.path.getsize(args.out),
                      "spans": len(kept["spans"]),
                      "expect": kept["expect"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
