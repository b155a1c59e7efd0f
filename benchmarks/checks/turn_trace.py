#!/usr/bin/env python3
"""Cut a stretch out of a traced run's profile for `lib/turn.py`'s tests
(`tests/benchmark/recorded_turn_trace.*.json.gz`): what
`checks/request_trace.py` keeps (its `cut`: the `ray_tpu:*` spans, device
0's module events, its operations as the merged intervals in which any
ran) and, for every decode launch kept, its operations by name as
(events, ns): all that `lib/turn.launch_fixed` takes from them.

    python3 benchmarks/run.py --workload <cell> --seed <n> --trace 1
    python3 benchmarks/checks/turn_trace.py --workload <cell> \\
        --start-s 1.0 --length-s 1.5 --out chiprun_out/<name>.json.gz

`expect_turn` is `lib/turn.reduce_turn` of what was kept.
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

from checks import request_trace  # noqa: E402
from lib import harness, progspans, turn, xplane  # noqa: E402


def cut(raw, start_s: float, length_s: float):
    kept = request_trace.cut(raw, start_s, length_s)
    modules = [tuple(m) for m in kept["devices"]["/device:TPU:0"]["modules"]]
    by_start = turn.ops_by_launch(raw, modules)
    kept["decode_ops"] = {repr(s): ops for s, ops in by_start.items()}
    return kept


def as_raw(kept):
    """A kept stretch as `read_profile` would return it, the decode
    launches' operations beside it."""
    raw = request_trace.as_raw(kept)
    raw["decode_ops"] = {float(s): {op: tuple(v) for op, v in ops.items()}
                         for s, ops in kept.get("decode_ops", {}).items()}
    return raw


def load(path: str):
    """(the stretch as `lib/turn.reduce_turn` takes it, the file)."""
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
    said = []
    reduced = turn.reduce_turn(as_raw(json.loads(json.dumps(kept))),
                               lambda **kv: said.append(kv))
    kept["expect_turn"] = reduced.summary()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(kept, f)
    print(json.dumps({"out": args.out, "bytes": os.path.getsize(args.out),
                      "spans": len(kept["spans"]),
                      "decode_launches": len(kept["decode_ops"]),
                      "said": said, "expect_turn": kept["expect_turn"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
