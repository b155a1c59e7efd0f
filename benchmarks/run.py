#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One new process a run; the last line of its standard output is one JSON
object (`correct`, `attempted`, `failed`, `metrics`, `device`, and with
`--trace 1` `breakdown`). Everything else goes on earlier lines or under
`.bench_out/`. A run that finds no TPU, or fewer chips than the cell
asks for, exits non-zero and prints no result.

    python3 benchmarks/run.py --workload <cell> --sweep 0.8,1.0,1.2 [--seconds s]

plays an open-loop cell's trace at a ladder of rates in one process and
writes the knee to `benchmarks/sweeps/<cell>.json`.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None, *, root: str = ROOT, rehearse: bool = False,
         out=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--sweep", default="")
    args = ap.parse_args(argv)
    t_start = T_START if argv is None else time.monotonic()

    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    if not os.path.isdir(os.path.join(ROOT, "ray_tpu")):
        raise SystemExit("the system under test (ray_tpu/) is not in this "
                         "checkout: nothing to measure")
    # The compile cache sits at a fixed path inside the checkout unless
    # the machine names one; ray_tpu's own default is the same directory.
    if not rehearse:
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(ROOT, ".jax_cache"))
        # The runtime's session files go inside the checkout too, not to
        # its fixed default under /tmp.
        os.environ.setdefault("RAY_TPU_TMPDIR",
                              os.path.join(ROOT, ".bench_out", "ray_tpu"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from lib import harness
    from lib.spec import load_json

    seconds = args.seconds if args.seconds is not None else float(
        load_json(os.path.join(root, "BENCHMARK.json"))["run_seconds"])
    if args.sweep:
        from lib import sweep

        return sweep.run_sweep(root, args.workload, args.seed, seconds,
                               [float(r) for r in args.sweep.split(",")],
                               t_start, rehearse=rehearse)
    return harness.run_cell(root, args.workload, args.seed, seconds,
                            bool(args.trace), t_start, rehearse=rehearse,
                            out=out)


if __name__ == "__main__":
    sys.exit(main())
