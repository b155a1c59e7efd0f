#!/usr/bin/env python3
"""`checks/serve_logits.py`'s readings for a routed configuration of any
period-stack architecture, under the cell's activation dtype or another:

    python3 benchmarks/checks/routed_logits.py --workload <serving cell> \\
        --seeds 11,2147483648,... [--dtype bfloat16] [--control 3]

The same seeds' loop and the same two readings (`serve_logits.read_seed`:
the program's prefill and decode through the cache against the
configuration's float32 reference at the cell's `check` sizes, and the
control on weights rounded to 8-bit floats), with two differences.
Routing flips are counted for every stack that can say which experts it
chose (`chosen_experts` of the configuration's stack module and of its
reference), not for one architecture by name; and `--dtype` replaces the
cell's `model.dtype` for this reading, so bf16 activations can be read
beside float32 ones on the same seeds. One JSON line a seed, then one
with the sound runs' readings, the share of token-layer pairs flipped,
the control's smallest reading and the limit. No timed window; it prints
no result line and is no cell.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def routing_flips(spec, cfg, params, tokens):
    """(token-layer pairs whose chosen experts differ between program and
    reference, pairs compared), or None where either cannot say."""
    import numpy as np

    from ray_tpu.models.transformer import stack

    ours_fn = getattr(stack(cfg), "chosen_experts", None)
    if ours_fn is None or not hasattr(spec.reference, "chosen_experts"):
        return None
    ours = ours_fn(cfg, params, tokens)
    theirs = spec.reference.chosen_experts(spec.config, params, tokens)
    flips = sum(int(np.sum(np.any(
        np.sort(np.asarray(a), -1) != np.sort(np.asarray(b), -1), axis=-1)))
        for a, b in zip(ours, theirs))
    return flips, len(ours) * len(tokens)


def main(argv=None, *, root: str = ROOT, rehearse: bool = False,
         out=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--dtype", default=None,
                    help="activation dtype in place of the cell's")
    ap.add_argument("--control", type=int, default=3,
                    help="how many of the seeds also read the control")
    ap.add_argument("--control-len", type=int, default=512)
    args = ap.parse_args(argv)
    out = out or sys.stdout

    for p in (ROOT, BENCH, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    if not rehearse:
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import serve_logits
    from lib import harness, modelcfg, serving
    from lib.spec import Spec

    serve_logits.routing_flips = routing_flips
    spec = Spec(root, args.workload)
    if args.dtype:
        spec.sizes.setdefault("model", {})["dtype"] = args.dtype
    harness.Context(spec, 0, 0.0, False, time.monotonic(),
                    rehearse).devices()     # a TPU with the cell's chips
    cfg = modelcfg.transformer_config(spec.config, spec.sizes)

    rows = []
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        row = serve_logits.read_seed(spec, cfg, seed, n < args.control,
                                     args.control_len, rehearse)
        row["seconds"] = time.monotonic() - t0
        rows.append(row)
        print(json.dumps(row), file=out, flush=True)
    controls = [r["control_rel_rms_err"] for r in rows
                if "control_rel_rms_err" in r]
    flips = sum(r.get("routing_flips", 0) for r in rows)
    pairs = sum(r.get("routing_pairs", 0) for r in rows)
    readings = [r["logit_rel_rms_err"] for r in rows]
    print(json.dumps({
        "workload": args.workload, "dtype": str(cfg.dtype.__name__),
        "seeds": len(rows), "sound_readings": readings,
        "sound_largest_rel_rms_err": max(readings),
        "over_limit": sum(r > serving.LOGIT_REL_TOL for r in readings),
        "control_smallest_rel_rms_err": min(controls) if controls else None,
        "routing_flips": flips, "routing_pairs": pairs,
        "routing_flip_share": flips / pairs if pairs else None,
        "limit": serving.LOGIT_REL_TOL}), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
