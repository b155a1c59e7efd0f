#!/usr/bin/env python3
"""The reading a block-generation cell's `correct` stands on, and its two
controls, many seeds in one process (they share the compiled programs).

    python3 benchmarks/checks/blockgen_logits.py --workload <cell> \\
        --seeds 11,2147483648,... [--control 2] [--control-len 256]

For every seed, as `drivers/serve_closed_blocks.check_blocks` does it
(the cell's own `check` sizes): weights from the seed, the program's
prefill and its passes through the cache against the configuration's
float32 reference: `logit_rel_rms_err` over every pass and over the
shortest prompt alone, beside the harness's limit. For the first
`--control` seeds also the controls, each held to the limit it should
come out as not correct:

- the mask: the same program readings against the reference under a
  causal mask (`block_length` 1 handed to the reference), over the
  shortest prompt, where the keys a causal mask hides are a third of
  what a row sees;
- the precision: the reference on weights rounded to 8-bit floats
  (`checks/serve_logits.fp8_in_place`) against the reference on the
  weights as they are, over one block-masked sequence of `--control-len`
  tokens.

One JSON line a seed, then one with the sound runs' largest readings, the
controls' smallest and the limit. No timed window; it prints no result
line and is no cell.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def main(argv=None, *, root: str = ROOT, rehearse: bool = False,
         out=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--control", type=int, default=2,
                    help="how many of the seeds also read the controls")
    ap.add_argument("--control-len", type=int, default=256)
    args = ap.parse_args(argv)
    out = out or sys.stdout
    for p in (ROOT, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    if not rehearse:
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import numpy as np

    from lib import harness, modelcfg, serving
    from lib.spec import Spec

    spec = Spec(root, args.workload)
    driver = spec.load_module("drivers", spec.traffic["driver"])
    rounding = spec.load_module("checks", "serve_logits")
    harness.Context(spec, 0, 0.0, False, time.monotonic(),
                    rehearse).devices()     # a TPU with the cell's chips
    cfg = modelcfg.transformer_config(spec.config, spec.sizes)
    slots, max_seq = int(spec.sizes["slots"]), int(spec.sizes["max_seq_len"])
    causal = dict(spec.config, block_length=1)
    rows = []
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = harness.Context(spec, seed, 0.0, False, time.monotonic(),
                              rehearse)
        params = modelcfg.make_params(cfg, seed)
        jax.block_until_ready(params)
        row = {"seed": seed}
        row.update(driver.check_blocks(ctx, cfg, params, slots, max_seq))
        if n < args.control:
            wrong = driver.check_blocks(ctx, cfg, params, slots, max_seq,
                                        ref_arch=causal)
            row["control_causal_rel_rms_err_shortest"] = wrong[
                "logit_rel_rms_err_shortest"]
            row["control_causal_ok"] = wrong["ok"]
            rng = np.random.default_rng([seed, 0x636F6E74])
            tokens = rng.integers(0, cfg.vocab_size,
                                  size=args.control_len).tolist()
            ref = np.asarray(spec.reference.forward_logits(
                spec.config, params, tokens), np.float32)
            params = rounding.fp8_in_place(params)
            row["control_fp8_rel_rms_err"] = rounding._rel_rms(
                spec.reference.forward_logits(spec.config, params, tokens),
                ref)
        del params
        gc.collect()
        rows.append(row)
        print(json.dumps(row), file=out, flush=True)

    def least(key):
        vals = [r[key] for r in rows if key in r]
        return min(vals) if vals else None

    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "limit": serving.LOGIT_REL_TOL,
        "sound_largest_rel_rms_err": max(
            r["logit_rel_rms_err"] for r in rows),
        "sound_largest_rel_rms_err_shortest": max(
            r["logit_rel_rms_err_shortest"] for r in rows),
        "over_limit": sum(not r["ok"] for r in rows),
        "control_causal_smallest_rel_rms_err_shortest": least(
            "control_causal_rel_rms_err_shortest"),
        "control_fp8_smallest_rel_rms_err": least(
            "control_fp8_rel_rms_err")}), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
